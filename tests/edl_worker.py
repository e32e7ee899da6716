"""Stateless EDL trainer for the kill/re-dispatch/resume integration
test — now a THIN SHIM over ``distributed.ElasticTrainJob`` (ISSUE 13):
the job owns claims, ack-after-dispatch-sync, async sharded checkpoints
and membership heartbeats; the worker just builds the model, decodes
records, and reports what the job did as one JSON line:
{"tag", "resumed", "start_step", "tasks": [...]}.

Env: MASTER_ENDPOINT, CKPT_DIR, EDL_HANG_AFTER (finish N tasks then
hang holding the NEXT claim — the crash site for the test's kill),
DATA_DIM.
"""

import json
import os
import pickle
import time


def main():
    os.environ['JAX_PLATFORMS'] = 'cpu'
    # each EDL trainer runs its own 2-device virtual mesh so the
    # checkpointed model is genuinely SHARDED (VERDICT r3 next-#5: the
    # replacement must resume a sharded model, not single-chip state)
    # append unconditionally: the LAST occurrence of the flag wins, so
    # an ambient count (e.g. the suite's 8) is overridden to this
    # worker's 2-device mesh (same pattern as tests/dist_worker.py)
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        ' --xla_force_host_platform_device_count=2').strip()
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel
    from paddle_tpu.distributed import ElasticTrainJob, MasterClient
    from paddle_tpu.parallel.multihost import parse_elastic_env

    tag, endpoint = parse_elastic_env()
    ckpt_dir = os.environ['CKPT_DIR']
    hang_after = int(os.environ.get('EDL_HANG_AFTER', '-1'))
    dim = int(os.environ.get('DATA_DIM', '8'))

    def build():
        main_prog = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main_prog, startup):
            x = fluid.layers.data('x', shape=[dim])
            y = fluid.layers.data('y', shape=[1])
            hid = fluid.layers.fc(x, size=4, act='tanh')
            pred = fluid.layers.fc(hid, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(0.05).minimize(loss)
        # shard the hidden weight's output dim over the 2-way tp axis:
        # the checkpoint is written from (and resumed into) a sharded
        # scope
        parallel.shard(main_prog.all_parameters()[0], None, 'tp')
        return main_prog, startup, loss

    def batch_fn(records):
        rows = [pickle.loads(r) for r in records]
        return {'x': np.stack([r[0] for r in rows]).astype('float32'),
                'y': np.stack([r[1] for r in rows]).astype('float32')}

    client = MasterClient(endpoint)
    job = ElasticTrainJob(
        build, client, ckpt_dir, batch_fn, worker_id=tag,
        steps_per_dispatch=1, checkpoint_every=1,
        mesh_for=lambda n: {'tp': 2})

    if hang_after >= 0:
        def hang_hook(tid, task, ordinal):
            if ordinal >= hang_after:
                # let the in-flight dispatches deliver + ack so exactly
                # ``hang_after`` tasks are done, then hang HOLDING this
                # claim — the crash site (the test SIGKILLs us here and
                # the claim lease-times-out and re-dispatches)
                deadline = time.time() + 60
                while time.time() < deadline and (
                        len(job.tasks_done) < hang_after or
                        (job.ckpt.metrics()['last_step'] or 0) <
                        hang_after):
                    time.sleep(0.02)  # acks delivered AND ckpt committed
                print(json.dumps({'tag': tag, 'hanging_on': tid}),
                      flush=True)
                time.sleep(300)
        job.task_hook = hang_hook

    job.run()
    print(json.dumps({'tag': tag, 'resumed': job.resumed,
                      'start_step': job.start_step,
                      'tasks': job.tasks_done}), flush=True)


if __name__ == '__main__':
    main()
