"""Data-parallel trainer process for the multi-process distributed test.

Reference pattern: test_dist_base.py:155-290 spawns trainer processes on
localhost and asserts dist loss ~= local loss.  TPU-native shape of the
same proof: each process joins the JAX distributed runtime through the
PADDLE_* env contract (parallel/multihost.py), the mesh spans every
process's virtual CPU devices, and ONE SPMD program trains over the
global batch with compiler-inserted gradient all-reduces — no pserver,
no send/recv ops.

Every process generates the identical global batch (same seed) and
contributes its addressable shard; rank 0's losses are the result.
Prints one JSON line: {"pid": N, "losses": [...]}.
"""
import json
import os


def main():
    # mirror tests/conftest.py: a 2-virtual-device CPU backend, named
    # before jax is imported
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        ' --xla_force_host_platform_device_count=2').strip()
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel.multihost import init_distributed_env

    nproc, pid = init_distributed_env()
    assert len(jax.devices()) == 2 * nproc, (
        'global device view must span all processes: %d devices, %d procs' %
        (len(jax.devices()), nproc))

    steps = int(os.environ.get('DIST_TEST_STEPS', '5'))
    mode = os.environ.get('DIST_TEST_MODE', 'dp')

    if mode == 'dp_sp':
        # cross-process SEQUENCE parallelism: the 'sp' axis spans devices
        # in DIFFERENT processes, so ring attention's lax.ppermute
        # rotations of K/V blocks cross the process boundary — the
        # multi-host long-context story (SURVEY §5.7)
        _run_dp_sp(jax, np, fluid, pid, steps)
        return
    if mode == 'pp':
        # cross-process PIPELINE parallelism: stages live in different
        # processes; every activation hop (and its backward transpose)
        # is a ppermute across the process boundary
        _run_pp(jax, np, pid, steps)
        return

    batch = int(os.environ.get('DIST_TEST_BATCH', '32'))
    rng = np.random.RandomState(42)
    from paddle_tpu.models import mnist
    model = mnist.build(nn_type='mlp', lr=0.01)
    model['startup'].random_seed = 7
    model['main'].random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    mesh = None
    if mode == 'dp_tp':
        # cross-process dp x tp: the tp axis spans devices living in
        # DIFFERENT processes, so the classifier matmul's collectives
        # cross the process boundary (VERDICT r2 next-#5)
        from paddle_tpu import parallel
        devs = jax.devices()
        mesh = parallel.make_mesh({'dp': len(devs) // 2, 'tp': 2}, devs)
        fc_w = model['main'].all_parameters()[-2]
        parallel.shard(fc_w, None, 'tp')
    losses = []
    with fluid.scope_guard(scope):
        exe.run(model['startup'])
        pe = fluid.ParallelExecutor(loss_name=model['loss'].name,
                                    main_program=model['main'],
                                    scope=scope, mesh=mesh)
        # one fixed global batch, every step: the loss must fall (overfit)
        # and every process feeds the identical global array, each
        # materializing only its addressable shard
        img = rng.standard_normal((batch, 784)).astype('float32')
        label = rng.randint(0, 10, (batch, 1)).astype('int64')
        for _ in range(steps):
            loss_v, = pe.run([model['loss']],
                             feed={'img': img, 'label': label})
            losses.append(float(np.asarray(loss_v).flatten()[0]))
    print(json.dumps({'pid': pid, 'losses': losses}), flush=True)


def _run_dp_sp(jax, np, fluid, pid, steps):
    from paddle_tpu import parallel
    from paddle_tpu.models import transformer

    devs = jax.devices()
    mesh = parallel.make_mesh({'dp': 1, 'sp': len(devs)}, devs)
    T = 32  # fixed GLOBAL length: 1-proc shards 16 tokens, 2-proc 8
    model = transformer.build(src_vocab=64, trg_vocab=64, max_len=T,
                              n_layer=1, n_head=2, d_model=16, d_ff=32)
    model['startup'].random_seed = 7
    model['main'].random_seed = 7
    rng = np.random.RandomState(42)
    batch = 2
    src = rng.randint(2, 64, (batch, T)).astype('int64')
    trg = np.concatenate([np.zeros((batch, 1), 'int64'), src[:, :-1]],
                         axis=1)
    scope = fluid.core.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        pe = fluid.ParallelExecutor(loss_name=model['loss'].name,
                                    main_program=model['main'],
                                    scope=scope, mesh=mesh)
        for _ in range(steps):
            loss_v, = pe.run([model['loss'].name],
                             feed={'src_ids': src, 'trg_ids': trg,
                                   'lbl_ids': src})
            losses.append(float(np.asarray(loss_v).flatten()[0]))
    print(json.dumps({'pid': pid, 'losses': losses}), flush=True)


# shared between _run_pp and the sequential oracle in
# test_dist_train.py::test_two_process_pipeline_parallel — edit here,
# both sides follow
PP_CFG = {'d': 16, 'm': 8, 'mb': 2, 'seed': 7, 'lr': 0.2}


def _run_pp(jax, np, pid, steps):
    """4-stage GPipe over a 'pp' axis spanning both processes (2 local
    devices each): deterministic init so the test can oracle the loss
    trajectory against the sequential composition."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import parallel

    devs = jax.devices()
    mesh = parallel.make_mesh({'pp': len(devs)}, devs)
    d, m, mb = PP_CFG['d'], PP_CFG['m'], PP_CFG['mb']
    rng = np.random.RandomState(PP_CFG['seed'])
    stages = [{'w': (rng.standard_normal((d, d)) / 4.0).astype('float32'),
               'b': np.zeros((d,), 'float32')} for _ in range(len(devs))]
    stacked_host = {
        k: np.stack([s[k] for s in stages]) for k in ('w', 'b')}
    x = rng.standard_normal((m, mb, d)).astype('float32')

    def put(a, spec):
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(a.shape, sh,
                                            lambda idx: a[idx])

    params = {k: put(v, P('pp')) for k, v in stacked_host.items()}
    xg = put(x, P())
    fn = parallel.pipeline_spmd(
        lambda p, h: jnp.tanh(h @ p['w'] + p['b']), mesh)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(
            lambda q: jnp.mean(fn(q, xg) ** 2))(p)
        return loss, jax.tree_util.tree_map(
            lambda a, b: a - PP_CFG['lr'] * b, p, g)

    losses = []
    for _ in range(steps):
        loss, params = step(params)
        losses.append(float(loss))
    print(json.dumps({'pid': pid, 'losses': losses}), flush=True)


if __name__ == '__main__':
    main()
