"""Elastic training jobs (ISSUE 13): async sharded checkpoints
(manifest commit, retention, crashed-write hygiene), kill-and-replace
resume parity (bitwise, SGD), ack-after-dispatch-sync, and dp
shrink/grow across simulated host loss on the 8-dev virtual mesh
(reference: go/master/service.go timeouts + stateless trainers;
PAPER.md §EDL master / checkpointing pserver)."""

import os
import pickle
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import (AsyncShardedCheckpoint,
                                    CheckpointWriteError,
                                    ElasticTrainJob, Master)
from paddle_tpu.fluid.dataflow import FeedPipelineError
from paddle_tpu.runtime.native import RecordIOWriter

DIM = 8
RECORDS_PER_TASK = 4
N_TASKS = 6


# ---------------------------------------------------------------------
# AsyncShardedCheckpoint
# ---------------------------------------------------------------------

def _arrays(seed):
    rng = np.random.RandomState(seed)
    return {'w': rng.standard_normal((4, 3)).astype('float32'),
            'b': rng.standard_normal((3, )).astype('float32')}


def test_checkpoint_roundtrip_and_retention(tmp_path):
    store = AsyncShardedCheckpoint(str(tmp_path), keep=2)
    for step in range(1, 6):
        store.save(step, _arrays(step), extras={'step': step,
                                                'rng': ['exe', 0, step]},
                   wait=True)
    step, arrays, extras = store.load()
    assert step == 5 and extras['rng'] == ['exe', 0, 5]
    np.testing.assert_array_equal(arrays['w'], _arrays(5)['w'])
    # retention: exactly `keep` manifests survive, and every shard file
    # on disk is referenced by a live manifest (no orphans)
    manifests = [f for f in os.listdir(str(tmp_path))
                 if f.startswith('MANIFEST-')]
    assert len(manifests) == 2, manifests
    shard_dirs = sorted(os.listdir(str(tmp_path / 'shards')))
    assert shard_dirs == ['%012d' % 4, '%012d' % 5], shard_dirs
    store.close()


def test_checkpoint_crashed_write_hygiene(tmp_path):
    """A crashed write (tmp shard dir + manifest tmp, no committed
    manifest) and an orphaned shard dir are both swept on open — no
    shard file without a live manifest survives."""
    store = AsyncShardedCheckpoint(str(tmp_path), keep=3)
    store.save(7, _arrays(7), wait=True)
    store.close()
    # simulate a crash mid-write and a crashed prune
    os.makedirs(str(tmp_path / 'shards' / '000000000042.tmp'))
    with open(str(tmp_path / 'shards' / '000000000042.tmp' / 'w'),
              'wb') as f:
        f.write(b'partial')
    os.makedirs(str(tmp_path / 'shards' / '000000000041'))
    with open(str(tmp_path / 'MANIFEST-000000000042.json.tmp'),
              'w') as f:
        f.write('{')
    store2 = AsyncShardedCheckpoint(str(tmp_path), keep=3)
    assert sorted(os.listdir(str(tmp_path / 'shards'))) == \
        ['%012d' % 7]
    assert not [f for f in os.listdir(str(tmp_path))
                if f.endswith('.tmp')]
    # the committed manifest still loads
    step, arrays, _ = store2.load()
    assert step == 7
    np.testing.assert_array_equal(arrays['b'], _arrays(7)['b'])
    store2.close()


def test_checkpoint_writer_error_surfaces(tmp_path):
    """A failed background write is a typed error on wait() — a dead
    writer must never masquerade as durability."""
    store = AsyncShardedCheckpoint(str(tmp_path), keep=2)
    # a var name with a path separator points the shard write at a
    # nonexistent subdirectory — the writer fails
    store.save(1, {'nested/name': np.zeros(2, 'float32')})
    with pytest.raises(CheckpointWriteError):
        store.wait()
    assert store.metrics()['errors'] == 1
    store.close()


def test_checkpoint_cross_job_gc_spares_live_jobs(tmp_path):
    """Cross-job retention (ISSUE 17 satellite): gc(root, keep_jobs=)
    removes only DEAD job dirs beyond the bound, oldest-manifest
    first.  A LIVE job (open store, ACTIVE marker present) is never
    touched — its manifests survive byte-for-byte — and neither is a
    dir that isn't a checkpoint store at all."""
    root = str(tmp_path)
    dirs = {n: os.path.join(root, n) for n in 'abcd'}
    stores = {}
    for i, n in enumerate('abcd'):
        stores[n] = AsyncShardedCheckpoint(dirs[n], keep=2, sync=True)
        stores[n].save(10 + i, _arrays(i), wait=True)
        # pin distinct manifest mtimes: a oldest ... d newest
        t = 1_000_000_000 + 100 * i
        os.utime(os.path.join(
            dirs[n], 'MANIFEST-%012d.json' % (10 + i)), (t, t))
    for n in 'bcd':
        stores[n].close()           # dead jobs; 'a' stays live
    os.makedirs(os.path.join(root, 'misc'))
    with open(os.path.join(root, 'misc', 'notes.txt'), 'w') as f:
        f.write('not a checkpoint dir')
    before_a = sorted(os.listdir(dirs['a']))

    removed = AsyncShardedCheckpoint.gc(root, keep_jobs=1)
    # dead jobs b, c pruned (oldest first); newest dead d kept
    assert removed == [dirs['b'], dirs['c']]
    assert not os.path.exists(dirs['b'])
    assert sorted(os.listdir(dirs['a'])) == before_a  # live: untouched
    assert os.path.exists(os.path.join(root, 'misc', 'notes.txt'))
    # the surviving dead job still loads (reopening re-marks it live,
    # so close again before the final sweep)
    reopened = AsyncShardedCheckpoint(dirs['d'], keep=2, sync=True)
    step, arrays, _ = reopened.load()
    assert step == 13
    np.testing.assert_array_equal(arrays['w'], _arrays(3)['w'])
    reopened.close()
    # the live store keeps working after gc, then counts as dead once
    # closed
    stores['a'].save(20, _arrays(9), wait=True)
    stores['a'].close()
    with pytest.raises(ValueError, match='keep_jobs'):
        AsyncShardedCheckpoint.gc(root, keep_jobs=-1)
    removed2 = AsyncShardedCheckpoint.gc(root, keep_jobs=0)
    assert dirs['a'] in removed2
    assert sorted(os.listdir(root)) == ['misc']


def test_checkpoint_gc_keep_hours_age_sweep(tmp_path):
    """Age-based retention (ISSUE 19 satellite): gc(keep_hours=)
    removes a DEAD store whose newest manifest is older than the
    cutoff even when the keep_jobs count would retain it; young dead
    stores and live stores survive, and the count-based cut still
    applies on top."""
    import time
    root = str(tmp_path)
    dirs = {n: os.path.join(root, n) for n in 'abc'}
    for i, n in enumerate('abc'):
        s = AsyncShardedCheckpoint(dirs[n], keep=2, sync=True)
        s.save(10 + i, _arrays(i), wait=True)
        s.close()
    # 'a': ancient (two days old); 'b', 'c': fresh
    old = time.time() - 48 * 3600
    os.utime(os.path.join(dirs['a'], 'MANIFEST-%012d.json' % 10),
             (old, old))
    with pytest.raises(ValueError, match='keep_hours'):
        AsyncShardedCheckpoint.gc(root, keep_hours=-1)
    # keep_jobs=3 alone would retain everything; the age sweep still
    # removes the ancient store and ONLY it
    removed = AsyncShardedCheckpoint.gc(root, keep_jobs=3,
                                        keep_hours=24)
    assert removed == [dirs['a']]
    assert os.path.exists(dirs['b']) and os.path.exists(dirs['c'])
    # count-based cut composes: keep_jobs=1 prunes 'b' (older of the
    # two fresh stores) regardless of age
    removed2 = AsyncShardedCheckpoint.gc(root, keep_jobs=1,
                                         keep_hours=24)
    assert removed2 == [dirs['b']]
    # a LIVE ancient store is never age-swept
    live = AsyncShardedCheckpoint(dirs['c'], keep=2, sync=True)
    mani = os.path.join(dirs['c'], 'MANIFEST-%012d.json' % 12)
    os.utime(mani, (old, old))
    assert AsyncShardedCheckpoint.gc(root, keep_jobs=0,
                                     keep_hours=24) == []
    live.close()
    assert AsyncShardedCheckpoint.gc(root, keep_jobs=0,
                                     keep_hours=24) == [dirs['c']]


# ---------------------------------------------------------------------
# ElasticTrainJob
# ---------------------------------------------------------------------

def _write_dataset(path, n_tasks=N_TASKS, records_per_task=RECORDS_PER_TASK):
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path)
    for _ in range(records_per_task * n_tasks):
        x = rng.standard_normal(DIM).astype('float32')
        y = np.array([x.sum() * 0.5], 'float32')
        w.write(pickle.dumps((x, y)))
    w.close()


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[DIM])
        y = fluid.layers.data('y', shape=[1])
        hid = fluid.layers.fc(x, size=4, act='tanh')
        pred = fluid.layers.fc(hid, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    return main, startup, loss


def _batch_fn(records):
    rows = [pickle.loads(r) for r in records]
    return {'x': np.stack([r[0] for r in rows]).astype('float32'),
            'y': np.stack([r[1] for r in rows]).astype('float32')}


def _final_params(job):
    return {n: np.asarray(job._scope.find_var(n).value())
            for n in job._persistable_names()
            if job._scope.find_var(n) is not None
            and job._scope.find_var(n).value() is not None}


def _run_reference(tmp_path, **job_kw):
    """The uninterrupted run the elastic variants are pinned against."""
    data = str(tmp_path / 'ref.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=120)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)
    job = ElasticTrainJob(_build, master, str(tmp_path / 'ref_ckpt'),
                          _batch_fn, worker_id='ref', **job_kw)
    job.run()
    params = _final_params(job)
    losses = list(job.losses)
    job.close()
    master.close()
    return params, losses


class _Killed(Exception):
    pass


def test_kill_resume_bitwise_parity_cpu(tmp_path):
    """The acceptance pin: a worker killed holding a claim; the claim's
    lease times out and re-dispatches; the replacement resumes from the
    newest manifest, REPLAYS NOTHING, and final params are BITWISE
    identical to an uninterrupted run (SGD)."""
    ref_params, ref_losses = _run_reference(tmp_path)

    data = str(tmp_path / 'train.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=1.0)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)

    def kill_hook(tid, task, ordinal):
        if ordinal == N_TASKS - 1:  # die holding the LAST task's claim
            raise _Killed('simulated host loss holding tid %d' % tid)

    a = ElasticTrainJob(_build, master, str(tmp_path / 'ckpt'),
                        _batch_fn, worker_id='A', task_hook=kill_hook)
    with pytest.raises(FeedPipelineError) as ei:
        a.run()
    assert isinstance(ei.value.__cause__, _Killed)
    # the dead worker's claim is still leased out — acked only after a
    # delivered dispatch, so the crashed claim was NEVER acked
    todo, pending, done, discarded = master.counts()
    assert pending == 1 and done == N_TASKS - 1, (todo, pending, done)

    b = ElasticTrainJob(_build, master, str(tmp_path / 'ckpt'),
                        _batch_fn, worker_id='B')
    b.run()
    # B had to wait out the dead worker's lease: the in-flight task
    # lease timed out and was RE-dispatched (go/master/service.go:140)
    assert b.resumed and b.start_step == N_TASKS - 1
    assert len(b.tasks_done) == 1, b.tasks_done  # replays nothing
    assert master.counts() == (0, 0, N_TASKS, 0)
    assert b.metrics()['tasks_done'] == 1
    b_params = _final_params(b)
    assert set(b_params) == set(ref_params)
    for n, ref in ref_params.items():
        assert np.array_equal(ref, b_params[n]), \
            'param %s diverged (max %g)' % (
                n, np.abs(ref - b_params[n]).max())
    a.close()
    b.close()
    master.close()


def test_ack_only_after_dispatch_sync(tmp_path):
    """A worker crashing before its FIRST dispatch delivers leaves
    every claim unacked: task_finished rides the pipeline's
    on_delivered hook, never the claim."""
    data = str(tmp_path / 'd.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=60)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)

    def hook(tid, task, ordinal):
        if ordinal == 0:
            raise _Killed('die before anything dispatches')

    job = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                          _batch_fn, worker_id='A', task_hook=hook)
    with pytest.raises(FeedPipelineError):
        job.run()
    todo, pending, done, discarded = master.counts()
    assert done == 0 and pending == 1, (todo, pending, done)
    job.close()
    master.close()


def test_resume_restores_master_cursor_for_whole_job_restart(tmp_path):
    """The manifest carries the master task cursor: a WHOLE-job restart
    (fresh master, restore_master=True) resumes the queue at the acked
    frontier and finishes the pass without replaying done tasks."""
    data = str(tmp_path / 'd.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=60)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)

    def hook(tid, task, ordinal):
        if ordinal == 3:
            raise _Killed('whole-job loss after 3 acked tasks')

    a = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                        _batch_fn, worker_id='A', task_hook=hook)
    with pytest.raises(FeedPipelineError):
        a.run()
    master.close()

    # a FRESH master with no store: the manifest's cursor blob is the
    # only memory of the pass
    master2 = Master(chunk_timeout_secs=1.0)
    b = ElasticTrainJob(_build, master2, str(tmp_path / 'ck'),
                        _batch_fn, worker_id='B', restore_master=True)
    b.run()
    assert b.resumed and b.start_step == 3
    todo, pending, done, discarded = master2.counts()
    assert done == N_TASKS and todo == 0 and pending == 0
    # the restored cursor returned the crashed claim to todo — B
    # trained the remaining 3 tasks exactly once
    assert len(b.tasks_done) == 3, b.tasks_done
    b.close()
    master2.close()


@pytest.fixture
def eight_devices():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip('needs the 8-device virtual mesh')


def _mesh_job_kw():
    return dict(mesh_for=lambda n: {'dp': 2 * n}, heartbeat_interval=0.2)


def test_dp_shrink_4_to_2_on_host_loss(tmp_path, eight_devices):
    """Simulated host loss mid-pass: the peer's lease expires, the
    membership epoch bumps, and the job re-forms its mesh dp 4 -> 2 at
    a dispatch boundary, re-shards live state, and finishes with
    allclose-identical params to an uninterrupted dp=4 run (the only
    difference is the cross-extent reduction order)."""
    ref_params, _ = _run_reference(
        tmp_path, mesh_for=lambda n: {'dp': 4})

    data = str(tmp_path / 'train.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=120, worker_lease_secs=1.0)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)
    master.register_worker('peer')  # the host that will be "lost"

    def hook(tid, task, ordinal):
        if ordinal == 2:
            time.sleep(1.5)  # outlive the peer's lease mid-pass

    job = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                          _batch_fn, worker_id='A', task_hook=hook,
                          **_mesh_job_kw())
    job.run()
    m = job.metrics()
    assert m['resizes'] >= 1 and m['dp_extent'] == 2, m
    assert m['membership_epoch'] >= 2, m
    assert job.step == N_TASKS  # every task trained exactly once
    assert master.counts() == (0, 0, N_TASKS, 0)
    got = _final_params(job)
    for n, ref in ref_params.items():
        np.testing.assert_allclose(ref, got[n], rtol=1e-5, atol=1e-6,
                                   err_msg='param %s diverged' % n)
    job.close()
    master.close()


def test_dp_grow_2_to_4_on_join(tmp_path, eight_devices):
    """A replacement/extra host joins mid-pass: epoch bumps, the job
    grows dp 2 -> 4 and continues with allclose-identical params."""
    ref_params, _ = _run_reference(
        tmp_path, mesh_for=lambda n: {'dp': 4})

    data = str(tmp_path / 'train.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=120, worker_lease_secs=600)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)

    def hook(tid, task, ordinal):
        if ordinal == 2:
            master.register_worker('late-peer')
            time.sleep(0.8)  # let the heartbeat observe the join

    job = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                          _batch_fn, worker_id='G', task_hook=hook,
                          **_mesh_job_kw())
    job.run()
    m = job.metrics()
    assert m['resizes'] >= 1 and m['dp_extent'] == 4, m
    assert job.step == N_TASKS
    assert master.counts() == (0, 0, N_TASKS, 0)
    got = _final_params(job)
    for n, ref in ref_params.items():
        np.testing.assert_allclose(ref, got[n], rtol=1e-5, atol=1e-6,
                                   err_msg='param %s diverged' % n)
    job.close()
    master.close()


def test_mesh_kill_resume_parity(tmp_path, eight_devices):
    """Satellite 3's mesh variant: killed-mid-task on the dp mesh, the
    replacement resumes the SHARDED state from the manifest at the same
    extent — bitwise (same mesh, same reduction order)."""
    ref_params, _ = _run_reference(
        tmp_path, mesh_for=lambda n: {'dp': 2})

    data = str(tmp_path / 'train.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=1.0)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)

    def kill_hook(tid, task, ordinal):
        if ordinal == N_TASKS - 1:
            raise _Killed('die holding the last claim')

    a = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                        _batch_fn, worker_id='A', task_hook=kill_hook,
                        mesh_for=lambda n: {'dp': 2})
    with pytest.raises(FeedPipelineError):
        a.run()
    b = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                        _batch_fn, worker_id='B',
                        mesh_for=lambda n: {'dp': 2})
    b.run()
    assert b.resumed and b.start_step == N_TASKS - 1
    assert master.counts() == (0, 0, N_TASKS, 0)
    got = _final_params(b)
    for n, ref in ref_params.items():
        assert np.array_equal(ref, got[n]), \
            'param %s diverged (max %g)' % (n,
                                            np.abs(ref - got[n]).max())
    a.close()
    b.close()
    master.close()


def test_job_gauges_ride_the_metrics_stack(tmp_path):
    """Job-level gauges (tasks done/requeued, checkpoint age/bytes/
    stalls, membership epoch) surface through metrics() and register
    with the profiler metrics-source registry (PR 6 stack)."""
    from paddle_tpu.fluid import profiler as _profiler
    data = str(tmp_path / 'd.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=60)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)
    job = ElasticTrainJob(_build, master, str(tmp_path / 'ck'),
                          _batch_fn, worker_id='A',
                          watchdog_stall_s=30.0, name='elastic-gauges')
    job.run()
    m = job.metrics()
    for key in ('tasks_done', 'tasks_failed', 'tasks_requeued',
                'membership_epoch', 'checkpoint_age_s',
                'checkpoint_bytes', 'checkpoint_stalls', 'dp_extent',
                'resumed', 'step'):
        assert key in m, key
    assert m['tasks_done'] == N_TASKS
    assert m['checkpoint_bytes'] > 0
    assert m['membership_epoch'] >= 1
    # registered as a metrics source under the job's name (the same
    # registry the profiler sidecar collects)
    collected = _profiler._collect_metrics()
    assert any('elastic-gauges' in k for k in collected), \
        sorted(collected)
    job.close()
    master.close()


def test_checkpointing_job_rejects_deep_pipeline(tmp_path):
    from paddle_tpu.distributed import ElasticJobError
    with pytest.raises(ElasticJobError, match='pipeline_depth'):
        ElasticTrainJob(_build, None, str(tmp_path), _batch_fn,
                        pipeline_depth=2, checkpoint_every=1)


def test_parse_elastic_env_contract():
    """The PADDLE_* env contract extends to elastic workers: trainer id
    -> worker id, master endpoint from either spelling."""
    from paddle_tpu.parallel.multihost import parse_elastic_env
    wid, ep = parse_elastic_env({'PADDLE_TRAINER_ID': '3',
                                 'PADDLE_MASTER_ENDPOINT': 'h:1234'})
    assert (wid, ep) == ('trainer-3', 'h:1234')
    wid, ep = parse_elastic_env({'WORKER_TAG': 'B',
                                 'MASTER_ENDPOINT': 'h:9'})
    assert (wid, ep) == ('B', 'h:9')
    wid, ep = parse_elastic_env({})
    assert wid == 'trainer-0' and ep is None


def test_trainer_checkpoints_ride_the_manifest_store(tmp_path):
    """fluid.Trainer's CheckpointConfig path now rides
    AsyncShardedCheckpoint: saves commit manifests (bounded retention),
    resume picks the newest manifest, and a LEGACY <dir>/<serial>/
    checkpoint still resumes — then is pruned once a manifest commits."""
    ckpt = str(tmp_path / 'ck')

    def train_fn():
        x = fluid.layers.data('x', shape=[4])
        y = fluid.layers.data('y', shape=[1])
        pred = fluid.layers.fc(x, size=1)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))

    def opt_fn():
        return fluid.optimizer.SGD(0.1)

    rng = np.random.RandomState(0)
    batches = [[(rng.standard_normal(4).astype('float32'),
                 np.array([1.0], 'float32')) for _ in range(4)]
               for _ in range(6)]

    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, step_interval=2,
                                 max_num_checkpoints=2)
    with fluid.unique_name.guard():
        t = fluid.Trainer(train_fn, opt_fn, checkpoint_config=cfg)

    def drain(event):
        # the writer is latest-wins: a save landing while the one before
        # is still being written replaces it.  Waiting at each step's end
        # lets all three saves commit, so retention is what prunes.
        if isinstance(event, fluid.EndStepEvent):
            t._ckpt_store.wait()

    t.train(1, drain, reader=lambda: iter(batches), feed_order=['x', 'y'])
    manifests = sorted(f for f in os.listdir(ckpt)
                       if f.startswith('MANIFEST-'))
    # retention == max_num_checkpoints: of serials 1, 3, 5 the last two
    assert manifests == ['MANIFEST-000000000003.json',
                         'MANIFEST-000000000005.json']

    # resume: a fresh Trainer loads the newest manifest
    cfg2 = fluid.CheckpointConfig(checkpoint_dir=ckpt, step_interval=2,
                                  max_num_checkpoints=2)
    with fluid.unique_name.guard():
        t2 = fluid.Trainer(train_fn, opt_fn, checkpoint_config=cfg2)
    assert cfg2.load_serial is not None
    store = AsyncShardedCheckpoint(ckpt, keep=2)
    _step, arrays, _extras = store.load()
    got = np.asarray(t2.scope.find_var('fc_0.w_0').value())
    np.testing.assert_array_equal(arrays['fc_0.w_0'], got)
    store.close()

    # legacy serial-dir layout still resumes, and is dropped once the
    # new-format manifest commits
    legacy = str(tmp_path / 'legacy')
    os.makedirs(os.path.join(legacy, '7'))
    from paddle_tpu.fluid import proto_serde
    w = np.full((4, 1), 3.5, 'float32')
    with open(os.path.join(legacy, '7', 'fc_0.w_0'), 'wb') as f:
        f.write(proto_serde.serialize_lod_tensor(w))
    with open(os.path.join(legacy, '7', 'fc_0.b_0'), 'wb') as f:
        f.write(proto_serde.serialize_lod_tensor(
            np.zeros((1, ), 'float32')))
    with open(os.path.join(legacy, '7', 'learning_rate_0'), 'wb') as f:
        f.write(proto_serde.serialize_lod_tensor(
            np.asarray(0.1, 'float32')))
    cfg3 = fluid.CheckpointConfig(checkpoint_dir=legacy,
                                  step_interval=1,
                                  max_num_checkpoints=2)
    with fluid.unique_name.guard():
        t3 = fluid.Trainer(train_fn, opt_fn, checkpoint_config=cfg3)
    np.testing.assert_array_equal(
        np.asarray(t3.scope.find_var('fc_0.w_0').value()), w)
    t3.train(1, lambda e: None, reader=lambda: iter(batches),
             feed_order=['x', 'y'])
    assert any(f.startswith('MANIFEST-') for f in os.listdir(legacy))
    assert not os.path.isdir(os.path.join(legacy, '7'))


# ---------------------------------------------------------------------
# resilient control plane (ISSUE 15)
# ---------------------------------------------------------------------

def test_elastic_endpoints_lane_runs_and_exports_gauges(tmp_path):
    """endpoints= builds (and owns) a ResilientMasterClient: the job
    runs a normal fault-free pass over the RPC door, exports the
    retry-lane gauges, and close() releases the owned client."""
    from paddle_tpu.distributed import MasterServer, RetryPolicy
    data = str(tmp_path / 'ep.recordio')
    _write_dataset(data)
    master = Master(chunk_timeout_secs=60)
    master.set_dataset([data], records_per_task=RECORDS_PER_TASK)
    server = MasterServer(master)
    job = ElasticTrainJob(
        _build, None, str(tmp_path / 'job'), _batch_fn,
        worker_id='ep-w', checkpoint_every=2,
        endpoints=[server.endpoint],
        retry_policy=RetryPolicy(seed=3))
    try:
        job.run()
        meta = job.metrics()
        assert meta['tasks_done'] == N_TASKS, meta
        assert meta['tasks_deduped'] == 0, meta
        assert meta['master_retries'] == 0, meta
        assert meta['master_failovers'] == 0, meta
        assert meta['master_client']['calls'] > N_TASKS, meta
        assert meta['master_unreachable_s'] is None, meta
        assert master.counts() == (0, 0, N_TASKS, 0)
    finally:
        job.close()
        server.close()
        master.close()
    # close() closed the owned client: further calls are typed
    from paddle_tpu.distributed import MasterUnavailableError
    with pytest.raises(MasterUnavailableError):
        job.master.counts()


def test_elastic_endpoints_construction_contract(tmp_path):
    """master= XOR endpoints=; retry_policy= belongs to the
    endpoints= lane only."""
    from paddle_tpu.distributed import ElasticJobError, RetryPolicy
    m = Master(chunk_timeout_secs=60)
    with pytest.raises(ElasticJobError, match='not both'):
        ElasticTrainJob(_build, m, str(tmp_path), _batch_fn,
                        endpoints=['h:1'])
    with pytest.raises(ElasticJobError, match='retry_policy'):
        ElasticTrainJob(_build, m, str(tmp_path), _batch_fn,
                        retry_policy=RetryPolicy())
    with pytest.raises(ElasticJobError, match='master= or endpoints='):
        ElasticTrainJob(_build, None, str(tmp_path), _batch_fn)
    m.close()
