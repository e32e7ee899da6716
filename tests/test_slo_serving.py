"""Tail-latency serving SLOs (ISSUE 8): deadline-aware lot formation
(EDF within priority classes) + typed shedding, per-model overload
admission control, the open-loop load harness, and the fleet prewarm
catalog.

The acceptance invariants covered here on CPU: a past-deadline request
resolves to DeadlineExceededError with a 'shed' trace stage (never
served late, never hung); FIFO mode and SLO-less traffic behave exactly
as before; the registry refuses overload at the door with a typed
retry-after hint; and a fresh registry restored via prewarm(catalog)
serves the recorded rung cross-product with compile_count delta 0.
The paired goodput gate itself lives in tools/perf_gate.py ('slo') and
its CPU smoke in test_perf_gate.py.
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.serving.errors import DeadlineExceededError, \
    EngineClosedError, OverloadedError


# ---- batcher scheduling (no jit, no engine) ----------------------------


def _req(sig='s', rows=1, priority=0, deadline_ms=None):
    return serving.InferenceRequest({'x': rows}, rows, sig,
                                    priority=priority,
                                    deadline_ms=deadline_ms)


def test_edf_orders_priority_then_deadline():
    """Lot heads form highest-priority-first, earliest-deadline within
    a class; undeadlined requests order after deadlined peers."""
    mb = serving.MicroBatcher(max_batch_size=8, scheduling='edf')
    r_plain = mb.submit(_req())
    r_late_dl = mb.submit(_req(priority=1, deadline_ms=5000))
    r_soon_dl = mb.submit(_req(priority=1, deadline_ms=500))
    r_low_dl = mb.submit(_req(priority=0, deadline_ms=100))
    lot = mb.next_lot(force=True)
    assert lot == [r_soon_dl, r_late_dl, r_low_dl, r_plain]


def test_priority_aging_promotes_starving_request():
    """The starvation escape hatch (ISSUE 11 satellite; ROADMAP item 5
    leftover): a low-priority request that has waited k full aging
    windows competes as priority + k at lot formation, so it eventually
    outranks FRESH high-priority arrivals — while WITHOUT the knob
    strict priority starves it forever."""
    aged = _req(priority=0)
    aged.enqueue_t -= 1.0  # has starved ~10 aging windows
    fresh = _req(priority=2)

    mb = serving.MicroBatcher(max_batch_size=1, scheduling='edf',
                              priority_aging_s=0.1)
    mb.submit(fresh)
    mb.submit(aged)
    lot = mb.next_lot(force=True)
    assert lot == [aged], 'the aged request must head the lot'
    assert mb.next_lot(force=True) == [fresh]
    # real priority is untouched — only the scheduling order moved
    assert aged.priority == 0

    # the counterfactual: strict priority (no aging) starves it
    aged2 = _req(priority=0)
    aged2.enqueue_t -= 1.0
    fresh2 = _req(priority=2)
    mb2 = serving.MicroBatcher(max_batch_size=1, scheduling='edf')
    mb2.submit(fresh2)
    mb2.submit(aged2)
    assert mb2.next_lot(force=True) == [fresh2]


def test_priority_aging_never_inverts_edf_within_a_class():
    """Aging targets CROSS-class starvation only: a class alone in the
    queue keeps pure EDF order — an aged undeadlined request must not
    cut ahead of a deadline-imminent peer of its own class (promotion
    engages only below the highest pending real class)."""
    aged = _req(priority=0)            # undeadlined, waited many windows
    aged.enqueue_t -= 1.0
    urgent = _req(priority=0, deadline_ms=5000)
    mb = serving.MicroBatcher(max_batch_size=1, scheduling='edf',
                              priority_aging_s=0.1)
    mb.submit(aged)
    mb.submit(urgent)
    assert mb.next_lot(force=True) == [urgent], \
        'EDF within the class must hold when nothing outranks it'


def test_priority_aging_rejects_fifo_contradiction():
    """MicroBatcher mirrors ServingConfig: fifo never sorts, so a
    silently-ignored aging window is a typed error, not a no-op."""
    with pytest.raises(ValueError):
        serving.MicroBatcher(scheduling='fifo', priority_aging_s=1.0)


def test_priority_aging_below_window_keeps_strict_priority():
    """Inside the first aging window nothing is promoted: fresh
    high-priority traffic schedules first exactly as before."""
    low = _req(priority=0)
    high = _req(priority=1)
    mb = serving.MicroBatcher(max_batch_size=1, scheduling='edf',
                              priority_aging_s=30.0)
    mb.submit(low)
    mb.submit(high)
    assert mb.next_lot(force=True) == [high]


def test_priority_aging_config_plumbs_and_validates():
    """ServingConfig(priority_aging_ms=) reaches the engine's batcher;
    non-positive windows and the fifo contradiction are typed errors."""
    cfg = serving.ServingConfig(priority_aging_ms=250.0)
    assert cfg.priority_aging_s == 0.25
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        y = fluid.layers.fc(x, size=2)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    eng = serving.InferenceEngine(
        prog.clone(for_test=True), feed_names=['x'], fetch_list=[y],
        scope=scope, config=cfg)
    try:
        assert eng._batcher.priority_aging_s == 0.25
    finally:
        eng.stop()
    with pytest.raises(ValueError):
        serving.ServingConfig(priority_aging_ms=0)
    with pytest.raises(ValueError):
        serving.ServingConfig(priority_aging_ms=-5)
    with pytest.raises(ValueError):
        serving.ServingConfig(scheduling='fifo', priority_aging_ms=100)
    with pytest.raises(ValueError):
        serving.MicroBatcher(priority_aging_s=0)


def test_edf_degrades_to_fifo_without_slo_fields():
    """No priorities, no deadlines: EDF is arrival order exactly."""
    mb = serving.MicroBatcher(max_batch_size=8, scheduling='edf')
    reqs = [mb.submit(_req()) for _ in range(5)]
    assert mb.next_lot(force=True) == reqs


def test_fifo_mode_never_sheds_or_reorders():
    """The baseline engine: strict arrival order, expired requests are
    still served (late) — exactly what the slo gate pairs against."""
    mb = serving.MicroBatcher(max_batch_size=8, scheduling='fifo')
    r_first = mb.submit(_req(deadline_ms=0.001))
    r_urgent = mb.submit(_req(priority=5, deadline_ms=10))
    time.sleep(0.002)  # r_first is now past its deadline
    lot = mb.next_lot(force=True)
    assert lot == [r_first, r_urgent]
    assert not r_first.done()


def test_edf_sheds_expired_and_unmeetable_requests():
    """Expired requests shed typed; so do requests whose deadline is
    still ahead but inside the service-estimate horizon (they could
    only be served late — shedding them first is the whole point)."""
    mb = serving.MicroBatcher(max_batch_size=8, scheduling='edf',
                              service_estimate_fn=lambda: 0.05)
    expired = mb.submit(_req(deadline_ms=0.001))
    unmeetable = mb.submit(_req(deadline_ms=20))  # < 50ms horizon
    viable = mb.submit(_req(deadline_ms=5000))
    time.sleep(0.002)
    lot = mb.next_lot(force=True)
    assert lot == [viable]
    for r in (expired, unmeetable):
        with pytest.raises(DeadlineExceededError):
            r.result(1)
    assert viable.deadline_t is not None and not viable.done()


def test_shed_by_class_sheds_lowest_class_first():
    """Load-shedding by CLASS (ISSUE 12 satellite; ROADMAP item 5
    leftover): capacity for ~one request within the shared deadline —
    the default per-request horizon would keep BOTH (each fits alone),
    serving the low-class one at the high-class one's expense.  With
    shed_by_class the backlog accumulates in scheduling order, so the
    LOW class's deadlined request (served last) is the one that sheds;
    the high class survives."""
    est = lambda r: 0.06
    # the counterfactual: per-request horizon admits both
    mb0 = serving.MicroBatcher(max_batch_size=1, scheduling='edf',
                               service_estimate_for=est)
    hi0 = mb0.submit(_req(sig='a', priority=1, deadline_ms=100))
    lo0 = mb0.submit(_req(sig='b', priority=0, deadline_ms=100))
    mb0.next_lot(force=True)
    assert not lo0.done() or lo0._error is None
    # shed_by_class: the low class's finish = est(hi) + est(lo) > 100ms
    mb = serving.MicroBatcher(max_batch_size=1, scheduling='edf',
                              service_estimate_for=est,
                              shed_by_class=True)
    hi = mb.submit(_req(sig='a', priority=1, deadline_ms=100))
    lo = mb.submit(_req(sig='b', priority=0, deadline_ms=100))
    lot = mb.next_lot(force=True)
    assert lot == [hi] and not hi.done()
    with pytest.raises(DeadlineExceededError):
        lo.result(1)


def test_shed_by_class_preserves_same_class_edf_order():
    """The pinned counterfactual: within ONE class shed_by_class never
    reorders — survivors form lots in exactly the EDF order the
    default scheduler produces, and the cumulative walk dooms the
    LATEST-deadline request of the class first (it is served last)."""
    est = lambda r: 0.04
    mb = serving.MicroBatcher(max_batch_size=8, scheduling='edf',
                              service_estimate_for=est,
                              shed_by_class=True)
    r_soon = mb.submit(_req(sig='s', deadline_ms=100))
    r_mid = mb.submit(_req(sig='s', deadline_ms=200))
    r_late = mb.submit(_req(sig='s', deadline_ms=130))
    # cumulative: soon at 40ms ok, mid at 80ms ok, late (EDF-sorted
    # between them: 130ms deadline) at 80ms ok... walk order is EDF:
    # soon(100), late(130), mid(200) — cum 40/80/120ms, all meetable
    lot = mb.next_lot(force=True)
    assert lot == [r_soon, r_late, r_mid]
    # now an unmeetable tail: same class, latest deadline — it sheds,
    # the earlier-deadline peers keep their exact EDF order
    mb2 = serving.MicroBatcher(max_batch_size=8, scheduling='edf',
                               service_estimate_for=est,
                               shed_by_class=True)
    a = mb2.submit(_req(sig='s', deadline_ms=50))
    b = mb2.submit(_req(sig='s', deadline_ms=90))
    c = mb2.submit(_req(sig='s', deadline_ms=100))  # cum 120ms > 100
    lot2 = mb2.next_lot(force=True)
    assert lot2 == [a, b]
    with pytest.raises(DeadlineExceededError):
        c.result(1)


def test_shed_by_class_config_plumbs_and_validates():
    cfg = serving.ServingConfig(shed_by_class=True)
    assert cfg.shed_by_class
    with pytest.raises(ValueError, match='shed_by_class'):
        serving.ServingConfig(scheduling='fifo', shed_by_class=True)
    with pytest.raises(ValueError, match='shed_by_class'):
        serving.MicroBatcher(scheduling='fifo', shed_by_class=True)
    # the engine hands the knob to its batcher
    import paddle_tpu.fluid as fluid
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        pred = fluid.layers.fc(x, 4)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['x'], fetch_list=[pred],
        place=fluid.CPUPlace(), scope=scope,
        config=serving.ServingConfig(shed_by_class=True))
    try:
        assert eng._batcher.shed_by_class
    finally:
        eng.stop()


def test_age_stats():
    mb = serving.MicroBatcher(max_batch_size=8)
    assert mb.age_stats() is None
    mb.submit(_req())
    time.sleep(0.005)
    mb.submit(_req())
    st = mb.age_stats()
    assert st['depth'] == 2
    assert st['oldest_s'] >= st['mean_s'] > 0
    mb.next_lot(force=True)
    assert mb.age_stats() is None


def test_closed_batcher_raises_typed():
    mb = serving.MicroBatcher()
    mb.close()
    with pytest.raises(EngineClosedError):
        mb.submit(_req())


def test_scheduling_validation():
    with pytest.raises(ValueError, match='scheduling'):
        serving.MicroBatcher(scheduling='lifo')
    with pytest.raises(ValueError, match='scheduling'):
        serving.ServingConfig(scheduling='priority')
    with pytest.raises(ValueError, match='admit_queue_depth'):
        serving.ServingConfig(admit_queue_depth=0)
    with pytest.raises(ValueError, match='admit_queue_age_ms'):
        serving.ServingConfig(admit_queue_age_ms=0)


# ---- engine-level shed + queue-age metrics -----------------------------


def _scorer(seed=7):
    """Tiny MLP inference program + a scope holding its params."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [6])
        h = fluid.layers.fc(x, 8, act='relu')
        pred = fluid.layers.fc(h, 4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return prog.clone(for_test=True), pred, scope


@pytest.fixture(scope='module')
def scorer_engine():
    prog, pred, scope = _scorer()
    eng = serving.InferenceEngine(
        prog, feed_names=['x'], fetch_list=[pred], scope=scope,
        config=serving.ServingConfig(max_batch_size=8, max_wait_ms=1,
                                     bucket_sizes=[8])).start()
    rng = np.random.RandomState(0)
    eng.infer({'x': rng.rand(3, 6).astype('float32')}, timeout=60)
    yield eng, rng
    eng.stop()


def test_engine_sheds_expired_request_typed_and_staged(scorer_engine):
    """The end-to-end shed contract: typed error on the future, 'shed'
    stage in the trace breakdown, the metrics counter — and the engine
    keeps serving afterwards."""
    eng, rng = scorer_engine
    shed_before = eng.metrics()['shed']
    fut = eng.submit({'x': rng.rand(2, 6).astype('float32')},
                     deadline_ms=0.001)
    with pytest.raises(DeadlineExceededError) as ei:
        fut.result(10)
    assert ei.value.trace_id == fut.trace_id
    bd = fut.breakdown()
    assert 'shed' in bd['stages_ms']
    m = eng.metrics()
    assert m['shed'] == shed_before + 1
    # shed is not an error: the dispatch path never saw the request
    assert m['errors'] == 0
    # and the engine still serves
    out, = eng.infer({'x': rng.rand(2, 6).astype('float32')},
                     timeout=60)
    assert np.isfinite(out).all()


def test_within_deadline_result_identical_to_undeadlined(scorer_engine):
    """A deadline that is met must not change the answer: same feed
    with and without an SLO is bitwise-equal (scheduling may only
    change WHEN/WHETHER, never WHAT)."""
    eng, rng = scorer_engine
    feed = {'x': rng.rand(4, 6).astype('float32')}
    plain, = eng.infer(dict(feed), timeout=60)
    slo_fut = eng.submit(dict(feed), priority=1, deadline_ms=10_000)
    slo, = slo_fut.result(60)
    assert np.array_equal(plain, slo)
    assert 'shed' not in slo_fut.breakdown()['stages_ms']


def test_queue_age_rides_engine_metrics():
    """The satellite: a stalled queue is visible in metrics() without
    waiting for the watchdog dump.  A never-started engine's queue
    holds whatever is enqueued (no worker, no inline drain), which is
    exactly the stall the gauges must surface."""
    prog, pred, scope = _scorer(seed=31)
    eng = serving.InferenceEngine(
        prog, feed_names=['x'], fetch_list=[pred], scope=scope)
    assert eng.metrics()['queue_age_oldest_s'] is None  # idle queue
    eng._batcher.submit(_req())
    time.sleep(0.01)
    eng._batcher.submit(_req())
    m = eng.metrics()
    assert m['queue_depth'] == 2
    assert m['queue_age_oldest_s'] >= 0.01
    assert m['queue_age_oldest_s'] >= m['queue_age_mean_s'] > 0
    for r in eng._batcher.next_lot(force=True):
        r.set_result(None)  # drain by hand: nothing must dangle
    assert eng.metrics()['queue_age_oldest_s'] is None
    eng.stop()


# ---- registry overload admission ---------------------------------------


def test_registry_overload_admission_typed_with_retry_hint():
    prog, pred, scope = _scorer(seed=11)
    reg = serving.ModelRegistry(config=serving.ServingConfig(
        max_batch_size=8, max_wait_ms=1, bucket_sizes=[8],
        admit_queue_depth=2, admit_queue_age_ms=60_000))
    reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope)
    rng = np.random.RandomState(0)

    def feed():
        return {'x': rng.rand(2, 6).astype('float32')}

    with reg:
        reg.infer('m', feed(), timeout=60)  # warm, queue empty
        eng = reg._entry('m').engine
        with eng.paused():  # the worker holds still: the queue grows
            held = [reg.submit('m', feed()) for _ in range(2)]
            with pytest.raises(OverloadedError) as ei:
                reg.submit('m', feed())
            assert ei.value.model == 'm'
            assert ei.value.queue_depth >= 2
            assert ei.value.retry_after_s > 0
        for f in held:  # the pause lifted: queued work still serves
            assert np.isfinite(f.result(60)[0]).all()
        # below the watermark again: admitted
        reg.infer('m', feed(), timeout=60)
        m = reg.metrics()
        assert m['overload_rejects'] == 1
        assert m['models']['m']['router']['overload_rejects'] == 1
        # HBM admission_rejects is a DIFFERENT counter and stayed 0
        assert m['admission_rejects'] == 0
    reg.stop()


# ---- unload/submit races (the satellite's typed-error bar) -------------


def test_unload_vs_submit_race_typed_never_hangs():
    """submit() racing unload(): every future resolves (result or a
    typed error) inside the timeout — nothing hangs, nothing leaks an
    untyped crash.  (The threaded hammer lives in test_model_registry's
    race coverage; this is the deterministic core.)"""
    prog, pred, scope = _scorer(seed=13)
    reg = serving.ModelRegistry()
    reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope)
    rng = np.random.RandomState(0)
    with reg:
        fut = reg.submit('m', {'x': rng.rand(2, 6).astype('float32')})
        reg.unload('m')  # drains the queue: the future must resolve
        assert np.isfinite(fut.result(30)[0]).all()
        with pytest.raises(KeyError):
            reg.submit('m', {'x': rng.rand(2, 6).astype('float32')})
        # direct engine submit after stop: typed, synchronous
        eng = serving.InferenceEngine(
            prog, feed_names=['x'], fetch_list=[pred], scope=scope)
        eng.stop()
        with pytest.raises(EngineClosedError):
            eng.submit({'x': rng.rand(1, 6).astype('float32')})
    reg.stop()


# ---- prewarm catalog ---------------------------------------------------


@pytest.fixture
def jax_cache_config(monkeypatch):
    """The test owns jax's compile-cache directory: the environment's
    placement (if any) is set aside, the config starts at None, and
    whatever the test did is undone after."""
    import jax
    monkeypatch.delenv(fluid.flags.COMPILE_CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update('jax_compilation_cache_dir', None)
    yield
    fluid.FLAGS.xla_compile_cache_dir = ''
    jax.config.update('jax_compilation_cache_dir', before)


def test_warm_catalog_prewarm_compile_delta_zero(tmp_path,
                                                 jax_cache_config):
    """The ISSUE 8 prewarm acceptance: warm() records the compile
    catalog inside the persistent compile cache directory (here placed
    by FLAGS_xla_compile_cache_dir); a FRESH registry restored via
    prewarm(catalog) serves the recorded rung cross-product with
    compile_count delta 0 on first traffic."""
    cache = str(tmp_path / 'xla-cache')
    fluid.FLAGS.xla_compile_cache_dir = cache
    try:
        prog, pred, scope = _scorer(seed=17)
        reg = serving.ModelRegistry(config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=1, bucket_sizes=[4, 8]))
        reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
                 scope=scope)
        with reg:
            served = reg.warm('m', bucket_ladder=[4, 8])
        assert served == 2
        path = reg.warm_catalog_path()
        assert path and os.path.exists(path)
        assert reg.warm_catalog() == [
            {'model': 'm', 'bucket_ladder': [4, 8], 'trailing': None,
             'decode_prefill': None}]
        reg.stop()

        # a fresh process's registry: same weights, EMPTY executor
        # caches — prewarm must rebuild every recorded signature
        reg2 = serving.ModelRegistry(config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=1, bucket_sizes=[4, 8]))
        reg2.load('m', program=prog, feed_names=['x'],
                  fetch_list=[pred], scope=scope)
        with reg2:
            out = reg2.prewarm()  # reads the catalog next to the cache
            assert out['replayed'] == 1 and out['served'] == 2
            assert out['skipped_models'] == []
            before = reg2.metrics()['models']['m'][
                'executor_compile_count']
            rng = np.random.RandomState(3)
            # first real traffic ACROSS the recorded rung ladder
            for rows in (2, 4, 5, 8):
                out_v, = reg2.infer(
                    'm', {'x': rng.rand(rows, 6).astype('float32')},
                    timeout=60)
                assert np.isfinite(out_v).all()
            after = reg2.metrics()['models']['m'][
                'executor_compile_count']
            assert after - before == 0, (before, after)
        reg2.stop()
    finally:
        fluid.FLAGS.xla_compile_cache_dir = ''


def test_warm_catalog_follows_env_placed_cache(tmp_path, monkeypatch,
                                               jax_cache_config):
    """The catalog is found from the directory jax's cache REALLY uses,
    not from our flag: with the cache placed from outside (JAX reads
    JAX_COMPILATION_CACHE_DIR into its config at import — mimicked
    here) and the flag empty, warm() persists next to it, creating the
    directory JAX itself only creates at its first write."""
    import jax
    outside = str(tmp_path / 'placed-outside')
    monkeypatch.setenv(fluid.flags.COMPILE_CACHE_ENV, outside)
    jax.config.update('jax_compilation_cache_dir', outside)
    fluid.FLAGS.xla_compile_cache_dir = str(tmp_path / 'ignored')
    assert fluid.flags.compile_cache_dir() == outside
    prog, pred, scope = _scorer(seed=23)
    reg = serving.ModelRegistry(config=serving.ServingConfig(
        max_batch_size=4, max_wait_ms=1, bucket_sizes=[4]))
    reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope)
    with reg:
        reg.warm('m', bucket_ladder=[4])
    reg.stop()
    assert reg.warm_catalog_path() == os.path.join(
        outside, 'serving_warm_catalog.json')
    assert os.path.exists(reg.warm_catalog_path())
    assert not os.path.exists(str(tmp_path / 'ignored'))


def test_warm_catalog_merges_on_staged_restart(tmp_path, jax_cache_config):
    """A restart that stages only SOME models must not delete the
    others' replay records when its own warms persist: the catalog
    write merges with what is on disk."""
    import json
    cache = str(tmp_path / 'xla-cache')
    fluid.FLAGS.xla_compile_cache_dir = cache
    try:
        p1, f1, s1 = _scorer(seed=37)
        p2, f2, s2 = _scorer(seed=38)
        reg = serving.ModelRegistry(config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=1, bucket_sizes=[4]))
        reg.load('m1', program=p1, feed_names=['x'], fetch_list=[f1],
                 scope=s1)
        reg.load('m2', program=p2, feed_names=['x'], fetch_list=[f2],
                 scope=s2)
        with reg:
            reg.warm('m1', bucket_ladder=[4])
            reg.warm('m2', bucket_ladder=[4])
        path = reg.warm_catalog_path()
        reg.stop()
        # staged restart: only m1 comes back up, prewarms, re-warms
        reg2 = serving.ModelRegistry(config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=1, bucket_sizes=[4]))
        reg2.load('m1', program=p1, feed_names=['x'], fetch_list=[f1],
                  scope=s1)
        with reg2:
            out = reg2.prewarm()
            assert out['skipped_models'] == ['m2']
            reg2.warm('m1', bucket_ladder=[4])
        reg2.stop()
        models = {r['model'] for r in json.load(open(path))}
        assert models == {'m1', 'm2'}  # m2's record survived
    finally:
        fluid.FLAGS.xla_compile_cache_dir = ''


def test_prewarm_skips_unloaded_models_and_validates(tmp_path,
                                                     jax_cache_config):
    prog, pred, scope = _scorer(seed=19)
    reg = serving.ModelRegistry()
    reg.load('m', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope)
    with reg:
        out = reg.prewarm(catalog=[
            {'model': 'ghost', 'bucket_ladder': [4]},
            {'model': 'm', 'bucket_ladder': [4], 'trailing': None,
             'decode_prefill': None},
        ])
        assert out == {'served': 1, 'replayed': 1,
                       'skipped_models': ['ghost']}
        with pytest.raises(ValueError, match='catalog'):
            reg.prewarm()  # no cache dir, no default path
    reg.stop()


# ---- per-signature service profile (ISSUE 9) ---------------------------


def test_service_profile_estimates_and_floor():
    """ServiceTimeProfile unit contract: per-key min-of-window
    estimates, cost seeds that never override observations, a global
    floor over all keys, and the bounded-signature eviction."""
    p = serving.ServiceTimeProfile(window=3, max_signatures=2)
    assert p.estimate('a') is None and p.floor() is None
    assert p.seed('a', 0.050)
    assert p.estimate('a') == pytest.approx(0.050)
    # a compile-heavy first wall does not poison the estimate: the
    # seed stays the min
    p.observe('a', 0.400)
    assert p.estimate('a') == pytest.approx(0.050)
    p.observe('a', 0.010)
    assert p.estimate('a') == pytest.approx(0.010)
    # a second seed (or one after observations) is refused
    assert not p.seed('a', 0.001)
    p.observe('b', 0.200)
    assert p.floor() == pytest.approx(0.010)
    # window rolls: three more walls push the 10ms one out
    for w in (0.030, 0.040, 0.050):
        p.observe('a', w)
    assert p.estimate('a') == pytest.approx(0.030)
    # bounded: a third signature evicts the least recently observed
    p.observe('c', 0.001)
    assert p.signatures() == 2
    snap = p.snapshot()
    assert len(snap) == 2
    for rec in snap.values():
        assert set(rec) == {'est_ms', 'ewma_ms', 'seeded', 'observed'}
    with pytest.raises(ValueError):
        serving.ServiceTimeProfile(window=0)
    with pytest.raises(ValueError):
        serving.ServiceTimeProfile(alpha=0.0)


def test_engine_shed_horizon_is_per_signature():
    """The MicroBatcher horizon path provably uses per-signature
    estimates (the ISSUE 9 acceptance pin): with a slow signature
    profiled at 100ms and a fast one at 1ms, a 50ms-deadline
    slow-signature request sheds AT LOT FORMATION while the same-
    deadline fast one is admitted — under the old global min-wall
    horizon (1ms) both would have been admitted."""
    shed = []
    prof = serving.ServiceTimeProfile()
    for _ in range(3):
        prof.observe('fast', 0.001)
        prof.observe('slow', 0.100)

    def est(req):
        e = prof.estimate(req.sig)
        return 3.0 * (e if e is not None else (prof.floor() or 0.0))

    mb = serving.MicroBatcher(max_batch_size=8, scheduling='edf',
                              on_shed=shed.append,
                              service_estimate_for=est)
    fast = mb.submit(_req(sig='fast', deadline_ms=50))
    slow = mb.submit(_req(sig='slow', deadline_ms=50))
    # an UNSEEN signature falls back to the global floor (the old
    # estimator): admitted under a 50ms deadline
    unseen = mb.submit(_req(sig='new', deadline_ms=50))
    lot = mb.next_lot(timeout=0, force=True)
    assert shed == [slow]
    assert fast in lot and slow not in lot
    lots = [lot]
    while True:
        more = mb.next_lot(timeout=0, force=True)
        if not more:
            break
        lots.append(more)
    assert any(unseen in l for l in lots)
    # the engine wires exactly this path: structural pin
    import inspect
    src = inspect.getsource(
        __import__('paddle_tpu.serving.engine',
                   fromlist=['engine']).InferenceEngine._service_estimate)
    assert 'profile.estimate(req.sig)' in src
    engine_init = inspect.getsource(
        __import__('paddle_tpu.serving.engine',
                   fromlist=['engine']).InferenceEngine.__init__)
    assert 'service_estimate_for' in engine_init


def test_adaptive_admission_scales_watermarks(monkeypatch):
    """ServingConfig(adaptive_admission=True): the registry's depth
    watermark scales by the measured drain/arrival ratio — a
    keeping-up engine (drain >= arrival) absorbs a burst the static
    mark would have rejected; one falling behind rejects at HALF the
    static depth.  Rates and queue depth are pinned directly (no
    timing races)."""
    prog, pred, scope = _scorer(seed=31)
    reg = serving.ModelRegistry()
    eng = reg.load('m', program=prog, feed_names=['x'],
                   fetch_list=[pred], scope=scope,
                   config=serving.ServingConfig(
                       admit_queue_depth=4, adaptive_admission=True))
    try:
        monkeypatch.setattr(eng._batcher, 'depth', lambda: 5)
        monkeypatch.setattr(eng._batcher, 'oldest_age', lambda: 0.0)
        # drain 2x arrival -> effective depth 8: depth 5 admits
        monkeypatch.setattr(eng, 'rate_stats', lambda: {
            'arrival_req_s': 10.0, 'drain_req_s': 20.0})
        reg._check_admission('m')  # no raise
        # arrival 2x drain -> effective depth 2: depth 5 rejects
        monkeypatch.setattr(eng, 'rate_stats', lambda: {
            'arrival_req_s': 20.0, 'drain_req_s': 10.0})
        with pytest.raises(OverloadedError):
            reg._check_admission('m')
        # unmeasurable rates: the static mark stands (depth 5 >= 4)
        monkeypatch.setattr(eng, 'rate_stats', lambda: {
            'arrival_req_s': None, 'drain_req_s': None})
        with pytest.raises(OverloadedError):
            reg._check_admission('m')
    finally:
        reg.stop()
    # the contradiction guard: adapting nothing is a typed error
    with pytest.raises(ValueError, match='adaptive_admission'):
        serving.ServingConfig(adaptive_admission=True)


# ---- decode-lane deadline budget ---------------------------------------


def test_generate_deadline_sheds_at_step_boundary():
    """A generation request whose deadline passes is shed at a decode
    step boundary (slot released, typed error, 'shed' stage) while an
    undeadlined peer generates to completion."""
    from paddle_tpu.models import seq2seq
    m = seq2seq.build_step_decode(
        src_dict_dim=40, trg_dict_dim=30, embedding_dim=8,
        encoder_size=12, decoder_size=12, max_len=10)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe,
        config=serving.ServingConfig(max_batch_size=4, max_wait_ms=1,
                                     decode_slots=2, decode_steps=2),
        generation=spec).start()
    rng = np.random.RandomState(0)

    def prompt(l):
        return fluid.create_lod_tensor(
            rng.randint(2, 40, size=(l, 1)).tolist(), [[l]])

    # warm (compiles prefill + the decode scan)
    ref = eng.generate({'src_word_id': prompt(3)}, timeout=120)
    assert len(ref) >= 1
    dead = eng.submit_generate({'src_word_id': prompt(4)},
                               deadline_ms=0.001)
    live = eng.submit_generate({'src_word_id': prompt(5)})
    with pytest.raises(DeadlineExceededError) as ei:
        dead.result(60)
    assert ei.value.where in ('admit', 'decode', 'queue')
    assert 'shed' in dead.breakdown()['stages_ms']
    out = live.result(60)
    assert len(out) >= 1  # the live generation was untouched
    m2 = eng.metrics()
    assert m2['shed'] >= 1
    assert m2['decode']['free_slots'] == eng._decode_cache.slots
    eng.stop()


# ---- the open-loop harness ---------------------------------------------


def test_loadgen_stream_is_deterministic_and_report_consistent():
    prog, pred, scope = _scorer(seed=23)
    eng = serving.InferenceEngine(
        prog, feed_names=['x'], fetch_list=[pred], scope=scope,
        config=serving.ServingConfig(max_batch_size=8, max_wait_ms=1,
                                     bucket_sizes=[8])).start()
    rng0 = np.random.RandomState(0)
    eng.infer({'x': rng0.rand(2, 6).astype('float32')}, timeout=60)

    def feed_fn(rng):
        return {'x': rng.rand(2, 6).astype('float32')}

    classes = [serving.TrafficClass(feed_fn, deadline_ms=10_000),
               serving.TrafficClass(feed_fn, priority=1, weight=0.5)]
    g1 = serving.OpenLoopLoadGen(eng, classes, rate=500.0,
                                 n_requests=24, seed=4)
    g2 = serving.OpenLoopLoadGen(eng, classes, rate=500.0,
                                 n_requests=24, seed=4)
    a1, p1, f1, j1 = g1._draw()
    a2, p2, f2, j2 = g2._draw()
    assert np.array_equal(a1, a2) and np.array_equal(p1, p2)
    assert j1 is None and j2 is None  # retry jitter only when enabled
    assert all(np.array_equal(x1['x'], x2['x'])
               for x1, x2 in zip(f1, f2))
    rep = g1.run()
    assert rep['offered'] == 24
    assert (rep['completed'] + rep['shed'] + rep['overload_rejected'] +
            rep['errors']) == rep['offered']
    assert rep['goodput'] + rep['late'] == rep['completed']
    assert rep['goodput'] > 0
    assert rep['p50_ms'] is not None and rep['p999_ms'] is not None
    eng.stop()

    with pytest.raises(ValueError, match='rate'):
        serving.OpenLoopLoadGen(eng, classes, rate=0, n_requests=1)
    with pytest.raises(ValueError, match='n_requests'):
        serving.OpenLoopLoadGen(eng, classes, rate=1.0)


@pytest.mark.slow
def test_sustained_open_loop_mixed_traffic_harness():
    """The sustained harness (slow-marked): a registry fleet — one
    forward model with SLOs + admission watermarks, one generation
    model — under seconds of open-loop Poisson load.  Asserts the
    report's goodput/tail numbers exist, typed outcomes partition the
    offered stream, and the registry counters stay coherent."""
    from paddle_tpu.models import seq2seq
    prog, pred, scope = _scorer(seed=29)
    reg = serving.ModelRegistry()
    reg.load('fwd', program=prog, feed_names=['x'], fetch_list=[pred],
             scope=scope,
             config=serving.ServingConfig(
                 max_batch_size=8, max_wait_ms=1, bucket_sizes=[8],
                 admit_queue_depth=64))
    m = seq2seq.build_step_decode(
        src_dict_dim=40, trg_dict_dim=30, embedding_dim=8,
        encoder_size=12, decoder_size=12, max_len=8)
    exe = fluid.Executor(fluid.CPUPlace())
    gscope = fluid.core.Scope()
    with fluid.scope_guard(gscope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    reg.load('gen', program=m['prefill'],
             fetch_list=m['prefill_fetches'], scope=gscope,
             executor=exe,
             generation=serving.GenerationSpec.from_model(m),
             config=serving.ServingConfig(max_batch_size=4,
                                          max_wait_ms=1,
                                          decode_slots=4,
                                          decode_steps=2))
    grng = np.random.RandomState(0)

    def fwd_feed(rng):
        return {'x': rng.rand(2, 6).astype('float32')}

    def gen_feed(rng):
        l = int(rng.randint(2, 6))
        return {'src_word_id': fluid.create_lod_tensor(
            rng.randint(2, 40, size=(l, 1)).tolist(), [[l]])}

    with reg:
        reg.infer('fwd', fwd_feed(grng), timeout=120)
        reg.generate('gen', gen_feed(grng), timeout=120)
        rep = serving.OpenLoopLoadGen(
            reg,
            [serving.TrafficClass(fwd_feed, model='fwd',
                                  deadline_ms=250),
             serving.TrafficClass(fwd_feed, model='fwd', priority=1,
                                  deadline_ms=250, weight=0.25),
             serving.TrafficClass(gen_feed, model='gen',
                                  kind='generate', weight=0.2,
                                  deadline_ms=2_000, max_len=8)],
            rate=120.0, duration_s=3.0, seed=1).run()
        assert rep['offered'] >= 300
        assert (rep['completed'] + rep['shed'] +
                rep['overload_rejected'] + rep['errors']) == \
            rep['offered']
        assert rep['errors'] == 0
        assert rep['goodput'] > 0 and rep['p99_ms'] is not None
        metrics = reg.metrics()
        assert metrics['models']['fwd']['errors'] == 0
        assert metrics['models']['gen']['errors'] == 0
        shed_counted = sum(metrics['models'][n]['shed']
                           for n in ('fwd', 'gen'))
        assert shed_counted + metrics['overload_rejects'] >= \
            rep['shed'] + rep['overload_rejected']
    reg.stop()


def test_loadgen_retries_overloaded_once_honoring_hint():
    """ISSUE 15 satellite: retry_overloaded honors the typed
    OverloadedError's retry_after_s hint with exactly ONE bounded
    re-submit per rejected request — retried requests that then land
    count as completions (retry_success), a request overloaded on its
    retry too stays rejected, and nothing retries with the flag
    off."""
    import time as _time
    from paddle_tpu.serving import OverloadedError

    class _Fut(object):
        latency_s = 0.001

        def result(self, timeout=None):
            return ['ok']

        def breakdown(self):
            return {}

    class _Target(object):
        """Rejects every request's FIRST submission (with a 10ms
        retry-after hint); the retry succeeds — except when
        always_reject, where every submission is rejected."""

        def __init__(self, always_reject=False):
            self.attempts = {}
            self.times = {}
            self.always_reject = always_reject

        def submit(self, feed, priority=0, deadline_ms=None):
            k = id(feed)
            n = self.attempts[k] = self.attempts.get(k, 0) + 1
            self.times.setdefault(k, []).append(_time.time())
            if n == 1 or self.always_reject:
                raise OverloadedError('m', 3, 0.0, retry_after_s=0.01)
            return _Fut()

    def feed_fn(rng):
        return {'x': rng.rand(1)}

    n = 12
    tgt = _Target()
    rep = serving.OpenLoopLoadGen(
        tgt, [serving.TrafficClass(feed_fn)], rate=400.0,
        n_requests=n, seed=3, retry_overloaded=True).run()
    assert rep['overload_retries'] == n, rep
    assert rep['retry_success'] == n, rep
    assert rep['completed'] == n and rep['overload_rejected'] == 0
    # ONE retry per request, never more
    assert all(v == 2 for v in tgt.attempts.values()), tgt.attempts
    # the hint was honored: every retry fired >= retry_after_s after
    # its rejection (plus the small seeded jitter)
    for times in tgt.times.values():
        assert times[1] - times[0] >= 0.01 - 1e-4, times

    # still overloaded on the retry: stays rejected, retry bounded
    tgt2 = _Target(always_reject=True)
    rep2 = serving.OpenLoopLoadGen(
        tgt2, [serving.TrafficClass(feed_fn)], rate=400.0,
        n_requests=n, seed=3, retry_overloaded=True,
        keep_records=True).run()
    assert rep2['overload_retries'] == n and rep2['retry_success'] == 0
    assert rep2['overload_rejected'] == n, rep2
    assert all(v == 2 for v in tgt2.attempts.values())
    assert all(r.get('retried') for r in rep2['records']), \
        rep2['records'][:2]

    # flag off: the hint is recorded, nothing retries
    tgt3 = _Target()
    rep3 = serving.OpenLoopLoadGen(
        tgt3, [serving.TrafficClass(feed_fn)], rate=400.0,
        n_requests=n, seed=3).run()
    assert rep3['overload_retries'] == 0 and rep3['retry_success'] == 0
    assert rep3['overload_rejected'] == n
    assert all(v == 1 for v in tgt3.attempts.values())
