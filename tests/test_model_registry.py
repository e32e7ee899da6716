"""Multi-model serving (ISSUE 4 tentpole): ModelRegistry + HBM arbiter.

The acceptance invariant: a registry hosting >=3 models under an HBM
budget that FORCES eviction serves an interleaved request stream with
results bitwise-equal to per-model standalone engines — on CPU and the
8-device virtual mesh — while the eviction/reload/admission counters
and the per-model ':serving/<model>' timeline rows stay observable.
"""

import json
import os
import sys
import tempfile
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.serving.arbiter import HBMArbiter, program_seed_bytes

from helpers import assert_close_across_executables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _save_model(td, seed, width=16):
    """One save_inference_model dir: tiny MLP classifier, f32, seeded
    weights so every model is distinct and every comparison is exact."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [6])
        h = fluid.layers.fc(x, width, act='relu')
        pred = fluid.layers.fc(h, 4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(td, ['x'], [pred], exe,
                                      main_program=prog)
    return td


@pytest.fixture(scope='module')
def model_dirs(tmp_path_factory):
    root = tmp_path_factory.mktemp('models')
    dirs = {}
    for i, name in enumerate(['mA', 'mB', 'mC']):
        d = str(root / name)
        os.makedirs(d)
        _save_model(d, seed=i + 1)
        dirs[name] = d
    return dirs


def _seed_bytes(dirname):
    eng = serving.InferenceEngine.from_saved_model(dirname)
    try:
        return program_seed_bytes(eng._program, max(eng.buckets.sizes))
    finally:
        eng.stop()


def _standalone_results(dirname, reqs, parallel=False):
    eng = serving.InferenceEngine.from_saved_model(dirname,
                                                   parallel=parallel)
    try:
        return [eng.infer(r)[0] for r in reqs]
    finally:
        eng.stop()


# ---- the acceptance bar ------------------------------------------------

def test_interleaved_stream_under_forcing_budget_bitwise_cpu(model_dirs):
    """3 models under a budget sized for ~2: the interleaved stream
    forces evictions + transparent reloads, and every result is
    bitwise-equal to a standalone per-model engine.  Counters and the
    per-model ':serving/<model>' timeline rows are asserted."""
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        from timeline import Timeline
    finally:
        sys.path.pop(0)
    rng = np.random.RandomState(0)
    reqs = [{'x': rng.rand(n, 6).astype('float32')}
            for n in [3, 2, 5, 1, 4]]
    refs = {name: _standalone_results(d, reqs)
            for name, d in model_dirs.items()}

    seed = max(_seed_bytes(d) for d in model_dirs.values())
    reg = serving.ModelRegistry(hbm_budget_bytes=int(2.5 * seed))
    for name, d in model_dirs.items():
        reg.load(name, d)
    td = tempfile.mkdtemp()
    p = os.path.join(td, 'prof')
    with fluid.profiler.profiler('CPU', profile_path=p):
        with reg:
            for j, q in enumerate(reqs):
                for name in model_dirs:  # strict interleave A,B,C,...
                    out, = reg.infer(name, q, timeout=60)
                    assert np.array_equal(out, refs[name][j]), (name, j)
    m = reg.metrics()
    # the budget really forced arbitration, and reloads were transparent
    assert m['evictions'] >= 1, m
    assert m['reloads'] >= 1, m
    assert m['admission_rejects'] == 0
    assert all(m['models'][n]['router']['requests'] == len(reqs)
               for n in model_dirs)
    assert all(m['models'][n]['errors'] == 0 for n in model_dirs)
    # per-model spans landed in per-model timeline rows
    sidecar = json.load(open(p + '.events.json'))
    names = {e['name'] for e in sidecar['host_events']}
    for n in model_dirs:
        assert any(ev.startswith('serving/%s/dispatch' % n)
                   for ev in names), (n, names)
    trace = json.loads(Timeline({'t': sidecar}).generate_chrome_trace())
    rows = {e['args']['name'] for e in trace['traceEvents']
            if e['ph'] == 'M'}
    assert {'t:serving/%s' % n for n in model_dirs} <= rows, rows
    # the registry's own snapshot rode the sidecar too
    assert sidecar['metrics']['model-registry']['evictions'] >= 1
    reg.stop()


def test_interleaved_stream_under_forcing_budget_on_virtual_mesh(
        model_dirs):
    """The dp>1 half of the acceptance bar: a parallel registry on the
    8-device mesh under a forcing budget — interleaved results match
    standalone parallel engines bitwise (same executable on both
    sides), with >=1 eviction."""
    rng = np.random.RandomState(1)
    reqs = [{'x': rng.rand(n, 6).astype('float32')} for n in [5, 11, 3]]
    refs = {name: _standalone_results(d, reqs, parallel=True)
            for name, d in model_dirs.items()}
    seed = max(_seed_bytes(d) for d in model_dirs.values())
    reg = serving.ModelRegistry(hbm_budget_bytes=int(2.5 * seed),
                                parallel=True)
    for name, d in model_dirs.items():
        reg.load(name, d)
    with reg:
        for j, q in enumerate(reqs):
            for name in model_dirs:
                out, = reg.infer(name, q, timeout=120)
                assert np.array_equal(out, refs[name][j]), (name, j)
    m = reg.metrics()
    assert m['evictions'] >= 1 and m['admission_rejects'] == 0
    # every bucket each dp engine compiled is mesh-divisible
    for n in model_dirs:
        assert all(b % 8 == 0
                   for b in m['models'][n]['buckets']['active'])
    reg.stop()


# ---- arbiter: eviction round trip, admission, accounting ---------------

def test_eviction_reload_round_trip_is_bitwise(model_dirs):
    """evict_to_host() demotes every device buffer to a host ndarray
    and drops the executables; the next request transparently re-stages
    and recompiles, and its result is bitwise-equal to the unevicted
    run.  The scope's param VALUES survive the round trip bitwise."""
    eng = serving.InferenceEngine.from_saved_model(model_dirs['mA'])
    rng = np.random.RandomState(2)
    r = {'x': rng.rand(3, 6).astype('float32')}
    out_before, = eng.infer(r)
    assert eng.device_footprint() > 0  # params cached back on device
    params_before = {
        n: np.asarray(eng._scope.find_var(n).value())
        for n in eng._scope.local_var_names()
        if eng._scope.find_var(n).value() is not None}
    compiles_before = eng.metrics()['compiles']
    moved, dropped = eng.evict_to_host()
    assert moved > 0 and dropped >= 1
    assert eng.device_footprint() == 0  # nothing device-resident
    for n, v in params_before.items():
        after = np.asarray(eng._scope.find_var(n).value())
        assert np.array_equal(v, after), n  # bitwise demotion
    out_after, = eng.infer(r)
    assert np.array_equal(out_before, out_after)
    # the reload recompiled (the executables were really dropped) and
    # re-pinned the weights
    assert eng.metrics()['compiles'] > compiles_before
    assert eng.device_footprint() > 0
    eng.stop()


def test_admission_reject_raises_typed_error(model_dirs):
    """A model whose seed estimate can NEVER fit raises HBMBudgetError
    at load() with nothing loaded; the reject is counted."""
    reg = serving.ModelRegistry(hbm_budget_bytes=64)  # absurdly small
    with pytest.raises(serving.HBMBudgetError) as ei:
        reg.load('big', model_dirs['mA'])
    assert ei.value.model == 'big'
    assert ei.value.need_bytes > ei.value.budget_bytes == 64
    assert reg.models() == []
    assert reg.metrics()['admission_rejects'] == 1
    # and a second model colliding with a LOADED name is a clean error
    reg2 = serving.ModelRegistry()
    reg2.load('m', model_dirs['mA'])
    with pytest.raises(ValueError, match='already loaded'):
        reg2.load('m', model_dirs['mB'])
    reg2.unload('m')
    with pytest.raises(KeyError):
        reg2.unload('m')
    reg.stop()
    reg2.stop()


@pytest.mark.parametrize('parallel', [False, True],
                         ids=['cpu', 'mesh8'])
def test_budget_accounting_matches_live_buffer_stats(model_dirs,
                                                     parallel):
    """Once a model serves, its account is corrected from the seed
    estimate to LIVE jax buffer stats: status() hbm_bytes ==
    device_footprint() == the independently-summed nbytes of the
    scope's device arrays (global bytes on the 8-device mesh)."""
    import jax
    reg = serving.ModelRegistry(parallel=parallel)
    eng = reg.load('m', model_dirs['mB'])
    rng = np.random.RandomState(3)
    status = reg.status()['models']['m']
    assert status['account_source'] == 'seed'
    assert status['device_footprint'] == 0
    reg.infer('m', {'x': rng.rand(4, 6).astype('float32')}, timeout=60)
    reg._ensure_resident('m')  # the dispatch-time correction point
    status = reg.status()['models']['m']
    independent = sum(
        int(v.nbytes) for v in
        (eng._scope.find_var(n).value()
         for n in eng._scope.local_var_names())
        if isinstance(v, jax.Array))
    assert independent > 0
    assert status['device_footprint'] == independent
    assert status['hbm_bytes'] == independent
    assert status['account_source'] == 'live'
    reg.stop()


def _ctr_sharded_setup(vocab=4096, embed=16, budget_frac=True):
    """A small CTR model with its table row-sharded over 'mp' on the
    8-dev virtual mesh, plus a budget strictly between the per-device
    sharded layout and the full unsharded table — the ISSUE 11
    admission scenario."""
    import jax
    from paddle_tpu import parallel
    from paddle_tpu.models import ctr as ctr_model
    mesh = parallel.make_mesh({'dp': 4, 'mp': 2}, jax.devices()[:8])
    with fluid.unique_name.guard():
        # SGD: no [V, E] Adam moments in the shared scope — the
        # admission arithmetic below sizes the budget around ONE table
        m = ctr_model.build(sparse_dim=vocab, embed_size=embed,
                            hidden_sizes=(32, 16), is_sparse=True,
                            optimizer=fluid.optimizer.SGD(
                                learning_rate=0.05))
    parallel.shard(m['test'].global_block().var('ctr_embedding'),
                   'mp', None)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(m['startup'])
    table_bytes = vocab * embed * 4
    seed = program_seed_bytes(m['test'], 64)
    budget = int(seed - table_bytes + table_bytes // 2
                 + table_bytes // 4) if budget_frac else None
    return m, scope, mesh, table_bytes, budget


def _ctr_batch(rng, vocab, rows=16):
    return {'dense': rng.rand(rows, 13).astype('float32'),
            'sparse_ids': rng.randint(0, vocab, (rows, 26))
            .astype('int64'),
            'label': np.zeros((rows, 1), 'int64')}


def test_sharded_table_admits_past_per_device_budget():
    """The ISSUE 11 acceptance: a table sized past a single device's
    arbiter budget is admitted SHARDED (its account charged at the
    per-device shard bytes) but the identical unsharded program draws
    the typed HBMBudgetError — and the sharded model really serves."""
    from paddle_tpu.serving.registry import EMBED_TABLE_SUFFIX
    m, scope, mesh, table_bytes, budget = _ctr_sharded_setup()
    cfg = serving.ServingConfig(max_batch_size=64, max_wait_ms=2)
    reg = serving.ModelRegistry(mesh=mesh, hbm_budget_bytes=budget,
                                config=cfg)
    try:
        reg.load('ctr', program=m['test'], feed_names=m['feeds'],
                 fetch_list=[m['prediction']], scope=scope)
        acct = 'ctr%s:ctr_embedding' % EMBED_TABLE_SUFFIX
        snap = reg.arbiter.snapshot()
        assert acct in snap['accounts'], snap['accounts']
        # seeded at the PER-DEVICE share (mp=2): half the global table
        assert snap['accounts'][acct]['bytes'] == -(-table_bytes // 2)
        rng = np.random.RandomState(0)
        out, = reg.infer('ctr', _ctr_batch(rng, 4096), timeout=600)
        assert np.isfinite(np.asarray(out)).all()
        # the SECOND routed request's correction sees the staged
        # sharded layout: the account tracks LIVE per-device bytes and
        # stays under the global table size
        reg.infer('ctr', _ctr_batch(rng, 4096), timeout=600)
        snap = reg.arbiter.snapshot()
        assert snap['accounts'][acct]['source'] == 'live'
        assert snap['accounts'][acct]['bytes'] < table_bytes
    finally:
        reg.stop()
    # the unsharded counterfactual under the SAME budget: typed reject
    with fluid.unique_name.guard():
        from paddle_tpu.models import ctr as ctr_model
        plain = ctr_model.build(sparse_dim=4096, embed_size=16,
                                hidden_sizes=(32, 16), is_sparse=True,
                                optimizer=fluid.optimizer.SGD(
                                    learning_rate=0.05))
    scope2 = fluid.core.Scope()
    with fluid.scope_guard(scope2):
        fluid.Executor(fluid.CPUPlace()).run(plain['startup'])
    reg2 = serving.ModelRegistry(hbm_budget_bytes=budget, config=cfg)
    try:
        with pytest.raises(serving.HBMBudgetError):
            reg2.load('ctr', program=plain['test'],
                      feed_names=plain['feeds'],
                      fetch_list=[plain['prediction']], scope=scope2)
        assert 'ctr' not in reg2.status()['models']
    finally:
        reg2.stop()


def test_sharded_table_account_evicts_and_restages():
    """The table account demotes on its OWN (the shards copy back to
    one host ndarray; the model keeps serving by transparently
    re-staging), and unload drops every table account."""
    import jax
    from paddle_tpu.serving.registry import EMBED_TABLE_SUFFIX
    m, scope, mesh, table_bytes, _ = _ctr_sharded_setup(
        budget_frac=False)
    reg = serving.ModelRegistry(
        mesh=mesh,
        config=serving.ServingConfig(max_batch_size=64, max_wait_ms=2))
    acct = 'ctr%s:ctr_embedding' % EMBED_TABLE_SUFFIX
    try:
        reg.load('ctr', program=m['test'], feed_names=m['feeds'],
                 fetch_list=[m['prediction']], scope=scope)
        rng = np.random.RandomState(1)
        feed = _ctr_batch(rng, 4096)
        base, = reg.infer('ctr', feed, timeout=600)
        # demote just the table: the var leaves the device bitwise
        moved = reg.arbiter.evict(acct, reg._evict_to_host)
        assert moved > 0
        v = scope.find_var('ctr_embedding').value()
        assert not isinstance(v, jax.Array)
        assert not reg.arbiter.is_resident(acct)
        # the next routed request re-stages the table transparently and
        # answers bitwise-identically
        again, = reg.infer('ctr', feed, timeout=600)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(again))
        assert reg.arbiter.is_resident(acct)
        reg.unload('ctr')
        assert acct not in reg.arbiter.snapshot()['accounts']
    finally:
        reg.stop()


def test_model_name_colon_rejected():
    """':' is the arbiter account-suffix namespace (':decode-cache',
    ':embed-table:'): a model named into it would misroute eviction,
    so load() rejects it typed, like '/'."""
    reg = serving.ModelRegistry()
    try:
        with pytest.raises(ValueError):
            reg.load('a:embed-table:b', program=fluid.Program(),
                     fetch_list=[])
    finally:
        reg.stop()


def test_arbiter_lru_policy_and_set_budget():
    """Unit: LRU victim selection, reload counting, budget re-pointing."""
    arb = HBMArbiter(budget_bytes=100)
    evicted = []

    def evict_cb(name):
        evicted.append(name)
        return 40  # live bytes

    arb.admit('a', 40)
    arb.ensure('a', evict_cb)
    arb.admit('b', 40)
    arb.ensure('b', evict_cb)
    assert arb.resident_bytes() == 80 and not evicted
    arb.touch('a')  # b is now least-recently-used
    arb.admit('c', 40)
    arb.ensure('c', evict_cb)
    assert evicted == ['b']
    assert arb.evictions == 1 and arb.reloads == 0
    # b comes back: a (LRU) is the next victim; b's return is a RELOAD
    arb.ensure('b', evict_cb)
    assert evicted == ['b', 'a'] and arb.reloads == 1
    # a budget TIGHTENED below a model's own bytes: ensure evicts every
    # peer, still can't fit, and raises the typed reject
    arb.set_budget(30)
    with pytest.raises(serving.HBMBudgetError):
        arb.ensure('b', evict_cb)
    # widening the budget serves again
    arb.set_budget(1000)
    arb.ensure('b', evict_cb)
    assert arb.is_resident('b')
    snap = arb.snapshot()
    assert snap['admission_rejects'] == 1
    assert snap['accounts']['b']['source'] == 'live'


# ---- lifecycle: warm, thread-safety ------------------------------------

def test_warm_precompiles_the_bucket_ladder(model_dirs):
    """warm() pre-compiles every ladder entry with zero-filled
    requests: real traffic inside the ladder then adds NO compiles."""
    reg = serving.ModelRegistry(
        config=serving.ServingConfig(max_batch_size=8,
                                     bucket_sizes=[4, 8]))
    reg.load('m', model_dirs['mC'])
    assert reg.warm('m') == 2  # one request per ladder entry
    compiles = reg.metrics()['models']['m']['compiles']
    assert compiles >= 2
    rng = np.random.RandomState(4)
    for n in (3, 4, 7, 8):
        reg.infer('m', {'x': rng.rand(n, 6).astype('float32')},
                  timeout=60)
    assert reg.metrics()['models']['m']['compiles'] == compiles
    reg.stop()


def test_lifecycle_is_thread_safe_against_in_flight_requests(model_dirs):
    """load/unload/evict racing a concurrent request stream from N
    threads: every submitted future resolves (correct value or a clean
    'not loaded' error) and no worker dies."""
    seed = max(_seed_bytes(d) for d in model_dirs.values())
    reg = serving.ModelRegistry(hbm_budget_bytes=int(2.5 * seed))
    reg.load('mA', model_dirs['mA'])
    reg.load('mB', model_dirs['mB'])
    rng = np.random.RandomState(5)
    reqs = [{'x': rng.rand(2, 6).astype('float32')} for _ in range(8)]
    refs = {n: _standalone_results(model_dirs[n], reqs)
            for n in ('mA', 'mB')}
    errors = []

    def client(model):
        try:
            for j, q in enumerate(reqs):
                try:
                    out, = reg.infer(model, q, timeout=60)
                except KeyError:
                    continue  # unloaded mid-stream: a clean router error
                assert np.array_equal(out, refs[model][j]), (model, j)
        except Exception as e:  # surfaced below, not swallowed
            errors.append(repr(e))

    def churner():
        try:
            for _ in range(3):
                reg.load('mC', model_dirs['mC'])
                reg.infer('mC',
                          {'x': rng.rand(3, 6).astype('float32')},
                          timeout=60)
                reg.unload('mC')
        except Exception as e:
            errors.append(repr(e))

    with reg:
        threads = [threading.Thread(target=client, args=(m, ))
                   for m in ('mA', 'mB') for _ in range(2)]
        threads.append(threading.Thread(target=churner))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors, errors
    m = reg.metrics()
    assert all(m['models'][n]['errors'] == 0 for n in m['models'])
    reg.stop()


# ---- concurrent predictor contract (VERDICT next-#9) -------------------

def test_concurrent_engines_share_one_executor_compile_cache(model_dirs):
    """Two engines over ONE shared Executor, hammered from N threads:
    the executor's compile cache (an LRU OrderedDict) is shared mutable
    state — the cache lock must keep concurrent resolves from
    corrupting it.  Every future resolves to the right model's value
    — to a few ulp: three threads' requests coalesce into lots of other
    shapes than the one-request lots the references ran, which are
    other executables."""
    place = fluid.CPUPlace()
    exe = fluid.Executor(place)  # ONE executor, shared
    engines, refs = {}, {}
    rng = np.random.RandomState(6)
    reqs = [{'x': rng.rand(1 + (i % 4), 6).astype('float32')}
            for i in range(12)]
    for name in ('mA', 'mB'):
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            prog, feeds, fetches = fluid.io.load_inference_model(
                model_dirs[name], exe)
        engines[name] = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, place=place, name='shared-' + name)
        refs[name] = [engines[name].infer(q)[0] for q in reqs]
    errors = []

    def client(name):
        try:
            for j, q in enumerate(reqs):
                out, = engines[name].infer(q, timeout=60)
                assert_close_across_executables(out, refs[name][j],
                                                err_msg=(name, j))
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(n, ))
               for n in ('mA', 'mB') for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for eng in engines.values():
        eng.stop()


def test_cloned_predictors_run_concurrently(model_dirs):
    """The reference thread contract (paddle_inference_api.h:90):
    PaddlePredictor.clone() + concurrent run() from N threads over the
    shared scope/weights is safe and every output matches the
    single-threaded reference."""
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    cfg = NativeConfig(model_dir=model_dirs['mA'], use_tpu=False)
    root = create_paddle_predictor(cfg)
    rng = np.random.RandomState(7)
    reqs = [{'x': rng.rand(1 + (i % 3), 6).astype('float32')}
            for i in range(10)]
    refs = [root.run(q)[0].data for q in reqs]
    errors = []

    def client(pred):
        try:
            for j, q in enumerate(reqs):
                out = pred.run(q)[0].data
                assert np.array_equal(out, refs[j]), j
        except Exception as e:
            errors.append(repr(e))

    preds = [root] + [root.clone() for _ in range(3)]
    threads = [threading.Thread(target=client, args=(p, ))
               for p in preds]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


# ---- unload/submit races (ISSUE 8 satellite) ---------------------------

def _hammer_outcomes(reg, submit_fn, unload_reload, n_threads=4):
    """Race ``submit_fn`` from N threads against ``unload_reload``
    churning the model table; classify every outcome.  The bar: every
    future RESOLVES (result or a typed error) — 'HANG' and untyped
    crashes are failures."""
    import time as _time
    stop = threading.Event()
    outcomes, lock = [], threading.Lock()

    def note(tag):
        with lock:
            outcomes.append(tag)

    def client():
        while not stop.is_set():
            try:
                fut = submit_fn()
            except (KeyError, serving.EngineClosedError) as e:
                note(type(e).__name__)
                _time.sleep(0.001)
                continue
            except Exception as e:  # untyped submit crash = failure
                note('UNTYPED_SUBMIT:' + repr(e))
                continue
            try:
                fut.result(60)
                note('ok')
            except (serving.EngineClosedError,
                    serving.DeadlineExceededError) as e:
                note(type(e).__name__)
            except TimeoutError:
                note('HANG')
            except Exception as e:
                note('UNTYPED_RESULT:' + repr(e))

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    for t in threads:
        t.start()
    unload_reload()
    stop.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads), 'client thread hung'
    return outcomes


@pytest.mark.parametrize('parallel', [False, True], ids=['cpu', 'mesh8'])
def test_unload_submit_race_hammer(model_dirs, parallel):
    """submit() racing unload()/load() churn, on CPU and the 8-dev
    mesh: every future resolves to a result or a TYPED error (KeyError
    for a forgotten model, EngineClosedError for a stopping engine) —
    never a hang, never an untyped crash."""
    import time as _time
    reg = serving.ModelRegistry(parallel=parallel)
    reg.load('mA', model_dirs['mA'])
    rng = np.random.RandomState(0)
    feed = {'x': rng.rand(4, 6).astype('float32')}
    with reg:
        reg.infer('mA', feed, timeout=300)  # warm the serving rung

        def churn():
            for _ in range(2):
                _time.sleep(0.05)
                reg.unload('mA')
                _time.sleep(0.05)
                reg.load('mA', model_dirs['mA'])
            _time.sleep(0.05)

        outcomes = _hammer_outcomes(
            reg, lambda: reg.submit('mA', feed), churn)
    reg.stop()
    bad = [o for o in outcomes if o == 'HANG' or o.startswith('UNTYPED')]
    assert not bad, bad[:5]
    assert 'ok' in outcomes  # traffic really flowed...
    assert 'KeyError' in outcomes or 'EngineClosedError' in outcomes, \
        outcomes[:10]  # ...and really raced the unloads


def test_unload_submit_generate_race_hammer():
    """The decode lane's half of the race bar: submit_generate()
    against a generation model mid-unload() resolves typed — a prompt
    caught between prefill and slot admission must still resolve its
    future when the engine drains.  decode_pipeline_depth=3 (ISSUE 9)
    keeps a CHAIN of scans in flight under the unload, so the race
    also covers stop-drain harvesting a non-empty chain."""
    import time as _time
    from paddle_tpu.models import seq2seq
    m = seq2seq.build_step_decode(
        src_dict_dim=40, trg_dict_dim=30, embedding_dim=8,
        encoder_size=12, decoder_size=12, max_len=6)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    rng = np.random.RandomState(1)

    def load():
        reg.load('gen', program=m['prefill'],
                 fetch_list=m['prefill_fetches'], scope=scope,
                 executor=exe,
                 generation=serving.GenerationSpec.from_model(m),
                 config=serving.ServingConfig(
                     max_batch_size=4, max_wait_ms=1, decode_slots=2,
                     decode_steps=2, decode_pipeline_depth=3))

    def prompt():
        l = int(rng.randint(2, 5))
        return {'src_word_id': fluid.create_lod_tensor(
            rng.randint(2, 40, size=(l, 1)).tolist(), [[l]])}

    reg = serving.ModelRegistry()
    load()
    with reg:
        reg.generate('gen', prompt(), timeout=300)  # warm prefill+scan

        def churn():
            _time.sleep(0.05)
            reg.unload('gen')
            _time.sleep(0.05)
            load()
            _time.sleep(0.1)

        outcomes = _hammer_outcomes(
            reg, lambda: reg.submit_generate('gen', prompt()), churn,
            n_threads=3)
    reg.stop()
    bad = [o for o in outcomes if o == 'HANG' or o.startswith('UNTYPED')]
    assert not bad, bad[:5]
    assert 'ok' in outcomes
