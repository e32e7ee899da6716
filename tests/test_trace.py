"""Request-level tracing, per-executable cost accounting, and the
flight recorder (ISSUE 6).

The acceptance bars: a traced submit() returns a per-request stage
breakdown whose stages sum (within tolerance) to the measured
end-to-end latency, with BITWISE-identical results tracing on/off;
every cached executable on both executors carries a cost-registry
entry under FLAGS_cost_accounting; a forced worker error or injected
stall dumps the flight recorder WITH the in-flight trace ids; and the
Chrome trace-event export is schema-valid for Perfetto.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.flight_recorder.clear()
    trace.flight_recorder.last_dump = None
    trace.clear_spans()
    yield
    trace.flight_recorder.clear()
    fluid.FLAGS.cost_accounting = False


def _save_load_model(tmpdir, seed=0):
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [6])
        h = fluid.layers.fc(x, 16, act='relu')
        pred = fluid.layers.fc(h, 4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(tmpdir, ['x'], [pred], exe,
                                      main_program=prog)
        loaded, feeds, fetches = fluid.io.load_inference_model(tmpdir, exe)
    return loaded, feeds, fetches, exe, scope


def _requests(rng, sizes):
    return [{'x': rng.rand(n, 6).astype('float32')} for n in sizes]


# ---- span contexts -----------------------------------------------------

def test_trace_context_breakdown_unit():
    """The mark chain -> stage derivation, and stages summing to e2e."""
    ctx = trace.TraceContext()
    t = ctx.t0
    ctx.add_stage('pad', 0.001)
    ctx.mark('enqueue', t + 0.001)
    ctx.mark('collect', t + 0.003)
    ctx.mark('lot', t + 0.004)
    ctx.mark('dispatch', t + 0.005)
    ctx.mark('sync', t + 0.009)
    stages = ctx.finalize(end=t + 0.010)
    assert ctx.trace_id.startswith('tr-')
    assert abs(stages['queue'] - 0.002) < 1e-6
    assert abs(stages['pad'] - 0.002) < 1e-6  # prepare half + lot half
    assert abs(stages['dispatch'] - 0.001) < 1e-6
    assert abs(stages['device'] - 0.004) < 1e-6
    assert abs(stages['trim'] - 0.001) < 1e-6
    assert abs(sum(stages.values()) - ctx.e2e_s) < 1e-6
    bd = ctx.breakdown()
    assert bd['trace_id'] == ctx.trace_id
    assert list(bd['stages_ms']) == [s for s in trace.STAGES
                                     if s in stages]


def test_engine_breakdown_sums_to_e2e():
    """Served requests come back with a per-request stage breakdown
    whose stages cover the measured end-to-end latency (the uncovered
    gaps are code-only, no waits)."""
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, config=serving.ServingConfig(max_wait_ms=2))
        rng = np.random.RandomState(0)
        with eng:
            futs = [eng.submit(r) for r in _requests(rng, [3, 2, 5, 4])]
            for f in futs:
                f.result(60)
        for f in futs:
            bd = f.breakdown()
            assert bd is not None and bd['trace_id'].startswith('tr-')
            stages = bd['stages_ms']
            # the queued path hits every boundary mark
            for stage in ('queue', 'pad', 'dispatch', 'device', 'trim'):
                assert stage in stages, bd
            covered = sum(stages.values())
            assert covered <= bd['e2e_ms'] + 0.01, bd
            gap = bd['e2e_ms'] - covered
            assert gap <= max(0.25 * bd['e2e_ms'], 50.0), bd
        m = eng.metrics()
        assert m['traced_requests'] == 4
        assert set(m['stages_ms_mean']) >= {'queue', 'device'}


def test_inline_engine_breakdown_and_lot_records():
    """The synchronous (never-started) engine traces too, and every
    dispatch leaves a lot record in the flight-recorder ring."""
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe)
        req = eng.submit({'x': np.ones((3, 6), 'float32')})
        req.result(60)
        bd = req.breakdown()
        assert bd['e2e_ms'] > 0
        assert 'device' in bd['stages_ms']
        recs = [r for r in trace.flight_recorder.records()
                if r['kind'] == 'serving_dispatch']
        assert any(req.trace_id in (r.get('trace_ids') or [])
                   for r in recs)
        eng.stop()


def test_registry_threads_one_trace_id_with_arbitration_stage():
    """A routed request's breakdown carries the registry's arbitration
    window AND the engine's stages under ONE trace id (the ambient
    attach handoff)."""
    with tempfile.TemporaryDirectory() as td:
        _save_load_model(td)
        reg = serving.ModelRegistry()
        reg.load('m', td)
        with reg:
            req = reg.submit('m', {'x': np.ones((2, 6), 'float32')})
            req.result(60)
        bd = req.breakdown()
        assert 'arbitration' in bd['stages_ms'], bd
        assert 'device' in bd['stages_ms'], bd
        m = reg.metrics()['models']['m']
        assert m['traced_requests'] >= 1
        assert 'arbitration' in m['stages_ms_mean']


def test_tracing_on_off_bitwise_identical():
    """The whole observability layer is read-only on the data path:
    the same requests served inside a tracing() window with cost
    accounting on return bitwise-identical fetches."""
    rng = np.random.RandomState(7)
    reqs = _requests(rng, [3, 5, 2, 4])
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, config=serving.ServingConfig(max_wait_ms=2))
        with eng:
            plain = [f.result(60)[0]
                     for f in [eng.submit(r) for r in reqs]]
            fluid.FLAGS.cost_accounting = True
            with trace.tracing():
                traced = [f.result(60)[0]
                          for f in [eng.submit(r) for r in reqs]]
        for a, b in zip(plain, traced):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- cost registry -----------------------------------------------------

def test_cost_registry_covers_executor():
    """Under FLAGS_cost_accounting every cached executable the Executor
    dispatches (plain run, the train scan, the eval scan) carries a
    cost-registry entry with XLA's own FLOPs/bytes."""
    fluid.FLAGS.cost_accounting = True
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [8])
        loss = fluid.layers.mean(fluid.layers.fc(x, 16))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    feed = {'x': np.ones((4, 8), 'float32')}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
        exe.run_multi(prog, feed=feed, fetch_list=[loss], steps=3)
        exe.run_eval_multi(prog, feed=feed, fetch_list=[loss], steps=2)
    report = exe.cost_report()
    kinds = {e['kind'] for e in report}
    assert {'run', 'multi', 'eval_multi'} <= kinds, kinds
    for e in report:
        assert e['flops'] > 0, e
        assert e['flops_per_step'] <= e['flops']
        assert e['bytes_accessed'] > 0, e
        assert e['steps'] >= 1
    multi = next(e for e in report if e['kind'] == 'multi')
    assert multi['steps'] == 3
    assert multi['fetch_names'] == [loss.name]


def test_cost_registry_covers_parallel_executor():
    """The SPMD twin: ParallelExecutor's sharded executables carry
    entries too (run + the dp train scan + the dp eval scan)."""
    fluid.FLAGS.cost_accounting = True
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [8])
        loss = fluid.layers.mean(fluid.layers.fc(x, 16))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.core.Scope()
    feed = {'x': np.ones((16, 8), 'float32')}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(loss_name=loss.name,
                                    main_program=prog, scope=scope)
        pe.run([loss.name], feed=feed)
        pe.run_multi([loss.name], feed=feed, steps=2)
        pe.run_eval_multi([loss.name], feed=feed, steps=2)
    report = pe.cost_report()
    kinds = {e['kind'] for e in report}
    assert {'run', 'multi', 'eval_multi'} <= kinds, kinds
    assert all(e['flops'] > 0 for e in report)


def test_cost_accounting_off_is_empty_and_free():
    """Flag off (the default): no entries, no AOT compiles."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed={'x': np.ones((2, 4), 'float32')},
                fetch_list=[loss])
    assert exe.cost_report() == []


def test_engine_metrics_report_cost_derived_throughput():
    """A serving engine under cost accounting reports achieved
    FLOPs/sec from the drained dispatches' cost entries."""
    fluid.FLAGS.cost_accounting = True
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, config=serving.ServingConfig(max_wait_ms=2))
        rng = np.random.RandomState(1)
        with eng:
            for f in [eng.submit(r) for r in _requests(rng, [4, 4, 4])]:
                f.result(60)
        m = eng.metrics()
        assert m['device_flops_per_s'] is not None and \
            m['device_flops_per_s'] > 0, m


# ---- flight recorder + watchdog ----------------------------------------

def test_worker_error_dumps_inflight_trace_ids():
    """A dispatch that explodes errors its own futures AND dumps the
    ring — the dump names the in-flight trace ids."""
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, config=serving.ServingConfig(max_wait_ms=1))

        def boom(*a, **k):
            raise RuntimeError('injected dispatch failure')

        eng._exe = type(exe)(fluid.CPUPlace())
        eng._exe._dispatch_eval_multi = boom
        with eng:
            req = eng.submit({'x': np.ones((2, 6), 'float32')})
            with pytest.raises(RuntimeError, match='injected'):
                req.result(60)
        dump = trace.flight_recorder.last_dump
        assert dump is not None
        assert dump['reason'].startswith('worker_error:')
        assert req.trace_id in dump['extra']['trace_ids']
        # the ring itself holds the lot record of the doomed dispatch
        assert any(r['kind'] == 'serving_dispatch' and
                   req.trace_id in (r.get('trace_ids') or [])
                   for r in dump['records'])


def test_watchdog_stall_dump_names_queued_trace_ids():
    """An injected stall (worker paused, requests aging past the
    threshold) trips the queue-age probe and the dump carries the
    queued trace ids."""
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe,
            config=serving.ServingConfig(max_wait_ms=1,
                                         watchdog_stall_s=0.02))
        with eng:
            assert eng._watchdog_probe in trace.watchdog._probes
            with eng.paused():
                # a full-flush head lot parks the stuck worker on the
                # cycle lock; the rest age in the queue past threshold
                head = eng.submit({'x': np.ones((32, 6), 'float32')})
                futs = [eng.submit({'x': np.ones((2, 6), 'float32')})
                        for _ in range(2)]
                time.sleep(0.08)
                tripped = trace.watchdog.check()
                assert eng._watchdog_probe in tripped
                dump = trace.flight_recorder.last_dump
                assert dump['reason'] == 'stall:%s' % eng._watchdog_probe
                for f in futs:
                    assert f.trace_id in dump['extra']['queued_trace_ids']
            for f in [head] + futs:  # the pause ends, the stall clears
                f.result(60)
        # stop() unregisters the probe
        assert eng._watchdog_probe is None


def test_watchdog_trips_once_per_episode_and_rearms():
    age = {'v': 0.0}
    wd = trace.Watchdog()
    wd.register('probe', lambda: age['v'], 1.0)
    try:
        assert wd.check() == []
        age['v'] = 2.0
        assert wd.check() == ['probe']
        assert wd.check() == []  # still stalled: no re-dump
        age['v'] = 0.1
        assert wd.check() == []  # recovered: re-armed
        age['v'] = 3.0
        assert wd.check() == ['probe']  # next episode trips again
        # full recovery (age None: drained queue) re-arms too — a new
        # stall whose FIRST observed age already exceeds the threshold
        # must still dump
        age['v'] = None
        assert wd.check() == []
        age['v'] = 5.0
        assert wd.check() == ['probe']
    finally:
        wd.unregister('probe')


def test_watchdog_same_name_probes_both_monitored():
    """Two same-named subsystems (two registries both hosting 'ranker')
    keep SEPARATE probes — the second registration uniquifies instead
    of clobbering, and an owner-checked unregister from a stale
    finalizer leaves the survivor monitored."""
    wd = trace.Watchdog()
    a, b = {'v': 0.0}, {'v': 0.0}
    fn_a, fn_b = (lambda: a['v']), (lambda: b['v'])
    k1 = wd.register('probe', fn_a, 1.0)
    k2 = wd.register('probe', fn_b, 1.0)
    try:
        assert k1 == 'probe' and k2 == 'probe#2'
        b['v'] = 5.0
        assert wd.check() == [k2]  # the SECOND engine's stall dumps
        # a stale owner's unregister must not kill the survivor
        wd.unregister(k2, age_fn=fn_a)
        assert k2 in wd._probes
        wd.unregister(k2, age_fn=fn_b)
        assert k2 not in wd._probes
    finally:
        wd.unregister(k1)
        wd.unregister(k2)


def test_flight_recorder_ring_bounded_and_file_dump():
    fr = trace.FlightRecorder(capacity=4)
    for i in range(10):
        fr.record('x', i=i)
    recs = fr.records()
    assert len(recs) == 4
    assert [r['i'] for r in recs] == [6, 7, 8, 9]
    with tempfile.TemporaryDirectory() as td:
        fr.dump_path = os.path.join(td, 'dump.json')
        dump = fr.dump('test_reason', note='hello')
        assert dump['extra']['note'] == 'hello'
        on_disk = json.load(open(fr.dump_path))
        assert on_disk['reason'] == 'test_reason'
        assert len(on_disk['records']) == 4
    assert fr.dump_count == 1
    assert fr.last_dump['reason'] == 'test_reason'


def test_feed_pipeline_registers_feed_stall_probe():
    """FeedPipeline(watchdog_stall_s=...) probes how long the dispatch
    loop has been blocked on staging; close() unregisters."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    src = [{'x': np.ones((2, 4), 'float32')} for _ in range(4)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        pipe = fluid.FeedPipeline(exe, fetch_list=[loss], program=prog,
                                  source=iter(src), steps=2, scope=scope,
                                  watchdog_stall_s=0.01)
        pipe.start()
        probe = pipe._watchdog_probe
        assert probe in trace.watchdog._probes
        assert pipe._feed_stall_age() is None  # not waiting yet
        # inject a stall: pretend the dispatch loop has been waiting
        pipe._waiting_since = time.time() - 1.0
        tripped = trace.watchdog.check()
        assert probe in tripped
        assert trace.flight_recorder.last_dump['reason'] == \
            'stall:%s' % probe
        pipe._waiting_since = None
        out = pipe.run()  # drive to EOF: the pipeline still works
        assert len(out) == 2
    assert pipe._watchdog_probe is None
    assert probe not in trace.watchdog._probes


# ---- spans + Chrome export ---------------------------------------------

def test_spans_capture_and_chrome_export_schema():
    """A traced serving session's span log exports to schema-valid
    chrome trace JSON: per-thread lanes (thread_name metadata), complete
    'X' events in microseconds, trace ids in args — Perfetto's format."""
    from trace_export import to_chrome_trace
    with tempfile.TemporaryDirectory() as td:
        prog, feeds, fetches, exe, scope = _save_load_model(td)
        eng = serving.InferenceEngine(
            prog, feed_names=feeds, fetch_list=fetches, scope=scope,
            executor=exe, name='traced-eng',
            config=serving.ServingConfig(max_wait_ms=2))
        rng = np.random.RandomState(2)
        with eng, trace.tracing():
            futs = [eng.submit(r) for r in _requests(rng, [3, 4])]
            ids = [f.result(60) and f.trace_id for f in futs]
            spans_path = os.path.join(td, 'spans.json')
            n = trace.dump_spans(spans_path)
        assert n > 0
        data = json.load(open(spans_path))
        # a tracing()-ONLY window (no profiler running) still mirrors
        # the serving worker's events into the span log — the
        # documented contract behind the exporter's lanes
        span_names = {s['name'] for s in data['spans']}
        assert any('queue_wait' in sn for sn in span_names), span_names
        assert any('dispatch[' in sn for sn in span_names), span_names
        chrome = to_chrome_trace(data['spans'])
        evs = chrome['traceEvents']
        assert chrome['displayTimeUnit'] == 'ms'
        meta = [e for e in evs if e['ph'] == 'M']
        slices = [e for e in evs if e['ph'] == 'X']
        assert meta and slices
        assert all(e['name'] == 'thread_name' for e in meta)
        lanes = {e['args']['name'] for e in meta}
        assert 'traced-eng' in lanes  # the worker thread's lane
        for s in slices:
            assert {'name', 'cat', 'ts', 'dur', 'pid', 'tid'} <= set(s)
            assert s['ts'] >= 0 and s['dur'] >= 0
            assert isinstance(s['ts'], float)
        # the per-request spans carry their trace ids into args
        tagged = {s['args'].get('trace_id') for s in slices
                  if s['args'].get('trace_id')}
        assert set(ids) <= tagged
        json.dumps(chrome)  # serializable end to end


def test_spans_cleared_per_window_and_off_outside():
    trace.record_span('outside', time.time(), 0.001)
    assert trace.spans() == []  # no-op outside a window
    with trace.tracing():
        trace.record_span('first', time.time(), 0.001)
        assert len(trace.spans()) == 1
    with trace.tracing():
        # a fresh OUTERMOST window clears the previous session's spans
        trace.record_span('second', time.time(), 0.001)
        spans = trace.spans()
    assert [s['name'] for s in spans] == ['second']


def test_trace_export_cli_roundtrip_and_graceful_errors():
    script = os.path.join(REPO, 'tools', 'trace_export.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    with tempfile.TemporaryDirectory() as td:
        spans = [{'name': 'serving/e/request', 'start_s': 1.0,
                  'dur_s': 0.5, 'lane': 'worker', 'trace_id': 'tr-1'}]
        src = os.path.join(td, 'spans.json')
        json.dump({'spans': spans}, open(src, 'w'))
        out = os.path.join(td, 'trace.json')
        subprocess.check_call([sys.executable, script, src, '-o', out],
                              env=env)
        chrome = json.load(open(out))
        assert any(e['ph'] == 'X' and e['args'].get('trace_id') == 'tr-1'
                   for e in chrome['traceEvents'])
        # empty + truncated + wrong-shape inputs: one-line error,
        # nonzero exit, no traceback
        for content in ('', '{"spans": [tru', '{"nope": 1}'):
            bad = os.path.join(td, 'bad.json')
            open(bad, 'w').write(content)
            proc = subprocess.run(
                [sys.executable, script, bad, '-o', out], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            assert proc.returncode != 0, content
            err = proc.stderr.decode()
            assert 'trace_export:' in err, err
            assert 'Traceback' not in err, err


def test_timeline_degrades_on_empty_or_truncated_sidecar():
    """The satellite: tools/timeline.py on an empty/truncated/wrong
    .events.json exits nonzero with a clear one-line error naming the
    file, instead of a raw traceback."""
    script = os.path.join(REPO, 'tools', 'timeline.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, 'timeline.json')
        cases = {'empty': '', 'truncated': '{"host_events": [{"na',
                 'wrong': '{"not_events": []}'}
        for label, content in cases.items():
            p = os.path.join(td, label + '.events.json')
            open(p, 'w').write(content)
            proc = subprocess.run(
                [sys.executable, script, '--profile_path', p,
                 '--timeline_path', out], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            assert proc.returncode != 0, label
            err = proc.stderr.decode()
            assert 'timeline:' in err, err
            assert p in err, err
            assert 'Traceback' not in err, err
        # missing file too
        proc = subprocess.run(
            [sys.executable, script, '--profile_path',
             os.path.join(td, 'nope.events.json'),
             '--timeline_path', out], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        assert proc.returncode != 0
        assert 'Traceback' not in proc.stderr.decode()


# ---- profiler concurrency (satellite) ----------------------------------

def test_profiler_concurrent_events_and_source_churn():
    """Hammer record_event + register/unregister_metrics_source from N
    threads inside an active window: no exceptions, every event lands,
    and the sidecar stays coherent (live + final snapshots, no clobbered
    keys)."""
    from paddle_tpu.fluid import profiler as prof
    n_threads, per_thread = 6, 50
    errors = []

    def hammer(tid):
        try:
            for i in range(per_thread):
                prof.record_event('hammer/t%d' % tid, 0.001)
                key = prof.register_metrics_source(
                    'churn-src', lambda t=tid, j=i: {'t': t, 'j': j})
                if i % 3 == 0:
                    prof.record_event('hammer/shared', 0.001)
                prof.unregister_metrics_source(key)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    with tempfile.NamedTemporaryFile(mode='r', suffix='.prof') as f:
        with fluid.profiler.profiler('CPU', profile_path=f.name):
            threads = [threading.Thread(target=hammer, args=(t, ))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            # a persistent source registered mid-churn survives it
            stable = prof.register_metrics_source('stable',
                                                  lambda: {'ok': 1})
        sidecar = json.load(open(f.name + '.events.json'))
        prof.unregister_metrics_source(stable)
    assert not errors, errors
    by_name = {}
    for ev in sidecar['host_events']:
        by_name[ev['name']] = by_name.get(ev['name'], 0) + 1
    for t in range(n_threads):
        assert by_name['hammer/t%d' % t] == per_thread
    assert by_name['hammer/shared'] == n_threads * ((per_thread + 2) // 3)
    assert sidecar['metrics'].get('stable') == {'ok': 1}
    # unregistered-mid-window churn sources leave final snapshots, not
    # corrupted tables: every surviving key is churn-src or a uniquified
    # churn-src#N, each with the snapshot shape the source returned
    finals = {k: v for k, v in sidecar['metrics'].items()
              if k.startswith('churn-src')}
    assert finals
    for snap in finals.values():
        assert set(snap) == {'t', 'j'}


# ---- arbiter audit (satellite) -----------------------------------------

def test_arbiter_audit_drift_unit():
    from paddle_tpu.serving.arbiter import HBMArbiter
    arb = HBMArbiter(budget_bytes=None)
    arb.admit('a', 1000)
    arb.ensure('a', lambda v: 0)
    arb.admit('b', 500)
    arb.ensure('b', lambda v: 0)
    audit = arb.audit(live_bytes=1800)
    assert audit['accounted_bytes'] == 1500
    assert audit['live_bytes'] == 1800
    assert audit['drift_bytes'] == 300
    snap = arb.snapshot()
    assert snap['audit']['drift_bytes'] == 300


def test_arbiter_audit_live_arrays_default():
    """The default live_bytes path really walks jax.live_arrays(): a
    pinned device buffer is visible as live bytes."""
    import jax
    from paddle_tpu.serving.arbiter import HBMArbiter
    arr = jax.device_put(np.ones((256, 256), 'float32'))
    arr.block_until_ready()
    arb = HBMArbiter()
    audit = arb.audit()
    assert audit['live_bytes'] >= arr.nbytes
    assert isinstance(audit['drift_bytes'], int)
    assert arb.last_audit is audit or arb.last_audit == audit
    del arr


def test_registry_metrics_surface_audit():
    with tempfile.TemporaryDirectory() as td:
        _save_load_model(td)
        reg = serving.ModelRegistry()
        reg.load('m', td)
        with reg:
            reg.infer('m', {'x': np.ones((2, 6), 'float32')}, timeout=60)
            audit = reg.audit()
            m = reg.metrics()
        assert m['audit'] == audit
        assert audit['accounted_bytes'] >= 0
        assert audit['live_bytes'] > 0


# ---- host spans, compile log, Fluid-op scopes (ISSUE 24) ----------------

def _chipbench_scopes():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'cb_scopes_for_trace', os.path.join(REPO, 'chipbench', 'scopes.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_span_lands_on_the_profiler_host_plane_and_nests(tmp_path):
    import jax
    scopes = _chipbench_scopes()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span('paddle_tpu/test/outer', steps=4, executor='X'):
            with trace.span('paddle_tpu/test/inner'):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    spans = {name: (start, end) for start, end, name, _ in scopes.load(
        scopes.xplane.find_trace(str(tmp_path)))['spans']}
    assert set(spans) == {'paddle_tpu/test/outer', 'paddle_tpu/test/inner'}
    outer, inner = spans['paddle_tpu/test/outer'], \
        spans['paddle_tpu/test/inner']
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 0.002
    # no profiler window, no tracing() window: nothing else was recorded
    assert trace.spans() == []


def test_span_feeds_the_older_tables_under_the_legacy_name(tmp_path):
    from paddle_tpu.fluid import profiler
    with trace.span('paddle_tpu/test/off', event='legacy/off') as sp:
        assert sp.recording is False
    with trace.tracing():
        with trace.span('paddle_tpu/test/a', event='legacy/a[x4]') as sp:
            assert sp.recording is True
        with trace.span('paddle_tpu/test/late') as sp:
            sp.event = 'legacy/late'   # named by what the block found
        with trace.span('paddle_tpu/test/unnamed'):
            pass
    assert [s['name'] for s in trace.spans()] == ['legacy/a[x4]',
                                                  'legacy/late']
    with profiler.profiler('CPU', profile_path=str(tmp_path / 'prof')):
        with trace.span('paddle_tpu/test/b', event='legacy/b'):
            pass
    with open(str(tmp_path / 'prof')) as f:
        assert 'legacy/b' in f.read()


def _tiny_regression():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [8])
        y = fluid.layers.data('y', [1])
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act='relu'), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        test_prog = prog.clone(for_test=True)
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {'x': np.ones((4, 8), 'float32'), 'y': np.ones((4, 1), 'float32')}
    return prog, test_prog, startup, feed, pred, loss


def _step_compiles(entries):
    """kinds of the log's entries that name an executor's step program
    (jitted helpers of jax.numpy are traced inside it, under their own
    names)."""
    return sorted(e['kind'] for e in entries
                  if 'paddle_tpu_step' in str(e['fun_name']))


def test_compile_log_counts_what_jax_compiles():
    prog, test_prog, startup, feed, pred, loss = _tiny_regression()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        n = len(trace.compile_log())
        exe.run(test_prog, feed=feed, fetch_list=[pred])
        fresh = trace.compile_log()[n:]
        assert _step_compiles(fresh) == ['backend_compile', 'lower', 'trace']
        assert all(e['seconds'] >= 0 and e['t_end'] <= time.perf_counter()
                   for e in fresh)
        n = len(trace.compile_log())
        exe.run(test_prog, feed=feed, fetch_list=[pred])
        assert _step_compiles(trace.compile_log()[n:]) == []
        # a training program compiles once too: staging commits the
        # startup program's uncommitted state to the device, so the
        # first run presents the signature of every later one (its
        # outputs come back committed)
        n = len(trace.compile_log())
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert _step_compiles(trace.compile_log()[n:]) == \
            ['backend_compile', 'lower', 'trace']
        n, count = len(trace.compile_log()), exe.compile_count
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert _step_compiles(trace.compile_log()[n:]) == []
        assert exe.compile_count == count
    t_mid = fresh[-1]['t_end']
    before, after = trace.compile_summary(until=t_mid), \
        trace.compile_summary(since=t_mid)
    whole = trace.compile_summary()
    for kind in ('trace', 'lower', 'backend_compile', 'cache_hit',
                 'cache_miss', 'cache_retrieval'):
        assert before[kind]['count'] + after[kind]['count'] == \
            whole[kind]['count']
    assert whole['backend_compile']['count'] >= 3
    # nested traces are not counted twice: the seconds of a kind are a
    # union of intervals, never more than the sum of the durations
    assert whole['trace']['seconds'] <= sum(
        e['seconds'] for e in trace.compile_log() if e['kind'] == 'trace')


def _lowered_step(exe, prog, feed, fetch):
    import jax
    scope = fluid.global_scope()
    _, _, feed_arrays, compiled = exe._resolve_and_compile(
        prog, feed, [fetch], scope, pop_readers=False)
    args = compiled._materialize_args(scope, feed_arrays)
    return compiled._jit.lower(*args, jax.random.PRNGKey(0))


def test_lowered_module_carries_the_fluid_op_scopes(monkeypatch):
    import contextlib
    import jax
    prog, _, startup, feed, _, loss = _tiny_regression()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        text = _lowered_step(exe, prog, feed, loss).as_text(debug_info=True)
        assert 'jit_paddle_tpu_step' in text
        for scope in ('paddle_tpu.step/mul.fc_', 'paddle_tpu.step/mul_grad.',
                      'paddle_tpu.step/sgd.fc_'):
            assert scope in text, scope
        # XLA cuts an operation's name at the first '@': scopes carry none
        assert '@GRAD' not in ''.join(
            l for l in text.splitlines() if 'paddle_tpu.step/' in l)
        # scopes change the module's locations and nothing else
        plain = _lowered_step(exe, prog, feed, loss).as_text()
        monkeypatch.setattr(jax, 'named_scope',
                            lambda name: contextlib.nullcontext())
        exe2 = fluid.Executor(fluid.CPUPlace())
        assert _lowered_step(exe2, prog, feed, loss).as_text() == plain


def test_recurrent_program_nests_its_block_ops_scopes():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[4, 3, 2], dtype='float32',
                              append_batch_size=False)
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            x_t = rnn.step_input(x)
            mem = rnn.memory(shape=[2], batch_ref=x_t, init_value=0.0,
                             ref_batch_dim_idx=0)
            acc = fluid.layers.elementwise_add(mem, fluid.layers.fc(x_t, 2))
            rnn.update_memory(mem, acc)
            rnn.output(acc)
        loss = fluid.layers.mean(rnn())
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {'x': np.ones((4, 3, 2), 'float32')}
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        hlo = _lowered_step(exe, prog, feed, loss).compile().as_text()
    import re
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = _chipbench_scopes()
    classes = scopes.load_classes()
    nested = [n for n in names if re.search(
        r'paddle_tpu\.step/recurrent\..*/mul\.fc_\d+\.tmp_\d+/dot_general',
        n)]
    assert nested, sorted(names)
    # the innermost scope owns the operation: the matmul inside the loop
    # is the mul's, the loop's own slicing the recurrent's
    assert scopes.fluid_scope(nested[0], classes)[1].startswith('mul.fc_')
    own = [n for n in names if n.endswith('/while/body/dynamic_slice')
           and 'recurrent_grad' not in n]
    assert own and scopes.fluid_scope(own[0], classes)[1].startswith(
        'recurrent.')
    assert any('/recurrent_grad.' in n and '/mul.fc_' in n for n in names)
