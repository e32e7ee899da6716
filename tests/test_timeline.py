"""Chrome-trace timeline export (reference tools/timeline.py:115 —
profiler dump -> chrome://tracing JSON), VERDICT r4 next-#6."""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

import paddle_tpu.fluid as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))


def _profiled_run(profile_path):
    prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 8))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with fluid.profiler.profiler('CPU', profile_path=profile_path):
            for _ in range(3):
                exe.run(prog,
                        feed={'x': np.zeros((2, 4), dtype='float32')},
                        fetch_list=[loss])


def test_events_sidecar_written():
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, 'prof')
        _profiled_run(p)
        sidecar = json.load(open(p + '.events.json'))
        names = [e['name'] for e in sidecar['host_events']]
        assert len(names) == 3
        assert all(n.startswith('executor_run/block0') for n in names)
        assert all(e['dur_s'] >= 0 for e in sidecar['host_events'])
        # events carry real timestamps (monotone starts)
        starts = [e['start_s'] for e in sidecar['host_events']]
        assert starts == sorted(starts)


def test_timeline_library_roundtrip():
    from timeline import Timeline
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, 'prof')
        _profiled_run(p)
        prof = json.load(open(p + '.events.json'))
        trace = json.loads(Timeline({'trainer': prof})
                           .generate_chrome_trace())
        evs = trace['traceEvents']
        meta = [e for e in evs if e['ph'] == 'M']
        slices = [e for e in evs if e['ph'] == 'X']
        assert any(e['args']['name'] == 'trainer:host' for e in meta)
        assert len(slices) == 3
        for s in slices:
            assert {'ts', 'dur', 'pid', 'tid', 'name', 'cat'} <= set(s)
            assert s['cat'] == 'host'


def test_timeline_cli_multi_trainer():
    with tempfile.TemporaryDirectory() as td:
        p1, p2 = os.path.join(td, 'a'), os.path.join(td, 'b')
        _profiled_run(p1)
        _profiled_run(p2)
        out = os.path.join(td, 'timeline.json')
        subprocess.check_call(
            [sys.executable, os.path.join(REPO, 'tools', 'timeline.py'),
             '--profile_path',
             't1=%s.events.json,t2=%s.events.json' % (p1, p2),
             '--timeline_path', out],
            env=dict(os.environ, JAX_PLATFORMS='cpu'))
        trace = json.load(open(out))
        pids = {e['args']['name'] for e in trace['traceEvents']
                if e['ph'] == 'M'}
        assert {'t1:host', 't2:host'} <= pids
        # distinct pids per trainer
        assert len({e['pid'] for e in trace['traceEvents']}) >= 2
