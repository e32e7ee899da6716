"""``chipbench/layer_metrics/train_scan_lowerings.setup.py`` (PR 29): how
often set-up lowered the K-step training program, from the program's own
``fluid.trace.compile_log()``.  One signature for the life of the process
reads 1."""

import importlib.util
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark, run_cell  # noqa: E402

NAME = 'train_scan_lowerings.setup'
SCAN, STEP = 'jit(paddle_tpu_train_scan)', 'jit(paddle_tpu_step)'


def reader():
    spec = importlib.util.spec_from_file_location(
        'cb_train_scan_lowerings',
        os.path.join(BENCH, 'layer_metrics', NAME + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def event(kind, fun_name, t_end):
    return {'kind': kind, 'fun_name': fun_name, 'seconds': 0.5,
            't_end': t_end}


def as_run_py(monkeypatch, t_process, log):
    """``run.py`` as the running program, started at ``t_process``, over
    a program whose compile log is ``log``."""
    from paddle_tpu.fluid import trace
    monkeypatch.setitem(sys.modules, '__main__',
                        types.SimpleNamespace(T_PROCESS=t_process))
    monkeypatch.setattr(trace, 'compile_log', lambda: list(log))


def test_reader_matches_its_benchmark_entry():
    entry = next(m for m in benchmark()['per_layer'] if m['name'] == NAME)
    module = reader()
    # no ``workloads`` key: every cell sets up, so every cell reports it
    assert entry == {'name': NAME, 'layer': module.LAYER,
                     'unit': module.UNIT, 'better': module.BETTER,
                     'source': module.SOURCE, 'moves': module.MOVES}
    assert (module.UNIT, module.MOVES) == ('count', 'setup_s')


@pytest.mark.parametrize('log, count', [
    ([], 0),
    # the one-chip set-up before PR 29: a lowering for the uncommitted
    # start-up state, one more for the state the first step wrote back
    ([event('lower', SCAN, 120.0), event('backend_compile', SCAN, 125.0),
      event('lower', SCAN, 131.0), event('backend_compile', SCAN, 136.0)],
     2),
    # the trace, the compile and the load are not lowerings; the fetched
    # step is another program; a lowering inside the window is not set-up
    ([event('trace', 'paddle_tpu_train_scan', 110.0),
      event('lower', STEP, 105.0), event('lower', SCAN, 120.0),
      event('backend_compile', SCAN, 125.0),
      event('cache_hit', None, 125.0), event('lower', SCAN, 140.0)], 1),
])
def test_reader_counts_the_lowerings_before_the_window(monkeypatch, log,
                                                       count):
    as_run_py(monkeypatch, 100.0, log)
    assert reader().read({'end_to_end': {'setup_s': 39.5}}) == count


def test_reader_reads_nothing_from_a_program_without_a_compile_log(
        monkeypatch):
    """A program older than ``fluid.trace.compile_log``: None, no raise."""
    from paddle_tpu.fluid import trace
    as_run_py(monkeypatch, 100.0, [])
    monkeypatch.delattr(trace, 'compile_log')
    assert reader().read({'end_to_end': {'setup_s': 39.5}}) is None


@pytest.mark.parametrize('main, record', [
    (types.SimpleNamespace(), {'end_to_end': {'setup_s': 39.5}}),
    (types.SimpleNamespace(T_PROCESS=100.0), {'end_to_end': {}}),
    (types.SimpleNamespace(T_PROCESS=100.0), {}),
])
def test_reader_reads_nothing_where_no_window_opened(monkeypatch, main,
                                                     record):
    """Not under ``run.py``, or a record without ``setup_s``."""
    monkeypatch.setitem(sys.modules, '__main__', main)
    assert reader().read(record) is None


def test_traced_rehearsal_lowers_the_train_scan_once():
    """``nmt_train_1chip`` runs the startup program and then the lane
    through ``FeedPipeline``: the start-up state is staged committed, so
    the two warm-up dispatches share one lowering."""
    result, _ = run_cell('nmt_train_1chip', trace=1)
    assert result['metrics'][NAME] == {'value': 1, 'unit': 'count'}
