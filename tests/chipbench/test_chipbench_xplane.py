"""chipbench/xplane.py against the small trace recorded on the v5e
(chipbench/testdata/probe.xplane.pb, written by record_probe.py): four
rounds of two 2048^2 matmul programs, a 20 ms host pause, one reduction."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH  # noqa: E402

spec = importlib.util.spec_from_file_location(
    'cb_xplane', os.path.join(BENCH, 'xplane.py'))
xplane = importlib.util.module_from_spec(spec)
spec.loader.exec_module(xplane)
TRACE = os.path.join(BENCH, 'testdata', 'probe.xplane.pb')


@pytest.fixture(scope='module')
def reduced():
    return xplane.reduce(TRACE)


def test_interval_arithmetic_by_hand():
    merged = xplane.union([(0, 2, 'a'), (1, 3, 'b'), (5, 6, 'c')])
    assert merged == [(0, 3), (5, 6)] and xplane.total(merged) == 4
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (6, 10)]
    assert xplane.subtract([(0, 3), (5, 6)], [(1, 2), (2.5, 5.5)]) == [
        (0, 1), (2, 2.5), (5.5, 6)]
    assert xplane.clip([(0, 4, 'x'), (6, 9, 'y')], 2, 7) == [
        (2, 4, 'x'), (6, 7, 'y')]


def test_a_loop_does_not_count_its_body_twice():
    events = [(0.0, 10.0, 'while'), (1.0, 4.0, 'dot'), (4.0, 9.0, 'add'),
              (10.0, 12.0, 'copy')]
    assert sorted(xplane.self_times(events)) == [
        ('add', 5.0), ('copy', 2.0), ('dot', 3.0), ('while', 2.0)]
    assert [e[2] for e in xplane.leaves(events)] == ['dot', 'add', 'copy']


def test_op_name_and_collectives():
    assert xplane.op_name(
        '%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop') == \
        'fusion.3'
    for name in ('all-reduce.7', 'all-reduce-start', 'all-gather-done.2',
                 'reduce-scatter', 'collective-permute-start.1'):
        assert xplane.COLLECTIVE.match(name)
    for name in ('fusion.3', 'all-reduce-fusion', 'copy-start'):
        assert not xplane.COLLECTIVE.match(name)


def test_recorded_trace_planes_and_step_program(reduced):
    assert list(reduced['devices']) == [0]
    dev = reduced['devices'][0]
    assert dev['step_module'].startswith('jit_small_step')
    # eight ran; the first and the last are never counted (a trace cuts
    # the runs under way at its edges)
    assert dev['step_runs'] == 6
    # each run two 2048^3 bf16 products: about 0.18-0.19 ms on a v5e
    assert all(1.7e-4 < s < 2.0e-4 for s in dev['step_run_s'])


def test_recorded_trace_busy_union(reduced):
    dev = reduced['devices'][0]
    # 6 runs (0.1816 + 0.1932 ms alternating) and 3 reductions of ~12.4 us
    # between the second run's start and the seventh's end: 1.161 ms busy
    # of a 68.37 ms stretch
    assert dev['busy_s'] == pytest.approx(1.1612e-3, rel=1e-3)
    assert dev['window_s'] == pytest.approx(68.369e-3, rel=1e-3)
    assert dev['idle_share'] == pytest.approx(0.98302, abs=1e-4)
    assert reduced['busy_s'] == dev['busy_s'] and reduced['worst'] is dev
    assert dev['collective_s'] == 0


def test_recorded_trace_known_gap_and_top_operation(reduced):
    dev = reduced['devices'][0]
    # the three 20 ms sleeps are the longest gaps, each under the span the
    # probe wrapped round its sleep
    assert [g[0] for g in dev['gaps'][:3]] == ['chipbench/host_pause'] * 3
    assert all(0.020 < g[1] < 0.024 for g in dev['gaps'][:3])
    assert dev['ops'][0][0] == 'convolution_tanh_fusion'
    assert dev['ops'][0][1] == pytest.approx(5.45e-4, rel=1e-2)
    # gaps between the six counted runs: 22.6, 0.04, 22.4, 0.001, 22.2 ms
    assert dev['step_gap_median_s'] == pytest.approx(0.022175, rel=1e-3)


def test_no_trace_no_numbers(tmp_path):
    assert xplane.find_trace(str(tmp_path)) is None
