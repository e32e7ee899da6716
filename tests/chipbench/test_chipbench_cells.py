"""Every cell of BENCHMARK.json runs with --cpu-tiny as a process, and its
last line parses to exactly the contract's keys.  CPU numbers: rehearsal
only, never speeds."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import (DEVICE_KEYS, RESULT_KEYS, benchmark,  # noqa
                               run_cell)

B = benchmark()
CELLS = [w['name'] for w in B['workloads']]


@pytest.mark.parametrize('cell', CELLS)
def test_cell_runs_and_prints_the_contract_line(cell):
    result, lines = run_cell(cell, trace=0)
    assert set(result) == RESULT_KEYS
    assert set(result['device']) == DEVICE_KEYS
    assert result['device']['platform'] == 'cpu'
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    want = {m['name'] for m in B['end_to_end']
            if cell in m.get('workloads', CELLS)}
    assert set(result['metrics']) == want
    units = {m['name']: m['unit'] for m in B['end_to_end']}
    for name, m in result['metrics'].items():
        assert set(m) == {'value', 'unit'} and m['unit'] == units[name]
        assert m['value'] > 0
    # the platform is stated first, the losses on an earlier line
    assert lines[0].startswith('chipbench: cell=%s platform=cpu' % cell)
    assert any(l.startswith('chipbench: losses ') for l in lines)


def test_without_a_tpu_and_without_cpu_tiny_no_result(tmp_path):
    import subprocess
    from chipbench_helpers import ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'chipbench', 'run.py'),
         '--workload', CELLS[0], '--seed', '1', '--seconds', '1',
         '--trace', '0'],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert 'needs a TPU' in proc.stderr
    assert not proc.stdout.strip().splitlines()[-1].startswith('{')
