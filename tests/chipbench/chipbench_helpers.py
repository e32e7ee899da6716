"""Shared by the chipbench rehearsal tests (imported by file name: tests/
has no packages).  No JAX and no device call at import time."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, 'chipbench')
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}
DEVICE_KEYS = {'platform', 'kind', 'count', 'memory_peak_bytes'}


def benchmark(root=ROOT):
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        return json.load(f)


def names_in(directory, root=BENCH):
    """File names of one of the benchmark's directories, suffix cut."""
    return sorted(os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(root, directory))
                  if not f.startswith(('_', '.')))


def run_cell(cell, trace, root=ROOT, seconds=1.0, seed=2147483659):
    """One ``--cpu-tiny`` run as a process; (parsed last line, stdout)."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop('BENCH_RUN', None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, 'chipbench', 'run.py'),
         '--workload', cell, '--seed', str(seed), '--seconds', str(seconds),
         '--trace', str(trace), '--cpu-tiny'],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines
