"""Adding a cell and a layer metric is data only: new files in a copy of
chipbench/ and new BENCHMARK.json entries, no existing file touched; and
the traced run reports the per-layer metrics its sources can give."""

import hashlib
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import (BENCH, DEVICE_KEYS, RESULT_KEYS, ROOT,  # noqa
                               benchmark, run_cell)

NEW_METRIC = '''"""Steps the window delivered (a test's metric)."""
LAYER = 'executors'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_tokens_per_s'


def read(record):
    return record['window']['steps']
'''


def digests(root):
    out = {}
    for base, _, files in os.walk(root):
        if '__pycache__' in base or '.chipbench_out' in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, 'rb') as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, 'chipbench'),
                    ignore=shutil.ignore_patterns('__pycache__'))
    before = digests(os.path.join(root, 'chipbench'))

    def write(rel, text):
        path = os.path.join(root, 'chipbench', rel)
        assert not os.path.exists(path)
        with open(path, 'w') as f:
            f.write(text)

    write('traffic/zipf2_b16_l64.json', json.dumps({
        'batch': 16, 'length': 64, 'ids': {'dist': 'zipf', 'exponent': 2.0, 'first': 2},
        'cpu_tiny': {'batch': 4, 'length': 8}}))
    write('workloads/tbase_train_small_1chip.json', json.dumps({
        'name': 'tbase_train_small_1chip', 'config': 'transformer-base',
        'traffic': 'zipf2_b16_l64', 'chips': 1, 'driver': 'train_loop',
        'steps_per_dispatch': 2,
        'who': 'a test', 'why': 'a small batch'}))
    write('layer_metrics/steps_delivered.train.py', NEW_METRIC)
    b = benchmark()
    b['workloads'].append({
        'name': 'tbase_train_small_1chip', 'config': 'transformer-base',
        'traffic': 'zipf2_b16_l64', 'chips': 1, 'why': 'a small batch'})
    b['per_layer'].append({
        'name': 'steps_delivered.train', 'unit': 'count',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'executors', 'moves': 'train_tokens_per_s',
        'workloads': ['tbase_train_small_1chip']})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(b, f)

    result, _ = run_cell('tbase_train_small_1chip', trace=1, root=root,
                         seconds=2.0)
    assert RESULT_KEYS <= set(result) <= RESULT_KEYS | {'breakdown'}
    assert DEVICE_KEYS <= set(result['device'])
    assert result['correct'] is True
    m = result['metrics']
    assert m['steps_delivered.train']['value'] == result['attempted'] > 0
    assert m['compiles_in_window.train']['value'] == 0
    assert m['feed_stall_share.train']['unit'] == '%'
    # the collective metrics list another cell; a CPU run has no device
    # plane, so the trace's readers return nothing and are left out
    assert 'collective_ms_per_step' not in m
    assert 'step_device_ms.train' not in m
    assert 'model_flops_util.train' not in m
    after = digests(os.path.join(root, 'chipbench'))
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        'layer_metrics/steps_delivered.train.py',
        'traffic/zipf2_b16_l64.json',
        'workloads/tbase_train_small_1chip.json']
