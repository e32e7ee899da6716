"""The two readers PR 26 added, ``ssm_device_ms.train`` and
``ssd_scan_roofline.train``: a number on a trace recorded on the v5e that
holds the state-space ops' scopes (``granite_scoped.xplane.pb.gz``, the
configuration's toy model; ``testdata/record_granite_scoped.py``), 0.0 /
nothing on the toy transformer's trace, which holds none, and nothing
without a trace."""

import gzip
import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark  # noqa: E402

NAMES = ['ssm_device_ms.train', 'ssd_scan_roofline.train']
CELL = {'name': 'granite_h_train_1chip', 'config': 'granite-4.0-h-micro',
        'traffic': 'zipf_b1_l1024'}


def by_path(*parts):
    spec = importlib.util.spec_from_file_location(
        'cb_' + parts[-1].replace('.', '_'), os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def record_of(trace_name, tmp_path):
    """What run.py hands a reader, for a recorded file."""
    scopes = by_path('scopes.py')
    path = str(tmp_path / trace_name)
    with gzip.open(os.path.join(BENCH, 'testdata', trace_name + '.gz')) \
            as src, open(path, 'wb') as dst:
        dst.write(src.read())
    with open(os.path.join(BENCH, 'peaks.json')) as f:
        peaks = json.load(f)['peaks']['TPU v5 lite']
    return {'trace': scopes.xplane.reduce(path), 'steps_per_dispatch': 2,
            'cell': CELL, 'peaks': peaks, 'scopes': scopes.reduce(path)}


@pytest.mark.parametrize('name', NAMES)
def test_reader_agrees_with_its_entry_and_reads_nothing_without_a_trace(
        name):
    entry = next(m for m in benchmark()['per_layer'] if m['name'] == name)
    module = by_path('layer_metrics', name + '.py')
    assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
            module.MOVES) == (entry['layer'], entry['unit'],
                              entry['better'], entry['source'],
                              entry['moves'])
    assert entry['workloads'] == ['granite_h_train_1chip']
    assert module.read({'trace': None, 'cell': CELL, 'peaks': None}) is None


def test_readers_on_the_recorded_state_space_trace(tmp_path):
    record = record_of('granite_scoped.xplane.pb', tmp_path)
    worst = record['scopes']['worst']
    kinds = {s.split('.', 1)[0] for s in worst['scopes']}
    assert {'ssd_scan', 'ssd_scan_grad', 'causal_conv1d',
            'causal_conv1d_grad', 'gated_rms_norm',
            'gated_rms_norm_grad'} <= kinds
    ms = by_path('layer_metrics', NAMES[0] + '.py').read(record)
    step_ms = by_path('layer_metrics', 'step_device_ms.train.py').read(
        record)
    other_ms = by_path('layer_metrics', 'other_ops_device_ms.train.py').read(
        record)
    # the state-space ops are 'other' to scope_classes.json: a part of it
    assert 0.0 < ms < other_ms < step_ms
    scan_s = by_path('layer_metrics', NAMES[0] + '.py').seconds_per_step(
        record, ('ssd_scan', ))
    assert 0.0 < 1e3 * scan_s < ms
    # the share divides the published widths' work by the toy's time: a
    # number, and no statement about the toy
    share = by_path('layer_metrics', NAMES[1] + '.py').read(record)
    assert isinstance(share, float) and share > 0.0


def test_readers_on_a_trace_without_state_space_ops(tmp_path):
    record = record_of('scoped.xplane.pb', tmp_path)
    assert by_path('layer_metrics', NAMES[0] + '.py').read(record) == 0.0
    assert by_path('layer_metrics', NAMES[1] + '.py').read(record) is None


def test_the_counted_work_follows_the_shapes():
    """``ssd_scan_work``: nine scans, four chunks each, at the published
    widths: operations and bytes by hand."""
    builder = by_path('models', 'granite_hybrid_train.py')
    with open(os.path.join(BENCH, 'configs',
                           'granite-4.0-h-micro.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, 'traffic', 'zipf_b1_l1024.json')) as f:
        traffic = json.load(f)
    flops, nbytes = builder.ssd_scan_work(cfg, traffic)
    macs = 128 * (128 + 4096) + 2 * 64 * 64 * 128      # a position, forward
    assert flops == 9 * 4 * 2 * macs * 1024
    row = (4096 + 64 + 256) * 2                        # X, dt, B, C in bf16
    states = 4 * 64 * 64 * 128 * 4                     # f32, four chunks
    assert nbytes == 9 * (1024 * (3 * row + 2 * 4096 * 2) + 2 * states)
    # and the model's: 4.7-4.9 TFLOP a step of 1024 tokens
    per_token = builder.train_flops_per_token(cfg, traffic)
    assert 4.7e12 < per_token * 1024 < 4.9e12
