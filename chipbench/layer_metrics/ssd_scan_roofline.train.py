"""Share of its roofline the ``ssd_scan`` ops reach, forward and gradient
together: the least time the chip could take for the work a step's scans
need (the larger of operations over the bf16 peak and bytes over the HBM
peak of ``chipbench/peaks.json``) over the device self time a step of the
``ssd_scan.*`` and ``ssd_scan_grad.*`` scopes.  Operations and bytes are
from shapes, by the builder's ``ssd_scan_work`` (kept with the benchmark:
the same whatever implements the op).  None without a trace, in a cell
whose builder counts no such work, or where the step holds no such scope."""
import json
import os

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_path(name, *parts):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _file(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def read(record):
    if not record.get('trace') or not record.get('peaks'):
        return None
    cell = record['cell']
    cfg = _file('configs', cell['config'] + '.json')
    builder = _by_path('chipbench_builder_' + cfg['builder'], 'models',
                       cfg['builder'] + '.py')
    if not hasattr(builder, 'ssd_scan_work'):
        return None
    secs = _by_path('chipbench_ssm_device_ms', 'layer_metrics',
                    'ssm_device_ms.train.py').seconds_per_step(
                        record, ('ssd_scan', ))
    if not secs:
        return None
    flops, nbytes = builder.ssd_scan_work(
        cfg, _file('traffic', cell['traffic'] + '.json'))
    peaks = record['peaks']
    least = max(flops / peaks['bf16_flops_per_s'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / secs
