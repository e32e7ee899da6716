"""Share of its roofline the ``moe_experts`` ops reach, forward and gradient
together: the least time the chip could take for the work EVERY load of a
step's held experts needs (the larger of operations over the bf16 peak and
bytes over the HBM peak of ``chipbench/peaks.json``) over the device self
time a step of the ``moe_experts.*`` and ``moe_experts_grad.*`` scopes.
Operations and bytes are from shapes, by the builder's ``moe_experts_work``
(kept with the benchmark: the same whatever implements the op; it counts
one pass over each held weight a product and no rows, since the rows vary
with the seed's load: a lower bound, so the share stays under 100; a
product that pays for the whole buffer of pairs reads about a sixteenth of
one that skips its empty tiles).  None without a trace, in a cell whose
builder counts no such work, or where the step holds no such scope."""
import importlib.util
import json
import os

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_path(name, *parts):
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
    if not hasattr(builder, 'moe_experts_work'):
        return None
    secs = _by_path('chipbench_moe_device_ms', 'layer_metrics',
                    'moe_device_ms.train.py').seconds_per_step(
                        record, ('moe_experts', ))
    if not secs:
        return None
    flops, nbytes = builder.moe_experts_work(
        cfg, _file('traffic', cell['traffic'] + '.json'))
    peaks = record['peaks']
    least = max(flops / peaks['bf16_flops_per_s'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / secs
