"""Device self time per training step, on the device that idles most, of the
operations whose innermost ``jax.named_scope`` names a Fluid op of the
state-space mixer: ``ssd_scan``, ``causal_conv1d`` or ``gated_rms_norm``,
gradients included.  From ``chipbench/scopes.py``'s per-scope table (the
scope is ``ops.registry.run_op``'s).  0.0 where the step holds none of them,
None without a trace."""
import re

LAYER = 'model step'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'
OP_TYPES = ('ssd_scan', 'causal_conv1d', 'gated_rms_norm')


def _scopes():
    import importlib.util
    import os
    import sys
    if 'chipbench_scopes' not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            'chipbench_scopes', os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), 'scopes.py'))
        sys.modules['chipbench_scopes'] = \
            importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules['chipbench_scopes'])
    return sys.modules['chipbench_scopes']


def seconds_per_step(record, op_types):
    """Self seconds a step of the Fluid scopes whose op type (a ``_grad``
    suffix cut) is in ``op_types``, on the worst device; None without a
    trace whose operations carry the step's scopes."""
    scopes = _scopes()
    reduced = scopes.of_record(record)
    worst = reduced and reduced['worst']
    if not worst or not worst['scoped'] or not worst['step_runs']:
        return None
    pattern = scopes.load_classes()['scope_pattern']
    total = 0.0
    for scope, row in worst['scopes'].items():
        op_type = re.match(pattern, scope).group(1)
        if op_type.endswith('_grad'):
            op_type = op_type[:-len('_grad')]
        if op_type in op_types:
            total += row['self_s']
    return total / (worst['step_runs'] * record['steps_per_dispatch'])


def read(record):
    secs = seconds_per_step(record, OP_TYPES)
    return None if secs is None else 1e3 * secs
