"""Device self time per training step, on the device that idles most, of the
operations whose innermost ``jax.named_scope`` names a Fluid op of the routed
experts: ``moe_router`` or ``moe_experts``, gradients included (the router's
product, scores and selection; the sort, the gathers into and out of the
pairs' buffer and the held experts' grouped products).  A part of
``other_ops_device_ms.train``, as ``ssm_device_ms.train`` is.  From
``chipbench/scopes.py``'s per-scope table by ``ssm_device_ms.train``'s
reader.  0.0 where the step holds none of them, None without a trace."""
import importlib.util
import os

LAYER = 'model step'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'
OP_TYPES = ('moe_router', 'moe_experts')


def seconds_per_step(record, op_types):
    spec = importlib.util.spec_from_file_location(
        'chipbench_ssm_device_ms', os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            'ssm_device_ms.train.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.seconds_per_step(record, op_types)


def read(record):
    secs = seconds_per_step(record, OP_TYPES)
    return None if secs is None else 1e3 * secs
