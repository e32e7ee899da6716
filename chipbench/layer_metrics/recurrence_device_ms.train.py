"""Device self time per training step, on the device that idles most, of the
operations whose innermost ``jax.named_scope`` names the Fluid ops of class
``recurrence`` (``recurrent``, ``while``, ``lstm``, ``gru``, ... and their
gradients): the loops' own machinery and carries; the ops nested in a loop
go to their own class.  The scope is ``ops.registry.run_op``'s;
``chipbench/scopes.py`` reads it from the trace's ``tf_op``.  0.0 where the
class owns no operation."""
LAYER = 'model step'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


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


def read(record):
    return _scopes().bucket_ms_per_step(record, 'recurrence')
