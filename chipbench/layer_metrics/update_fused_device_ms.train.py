"""Device self time per training step, on the device that idles most, of the
operations outside the ``optimizer`` bucket whose fused computation holds
instructions of an ``optimizer``-class Fluid op (``adam.*``, ``sgd.*``, ...:
``scope_classes.json``): XLA fuses a parameter's update into the product that
makes its gradient, the fusion carries its root's scope, and the class
``optimizer`` never sees it.  ``inside`` of the operation's row in the
program's record (``fluid.trace.executable_record``) lists every scope fused
there; ``chipbench/executable_ops.py`` joins it with the trace.  A fusion's
time is not split between its product and its update: with
``optimizer_device_ms.train`` this bounds from above what the updates cost.
0.0 where no such fusion ran, None without a trace or the record."""
LAYER = 'model step'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def _executable_ops():
    import importlib.util
    import os
    import sys
    if 'chipbench_executable_ops' not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            'chipbench_executable_ops', os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
                'executable_ops.py'))
        sys.modules['chipbench_executable_ops'] = \
            importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules['chipbench_executable_ops'])
    return sys.modules['chipbench_executable_ops']


def read(record):
    ops = _executable_ops()
    return ops.ms_per_step(record, ops.carries_update())
