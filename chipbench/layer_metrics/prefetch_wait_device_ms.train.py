"""Device self time per training step, on the device that idles most, of the
done halves of asynchronous copies and slices (``copy-done``, ``slice-done``
by the ``opcode`` of the operation's row in the program's record of its step
executable, ``fluid.trace.executable_record``): where the device waits for a
prefetch of memory-space assignment that did not finish behind other work.
The trace gives the time, the record says which operations are such waits
(they carry no ``tf_op``; most sit in ``unscoped``);
``chipbench/executable_ops.py`` joins the two.  0.0 where the step has none,
None without a trace or without the record."""
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
    return ops.ms_per_step(record, ops.is_wait)
