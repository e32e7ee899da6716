"""Of the device self time of the operations in the ``unscoped`` bucket (inside
``paddle_tpu.step`` under no Fluid op's scope, or with no ``tf_op`` at all:
what the compiler made itself), the share whose row in the program's record
of its own step executable has an ``owner``: the Fluid op a prefetch's wait,
a layout copy or a scopeless fusion works for.  The record is
``fluid.trace.executable_record('paddle_tpu_train_scan')``; its rows are
keyed by the operation names the trace prints (``chipbench/executable_ops.py``
joins the two).  The instrument's coverage of what the scopes do not reach.
None without a trace, where the program keeps no such record (or fails to
make it), or where nothing is unscoped."""
LAYER = 'model step'
UNIT = '%'
BETTER = 'higher'
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
    both = ops.joined(record)
    if both is None:
        return None
    unscoped = [(secs, row) for _, secs, bucket, row in both[0]
                if bucket == ops.scopes().BUCKET_UNSCOPED]
    total = sum(secs for secs, _ in unscoped)
    if not total:
        return None
    return 100.0 * sum(secs for secs, row in unscoped
                       if row and row.get('owner')) / total
