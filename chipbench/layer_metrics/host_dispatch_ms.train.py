"""Median duration of the program's ``paddle_tpu/executor/dispatch`` span
(``Executor`` / ``ParallelExecutor._dispatch_multi_scanned``: resolve, stage
state, launch, write back) inside the traced stretch, read from the host
plane of the same ``.xplane.pb`` as the device's operations."""
LAYER = 'executors'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
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
    return _scopes().span_median_ms(record, 'paddle_tpu/executor/dispatch')
