"""Seconds before the window in XLA's backend compile, a load from the
persistent cache included (``fluid.trace.compile_log`` kind
``backend_compile``): the part of set-up a warm cache shortens."""
LAYER = 'executors'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'setup_s'


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
    got = _scopes().compile_seconds(record, ('backend_compile', ),
                                    window=False)
    return got and got['seconds']
