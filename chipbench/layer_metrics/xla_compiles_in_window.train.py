"""Executables JAX built or loaded inside the window, by JAX's own report
(``fluid.trace.compile_log``): ``backend_compile`` events, and ``cache_hit``
events once more (a load from the persistent cache is both).  Any thread,
any function; must read 0."""
LAYER = 'executors'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
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
    got = _scopes().compile_seconds(
        record, ('backend_compile', 'cache_hit'), window=True)
    return got and got['count']
