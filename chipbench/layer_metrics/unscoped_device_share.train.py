"""Share of the device's busy time in operations inside ``paddle_tpu.step``
whose ``tf_op`` names no Fluid op, or that carry no ``tf_op`` at all: what
the scopes of ``ops.registry.run_op`` do not reach.  The instrument's own
coverage; a jump after a PR that renamed scopes means the compile cache
served executables with the old names."""
LAYER = 'model step'
UNIT = '%'
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
    return _scopes().bucket_share(record, 'unscoped')
