"""Device self time per training step of the operations whose ``tf_op`` lies
outside ``paddle_tpu.step``: the K-step scan's own ``while``, its carries
and the slicing of the feed block (``_CompiledBlock._make_multi``), which
belong to no Fluid op."""
LAYER = 'executors'
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
    return _scopes().bucket_ms_per_step(record, 'scan_lane')
