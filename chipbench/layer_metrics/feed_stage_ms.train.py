"""Median duration of the program's ``paddle_tpu/feed/stage`` span (one block
in ``FeedPipeline._stage_loop``: source drain, LoD padding, stacking,
``device_put``) inside the traced stretch, from the trace's host plane."""
LAYER = 'input pipeline'
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
    return _scopes().span_median_ms(record, 'paddle_tpu/feed/stage')
