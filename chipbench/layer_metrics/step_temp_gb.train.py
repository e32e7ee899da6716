"""The K-step training program's temporaries by XLA's own
``memory_analysis()``: ``memory['temp']`` of the program's record of the
executable it runs (``fluid.trace.executable_record('paddle_tpu_train_scan')``,
made after the window; ``chipbench/executable_ops.py`` asks for it).
``peak_hbm_gb.train`` holds them beside the arguments and whatever else was
loaded; this is the part recomputation across ops sets out to lower.  None
where the program keeps no such record."""
LAYER = 'device'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'
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
    made = _executable_ops().program_record()
    memory = made and made.get('memory')
    return memory['temp'] / 1e9 if memory else None
