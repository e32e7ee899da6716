"""Times JAX lowered the executors' K-step training program before the
window opened, by JAX's own report: ``lower`` events of
``fluid.trace.compile_log()`` named ``jit(paddle_tpu_train_scan)`` whose
end falls before the opening (``run.py``'s first statement + ``setup_s``,
as ``scopes.py:compile_seconds`` reckons it).  One argument signature for
the life of the process reads 1; a second lowering is a second load (or,
cold, a second compile) of the largest program the process has.  None where
``run.py`` is not the running program, or the program has no compile log."""
import sys

LAYER = 'executors'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'setup_s'

FUN_NAME = 'jit(paddle_tpu_train_scan)'


def read(record):
    t_process = getattr(sys.modules.get('__main__'), 'T_PROCESS', None)
    setup_s = record.get('end_to_end', {}).get('setup_s')
    try:
        from paddle_tpu.fluid import trace
        log = trace.compile_log()
    except (ImportError, AttributeError):
        return None
    if t_process is None or setup_s is None:
        return None
    return sum(1 for e in log
               if e['kind'] == 'lower' and e['fun_name'] == FUN_NAME
               and e['t_end'] < t_process + setup_s)
