"""Share of the ``flash_attention`` ops of the programs this process
lowered (a cell lowers its startup program, which holds none, and its step
program) that the lowering sent to the fused Pallas kernel, by the
program's own record of its choices
(``fluid.trace.lowering_choices('flash_attention')``).  None where the
program keeps no such record, or lowered no such op."""
LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_tokens_per_s'


def read(record):
    try:
        from paddle_tpu.fluid import trace
        programs = trace.lowering_choices('flash_attention')
    except (ImportError, AttributeError):
        return None
    ops = sum(sum(counts.values()) for counts in programs)
    if not ops:
        return None
    return 100.0 * sum(counts.get('pallas', 0) for counts in programs) / ops
