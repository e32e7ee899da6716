"""Share of the parameter bytes that the gradient ops of the programs this
process lowered read beside another gradient (a cell lowers its startup
program, which holds no gradient op, and its step program) whose in-place
update the lowering ordered after the gradient op's reads, by the program's
own record (``fluid.trace.lowering_choices('param_update_order',
seen=True)``: an op's parameters are ``'tied'`` to its other gradients, or
``'untied'``, with their ``mb`` as one device holds them).  None where the
program keeps no such record, or no gradient op read a parameter.

A record of where the size rule engaged, not a quantity to push up: the
right value is whatever the rule yields on the cell's shapes, and 100 is no
goal (tying every op reads 100 and cost the transformer 15% of its modelled
cycles and 2 GB of temporaries: ISSUE 32's variant U).  ``BETTER`` says
``higher`` because the schema wants a direction and ISSUE 32 gave that one;
no bound hangs on it.  Only the generic ``<op>_grad`` lowering keeps the
record: a parameter read by a gradient op with a lowering of its own
(``lookup_table_grad``: NMT's embeddings) is in neither sum.  What the
tie is for, whole copies of state left in the step program, is read with no
chip by ``tools/compile_for_v5e.py`` (lower is better, 0 in every cell)."""
LAYER = 'model step'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_tokens_per_s'


def read(record):
    try:
        from paddle_tpu.fluid import trace
        programs = trace.lowering_choices('param_update_order', seen=True)
    except (ImportError, AttributeError, TypeError):
        return None
    ops = [op for program in programs for op in program.values()]
    mb = sum(op['mb'] for op in ops)
    if not mb:
        return None
    return 100.0 * sum(op['mb'] for op in ops if op['choice'] == 'tied') / mb
