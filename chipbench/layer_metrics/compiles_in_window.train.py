"""Executables the cell's executor compiled inside the window: should
read 0 (the warm-up dispatches compile every shape)."""
LAYER = 'executors'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_tokens_per_s'


def read(record):
    return record['counted']['compiles']
