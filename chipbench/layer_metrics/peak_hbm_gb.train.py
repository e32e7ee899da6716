"""``memory_stats()`` after the window: peak bytes in use plus peak bytes
reserved (the programs' temporaries), the largest over the cell's devices."""
LAYER = 'device'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_tokens_per_s'


def read(record):
    return record['memory_peak_bytes'] / 1e9 or None
