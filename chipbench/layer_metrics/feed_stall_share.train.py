"""Share of the window the dispatch loop waited for a staged block."""
LAYER = 'input pipeline'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'train_tokens_per_s'


def read(record):
    counted = record['counted']
    return 100.0 * counted['feed_stall_s'] / counted['seconds']
