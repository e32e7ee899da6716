"""1 - (union of the intervals in which an operation runs on the device)
over the traced stretch; the worst device of the cell."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(record):
    trace = record.get('trace')
    if not trace or trace['worst']['idle_share'] is None:
        return None
    return 100.0 * trace['worst']['idle_share']
