"""Share of the collective time in which no other operation runs on that
device: what the all-reduce adds to the step."""
LAYER = 'mesh'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(record):
    trace = record.get('trace')
    if not trace or not trace['worst']['collective_s']:
        return None
    worst = trace['worst']
    return 100.0 * worst['collective_exposed_s'] / worst['collective_s']
