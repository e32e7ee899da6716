"""Time per training step in which a collective operation is under way
on the device that idles most (asynchronous ones from start to done)."""
LAYER = 'mesh'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(record):
    trace = record.get('trace')
    if not trace or not trace['worst']['step_runs']:
        return None
    worst = trace['worst']
    if not worst['collective_s']:
        return None
    steps = worst['step_runs'] * record['steps_per_dispatch']
    return 1e3 * worst['collective_s'] / steps
