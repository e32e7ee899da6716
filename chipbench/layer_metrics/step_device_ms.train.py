"""Device busy time per training step: the busy time between the first and
the last whole step program of the traced stretch, over the steps they
ran, on the device that idles most."""
LAYER = 'model step'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(record):
    trace = record.get('trace')
    if not trace or not trace['worst']['step_runs']:
        return None
    worst = trace['worst']
    steps = worst['step_runs'] * record['steps_per_dispatch']
    return 1e3 * worst['busy_s'] / steps
