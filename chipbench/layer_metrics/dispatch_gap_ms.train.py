"""Median device-idle gap between two consecutive step programs of the
traced stretch, on the device that idles most."""
LAYER = 'executors'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(record):
    trace = record.get('trace')
    if not trace or trace['worst']['step_gap_median_s'] is None:
        return None
    return 1e3 * trace['worst']['step_gap_median_s']
