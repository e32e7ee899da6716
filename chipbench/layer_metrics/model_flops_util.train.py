"""End-to-end utilization: tokens per second times the operations a
token's training needs (from the configuration's shapes, by the builder's
``train_flops_per_token``) over chips times the bf16 peak of
``chipbench/peaks.json``.  Not a roofline share: it includes idle time."""
LAYER = 'model step'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'host_clock'
MOVES = 'train_tokens_per_s'


def read(record):
    if not record['peaks']:
        return None   # a CPU rehearsal has no peak
    counted = record['counted']
    rate = counted['tokens'] / counted['seconds']
    return 100.0 * rate * record['flops_per_token'] / (
        record['chips'] * record['peaks']['bf16_flops_per_s'])
