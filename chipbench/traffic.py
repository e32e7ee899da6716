"""The one generator of training traffic: token batches from a traffic
file's parameters and a seed.

A traffic file (``chipbench/traffic/<name>.json``) says how many sequences
a step holds (``batch``, over all chips of the cell), how long each is
(``length``; every sequence is full, so no position is padding) and how
token ids are distributed (``ids``).  The same seed gives the same stream,
and every seed gives the same shapes: only the ids differ, so the seed
never changes the work.
"""

import numpy as np


def id_probabilities(ids, vocab):
    """Probability of each id in [0, vocab) under the ``ids`` parameters.
    Ids below ``first`` (padding, start and end marks) are never drawn."""
    first = int(ids.get('first', 0))
    ranks = np.arange(1, vocab - first + 1, dtype=np.float64)
    if ids['dist'] != 'zipf':
        raise ValueError('traffic: unknown id distribution %r' % ids['dist'])
    weights = ranks ** -float(ids['exponent'])
    p = np.zeros(vocab)
    p[first:] = weights / weights.sum()
    return p


def token_batches(traffic, vocab, seed):
    """Endless stream of {'src': [B, L], 'trg': [B, L], 'next': [B, L]}
    int64 batches: source and target ids drawn independently, ``next`` the
    target shifted left by one with the ``end`` id last (the label of
    next-token training)."""
    rng = np.random.default_rng([int(seed), 0x7a1f])
    batch, length = int(traffic['batch']), int(traffic['length'])
    ids = traffic['ids']
    cdf = np.cumsum(id_probabilities(ids, vocab))
    cdf[-1] = 1.0
    end = int(ids.get('end', 1))

    def draw():
        return np.searchsorted(cdf, rng.random((batch, length)),
                               side='right').astype(np.int64)

    while True:
        src, trg = draw(), draw()
        nxt = np.concatenate(
            [trg[:, 1:], np.full((batch, 1), end, np.int64)], axis=1)
        yield {'src': src, 'trg': trg, 'next': nxt}


def tokens_per_step(traffic):
    """Target-side tokens one step trains on."""
    return int(traffic['batch']) * int(traffic['length'])
