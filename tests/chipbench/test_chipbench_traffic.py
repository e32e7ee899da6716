"""chipbench/traffic.py against hand-worked cases, and the operation
counts of the two builders against sums worked by hand."""

import importlib.util
import itertools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH  # noqa: E402


def module(*parts):
    spec = importlib.util.spec_from_file_location(
        'cb_' + parts[-1][:-3], os.path.join(BENCH, *parts))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


traffic = module('traffic.py')
MIX = {'batch': 4, 'length': 6,
       'ids': {'dist': 'zipf', 'exponent': 1.0, 'first': 2, 'end': 1}}


def take(seed, n=3, mix=MIX, vocab=50):
    return list(itertools.islice(traffic.token_batches(mix, vocab, seed), n))


def test_same_seed_same_stream():
    for a, b in zip(take(2147483659), take(2147483659)):
        for key in ('src', 'trg', 'next'):
            assert np.array_equal(a[key], b[key])


def test_another_seed_same_shapes_other_ids():
    a, b = take(1), take(2)
    assert all(x[k].shape == y[k].shape == (4, 6) and x[k].dtype == np.int64
               for x, y in zip(a, b) for k in x)
    assert not np.array_equal(a[0]['src'], b[0]['src'])


def test_next_is_the_target_shifted_with_the_end_mark_last():
    for batch in take(5):
        assert np.array_equal(batch['next'][:, :-1], batch['trg'][:, 1:])
        assert (batch['next'][:, -1] == 1).all()
        assert batch['src'].min() >= 2 and batch['src'].max() < 50


def test_zipf_probabilities_by_hand():
    # three drawable ids, weights 1, 1/2, 1/3 over their sum 11/6
    p = traffic.id_probabilities(MIX['ids'], 5)
    assert np.allclose(p, [0, 0, 6 / 11, 3 / 11, 2 / 11])
    flat = traffic.id_probabilities({'dist': 'zipf', 'exponent': 0.0}, 4)
    assert np.allclose(flat, [0.25] * 4)
    with pytest.raises(ValueError):
        traffic.id_probabilities({'dist': 'other'}, 4)


def test_frequent_ids_are_drawn_more_often():
    mix = dict(MIX, batch=64, length=64)
    ids = np.concatenate([b['src'].ravel() for b in take(0, 4, mix, 1000)])
    counts = np.bincount(ids, minlength=1000)
    assert counts[2] > counts[10] > counts[500]
    assert traffic.tokens_per_step(mix) == 64 * 64


def test_transformer_operations_per_token_by_hand():
    cfg = {'d_model': 512, 'd_ff': 2048, 'n_layer': 6, 'trg_vocab': 30000}
    # multiply-adds a token: encoder layer 3407872, decoder layer 4587520
    # (causal self-attention at half), projection 15360000
    want = 6.0 * (6 * 3407872 + 6 * 4587520 + 15360000)
    got = module('models', 'transformer_train.py').train_flops_per_token(
        cfg, {'length': 256})
    assert got == want == 379994112.0


def test_nmt_operations_per_token_by_hand():
    cfg = {'embedding_dim': 512, 'encoder_size': 512, 'decoder_size': 512,
           'trg_dict_dim': 30000}
    want = 6.0 * (1048576 + 1048576 + 262144 + 8192     # encoder
                  + 262144 + 16384 + 16384              # attention
                  + 1572864 + 786432 + 15360000)        # decoder, head
    got = module('models', 'nmt_attn_train.py').train_flops_per_token(
        cfg, {'length': 32})
    assert got == want
