"""Trailing-dim shape bucketing (ISSUE 5): seq-len/resolution ladders
for the serving engine and the feed pipeline.

One policy (fluid.shape_policy) seeds three consumers: the executor's
LoD lowering (_lod_to_padded), the serving engine's TrailingDimBuckets
(mixed-length requests coalesce into shared executables, bitwise-equal
to per-request runs), and run_multi/run_eval_multi's feed_list
normalization (lots disagreeing on a seq feed's padded T re-quantize
to one rung).  FeedPipeline's bucketed variant routes a length-skewed
reader's batches to per-bucket scan blocks instead of splitting at
every boundary.
"""

import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import shape_policy

from helpers import assert_close_across_executables


# ---- the shared ladder policy ------------------------------------------

def test_seq_ladder_policy_pinned():
    """One place to tune _SEQ_BUCKET: the executor's aliases ARE the
    shape_policy functions, and the ladder values are pinned."""
    from paddle_tpu.fluid import executor
    assert executor._bucketed_len is shape_policy.bucketed_len
    assert executor._SEQ_BUCKET == shape_policy.SEQ_BUCKET == 16
    # linear region: multiples of 16 up to 256
    assert [shape_policy.bucketed_len(l) for l in (1, 16, 17, 100, 256)] \
        == [16, 16, 32, 112, 256]
    # geometric region: x1.25 lane-aligned steps
    assert shape_policy.bucketed_len(257) == 320
    assert shape_policy.bucketed_len(321) == 400
    # the materialized ladder agrees with the quantizer
    ladder = shape_policy.seq_ladder(320)
    assert ladder[:4] == [16, 32, 48, 64] and ladder[-1] == 320
    assert all(shape_policy.bucketed_len(r) == r for r in ladder)


def test_trailing_dim_buckets_unit():
    """Default policy rungs, explicit list/dict ladders, oversize
    handling, and the bounded LRU active set."""
    tb = serving.TrailingDimBuckets()
    assert tb.bucket_for('x', 1, 7) == 16
    assert tb.bucket_for('x', 1, 40) == 48
    assert tb.ladder_axes('x') == []
    # explicit list ladder binds axis 1; dict form names the axes
    tb2 = serving.TrailingDimBuckets(
        ladders={'img': {2: [224, 256], 3: [224, 256]}, 'x': [8, 16]})
    assert tb2.ladder_axes('img') == [2, 3] and tb2.ladder_axes('x') == [1]
    assert tb2.bucket_for('img', 2, 200) == 224
    assert tb2.bucket_for('x', 1, 9) == 16
    # above the explicit top: own exact rung, counted oversized
    assert tb2.bucket_for('x', 1, 40) == 40
    assert tb2.report()['oversized'] == 1
    # bounded active set, LRU eviction accounted
    small = serving.TrailingDimBuckets(max_buckets=2)
    for ext in (5, 20, 40, 70):
        small.bucket_for('x', 1, ext)
    rep = small.report()
    assert len(rep['active']) == 2 and rep['evictions'] == 2
    with pytest.raises(ValueError, match='extent'):
        small.bucket_for('x', 1, 0)


def test_bucket_report_never_races_lru_eviction():
    """The ISSUE 5 lock audit's regression: hammer bucket_for from N
    threads (forcing constant LRU eviction) while report() snapshots —
    every snapshot must be internally consistent (active == hit keys)
    and nothing may raise (the OrderedDict is never iterated
    mid-mutation)."""
    sets = [serving.ShapeBucketSet(1 << 14, max_buckets=3),
            serving.TrailingDimBuckets(max_buckets=3)]
    errors, stop = [], threading.Event()

    def hammer(bs, seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(400):
                ext = int(rng.randint(1, 1 << 12))
                if isinstance(bs, serving.TrailingDimBuckets):
                    bs.bucket_for('f%d' % (ext % 5), 1, ext)
                else:
                    bs.bucket_for(ext)
        except Exception as e:  # surfaced below
            errors.append(repr(e))

    def snapshot(bs):
        try:
            while not stop.is_set():
                rep = bs.report()
                assert sorted(rep['active']) == sorted(rep['hits']), rep
                assert rep['evictions'] >= 0
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=hammer, args=(bs, i))
               for i, bs in enumerate(sets) for _ in range(3)]
    snappers = [threading.Thread(target=snapshot, args=(bs, ))
                for bs in sets]
    for t in threads + snappers:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in snappers:
        t.join()
    assert not errors, errors
    for bs in sets:
        rep = bs.report()
        assert len(rep['active']) <= 3


# ---- serving: mixed-length coalescing ----------------------------------

def _seq_model(seed=3):
    """Embedding + masked sum-pool + fc: per-row outputs depend only on
    the row's REAL positions (sequence_pool masks by @SEQLEN), so
    trailing zero-pad is output-preserving by construction."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('ids', shape=[1], dtype='int64', lod_level=1)
        emb = fluid.layers.embedding(x, size=[50, 8])
        pooled = fluid.layers.sequence_pool(emb, pool_type='sum')
        pred = fluid.layers.fc(pooled, 4, act='softmax')
    test_prog = prog.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return test_prog, pred, exe, scope


def _lod_request(rng, lens):
    rows = [rng.randint(0, 50, size=(l, 1)).tolist() for l in lens]
    return {'ids': fluid.create_lod_tensor(rows, [list(lens)])}


def test_engine_mixed_length_lod_bitwise_parity():
    """The acceptance bar (ISSUE 5): a mixed-length stream (>= 4
    distinct seq-lens over 2 ladder rungs) coalesces into shared lots
    and comes back equal to per-request exe.run — to a few ulp: the
    lot's scan and the per-request step are two executables — and
    the engine compiles at most half as many executables as the stream
    has distinct lengths (the exact-shape path's per-shape count)."""
    test_prog, pred, exe, scope = _seq_model()
    rng = np.random.RandomState(0)
    reqs = [_lod_request(rng, lens) for lens in
            ([3, 7], [12, 2, 5], [9], [30, 4], [14], [27, 20])]
    refs = []
    with fluid.scope_guard(scope):
        for r in reqs:
            ref, = exe.run(test_prog, feed=r, fetch_list=[pred])
            refs.append(ref)
    eng = serving.InferenceEngine(
        test_prog, feed_names=['ids'], fetch_list=[pred],
        scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch_size=16, max_wait_ms=40))
    c0 = exe.compile_count
    with eng:
        futs = [eng.submit(r) for r in reqs]
        outs = [f.result(30) for f in futs]
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out[0].shape == ref.shape, i
        assert_close_across_executables(out[0], ref,
                                        err_msg='request %d' % i)
    m = eng.metrics()
    assert m['requests'] == 6
    assert m['lots'] < m['requests'], 'mixed lengths must coalesce'
    distinct_lens = 8  # per-request max-lens span 8 distinct values
    assert (exe.compile_count - c0) * 2 <= distinct_lens
    # two rungs were hit (16 and 32), padding waste is measured
    hits = m['trailing_buckets']['hits']
    assert {'ids[1]:16', 'ids[1]:32'} <= set(hits)
    assert 0.0 < m['trailing_padding_waste'] < 1.0


def test_dense_explicit_ladder_halves_executables():
    """The resolution-ladder opt-in on DENSE feeds (where exact shapes
    really fragment): the same 8-distinct-length stream costs the
    bucketed engine at most HALF the exact engine's executables, and
    results match per-request runs."""
    dim = 6
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 5
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)  # zero-pad neutral
        pred = fluid.layers.fc(pooled, 3, act='softmax')
    test_prog = prog.clone(for_test=True)
    exe0 = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe0.run(startup)
    rng = np.random.RandomState(1)
    lengths = [3, 6, 9, 12, 18, 24, 35, 45]
    reqs = [{'x': rng.rand(2, l, dim).astype('float32')} for l in lengths]

    def engine(trailing):
        ladder = {'x': shape_policy.seq_ladder(max(lengths))} \
            if trailing else None
        return serving.InferenceEngine(
            test_prog, feed_names=['x'], fetch_list=[pred], scope=scope,
            executor=fluid.Executor(fluid.CPUPlace()),
            config=serving.ServingConfig(
                max_batch_size=8, max_wait_ms=20, bucket_sizes=[8],
                steps_per_dispatch=1, trailing_buckets=trailing,
                trailing_ladders=ladder))

    refs = []
    with fluid.scope_guard(scope):
        for r in reqs:
            ref, = exe0.run(test_prog, feed=r, fetch_list=[pred])
            refs.append(ref)
    bucketed, exact = engine(True), engine(False)
    for r, ref in zip(reqs, refs):
        out, = bucketed.infer(r, timeout=30)
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, atol=1e-6)
        exact.infer(r, timeout=30)
    nb = bucketed.metrics()['executor_compile_count']
    ne = exact.metrics()['executor_compile_count']
    assert nb * 2 <= ne, (nb, ne)
    bucketed.stop()
    exact.stop()


def test_engine_mixed_length_dp_sharded_on_virtual_mesh():
    """Mixed-length LoD requests through dp>1 sharded serving on the
    8-device mesh: trailing rungs quantize, batch buckets align to the
    dp extent, results match single-device inference."""
    test_prog, pred, exe, scope = _seq_model(seed=11)
    rng = np.random.RandomState(7)
    reqs = [_lod_request(rng, lens) for lens in
            ([3, 7, 5], [12, 2], [25, 9, 4, 8], [18])]
    refs = []
    with fluid.scope_guard(scope):
        for r in reqs:
            ref, = exe.run(test_prog, feed=r, fetch_list=[pred])
            refs.append(ref)
    eng = serving.InferenceEngine(
        test_prog, feed_names=['ids'], fetch_list=[pred],
        scope=scope, parallel=True,
        config=serving.ServingConfig(max_batch_size=16, max_wait_ms=20))
    with eng:
        futs = [eng.submit(r) for r in reqs]
        outs = [f.result(60) for f in futs]
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out[0].shape == ref.shape, i
        np.testing.assert_allclose(out[0], ref, rtol=2e-4, atol=1e-5,
                                   err_msg='request %d' % i)
    assert all(b % 8 == 0 for b in eng.metrics()['buckets']['active'])


def test_padded_sequence_off_rung_trims_to_caller_extent():
    """A PaddedSequence arriving at an off-ladder T re-pads to its rung
    for dispatch and the fetch trims BACK to the caller's extent —
    shapes match a direct exe.run, values to the documented
    cross-executable tolerance."""
    test_prog, pred, exe, scope = _seq_model(seed=13)
    rng = np.random.RandomState(2)
    ps = fluid.core.PaddedSequence(
        rng.randint(0, 50, size=(2, 10, 1)).astype('int64'),
        np.array([10, 6], np.int32))
    eng = serving.InferenceEngine(test_prog, feed_names=['ids'],
                                  fetch_list=[pred], scope=scope,
                                  executor=exe)
    out, = eng.infer({'ids': ps})
    with fluid.scope_guard(scope):
        ref, = exe.run(test_prog, feed={'ids': ps}, fetch_list=[pred])
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert eng.metrics()['trailing_buckets']['hits'].get('ids[1]:16') == 1


def test_ambiguous_rung_claims_are_order_independent():
    """Review regression: a feed sitting exactly ON a rung must void
    that rung's trim REGARDLESS of dict iteration order — otherwise a
    fetch mirroring the exact-rung feed is wrongly sliced to the other
    feed's real extent.  Both name orders must deliver at the rung."""
    dim = 3
    for first, second in (('a', 'b'), ('b', 'a')):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            fa = fluid.layers.data(first, shape=[-1, dim], dtype='float32')
            fb = fluid.layers.data(second, shape=[-1, dim],
                                   dtype='float32')
            out = fluid.layers.elementwise_add(
                *( (fa, fb) if first == 'a' else (fb, fa) ))
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        eng = serving.InferenceEngine(
            prog, feed_names=['a', 'b'], fetch_list=[out], scope=scope,
            executor=exe,
            config=serving.ServingConfig(
                trailing_ladders={'a': [16], 'b': [16]}))
        rng = np.random.RandomState(8)
        # 'a' sits exactly on the rung, 'b' pads 12 -> 16: the shared
        # rung 16 is ambiguous, so fetches deliver AT the rung (16),
        # never sliced to 12
        o, = eng.infer({'a': rng.rand(2, 16, dim).astype('float32'),
                        'b': rng.rand(2, 12, dim).astype('float32')})
        assert o.shape == (2, 16, dim), (first, o.shape)
        eng.stop()


def test_config_rejects_ladders_with_bucketing_disabled():
    with pytest.raises(ValueError, match='trailing_ladders'):
        serving.ServingConfig(trailing_buckets=False,
                              trailing_ladders={'x': [8]})
    # axis 0 is the batch dim — that ladder is ShapeBucketSet's job
    with pytest.raises(ValueError, match='axis'):
        serving.TrailingDimBuckets(ladders={'img': {0: [224]}})


def test_warm_rejects_unknown_trailing_feed():
    test_prog, pred, exe, scope = _seq_model(seed=31)
    reg = serving.ModelRegistry(place=fluid.CPUPlace())
    reg.load('m', program=test_prog, feed_names=['ids'],
             fetch_list=[pred], scope=scope, executor=exe)
    with pytest.raises(ValueError, match='not feeds'):
        reg.warm('m', trailing={'idz': [16]})  # typo must not no-op
    # an empty extent list is a typed error, not a raw IndexError
    with pytest.raises(ValueError, match='empty'):
        reg.warm('m', trailing={'ids': []})
    reg.stop()


def test_warm_rejects_feed_without_trailing_axis():
    """Review regression: warm(trailing=) on a 1-D feed would silently
    drop the extents and warm duplicate all-zero signatures while
    reporting them as served rungs — reject it like a typo'd name."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        w = fluid.layers.data('w', shape=[-1], append_batch_size=False,
                              dtype='float32')
        out = fluid.layers.scale(w, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    reg = serving.ModelRegistry(place=fluid.CPUPlace())
    reg.load('m', program=prog, feed_names=['w'], fetch_list=[out],
             scope=scope, executor=exe)
    with pytest.raises(ValueError, match='no trailing axis'):
        reg.warm('m', trailing={'w': [16, 32]})
    reg.stop()


def test_out_of_range_ladder_axis_is_loud():
    """Review regression: a configured ladder axis the data doesn't
    have must raise, not silently skip bucketing for that feed — and
    the raise must fire BEFORE any feed of the request touches bucket
    hits or padding metrics (rejected requests leave no trailing
    trace, even when another feed of the same request is valid)."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        a = fluid.layers.data('a', shape=[-1, 3], dtype='float32')
        b = fluid.layers.data('b', shape=[-1, 3], dtype='float32')
        out = fluid.layers.elementwise_add(a, b)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['a', 'b'], fetch_list=[out], scope=scope,
        executor=exe,
        config=serving.ServingConfig(
            trailing_ladders={'a': [16],           # valid axis 1
                              'b': {3: [16, 32]}}))  # data has no ax 3
    rng = np.random.RandomState(11)
    with pytest.raises(ValueError, match='axis 3'):
        eng.submit({'a': rng.rand(2, 12, 3).astype('float32'),
                    'b': rng.rand(2, 12, 3).astype('float32')})
    m = eng.metrics()
    assert m['trailing_padded_cells'] == 0
    assert m['trailing_real_cells'] == 0
    assert not m['trailing_buckets']['hits']
    eng.stop()


def test_zero_width_bucketed_axis_rejected_without_trace():
    """Review regression: a zero-width bucketed axis is a typed error
    raised BEFORE any feed of the request records rung hits or padding
    cells (bucket_for would raise the same complaint mid-loop, after
    another feed was already accounted)."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        a = fluid.layers.data('a', shape=[-1, 3], dtype='float32')
        b = fluid.layers.data('b', shape=[-1, 3], dtype='float32')
        out = fluid.layers.elementwise_add(a, b)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['a', 'b'], fetch_list=[out], scope=scope,
        executor=exe,
        config=serving.ServingConfig(
            trailing_ladders={'a': [8], 'b': [8]}))
    rng = np.random.RandomState(13)
    with pytest.raises(ValueError, match='zero width'):
        eng.submit({'a': rng.rand(2, 4, 3).astype('float32'),
                    'b': np.zeros((2, 0, 3), 'float32')})
    m = eng.metrics()
    assert m['trailing_padded_cells'] == 0
    assert not m['trailing_buckets']['hits']
    eng.stop()


def test_warm_rejects_extents_that_miss_the_ladder_axis():
    """Review regression: flat warm extents substitute axis 1 — a feed
    whose engine ladder binds OTHER axes (dict form), or whose axis 1
    is static, would warm signatures real traffic never produces while
    reporting them as served rungs.  Both are typed errors."""
    def one_feed_model(name, shape):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            v = fluid.layers.data(name, shape=shape, dtype='float32')
            out = fluid.layers.scale(v, scale=2.0)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        return prog, out, exe, scope

    reg = serving.ModelRegistry(
        place=fluid.CPUPlace(),
        config=serving.ServingConfig(trailing_ladders={'img': {2: [8]}}))
    prog, out, exe, scope = one_feed_model('img', [16, -1])
    reg.load('m_img', program=prog, feed_names=['img'],
             fetch_list=[out], scope=scope, executor=exe)
    with pytest.raises(ValueError, match='axis 1 only'):
        reg.warm('m_img', trailing={'img': [8]})  # ladder binds axis 2
    prog, out, exe, scope = one_feed_model('w', [16, 3])
    reg.load('m_w', program=prog, feed_names=['w'],
             fetch_list=[out], scope=scope, executor=exe)
    with pytest.raises(ValueError, match='STATIC'):
        reg.warm('m_w', trailing={'w': [16]})     # axis 1 is static
    reg.stop()


def test_axis2_only_bucketed_feed_static_ax1_voids_trim():
    """Review regression: a feed whose ladders live ONLY on axes >= 2
    is still non-bucketed on axis 1 — its static axis-1 extent must
    void a coinciding rung's trim exactly like a fully non-bucketed
    feed's would (a fetch of that width could mirror either axis)."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        a = fluid.layers.data('a', shape=[-1, 3], dtype='float32')
        img = fluid.layers.data('img', shape=[16, -1], dtype='float32')
        out = fluid.layers.concat([a, img], axis=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['a', 'img'], fetch_list=[out], scope=scope,
        executor=exe,
        config=serving.ServingConfig(
            trailing_ladders={'a': [16], 'img': {2: [4]}}))
    rng = np.random.RandomState(9)
    # 'a' pads 12 -> rung 16; 'img' is static 16 on axis 1 (bucketed
    # only on axis 2, 3 -> 4): the 16 rung is ambiguous with img's
    # static extent, so the fetch keeps T=16 instead of trimming to 12
    o, = eng.infer({'a': rng.rand(2, 12, 3).astype('float32'),
                    'img': rng.rand(2, 16, 3).astype('float32')})
    assert o.shape == (2, 16, 7)
    eng.stop()


def test_static_feed_extent_voids_coinciding_trim():
    """A NON-bucketed feed whose static axis-1 extent equals another
    feed's rung voids that rung's trim: a fetch of that width could
    mirror either axis, so it delivers AT the rung."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        a = fluid.layers.data('a', shape=[-1, 3], dtype='float32')
        b = fluid.layers.data('b', shape=[16, 3], dtype='float32')
        out = fluid.layers.elementwise_add(a, b)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['a', 'b'], fetch_list=[out], scope=scope,
        executor=exe,
        config=serving.ServingConfig(trailing_ladders={'a': [16]}))
    rng = np.random.RandomState(9)
    # 'a' pads 12 -> 16; 'b' is static [B, 16, 3]: the 16 rung is
    # ambiguous with b's static extent, so the fetch keeps T=16
    o, = eng.infer({'a': rng.rand(2, 12, 3).astype('float32'),
                    'b': rng.rand(2, 16, 3).astype('float32')})
    assert o.shape == (2, 16, 3)
    eng.stop()


def test_fetch_static_width_voids_coinciding_trim():
    """Review regression (confirmed silent corruption): a fetch whose
    STATIC axis-1 width equals a request's trailing rung — a 16-class
    softmax under the 16 rung — is the fetch's OWN class axis, not a
    mirrored rung-padded seq axis, and must never be trimmed to the
    request's real extent."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 17
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('ids', shape=[1], dtype='int64', lod_level=1)
        emb = fluid.layers.embedding(x, size=[50, 8])
        pooled = fluid.layers.sequence_pool(emb, pool_type='sum')
        pred = fluid.layers.fc(pooled, 16, act='softmax')  # 16 == rung
    test_prog = prog.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(4)
    # real T=10 pads to rung 16 and records trailing={16: 10}; the
    # [rows, 16] class-probability fetch must come back whole
    ps = fluid.core.PaddedSequence(
        rng.randint(0, 50, size=(2, 10, 1)).astype('int64'),
        np.array([10, 6], np.int32))
    eng = serving.InferenceEngine(test_prog, feed_names=['ids'],
                                  fetch_list=[pred], scope=scope,
                                  executor=exe)
    out, = eng.infer({'ids': ps})
    with fluid.scope_guard(scope):
        ref, = exe.run(test_prog, feed={'ids': ps}, fetch_list=[pred])
    assert out.shape == ref.shape == (2, 16)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    eng.stop()


def test_rejected_request_leaves_no_trailing_trace():
    """Review regression: a request rejected at validation (feeds
    disagreeing on the batch dim) must leave the trailing accounting
    untouched — bucketing pads and records waste only AFTER the leads
    check passes."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        a = fluid.layers.data('a', shape=[-1, 3], dtype='float32')
        b = fluid.layers.data('b', shape=[-1, 3], dtype='float32')
        out = fluid.layers.elementwise_add(a, b)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.InferenceEngine(
        prog, feed_names=['a', 'b'], fetch_list=[out], scope=scope,
        executor=exe,
        config=serving.ServingConfig(
            trailing_ladders={'a': [16], 'b': [16]}))
    rng = np.random.RandomState(5)
    with pytest.raises(ValueError, match='disagree'):
        eng.submit({'a': rng.rand(2, 12, 3).astype('float32'),
                    'b': rng.rand(3, 12, 3).astype('float32')})
    m = eng.metrics()
    assert m['trailing_padded_cells'] == 0
    assert m['trailing_padding_waste'] is None
    assert not m['trailing_buckets']['hits']
    eng.stop()


def test_trailing_disabled_preserves_unbatchable_lod_path():
    """trailing_buckets=False restores the old contract: every LoD
    request is its own unbatchable lot (no coalescing, no trailing
    report)."""
    test_prog, pred, exe, scope = _seq_model(seed=17)
    rng = np.random.RandomState(3)
    eng = serving.InferenceEngine(
        test_prog, feed_names=['ids'], fetch_list=[pred],
        scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch_size=16, max_wait_ms=20,
                                     trailing_buckets=False))
    with eng:
        futs = [eng.submit(_lod_request(rng, [4, 4])) for _ in range(3)]
        for f in futs:
            f.result(30)
    m = eng.metrics()
    assert m['lots'] == m['requests'] == 3  # nothing coalesced
    assert m['trailing_buckets'] is None


def test_warm_trailing_rungs_precompile():
    """ModelRegistry.warm(trailing=...) pre-compiles the seq-len rungs
    of an LoD-declared feed: same-rung real traffic then serves with no
    new executable."""
    test_prog, pred, exe, scope = _seq_model(seed=19)
    reg = serving.ModelRegistry(
        place=fluid.CPUPlace(),
        config=serving.ServingConfig(max_batch_size=4,
                                     bucket_sizes=[2, 4]))
    reg.load('m', program=test_prog, feed_names=['ids'],
             fetch_list=[pred], scope=scope, executor=exe)
    # iterator-valued extents must survive validation (review
    # regression: the empty-check used to drain them)
    served = reg.warm('m', trailing={'ids': iter([16, 32])})
    assert served == 4  # 2 batch rungs x 2 trailing rungs
    eng = reg._entry('m').engine
    c0 = eng.metrics()['executor_compile_count']
    rng = np.random.RandomState(4)
    reg.infer('m', _lod_request(rng, [7, 3]))     # rung 16
    reg.infer('m', _lod_request(rng, [20, 30]))   # rung 32
    assert eng.metrics()['executor_compile_count'] == c0
    reg.stop()


def test_warm_multi_feed_cross_product():
    """Review regression: several trailing feeds warm the FULL
    cross-product of their rungs.  Trailing extents correlate in real
    traffic (both sides of a translation pair bucket long together),
    so the correlated long-long signature must hit a warm executable,
    not pay a cold compile."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 37
    with fluid.program_guard(prog, startup):
        src = fluid.layers.data('src', shape=[1], dtype='int64',
                                lod_level=1)
        trg = fluid.layers.data('trg', shape=[1], dtype='int64',
                                lod_level=1)
        ps = fluid.layers.sequence_pool(
            fluid.layers.embedding(src, size=[50, 8]), pool_type='sum')
        pt = fluid.layers.sequence_pool(
            fluid.layers.embedding(trg, size=[50, 8]), pool_type='sum')
        pred = fluid.layers.fc(fluid.layers.concat([ps, pt], axis=1),
                               4, act='softmax')
    test_prog = prog.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    reg = serving.ModelRegistry(
        place=fluid.CPUPlace(),
        config=serving.ServingConfig(max_batch_size=4,
                                     bucket_sizes=[4]))
    reg.load('m', program=test_prog, feed_names=['src', 'trg'],
             fetch_list=[pred], scope=scope, executor=exe)
    served = reg.warm('m', trailing={'src': [16, 32],
                                     'trg': [16, 32]})
    assert served == 4  # 1 batch rung x the full 2x2 combo grid
    eng = reg._entry('m').engine
    c0 = eng.metrics()['executor_compile_count']
    rng = np.random.RandomState(6)

    def req(src_lens, trg_lens):
        return {
            'src': fluid.create_lod_tensor(
                [rng.randint(0, 50, size=(l, 1)).tolist()
                 for l in src_lens], [list(src_lens)]),
            'trg': fluid.create_lod_tensor(
                [rng.randint(0, 50, size=(l, 1)).tolist()
                 for l in trg_lens], [list(trg_lens)]),
        }

    reg.infer('m', req([20, 30], [25, 17]))   # (32, 32) — correlated
    reg.infer('m', req([3, 7], [28, 5]))      # (16, 32) — mixed
    assert eng.metrics()['executor_compile_count'] == c0
    reg.stop()


def test_bucket_bounds_must_be_positive():
    """Review regression: a <1 active-set bound would make every miss
    insert-then-evict its own key (always-empty active set, evictions
    == misses) — reject it like the sibling knobs."""
    with pytest.raises(ValueError, match='max_trailing_buckets'):
        serving.ServingConfig(max_trailing_buckets=0)
    with pytest.raises(ValueError, match='max_buckets'):
        serving.ServingConfig(max_buckets=0)
    with pytest.raises(ValueError, match='max_buckets'):
        serving.TrailingDimBuckets(max_buckets=0)
    with pytest.raises(ValueError, match='max_buckets'):
        serving.ShapeBucketSet(8, max_buckets=-1)


# ---- executors: trailing feed_list normalization -----------------------

def test_run_eval_multi_mixed_trailing_lots_normalize():
    """run_eval_multi(feed_list=) lots disagreeing on a seq feed's
    padded T re-quantize onto the shared ladder instead of failing the
    uniformity check; per-lot results match plain runs."""
    test_prog, pred, exe, scope = _seq_model(seed=23)
    rng = np.random.RandomState(5)
    lots = [_lod_request(rng, [3, 7]),    # rung 16
            _lod_request(rng, [25, 4]),   # rung 32
            _lod_request(rng, [9, 12])]   # rung 16
    with fluid.scope_guard(scope):
        outs = exe.run_eval_multi(test_prog, feed_list=lots,
                                  fetch_list=[pred])
        for k, lot in enumerate(lots):
            ref, = exe.run(test_prog, feed=lot, fetch_list=[pred])
            np.testing.assert_allclose(np.asarray(outs[0][k]), ref,
                                       atol=1e-6, err_msg='lot %d' % k)


def test_run_multi_mixed_trailing_lots_train():
    """The TRAIN path's mirror: run_multi(feed_list=) over lots whose
    seq feeds bucket to different rungs trains without a uniformity
    crash (the lots re-quantize to one rung; the seq lowerings mask the
    extra positions)."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 29
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('ids', shape=[1], dtype='int64', lod_level=1)
        emb = fluid.layers.embedding(x, size=[50, 8])
        pooled = fluid.layers.sequence_pool(emb, pool_type='sum')
        pred = fluid.layers.fc(pooled, 4, act='softmax')
        label = fluid.layers.data('label', shape=[1], dtype='int64')
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    rng = np.random.RandomState(6)

    def lot(lens):
        f = _lod_request(rng, lens)
        f['label'] = rng.randint(0, 4, (len(lens), 1)).astype('int64')
        return f

    with fluid.scope_guard(scope):
        exe.run(startup)
        out, = exe.run_multi(prog, feed_list=[lot([3, 8]), lot([20, 5])],
                             fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()


# ---- FeedPipeline: the bucketed variant --------------------------------

def _reader_prog(batches, seed=0):
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        rd = fluid.layers.py_reader(capacity=16, shapes=[[-1, 4], [-1, 1]],
                                    dtypes=['float32', 'int64'])
        x, label = fluid.layers.read_file(rd)
        pred = fluid.layers.fc(x, 3, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.5).minimize(loss)
    rd.decorate_tensor_provider(lambda: iter(batches))
    return prog, startup, rd, loss


def _param_value(prog, scope):
    name = [v for v in prog.global_block().vars if v.endswith('.w_0')][0]
    return np.array(fluid.executor.fetch_var(name, scope))


def test_feed_pipeline_bucketed_routes_and_matches_replay():
    """A length-skewed reader (interleaved shape buckets — the
    non-bucketed path would split at EVERY boundary) pipelines full
    K-step blocks per bucket; the realized order is observable in
    dispatch_log, and the final state is BITWISE-equal to sequential
    run() calls replayed in that order."""
    rng = np.random.RandomState(0)

    def batch(rows):
        return (rng.rand(rows, 4).astype('float32'),
                rng.randint(0, 3, (rows, 1)).astype('int64'))

    pattern = [8, 5, 8, 5, 8, 5, 8]
    batches = [batch(r) for r in pattern]
    prog, startup, rd, loss = _reader_prog(batches)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        rd.start()
        pipe = fluid.FeedPipeline(exe, fetch_list=[loss], program=prog,
                                  reader=rd, steps=2, pipeline_depth=2,
                                  scope=scope, bucketed=True)
        outs = pipe.run()
        w = _param_value(prog, scope)
    # buckets fill across boundaries: 2 full 2-step blocks per bucket,
    # one 1-step tail for the odd 8-row batch
    assert list(pipe.dispatch_log) == [[0, 2], [1, 3], [4, 6], [5]]
    # bounded for open-ended pipelines (review regression)
    assert pipe.dispatch_log.maxlen is not None
    m = pipe.metrics()
    assert m['bucketed'] is True and m['dispatches'] == 4
    assert m['partial_blocks'] == 1 and m['eof'] is True
    assert m['open_buckets'] == 0

    # replay: sequential run() over the stream REORDERED to the
    # realized dispatch order — scanned-vs-sequential is the proven
    # contract, so state must land bitwise-identically
    order = [i for d in pipe.dispatch_log for i in d]
    re_batches = [batches[i] for i in order]
    prog2, startup2, rd2, loss2 = _reader_prog(re_batches)
    exe2 = fluid.Executor(fluid.CPUPlace())
    s2 = fluid.core.Scope()
    with fluid.scope_guard(s2):
        exe2.run(startup2)
        rd2.start()
        for _ in range(len(re_batches)):
            out2, = exe2.run(prog2, fetch_list=[loss2])
        w2 = _param_value(prog2, s2)
    np.testing.assert_array_equal(np.asarray(outs[-1][0]),
                                  np.asarray(out2))
    np.testing.assert_array_equal(w, w2)


def test_feed_pipeline_bucketed_open_bucket_bound():
    """More open buckets than max_open_buckets flush the least-
    recently-fed one early as a shorter block (bounded staging memory),
    counted in bucket_early_flushes — nothing is dropped."""
    rng = np.random.RandomState(1)

    def batch(rows):
        return (rng.rand(rows, 4).astype('float32'),
                rng.randint(0, 3, (rows, 1)).astype('int64'))

    pattern = [8, 5, 3, 8, 5, 3]  # 3 buckets, bound of 2
    batches = [batch(r) for r in pattern]
    prog, startup, rd, loss = _reader_prog(batches, seed=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        rd.start()
        pipe = fluid.FeedPipeline(exe, fetch_list=[loss], program=prog,
                                  reader=rd, steps=4, pipeline_depth=2,
                                  scope=scope, bucketed=True,
                                  max_open_buckets=2)
        outs = pipe.run()
    m = pipe.metrics()
    assert m['bucket_early_flushes'] >= 1
    # every drained batch trained exactly once
    trained = sorted(i for d in pipe.dispatch_log for i in d)
    assert trained == list(range(len(batches)))
    assert m['steps_dispatched'] == len(batches)
    assert len(outs) == m['dispatches']
