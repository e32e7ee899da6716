"""Chunked prefill with decode-priority interleaving (ISSUE 14): the
model-zoo chunk programs chain BITWISE to the monolithic prefill, the
engine's chunk lane is token-identical to the monolithic lane (and to
per-request reference decode) across pipeline depths, executors and
model families, the prefilling slot phase survives eviction and
shedding, and over-length prompts reject typed at submit."""

import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.models import seq2seq, transformer

from helpers import assert_close_across_executables

V_SRC, V_TRG, DIM, CHUNK = 40, 30, 12, 16


@pytest.fixture(scope='module')
def nmt_chunk():
    """Chunk-capable stepwise NMT decode model + params scope."""
    m = seq2seq.build_step_decode(
        src_dict_dim=V_SRC, trg_dict_dim=V_TRG, embedding_dim=8,
        encoder_size=DIM, decoder_size=DIM, max_len=10, chunk=CHUNK)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['chunk_startup'])
        exe.run(m['step_startup'])
    return m, exe, scope


@pytest.fixture(scope='module')
def tf_chunk():
    """Chunk-capable KV-cache transformer decode model + scope."""
    m = transformer.build_step_decode(vocab=30, d_model=8, d_k=8,
                                      max_ctx=32, max_len=6, chunk=CHUNK)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['chunk_startup'])
        exe.run(m['step_startup'])
    return m, exe, scope


def _prompt(rng, l):
    ids = rng.randint(2, V_SRC, size=(l, 1))
    return fluid.create_lod_tensor(ids.tolist(), [[l]])


def _reference_decode(m, exe, scope, prompt, max_len):
    with fluid.scope_guard(scope):
        boot, = exe.run(m['prefill'], feed={'src_word_id': prompt},
                        fetch_list=m['prefill_fetches'])
        h, t, toks = boot, np.array([[m['start_id']]], np.int64), []
        for _ in range(max_len):
            lg, h2 = exe.run(m['step'],
                             feed={'gen_token': t, 'gen_hidden': h},
                             fetch_list=[m['logits'], m['state'][0][1]])
            nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
            toks.append(nxt)
            if nxt == m['end_id']:
                break
            h, t = h2, np.array([[nxt]], np.int64)
        return toks


def _tf_reference(m, exe, scope, prompt):
    mc = m['max_ctx']
    l = prompt.shape[0]
    with fluid.scope_guard(scope):
        k0, v0, p0 = exe.run(
            m['prefill'],
            feed={'gen_src': prompt[None],
                  'gen_src_len': np.array([[l]], np.float32)},
            fetch_list=m['prefill_fetches'])
        k = np.zeros((1, mc, 8), np.float32)
        k[:, :l] = k0
        v = np.zeros((1, mc, 8), np.float32)
        v[:, :l] = v0
        p = p0.astype(np.float32)
        t = np.array([[m['start_id']]], np.int64)
        toks = []
        for _ in range(m['max_len']):
            lg, k, v, p = exe.run(
                m['step'],
                feed={'gen_token': t, 'gen_k': k, 'gen_v': v,
                      'gen_pos': p},
                fetch_list=[m['logits']] + [f for _, f in m['state']])
            nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
            toks.append(nxt)
            if nxt == m['end_id']:
                break
            t = np.array([[nxt]], np.int64)
        return toks


def _chain_chunks(m, exe, scope, carry, flat, length, slot, budget):
    """Drive the raw chunk dispatch over one prompt in CHUNK blocks."""
    c = m['chunk_width']
    s = np.shape(carry['token'])[0]
    chunk_arg = {'token': m['chunk_token'], 'len': m.get('chunk_len'),
                 'state': m['chunk_state'], 'start_id': m['start_id']}
    cursor = 0
    while cursor < length:
        n = min(c, length - cursor)
        blk = np.zeros((s, c, 1), np.int64)
        blk[slot, :n, 0] = flat[cursor:cursor + n]
        lens = np.zeros((s, ), np.int32)
        lens[slot] = n
        feed = {'gen_ctok': blk, 'gen_ctok@SEQLEN': lens}
        if m.get('chunk_len'):
            feed[m['chunk_len']] = lens.astype('float32')[:, None]
        aux = {'active': lens > 0,
               'finish': np.arange(s) == (
                   slot if cursor + n >= length else -1),
               'budget': np.full((s, ), budget, np.int32)}
        with fluid.scope_guard(scope):
            carry, _, _ = exe._dispatch_chunk_prefill(
                feed=feed, carry=carry, aux=aux, chunk=chunk_arg,
                program=m['chunk'], scope=scope)
        cursor += n
    return carry


# ---- model-level chunk chaining exactness ------------------------------


def test_nmt_chunk_chain_bitwise(nmt_chunk):
    """Chained GRU chunk dispatches == the monolithic prefill (same
    masked scan, same shared weights, split at token boundaries) to a
    few ulp — the [2, C] chunk advance and the [1, 37] prefill are two
    executables; inactive slots' slabs stay untouched BITWISE and the
    finishing chunk flips the carry to decoding."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(0)
    length = 37  # 3 chunks, ragged tail
    ids = rng.randint(2, V_SRC, size=(length, 1)).astype('int64')
    prompt = fluid.create_lod_tensor(ids.tolist(), [[length]])
    with fluid.scope_guard(scope):
        boot, = exe.run(m['prefill'], feed={'src_word_id': prompt},
                        fetch_list=m['prefill_fetches'])
    carry = {'slots': {'gen_hidden': np.zeros((2, DIM), 'float32')},
             'token': np.full((2, 1), m['end_id'], np.int64),
             'alive': np.zeros((2, ), bool),
             'remaining': np.zeros((2, ), np.int32)}
    carry = _chain_chunks(m, exe, scope, carry, ids.reshape(-1),
                          length, slot=0, budget=7)
    h = np.asarray(carry['slots']['gen_hidden'])
    assert_close_across_executables(h[0], np.asarray(boot)[0])
    np.testing.assert_array_equal(h[1], np.zeros(DIM, 'float32'))
    assert np.asarray(carry['alive']).tolist() == [True, False]
    assert int(np.asarray(carry['token'])[0, 0]) == m['start_id']
    assert int(np.asarray(carry['remaining'])[0]) == 7


def test_tf_chunk_chain_writes_exact_kv(tf_chunk):
    """Chained transformer chunks write EXACTLY the prompt's K/V rows
    (bitwise vs the monolithic projections) and advance the position
    cursor; rows past the prompt stay zero."""
    m, exe, scope = tf_chunk
    rng = np.random.RandomState(1)
    length, mc = 21, m['max_ctx']
    ids = rng.randint(2, 30, size=(length, 1)).astype('int64')
    with fluid.scope_guard(scope):
        k0, v0, _ = exe.run(
            m['prefill'],
            feed={'gen_src': ids[None],
                  'gen_src_len': np.array([[length]], np.float32)},
            fetch_list=m['prefill_fetches'])
    carry = {'slots': {'gen_k': np.zeros((2, mc, 8), 'float32'),
                       'gen_v': np.zeros((2, mc, 8), 'float32'),
                       'gen_pos': np.zeros((2, 1), 'float32')},
             'token': np.full((2, 1), m['end_id'], np.int64),
             'alive': np.zeros((2, ), bool),
             'remaining': np.zeros((2, ), np.int32)}
    carry = _chain_chunks(m, exe, scope, carry, ids.reshape(-1),
                          length, slot=0, budget=6)
    k = np.asarray(carry['slots']['gen_k'])
    v = np.asarray(carry['slots']['gen_v'])
    pos = np.asarray(carry['slots']['gen_pos'])
    np.testing.assert_array_equal(k[0, :length], np.asarray(k0)[0])
    np.testing.assert_array_equal(v[0, :length], np.asarray(v0)[0])
    np.testing.assert_array_equal(
        k[0, length:], np.zeros((mc - length, 8), 'float32'))
    assert pos[0, 0] == length and pos[1, 0] == 0


# ---- engine lane -------------------------------------------------------


def _engine(m, exe, scope, spec, name, chunk=None, depth=2, slots=4,
            parallel=False, **cfg):
    return serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=None if parallel else exe,
        parallel=parallel, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=2, decode_slots=slots,
            decode_steps=3, decode_pipeline_depth=depth,
            prefill_chunk=chunk, **cfg),
        generation=spec, name=name)


def test_chunked_engine_token_identical_across_depths(nmt_chunk):
    """The acceptance pin: chunked prefill is token-identical to the
    monolithic lane (prefill_chunk=None — the bitwise PR 9 lane) and
    to per-request reference decode, across decode_pipeline_depth 1
    and 2, over a mixed short/long prompt stream; chunk dispatches
    really happened and the chunk lane compiles a BOUNDED executable
    set (one chunk width, every prompt length)."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(2)
    lens = [3, 40, 9, 25, 5, 33]
    prompts = [_prompt(rng, l) for l in lens]
    max_lens = [7 + (i % 3) for i in range(len(prompts))]
    refs = [_reference_decode(m, exe, scope, p, ml)
            for p, ml in zip(prompts, max_lens)]
    spec = serving.GenerationSpec.from_model(m)
    assert spec.supports_chunked_prefill
    outs = {}
    for depth in (1, 2):
        for mode in (None, CHUNK):
            eng = _engine(m, exe, scope, spec,
                          'ck-%s-d%d' % (mode, depth), chunk=mode,
                          depth=depth)
            with eng:
                futs = [eng.submit_generate({'src_word_id': p},
                                            max_len=ml)
                        for p, ml in zip(prompts, max_lens)]
                outs[(mode, depth)] = [list(f.result(120))
                                       for f in futs]
            md = eng.metrics()['decode']
            if mode is None:
                assert md['prefill_chunks'] == 0
                assert md['prefill_lots'] > 0
            else:
                assert md['prefill_chunks'] >= 2
                assert md['prefill_lots'] == 0
                assert md['prefill_chunk_tokens'] == sum(lens)
    for key, got in outs.items():
        assert got == refs, key


def test_chunked_engine_bounded_executables(nmt_chunk):
    """New prompt LENGTHS mint no new chunk-lane executables: the
    chunk block shape is fixed at [S, C, 1], so a fresh length rides
    the same executable — while the monolithic lane compiles one
    prefill executable per trailing rung."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(3)
    spec = serving.GenerationSpec.from_model(m)
    # a FRESH executor so executor_compile_count isolates this engine
    own = fluid.Executor(fluid.CPUPlace())
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=own, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=2, decode_slots=4,
            decode_steps=3, prefill_chunk=CHUNK),
        generation=spec, name='ck-bound')
    with eng:
        p = _prompt(rng, 20)
        want = _reference_decode(m, exe, scope, p, 4)
        assert list(eng.submit_generate(
            {'src_word_id': p}, max_len=4).result(120)) == want
        warm = eng.metrics()['executor_compile_count']
        # three NEW distinct lengths — every one decomposes into the
        # same C-wide blocks, so nothing recompiles
        for l in (7, 23, 39):
            p = _prompt(rng, l)
            want = _reference_decode(m, exe, scope, p, 4)
            assert list(eng.submit_generate(
                {'src_word_id': p}, max_len=4).result(120)) == want
        assert eng.metrics()['executor_compile_count'] == warm


def test_chunked_engine_inline_mode(nmt_chunk):
    """A never-start()ed chunked engine drains the chunk lane
    synchronously on the submitter's thread."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(4)
    prompts = [_prompt(rng, l) for l in (30, 5)]
    refs = [_reference_decode(m, exe, scope, p, 8) for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-inline', chunk=CHUNK,
                  slots=2)
    outs = [list(eng.generate({'src_word_id': p}, max_len=8,
                              timeout=120)) for p in prompts]
    eng.stop()
    assert outs == refs


def test_chunked_engine_transformer_kv(tf_chunk):
    """The KV-cache family through the chunked engine lane: partial
    KV accumulates across chunk dispatches in the slab, outputs
    token-identical to per-request reference decode."""
    m, exe, scope = tf_chunk
    rng = np.random.RandomState(5)
    lens = [3, 21, 5, 14]
    prompts = [rng.randint(2, 30, size=(l, 1)).astype('int64')
               for l in lens]
    refs = [_tf_reference(m, exe, scope, p) for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-tf', chunk=CHUNK, slots=2)
    with eng:
        futs = [eng.submit_generate(
            {'gen_src': p[None],
             'gen_src_len': np.array([[p.shape[0]]], np.float32)})
            for p in prompts]
        outs = [list(f.result(120)) for f in futs]
    assert outs == refs
    assert eng.metrics()['decode']['prefill_chunks'] >= 2


def test_chunked_engine_spmd_mesh(nmt_chunk):
    """Chunked prefill on the 8-device mesh (dp-sharded slots + chunk
    blocks): token-identical to reference decode at both pipeline
    depths."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(6)
    prompts = [_prompt(rng, l) for l in (3, 26, 18)]
    refs = [_reference_decode(m, exe, scope, p, 5) for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    for depth in (1, 2):
        eng = _engine(m, exe, scope, spec, 'ck-spmd-d%d' % depth,
                      chunk=CHUNK, depth=depth, slots=8, parallel=True)
        with eng:
            futs = [eng.submit_generate({'src_word_id': p}, max_len=5)
                    for p in prompts]
            outs = [list(f.result(300)) for f in futs]
        assert outs == refs, depth
        assert eng.metrics()['decode']['prefill_chunks'] >= 2


def test_evict_mid_prefill_resumes(nmt_chunk):
    """Arbiter eviction racing a chunked prefill: the paused window
    flushes the chain, slabs (with PARTIAL prefill state) demote to
    host bitwise, and the next chunk dispatch re-stages transparently
    — tokens stay exact."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(7)
    prompts = [_prompt(rng, l) for l in (40, 33, 6)]
    refs = [_reference_decode(m, exe, scope, p, 8) for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-evict', chunk=CHUNK,
                  slots=2).start()
    futs = [eng.submit_generate({'src_word_id': p}, max_len=8)
            for p in prompts]
    # wait until some prompt is mid-prefill, then evict the cache
    deadline = time.time() + 20
    while time.time() < deadline:
        if eng._decode_cache.snapshot()['prefilling'] > 0:
            break
        time.sleep(0.001)
    moved = eng.evict_decode_cache()
    assert moved > 0
    outs = [list(f.result(120)) for f in futs]
    eng.stop()
    assert outs == refs


def test_shed_during_chunked_prefill(nmt_chunk):
    """A deadlined prompt that expires mid-prefill sheds typed at a
    flush boundary, frees its prefilling slot, and the engine keeps
    serving."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(8)
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-shed', chunk=CHUNK,
                  slots=2).start()
    doomed = eng.submit_generate({'src_word_id': _prompt(rng, 40)},
                                 max_len=8, deadline_ms=0.001)
    with pytest.raises(serving.DeadlineExceededError):
        doomed.result(60)
    prompt = _prompt(rng, 20)
    want = _reference_decode(m, exe, scope, prompt, 6)
    out = list(eng.submit_generate({'src_word_id': prompt},
                                   max_len=6).result(120))
    eng.stop()
    assert out == want
    assert eng.metrics()['shed'] >= 1
    assert eng._decode_cache.snapshot()['prefilling'] == 0


def test_stall_metrics_reported(nmt_chunk):
    """The decode metrics block reports the chunk lane's counters and
    the inter-token stall gauge fields."""
    m, exe, scope = nmt_chunk
    rng = np.random.RandomState(9)
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-metrics', chunk=CHUNK)
    with eng:
        eng.submit_generate({'src_word_id': _prompt(rng, 25)},
                            max_len=6).result(120)
    md = eng.metrics()['decode']
    for field in ('prefill_chunks', 'prefill_chunk_tokens',
                  'max_decode_stall_cycles', 'max_decode_stall_s'):
        assert field in md
    assert md['prefill_chunks'] == 2  # ceil(25/16)
    assert md['prefill_chunk_tokens'] == 25


# ---- prefilling slot phase (unit) --------------------------------------


def test_slot_cache_prefilling_phase(nmt_chunk):
    """admit_prefilling zeroes the slot, keeps it inert, tracks the
    cursor; finish_prefill leaves the phase; release clears it."""
    from paddle_tpu.serving.decode import GenerationRequest, \
        SlotStateCache
    m, _, _ = nmt_chunk
    spec = serving.GenerationSpec.from_model(m)
    cache = SlotStateCache(spec, 2)
    req = GenerationRequest({'x': np.zeros((1, 2))}, 1, ('gen', ),
                            max_len=4)
    idx = cache.admit_prefilling(req)
    assert req.prefilling and req.slot == idx
    assert cache.snapshot()['prefilling'] == 1
    assert cache.prefilling_items() == [(idx, req, 0)]
    assert not cache.carry()['alive'][idx]
    assert cache.advance_prefill(idx, 16) == 16
    assert cache.prefilling_items() == [(idx, req, 16)]
    cache.finish_prefill(idx)
    assert not req.prefilling
    assert cache.snapshot()['prefilling'] == 0
    cache.release(idx)
    assert cache.free_slots() == 2
    # release mid-prefill clears the cursor too
    req2 = GenerationRequest({'x': np.zeros((1, 2))}, 1, ('gen', ),
                             max_len=4)
    idx2 = cache.admit_prefilling(req2)
    cache.release(idx2)
    assert cache.snapshot()['prefilling'] == 0


# ---- validation / typed rejects ----------------------------------------


def test_prefill_chunk_config_validation(nmt_chunk):
    m, exe, scope = nmt_chunk
    spec = serving.GenerationSpec.from_model(m)
    # rung quantization at the config
    assert serving.ServingConfig(prefill_chunk=20).prefill_chunk == 32
    with pytest.raises(ValueError, match='prefill_chunk must be'):
        serving.ServingConfig(prefill_chunk=0)
    # prefill_chunk without generation=
    with pytest.raises(ValueError, match='generation'):
        serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=fluid.CPUPlace(),
            config=serving.ServingConfig(prefill_chunk=CHUNK),
            name='ck-nogen')
    # a model built WITHOUT a chunk program
    plain = seq2seq.build_step_decode(
        src_dict_dim=V_SRC, trg_dict_dim=V_TRG, embedding_dim=8,
        encoder_size=DIM, decoder_size=DIM, max_len=10)
    pspec = serving.GenerationSpec.from_model(plain)
    assert not pspec.supports_chunked_prefill
    with pytest.raises(ValueError, match='chunk program'):
        serving.InferenceEngine(
            plain['prefill'], fetch_list=plain['prefill_fetches'],
            scope=scope, executor=exe, place=fluid.CPUPlace(),
            config=serving.ServingConfig(prefill_chunk=CHUNK),
            generation=pspec, name='ck-nochunk')
    # chunk-width mismatch between config and model
    with pytest.raises(ValueError, match='chunk width'):
        serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=fluid.CPUPlace(),
            config=serving.ServingConfig(prefill_chunk=2 * CHUNK),
            generation=spec, name='ck-mismatch')


def test_empty_prompt_typed_reject_when_chunking(nmt_chunk):
    """A zero-length prompt has no chunk to dispatch — under chunked
    prefill it must reject typed at submit instead of admitting into
    a prefilling slot whose finishing chunk never fires (a hung
    future and a leaked slot)."""
    m, exe, scope = nmt_chunk
    spec = serving.GenerationSpec.from_model(m)
    eng = _engine(m, exe, scope, spec, 'ck-empty', chunk=CHUNK,
                  slots=2)
    empty = fluid.create_lod_tensor(np.zeros((0, 1), 'int64'), [[0]])
    with pytest.raises(ValueError, match='empty'):
        eng.submit_generate({'src_word_id': empty})
    # the engine still serves afterward
    rng = np.random.RandomState(15)
    p = _prompt(rng, 5)
    want = _reference_decode(m, exe, scope, p, 4)
    assert list(eng.generate({'src_word_id': p}, max_len=4,
                             timeout=120)) == want
    eng.stop()


def test_generation_spec_chunk_validation(nmt_chunk):
    m, exe, scope = nmt_chunk

    def build(**kw):
        base = dict(
            prompt_feed='src_word_id', chunk_program=m['chunk'],
            chunk_token='gen_ctok', chunk_state=m['chunk_state'],
            chunk_width=CHUNK)
        base.update(kw)
        return serving.GenerationSpec(
            m['prefill'], m['step'], m['prefill_feeds'],
            m['prefill_fetches'], 'gen_token', m['logits'], m['state'],
            **base)

    with pytest.raises(ValueError, match='prompt_feed'):
        build(prompt_feed=None)
    with pytest.raises(ValueError, match='chunk_token'):
        build(chunk_token=None)
    with pytest.raises(ValueError, match='ladder rung'):
        build(chunk_width=CHUNK + 3)
    with pytest.raises(ValueError, match='exactly the decode state'):
        build(chunk_state=[('bogus', m['chunk_state'][0][1])])


def test_over_length_prompt_typed_reject_both_families(tf_chunk,
                                                       nmt_chunk):
    """ISSUE 14 satellite: a prompt (or prompt + max_len budget) past
    the decode KV context is a typed ValueError AT SUBMIT — for the
    KV-cache family which HAS a context bound; the recurrent NMT
    family has none and must keep accepting arbitrarily long prompts
    (its state is a fixed-size hidden, nothing to overflow)."""
    m, exe, scope = tf_chunk
    rng = np.random.RandomState(10)
    spec = serving.GenerationSpec.from_model(m)
    assert spec.max_ctx == 32
    for chunk in (None, CHUNK):
        eng = _engine(m, exe, scope, spec, 'ck-rej-%s' % chunk,
                      chunk=chunk, slots=2)
        long_p = rng.randint(2, 30, size=(40, 1)).astype('int64')
        with pytest.raises(ValueError, match='max_ctx'):
            eng.submit_generate(
                {'gen_src': long_p[None],
                 'gen_src_len': np.array([[40]], np.float32)})
        near = rng.randint(2, 30, size=(28, 1)).astype('int64')
        with pytest.raises(ValueError, match='max_len'):
            eng.submit_generate(
                {'gen_src': near[None],
                 'gen_src_len': np.array([[28]], np.float32)},
                max_len=6)
        # within budget still serves
        ok = rng.randint(2, 30, size=(5, 1)).astype('int64')
        out = eng.generate(
            {'gen_src': ok[None],
             'gen_src_len': np.array([[5]], np.float32)},
            max_len=4, timeout=120)
        assert list(out) == _tf_reference(m, exe, scope, ok)[:4] or \
            len(out) <= 4
        eng.stop()
    # the recurrent family: no max_ctx, a 60-token prompt is fine
    mn, exen, scopen = nmt_chunk
    nspec = serving.GenerationSpec.from_model(mn)
    assert nspec.max_ctx is None
    eng = _engine(mn, exen, scopen, nspec, 'ck-rej-nmt', chunk=CHUNK,
                  slots=2)
    prompt = _prompt(rng, 60)
    want = _reference_decode(mn, exen, scopen, prompt, 5)
    assert list(eng.generate({'src_word_id': prompt}, max_len=5,
                             timeout=120)) == want
    eng.stop()
