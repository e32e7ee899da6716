"""Continuous-batching autoregressive decode (ISSUE 7): the in-jit
decode scan on both executors, the engine's generation lane
(submit_generate -> prefill lots -> slot admission -> K-step decode
scans), registry/arbiter decode-cache accounts, and the trace/flight
coverage.  The ground-truth oracle everywhere is PER-REQUEST REFERENCE
DECODE: one prefill run plus one step run per token, host-driven — the
lane must be token-identical to it at a fraction of the dispatches."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import trace as trace_mod
from paddle_tpu.models import seq2seq, transformer

from helpers import assert_close_across_executables

V_SRC, V_TRG, DIM = 40, 30, 12


@pytest.fixture(scope='module')
def nmt_decode():
    """Tiny stepwise NMT decode model + a scope holding its params."""
    m = seq2seq.build_step_decode(
        src_dict_dim=V_SRC, trg_dict_dim=V_TRG, embedding_dim=8,
        encoder_size=DIM, decoder_size=DIM, max_len=10)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    return m, exe, scope


def _prompt(rng, l):
    ids = rng.randint(2, V_SRC, size=(l, 1))
    return fluid.create_lod_tensor(ids.tolist(), [[l]])


def _reference_decode(m, exe, scope, prompt, max_len):
    """One prefill exe.run + one step exe.run PER TOKEN (the reference
    serving shape the decode lane replaces); returns (tokens,
    dispatches)."""
    with fluid.scope_guard(scope):
        boot, = exe.run(m['prefill'], feed={'src_word_id': prompt},
                        fetch_list=m['prefill_fetches'])
        h, t, toks, n = boot, np.array([[m['start_id']]], np.int64), [], 1
        for _ in range(max_len):
            lg, h2 = exe.run(m['step'],
                             feed={'gen_token': t, 'gen_hidden': h},
                             fetch_list=[m['logits'], m['state'][0][1]])
            n += 1
            nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
            toks.append(nxt)
            if nxt == m['end_id']:
                break
            h, t = h2, np.array([[nxt]], np.int64)
        return toks, n


# ---- executor-level decode scan ---------------------------------------


def test_run_decode_multi_matches_per_slot_reference(nmt_decode):
    """K-steps-per-dispatch greedy scan == a per-slot host loop over
    the same step program (mixed stop conditions: EOS and budget), and
    the decode executable compiles ONCE across same-shape dispatches."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(0)
    S = 4
    h0 = rng.standard_normal((S, DIM)).astype('float32')
    budgets = np.array([5, 3, 8, 6], np.int32)

    ref = []
    with fluid.scope_guard(scope):
        for s in range(S):
            h = h0[s:s + 1]
            t = np.array([[m['start_id']]], np.int64)
            toks = []
            for _ in range(int(budgets[s])):
                lg, hn = exe.run(
                    m['step'], feed={'gen_token': t, 'gen_hidden': h},
                    fetch_list=[m['logits'], m['state'][0][1]])
                nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
                toks.append(nxt)
                if nxt == m['end_id']:
                    break
                h, t = hn, np.array([[nxt]], np.int64)
            ref.append(toks)

    decode = {'token': 'gen_token', 'logits': m['logits'],
              'state': m['state'], 'end_id': m['end_id']}
    carry = {'slots': {'gen_hidden': h0.copy()},
             'token': np.full((S, 1), m['start_id'], np.int64),
             'alive': np.ones((S, ), bool), 'remaining': budgets.copy()}
    got = [[] for _ in range(S)]
    before = exe.compile_count
    with fluid.scope_guard(scope):
        for _ in range(4):
            carry, toks, alive_in = exe.run_decode_multi(
                m['step'], carry=carry, steps=3, decode=decode,
                scope=scope)
            toks, alive_in = np.asarray(toks), np.asarray(alive_in)
            for i in range(toks.shape[0]):
                for s in range(S):
                    if alive_in[i, s]:
                        got[s].append(int(toks[i, s]))
            if not np.asarray(carry['alive']).any():
                break
    assert got == ref
    # one block compile + ONE decode-scan executable for the repeated
    # (steps, carry shape) signature
    assert exe.compile_count - before <= 2


def test_run_decode_multi_validates_carry_and_spec(nmt_decode):
    m, exe, scope = nmt_decode
    decode = {'token': 'gen_token', 'logits': m['logits'],
              'state': m['state'], 'end_id': m['end_id']}
    carry = {'slots': {'gen_hidden': np.zeros((2, DIM), 'float32')},
             'token': np.zeros((2, 1), np.int64),
             'alive': np.zeros((2, ), bool),
             'remaining': np.zeros((2, ), np.int32)}
    with pytest.raises(ValueError, match='missing'):
        exe.run_decode_multi(m['step'], carry={'slots': {}}, steps=2,
                             decode=decode, scope=scope)
    with pytest.raises(ValueError, match='decode='):
        exe.run_decode_multi(m['step'], carry=carry, steps=2,
                             decode={'token': 'gen_token'}, scope=scope)
    bad = dict(carry, slots={'nope': np.zeros((2, 2), 'float32')})
    with pytest.raises(ValueError, match='do not match'):
        exe.run_decode_multi(m['step'], carry=bad, steps=2,
                             decode=decode, scope=scope)
    with pytest.raises(ValueError, match='steps'):
        exe.run_decode_multi(m['step'], carry=carry, steps=0,
                             decode=decode, scope=scope)


def test_run_decode_multi_spmd_parity(nmt_decode):
    """The GSPMD decode scan (slots sharded over dp on the 8-device
    mesh) is token-identical to the single-device reference loop."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(1)
    S = 8
    h0 = rng.standard_normal((S, DIM)).astype('float32')
    budgets = np.array([5, 3, 8, 6, 2, 7, 4, 6], np.int32)
    ref = []
    with fluid.scope_guard(scope):
        for s in range(S):
            h = h0[s:s + 1]
            t = np.array([[m['start_id']]], np.int64)
            toks = []
            for _ in range(int(budgets[s])):
                lg, hn = exe.run(
                    m['step'], feed={'gen_token': t, 'gen_hidden': h},
                    fetch_list=[m['logits'], m['state'][0][1]])
                nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
                toks.append(nxt)
                if nxt == m['end_id']:
                    break
                h, t = hn, np.array([[nxt]], np.int64)
            ref.append(toks)
    pe = fluid.ParallelExecutor(main_program=m['step'], scope=scope)
    decode = {'token': 'gen_token', 'logits': m['logits'],
              'state': m['state'], 'end_id': m['end_id']}
    carry = {'slots': {'gen_hidden': h0.copy()},
             'token': np.full((S, 1), m['start_id'], np.int64),
             'alive': np.ones((S, ), bool), 'remaining': budgets.copy()}
    got = [[] for _ in range(S)]
    with fluid.scope_guard(scope):
        for _ in range(4):
            carry, toks, alive_in = pe.run_decode_multi(
                carry=carry, steps=3, decode=decode)
            toks, alive_in = np.asarray(toks), np.asarray(alive_in)
            for i in range(toks.shape[0]):
                for s in range(S):
                    if alive_in[i, s]:
                        got[s].append(int(toks[i, s]))
            if not np.asarray(carry['alive']).any():
                break
    assert got == ref
    # ragged slot counts reject instead of silently resharding
    bad = {'slots': {'gen_hidden': np.zeros((3, DIM), 'float32')},
           'token': np.zeros((3, 1), np.int64),
           'alive': np.zeros((3, ), bool),
           'remaining': np.zeros((3, ), np.int32)}
    with pytest.raises(ValueError, match='dp extent'):
        pe.run_decode_multi(carry=bad, steps=2, decode=decode)


# ---- engine generation lane -------------------------------------------


def test_engine_generation_token_identical_and_amortized(nmt_decode):
    """The ISSUE 7 acceptance smoke: N=8 mixed-length generation
    requests through the decode lane are TOKEN-IDENTICAL to per-request
    reference decode while issuing <= 1/3 the dispatches, with the
    executable count bounded by prefill rungs + the decode scan."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(2)
    lens = [3, 6, 9, 4, 8, 5, 7, 2]
    prompts = [_prompt(rng, l) for l in lens]
    max_lens = [8 + (i % 3) for i in range(len(prompts))]
    refs, ref_disp = [], 0
    for p, ml in zip(prompts, max_lens):
        toks, n = _reference_decode(m, exe, scope, p, ml)
        refs.append(toks)
        ref_disp += n

    spec = serving.GenerationSpec.from_model(m)
    # a FRESH executor so executor_compile_count isolates THIS engine's
    # executable set (the module fixture's exe accumulates across tests)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=fluid.Executor(fluid.CPUPlace()), place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=2, decode_slots=4,
            decode_steps=4),
        generation=spec, name='gen-parity')
    with eng:
        futs = [eng.submit_generate({'src_word_id': p}, max_len=ml)
                for p, ml in zip(prompts, max_lens)]
        outs = [list(f.result(120)) for f in futs]
    assert outs == refs
    mm = eng.metrics()
    d = mm['decode']
    lane_disp = mm['dispatches'] + d['dispatches']
    assert lane_disp * 3 <= ref_disp, (lane_disp, ref_disp)
    assert d['requests'] == d['finished'] == len(prompts)
    assert d['tokens'] == sum(len(r) for r in refs)
    assert d['tokens_per_dispatch'] > 1
    assert 0.0 < d['slot_occupancy'] <= 1.0
    # executable bound: prefill rung executables (per (bucket, rung)
    # signature x scan-width) + ONE decode-step executable per slot-
    # batch shape; with one slot shape this stays far under the
    # reference's per-request compile-free-but-dispatch-heavy loop
    assert mm['executor_compile_count'] <= 2 * len(set(lens)) + 1
    # trace: decode requests carry prefill/decode/detokenize stages and
    # the decode_steps count, summing to the measured e2e
    bd = futs[0].breakdown()
    assert bd['decode_steps'] == len(outs[0])
    for stage in ('queue', 'prefill', 'decode', 'detokenize'):
        assert stage in bd['stages_ms'], bd
    assert 'device' not in bd['stages_ms']
    gap = bd['e2e_ms'] - sum(bd['stages_ms'].values())
    assert abs(gap) < max(5.0, 0.1 * bd['e2e_ms']), bd


def test_engine_generation_late_join_continuous(nmt_decode):
    """Requests submitted WHILE the slot batch is decoding join at a
    step boundary (no drain barrier) and still decode exactly."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(4)
    lens_a, lens_b = [6, 9], [3, 7, 5]
    pa = [_prompt(rng, l) for l in lens_a]
    pb = [_prompt(rng, l) for l in lens_b]
    refs = [_reference_decode(m, exe, scope, p, 10)[0]
            for p in pa + pb]
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=1, decode_slots=2,
            decode_steps=2),
        generation=spec, name='gen-latejoin')
    with eng:
        futs = [eng.submit_generate({'src_word_id': p}, max_len=10)
                for p in pa]
        # wait for the first wave to be mid-decode, then pile on
        deadline = time.time() + 10
        while time.time() < deadline:
            d = eng.metrics()['decode']
            if d is not None and d['dispatches'] > 0:
                break
            time.sleep(0.005)
        futs += [eng.submit_generate({'src_word_id': p}, max_len=10)
                 for p in pb]
        outs = [list(f.result(120)) for f in futs]
    assert outs == refs


def test_mixed_traffic_hammer(nmt_decode):
    """Concurrent submit() forward requests and submit_generate()
    decode requests against ONE engine: decode outputs token-identical
    to sequential per-request runs, forward outputs equal to plain
    exe.run to a few ulp (the lot's eval scan and the step program are
    two executables), forward metrics unperturbed by the decode lane."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(5)
    lens = [3, 6, 9, 4]
    prompts = [_prompt(rng, l) for l in lens]
    refs = [_reference_decode(m, exe, scope, p, 8)[0] for p in prompts]
    # the forward surface is the prefill program itself (a perfectly
    # ordinary eval program): its reference is plain exe.run
    fwd_feeds = [{'src_word_id': _prompt(rng, l)} for l in (4, 7, 5, 8)]
    with fluid.scope_guard(scope):
        fwd_refs = [exe.run(m['prefill'], feed=dict(f),
                            fetch_list=m['prefill_fetches'])[0]
                    for f in fwd_feeds]
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=2, decode_slots=2,
            decode_steps=3),
        generation=spec, name='gen-hammer')
    results = {}

    def gen_client():
        futs = [eng.submit_generate({'src_word_id': p}, max_len=8)
                for p in prompts]
        results['gen'] = [list(f.result(120)) for f in futs]

    def fwd_client():
        futs = [eng.submit(dict(f)) for f in fwd_feeds]
        results['fwd'] = [f.result(120)[0] for f in futs]

    with eng:
        threads = [threading.Thread(target=gen_client),
                   threading.Thread(target=fwd_client)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert results['gen'] == refs
    for got, want in zip(results['fwd'], fwd_refs):
        assert_close_across_executables(got, want)
    mm = eng.metrics()
    # forward-path accounting counts ONLY forward traffic: generation
    # requests ride their own decode block
    assert mm['requests'] == len(fwd_feeds)
    assert mm['errors'] == 0
    assert mm['decode']['finished'] == len(prompts)


def test_mixed_traffic_spmd_mesh(nmt_decode):
    """The same mixed hammer on the 8-device mesh (dp-sharded slots +
    dp-sharded forward lots): decode token-identical, forward equal."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(6)
    prompts = [_prompt(rng, l) for l in (3, 6, 5, 4)]
    refs = [_reference_decode(m, exe, scope, p, 6)[0] for p in prompts]
    fwd_feed = {'src_word_id': _prompt(rng, 8)}
    with fluid.scope_guard(scope):
        fwd_ref, = exe.run(m['prefill'], feed=dict(fwd_feed),
                           fetch_list=m['prefill_fetches'])
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        parallel=True, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=8, max_wait_ms=2, decode_slots=8,
            decode_steps=3),
        generation=spec, name='gen-spmd')
    assert eng._decode_cache.slots % 8 == 0
    with eng:
        futs = [eng.submit_generate({'src_word_id': p}, max_len=6)
                for p in prompts]
        ffut = eng.submit(dict(fwd_feed))
        outs = [list(f.result(180)) for f in futs]
        fwd_out = ffut.result(180)[0]
    assert outs == refs
    np.testing.assert_allclose(np.asarray(fwd_out), np.asarray(fwd_ref),
                               atol=1e-6)


# ---- pipelined decode chain (ISSUE 9) ---------------------------------


def test_chained_lane_token_identical_and_fewer_syncs(nmt_decode):
    """The ISSUE 9 acceptance smoke at engine level: the chained lane
    (decode_pipeline_depth=2) is bitwise token-identical to the
    per-scan-sync lane (depth 1) over the same mixed-length stream,
    with strictly fewer device-idling host syncs, at the same dispatch
    count (chaining must not add wasted frozen scans here — the
    budget-aware dispatch bound)."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(12)
    lens = [3, 6, 9, 4, 8, 5]
    prompts = [_prompt(rng, l) for l in lens]
    spec = serving.GenerationSpec.from_model(m)
    outs, mets = {}, {}
    for depth in (1, 2):
        eng = serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=fluid.CPUPlace(),
            config=serving.ServingConfig(
                max_batch_size=8, max_wait_ms=2, decode_slots=4,
                decode_steps=3, decode_pipeline_depth=depth),
            generation=spec, name='gen-chain-d%d' % depth)
        with eng:
            futs = [eng.submit_generate({'src_word_id': p}, max_len=8)
                    for p in prompts]
            outs[depth] = [list(f.result(120)) for f in futs]
        mets[depth] = eng.metrics()['decode']
    assert outs[2] == outs[1]
    d1, d2 = mets[1], mets[2]
    # the synced lane pays one device-idling sync per scan by
    # construction; the chained lane only syncs at flush/tail points
    assert d1['host_syncs'] == d1['dispatches']
    assert d2['host_syncs'] < d1['host_syncs']
    assert d2['dispatches'] <= d1['dispatches'] + 1
    assert d2['tokens'] == d1['tokens']
    assert d2['host_syncs_per_token'] < d1['host_syncs_per_token']
    # the chain really held scans in flight: some harvests were
    # non-blocking (harvests > syncs)
    assert d2['harvests'] > d2['host_syncs']


def test_stop_races_inflight_decode_chain(nmt_decode):
    """ISSUE 9 satellite: stop() racing an in-flight decode chain —
    the stop-drain harvests the chain dry, every generation future
    resolves (token-correct for admitted work, typed for post-close
    submits), and nothing hangs."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(13)
    lens = [4, 7, 5, 8, 3, 6]
    prompts = [_prompt(rng, l) for l in lens]
    refs = [_reference_decode(m, exe, scope, p, 10)[0]
            for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    for trial in range(3):
        eng = serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=fluid.CPUPlace(),
            config=serving.ServingConfig(
                max_batch_size=8, max_wait_ms=1, decode_slots=2,
                decode_steps=1, decode_pipeline_depth=3),
            generation=spec, name='gen-stoprace-%d' % trial).start()
        futs = [eng.submit_generate({'src_word_id': p}, max_len=10)
                for p in prompts]
        # let the chain build (decode scans in flight), then stop
        deadline = time.time() + 10
        while time.time() < deadline:
            d = eng.metrics()['decode']
            if d is not None and d['dispatches'] > trial:
                break
            time.sleep(0.002)
        eng.stop()
        assert not eng._decode_inflight  # the chain drained
        for f, want in zip(futs, refs):
            # stop() drains the queue and the lane: every pre-close
            # submit must deliver its exact tokens
            assert list(f.result(60)) == want
        with pytest.raises(serving.EngineClosedError):
            eng.submit_generate({'src_word_id': prompts[0]})


def test_stop_races_inflight_decode_chain_mesh(nmt_decode):
    """The same stop-vs-chain race on the 8-device mesh (dp-sharded
    slots): the chain drains, futures resolve token-identical."""
    m, exe, scope = nmt_decode
    rng = np.random.RandomState(14)
    prompts = [_prompt(rng, l) for l in (3, 5, 4)]
    refs = [_reference_decode(m, exe, scope, p, 5)[0] for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        parallel=True, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=1, decode_slots=8,
            decode_steps=1, decode_pipeline_depth=2),
        generation=spec, name='gen-stoprace-mesh').start()
    futs = [eng.submit_generate({'src_word_id': p}, max_len=5)
            for p in prompts]
    deadline = time.time() + 30
    while time.time() < deadline:
        d = eng.metrics()['decode']
        if d is not None and d['dispatches'] > 0:
            break
        time.sleep(0.002)
    eng.stop()
    assert not eng._decode_inflight
    for f, want in zip(futs, refs):
        assert list(f.result(120)) == want


# ---- KV-cache (transformer) state ------------------------------------


def test_kv_cache_decode_token_identical():
    """A REAL per-slot KV cache ([S, max_ctx, d_k] slabs + position
    counter) through the lane: narrow prefill prefixes zero-pad into
    the slab, the step's one_hot scatter + masked attention extend it,
    outputs token-identical to per-request decode."""
    MC = 16
    m = transformer.build_step_decode(vocab=30, d_model=8, d_k=8,
                                      max_ctx=MC, max_len=6)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    rng = np.random.RandomState(7)
    lens = [3, 5, 4, 6]
    prompts = [rng.randint(2, 30, size=(l, 1)).astype('int64')
               for l in lens]

    def ref(prompt):
        l = prompt.shape[0]
        with fluid.scope_guard(scope):
            k0, v0, p0 = exe.run(
                m['prefill'],
                feed={'gen_src': prompt[None],
                      'gen_src_len': np.array([[l]], np.float32)},
                fetch_list=m['prefill_fetches'])
            k = np.zeros((1, MC, 8), np.float32)
            k[:, :l] = k0
            v = np.zeros((1, MC, 8), np.float32)
            v[:, :l] = v0
            p = p0.astype(np.float32)
            t = np.array([[m['start_id']]], np.int64)
            toks = []
            for _ in range(m['max_len']):
                lg, k, v, p = exe.run(
                    m['step'],
                    feed={'gen_token': t, 'gen_k': k, 'gen_v': v,
                          'gen_pos': p},
                    fetch_list=[m['logits']] +
                    [f for _, f in m['state']])
                nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
                toks.append(nxt)
                if nxt == m['end_id']:
                    break
                t = np.array([[nxt]], np.int64)
            return toks

    refs = [ref(p) for p in prompts]
    spec = serving.GenerationSpec.from_model(m)
    assert spec.slot_shapes['gen_k'] == (MC, 8)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(),
        config=serving.ServingConfig(
            max_batch_size=4, max_wait_ms=2, decode_slots=2,
            decode_steps=3,
            trailing_ladders={'gen_src': [4, 8]}),
        generation=spec, name='kv-gen')
    with eng:
        futs = [eng.submit_generate(
            {'gen_src': p[None],
             'gen_src_len': np.array([[p.shape[0]]], np.float32)})
            for p in prompts]
        outs = [list(f.result(180)) for f in futs]
    assert outs == refs


# ---- registry / arbiter ----------------------------------------------


def test_registry_decode_cache_account_warm_evict(nmt_decode):
    """The decode-state cache is a first-class HBMArbiter account:
    admitted at load, warmable (decode_prefill rungs), evictable on
    its own (slabs demote to host bitwise, generation resumes after
    transparent re-staging), and dropped at unload."""
    m, exe, scope = nmt_decode
    spec = serving.GenerationSpec.from_model(m)
    reg = serving.ModelRegistry()
    eng = reg.load('nmt', program=m['prefill'],
                   feed_names=m['prefill_feeds'],
                   fetch_list=m['prefill_fetches'], scope=scope,
                   executor=exe, generation=spec,
                   config=serving.ServingConfig(decode_slots=2,
                                                decode_steps=3))
    try:
        snap = reg.arbiter.snapshot()
        acct = snap['accounts']['nmt:decode-cache']
        assert acct['resident'] and acct['bytes'] == \
            spec.cache_nbytes(eng._decode_cache.slots)
        # warm the prefill rungs + decode scan, then serve: no new
        # compiles at a warmed rung
        assert reg.warm('nmt', decode_prefill=[4]) == 1
        cc0 = eng.metrics()['executor_compile_count']
        rng = np.random.RandomState(8)
        prompt = _prompt(rng, 4)
        want = _reference_decode(m, exe, scope, prompt, 6)[0]
        out = reg.generate('nmt', {'src_word_id': prompt}, max_len=6)
        assert list(out) == want
        assert eng.metrics()['executor_compile_count'] == cc0
        # evict ONLY the cache: slabs demote to host, next generation
        # re-stages transparently and stays bitwise
        moved = reg._evict_to_host('nmt:decode-cache')
        assert moved > 0
        assert isinstance(eng._decode_cache._slabs['gen_hidden'],
                          np.ndarray)
        out2 = reg.generate('nmt', {'src_word_id': prompt}, max_len=6)
        assert list(out2) == want
        reg.unload('nmt')
        assert 'nmt:decode-cache' not in \
            reg.arbiter.snapshot()['accounts']
    finally:
        reg.stop()


def test_registry_cache_alone_over_budget_is_typed_reject(nmt_decode):
    """A decode cache that can NEVER fit the budget is an
    HBMBudgetError at load() — typed, with nothing leaked — not an OOM
    mid-generation."""
    m, exe, scope = nmt_decode
    from paddle_tpu.serving.arbiter import program_seed_bytes
    # size the cache far above the model seed, then pick a budget
    # between them: the model admits, the cache alone cannot fit
    big = serving.GenerationSpec.from_model(m)
    big.slot_shapes['gen_hidden'] = (1 << 16, )
    model_seed = program_seed_bytes(m['prefill'], 32)
    cache_bytes = big.cache_nbytes(64)
    assert cache_bytes > 4 * model_seed
    reg = serving.ModelRegistry(
        hbm_budget_bytes=model_seed + cache_bytes // 2)
    try:
        with pytest.raises(serving.HBMBudgetError) as ei:
            reg.load('big', program=m['prefill'],
                     feed_names=m['prefill_feeds'],
                     fetch_list=m['prefill_fetches'], scope=scope,
                     executor=exe, generation=big,
                     config=serving.ServingConfig(decode_slots=64))
        assert ei.value.model == 'big:decode-cache'
        assert reg.models() == []
        assert reg.arbiter.snapshot()['accounts'] == {}
    finally:
        reg.stop()


# ---- observability ----------------------------------------------------


def test_decode_error_dumps_slot_map(nmt_decode, monkeypatch):
    """A decode-scan failure errors the slotted requests' futures (the
    worker survives) and the flight dump carries the slot map."""
    m, exe, scope = nmt_decode
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(),
        config=serving.ServingConfig(decode_slots=2, decode_steps=2),
        generation=spec, name='gen-err')
    monkeypatch.setattr(
        exe, '_dispatch_decode_multi',
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError('boom')))
    rng = np.random.RandomState(9)
    fut = eng.submit_generate({'src_word_id': _prompt(rng, 4)},
                              max_len=4)
    with pytest.raises(RuntimeError, match='boom'):
        fut.result(60)
    dump = trace_mod.flight_recorder.last_dump
    assert dump['reason'] == 'decode_error:gen-err'
    sm = dump['extra']['slot_map']
    assert sm['active'] == 1
    assert fut.trace_id in sm['slot_trace_ids']
    # the engine survives the failed scan: undo the fault and serve
    monkeypatch.undo()
    prompt = _prompt(rng, 3)
    want = _reference_decode(m, exe, scope, prompt, 4)[0]
    out = eng.generate({'src_word_id': prompt}, max_len=4, timeout=60)
    assert list(out) == want
    eng.stop()


def test_stall_context_carries_decode_slot_map(nmt_decode):
    """The watchdog's stall dump view includes the decode slot map and
    the pending-admission count for a generation engine."""
    m, exe, scope = nmt_decode
    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(),
        config=serving.ServingConfig(decode_slots=2),
        generation=spec, name='gen-stall')
    ctx = eng._stall_context()
    assert ctx['decode_slot_map']['slots'] == 2
    assert ctx['decode_slot_map']['free'] == 2
    assert ctx['decode_pending'] == 0
    eng.stop()


# ---- units ------------------------------------------------------------


def test_microbatcher_separates_kinds():
    """Same-signature requests of different kinds never share a lot."""
    from paddle_tpu.serving.batcher import InferenceRequest, MicroBatcher
    from paddle_tpu.serving.decode import GenerationRequest
    b = MicroBatcher(max_batch_size=8, max_wait_s=60)
    sig = (('x', (2, ), 'float32'), )
    fwd = InferenceRequest({'x': np.zeros((1, 2))}, 1, sig)
    gen = GenerationRequest({'x': np.zeros((1, 2))}, 1, sig, max_len=4)
    fwd2 = InferenceRequest({'x': np.zeros((1, 2))}, 1, sig)
    for r in (fwd, gen, fwd2):
        b.submit(r)
    lot = b.next_lot(timeout=0, force=True)
    assert lot == [fwd, fwd2]
    assert b.next_lot(timeout=0, force=True) == [gen]


def test_generation_spec_validation(nmt_decode):
    m, exe, scope = nmt_decode
    with pytest.raises(ValueError, match='align'):
        serving.GenerationSpec(
            m['prefill'], m['step'], m['prefill_feeds'], [],
            'gen_token', m['logits'], m['state'])
    with pytest.raises(ValueError, match='state pair'):
        serving.GenerationSpec(
            m['prefill'], m['step'], m['prefill_feeds'], [],
            'gen_token', m['logits'], [])
    with pytest.raises(ValueError, match='max_len'):
        serving.GenerationSpec(
            m['prefill'], m['step'], m['prefill_feeds'],
            m['prefill_fetches'], 'gen_token', m['logits'], m['state'],
            max_len=0)
    spec = serving.GenerationSpec.from_model(m)
    assert spec.slot_shapes['gen_hidden'] == (DIM, )
    assert spec.cache_nbytes(4) > 0
    # submit_generate validations ride a throwaway engine
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(), generation=spec,
        name='gen-val')
    with pytest.raises(ValueError, match='do not match'):
        eng.submit_generate({'bogus': np.zeros((1, 2))})
    rng = np.random.RandomState(11)
    with pytest.raises(ValueError, match='max_len'):
        eng.submit_generate({'src_word_id': _prompt(rng, 3)}, max_len=0)
    with pytest.raises(ValueError, match='ONE sequence'):
        eng.submit_generate({'src_word_id': fluid.create_lod_tensor(
            [[[2]], [[3]]], [[1, 1]])})
    eng.stop()
    plain = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(), name='no-gen')
    with pytest.raises(RuntimeError, match='generation'):
        plain.submit_generate({'src_word_id': _prompt(rng, 3)})
    plain.stop()
    # an LoD prompt with trailing bucketing DISABLED rides the
    # unbatchable path: the reject must say why, not 'got None rows'
    nobuck = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=fluid.CPUPlace(), generation=spec,
        config=serving.ServingConfig(trailing_buckets=False),
        name='gen-nobuck')
    with pytest.raises(ValueError, match='trailing bucketing'):
        nobuck.submit_generate({'src_word_id': _prompt(rng, 3)})
    nobuck.stop()
    # generation= with a saved-model dir is rejected BEFORE an engine
    # (and its profiler registration) exists
    reg = serving.ModelRegistry()
    with pytest.raises(ValueError, match='requires program='):
        reg.load('saved', dirname='/nonexistent', generation=spec)
    assert reg.models() == []
    reg.stop()
