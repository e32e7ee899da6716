"""Serving fleet tier (ISSUE 17): ReplicaServer + FleetRouter.

Router POLICY (balance, affinity, overload, failover, exactly-once)
is tested against toy duck-typed registries — precise control over
refusals and execution counts, no device work.  END-TO-END token
identity under replica kill runs against REAL ModelRegistry replicas
sharing one parameter scope: the chaos lane (seeded FaultInjector lost
responses + a mid-stream ``ReplicaServer.close()`` kill) must finish
every request exactly once with token output identical to the
fault-free single-registry reference — the PR-15 master-kill contract,
lifted to the serving fleet."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.distributed import FaultInjector, \
    ServiceUnavailableError
from paddle_tpu.distributed.transport import RetryPolicy
from paddle_tpu.models import seq2seq
from paddle_tpu.serving.fleet import _wire_decode, _wire_encode

# fast-failing retries: a dropped response costs one socket-timeout
# stall (2s) before the retry, a dead replica refuses instantly
_FAST = dict(retry=RetryPolicy(max_attempts=3, base_backoff_s=0.01,
                               max_backoff_s=0.05, deadline_s=15.0),
             timeout=2.0)


# ---- toy replica registry (duck-typed ModelRegistry surface) ----------


class _InstantFuture(object):
    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        return self._value


class _ToyRegistry(object):
    """Deterministic, instant registry: infer doubles feed['x'],
    generate counts up from feed['x'][0].  ``overloaded`` flips the
    typed at-the-door refusal; ``executed`` records every real
    execution (the exactly-once ledger)."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.depth = 0
        self.overloaded = False
        self.executed = []
        self._lock = threading.Lock()

    def _admit(self, model):
        if self.overloaded:
            raise serving.OverloadedError(model, 7, 0.0, 0.25)

    def submit(self, model, feed, return_numpy=True, priority=0,
               deadline_ms=None):
        self._admit(model)
        if self.delay_s:
            time.sleep(self.delay_s)
        x = np.asarray(feed['x'])
        with self._lock:
            self.executed.append(('infer', float(x.ravel()[0])))
        return _InstantFuture([x * 2.0])

    def submit_generate(self, model, feed, max_len=None, priority=0,
                        deadline_ms=None):
        self._admit(model)
        if self.delay_s:
            time.sleep(self.delay_s)
        seed = int(np.asarray(feed['x']).ravel()[0])
        with self._lock:
            self.executed.append(('generate', seed))
        n = int(max_len or 4)
        return _InstantFuture(np.arange(seed, seed + n, dtype=np.int64))

    def queue_depths(self):
        return {'toy': self.depth}

    def status(self):
        return {'models': {'toy': {'queue_depth': self.depth}}}

    def metrics(self):
        return {'models': {'toy': {}}}


def _toy_fleet(n=2, **router_kw):
    regs = [_ToyRegistry() for _ in range(n)]
    reps = [serving.ReplicaServer(r) for r in regs]
    kw = dict(_FAST)
    kw.update(router_kw)
    router = serving.FleetRouter(reps, **kw)
    return regs, reps, router


def _shutdown(reps, router):
    router.close()
    for r in reps:
        r.close()


# ---- wire codec -------------------------------------------------------


def test_wire_codec_roundtrips_arrays_and_lod():
    rng = np.random.RandomState(0)
    cases = [
        rng.standard_normal((3, 4)).astype('float32'),
        np.arange(6, dtype=np.int64).reshape(2, 3),
        np.zeros((0, 4), np.float32),          # empty keeps shape
        np.array(3.5, np.float64),             # 0-d
        np.array([True, False]),
    ]
    for arr in cases:
        back = _wire_decode(_wire_encode(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert np.array_equal(back, arr)
    lt = fluid.create_lod_tensor(
        np.arange(5, dtype=np.int64).reshape(5, 1).tolist(), [[2, 3]])
    back = _wire_decode(_wire_encode(lt))
    assert [list(l) for l in back.lod()] == [list(l) for l in lt.lod()]
    assert np.array_equal(np.asarray(back.numpy()),
                          np.asarray(lt.numpy()))
    # nesting + plain scalars survive untouched
    nested = {'a': [1, 'x', None], 'b': {'c': np.float32(2.5)}}
    out = _wire_decode(_wire_encode(nested))
    assert out['a'] == [1, 'x', None] and out['b']['c'] == 2.5


# ---- routing policy (toy replicas) ------------------------------------


def test_infer_parity_and_balance_across_replicas():
    """Results match the registry's own math and a burst of forwards
    spreads over BOTH replicas (score-balanced, no affinity)."""
    regs, reps, router = _toy_fleet(2)
    try:
        futs = [router.submit('toy', {'x': np.full((2, 2), float(i))})
                for i in range(12)]
        for i, f in enumerate(futs):
            out, = f.result(10)
            assert np.array_equal(out, np.full((2, 2), 2.0 * i))
            assert f.latency_s is not None and f.breakdown()['replica'] \
                in (0, 1)
        m = router.metrics()
        assert m['dispatches'] == 12 and m['failovers'] == 0
        assert all(m['replicas'][i]['dispatches'] > 0 for i in (0, 1))
        assert sum(len(r.executed) for r in regs) == 12
    finally:
        _shutdown(reps, router)


def test_session_affinity_pins_generates_while_forwards_float():
    regs, reps, router = _toy_fleet(2)
    try:
        sessions = ['s%d' % i for i in range(4)]
        for rnd in range(3):               # 3 generates per session
            for i, s in enumerate(sessions):
                tok = router.generate('toy', {'x': np.array([10 * i])},
                                      max_len=3, session=s)
                assert list(tok) == [10 * i, 10 * i + 1, 10 * i + 2]
        # interleave forwards: they must NOT be captured by affinity
        for i in range(8):
            router.infer('toy', {'x': np.array([[float(i)]])})
        log = router.session_dispatches()
        assert set(log) == set(sessions)
        for s in sessions:
            assert len(log[s]) == 3 and len(set(log[s])) == 1
        m = router.metrics()
        assert all(m['replicas'][i]['dispatches'] > 0 for i in (0, 1))
        assert m['sessions'] == 4
    finally:
        _shutdown(reps, router)


def test_overload_routes_around_one_hot_replica():
    regs, reps, router = _toy_fleet(2)
    try:
        regs[0].overloaded = True
        for i in range(4):
            out, = router.infer('toy', {'x': np.array([[1.0]])})
            assert out[0, 0] == 2.0
        m = router.metrics()
        assert m['routed_around_overload'] >= 1
        assert m['fleet_overloads'] == 0
        assert all(kind == 'infer' for kind, _ in regs[1].executed)
        assert not any(k == 'infer' for k, _ in regs[0].executed)
    finally:
        _shutdown(reps, router)


def test_fleet_overload_is_typed_with_min_retry_after():
    """Every live replica refusing -> ONE typed fleet-level
    OverloadedError carrying the smallest retry_after hint."""
    regs, reps, router = _toy_fleet(2)
    try:
        for r in regs:
            r.overloaded = True
        with pytest.raises(serving.OverloadedError) as ei:
            router.infer('toy', {'x': np.array([[1.0]])})
        assert ei.value.retry_after_s == pytest.approx(0.25)
        assert router.metrics()['fleet_overloads'] == 1
    finally:
        _shutdown(reps, router)


def test_pinned_session_overload_is_final_not_migrated():
    """Decode state does not migrate for LOAD: the pinned replica's
    refusal is the fleet answer even with an idle replica next door."""
    regs, reps, router = _toy_fleet(2)
    try:
        router.generate('toy', {'x': np.array([0])}, max_len=2,
                        session='pin')
        pinned = router.session_dispatches()['pin'][0]
        regs[pinned].overloaded = True
        with pytest.raises(serving.OverloadedError):
            router.generate('toy', {'x': np.array([0])}, max_len=2,
                            session='pin')
        # an unpinned generate still routes around the hot replica
        tok = router.generate('toy', {'x': np.array([5])}, max_len=2)
        assert list(tok) == [5, 6]
        assert len(set(router.session_dispatches()['pin'])) == 1
    finally:
        _shutdown(reps, router)


def test_replica_death_fails_over_and_repins_session():
    regs, reps, router = _toy_fleet(2)
    try:
        router.generate('toy', {'x': np.array([0])}, max_len=2,
                        session='s')
        pinned = router.session_dispatches()['s'][0]
        reps[pinned].close()               # host loss, mid-stream
        tok = router.generate('toy', {'x': np.array([3])}, max_len=2,
                              session='s')
        assert list(tok) == [3, 4]         # re-prefilled on survivor
        log = router.session_dispatches()['s']
        assert len(set(log)) == 2 and log[-1] != pinned
        m = router.metrics()
        assert m['replica_deaths'] == 1 and m['failovers'] >= 1 \
            and m['re_prefills'] >= 1
        assert m['replicas'][pinned]['dead']
        # forwards keep flowing on the survivor
        out, = router.infer('toy', {'x': np.array([[2.0]])})
        assert out[0, 0] == 4.0
    finally:
        _shutdown(reps, router)


def test_all_replicas_dead_is_typed_unavailable():
    regs, reps, router = _toy_fleet(2)
    try:
        for r in reps:
            r.close()
        with pytest.raises(ServiceUnavailableError):
            router.infer('toy', {'x': np.array([[1.0]])})
    finally:
        _shutdown(reps, router)


def test_lost_response_dedups_exactly_once():
    """A scripted lost response makes the resilient client RETRY the
    same rid; the replica's dedup window replays the recorded answer —
    the registry executed the request ONCE."""
    fi = FaultInjector(seed=3)
    fi.script('server_send', 'infer', 'drop_response', nth=1, times=1)
    reg = _ToyRegistry()
    rep = serving.ReplicaServer(reg, fault_injector=fi)
    router = serving.FleetRouter([rep], **_FAST)
    try:
        # the lost response costs one 2s socket-timeout stall before
        # the retry lands — wait past it
        out, = router.infer('toy', {'x': np.array([[4.0]])},
                            timeout=10)
        assert out[0, 0] == 8.0
        assert fi.applied == 1
        assert len(reg.executed) == 1      # dedup, not re-execution
        served = router._rpc(router._replicas[0], 'metrics')['served']
        assert served['dedup_replays'] == 1 and served['infers'] == 1
    finally:
        _shutdown([rep], router)


def test_status_and_metrics_over_the_wire():
    regs, reps, router = _toy_fleet(2)
    try:
        regs[1].depth = 5
        st = router.status()
        assert not st[0]['dead'] and not st[1]['dead']
        assert st[1]['depth'] == 5
        assert st[0]['status']['models']['toy']['queue_depth'] == 0
        reps[0].close()
        st = router.status()
        assert st[0]['dead'] and not st[1]['dead']
        m = router.metrics()
        assert m['replicas'][0]['dead']
    finally:
        _shutdown(reps, router)


def test_submit_rejects_non_numpy_and_closed_router():
    regs, reps, router = _toy_fleet(1)
    try:
        with pytest.raises(ValueError, match='return_numpy'):
            router.submit('toy', {'x': np.zeros(1)}, return_numpy=False)
    finally:
        _shutdown(reps, router)
    with pytest.raises(RuntimeError, match='closed'):
        router.submit('toy', {'x': np.zeros(1)})


# ---- end-to-end: real registries, token identity under chaos ----------

V_SRC, DIM = 24, 10


@pytest.fixture(scope='module')
def gen_model():
    m = seq2seq.build_step_decode(
        src_dict_dim=V_SRC, trg_dict_dim=20, embedding_dim=6,
        encoder_size=DIM, decoder_size=DIM, max_len=8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    return m, exe, scope


def _prompt(rng, l):
    ids = rng.randint(2, V_SRC, size=(l, 1))
    return fluid.create_lod_tensor(ids.tolist(), [[l]])


def _load_replica(m, exe, scope):
    """One replica registry over the SHARED parameter scope — replicas
    serve the same weights, so greedy decode is token-identical
    across them (the re-prefill failover invariant)."""
    reg = serving.ModelRegistry()
    reg.load('nmt', program=m['prefill'],
             feed_names=m['prefill_feeds'],
             fetch_list=m['prefill_fetches'], scope=scope,
             executor=exe,
             generation=serving.GenerationSpec.from_model(m),
             config=serving.ServingConfig(decode_slots=2,
                                          decode_steps=3))
    return reg


def test_fleet_generate_token_identical_under_replica_kill(gen_model):
    """THE chaos acceptance: 2 replicas, pinned decode sessions, a
    seeded lost-response fault AND a mid-stream replica kill — every
    request finishes exactly once, token-identical to the fault-free
    single-registry reference."""
    m, exe, scope = gen_model
    rng = np.random.RandomState(11)
    sessions = ['s%d' % i for i in range(3)]
    prompts = {s: [_prompt(rng, 3 + (i + j) % 3) for j in range(2)]
               for i, s in enumerate(sessions)}

    # fault-free reference: one plain registry
    ref_reg = _load_replica(m, exe, scope)
    want = {}
    with ref_reg:
        for s in sessions:
            want[s] = [list(ref_reg.generate(
                'nmt', {'src_word_id': p}, max_len=6))
                for p in prompts[s]]

    fi = FaultInjector(seed=7)
    fi.script('server_send', 'generate', 'drop_response', nth=1,
              times=1)
    regs = [_load_replica(m, exe, scope) for _ in range(2)]
    reps = [serving.ReplicaServer(regs[0], fault_injector=fi),
            serving.ReplicaServer(regs[1])]
    router = serving.FleetRouter(reps, **_FAST)
    try:
        with regs[0], regs[1]:
            got = {s: [] for s in sessions}
            # round 1 pins every session
            for s in sessions:
                got[s].append(list(router.generate(
                    'nmt', {'src_word_id': prompts[s][0]}, max_len=6,
                    session=s, timeout=60)))
            log1 = router.session_dispatches()
            assert all(len(set(log1[s])) == 1 for s in sessions)
            # kill the replica that holds at least one pinned session
            victim = log1[sessions[0]][0]
            reps[victim].close()
            # round 2: victims re-prefill on the survivor, the rest
            # stay pinned
            for s in sessions:
                got[s].append(list(router.generate(
                    'nmt', {'src_word_id': prompts[s][1]}, max_len=6,
                    session=s, timeout=60)))
        assert got == want                 # zero lost, zero mutated
        assert fi.applied == 1        # the scripted lost response
        m_ = router.metrics()
        assert m_['replica_deaths'] == 1 and m_['failovers'] >= 1
        log2 = router.session_dispatches()
        survivor = 1 - victim
        for s in sessions:
            # structurally affine: one replica fault-free, at most two
            # across a kill, and post-kill everything sits on the
            # survivor
            assert len(set(log2[s])) <= 2
            assert log2[s][-1] == survivor
    finally:
        _shutdown(reps, router)


def test_fleet_infer_parity_with_direct_registry(gen_model):
    """Forward lots through the router == the registry's own outputs
    (the codec is lossless end to end), balanced over both replicas."""
    m, exe, scope = gen_model
    rng = np.random.RandomState(5)
    prompts = [_prompt(rng, 3 + i % 3) for i in range(6)]

    ref_reg = _load_replica(m, exe, scope)
    with ref_reg:
        want = [np.asarray(ref_reg.infer(
            'nmt', {'src_word_id': p})[0]) for p in prompts]

    regs = [_load_replica(m, exe, scope) for _ in range(2)]
    reps = [serving.ReplicaServer(r) for r in regs]
    router = serving.FleetRouter(reps, **_FAST)
    try:
        with regs[0], regs[1]:
            # one prompt in flight at a time: a replica coalesces what
            # arrives together into one lot, which is another executable
            # than the reference's one-prompt lots and may differ in the
            # last bits; the unprobed replica scores best, so both serve
            got = [np.asarray(router.submit(
                'nmt', {'src_word_id': p}).result(60)[0]) for p in prompts]
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=0, atol=0)
        m_ = router.metrics()
        assert all(m_['replicas'][i]['dispatches'] > 0 for i in (0, 1))
    finally:
        _shutdown(reps, router)
