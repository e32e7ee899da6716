"""Perf-regression gate: framework vs independent pure-JAX bound, in ONE
process with INTERLEAVED timing blocks, for all three compute-bound
bench configs (VERDICT r4 next-#3; r3 next-#8 established the pattern
for ResNet).

Invariant per config: the whole-program XLA compile must not cost
throughput vs hand-rolled JAX — gated on the MAX of PER-BLOCK ratios
(each comparison shares a drift window; ADVICE r4 #3 killed the old
max(fw)/max(bd) cross-window pairing).

Run on TPU hardware:
    python tools/perf_gate.py [resnet|transformer|nmt|resnet_infer|
        feed_pipeline|multi_model|trailing_dim|trace_overhead|decode|
        decode_overlap|chunked_prefill|slo|sparse_grad|embed_cache|
        elastic|master_chaos|all]
Prints one JSON line per config and exits non-zero off-TPU (ONE process:
framework and bound share the chip, no child is started);
tests/test_perf_gate.py drives the hardware-free run_* smokes on CPU.  ``resnet_infer`` (ISSUE 2) has no bound side —
its deliverable is the paired ``multi_vs_dispatch`` block: the measured
dispatch tax Executor.run_eval_multi removes from the serving path.
``feed_pipeline`` (ISSUE 3) likewise pairs overlapped-vs-blocked input
staging: the throughput fluid.FeedPipeline recovers by staging scan
block N+1 while dispatch N computes (feed_stall ~ 0 after warmup).
``multi_model`` (ISSUE 4) pairs resident-vs-evict-reload serving: two
models under ONE ModelRegistry HBM budget sized for only one of them —
the evict-reload window's latency tax is the measured cost of LRU
weight arbitration (host demotion + re-stage + recompile per swap),
the resident window the same registry with no arbitration pressure.
``trailing_dim`` (ISSUE 5) pairs bucketed-vs-exact-shape serving on a
SKEWED synthetic length distribution: the bucketed engine quantizes
request seq-lens onto the shared TrailingDimBuckets ladder (mixed
lengths coalesce, bounded executables), the exact engine serves every
distinct length as its own per-shape lot/executable — the deliverable
is the executable-count, padding-waste and throughput deltas.
``trace_overhead`` (ISSUE 6) pairs tracing-on vs tracing-off serving
over ONE engine/scope: the traced window runs inside a
fluid.trace.tracing() span-capture window (per-request stage
breakdowns are always on; the window adds the span log every profiler
event mirrors into), the untraced window is the same engine outside
it — the record asserts the observability layer's request-path
overhead stays bounded (traced_vs_untraced >= PERF_GATE_TRACE_MIN,
default 0.8, on the best shared drift window).
``decode`` (ISSUE 7) pairs continuous-batching generation against
one-call-per-step per-request decode over the same mixed-length
request stream: the lane side runs prompts through the engine's
slot-based decode lane (prefill lots + K-step in-jit decode scans),
the reference side replays the reference's serving shape (one graph
call per decode step per request) — outputs are asserted
token-identical, and the hard gates are ``dispatch_ratio`` <=
PERF_GATE_DECODE_RATIO_MAX (default 1/3) and ``tokens_per_dispatch``
>= PERF_GATE_DECODE_TPD_MIN (default 4.0).
``slo`` (ISSUE 8) pairs deadline-scheduled vs FIFO serving under the
SAME overloaded open-loop Poisson stream (serving.OpenLoopLoadGen,
one seed — identical arrivals and payloads on both sides): the EDF
engine schedules earliest-deadline-first and SHEDS past-deadline work
(typed DeadlineExceededError + 'shed' trace stage), the FIFO engine
serves everything late.  Within-deadline responses are asserted
bitwise-identical across the two engines, and the hard gate is
``goodput_ratio`` (in-deadline responses, EDF over FIFO) >=
PERF_GATE_SLO_GOODPUT_MIN (default 1.3).  ISSUE 9 sharpened the shed
contract: the record also runs a DETERMINISTIC per-signature horizon
check — a mixed-shape queue whose slow signature measures 200x the
fast one sheds the slow-signature request at lot formation while the
old global min-wall horizon would have admitted it toward certain
deadline death (and keeps the fast request either way).
``sparse_grad`` (ISSUE 11) pairs the SPARSE embedding-gradient lane
(``is_sparse=True``: the lookup backward is a SparseRows rows/values
pytree and the optimizer applies ONE row-subset scatter-update per
step — the dense [V, D] gradient is never built inside the jit)
against the DENSE lane (``is_sparse=False``: scatter-add into a full
[V, D] grad + a dense optimizer sweep) over the IDENTICAL seeded
zipfian-id CTR stream, trained K steps per dispatch through
Executor.run_multi on BOTH sides.  Final params are asserted
allclose-identical first; the hard gates are ``step_time_ratio``
(sparse wall over dense wall, best shared drift window) <=
PERF_GATE_SPARSE_RATIO_MAX (default 1.0 — sparsity must never cost
step time) and the STRUCTURAL assert that no [V, D]-sized gradient
buffer appears in the sparse lane's cost report: its timed
executable's XLA temp-buffer bytes stay BELOW one table's size while
the dense lane's meet or exceed it (the counterfactual proving the
probe sees the buffer).
``embed_cache`` (ISSUE 12) pairs the TWO-TIER hot-row embedding cache
(a [C, D] HBM slab + host-resident [V, D] master, ids remapped to
slots, row exchange between scan dispatches) against full-table
training over the IDENTICAL seeded hot-zipfian CTR stream.  Final
params are asserted allclose with the table itself BITWISE (SGD
exact); the hard gates are ``hit_rate`` >= PERF_GATE_EMBED_HIT_MIN
(default 0.9) at the smoke's skew, ``host_bytes_reduction`` — the
MEASURED every-step-exchange lane's host bytes/step (residency
invalidated before every single-step dispatch: the reference
remote-updater traffic shape) over the cached lane's — >=
PERF_GATE_EMBED_HOST_RATIO (default 4.0), and the STRUCTURAL assert
that the cached lane's timed executable allocates less XLA temp
memory than one full table (the device working set really is the
slab).
``elastic`` (ISSUE 13) pairs the elastic job's ASYNC checkpoint lane
against the no-checkpoint lane (and the SYNCHRONOUS inline-write lane
as the comparator) over the IDENTICAL seeded train stream through ONE
warmed executor/scope: each window trains the same K-step dispatches,
the async lane captures donated-safe host copies and hands the write
to ``AsyncShardedCheckpoint``'s background thread, the sync lane
serializes + commits inline, the bare lane does neither.  The hard
gate is ``checkpoint_overhead_ratio`` (async wall over no-checkpoint
wall, best shared drift window) <= PERF_GATE_ELASTIC_OVERHEAD
(default 1.05 — durability must not cost step time); the record also
runs the KILL-RESUME goodput check: a real ``ElasticTrainJob`` killed
holding a claim, the claim's lease observed timing out and
re-dispatching, the replacement resuming from the newest manifest
with ZERO replayed steps and BITWISE-identical final params to an
uninterrupted run (SGD).
``master_chaos`` (ISSUE 15) pairs bare-``MasterClient`` vs
``ResilientMasterClient`` ELASTIC windows — each window one full
``ElasticTrainJob`` pass over the same seeded dataset, NO faults
injected: the hard gate ``retry_layer_overhead_ratio`` (resilient
wall over bare wall, best shared window) <= PERF_GATE_CHAOS_OVERHEAD
(default 1.05) bounds what request-id minting + the server dedup
window + the reconnect machinery cost a training job on the happy
path; a secondary pure-RPC claim+finish drain pair isolates the
per-RPC tax (``rpc_drain_overhead_ratio``, tripwire-bounded by
PERF_GATE_CHAOS_RPC_MAX, default 1.6 — an accidental extra round
trip per call would read ~2x).  The record
then folds in the FUNCTIONAL chaos contract: ``check_master_chaos``
(an ElasticTrainJob under a seeded FaultInjector — dropped
task_finished/get_task responses, heartbeats delayed to just under
the lease TTL, the primary master killed mid-pass with a claim
outstanding and a standby promoted from a replicated snapshot —
finishing with zero lost / zero double-processed records and
BITWISE-identical final params vs the fault-free run) and
``check_dedup_replay`` (a replayed task_failed does NOT advance the
failure count even when the task was re-claimed in between; a fresh
request id — the counterfactual — discards at failure_max).
``decode_overlap`` (ISSUE 9) pairs the CHAINED decode lane
(decode_pipeline_depth >= 2: scan N+1 enqueued against scan N's
device-resident donated output carry, token blocks harvested while
the next scan computes) against the per-scan-sync lane
(decode_pipeline_depth=1 — one device-idling host round trip per
scan) over the IDENTICAL mixed-length generation stream.  Outputs are
asserted token-identical; the hard gates are the host-syncs-per-token
REDUCTION >= PERF_GATE_DECODE_SYNC_RATIO (default 2.0) and the CPU
tokens/s ratio (chained over synced, best shared block) >=
PERF_GATE_DECODE_TPS_MIN (default 0.8 — the overlap must never cost
throughput; on hardware it recovers the harvest round trip).
``chunked_prefill`` (ISSUE 14) pairs CHUNKED prefill
(ServingConfig(prefill_chunk=C): a prompt admits into a PREFILLING
decode slot and its tokens ride C-wide chunk dispatches interleaved
with decode scans under decode priority) against the monolithic
prefill-lot lane over the IDENTICAL mixed long-prompt + decode stream
(one scope/executor).  Outputs are asserted token-identical; the hard
gates are the max decode inter-token stall REDUCTION (the gauge:
worker cycles — wall over the lane's min scan wall — between a slot's
consecutive harvests while prefill work was in flight) >=
PERF_GATE_CP_STALL_RATIO (default 2.0), chunk dispatches > 0, and the
STRUCTURAL executable bound: new prompt lengths recompile NOTHING on
the chunked lane (every length decomposes into the same C-wide
blocks) while the monolithic lane mints one executable per fresh rung
— the counterfactual proving the probe bites.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = int(os.environ.get('PERF_GATE_STEPS', '10'))
BLOCKS = int(os.environ.get('PERF_GATE_BLOCKS', '3'))

# bs512 resnet / bs128 transformer don't co-reside with their bound's
# params+Adam state+activations on one 16GB chip; half batch keeps the
# ratio meaningful (both sides at the same operating point)
RESNET_BATCH = int(os.environ.get('PERF_GATE_BATCH', '256'))
TF_BATCH = int(os.environ.get('PERF_GATE_TF_BATCH', '64'))
NMT_BATCH = int(os.environ.get('PERF_GATE_NMT_BATCH', '256'))


def _fw_timed_block(model, feed, loss_var, per_step_items):
    """Compile+warm a framework step; returns (per-dispatch timed-block
    closure, multi-step timed-block closure).  The per-dispatch closure
    is the gate statistic's side (symmetric with the bound's python
    step loop); the multi-step closure times Executor.run_multi —
    K steps as ONE device dispatch — so the record also shows how much
    dispatch tax the multi-step path removes on this hardware."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    place = fluid.TPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.amp_guard(True):
        exe.run(model['startup'])
        for _ in range(2):
            exe.run(model['main'], feed=feed, fetch_list=[loss_var])
            exe.run(model['main'], feed=feed, fetch_list=[])
        # warm the STEPS-step multi executable too (static jit arg)
        exe.run_multi(model['main'], feed=feed, fetch_list=[loss_var],
                      steps=STEPS)

    def timed_block(steps=STEPS):
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(model['main'], feed=feed, fetch_list=[])
            loss_v, = exe.run(model['main'], feed=feed,
                              fetch_list=[loss_var])
            elapsed = time.time() - t0
        assert np.isfinite(np.asarray(loss_v)).all()
        return per_step_items * steps / elapsed

    def timed_block_multi(steps=STEPS):
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            t0 = time.time()
            loss_v, = exe.run_multi(model['main'], feed=feed,
                                    fetch_list=[loss_var], steps=steps)
            elapsed = time.time() - t0
        assert np.isfinite(np.asarray(loss_v)).all()
        return per_step_items * steps / elapsed

    return timed_block, timed_block_multi


def build_resnet():
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet
    import functools
    import jax.numpy as jnp
    import jax_resnet_bound as bound

    model = resnet.build(depth=50, class_dim=1000,
                         image_shape=(3, 224, 224), lr=0.1)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace().jax_device()
    feed = {
        'img': jax.device_put(
            rng.standard_normal(
                (RESNET_BATCH, 3, 224, 224)).astype('float32'), dev),
        'label': jax.device_put(
            rng.randint(0, 1000, size=(RESNET_BATCH, 1)).astype('int64'),
            dev),
    }
    fw, fw_multi = _fw_timed_block(model, feed, model['loss'],
                                   RESNET_BATCH)

    params = bound.make_params(jax.random.PRNGKey(0), 'NCHW')
    vel = [{k: jnp.zeros_like(v) for k, v in p.items()} for p in params]
    state = {'params': jax.device_put(params, dev),
             'vel': jax.device_put(vel, dev)}
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((RESNET_BATCH, 3, 224, 224)), jnp.float32), dev)
    label = jax.device_put(
        rng.randint(0, 1000, size=(RESNET_BATCH, )).astype(np.int32), dev)
    step = functools.partial(bound.train_step, layout='NCHW', remat=False)
    for _ in range(2):
        state['params'], state['vel'], loss = step(
            state['params'], state['vel'], x, label)
    jax.block_until_ready(loss)

    def bd(steps=STEPS):
        t0 = time.time()
        for _ in range(steps):
            state['params'], state['vel'], loss = step(
                state['params'], state['vel'], x, label)
        float(loss)
        return RESNET_BATCH * steps / (time.time() - t0)

    return fw, fw_multi, bd


def build_transformer():
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer
    import jax_transformer_bound as bound

    seq = 256
    model = transformer.build(src_vocab=30000, trg_vocab=30000,
                              max_len=seq, n_layer=6, n_head=8,
                              d_model=512, d_ff=2048)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace().jax_device()
    ids = lambda: jax.device_put(
        rng.randint(1, 30000, size=(TF_BATCH, seq)).astype('int64'), dev)
    feed = {'src_ids': ids(), 'trg_ids': ids(), 'lbl_ids': ids()}
    fw, fw_multi = _fw_timed_block(model, feed, model['loss'],
                                   TF_BATCH * seq)
    _, bd = bound.build(attn_impl='dense', batch=TF_BATCH, seq=seq)
    return fw, fw_multi, (lambda steps=STEPS: bd(steps))


def build_nmt():
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import seq2seq
    import jax_nmt_bound as bound

    seq = 32
    model = seq2seq.build(src_dict_dim=30000, trg_dict_dim=30000,
                          embedding_dim=512, encoder_size=512,
                          decoder_size=512)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace().jax_device()

    # PRE-STAGED padded feeds (the double-buffer reader's form): the
    # bound's feeds are device-resident, so the framework's must be too
    # or the ratio measures per-step host upload + padding, which the
    # bound does not pay
    def staged(ids):
        data = jax.device_put(ids.astype('int64')[..., None], dev)
        lens = jax.device_put(
            np.full((NMT_BATCH, ), seq, np.int32), dev)
        return fluid.core.PaddedSequence(data, lens)

    src = rng.randint(3, 30000, size=(NMT_BATCH, seq))
    trg = rng.randint(3, 30000, size=(NMT_BATCH, seq))
    feed = {'src_word_id': staged(src), 'target_language_word': staged(trg),
            'target_language_next_word': staged(trg)}
    fw, fw_multi = _fw_timed_block(model, feed, model['loss'],
                                   NMT_BATCH * seq)
    _, bd = bound.build(batch=NMT_BATCH, seq=seq)
    return fw, fw_multi, (lambda steps=STEPS: bd(steps))


def build_resnet_infer():
    """The serving-engine operating point (ISSUE 2): ResNet-50 EVAL
    program (save/load_inference_model round trip, bs256 f32), per-
    dispatch pipelined loop vs Executor.run_eval_multi — K in-jit eval
    steps per dispatch.  No pure-JAX bound side (the train gates own
    that invariant); the record's deliverable is the PAIRED
    multi_vs_dispatch block: the measured dispatch tax the eval scan
    removes from serving."""
    import tempfile
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    model = resnet.build(depth=50, class_dim=1000,
                         image_shape=(3, 224, 224), lr=0.1)
    place = fluid.TPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(model['startup'])
        with tempfile.TemporaryDirectory() as td:
            fluid.io.save_inference_model(
                td, model['feeds'][:1], [model['prediction']], exe,
                main_program=model['test'])
            prog, feeds, fetches = fluid.io.load_inference_model(td, exe)
        import jax
        x = jax.device_put(
            rng.standard_normal(
                (RESNET_BATCH, 3, 224, 224)).astype('float32'),
            place.jax_device())
        staged = {feeds[0]: x}
        # warm every executable the timed blocks hit: both per-dispatch
        # cache entries AND the STEPS-step eval scan (static jit arg)
        for _ in range(2):
            exe.run(prog, feed=staged, fetch_list=[])
            exe.run(prog, feed=staged, fetch_list=fetches)
        exe.run_eval_multi(prog, feed=staged, fetch_list=fetches,
                           steps=STEPS)

    def timed_block(steps=STEPS):
        with fluid.scope_guard(scope):
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(prog, feed=staged, fetch_list=[])
            out, = exe.run(prog, feed=staged, fetch_list=fetches)
            elapsed = time.time() - t0
        assert np.isfinite(np.asarray(out)).all()
        return RESNET_BATCH * steps / elapsed

    def timed_block_multi(steps=STEPS):
        with fluid.scope_guard(scope):
            t0 = time.time()
            out, = exe.run_eval_multi(prog, feed=staged,
                                      fetch_list=fetches, steps=steps)
            elapsed = time.time() - t0
        assert np.isfinite(np.asarray(out)).all()
        return RESNET_BATCH * steps / elapsed

    return timed_block, timed_block_multi, None


def build_feed_pipeline():
    """Overlapped vs blocked input staging at the ResNet operating point
    (ISSUE 3): FRESH host batches every step, so feed preparation (host
    generate + stack + device_put) is real work.  The
    BLOCKED side stages each K-batch scan block synchronously on the
    dispatch path (run_multi(feed_list=...)); the OVERLAPPED side rides
    fluid.FeedPipeline — staging on a background thread, pipeline_depth
    2, donated scanned blocks — so block N+1 stages while N computes.
    No pure-JAX bound side (the train gates own that invariant); the
    deliverable is the paired ``overlapped_vs_blocked`` block plus the
    post-warmup feed_stall (~0 when staging fully hides)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    k = int(os.environ.get('PERF_GATE_FEED_STEPS', '4'))
    dispatches = int(os.environ.get('PERF_GATE_FEED_DISPATCHES', '2'))
    model = resnet.build(depth=50, class_dim=1000,
                         image_shape=(3, 224, 224), lr=0.1)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)

    def batch():
        return {'img': rng.standard_normal(
                    (RESNET_BATCH, 3, 224, 224)).astype('float32'),
                'label': rng.randint(
                    0, 1000, size=(RESNET_BATCH, 1)).astype('int64')}

    with fluid.scope_guard(scope), fluid.amp_guard(True):
        exe.run(model['startup'])
        # warm the k-step scanned executable (static jit arg + scanned
        # feed structure both key compiles)
        exe.run_multi(model['main'], feed_list=[batch() for _ in range(k)],
                      fetch_list=[model['loss']])

    def blocked():
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            t0 = time.time()
            for _ in range(dispatches):
                loss_v, = exe.run_multi(
                    model['main'], feed_list=[batch() for _ in range(k)],
                    fetch_list=[model['loss']])
            elapsed = time.time() - t0
        assert np.isfinite(np.asarray(loss_v)).all()
        return RESNET_BATCH * k * dispatches / elapsed

    last_metrics = {}

    def overlapped():
        from paddle_tpu.fluid.dataflow import FeedPipeline
        src = (batch() for _ in range((dispatches + 1) * k))
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            pipe = FeedPipeline(exe, fetch_list=[model['loss']],
                                program=model['main'], source=src,
                                steps=k, pipeline_depth=2, scope=scope)
            it = iter(pipe)
            next(it)  # warmup dispatch: the first block can't overlap
            t0 = time.time()
            n = sum(1 for _ in it)
            elapsed = time.time() - t0
            last_metrics.clear()
            last_metrics.update(pipe.metrics())
        assert n == dispatches, n
        return RESNET_BATCH * k * dispatches / elapsed

    return blocked, overlapped, (k, dispatches, last_metrics)


def run_feed_pipeline():
    """The feed_pipeline record: interleaved blocked/overlapped windows
    (same pairing rule as the hard gates — each ratio shares a drift
    window), plus the last overlapped window's pipeline metrics."""
    blocked, overlapped, (k, dispatches, metrics) = build_feed_pipeline()
    bl, ov = [], []
    for _ in range(BLOCKS):
        bl.append(blocked())
        ov.append(overlapped())
    rec = {
        'config': 'feed_pipeline',
        'blocked_imgs_per_sec': round(max(bl), 1),
        'overlapped_imgs_per_sec': round(max(ov), 1),
        'blocked_blocks': [round(v, 1) for v in bl],
        'overlapped_blocks': [round(v, 1) for v in ov],
        # the PAIRED deliverable: how much throughput overlapped staging
        # recovers from the blocked feed path, per shared window
        'overlapped_vs_blocked': round(
            max(o / b for o, b in zip(ov, bl)), 4),
        # ~0 after warmup when staging fully hides behind compute (the
        # ISSUE 3 acceptance signal)
        'feed_stall_s': round(metrics.get('feed_stall_s', 0.0), 4),
        'overlap_ratio': round(metrics.get('overlap_ratio', 0.0), 4),
        'steps_per_dispatch': k, 'dispatches_per_block': dispatches,
        'blocks': BLOCKS,
    }
    print(json.dumps(rec), flush=True)
    return rec


def build_multi_model():
    """Two ResNet-18 eval models under ONE ModelRegistry (ISSUE 4),
    budget sized so only one fits resident: the RESIDENT window serves
    one model repeatedly (no arbitration), the EVICT-RELOAD window
    alternates models so EVERY request pays an LRU eviction (weights
    demoted to host) + transparent reload (re-stage + recompile).  The
    paired ratio is the measured arbitration tax a capacity planner
    trades against buying a second chip."""
    import tempfile
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import resnet

    batch = int(os.environ.get('PERF_GATE_MM_BATCH', '64'))
    reqs = int(os.environ.get('PERF_GATE_MM_REQS', '4'))
    place = fluid.TPUPlace()
    dirs = []
    for seed in (0, 1):
        model = resnet.build(depth=18, class_dim=1000,
                             image_shape=(3, 224, 224), lr=0.1)
        model['startup'].random_seed = seed
        exe = fluid.Executor(place)
        scope = fluid.core.Scope()
        td = tempfile.mkdtemp()
        with fluid.scope_guard(scope):
            exe.run(model['startup'])
            fluid.io.save_inference_model(
                td, model['feeds'][:1], [model['prediction']], exe,
                main_program=model['test'])
        dirs.append(td)
    rng = np.random.RandomState(0)
    x = rng.standard_normal((batch, 3, 224, 224)).astype('float32')
    reg = serving.ModelRegistry(
        place=place,
        config=serving.ServingConfig(max_batch_size=batch,
                                     bucket_sizes=[batch]))
    names = ['mm0', 'mm1']
    feeds = {}
    for name, d in zip(names, dirs):
        eng = reg.load(name, d)
        feeds[name] = {eng._feed_names[0]: x}
    # warm both (resident, compiled) then tighten the budget so only
    # ONE model's LIVE footprint fits at a time.  device_footprint, not
    # the account's hbm_bytes: accounts may still carry the seed
    # estimate here (the routing-time correction fires BEFORE a
    # model's first dispatch stages anything), and a seed-sized budget
    # would fit both models — measuring no arbitration at all
    for name in names:
        out, = reg.infer(name, feeds[name], timeout=600)
        assert np.isfinite(np.asarray(out)).all()
    # second pass: the routing-time correction now sees the staged
    # buffers, pulling each ACCOUNT down from the seed estimate to live
    # bytes — a seed-sized account under the tightened budget below
    # would be rejected outright instead of arbitrated
    for name in names:
        reg.infer(name, feeds[name], timeout=600)
    status = reg.status()['models']
    live = max(s['device_footprint'] for s in status.values())
    assert live > 0
    reg.arbiter.set_budget(int(1.5 * live))

    def resident():
        reg.infer(names[0], feeds[names[0]], timeout=600)  # make resident
        t0 = time.time()
        for _ in range(reqs):
            reg.infer(names[0], feeds[names[0]], timeout=600)
        return batch * reqs / (time.time() - t0)

    def evict_reload():
        # the resident window left names[0] resident: start on names[1]
        # so EVERY timed request pays an eviction + reload
        t0 = time.time()
        for i in range(reqs):
            name = names[(i + 1) % 2]
            reg.infer(name, feeds[name], timeout=600)
        return batch * reqs / (time.time() - t0)

    return resident, evict_reload, (reg, batch, reqs)


def run_multi_model():
    """The multi_model record: interleaved resident/evict-reload
    windows (each ratio shares a drift window, the gates' pairing
    rule), plus the registry's arbitration counters."""
    resident, evict_reload, (reg, batch, reqs) = build_multi_model()
    res, ev = [], []
    for _ in range(BLOCKS):
        res.append(resident())
        ev.append(evict_reload())
    m = reg.metrics()
    # the deliverable is the arbitration tax: a record with no forced
    # evictions would be measuring nothing
    assert m['evictions'] >= BLOCKS * reqs // 2, m['evictions']
    rec = {
        'config': 'multi_model',
        'models': 2,
        'budget_mb': round(m['budget_bytes'] / 1024.0 / 1024.0, 2),
        'resident_imgs_per_sec': round(max(res), 1),
        'evict_reload_imgs_per_sec': round(max(ev), 1),
        'resident_blocks': [round(v, 1) for v in res],
        'evict_reload_blocks': [round(v, 1) for v in ev],
        # the PAIRED deliverable: throughput kept under forced
        # per-request arbitration vs the resident baseline, per shared
        # drift window
        'reload_tax': round(max(e / r for e, r in zip(ev, res)), 4),
        'evictions': m['evictions'],
        'reloads': m['reloads'],
        'admission_rejects': m['admission_rejects'],
        'requests_per_window': reqs, 'batch': batch, 'blocks': BLOCKS,
    }
    reg.stop()
    print(json.dumps(rec), flush=True)
    return rec


def build_trailing_dim():
    """Bucketed vs exact-shape serving on a SKEWED synthetic length
    distribution (ISSUE 5): one padding-neutral seq scorer (masked-sum
    pooling over the time axis, so zero-padded positions contribute
    nothing) served through TWO engines over the same scope — the
    BUCKETED one quantizes request seq-lens onto the shared seq-len
    ladder (fluid.shape_policy — mixed-length requests coalesce,
    executables bounded by the rung count), the EXACT one disables
    trailing bucketing so every distinct length is its own per-shape
    lot + executable (today's fragmentation, the baseline).  Requests
    are DENSE [rows, T, dim] lots — the path where exact shapes really
    fragment (LoD feeds already rung-quantize inside the executor's
    lowering).  Each engine gets its own Executor so compile_count
    isolates the executable sets."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import shape_policy

    rows = int(os.environ.get('PERF_GATE_TD_ROWS', '8'))
    reqs_per_window = int(os.environ.get('PERF_GATE_TD_REQS', '16'))
    dim, classes = 64, 1000
    # skewed: mass on short lengths, a long tail — 8 distinct lengths
    # quantizing onto 3 ladder rungs (16, 32, 48)
    lengths = [3, 6, 9, 12, 18, 24, 35, 45]
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 0
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)
        pred = fluid.layers.fc(pooled, classes, act='softmax')
    test_prog = prog.clone(for_test=True)
    place = fluid.TPUPlace()
    scope = fluid.core.Scope()
    exe0 = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe0.run(startup)

    rng = np.random.RandomState(0)
    streams = [
        {'x': rng.standard_normal(
            (rows, lengths[i % len(lengths)], dim)).astype('float32')}
        for i in range(reqs_per_window)
    ]

    def make_engine(trailing):
        ladder = {'x': shape_policy.seq_ladder(max(lengths))} \
            if trailing else None
        # ONE batch bucket + one lot per scan on BOTH sides, so the
        # executable count isolates the TRAILING dimension: bucketed =
        # one executable per ladder rung, exact = one per distinct
        # request length
        return serving.InferenceEngine(
            test_prog, feed_names=['x'], fetch_list=[pred],
            scope=scope, executor=fluid.Executor(place), place=place,
            config=serving.ServingConfig(
                max_batch_size=rows * 4, max_wait_ms=2,
                bucket_sizes=[rows * 4], steps_per_dispatch=1,
                trailing_buckets=trailing, trailing_ladders=ladder))

    bucketed_eng = make_engine(True).start()
    exact_eng = make_engine(False).start()
    for eng in (bucketed_eng, exact_eng):  # warm every stream shape
        for r in streams:
            eng.infer(r, timeout=600)

    def window(eng):
        def run():
            # open-loop-ish: submit the whole window, then wait — the
            # micro-batcher coalesces same-rung mixed-length requests
            # (the bucketed engine's whole point); the exact engine
            # only coalesces same-shape ones
            t0 = time.time()
            futs = [eng.submit(r) for r in streams]
            for f in futs:
                out, = f.result(600)
                assert np.isfinite(np.asarray(out)).all()
            return len(streams) * rows / (time.time() - t0)
        return run

    return (window(bucketed_eng), window(exact_eng),
            (bucketed_eng, exact_eng, rows, reqs_per_window))


def run_trailing_dim():
    """The trailing_dim record: interleaved bucketed/exact windows
    (each ratio shares a drift window — the gates' pairing rule), plus
    the executable-count and padding-waste deltas (the ISSUE 5
    acceptance numbers: bucketed serving must compile at most HALF the
    exact path's executables on the skewed stream)."""
    bucketed, exact, (b_eng, e_eng, rows, nreq) = build_trailing_dim()
    bu, ex = [], []
    for _ in range(BLOCKS):
        bu.append(bucketed())
        ex.append(exact())
    bm, em = b_eng.metrics(), e_eng.metrics()
    rec = {
        'config': 'trailing_dim',
        'bucketed_rows_per_sec': round(max(bu), 1),
        'exact_rows_per_sec': round(max(ex), 1),
        'bucketed_blocks': [round(v, 1) for v in bu],
        'exact_blocks': [round(v, 1) for v in ex],
        # the PAIRED deliverable: throughput kept (or recovered) by
        # coalescing mixed-length requests, per shared drift window
        'bucketed_vs_exact': round(
            max(b / e for b, e in zip(bu, ex)), 4),
        # the executable-count delta: the compile budget trailing-dim
        # bucketing buys on a length-skewed stream
        'executables_bucketed': bm['executor_compile_count'],
        'executables_exact': em['executor_compile_count'],
        'executable_ratio': round(
            bm['executor_compile_count'] /
            max(em['executor_compile_count'], 1), 4),
        'padding_waste': bm['trailing_padding_waste'],
        'bucketed_lots': bm['lots'], 'exact_lots': em['lots'],
        'requests_per_window': nreq, 'rows_per_request': rows,
        'blocks': BLOCKS,
    }
    b_eng.stop()
    e_eng.stop()
    print(json.dumps(rec), flush=True)
    return rec


def build_trace_overhead():
    """Tracing-on vs tracing-off serving over ONE scope (ISSUE 6): the
    same engine (dense seq scorer, one batch bucket, one lot per scan)
    serves the same request stream in paired windows — the TRACED
    window inside a fluid.trace.tracing() span-capture window, the
    untraced window outside it.  Per-request TraceContexts (stage
    breakdowns on every response) are unconditionally on, so the pair
    isolates the optional layer: the span log every profiler event and
    delivered request mirrors into, the Chrome exporter's source."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import trace

    rows = int(os.environ.get('PERF_GATE_TR_ROWS', '8'))
    reqs_per_window = int(os.environ.get('PERF_GATE_TR_REQS', '16'))
    dim, classes, seq = 64, 1000, 24
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 0
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)
        pred = fluid.layers.fc(pooled, classes, act='softmax')
    test_prog = prog.clone(for_test=True)
    place = fluid.TPUPlace()
    scope = fluid.core.Scope()
    exe0 = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe0.run(startup)
    rng = np.random.RandomState(0)
    streams = [
        {'x': rng.standard_normal((rows, seq, dim)).astype('float32')}
        for _ in range(reqs_per_window)
    ]
    eng = serving.InferenceEngine(
        test_prog, feed_names=['x'], fetch_list=[pred], scope=scope,
        executor=fluid.Executor(place), place=place,
        config=serving.ServingConfig(
            max_batch_size=rows * 4, max_wait_ms=2,
            bucket_sizes=[rows * 4], steps_per_dispatch=1)).start()
    for r in streams:  # warm the executable set
        eng.infer(r, timeout=600)

    def window():
        t0 = time.time()
        futs = [eng.submit(r) for r in streams]
        for f in futs:
            out, = f.result(600)
            assert np.isfinite(np.asarray(out)).all()
        return len(streams) * rows / (time.time() - t0)

    def traced_window():
        with trace.tracing():
            return window()

    return traced_window, window, (eng, trace, rows, reqs_per_window)


def run_trace_overhead():
    """The trace_overhead record: interleaved untraced/traced windows
    (each ratio shares a drift window — the gates' pairing rule); the
    HARD assertion is the bounded-overhead acceptance (ISSUE 6): the
    best shared-window traced/untraced ratio must clear
    PERF_GATE_TRACE_MIN (default 0.8)."""
    traced, untraced, (eng, trace, rows, nreq) = build_trace_overhead()
    tr, un = [], []
    for _ in range(BLOCKS):
        un.append(untraced())
        tr.append(traced())
    spans = trace.spans()  # the LAST traced window's span log
    m = eng.metrics()
    rec = {
        'config': 'trace_overhead',
        'untraced_rows_per_sec': round(max(un), 1),
        'traced_rows_per_sec': round(max(tr), 1),
        'untraced_blocks': [round(v, 1) for v in un],
        'traced_blocks': [round(v, 1) for v in tr],
        # the PAIRED deliverable: throughput kept with the span-capture
        # window on, per shared drift window
        'traced_vs_untraced': round(
            max(t / u for t, u in zip(tr, un)), 4),
        'spans_last_window': len(spans),
        'span_lanes': len({s.get('lane') for s in spans}),
        'traced_requests': m['traced_requests'],
        'stages_ms_mean': m['stages_ms_mean'],
        'requests_per_window': nreq, 'rows_per_request': rows,
        'blocks': BLOCKS,
    }
    eng.stop()
    # the bounded-overhead gate: tracing must not tax the request path
    # beyond the configured floor on the best shared window
    floor = float(os.environ.get('PERF_GATE_TRACE_MIN', '0.8'))
    assert rec['traced_vs_untraced'] >= floor, rec
    assert rec['spans_last_window'] > 0, rec
    print(json.dumps(rec), flush=True)
    return rec


def build_decode():
    """Continuous-batching decode vs ONE-CALL-PER-STEP per-request
    decode over the SAME mixed-length request stream (ISSUE 7): the
    lane side serves N prompts through the engine's generation lane
    (prefill lots coalesce, K decode steps per in-jit scan over the
    slot batch, continuous admission), the reference side replays the
    reference serving shape — per request, one prefill exe.run plus
    one step exe.run PER TOKEN.  Functional on the CPU smoke (the
    parity + dispatch-accounting deliverables) and TPU alike; outputs
    are asserted TOKEN-IDENTICAL between the two sides before any
    number is reported."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import seq2seq

    n_req = int(os.environ.get('PERF_GATE_DEC_REQS', '8'))
    slots = int(os.environ.get('PERF_GATE_DEC_SLOTS', '4'))
    k_steps = int(os.environ.get('PERF_GATE_DEC_STEPS', '4'))
    max_len = int(os.environ.get('PERF_GATE_DEC_LEN', '12'))
    m = seq2seq.build_step_decode(src_dict_dim=100, trg_dict_dim=80,
                                  embedding_dim=16, encoder_size=32,
                                  decoder_size=32, max_len=max_len)
    place = fluid.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    rng = np.random.RandomState(0)
    lens = [3 + (i * 5) % 13 for i in range(n_req)]
    prompts = [fluid.create_lod_tensor(
        rng.randint(2, 100, size=(l, 1)).tolist(), [[l]]) for l in lens]

    spec = serving.GenerationSpec.from_model(m)
    eng = serving.InferenceEngine(
        m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
        executor=exe, place=place,
        config=serving.ServingConfig(
            max_batch_size=n_req, max_wait_ms=2, decode_slots=slots,
            decode_steps=k_steps),
        generation=spec, name='perf-gate-decode').start()

    def lane_window():
        """(tokens/s, engine dispatches this window, tokens, outputs)."""
        m0 = eng.metrics()
        d0 = (m0['decode'] or {})
        before = m0['dispatches'] + d0.get('dispatches', 0)
        t0 = time.time()
        futs = [eng.submit_generate({'src_word_id': p}) for p in prompts]
        outs = [list(f.result(600)) for f in futs]
        elapsed = time.time() - t0
        m1 = eng.metrics()
        after = m1['dispatches'] + m1['decode']['dispatches']
        tokens = sum(len(o) for o in outs)
        return tokens / elapsed, after - before, tokens, outs

    def ref_window():
        """The per-step serving shape: dispatches = sum(1 + steps)."""
        outs, dispatches = [], 0
        t0 = time.time()
        with fluid.scope_guard(scope):
            for p in prompts:
                boot, = exe.run(m['prefill'], feed={'src_word_id': p},
                                fetch_list=m['prefill_fetches'])
                dispatches += 1
                h = boot
                t = np.array([[m['start_id']]], np.int64)
                toks = []
                for _ in range(max_len):
                    lg, h2 = exe.run(
                        m['step'],
                        feed={'gen_token': t, 'gen_hidden': h},
                        fetch_list=[m['logits'], m['state'][0][1]])
                    dispatches += 1
                    nxt = int(np.argmax(lg.reshape(1, -1), axis=-1)[0])
                    toks.append(nxt)
                    if nxt == m['end_id']:
                        break
                    h, t = h2, np.array([[nxt]], np.int64)
                outs.append(toks)
        elapsed = time.time() - t0
        tokens = sum(len(o) for o in outs)
        return tokens / elapsed, dispatches, tokens, outs

    return lane_window, ref_window, (eng, n_req, slots, k_steps)


def run_decode():
    """The decode record: interleaved lane/reference windows (each
    ratio shares a drift window — the gates' pairing rule), with the
    ISSUE 7 acceptance numbers as HARD asserts: outputs token-identical
    across the two sides, `dispatch_ratio` (lane dispatches over
    one-call-per-step dispatches) at most PERF_GATE_DECODE_RATIO_MAX
    (default 1/3), and `tokens_per_dispatch` at least
    PERF_GATE_DECODE_TPD_MIN (default 4.0)."""
    lane, ref, (eng, n_req, slots, k_steps) = build_decode()
    lane(), ref()  # warm both executable sets outside the windows
    la, rf = [], []
    lane_disp = ref_disp = lane_tokens = 0
    for _ in range(BLOCKS):
        lv, ld, lt, louts = lane()
        rv, rd, rt, routs = ref()
        assert louts == routs, 'decode lane diverged from per-request ' \
            'reference decode: %r vs %r' % (louts[:2], routs[:2])
        la.append(lv)
        rf.append(rv)
        lane_disp, ref_disp, lane_tokens = ld, rd, lt
    md = eng.metrics()['decode']
    rec = {
        'config': 'decode',
        'lane_tokens_per_sec': round(max(la), 1),
        'ref_tokens_per_sec': round(max(rf), 1),
        'lane_blocks': [round(v, 1) for v in la],
        'ref_blocks': [round(v, 1) for v in rf],
        # the PAIRED deliverable: throughput recovered by continuous
        # batching + the in-jit decode scan, per shared drift window
        'lane_vs_ref': round(max(l / r for l, r in zip(la, rf)), 4),
        # the ISSUE 7 acceptance numbers: dispatch amortization
        'lane_dispatches': lane_disp,
        'ref_dispatches': ref_disp,
        'dispatch_ratio': round(lane_disp / max(ref_disp, 1), 4),
        'tokens_per_dispatch': round(lane_tokens / max(lane_disp, 1), 3),
        'steps_per_dispatch': md['steps_per_dispatch'],
        'slot_occupancy': md['slot_occupancy'],
        'requests_per_window': n_req, 'decode_slots': slots,
        'decode_steps': k_steps, 'blocks': BLOCKS,
    }
    eng.stop()
    ratio_max = float(os.environ.get('PERF_GATE_DECODE_RATIO_MAX',
                                     str(1.0 / 3.0)))
    tpd_min = float(os.environ.get('PERF_GATE_DECODE_TPD_MIN', '4.0'))
    assert rec['dispatch_ratio'] <= ratio_max, rec
    assert rec['tokens_per_dispatch'] >= tpd_min, rec
    print(json.dumps(rec), flush=True)
    return rec


def build_decode_overlap():
    """Chained (host-sync-free) vs per-scan-sync decode lanes over the
    IDENTICAL mixed-length generation stream (ISSUE 9): two engines
    serve the SAME stepwise NMT decode model (one scope — weights
    genuinely shared), differing ONLY in decode_pipeline_depth: the
    synced side (depth 1) pays one device-idling host round trip per
    K-step scan (dispatch, sync tokens, bookkeep, dispatch), the
    chained side (depth >= 2) enqueues scan N+1 against scan N's
    device-resident output carry and harvests N's token block while
    N+1 computes — admission/shed/eviction ride chain-flush points, so
    outputs stay token-identical.  The deliverables are the
    host-syncs-per-token reduction (counted by the engines themselves:
    a harvest that blocked with nothing in flight behind it) and the
    paired tokens/s ratio."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import seq2seq

    n_req = int(os.environ.get('PERF_GATE_DOV_REQS', '8'))
    slots = int(os.environ.get('PERF_GATE_DOV_SLOTS', '4'))
    k_steps = int(os.environ.get('PERF_GATE_DOV_STEPS', '4'))
    max_len = int(os.environ.get('PERF_GATE_DOV_LEN', '12'))
    depth = int(os.environ.get('PERF_GATE_DOV_DEPTH', '2'))
    m = seq2seq.build_step_decode(src_dict_dim=100, trg_dict_dim=80,
                                  embedding_dim=16, encoder_size=32,
                                  decoder_size=32, max_len=max_len)
    place = fluid.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['step_startup'])
    rng = np.random.RandomState(0)
    lens = [3 + (i * 5) % 13 for i in range(n_req)]
    prompts = [fluid.create_lod_tensor(
        rng.randint(2, 100, size=(l, 1)).tolist(), [[l]]) for l in lens]
    spec = serving.GenerationSpec.from_model(m)

    def make_engine(pipeline_depth, name):
        # ONE shared executor: both lanes resolve the same prefill/
        # step executables, so the paired windows measure the
        # pipelining policy, not compile weather
        return serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=place,
            config=serving.ServingConfig(
                max_batch_size=n_req, max_wait_ms=2,
                decode_slots=slots, decode_steps=k_steps,
                decode_pipeline_depth=pipeline_depth),
            generation=spec, name=name).start()

    synced = make_engine(1, 'perf-gate-dov-synced')
    chained = make_engine(depth, 'perf-gate-dov-chained')

    def window(eng):
        """(tokens/s, syncs_per_token, tokens, outputs) for one pass
        of the stream — sync accounting from the engine's own
        metrics() deltas."""
        d0 = eng.metrics()['decode'] or \
            {'host_syncs': 0, 'tokens': 0}
        t0 = time.time()
        futs = [eng.submit_generate({'src_word_id': p}) for p in prompts]
        outs = [list(f.result(600)) for f in futs]
        elapsed = time.time() - t0
        d1 = eng.metrics()['decode']
        syncs = d1['host_syncs'] - d0['host_syncs']
        tokens = d1['tokens'] - d0['tokens']
        return tokens / elapsed, syncs / max(tokens, 1), tokens, outs

    return (lambda: window(synced)), (lambda: window(chained)), \
        (synced, chained, n_req, slots, k_steps, depth)


def run_decode_overlap():
    """The decode_overlap record: interleaved synced/chained windows
    over the identical stream (each ratio shares a drift window — the
    gates' pairing rule).  HARD asserts (the ISSUE 9 acceptance):
    chained outputs bitwise token-identical to the per-scan-sync
    lane's, host syncs per emitted token reduced by at least
    PERF_GATE_DECODE_SYNC_RATIO (default 2.0), and the chained lane's
    CPU tokens/s at least PERF_GATE_DECODE_TPS_MIN (default 0.8) of
    the synced lane's on the best shared block."""
    sync_w, chain_w, (synced, chained, n_req, slots, k_steps, depth) = \
        build_decode_overlap()
    try:
        sync_w(), chain_w()  # warm the shared executable set
        sy, ch, tps_ratios = [], [], []
        sync_spt = chain_spt = tokens = 0
        for _ in range(BLOCKS):
            sv, s_spt, s_tok, s_outs = sync_w()
            cv, c_spt, c_tok, c_outs = chain_w()
            assert c_outs == s_outs, \
                'chained decode lane diverged from the per-scan-sync ' \
                'lane: %r vs %r' % (c_outs[:2], s_outs[:2])
            sy.append(sv)
            ch.append(cv)
            tps_ratios.append(cv / sv)
            sync_spt, chain_spt, tokens = s_spt, c_spt, s_tok
        m_sync = synced.metrics()['decode']
        m_chain = chained.metrics()['decode']
    finally:
        synced.stop()
        chained.stop()
    rec = {
        'config': 'decode_overlap',
        'chained_tokens_per_sec': round(max(ch), 1),
        'synced_tokens_per_sec': round(max(sy), 1),
        'chained_blocks': [round(v, 1) for v in ch],
        'synced_blocks': [round(v, 1) for v in sy],
        # the PAIRED deliverables: host-sync reduction + throughput
        # kept, per shared drift window
        'chained_vs_synced': round(max(tps_ratios), 4),
        'sync_per_token_synced': round(sync_spt, 4),
        'sync_per_token_chained': round(chain_spt, 4),
        'host_sync_reduction': round(
            sync_spt / max(chain_spt, 1e-9), 4),
        'chained_host_syncs': m_chain['host_syncs'],
        'synced_host_syncs': m_sync['host_syncs'],
        'chain_flushes': m_chain['chain_flushes'],
        'tokens_per_window': tokens,
        'requests_per_window': n_req, 'decode_slots': slots,
        'decode_steps': k_steps, 'decode_pipeline_depth': depth,
        'blocks': BLOCKS,
    }
    sync_floor = float(os.environ.get('PERF_GATE_DECODE_SYNC_RATIO',
                                      '2.0'))
    tps_floor = float(os.environ.get('PERF_GATE_DECODE_TPS_MIN', '0.8'))
    assert rec['host_sync_reduction'] >= sync_floor, rec
    assert rec['chained_vs_synced'] >= tps_floor, rec
    print(json.dumps(rec), flush=True)
    return rec


def build_chunked_prefill():
    """Chunked vs monolithic prefill over the IDENTICAL mixed
    long-prompt + decode stream (ISSUE 14): two engines serve the SAME
    chunk-capable stepwise NMT decode model (one scope + executor —
    weights and executables genuinely shared), differing ONLY in
    ServingConfig(prefill_chunk=): the monolithic side prefills each
    prompt as ONE rung-padded lot whose drain freezes every in-flight
    decode slot for the whole prompt's wall, the chunked side admits
    the prompt into a PREFILLING slot and rides at most one C-token
    chunk per worker cycle between decode scans — so the max decode
    inter-token stall is one chunk, not one prompt.  Each window:
    decode-active short generations, then a LONG prompt lands
    mid-stream; deliverables are token identity, the stall-gauge
    reduction, and the bounded-executable structural check (new prompt
    lengths recompile NOTHING on the chunked lane)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import seq2seq

    chunk = int(os.environ.get('PERF_GATE_CP_CHUNK', '64'))
    # the long prompt must be COMPUTE-dominated (many recurrence steps)
    # or every gap measures the dispatch overhead both lanes share and
    # the ratio compresses toward 1
    long_len = int(os.environ.get('PERF_GATE_CP_LONG', '4096'))
    # one slot stays free for the long prompt, so its chunks interleave
    # with the shorts' decode scans from the first cycle
    n_short = int(os.environ.get('PERF_GATE_CP_SHORT', '3'))
    slots = int(os.environ.get('PERF_GATE_CP_SLOTS', '4'))
    k_steps = int(os.environ.get('PERF_GATE_CP_STEPS', '2'))
    max_len = int(os.environ.get('PERF_GATE_CP_LEN', '24'))
    dim = int(os.environ.get('PERF_GATE_CP_DIM', '96'))
    m = seq2seq.build_step_decode(src_dict_dim=100, trg_dict_dim=80,
                                  embedding_dim=16, encoder_size=dim,
                                  decoder_size=dim, max_len=max_len,
                                  chunk=chunk)
    place = fluid.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        exe.run(m['chunk_startup'])
        exe.run(m['step_startup'])
    rng = np.random.RandomState(0)

    def prompt(l):
        return fluid.create_lod_tensor(
            rng.randint(2, 100, size=(l, 1)).tolist(), [[l]])

    short_lens = [3 + (i * 3) % 7 for i in range(n_short)]
    shorts = [prompt(l) for l in short_lens]
    long_prompt = prompt(long_len)
    spec = serving.GenerationSpec.from_model(m)

    def make_engine(chunked, name):
        return serving.InferenceEngine(
            m['prefill'], fetch_list=m['prefill_fetches'], scope=scope,
            executor=exe, place=place,
            config=serving.ServingConfig(
                max_batch_size=n_short + 1, max_wait_ms=1,
                decode_slots=slots, decode_steps=k_steps,
                prefill_chunk=chunk if chunked else None),
            generation=spec, name=name).start()

    def window(eng):
        """One pass of the mixed stream: short generations get the
        decode lane busy, then the long prompt lands mid-decode (the
        stall gauge needs a harvest before AND after the prefill).
        Returns (all outputs, decode-metrics snapshot)."""
        d0 = eng.metrics()['decode'] or {'harvests': 0}
        # staggered budgets: the shorts finish at DIFFERENT step
        # boundaries, keeping the decode lane live (and its harvests
        # observing the prefill) for the whole prefill window
        futs = [eng.submit_generate({'src_word_id': p},
                                    max_len=max_len - 2 * i)
                for i, p in enumerate(shorts)]
        deadline = time.time() + 120
        while time.time() < deadline:
            d = eng.metrics()['decode']
            if d and d['harvests'] > d0['harvests']:
                break
            time.sleep(0.0005)
        futs.append(eng.submit_generate({'src_word_id': long_prompt},
                                        max_len=8))
        outs = [list(f.result(600)) for f in futs]
        return outs, eng.metrics()['decode']

    return (make_engine, window, prompt,
            (exe, chunk, long_len, short_lens, slots, k_steps))


def run_chunked_prefill():
    """The chunked_prefill record (ISSUE 14 acceptance): one seeded
    mixed long-prompt + decode stream through chunked vs monolithic
    engines over ONE shared scope/executor.  HARD asserts: every
    generated output token-identical across the lanes, the max decode
    inter-token stall (worker cycles between a slot's consecutive
    harvests while a prefill is in flight) reduced by at least
    PERF_GATE_CP_STALL_RATIO (default 2.0), chunk dispatches really
    happened, and the chunked lane's prefill executables are bounded
    by the rung ladder — serving NEW prompt lengths after warm
    recompiles NOTHING (while the monolithic lane mints one executable
    per fresh rung — the counterfactual proving the probe bites)."""
    make_engine, window, prompt, \
        (exe, chunk, long_len, short_lens, slots, k_steps) = \
        build_chunked_prefill()
    # warm pass on throwaway engines: compiles (prefill rungs, chunk
    # block, decode scans) land outside the measured windows, so the
    # stall gauges never see a compile wall
    warm_m, warm_c = make_engine(False, 'perf-gate-cp-warm-mono'), \
        make_engine(True, 'perf-gate-cp-warm-chunk')
    try:
        window(warm_m), window(warm_c)
    finally:
        warm_m.stop()
        warm_c.stop()
    mono = make_engine(False, 'perf-gate-cp-mono')
    chunked = make_engine(True, 'perf-gate-cp-chunked')
    try:
        identical = True
        for _ in range(BLOCKS):
            mo, _dm = window(mono)
            co, _dc = window(chunked)
            assert co == mo, \
                'chunked prefill diverged from the monolithic lane: ' \
                '%r vs %r' % (co[:2], mo[:2])
            identical = identical and co == mo
        dm = mono.metrics()['decode']
        dc = chunked.metrics()['decode']
        # structural executable bound: NEW lengths (fresh rungs) after
        # warm — the chunked lane serves them through the same C-wide
        # chunk executable (delta 0); the monolithic lane compiles the
        # fresh rung (delta > 0), proving the counter really counts
        cc0 = chunked.metrics()['executor_compile_count']
        chunked.submit_generate({'src_word_id': prompt(75)},
                                max_len=4).result(600)
        chunked.submit_generate({'src_word_id': prompt(130)},
                                max_len=4).result(600)
        chunked_new_len_compiles = \
            chunked.metrics()['executor_compile_count'] - cc0
        cm0 = mono.metrics()['executor_compile_count']
        mono.submit_generate({'src_word_id': prompt(200)},
                             max_len=4).result(600)
        mono_new_rung_compiles = \
            mono.metrics()['executor_compile_count'] - cm0
    finally:
        mono.stop()
        chunked.stop()
    stall_ratio = dm['max_decode_stall_cycles'] / \
        max(dc['max_decode_stall_cycles'], 1e-9)
    rec = {
        'config': 'chunked_prefill',
        'outputs_token_identical': identical,
        'mono_max_stall_cycles': dm['max_decode_stall_cycles'],
        'chunked_max_stall_cycles': dc['max_decode_stall_cycles'],
        'mono_max_stall_s': dm['max_decode_stall_s'],
        'chunked_max_stall_s': dc['max_decode_stall_s'],
        'stall_reduction': round(stall_ratio, 4),
        'stall_reduction_s': round(
            dm['max_decode_stall_s'] /
            max(dc['max_decode_stall_s'], 1e-9), 4),
        'prefill_chunks': dc['prefill_chunks'],
        'prefill_chunk_tokens': dc['prefill_chunk_tokens'],
        'mono_prefill_lots': dm['prefill_lots'],
        'chunked_new_len_compiles': chunked_new_len_compiles,
        'mono_new_rung_compiles': mono_new_rung_compiles,
        'chunk': chunk, 'long_len': long_len,
        'short_lens': short_lens, 'decode_slots': slots,
        'decode_steps': k_steps, 'blocks': BLOCKS,
    }
    stall_floor = float(os.environ.get('PERF_GATE_CP_STALL_RATIO',
                                       '2.0'))
    assert rec['outputs_token_identical'], rec
    assert rec['prefill_chunks'] > 0, rec
    # gate on the WALL ratio: the cycles gauge normalizes each lane by
    # its OWN min scan wall (right for absolute readings, but the two
    # engines' floors differ under interleaved load), while the raw
    # max-stall walls compare in one unit
    assert rec['stall_reduction_s'] >= stall_floor, rec
    assert rec['chunked_new_len_compiles'] == 0, rec
    assert rec['mono_new_rung_compiles'] > 0, rec
    print(json.dumps(rec), flush=True)
    return rec


def build_sparse_grad():
    """Sparse vs dense embedding-gradient training over the IDENTICAL
    seeded skewed (zipfian) id stream (ISSUE 11): two CTR models — one
    ``is_sparse=True`` (SparseRows lookup backward + row-subset SGD
    scatter-update, no [V, D] grad ever built), one ``is_sparse=False``
    (dense scatter-add grad + full-table update) — with pinned seeds,
    each trained K steps per dispatch via Executor.run_multi on its own
    executor/scope under FLAGS_cost_accounting.  SGD is the paired
    optimizer deliberately: its sparse branch is EXACT (reference
    sgd_op.h SelectedRows), so final params must match allclose across
    the whole run; adaptive optimizers are lazy-by-design (untouched
    rows' moments do not decay — pinned separately in
    tests/test_sparse.py) and would diverge legitimately."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model

    vocab = int(os.environ.get('PERF_GATE_SP_VOCAB', '20000'))
    embed = int(os.environ.get('PERF_GATE_SP_EMBED', '32'))
    batch = int(os.environ.get('PERF_GATE_SP_BATCH', '64'))
    k_steps = int(os.environ.get('PERF_GATE_SP_STEPS', '8'))
    fluid.FLAGS.cost_accounting = True
    place = fluid.default_place()

    from paddle_tpu.dataset import ctr as ctr_data
    rng = np.random.RandomState(0)
    # the skewed CTR id distribution: zipf mass on a few hot ids, a
    # long tail — the regime the sparse lane exists for (the ONE
    # construction shared with bench.py ctr and load_gen --ctr-frac)
    feeds = [ctr_data.zipf_batch(rng, batch, vocab)
             for _ in range(k_steps)]

    def lane(is_sparse):
        with fluid.unique_name.guard():
            # both lanes name their vars identically (fc_0.w_0, ...),
            # so the final-param parity check covers EVERY weight, not
            # just the ParamAttr-pinned table
            m = ctr_model.build(
                sparse_dim=vocab, embed_size=embed, hidden_sizes=(64, 32),
                is_sparse=is_sparse,
                optimizer=fluid.optimizer.SGD(learning_rate=0.05))
        m['main'].random_seed = 0
        m['startup'].random_seed = 0
        exe = fluid.Executor(place)
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(m['startup'])
            # warm the K-step scanned executable (static jit arg)
            exe.run_multi(m['main'], feed_list=[dict(f) for f in feeds],
                          fetch_list=[m['loss']])

        def window():
            with fluid.scope_guard(scope):
                t0 = time.time()
                lv, = exe.run_multi(m['main'],
                                    feed_list=[dict(f) for f in feeds],
                                    fetch_list=[m['loss']])
                elapsed = time.time() - t0
            assert np.isfinite(np.asarray(lv)).all()
            return batch * k_steps / elapsed

        return window, exe, scope

    sparse_w, sparse_exe, sparse_scope = lane(True)
    dense_w, dense_exe, dense_scope = lane(False)
    ctx = {
        'sparse_exe': sparse_exe, 'dense_exe': dense_exe,
        'sparse_scope': sparse_scope, 'dense_scope': dense_scope,
        'vocab': vocab, 'embed': embed, 'batch': batch,
        'k_steps': k_steps, 'table_bytes': vocab * embed * 4,
        'touched_rows': batch * 26,
    }
    return sparse_w, dense_w, ctx


def run_sparse_grad():
    """The sparse_grad record: interleaved sparse/dense windows over
    the identical seeded zipfian stream (each ratio shares a drift
    window — the gates' pairing rule).  HARD asserts (the ISSUE 11
    acceptance): final params allclose-identical across the two lanes,
    ``step_time_ratio`` (sparse wall over dense wall, best shared
    window) <= PERF_GATE_SPARSE_RATIO_MAX (default 1.0), and the
    structural no-dense-grad-buffer check — the sparse lane's timed
    executable allocates LESS XLA temp memory than one [V, D] table
    (the dense gradient cannot be hiding in there), while the dense
    lane's allocates at least that much (the probe provably sees the
    buffer it is asserting absent)."""
    import numpy as np
    sparse_w, dense_w, ctx = build_sparse_grad()
    sp, de = [], []
    for _ in range(BLOCKS):
        sp.append(sparse_w())
        de.append(dense_w())
    # parity first: a fast-but-wrong sparse lane must never pass.  Both
    # lanes ran the same warm + BLOCKS dispatches over the same feeds.
    names = sorted(
        n for n in ctx['sparse_scope'].local_var_names()
        if ctx['dense_scope'].find_var(n) is not None)
    params_checked = 0
    for n in names:
        a = np.asarray(ctx['sparse_scope'].find_var(n).value())
        b = np.asarray(ctx['dense_scope'].find_var(n).value())
        if a.dtype.kind != 'f':
            continue
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5,
            err_msg='sparse lane diverged from dense at %r' % n)
        params_checked += 1
    assert params_checked > 0
    table_bytes = ctx['table_bytes']
    # the structural gate: the [V, D] grad buffer is a TEMP in the
    # dense executable and must not exist in the sparse one
    def _temp(exe):
        entries = [e for e in exe.cost_report()
                   if e.get('kind') == 'multi'
                   and e.get('temp_bytes') is not None]
        return max((e['temp_bytes'] for e in entries), default=None)
    sparse_temp = _temp(ctx['sparse_exe'])
    dense_temp = _temp(ctx['dense_exe'])
    rec = {
        'config': 'sparse_grad',
        'sparse_rows_per_sec': round(max(sp), 1),
        'dense_rows_per_sec': round(max(de), 1),
        'sparse_blocks': [round(v, 1) for v in sp],
        'dense_blocks': [round(v, 1) for v in de],
        # the PAIRED deliverable: sparse step time over dense step time
        # on the best shared drift window (<= 1.0 = sparsity is free or
        # better); rows/s form alongside
        'step_time_ratio': round(min(d / s for s, d in zip(sp, de)), 4),
        'sparse_vs_dense': round(max(s / d for s, d in zip(sp, de)), 4),
        'vocab': ctx['vocab'], 'embed_dim': ctx['embed'],
        'batch': ctx['batch'], 'steps_per_dispatch': ctx['k_steps'],
        'params_checked': params_checked,
        # the sparse lane's per-step gradient is rows x D, not V x D
        'grad_bytes_dense': table_bytes,
        'grad_bytes_sparse': ctx['touched_rows'] * ctx['embed'] * 4,
        'sparse_grad_bytes_avoided_per_step':
            table_bytes - ctx['touched_rows'] * ctx['embed'] * 4,
        'table_bytes': table_bytes,
        'sparse_temp_bytes': sparse_temp,
        'dense_temp_bytes': dense_temp,
        'blocks': BLOCKS,
    }
    ratio_max = float(os.environ.get('PERF_GATE_SPARSE_RATIO_MAX', '1.0'))
    assert rec['step_time_ratio'] <= ratio_max, rec
    if sparse_temp is not None and dense_temp is not None:
        # no dense [V, D] gradient buffer in the sparse lane's cost
        # report — and the dense lane proves the probe detects one
        assert sparse_temp < table_bytes, rec
        assert dense_temp >= table_bytes, rec
    else:
        # a backend without memory analysis cannot run the structural
        # half; the step-time + parity gates above still bind
        rec['temp_analysis'] = 'unavailable'
    print(json.dumps(rec), flush=True)
    return rec


def build_embed_cache():
    """Two-tier hot-row embedding cache vs full-table training over the
    IDENTICAL seeded hot-zipfian CTR stream (ISSUE 12): the CACHED lane
    holds only a [C, D] slab on device (the [V, D] master is
    host-resident in AsyncSparseEmbedding; ids remap to slots, the
    block row exchange runs between dispatches), the UNCACHED lane is
    the PR 10 fast path with the whole table resident.  SGD is the
    paired optimizer: its sparse branch is exact, so the cached lane's
    flushed host table must match the uncached table BITWISE."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model
    from paddle_tpu.dataset import ctr as ctr_data
    from paddle_tpu.distributed import CachedEmbeddingTable

    vocab = int(os.environ.get('PERF_GATE_EC_VOCAB', '16384'))
    embed = int(os.environ.get('PERF_GATE_EC_EMBED', '16'))
    batch = int(os.environ.get('PERF_GATE_EC_BATCH', '64'))
    k_steps = int(os.environ.get('PERF_GATE_EC_STEPS', '8'))
    capacity = int(os.environ.get('PERF_GATE_EC_CAPACITY', '2048'))
    hot_frac = float(os.environ.get('PERF_GATE_EC_HOT_FRAC', '0.95'))
    fluid.FLAGS.cost_accounting = True
    place = fluid.default_place()

    rng = np.random.RandomState(0)
    # the smoke's skew: hot-fraction-sharpened zipf (the ONE shared
    # construction, dataset.ctr.zipf_batch) — the regime where a small
    # hot-row working set absorbs nearly every lookup
    feeds = [ctr_data.zipf_batch(rng, batch, vocab, hot_frac=hot_frac)
             for _ in range(k_steps * (BLOCKS + 1))]

    def lane(cached, capacity=capacity):
        with fluid.unique_name.guard():
            m = ctr_model.build(
                sparse_dim=vocab, embed_size=embed, hidden_sizes=(64, 32),
                is_sparse=True,
                optimizer=fluid.optimizer.SGD(learning_rate=0.05))
        m['main'].random_seed = 0
        m['startup'].random_seed = 0
        exe = fluid.Executor(place)
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(m['startup'])
        cache = None
        if cached:
            cache = CachedEmbeddingTable.from_scope(
                scope, m['main'], 'ctr_embedding', capacity,
                ['sparse_ids'])

        def window(block):
            fl = [dict(f) for f in
                  feeds[block * k_steps:(block + 1) * k_steps]]
            with fluid.scope_guard(scope):
                t0 = time.time()
                lv, = exe.run_multi(
                    m['main'], feed_list=fl, fetch_list=[m['loss']],
                    embed_caches=[cache] if cache else None)
                elapsed = time.time() - t0
            assert np.isfinite(np.asarray(lv)).all()
            return batch * k_steps / elapsed

        return window, exe, scope, cache, m

    cached_w, cached_exe, cached_scope, cache, _cm = lane(True)
    plain_w, plain_exe, plain_scope, _, _pm = lane(False)
    ctx = {
        'cached_exe': cached_exe, 'plain_exe': plain_exe,
        'cached_scope': cached_scope, 'plain_scope': plain_scope,
        'cache': cache, 'vocab': vocab, 'embed': embed, 'batch': batch,
        'k_steps': k_steps, 'capacity': capacity, 'hot_frac': hot_frac,
        'table_bytes': vocab * embed * 4, 'feeds': feeds, 'lane': lane,
    }
    return cached_w, plain_w, ctx


def run_embed_cache():
    """The embed_cache record (ISSUE 12 acceptance): cached-vs-uncached
    lanes over ONE seeded hot-zipfian stream.  HARD asserts — final
    params allclose across the lanes with the table itself BITWISE
    (SGD exact); ``hit_rate`` >= PERF_GATE_EMBED_HIT_MIN (0.9) at the
    smoke's skew; ``host_bytes_reduction`` (the measured
    every-STEP-exchange lane's host bytes/step over the cached lane's)
    >= PERF_GATE_EMBED_HOST_RATIO (4.0); and the STRUCTURAL assert
    that the cached lane's timed executable allocates LESS XLA temp
    memory than one full [V, D] table — the working set on device
    really is the slab, not the table."""
    import numpy as np
    cached_w, plain_w, ctx = build_embed_cache()
    ca, pl = [], []
    for b in range(BLOCKS):
        ca.append(cached_w(b))
        pl.append(plain_w(b))
    cache = ctx['cache']
    cache.flush()
    cache_metrics = cache.metrics()
    # parity FIRST: a fast-but-wrong cache must never pass.  The
    # flushed host master is the cached lane's full-table truth.
    cached_table = cache.table()
    plain_table = np.asarray(
        ctx['plain_scope'].find_var('ctr_embedding').value())
    assert np.array_equal(cached_table, plain_table), \
        'cached lane table diverged from full-table lane (SGD must be ' \
        'EXACT; max diff %g)' % np.abs(cached_table - plain_table).max()
    names = sorted(
        n for n in ctx['cached_scope'].local_var_names()
        if n != 'ctr_embedding'
        and ctx['plain_scope'].find_var(n) is not None)
    params_checked = 1
    for n in names:
        a = np.asarray(ctx['cached_scope'].find_var(n).value())
        b = np.asarray(ctx['plain_scope'].find_var(n).value())
        if a.dtype.kind != 'f' or a.shape != b.shape:
            continue
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5,
            err_msg='cached lane diverged from full-table at %r' % n)
        params_checked += 1
    assert params_checked > 1
    # the EVERY-STEP-EXCHANGE comparator (the reference remote-updater
    # shape): same machinery, residency invalidated before every
    # single-step dispatch — each step fetches its whole row set from
    # host and flushes its dirty rows back.  Measured, not modeled.
    k_steps, batch = ctx['k_steps'], ctx['batch']
    ex_w, ex_exe, ex_scope, ex_cache, ex_m = ctx['lane'](True)
    import paddle_tpu.fluid as fluid
    with fluid.scope_guard(ex_scope):
        for f in ctx['feeds'][:k_steps]:
            ex_cache.invalidate()
            ex_exe.run_multi(ex_m['main'], feed_list=[dict(f)],
                             fetch_list=[ex_m['loss']],
                             embed_caches=[ex_cache])
    ex_cache.flush()
    ex_metrics = ex_cache.metrics()
    exchange_bps = ex_metrics['host_bytes'] / k_steps
    cached_bps = cache_metrics['host_bytes_per_step']
    table_bytes = ctx['table_bytes']

    def _temp(exe):
        entries = [e for e in exe.cost_report()
                   if e.get('kind') == 'multi'
                   and e.get('temp_bytes') is not None]
        return max((e['temp_bytes'] for e in entries), default=None)

    cached_temp = _temp(ctx['cached_exe'])
    rec = {
        'config': 'embed_cache',
        'cached_rows_per_sec': round(max(ca), 1),
        'uncached_rows_per_sec': round(max(pl), 1),
        'cached_blocks': [round(v, 1) for v in ca],
        'uncached_blocks': [round(v, 1) for v in pl],
        'step_time_ratio': round(min(p / c for c, p in zip(ca, pl)), 4),
        'hit_rate': round(cache_metrics['hit_rate'], 4),
        'prefetch_stalls': cache_metrics['prefetch_stalls'],
        'exchanges': cache_metrics['exchanges'],
        'host_bytes_per_step_cached': round(cached_bps, 1),
        'host_bytes_per_step_exchange': round(exchange_bps, 1),
        'host_bytes_reduction': round(exchange_bps /
                                      max(cached_bps, 1e-9), 2),
        'table_bytes': table_bytes,
        'slab_bytes': cache.slab_nbytes(),
        'cached_temp_bytes': cached_temp,
        'params_checked': params_checked,
        'vocab': ctx['vocab'], 'embed_dim': ctx['embed'],
        'batch': batch, 'steps_per_dispatch': k_steps,
        'capacity': ctx['capacity'], 'hot_frac': ctx['hot_frac'],
        'blocks': BLOCKS,
    }
    cache.close()
    ex_cache.close()
    hit_min = float(os.environ.get('PERF_GATE_EMBED_HIT_MIN', '0.9'))
    host_ratio = float(os.environ.get('PERF_GATE_EMBED_HOST_RATIO',
                                      '4.0'))
    assert rec['hit_rate'] >= hit_min, rec
    assert rec['host_bytes_reduction'] >= host_ratio, rec
    if cached_temp is not None:
        # the structural half: the timed executable's temp buffers stay
        # below ONE full table — the device working set is the slab
        assert cached_temp < table_bytes, rec
    else:
        rec['temp_analysis'] = 'unavailable'
    print(json.dumps(rec), flush=True)
    return rec


def build_pserver():
    """Sharded parameter-server tier vs the single-process master
    (ISSUE 19): both lanes run the SAME CachedEmbeddingTable machinery
    over the IDENTICAL seeded hot-zipfian CTR stream
    (dataset.ctr.zipf_batch) — the SHARDED lane's host tier is a
    ShardedEmbeddingClient over PERF_GATE_PS_SHARDS PServerShard
    row-range processes behind the resilient transport, the SINGLE
    lane's is the in-process AsyncSparseEmbedding.  SGD is the paired
    optimizer: row-range routing merges partials in id order, so the
    sharded lane's flushed table must match the single lane BITWISE."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model
    from paddle_tpu.dataset import ctr as ctr_data
    from paddle_tpu.distributed import (CachedEmbeddingTable,
                                        sharded_cache_from_scope)

    vocab = int(os.environ.get('PERF_GATE_PS_VOCAB', '16384'))
    embed = int(os.environ.get('PERF_GATE_PS_EMBED', '16'))
    batch = int(os.environ.get('PERF_GATE_PS_BATCH', '64'))
    k_steps = int(os.environ.get('PERF_GATE_PS_STEPS', '8'))
    capacity = int(os.environ.get('PERF_GATE_PS_CAPACITY', '2048'))
    hot_frac = float(os.environ.get('PERF_GATE_PS_HOT_FRAC', '0.95'))
    n_shards = int(os.environ.get('PERF_GATE_PS_SHARDS', '4'))
    fluid.FLAGS.cost_accounting = True
    place = fluid.default_place()

    rng = np.random.RandomState(0)
    feeds = [ctr_data.zipf_batch(rng, batch, vocab, hot_frac=hot_frac)
             for _ in range(k_steps * (BLOCKS + 1))]

    def lane(sharded, capacity=capacity):
        with fluid.unique_name.guard():
            m = ctr_model.build(
                sparse_dim=vocab, embed_size=embed, hidden_sizes=(64, 32),
                is_sparse=True,
                optimizer=fluid.optimizer.SGD(learning_rate=0.05))
        m['main'].random_seed = 0
        m['startup'].random_seed = 0
        exe = fluid.Executor(place)
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(m['startup'])
        client = shard_procs = None
        if sharded:
            cache, client, shard_procs = sharded_cache_from_scope(
                scope, m['main'], 'ctr_embedding', capacity,
                ['sparse_ids'], shards=n_shards)
        else:
            cache = CachedEmbeddingTable.from_scope(
                scope, m['main'], 'ctr_embedding', capacity,
                ['sparse_ids'])

        def window(block):
            fl = [dict(f) for f in
                  feeds[block * k_steps:(block + 1) * k_steps]]
            with fluid.scope_guard(scope):
                t0 = time.time()
                lv, = exe.run_multi(
                    m['main'], feed_list=fl, fetch_list=[m['loss']],
                    embed_caches=[cache])
                elapsed = time.time() - t0
            assert np.isfinite(np.asarray(lv)).all()
            return batch * k_steps / elapsed

        return window, exe, scope, cache, client, shard_procs, m

    sh_w, sh_exe, sh_scope, sh_cache, sh_client, sh_procs, _m1 = \
        lane(True)
    si_w, si_exe, si_scope, si_cache, _c, _p, _m2 = lane(False)
    ctx = {
        'sharded_scope': sh_scope, 'single_scope': si_scope,
        'sharded_cache': sh_cache, 'single_cache': si_cache,
        'sharded_client': sh_client, 'shard_procs': sh_procs,
        'vocab': vocab, 'embed': embed, 'batch': batch,
        'k_steps': k_steps, 'capacity': capacity,
        'hot_frac': hot_frac, 'n_shards': n_shards,
        'feeds': feeds, 'lane': lane,
    }
    return sh_w, si_w, ctx


def check_pserver_chaos(tmpdir):
    """The seeded shard-chaos contract (ISSUE 19 acceptance),
    functional and deterministic: cached CTR training over 4 shards
    while a seeded FaultInjector drops a write_rows response on the
    wire (the retry must dedup-replay, not double-apply) and, mid-
    pass, shard 0 is KILLED with no final flush and restored at the
    same port from its last AsyncShardedCheckpoint commit (dedup
    window restored alongside).  Training finishes BITWISE vs the
    fault-free single-process master: zero lost writes, zero
    double-applied writes."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model
    from paddle_tpu.dataset import ctr as ctr_data
    from paddle_tpu.distributed import (CachedEmbeddingTable,
                                        FaultInjector, PServerShard,
                                        sharded_cache_from_scope)
    from paddle_tpu.distributed.transport import RetryPolicy

    vocab, embed, capacity, batch, k_steps, blocks = \
        512, 8, 512, 16, 4, 3
    rng = np.random.RandomState(0)
    feeds = [ctr_data.zipf_batch(rng, batch, vocab)
             for _ in range(k_steps * blocks)]

    def lane(chaos):
        with fluid.unique_name.guard():
            m = ctr_model.build(
                sparse_dim=vocab, embed_size=embed, hidden_sizes=(16, ),
                is_sparse=True,
                optimizer=fluid.optimizer.SGD(learning_rate=0.05))
        m['main'].random_seed = 0
        m['startup'].random_seed = 0
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(m['startup'])
        client = procs = fi = None
        replays = 0
        if chaos:
            fi = FaultInjector(seed=0)
            fi.script('server_send', 'write_rows', 'drop_response',
                      nth=1)
            cache, client, procs = sharded_cache_from_scope(
                scope, m['main'], 'ctr_embedding', capacity,
                ['sparse_ids'], shards=4, checkpoint_root=tmpdir,
                fault_injector=fi, timeout=0.75,
                retry=RetryPolicy(seed=0, base_backoff_s=0.02))
        else:
            cache = CachedEmbeddingTable.from_scope(
                scope, m['main'], 'ctr_embedding', capacity,
                ['sparse_ids'])
        with fluid.scope_guard(scope):
            for blk in range(blocks):
                exe.run_multi(
                    m['main'],
                    feed_list=[dict(f) for f in
                               feeds[blk * k_steps:(blk + 1) * k_steps]],
                    fetch_list=[m['loss']], embed_caches=[cache])
                if chaos and blk == 0:
                    # mid-pass host loss: quiesce the exchange
                    # pipeline, make shard 0's last mutations durable,
                    # KILL it, restore at the SAME port from the
                    # commit — the client's reconnect lane resumes
                    cache.flush()
                    victim = procs[0]
                    port = victim.port
                    victim.checkpoint(wait=True)
                    victim.kill()
                    replays += victim.dedup_replays
                    procs[0] = PServerShard.restore(
                        os.path.join(tmpdir, 'shard-%05d' % 0),
                        port=port)
        table = cache.table()
        rpc = client.metrics() if client else None
        if procs:
            replays += sum(s.dedup_replays for s in procs)
        cache.close()
        if procs:
            for s in procs:
                s.close()
        return table, rpc, replays, fi

    chaos_table, rpc, replays, fi = lane(True)
    ref_table, _, _, _ = lane(False)
    bitwise = np.array_equal(chaos_table, ref_table)
    assert bitwise, \
        'chaos-run table diverged from the fault-free single-process ' \
        'master (max diff %g)' % np.abs(chaos_table - ref_table).max()
    lanes = rpc['shards']
    assert fi.applied >= 1, fi.counts()
    assert replays >= 1, replays
    assert sum(m['retries'] for m in lanes) >= 1, lanes
    assert sum(m['reconnects'] for m in lanes) >= 1, lanes
    return {
        'chaos_bitwise_table': True,
        'chaos_lost_writes': 0,
        'chaos_double_applied_writes': 0,
        'chaos_dedup_replays': replays,
        'chaos_retries': sum(m['retries'] for m in lanes),
        'chaos_reconnects': sum(m['reconnects'] for m in lanes),
        'chaos_injected_faults': fi.applied,
        'chaos_shard_restarts': 1,
    }


def run_pserver():
    """The pserver record (ISSUE 19): sharded-vs-single-process-master
    cached lanes over ONE seeded zipfian stream.  HARD asserts — the
    sharded lane's flushed table (and every co-cached accumulator)
    BITWISE equals the single lane's, final params allclose;
    ``hit_rate`` and ``host_bytes_reduction`` hold the SAME gates as
    embed_cache (PERF_GATE_EMBED_HIT_MIN / PERF_GATE_EMBED_HOST_RATIO
    — the tier must not change what the cache fetches or writes back);
    and the seeded shard-kill chaos block (drop_response + mid-pass
    kill-and-restore) finishes bitwise with zero lost / zero
    double-applied writes."""
    import shutil
    import tempfile
    import numpy as np
    sh_w, si_w, ctx = build_pserver()
    sh, si = [], []
    for b in range(BLOCKS):
        sh.append(sh_w(b))
        si.append(si_w(b))
    sh_cache, si_cache = ctx['sharded_cache'], ctx['single_cache']
    sh_cache.flush()
    si_cache.flush()
    sh_metrics = sh_cache.metrics()
    si_metrics = si_cache.metrics()
    # parity FIRST: a fast-but-wrong tier must never pass.  Weight AND
    # accumulators, bitwise across the host-tier boundary.
    sh_table = sh_cache.table()
    si_table = si_cache.table()
    assert np.array_equal(sh_table, si_table), \
        'sharded lane table diverged from the single-process master ' \
        '(max diff %g)' % np.abs(sh_table - si_table).max()
    for name in sh_cache.tables[1:]:
        assert np.array_equal(sh_cache.table(name),
                              si_cache.table(name)), name
    names = sorted(
        n for n in ctx['sharded_scope'].local_var_names()
        if n != 'ctr_embedding'
        and ctx['single_scope'].find_var(n) is not None)
    params_checked = 1
    for n in names:
        a = np.asarray(ctx['sharded_scope'].find_var(n).value())
        b = np.asarray(ctx['single_scope'].find_var(n).value())
        if a.dtype.kind != 'f' or a.shape != b.shape:
            continue
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5,
            err_msg='sharded lane diverged from single-process at %r'
            % n)
        params_checked += 1
    assert params_checked > 1
    # identical exchange traffic across the host-tier boundary: the
    # cache must fetch and write back the SAME rows either way
    for key in ('hits', 'misses', 'host_fetch_bytes',
                'host_writeback_bytes'):
        assert sh_metrics[key] == si_metrics[key], key
    # the EVERY-STEP-EXCHANGE comparator, on the SHARDED tier: same
    # machinery, residency invalidated before every single-step
    # dispatch — the hot-row slab's host-byte (here: RPC-byte)
    # reduction, measured against the tier that pays per row
    import paddle_tpu.fluid as fluid
    k_steps, batch = ctx['k_steps'], ctx['batch']
    ex_w, ex_exe, ex_scope, ex_cache, ex_client, ex_procs, ex_m = \
        ctx['lane'](True)
    with fluid.scope_guard(ex_scope):
        for f in ctx['feeds'][:k_steps]:
            ex_cache.invalidate()
            ex_exe.run_multi(ex_m['main'], feed_list=[dict(f)],
                             fetch_list=[ex_m['loss']],
                             embed_caches=[ex_cache])
    ex_cache.flush()
    exchange_bps = ex_cache.metrics()['host_bytes'] / k_steps
    cached_bps = sh_metrics['host_bytes_per_step']
    rpc = ctx['sharded_client'].metrics()
    rec = {
        'config': 'pserver',
        'sharded_rows_per_sec': round(max(sh), 1),
        'single_rows_per_sec': round(max(si), 1),
        'sharded_blocks': [round(v, 1) for v in sh],
        'single_blocks': [round(v, 1) for v in si],
        'step_time_ratio': round(min(s / c for c, s in zip(sh, si)), 4),
        'hit_rate': round(sh_metrics['hit_rate'], 4),
        'exchanges': sh_metrics['exchanges'],
        'host_bytes_per_step_cached': round(cached_bps, 1),
        'host_bytes_per_step_exchange': round(exchange_bps, 1),
        'host_bytes_reduction': round(exchange_bps /
                                      max(cached_bps, 1e-9), 2),
        'params_checked': params_checked,
        'shards': ctx['n_shards'],
        'rpc_calls': sum(m['calls'] for m in rpc['shards']),
        'rpc_retries': sum(m['retries'] for m in rpc['shards']),
        'vocab': ctx['vocab'], 'embed_dim': ctx['embed'],
        'batch': batch, 'steps_per_dispatch': k_steps,
        'capacity': ctx['capacity'], 'hot_frac': ctx['hot_frac'],
        'blocks': BLOCKS,
    }
    sh_cache.close()
    si_cache.close()
    ex_cache.close()
    for s in ctx['shard_procs'] + (ex_procs or []):
        s.close()
    tmpdir = tempfile.mkdtemp(prefix='perf_gate_pserver_')
    try:
        rec.update(check_pserver_chaos(tmpdir))
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    # gates UNCHANGED from embed_cache: the tier must not change what
    # the cache fetches, hits, or writes back
    hit_min = float(os.environ.get('PERF_GATE_EMBED_HIT_MIN', '0.9'))
    host_ratio = float(os.environ.get('PERF_GATE_EMBED_HOST_RATIO',
                                      '4.0'))
    assert rec['hit_rate'] >= hit_min, rec
    assert rec['host_bytes_reduction'] >= host_ratio, rec
    assert rec['chaos_bitwise_table'], rec
    assert rec['chaos_lost_writes'] == 0, rec
    assert rec['chaos_double_applied_writes'] == 0, rec
    assert rec['chaos_dedup_replays'] >= 1, rec
    print(json.dumps(rec), flush=True)
    return rec


def build_elastic():
    """The checkpoint-overhead trio (ISSUE 13): one warmed
    executor/scope trains identical seeded K-step dispatches under
    three durability modes — none, ASYNC manifest checkpoints
    (capture host copies, write on the store's background thread),
    and SYNCHRONOUS inline writes (the comparator: what a blocking
    pserver-style save would cost every interval).  Windows reuse the
    SAME executable, so the pair measures checkpoint policy, not
    compile weather."""
    import shutil
    import tempfile
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import io as fluid_io
    from paddle_tpu.distributed import AsyncShardedCheckpoint

    dim = int(os.environ.get('PERF_GATE_EL_DIM', '128'))
    hidden = int(os.environ.get('PERF_GATE_EL_HIDDEN', '256'))
    batch = int(os.environ.get('PERF_GATE_EL_BATCH', '128'))
    k_steps = int(os.environ.get('PERF_GATE_EL_STEPS', '8'))
    dispatches = int(os.environ.get('PERF_GATE_EL_DISPATCHES', '6'))
    # checkpoint every N delivered dispatches (the job's
    # checkpoint_every — periodic durability, not per-step)
    interval = int(os.environ.get('PERF_GATE_EL_INTERVAL', '2'))

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[dim])
        y = fluid.layers.data('y', shape=[1])
        hid = fluid.layers.fc(x, size=hidden, act='tanh')
        pred = fluid.layers.fc(hid, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(0.01).minimize(loss)

    place = fluid.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(7)
    feeds = [{'x': rng.standard_normal((batch, dim)).astype('float32'),
              'y': rng.standard_normal((batch, 1)).astype('float32')}
             for _ in range(k_steps)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        # warm the K-step scanned executable (and its allocator /
        # autotune weather) until a repeat run costs what the timed
        # windows will; every window reuses the same executable
        for _ in range(3):
            exe.run_multi(main, feed_list=[dict(f) for f in feeds],
                          fetch_list=[loss])

    persistables = [v.name for v in main.list_vars()
                    if fluid_io.is_persistable(v)]

    def capture():
        # the job's donated-safe host-copy point (_state_arrays)
        return {n: np.asarray(scope.find_var(n).value())
                for n in persistables
                if scope.find_var(n) is not None
                and scope.find_var(n).value() is not None}

    tmpdir = tempfile.mkdtemp(prefix='perf_gate_elastic_')
    stores = {
        'async': AsyncShardedCheckpoint(
            os.path.join(tmpdir, 'async'), keep=2),
        'sync': AsyncShardedCheckpoint(
            os.path.join(tmpdir, 'sync'), keep=2, sync=True),
    }
    counter = [0]

    def window(mode):
        def run():
            with fluid.scope_guard(scope):
                t0 = time.time()
                for _ in range(dispatches):
                    exe.run_multi(main,
                                  feed_list=[dict(f) for f in feeds],
                                  fetch_list=[loss])
                    counter[0] += 1
                    if mode != 'none' and counter[0] % interval == 0:
                        stores[mode].save(counter[0], capture(),
                                          extras={'step': counter[0]})
                if mode == 'async':
                    # drain OUTSIDE the timed region on close; the
                    # step loop itself never waited
                    pass
                wall = time.time() - t0
            return dispatches * k_steps * batch / wall, wall
        return run

    ctx = {'stores': stores, 'tmpdir': tmpdir, 'batch': batch,
           'k_steps': k_steps, 'dispatches': dispatches,
           'interval': interval,
           'cleanup': lambda: shutil.rmtree(tmpdir, ignore_errors=True)}
    return window('none'), window('async'), window('sync'), ctx


def _elastic_toy_dataset(path, dim=8, rpt=8, n_tasks=6):
    """The seeded (x, y) RecordIO dataset every elastic toy job
    trains on — ONE definition so the kill-resume, chaos and window
    lanes provably share a stream."""
    import pickle
    import numpy as np
    from paddle_tpu.runtime.native import RecordIOWriter
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path)
    for _ in range(rpt * n_tasks):
        xv = rng.standard_normal(dim).astype('float32')
        w.write(pickle.dumps((xv, np.array([xv.sum() * 0.5],
                                           'float32'))))
    w.close()


def _elastic_toy_build(dim=8):
    """build_fn for the elastic toy jobs (fc/tanh/fc, SGD)."""
    def build():
        import paddle_tpu.fluid as fluid
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[dim])
            y = fluid.layers.data('y', shape=[1])
            hid = fluid.layers.fc(x, size=4, act='tanh')
            pred = fluid.layers.fc(hid, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(0.05).minimize(loss)
        return main, startup, loss
    return build


def _elastic_toy_batch(records):
    import pickle
    import numpy as np
    rows = [pickle.loads(r) for r in records]
    return {'x': np.stack([r[0] for r in rows]).astype('float32'),
            'y': np.stack([r[1] for r in rows]).astype('float32')}


def _elastic_toy_params(job):
    import numpy as np
    return {n: np.asarray(job._scope.find_var(n).value())
            for n in job._persistable_names()
            if job._scope.find_var(n) is not None
            and job._scope.find_var(n).value() is not None}


def check_kill_resume(tmpdir):
    """The kill-resume goodput check (ISSUE 13 acceptance), functional
    and deterministic: an ElasticTrainJob killed holding its LAST
    claim; the claim's lease observed timing out and re-dispatching; a
    replacement job resumes from the newest manifest, replays ZERO
    steps, and final params are BITWISE-identical to an uninterrupted
    run (SGD).  Returns the record block run_elastic folds in."""
    import numpy as np
    from paddle_tpu.distributed import ElasticTrainJob, Master
    from paddle_tpu.fluid.dataflow import FeedPipelineError

    dim, rpt, n_tasks = 8, 8, 6
    data = os.path.join(tmpdir, 'kill_resume.recordio')
    _elastic_toy_dataset(data, dim=dim, rpt=rpt, n_tasks=n_tasks)
    build = _elastic_toy_build(dim)
    batch_fn = _elastic_toy_batch
    params_of = _elastic_toy_params

    # uninterrupted reference
    m0 = Master(chunk_timeout_secs=120)
    m0.set_dataset([data], records_per_task=rpt)
    ref = ElasticTrainJob(build, m0, os.path.join(tmpdir, 'ref'),
                          batch_fn, worker_id='ref')
    ref.run()
    ref_params = params_of(ref)
    ref.close()
    m0.close()

    class _Killed(Exception):
        pass

    def kill_hook(tid, task, ordinal):
        if ordinal == n_tasks - 1:
            raise _Killed('killed holding tid %d' % tid)

    master = Master(chunk_timeout_secs=1.0)
    master.set_dataset([data], records_per_task=rpt)
    t0 = time.time()
    a = ElasticTrainJob(build, master, os.path.join(tmpdir, 'job'),
                        batch_fn, worker_id='A', task_hook=kill_hook)
    try:
        a.run()
        raise AssertionError('kill hook never fired')
    except FeedPipelineError:
        pass
    assert master.counts()[1] == 1, master.counts()  # claim still leased
    b = ElasticTrainJob(build, master, os.path.join(tmpdir, 'job'),
                        batch_fn, worker_id='B')
    b.run()  # waits out the lease: the re-dispatch IS the resume path
    wall = time.time() - t0
    assert b.resumed and b.start_step == n_tasks - 1, \
        (b.resumed, b.start_step)
    replayed = (a.step + len(b.tasks_done)) - n_tasks
    assert replayed == 0, 'resume replayed %d steps' % replayed
    assert master.counts() == (0, 0, n_tasks, 0), master.counts()
    got = params_of(b)
    bitwise = all(np.array_equal(ref_params[n], got[n])
                  for n in ref_params)
    assert bitwise, 'kill-resume params diverged from uninterrupted run'
    goodput = n_tasks * rpt / max(wall, 1e-9)
    a.close()
    b.close()
    master.close()
    return {'kill_resume_bitwise': True, 'resume_replayed_steps': 0,
            'kill_resume_rows_per_sec': round(goodput, 1),
            'kill_resume_wall_s': round(wall, 2),
            'lease_redispatched': True}


def run_elastic():
    """The elastic record: interleaved none/async/sync checkpoint
    windows over one warmed executor (each ratio shares a drift
    window).  HARD asserts (the ISSUE 13 acceptance):
    ``checkpoint_overhead_ratio`` (async wall over no-checkpoint wall,
    best shared window) <= PERF_GATE_ELASTIC_OVERHEAD (default 1.05),
    the async lane's writes all committed (manifests exist, writer
    drained clean), and the kill-resume check — zero replayed steps,
    bitwise params, the dead claim's lease observed re-dispatching."""
    bare_w, async_w, sync_w, ctx = build_elastic()
    bare, asyn, sync = [], [], []
    try:
        for _ in range(BLOCKS):
            # the GATED pair (bare, async) stays adjacent per block;
            # the async store drains OUTSIDE the timed windows so its
            # trailing background write never bleeds into the sync
            # window (or the next block's bare denominator)
            bare.append(bare_w())
            asyn.append(async_w())
            ctx['stores']['async'].wait()
            sync.append(sync_w())
        ctx['stores']['async'].wait()  # all enqueued writes committed
        async_metrics = ctx['stores']['async'].metrics()
        sync_metrics = ctx['stores']['sync'].metrics()
        rec = {
            'config': 'elastic',
            'bare_rows_per_sec': round(max(r for r, _ in bare), 1),
            'async_rows_per_sec': round(max(r for r, _ in asyn), 1),
            'sync_rows_per_sec': round(max(r for r, _ in sync), 1),
            'bare_blocks': [round(r, 1) for r, _ in bare],
            'async_blocks': [round(r, 1) for r, _ in asyn],
            'sync_blocks': [round(r, 1) for r, _ in sync],
            # the HARD gate: async checkpointing's step-time tax over
            # the bare lane, best shared drift window
            'checkpoint_overhead_ratio': round(
                min(aw / bw for (_, aw), (_, bw) in zip(asyn, bare)),
                4),
            # the deliverable comparator: what the blocking write costs
            'sync_overhead_ratio': round(
                min(sw / bw for (_, sw), (_, bw) in zip(sync, bare)),
                4),
            'async_saves': async_metrics['saves'],
            'async_stalls': async_metrics['stalls'],
            'async_bytes_written': async_metrics['bytes_written'],
            'sync_saves': sync_metrics['saves'],
            'batch': ctx['batch'], 'steps_per_dispatch': ctx['k_steps'],
            'dispatches_per_window': ctx['dispatches'],
            'checkpoint_interval': ctx['interval'],
            'blocks': BLOCKS,
        }
        assert async_metrics['errors'] == 0, async_metrics
        assert async_metrics['saves'] > 0, async_metrics
        rec.update(check_kill_resume(ctx['tmpdir']))
        floor = float(os.environ.get('PERF_GATE_ELASTIC_OVERHEAD',
                                     '1.05'))
        assert rec['checkpoint_overhead_ratio'] <= floor, rec
        assert rec['resume_replayed_steps'] == 0, rec
        assert rec['kill_resume_bitwise'], rec
    finally:
        for store in ctx['stores'].values():
            try:
                store.close()
            except Exception:
                pass
        ctx['cleanup']()
    print(json.dumps(rec), flush=True)
    return rec


def build_master_chaos():
    """Resilient-vs-bare ELASTIC windows (ISSUE 15): each window runs
    one full ``ElasticTrainJob`` pass over the SAME seeded dataset
    against its own Master/MasterServer — the bare side holds a plain
    ``MasterClient``, the resilient side takes the ``endpoints=`` lane
    (request-id minting, the server's dedup window, the reconnect/
    backoff machinery — all on the no-fault happy path).  The paired
    ratio is what control-plane fault tolerance costs a training job
    when NOTHING is failing.  A secondary pure-RPC drain pair
    (claim+finish every task through each client, no training)
    isolates the per-RPC tax as a diagnostic — on loopback the dedup
    bookkeeping + request-id fields are visible there (~1.1-1.2x of a
    ~20us no-op RPC) while staying invisible at job scale.  The chaos
    contract itself is functional, not timed — run_master_chaos folds
    in ``check_master_chaos`` and ``check_dedup_replay``."""
    import shutil
    import tempfile
    from paddle_tpu.distributed import (ElasticTrainJob, Master,
                                        MasterClient, MasterServer,
                                        ResilientMasterClient,
                                        RetryPolicy)

    dim = 8
    rpt = int(os.environ.get('PERF_GATE_CHAOS_RPT', '8'))
    n_tasks = int(os.environ.get('PERF_GATE_CHAOS_TASKS', '6'))
    drain_tasks = int(os.environ.get('PERF_GATE_CHAOS_DRAIN_TASKS',
                                     '64'))
    tmpdir = tempfile.mkdtemp(prefix='perf_gate_mchaos_')
    data = os.path.join(tmpdir, 'train.recordio')
    _elastic_toy_dataset(data, dim=dim, rpt=rpt, n_tasks=n_tasks)
    build = _elastic_toy_build(dim)
    batch_fn = _elastic_toy_batch
    counter = [0]

    def elastic_window(resilient):
        def run():
            counter[0] += 1
            master = Master(chunk_timeout_secs=120)
            master.set_dataset([data], records_per_task=rpt)
            server = MasterServer(master)
            ckpt = os.path.join(tmpdir, 'w%03d' % counter[0])
            cli = None
            kwargs = {}
            if resilient:
                kwargs['endpoints'] = [server.endpoint]
                kwargs['retry_policy'] = RetryPolicy(seed=0)
                job_master = None
            else:
                cli = job_master = MasterClient(server.endpoint)
            t0 = time.time()
            job = ElasticTrainJob(build, job_master, ckpt, batch_fn,
                                  worker_id='w%d' % counter[0],
                                  checkpoint_every=0, **kwargs)
            job.run()
            wall = time.time() - t0
            assert len(job.tasks_done) == n_tasks, job.metrics()
            job.close()
            if cli is not None:
                cli.close()
            server.close()
            master.close()
            return n_tasks * rpt / wall, wall
        return run

    def drain_window(resilient):
        """Pure control-plane drain: the per-RPC diagnostic pair."""
        def run():
            master = Master(chunk_timeout_secs=60)
            for i in range(drain_tasks):
                master._q.add_task(json.dumps(
                    {'path': 'mem', 'start': i * 8,
                     'count': 8}).encode())
            master._seq += 1
            server = MasterServer(master)
            cli = (ResilientMasterClient([server.endpoint],
                                         retry=RetryPolicy(seed=0))
                   if resilient else MasterClient(server.endpoint))
            t0 = time.time()
            done = 0
            while True:
                tid, task = cli.get_task()
                if tid == -1:
                    break
                if task is None:
                    time.sleep(0.001)
                    continue
                cli.task_finished(tid)
                done += 1
            wall = time.time() - t0
            assert done == drain_tasks, (done, drain_tasks)
            cli.close()
            server.close()
            master.close()
            return drain_tasks / wall, wall
        return run

    ctx = {'n_tasks': n_tasks, 'rpt': rpt,
           'drain_tasks': drain_tasks,
           'drain_windows': (drain_window(False), drain_window(True)),
           'cleanup': lambda: shutil.rmtree(tmpdir,
                                            ignore_errors=True)}
    return elastic_window(False), elastic_window(True), ctx


def check_dedup_replay():
    """The exactly-once pin (ISSUE 15 acceptance): a replayed
    ``task_failed`` must NOT advance the failure count.  The
    adversarial interleave — response lost, the task re-claimed, THEN
    the retry lands — is exactly where a bare re-execution would fail
    the NEW claim and discard the task at failure_max=2; the dedup
    window replays the recorded response instead.  The counterfactual
    (a genuinely new request id) proves the probe bites."""
    from paddle_tpu.distributed import Master
    m = Master(chunk_timeout_secs=60, failure_max=2)
    m._q.add_task(b'{"path": "mem", "start": 0, "count": 1}')
    m._seq += 1
    tid, _ = m.get_task()

    def fail():
        return {'discarded': m.task_failed(tid)}

    r1 = m.dedup_execute('worker-0', '1', fail)
    assert r1 == {'discarded': 0}, r1
    tid2, _ = m.get_task()  # re-claimed between the loss and the retry
    assert tid2 == tid, (tid2, tid)
    r2 = m.dedup_execute('worker-0', '1', fail)  # the RETRY: replays
    assert r2 == r1, (r2, r1)
    assert m.counts()[3] == 0, m.counts()  # failure count NOT advanced
    # counterfactual: a NEW rid executes for real and discards
    r3 = m.dedup_execute('worker-0', '2', fail)
    assert r3 == {'discarded': 1}, r3
    m.close()
    return {'replayed_task_failed_deduped': True,
            'dedup_counterfactual_discards': True}


def check_master_chaos(tmpdir):
    """The seeded chaos contract (ISSUE 15 acceptance), functional
    and deterministic: an ElasticTrainJob driven through a
    ``ResilientMasterClient`` over [primary, standby] endpoints while
    a seeded ``FaultInjector`` drops a ``task_finished`` response and
    a ``get_task`` response on the primary (retries must dedup-replay
    — a leaked claim would reorder training and break bitwise parity)
    and stretches heartbeats to just under the lease TTL (late but
    live: no membership flap).  Mid-pass, while the job holds a
    claim, the primary dies with NO final flush (host loss) and a
    standby promoted from a replicated snapshot takes over at the
    second endpoint.  The job finishes with ZERO lost and ZERO
    double-processed task records and BITWISE-identical final params
    (SGD) vs the fault-free run."""
    import socket as socket_mod
    import numpy as np
    from paddle_tpu.distributed import (ElasticTrainJob, FaultInjector,
                                        Master, MasterServer,
                                        ResilientMasterClient,
                                        RetryPolicy, SnapshotReplica)

    dim, rpt, n_tasks = 8, 8, 6
    data = os.path.join(tmpdir, 'chaos.recordio')
    _elastic_toy_dataset(data, dim=dim, rpt=rpt, n_tasks=n_tasks)
    build = _elastic_toy_build(dim)
    batch_fn = _elastic_toy_batch
    params_of = _elastic_toy_params

    # fault-free reference (same seeds, no faults, no failover)
    m0 = Master(chunk_timeout_secs=120)
    m0.set_dataset([data], records_per_task=rpt)
    ref = ElasticTrainJob(build, m0, os.path.join(tmpdir, 'ref'),
                          batch_fn, worker_id='ref',
                          checkpoint_every=0)
    ref.run()
    ref_params = params_of(ref)
    ref.close()
    m0.close()

    # the chaos lane: primary on store A, standby endpoint reserved
    primary = Master(store_path=os.path.join(tmpdir, 'chaos_a'),
                     chunk_timeout_secs=60, worker_lease_secs=2.0)
    primary.set_dataset([data], records_per_task=rpt)
    server_fi = FaultInjector(seed=0)
    server_fi.script('server_send', 'task_finished', 'drop_response',
                     nth=1)
    server_fi.script('server_send', 'get_task', 'drop_response',
                     nth=2)
    server = MasterServer(primary, fault_injector=server_fi)
    sock = socket_mod.socket()
    sock.bind(('127.0.0.1', 0))
    standby_port = sock.getsockname()[1]
    sock.close()
    endpoints = [server.endpoint, '127.0.0.1:%d' % standby_port]
    replica = SnapshotReplica(server.endpoint,
                              os.path.join(tmpdir, 'chaos_b'))
    client_fi = FaultInjector(seed=1)
    # delayed heartbeats just under the 2s lease: late but live — the
    # membership set must not flap (no spurious resize/epoch churn)
    client_fi.script('client_send', 'heartbeat', 'delay', nth=1,
                     times=4, delay_s=0.5)
    cli = ResilientMasterClient(
        endpoints, timeout=0.75, fault_injector=client_fi,
        retry=RetryPolicy(max_attempts=10, base_backoff_s=0.05,
                          deadline_s=60.0, seed=0))

    promoted = {}
    trained = []

    def chaos_hook(tid, task, ordinal):
        trained.append((task['path'], task['start']))
        if ordinal == 3 and not promoted:
            # mirror the freshest queue state, then HOST LOSS: the
            # primary's server dies with a claim outstanding and no
            # final snapshot flush; the standby promotes from the
            # replica at the pre-agreed second endpoint
            replica.pull()
            server.close()
            sm = Master(store_path=os.path.join(tmpdir, 'chaos_b'),
                        chunk_timeout_secs=60, worker_lease_secs=2.0)
            promoted['master'] = sm
            promoted['server'] = MasterServer(sm, port=standby_port)

    job = ElasticTrainJob(build, cli, os.path.join(tmpdir, 'chaos_j'),
                          batch_fn, worker_id='chaos',
                          checkpoint_every=0, heartbeat_interval=0.2,
                          poll_interval=0.02, task_hook=chaos_hook)
    try:
        job.run()
        got = params_of(job)
        jm = job.metrics()
        cm = cli.metrics()
        standby = promoted['master']
        counts = standby.counts()
        # zero lost, zero double-processed, in original order
        assert counts == (0, 0, n_tasks, 0), counts
        assert len(trained) == n_tasks, trained
        assert len(set(trained)) == n_tasks, trained
        assert trained == sorted(trained), trained
        bitwise = all(np.array_equal(ref_params[n], got[n])
                      for n in ref_params)
        assert bitwise, \
            'chaos-run params diverged from the fault-free run'
        assert jm['tasks_deduped'] >= 1, jm
        assert cm['failovers'] >= 1, cm
        assert cm['retries'] >= 1, cm
        assert jm['resizes'] == 0, jm  # late heartbeats never flapped
        rec = {
            'chaos_bitwise_params': True,
            'chaos_lost': 0,
            'chaos_double_processed': 0,
            'chaos_tasks_trained': len(trained),
            'chaos_deduped_acks': jm['tasks_deduped'],
            'chaos_failovers': cm['failovers'],
            'chaos_retries': cm['retries'],
            'chaos_reconnects': cm['reconnects'],
            'chaos_injected_faults': server_fi.applied +
            client_fi.applied,
        }
    finally:
        job.close()
        cli.close()
        for k in ('server',):
            if k in promoted:
                promoted[k].close()
        if 'master' in promoted:
            promoted['master'].close()
        try:
            server.close()
        except Exception:
            pass
    return rec


def run_master_chaos():
    """The master_chaos record (ISSUE 15): interleaved bare/resilient
    ELASTIC windows (one full job pass each; ratios share a drift
    window) + the pure-RPC drain diagnostic pair + the functional
    chaos contract.  HARD asserts: ``retry_layer_overhead_ratio``
    (resilient job wall over bare job wall, best shared window, NO
    faults injected) <= PERF_GATE_CHAOS_OVERHEAD (default 1.05); the
    rpc drain tripwire <= PERF_GATE_CHAOS_RPC_MAX (default 1.6); the
    seeded chaos run's no-loss / no-duplicate / bitwise-params
    contract; and the replayed-task_failed dedup pin with its
    discarding counterfactual."""
    import shutil
    import tempfile
    bare_w, res_w, ctx = build_master_chaos()
    drain_bare_w, drain_res_w = ctx['drain_windows']
    bare, res, dbare, dres = [], [], [], []
    try:
        # warm both lanes once (first-job trace/compile weather would
        # otherwise land entirely on the bare side of block 1)
        bare_w()
        res_w()
        for _ in range(BLOCKS):
            # the GATED pair stays adjacent per block
            bare.append(bare_w())
            res.append(res_w())
            dbare.append(drain_bare_w())
            dres.append(drain_res_w())
    finally:
        ctx['cleanup']()
    rec = {
        'config': 'master_chaos',
        'bare_rows_per_sec': round(max(r for r, _ in bare), 1),
        'resilient_rows_per_sec': round(max(r for r, _ in res), 1),
        'bare_blocks': [round(r, 1) for r, _ in bare],
        'resilient_blocks': [round(r, 1) for r, _ in res],
        # the HARD gate: what the retry layer costs an elastic
        # training job when nothing is failing, best shared window
        'retry_layer_overhead_ratio': round(
            min(rw / bw for (_, rw), (_, bw) in zip(res, bare)), 4),
        # the per-RPC diagnostic pair: claim+finish drains with no
        # training — the dedup bookkeeping IS visible here on
        # loopback (no-op RPCs are ~20us), bounded loosely as a
        # catastrophic-regression tripwire (an accidental extra
        # round trip per call would read ~2x)
        'rpc_drain_overhead_ratio': round(
            min(rw / bw for (_, rw), (_, bw) in zip(dres, dbare)), 4),
        'rpc_bare_tasks_per_sec': round(max(r for r, _ in dbare), 1),
        'rpc_resilient_tasks_per_sec': round(
            max(r for r, _ in dres), 1),
        'tasks_per_window': ctx['n_tasks'],
        'rows_per_task': ctx['rpt'],
        'drain_tasks_per_window': ctx['drain_tasks'],
        'blocks': BLOCKS,
    }
    tmpdir = tempfile.mkdtemp(prefix='perf_gate_chaos_')
    try:
        rec.update(check_master_chaos(tmpdir))
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    rec.update(check_dedup_replay())
    floor = float(os.environ.get('PERF_GATE_CHAOS_OVERHEAD', '1.05'))
    assert rec['retry_layer_overhead_ratio'] <= floor, rec
    rpc_max = float(os.environ.get('PERF_GATE_CHAOS_RPC_MAX', '1.6'))
    assert rec['rpc_drain_overhead_ratio'] <= rpc_max, rec
    assert rec['chaos_bitwise_params'], rec
    assert rec['chaos_lost'] == 0, rec
    assert rec['chaos_double_processed'] == 0, rec
    assert rec['chaos_failovers'] >= 1, rec
    assert rec['replayed_task_failed_deduped'], rec
    print(json.dumps(rec), flush=True)
    return rec


def build_fleet():
    """Fleet-vs-single serving windows (ISSUE 17): one forward scorer
    + one stepwise decode model, each with ONE scope + ONE executor
    shared by the single-registry baseline and every fleet replica —
    identical weights (the bitwise asserts) and a shared compile cache
    (replica N never pays the fwd/decode compile again).  The paired
    stream is two phases: phase A (untimed) carries the seeded
    lost-response fault and pins every decode session; the victim
    replica — whichever holds session 0's SlotStateCache slots — is
    then killed with sessions mid-stream, and phase B is the TIMED
    post-kill window: the survivor serves the whole stream (failover,
    re-prefill, re-pin included) against the fault-free single
    registry serving the identical phase-B requests.  Every output is
    compared 1:1 against the single-registry reference — exactly-once
    delivery IS the bitwise ledger, and the dropped response's retry
    must land as a dedup REPLAY, not a second execution."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.distributed import FaultInjector, RetryPolicy

    n_req = int(os.environ.get('PERF_GATE_FLEET_REQS', '32'))
    n_sessions = int(os.environ.get('PERF_GATE_FLEET_SESSIONS', '3'))
    # the client socket timeout IS the price of the scripted
    # drop_response (one recv stall in the untimed phase A); it must
    # still clear the survivor's worst per-RPC wall in phase B
    cli_timeout = float(os.environ.get('PERF_GATE_FLEET_TIMEOUT',
                                       '5.0'))
    dim, classes, rows, seq = 16, 64, 4, 12
    max_len = 6

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 0
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)
        pred = fluid.layers.fc(pooled, classes, act='softmax')
    test_prog = prog.clone(for_test=True)
    place = fluid.default_place()
    fwd_scope = fluid.core.Scope()
    fwd_exe = fluid.Executor(place)
    with fluid.scope_guard(fwd_scope):
        fwd_exe.run(startup)

    from paddle_tpu.models import seq2seq
    with fluid.unique_name.guard():
        gm = seq2seq.build_step_decode(
            src_dict_dim=24, trg_dict_dim=20, embedding_dim=6,
            encoder_size=10, decoder_size=10, max_len=8)
    gm['prefill'].random_seed = 3
    gen_exe = fluid.Executor(place)
    gen_scope = fluid.core.Scope()
    with fluid.scope_guard(gen_scope):
        gen_exe.run(gm['prefill_startup'])
        gen_exe.run(gm['step_startup'])
    gspec = serving.GenerationSpec.from_model(gm)
    src_feed = gm['prefill_feeds'][0]

    def make_registry():
        reg = serving.ModelRegistry()
        reg.load('fwd', program=test_prog, feed_names=['x'],
                 fetch_list=[pred], scope=fwd_scope, executor=fwd_exe)
        reg.load('nmt', program=gm['prefill'],
                 feed_names=gm['prefill_feeds'],
                 fetch_list=gm['prefill_fetches'], scope=gen_scope,
                 executor=gen_exe, generation=gspec,
                 config=serving.ServingConfig(decode_slots=4,
                                              decode_steps=3))
        reg.start()
        return reg

    # the whole offered stream is pre-built and seeded: both lanes
    # (and every block) replay the identical requests
    rng = np.random.RandomState(17)
    sessions = ['s%d' % i for i in range(n_sessions)]

    def _prompt(l):
        return fluid.create_lod_tensor(
            rng.randint(2, 24, size=(l, 1)).tolist(), [[l]])

    feeds, prompts = {}, {}
    for k, ph in enumerate(('a', 'b')):
        feeds[ph] = [rng.standard_normal(
            (rows, seq, dim)).astype('float32') for _ in range(n_req)]
        prompts[ph] = [_prompt(3 + (i + k) % 3)
                       for i in range(n_sessions)]

    def drive(target, phase, with_sessions=False):
        """Submit the phase's whole stream, then gather in submission
        order.  Returns (outputs, lost, wall_s)."""
        t0 = time.time()
        # router lane: cli_timeout stays the per-recv stall bound, but
        # the SERVER-side budget is wide — a contended window then
        # costs stall+retry (the dedup window replays), never a loss
        skw = {'timeout': 60} if with_sessions else {}
        futs = [('fwd', target.submit('fwd', {'x': f}, **skw))
                for f in feeds[phase]]
        for i, s in enumerate(sessions):
            kw = dict(skw, session=s) if with_sessions else {}
            futs.append(('gen', target.submit_generate(
                'nmt', {src_feed: prompts[phase][i]},
                max_len=max_len, **kw)))
        out, lost = [], 0
        for kind, fut in futs:
            try:
                r = fut.result(120)
            except Exception:
                lost += 1
                out.append(None)
                continue
            out.append(np.asarray(r[0] if kind == 'fwd' else r))
        return out, lost, time.time() - t0

    # snappy retries: a dead replica must cost milliseconds of
    # connect-refused probing, not the default backoff ladder — the
    # timed post-kill window measures the fleet, not the retry timer
    retry = RetryPolicy(max_attempts=4, base_backoff_s=0.02,
                        max_backoff_s=0.2, deadline_s=60.0, seed=0)

    # the bitwise REFERENCE is the fault-free single registry driven
    # in-process (the ISSUE 17 oracle: no router, no faults)
    base_reg = make_registry()
    ref = {}
    for ph in ('a', 'b'):
        ref[ph], lost, _ = drive(base_reg, ph)
        assert lost == 0, 'fault-free reference lost %d' % lost

    # the TIMED baseline serves the same registry through a 1-replica
    # fleet tier, so the goodput ratio isolates what the KILL costs
    # (failover probing, re-prefill, survivor ownership) — not the
    # wire codec both lanes pay equally
    base_srv = serving.ReplicaServer(base_reg)
    base_router = serving.FleetRouter([base_srv], retry=retry,
                                      timeout=cli_timeout)
    drive(base_router, 'b', with_sessions=True)  # warm the lane

    def single_window():
        """The single-replica baseline, re-timed per block so each
        ratio shares a drift window with its fleet pair."""
        out, lost, wall = drive(base_router, 'b', with_sessions=True)
        assert lost == 0, lost
        return (n_req + n_sessions) / wall, out

    def fleet_window():
        """One full chaos pass: 2 replicas, the seeded drop fault in
        phase A, the pinned-victim kill between rounds (sessions hold
        live decode slots), the TIMED post-kill phase B."""
        fi = FaultInjector(seed=7)
        fi.script('server_send', 'infer', 'drop_response', nth=1,
                  times=1)
        regs = [make_registry() for _ in range(2)]
        servers = [serving.ReplicaServer(regs[0], fault_injector=fi),
                   serving.ReplicaServer(regs[1])]
        router = serving.FleetRouter(servers, retry=retry,
                                     timeout=cli_timeout)
        try:
            got_a, lost_a, _ = drive(router, 'a', with_sessions=True)
            log1 = router.session_dispatches()
            aff1 = max(len(set(log1[s])) for s in sessions)
            victim = log1[sessions[0]][0]
            servers[victim].close()
            got_b, lost_b, wall = drive(router, 'b',
                                        with_sessions=True)
            log2 = router.session_dispatches()
            rm = router.metrics()
            stats = {
                'lost': lost_a + lost_b,
                'bitwise': all(
                    g is not None and np.array_equal(g, w)
                    for g, w in zip(got_a + got_b,
                                    ref['a'] + ref['b'])),
                'injected': fi.applied,
                'replays': sum(s._dedup.replays for s in servers),
                'failovers': rm['failovers'],
                'deaths': rm['replica_deaths'],
                're_prefills': rm['re_prefills'],
                'affinity_pre_kill_max_distinct': aff1,
                'affinity_max_distinct': max(
                    len(set(log2[s])) for s in sessions),
                'post_kill_on_survivor': all(
                    log2[s][-1] == 1 - victim for s in sessions),
            }
            return (n_req + n_sessions) / wall, stats
        finally:
            router.close()
            for srv in servers:
                srv.close()
            for reg in regs:
                reg.stop()

    def cleanup():
        base_router.close()
        base_srv.close()
        base_reg.stop()

    ctx = {'n_req': n_req, 'n_sessions': n_sessions,
           'cleanup': cleanup}
    return single_window, fleet_window, ctx


def run_fleet():
    """The fleet record (ISSUE 17): interleaved single-registry /
    fleet-under-kill windows over the identical seeded stream.  HARD
    gates: ``fleet_lost`` == 0 and ``fleet_duplicated`` == 0 in EVERY
    window (every request finishes exactly once — the dropped
    response's retry must surface as a dedup replay, never a second
    result); ``fleet_bitwise_outputs`` (every fleet output, across the
    fault AND the kill, bitwise-equal to the fault-free
    single-registry reference); affinity STRUCTURAL (one replica per
    session fault-free, at most two across the kill, post-kill all on
    the survivor); and ``post_kill_goodput_ratio`` — the survivor's
    timed phase-B goodput over the single registry's, best shared
    window — >= PERF_GATE_FLEET_GOODPUT (default 0.25: the timed
    window DELIBERATELY contains the failover transition — every
    victim-bound dispatch pays the connect-refused probe ladder until
    the first failure marks the replica dead — so the gate bounds the
    worst post-kill window, not the settled survivor steady state;
    with real per-request service walls the fixed probing tax
    shrinks against the stream and the ratio climbs toward 1)."""
    single_w, fleet_w, ctx = build_fleet()
    singles, fleets = [], []
    try:
        for _ in range(BLOCKS):
            singles.append(single_w())
            fleets.append(fleet_w())
    finally:
        ctx['cleanup']()
    ratios = [fg / sg for (fg, _), (sg, _) in zip(fleets, singles)]
    worst = {k: max(st[k] for _, st in fleets)
             for k in ('lost', 'affinity_pre_kill_max_distinct',
                       'affinity_max_distinct')}
    every = {k: min(st[k] for _, st in fleets)
             for k in ('injected', 'replays', 'failovers', 'deaths',
                       're_prefills')}
    rec = {
        'config': 'fleet',
        'post_kill_goodput_req_s': round(max(g for g, _ in fleets), 1),
        'single_goodput_req_s': round(max(g for g, _ in singles), 1),
        'fleet_goodput_blocks': [round(g, 1) for g, _ in fleets],
        'single_goodput_blocks': [round(g, 1) for g, _ in singles],
        # the HARD goodput gate: what one replica's death costs the
        # offered stream once the survivor owns it, best shared window
        'post_kill_goodput_ratio': round(max(ratios), 4),
        'fleet_lost': worst['lost'],
        # >1 result for a logical request is structurally impossible
        # (futures finish once); the substantive exactly-once check is
        # the bitwise 1:1 ledger + the replayed (not re-executed) retry
        'fleet_duplicated': 0 if all(st['bitwise']
                                     for _, st in fleets) else -1,
        'fleet_bitwise_outputs': all(st['bitwise'] for _, st in fleets),
        'fleet_injected_faults': every['injected'],
        'fleet_dedup_replays': every['replays'],
        'fleet_failovers': every['failovers'],
        'fleet_replica_deaths': every['deaths'],
        'fleet_re_prefills': every['re_prefills'],
        'fleet_affinity_pre_kill_max_distinct':
            worst['affinity_pre_kill_max_distinct'],
        'fleet_affinity_max_distinct': worst['affinity_max_distinct'],
        'fleet_post_kill_on_survivor': all(
            st['post_kill_on_survivor'] for _, st in fleets),
        'requests_per_phase': ctx['n_req'],
        'sessions': ctx['n_sessions'],
        'blocks': BLOCKS,
    }
    floor = float(os.environ.get('PERF_GATE_FLEET_GOODPUT', '0.25'))
    assert rec['post_kill_goodput_ratio'] >= floor, rec
    assert rec['fleet_lost'] == 0, rec
    assert rec['fleet_duplicated'] == 0, rec
    assert rec['fleet_bitwise_outputs'], rec
    assert rec['fleet_injected_faults'] >= 1, rec
    assert rec['fleet_dedup_replays'] >= 1, rec
    assert rec['fleet_failovers'] >= 1, rec
    assert rec['fleet_replica_deaths'] == 1, rec
    assert rec['fleet_re_prefills'] >= 1, rec
    # affinity structural: one replica per session fault-free, at most
    # two across the kill, and post-kill everything on the survivor
    assert rec['fleet_affinity_pre_kill_max_distinct'] == 1, rec
    assert rec['fleet_affinity_max_distinct'] <= 2, rec
    assert rec['fleet_post_kill_on_survivor'], rec
    print(json.dumps(rec), flush=True)
    return rec


def check_profile_shed():
    """ISSUE 9's sharpened shed contract, checked DETERMINISTICALLY
    (no model, no timing): a MicroBatcher fed the per-signature
    ServiceTimeProfile horizon sheds the slow-signature request whose
    3x-estimate cannot meet its deadline — while the SAME queue under
    the old global min-wall horizon (dragged down by the fast
    signature's wall) admits it toward certain deadline death.  The
    fast-signature request is kept by both.  Returns the record block
    run_slo folds in."""
    from paddle_tpu.serving import (DeadlineExceededError,
                                    InferenceRequest, MicroBatcher,
                                    ServiceTimeProfile)
    prof = ServiceTimeProfile()
    for _ in range(3):
        prof.observe('fast', 0.001)   # 1ms signature
        prof.observe('slow', 0.200)   # 200ms signature

    def est(req):
        e = prof.estimate(req.sig)
        return 3.0 * (e if e is not None else (prof.floor() or 0.0))

    def drive(batcher):
        fast = InferenceRequest({'x': 0}, 1, 'fast', deadline_ms=50.0)
        slow = InferenceRequest({'x': 0}, 1, 'slow', deadline_ms=50.0)
        batcher.submit(fast)
        batcher.submit(slow)
        lots = []
        while True:
            lot = batcher.next_lot(timeout=0, force=True)
            if not lot:
                break
            lots.extend(lot)
        return fast, slow, lots

    fast, slow, lots = drive(MicroBatcher(
        max_batch_size=4, max_wait_s=0.001, service_estimate_for=est))
    assert fast in lots and not fast.done(), \
        'per-signature horizon shed the FAST request'
    assert slow.done() and slow not in lots, \
        'per-signature horizon admitted the doomed slow-signature ' \
        'request'
    try:
        slow.result(0)
        raise AssertionError('slow request resolved without error')
    except DeadlineExceededError:
        pass
    # the counterfactual: the old GLOBAL horizon is the min wall over
    # ALL signatures (the fast one's 1ms) — it admits the slow request
    gfast, gslow, glots = drive(MicroBatcher(
        max_batch_size=4, max_wait_s=0.001,
        service_estimate_fn=lambda: 3.0 * 0.001))
    assert gfast in glots and gslow in glots, \
        'global horizon unexpectedly shed: %r' % ([gfast, gslow], )
    return {'profile_shed_slow': True, 'profile_kept_fast': True,
            'global_horizon_admitted_slow': True}


def build_slo():
    """Deadline-scheduled vs FIFO serving under the SAME overloaded
    open-loop Poisson stream (ISSUE 8): one padding-neutral dense seq
    scorer + ONE scope served through TWO engines — the EDF side
    schedules lots earliest-deadline-first and SHEDS past-deadline work
    (typed DeadlineExceededError, 'shed' trace stage), the FIFO side is
    yesterday's engine: strict arrival order, every request served even
    when its answer is already worthless.  Both sides are driven by
    serving.OpenLoopLoadGen with the SAME seed (identical arrivals,
    class picks and payloads), at a rate calibrated to
    PERF_GATE_SLO_OVERLOAD x the measured closed-burst capacity, with
    deadlines a few dispatch-walls wide — so the FIFO queue grows
    without bound and serves ever-deader requests while the EDF queue
    sheds them and keeps answering live ones in time.  The deliverable
    is the GOODPUT ratio (responses inside deadline, EDF over FIFO);
    within-deadline responses are asserted bitwise-identical across
    the two engines first.  Functional on the CPU smoke and TPU
    alike."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving

    rows = int(os.environ.get('PERF_GATE_SLO_ROWS', '4'))
    n_req = int(os.environ.get('PERF_GATE_SLO_REQS', '96'))
    # 4x: the closed calibration burst UNDERESTIMATES sustained
    # capacity (a short burst never reaches steady-state pipelining),
    # so the multiplier must overshoot or the 'overloaded' stream
    # barely loads the engine and the pair measures nothing
    overload = float(os.environ.get('PERF_GATE_SLO_OVERLOAD', '4.0'))
    # deadline width in dispatch walls: > the 2x-min-wall shed horizon
    # (or EDF sheds everything), << the offered window (or FIFO meets
    # most deadlines and the pair measures nothing)
    dl_walls = float(os.environ.get('PERF_GATE_SLO_DEADLINE_WALLS',
                                    '4.0'))
    dim, classes = 16, 64
    seq = 12
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 0
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)
        pred = fluid.layers.fc(pooled, classes, act='softmax')
    test_prog = prog.clone(for_test=True)
    place = fluid.default_place()
    scope = fluid.core.Scope()
    exe0 = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe0.run(startup)

    def make_engine(scheduling):
        # ONE batch bucket, one lot per scan, fixed request shape: each
        # side compiles exactly one executable, so the paired windows
        # measure scheduling policy, not compile weather
        return serving.InferenceEngine(
            test_prog, feed_names=['x'], fetch_list=[pred],
            scope=scope, executor=fluid.Executor(place), place=place,
            config=serving.ServingConfig(
                max_batch_size=rows * 4, max_wait_ms=2,
                bucket_sizes=[rows * 4], steps_per_dispatch=1,
                scheduling=scheduling),
            name='slo-%s' % scheduling)

    edf_eng = make_engine('edf').start()
    fifo_eng = make_engine('fifo').start()

    def feed_fn(rng):
        return {'x': rng.standard_normal(
            (rows, seq, dim)).astype('float32')}

    warm_rng = np.random.RandomState(99)
    for eng in (edf_eng, fifo_eng):
        # warm the executable AND the engine's service-wall window (the
        # shed horizon's estimator) with a drained burst
        eng.infer(feed_fn(warm_rng), timeout=600)
        futs = [eng.submit(feed_fn(warm_rng)) for _ in range(8)]
        for f in futs:
            f.result(600)
    # calibrate in TWO steps.  (1) closed warm burst -> per-dispatch
    # wall (48 requests = 12 full lots: long enough that thread wakeup
    # noise stops dominating).  (2) an OPEN-loop probe at the burst
    # rate -> sustained capacity INCLUDING the submitter thread's own
    # cost — on a CPU-constrained host the submit path (prepare + lock
    # + trace) contends with the worker, so the closed burst alone
    # overestimates what an open-loop stream can actually be served
    # at, and an 'overload' derived from it is several times deeper
    # than intended (both goodputs then collapse into timing noise).
    t0 = time.time()
    futs = [edf_eng.submit(feed_fn(warm_rng)) for _ in range(48)]
    for f in futs:
        f.result(600)
    burst_s = max(time.time() - t0, 1e-6)
    wall_s = burst_s / 12.0  # 4 requests per full lot at capacity
    probe = serving.OpenLoopLoadGen(
        edf_eng, [serving.TrafficClass(feed_fn, name='probe')],
        rate=48.0 / burst_s, n_requests=96, seed=7).run()
    capacity = min(48.0 / burst_s, probe['sustained_req_s'])
    rate = overload * capacity
    # deadline a few dispatch walls wide, floored high enough that
    # scheduler/timer jitter (single-digit ms) stays small against it
    deadline_ms = max(dl_walls * wall_s * 1e3, 40.0)
    # keep the offered window >> the deadline (or FIFO meets most
    # deadlines by default), but bounded — a huge stream just deepens
    # the queues until submitter overhead IS the bottleneck
    n_req = max(n_req, min(int(6.0 * (deadline_ms / 1e3) * rate), 800))

    def window(eng, seed=0):
        gen = serving.OpenLoopLoadGen(
            eng,
            [serving.TrafficClass(feed_fn, deadline_ms=deadline_ms,
                                  name='slo')],
            rate=rate, n_requests=n_req, seed=seed, keep_records=True)
        return gen.run()

    return (lambda seed=0: window(edf_eng, seed)), \
        (lambda seed=0: window(fifo_eng, seed)), \
        (edf_eng, fifo_eng, rate, deadline_ms, n_req)


def run_slo():
    """The slo record: interleaved EDF/FIFO windows over the identical
    seeded stream (each ratio shares a drift window — the gates'
    pairing rule).  HARD asserts (the ISSUE 8 acceptance): every
    within-deadline EDF response bitwise-equal to the FIFO engine's for
    the same request; shed requests carry DeadlineExceededError and a
    'shed' trace stage; goodput_ratio >= PERF_GATE_SLO_GOODPUT_MIN
    (default 1.3)."""
    edf, fifo, (edf_eng, fifo_eng, rate, deadline_ms, n_req) = \
        build_slo()
    try:
        rec = _run_slo_blocks(edf, fifo, rate, deadline_ms, n_req)
    finally:
        # an assert inside the block loop must not leak two serving
        # workers into the NEXT config's paired windows ('all' mode)
        edf_eng.stop()
        fifo_eng.stop()
    floor = float(os.environ.get('PERF_GATE_SLO_GOODPUT_MIN', '1.3'))
    assert rec['edf_goodput'] > 0, rec
    assert rec['edf_shed'] > 0 and rec['shed_checked'] > 0, rec
    assert rec['bitwise_checked'] > 0, rec
    assert rec['goodput_ratio'] >= floor, rec
    # the ISSUE 9 sharpened shed contract: per-signature horizon sheds
    # what the global one would have admitted (deterministic check)
    rec.update(check_profile_shed())
    assert rec['profile_shed_slow'] and \
        rec['global_horizon_admitted_slow'], rec
    print(json.dumps(rec), flush=True)
    return rec


def _run_slo_blocks(edf, fifo, rate, deadline_ms, n_req):
    """The measurement loop run_slo wraps in its engine-stopping
    try/finally: interleaved windows, per-block bitwise + shed-contract
    checks, and the best-shared-window record."""
    import numpy as np
    from paddle_tpu.serving import DeadlineExceededError
    ratios, blocks_e, blocks_f = [], [], []
    shed_checked = bitwise_checked = 0
    for b in range(BLOCKS):
        rep_f = fifo()
        rep_e = edf()
        # the bitwise bar: a request the EDF engine answered in time
        # must carry the SAME bytes the FIFO engine produced for it
        # (deadline scheduling may only change WHEN/WHETHER, never WHAT)
        frecs = {r['i']: r for r in rep_f['records']}
        for r in rep_e['records']:
            if r['status'] in ('good', 'late'):
                fr = frecs[r['i']]
                assert fr['status'] in ('good', 'late'), (r, fr)
                for a, bv in zip(r['result'], fr['result']):
                    assert np.array_equal(np.asarray(a),
                                          np.asarray(bv)), \
                        'EDF result diverged from FIFO for request ' \
                        '%d' % r['i']
                    bitwise_checked += 1
            elif r['status'] == 'shed':
                # typed + staged: the shed contract
                assert isinstance(r['error'], DeadlineExceededError), \
                    r['error']
                bd = r.get('breakdown')
                assert bd and 'shed' in bd['stages_ms'], bd
                shed_checked += 1
        ratios.append(rep_e['goodput'] / max(rep_f['goodput'], 1.0))
        blocks_e.append(rep_e)
        blocks_f.append(rep_f)
    best = max(range(BLOCKS), key=lambda i: ratios[i])
    be, bf = blocks_e[best], blocks_f[best]
    rec = {
        'config': 'slo',
        'offered_req_s': round(rate, 1),
        'deadline_ms': round(deadline_ms, 2),
        'requests_per_window': n_req,
        'edf_goodput': be['goodput'],
        'fifo_goodput': bf['goodput'],
        'edf_goodput_blocks': [r['goodput'] for r in blocks_e],
        'fifo_goodput_blocks': [r['goodput'] for r in blocks_f],
        # the PAIRED deliverable: within-deadline responses kept under
        # identical overload, deadline scheduler over FIFO, per shared
        # drift window
        'goodput_ratio': round(max(ratios), 4),
        'edf_goodput_req_s': be['goodput_req_s'],
        'fifo_goodput_req_s': bf['goodput_req_s'],
        'edf_shed': be['shed'], 'fifo_shed': bf['shed'],
        'edf_late': be['late'], 'fifo_late': bf['late'],
        'edf_p50_ms': be['p50_ms'], 'fifo_p50_ms': bf['p50_ms'],
        'edf_p99_ms': be['p99_ms'], 'fifo_p99_ms': bf['p99_ms'],
        'edf_p999_ms': be['p999_ms'], 'fifo_p999_ms': bf['p999_ms'],
        'bitwise_checked': bitwise_checked,
        'shed_checked': shed_checked,
        'blocks': BLOCKS,
    }
    return rec


CONFIGS = {
    'resnet': (build_resnet, 'imgs_per_sec'),
    'transformer': (build_transformer, 'tokens_per_sec'),
    'nmt': (build_nmt, 'tokens_per_sec'),
    'resnet_infer': (build_resnet_infer, 'imgs_per_sec'),
    'feed_pipeline': (build_feed_pipeline, 'imgs_per_sec'),
    'multi_model': (build_multi_model, 'imgs_per_sec'),
    'trailing_dim': (build_trailing_dim, 'rows_per_sec'),
    'trace_overhead': (build_trace_overhead, 'rows_per_sec'),
    'decode': (build_decode, 'tokens_per_sec'),
    'decode_overlap': (build_decode_overlap, 'tokens_per_sec'),
    'chunked_prefill': (build_chunked_prefill, 'tokens_per_sec'),
    'slo': (build_slo, 'goodput_req_s'),
    'sparse_grad': (build_sparse_grad, 'rows_per_sec'),
    'embed_cache': (build_embed_cache, 'rows_per_sec'),
    'pserver': (build_pserver, 'rows_per_sec'),
    'elastic': (build_elastic, 'rows_per_sec'),
    'master_chaos': (build_master_chaos, 'rows_per_sec'),
    'fleet': (build_fleet, 'goodput_req_s'),
}


def run_config(name):
    if name == 'feed_pipeline':
        return run_feed_pipeline()
    if name == 'multi_model':
        return run_multi_model()
    if name == 'trailing_dim':
        return run_trailing_dim()
    if name == 'trace_overhead':
        return run_trace_overhead()
    if name == 'decode':
        return run_decode()
    if name == 'decode_overlap':
        return run_decode_overlap()
    if name == 'chunked_prefill':
        return run_chunked_prefill()
    if name == 'slo':
        return run_slo()
    if name == 'sparse_grad':
        return run_sparse_grad()
    if name == 'embed_cache':
        return run_embed_cache()
    if name == 'pserver':
        return run_pserver()
    if name == 'elastic':
        return run_elastic()
    if name == 'master_chaos':
        return run_master_chaos()
    if name == 'fleet':
        return run_fleet()
    build, unit = CONFIGS[name]
    # both sides compiled first, then INTERLEAVED blocks: a drift window
    # between two monolithic measurements would otherwise decide the
    # hard gate, not the build under test
    fw_block, fw_multi_block, bd_block = build()
    fw, fw_multi, bd = [], [], []
    for _ in range(BLOCKS):
        # the GATED pair (fw, bd) stays adjacent — the fw_multi run
        # must not widen the drift window the hard gate relies on
        fw.append(fw_block())
        if bd_block is not None:
            bd.append(bd_block())
        fw_multi.append(fw_multi_block())
    rec = {
        'config': name,
        'framework_' + unit: round(max(fw), 1),
        'framework_multi_' + unit: round(max(fw_multi), 1),
        'framework_blocks': [round(v, 1) for v in fw],
        'framework_multi_blocks': [round(v, 1) for v in fw_multi],
        # the PAIRED multi_vs_dispatch block: run_multi (or the eval
        # scan) vs the per-dispatch loop, per block — the measured
        # dispatch tax the multi-step path removes, with no
        # cross-window flattery (same pairing rule as the hard gate)
        'multi_vs_dispatch': round(
            max(m / f for m, f in zip(fw_multi, fw)), 4),
        'steps': STEPS, 'blocks': BLOCKS,
    }
    if bd_block is not None:
        ratios = [f / b for f, b in zip(fw, bd)]
        rec.update({
            'bound_' + unit: round(max(bd), 1),
            'bound_blocks': [round(v, 1) for v in bd],
            'ratios': [round(r, 4) for r in ratios],
            # gate statistic: best per-block ratio — each block pair
            # shares a drift window (ADVICE r4 #3).  The per-dispatch
            # side stays the gate (symmetric with the bound's python
            # step loop).
            'ratio': round(max(ratios), 4),
        })
    print(json.dumps(rec), flush=True)
    return rec


def main():
    import jax
    backend = jax.default_backend()
    if backend != 'tpu':
        # a gate that cannot measure must not read as a pass
        sys.exit('perf_gate: needs a TPU, JAX found backend %r — the '
                 'CPU-sized smokes are the run_* functions '
                 'tests/test_perf_gate.py drives' % backend)
    which = sys.argv[1] if len(sys.argv) > 1 else 'resnet'
    names = list(CONFIGS) if which == 'all' else [which]
    for name in names:
        run_config(name)


if __name__ == '__main__':
    main()
