"""Driver benchmark.

ONE PROCESS FOR EACH CHIP: a chip belongs to one process at a time, and
a parent that has touched JAX holds it — a child that needs it then
fails or hangs.  So the parent process imports NO jax (and nothing of
paddle_tpu, whose import may touch it) and runs the configs as children
ONE AT A TIME, each in its own subprocess under a hard wall-clock
budget; each child is the only process on the chip while it runs.  The
parent emits a full contract-shaped JSON line after EVERY config
completes, so a hung child costs only its own budget: every
already-finished number is already on stdout and in BENCH_PARTIAL.json
(written to the current directory).  The LAST JSON line on stdout is
always the most complete record.  The parent exits non-zero when ANY
config failed or timed out.

A child finds its device itself and FAILS when there is no accelerator
— it never falls back to the CPU.  BENCH_FORCE_CPU=1 is the one way to
run the toy-sized CPU smoke (tests/test_bench_contract.py); such
records say platform "cpu" and carry no device metric (mfu is null).
Every record names ``platform``, ``device_kind`` and ``device_count``
as JAX reports them.

Top-level keys keep the driver contract: metric/value/unit/vs_baseline
are the ResNet-50 headline when it finished, else the first config that
did ("headline from whatever finished", VERDICT r3 next-#1 — a resnet
timeout must not zero the run; its TIMEOUT record stays in `configs`
and `vs_baseline` goes null since only resnet has a published
baseline).  `configs`
carries one fully-schema'd record per benchmark config — value, unit,
mfu, vs_baseline (null where the reference published no number), ms per
step — so nothing rides piggyback on the headline record
(VERDICT r2 next-#10).

Configs (reference benchmark/fluid suite + the contrib/float16 flow).
ALL configs are device-true with uniform device_true/steps_per_dispatch
fields: TRAIN configs via Executor.run_multi (K steps per device
dispatch, in-jit fori_loop), the inference config via
Executor.run_eval_multi (K eval steps per dispatch, in-jit lax.scan
collecting every step's predictions — the serving engine's executable,
closing the ROADMAP dispatch-tax ledger):
  resnet             ResNet-50 ImageNet train, bs512 224^2 (models/resnet.py)
  nmt                WMT14 seq2seq+attention 512/512/512 dict30k, bs512 seq32
  transformer        transformer-base 6L d512 ff2048 h8, bs128 seq256
  stacked_lstm       IMDB stacked dynamic LSTM (3x128), bs128 seq64
  resnet_infer_bf16  ResNet-50 INFERENCE bs256, Float16Transpiler'd to
                     bf16, with a same-process f32 speedup ratio
  ctr                wide&deep CTR train+serve (ISSUE 11): zipfian id
                     traffic into a MESH-ROW-SHARDED sparse embedding
                     table ({dp, mp} mesh — the 8-dev virtual mesh on
                     the CPU smoke), SparseRows gradients end to end
                     (no dense [V, D] grad on device), a served
                     inference block through the ModelRegistry, and
                     the per-device embed-table arbiter account with
                     its sharded-vs-unsharded admission counterfactual

Baseline: the reference's best published ResNet-50 training number,
84.08 imgs/sec (2x Xeon 6148 MKL-DNN, BASELINE.md — the K40m GPU tables
predate ResNet-50); no in-tree baseline exists for the sequence configs.

MFU: XLA-cost-analysis-derived (ISSUE 6) — every child runs under
FLAGS_cost_accounting, so the timed executable's own FLOPs
(Executor.cost_report(), the `cost` block per config) divide by the
chip's bf16 peak from DEVICE_PEAKS (keyed by device_kind; a kind that
is not in the table is an error); the hand-derived analytic counts
(documented per config below) stay as `mfu_analytic` cross-checks and
as the fallback when capture is off (BENCH_COST_ACCOUNTING=0).  Every
timed block is ONE multi-step dispatch ending in a host fetch, so the
per-dispatch host cost (not yet measured on the v5e) is paid once per
K steps.

Every TRAIN config also reports a ``feed_overlap`` block (ISSUE 3):
fresh batches every step staged through fluid.FeedPipeline, so host
batch prep + H2D transfer of scan block N+1 overlaps device compute of
dispatch N — feed_stall ~ 0 after warmup means the device-true numbers
hold with REAL per-step input, not just a pre-staged constant batch.
Children share a persistent XLA compilation cache
(fluid.flags.enable_compile_cache: the directory
JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache) so re-runs
warm-start their compiles from disk.

The nmt and transformer configs also report a ``decode`` block
(ISSUE 7): mixed-length prompts served through the engine's
continuous-batching generation lane (prefill lots + K-step in-jit
decode scans over the slot cache — GRU hidden state for NMT, a real
[S, max_ctx, d_k] KV cache for the transformer), CPU-smoked so the
lane really fires; the numbers are tokens/s, steps-per-dispatch, slot
occupancy, and (ISSUE 9) host-syncs-per-token — the device-idling
round trips the chained decode lane (decode_pipeline_depth >= 2)
avoids vs one-per-scan on the synced baseline.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# Peak rates of one chip, keyed by jax's ``device_kind`` — the ONE table
# (tools/jax_*_bound.py import it).  A kind that is not here is an
# error, never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s.  The kind string is what jax reports on the chip
    # the chip tool hands out (confirmed there, PR 21).
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
}
BASELINE_RESNET_IMGS_PER_SEC = 84.08


def device_peak(device_kind, rate='bf16_flops'):
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            'no peak rates for device_kind %r — add it to '
            'bench.DEVICE_PEAKS with its source (known: %s)'
            % (device_kind, sorted(DEVICE_PEAKS)))
    return DEVICE_PEAKS[device_kind][rate]


def peak_flops():
    """bf16 peak of the chip this child runs on."""
    import jax
    return device_peak(jax.devices()[0].device_kind)


# Per-config wall-clock budgets (seconds).  ResNet gets extra headroom
# for the bs512 224^2 compile, transformer for its 6-layer bs128
# seq256 compile, the inference config for its two (f32 + bf16)
# compiles; nmt and transformer also pay their trailing_bucket serving
# compiles (ISSUE 5, small-batch eval rungs) and their decode-lane
# compiles (ISSUE 7: prefill rungs + the decode-scan executable).
BUDGETS = {'resnet': 280, 'nmt': 270, 'transformer': 380,
           'stacked_lstm': 220, 'resnet_infer_bf16': 340, 'ctr': 300}
if os.environ.get('BENCH_BUDGET'):  # uniform override, mainly for tests
    BUDGETS = {k: int(os.environ['BENCH_BUDGET']) for k in BUDGETS}
PARTIAL_PATH = os.path.join(os.getcwd(), 'BENCH_PARTIAL.json')


def _timed_steps_multi(exe, prog, feed, loss_var, steps, blocks=3):
    """Device-true timing, best-of-`blocks`: each block is ONE
    Executor.run_multi dispatch of `steps` iterations (in-jit
    fori_loop), so the per-dispatch host cost is paid once per block,
    not once per step.  The mean is reported alongside the best.  The
    warmup runs with the SAME `steps` — a static jit argument, so a
    different-steps warmup would leave the timed executable
    uncompiled."""
    loss_v, = exe.run_multi(prog, feed=feed, fetch_list=[loss_var],
                            steps=steps)
    per_block = []
    for _ in range(blocks):
        t0 = time.time()
        loss_v, = exe.run_multi(prog, feed=feed, fetch_list=[loss_var],
                                steps=steps)
        per_block.append(time.time() - t0)
    return (min(per_block), sum(per_block) / len(per_block),
            float(np.asarray(loss_v).flatten()[0]))


def _cost_block(exe, steps_per_sec, on_tpu, kind='multi'):
    """ISSUE 6: XLA-cost-analysis-derived MFU.  Under
    FLAGS_cost_accounting (enabled for every bench child) the executor
    captured the timed executable's own cost/memory analysis
    (Executor.cost_report()); the dominant `kind` entry IS the timed
    K-step scan, so per-step FLOPs x measured steps/sec over the chip's
    peak is achieved MFU with XLA's numerator instead of the
    hand-derived analytic count (which stays as mfu_analytic for
    cross-checking).  None when capture is off or the backend exposes
    no analysis — the config's mfu then falls back to analytic."""
    entries = [e for e in exe.cost_report()
               if e.get('kind') == kind and e.get('flops')]
    if not entries:
        return None
    e = max(entries, key=lambda r: r['flops'])
    return {
        'source': 'xla_cost_analysis',
        'flops_per_step': e['flops_per_step'],
        'bytes_accessed_per_step': round(
            e['bytes_accessed'] / max(e['steps'], 1), 1),
        'mfu': (round(e['flops_per_step'] * steps_per_sec / peak_flops(),
                      4) if on_tpu else None),
    }


def _feed_overlap_block(exe, prog, loss_var, batch_fn, steps,
                        pipeline_depth=2, dispatches=2):
    """The ISSUE 3 paired measurement: FRESH batches every step, staged
    through fluid.FeedPipeline so host batch prep + H2D transfer of scan
    block N+1 overlaps device compute of dispatch N.  Times the post-
    warmup dispatches and reports the pipeline's own stall/overlap
    counters — the device-true configs' evidence that real per-step
    input no longer costs host staging on the dispatch path."""
    import paddle_tpu.fluid as fluid
    src = (batch_fn(i) for i in range((dispatches + 1) * steps))
    pipe = fluid.FeedPipeline(exe, fetch_list=[loss_var], program=prog,
                              source=src, steps=steps,
                              pipeline_depth=pipeline_depth)
    it = iter(pipe)
    next(it)  # warmup dispatch (compiles the scanned executable)
    t0, n = time.time(), 0
    for out in it:
        n += 1
    # sustained window, not per-yield gaps: the async runtime runs
    # ahead of the sync points, so individual yield gaps are bimodal
    elapsed = time.time() - t0
    assert np.isfinite(np.asarray(out)).all()
    m = pipe.metrics()
    return {
        'steps_per_dispatch': steps,
        'pipeline_depth': pipeline_depth,
        'dispatches': m['dispatches'],
        'ms_per_step_overlapped':
            round(elapsed / (n * steps) * 1e3, 2) if n else None,
        'feed_stall_ms_per_dispatch': round(
            m['feed_stall_s'] / max(m['dispatches'] - 1, 1) * 1e3, 3),
        'overlap_ratio': round(m['overlap_ratio'], 4),
    }


def _trailing_bucket_block(test_prog, startup_prog, feed_names, fetch_var,
                           make_request, lengths, place,
                           trailing_ladders=None, rows=4):
    """The ISSUE 5 paired measurement: a DISTINCT-length request stream
    served through the trailing-bucketed engine really coalesces —
    requests whose seq-lens fall in one ladder rung (or pad to one
    explicit rung) share lots and executables instead of fragmenting
    per shape.  Functional on CPU (the smoke path) and TPU alike, like
    PR 4's multi_model block: the record proves lots < requests and
    reports the executable count + padding-waste the ladder buys."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_prog)
    eng = serving.InferenceEngine(
        test_prog, feed_names=list(feed_names), fetch_list=[fetch_var],
        scope=scope, executor=exe, place=place,
        config=serving.ServingConfig(
            max_batch_size=rows * len(lengths), max_wait_ms=20,
            trailing_ladders=trailing_ladders))
    reqs = [make_request(l, rows) for l in lengths]
    with eng:
        for f in [eng.submit(r) for r in reqs]:  # warm the rungs
            f.result(600)
        t0 = time.time()
        futs = [eng.submit(r) for r in reqs]
        for f in futs:
            out = f.result(600)
        elapsed = time.time() - t0
    assert np.isfinite(np.asarray(out[0])).all()
    m = eng.metrics()
    # the whole point: distinct-length requests really coalesced
    assert m['lots'] < m['requests'], \
        'distinct-length requests failed to coalesce (%d lots / %d ' \
        'requests)' % (m['lots'], m['requests'])
    return {
        'distinct_lengths': len(set(lengths)),
        'requests': m['requests'],
        'lots': m['lots'],
        'executables': m['executor_compile_count'],
        'trailing_padding_waste': m['trailing_padding_waste'],
        'trailing_hits': m['trailing_buckets']['hits'],
        'rows_per_sec': round(rows * len(lengths) / elapsed, 2),
    }


def _decode_block(model, make_prompt, lens, place, slots=4, k_steps=4,
                  trailing_ladders=None):
    """The ISSUE 7 generation block: N mixed-length prompts served
    through the engine's continuous-batching decode lane (prefill lots
    coalesce, K greedy steps per in-jit decode scan over the slot
    batch, step-boundary admission).  Functional on CPU (the smoke
    path) and TPU alike, like the trailing_bucket block: the record
    proves the lane really fired (decode scans > 0, every request
    finished) and reports tokens/s, steps-per-dispatch and the slot
    occupancy continuous batching achieved."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(model['prefill_startup'])
        exe.run(model['step_startup'])
    spec = serving.GenerationSpec.from_model(model)
    eng = serving.InferenceEngine(
        model['prefill'], fetch_list=model['prefill_fetches'],
        scope=scope, executor=exe, place=place,
        config=serving.ServingConfig(
            max_batch_size=len(lens), max_wait_ms=5,
            decode_slots=slots, decode_steps=k_steps,
            trailing_ladders=trailing_ladders),
        generation=spec)
    with eng:
        for f in [eng.submit_generate(make_prompt(l)) for l in lens]:
            f.result(600)  # warm prefill rungs + the decode scan
        t0 = time.time()
        futs = [eng.submit_generate(make_prompt(l)) for l in lens]
        outs = [f.result(600) for f in futs]
        elapsed = time.time() - t0
    m = eng.metrics()
    d = m['decode']
    tokens = sum(len(o) for o in outs)
    # the whole point: the decode lane amortized dispatches
    assert d['dispatches'] > 0 and d['finished'] == 2 * len(lens), d
    assert d['tokens_per_dispatch'] > 1, d
    return {
        'requests': len(lens),
        'distinct_prompt_lengths': len(set(lens)),
        'tokens': tokens,
        'tokens_per_sec': round(tokens / elapsed, 2),
        'decode_dispatches': d['dispatches'],
        'prefill_lots': d['prefill_lots'],
        'steps_per_dispatch': d['steps_per_dispatch'],
        'tokens_per_dispatch': d['tokens_per_dispatch'],
        'slot_occupancy': d['slot_occupancy'],
        # pipelined decode (ISSUE 9): device-idling host round trips
        # per emitted token — the chained lane's whole deliverable
        # (decode_pipeline_depth >= 2 overlaps harvest with compute)
        'host_syncs_per_token': d['host_syncs_per_token'],
        'chain_flushes': d['chain_flushes'],
        'decode_pipeline_depth': eng.config.decode_pipeline_depth,
        # chunked prefill (ISSUE 14): these blocks run the monolithic
        # lane (prefill_chunk=None), so chunks stay 0 and the stall
        # gauge reports whatever the prompt mix imposed — the chunked
        # counterfactual is tools/perf_gate.py chunked_prefill
        'prefill_chunks': d['prefill_chunks'],
        'max_decode_stall_cycles': d['max_decode_stall_cycles'],
        'decode_slots': slots,
        'executables': m['executor_compile_count'],
    }


def _run(model, feed, on_tpu, steps, batch_fn=None, overlap_steps=None):
    """Returns (best_block_elapsed, mean_block_elapsed, steps_per_block,
    feed_overlap, cost); every block runs as one multi-step device
    dispatch (device-true), batch_fn (fresh batch per step) drives the
    paired overlapped-input measurement, and cost is the timed
    executable's XLA-cost-analysis block (ISSUE 6)."""
    import paddle_tpu.fluid as fluid
    if not on_tpu:
        steps = 2  # CPU path is a smoke test, not a benchmark
    place = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.amp_guard(on_tpu):
        exe.run(model['startup'])
        elapsed, mean_elapsed, loss = _timed_steps_multi(
            exe, model['main'], feed, model['loss'], steps,
            blocks=3 if on_tpu else 1)
        cost = _cost_block(exe, steps / elapsed, on_tpu)
        feed_overlap = None
        if batch_fn is not None:
            feed_overlap = _feed_overlap_block(
                exe, model['main'], model['loss'], batch_fn,
                overlap_steps if on_tpu and overlap_steps else steps)
    assert np.isfinite(loss)
    return elapsed, mean_elapsed, steps, feed_overlap, cost


def _stage(feed, place_on_tpu):
    if not place_on_tpu:
        return feed
    import jax
    import paddle_tpu.fluid as fluid
    dev = fluid.TPUPlace().jax_device()
    return {k: (v if isinstance(v, fluid.core.LoDTensor)
                else jax.device_put(np.asarray(v), dev))
            for k, v in feed.items()}


def bench_resnet(on_tpu, steps=20):
    """FLOPs/img 23.15e9: conv+fc MACs x2, train=3x fwd — the analytic
    count tools/jax_resnet_bound.py cross-checks."""
    from paddle_tpu.models import resnet
    batch = 512 if on_tpu else 8
    shape = (3, 224, 224) if on_tpu else (3, 64, 64)
    model = resnet.build(depth=50, class_dim=1000, image_shape=shape, lr=0.1)
    rng = np.random.RandomState(0)
    feed = _stage({
        'img': rng.standard_normal((batch, ) + shape).astype('float32'),
        'label': rng.randint(0, 1000, size=(batch, 1)).astype('int64'),
    }, on_tpu)
    brng = np.random.RandomState(1)

    def batch_fn(i):
        return {'img': brng.standard_normal(
                    (batch, ) + shape).astype('float32'),
                'label': brng.randint(
                    0, 1000, size=(batch, 1)).astype('int64')}

    # overlap block at K=4: a K=20 scanned block of bs512 224^2 images
    # (2 in flight) would not co-reside with the model on a 16GB chip
    elapsed, mean_elapsed, steps, feed_overlap, cost = _run(
        model, feed, on_tpu, steps, batch_fn=batch_fn, overlap_steps=4)
    v = batch * steps / elapsed
    mfu_analytic = round(v * 23.15e9 / peak_flops(), 4) if on_tpu else None
    return {
        'metric': 'resnet50_train_imgs_per_sec_per_chip',
        'value': round(v, 2), 'unit': 'imgs/sec',
        'ms_per_step': round(elapsed / steps * 1000, 2),
        'ms_per_step_mean': round(mean_elapsed / steps * 1000, 2),
        # cost-analysis-derived when captured (ISSUE 6), analytic else
        'mfu': (cost['mfu'] if cost and cost.get('mfu') is not None
                else mfu_analytic),
        'mfu_analytic': mfu_analytic,
        'cost': cost,
        'vs_baseline': round(v / BASELINE_RESNET_IMGS_PER_SEC, 3),
        'device_true': True, 'steps_per_dispatch': steps,
        'feed_overlap': feed_overlap,
    }


def bench_nmt(on_tpu, steps=20, seq_len=32):
    """FLOPs/token 1.404e8: measured 2.3 TFLOP/step at bs512 seq32 via
    XLA cost analysis (round-2 README profile) / (512*32) tokens."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import seq2seq
    batch = 512 if on_tpu else 8
    dict_dim, dim = (30000, 512) if on_tpu else (100, 16)
    model = seq2seq.build(src_dict_dim=dict_dim, trg_dict_dim=dict_dim,
                          embedding_dim=dim, encoder_size=dim,
                          decoder_size=dim)
    rng = np.random.RandomState(0)

    # feeds arrive as the double-buffer reader delivers them in real
    # training: padded + device-staged a step ahead (PaddedSequence —
    # PARITY L11).  Feeding host LoD tensors instead re-uploads and
    # re-pads every step on the dispatch path.
    def staged(ids):
        if not on_tpu:
            rows = [r.reshape(-1, 1).tolist() for r in ids]
            return fluid.create_lod_tensor(rows,
                                           [[seq_len] * len(rows)])
        import jax
        dev = fluid.TPUPlace().jax_device()
        return fluid.core.PaddedSequence(
            jax.device_put(ids.astype('int64')[..., None], dev),
            jax.device_put(np.full((batch, ), seq_len, np.int32), dev))

    src = staged(rng.randint(3, dict_dim, size=(batch, seq_len)))
    trg = staged(rng.randint(3, dict_dim, size=(batch, seq_len)))
    feed = {'src_word_id': src, 'target_language_word': trg,
            'target_language_next_word': trg}
    brng = np.random.RandomState(1)

    def batch_fn(i):
        # the reader's real form: host LoD tensors, padded + staged by
        # the pipeline's background thread
        def lod(ids):
            rows = [r.reshape(-1, 1).tolist() for r in ids]
            return fluid.create_lod_tensor(rows, [[seq_len] * len(rows)])
        s = lod(brng.randint(3, dict_dim, size=(batch, seq_len)))
        t = lod(brng.randint(3, dict_dim, size=(batch, seq_len)))
        return {'src_word_id': s, 'target_language_word': t,
                'target_language_next_word': t}

    elapsed, mean_elapsed, steps, feed_overlap, cost = _run(
        model, feed, on_tpu, steps, batch_fn=batch_fn)

    # ISSUE 5: the inference path's trailing-bucket block — mixed
    # seq-len LoD requests quantize onto the shared seq-len ladder
    # (two rungs here) and coalesce in the serving engine
    trng = np.random.RandomState(2)

    def nmt_request(l, rows):
        def lod(ids):
            return fluid.create_lod_tensor(
                [r.reshape(-1, 1).tolist() for r in ids],
                [[l] * rows])
        s = lod(trng.randint(3, dict_dim, size=(rows, l)))
        t = lod(trng.randint(3, dict_dim, size=(rows, l)))
        return {'src_word_id': s, 'target_language_word': t,
                'target_language_next_word': t}

    trailing_bucket = _trailing_bucket_block(
        model['test'], model['startup'], model['feeds'],
        model['prediction'], nmt_request,
        lengths=[4, 7, 9, 12, 20, 26],  # 6 distinct lens, 2 rungs
        place=fluid.TPUPlace() if on_tpu else fluid.CPUPlace())

    # ISSUE 7: the generation path's decode block — mixed-length
    # prompts through the continuous-batching decode lane (stepwise
    # greedy NMT decode, slot-cached GRU hidden state)
    dec_model = seq2seq.build_step_decode(
        src_dict_dim=dict_dim, trg_dict_dim=dict_dim,
        embedding_dim=dim, encoder_size=dim, decoder_size=dim,
        max_len=16 if on_tpu else 8)
    drng = np.random.RandomState(3)

    def nmt_prompt(l):
        ids = drng.randint(3, dict_dim, size=(l, 1))
        return {'src_word_id': fluid.create_lod_tensor(
            ids.tolist(), [[l]])}

    decode = _decode_block(
        dec_model, nmt_prompt, lens=[3, 6, 9, 4, 8, 5],
        place=fluid.TPUPlace() if on_tpu else fluid.CPUPlace())
    v = batch * seq_len * steps / elapsed
    mfu_analytic = round(v * 1.404e8 / peak_flops(), 4) if on_tpu else None
    return {
        'metric': 'nmt_train_tokens_per_sec_per_chip',
        'value': round(v, 2), 'unit': 'tokens/sec',
        'ms_per_step': round(elapsed / steps * 1000, 2),
        'ms_per_step_mean': round(mean_elapsed / steps * 1000, 2),
        'mfu': (cost['mfu'] if cost and cost.get('mfu') is not None
                else mfu_analytic),
        'mfu_analytic': mfu_analytic,
        'cost': cost,
        'vs_baseline': None,  # reference published no NMT number
        'device_true': True, 'steps_per_dispatch': steps,
        'feed_overlap': feed_overlap,
        'trailing_bucket': trailing_bucket,
        'decode': decode,
    }


def _transformer_flops_per_token(n_layer, d, d_ff, seq, vocab):
    """Train FLOPs per (batch*seq) token: MACs x 2 x 3 (fwd, train=3x).
    Per-token MACs: enc layer = 4d^2 (QKVO) + 2*d*d_ff (ffn) + 2*seq*d
    (scores + context); dec layer adds the cross attention (8d^2 +
    4*seq*d); plus the vocab projection."""
    enc = n_layer * (4 * d * d + 2 * d * d_ff + 2 * seq * d)
    dec = n_layer * (8 * d * d + 2 * d * d_ff + 4 * seq * d)
    return 3.0 * 2.0 * (enc + dec + vocab * d)


def bench_transformer(on_tpu, steps=10):
    from paddle_tpu.models import transformer
    batch, seq = (128, 256) if on_tpu else (4, 16)
    n_layer, n_head, d, d_ff, vocab = \
        (6, 8, 512, 2048, 30000) if on_tpu else (2, 4, 64, 128, 100)
    model = transformer.build(src_vocab=vocab, trg_vocab=vocab,
                              max_len=seq, n_layer=n_layer, n_head=n_head,
                              d_model=d, d_ff=d_ff)
    rng = np.random.RandomState(0)
    ids = lambda: rng.randint(1, vocab, size=(batch, seq)).astype('int64')
    feed = _stage({'src_ids': ids(), 'trg_ids': ids(), 'lbl_ids': ids()},
                  on_tpu)
    brng = np.random.RandomState(1)

    def batch_fn(i):
        bid = lambda: brng.randint(
            1, vocab, size=(batch, seq)).astype('int64')
        return {'src_ids': bid(), 'trg_ids': bid(), 'lbl_ids': bid()}

    elapsed, mean_elapsed, steps, feed_overlap, cost = _run(
        model, feed, on_tpu, steps, batch_fn=batch_fn, overlap_steps=4)

    # ISSUE 5: the inference path's trailing-bucket block — the
    # transformer's dense [B, T] id feeds ride an EXPLICIT per-feed
    # resolution-style ladder (one rung: the model's max_len), so
    # shorter requests zero-pad up and coalesce instead of fragmenting
    # per length (padded label positions score pad-token 0; the timed
    # quantity is serving shape economics, like the train feeds'
    # random ids)
    import paddle_tpu.fluid as fluid
    trng = np.random.RandomState(2)

    def tf_request(l, rows):
        bid = lambda: trng.randint(
            1, vocab, size=(rows, l)).astype('int64')
        return {'src_ids': bid(), 'trg_ids': bid(), 'lbl_ids': bid()}

    trailing_bucket = _trailing_bucket_block(
        model['test'], model['startup'], model['feeds'],
        model['prediction'], tf_request,
        lengths=[seq // 4, seq // 2, 3 * seq // 4, seq],
        place=fluid.TPUPlace() if on_tpu else fluid.CPUPlace(),
        trailing_ladders={n: [seq] for n in model['feeds']})

    # ISSUE 7: the generation path's decode block — the KV-cache
    # stepwise decoder (slot slabs [S, max_ctx, d_k], one_hot scatter +
    # masked incremental attention per step), mixed prompt lengths
    # riding a dense prompt ladder
    dec_model = transformer.build_step_decode(
        vocab=vocab, d_model=d, d_k=d, max_ctx=seq,
        max_len=16 if on_tpu else 8)
    drng = np.random.RandomState(3)

    def tf_prompt(l):
        return {'gen_src': drng.randint(
                    2, vocab, size=(1, l, 1)).astype('int64'),
                'gen_src_len': np.array([[l]], np.float32)}

    decode = _decode_block(
        dec_model, tf_prompt, lens=[3, 6, 9, 4, 8, 5],
        place=fluid.TPUPlace() if on_tpu else fluid.CPUPlace(),
        trailing_ladders={'gen_src': [4, 8, 12]})
    v = batch * seq * steps / elapsed
    fpt = _transformer_flops_per_token(n_layer, d, d_ff, seq, vocab)
    mfu_analytic = round(v * fpt / peak_flops(), 4) if on_tpu else None
    return {
        'metric': 'transformer_base_train_tokens_per_sec_per_chip',
        'value': round(v, 2), 'unit': 'tokens/sec',
        'ms_per_step': round(elapsed / steps * 1000, 2),
        'ms_per_step_mean': round(mean_elapsed / steps * 1000, 2),
        'mfu': (cost['mfu'] if cost and cost.get('mfu') is not None
                else mfu_analytic),
        'mfu_analytic': mfu_analytic,
        'cost': cost,
        'vs_baseline': None,  # reference published no transformer number
        'device_true': True, 'steps_per_dispatch': steps,
        'feed_overlap': feed_overlap,
        'trailing_bucket': trailing_bucket,
        'decode': decode,
    }


def bench_stacked_lstm(on_tpu, steps=20, seq_len=64):
    """IMDB stacked LSTM (3 layers, h=128 — the reference benchmark
    model's width).  FLOPs/token: 2 MACs x (layer1 128->512 x-proj +
    128->512 recurrence; layers 2-3 concat-256->512 + recurrence), x3
    for training ~= 3.2e6 — the model is tiny; the metric is
    throughput, and one dispatch per step is host-bound."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import stacked_lstm
    batch = 128 if on_tpu else 8
    model = stacked_lstm.build()
    rng = np.random.RandomState(0)
    rows = [rng.randint(0, 5149, size=(seq_len, 1)).tolist()
            for _ in range(batch)]
    feed = {'words': fluid.create_lod_tensor(rows, [[seq_len] * batch]),
            'label': rng.randint(0, 2, size=(batch, 1)).astype('int64')}
    fpt = 3.0 * 2.0 * (128 * 512 + 128 * 512 + 2 * (256 * 512 + 128 * 512))

    # This model's step is a few ms, so per-call timing measures the
    # host's per-dispatch cost (VERDICT r3 weak-#7 / r4 next-#4).  The
    # HEADLINE is device-true: Executor.run_multi runs K steps as ONE
    # fori_loop dispatch, so wall clock measures the chip.  The
    # single-dispatch-per-step number stays as a secondary field.
    place = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    k = steps if on_tpu else 2
    blocks = 3 if on_tpu else 1
    with fluid.scope_guard(scope), fluid.amp_guard(on_tpu):
        exe.run(model['startup'])
        # warm with steps=k: `steps` is a static jit argument, so a
        # steps=2 warmup would leave the k-step executable uncompiled
        # and the first timed block would include the XLA compile
        loss_v, = exe.run_multi(model['main'], feed=feed,
                                fetch_list=[model['loss']], steps=k)
        per_block = []
        for _ in range(blocks):
            t0 = time.time()
            loss_v, = exe.run_multi(model['main'], feed=feed,
                                    fetch_list=[model['loss']], steps=k)
            per_block.append(time.time() - t0)
        # secondary: the old one-dispatch-per-step path (warm BOTH its
        # cache entries first — fetch_list=[] and [loss] each key a
        # separate single-step compile that run_multi never built)
        exe.run(model['main'], feed=feed, fetch_list=[])
        exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
        t0 = time.time()
        for _ in range(max(k // 4, 1) - 1):
            exe.run(model['main'], feed=feed, fetch_list=[])
        exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
        disp_elapsed = time.time() - t0
        # ISSUE 3 paired block: fresh LoD batches per step, staged
        # overlapped through the FeedPipeline
        brng = np.random.RandomState(1)

        def batch_fn(i):
            rows = [brng.randint(0, 5149, size=(seq_len, 1)).tolist()
                    for _ in range(batch)]
            return {'words': fluid.create_lod_tensor(
                        rows, [[seq_len] * batch]),
                    'label': brng.randint(
                        0, 2, size=(batch, 1)).astype('int64')}

        feed_overlap = _feed_overlap_block(
            exe, model['main'], model['loss'], batch_fn, k)
    assert np.isfinite(np.asarray(loss_v)).all()
    elapsed, mean_elapsed = min(per_block), sum(per_block) / len(per_block)
    cost = _cost_block(exe, k / elapsed, on_tpu)
    v = batch * seq_len * k / elapsed
    v_disp = batch * seq_len * max(k // 4, 1) / disp_elapsed
    mfu_analytic = round(v * fpt / peak_flops(), 4) if on_tpu else None
    return {
        'metric': 'stacked_lstm_train_tokens_per_sec_per_chip',
        'value': round(v, 2), 'unit': 'tokens/sec',
        'ms_per_step': round(elapsed / k * 1000, 2),
        'ms_per_step_mean': round(mean_elapsed / k * 1000, 2),
        'mfu': (cost['mfu'] if cost and cost.get('mfu') is not None
                else mfu_analytic),
        'mfu_analytic': mfu_analytic,
        'cost': cost,
        'vs_baseline': None,  # reference LSTM tables are a different net
        'device_true': True, 'steps_per_dispatch': k,
        'tokens_per_sec_dispatch_bound': round(v_disp, 2),
        'feed_overlap': feed_overlap,
    }


def bench_resnet_infer_bf16(on_tpu, steps=10):
    """Half-precision INFERENCE via the Float16Transpiler program
    rewrite (reference contrib/float16 float16_benchmark.md measures
    the same rewrite on V100): ResNet-50 eval program, f32 vs
    transpiled-bf16, interleaved in THIS process so the ratio is
    drift-free.  value = bf16 imgs/sec; speedup_vs_f32 is the paired
    ratio.

    DEVICE-TRUE (closing the last dispatch-tax ledger row): each timed
    block is ONE Executor.run_eval_multi dispatch — `steps` eval
    iterations as an in-jit lax.scan collecting every step's
    predictions — so the per-dispatch host cost is paid once per K
    eval steps.  The serving engine (paddle_tpu.serving) rides the
    same executable."""
    import tempfile
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    batch = 256 if on_tpu else 4
    shape = (3, 224, 224) if on_tpu else (3, 32, 32)
    blocks = 3 if on_tpu else 1
    k = steps if on_tpu else 4  # steps per dispatch (CPU smoke: small)
    model = resnet.build(depth=50 if on_tpu else 18, class_dim=1000,
                         image_shape=shape, lr=0.1)
    place = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    rng = np.random.RandomState(0)
    x = rng.standard_normal((batch, ) + shape).astype('float32')

    def build_runner(half):
        exe = fluid.Executor(place)
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(model['startup'])
            with tempfile.TemporaryDirectory() as td:
                fluid.io.save_inference_model(
                    td, model['feeds'][:1], [model['prediction']], exe,
                    main_program=model['test'])
                prog, feeds, fetches = fluid.io.load_inference_model(
                    td, exe)
            if half:
                fluid.InferenceTranspiler().transpile(prog, scope=scope)
                fluid.Float16Transpiler().transpile(
                    prog, scope=scope, dtype='bfloat16',
                    feeded_var_names=feeds, fetch_var_names=fetches)
            staged = _stage({feeds[0]: x}, on_tpu)
            # warm with the SAME k — `steps` is a static jit argument of
            # the eval scan, so a different-steps warmup would leave the
            # timed executable uncompiled (the run_multi lesson)
            exe.run_eval_multi(prog, feed=staged, fetch_list=fetches,
                               steps=k)

        def block():
            with fluid.scope_guard(scope):
                t0 = time.time()
                out, = exe.run_eval_multi(prog, feed=staged,
                                          fetch_list=fetches, steps=k)
                el = time.time() - t0
            assert np.isfinite(np.asarray(out)).all()
            return batch * k / el

        return block, (prog, feeds, fetches, scope), exe

    def multi_model_block(handles):
        """The ISSUE 4 paired measurement: BOTH variants (f32 + bf16 —
        two distinct models sharing one chip) hosted by a ModelRegistry
        under an HBM budget sized for only one of them.  The resident
        window serves one model repeatedly; the evict-reload window
        alternates, so every swap pays the arbiter's LRU eviction
        (weights demoted to host) + transparent reload (re-stage +
        recompile) — the measured cost of multi-tenant weight
        arbitration at this operating point."""
        from paddle_tpu import serving
        reg = serving.ModelRegistry(
            place=place,
            config=serving.ServingConfig(max_batch_size=batch,
                                         bucket_sizes=[batch]))
        feed_by_model = {}
        for name, (prog, feeds, fetches, scope) in handles.items():
            reg.load(name, program=prog, feed_names=feeds,
                     fetch_list=fetches, scope=scope)
            feed_by_model[name] = {feeds[0]: x}
        names = list(handles)
        for name in names:  # resident warm (compiles + live stats)
            reg.infer(name, feed_by_model[name], timeout=600)
        # accounts are live here: the bench scopes were pre-staged by
        # the timed blocks, so the first routed request per model
        # corrected its account to real device bytes
        live = max(s['hbm_bytes']
                   for s in reg.status()['models'].values())
        reg.arbiter.set_budget(int(1.5 * live))
        reps = 2
        reg.infer(names[0], feed_by_model[names[0]], timeout=600)
        t0 = time.time()
        for _ in range(reps):
            reg.infer(names[0], feed_by_model[names[0]], timeout=600)
        resident_ips = batch * reps / (time.time() - t0)
        # the resident window left names[0] resident: start on
        # names[1] so EVERY timed request pays an evict + reload
        t0 = time.time()
        for i in range(reps):
            name = names[(i + 1) % 2]
            reg.infer(name, feed_by_model[name], timeout=600)
        evict_ips = batch * reps / (time.time() - t0)
        m = reg.metrics()
        reg.stop()
        return {
            'models': len(names),
            'budget_mb': round(m['budget_bytes'] / 1024.0 / 1024.0, 2),
            'resident_imgs_per_sec': round(resident_ips, 2),
            'evict_reload_imgs_per_sec': round(evict_ips, 2),
            'reload_tax': round(evict_ips / resident_ips, 4),
            'evictions': m['evictions'],
            'reloads': m['reloads'],
            'admission_rejects': m['admission_rejects'],
        }

    f32_block, f32_handles, _f32_exe = build_runner(False)
    bf16_block, bf16_handles, bf16_exe = build_runner(True)
    f32_v, bf16_v, ratios = [], [], []
    for _ in range(blocks):
        a = f32_block()
        b = bf16_block()
        f32_v.append(a)
        bf16_v.append(b)
        ratios.append(b / a)
    # ISSUE 6: the eval scan's own XLA cost analysis — imgs/sec / batch
    # is steps/sec, so this is the served executable's achieved MFU
    cost = _cost_block(bf16_exe, max(bf16_v) / batch, on_tpu,
                       kind='eval_multi')
    mm = multi_model_block({'resnet_f32': f32_handles,
                            'resnet_bf16': bf16_handles})
    return {
        'metric': 'resnet50_infer_bf16_imgs_per_sec_per_chip',
        'value': round(max(bf16_v), 2), 'unit': 'imgs/sec',
        'ms_per_step': round(batch * k / max(bf16_v) / k * 1000, 2),
        'ms_per_step_mean': None,
        'mfu': cost['mfu'] if cost else None,
        'cost': cost,
        'vs_baseline': None,  # reference published V100 fp16 numbers only
        'f32_imgs_per_sec': round(max(f32_v), 2),
        'speedup_vs_f32': round(max(ratios), 3),
        # uniform with the train configs: K in-jit eval steps per
        # dispatch via run_eval_multi (ROADMAP dispatch-tax ledger)
        'device_true': True, 'steps_per_dispatch': k,
        # ISSUE 4: both variants as two registry-hosted models under
        # one HBM budget — paired resident vs evict-reload serving
        'multi_model': mm,
    }


def _ctr_serving_block(test_prog, feeds, pred, scope, mesh, place, vocab,
                       embed, hidden, batch_fn, reqs=6):
    """The ISSUE 11 serving half: the trained CTR program loads into a
    ModelRegistry (row-sharded over the SAME mesh the trainer used —
    the table's arbiter account is charged at its per-device shard
    bytes) and ``submit`` serves skewed id-batches through the normal
    lot machinery.  The block also runs the admission counterfactual
    when the mesh really splits rows: under a budget sized BELOW the
    full table (plus headroom above the per-device shard), the sharded
    load was admitted while the identical UNSHARDED program draws the
    typed HBMBudgetError."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel, serving
    from paddle_tpu.serving.arbiter import program_seed_bytes
    from paddle_tpu.serving.registry import EMBED_TABLE_SUFFIX

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mp = int(axes.get('mp', 1))
    table_bytes = vocab * embed * 4
    max_batch = 256
    # serve from a CLEAN inference scope (trained params copied to
    # host, optimizer state left behind) — the save/load_inference_model
    # shape: a trainer scope's [V, D] Adam moments are not part of the
    # serving footprint the admission budget is sized for
    serve_scope = fluid.core.Scope()
    test_vars = {v.name for v in test_prog.global_block().vars.values()
                 if getattr(v, 'persistable', False)}
    for n in scope.local_var_names():
        if n in test_vars:
            serve_scope.var(n).set_value(
                np.asarray(scope.find_var(n).value()))
    scope = serve_scope
    budget = None
    if mp > 1:
        # below the full table + model, above the sharded layout +
        # model — seeded at the SAME top bucket the registry admits at
        seed = program_seed_bytes(test_prog, max_batch)
        budget = int(seed - table_bytes + table_bytes // mp
                     + table_bytes // 4)
    reg = serving.ModelRegistry(
        place=place, mesh=mesh, hbm_budget_bytes=budget,
        config=serving.ServingConfig(max_batch_size=max_batch,
                                     max_wait_ms=5))
    try:
        reg.load('ctr', program=test_prog, feed_names=list(feeds),
                 fetch_list=[pred], scope=scope)
        n_rows = 0
        t0 = time.time()
        futs = [reg.submit('ctr', batch_fn(i)) for i in range(reqs)]
        for f in futs:
            out, = f.result(600)
            assert np.isfinite(np.asarray(out)).all()
            n_rows += np.shape(out)[0]
        elapsed = time.time() - t0
        snap = reg.arbiter.snapshot()
        table_accounts = {n: a for n, a in snap['accounts'].items()
                          if EMBED_TABLE_SUFFIX in n}
        m = reg.metrics()['models']['ctr']
        return _ctr_serving_rec(reqs, n_rows, elapsed, m, table_accounts,
                                table_bytes, budget, mp, place, vocab,
                                embed, hidden, max_batch)
    finally:
        # a failed serve/assert must not leak the registry's worker
        # thread and staged device arrays into the rest of the child
        reg.stop()


def _ctr_serving_rec(reqs, n_rows, elapsed, m, table_accounts, table_bytes,
                     budget, mp, place, vocab, embed, hidden, max_batch):
    """Back half of _ctr_serving_block: the unsharded admission
    counterfactual + the record."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    rejected_unsharded = None
    if budget is not None:
        # the counterfactual: the SAME model shape/budget with no mesh
        # keeps the table whole on one device — typed reject at load
        with fluid.unique_name.guard():
            from paddle_tpu.models import ctr as ctr_model
            plain = ctr_model.build(
                sparse_dim=vocab, embed_size=embed, hidden_sizes=hidden,
                is_sparse=True,
                optimizer=fluid.optimizer.SGD(learning_rate=0.05))
        scope2 = fluid.core.Scope()
        with fluid.scope_guard(scope2):
            fluid.Executor(place).run(plain['startup'])
        reg2 = serving.ModelRegistry(
            place=place, hbm_budget_bytes=budget,
            config=serving.ServingConfig(max_batch_size=max_batch,
                                         max_wait_ms=5))
        try:
            reg2.load('ctr-unsharded', program=plain['test'],
                      feed_names=plain['feeds'],
                      fetch_list=[plain['prediction']], scope=scope2)
            rejected_unsharded = False
        except serving.HBMBudgetError:
            rejected_unsharded = True
        finally:
            reg2.stop()
        assert rejected_unsharded, (
            'an unsharded table past the per-device budget must draw '
            'the typed HBMBudgetError')
    rec = {
        'requests': reqs,
        'rows': int(n_rows),
        'rows_per_sec': round(n_rows / elapsed, 2),
        'lots': m['lots'],
        'table_accounts': table_accounts,
        'table_bytes': table_bytes,
        'hbm_budget_bytes': budget,
        'unsharded_rejected_typed': rejected_unsharded,
    }
    return rec


def _ctr_cache_block(on_tpu, vocab, embed):
    """The ISSUE 12 cache half: a FeedPipeline-driven train over the
    two-tier hot-row embedding store — the staging thread computes
    block N+1's miss set and runs the host row exchange while dispatch
    N computes, so the prefetch genuinely overlaps (asserted: the
    overlap ratio must be > 0 on this very smoke).  Reports the cache
    deliverables: hit rate at the skewed stream, host bytes per step
    (vs the full per-step exchange a remote-updater design pays), and
    the measured prefetch overlap."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model
    from paddle_tpu.dataset import ctr as ctr_data
    from paddle_tpu.distributed import CachedEmbeddingTable

    batch, k, blocks = (256, 8, 6) if on_tpu else (32, 4, 6)
    capacity = max(vocab // 8, 512)
    hot_frac = 0.95
    with fluid.unique_name.guard():
        m = ctr_model.build(
            sparse_dim=vocab, embed_size=embed, hidden_sizes=(64, 32),
            is_sparse=True,
            optimizer=fluid.optimizer.SGD(learning_rate=0.05))
    m['main'].random_seed = 0
    m['startup'].random_seed = 0
    exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                         else fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['startup'])
    cache = CachedEmbeddingTable.from_scope(
        scope, m['main'], 'ctr_embedding', capacity, ['sparse_ids'])
    rng = np.random.RandomState(7)

    def source():
        for _ in range(blocks * k):
            yield ctr_data.zipf_batch(rng, batch, vocab,
                                      hot_frac=hot_frac)

    try:
        t0 = time.time()
        pipe = fluid.FeedPipeline(exe, [m['loss']], program=m['main'],
                                  source=source(), steps=k, scope=scope,
                                  embed_caches=[cache])
        outs = pipe.run()
        elapsed = time.time() - t0
        assert len(outs) == blocks and all(
            np.isfinite(np.asarray(o[0])).all() for o in outs)
        cache.flush()
        cm = cache.metrics()
        # the acceptance pin: the staged prefetch really ran ahead of
        # at least one dispatch on this very smoke
        assert cm['prefetch_overlap_ratio'] is not None and \
            cm['prefetch_overlap_ratio'] > 0, cm
        return {
            'rows_per_sec': round(batch * k * blocks / elapsed, 1),
            'hit_rate': round(cm['hit_rate'], 4),
            'host_bytes_per_step': round(cm['host_bytes_per_step'], 1),
            'prefetch_overlap_ratio': round(
                cm['prefetch_overlap_ratio'], 4),
            'prefetch_stalls': cm['prefetch_stalls'],
            'exchanges': cm['exchanges'],
            'writeback_rows': cm['writeback_rows'],
            'capacity': capacity, 'hot_frac': hot_frac,
            'slab_bytes': cache.slab_nbytes(),
            'table_bytes': cache.master_nbytes(),
        }
    finally:
        cache.close()


def bench_ctr(on_tpu, steps=20):
    """Sharded sparse-embedding CTR workload (ISSUE 11, ROADMAP item
    4): wide&deep over a row-sharded embedding table, trained
    device-true through ParallelExecutor.run_multi with
    ``is_sparse=True`` — the lookup backward is a SparseRows
    rows/values pytree and the optimizer update is ONE row-subset
    scatter per step, so the dense [V, D] gradient never exists on
    device.  Id traffic is skewed (zipfian — the CTR regime), the
    table + its accumulators row-shard over the mesh's 'mp' axis via
    the DistributeTranspiler sparse pass, and the serving block loads
    the trained program into a ModelRegistry over the same mesh.
    FLOPs/sample (analytic): dense tower MACs x2 x3 (fwd+bwd) —
    embedding gather/scatter is memory-bound and excluded."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel
    from paddle_tpu.models import ctr as ctr_model
    from paddle_tpu.dataset import ctr as ctr_data

    batch = 1024 if on_tpu else 64
    vocab = 1000000 if on_tpu else 8192
    embed = 64 if on_tpu else 16
    hidden = (256, 128) if on_tpu else (64, 32)
    if not on_tpu:
        steps = 2  # CPU path is a smoke test, not a benchmark
    devices = jax.devices()
    mp = 2 if len(devices) >= 2 else 1
    dp = max(len(devices) // mp, 1)
    mesh = parallel.make_mesh({'dp': dp, 'mp': mp}, devices[:dp * mp])

    m = ctr_model.build(sparse_dim=vocab, embed_size=embed,
                        hidden_sizes=hidden, is_sparse=True,
                        is_distributed=True,
                        optimizer=fluid.optimizer.Adam(learning_rate=1e-3))
    t = fluid.DistributeTranspiler()
    t.config.sparse_shard_axis = 'mp'
    t.transpile(0, program=m['main'], startup_program=m['startup'],
                trainers=1)
    assert t.distributed_lookup_tables == ['ctr_embedding']
    # the test clone predates the transpile: annotate its table too so
    # the SERVING side lays rows out over the mesh as well
    parallel.shard(m['test'].global_block().var('ctr_embedding'),
                   'mp', None)

    rng = np.random.RandomState(0)

    def batch_fn(i):
        # zipfian ids: mass on a few hot rows, a long tail — the
        # skewed traffic the sparse lane exists for (ONE construction
        # shared with perf_gate sparse_grad and load_gen --ctr-frac)
        return ctr_data.zipf_batch(rng, batch, vocab)

    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(m['startup'])
        pe = fluid.ParallelExecutor(loss_name=m['loss'].name,
                                    main_program=m['main'], scope=scope,
                                    mesh=mesh)
        feeds = [batch_fn(i) for i in range(steps)]
        # warm the K-step scanned executable (static jit arg)
        lv, = pe.run_multi([m['loss'].name], feed_list=feeds)
        per_block = []
        for _ in range(3 if on_tpu else 1):
            t0 = time.time()
            lv, = pe.run_multi([m['loss'].name], feed_list=feeds)
            per_block.append(time.time() - t0)
        elapsed, mean_elapsed = min(per_block), np.mean(per_block)
        loss = float(np.asarray(lv).flatten()[0])
        assert np.isfinite(loss)
        table = scope.find_var('ctr_embedding').value()
        # a size-1 'mp' axis (one chip) reports fully-replicated
        assert mp == 1 or (hasattr(table, 'sharding') and
                           not table.sharding.is_fully_replicated), \
            'the CTR table must really be row-sharded over the mesh'
        cost = _cost_block(pe, steps / elapsed, on_tpu)
        serving_block = _ctr_serving_block(
            m['test'], m['feeds'], m['prediction'], scope, mesh,
            fluid.TPUPlace() if on_tpu else fluid.CPUPlace(),
            vocab, embed, hidden, batch_fn)

    v = batch * steps / elapsed
    touched = batch * ctr_data.SPARSE_SLOTS
    # dense tower fwd MACs x2 x3 (train); the sparse lane's win is the
    # MEMORY it never touches, reported as bytes-avoided alongside
    d_in = ctr_data.DENSE_DIM + ctr_data.SPARSE_SLOTS * embed
    macs = d_in * hidden[0] + hidden[0] * hidden[1] + hidden[1] \
        + ctr_data.DENSE_DIM
    flops_per_sample = macs * 2 * 3
    mfu_analytic = round(v * flops_per_sample / peak_flops(), 4) \
        if on_tpu else None
    return {
        'metric': 'ctr_train_samples_per_sec',
        'value': round(v, 2), 'unit': 'samples/sec',
        'ms_per_step': round(elapsed / steps * 1000, 2),
        'ms_per_step_mean': round(mean_elapsed / steps * 1000, 2),
        'mfu': (cost['mfu'] if cost and cost.get('mfu') is not None
                else mfu_analytic),
        'mfu_analytic': mfu_analytic,
        'cost': cost,
        'vs_baseline': None,  # reference published no CTR number
        'device_true': True, 'steps_per_dispatch': steps,
        'loss': round(loss, 5),
        'mesh': {'dp': dp, 'mp': mp},
        'vocab': vocab, 'embed_dim': embed, 'batch': batch,
        'embedding_rows_per_sec': round(v * ctr_data.SPARSE_SLOTS, 1),
        # the sparse lane's deliverable: the [V, D] grad bytes each
        # step never materializes (vs rows x D it actually writes)
        'sparse_grad_bytes_avoided_per_step':
            (vocab - touched) * embed * 4,
        'table_row_sharded': mp > 1,
        'serving': serving_block,
        # ISSUE 12: the two-tier hot-row cache block (overlapped
        # prefetch asserted > 0 inside)
        'cache': _ctr_cache_block(on_tpu, vocab, embed),
    }


CONFIGS = {
    'resnet': bench_resnet,
    'nmt': bench_nmt,
    'transformer': bench_transformer,
    'stacked_lstm': bench_stacked_lstm,
    'resnet_infer_bf16': bench_resnet_infer_bf16,
    'ctr': bench_ctr,
}


def run_one(name):
    """Child mode: run a single config, print exactly one JSON line.
    The child is the one process on the chip; without BENCH_FORCE_CPU=1
    a child that finds no accelerator exits non-zero."""
    force_cpu = os.environ.get('BENCH_FORCE_CPU') == '1'
    if force_cpu:
        os.environ['JAX_PLATFORMS'] = 'cpu'  # before jax is imported
        if name == 'ctr':
            # the CTR smoke trains/serves over a {dp, mp} mesh: on the
            # CPU that is the 8-device VIRTUAL mesh, which must be
            # forced before jax initializes its backend
            flags = os.environ.get('XLA_FLAGS', '')
            if '--xla_force_host_platform_device_count' not in flags:
                os.environ['XLA_FLAGS'] = (
                    flags + ' --xla_force_host_platform_device_count=8'
                ).strip()
    import jax
    import paddle_tpu.fluid as fluid
    devices = jax.devices()
    on_tpu = not force_cpu
    if on_tpu and not fluid.core.is_compiled_with_tpu():
        sys.exit('bench: JAX found no accelerator (platform %r) and '
                 'BENCH_FORCE_CPU is not 1 — refusing to write toy-size '
                 'CPU numbers under device metric names'
                 % devices[0].platform)
    # persistent XLA compilation cache shared by all config children:
    # a re-run (and configs sharing executables) warm-starts compiles
    # from disk instead of re-tracing ResNet/transformer from scratch
    fluid.flags.enable_compile_cache()
    # per-executable cost accounting (ISSUE 6): device-true configs
    # report XLA-cost-analysis-derived MFU instead of the hand-derived
    # analytic counts.  BENCH_COST_ACCOUNTING=0 opts out (the capture's
    # AOT analysis costs one extra XLA compile per executable, amortized
    # by the shared compile cache above).
    if os.environ.get('BENCH_COST_ACCOUNTING', '1') != '0':
        fluid.FLAGS.cost_accounting = True
    rec = CONFIGS[name](on_tpu)
    rec.update(platform=devices[0].platform,
               device_kind=devices[0].device_kind,
               device_count=len(devices))
    print(json.dumps(rec), flush=True)


def _headline(configs):
    """ResNet if it produced a number, else the first config that did,
    else the ResNet failure record (driver contract needs a headline)."""
    done = [c for c in configs if c.get('value') is not None]
    for c in done:
        if c['metric'].startswith('resnet'):
            return c
    if done:
        return done[0]
    return configs[0] if configs else {
        'metric': 'resnet50_train_imgs_per_sec_per_chip',
        'value': None, 'unit': None, 'vs_baseline': None,
        'error': 'no config ran'}


def _emit(configs, partial):
    """One full contract-shaped JSON line; also rewrite the partial file
    atomically so the driver can parse it even if stdout is lost."""
    head = _headline(configs)
    line = json.dumps({
        'metric': head['metric'],
        'value': head['value'],
        'unit': head['unit'],
        'vs_baseline': head['vs_baseline'],
        'mfu': head.get('mfu'),
        'partial': partial,
        'configs': configs,
    })
    print(line, flush=True)
    # atomic partial rewrite with GUARANTEED tmp cleanup: an abort
    # between write and rename (the SIGALRM bail, a crash mid-emit)
    # must not strand BENCH_PARTIAL.json.tmp in the repo — it has come
    # back three times (PR 3, PR 6, PR 8) from exactly that window
    tmp = PARTIAL_PATH + '.tmp'
    try:
        with open(tmp, 'w') as f:
            f.write(line + '\n')
        os.replace(tmp, PARTIAL_PATH)
    except OSError:
        pass  # read-only fs must not kill the bench
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return head


def _run_child(name, budget):
    """Run one config in a subprocess under a hard wall-clock budget.
    The child gets its own session so a hung XLA call is killed as a
    whole process group — nothing in the parent can block, and the
    chip is free again for the next child."""
    t0 = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), '--config', name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        # Kill the whole session: a grandchild holding the inherited
        # pipe fds would otherwise keep communicate() blocked past the
        # budget (and could keep holding the TPU for later configs).
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        stdout, _ = proc.communicate()
        return {'metric': name + '_TIMEOUT', 'value': None, 'unit': None,
                'mfu': None, 'vs_baseline': None,
                'error': 'wall-clock budget %ds exceeded; '
                         'partial output: %r'
                         % (budget, (stdout or b'')[-200:])}
    elapsed = time.time() - t0
    out = stdout.decode('utf-8', 'replace').strip().splitlines()
    for ln in reversed(out):
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict) and 'metric' in rec:
            rec['wall_s'] = round(elapsed, 1)
            return rec
    return {'metric': name + '_FAILED', 'value': None, 'unit': None,
            'mfu': None, 'vs_baseline': None,
            'error': 'rc=%d stderr tail: %s' %
            (proc.returncode,
             stderr.decode('utf-8', 'replace')[-300:])}


def main():
    # Backstop: if anything in the parent itself wedges, force a final
    # flush + exit.  The parent imports no jax (one process for each
    # chip: the children need it), so this should be moot.
    total_budget = sum(BUDGETS.values()) + 120

    def _bail(signum, frame):
        _emit(state['configs'], partial=True)
        os._exit(3)

    state = {'configs': []}
    # a PREVIOUS run killed inside _emit's write->rename window left
    # its tmp behind; clear it so aborted runs stop accreting strays
    try:
        os.remove(PARTIAL_PATH + '.tmp')
    except OSError:
        pass
    signal.signal(signal.SIGALRM, _bail)
    signal.alarm(total_budget)

    for name in CONFIGS:
        state['configs'].append(_run_child(name, BUDGETS[name]))
        if len(state['configs']) < len(CONFIGS):
            _emit(state['configs'], partial=True)
    signal.alarm(0)
    _emit(state['configs'], partial=False)
    bad = [c['metric'] for c in state['configs']
           if c['metric'].endswith(('_FAILED', '_TIMEOUT'))]
    if bad:
        # the full report (incl. the other configs' numbers and the
        # errors) is already on stdout; exit nonzero for the driver
        raise SystemExit('bench configs did not finish: %s' % bad)


if __name__ == '__main__':
    if len(sys.argv) == 3 and sys.argv[1] == '--config':
        run_one(sys.argv[2])
    else:
        main()
