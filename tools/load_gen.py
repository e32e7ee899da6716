"""Open-loop serving load harness CLI (ISSUE 8).

Drives a ModelRegistry with a seeded Poisson request stream
(serving.OpenLoopLoadGen) and prints one JSON report line: sustained
req/s, p50/p99/p99.9 latency, GOODPUT (responses inside their
deadline), shed / overload-rejected / late counts, plus the registry's
own metrics snapshot.  Works against synthetic built-in models (the
default — zero setup, runs on CPU or TPU) or a directory of
save_inference_model exports.

Generate traffic (ISSUE 9): ``--generate-frac`` routes that share of
the offered stream to a synthetic generation model's continuous-
batching decode lane (kind='generate' TrafficClass); the report then
carries a ``decode`` block per generation model — decode tokens/s over
the offered window and HOST-SYNCS-PER-TOKEN (device-idling host round
trips the chained decode lane avoids; compare --decode-depth 1 vs 2
to see the pipelining win under open-loop load).

Fleet (ISSUE 17): ``--replicas N`` serves the SAME offered stream
through N replica registries behind ``serving.ReplicaServer`` +
``serving.FleetRouter`` (the resilient, affinity-aware fleet tier) —
the report gains a ``fleet`` block with the router's dispatch /
failover / overload counters and one per-replica block each carrying
that replica's registry view.  Synthetic forward + generate traffic
only (``--model-dir`` and ``--ctr-frac`` stay single-registry).

Parameter servers (ISSUE 19): ``--pservers N`` bypasses the serving
stack and drives the sharded embedding tier directly — ``--requests``
seeded zipfian id batches (``dataset.ctr.zipf_batch``) fetch + push
through a ``ShardedEmbeddingClient`` over N row-range ``PServerShard``
processes; the one-line report carries rows/s, per-shard RPC counters,
and a hard ``bitwise_parity`` check against an identically-driven
single-process ``AsyncSparseEmbedding`` master.

Overload retries (ISSUE 15): ``--retry-overloaded`` honors the typed
``OverloadedError``'s ``retry_after_s`` hint — ONE seeded re-submit
per rejected request, fired between arrivals so the offered stream's
timing is untouched; the report gains ``overload_retries`` and
``retry_success``, so the harness exercises the documented client
contract instead of just recording the hint.

Examples:

    # overload a single synthetic model 3x past its measured capacity,
    # 50ms deadlines, deadline scheduling:
    python tools/load_gen.py --requests 500 --overload 3 --deadline-ms 50

    # absolute rate, two models, mixed priorities, FIFO baseline:
    python tools/load_gen.py --models 2 --rate 400 --scheduling fifo

    # 30% generate traffic through the chained decode lane:
    python tools/load_gen.py --generate-frac 0.3 --rate 50

    # your own exported model dir:
    python tools/load_gen.py --model-dir /models/ranker --rate 100
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_generation(seed, max_len=8, chunk=None):
    """One tiny stepwise NMT decode model (prefill + step programs)
    + its GenerationSpec and scope — the synthetic generate-traffic
    target (the same toy the decode perf gates drive).  ``chunk``
    (ISSUE 14) builds the chunked-prefill program too, for
    --gen-chunk traffic."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import seq2seq
    m = seq2seq.build_step_decode(
        src_dict_dim=50, trg_dict_dim=40, embedding_dim=8,
        encoder_size=16, decoder_size=16, max_len=max_len,
        chunk=chunk)
    m['prefill'].random_seed = seed
    exe = fluid.Executor(fluid.default_place())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['prefill_startup'])
        if chunk is not None:
            exe.run(m['chunk_startup'])
        exe.run(m['step_startup'])
    return m, serving.GenerationSpec.from_model(m), scope


def _build_ctr(seed, vocab):
    """The zipfian-id CTR traffic target (ISSUE 11): a small wide&deep
    CTR inference program (models/ctr) + its scope.  Requests are
    skewed id-batches — zipf mass on a few hot rows, a long tail — the
    sparse-embedding serving shape; the report's ``ctr`` block carries
    rows/s over the offered window."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ctr as ctr_model
    with fluid.unique_name.guard():
        m = ctr_model.build(sparse_dim=vocab, embed_size=16,
                            hidden_sizes=(32, 16), is_sparse=True)
    m['main'].random_seed = seed
    m['startup'].random_seed = seed
    exe = fluid.Executor(fluid.default_place())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(m['startup'])
    return m, scope


def _build_synthetic(seed, dim=16, classes=64):
    """One tiny dense scorer program (f32, softmax head) + its scope —
    the same padding-neutral shape the serving perf gates use."""
    import paddle_tpu.fluid as fluid
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data('x', shape=[-1, dim], dtype='float32')
        pooled = fluid.layers.reduce_sum(x, dim=1)
        pred = fluid.layers.fc(pooled, classes, act='softmax')
    place = fluid.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return prog.clone(for_test=True), pred, scope, place


def _run_fleet(args):
    """--replicas N (ISSUE 17): N replica registries — identical
    synthetic weights (same build seeds) — behind ReplicaServer +
    FleetRouter, serving ONE offered stream.  The report keeps the
    loadgen surface (goodput, percentiles, shed/overload counts) and
    gains ``fleet`` (router dispatch/failover/overload counters, per-
    replica dispatch shares) plus one block per replica with that
    registry's own overload/queue view."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving

    if args.model_dir:
        raise SystemExit('--replicas serves the synthetic fleet; '
                         '--model-dir is single-registry only')
    if args.ctr_frac > 0:
        raise SystemExit('--replicas does not combine with --ctr-frac '
                         '(the ctr report block reads single-registry '
                         'engine internals)')

    def _mk_cfg(**extra):
        return serving.ServingConfig(
            max_batch_size=args.max_batch,
            max_wait_ms=args.max_wait_ms,
            scheduling=args.scheduling,
            admit_queue_depth=args.admit_depth,
            admit_queue_age_ms=args.admit_age_ms, **extra)

    dim = 16
    names = ['syn%d' % i for i in range(max(args.models, 1))]
    gen_names = []
    regs = []
    for _ in range(args.replicas):
        reg = serving.ModelRegistry(config=_mk_cfg())
        for i, name in enumerate(names):
            # same seed per model across replicas: identical weights,
            # so any replica answers any request identically
            prog, pred, scope, _ = _build_synthetic(seed=i + 1, dim=dim)
            reg.load(name, program=prog, feed_names=['x'],
                     fetch_list=[pred], scope=scope)
        regs.append(reg)

    def feed_fn(rng, _dim=dim):
        return {'x': rng.rand(args.rows, args.seq,
                              _dim).astype('float32')}

    gen_feed_fn = None
    if args.generate_frac > 0:
        if not (0.0 < args.generate_frac < 1.0):
            raise SystemExit('--generate-frac must be in (0, 1)')
        for reg in regs:
            gm, gspec, gscope = _build_generation(
                seed=args.seed + 1, max_len=args.gen_max_len,
                chunk=args.gen_chunk)
            reg.load('gen0', program=gm['prefill'],
                     feed_names=gm['prefill_feeds'],
                     fetch_list=gm['prefill_fetches'], scope=gscope,
                     generation=gspec, config=_mk_cfg(
                         decode_pipeline_depth=args.decode_depth,
                         prefill_chunk=(gspec.chunk_width
                                        if args.gen_chunk is not None
                                        else None)))
        gen_names.append('gen0')
        lo = 3
        hi = (max(args.gen_prompt_len, lo + 1)
              if args.gen_prompt_len is not None else 9)

        def gen_feed_fn(rng, _lo=lo, _hi=hi):
            l = int(rng.randint(_lo, _hi + 1))
            return {'src_word_id': fluid.create_lod_tensor(
                rng.randint(2, 50, size=(l, 1)).tolist(), [[l]])}

    classes = []
    fwd_weight = max(1.0 - args.generate_frac, 1e-6) / len(names)
    for name in names:
        if args.priority_frac > 0:
            classes.append(serving.TrafficClass(
                feed_fn, model=name,
                weight=fwd_weight * args.priority_frac,
                deadline_ms=args.deadline_ms, priority=1,
                name=name + ':p1'))
        classes.append(serving.TrafficClass(
            feed_fn, model=name,
            weight=fwd_weight * max(1.0 - args.priority_frac, 1e-6),
            deadline_ms=args.deadline_ms, priority=0,
            name=name + ':p0'))
    for name in gen_names:
        classes.append(serving.TrafficClass(
            gen_feed_fn, model=name, kind='generate',
            weight=args.generate_frac, max_len=args.gen_max_len,
            deadline_ms=args.deadline_ms, name=name + ':generate'))

    servers, router = [], None
    try:
        rng = np.random.RandomState(args.seed)
        for reg in regs:
            reg.start()
            # warm every replica's serving signatures DIRECTLY (the
            # router would only warm whichever replica it picked)
            for name in names:
                reg.infer(name, feed_fn(rng), timeout=600)
            for name in gen_names:
                reg.generate(name, gen_feed_fn(rng), timeout=600)
        servers = [serving.ReplicaServer(reg) for reg in regs]
        router = serving.FleetRouter(servers, timeout=600.0)
        t0 = time.time()
        burst = [router.submit(names[i % len(names)], feed_fn(rng))
                 for i in range(16)]
        for f in burst:
            f.result(600)
        capacity = 16 / max(time.time() - t0, 1e-9)
        rate = args.rate if args.rate else capacity * args.overload
        gen = serving.OpenLoopLoadGen(
            router, classes, rate=rate,
            n_requests=None if args.duration else args.requests,
            duration_s=args.duration, seed=args.seed,
            retry_overloaded=args.retry_overloaded)
        report = gen.run()
        report['measured_capacity_req_s'] = round(capacity, 3)
        fleet = router.metrics()
        report['fleet'] = fleet
        report['replicas'] = {}
        for idx, reg in enumerate(regs):
            metrics = reg.metrics()
            block = {
                'dispatches': fleet['replicas'][idx]['dispatches'],
                'overload_rejects': metrics['overload_rejects'],
                'models': {
                    n: {k: metrics['models'][n][k]
                        for k in ('shed', 'queue_depth', 'compiles',
                                  'p50_latency_ms', 'p99_latency_ms')}
                    for n in names + gen_names
                },
            }
            if gen_names:
                block['decode'] = {
                    n: (reg._entry(n).engine.metrics()['decode'] or {})
                    for n in gen_names
                }
            report['replicas'][idx] = block
    finally:
        if router is not None:
            router.close()
        for srv in servers:
            srv.close()
        for reg in regs:
            reg.stop()
    print(json.dumps(report), flush=True)
    return report


def _run_pserver(args):
    """--pservers N (ISSUE 19): drive the sharded parameter-server
    embedding tier directly — fetch_rows + push_grad over a
    ``ShardedEmbeddingClient`` across N row-range ``PServerShard``
    processes, fed the seeded zipfian id stream
    (``dataset.ctr.zipf_batch``, the one shared skew construction).
    The report carries rows/s for the fetch+push loop, the per-shard
    RPC counters, and ``bitwise_parity`` vs an identically-driven
    single-process ``AsyncSparseEmbedding`` master — the tier's
    correctness bar, measured on the way out."""
    import numpy as np
    from paddle_tpu.dataset import ctr as ctr_data
    from paddle_tpu.distributed import (AsyncSparseEmbedding,
                                        PServerShard,
                                        ShardedEmbeddingClient,
                                        shard_row_ranges)

    if args.model_dir or args.ctr_frac > 0 or args.generate_frac > 0 \
            or args.replicas > 1:
        raise SystemExit('--pservers drives the embedding tier '
                         'directly; it does not combine with '
                         '--model-dir/--ctr-frac/--generate-frac/'
                         '--replicas')
    vocab, dim, lr = args.ctr_vocab, 16, 0.05
    batches = max(args.requests, 1)
    rng = np.random.RandomState(args.seed)
    init = np.random.RandomState(args.seed + 1).rand(
        vocab, dim).astype('float32')
    feeds = [ctr_data.zipf_batch(rng, args.rows, vocab,
                                 hot_frac=args.ctr_hot_frac)
             for _ in range(batches)]
    grads = [np.random.RandomState(1000 + i).rand(
        f['sparse_ids'].size, dim).astype('float32')
        for i, f in enumerate(feeds)]

    shards = [PServerShard({'emb': init[lo:hi]}, row_start=lo, lr=lr)
              for lo, hi in shard_row_ranges(vocab, args.pservers)]
    client = ShardedEmbeddingClient([s.endpoint for s in shards])
    rows_seen = 0
    t0 = time.time()
    for f, g in zip(feeds, grads):
        ids = f['sparse_ids'].ravel()
        client.fetch_rows(ids)
        client.push_grad(ids, g)
        rows_seen += ids.size
    client.drain()
    elapsed = max(time.time() - t0, 1e-9)
    sharded_table = client.table()
    rpc = client.metrics()

    # the single-process master, identically driven: parity is part
    # of the report, not a separate test run
    single = AsyncSparseEmbedding(vocab, dim, lr=lr, table=init)
    for f, g in zip(feeds, grads):
        ids = f['sparse_ids'].ravel()
        single.fetch_rows(ids)
        single.push_grad(ids, g)
    single.drain()
    parity = bool(np.array_equal(sharded_table, single.table()))

    report = {
        'pservers': args.pservers,
        'vocab': vocab,
        'embed_dim': dim,
        'batches': batches,
        'rows_per_batch': int(feeds[0]['sparse_ids'].size),
        'rows_per_sec': round(rows_seen / elapsed, 1),
        'pushed': rpc['pushed'],
        'applied': rpc['applied'],
        'bitwise_parity': parity,
        'rpc_calls': sum(m['calls'] for m in rpc['shards']),
        'rpc_retries': sum(m['retries'] for m in rpc['shards']),
        'rpc_failovers': sum(m['failovers'] for m in rpc['shards']),
        'shard_rows': [s.metrics()['rows'] for s in shards],
    }
    client.close()
    for s in shards:
        s.close()
    single.close()
    assert parity, ('sharded tier diverged from the single-process '
                    'master', report)
    print(json.dumps(report), flush=True)
    return report


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument('--rate', type=float, default=None,
                   help='offered req/s (Poisson intensity); default: '
                        'measured capacity x --overload')
    p.add_argument('--overload', type=float, default=2.0,
                   help='rate multiplier over measured capacity when '
                        '--rate is not given (default 2.0)')
    p.add_argument('--requests', type=int, default=200)
    p.add_argument('--duration', type=float, default=None,
                   help='offered seconds (overrides --requests when set)')
    p.add_argument('--deadline-ms', type=float, default=None,
                   help='per-request deadline; unset = no deadlines '
                        '(everything counts toward goodput)')
    p.add_argument('--priority-frac', type=float, default=0.0,
                   help='fraction of traffic submitted at priority 1 '
                        '(the rest at 0)')
    p.add_argument('--generate-frac', type=float, default=0.0,
                   help='fraction of traffic routed to a synthetic '
                        'generation model\'s decode lane '
                        '(kind=generate; reports decode tokens/s and '
                        'host-syncs-per-token)')
    p.add_argument('--gen-max-len', type=int, default=8,
                   help='generation budget per generate request')
    p.add_argument('--gen-prompt-len', type=int, default=None,
                   help='LONG-prompt generate traffic (ISSUE 14): '
                        'prompts draw lengths up to this bound '
                        '(default: the short 3..9 mix) — the regime '
                        'where monolithic prefill stalls in-flight '
                        'decodes; pair with --gen-chunk to bound the '
                        'stall')
    p.add_argument('--gen-chunk', type=int, default=None,
                   help='serve generate traffic with CHUNKED prefill '
                        '(ServingConfig prefill_chunk=C, rung-'
                        'quantized); the decode report then carries '
                        'prefill_chunks and the bounded stall gauge')
    p.add_argument('--ctr-frac', type=float, default=0.0,
                   help='fraction of traffic routed to a sparse-'
                        'embedding CTR model as seeded ZIPFIAN '
                        'id-batches (ISSUE 11); the report gains a '
                        'ctr block with rows/s')
    p.add_argument('--ctr-vocab', type=int, default=4096,
                   help='CTR embedding vocab for --ctr-frac traffic')
    p.add_argument('--ctr-hot-frac', type=float, default=None,
                   help='sharpen the CTR id skew (ISSUE 12): this '
                        'fraction of lookups folds into a hot set of '
                        'vocab/16 ids — the hot-row embedding cache '
                        'regime (None keeps the plain zipf stream)')
    p.add_argument('--decode-depth', type=int, default=2,
                   help='decode_pipeline_depth of the generation '
                        'model (1 = per-scan-sync baseline)')
    p.add_argument('--models', type=int, default=1,
                   help='number of synthetic models to mix across')
    p.add_argument('--pservers', type=int, default=0,
                   help='drive the sharded parameter-server embedding '
                        'tier (ISSUE 19): fetch+push --requests seeded '
                        'zipfian batches over N row-range shards and '
                        'report rows/s, RPC counters, and bitwise '
                        'parity vs the single-process master')
    p.add_argument('--replicas', type=int, default=1,
                   help='serve through N replica registries behind '
                        'the fleet router (ISSUE 17); the report '
                        'gains fleet + per-replica blocks')
    p.add_argument('--model-dir', default=None,
                   help='serve this save_inference_model dir instead '
                        'of synthetic models (single feed)')
    p.add_argument('--rows', type=int, default=4,
                   help='rows per request')
    p.add_argument('--seq', type=int, default=12,
                   help='synthetic request trailing extent')
    p.add_argument('--max-batch', type=int, default=16)
    p.add_argument('--max-wait-ms', type=float, default=2.0)
    p.add_argument('--scheduling', choices=['edf', 'fifo'], default='edf')
    p.add_argument('--admit-depth', type=int, default=None,
                   help='overload admission watermark: queue depth')
    p.add_argument('--admit-age-ms', type=float, default=None,
                   help='overload admission watermark: oldest queue age')
    p.add_argument('--retry-overloaded', action='store_true',
                   help='honor the OverloadedError.retry_after_s hint '
                        'with ONE seeded re-submit per rejected '
                        'request (ISSUE 15); the report gains '
                        'overload_retries/retry_success')
    p.add_argument('--seed', type=int, default=0)
    args = p.parse_args(argv)

    import numpy as np
    import paddle_tpu.fluid as fluid  # noqa: F401 (registers flags)
    from paddle_tpu import serving

    if args.pservers > 0:
        return _run_pserver(args)
    if args.replicas > 1:
        return _run_fleet(args)

    cfg = serving.ServingConfig(
        max_batch_size=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        scheduling=args.scheduling,
        admit_queue_depth=args.admit_depth,
        admit_queue_age_ms=args.admit_age_ms)
    reg = serving.ModelRegistry(config=cfg)
    names = []
    if args.model_dir:
        reg.load('model', args.model_dir)
        names.append('model')
        feed_name = reg._entry('model').engine._feed_names[0]

        def feed_fn(rng, _dim=None):
            # the exported model declares its own feed shape; fall back
            # to a flat f32 vector when dims are dynamic
            var = (reg._entry('model').engine._program
                   .global_block().vars[feed_name])
            shape = [int(d) if int(d) > 0 else args.seq
                     for d in var.shape]
            shape[0] = args.rows
            return {feed_name: rng.rand(*shape).astype('float32')}
    else:
        dim = 16
        for i in range(max(args.models, 1)):
            name = 'syn%d' % i
            prog, pred, scope, place = _build_synthetic(seed=i + 1,
                                                        dim=dim)
            reg.load(name, program=prog, feed_names=['x'],
                     fetch_list=[pred], scope=scope)
            names.append(name)

        def feed_fn(rng, _dim=dim):
            return {'x': rng.rand(args.rows, args.seq,
                                  _dim).astype('float32')}

    gen_names = []
    if args.generate_frac > 0:
        if not (0.0 < args.generate_frac < 1.0):
            raise SystemExit('--generate-frac must be in (0, 1)')
        gm, gspec, gscope = _build_generation(seed=args.seed + 1,
                                              max_len=args.gen_max_len,
                                              chunk=args.gen_chunk)
        gcfg = serving.ServingConfig(
            max_batch_size=args.max_batch,
            max_wait_ms=args.max_wait_ms,
            scheduling=args.scheduling,
            decode_pipeline_depth=args.decode_depth,
            prefill_chunk=(gspec.chunk_width
                           if args.gen_chunk is not None else None))
        reg.load('gen0', program=gm['prefill'],
                 feed_names=gm['prefill_feeds'],
                 fetch_list=gm['prefill_fetches'], scope=gscope,
                 generation=gspec, config=gcfg)
        gen_names.append('gen0')
        lo = 3
        hi = (max(args.gen_prompt_len, lo + 1)
              if args.gen_prompt_len is not None else 9)

        def gen_feed_fn(rng, _lo=lo, _hi=hi):
            import paddle_tpu.fluid as fluid
            l = int(rng.randint(_lo, _hi + 1))
            return {'src_word_id': fluid.create_lod_tensor(
                rng.randint(2, 50, size=(l, 1)).tolist(), [[l]])}

    ctr_names = []
    if args.ctr_frac > 0:
        if not (0.0 < args.ctr_frac < 1.0) or \
                args.ctr_frac + args.generate_frac >= 1.0:
            raise SystemExit('--ctr-frac must be in (0, 1) and leave a '
                             'forward share with --generate-frac')
        cm, cscope = _build_ctr(seed=args.seed + 2,
                                vocab=args.ctr_vocab)
        reg.load('ctr0', program=cm['test'], feed_names=cm['feeds'],
                 fetch_list=[cm['prediction']], scope=cscope)
        ctr_names.append('ctr0')

        def ctr_feed_fn(rng, _v=args.ctr_vocab, _rows=args.rows,
                        _hot=args.ctr_hot_frac):
            from paddle_tpu.dataset import ctr as ctr_data
            return ctr_data.zipf_batch(rng, _rows, _v, hot_frac=_hot)

    classes = []
    # the forward share splits across the forward models: per-model
    # weights must sum to (1 - generate_frac - ctr_frac) or the special
    # classes' documented shares of the offered stream dilute as
    # --models grows
    fwd_weight = max(1.0 - args.generate_frac - args.ctr_frac, 1e-6) \
        / max(len(names), 1)
    for name in names:
        if args.priority_frac > 0:
            classes.append(serving.TrafficClass(
                feed_fn, model=name,
                weight=fwd_weight * args.priority_frac,
                deadline_ms=args.deadline_ms, priority=1,
                name=name + ':p1'))
        classes.append(serving.TrafficClass(
            feed_fn, model=name,
            weight=fwd_weight * max(1.0 - args.priority_frac, 1e-6),
            deadline_ms=args.deadline_ms, priority=0,
            name=name + ':p0'))
    for name in gen_names:
        classes.append(serving.TrafficClass(
            gen_feed_fn, model=name, kind='generate',
            weight=args.generate_frac, max_len=args.gen_max_len,
            deadline_ms=args.deadline_ms, name=name + ':generate'))
    for name in ctr_names:
        classes.append(serving.TrafficClass(
            ctr_feed_fn, model=name, weight=args.ctr_frac,
            deadline_ms=args.deadline_ms, name=name + ':ctr'))

    with reg:
        # warm every model's serving signature, then measure capacity
        # with a short closed burst (the rate anchor for --overload)
        rng = np.random.RandomState(args.seed)
        for name in names:
            reg.infer(name, feed_fn(rng), timeout=600)
        for name in gen_names:
            # warm the prefill rungs + the decode-scan executable
            reg.generate(name, gen_feed_fn(rng), timeout=600)
        for name in ctr_names:
            reg.infer(name, ctr_feed_fn(rng), timeout=600)
        # decode baseline AFTER warmup: the report's tokens/s and
        # host-syncs-per-token must cover the offered stream only
        decode_base = {
            name: dict(reg._entry(name).engine.metrics()['decode']
                       or {})
            for name in gen_names
        }
        ctr_base = {
            name: int(reg._entry(name).engine.metrics()['rows'])
            for name in ctr_names
        }
        t0 = time.time()
        burst = []
        deadline = time.time() + 60.0
        for i in range(16):
            while True:
                try:
                    burst.append(reg.submit(names[i % len(names)],
                                            feed_fn(rng)))
                    break
                except serving.OverloadedError as e:
                    # a tight --admit-depth can reject the closed
                    # calibration burst itself: under
                    # --retry-overloaded honor the hint (the
                    # documented client contract), bounded by a
                    # deadline so a wedged registry surfaces the
                    # typed error instead of hanging the CLI
                    if not args.retry_overloaded or \
                            time.time() >= deadline:
                        raise
                    time.sleep(max(e.retry_after_s, 1e-3))
        for f in burst:
            f.result(600)
        capacity = 16 / max(time.time() - t0, 1e-9)
        rate = args.rate if args.rate else capacity * args.overload
        gen = serving.OpenLoopLoadGen(
            reg, classes, rate=rate,
            # --duration overrides --requests (which always has its
            # default); the loadgen only reads duration_s when
            # n_requests is None
            n_requests=None if args.duration else args.requests,
            duration_s=args.duration, seed=args.seed,
            retry_overloaded=args.retry_overloaded)
        report = gen.run()
        report['measured_capacity_req_s'] = round(capacity, 3)
        metrics = reg.metrics()
        report['registry'] = {
            'overload_rejects': metrics['overload_rejects'],
            'models': {
                n: {k: metrics['models'][n][k]
                    for k in ('shed', 'queue_depth', 'compiles',
                              'p50_latency_ms', 'p99_latency_ms')}
                for n in names + gen_names + ctr_names
            },
        }
        if ctr_names:
            # zipfian CTR traffic deliverable (ISSUE 11): embedding
            # id-rows served per second over the measured window
            report['ctr'] = {}
            for name in ctr_names:
                rows = int(reg._entry(name).engine.metrics()['rows']) \
                    - ctr_base[name]
                report['ctr'][name] = {
                    'rows': rows,
                    'rows_per_s': round(
                        rows / max(report['elapsed_s'], 1e-9), 3),
                    'vocab': args.ctr_vocab,
                }
        if gen_names:
            # decode-lane deliverables (ISSUE 9): tokens/s over the
            # measured window and host-syncs-per-token — the number
            # the chained lane (decode_pipeline_depth >= 2) drives
            # toward zero vs one-per-scan on the synced baseline
            report['decode'] = {}
            for name in gen_names:
                d = reg._entry(name).engine.metrics()['decode'] or {}
                base = decode_base.get(name) or {}
                tokens = (d.get('tokens') or 0) - \
                    (base.get('tokens') or 0)
                syncs = (d.get('host_syncs') or 0) - \
                    (base.get('host_syncs') or 0)
                report['decode'][name] = {
                    'tokens': tokens,
                    'tokens_per_s': round(
                        tokens / max(report['elapsed_s'], 1e-9), 3),
                    'host_syncs': syncs,
                    'host_syncs_per_token': (
                        round(syncs / tokens, 4) if tokens else None),
                    'chain_flushes': (d.get('chain_flushes') or 0) -
                    (base.get('chain_flushes') or 0),
                    'decode_pipeline_depth': args.decode_depth,
                    # chunked prefill (ISSUE 14): chunk dispatches over
                    # the measured window + the cumulative inter-token
                    # stall gauge (worker-cycle units; bounded by one
                    # chunk under --gen-chunk, by the longest prompt
                    # without it)
                    'prefill_chunks': (d.get('prefill_chunks') or 0) -
                    (base.get('prefill_chunks') or 0),
                    'prefill_chunk': args.gen_chunk,
                    'max_decode_stall_cycles':
                        d.get('max_decode_stall_cycles'),
                }
    reg.stop()
    print(json.dumps(report), flush=True)
    return report


if __name__ == '__main__':
    main()
