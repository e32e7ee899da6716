"""Overlapped input pipeline: reader-fed multi-step dispatch with
double-buffered device staging.

The reference Fluid stack overlaps host decode/transfer with device
compute through py_reader + double_buffer (executor.cc:321-339 pulls
fresh data every iteration; create_double_buffer_reader_op.cc stages the
next batch ahead).  Our multi-step scan path (`run_multi`) removed the
per-step dispatch tax but left feed preparation ON the dispatch critical
path: every K-step block was stacked and `device_put` synchronously
before the dispatch could issue.

`FeedPipeline` retires that tax:

  1. a background STAGING thread drains K fresh minibatches per block
     from a py_reader feeder (or any iterator of feed dicts), prepares
     them (LoD -> padded + @SEQLEN), stacks them into ONE scanned
     [K, ...] block, and places it on device — plain `device_put` for
     `Executor`, dp-sharded placement via the compiled block's
     `scanned_sharding` (parallel.scanned_spec) for `ParallelExecutor`;
  2. the DISPATCH loop issues each staged block through the executor's
     async front half (`_dispatch_multi_scanned`, one body and one
     signature for both executors — no host sync), so
     while dispatch N computes on device, block N+1 is already being
     staged and block N-1's fetches are being delivered;
  3. a bounded ``pipeline_depth`` of dispatches stays in flight (2 =
     double buffering); the scanned block is DONATED on device so two
     in-flight dispatches recycle the feed buffer instead of holding
     2x K batches alive;
  4. feed-stall seconds, overlap ratio and queue depth surface through
     the `fluid.profiler` metrics-source registry (and ``pipeline/``
     timeline spans, rendered by tools/timeline.py in a ``:pipeline``
     row).

`run_multi(reader=..., steps=K)` is the synchronous one-dispatch form:
it drains K DISTINCT batches from the reader (matching the reference
per-iteration pull) and trains on them as one scanned dispatch — scope
state lands exactly as K sequential run() calls over the same batch
stream would leave it.
"""

import collections
import threading
import time
import queue as _queue

from . import core
from . import profiler as _profiler
from . import trace as _trace
from .executor import (prepare_feed_arrays, feed_signature, stack_steps,
                       _current_scope)
from .framework import default_main_program, Variable

__all__ = ['FeedPipeline', 'FeedPipelineError', 'drain_reader_feed_list']


class FeedPipelineError(RuntimeError):
    """A FeedPipeline staging-thread failure (the source reader or the
    stager itself raised).  Raised at most ONCE per pipeline — by the
    iteration loop when it hits the EOF sentinel, or by ``close()`` for
    an error that raced the close and was never delivered — with the
    original exception as ``__cause__``."""


def check_reader_args(what, feed, feed_list, steps=None,
                      require_steps=False):
    """Shared reader-mode argument validation for the four reader-fed
    multi paths (Executor/ParallelExecutor × run_multi/run_eval_multi):
    reader= is exclusive with feed=/feed_list=, and the EVAL paths
    (require_steps) have no default step count — a drain-contract
    change must not leave the four sites validating differently."""
    if feed is not None or feed_list is not None:
        raise ValueError('%s: pass reader= OR feed/feed_list' % what)
    if require_steps and (steps is None or int(steps) < 1):
        raise ValueError('%s: reader= needs steps >= 1, got %r'
                         % (what, steps))

_PIPELINE_SEQ = [0]
_PIPELINE_SEQ_LOCK = threading.Lock()

# most recent dispatches kept in FeedPipeline.dispatch_log — far above
# any contract test's horizon, bounded for open-ended pipelines
_DISPATCH_LOG_CAP = 4096


def find_read_op(program, reader=None):
    """The program's read op (optionally the one consuming ``reader``).
    Reader-driven run_multi composes with exactly ONE reader: a program
    pulling from several queues has no single batch stream to contract
    against K sequential run() calls."""
    ops = [op for op in program.global_block().ops if op.type == 'read']
    if reader is not None:
        name = reader.name if isinstance(reader, Variable) else str(reader)
        ops = [op for op in ops if op.input('Reader')[0] == name]
        if not ops:
            raise RuntimeError(
                'run_multi(reader=...): the program has no read op '
                'consuming reader %r' % name)
    if not ops:
        raise RuntimeError(
            'run_multi(reader=...): the program is not reader-fed — '
            'pass feed= or feed_list= instead')
    if len(ops) > 1:
        raise RuntimeError(
            'run_multi(reader=...): the program reads from %d readers; '
            'reader-driven multi-step dispatch supports exactly one'
            % len(ops))
    return ops[0]


def _feeder_of(program, reader, place=None):
    """(feeder, output names) for the program's read op; binds the
    prefetch target to the consuming executor like run()'s pop path."""
    from .layers import io as layers_io
    op = find_read_op(program, reader)
    reader_name = op.input('Reader')[0]
    feeder = layers_io.get_reader_feeder(reader_name)
    if feeder is None:
        raise RuntimeError('no py_reader registered for %r' % reader_name)
    if place is not None:
        feeder._executor_place = place
    return feeder, list(op.output('Out'))


def drain_reader_feed_list(program, reader, steps, place=None):
    """Pop up to ``steps`` FRESH minibatches from the program's reader
    queue, as a run_multi-shaped feed_list of PREPARED feed dicts (the
    reference multi-iteration loop pulls fresh data every iteration,
    executor.cc:321-339).  The drain stops at a shape-bucket boundary —
    a ragged drop_last=False tail batch is PUSHED BACK onto the stream
    for the next call instead of crashing the scan's uniformity check
    (and losing the drained prefix).  A stream ending mid-block returns
    the shorter tail; an already-exhausted reader raises
    core.EOFException exactly like run()."""
    # NOTE twin of FeedPipeline._next_block's drain loop — same
    # pop/prepare/bucket-boundary contract, feeder.push_back as the
    # leftover mechanism (the next CALL re-drains the same feeder) and
    # pre-pad grouping (padding happens downstream in PE.run_multi's
    # feed_list normalize).  A boundary-semantics change must land in
    # BOTH.
    feeder, names = _feeder_of(program, reader, place)
    out, sig0 = [], None
    for _ in range(int(steps)):
        batch = feeder.pop()
        if batch is None:
            break
        prepared = prepare_feed_arrays(dict(zip(names, batch)))
        sig = feed_signature(prepared)
        if out and sig != sig0:
            feeder.push_back(batch)
            break
        sig0 = sig
        out.append(prepared)
    if not out:
        raise core.EOFException(
            'reader is exhausted — call reader.reset() and '
            'reader.start() for the next pass')
    return out


class _Block(object):
    """One staged K-step scan block."""

    __slots__ = ('steps', 'sig_feed', 'scanned', 'placed', 'real',
                 'padded', 'batch_feed_names', 'indices', 'exchanges')

    def __init__(self, steps, sig_feed, scanned, placed, real=0, padded=0,
                 batch_feed_names=None, indices=None):
        self.steps = steps
        self.sig_feed = sig_feed  # per_step[0]: keys the compile cache
        self.scanned = scanned  # {name: [K, ...]}
        self.placed = placed
        # the LAST step's real/padded row counts (fetches come from the
        # last iteration): batch-led fetches of a dp-padded lot trim
        # back to the real rows, like PE.run_multi's
        self.real = real
        self.padded = padded
        # pre-pad provenance from the padding pass: which feeds are
        # batch-led, so an aux feed whose rows merely coincide with the
        # padded lot size is never masked or trimmed (PR 1 contract)
        self.batch_feed_names = batch_feed_names
        # source ordinals of the drained batches this block carries —
        # the bucketed variant reorders across buckets, and
        # ``FeedPipeline.dispatch_log`` makes the realized training
        # order observable (and contract-testable)
        self.indices = indices
        # (cache, exchange) pairs staged by the prefetch hook (ISSUE
        # 12): the dispatch loop applies them right before this block's
        # dispatch — the host fetch they started OVERLAPS the previous
        # dispatch's device compute
        self.exchanges = ()


class FeedPipeline(object):
    """Reader-fed multi-step training with double-buffered device
    staging: block N+1 stages on a background thread while dispatch N
    computes; up to ``pipeline_depth`` dispatches stay in flight.

    executor: `fluid.Executor` or `fluid.ParallelExecutor`.
    fetch_list: fetch targets (the LAST step of each dispatch delivers).
    reader: a py_reader Variable the program consumes via read_file, OR
    source: any iterator of feed dicts (the Trainer's DataFeeder form).
    steps: minibatches per dispatch (the scan length K).
    pipeline_depth: staged blocks ahead + dispatches in flight (2 =
        double buffering).
    bucketed: route each drained batch to its shape-bucket's OPEN
        block instead of closing a block at every bucket boundary
        (ISSUE 5) — one scan executable per (batch, trailing) bucket,
        so a length-skewed reader pipelines full K-step blocks without
        an upstream bucketing pass.  Batches stay in reader order
        WITHIN a bucket; dispatches issue in bucket-completion order,
        recorded per dispatch in ``dispatch_log`` (source ordinals).
    max_open_buckets: bound on concurrently accumulating buckets; the
        least-recently-fed one flushes early as a shorter block beyond
        it (the boundary push-back generalized to bounded memory).
    watchdog_stall_s: feed-stall threshold (seconds) for the trace
        watchdog (ISSUE 6) — a started pipeline registers a probe over
        how long the dispatch loop has currently been blocked on the
        staging queue; crossing it dumps the flight recorder.  None
        (default) registers no probe.  With ``embed_caches`` set, the
        same threshold also arms a prefetch-stall probe per cache
        (how long the dispatch loop has been waiting on a late host
        row fetch).
    embed_caches: two-tier embedding stores (ISSUE 12,
        ``distributed.CachedEmbeddingTable``) — the STAGING thread
        remaps each block's id feeds to slab slots and starts the
        block's host row exchange (miss fetch + dirty-eviction
        writeback) while the PREVIOUS dispatch still computes; the
        dispatch loop applies the exchange just before the block
        dispatches.  A fetch that has not landed in time is a counted
        ``prefetch_stall``, never a correctness hazard.

    Iterate the pipeline to drive it: each item is one dispatch's
    converted last-step fetches.  ``metrics()`` snapshots feed-stall
    seconds, overlap ratio and queue depth; inside a profiler window the
    same snapshot rides the ``.events.json`` sidecar and ``pipeline/``
    spans land in the timeline (`tools/timeline.py` renders them in a
    ``:pipeline`` row)."""

    def __init__(self, executor, fetch_list, program=None, reader=None,
                 source=None, steps=1, pipeline_depth=2, scope=None,
                 return_numpy=True, name=None, bucketed=False,
                 max_open_buckets=4, watchdog_stall_s=None,
                 embed_caches=None, on_delivered=None):
        if (reader is None) == (source is None):
            raise ValueError('FeedPipeline: pass reader= OR source=')
        if int(steps) < 1:
            raise ValueError('FeedPipeline: steps must be >= 1')
        if int(pipeline_depth) < 1:
            raise ValueError('FeedPipeline: pipeline_depth must be >= 1')
        if int(max_open_buckets) < 1:
            raise ValueError('FeedPipeline: max_open_buckets must be >= 1')
        self._exe = executor
        self._is_spmd = hasattr(executor, '_mesh')
        if self._is_spmd:
            if program is not None or scope is not None:
                raise ValueError(
                    'FeedPipeline: a ParallelExecutor runs its OWN '
                    'main_program in its own scope — drop program=/'
                    'scope=, or build the ParallelExecutor over them')
            self._program = executor._main_program
            self._scope = executor._scope
            # lots whose batch is not divisible by the dp extent pad
            # with masked samples on the staging thread (the PR 1
            # machinery), exactly like PE.run_multi's explicit lots
            self._pad = executor._pad_ragged
        else:
            self._program = (program if program is not None
                             else default_main_program())
            self._scope = scope if scope is not None else _current_scope()
        self._fetch_list = fetch_list
        self.steps = int(steps)
        self.pipeline_depth = int(pipeline_depth)
        self._return_numpy = return_numpy
        if reader is not None:
            place = None if self._is_spmd else self._exe.place
            feeder, names = _feeder_of(self._program, reader, place)
            self._next_batch = self._reader_batches(feeder, names)
        else:
            self._next_batch = iter(source)
        self._staged = _queue.Queue(maxsize=self.pipeline_depth)
        self._inflight = []
        self._pending = None  # a prepared batch held across a bucket split
        self._embed_caches = list(embed_caches or [])
        for cache in self._embed_caches:
            cache.check_scope(self._scope, 'FeedPipeline')
        # bucketed variant (ISSUE 5): instead of CLOSING a block at a
        # shape-bucket boundary, route each drained batch to its
        # bucket's open block — one scan executable per (batch,
        # trailing) bucket — so a length-skewed reader pipelines
        # without an upstream bucketing pass.  ``_open`` maps feed
        # signature -> the bucket's accumulating per-step list; at most
        # ``max_open_buckets`` stay open (the LRU one flushes early as
        # a shorter block — the bucket-boundary push-back generalized:
        # bounded staging memory instead of a pushed-back tail).
        self.bucketed = bool(bucketed)
        self.max_open_buckets = int(max_open_buckets)
        self._open = collections.OrderedDict()
        self._drained = 0  # source ordinal of the next drained batch
        # realized training order (bucketed mode only): one list of
        # source ordinals per dispatch, appended when the dispatch
        # issues — non-bucketed dispatches stay in reader order, so
        # nothing is recorded there.  Bounded: an open-ended source=
        # pipeline keeps only the most recent window instead of
        # growing forever
        self.dispatch_log = collections.deque(maxlen=_DISPATCH_LOG_CAP)
        # delivery hook (ISSUE 13): called AFTER a dispatch's fetches
        # convert (i.e. the dispatch has synced) with the dispatch's
        # source ordinals and converted fetches — the elastic job's
        # ack-after-sync point (a task is reported finished only once
        # the dispatch that trained on it has completed on device)
        self._on_delivered = on_delivered
        self._placer = None  # set before the first placed block
        self._error = None
        self._error_delivered = False
        self._closed = False
        self._thread = None
        self._started = False
        # trace watchdog (ISSUE 6): a feed-stall probe over how long
        # the dispatch loop has CURRENTLY been waiting on the staging
        # queue — a stall crossing the threshold dumps the flight
        # recorder (what the stager and the executors had in flight)
        self.watchdog_stall_s = (float(watchdog_stall_s)
                                 if watchdog_stall_s is not None else None)
        self._watchdog_probe = None
        self._watchdog_age_fn = None
        self._waiting_since = None
        # metrics: the staging thread owns stage_*, the dispatch loop
        # owns the rest — disjoint keys, snapshot() copies
        self._m = {'blocks_staged': 0, 'stage_s': 0.0, 'stage_s_first': 0.0,
                   'dispatches': 0, 'steps_dispatched': 0,
                   'feed_stall_s': 0.0, 'partial_blocks': 0, 'eof': False,
                   'bucket_early_flushes': 0, 'feed_devices': 0}
        with _PIPELINE_SEQ_LOCK:
            _PIPELINE_SEQ[0] += 1
            seq = _PIPELINE_SEQ[0]
        self.name = name or ('feed-pipeline-%d' % seq)
        # sidecar metrics source, weakly bound like the serving engine's
        # so a profiled window dumps the snapshot without keeping dead
        # pipelines alive
        import weakref
        ref = weakref.ref(self)
        self._metrics_fn = lambda: (ref().metrics() if ref() else None)
        self._metrics_key = _profiler.register_metrics_source(
            self.name, self._metrics_fn)
        weakref.finalize(self, _profiler.unregister_metrics_source,
                         self._metrics_key, self._metrics_fn)

    # ---- sources -------------------------------------------------------

    @staticmethod
    def _reader_batches(feeder, names):
        while True:
            batch = feeder.pop()
            if batch is None:
                return
            yield dict(zip(names, batch))

    # ---- staging thread ------------------------------------------------

    def _put(self, item):
        while not self._closed:
            try:
                self._staged.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def _next_block(self):
        # NOTE twin of drain_reader_feed_list's drain loop — same
        # pop/prepare/bucket-boundary contract, different leftover
        # mechanism (self._pending here vs feeder.push_back there,
        # because a plain `source=` iterator has nothing to push back
        # to) and post-pad grouping here (the sync path's padding
        # happens downstream in PE.run_multi's feed_list normalize).
        # A boundary-semantics change must land in BOTH.
        per_step, sig0, last_rp, bn0, indices = [], None, (0, 0), None, []
        while len(per_step) < self.steps:
            if self._closed:
                # close() mid-drain: stop consuming the source — a
                # zombie stager finishing its K-batch block would
                # silently eat up to `steps` more reader batches from
                # a pass the user may keep reading manually
                return None
            if self._pending is not None:
                (prepared, rp, bn, idx), self._pending = \
                    self._pending, None
            else:
                drained = self._drain_prepared()
                if drained is None:
                    break
                prepared, rp, bn, idx = drained
            sig = feed_signature(prepared)
            if per_step and sig != sig0:
                # shape-bucket boundary (e.g. a ragged FINAL batch,
                # drop_last=False): close this block and start the next
                # one at the new signature — a shorter tail block is
                # one extra (steps, shape) compile, never a crash
                self._pending = (prepared, rp, bn, idx)
                break
            sig0 = sig
            if not per_step:
                bn0 = bn  # the block's compile records step 0's view
            per_step.append(prepared)
            indices.append(idx)
            last_rp = rp
        if not per_step:
            return None
        return self._finish_block(per_step, last_rp, bn0, indices)

    def _drain_prepared(self):
        """Pop + prepare (+ dp-pad under SPMD) ONE source batch; None at
        EOF.  Returns (prepared, (real, padded), batch_names, ordinal)."""
        try:
            batch = next(self._next_batch)
        except StopIteration:
            return None
        prepared = prepare_feed_arrays(dict(batch))
        rp, bn = (0, 0), None
        if self._is_spmd:
            # dp-pad ragged lots (masked samples) BEFORE the bucket
            # grouping, so a non-divisible tail becomes its own padded
            # block instead of failing the sharded device_put on the
            # staging thread; the report records pre-pad batch-led
            # provenance
            rpt = {}
            prepared, real, padded = self._pad(prepared, report=rpt)
            rp, bn = (real, padded), rpt.get('batch_names')
        idx = self._drained
        self._drained += 1
        return prepared, rp, bn, idx

    def _finish_block(self, per_step, last_rp, bn0, indices):
        # the prefetch hook (ISSUE 12): remap each cache's id feeds to
        # slab slots and START the block's host row exchange HERE, on
        # the staging thread — the master-table fetch runs while the
        # previous dispatch computes on device
        exchanges = [(cache, cache.stage_feed_list(per_step,
                                                   steps=len(per_step)))
                     for cache in self._embed_caches]
        # uniformity holds by construction: every step shares one sig
        stacked = {n: stack_steps([fa[n] for fa in per_step])
                   for n in per_step[0]}
        placer = self._placer
        if placer is not None:
            stacked = {n: placer(n, v) for n, v in stacked.items()}
        block = _Block(len(per_step), per_step[0], stacked,
                       placer is not None, last_rp[0], last_rp[1], bn0,
                       indices)
        block.exchanges = exchanges
        return block

    def _pop_open(self, last=False):
        """Flush one open bucket as a (possibly shorter) block — always
        the least-recently-FED one (appends move_to_end, so the front
        of ``_open`` is the stalest bucket), both under the
        max_open_buckets bound and when EOF drains the partials."""
        _, entry = self._open.popitem(last=last)
        per_step, last_rp, bn0, indices = entry
        return self._finish_block(per_step, last_rp, bn0, indices)

    def _next_block_bucketed(self):
        """The bucketed drain (ISSUE 5): route each drained batch to
        its feed-signature bucket's OPEN block; a bucket reaching
        ``steps`` emits.  More than ``max_open_buckets`` distinct
        shapes in flight flush the least-recently-fed bucket early as
        a shorter block (bounded staging memory — the generalization
        of the non-bucketed path's boundary push-back); EOF flushes
        the remaining partials in least-recently-fed order.  Interleaved
        shape-skewed readers thus pipeline full K-step blocks — one
        scan executable per (batch, trailing) bucket — instead of
        fragmenting into 1-step blocks at every boundary."""
        while True:
            if self._closed:
                return None
            drained = self._drain_prepared()
            if drained is None:
                break
            prepared, rp, bn, idx = drained
            sig = feed_signature(prepared)
            entry = self._open.get(sig)
            if entry is None:
                entry = self._open[sig] = [[], (0, 0), bn, []]
            entry[0].append(prepared)
            entry[1] = rp
            entry[3].append(idx)
            self._open.move_to_end(sig)
            if len(entry[0]) >= self.steps:
                del self._open[sig]
                return self._finish_block(*entry)
            if len(self._open) > self.max_open_buckets:
                self._m['bucket_early_flushes'] += 1
                return self._pop_open(last=False)
        if self._open:
            return self._pop_open(last=False)
        return None

    def _stage_loop(self):
        first = True
        try:
            while not self._closed:
                t0 = time.time()
                # one block: source drain, LoD padding, stacking and the
                # device_put (a block the source ends on records nothing
                # in the older tables)
                with _trace.span('paddle_tpu/feed/stage',
                                 steps=self.steps) as sp:
                    block = (self._next_block_bucketed() if self.bucketed
                             else self._next_block())
                    if block is not None:
                        sp.event = 'pipeline/stage[x%d]' % block.steps
                if block is None:
                    self._m['eof'] = True
                    break
                dt = time.time() - t0
                self._m['blocks_staged'] += 1
                self._m['stage_s'] += dt
                if first:
                    self._m['stage_s_first'] = dt
                    first = False
                if block.steps < self.steps:
                    self._m['partial_blocks'] += 1
                if not self._put(block):
                    return
        except BaseException as e:
            self._error = e
        finally:
            self._put(None)

    # ---- dispatch loop -------------------------------------------------

    def _feed_stall_age(self):
        """Seconds the dispatch loop has been blocked on the staging
        queue RIGHT NOW (None when it is not waiting) — the watchdog's
        feed-stall probe."""
        since = self._waiting_since
        return (time.time() - since) if since is not None else None

    def start(self):
        if self._closed:
            raise RuntimeError('FeedPipeline is closed')
        if not self._started:
            self._started = True
            self._thread = threading.Thread(
                target=self._stage_loop, name=self.name, daemon=True)
            self._thread.start()
            if self.watchdog_stall_s is not None and \
                    self._watchdog_probe is None:
                # weak closure + GC finalizer, like the metrics source:
                # the global watchdog must not pin a dropped pipeline
                import weakref
                ref = weakref.ref(self)

                def age(ref=ref):
                    pipe = ref()
                    return pipe._feed_stall_age() if pipe else None

                self._watchdog_probe = _trace.watchdog.register(
                    'pipeline/%s/feed_stall' % self.name, age,
                    self.watchdog_stall_s)
                self._watchdog_age_fn = age
                weakref.finalize(self, _trace.watchdog.unregister,
                                 self._watchdog_probe, age)
                from ..distributed.embed_cache import register_stall_probe
                for cache in self._embed_caches:
                    # a late host row fetch stalls the dispatch loop the
                    # same way a slow reader does — same threshold, its
                    # own probe name (ISSUE 12)
                    register_stall_probe(
                        self,
                        'pipeline/%s/embed_cache/%s/prefetch_stall'
                        % (self.name, cache.var),
                        cache, self.watchdog_stall_s)
        return self

    def _ensure_placer(self, block):
        """Resolve the executor-specific device placement for scanned
        blocks.  `Executor` stages to its place; `ParallelExecutor`
        needs the compiled block's per-feed GSPMD sharding shifted
        right of the steps axis (`parallel.scanned_spec`), which only
        exists after the first resolve — so the FIRST block is placed
        here on the dispatch thread, and every later block is placed by
        the staging thread."""
        import jax
        if self._placer is not None:
            return
        if self._is_spmd:
            fetch_names = self._exe._fetch_names(self._fetch_list)
            compiled = self._exe._resolve(fetch_names, block.sig_feed,
                                          block.batch_feed_names)

            def placer(n, v):
                try:
                    sharding = compiled.scanned_sharding(n)
                except KeyError:
                    # a name outside the first resolve's feed set (the
                    # @SAMPLE_MASK a padded tail block adds): batch-led
                    # by construction, so the default dp spec applies
                    from jax.sharding import NamedSharding, \
                        PartitionSpec as P
                    from ..parallel.api import scanned_spec
                    spec = (P(compiled.batch_axis) if compiled.batch_axis
                            in compiled.mesh.axis_names else P())
                    sharding = NamedSharding(compiled.mesh,
                                             scanned_spec(spec))
                return jax.device_put(v, sharding)

            self._placer = placer
        else:
            dev = self._exe.place.jax_device()
            self._placer = lambda n, v, _dev=dev: jax.device_put(v, _dev)

    def _dispatch(self, block):
        # the executors add their own 'multi_dispatch' flight records;
        # this one carries the PIPELINE's view (block provenance) so a
        # stall dump shows which source batches were in flight
        _trace.flight_recorder.record(
            'pipeline_dispatch', pipeline=self.name, steps=block.steps,
            indices=list(block.indices or []),
            trace_id=getattr(_trace.current(), 'trace_id', None))
        self._ensure_placer(block)
        if not block.placed:
            block.scanned = {n: self._placer(n, v)
                             for n, v in block.scanned.items()}
            block.placed = True
        # devices the scanned block is really laid out over: under a
        # mesh, 1 would mean every batch landed on one chip
        self._m['feed_devices'] = max(
            (len(v.sharding.device_set) for v in block.scanned.values()),
            default=0)
        for cache, ex in block.exchanges:
            # the overlapped prefetch's device half: evicted dirty rows
            # gather out, fetched miss rows scatter in — right before
            # the dispatch that needs them (late fetch = counted stall)
            cache.apply(ex)
        fetches, compiled = self._exe._dispatch_multi_scanned(
            self._fetch_list, block.sig_feed, block.scanned, block.steps,
            batch_feed_names=block.batch_feed_names,
            program=self._program, scope=self._scope)
        self._m['dispatches'] += 1
        self._m['steps_dispatched'] += block.steps
        if self.bucketed:
            # only the bucketed variant reorders across buckets; the
            # sequential path's order is trivial, and an open-ended
            # source= pipeline must not grow a log it never reads
            self.dispatch_log.append(list(block.indices or []))
        self._inflight.append((fetches, compiled, block, time.time()))

    def _drain_one(self):
        fetches, compiled, block, t0 = self._inflight.pop(0)
        # the host's wait for the device, and the fetch conversion
        with _trace.span('paddle_tpu/feed/deliver', steps=block.steps):
            if self._is_spmd:
                # batch-led fetches of a dp-padded tail lot trim back to
                # the real row count, exactly like PE.run_multi's
                out = self._exe._convert_fetches(
                    fetches, self._return_numpy, block.real, block.padded,
                    compiled=compiled)
            else:
                out = self._exe._convert_fetches(fetches,
                                                 self._return_numpy)
        # the older tables' slice runs from the dispatch to here
        _profiler.record_event('pipeline/dispatch[x%d]' % block.steps,
                               time.time() - t0, start=t0)
        if self._on_delivered is not None:
            self._on_delivered(list(block.indices or []), out)
        return out

    def __iter__(self):
        self.start()
        try:
            while True:
                t0 = time.time()
                if self._m['dispatches'] > 0:
                    # the FIRST get is warmup (nothing to overlap with
                    # yet) — the probe must match the feed_stall metric
                    # semantics below, or a slow-staging first block
                    # dumps a spurious 'stall' during normal warmup
                    self._waiting_since = t0
                # the wait for a staged block IS feed_stall_s
                with _trace.span('paddle_tpu/feed/wait') as sp:
                    try:
                        block = self._staged.get()
                    finally:
                        self._waiting_since = None
                    stall = time.time() - t0
                    if block is not None and self._m['dispatches'] > 0 \
                            and stall > 1e-4:
                        sp.event = 'pipeline/feed_stall'
                if block is None:
                    # the EOF sentinel's wait delayed no dispatch — it
                    # must not count as feed stall (it would skew the
                    # 'feed_stall ~ 0' acceptance metric)
                    self._raise_stage_error()
                    break
                if self._m['dispatches'] > 0:
                    # the FIRST get always waits (nothing to overlap
                    # with yet); only post-warmup waits are feed stall
                    self._m['feed_stall_s'] += stall
                self._dispatch(block)
                while len(self._inflight) >= self.pipeline_depth:
                    yield self._drain_one()
            while self._inflight:
                yield self._drain_one()
        finally:
            # quiet close: the sentinel path above already raised any
            # stage error into the consumer; an ABANDONED iterator
            # (break / GC teardown) must not raise from a generator
            # finally — that masks the primary exception or surfaces
            # as an ignored-exception warning at GC.  An explicit
            # pipe.close() by the owner still raises (the close-race
            # contract).
            self._close_quiet()

    def run(self):
        """Drive the pipeline to EOF; returns the per-dispatch list of
        converted last-step fetches."""
        return list(self)

    def metrics(self):
        m = dict(self._m)
        m['queue_depth'] = self._staged.qsize()
        m['inflight'] = len(self._inflight)
        m['pipeline_depth'] = self.pipeline_depth
        m['steps_per_dispatch'] = self.steps
        m['bucketed'] = self.bucketed
        m['open_buckets'] = len(self._open)
        # staging hidden behind compute: of the staging seconds spent
        # AFTER the first dispatch could run, the fraction the dispatch
        # loop did NOT wait for (feed_stall ~ 0 => ratio ~ 1)
        denom = m['stage_s'] - m['stage_s_first']
        if denom > 0:
            m['overlap_ratio'] = max(0.0, min(
                1.0, (denom - m['feed_stall_s']) / denom))
        else:
            m['overlap_ratio'] = 1.0 if m['feed_stall_s'] < 1e-3 else 0.0
        if self._embed_caches:
            m['embed_cache'] = {c.var: c.metrics()
                                for c in self._embed_caches}
        return m

    def _drain_staged(self):
        try:
            while True:
                self._staged.get_nowait()
        except _queue.Empty:
            pass

    def _raise_stage_error(self):
        """Surface a staging-thread failure exactly ONCE as the typed
        FeedPipelineError (ISSUE 13 satellite): the iteration loop
        raises it when the EOF sentinel lands; an error that races
        close() — the stager crashing while the pipeline shuts down —
        is raised by close() instead, and a second close() (or the
        iterator's finally re-entering close) never re-raises."""
        if self._error is None or self._error_delivered:
            return
        self._error_delivered = True
        err = self._error
        raise FeedPipelineError(
            'FeedPipeline source failed: %r' % (err, )) from err

    def close(self):
        if self._closed:
            return
        self._closed = True
        # unblock a stager stuck on a full queue...
        self._drain_staged()
        if self._thread is not None:
            # bounded join: _closed is set, so the stager's put() loop
            # exits and _next_block stops consuming — a stage-thread
            # exception during this window is captured, not a hang
            self._thread.join(timeout=5)
            self._thread = None
        # ...and drop the block its unblocked put() may have deposited
        # AFTER the first drain — a staged ResNet-scale device block
        # pinned in the queue would hold HBM for as long as the caller
        # keeps the pipeline object (e.g. to read metrics())
        self._drain_staged()
        self._inflight = []
        if self._watchdog_probe is not None:
            _trace.watchdog.unregister(self._watchdog_probe,
                                       self._watchdog_age_fn)
            self._watchdog_probe = None
        _profiler.unregister_metrics_source(self._metrics_key,
                                            self._metrics_fn)
        # a racing stage-thread error nobody iterated into: surface it
        # here, once, AFTER the pipeline is fully torn down (resources
        # above are released whether or not this raises)
        self._raise_stage_error()

    def _close_quiet(self):
        """close() with a racing stage error recorded but not raised —
        for paths where raising would mask a primary exception (the
        error is still marked delivered, so no later close re-raises
        a half-reported failure)."""
        try:
            self.close()
        except FeedPipelineError:
            pass

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # a primary exception is propagating: never mask it with
            # the close-race error
            self._close_quiet()
        else:
            self.close()
