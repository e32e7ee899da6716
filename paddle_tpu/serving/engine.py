"""The TPU-native inference serving engine.

The reference ships inference as a per-request ABI
(paddle_inference_api.h: PaddlePredictor.Run — one graph execution per
call).  Every dispatch pays a fixed host cost (feed staging, the jit
call, the fetch — to be measured per dispatch on the v5e), so a
request-per-dispatch server spends the chip's time on the host.  This
engine amortizes it the same way Executor.run_multi does for training,
behind a request-facing surface:

  1. **dynamic micro-batching** — submitted requests coalesce in a
     MicroBatcher up to max_batch_size rows / a max_wait deadline;
  2. **shape bucketing** — each lot pads (masked, replicated last real
     row — the @SAMPLE_MASK machinery) to a bounded ShapeBucketSet
     ladder entry, so wandering request sizes map to a small fixed set
     of XLA executables; fetches trim back to real row counts.  The
     TRAILING dims bucket too (ISSUE 5, TrailingDimBuckets): variable
     seq-len/resolution extents quantize onto the shared
     fluid.shape_policy ladder (LoD feeds lower to padded + @SEQLEN at
     submit), so mixed-length requests coalesce instead of fragmenting
     into per-shape lots and per-shape executables; per-request fetches
     trim back to real trailing extents;
  3. **pipelined multi-step eval dispatch** — up to steps_per_dispatch
     same-bucket lots ship as ONE Executor.run_eval_multi scan (K eval
     batches per dispatch, donated scanned block), and up to
     pipeline_depth dispatches stay in flight so host feed/fetch
     overlaps device compute; dp>1 serving shards lots batch-dim over
     the mesh via ParallelExecutor.run_eval_multi;
  4. **metrics** — queue depth, batch fill ratio, p50/p99 latency,
     dispatch/compile counts, surfaced through fluid.profiler's
     timeline sidecar so tools/timeline.py renders serving spans.

Synchronous use needs no thread: an engine that was never ``start()``ed
dispatches inline on the submitter's thread (fluid.Inferencer runs this
mode).  ``start()`` spawns the worker loop for the queued mode.
"""

import contextlib
import threading
import time
import weakref
from collections import deque

import numpy as np

from ..fluid import core
from ..fluid import profiler as _profiler
from ..fluid import trace as _trace
from ..fluid.executor import Executor, feed_signature, _is_host_op, \
    fetch_batch_led, prepare_feed_arrays
from ..ops.registry import SEQLEN_SUFFIX, ROWS_SUFFIX, SAMPLE_MASK_NAME
from ..fluid.parallel_executor import ParallelExecutor, pad_ragged_batch, \
    _lead
from .batcher import InferenceRequest, MicroBatcher
from .buckets import ShapeBucketSet, TrailingDimBuckets
from .errors import DeadlineExceededError, EngineClosedError
from .metrics import EngineMetrics, RateWindow
from .profile import ServiceTimeProfile

__all__ = ['ServingConfig', 'InferenceEngine']

_ENGINE_SEQ = [0]
_ENGINE_SEQ_LOCK = threading.Lock()


class ServingConfig(object):
    """Engine knobs (documented in README 'Serving engine').

    max_batch_size: rows per lot before a full flush.
    max_wait_ms: oldest-request age forcing a deadline flush — the
        latency bound at low traffic.
    steps_per_dispatch: max same-bucket lots per run_eval_multi scan.
    pipeline_depth: dispatches kept in flight before the worker blocks
        on the oldest one's results (2 = double buffering).
    bucket_sizes: explicit ladder for the ShapeBucketSet (None = powers
        of two up to max_batch_size).
    max_buckets: bound on the active bucket set (LRU accounting).
    trailing_buckets: quantize variable TRAILING dims onto the shared
        seq-len ladder (fluid.shape_policy — the same policy the
        executor applies to LoD max-lens), so mixed-length sequence
        requests share a signature and coalesce: single-level LoD
        feeds lower to padded [B, T, ...] + @SEQLEN at submit, and
        PaddedSequence data re-pads to its rung.  Padded positions are
        masked by the @SEQLEN lowerings, so batched results stay
        bitwise-equal to per-request runs.  False restores the old
        behavior (every LoD/PaddedSequence request is its own
        unbatchable lot).
    trailing_ladders: EXPLICIT per-feed trailing ladders for DENSE
        feeds — ``{'img': [224, 256]}`` (axis 1) or
        ``{'img': {2: [224, 256], 3: [224, 256]}}`` (named axes): the
        resolution-ladder opt-in.  The engine zero-pads those axes up
        to the covering rung; because a dense feed carries no @SEQLEN
        masking contract, this is only output-preserving for models
        that ignore trailing padding (masked pooling/attention, padded
        detection inputs) — opting in asserts that.
    max_trailing_buckets: bound on the active trailing set (LRU
        accounting, like max_buckets for the batch ladder).
    watchdog_stall_s: queue-age stall threshold (seconds) for the
        trace watchdog (ISSUE 6) — a started engine registers a probe
        over its oldest queued request's age; crossing the threshold
        dumps the flight recorder (the post-mortem of a stuck worker).
        None (default) registers no probe.
    decode_slots: slot count of the generation lane's resident decode
        cache (ISSUE 7) — the continuous-batching degree.  Rounded UP
        to the mesh's dp extent for sharded serving.  Only meaningful
        when the engine was built with ``generation=``.
    decode_steps: decode-scan steps per device dispatch (the K of the
        in-jit greedy loop) — the generation lane's dispatch-tax
        amortizer, bounded below the per-request latency a step
        boundary adds to admission.
    prefill_chunk: chunked prefill (ISSUE 14) — split every prompt
        into C-token blocks and interleave them with decode scans
        under DECODE PRIORITY, so the max decode inter-token stall a
        long prompt can impose is ONE chunk's wall, not the whole
        prompt's.  The value is quantized up to the shared seq-len
        rung ladder (fluid.shape_policy) and must match the chunk
        width the generation model was built with
        (``build_step_decode(chunk=C)``); requests admit into a
        ``prefilling`` decode slot (partial state in the slabs, inert
        in decode scans) and each worker cycle rides AT MOST one chunk
        dispatch, budgeted by the measured chunk wall against the
        earliest active decode deadline's headroom (ServiceTimeProfile
        — a chunk that would push the next step boundary past an
        imminent deadline waits a cycle).  Chunked prefill is EXACT:
        generated tokens are identical to the monolithic lane for both
        model families (the chunk programs chain bitwise).  None (the
        default) keeps the monolithic PR 9 prefill-lot lane bitwise.
    decode_pipeline_depth: decode scans kept in flight (ISSUE 9 — the
        decode lane's pipeline_depth).  At 2 (the default) scan N+1 is
        enqueued against scan N's device-resident output carry BEFORE
        N's token block is harvested, so the host's detokenize/EOS/
        release bookkeeping overlaps device compute and the device
        never idles on a host round trip between scans; admission,
        eviction and shedding happen at chain-FLUSH points (every
        in-flight scan harvested first), keeping outputs
        token-identical to the per-scan-sync lane.  1 restores that
        lane exactly: dispatch, sync, bookkeep, dispatch — one
        device-idling host sync per scan (the baseline side of the
        ``decode_overlap`` perf gate).
    adaptive_admission: scale the overload admission watermarks by the
        measured queue-drain rate vs the arrival rate (ISSUE 9) — an
        engine whose drain keeps up with arrivals tolerates a deeper
        queue (burst absorption, up to 2x the static watermark); one
        falling behind admits at a proportionally SHALLOWER depth
        (down to half), shedding load before the queue is hopeless.
        Only meaningful with admit_queue_depth/admit_queue_age_ms set;
        False (the PR 8 default) keeps the watermarks static.
    scheduling: 'edf' (default) — deadline-aware lot formation (ISSUE
        8): highest priority first, earliest-deadline-first within a
        priority class, and past-deadline (or no-longer-meetable)
        requests SHED with a typed DeadlineExceededError instead of
        served late.  Requests without priorities/deadlines keep exact
        FIFO order, so the default changes nothing for pre-SLO
        callers.  'fifo' restores strict arrival order with no
        shedding — the baseline side of the ``slo`` perf gate.
    priority_aging_ms: starvation escape hatch for strict priority
        (ISSUE 11 satellite; ROADMAP item 5 leftover).  Under EDF a
        saturated high-priority stream starves a low class FOREVER;
        with aging set, each full window a request has waited promotes
        its EFFECTIVE class by one at lot formation (a request aging
        ``k`` windows competes as ``priority + k``), so starving
        low-priority work eventually outranks fresh high-priority
        arrivals.  Promotion engages only BELOW the highest pending
        real class — a class alone in the queue keeps pure EDF order
        (aging never cuts an undeadlined request ahead of a
        deadline-imminent peer of its own class).  None (default)
        keeps strict priority.
    shed_by_class: load-shedding by priority CLASS (ISSUE 12
        satellite; ROADMAP item 5 leftover).  The default shed rule
        judges each deadlined request against its OWN service estimate
        only; under overload that serves doomed low-class work at the
        expense of meetable high-class work.  With shed_by_class the
        shed pass walks the queue in scheduling order (highest class
        first, EDF within a class) ACCUMULATING the service estimates
        of everything ahead — a deadlined request sheds when the
        backlog in front of it already pushes its finish past the
        deadline, so the lowest-priority-class deadlined work sheds
        FIRST (it is served last, so the backlog dooms it first).
        Same-class EDF order is untouched (pinned).  EDF only.
    admit_queue_depth / admit_queue_age_ms: per-model admission
        watermarks the ModelRegistry enforces at ROUTING time — a
        request routed while the engine's queue is at least this deep
        (or its oldest queued request at least this old) is refused
        with a typed OverloadedError carrying a retry-after hint,
        instead of queueing toward certain deadline death.  None
        (default) disables that watermark; direct engine.submit()
        callers are never admission-checked (the registry is the
        fleet's front door).
    """

    def __init__(self, max_batch_size=32, max_wait_ms=5.0,
                 steps_per_dispatch=4, pipeline_depth=2,
                 bucket_sizes=None, max_buckets=16,
                 trailing_buckets=True, trailing_ladders=None,
                 max_trailing_buckets=32, watchdog_stall_s=None,
                 decode_slots=8, decode_steps=4, decode_pipeline_depth=2,
                 prefill_chunk=None, scheduling='edf',
                 admit_queue_depth=None, admit_queue_age_ms=None,
                 adaptive_admission=False, priority_aging_ms=None,
                 shed_by_class=False):
        if int(steps_per_dispatch) < 1:
            raise ValueError('steps_per_dispatch must be >= 1')
        if int(pipeline_depth) < 1:
            raise ValueError('pipeline_depth must be >= 1')
        if int(max_buckets) < 1:
            raise ValueError('max_buckets must be >= 1')
        if int(max_trailing_buckets) < 1:
            # a 0 bound would make every bucket_for miss insert-then-
            # evict its own key: an always-empty active set and an
            # evictions counter equal to the miss count
            raise ValueError('max_trailing_buckets must be >= 1')
        self.max_batch_size = int(max_batch_size)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.pipeline_depth = int(pipeline_depth)
        self.bucket_sizes = bucket_sizes
        self.max_buckets = int(max_buckets)
        if trailing_ladders and not trailing_buckets:
            raise ValueError(
                'ServingConfig: trailing_ladders= requires trailing '
                'bucketing — drop trailing_buckets=False, or drop the '
                'ladders')
        self.trailing_buckets = bool(trailing_buckets)
        self.trailing_ladders = trailing_ladders
        self.max_trailing_buckets = int(max_trailing_buckets)
        self.watchdog_stall_s = (float(watchdog_stall_s)
                                 if watchdog_stall_s is not None else None)
        if int(decode_slots) < 1:
            raise ValueError('decode_slots must be >= 1')
        if int(decode_steps) < 1:
            raise ValueError('decode_steps must be >= 1')
        self.decode_slots = int(decode_slots)
        self.decode_steps = int(decode_steps)
        if int(decode_pipeline_depth) < 1:
            raise ValueError('decode_pipeline_depth must be >= 1 '
                             '(1 = the per-scan-sync lane)')
        self.decode_pipeline_depth = int(decode_pipeline_depth)
        if prefill_chunk is not None:
            if int(prefill_chunk) < 1:
                raise ValueError('prefill_chunk must be >= 1 (or None '
                                 'for monolithic prefill)')
            from ..fluid.shape_policy import bucketed_len
            prefill_chunk = bucketed_len(int(prefill_chunk))
        self.prefill_chunk = prefill_chunk
        self.adaptive_admission = bool(adaptive_admission)
        if scheduling not in ('edf', 'fifo'):
            raise ValueError(
                "ServingConfig: scheduling must be 'edf' or 'fifo', "
                'got %r' % (scheduling, ))
        self.scheduling = scheduling
        if priority_aging_ms is not None and float(priority_aging_ms) <= 0:
            raise ValueError('priority_aging_ms must be > 0 (or None '
                             'for strict priority)')
        if priority_aging_ms is not None and scheduling == 'fifo':
            raise ValueError(
                'ServingConfig: priority_aging_ms only applies to EDF '
                "scheduling — drop scheduling='fifo', or drop the aging "
                'window')
        self.priority_aging_s = (float(priority_aging_ms) / 1e3
                                 if priority_aging_ms is not None else None)
        if shed_by_class and scheduling == 'fifo':
            raise ValueError(
                'ServingConfig: shed_by_class only applies to EDF '
                "scheduling — drop scheduling='fifo', or drop "
                'shed_by_class')
        self.shed_by_class = bool(shed_by_class)
        if admit_queue_depth is not None and int(admit_queue_depth) < 1:
            raise ValueError('admit_queue_depth must be >= 1 (or None '
                             'to disable the depth watermark)')
        if admit_queue_age_ms is not None and \
                float(admit_queue_age_ms) <= 0:
            raise ValueError('admit_queue_age_ms must be > 0 (or None '
                             'to disable the age watermark)')
        self.admit_queue_depth = (int(admit_queue_depth)
                                  if admit_queue_depth is not None
                                  else None)
        self.admit_queue_age_s = (float(admit_queue_age_ms) / 1e3
                                  if admit_queue_age_ms is not None
                                  else None)
        if self.adaptive_admission and self.admit_queue_depth is None \
                and self.admit_queue_age_s is None:
            raise ValueError(
                'ServingConfig: adaptive_admission needs a watermark '
                'to adapt — set admit_queue_depth and/or '
                'admit_queue_age_ms, or drop adaptive_admission')


class _Lot(object):
    """One padded, bucket-shaped batch of coalesced requests.
    ``kind`` ('forward' | 'generate') routes the dispatch: forward lots
    run the engine's program, generate lots run the generation spec's
    PREFILL program and their results admit into decode slots."""

    __slots__ = ('requests', 'feed', 'real', 'bucket', 'sig', 'kind')

    def __init__(self, requests, feed, real, bucket, sig, kind='forward'):
        self.requests = requests
        self.feed = feed
        self.real = real  # None for an unbatchable (LoD) lot
        self.bucket = bucket
        self.sig = sig
        self.kind = kind


class InferenceEngine(object):
    """Serve a loaded inference program (fluid.io.load_inference_model)
    through micro-batched, bucketed, pipelined eval dispatches."""

    def __init__(self, program, feed_names=None, fetch_list=None,
                 place=None, scope=None, executor=None, parallel=False,
                 mesh=None, config=None, name=None, generation=None,
                 embed_caches=None):
        if fetch_list is None:
            raise ValueError('InferenceEngine: fetch_list is required '
                             '(the fetch targets returned by '
                             'load_inference_model)')
        self._program = program
        self._feed_names = list(feed_names) if feed_names else None
        self._fetch_list = list(fetch_list)
        # static axis-1 widths of the fetch targets: a fetch of such a
        # width (a class/hidden axis — fc(.., 16) under a 16 rung) can
        # NOT be a mirrored rung-padded seq axis, so _bucket_trailing
        # voids any rung coinciding with one (same reasoning as the
        # static-feed guard there); dynamic seq fetches carry -1 on
        # axis 1 and stay trimmable
        self._fetch_static_ax1 = set()
        for v in self._fetch_list:
            shape = tuple(getattr(v, 'shape', None) or ())
            if len(shape) >= 2 and int(shape[1]) > 0:
                self._fetch_static_ax1.add(int(shape[1]))
        self._scope = scope if scope is not None else core.Scope()
        self.config = config if config is not None else ServingConfig()
        # host ops (save/print/readers) cannot run inside the eval scan:
        # such programs serve EAGERLY — one exe.run per request, no
        # padding/coalescing — preserving the Executor's per-step host-
        # op semantics (the pre-engine Inferencer behavior)
        self._eager = any(_is_host_op(op)
                          for op in program.global_block().ops)
        # two-tier embedding stores (ISSUE 12): inference lookups hit
        # the SAME hot-row slab training uses — the worker remaps each
        # lot's id feeds to slab slots and applies the row exchange
        # (misses fetch from the host master; inference stages are
        # never dirty, so its evictions write nothing back).
        # Validated HERE, before any generation/PE machinery builds:
        # the unsupported combinations must fail fast and leak nothing.
        self._embed_caches = list(embed_caches or [])
        if self._embed_caches and self._eager:
            raise NotImplementedError(
                'embed_caches cannot serve host-op (eager) programs — '
                'the per-request exe.run path has no lot to stage an '
                'exchange for')
        if self._embed_caches and generation is not None:
            # the prefill lots and decode-step dispatches do not remap
            # id feeds to slab slots: raw vocab ids against the [C, D]
            # slab would silently gather wrong rows — reject the
            # combination until the generation lane learns to stage
            raise NotImplementedError(
                'embed_caches cannot serve generation= engines yet — '
                'the prefill/decode dispatch paths do not remap lookup '
                'ids to slab slots')
        for _cache in self._embed_caches:
            _cache.check_scope(self._scope, 'InferenceEngine')
        self._pe = None
        if parallel or mesh is not None:
            if self._eager:
                raise NotImplementedError(
                    'sharded serving cannot run host-op programs — '
                    'remove the host ops or serve with parallel=False')
            self._pe = ParallelExecutor(main_program=program,
                                        scope=self._scope, mesh=mesh)
            multiple = self._pe._dp_extent()
        else:
            multiple = 1
        place = place if place is not None else core.default_place()
        self._exe = executor if executor is not None else Executor(place)
        self.buckets = ShapeBucketSet(self.config.max_batch_size,
                                      sizes=self.config.bucket_sizes,
                                      multiple=multiple,
                                      max_buckets=self.config.max_buckets)
        # the trailing-dim twin (ISSUE 5): None when disabled (or for
        # eager host-op programs, whose per-request exe.run path never
        # coalesces anyway)
        self.trailing = None
        if self.config.trailing_buckets and not self._eager:
            self.trailing = TrailingDimBuckets(
                ladders=self.config.trailing_ladders,
                max_buckets=self.config.max_trailing_buckets)
        # deadline-aware lot formation (ISSUE 8): the engine owns the
        # shed side effects (typed error + 'shed' trace stage + the
        # counter), and feeds the batcher its service estimate so
        # hopeless requests shed BEFORE burning a dispatch.  The
        # estimate is 3x the MINIMUM recent dispatch wall: min, not
        # mean — a compile-heavy cold dispatch (hundreds of ms) would
        # poison a mean into shedding EVERYTHING under tight deadlines,
        # and a total shed stops drains, so a poisoned mean could never
        # recover; min bounds the true service floor.  The 3x margin
        # matters because EDF always picks the most at-risk request:
        # with only ~1 dispatch-wall of slack the pick lands AT the
        # deadline and timing jitter turns it late — 3x leaves a full
        # dispatch of slack after the pick.
        # ISSUE 9 sharpens WHICH wall: the horizon is now per
        # SIGNATURE (ServiceTimeProfile, min-of-recent-walls per
        # coalescing sig, cost-registry seeded) — a mixed-shape queue
        # sheds the slow-signature request the global minimum would
        # have admitted; unseen signatures fall back to the global
        # floor, which is exactly the old estimator.
        ref0 = weakref.ref(self)
        self._service_walls = deque(maxlen=8)
        self._profile = ServiceTimeProfile()
        self._batcher = MicroBatcher(
            self.config.max_batch_size, self.config.max_wait_s,
            scheduling=self.config.scheduling,
            on_shed=lambda req: (ref0() and ref0()._shed_request(req)),
            service_estimate_for=lambda req: (
                ref0()._service_estimate(req) if ref0() else 0.0),
            priority_aging_s=self.config.priority_aging_s,
            shed_by_class=self.config.shed_by_class)
        # arrival vs drain rates (ISSUE 9): the adaptive admission
        # watermarks' inputs — noted at submit and at delivery
        self._arrivals = RateWindow()
        self._drains = RateWindow()
        # generation lane (ISSUE 7): a GenerationSpec turns on
        # submit_generate — prompts prefill through the normal lot
        # machinery, then decode in the slot-batched in-jit scan
        self.generation = generation
        self._decode_cache = None
        self._gen_ready = deque()  # (request, prefill values) awaiting a slot
        # pipelined decode chain (ISSUE 9/14): in-flight dispatches not
        # yet harvested, kind-tagged — ('decode', toks_dev,
        # alive_in_dev, k, t_disp, slot->req snapshot, slot-map snap)
        # or ('chunk', ok_dev, None, width, t_disp, None, snap); FIFO =
        # device order, bounded by decode_pipeline_depth
        self._decode_inflight = deque()
        # raw scan walls (dispatch -> harvest sync) — the decode lane's
        # own service floor for per-token deadline estimates
        self._decode_walls = deque(maxlen=8)
        # chunked prefill (ISSUE 14): prompts awaiting a prefilling
        # slot, measured chunk walls (the decode-priority budget), and
        # the prefill-activity flag feeding the inter-token stall gauge
        self._chunk_pending = deque()
        self._chunk_walls = deque(maxlen=8)
        self._prefill_since_harvest = False
        self._last_harvest_t = None
        self._last_harvest_alive = frozenset()
        self._chunking = False
        self._pe_prefill = self._pe_step = self._pe_chunk = None
        if generation is None and self.config.prefill_chunk is not None:
            raise ValueError(
                'ServingConfig(prefill_chunk=) only applies to '
                'generation= engines — there is no prefill to chunk')
        if generation is not None:
            if self._eager:
                raise NotImplementedError(
                    'generation serving cannot run host-op programs — '
                    'the decode scan is pure compute')
            from .decode import SlotStateCache
            self._decode_cache = SlotStateCache(
                generation, self.config.decode_slots, multiple=multiple)
            self._gen_decode_arg = generation.decode_arg()
            if self.config.prefill_chunk is not None:
                if not generation.supports_chunked_prefill:
                    raise ValueError(
                        'ServingConfig(prefill_chunk=%d): this '
                        'generation model has no chunk program — build '
                        'it with build_step_decode(chunk=%d) (and run '
                        'its chunk_startup), or drop prefill_chunk'
                        % (self.config.prefill_chunk,
                           self.config.prefill_chunk))
                if generation.chunk_width != self.config.prefill_chunk:
                    raise ValueError(
                        'ServingConfig(prefill_chunk=%d) does not match '
                        'the model\'s chunk width %d — the chunk '
                        'executable\'s block shape is fixed at build '
                        'time' % (self.config.prefill_chunk,
                                  generation.chunk_width))
                self._gen_chunk_arg = generation.chunk_arg()
                self._chunking = True
            if self._pe is not None:
                # PE binds one program each: the prefill and step
                # programs get their own sharded executors over the
                # SAME mesh + scope (weights shared)
                self._pe_prefill = ParallelExecutor(
                    main_program=generation.prefill_program,
                    scope=self._scope, mesh=self._pe._mesh)
                self._pe_step = ParallelExecutor(
                    main_program=generation.step_program,
                    scope=self._scope, mesh=self._pe._mesh)
                if self._chunking:
                    self._pe_chunk = ParallelExecutor(
                        main_program=generation.chunk_program,
                        scope=self._scope, mesh=self._pe._mesh)
        self._metrics = EngineMetrics()
        self._inflight = deque()
        self._last_sync_t = 0.0  # previous drain's sync, clips MFU windows
        self._carry = deque()  # flushed lots awaiting a matching block
        self._inline_lock = threading.Lock()
        # the pause gate: the worker holds it for exactly one
        # collect->dispatch->drain cycle; paused() (the registry's
        # eviction window) holds it for the whole pause, excluding new
        # dispatches while weights move between device and host
        self._cycle_lock = threading.RLock()
        # cross-engine fair-dispatch turnstile: None = no gate (a lone
        # engine); the ModelRegistry shares ONE lock across its engines
        # so each device dispatch is a bounded critical section and no
        # model's worker can hog the device between another's dispatches
        self._gate = None
        self._thread = None
        self._closed = False
        self._warned_unsliced = False
        self._watchdog_probe = None
        self._watchdog_age_fn = None
        with _ENGINE_SEQ_LOCK:
            _ENGINE_SEQ[0] += 1
            seq = _ENGINE_SEQ[0]
        self.name = name or ('serving-engine-%d' % seq)
        # timeline spans are KEYED by engine name (serving/<name>/...):
        # two engines profiled in one window land in separate timeline
        # rows instead of interleaving in one anonymous ':serving' row
        self._spans = 'serving/%s/' % self.name
        # profiler sidecar: a weakly-bound metrics source, so profiled
        # runs dump the serving snapshot without keeping dead engines
        # alive (tools/timeline.py renders the spans; the sidecar's
        # 'metrics' block carries the counters).  The registry returns
        # the KEY the source landed under — a second engine reusing the
        # same name is uniquified (name#2), so neither snapshot is lost.
        ref = weakref.ref(self)
        self._metrics_fn = lambda: (ref().metrics() if ref() else None)
        self._metrics_key = _profiler.register_metrics_source(
            self.name, self._metrics_fn)
        # an inline-mode engine may never be stop()ped: drop its
        # registration at GC so the source table can't grow unbounded
        weakref.finalize(self, _profiler.unregister_metrics_source,
                         self._metrics_key, self._metrics_fn)

    @classmethod
    def from_saved_model(cls, dirname, place=None, model_filename=None,
                         params_filename=None, **kwargs):
        """Build an engine straight from a save_inference_model dir
        (own scope + executor; the request-facing analog of
        create_paddle_predictor)."""
        from ..fluid import io as fluid_io
        from ..fluid.executor import scope_guard
        place = place if place is not None else core.default_place()
        exe = Executor(place)
        scope = core.Scope()
        with scope_guard(scope):
            program, feed_names, fetch_targets = \
                fluid_io.load_inference_model(
                    dirname, exe, model_filename=model_filename,
                    params_filename=params_filename)
        return cls(program, feed_names=feed_names,
                   fetch_list=fetch_targets, place=place, scope=scope,
                   executor=exe, **kwargs)

    # ---- lifecycle ----------------------------------------------------

    def start(self):
        """Spawn the worker thread (queued mode)."""
        if self._closed:
            raise RuntimeError('engine is closed')
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._serve_loop, name=self.name, daemon=True)
            self._thread.start()
            if self.config.watchdog_stall_s is not None and \
                    self._watchdog_probe is None:
                # a queued request aging past the threshold means the
                # worker is stuck — dump what was in flight before the
                # stall takes it to its grave.  WEAK closures, like the
                # metrics source: the global watchdog must not pin a
                # dropped engine (and its scope's device buffers) alive
                ref = weakref.ref(self)

                def age(ref=ref):
                    eng = ref()
                    return eng._batcher.oldest_age() if eng else None

                def ctx(ref=ref):
                    eng = ref()
                    return eng._stall_context() if eng else None

                self._watchdog_probe = _trace.watchdog.register(
                    'serving/%s/queue_age' % self.name, age,
                    self.config.watchdog_stall_s, context_fn=ctx)
                self._watchdog_age_fn = age
                # a started engine dropped without stop(): the probe
                # unregisters at GC (owner-checked — the key may have
                # been reused by a successor by then)
                weakref.finalize(self, _trace.watchdog.unregister,
                                 self._watchdog_probe, age)
                from ..distributed.embed_cache import register_stall_probe
                for cache in self._embed_caches:
                    # a late host row fetch stalls the worker exactly
                    # like a stuck queue — same threshold, its own
                    # prefetch-stall probe (ISSUE 12)
                    register_stall_probe(
                        self,
                        'serving/%s/embed_cache/%s/prefetch_stall'
                        % (self.name, cache.var),
                        cache, self.config.watchdog_stall_s)
        return self

    def _stall_context(self):
        """The stall dump's in-flight view: trace ids still queued (a
        stuck worker never dispatched them, so the ring has no record)
        plus those dispatched but not yet drained."""
        inflight = []
        try:
            for _, lots, _, _, _, _ in list(self._inflight):
                for lot in lots:
                    inflight.extend(r.trace_id for r in lot.requests)
        except RuntimeError:
            # a drain mutated the deque mid-snapshot (the watchdog
            # thread races the worker); the queued ids below are
            # independent and must still make the dump
            pass
        ctx = {'queued_trace_ids': self._batcher.pending_trace_ids(),
               'inflight_trace_ids': inflight}
        if self._decode_cache is not None:
            # the decode lane's view: who holds each slot (a stalled
            # worker strands THEM mid-generation), how many prefilled
            # requests were still waiting for one, and the in-flight
            # CHAIN (ISSUE 9) — scans dispatched but never harvested
            # are exactly what a wedged chained lane looks like
            ctx['decode_slot_map'] = self._decode_cache.snapshot()
            ctx['decode_pending'] = len(self._gen_ready) + \
                len(self._chunk_pending)
            now = time.time()
            try:
                ctx['decode_chain'] = [
                    {'kind': e[0], 'steps': e[3],
                     'age_s': round(now - e[4], 4)}
                    for e in list(self._decode_inflight)]
            except RuntimeError:
                # a harvest mutated the deque mid-snapshot (watchdog
                # thread races the worker); the slot map above stands
                ctx['decode_chain'] = None
        return ctx

    def stop(self):
        """Drain the queue and all in-flight dispatches, then join."""
        if self._closed:
            return
        self._closed = True
        self._batcher.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        else:
            self._drain_inline()
        if self._watchdog_probe is not None:
            _trace.watchdog.unregister(self._watchdog_probe,
                                       self._watchdog_age_fn)
            self._watchdog_probe = None
        _profiler.unregister_metrics_source(self._metrics_key,
                                            self._metrics_fn)

    close = stop

    @contextlib.contextmanager
    def paused(self):
        """Quiesce the engine: block inline submitters and the worker's
        dispatch cycles, drain every in-flight dispatch, and hold the
        engine idle for the duration of the with-block.  The HBM
        arbiter's eviction window — weights can move device<->host with
        no dispatch in flight.  submit() keeps queueing; queued requests
        simply wait out the pause."""
        with self._inline_lock:
            with self._cycle_lock:
                while self._inflight:
                    self._drain_one()
                if self._decode_cache is not None:
                    # the decode chain counts as in-flight dispatches
                    # too (ISSUE 9): an eviction moving slabs while a
                    # chained scan still references them would tear
                    # the carry — flush to a consistent boundary
                    self._decode_flush()
                yield self

    # ---- footprint / eviction (the ModelRegistry's arbiter hooks) ------

    def device_footprint(self):
        """Live HBM bytes attributable to this engine's model: the sum
        of device-resident (jax.Array) buffers held by its scope — the
        params the executor's cache_back staging pinned on device.
        (Executable HBM is XLA-internal; the arbiter carries it in the
        seed estimate.)  Sharded arrays report their GLOBAL byte size."""
        import jax
        total = 0
        for name in self._scope.local_var_names():
            v = self._scope.find_var(name).value()
            if isinstance(v, jax.Array):
                total += int(v.nbytes)
        return total

    def drop_executables(self, programs=None):
        """Drop every compiled executable for THIS engine's programs
        from its executor(s): the compile-cache entries (and their
        jitted multi/eval/decode scans) die, releasing XLA's
        device-side executable buffers.  Returns the number of cache
        entries dropped.  Only these programs' entries go — an executor
        shared with other models keeps theirs.  ``programs`` narrows
        the purge (the decode-cache eviction drops only the
        prefill/step executables); the default covers the engine's
        forward program plus the generation programs, if any."""
        if programs is None:
            programs = [self._program]
            if self.generation is not None:
                programs += [self.generation.prefill_program,
                             self.generation.step_program]
                if self.generation.chunk_program is not None:
                    programs.append(self.generation.chunk_program)
        pids = {id(p) for p in programs}
        dropped = 0
        for runner in (self._exe, self._pe, self._pe_prefill,
                       self._pe_step, self._pe_chunk):
            cache = getattr(runner, '_cache', None)
            if not cache:
                continue
            # the purge must exclude concurrent resolves: another model
            # sharing this executor may be between its cache get() and
            # move_to_end() on another thread (both executors expose
            # _cache_lock — Executor's from the concurrent-predictor
            # contract, ParallelExecutor's from the cost-registry work)
            lock = getattr(runner, '_cache_lock', None)
            with lock if lock is not None else contextlib.nullcontext():
                for k in [k for k in list(cache) if k[0] in pids]:
                    cache.pop(k, None)
                    dropped += 1
        return dropped

    def evict_to_host(self):
        """Demote the model to host memory under a paused() window:
        every device-resident scope buffer is copied back to a host
        ndarray (bitwise — dtype and values preserved, so the
        eviction->reload round trip is exact) and the program's
        executables are dropped.  Returns (bytes_moved,
        executables_dropped).  Reload is TRANSPARENT: the next dispatch
        re-stages host arrays through the normal cache_back path and
        recompiles on first use."""
        import jax
        with self.paused():
            moved = 0
            for name in self._scope.local_var_names():
                var = self._scope.find_var(name)
                v = var.value()
                if isinstance(v, jax.Array):
                    arr = np.asarray(v)
                    var.set_value(arr)
                    moved += int(arr.nbytes)
            dropped = self.drop_executables()
        return moved, dropped

    @staticmethod
    def _shard_nbytes(v):
        """ONE device's byte share of a live jax.Array — the shard
        shape when the sharding exposes it, the whole array otherwise
        (replicated arrays' shard IS the whole array).  The single
        per-device-bytes rule shared by ``hbm_footprint`` and
        ``table_live_bytes`` so arbiter billing and the footprint
        correction can never disagree."""
        try:
            shard = v.sharding.shard_shape(v.shape)
            return int(np.prod(shard)) * int(v.dtype.itemsize)
        except Exception:
            return int(v.nbytes)

    def hbm_footprint(self):
        """PER-DEVICE live HBM bytes attributable to this engine's
        scope (ISSUE 11): like ``device_footprint()`` but shard-aware —
        a mesh-row-sharded array (an 'mp' embedding table, a trainer
        scope's co-sharded moments) bills only ONE device's shard
        bytes, because the arbiter's budget is one chip's HBM.
        Replicated arrays (the plain dp case) are unchanged: their
        shard is the whole array, so this equals device_footprint()."""
        import jax
        total = 0
        for name in self._scope.local_var_names():
            v = self._scope.find_var(name).value()
            if isinstance(v, jax.Array):
                total += self._shard_nbytes(v)
        return total

    def table_live_bytes(self, var_name):
        """(global_bytes, per_device_bytes) of a mesh-row-sharded
        table's LIVE device array (ISSUE 11) — the arbiter bills the
        table's own account in per-device units (one chip holds only
        its shard), while ``device_footprint`` counts global bytes.
        (0, 0) when the var is host-resident or missing."""
        import jax
        var = self._scope.find_var(var_name)
        v = var.value() if var is not None else None
        if not isinstance(v, jax.Array):
            return 0, 0
        return int(v.nbytes), self._shard_nbytes(v)

    def embed_cache_of(self, var_name):
        """This engine's two-tier cache serving ``var_name`` (ISSUE
        12); KeyError when the var is not cached."""
        for cache in self._embed_caches:
            if cache.var == var_name:
                return cache
        raise KeyError('engine %r has no embed cache for %r'
                       % (self.name, var_name))

    def embed_cache_live_bytes(self, var_name):
        """Live DEVICE bytes of one cache's slabs (weight + optimizer
        accumulators) — the ``:embed-cache`` account's live
        correction; 0 while the slabs sit on host."""
        import jax
        cache = self.embed_cache_of(var_name)
        total = 0
        for name in cache.tables:
            var = self._scope.find_var(name)
            v = var.value() if var is not None else None
            if isinstance(v, jax.Array):
                total += self._shard_nbytes(v)
        return total

    def evict_embed_cache_to_host(self, var_name):
        """Demote ONE two-tier cache's slabs to host under a paused
        window (ISSUE 12; the arbiter's ``:embed-cache`` evict
        callback).  The flush inside first applies any staged exchange
        and writes every dirty row back to the host master — no torn
        slab even with a prefetch in flight — then the slabs demote
        bitwise and the next dispatch re-stages them transparently.
        Returns the bytes freed."""
        cache = self.embed_cache_of(var_name)
        with self.paused():
            return cache.evict_to_host()

    def evict_table_to_host(self, var_name):
        """Demote ONE mesh-row-sharded embedding table to host under a
        paused window (ISSUE 11; the arbiter's ``:embed-table`` evict
        callback): the shards copy back to a single bitwise host
        ndarray, and the next dispatch re-stages it sharded through the
        normal path.  Returns the PER-DEVICE bytes freed — the unit the
        table's account is charged in."""
        import jax
        with self.paused():
            var = self._scope.find_var(var_name)
            v = var.value() if var is not None else None
            if not isinstance(v, jax.Array):
                return 0
            _, per_dev = self.table_live_bytes(var_name)
            var.set_value(np.asarray(v))
        return per_dev

    @contextlib.contextmanager
    def _gated(self):
        gate = self._gate
        if gate is None:
            yield
        else:
            with gate:
                yield

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # ---- request surface ----------------------------------------------

    def _service_estimate(self, req):
        """The shed horizon for ONE pending request (ISSUE 9): 3x the
        service-floor estimate of the request's OWN coalescing
        signature (min of that signature's recent dispatch walls,
        cost-seeded), falling back to the profile's global floor —
        and, before anything was ever profiled, to the engine-wide
        min-wall window (exactly the PR 8 global horizon, so the
        per-signature path only ever sharpens)."""
        est = self._profile.estimate(req.sig)
        if est is None:
            est = self._profile.floor()
        if est is None:
            est = (min(self._service_walls)
                   if self._service_walls else 0.0)
        return 3.0 * est

    def rate_stats(self):
        """Measured arrival vs drain rates (requests/s over the recent
        window; None while idle or single-sample) — the adaptive
        admission watermarks' inputs, surfaced for metrics()."""
        return {'arrival_req_s': self._arrivals.rate(),
                'drain_req_s': self._drains.rate()}

    def queue_depth(self):
        """Current micro-batch queue depth — the cheap load gauge
        (no metrics snapshot, no arbiter walk) the registry's
        status() and the fleet replica's per-response load report
        read (ISSUE 17)."""
        return self._batcher.depth()

    def _shed_request(self, req, where='queue'):
        """Resolve one past-deadline request as SHED (ISSUE 8): typed
        DeadlineExceededError, a 'shed' trace stage (the seconds the
        request sat before the scheduler dropped it), a flight-recorder
        record, and the metrics counter.  Called by the batcher at lot
        formation, by decode-slot admission, and by the decode lane's
        step-boundary deadline check."""
        if req.done():
            return
        now = time.time()
        late_ms = (round((now - req.deadline_t) * 1e3, 3)
                   if req.deadline_t is not None else None)
        if req.trace is not None:
            req.trace.add_stage('shed', now - req.enqueue_t)
            self._metrics.note_stages(req.trace.finalize(end=now))
        self._metrics.note_shed()
        _trace.flight_recorder.record(
            'serving_shed', engine=self.name, where=where,
            trace_id=req.trace_id, deadline_ms=req.deadline_ms,
            late_by_ms=late_ms)
        req.set_error(DeadlineExceededError(
            req.trace_id, req.deadline_ms, late_ms, where=where))

    def submit(self, feed, return_numpy=True, priority=0,
               deadline_ms=None):
        """Enqueue one request; returns an InferenceRequest future.
        When the engine is not start()ed, the dispatch runs inline on
        this thread (synchronous mode) and the future is already done.

        ``priority`` / ``deadline_ms`` (ISSUE 8): under the default
        'edf' scheduling, higher-priority requests form lots first,
        earliest deadline first within a class, and a request whose
        deadline passes while it waits is SHED — its future raises
        DeadlineExceededError and its trace carries a 'shed' stage —
        instead of being served late."""
        if self._closed:
            raise EngineClosedError('engine is closed')
        if not isinstance(feed, dict) or not feed:
            raise ValueError('feed must be a non-empty {name: data} dict')
        if self._feed_names is not None:
            missing = set(self._feed_names) - set(feed)
            extra = set(feed) - set(self._feed_names)
            if missing or extra:
                raise ValueError(
                    'feed names %s do not match the inference program '
                    '(missing %s, unexpected %s)' %
                    (sorted(feed), sorted(missing), sorted(extra)))
        # ONE trace id per request (ISSUE 6): adopt the ambient context
        # when a router (the ModelRegistry) attached one — its
        # arbitration seconds are already accumulated on it — else mint
        # a fresh one here.  The prepare half of 'pad' (LoD lowering,
        # trailing-rung padding) happens on THIS thread before the
        # request ever queues, so it is measured here; the lot-padding
        # half accrues between the worker's collect/lot marks.
        ctx = _trace.current() or _trace.TraceContext()
        t_prep = time.time()
        feed, rows, sig, trims = self._prepare_request(feed)
        ctx.add_stage('pad', time.time() - t_prep)
        req = InferenceRequest(feed, rows, sig, return_numpy=return_numpy,
                               trailing=trims, trace=ctx,
                               priority=priority, deadline_ms=deadline_ms)
        self._metrics.note_request(rows or 1)
        self._arrivals.note()
        ctx.mark('enqueue')
        self._batcher.submit(req)
        if self._thread is None:
            self._drain_inline()
        return req

    def infer(self, feed, return_numpy=True, timeout=None):
        """Synchronous convenience: submit + wait."""
        return self.submit(feed, return_numpy=return_numpy).result(timeout)

    def submit_generate(self, feed, max_len=None, return_numpy=True,
                        priority=0, deadline_ms=None):
        """Enqueue one GENERATION request (ISSUE 7): ``feed`` is the
        prompt (the generation spec's prefill feeds, ONE sequence —
        rows must be 1), ``max_len`` the per-request step budget
        (capped by the spec's).  Returns a GenerationRequest future
        resolving to the generated token ids (greedy; EOS-terminated
        or cut at max_len) — token-identical to a per-request
        host-driven decode of the same prefill + step programs.

        The prompt coalesces into PREFILL lots with other generation
        requests (micro-batched, shape-bucketed, seq-len rung-
        quantized like any forward request); the prefilled state then
        ADMITS into a free decode slot at the next step boundary and
        rides the slot-batched in-jit decode scan — continuous
        batching, no drain barrier against requests already decoding.

        ``priority`` / ``deadline_ms`` ride the prefill lot like any
        forward request; the decode lane additionally checks the
        deadline at every step boundary (between K-step scans) — an
        expired generation releases its slot and sheds with whatever
        tokens it had, so dead decodes stop starving live ones."""
        from .decode import GenerationRequest
        if self.generation is None:
            raise RuntimeError(
                'submit_generate: this engine serves no generation '
                'model — construct it with generation=GenerationSpec(...)')
        if self._closed:
            raise EngineClosedError('engine is closed')
        spec = self.generation
        if not isinstance(feed, dict) or not feed:
            raise ValueError('feed must be a non-empty {name: data} dict')
        missing = set(spec.prefill_feeds) - set(feed)
        extra = set(feed) - set(spec.prefill_feeds)
        if missing or extra:
            raise ValueError(
                'submit_generate: feed names %s do not match the '
                'prefill program (missing %s, unexpected %s)'
                % (sorted(feed), sorted(missing), sorted(extra)))
        max_len = spec.max_len if max_len is None else int(max_len)
        if max_len < 1:
            raise ValueError('submit_generate: max_len must be >= 1')
        max_len = min(max_len, spec.max_len)
        # typed over-length reject (ISSUE 14 satellite): a prompt (or
        # prompt + generation budget) past the decode KV context would
        # otherwise surface as an opaque XLA shape/scatter error deep
        # inside prefill — or scatter silently off the slab mid-decode.
        # Measured HERE, on the raw feed, before any padding touches it.
        prompt_ids = prompt_len = None
        if spec.prompt_feed is not None and spec.prompt_feed in feed \
                and (self._chunking or spec.max_ctx is not None):
            # only when someone consumes it: the chunk lane slices it,
            # the max_ctx reject measures it — a plain monolithic
            # engine without a context bound must not pay the copy
            prompt_ids, prompt_len = spec.prompt_ids(feed)
        if self._chunking and prompt_len is not None and prompt_len < 1:
            # a zero-length prompt has no chunk to dispatch — without
            # this it would admit into a prefilling slot whose
            # finishing chunk never fires (the future would hang and
            # the slot leak)
            raise ValueError(
                'submit_generate: the prompt is empty — chunked '
                'prefill needs at least one token to consume')
        if spec.max_ctx is not None and prompt_len is not None:
            if prompt_len > spec.max_ctx:
                raise ValueError(
                    'submit_generate: prompt length %d exceeds the '
                    'decode context max_ctx=%d — the KV slab has no '
                    'row to hold token %d'
                    % (prompt_len, spec.max_ctx, spec.max_ctx))
            if prompt_len + max_len > spec.max_ctx:
                raise ValueError(
                    'submit_generate: prompt length %d + max_len %d '
                    'exceeds the decode context max_ctx=%d — generated '
                    'tokens would scatter off the KV slab; shorten the '
                    'prompt or lower max_len'
                    % (prompt_len, max_len, spec.max_ctx))
        if self._chunking and prompt_ids is None:
            raise ValueError(
                'submit_generate: chunked prefill needs the prompt '
                'feed %r in the request' % (spec.prompt_feed, ))
        ctx = _trace.current() or _trace.TraceContext()
        if self._chunking:
            # chunked prefill never forms a prefill lot, so the
            # rung-padding pass (_prepare_request) would be a wasted
            # full-prompt copy on the caller thread — long prompts are
            # exactly this lane's workload.  Only the one-sequence
            # check remains; the request carries no feed (the chunk
            # lane reads prompt_tokens) and a constant coalescing sig
            # (chunk-pending requests never share an executable).
            rows = self._chunk_prompt_rows(feed[spec.prompt_feed])
            if rows != 1:
                raise ValueError(
                    'submit_generate: the prompt must be ONE sequence '
                    '(got %r rows) — submit one request per sequence '
                    'so each occupies one decode slot' % (rows, ))
            feed, sig = None, ('gen-chunk', )
        else:
            t_prep = time.time()
            feed, rows, sig, _trims = self._prepare_request(feed)
            ctx.add_stage('pad', time.time() - t_prep)
            if rows is None:
                # the unbatchable path (nested LoD, or an LoD prompt
                # with trailing bucketing disabled) has no coalescible
                # prefill signature — say WHY instead of 'got None
                # rows'
                raise ValueError(
                    'submit_generate: this prompt cannot ride the '
                    'batched prefill path — nested (2-level) LoD '
                    'prompts are unsupported, and LoD prompts need '
                    'trailing bucketing (drop '
                    'ServingConfig(trailing_buckets=False))')
            if rows != 1:
                raise ValueError(
                    'submit_generate: the prompt must be ONE sequence '
                    '(got %r rows) — submit one request per sequence '
                    'so each occupies one decode slot' % (rows, ))
            # the 'gen' sig prefix keeps prefill lots out of forward
            # lots even when the raw feed signatures collide
            sig = ('gen', ) + tuple(sig)
        req = GenerationRequest(feed, 1, sig, max_len,
                                return_numpy=return_numpy, trace=ctx,
                                priority=priority,
                                deadline_ms=deadline_ms)
        if self._chunking:
            req.prompt_tokens = prompt_ids
            req.prompt_len = prompt_len
        self._metrics.note_generate()
        self._arrivals.note()
        ctx.mark('enqueue')
        self._batcher.submit(req)
        if self._thread is None:
            self._drain_inline()
        return req

    @staticmethod
    def _chunk_prompt_rows(v):
        """How many sequences the prompt feed carries (the chunked
        lane's one-sequence check, without the monolithic path's
        rung-padding pass): LoD prompts count their top-level
        sequences (nested LoD rejected — flattening it into chunk
        blocks would silently concatenate sequences), dense prompts
        their leading dim."""
        if isinstance(v, core.LoDTensor) and v.lod():
            if len(v.lod()) >= 2:
                raise ValueError(
                    'submit_generate: nested (2-level) LoD prompts '
                    'are unsupported under chunked prefill')
            return max(len(v.lod()[-1]) - 1, 0)
        shape = np.shape(v.numpy() if isinstance(v, core.LoDTensor)
                         else v)
        return int(shape[0]) if shape else 0

    def generate(self, feed, max_len=None, timeout=None):
        """Synchronous convenience: submit_generate + wait."""
        return self.submit_generate(feed, max_len=max_len).result(timeout)

    def metrics(self):
        """Engine snapshot + bucket report + the executor's own XLA
        compile counter (the ground truth the bucket policy bounds)."""
        snap = self._metrics.snapshot(
            queue_depth=self._batcher.depth(),
            queue_age=self._batcher.age_stats())
        # the device this engine really dispatches to, as JAX reports
        # it: a server that came up on the wrong platform shows here
        snap['device'] = core.device_info(
            self._pe._mesh.devices.flat if self._pe is not None
            else [self._exe.place.jax_device()])
        snap['buckets'] = self.buckets.report()
        snap['trailing_buckets'] = (self.trailing.report()
                                    if self.trailing is not None else None)
        snap['executor_compile_count'] = (
            self._pe.compile_count if self._pe is not None
            else self._exe.compile_count)
        if self._pe is not None and self._pe_step is not None:
            # sharded generation compiles its prefill/step (and chunk)
            # executables on their own PEs — fold them into the
            # ground-truth count
            snap['executor_compile_count'] += (
                self._pe_prefill.compile_count +
                self._pe_step.compile_count)
            if self._pe_chunk is not None:
                snap['executor_compile_count'] += \
                    self._pe_chunk.compile_count
        snap['inflight'] = len(self._inflight)
        snap['decode'] = (self._metrics.decode_snapshot(
            active_slots=self._decode_cache.active_slots(),
            free_slots=self._decode_cache.free_slots(),
            pending=len(self._gen_ready) + len(self._chunk_pending),
            inflight_scans=len(self._decode_inflight))
            if self._decode_cache is not None else None)
        # the two-tier embedding cache's counters (ISSUE 12):
        # hit/miss/stall/writeback per cached table
        snap['embed_cache'] = ({c.var: c.metrics()
                                for c in self._embed_caches}
                               if self._embed_caches else None)
        # per-signature service profile + the rate pair the adaptive
        # watermarks read (ISSUE 9)
        snap['service_profile'] = self._profile.snapshot()
        rates = self.rate_stats()
        snap['arrival_req_s'] = (round(rates['arrival_req_s'], 3)
                                 if rates['arrival_req_s'] else None)
        snap['drain_req_s'] = (round(rates['drain_req_s'], 3)
                               if rates['drain_req_s'] else None)
        return snap

    # ---- request -> lot -----------------------------------------------

    def _prepare_request(self, feed):
        """(feed, rows, coalescing signature, trailing trim map) for a
        request.  With trailing bucketing on, single-level LoD feeds
        lower to padded [B, T, ...] + @SEQLEN here (the executor's own
        lowering, already rung-quantized) and PaddedSequence / dense
        ladder feeds zero-pad their trailing axes up to the covering
        TrailingDimBuckets rung — so mixed-length requests in one rung
        share a signature and coalesce.  Unbatchable feeds (host-op
        programs, scalars, NESTED LoD — whose outer @ROWS level is not
        row-aligned for per-request slicing — or any sequence feed with
        trailing bucketing disabled) come back as (feed, None, unique,
        None): single-request lots with no padding, the old path."""
        if self._eager:
            return feed, None, object(), None
        seq_like = False
        for v in feed.values():
            if isinstance(v, core.PaddedSequence):
                if self.trailing is None or v.rows is not None:
                    return feed, None, object(), None
                seq_like = True
            elif isinstance(v, core.LoDTensor) and v.lod():
                if self.trailing is None or len(v.lod()) >= 2:
                    return feed, None, object(), None
                seq_like = True
        items = prepare_feed_arrays(feed) if seq_like else dict(feed)
        # validate BEFORE bucketing: _bucket_trailing pads in place and
        # records padding-waste / rung-hit metrics — a request rejected
        # here (or routed to the unbatchable path) must leave no trace
        # in the trailing accounting
        leads = {}
        for name, v in sorted(items.items()):
            lead = _lead(v)
            if lead is None:
                return feed, None, object(), None
            if lead == 0:
                raise ValueError(
                    'feed %r has 0 rows — an empty request has no '
                    'result to serve' % name)
            leads[name] = lead
        if len(set(leads.values())) > 1:
            raise ValueError(
                'feeds disagree on the leading (batch) dim: %s — every '
                'input of one request must carry the same number of '
                'rows' % ({n: d for n, d in sorted(leads.items())}, ))
        trims = self._bucket_trailing(items) \
            if self.trailing is not None else None
        sig = []
        for name, v in sorted(items.items()):
            arr_like = v.numpy() if isinstance(v, core.LoDTensor) else v
            shape = tuple(np.shape(arr_like))
            dtype = getattr(arr_like, 'dtype', None)
            if dtype is None:
                dtype = np.asarray(arr_like).dtype
            sig.append((name, shape[1:], str(dtype)))
        return (items, int(next(iter(leads.values()))), tuple(sig),
                trims)

    def _bucket_trailing(self, items):
        """Quantize ``items``' variable trailing dims onto the
        TrailingDimBuckets ladder IN PLACE (zero-fill, the same pad
        _lod_to_padded applies): axis 1 of every feed carrying a
        @SEQLEN companion rides the shared seq-len policy; feeds named
        in ``trailing_ladders`` pad their configured axes.  Returns the
        axis-1 trim map {padded_extent: real_extent} for the deliver
        path (a padded extent claimed by two feeds with DIFFERENT real
        extents — including a feed sitting exactly ON the rung, or a
        NON-bucketed feed's static axis-1 extent, or a FETCH target's
        static axis-1 width, coinciding with it — is ambiguous and
        dropped: such fetches deliver at the rung, documented in
        _drain_one)."""
        claims = {}  # rung -> set of real axis-1 extents claiming it
        # extents a trim must never match: the static axis 1 of feeds
        # NOT bucketed on axis 1 (collected below — including feeds
        # whose ladders live on axes >= 2) and the fetch targets'
        # static axis 1 (a [B, 16] softmax under a 16 rung is the
        # fetch's OWN width, not rung padding)
        static_ax1 = set(self._fetch_static_ax1)
        plan = []  # (name, axes, explicit, shape) — validated upfront
        for name in list(items):
            if name.endswith((SEQLEN_SUFFIX, ROWS_SUFFIX)) or \
                    name == SAMPLE_MASK_NAME:
                continue
            explicit = set(self.trailing.ladder_axes(name))
            axes = set(explicit)
            if (name + SEQLEN_SUFFIX) in items:
                axes.add(1)
            v = items[name]
            shape = tuple(v.shape() if isinstance(v, core.LoDTensor)
                          else np.shape(v))
            for ax in sorted(explicit):
                if ax >= len(shape):
                    # a configured ladder axis the data doesn't have
                    # would otherwise be skipped silently — that feed
                    # would never coalesce and nothing would say why
                    # (the constructor already rejects axis < 1 for
                    # the same reason).  Raised HERE, before any feed
                    # touches bucket hits or padding metrics, so the
                    # rejected request leaves no trailing trace.
                    raise ValueError(
                        'trailing ladder for feed %r names axis '
                        '%d, but the request has only %d dims — '
                        'fix trailing_ladders' % (name, ax,
                                                  len(shape)))
            for ax in sorted(axes):
                if 1 <= ax < len(shape) and int(shape[ax]) < 1:
                    # bucket_for would raise the same complaint, but
                    # mid-loop — after OTHER feeds already recorded
                    # rung hits and padding cells
                    raise ValueError(
                        'feed %r has zero width on bucketed trailing '
                        'axis %d — an empty extent has nothing to '
                        'serve' % (name, ax))
            if 1 not in axes and len(shape) >= 2:
                static_ax1.add(int(shape[1]))
            if axes:
                plan.append((name, axes, explicit, shape))
        for name, axes, explicit, shape in plan:
            v = items[name]
            rows = max(int(shape[0]), 1) if shape else 1
            pads, prod_real, prod_rung = [], 1, 1
            seq_lens_sum, bucketed = None, False
            for ax in sorted(axes):
                if ax >= len(shape) or ax < 1:
                    continue
                real = int(shape[ax])
                rung = self.trailing.bucket_for(name, ax, real)
                bucketed = True
                if ax == 1 and (name + SEQLEN_SUFFIX) in items:
                    # the TRUE occupancy of a seq feed's time axis is
                    # its lengths sum — the rung pad a prepared LoD
                    # feed already carries (inside _lod_to_padded)
                    # must count as waste too, not just the extra pad
                    # this pass adds
                    seq_lens_sum = max(int(np.sum(np.asarray(
                        items[name + SEQLEN_SUFFIX]))), 0)
                    prod_rung *= rung
                else:
                    prod_real *= real
                    prod_rung *= rung
                if ax == 1:
                    claims.setdefault(rung, set()).add(real)
                if rung != real:
                    pads.append((ax, rung - real))
            if pads:
                arr = np.asarray(v.numpy() if isinstance(v, core.LoDTensor)
                                 else v)
                width = [(0, 0)] * arr.ndim
                for ax, p in pads:
                    width[ax] = (0, p)
                items[name] = np.pad(arr, width)
            if bucketed:
                base = seq_lens_sum if seq_lens_sum is not None else rows
                self._metrics.note_trailing(base * prod_real,
                                            rows * prod_rung)
        # order-independent ambiguity: a rung claimed by two feeds with
        # different real extents (even one sitting exactly ON it), or
        # coinciding with a NON-bucketed feed's static axis-1 extent (a
        # fetch of that width could mirror EITHER axis), has no single
        # trim answer
        trims = {rung: reals.pop() for rung, reals in claims.items()
                 if len(reals) == 1 and rung not in reals
                 and rung not in static_ax1}
        return trims or None

    def _make_lot(self, requests):
        now = time.time()
        for r in requests:
            if r.trace is not None:
                r.trace.mark('collect', now)
        if _profiler.is_profiler_enabled() or _trace.spans_enabled():
            # a tracing()-only window gets these spans too — the
            # documented contract is that every profiler event mirrors
            # into the span log, profiler running or not
            for r in requests:
                _profiler.record_event(self._spans + 'queue_wait',
                                       now - r.enqueue_t,
                                       start=r.enqueue_t)
        head = requests[0]
        if head.rows is None:
            # unbatchable (LoD/scalar feeds, or an eager host-op
            # program): its own lot, no padding — still a lot in the
            # metrics (real == bucket rows, so the fill ratio is
            # unaffected) or capacity math reads 'served nothing'
            self._metrics.note_lot(1, 1, deadline_flush=False)
            if head.trace is not None:
                head.trace.mark('lot')
            return _Lot(requests, dict(head.feed), None, None,
                        ('nobatch', id(head)), kind=head.kind)
        rows = sum(r.rows for r in requests)
        bucket = self.buckets.bucket_for(rows)
        names = set(head.feed)
        if len(requests) == 1:
            # pass values through untouched — pad_ragged_batch already
            # leaves device-staged arrays on device when nothing pads
            feed = dict(head.feed)
        else:
            feed = {n: np.concatenate([
                np.asarray(r.feed[n].numpy()
                           if isinstance(r.feed[n], core.LoDTensor)
                           else r.feed[n]) for r in requests])
                for n in names}
        # force_mask keeps ONE signature per bucket: a full lot and a
        # padded lot compile to the same executable (mask all-ones vs
        # ragged) instead of doubling the compile set
        feed, real, target = pad_ragged_batch(
            feed, 1, target=bucket, force_mask=True, batch_names=names)
        deadline_flush = rows < self.config.max_batch_size
        self._metrics.note_lot(real, target, deadline_flush)
        t_lot = time.time()
        for r in requests:
            if r.trace is not None:
                r.trace.mark('lot', t_lot)
        # kind is part of the block sig: a prefill lot must never share
        # a scan block with a forward lot of a coinciding signature
        return _Lot(requests, feed, real, target,
                    (head.kind, target, feed_signature(feed)),
                    kind=head.kind)

    # ---- dispatch / deliver -------------------------------------------

    def _dispatch(self, lots):
        """ONE run_eval_multi dispatch over K same-bucket lots; tracks
        it in the in-flight pipeline (no host sync here).  Host-op
        (eager) programs run one exe.run per lot instead — the scan
        cannot contain them."""
        if self._eager:
            return self._dispatch_eager(lots)
        t0 = time.time()
        prefill = lots[0].kind == 'generate'
        if prefill:
            # a prefill lot runs the generation spec's PREFILL program,
            # fetching the initial decoder state instead of the
            # engine's fetch list — same scan machinery, different
            # executable set
            program = self.generation.prefill_program
            fetch_list = self.generation.prefill_fetches
            runner = self._pe_prefill or self._exe
            self._metrics.note_prefill_lot()
            # the stall gauge's "prefill in flight" marker (ISSUE 14):
            # this lot's compute lands between decode scans on device
            self._prefill_since_harvest = True
        else:
            program = self._program
            fetch_list = self._fetch_list
            runner = self._pe or self._exe
        before = runner.compile_count
        trace_ids = [r.trace_id for lot in lots for r in lot.requests]
        # the flight recorder's lot record goes in BEFORE the dispatch:
        # when the dispatch itself wedges or errors, the dump must show
        # what was being dispatched, not just what already succeeded
        _trace.flight_recorder.record(
            'serving_dispatch', engine=self.name, lots=len(lots),
            lot_kind=lots[0].kind,
            bucket=lots[0].bucket, sig=repr(lots[0].sig)[:128],
            rows=[lot.real for lot in lots], trace_ids=trace_ids)
        feed_list = [l.feed for l in lots]
        try:
            if self._embed_caches and not prefill:
                # inference lookups ride the SAME hot-row slab (ISSUE
                # 12): remap the lots' id feeds to slots (copies — an
                # errored lot must keep its raw ids) and land the
                # exchange before the dispatch that reads the slab.
                # train=False: serving never dirties rows, evictions
                # are free.  A staging fault (capacity, out-of-range
                # ids) errors the lot's futures, never the worker.
                feed_list = [dict(f) for f in feed_list]
                for cache in self._embed_caches:
                    cache.apply(cache.stage_feed_list(
                        feed_list, train=False, steps=len(feed_list)))
            with self._gated():
                stacked, reals, target, compiled, k = \
                    runner._dispatch_eval_multi(
                        fetch_list, feed_list=feed_list, program=program,
                        scope=self._scope)
        except Exception as exc:
            self._metrics.note_error()
            _trace.flight_recorder.dump(
                'worker_error:%s' % self.name, error=repr(exc),
                trace_ids=trace_ids)
            for lot in lots:
                for req in lot.requests:
                    req.set_error(exc)
            return
        self._metrics.note_dispatch(k, runner.compile_count - before)
        t_disp = time.time()
        for lot in lots:
            for req in lot.requests:
                if req.trace is not None:
                    req.trace.mark('dispatch', t_disp)
        # snapshot the per-dispatch cost entry NOW: a later dispatch on
        # the same compiled block overwrites last_eval_cost before this
        # one drains (FIFO drain, pipeline_depth > 1 in flight)
        cost = getattr(compiled, 'last_eval_cost', None)
        self._inflight.append((stacked, lots, compiled, t0, t_disp, cost))

    def _dispatch_eager(self, lots):
        """Per-lot exe.run for host-op programs (save/print/readers):
        identical semantics to the pre-engine Inferencer, delivered
        synchronously — nothing to pipeline when every step round-trips
        the host anyway."""
        for lot in lots:
            t0 = time.time()
            req = lot.requests[0]  # eager lots are single-request
            before = self._exe.compile_count
            if req.trace is not None:
                req.trace.mark('dispatch', t0)
            _trace.flight_recorder.record(
                'serving_dispatch', engine=self.name, lots=1, eager=True,
                trace_ids=[req.trace_id])
            try:
                with self._gated():
                    outs = self._exe.run(self._program, feed=lot.feed,
                                         fetch_list=self._fetch_list,
                                         scope=self._scope,
                                         return_numpy=req.return_numpy)
            except Exception as exc:
                self._metrics.note_error()
                _trace.flight_recorder.dump(
                    'worker_error:%s' % self.name, error=repr(exc),
                    trace_ids=[req.trace_id])
                req.set_error(exc)
                continue
            self._metrics.note_dispatch(
                1, self._exe.compile_count - before)
            if req.trace is not None:
                # eager runs are synchronous: the device stage IS the
                # exe.run window, and delivery follows immediately
                req.trace.mark('sync')
                self._metrics.note_stages(req.trace.finalize())
            req.set_result(outs)
            if req.latency_s is not None:
                self._metrics.note_latency(req.latency_s)
            if _profiler.is_profiler_enabled() or _trace.spans_enabled():
                _profiler.record_event(self._spans + 'dispatch[eager]',
                                       time.time() - t0, start=t0)

    def _drain_one(self):
        """Deliver the OLDEST in-flight dispatch: host sync, trim each
        lot to its real rows, slice per request, resolve futures."""
        stacked, lots, compiled, t0, t_disp, cost = \
            self._inflight.popleft()
        try:
            arrays = [np.asarray(a) for a in stacked]  # the sync point
        except Exception as exc:
            self._metrics.note_error()
            _trace.flight_recorder.dump(
                'worker_error:%s' % self.name, error=repr(exc),
                trace_ids=[r.trace_id for lot in lots
                           for r in lot.requests])
            for lot in lots:
                for req in lot.requests:
                    req.set_error(exc)
            return
        t_sync = time.time()
        for lot in lots:
            for req in lot.requests:
                if req.trace is not None:
                    req.trace.mark('sync', t_sync)
        # achieved MFU: XLA's own FLOPs for the drained executable over
        # the wall window the device could have spent on THIS dispatch.
        # With pipeline_depth > 1 dispatch N+1 is issued while N still
        # executes, so [t_disp, t_sync] windows of consecutive drains
        # overlap — summing them double-counts wall time and halves the
        # reported rate under load.  Clip each window to start no
        # earlier than the previous drain's sync.  A backend whose
        # analysis yields no 'flops' must not grow the seconds
        # denominator either, or mixed entries deflate device_flops_per_s
        dev_start = max(t_disp, self._last_sync_t)
        if cost is not None and cost.get('flops') and t_sync > dev_start:
            self._metrics.note_device(cost['flops'], t_sync - dev_start)
        # service-time window (ISSUE 8): one dispatch's RAW issue->sync
        # span feeds the batcher's shed horizon — a deadlined request
        # that cannot be served within ~2x the recent MINIMUM span
        # sheds instead of burning the dispatch it would miss anyway.
        # Deliberately NOT the clipped device window above: under
        # pipeline_depth >= 2 the raw span includes the wait behind
        # earlier in-flight dispatches, and that wait IS part of the
        # time a newly formed lot takes to deliver — estimating from
        # the clipped window makes EDF pick requests it then serves
        # just past their deadline (measured: the slo gate's edf_late
        # jumps ~10x).  The min-of-8 still discards compile outliers.
        wall = max(t_sync - t0, 0.0)
        self._service_walls.append(wall)
        # per-signature profile (ISSUE 9): the same raw wall, keyed by
        # each lot's coalescing signature (every request in a lot
        # shares it — the batcher's coalescing rule), with a cost-
        # registry seed the first time a signature drains so the
        # min-window never bottoms out at a compile-polluted cold
        # wall.  ONE observation per distinct signature per dispatch:
        # the lots of a multi-lot scan block share their signature
        # (_collect_block's rule), and K duplicate appends would
        # shrink the min-window to ~8/K distinct dispatches of history
        for key in {lot.requests[0].sig for lot in lots}:
            if cost is not None and cost.get('flops'):
                rate = self._metrics.device_rate()
                if rate:
                    self._profile.seed(key, cost['flops'] / rate)
            self._profile.observe(key, wall)
        self._last_sync_t = t_sync
        led = fetch_batch_led(compiled, len(arrays))
        if not all(led) and not self._warned_unsliced and \
                any(len(lot.requests) > 1 for lot in lots):
            # a batch-REDUCED fetch (a mean/accuracy scalar) from a
            # coalesced lot is computed over EVERY rider's rows — there
            # is no per-request value to slice out, so each caller gets
            # the whole-lot number.  Say so once instead of silently
            # breaking per-request parity for such fetches.
            self._warned_unsliced = True
            import warnings
            warnings.warn(
                'serving engine %s: fetches %s are not per-row '
                '(batch-led) — coalesced requests receive the value '
                'computed over the WHOLE micro-batch, not their own '
                'rows.  Fetch per-row outputs, or serve such programs '
                'with max_batch_size=1.' %
                (self.name,
                 [n for n, is_led in zip(
                     getattr(compiled, 'fetch_names',
                             range(len(led))), led) if not is_led]))
        for j, lot in enumerate(lots):
            offset = 0
            for req in lot.requests:
                res = []
                for a, is_led in zip(arrays, led):
                    step = a[j]
                    if lot.real is not None and is_led \
                            and np.ndim(step) >= 1 \
                            and np.shape(step)[0] == lot.bucket:
                        step = step[offset:offset + req.rows]
                        if req.trailing is not None \
                                and np.ndim(step) >= 2:
                            # trailing-dim trim (ISSUE 5): a per-row
                            # fetch mirroring a rung-padded input axis
                            # (axis 1 == a padded extent this request
                            # recorded) trims back to the request's
                            # REAL extent — so a PaddedSequence/dense-
                            # ladder caller gets fetches shaped like
                            # its own input, not like the rung.
                            # (Extent-match is a heuristic like the
                            # batch one above; ambiguous extents were
                            # dropped at request build and deliver at
                            # the rung.  Residual: STATIC widths —
                            # feeds' and fetches' — void their rungs
                            # upfront, but a fetch whose axis 1 is
                            # dynamic AND whose runtime width lands on
                            # a claimed rung without mirroring the
                            # padded axis is indistinguishable here;
                            # disable trailing_buckets for such
                            # programs.)
                            real = req.trailing.get(np.shape(step)[1])
                            if real is not None:
                                step = step[:, :real]
                    if not req.return_numpy and req.kind != 'generate':
                        # a generate request's prefill slices feed slot
                        # admission — they stay raw arrays regardless
                        step = core.LoDTensor(np.asarray(step))
                    res.append(step)
                offset += req.rows or 0
                if req.kind == 'generate':
                    # a PREFILL result: the per-request state slices
                    # queue for slot admission at the next decode step
                    # boundary (continuous batching — no drain barrier
                    # against slots already decoding); the future
                    # resolves when the decode lane finishes the
                    # request
                    self._gen_ready.append((req, res))
                    continue
                if req.trace is not None:
                    # finalize BEFORE resolving the future: a caller
                    # woken by result() must see a complete breakdown
                    self._metrics.note_stages(req.trace.finalize())
                    _trace.record_span(
                        self._spans + 'request', req.trace.t0,
                        req.trace.e2e_s, trace_id=req.trace_id)
                req.set_result(res)
                self._drains.note()
                if req.latency_s is not None:
                    self._metrics.note_latency(req.latency_s)
        if _profiler.is_profiler_enabled() or _trace.spans_enabled():
            _profiler.record_event(
                self._spans + 'dispatch[x%d]' % len(lots),
                time.time() - t0, start=t0)

    # ---- decode lane (ISSUE 7) ----------------------------------------

    def _admit_ready(self):
        """Admit prefilled generation requests into free decode slots
        (step-boundary admission — the host half of continuous
        batching).  Returns how many were admitted."""
        admitted = 0
        while self._gen_ready and self._decode_cache.free_slots():
            req, values = self._gen_ready.popleft()
            if req.done():
                continue  # errored upstream; nothing to decode
            if self.config.scheduling == 'edf' and \
                    req.deadline_t is not None and \
                    time.time() > req.deadline_t:
                # prefilled but dead on arrival at the slot: shedding
                # here frees the slot-steps its whole generation would
                # have wasted.  'fifo' admits it anyway — that mode's
                # contract is serve-everything-late, nothing shed.
                self._shed_request(req, where='admit')
                continue
            try:
                self._decode_cache.admit(req, values)
            except Exception as exc:
                self._metrics.note_error()
                req.set_error(exc)
                continue
            if req.trace is not None:
                req.trace.mark('admit')
            admitted += 1
        return admitted

    def _decode_dispatch(self):
        """Enqueue ONE K-step decode scan against the cache's CURRENT
        carry — which, mid-chain, is the previous scan's device-
        resident output (donated in place on device): scan N+1 chains
        onto scan N with no token block materializing on host (ISSUE
        9).  The async token/alive outputs go on the in-flight chain
        for a later harvest.  Returns True when a scan dispatched."""
        cache = self._decode_cache
        k = self.config.decode_steps
        snap = cache.snapshot()
        # slot-map snapshot BEFORE the dispatch: a wedged or erroring
        # decode scan must leave the occupancy picture in the ring —
        # chain_depth records how many scans were already in flight
        _trace.flight_recorder.record(
            'decode_lot', engine=self.name, steps=k,
            chain_depth=len(self._decode_inflight), slot_map=snap)
        try:
            with self._gated():
                carry, toks, alive_in, _ = \
                    (self._pe_step or self._exe)._dispatch_decode_multi(
                        carry=cache.carry(), steps=k,
                        decode=self._gen_decode_arg,
                        program=self.generation.step_program,
                        scope=self._scope)
        except Exception as exc:
            self._decode_fail(exc, snap)
            return False
        # the cache's carry is now the NEW scan's async output: the
        # next dispatch chains onto it without waiting for this one
        cache.set_carry(carry)
        # capture the slot->request map AT DISPATCH: a slot released
        # (and re-admitted) at a later flush must not receive this
        # scan's tokens — the done() guard at harvest closes the loop
        reqs = [cache.request_at(s) for s in range(cache.slots)]
        self._decode_inflight.append(
            ('decode', toks, alive_in, k, time.time(), reqs, snap))
        return True

    # ---- chunked prefill (ISSUE 14) -----------------------------------

    def _admit_chunk_pending(self):
        """Admit pending chunked-prefill prompts into free slots in the
        PREFILLING phase (chain-flush points, like _admit_ready).
        Returns how many were admitted."""
        admitted = 0
        while self._chunk_pending and self._decode_cache.free_slots():
            req = self._chunk_pending.popleft()
            if req.done():
                continue
            if self.config.scheduling == 'edf' and \
                    req.deadline_t is not None and \
                    time.time() > req.deadline_t:
                self._shed_request(req, where='admit')
                continue
            self._decode_cache.admit_prefilling(req)
            admitted += 1
        return admitted

    def _chunk_estimate(self):
        """The expected wall of one chunk dispatch: the profile's
        estimate for the chunk signature (cost-seeded, min-of-recent-
        walls), falling back to the measured chunk-wall floor."""
        est = self._profile.estimate(('chunk', self.config.prefill_chunk))
        if est is None:
            est = min(self._chunk_walls) if self._chunk_walls else 0.0
        return est

    def _chunk_should_dispatch(self):
        """At most ONE prefill chunk rides each worker cycle (the call
        site enforces the once-per-cycle half) — and only when it fits
        the decode lane's deadline headroom: under EDF, if some ACTIVE
        decoding request's deadline lands before the next step boundary
        plus a chunk wall, the chunk waits a cycle instead of stalling
        the token that would make that deadline (decode priority — the
        whole point of chunking).  Without imminent deadlines the chunk
        always rides."""
        if not self._chunking:
            return False
        cache = self._decode_cache
        if not any(cur < req.prompt_len
                   for _, req, cur in cache.prefilling_items()
                   if req is not None):
            return False
        if self.config.scheduling == 'edf':
            deadlines = [
                req.deadline_t for req in cache.active_requests()
                if not req.prefilling and req.deadline_t is not None
                and not req.done()]
            if deadlines:
                est_scan = (min(self._decode_walls)
                            if self._decode_walls else 0.0)
                if time.time() + est_scan + self._chunk_estimate() > \
                        min(deadlines):
                    return False
        return True

    def _chunk_dispatch(self):
        """Dispatch ONE C-token chunk advancing EVERY prefilling slot
        (batched, masked — the chunk sibling of _decode_dispatch),
        chained on the cache's current carry.  Slots whose prompt ends
        inside this block transition to decoding ON DEVICE (the kernel
        flips token/alive/budget), so the next decode scan picks them
        up at a step boundary; their cursors/phases mirror host-side
        deterministically.  Returns True when a chunk dispatched."""
        cache = self._decode_cache
        spec = self.generation
        c = self.config.prefill_chunk
        s = cache.slots
        work = [(idx, req, cur) for idx, req, cur
                in cache.prefilling_items()
                if req is not None and cur < req.prompt_len]
        if not work:
            return False
        blk = np.zeros((s, c, 1), np.int64)
        lens = np.zeros((s, ), np.int32)
        active = np.zeros((s, ), bool)
        fin = np.zeros((s, ), bool)
        budget = np.zeros((s, ), np.int32)
        for idx, req, cur in work:
            n = min(c, req.prompt_len - cur)
            blk[idx, :n, 0] = req.prompt_tokens[cur:cur + n]
            lens[idx] = n
            active[idx] = True
            if cur + n >= req.prompt_len:
                fin[idx] = True
                budget[idx] = req.max_len
        feed = {spec.chunk_token: blk,
                spec.chunk_token + SEQLEN_SUFFIX: lens}
        if spec.chunk_len is not None:
            feed[spec.chunk_len] = lens.astype(np.float32)[:, None]
        aux = {'active': active, 'finish': fin, 'budget': budget}
        snap = cache.snapshot()
        _trace.flight_recorder.record(
            'chunk_lot', engine=self.name, width=int(c),
            prefilling=len(work), finishing=int(fin.sum()),
            chain_depth=len(self._decode_inflight), slot_map=snap)
        try:
            with self._gated():
                carry, ok, _ = \
                    (self._pe_chunk or self._exe)._dispatch_chunk_prefill(
                        feed=feed, carry=cache.carry(), aux=aux,
                        chunk=self._gen_chunk_arg,
                        program=spec.chunk_program, scope=self._scope)
        except Exception as exc:
            self._decode_fail(exc, snap)
            return False
        cache.set_carry(carry)
        self._metrics.note_chunk_dispatch(
            sum(int(lens[idx]) for idx, _, _ in work))
        self._prefill_since_harvest = True
        t_disp = time.time()
        for idx, req, cur in work:
            cache.advance_prefill(idx, int(lens[idx]))
            if fin[idx]:
                cache.finish_prefill(idx)
                if req.trace is not None:
                    # decode begins at this dispatch: the 'prefill'
                    # trace stage (collect -> admit) ends here
                    req.trace.mark('admit', t_disp)
        self._decode_inflight.append(
            ('chunk', ok, None, int(c), t_disp, None, snap))
        return True

    def _decode_harvest_one(self):
        """Harvest the OLDEST in-flight decode-lane dispatch (ISSUE 9 —
        the host half the per-scan-sync lane paid BETWEEN scans now
        runs while the next scan computes).  A 'chunk' entry (ISSUE
        14) syncs only its small completion marker: the chunk wall
        feeds the decode-priority budget (and a deferred device error
        poisons the chain exactly like a scan's).  A 'decode' entry
        syncs its token block, replays the scan's stop-condition
        masking host-side (EOS emitted / budget exhausted — the exact
        in-scan rule, so the host mirror never drifts from the device
        carry), delivers every request the scan finished, and releases
        their slots.  Returns True unless the chain was poisoned."""
        kind, payload, alive_dev, k, t_disp, reqs, snap = \
            self._decode_inflight.popleft()
        # a harvest with NOTHING in flight behind it is a device-idling
        # HOST SYNC — the quantity the chained lane minimizes (the
        # per-scan-sync lane pays one per scan).  Judged at pop,
        # counted only on a SUCCESSFUL sync: a poisoned harvest must
        # not inflate the harvests/host_syncs counters the
        # decode_overlap gate and bench/load_gen reports are built on
        blocking = not self._decode_inflight
        cache = self._decode_cache
        if kind == 'chunk':
            try:
                np.asarray(payload)          # the sync point
            except Exception as exc:
                self._decode_fail(exc, snap)
                return False
            wall = max(time.time() - t_disp, 0.0)
            self._chunk_walls.append(wall)
            self._profile.observe(('chunk', self.config.prefill_chunk),
                                  wall)
            # a chunk harvest is a real host sync too: the ISSUE 9
            # ledger must see a chunk lane degraded to per-dispatch
            # sync (blocking with nothing behind it), or the gauges
            # built to catch that would stay flat
            self._metrics.note_decode_harvest(blocking=blocking)
            if cache.active_slots() == 0 and not self._decode_inflight:
                # a chunk entry can be the LAST harvest of a busy
                # period (everything else shed): same idle reset as
                # the decode branch below
                self._reset_stall_gauge()
            return True
        toks_dev = payload
        try:
            toks = np.asarray(toks_dev)      # the sync point
            alive_in = np.asarray(alive_dev)
        except Exception as exc:
            self._decode_fail(exc, snap)
            return False
        self._metrics.note_decode_harvest(blocking=blocking)
        t_sync = time.time()
        self._decode_walls.append(max(t_sync - t_disp, 0.0))
        # inter-token stall gauge (ISSUE 14): the wall gap between
        # consecutive token-block harvests while PREFILL work (a
        # monolithic prefill lot or a chunk dispatch) was in flight,
        # in units of the lane's own min scan wall — "how many step
        # boundaries did an in-flight decode miss to someone's
        # prompt".  Counted only when some REQUEST was decoding across
        # the whole gap (alive at both harvest endpoints — keyed by
        # request identity, not slot index: a slot released and
        # re-admitted between harvests carries a DIFFERENT request
        # whose own prefill is not a stall, it is the prefill).
        # Chunking bounds the gauge at ~one chunk; the monolithic
        # lane pays the whole prompt.
        # the set holds the request OBJECTS (identity hash), not their
        # id()s: a freed request's recycled id could otherwise alias a
        # new admission across the gap
        alive_reqs = frozenset(
            reqs[int(s)]
            for s in np.nonzero(alive_in.any(axis=0))[0]
            if reqs[int(s)] is not None)
        if self._last_harvest_t is not None and \
                self._prefill_since_harvest and \
                (alive_reqs & self._last_harvest_alive):
            gap = max(t_sync - self._last_harvest_t, 0.0)
            floor = min(self._decode_walls) if self._decode_walls \
                else 0.0
            self._metrics.note_decode_stall(
                gap / max(floor, 1e-9), gap)
        self._last_harvest_t = t_sync
        self._last_harvest_alive = alive_reqs
        self._prefill_since_harvest = False
        end_id = self.generation.end_id
        finished = 0
        for s, req in enumerate(reqs):
            if req is None or req.done():
                # freed before this scan dispatched, or already
                # delivered/shed — a dead slot's alive_in column is
                # all-False, so there are no tokens to lose here
                continue
            req.tokens.extend(int(t) for t in toks[alive_in[:, s], s])
            # the scan's own stop rule, replayed host-side: a slot
            # dies when it emits end_id or exhausts its budget — so
            # finish-detection needs no extra device read (the carry's
            # alive leaf stays un-synced, free to chain)
            budget = min(req.max_len, self.generation.max_len)
            done = req.tokens and (req.tokens[-1] == end_id or
                                   len(req.tokens) >= budget)
            if done and req.slot == s:
                if req.trace is not None:
                    req.trace.mark('decode_end', t_sync)
                cache.release(s)
                self._finish_generate(req)
                finished += 1
        self._metrics.note_decode_dispatch(
            k, int(alive_in.sum()), k * cache.slots, finished)
        if cache.active_slots() == 0 and not self._decode_inflight:
            # lane going idle: the NEXT busy period's first harvest
            # must not measure the idle gap as a prefill stall
            self._reset_stall_gauge()
        if _profiler.is_profiler_enabled() or _trace.spans_enabled():
            _profiler.record_event(self._spans + 'decode[x%d]' % k,
                                   time.time() - t_sync, start=t_sync)
        return True

    def _reset_stall_gauge(self):
        """Clear the inter-token stall gauge's episode state (ISSUE
        14) when the decode lane goes idle — by harvest (either kind),
        shed, or a poisoned-chain reset.  Without this, the next busy
        period's first harvest would measure the whole idle gap
        against a STALE _last_harvest_t (and a recycled slot index
        could satisfy the alive-across-both-endpoints guard),
        permanently corrupting the max the chunked_prefill gate and
        the bench/load_gen reports are built on."""
        self._last_harvest_t = None
        self._last_harvest_alive = frozenset()
        self._prefill_since_harvest = False

    def _decode_fail(self, exc, snap):
        """A decode dispatch or harvest failed: the chain behind it is
        poisoned (every later scan consumed the bad carry), so error
        EVERY slotted request, drop the chain, and reset the cache to
        a fresh host-side carry — the worker survives and the next
        admission decodes from clean slabs."""
        self._metrics.note_error()
        _trace.flight_recorder.dump(
            'decode_error:%s' % self.name, error=repr(exc),
            slot_map=snap, chain_depth=len(self._decode_inflight))
        cache = self._decode_cache
        self._decode_inflight.clear()
        for req in cache.active_requests():
            cache.release(req.slot)
            if not req.done():
                req.set_error(exc)
        cache.reset()
        self._reset_stall_gauge()

    def _decode_flush(self):
        """Chain-flush point (ISSUE 9): harvest EVERY in-flight scan so
        the slot map and the carry are consistent — admission, shed
        deactivation and cache eviction mutate slots, and must never
        race a scan that was dispatched against the pre-mutation
        carry.  Returns True unless the chain was poisoned."""
        flushed = bool(self._decode_inflight)
        while self._decode_inflight:
            if not self._decode_harvest_one():
                return False
        if flushed:
            self._metrics.note_decode_flush()
        return True

    def _decode_mirror_alive(self, req):
        """The host's view of whether ``req``'s slot can still be
        alive, from HARVESTED tokens only (in-flight scans unknown —
        conservatively alive): the same stop rule the scan masks."""
        budget = min(req.max_len, self.generation.max_len)
        return len(req.tokens) < budget and (
            not req.tokens or req.tokens[-1] != self.generation.end_id)

    def _decode_should_dispatch(self):
        """Dispatch another scan only when some occupied slot can
        still be alive AFTER the scans already in flight: a request's
        remaining budget is deterministic (EOS only ends it sooner),
        so when every active request's budget is provably consumed by
        in-flight steps, another scan could only run frozen slots —
        harvest instead."""
        active = self._decode_cache.active_requests()
        if not active:
            return False
        for req in active:
            if req.prefilling:
                # a PREFILLING slot (ISSUE 14) is inert in the scan
                # (alive=False) until its finishing chunk dispatches —
                # it must not justify a scan of frozen slots
                continue
            if not self._decode_mirror_alive(req):
                continue
            budget = min(req.max_len, self.generation.max_len)
            inflight_steps = sum(
                e[3] for e in self._decode_inflight
                if e[0] == 'decode' and req in e[5])
            if budget - len(req.tokens) - inflight_steps > 0:
                return True
        return False

    def _decode_doomed(self):
        """Active generations whose deadline lands before even the
        NEXT step boundary — one measured scan wall away — can arrive
        (ISSUE 8, sharpened by ISSUE 9): any further tokens would be
        late anyway, so the slot is better spent on a live request.
        ONE predicate shared by _decode_needs_flush and the shed loop:
        if the two drifted, needs-flush could trip every cycle while
        the shed loop sheds nothing — silently degrading the chain to
        per-scan sync with token-identical outputs (no test would
        trip).  EDF only; 'fifo' never sheds."""
        if self.config.scheduling != 'edf':
            return []
        now = time.time()
        est = min(self._decode_walls) if self._decode_walls else 0.0
        return [req for req in self._decode_cache.active_requests()
                if req.deadline_t is not None and
                now + est > req.deadline_t]

    def _decode_needs_flush(self):
        """True when the next cycle must mutate slots: a deadlined
        active generation to shed, or prefilled requests with a free
        slot to admit into.  Deliberately NOT 'prefills waiting but no
        slot free': forcing a flush every cycle to poll for releases
        would degrade the chain to the per-scan-sync lane exactly when
        a backlog queues — the opportunistic and backpressure harvests
        already release finished slots as the chain advances, and the
        free slot trips this check on the next cycle."""
        cache = self._decode_cache
        if (self._gen_ready or self._chunk_pending) and \
                cache.free_slots():
            return True
        return bool(self._decode_doomed())

    def _decode_cycle(self):
        """One decode-lane turn (ISSUE 9, pipelined): flush the chain
        when admission or shedding must mutate slots, enqueue the next
        chained scan FIRST, then harvest the oldest in-flight scan
        behind it — the dispatch-before-harvest order is the whole
        point: scan N+1 is already queued on device while the host
        syncs N's token block, so the harvest round trip never idles
        the device.  decode_pipeline_depth=1 degenerates to the PR 7
        per-scan-sync lane: dispatch, harvest, repeat.  Returns True
        when the lane made progress (dispatched, harvested, admitted
        or shed)."""
        cache = self._decode_cache
        if cache is None:
            return False
        progressed = False
        if self._decode_needs_flush():
            progressed = True
            if not self._decode_flush():
                return True
            # shed at the flushed boundary: the chain is empty, so
            # deactivation mutates a consistent carry (the doomed
            # predicate is shared with _decode_needs_flush)
            for req in self._decode_doomed():
                slot = req.slot
                cache.release(slot)
                cache.deactivate(slot)
                if req.trace is not None:
                    req.trace.add_count('decode_steps',
                                        len(req.tokens))
                self._shed_request(req, where='decode')
            if cache.active_slots() == 0:
                # sheds can empty the lane with no harvest to follow:
                # the chain is flushed here, so idle-reset the stall
                # gauge before fresh admissions start a new episode
                self._reset_stall_gauge()
            self._admit_ready()
            if self._chunking:
                self._admit_chunk_pending()
        dispatched = False
        if self._decode_should_dispatch():
            dispatched = self._decode_dispatch()
            progressed = dispatched or progressed
        # at most ONE prefill chunk rides each cycle, AFTER the decode
        # dispatch (decode priority — ISSUE 14); it chains on the same
        # carry, so the max decode stall it can add is one chunk wall
        if self._chunk_should_dispatch():
            chunked = self._chunk_dispatch()
            dispatched = dispatched or chunked
            progressed = chunked or progressed
        if not dispatched:
            # nothing worth another dispatch: drain the chain so
            # finished requests deliver and their slots free
            while self._decode_inflight:
                progressed = True
                if not self._decode_harvest_one():
                    return True
        # pipeline backpressure: at most decode_pipeline_depth
        # dispatches in flight — the oldest harvests while the newest
        # computes
        while len(self._decode_inflight) >= \
                self.config.decode_pipeline_depth:
            progressed = True
            if not self._decode_harvest_one():
                break
        return progressed

    def _finish_generate(self, req):
        """Deliver one finished generation request: token ids out,
        trace finalized (prefill/decode/detokenize stages + the
        decode_steps count) BEFORE the future resolves."""
        out = np.asarray(req.tokens, np.int64)
        if req.trace is not None:
            req.trace.add_count('decode_steps', len(req.tokens))
            self._metrics.note_stages(req.trace.finalize())
            _trace.record_span(
                self._spans + 'generate', req.trace.t0,
                req.trace.e2e_s, trace_id=req.trace_id)
        req.set_result(out)
        self._drains.note()
        if req.latency_s is not None:
            self._metrics.note_latency(req.latency_s)

    def _gen_busy(self):
        """True while the generation lane has work: prefilled (or
        chunk-pending) requests awaiting slots, slots actively decoding
        or prefilling, or in-flight chained dispatches awaiting
        harvest."""
        return self._decode_cache is not None and (
            bool(self._gen_ready) or bool(self._chunk_pending) or
            bool(self._decode_inflight) or
            self._decode_cache.any_active())

    def evict_decode_cache(self):
        """Demote the decode slot cache to host memory under a
        paused() window (bitwise — in-flight generations resume exactly
        after transparent re-staging) and drop the prefill/step
        executables.  Returns bytes moved — the registry's arbiter
        calls this to release an idle generation model's slabs."""
        if self._decode_cache is None:
            return 0
        with self.paused():
            moved = self._decode_cache.to_host()
            programs = [self.generation.prefill_program,
                        self.generation.step_program]
            if self.generation.chunk_program is not None:
                programs.append(self.generation.chunk_program)
            self.drop_executables(programs=programs)
        return moved

    # ---- worker -------------------------------------------------------

    def _safe_make_lot(self, requests):
        """_make_lot that fails the LOT, not the worker: a malformed
        request must error its own future and leave the engine serving
        (an unhandled exception here would kill the daemon thread and
        strand every later caller)."""
        try:
            return self._make_lot(requests)
        except Exception as exc:
            self._metrics.note_error()
            for req in requests:
                req.set_error(exc)
            return None

    def _route_chunked(self, reqs):
        """Chunked-prefill routing (ISSUE 14): under
        ``prefill_chunk=C`` a generation lot never forms — the prompt
        tokens were captured at submit, so the requests queue for a
        PREFILLING slot and their prompts ride chunk dispatches
        instead of a prefill-program lot.  (They still travel the
        batcher for wake-ups, EDF ordering and queue-shed semantics.)
        Returns the requests that still need a lot; None when all were
        routed to the chunk lane."""
        if not self._chunking or not reqs or reqs[0].kind != 'generate':
            return reqs
        now = time.time()
        for req in reqs:
            if req.trace is not None:
                req.trace.mark('collect', now)
            self._chunk_pending.append(req)
        return None

    def _collect_block(self, first_lot):
        """Extend a block with already-flushable same-bucket lots, then
        TRIM to a power-of-two lot count (extras go back on the carry
        queue): `steps` is a static jit argument of the eval scan, so a
        free-running 1..K count would mint up to K executables per
        bucket under fluctuating traffic — the quantized ladder bounds
        it at log2(K)+1."""
        lots = [first_lot]
        while len(lots) < self.config.steps_per_dispatch:
            if self._carry:
                lot = self._carry.popleft()
            else:
                more = self._batcher.next_lot(timeout=0)
                if not more:
                    break
                lot = self._safe_make_lot(more)
                if lot is None:
                    continue
            if lot.sig != lots[0].sig:
                self._carry.appendleft(lot)
                break
            lots.append(lot)
        k = 1
        while k * 2 <= len(lots):
            k *= 2
        self._carry.extend(lots[k:])
        return lots[:k]

    def _serve_loop(self):
        poll = max(min(self.config.max_wait_s, 0.005), 0.001)
        while True:
            try:
                reqs = []
                if not self._carry:
                    # idle engine blocks on the queue's condition var
                    # (submit/close notify) OUTSIDE the cycle lock, so a
                    # paused() window never has to wait for traffic; an
                    # awaiting in-flight dispatch — or a busy decode
                    # lane, which must keep stepping between arrivals —
                    # warrants the short drain poll
                    reqs = self._batcher.next_lot(
                        timeout=poll if (self._inflight or
                                         self._gen_busy()) else None)
                    if reqs is None:
                        break  # closed and drained
                # one collect->dispatch->drain->decode cycle is the
                # pause unit: paused() holds the cycle lock while
                # weights move, and the worker parks HERE between cycles
                with self._cycle_lock:
                    if reqs:
                        reqs = self._route_chunked(reqs)
                    if self._carry and not reqs:
                        self._dispatch(
                            self._collect_block(self._carry.popleft()))
                    elif reqs:
                        lot = self._safe_make_lot(reqs)
                        if lot is not None:
                            self._dispatch(self._collect_block(lot))
                    elif self._inflight and not self._gen_busy():
                        self._drain_one()  # idle: deliver early
                    # pipeline backpressure: keep at most pipeline_depth
                    # dispatches in flight — host feeds N+1 while N
                    # computes
                    while len(self._inflight) >= self.config.pipeline_depth:
                        self._drain_one()
                    if self._decode_cache is not None:
                        # deliver completed dispatches even while the
                        # decode lane is busy: a forward future ready
                        # after one cycle must not wait out every
                        # active generation, and a prefill stuck in
                        # the pipeline while slots sit free starves
                        # admission
                        if self._inflight and self._gen_busy():
                            self._drain_one()
                        # one decode scan per cycle: forward lots and
                        # decode steps interleave on the worker, so
                        # neither lane can starve the other
                        self._decode_cycle()
            except Exception as exc:
                # belt-and-braces: _dispatch/_drain_one already error
                # their own lots' futures; whatever still escapes must
                # not kill the serving thread
                self._metrics.note_error()
                _trace.flight_recorder.dump(
                    'worker_error:%s' % self.name, error=repr(exc))
        with self._cycle_lock:
            while self._carry:
                self._dispatch([self._carry.popleft()])
            while self._inflight:
                self._drain_one()
            # run the generation lane dry: admitted requests decode to
            # their stop conditions, prefilled ones admit as slots
            # free, and the in-flight chain harvests to empty
            while self._gen_busy():
                if not self._decode_cycle():
                    break
            if self._decode_cache is not None:
                self._decode_flush()

    def _drain_inline(self):
        """Synchronous mode: flush + dispatch + deliver on the calling
        thread (no micro-batching across callers, no pipelining).
        Serialized by _inline_lock — concurrent submitters to a
        never-start()ed engine must not interleave on _inflight/_carry."""
        with self._inline_lock:
            while True:
                progressed = False
                if self._carry:
                    self._dispatch(
                        self._collect_block(self._carry.popleft()))
                    progressed = True
                else:
                    reqs = self._batcher.next_lot(timeout=0, force=True)
                    if reqs:
                        reqs = self._route_chunked(reqs)
                        if reqs:
                            lot = self._safe_make_lot(reqs)
                            if lot is not None:
                                self._dispatch(self._collect_block(lot))
                        progressed = True
                while self._inflight:
                    self._drain_one()
                    progressed = True
                # generation work drains synchronously too: decode
                # cycles run until every submitted request finished
                # (inline mode has no worker to step the lane later)
                if self._gen_busy():
                    progressed = self._decode_cycle() or progressed
                if not progressed and not self._carry:
                    break
