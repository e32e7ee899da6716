"""What the program records about its own time: five legs.

  1. **request traces** -- a ``TraceContext`` carries one trace id from
     the registry router / ``submit()`` across threads and layers
     (submit thread -> micro-batch queue -> worker -> drain), marking
     absolute stage boundaries so ``finalize()`` yields a per-request
     breakdown (arbitration / queue / pad / dispatch / device / trim)
     whose stages sum to the measured end-to-end latency.  The ambient
     ``attach()``/``current()`` pair hands a context across an API
     boundary (the ModelRegistry attaches before calling
     ``engine.submit``) without widening every signature.

  2. **host spans** -- ``span(name, event=..., **args)`` is the one way
     the executors and the FeedPipeline mark a stretch of host work.  It
     enters a ``jax.profiler.TraceAnnotation`` (a span costs about two
     microseconds while no profiler session runs) and, inside one, lands
     on the host plane of the same ``.xplane.pb`` as the device's
     operations: on the trace's clock, next to the idle gap it explains.
     Spans are named ``paddle_tpu/<layer>/<what>`` and wrap a dispatch
     or a block, never an op or a step.  Only while ``fluid.profiler``
     or a ``tracing()`` window is on does a span also stamp
     ``time.time()`` and feed the older host-clock tables under the
     name given as ``event=`` (``profiler.record_event`` ->
     ``record_span``; tools/timeline.py and tools/trace_export.py read
     those).  The device side of the same trace is named by the
     ``jax.named_scope`` of ``ops.registry.run_op`` (one scope per
     Fluid op, ``<op type>.<first output>``, under ``paddle_tpu.step``);
     ``chipbench/scopes.py`` reduces both to numbers.

  3. **compile log** -- ``compile_log()`` registers ``jax.monitoring``
     listeners once per process and keeps what JAX itself reports each
     time it traces, lowers, compiles or loads an executable from the
     persistent cache, with the function's name and
     ``time.perf_counter()`` at the end.  ``compile_summary(since,
     until)`` sums it per kind: the count of compiles inside a window,
     and set-up's split into lowering and compile-or-load.  It is
     touched only when JAX compiles.
     Beside it, ``lowering_choices(op_type)`` keeps what a lowering that
     picks among implementations chose for each op of a program
     (``flash_attention``: dense, pallas, ring, ulysses, with the head
     counts it saw; ``ssd_scan``: pallas with its block, or xla with its
     chunk; ``moe_experts``; ``param_update_order``).

  4. **the executables' own record** -- what XLA made of a lane's step
     program after the scopes were written.  The executors hand
     ``note_executable`` each lane's jitted function and the abstract
     twins of its arguments once a signature (a dictionary insert; no
     lowering, no compile, no text), and ``executable_record(fun_name)``
     makes the record when somebody reads it, once: XLA's memory
     analysis and one row for every operation of the optimized module
     under the name a device trace prints (``hlo_text.op_rows``:
     the Fluid op that owns each operation the compiler made itself,
     the scopes fused into each fusion, what each prefetch carries).
     While the executor lives the read lowers and compiles through
     JAX's own caches, which hand back the executable the lane runs: no
     second compile, no second load.  After it died the lane's body is
     traced again and the compile is a load from the persistent cache.
     ``aot_compile`` is the one ahead-of-time path: ``analyze_cost``
     (the per-executable cost registry under ``FLAGS_cost_accounting``),
     ``Executor.memory_analysis`` and ``tools/compile_for_v5e.py`` go
     through it too.

  5. **flight recorder** -- a bounded ring of the last N dispatch/lot
     records (trace ids, signatures, shapes, timings) that ``dump()``s
     on worker error or when the ``watchdog`` trips a registered stall
     probe (queue age / feed-stall thresholds) -- the post-mortem a
     stalled serving worker otherwise takes to its grave.
"""

import contextlib
import itertools
import json
import logging
import os
import threading
import time
import weakref
from collections import deque

__all__ = [
    'TraceContext', 'STAGES', 'new_trace_id', 'attach', 'current',
    'tracing', 'record_span', 'spans', 'clear_spans', 'dump_spans',
    'span', 'compile_log', 'compile_summary',
    'note_lowering_choice', 'lowering_choices',
    'note_executable', 'executable_record', 'aot_compile',
    'FlightRecorder', 'flight_recorder', 'Watchdog', 'watchdog',
    'analyze_cost',
]

# canonical per-request stages, in pipeline order: arbitration (the
# registry's residency gate, pre-enqueue), queue (enqueue -> lot
# collection), pad (request prepare + lot padding), dispatch (lot ready
# -> device dispatch issued, incl. carry/gate waits), device (dispatch
# -> host sync), trim (sync -> per-request slice delivered).
# GENERATION requests (ISSUE 7) replace the post-collection stages with
# prefill (lot -> slot admission: the prompt's pad/dispatch/device/trim
# as one stage), decode (admission -> last decode-scan sync) and
# detokenize (last sync -> delivery); their breakdown also carries a
# decode_steps count.
# SHED requests (ISSUE 8) end in a 'shed' stage instead: the seconds
# the request sat before the deadline scheduler dropped it (its future
# raises DeadlineExceededError — served stages before the shed, e.g. a
# generation's prefill, still appear).
STAGES = ('arbitration', 'queue', 'pad', 'prefill', 'dispatch',
          'device', 'trim', 'decode', 'detokenize', 'shed')

_ids = itertools.count(1)
_id_lock = threading.Lock()


def new_trace_id():
    with _id_lock:
        return 'tr-%06d' % next(_ids)


class TraceContext(object):
    """One request's trace: an id, absolute stage-boundary marks, and
    pre-accumulated stage seconds (stages measured where they happen —
    the registry's arbitration window, the submit path's prepare —
    before the boundary marks take over).  Thread-crossing is the
    point: the submit thread marks 'enqueue', the worker marks
    'collect'/'lot'/'dispatch', the drain marks 'sync', and
    ``finalize()`` (at delivery) turns the marks into the breakdown."""

    __slots__ = ('trace_id', 't0', 'marks', 'stage_s', 'e2e_s', 'counts')

    def __init__(self, trace_id=None):
        self.trace_id = trace_id or new_trace_id()
        self.t0 = time.time()
        self.marks = {}
        self.stage_s = {}
        self.e2e_s = None
        self.counts = {}

    def add_stage(self, stage, seconds):
        """Accumulate seconds measured outside the mark chain (e.g.
        'arbitration' by the registry, the prepare half of 'pad')."""
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + float(seconds)

    def add_count(self, name, n):
        """Accumulate a per-request integer (e.g. ``decode_steps`` —
        how many decode-scan steps this generation request consumed);
        rides ``breakdown()`` next to the stage times."""
        self.counts[name] = self.counts.get(name, 0) + int(n)

    def mark(self, name, t=None):
        self.marks[name] = time.time() if t is None else t

    def finalize(self, end=None):
        """Close the trace: derive the boundary-mark stages and the
        end-to-end wall clock.  Robust to missing marks (an errored
        request finalizes with whatever boundaries it reached).
        A GENERATION request (an 'admit' mark present — ISSUE 7)
        derives prefill/decode/detokenize instead of the per-lot
        pad/dispatch/device/trim splits: its prompt pass IS one stage,
        and everything after admission belongs to the decode scan."""
        end = time.time() if end is None else end
        m = self.marks

        def seg(a, b):
            return max(m[b] - m[a], 0.0) if a in m and b in m else 0.0

        self.add_stage('queue', seg('enqueue', 'collect'))
        if 'admit' in m:
            self.add_stage('prefill', seg('collect', 'admit'))
            if 'decode_end' in m:
                self.add_stage('decode', seg('admit', 'decode_end'))
                self.add_stage('detokenize',
                               max(end - m['decode_end'], 0.0))
            else:
                # errored before any scan drained: whatever remains is
                # decode-lane time
                self.add_stage('decode', max(end - m['admit'], 0.0))
        else:
            self.add_stage('pad', seg('collect', 'lot'))
            self.add_stage('dispatch', seg('lot', 'dispatch'))
            self.add_stage('device', seg('dispatch', 'sync'))
            if 'sync' in m:
                self.add_stage('trim', max(end - m['sync'], 0.0))
        self.e2e_s = end - self.t0
        return self.stage_s

    def breakdown(self):
        """The response-surface view: trace id, end-to-end ms, and the
        per-stage ms in canonical order (only stages that occurred),
        plus any per-request counts (generation requests carry
        ``decode_steps``)."""
        out = {
            'trace_id': self.trace_id,
            'e2e_ms': (round(self.e2e_s * 1e3, 3)
                       if self.e2e_s is not None else None),
            'stages_ms': {s: round(self.stage_s[s] * 1e3, 3)
                          for s in STAGES if s in self.stage_s},
        }
        if self.counts:
            out.update(self.counts)
        return out


# ---- ambient context (cross-layer handoff) ----------------------------

_ambient = threading.local()


@contextlib.contextmanager
def attach(ctx):
    """Make ``ctx`` the calling thread's ambient trace for the block —
    the registry router attaches before engine.submit() so the engine
    threads the SAME trace id instead of minting a new one."""
    prev = getattr(_ambient, 'ctx', None)
    _ambient.ctx = ctx
    try:
        yield ctx
    finally:
        _ambient.ctx = prev


def current():
    return getattr(_ambient, 'ctx', None)


# ---- span log (the Chrome exporter's source) --------------------------

_SPAN_CAP = 8192
_span_lock = threading.Lock()
_span_log = deque(maxlen=_SPAN_CAP)
_span_state = {'enabled': 0}


def spans_enabled():
    return _span_state['enabled'] > 0


@contextlib.contextmanager
def tracing():
    """Enable span capture for the block (nested windows stack); spans
    from a previous window are cleared on the OUTERMOST entry so each
    session exports its own record."""
    with _span_lock:
        if _span_state['enabled'] == 0:
            _span_log.clear()
        _span_state['enabled'] += 1
    try:
        yield
    finally:
        with _span_lock:
            _span_state['enabled'] -= 1


def record_span(name, start_s, dur_s, trace_id=None, lane=None):
    """One timed slice in the span log; ``lane`` defaults to the
    CURRENT thread's name — spans land in per-thread lanes, which is
    exactly how the Chrome exporter renders them."""
    if not spans_enabled():
        return
    span = {
        'name': name,
        'start_s': float(start_s),
        'dur_s': float(dur_s),
        'lane': lane or threading.current_thread().name,
    }
    if trace_id is not None:
        span['trace_id'] = trace_id
    with _span_lock:
        _span_log.append(span)


def spans():
    with _span_lock:
        return list(_span_log)


def clear_spans():
    with _span_lock:
        _span_log.clear()


def dump_spans(path):
    """Write the span log as the JSON file tools/trace_export.py
    consumes; returns the span count."""
    snapshot = spans()
    with open(path, 'w') as f:
        json.dump({'spans': snapshot}, f)
    return len(snapshot)


# ---- host spans on the profiler's clock --------------------------------

class span(object):
    """``with span('paddle_tpu/executor/launch', steps=4): ...`` -- a
    stretch of host work as a ``jax.profiler.TraceAnnotation`` (about
    two microseconds while no profiler session runs; inside one, an
    event named ``name`` with ``args`` as its stats on the calling
    thread's line of the trace's host plane).

    ``event`` is the span's name in the older host-clock tables
    (``pipeline/stage[x4]``, ``executor_run_multi/block0[x4]``): while
    ``fluid.profiler`` or a ``tracing()`` window is on, the span is also
    timed with ``time.time()`` and handed to ``profiler.record_event``
    under that name.  It may be set inside the block (``sp.event = ...``)
    where the name depends on what the block found; ``None`` records
    nothing there.  ``recording`` says whether that older leg is on, for
    the callers that then wait for the device so the slice covers its
    work."""

    __slots__ = ('event', 'recording', '_annotation', '_t0')

    def __init__(self, name, event=None, **args):
        from jax.profiler import TraceAnnotation
        self.event = event
        self._annotation = TraceAnnotation(name, **args)

    def __enter__(self):
        # fluid.profiler imports this module: looked up when used
        from . import profiler
        self.recording = (_span_state['enabled'] > 0
                          or profiler.is_profiler_enabled())
        self._annotation.__enter__()
        if self.recording:
            self._t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        if self.recording and self.event is not None:
            from . import profiler
            profiler.record_event(self.event, time.time() - self._t0,
                                  start=self._t0)
        return False


# ---- compile log (what JAX reports when it compiles) -------------------

# jax.monitoring's event -> the log's kind.  The backend_compile duration
# wraps compile_or_get_cached, so it covers a load from the persistent
# cache too; cache_hit / cache_miss / cache_retrieval say which it was
# (a miss is reported when the new entry is written; executables under
# the cache's size and compile-time thresholds report neither).
_COMPILE_DURATIONS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'backend_compile',
    '/jax/compilation_cache/cache_retrieval_time_sec': 'cache_retrieval',
}
_COMPILE_EVENTS = {
    '/jax/compilation_cache/cache_hits': 'cache_hit',
    '/jax/compilation_cache/cache_misses': 'cache_miss',
}
COMPILE_KINDS = tuple(_COMPILE_DURATIONS.values()) + tuple(
    _COMPILE_EVENTS.values())
_compile_lock = threading.Lock()
_compile_log = []
_compile_state = {'registered': False}


def _note_compile(kind, seconds, fun_name):
    entry = {'kind': kind, 'fun_name': fun_name, 'seconds': float(seconds),
             't_end': time.perf_counter()}
    with _compile_lock:
        _compile_log.append(entry)


def _on_duration(event, duration, **kwargs):
    kind = _COMPILE_DURATIONS.get(event)
    if kind is not None:
        _note_compile(kind, duration, kwargs.get('fun_name'))


def _on_event(event, **kwargs):
    kind = _COMPILE_EVENTS.get(event)
    if kind is not None:
        _note_compile(kind, 0.0, kwargs.get('fun_name'))


def compile_log():
    """Every trace, lowering, backend compile (or load) and persistent-
    cache hit, miss and retrieval JAX has reported in this process since
    the first call, oldest first: ``{'kind', 'fun_name', 'seconds',
    't_end'}`` with ``t_end = time.perf_counter()`` when the report
    came (the cache's three kinds carry no ``fun_name``: they follow
    the ``lower`` of the function they belong to).  The first call
    registers the ``jax.monitoring`` listeners, once per process; the
    executors make it when they are built, so a startup program's
    compiles are in the log."""
    with _compile_lock:
        if not _compile_state['registered']:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _compile_state['registered'] = True
        return list(_compile_log)


def compile_summary(since=None, until=None):
    """``{kind: {'count', 'seconds'}}`` over the log's entries whose
    ``t_end`` lies in ``[since, until)`` on ``time.perf_counter()``'s
    clock (either end open when None); every kind is present.
    ``seconds`` is the time in which at least one event of the kind was
    under way, not the sum of durations: JAX reports a jitted helper
    traced inside another function's trace with a duration of its own."""
    spans = {kind: [] for kind in COMPILE_KINDS}
    for e in compile_log():
        if (since is None or e['t_end'] >= since) and \
                (until is None or e['t_end'] < until):
            spans[e['kind']].append((e['t_end'] - e['seconds'], e['t_end']))
    out = {}
    for kind, ivs in spans.items():
        seconds, reach = 0.0, float('-inf')
        for start, end in sorted(ivs):
            if end > reach:
                seconds += end - max(start, reach)
                reach = end
        out[kind] = {'count': len(ivs), 'seconds': seconds}
    return out


# ---- what a lowering chose ----------------------------------------------

_choices = {}   # Program serial -> {(op_type, out_name): choice}


def note_lowering_choice(program, op_type, out_name, choice, **seen):
    """A lowering's record of the implementation it chose for the op of
    ``program`` that writes ``out_name`` (``flash_attention``: dense,
    pallas, ring or ulysses; ``ssd_scan``: pallas or xla), noted where the
    choice is made, with what it saw there that a reader may want beside
    it (``seen``: the head counts, the chunk).  Kept by output name: a
    program lowered again (another signature, a gradient's replay of the
    forward) overwrites its own entries and counts once."""
    with _compile_lock:
        _choices.setdefault(program._serial, {})[op_type, out_name] = (
            choice, seen)


def lowering_choices(op_type, seen=False):
    """One ``{choice: number of ops}`` for each Program that has had an
    ``op_type`` op lowered in this process, oldest first.  ``seen=True``:
    one ``{output name: dict(choice=..., **what the lowering saw)}`` a
    Program instead."""
    with _compile_lock:
        programs = [{out: c for (t, out), c in ops.items() if t == op_type}
                    for _, ops in sorted(_choices.items())]
    if seen:
        return [{out: dict(s, choice=c) for out, (c, s) in ops.items()}
                for ops in programs if ops]
    counted = [[c for c, _ in ops.values()] for ops in programs if ops]
    return [{c: ops.count(c) for c in sorted(set(ops))} for ops in counted]


# ---- flight recorder --------------------------------------------------

class FlightRecorder(object):
    """Bounded ring of recent dispatch/lot records.  Layers ``record``
    one small dict per dispatch (trace ids, sig, shape, timings);
    ``dump`` snapshots the ring on a worker error or a watchdog-tripped
    stall — the records ARE what was in flight.  ``last_dump`` keeps
    the most recent dump in memory (tests and post-mortems read it);
    ``dump_path`` (or the PADDLE_TPU_FLIGHT_DUMP env var) additionally
    writes each dump as JSON."""

    def __init__(self, capacity=256):
        self._lock = threading.Lock()
        self._records = deque(maxlen=int(capacity))
        self.last_dump = None
        self.dump_count = 0
        self.dump_path = None

    def record(self, kind, **fields):
        rec = dict(fields)
        rec['kind'] = kind
        rec['ts'] = time.time()
        with self._lock:
            self._records.append(rec)
        return rec

    def records(self):
        with self._lock:
            return list(self._records)

    def clear(self):
        with self._lock:
            self._records.clear()

    def dump(self, reason, **extra):
        dump = {
            'reason': reason,
            'ts': time.time(),
            'extra': extra,
            'records': self.records(),
        }
        with self._lock:
            self.last_dump = dump
            self.dump_count += 1
        path = self.dump_path or os.environ.get('PADDLE_TPU_FLIGHT_DUMP')
        if path:
            try:
                with open(path, 'w') as f:
                    json.dump(dump, f, default=repr)
            except OSError:
                pass  # a read-only fs must not mask the original error
        logging.getLogger('paddle_tpu').error(
            'flight recorder dump (%s): %d in-flight records',
            reason, len(dump['records']))
        return dump


flight_recorder = FlightRecorder()


# ---- watchdog ---------------------------------------------------------

class Watchdog(object):
    """Threshold probes over subsystem ages (oldest queued request,
    current feed stall).  A probe whose age crosses its threshold trips
    ONCE per stall episode (re-arming when the age drops back), dumping
    the flight recorder with the probe's name as the reason.  The
    polling thread starts with the first registration and exits with
    the last unregistration; ``check()`` runs one sweep synchronously
    (deterministic for tests)."""

    def __init__(self, interval_s=1.0):
        self.interval_s = float(interval_s)
        self._lock = threading.Lock()
        self._probes = {}  # name -> [age_fn, threshold_s, tripped]
        self._thread = None
        self._stop = threading.Event()

    def register(self, name, age_fn, threshold_s, context_fn=None):
        """Returns the KEY the probe landed under — a name already held
        by a live probe is uniquified (``name#2``, ...) instead of
        silently clobbered (two same-named engines must BOTH keep their
        stall monitoring; the profiler's metrics sources learned this
        the hard way).  Callers unregister by the returned key.

        ``context_fn`` (optional, zero-arg) is called when the probe
        trips and its result lands in the dump — the subsystem's own
        "what was in flight" view (e.g. the serving engine's queued +
        undrained trace ids), which the generic ring may not hold for
        work that stalled BEFORE dispatching."""
        with self._lock:
            key, n = name, 1
            while key in self._probes:
                n += 1
                key = '%s#%d' % (name, n)
            self._probes[key] = [age_fn, float(threshold_s), False,
                                 context_fn]
            if self._thread is None:
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._loop, name='trace-watchdog', daemon=True)
                self._thread.start()
        return key

    def unregister(self, name, age_fn=None):
        """Drop a probe by its registered key.  Pass ``age_fn`` to make
        the removal owner-checked: a stale GC finalizer whose key has
        since been re-registered by a NEW subsystem must not kill the
        survivor's monitoring."""
        with self._lock:
            if age_fn is not None and name in self._probes and \
                    self._probes[name][0] is not age_fn:
                return
            self._probes.pop(name, None)
            if not self._probes and self._thread is not None:
                self._stop.set()
                self._thread = None

    def check(self):
        """One sweep; returns the names that tripped this sweep."""
        with self._lock:
            probes = list(self._probes.items())
        tripped = []
        for name, state in probes:
            age_fn, threshold, was_tripped, context_fn = state
            try:
                age = age_fn()
            except Exception:
                continue  # a dying subsystem must not kill the watchdog
            if age is None:
                # nothing aging IS recovery (a drained queue, an idle
                # dispatch loop): re-arm, or a second stall episode
                # whose first observed age already exceeds the
                # threshold would never dump
                state[2] = False
                continue
            if age >= threshold and not was_tripped:
                state[2] = True
                tripped.append(name)
                extra = {}
                if context_fn is not None:
                    try:
                        extra = dict(context_fn() or {})
                    except Exception:
                        pass  # the stalled subsystem may be half-dead
                flight_recorder.dump('stall:%s' % name,
                                     age_s=round(float(age), 3),
                                     threshold_s=threshold, **extra)
            elif age < threshold:
                state[2] = False
        return tripped

    def _loop(self):
        stop = self._stop
        while not stop.wait(self.interval_s):
            self.check()


watchdog = Watchdog()


# ---- the one ahead-of-time path, and the executables' own record ---------

def _abstract(x):
    """A ShapeDtypeStruct twin of an array leaf, placed where a committed
    array lies (its sharding: then JAX's caches find the executable a
    dispatch of the same arguments made, and nothing is compiled twice);
    non-array leaves (static ints like the scan's step count) pass through
    untouched so jit's static_argnums still see their concrete values.
    Reads only what a donated (deleted) array still answers."""
    import jax
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    shape = getattr(x, 'shape', None)
    dtype = getattr(x, 'dtype', None)
    if shape is None or dtype is None or callable(shape):
        return x
    sharding = x.sharding if getattr(x, 'committed', False) else None
    return jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=sharding,
        weak_type=bool(getattr(x, 'weak_type', False)))


def aot_compile(jitted, args):
    """``jitted`` lowered with abstract twins of ``args`` and compiled: the
    ``jax.stages.Compiled`` to ask XLA about.  The twins never touch the
    real buffers, so this is safe before a dispatch whose arguments will be
    donated, and after it."""
    import jax
    return jitted.lower(*jax.tree_util.tree_map(_abstract, args)).compile()


def _memory_bytes(compiled):
    """XLA's ``memory_analysis()`` of a ``Compiled`` in bytes; None where
    the backend has none."""
    ma = compiled.memory_analysis()
    return None if ma is None else {
        key: int(getattr(ma, key + '_size_in_bytes', 0))
        for key in ('argument', 'output', 'alias', 'temp', 'generated_code')}


_executables = {}   # function name -> what note_executable was handed


def note_executable(jitted, args, rebuild):
    """The executors' half, where a lane meets a new argument signature:
    remember the lane's jitted function (weakly: a loaded program holds its
    temporaries' memory on the device, and dies with its executor), how to
    build it again (``rebuild()``: the block's, with no device memory
    behind it) and the abstract twins of ``args``, under the function's
    name (``paddle_tpu_train_scan``).  The newest signature of a name
    replaces the one before."""
    import jax
    from ..ops import registry
    noted = {'jitted': weakref.ref(jitted), 'rebuild': rebuild,
             'args': jax.tree_util.tree_map(_abstract, args),
             # the lowerings read it while a body is traced
             'amp': registry.amp_enabled()}
    with _compile_lock:
        _executables[jitted.__name__] = noted


def executable_record(fun_name):
    """What XLA made of the newest signature of the lane ``fun_name``
    (``paddle_tpu_train_scan``, ``paddle_tpu_eval_scan``, ...), made on the
    first read and kept; None where no such lane has run, or where a step of
    the making failed (logged; a reader is never raised into):

      fun_name, live   whether the executor's own jitted function was
                       still there: then ``lower`` and ``compile`` are
                       answered from JAX's caches with the executable the
                       lane runs; else the body was traced again and the
                       compile loads from the persistent cache
      memory           XLA's ``memory_analysis()``: ``argument``,
                       ``output``, ``alias``, ``temp``, ``generated_code``
                       bytes
      ops              ``hlo_text.op_rows`` of the optimized module
      seconds          what the making took: ``compile``, ``text``,
                       ``parse``
    """
    from ..ops import registry
    from . import hlo_text
    with _compile_lock:
        noted = _executables.get(fun_name)
    if noted is None:
        return None
    if 'record' not in noted:
        amp, record = registry.amp_enabled(), None
        try:
            t0 = time.perf_counter()
            jitted = noted['jitted']()
            live = jitted is not None
            if not live:
                # traced again: under the precision it was traced with
                registry.set_amp(noted['amp'])
                jitted = noted['rebuild']()
            compiled = aot_compile(jitted, noted['args'])
            memory = _memory_bytes(compiled)
            t1 = time.perf_counter()
            text = compiled.as_text()
            del compiled
            t2 = time.perf_counter()
            record = {'fun_name': fun_name, 'live': live, 'memory': memory,
                      'ops': hlo_text.op_rows(text),
                      'seconds': {'compile': t1 - t0, 'text': t2 - t1}}
            record['seconds']['parse'] = time.perf_counter() - t2
        except Exception as e:
            logging.getLogger('paddle_tpu').warning(
                'executable_record(%s): no record: %s: %s', fun_name,
                type(e).__name__, e)
        finally:
            registry.set_amp(amp)
        noted['record'] = record
    return noted['record']


# ---- per-executable cost accounting -----------------------------------

def analyze_cost(jitted, args, kind='run', steps=1, fetch_names=None):
    """AOT-compile ``jitted`` for ``args`` (``aot_compile``) and extract
    the executable's XLA cost/memory analyses.  Returns the cost-registry
    entry dict, or None when the backend exposes no analysis (the caller
    caches the outcome either way — analysis runs at most once per
    executable).  The compile is the dispatch's own: JAX's caches hand
    the same executable to whichever of the two comes second."""
    try:
        compiled = aot_compile(jitted, args)
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        memory = _memory_bytes(compiled)
    except Exception:
        return None
    steps = max(int(steps), 1)
    flops = float((ca or {}).get('flops', 0.0))
    entry = {
        'kind': kind,
        'steps': steps,
        'fetch_names': list(fetch_names or []),
        'flops': flops,
        'flops_per_step': flops / steps,
        'bytes_accessed': float((ca or {}).get('bytes accessed', 0.0)),
    }
    if memory is not None:
        entry.update({key + '_bytes': memory[key] for key in
                      ('argument', 'output', 'temp', 'generated_code')})
    return entry
