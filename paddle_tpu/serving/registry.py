"""Multi-model serving: N named InferenceEngines over ONE shared
device/mesh, with cross-model HBM arbitration.

The single-model engine (engine.py) already amortizes the per-dispatch
host cost; what a production server needs on top is the FLEET view the
reference stack never had (one predictor per process): which models are loaded,
what each one pins in device memory, and who gets evicted when the next
model arrives.  ``ModelRegistry`` is that subsystem:

  * **lifecycle** — ``load(name, dirname)`` (a save_inference_model
    dir) or ``load(name, program=...)`` builds a per-model engine with
    its own scope + executor over the registry's shared place/mesh;
    ``unload`` stops and forgets it; ``warm`` pre-compiles the bucket
    ladder; ``status()`` snapshots the fleet.  All thread-safe against
    in-flight requests.
  * **HBM arbiter** (arbiter.py) — every model's weight + executable
    footprint is accounted (seeded from
    ``fluid.contrib.memory_usage_calc``, corrected by live jax buffer
    stats once it serves), admission-controlled against
    ``hbm_budget_bytes``, and LRU-evicted to HOST memory when the
    budget forces it: the victim engine is paused (in-flight dispatches
    drain), its scope buffers demote to host ndarrays bitwise, and its
    executables drop — the next request to it transparently re-stages
    and recompiles (counted as a reload).
  * **router** — ``submit(model, feed)`` ensures residency, bumps the
    LRU, tracks per-model request/row rates, and forwards to the
    model's engine queue; each engine's worker drains its own queue
    while a shared dispatch GATE keeps device dispatches fair across
    models (one bounded critical section per dispatch — no model can
    hog the chip between another's dispatches).  The budget binds at
    ROUTING time: a request already queued on an engine when its model
    is evicted simply re-stages at its own dispatch (correct, slower),
    and the account is corrected at the model's next routing.
  * **observability** — per-model engine snapshots ride the profiler
    sidecar under the registry's metrics source; spans land in
    per-model ``:serving/<model>`` timeline rows (tools/timeline.py);
    ``metrics()`` carries the arbiter's eviction/reload/admission
    counters next to the router's rates.

    reg = serving.ModelRegistry(hbm_budget_bytes=2 << 30)
    reg.load('ranker', '/models/ranker')
    reg.load('retriever', '/models/retriever')
    with reg:                                  # starts every worker
        out, = reg.infer('ranker', {'x': batch})
    print(reg.status(), reg.metrics())
"""

import itertools
import json
import os
import threading
import time
import weakref

import numpy as np

from ..fluid import core
from ..fluid import profiler as _profiler
from ..fluid import trace as _trace
from ..fluid.flags import compile_cache_dir
from .arbiter import HBMArbiter, HBMBudgetError, program_seed_bytes
from .engine import InferenceEngine, ServingConfig
from .errors import OverloadedError

__all__ = ['ModelRegistry', 'WARM_CATALOG_BASENAME']

# the fleet's compile catalog (ISSUE 8): every registry.warm() call is
# recorded here as a replayable signature set (batch rungs x trailing
# rungs x decode-prefill extents), persisted INSIDE the persistent XLA
# compile cache directory (fluid.flags.compile_cache_dir) — the pairing
# is the point: the XLA cache holds the compiled executables keyed by
# traced signature, and the catalog holds WHICH signatures a fresh process
# must re-trace to hit them.  registry.prewarm(catalog) replays it so a
# restarted server compiles nothing on first traffic.
WARM_CATALOG_BASENAME = 'serving_warm_catalog.json'

# the decode-state cache's arbiter account rides next to its model's
# weight account under this suffix (ISSUE 7): `<model>:decode-cache` —
# evictable on its own (an idle generation model's slabs free without
# demoting its weights) and typed-rejected at load when the cache alone
# can never fit the budget
DECODE_CACHE_SUFFIX = ':decode-cache'

# a MESH-ROW-SHARDED embedding table's arbiter account rides next to
# its model's weight account under this suffix (ISSUE 11):
# `<model>:embed-table:<var>` — charged at the table's PER-DEVICE shard
# bytes (the budget is one chip's HBM; GSPMD lays only 1/extent of the
# rows on each device), so a table bigger than a single device's budget
# is admitted SHARDED while the same table unsharded stays inside the
# model's own full-size seed and draws the typed HBMBudgetError at load
EMBED_TABLE_SUFFIX = ':embed-table'

# a TWO-TIER cached table's account (ISSUE 12) bills the ``[C, D]`` HBM
# hot-row slab set (weight + optimizer accumulators), NOT the [V, D]
# master — that stays host-resident in the cache's AsyncSparseEmbedding
# tier.  `<model>:embed-cache:<var>` — a table bigger than the WHOLE
# mesh budget therefore ADMITS with overflow='host' semantics, while the
# identical program served without the cache keeps the full table in its
# model seed and draws the typed HBMBudgetError (the PR 10 behavior,
# now the pinned counterfactual).
EMBED_CACHE_SUFFIX = ':embed-cache'


def _row_sharded_tables(engine):
    """``{var_name: (global_bytes, per_device_bytes)}`` for every
    persistable >=2-D var of the engine's program whose sharding
    annotation row-shards it over a REAL mesh axis of the engine's own
    mesh.  Empty for single-device engines: an unsharded table lives
    whole on the one chip and stays inside the model's seed/footprint
    account."""
    pe = engine._pe
    if pe is None:
        return {}
    from ..parallel.api import sharding_of
    mesh_axes = dict(zip(pe._mesh.axis_names, pe._mesh.devices.shape))
    out = {}
    for var in engine._program.global_block().vars.values():
        if not getattr(var, 'persistable', False):
            continue
        shape = tuple(var.shape or ())
        if len(shape) < 2 or any(d is None or int(d) <= 0 for d in shape):
            continue
        spec = sharding_of(var)
        if spec is None or not len(spec) or spec[0] is None:
            continue
        axes = spec[0] if isinstance(spec[0], tuple) else (spec[0], )
        factor = 1
        for ax in axes:
            factor *= int(mesh_axes.get(ax, 1))
        if factor <= 1:
            continue
        itemsize = np.dtype(var.np_dtype).itemsize
        gbytes = int(np.prod([int(d) for d in shape])) * int(itemsize)
        out[var.name] = (gbytes, -(-gbytes // factor))
    return out


class _ModelEntry(object):
    __slots__ = ('name', 'engine', 'dirname', 'loaded_t', 'requests',
                 'rows', 'first_req_t', 'last_req_t', 'overload_rejects',
                 'table_accounts', 'embed_cache_accounts')

    def __init__(self, name, engine, dirname):
        self.name = name
        self.engine = engine
        self.dirname = dirname
        self.loaded_t = time.time()
        self.requests = 0
        self.rows = 0
        self.first_req_t = None
        self.last_req_t = None
        self.overload_rejects = 0
        # {account_name: table var name} for mesh-row-sharded embedding
        # tables (ISSUE 11) — per-device-charged sibling accounts
        self.table_accounts = {}
        # {account_name: table var name} for two-tier cached tables
        # (ISSUE 12) — slab-bytes-charged sibling accounts
        self.embed_cache_accounts = {}


class ModelRegistry(object):
    """Host N named models behind one router + HBM arbiter (module
    docstring has the design)."""

    def __init__(self, hbm_budget_bytes=None, place=None, parallel=False,
                 mesh=None, config=None, name=None):
        self.place = place if place is not None else core.default_place()
        self.parallel = bool(parallel) or mesh is not None
        self.mesh = mesh
        self.config = config  # default ServingConfig for loaded models
        self.name = name or 'model-registry'
        self.arbiter = HBMArbiter(hbm_budget_bytes)
        self._models = {}
        # the compile catalog (ISSUE 8): replayable records of every
        # warm() this registry served, persisted next to the XLA cache
        self._warm_catalog = []
        # ONE reentrant lock over the model table + arbiter decisions:
        # a submit ensuring residency (which may pause + evict another
        # model) must never interleave with a load/unload mutating the
        # table.  Engine queues drain on their own workers, so holding
        # this across an eviction stalls ROUTING, not in-flight serving.
        self._lock = threading.RLock()
        # the fair-dispatch turnstile shared by every hosted engine
        self._dispatch_gate = threading.Lock()
        self._started = False
        self._closed = False
        ref = weakref.ref(self)
        self._metrics_fn = lambda: (ref().metrics() if ref() else None)
        self._metrics_key = _profiler.register_metrics_source(
            self.name, self._metrics_fn)
        weakref.finalize(self, _profiler.unregister_metrics_source,
                         self._metrics_key, self._metrics_fn)

    # ---- lifecycle -----------------------------------------------------

    def load(self, name, dirname=None, program=None, feed_names=None,
             fetch_list=None, scope=None, executor=None, config=None,
             model_filename=None, params_filename=None, generation=None,
             embed_caches=None):
        """Load a model under ``name``: either a save_inference_model
        ``dirname`` (own scope + executor, the production form) or an
        explicit ``program`` (+ fetch_list, and a scope holding its
        params).  Admission-checked against the HBM budget BEFORE any
        device work: a model that can never fit raises HBMBudgetError
        with nothing loaded."""
        if not name or '/' in str(name) or ':' in str(name):
            raise ValueError(
                'model name must be a non-empty string without "/" or '
                '":" (it keys metrics sources, timeline rows, and the '
                'arbiter account namespace — ":decode-cache" / '
                '":embed-table:" suffixes route eviction), got %r'
                % (name, ))
        with self._lock:
            if self._closed:
                raise RuntimeError('registry is closed')
            if name in self._models:
                raise ValueError(
                    'model %r is already loaded — unload() it first '
                    '(in-place replacement would strand its queued '
                    'requests)' % name)
            cfg = config or self.config or ServingConfig()
            if dirname is not None:
                if generation is not None:
                    # checked BEFORE the engine exists: a post-
                    # construction raise here would leak its profiler
                    # registration + param scope (the cleanup except
                    # below only guards the admission path)
                    raise ValueError(
                        'load(%r): generation= requires program= (the '
                        'prefill/step programs reference live '
                        'Variables, which a saved-model dir cannot '
                        'carry)' % name)
                if embed_caches:
                    raise ValueError(
                        'load(%r): embed_caches= requires program= '
                        '(the cache is bound to a live scope holding '
                        'the slab vars)' % name)
                engine = InferenceEngine.from_saved_model(
                    dirname, place=self.place,
                    model_filename=model_filename,
                    params_filename=params_filename,
                    parallel=self.parallel, mesh=self.mesh,
                    config=cfg, name=name)
            elif program is not None:
                if fetch_list is None:
                    raise ValueError('load(program=...): fetch_list is '
                                     'required')
                engine = InferenceEngine(
                    program, feed_names=feed_names, fetch_list=fetch_list,
                    place=self.place, scope=scope, executor=executor,
                    parallel=self.parallel, mesh=self.mesh,
                    config=cfg, name=name, generation=generation,
                    embed_caches=embed_caches)
            else:
                raise ValueError('load(): pass dirname= or program=')
            cache_account = name + DECODE_CACHE_SUFFIX
            tables = _row_sharded_tables(engine)
            table_accounts = {
                '%s%s:%s' % (name, EMBED_TABLE_SUFFIX, var): var
                for var in tables
            }
            embed_cache_accounts = {
                '%s%s:%s' % (name, EMBED_CACHE_SUFFIX, c.var): c.var
                for c in engine._embed_caches
            }
            try:
                for var in tables:
                    # a pre-staged table (startup ran on the DEFAULT
                    # device, or a trainer's scope is being served
                    # directly) sits in the scope as one whole-table
                    # device array — the first routing correction would
                    # bill the model account its full GLOBAL bytes and
                    # reject a budget sized for the sharded layout.
                    # Demote it once; the first sharded dispatch lays
                    # it out over the mesh bitwise.
                    engine.evict_table_to_host(var)
                # admission gate: seed the account from the program's
                # var-sum estimate at the TOP bucket size (weights +
                # the largest lot's activations the executables pin)
                seed = program_seed_bytes(engine._program,
                                          max(engine.buckets.sizes))
                if tables:
                    # mesh-row-sharded tables (ISSUE 11) move out of
                    # the model's full-size seed into their own
                    # PER-DEVICE-charged accounts: only 1/extent of the
                    # rows lands on any one chip, so a table bigger
                    # than the whole budget still admits sharded —
                    # while the same table unsharded stays in the seed
                    # and draws the typed reject below
                    seed = max(
                        seed - sum(g for g, _ in tables.values()), 1024)
                if engine._embed_caches:
                    # TWO-TIER cached tables (ISSUE 12): the [V, D]
                    # master never goes on device — it moves out of the
                    # seed entirely, and the slab-sized account below
                    # is what the budget arbitrates.  A table past the
                    # WHOLE mesh budget therefore admits with the host
                    # overflow tier; the identical non-overflow program
                    # keeps it in the seed and draws the typed reject.
                    seed = max(
                        seed - sum(c.master_nbytes()
                                   for c in engine._embed_caches), 1024)
                self.arbiter.admit(name, seed)
                for acct, var in table_accounts.items():
                    self.arbiter.admit(acct, tables[var][1])
                for acct, var in embed_cache_accounts.items():
                    self.arbiter.admit(
                        acct, engine.embed_cache_of(var).slab_nbytes())
                if engine._decode_cache is not None:
                    # the decode-state cache is a FIRST-CLASS account:
                    # its slab bytes are exact (static slot shapes), and
                    # a cache that alone exceeds the budget is a typed
                    # reject at load, not an OOM mid-generation
                    self.arbiter.admit(
                        cache_account,
                        engine.generation.cache_nbytes(
                            engine._decode_cache.slots))
                entry = _ModelEntry(name, engine, dirname)
                entry.table_accounts = table_accounts
                entry.embed_cache_accounts = embed_cache_accounts
                self._models[name] = entry
                # make room NOW (evicting LRU peers), so the first
                # request pays staging, not arbitration
                self.arbiter.ensure(name, self._evict_to_host)
                for acct in table_accounts:
                    self.arbiter.ensure(acct, self._evict_to_host)
                for acct in embed_cache_accounts:
                    self.arbiter.ensure(acct, self._evict_to_host)
                if engine._decode_cache is not None:
                    self.arbiter.ensure(cache_account,
                                        self._evict_to_host)
            except Exception:
                # ANY failure (budget reject, an estimator choking on
                # an exotic var, ...) must not leak the constructed
                # engine — its profiler registration and param scope
                # would outlive the failed load
                self.arbiter.drop(name)
                self.arbiter.drop(cache_account)
                for acct in table_accounts:
                    self.arbiter.drop(acct)
                for acct in embed_cache_accounts:
                    self.arbiter.drop(acct)
                self._models.pop(name, None)
                engine.stop()
                raise
            engine._gate = self._dispatch_gate
            if self._started:
                engine.start()
            return engine

    def unload(self, name):
        """Stop the model's engine (drains its queue + in-flight
        dispatches), drop its account, and forget it."""
        with self._lock:
            entry = self._models.pop(name, None)
            if entry is None:
                raise KeyError('model %r is not loaded' % name)
            self.arbiter.drop(name)
            self.arbiter.drop(name + DECODE_CACHE_SUFFIX)
            for acct in entry.table_accounts:
                self.arbiter.drop(acct)
            for acct in entry.embed_cache_accounts:
                self.arbiter.drop(acct)
        entry.engine.stop()

    def warm(self, name, bucket_ladder=None, trailing=None,
             decode_prefill=None):
        """Pre-compile the model's executables across its bucket ladder
        (or an explicit one) with zero-filled requests, so first real
        traffic pays staging, not XLA compiles.  Returns the number of
        warm requests served.

        ``trailing`` extends the warm set along the TRAILING dims
        (ISSUE 5): ``{feed_name: [extents]}`` warms one request per
        (batch rung x trailing extent) for that feed — an LoD-declared
        feed warms as a zero-filled LoD batch of that uniform length
        (so the prepared signature, padded data + @SEQLEN, matches
        real traffic whose lengths bucket to the same rung), a dense
        feed substitutes the extent into axis 1.  Several trailing
        feeds warm the FULL cross-product of their rungs — trailing
        extents correlate in real traffic (both sides of a translation
        pair bucket long together), so the correlated multi-feed
        signatures are exactly the ones that must not stay cold; the
        warm set is len(ladder) x prod(len(extents)), which the caller
        bounds through the extents passed.

        ``decode_prefill`` warms the GENERATION lane (ISSUE 7): one
        zero-filled single-sequence prompt per extent runs through
        ``submit_generate`` with ``max_len=1`` — compiling the prefill
        executable at each prompt-length rung plus the decode-step
        scan executable, so first real generation traffic pays
        staging, not XLA compiles.  A decode-only call (no
        bucket_ladder/trailing) skips the forward-surface warm.

        Every successful warm is RECORDED into the registry's compile
        catalog (ISSUE 8) and — when a persistent compile cache is
        configured — persisted as ``serving_warm_catalog.json`` inside
        it, so ``prewarm()`` on a fresh process can replay the
        exact signature set this fleet compiled."""
        entry = self._entry(name)
        engine = entry.engine
        served = 0
        # materialize iterator-valued args ONCE, before anything reads
        # them: the catalog record and the warm body must see the same
        # extents (an iterator drained by the record would warm nothing
        # while recording rungs)
        if decode_prefill is not None:
            decode_prefill = [int(e) for e in decode_prefill]
        trailing = {str(f): [int(e) for e in v]
                    for f, v in (trailing or {}).items()} or None
        record = {
            'model': str(name),
            'bucket_ladder': ([int(b) for b in bucket_ladder]
                              if bucket_ladder is not None else None),
            'trailing': trailing,
            'decode_prefill': decode_prefill,
        }
        if decode_prefill is not None:
            spec = engine.generation
            if spec is None:
                raise ValueError(
                    'warm(%r): decode_prefill= but the model serves no '
                    'generation lane — load it with generation='
                    % name)
            extents = list(decode_prefill)
            if not extents:
                raise ValueError(
                    'warm(%r): decode_prefill is empty — pass at least '
                    'one prompt-length extent' % name)
            pblock = spec.prefill_program.global_block()
            for extent in dict.fromkeys(int(e) for e in extents):
                feed = {}
                for fname in spec.prefill_feeds:
                    var = pblock.vars[fname]
                    if not getattr(var, 'lod_level', 0):
                        raise ValueError(
                            'warm(%r): prefill feed %r is not a '
                            'sequence (lod_level=0) — decode_prefill '
                            'warms prompt-length rungs; warm dense '
                            'prompts with real traffic'
                            % (name, fname))
                    from ..fluid.lod_tensor import create_lod_tensor
                    shape = [int(d) for d in var.shape[1:]]
                    if any(d < 0 for d in shape):
                        raise ValueError(
                            'warm(%r): prefill feed %r has a non-batch '
                            'dynamic dim %s — warm it with real '
                            'traffic instead' % (name, fname, var.shape))
                    rows = np.zeros((extent, ) + tuple(shape),
                                    var.np_dtype).tolist()
                    feed[fname] = create_lod_tensor([rows], [[extent]])
                self.generate(name, feed, max_len=1, timeout=600)
                served += 1
            if bucket_ladder is None and not trailing:
                self._record_warm(record)
                return served
        ladder = list(bucket_ladder if bucket_ladder is not None
                      else engine.buckets.sizes)
        trailing = trailing or {}
        feed_names = engine._feed_names
        if not feed_names:
            raise ValueError(
                'warm(%r): the engine has no feed_names — load the '
                'model from a save_inference_model dir, or pass '
                'feed_names= at load()' % name)
        unknown = sorted(set(trailing) - set(feed_names))
        if unknown:
            # a typo'd key would silently warm NOTHING useful while
            # reporting served rungs
            raise ValueError(
                'warm(%r): trailing names %s are not feeds of this '
                'model (feeds: %s)' % (name, unknown, sorted(feed_names)))
        empty = sorted(f for f, extents in trailing.items()
                       if not list(extents))
        if empty:
            # an empty extent list would die later on trailing[f][0]
            # with a raw IndexError
            raise ValueError(
                'warm(%r): trailing extents for %s are empty — pass '
                'at least one extent per feed' % (name, empty))
        block = engine._program.global_block()

        def zero_feed(fname, rows, extent):
            var = block.vars[fname]
            shape = [int(d) for d in var.shape]
            shape[0] = int(rows)
            if getattr(var, 'lod_level', 0):
                if extent is None:
                    raise ValueError(
                        'warm(%r): feed %r is a sequence (lod_level=%d) '
                        '— pass trailing={%r: [extents]} to warm its '
                        'seq-len rungs' % (name, fname, var.lod_level,
                                           fname))
                if any(d < 0 for d in shape[1:]):
                    # the extent fills the TIME axis, not these: a seq
                    # feed with another dynamic dim would otherwise die
                    # inside np.zeros with a raw 'negative dimensions'
                    # error instead of this message
                    raise ValueError(
                        'warm(%r): feed %r has a non-batch dynamic dim '
                        '%s — warm it with real traffic instead'
                        % (name, fname, var.shape))
                from ..fluid.lod_tensor import create_lod_tensor
                t = int(extent)
                rows_data = [np.zeros((t, ) + tuple(shape[1:]),
                                      var.np_dtype).tolist()
                             for _ in range(int(rows))]
                return create_lod_tensor(rows_data, [[t] * int(rows)])
            if extent is not None:
                if len(shape) < 2:
                    # silently dropping the extent would warm duplicate
                    # all-zero signatures while reporting them as served
                    # rungs — the same 'warmed nothing while reporting
                    # rungs' failure the unknown-name check catches
                    raise ValueError(
                        'warm(%r): feed %r has no trailing axis '
                        '(shape %s) — drop it from trailing='
                        % (name, fname, var.shape))
                axes = set(engine.trailing.ladder_axes(fname)) \
                    if engine.trailing is not None else set()
                if axes and axes != {1}:
                    # flat extents substitute axis 1; a dict-form
                    # ladder on other axes would warm signatures real
                    # traffic never produces while reporting served
                    # rungs
                    raise ValueError(
                        'warm(%r): feed %r buckets on axes %s — flat '
                        'trailing extents warm axis 1 only; warm those '
                        'rungs with real traffic'
                        % (name, fname, sorted(axes)))
                if int(var.shape[1]) >= 0:
                    raise ValueError(
                        'warm(%r): feed %r has a STATIC axis-1 extent '
                        '%d — there are no axis-1 rungs to warm; drop '
                        'it from trailing='
                        % (name, fname, int(var.shape[1])))
                shape[1] = int(extent)
            if any(d < 0 for d in shape[1:]):
                raise ValueError(
                    'warm(%r): feed %r has a non-batch dynamic dim '
                    '%s — warm it with real traffic instead'
                    % (name, fname, var.shape))
            return np.zeros(shape, dtype=var.np_dtype)

        # the FULL cross-product of per-feed rungs: trailing extents
        # correlate in real traffic, so varying one feed while pinning
        # the others at their first extent would leave exactly the
        # dominant multi-feed signatures cold
        t_names = sorted(trailing)
        combos = list(itertools.product(
            *(list(dict.fromkeys(trailing[f])) for f in t_names)))
        for rows in ladder:
            for combo in combos or [()]:
                extents = dict(zip(t_names, combo))
                feed = {fname: zero_feed(fname, rows,
                                         extents.get(fname))
                        for fname in feed_names}
                self.infer(name, feed, timeout=600)
                served += 1
        self._record_warm(record)
        return served

    # ---- prewarm catalog (ISSUE 8) -------------------------------------

    def warm_catalog_path(self):
        """Where the compile catalog persists: inside the persistent
        XLA compile cache directory JAX really uses — placed by
        JAX_COMPILATION_CACHE_DIR or, failing that, by
        FLAGS_xla_compile_cache_dir (fluid.flags.compile_cache_dir) —
        or None when there is no cache (the catalog then lives
        in-memory only — ``warm_catalog()`` still returns it)."""
        cache_dir = compile_cache_dir()
        if not cache_dir:
            return None
        return os.path.join(cache_dir, WARM_CATALOG_BASENAME)

    def warm_catalog(self):
        """The recorded warm set: one replayable dict per distinct
        warm() call (model, bucket_ladder, trailing, decode_prefill)."""
        with self._lock:
            return [dict(r) for r in self._warm_catalog]

    def _record_warm(self, record):
        """Append one warm record (deduped — prewarm replays through
        warm(), which must not grow the catalog it is replaying) and
        persist the catalog atomically next to the XLA cache.  The
        write MERGES with what is already on disk: a staged restart
        that loaded (and re-warmed) only some models — or a peer
        process sharing the cache dir — has records there for models
        THIS registry never warmed, and overwriting would delete their
        replay set."""
        path = self.warm_catalog_path()
        # the read-merge-replace stays under self._lock: two threads
        # warming concurrently would otherwise race read-vs-replace and
        # one record would vanish from disk (a lost update).  Peer
        # PROCESSES sharing the cache dir can still interleave — the
        # merge shrinks that window but does not close it; same-process
        # durability is the contract the prewarm acceptance pins.
        with self._lock:
            if record not in self._warm_catalog:
                self._warm_catalog.append(record)
            if path is None:
                return
            catalog = [dict(r) for r in self._warm_catalog]
            tmp = path + '.tmp'
            try:
                # an environment-placed cache dir exists only once JAX
                # first writes to it
                os.makedirs(os.path.dirname(path), exist_ok=True)
                try:
                    with open(path) as f:
                        on_disk = json.load(f)
                except (OSError, ValueError):
                    on_disk = []
                merged = list(on_disk) + [r for r in catalog
                                          if r not in on_disk]
                with open(tmp, 'w') as f:
                    json.dump(merged, f, indent=1)
                    f.write('\n')
                os.replace(tmp, path)
            except OSError:
                # an unwritable cache dir must not fail the warm
                # itself — the in-memory catalog still serves
                # same-process prewarms
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    def prewarm(self, catalog=None):
        """Replay a compile catalog on THIS registry (the fleet
        cold-start path, ISSUE 8): for every record whose model is
        loaded, re-run ``warm()`` with the recorded bucket ladder x
        trailing rungs x decode-prefill extents.  With the process
        pointed at the SAME persistent compile cache the recording
        process used, each replayed compile is a
        disk hit, and first real traffic at the recorded signatures
        compiles nothing (``compile_count`` delta 0 — the acceptance
        bar).

        ``catalog``: a path to a catalog JSON, an already-loaded list
        of records, or None to read the default
        ``warm_catalog_path()``.  Records for models not currently
        loaded are skipped (reported, not raised — a fleet restart may
        stage models in stages).  Returns
        {'served', 'replayed', 'skipped_models'}."""
        if catalog is None:
            catalog = self.warm_catalog_path()
            if catalog is None:
                raise ValueError(
                    'prewarm(): no catalog given and no persistent '
                    'compile cache directory (JAX_COMPILATION_CACHE_DIR '
                    'or FLAGS_xla_compile_cache_dir) to read the '
                    'default from — pass a path or a record list')
        if isinstance(catalog, str):
            with open(catalog) as f:
                catalog = json.load(f)
        served = replayed = 0
        skipped = []
        for rec in list(catalog):
            model = rec.get('model')
            with self._lock:
                loaded = model in self._models
            if not loaded:
                skipped.append(model)
                continue
            served += self.warm(
                model, bucket_ladder=rec.get('bucket_ladder'),
                trailing=rec.get('trailing'),
                decode_prefill=rec.get('decode_prefill'))
            replayed += 1
        return {'served': served, 'replayed': replayed,
                'skipped_models': sorted(set(skipped))}

    def _entry(self, name):
        with self._lock:
            entry = self._models.get(name)
            if entry is None:
                raise KeyError(
                    'model %r is not loaded (loaded: %s)'
                    % (name, sorted(self._models)))
            return entry

    def models(self):
        with self._lock:
            return sorted(self._models)

    # ---- arbiter plumbing ----------------------------------------------

    def _evict_to_host(self, victim):
        """The arbiter's evict callback: pause the victim engine (its
        in-flight dispatches drain), demote its device buffers to host
        ndarrays bitwise, drop its executables.  Returns the live bytes
        moved (the arbiter's account correction).  A ``:decode-cache``
        victim demotes its model's decode slabs instead of the weights
        — an idle generation model's cache frees on its own."""
        if victim.endswith(DECODE_CACHE_SUFFIX):
            owner = victim[:-len(DECODE_CACHE_SUFFIX)]
            return self._models[owner].engine.evict_decode_cache()
        if EMBED_TABLE_SUFFIX + ':' in victim:
            # a sharded embedding table demotes on its OWN (ISSUE 11):
            # the var's mesh shards copy back to one host ndarray under
            # the owner's paused window; the moved bytes are the
            # PER-DEVICE share — the unit its account is charged in
            owner, _, var = victim.partition(EMBED_TABLE_SUFFIX + ':')
            return self._models[owner].engine.evict_table_to_host(var)
        if EMBED_CACHE_SUFFIX + ':' in victim:
            # a two-tier cache's slabs demote on their OWN (ISSUE 12):
            # paused-window flush (dirty rows back to the host master,
            # any staged exchange applied first) + bitwise slab
            # demotion; the next dispatch re-stages transparently
            owner, _, var = victim.partition(EMBED_CACHE_SUFFIX + ':')
            return self._models[owner].engine.evict_embed_cache_to_host(
                var)
        entry = self._models[victim]
        moved, _ = entry.engine.evict_to_host()
        return moved

    def audit(self):
        """Run the arbiter's ``jax.live_arrays()`` cross-check now and
        return it (also kept on the arbiter and surfaced as the
        ``audit`` block of ``metrics()``): accounted-resident bytes vs
        what the runtime actually holds live, drift included."""
        return self.arbiter.audit()

    def _ensure_resident(self, name, decode=False):
        """Dispatch-time gate: budget-arbitrate ``name`` resident (LRU
        peers evict as needed) and correct resident accounts to live
        buffer stats.  ``decode=True`` (a routed generation request)
        additionally ensures the model's decode-cache account — its
        slabs re-stage transparently at the next decode dispatch after
        an eviction."""
        with self._lock:
            entry = self._entry(name)
            if entry.table_accounts or entry.embed_cache_accounts:
                # sharded-table engines bill the model account at the
                # shard-aware PER-DEVICE footprint (the budget is one
                # chip's HBM — a trainer scope's co-sharded moments
                # must not bill global bytes), with each table's own
                # per-device share moved onto its account below
                footprint = entry.engine.hbm_footprint()
            else:
                footprint = entry.engine.device_footprint()
            for acct, var in entry.table_accounts.items():
                _, per_dev = entry.engine.table_live_bytes(var)
                footprint = max(footprint - per_dev, 0)
                self.arbiter.correct(acct, per_dev)
            for acct, var in entry.embed_cache_accounts.items():
                live = entry.engine.embed_cache_live_bytes(var)
                footprint = max(footprint - live, 0)
                self.arbiter.correct(acct, live)
            self.arbiter.correct(name, footprint)
            self.arbiter.ensure(name, self._evict_to_host)
            for acct in entry.table_accounts:
                self.arbiter.ensure(acct, self._evict_to_host)
            for acct in entry.embed_cache_accounts:
                self.arbiter.ensure(acct, self._evict_to_host)
            if decode:
                cache = name + DECODE_CACHE_SUFFIX
                self.arbiter.correct(
                    cache, entry.engine._decode_cache.nbytes())
                self.arbiter.ensure(cache, self._evict_to_host)
            return entry

    # ---- router --------------------------------------------------------

    def _check_admission(self, model):
        """Per-model overload admission (ISSUE 8): when the model's
        ServingConfig carries queue watermarks (admit_queue_depth /
        admit_queue_age_ms) and its engine's queue has crossed one,
        refuse the request at the DOOR with a typed OverloadedError —
        BEFORE paying arbitration (an eviction on behalf of a request
        that would only queue toward deadline death helps nobody).  The
        retry-after hint is one queue-drain window: the oldest queued
        age (how far behind the worker is) floored at the batching
        wait.  (The entry lookup is NOT returned: _ensure_resident must
        re-resolve it under the lock anyway, or it would race an
        unload between the two calls.)"""
        entry = self._entry(model)
        cfg = entry.engine.config
        depth_wm = cfg.admit_queue_depth
        age_wm = cfg.admit_queue_age_s
        if depth_wm is None and age_wm is None:
            return
        depth = entry.engine._batcher.depth()
        age = entry.engine._batcher.oldest_age() or 0.0
        if cfg.adaptive_admission and (
                (depth_wm is not None and depth >= 0.5 * depth_wm) or
                (age_wm is not None and age >= 0.5 * age_wm)):
            # adaptive watermarks (ISSUE 9): scale the static marks by
            # the measured drain/arrival ratio, clamped to [0.5, 2.0].
            # An engine whose drain keeps up (ratio >= 1) tolerates a
            # deeper queue — the static watermark was sized for a
            # falling-behind worst case, and rejecting an absorbable
            # burst wastes goodput; one falling behind (ratio < 1)
            # admits at a proportionally SHALLOWER depth, shedding at
            # the door while the queue can still drain what it holds.
            # Before both rates are measurable the static marks stand.
            # Gated on the queue being at least HALFWAY to a static
            # mark: below that no clamped scale can change the
            # verdict, so the hot submit path skips the two
            # lock-guarded rate() passes entirely.
            rates = entry.engine.rate_stats()
            arrival, drain = rates['arrival_req_s'], rates['drain_req_s']
            if arrival and drain:
                scale = min(max(drain / arrival, 0.5), 2.0)
                if depth_wm is not None:
                    depth_wm = max(depth_wm * scale, 1.0)
                if age_wm is not None:
                    age_wm = age_wm * scale
        if (depth_wm is not None and depth >= depth_wm) or \
                (age_wm is not None and age >= age_wm):
            with self._lock:
                entry.overload_rejects += 1
            raise OverloadedError(
                model, depth, age,
                retry_after_s=round(max(age, cfg.max_wait_s), 4))

    def submit(self, model, feed, return_numpy=True, priority=0,
               deadline_ms=None):
        """Route one request to ``model``: admission-check it against
        the model's overload watermarks (typed OverloadedError with a
        retry-after hint when the queue is past them), ensure the model
        is resident under the HBM budget (transparently reloading it /
        evicting LRU peers — the caller never sees the arbitration,
        only the latency), and enqueue on its engine.  ``priority`` /
        ``deadline_ms`` ride through to the engine's deadline scheduler
        (ISSUE 8).  Returns the engine's InferenceRequest future — its
        ``breakdown()`` carries the routed request's per-stage latency
        INCLUDING the arbitration window paid here (the trace context
        is attached before engine.submit, so the engine threads the
        registry's trace id instead of minting its own)."""
        self._check_admission(model)
        ctx = _trace.TraceContext()
        t0 = time.time()
        entry = self._ensure_resident(model)
        ctx.add_stage('arbitration', time.time() - t0)
        now = time.time()
        with self._lock:
            entry.requests += 1
            if entry.first_req_t is None:
                entry.first_req_t = now
            entry.last_req_t = now
        with _trace.attach(ctx):
            req = entry.engine.submit(feed, return_numpy=return_numpy,
                                      priority=priority,
                                      deadline_ms=deadline_ms)
        if req.rows:
            with self._lock:
                entry.rows += req.rows
        return req

    def infer(self, model, feed, return_numpy=True, timeout=None):
        """Synchronous convenience: submit + wait."""
        return self.submit(model, feed,
                           return_numpy=return_numpy).result(timeout)

    def submit_generate(self, model, feed, max_len=None, priority=0,
                        deadline_ms=None):
        """Route one GENERATION request (ISSUE 7): admission-check the
        overload watermarks, ensure the model AND its decode cache are
        resident under the HBM budget, then enqueue on its engine's
        decode lane.  ``priority`` / ``deadline_ms`` ride the prefill
        lot and the decode lane's step-boundary deadline check (ISSUE
        8).  Returns the engine's GenerationRequest future; its
        ``breakdown()`` carries the arbitration window plus the
        prefill/decode/detokenize stages."""
        self._check_admission(model)
        ctx = _trace.TraceContext()
        t0 = time.time()
        entry = self._ensure_resident(model, decode=True)
        ctx.add_stage('arbitration', time.time() - t0)
        now = time.time()
        with self._lock:
            entry.requests += 1
            if entry.first_req_t is None:
                entry.first_req_t = now
            entry.last_req_t = now
        with _trace.attach(ctx):
            req = entry.engine.submit_generate(feed, max_len=max_len,
                                               priority=priority,
                                               deadline_ms=deadline_ms)
        with self._lock:
            entry.rows += 1
        return req

    def generate(self, model, feed, max_len=None, timeout=None):
        """Synchronous convenience: submit_generate + wait."""
        return self.submit_generate(model, feed,
                                    max_len=max_len).result(timeout)

    # ---- start/stop ----------------------------------------------------

    def start(self):
        """Start every loaded model's worker (queued mode); models
        loaded later start automatically."""
        with self._lock:
            if self._closed:
                raise RuntimeError('registry is closed')
            self._started = True
            engines = [e.engine for e in self._models.values()]
        for eng in engines:
            eng.start()
        return self

    def stop(self):
        """Stop every engine (each drains its queue), then unregister
        the registry's metrics source."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            engines = [e.engine for e in self._models.values()]
        for eng in engines:
            eng.stop()
        _profiler.unregister_metrics_source(self._metrics_key,
                                            self._metrics_fn)

    close = stop

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # ---- observability -------------------------------------------------

    def status(self):
        """One fleet snapshot: per-model residency, HBM account (bytes +
        whether it is the seed estimate or live-corrected), live device
        footprint, queue depth, and request tallies — plus the arbiter's
        budget line."""
        with self._lock:
            arb = self.arbiter.snapshot()
            out = {'budget_bytes': arb['budget_bytes'],
                   'resident_bytes': arb['resident_bytes'],
                   # where loaded models run, as JAX reports it
                   'device': core.device_info(
                       self.mesh.devices.flat if self.mesh is not None
                       else [self.place.jax_device()]),
                   'models': {}}
            for name, entry in self._models.items():
                acct = arb['accounts'].get(name, {})
                out['models'][name] = {
                    'resident': acct.get('resident', False),
                    'hbm_bytes': acct.get('bytes', 0),
                    'account_source': acct.get('source'),
                    'device_footprint': entry.engine.device_footprint(),
                    'queue_depth': entry.engine.queue_depth(),
                    'requests': entry.requests,
                    'rows': entry.rows,
                    'dirname': entry.dirname,
                    'parallel': entry.engine._pe is not None,
                }
            return out

    def queue_depths(self):
        """Cheap per-model queue depths — the fleet replica's
        per-response load report (ISSUE 17): no arbiter snapshot, no
        device-footprint walk, just each engine's batcher depth."""
        with self._lock:
            entries = dict(self._models)
        return {name: entry.engine.queue_depth()
                for name, entry in entries.items()}

    def metrics(self):
        """Router + arbiter + per-model engine snapshots (this is what
        the profiler sidecar carries under the registry's source)."""
        with self._lock:
            entries = dict(self._models)
        arb = self.arbiter.snapshot()
        per_model = {}
        for name, entry in entries.items():
            snap = entry.engine.metrics()
            window = ((entry.last_req_t - entry.first_req_t)
                      if entry.requests > 1 and entry.first_req_t else None)
            snap['router'] = {
                'requests': entry.requests,
                'rows': entry.rows,
                'req_per_s': (round((entry.requests - 1) / window, 3)
                              if window else None),
                'overload_rejects': entry.overload_rejects,
            }
            per_model[name] = snap
        return {
            'models': per_model,
            'evictions': arb['evictions'],
            'reloads': arb['reloads'],
            'admission_rejects': arb['admission_rejects'],
            'overload_rejects': sum(e.overload_rejects
                                    for e in entries.values()),
            'budget_bytes': arb['budget_bytes'],
            'resident_bytes': arb['resident_bytes'],
            'audit': arb['audit'],
            'lru_order': arb['lru_order'],
        }
