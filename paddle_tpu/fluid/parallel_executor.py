"""ParallelExecutor: data-parallel (and tensor-parallel) SPMD execution.

Reference design (framework/parallel_executor.cc:119, details/*): clone the
program per GPU, build an SSA graph, insert NCCL AllReduce op-handles at
each param grad, run with a threadpool.  TPU-native design: the SAME traced
block as the single-device Executor, jitted once with GSPMD shardings —
feeds sharded batch-dim over the 'dp' mesh axis, params replicated (or
sharded per their annotations, paddle_tpu.parallel.shard), gradient
averaging emerges as compiler-inserted cross-replica sums on ICI.

BuildStrategy/ExecutionStrategy are accepted for API parity
(details/build_strategy.h:23, execution_strategy.h:21); reduce-scatter
('kReduce') maps to GSPMD's own choice of collectives.
"""

import functools
import threading

import numpy as np

from . import core
from . import trace as _trace
from .executor import _CompiledBlock, _current_scope, \
    prepare_feed_arrays, feed_signature, _is_host_op, \
    _reader_feed_list, stage_embed_caches, prepare_scanned_lots, \
    place_scanned, _count_lane, _var_name, fetch_batch_led, \
    _pop_readers_into_feed, convert_eval_fetches, collect_cost_report, \
    _dispatch_multi_scanned, _dispatch_eval_multi, \
    _dispatch_decode_multi, _dispatch_chunk_prefill
from .framework import default_main_program, Variable
from ..ops import registry

__all__ = ['ParallelExecutor', 'ExecutionStrategy', 'BuildStrategy']


def _lead(v):
    """Leading dim of a feed value (LoDTensor exposes shape() as a
    method, so np.shape would return the bound method); None for
    scalars."""
    shape = v.shape() if isinstance(v, core.LoDTensor) else np.shape(v)
    return int(shape[0]) if len(shape) >= 1 else None


def pad_ragged_batch(feed_arrays, multiple, target=None, force_mask=False,
                     skip=(), batch_names=None, sizes_only=False,
                     report=None):
    """DataBalance parity (details/data_balance_op_handle.cc) under static
    SPMD shapes: pad the lot's batch dim up to ``target`` (default: the
    next multiple of the mesh's dp extent) by replicating the last real
    sample, and inject a ``registry.SAMPLE_MASK_NAME`` feed (1.0 = real
    row, 0.0 = padding) so batch-mean lowerings — and, through jax.vjp,
    every gradient flowing out of them — weight by the REAL sample count.
    An epoch whose final lot isn't divisible by bs*ndev then trains with
    the numerics of the unpadded lot instead of dying on a raw JAX
    sharding error.

    The batch row count is the NON-DIVISIBLE leading dim among the
    dp-sharded feeds (names in ``skip`` — feeds with explicit sharding
    annotations — never vote): a divisible non-batch feed (a lookup
    table, a replicated aux input) cannot hijack the inference, and two
    feeds disagreeing on non-divisible rows is an error, not a guess.
    ``batch_names`` skips inference entirely — only those feeds are
    batch-led (run_multi's re-pad pass, where a lot that already
    divides carries no inference signal of its own).

    Returns (feed_arrays, n_real, n_padded); the input dict is returned
    untouched when the lot already divides (and no mask is forced).
    ``sizes_only`` runs just the inference — (None, n_real, n_padded) —
    so a probing pass over a feed_list never copies device-staged
    arrays through the host.  ``report`` (a dict) receives
    ``batch_names``: the feed names treated as batch-led, recorded
    PRE-padding — post-padding every batch feed shares the padded row
    count with any coinciding aux feed, so this is the only place the
    distinction still exists."""
    dims = set()
    for n, v in feed_arrays.items():
        if n in skip or isinstance(v, core.SelectedRows):
            continue
        if batch_names is not None and n not in batch_names:
            continue
        d = _lead(v)
        if d is not None:
            dims.add(d)
    dims = sorted(dims)
    if batch_names is not None:
        if len(dims) != 1:
            raise ValueError(
                'ragged lot is ambiguous: batch feeds %s disagree on '
                'rows %s' % (sorted(batch_names), dims))
        b = dims[0]
        if target is not None:
            tgt = int(target)
        else:
            tgt = -(-b // multiple) * multiple if multiple > 1 else b
    elif target is not None:
        # a lot that already divides carries no inference signal of its
        # own — the caller must say which feeds are batch-led
        raise ValueError(
            'pad_ragged_batch: target= requires batch_names=')
    elif multiple > 1:
        nondiv = [d for d in dims if d % multiple]
        if len(nondiv) > 1:
            raise ValueError(
                'ragged lot is ambiguous: feeds disagree on batch rows '
                '%s (each %% %d != 0) — pad them to one batch size '
                'first, or annotate non-batch feeds with '
                'paddle_tpu.parallel.shard' % (nondiv, multiple))
        b = nondiv[0] if nondiv else (dims[-1] if dims else 0)
        tgt = -(-b // multiple) * multiple if nondiv else b
    else:
        b = dims[-1] if dims else 0
        tgt = b
    if report is not None:
        report['batch_names'] = {
            n for n, v in feed_arrays.items()
            if n not in skip and not isinstance(v, core.SelectedRows)
            and (batch_names is None or n in batch_names)
            and _lead(v) == b}
    if b == 0 or (tgt == b and not force_mask):
        return (None if sizes_only else feed_arrays), b, b
    if sizes_only:
        return None, b, tgt
    out = {}
    pad = tgt - b
    for n, v in feed_arrays.items():
        if isinstance(v, core.LoDTensor):
            v = v.numpy()  # lod-free pass-through tensors (lod ones were
            # already lowered to padded + @SEQLEN by prepare_feed_arrays)
        if n in skip or isinstance(v, core.SelectedRows) \
                or (batch_names is not None and n not in batch_names) \
                or np.ndim(v) < 1 or np.shape(v)[0] != b \
                or not pad:
            out[n] = v  # not batch-leading, or nothing to append —
            # leave device-staged arrays on device
            continue
        a = np.asarray(v)
        # replicate the last REAL sample: always a valid row (in-range
        # indices, finite activations); its loss/grads are masked out
        out[n] = np.concatenate(
            [a, np.broadcast_to(a[-1:], (pad, ) + a.shape[1:])])
    mask = np.zeros((tgt, ), np.float32)
    mask[:b] = 1.0
    out[registry.SAMPLE_MASK_NAME] = mask
    return out, b, tgt


def normalize_ragged_feed_list(per_step, pad_fn):
    """Shared ragged-feed_list normalization behind run_multi and
    run_eval_multi (single-device and SPMD): size-probe every lot, and
    when any is ragged (or lots disagree in rows) re-pad ALL of them to
    the common target with masked samples so the scan's per-step
    structure stays uniform.  The batch feeds are the ones whose rows
    VARY across lots; all-identical lots fall back to the first pass's
    inference — a divisible aux feed can't vote either way.

    pad_fn(feed_arrays, **kw) -> (feed_arrays, n_real, n_padded) — the
    executor's padding policy (multiple=1 for single-device,
    ParallelExecutor._pad_ragged for the dp-extent rule).

    Returns (per_step, reals, target, batch_feed_names); ``reals`` is
    the per-lot real row count, or None when nothing was padded."""
    probed = [pad_fn(fa, sizes_only=True) for fa in per_step]
    target = max(p[2] for p in probed)
    if not any(p[2] != target or p[1] != target for p in probed):
        return per_step, None, target, None
    batch_names = {
        n for n in per_step[0]
        if len({_lead(fa[n]) for fa in per_step}) > 1
    } or {n for n, v in per_step[0].items()
          if _lead(v) == probed[0][1]}
    rpt = {}
    repadded = [pad_fn(fa, target=target, force_mask=True,
                       batch_names=batch_names, report=rpt)
                for fa in per_step]
    return ([p[0] for p in repadded], [p[1] for p in repadded], target,
            rpt.get('batch_names'))


class ExecutionStrategy(object):
    def __init__(self):
        self.num_threads = 0
        self.use_event = True
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100


class BuildStrategy(object):
    class ReduceStrategy(object):
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy(object):
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ''


class _SpmdCompiledBlock(_CompiledBlock):
    """A _CompiledBlock whose jit carries GSPMD shardings over a mesh."""

    def __init__(self, program, block_idx, feed_names, fetch_names, mesh,
                 scope, batch_axis='dp'):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        # build the plain traced fn + state analysis first; the place
        # (which the lowerings read for platform-specific choices) is
        # the mesh's own, never an assumed accelerator
        place = core.place_of(mesh.devices.flat[0])
        super(_SpmdCompiledBlock, self).__init__(
            program, block_idx, feed_names, fetch_names, place, scope)
        self.mesh = mesh
        # expose to mesh-aware lowerings (ring attention) at trace time
        self._spmd_ref['mesh'] = mesh
        self._spmd_ref['batch_axis'] = batch_axis
        self.batch_axis = batch_axis
        from ..parallel.api import sharding_of

        def var_sharding(name):
            v = self.block._find_var_recursive(name)
            spec = sharding_of(v) if v is not None else None
            return NamedSharding(mesh, spec if spec is not None else P())

        rw_shardings = {n: var_sharding(n) for n in self.state_rw}
        ro_shardings = {n: var_sharding(n) for n in self.state_ro}
        feed_shardings = {}
        for n in self.feed_names:
            v = self.block._find_var_recursive(n)
            spec = sharding_of(v)
            if spec is None:
                # shard batch dim over data parallel when the mesh has it
                spec = P(batch_axis) if batch_axis in mesh.axis_names \
                    else P()
            feed_shardings[n] = NamedSharding(mesh, spec)
        out_state_shardings = {
            n: var_sharding(n)
            for n in self.state_out
        }
        self._feed_shardings = feed_shardings
        self._state_shardings = dict(rw_shardings, **ro_shardings)
        self._out_state_shardings = out_state_shardings
        donate = (0, ) if self.state_rw else ()
        self._jit = jax.jit(
            self._fn,
            in_shardings=(rw_shardings, ro_shardings, feed_shardings, None),
            out_shardings=(out_state_shardings, None),
            donate_argnums=donate)

    def _materialize_args(self, scope, feed_values, cache_ro=False):
        """Sharded device staging: state and feeds go to the mesh via
        their GSPMD shardings (device arrays from a double-buffer
        prefetch reshard device-side).  The base class's run()/
        run_multi() call this polymorphically, so both the single-step
        and the K-steps-per-dispatch paths are shared with Executor.
        ``cache_ro`` mirrors the base class's host-state caching:
        READ-ONLY state staged from a host array is written back to the
        scope as its SHARDED device array, so every later dispatch
        reshards in place instead of re-uploading all params from the
        host — and the engine's
        ``device_footprint()`` sees the buffers the mesh really pins.
        RW state is never cached (its staged buffer is donated)."""
        import jax

        def to_value(val, desc):
            if isinstance(val, core.LoDTensor):
                val = val.numpy()
            return val  # sharded device_put happens below

        state_rw = self._state_from_scope(scope, self.state_rw, to_value)
        state_ro = self._state_from_scope(scope, self.state_ro, to_value)
        for name in list(state_rw) + list(state_ro):
            tgt = state_rw if name in state_rw else state_ro
            staged = jax.device_put(tgt[name],
                                    self._state_shardings[name])
            tgt[name] = staged
            if cache_ro and name in state_ro:
                var = scope.find_var(name)
                raw = var.value()
                if not isinstance(raw, jax.Array):
                    lod = raw.lod() if isinstance(raw, core.LoDTensor) \
                        else None
                    if not lod:
                        var.set_value(staged)
        feeds = {}
        for n, v in feed_values.items():
            if isinstance(v, core.LoDTensor):
                v = v.numpy()
            if not isinstance(v, jax.Array):
                v = np.asarray(v)
            feeds[n] = jax.device_put(v, self._feed_shardings[n])
        return state_rw, state_ro, feeds

    def _again(self):
        return functools.partial(
            _SpmdCompiledBlock, self.program, self.block.idx,
            self.feed_names, self.fetch_names, self.mesh, None,
            batch_axis=self.batch_axis)

    def scanned_sharding(self, name):
        """Sharding for a scanned feed: the per-step spec shifted right
        of the leading K (steps) axis, which is never sharded."""
        from jax.sharding import NamedSharding
        from ..parallel.api import scanned_spec
        return NamedSharding(
            self.mesh, scanned_spec(self._feed_shardings[name].spec))

    def _lane_shardings(self, lane, feeds, operand, spec):
        """A lane's jit over the mesh.  The scanned lanes: state per its
        annotations, constant feeds and the scanned block batch-dim
        over 'dp' (the block right of its unsharded K axis).  The
        carried lanes (ISSUE 7, 14): every slot-carry leaf (KV/hidden
        state, token, alive mask, step budget) shards its SLOT dim over
        the batch axis — the decode cache lives distributed across the
        mesh and updates in place there; the decode scan's emitted
        [K, S] token/alive stacks shard the slot dim right of the step
        axis, like every scanned output; the chunk lane's [S, C, 1]
        token block (and its @SEQLEN/length companions) and its aux
        active/finish/budget leaves ride the same row sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.api import scanned_spec
        rw_sh = {n: self._state_shardings[n] for n in self.state_rw}
        ro_sh = {n: self._state_shardings[n] for n in self.state_ro}
        if not lane.carried:
            feed_sh = {n: self._feed_shardings[n] for n in feeds}
            scanned_sh = {n: self.scanned_sharding(n) for n in operand}
            return ((rw_sh, ro_sh, feed_sh, scanned_sh, None),
                    (self._out_state_shardings, None))
        row_spec = P(self.batch_axis) \
            if self.batch_axis in self.mesh.axis_names else P()
        row = NamedSharding(self.mesh, row_spec)
        feed_sh = {n: self._feed_shardings.get(n, row) for n in feeds}
        carry_sh = {
            'state': rw_sh,
            'slots': {n: self._feed_shardings[n] for n in operand['slots']},
            'token': row, 'alive': row, 'remaining': row,
        }
        if lane.name == 'chunk':
            aux_sh = {'active': row, 'finish': row, 'budget': row}
            return ((ro_sh, feed_sh, carry_sh, aux_sh, None),
                    (carry_sh, row))
        # the decode carry's token is the step program's own feed
        carry_sh['token'] = self._feed_shardings[spec['token']]
        out_row = NamedSharding(self.mesh, scanned_spec(row_spec))
        return ((ro_sh, feed_sh, carry_sh, None),
                (carry_sh, out_row, out_row))


class ParallelExecutor(object):
    """API parity with reference parallel_executor.py:36."""

    def __init__(self,
                 use_cuda=False,
                 loss_name=None,
                 main_program=None,
                 share_vars_from=None,
                 exec_strategy=None,
                 build_strategy=None,
                 num_trainers=1,
                 trainer_id=0,
                 scope=None,
                 mesh=None,
                 **kwargs):
        from ..parallel import make_mesh
        self._main_program = main_program if main_program is not None \
            else default_main_program()
        self._scope = scope if scope is not None else _current_scope()
        self._mesh = mesh if mesh is not None else make_mesh()
        self._loss_name = loss_name
        self._cache = {}
        # guards cache iteration/mutation between the dispatch thread
        # and metrics/bench readers (cost_report) — and the engine's
        # drop_executables purge path picks it up by name, like the
        # Executor's (PR 4 concurrent-predictor contract)
        self._cache_lock = threading.Lock()
        self._rng = None
        self.exec_strategy = exec_strategy or ExecutionStrategy()
        self.build_strategy = build_strategy or BuildStrategy()
        self._batch_axis = 'dp'
        # observability (mirrors Executor): compile_count is this
        # executor's OWN cache misses (block builds + new (steps, shapes)
        # of a scan lane), what JAX really compiled is
        # trace.compile_log(); dispatch accounting lets the contract
        # tests pin K steps per dispatch
        self.compile_count = 0
        _trace.compile_log()  # listeners on before the first compile
        self.dispatch_count = 0
        self.steps_dispatched = 0

    @property
    def device_count(self):
        return int(np.prod(self._mesh.devices.shape))

    def _dp_extent(self):
        """Rows-per-lot divisibility requirement: the mesh's extent
        along the batch axis (1 when the mesh has no 'dp' axis —
        batch replicated, nothing to pad for)."""
        axes = dict(zip(self._mesh.axis_names, self._mesh.devices.shape))
        return int(axes.get(self._batch_axis, 1))

    def _annotated_feed_names(self, feed_arrays):
        """Feed names carrying an explicit sharding annotation (and
        their @SEQLEN/@ROWS sidebands): laid out per their spec, not
        dp-sharded on dim 0, so they must not vote in (or be padded
        by) ragged-batch inference."""
        from ..parallel.api import sharding_of
        block = self._main_program.block(0)
        skip = set()
        for n in feed_arrays:
            base = n
            for suffix in (registry.SEQLEN_SUFFIX, registry.ROWS_SUFFIX):
                if base.endswith(suffix):
                    base = base[:-len(suffix)]
            v = block.vars.get(base)
            if v is not None and sharding_of(v) is not None:
                skip.add(n)
        return skip

    def _pad_ragged(self, feed_arrays, **kw):
        return pad_ragged_batch(
            feed_arrays, self._dp_extent(),
            skip=self._annotated_feed_names(feed_arrays), **kw)

    def _next_rng(self, program=None):
        import jax
        if self._rng is None:
            self._rng = jax.random.PRNGKey(
                self._main_program.random_seed or 0)
        self._rng, key = jax.random.split(self._rng)
        return key

    def _fetch_names(self, fetch_list):
        if isinstance(fetch_list, (Variable, str)):
            fetch_list = [fetch_list]
        return [_var_name(f) for f in fetch_list]

    def _resolve(self, fetch_names, feed_arrays, batch_feed_names=None):
        with _trace.span('paddle_tpu/executor/resolve'):
            return self._lookup_or_build(fetch_names, feed_arrays,
                                         batch_feed_names)

    def _lookup_or_build(self, fetch_names, feed_arrays, batch_feed_names):
        """Find (or compile) the sharded executable for this
        (program version, fetch list, feed signature).
        batch_feed_names: which feeds the ragged padding treated as
        batch-led (recorded PRE-padding) — seeds the trace's provenance
        so an aux feed whose rows coincide with the padded batch size
        is never masked or trimmed."""
        program = self._main_program
        sig = feed_signature(feed_arrays)
        key = (id(program), program._version, tuple(fetch_names), sig,
               registry.amp_enabled())
        with self._cache_lock:
            compiled = self._cache.get(key)
        if compiled is None:
            host = [op.type for op in program.global_block().ops
                    if _is_host_op(op)]
            if host:
                raise NotImplementedError(
                    'ParallelExecutor cannot run programs containing host '
                    'ops %s — run them with fluid.Executor' % sorted(set(host)))
            self.compile_count += 1
            compiled = _SpmdCompiledBlock(program, 0, [n for n, _, _ in sig],
                                          fetch_names, self._mesh,
                                          self._scope,
                                          batch_axis=self._batch_axis)
            # the inference is deterministic in the feed signature, so
            # setting this once at compile time is consistent for every
            # later cache hit
            compiled._batch_feed_names = (
                frozenset(batch_feed_names)
                if batch_feed_names is not None else None)
            with self._cache_lock:
                self._cache[key] = compiled
        return compiled

    def _convert_fetches(self, fetches, return_numpy, real=0, padded=0,
                         compiled=None):
        if real != padded:
            # a per-sample fetch over a padded lot carries fabricated
            # rows: trim the BATCH-LED ones (per the trace's provenance,
            # recorded at compile time) back to the REAL count so eval
            # loops never score the replicated samples — a parameter
            # whose dim 0 coincides with the padded size stays whole
            led = fetch_batch_led(compiled, len(fetches))
            fetches = [
                f[:real] if is_led and getattr(f, 'ndim', 0) >= 1
                and np.shape(f)[0] == padded else f
                for f, is_led in zip(fetches, led)
            ]
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [core.LoDTensor(np.asarray(f)) for f in fetches]

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        program = self._main_program
        feed = feed if feed is not None else (feed_dict or {})
        fetch_names = self._fetch_names(fetch_list)
        feed = dict(feed)
        _pop_readers_into_feed(program, feed)
        rpt = {}
        feed_arrays, real, padded = self._pad_ragged(
            prepare_feed_arrays(feed), report=rpt)
        compiled = self._resolve(fetch_names, feed_arrays,
                                 rpt.get('batch_names'))
        fetches = compiled.run(self._scope, feed_arrays, self._next_rng())
        # count only dispatches that actually ran
        self.dispatch_count += 1
        self.steps_dispatched += 1
        return self._convert_fetches(fetches, return_numpy, real, padded,
                                     compiled=compiled)

    def run_multi(self, fetch_list, feed=None, steps=1, feed_list=None,
                  return_numpy=True, reader=None, embed_caches=None):
        """Run ``steps`` iterations as ONE GSPMD-sharded device dispatch
        (the SPMD counterpart of Executor.run_multi; the reference
        amortizes per-iteration overhead with its double-buffered
        multi-iteration loop, executor.cc:321-339).  Returns the LAST
        iteration's fetches; state persists to the scope exactly as
        ``steps`` sequential run() calls would.

        feed: one lot reused every iteration (fori_loop), OR
        feed_list: per-iteration lots scanned on device (``steps`` is
        then len(feed_list)), OR
        reader: the program's py_reader — ``steps`` DISTINCT fresh
        minibatches drain from its queue and ride the feed_list path
        (so ragged reader lots pad to the dp extent with masked
        samples exactly like explicit ones).  Ragged lots — including
        a ragged FINAL lot in feed_list — are padded to the dp extent
        with masked samples; loss/grad means weight by the real sample
        count."""
        feed_list = _reader_feed_list(self, 'run_multi', self._main_program,
                                      reader, feed, feed_list, steps)
        fetch_names = self._fetch_names(fetch_list)
        scanned = None
        exchanges = []

        def stage(lots, k):
            exchanges.extend(stage_embed_caches(
                embed_caches, self._scope, 'ParallelExecutor.run_multi',
                lots, k))

        if feed_list is not None:
            if feed is not None:
                raise ValueError('run_multi: pass feed OR feed_list')
            per_step, reals, target, batch_feed_names = \
                prepare_scanned_lots('run_multi', feed_list,
                                     self._pad_ragged, stage)
            steps = len(per_step)
            real, n_padded = \
                (reals[-1] if reals is not None else target), target
            compiled = self._resolve(fetch_names, per_step[0],
                                     batch_feed_names)
            scanned = place_scanned(compiled, per_step)
            feed_arrays = {}  # every feed name arrives via the scan
        else:
            rpt = {}
            prepared = prepare_feed_arrays(
                dict(feed if feed is not None else {}))
            stage([prepared], steps)
            feed_arrays, real, n_padded = self._pad_ragged(
                prepared, report=rpt)
            compiled = self._resolve(fetch_names, feed_arrays,
                                     rpt.get('batch_names'))
        for cache, ex in exchanges:
            # the block's row exchange lands right before its dispatch
            cache.apply(ex)
        fetches = compiled.run_lane('train', self._scope, feed_arrays,
                                    self._next_rng(), scanned, steps=steps)
        # each (steps, scanned shape signature) is its own XLA compile
        # of the multi-step executable (steps is static)
        _count_lane(self, compiled, 'train', steps, scanned, steps)
        # fetches come from the LAST iteration: trim to its real rows
        return self._convert_fetches(fetches, return_numpy, real, n_padded,
                                     compiled=compiled)

    # the lanes' async front halves are executor.py's, shared with
    # Executor; below, what they ask of an executor
    _dispatch_multi_scanned = _dispatch_multi_scanned
    _dispatch_eval_multi = _dispatch_eval_multi
    _dispatch_decode_multi = _dispatch_decode_multi
    _dispatch_chunk_prefill = _dispatch_chunk_prefill
    _label = 'ParallelExecutor.'

    def _bind(self, program, scope):
        if (program is not None and program is not self._main_program) \
                or (scope is not None and scope is not self._scope):
            raise ValueError(
                'a ParallelExecutor runs its OWN main_program in its own '
                'scope — drop program=/scope=, or build the '
                'ParallelExecutor over them')
        return self._main_program, self._scope

    def _resolve_block(self, program, scope, fetch_list, feed,
                       batch_feed_names=None):
        feed_arrays = prepare_feed_arrays(feed)
        compiled = self._resolve(self._fetch_names(fetch_list), feed_arrays,
                                 batch_feed_names)
        return program, scope, feed_arrays, compiled

    def _count_dispatch(self, steps):
        self.dispatch_count += 1
        self.steps_dispatched += steps

    def run_eval_multi(self, fetch_list, feed=None, steps=None,
                       feed_list=None, return_numpy=True, reader=None):
        """Run ``steps`` EVAL iterations as ONE GSPMD-sharded device
        dispatch and return EVERY iteration's fetches (the SPMD
        counterpart of Executor.run_eval_multi — dp>1 sharded serving).
        Same return convention: one [K, ...]-stacked entry per fetch,
        batch-led fetches over unequal ragged lots as per-step lists.
        ``reader=``: up to ``steps`` DISTINCT fresh eval minibatches
        drain from the program's py_reader per dispatch (the eval
        sweep's symmetric mode; drain contract as Executor's — tail on
        EOF mid-block, bucket-boundary push-back, EOFException when
        already exhausted)."""
        stacked, reals, target, compiled, k = self._dispatch_eval_multi(
            fetch_list, feed=feed, steps=steps, feed_list=feed_list,
            reader=reader)
        return convert_eval_fetches(stacked, reals, target, compiled, k,
                                    return_numpy)

    def run_decode_multi(self, feed=None, carry=None, steps=None,
                         decode=None):
        """K autoregressive greedy-decode steps as ONE GSPMD-sharded
        device dispatch over the whole slot batch (the SPMD counterpart
        of Executor.run_decode_multi — ISSUE 7).  The slot carry shards
        its slot dim over 'dp' (the slot count must be a multiple of
        the dp extent — the engine sizes its cache so), per-slot stop
        conditions are masked inside the scan, and the carry is donated
        on device so the distributed decode cache updates in place.
        Returns (carry', tokens [K, S], alive_in [K, S]), no host
        sync."""
        carry_out, toks, alive_in, _ = self._dispatch_decode_multi(
            feed=feed, carry=carry, steps=steps, decode=decode)
        return carry_out, toks, alive_in

    def cost_report(self):
        """Per-executable cost registry (ISSUE 6), the SPMD twin of
        Executor.cost_report(): every cached sharded executable's XLA
        cost/memory analysis captured under FLAGS_cost_accounting."""
        with self._cache_lock:
            blocks = list(self._cache.values())
        return collect_cost_report(blocks)

    def bcast_params(self):
        """Reference BCastParamsToDevices (parallel_executor.cc:169) — a
        no-op under GSPMD: replication is a sharding, not a copy loop."""
        pass
