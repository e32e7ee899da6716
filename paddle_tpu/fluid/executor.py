"""Executor: compiles whole program blocks to XLA and runs them on TPU.

The reference Executor interprets a ProgramDesc op-by-op, dispatching a
CPU/CUDA kernel per op with per-op InferShape (framework/executor.cc:321-339).
That design wastes a TPU: launch overhead per op, no fusion, host round-trips.
This Executor instead:

  1. partitions the block (currently: whole block) and traces every op's
     XLA lowering into ONE jitted function;
  2. threads persistable state (params, optimizer slots, BN stats) in and out
     functionally — the analog of in-place Scope variables;
  3. caches compiled executables keyed by (program version, feed signature,
     fetch list) — the analog of the reference's ExecutorPrepareContext +
     program cache (python/paddle/fluid/executor.py:283);
  4. falls back to eager op-by-op execution for programs containing host ops
     (save/load/print/reader) — those run unfused but with identical
     semantics.

API parity: Executor(place), run(program, feed, fetch_list, ...) matching
python/paddle/fluid/executor.py:256.
"""

import collections
import functools
import threading

import numpy as np

from . import core
from . import flags
from . import trace as _trace
from .framework import default_main_program, Variable
from .shape_policy import SEQ_BUCKET, bucketed_len
from ..ops import registry


def _check_nan_inf(pairs, where):
    """Post-execution NaN/Inf scan (reference FLAGS_check_nan_inf,
    framework/operator.cc): raises naming the first offending variable.
    The in-jit half is jax_debug_nans (toggled by the flag's setter),
    which attributes failures to the producing primitive."""
    for name, val in pairs:
        try:
            arr = np.asarray(val)
        except Exception:
            continue
        if arr.dtype.kind == 'f' and not np.all(np.isfinite(arr)):
            raise RuntimeError(
                'check_nan_inf: %s %r contains NaN/Inf' % (where, name))

__all__ = ['Executor', 'global_scope', 'scope_guard', '_switch_scope',
           'fetch_var']


def _block_until_ready(fetches):
    for f in fetches:  # sync without disturbing fetch types
        if hasattr(f, 'block_until_ready'):
            f.block_until_ready()


def fetch_var(name, scope=None, return_numpy=True):
    """Fetch a (typically persistable) variable's value straight from a
    scope without running a program (reference executor.py:174)."""
    assert isinstance(name, str)
    if scope is None:
        scope = global_scope()
    var = scope.find_var(name)
    assert var is not None, (
        'Cannot find ' + name + ' in scope. Perhaps you need to make the'
        ' variable persistable by using var.persistable = True in your'
        ' program.')
    value = var.value()
    if return_numpy:
        return as_numpy(value)
    if not isinstance(value, core.LoDTensor):
        value = core.LoDTensor(np.asarray(value))
    return value

_scope_stack = [core.global_scope()]


def global_scope():
    """The active scope: scope_guard swaps it, like the reference's
    _switch_scope (python/paddle/fluid/executor.py:41-63)."""
    return _scope_stack[-1]


def _current_scope():
    return _scope_stack[-1]


def _switch_scope(scope):
    _scope_stack[-1] = scope
    return _scope_stack[-1]


import contextlib


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


def _is_host_op(op):
    # host ops (save/load/print/readers) register in the host-op registry;
    # any op with a host impl forces the eager (unfused) execution path
    return registry.is_host_op_type(op.type)


def as_numpy(value):
    if isinstance(value, core.LoDTensor):
        return value.numpy()
    return np.asarray(value)


def _pop_readers_into_feed(program, feed, place=None):
    """For each read op, pop one minibatch from its py_reader queue and
    inject it as feeds (reference: reader ops produce LoDTensors inside the
    interpreter loop; here data stays ahead of the compiled step).  Raises
    core.EOFException when a reader is exhausted."""
    for op in program.global_block().ops:
        if op.type != 'read':
            continue
        from .layers import io as layers_io
        reader_name = op.input('Reader')[0]
        feeder = layers_io.get_reader_feeder(reader_name)
        if feeder is None:
            raise RuntimeError('no py_reader registered for %r' %
                               reader_name)
        if place is not None:
            # bind the prefetch target to the executor CONSUMING this
            # reader (per-feeder, so an interleaved CPU eval executor
            # can't re-route a TPU train reader's staging)
            feeder._executor_place = place
        batch = feeder.pop()
        if batch is None:
            raise core.EOFException(
                'reader %r is exhausted — call reader.reset() and '
                'reader.start() for the next pass' % reader_name)
        for name, value in zip(op.output('Out'), batch):
            feed[name] = value


def prepare_feed_arrays(feed):
    """Normalize a user feed dict: LoD feeds lower to padded [B, T, ...]
    plus a ``<name>@SEQLEN`` lengths entry (SURVEY §5.7); device arrays
    pass through untouched.  Shared by Executor and ParallelExecutor."""
    import jax
    feed_arrays = {}
    for name, value in feed.items():
        if isinstance(value, core.PaddedSequence):
            # already padded + device-staged by a double-buffer reader
            feed_arrays[name] = value.data
            feed_arrays[name + registry.SEQLEN_SUFFIX] = value.lengths
            if value.rows is not None:
                feed_arrays[name + registry.ROWS_SUFFIX] = value.rows
        elif isinstance(value, core.LoDTensor) and value.lod():
            padded, lengths = _lod_to_padded(value)
            feed_arrays[name] = padded
            feed_arrays[name + registry.SEQLEN_SUFFIX] = lengths
            lod = value.lod()
            if len(lod) >= 2:
                # nested sequence: also carry the outer level (number of
                # sub-sequences per top-level sequence)
                outer = np.asarray(lod[0], np.int64)
                feed_arrays[name + registry.ROWS_SUFFIX] = (
                    outer[1:] - outer[:-1]).astype(np.int32)
        elif isinstance(value,
                        (core.LoDTensor, core.SelectedRows, jax.Array)):
            feed_arrays[name] = value
        else:
            feed_arrays[name] = np.asarray(value)
    return feed_arrays


def validate_feed(program, feed_arrays):
    """Fail fast with the var name and dims when a feed does not match its
    data-layer declaration (the analog of the reference DataFeeder checks,
    data_feeder.py:29)."""
    block = program.block(0)
    for name, value in feed_arrays.items():
        if name.endswith((registry.SEQLEN_SUFFIX, registry.ROWS_SUFFIX)):
            continue
        if name == registry.SAMPLE_MASK_NAME:
            continue  # executor-injected ragged-batch mask, not a data var
        if isinstance(value, core.SelectedRows):
            continue  # row-subset feeds carry their own height metadata
        var = block.vars.get(name)
        if var is None or not getattr(var, 'shape', None):
            continue
        shape = tuple(var.shape)
        got = getattr(value, 'shape', None)  # no device->host copy
        if callable(got):  # core.LoDTensor exposes shape() as a method
            got = got()
        got = tuple(got) if got is not None else tuple(
            np.shape(as_numpy(value)))
        lod = getattr(var, 'lod_level', 0) or 0
        ranks = (len(shape), ) if not lod else (len(shape) + 1, len(shape))
        if len(got) not in ranks:
            raise ValueError(
                'feed %r: expected rank %s (declared shape %s%s), got '
                'shape %s' % (name, ranks[0], shape,
                              ', lod_level=%d' % lod if lod else '', got))
        # declared dims must match aligned from the right (leading
        # batch/time dims are free; -1 dims are wildcards)
        for want, have in zip(reversed(shape), reversed(got)):
            if want is not None and want > 0 and want != have:
                raise ValueError(
                    'feed %r: dim mismatch, declared shape %s%s but got '
                    'shape %s' % (name, shape,
                                  ' (lod_level=%d)' % lod if lod else '',
                                  got))


def feed_signature(feed_arrays):
    import jax

    def _sig_of(v):
        if isinstance(v, jax.Array):
            return tuple(v.shape), str(v.dtype)
        if isinstance(v, core.SelectedRows):
            t = v.get_tensor().numpy()
            return ('sr', ) + tuple(np.shape(t)), str(t.dtype)
        a = as_numpy(v)
        return tuple(np.shape(a)), str(a.dtype)

    return tuple((n, ) + _sig_of(v) for n, v in sorted(feed_arrays.items()))


def check_feed_list_uniform(per_step):
    """lax.scan needs a uniform per-step structure: every prepared batch
    must share feed_list[0]'s names, shapes AND dtypes (a mixed-dtype
    stack would silently promote the whole scanned axis past the
    compiled block's feed signature).  Uniformity is exactly 'same
    feed_signature', so reuse it."""
    sig0 = feed_signature(per_step[0])
    for i, fa in enumerate(per_step[1:], 1):
        if feed_signature(fa) != sig0:
            raise ValueError(
                'run_multi: feed_list[%d] differs in names, shapes or '
                'dtypes from feed_list[0] — all batches must '
                'share one shape bucket (pad to it, or group '
                'batches by bucket)' % i)


def check_feed_list_names(per_step, what):
    """Every lot must share feed_list[0]'s NAME set before any
    cross-lot inference walks those names over the others (shared by
    run_multi and run_eval_multi on both executors)."""
    names0 = set(per_step[0])
    for i, fa in enumerate(per_step[1:], 1):
        if set(fa) != names0:
            raise ValueError(
                '%s: feed_list[%d] differs in names from feed_list[0]'
                % (what, i))


def normalize_trailing_feed_list(per_step):
    """Trailing-dim twin of normalize_ragged_feed_list (ISSUE 5): lots
    whose SEQ feeds disagree on the padded time extent re-quantize onto
    the shared seq-len ladder instead of failing the scan's uniformity
    check.  Only feeds carrying a ``<name>@SEQLEN`` lengths companion
    participate — their lowerings mask by real length, so zero-padding
    axis 1 up to ``bucketed_len(max extent)`` is exactly the fill
    ``_lod_to_padded`` already applies per batch (a dense feed with no
    lengths has no masking contract, and stays an error).  Mutates and
    returns ``per_step``; device-staged arrays only round-trip the host
    on the disagreeing (ragged) path."""
    names0 = per_step[0]
    for name in list(names0):
        if name.endswith((registry.SEQLEN_SUFFIX, registry.ROWS_SUFFIX)):
            continue
        if (name + registry.SEQLEN_SUFFIX) not in names0:
            continue
        extents = []
        for fa in per_step:
            v = fa[name]
            shape = v.shape() if isinstance(v, core.LoDTensor) \
                else np.shape(v)
            if len(shape) < 2:
                extents = None
                break
            extents.append(int(shape[1]))
        if not extents or len(set(extents)) == 1:
            continue
        t = _bucketed_len(max(extents))
        for fa, e in zip(per_step, extents):
            if e == t:
                continue
            arr = np.asarray(fa[name].numpy()
                             if isinstance(fa[name], core.LoDTensor)
                             else fa[name])
            pad = [(0, 0)] * arr.ndim
            pad[1] = (0, t - e)
            fa[name] = np.pad(arr, pad)
    return per_step


def stack_steps(vals):
    """Stack per-iteration feeds along a new leading K axis for the
    scanned dispatch.  Device-resident values (the double-buffer
    prefetch form) stack ON DEVICE — np.stack would drag each batch
    back through the host only to re-upload the whole epoch."""
    import jax
    import jax.numpy as jnp
    if all(isinstance(v, jax.Array) for v in vals):
        return jnp.stack(vals)
    return np.stack([np.asarray(v) for v in vals])


def fetch_batch_led(compiled, n):
    """The trace's batch-led provenance side channel, defaulting to
    all-False before the first trace: the ONE reading of
    ``_fetch_batch_led`` shared by every consumer that trims padded
    rows (convert_eval_fetches, ParallelExecutor._convert_fetches, the
    serving engine's per-request slicer) — so a change to the side
    channel's convention has a single place to land."""
    return getattr(compiled, '_fetch_batch_led', None) or [False] * n


def convert_eval_fetches(stacked, reals, target, compiled, steps,
                         return_numpy):
    """Host-side back half of run_eval_multi (shared by Executor and
    ParallelExecutor): convert each [K, ...]-stacked fetch, trimming
    BATCH-LED fetches (per the trace's provenance side channel) from the
    padded row count ``target`` back to the per-step real counts.  Equal
    real counts trim as one slice (still a stacked array); unequal ones
    come back as a list of K per-step arrays."""
    led = fetch_batch_led(compiled, len(stacked))
    out = []
    for arr, is_led in zip(stacked, led):
        a = np.asarray(arr)
        if reals is not None and is_led and a.ndim >= 2 \
                and a.shape[1] == target:
            if len(set(reals)) == 1:
                a = a[:, :reals[0]]
            else:
                per = [a[i][:reals[i]] for i in range(steps)]
                out.append(per if return_numpy else
                           [core.LoDTensor(p) for p in per])
                continue
        out.append(a if return_numpy else core.LoDTensor(a))
    return out


def collect_cost_report(compiled_blocks):
    """Flatten compiled blocks' captured cost entries into the
    ``cost_report()`` list form shared by Executor and ParallelExecutor
    (ISSUE 6): one record per analyzed executable — kind, steps, XLA
    cost-analysis FLOPs (total and per step), bytes accessed, and the
    memory-analysis buffer sizes.  Entries exist only for executables
    dispatched under FLAGS_cost_accounting."""
    out = []
    for compiled in compiled_blocks:
        for key, entry in compiled.cost_entries().items():
            if entry is None:
                continue
            rec = dict(entry)
            rec['key'] = repr(key)
            out.append(rec)
    return out


def _var_name(v):
    return v.name if isinstance(v, Variable) else str(v)


def _state_pairs(state, empty):
    """A decode / chunk spec's ``state``: ordered (feed name, fetch
    name) pairs from pairs or a dict of names or Variables."""
    if isinstance(state, dict):
        state = list(state.items())
    state = tuple((str(feed_n), _var_name(fetch)) for feed_n, fetch in state)
    if not state:
        raise ValueError(empty)
    return state


def normalize_decode_spec(decode):
    """Validate + normalize the ``decode=`` argument shared by BOTH
    executors' ``run_decode_multi`` (ISSUE 7).  The spec names the
    autoregressive wiring of a STEP program:

      token:   the feed carrying the current token ([S, 1] int)
      logits:  the fetch (Variable or name) whose argmax is the next
               token ([S, vocab] — the greedy-decode selection)
      state:   ordered (feed_name, fetch) pairs — each step the fetch's
               value becomes the feed's next value (the KV/hidden slot
               state threading through the scan carry)
      context: feed names that live in the slot carry but never update
               (per-slot read-only state, e.g. encoder outputs)
      end_id:  the EOS token id (the per-slot stop condition, masked
               inside the scan next to the per-slot step budget)
    """
    if not isinstance(decode, dict):
        raise ValueError('decode= must be a dict (token/logits/state/'
                         'end_id), got %r' % (type(decode), ))
    missing = [k for k in ('token', 'logits', 'state', 'end_id')
               if k not in decode]
    if missing:
        raise ValueError('decode= is missing %s' % missing)
    return {
        'token': str(decode['token']),
        'logits': _var_name(decode['logits']),
        'state': _state_pairs(
            decode['state'],
            'decode= needs at least one state pair — a stateless step '
            'function has nothing to carry between decode steps'),
        'context': tuple(str(n) for n in decode.get('context', ())),
        'end_id': int(decode['end_id']),
    }


def canonical_decode_carry(carry):
    """Canonicalize the decode carry's array leaves to jax's dtype
    rules ONCE on the way in (shared by both executors'
    run_decode_multi).  Without jax x64, a host int64 token would
    compile one executable on the first dispatch and a DIFFERENT one
    (int32 — the scan's own output dtype) on every later dispatch:
    the signature must be stable across the carry round trip."""
    import jax.numpy as jnp

    def c(v):
        return v if hasattr(v, 'devices') else jnp.asarray(v)

    return {'slots': {n: c(v) for n, v in carry['slots'].items()},
            'token': c(carry['token']), 'alive': c(carry['alive']),
            'remaining': c(carry['remaining'])}


def check_decode_carry(carry, spec, what):
    """Fail fast when a decode carry does not match its spec: the slot
    dict must cover exactly the state + context feeds, and the
    token/alive/remaining leaves must be present (shared by both
    executors' run_decode_multi)."""
    if not isinstance(carry, dict):
        raise ValueError('%s: carry must be a dict, got %r'
                         % (what, type(carry)))
    missing = [k for k in ('slots', 'token', 'alive', 'remaining')
               if k not in carry]
    if missing:
        raise ValueError('%s: carry is missing %s' % (what, missing))
    want = set(n for n, _ in spec['state']) | set(spec['context'])
    have = set(carry['slots'])
    # @SEQLEN/@ROWS companions of context feeds ride along untouched
    extra = {n for n in have - want
             if not n.endswith((registry.SEQLEN_SUFFIX,
                                registry.ROWS_SUFFIX))}
    if want - have or extra:
        raise ValueError(
            '%s: carry slots %s do not match the decode spec (missing '
            '%s, unexpected %s)' % (what, sorted(have),
                                    sorted(want - have), sorted(extra)))


def normalize_chunk_spec(chunk):
    """Validate + normalize the ``chunk=`` argument shared by BOTH
    executors' ``run_chunk_prefill`` (ISSUE 14).  The spec names the
    chunked-prefill wiring of a CHUNK program — the C-token-block form
    of a generation model's prompt consumption:

      token:    the feed carrying one [S, C, 1] token block per slot
      len:      optional per-slot real-length feed ([S, 1] float — the
                transformer family masks its in-block scatter with it;
                the engine always ALSO injects the token feed's @SEQLEN
                companion for sequence-op masking)
      state:    ordered (step_feed_name, chunk_fetch) pairs — the
                chunk program's advanced value for every decode-state
                slab (must cover the decode spec's state feeds exactly)
      start_id: the BOS token written into finishing slots' carry
    """
    if not isinstance(chunk, dict):
        raise ValueError('chunk= must be a dict (token/len/state/'
                         'start_id), got %r' % (type(chunk), ))
    missing = [k for k in ('token', 'state', 'start_id')
               if k not in chunk]
    if missing:
        raise ValueError('chunk= is missing %s' % missing)
    return {
        'token': str(chunk['token']),
        'len': (str(chunk['len'])
                if chunk.get('len') is not None else None),
        'state': _state_pairs(
            chunk['state'],
            'chunk= needs at least one state pair — a chunk that '
            'advances no slab is a no-op'),
        'start_id': int(chunk['start_id']),
    }


def check_chunk_aux(aux, what, slots=None):
    """Fail fast when a chunk dispatch's per-slot aux leaves are
    malformed (shared by both executors' run_chunk_prefill): the
    active/finish masks and the finishing-slot step budget must all be
    present, one-dimensional, and ``slots`` long — a transposed or
    scalar leaf would otherwise surface as an opaque jit broadcasting
    error (or silently wrong finish masking) inside the chunk
    kernel."""
    if not isinstance(aux, dict):
        raise ValueError('%s: aux must be a dict, got %r'
                         % (what, type(aux)))
    missing = [k for k in ('active', 'finish', 'budget')
               if k not in aux]
    if missing:
        raise ValueError('%s: aux is missing %s' % (what, missing))
    for k in ('active', 'finish', 'budget'):
        shape = np.shape(aux[k])
        if len(shape) != 1 or \
                (slots is not None and int(shape[0]) != int(slots)):
            raise ValueError(
                '%s: aux[%r] must be a 1-D per-slot vector%s, got '
                'shape %s' % (what, k,
                              ' of length %d' % slots
                              if slots is not None else '', shape))


def _reject_reader_fed(program, what):
    """The PLAIN-FEED multi paths never compose with py_reader-fed
    programs: resolving would pop exactly ONE minibatch and the K-step
    loop would train on it K times with no signal (the reference
    multi-iteration loop, executor.cc:321-339, pulls fresh data every
    iteration).  run_multi(reader=..., steps=K) is the composing form:
    it drains K DISTINCT batches per dispatch (fluid.dataflow)."""
    prog = program if program is not None else default_main_program()
    if any(op.type == 'read' for op in prog.global_block().ops):
        # each multi path names ITS OWN reader= mode (train and eval
        # drains are symmetric since ISSUE 4's run_eval_multi reader=)
        composing = ('run_eval_multi(reader=..., steps=K)'
                     if 'eval' in what else
                     'run_multi(reader=..., steps=K)')
        raise RuntimeError(
            '%s does not compose with py_reader-fed programs through '
            'feed=/feed_list= — pass the reader (%s drains K fresh '
            'batches per dispatch), feed the batches explicitly, or '
            'use run() per step' % (what, composing))
    return prog


# The seq-len ladder policy lives in shape_policy so the serving
# engine's trailing ladder and the feed_list normalization share ONE
# tuning knob (ISSUE 5); the old private names stay as aliases.
_SEQ_BUCKET = SEQ_BUCKET
_bucketed_len = bucketed_len


def _lod_to_padded(lt, bucket=_SEQ_BUCKET):
    """Concatenated LoD tensor -> (padded [B, T, ...], lengths [B]).

    T is bucketed so recompiles are bounded (the static-shape answer to
    LoD's no-padding design, SURVEY §5.7; policy in _bucketed_len)."""
    data = lt.numpy()
    offsets = np.asarray(lt.lod()[-1], np.int64)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    b = len(lengths)
    max_len = int(lengths.max()) if b else 0
    t = _bucketed_len(max_len, bucket)
    out = np.zeros((b, t) + data.shape[1:], data.dtype)
    if b and len(data):
        # vectorized scatter: row i gets data[offsets[i]:offsets[i+1]]
        row = np.repeat(np.arange(b), lengths)
        pos = np.arange(len(data)) - np.repeat(offsets[:-1], lengths)
        out[row, pos] = data
    return out, lengths


def _to_device_value(value, var_desc, device):
    import jax
    if isinstance(value, core.SelectedRows):
        return value  # host-domain value; consumed by host ops as-is
    if isinstance(value, jax.Array):
        # committed to the device (state a step wrote back, feeds a
        # pipeline staged): passed on as it is, no round trip over the
        # host.  On the device but UNCOMMITTED (what a jit without a
        # committed input returns: every output of a startup program):
        # device_put commits it over the same buffer, no copy, so
        # jax.jit meets ONE argument signature from the first dispatch
        # on and does not lower and compile again when the first
        # step's outputs come back committed (_state_from_scope keeps
        # the committed array in the scope).  On another device: moved.
        if value.committed and device in value.devices():
            return value
        return jax.device_put(value, device)
    if isinstance(value, core.LoDTensor):
        value = value.numpy()
    arr = np.asarray(value)
    if var_desc is not None and arr.dtype != var_desc.np_dtype:
        # feeding python lists/floats: trust the declared dtype
        if np.issubdtype(arr.dtype, np.floating) and np.issubdtype(
                var_desc.np_dtype, np.floating):
            arr = arr.astype(var_desc.np_dtype)
    return jax.device_put(arr, device)


class _CompiledBlock(object):
    """One jitted XLA executable for a (program, feed-sig, fetch) triple."""

    def __init__(self, program, block_idx, feed_names, fetch_names, place,
                 scope):
        import jax
        self.program = program
        self.block = program.block(block_idx)
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.place = place
        block = self.block

        # read ops are satisfied on the host before the jitted call (their
        # outputs arrive as feeds), keeping the compute path fully fused
        ops = [op for op in block.ops
               if op.type not in ('feed', 'fetch', 'read')]
        self.ops = ops

        # Walk program order to find which persistable vars must come from
        # the scope (read-before-write) and which are written.
        defined = set(self.feed_names)
        state_in = []
        state_out = []

        def threadable(v):
            # SELECTED_ROWS-typed vars (sparse tables, row-subset grads)
            # live in the host domain: host ops manage them via the scope
            # directly, never as threaded jit state
            return (v is not None and v.persistable and
                    v.type != core.VarDesc.VarType.SELECTED_ROWS)

        for op in ops:
            reads = list(op.input_arg_names)
            if op.type in ('conditional_block', 'ifelse', 'switch_case'):
                # blended control flow READS every written var's old
                # value (the cond-false blend), so a startup-initialized
                # persistable updated in a branch must arrive as state_in
                reads += list(op.output_arg_names)
            for name in reads:
                if name in defined or name in state_in:
                    continue
                if threadable(block._find_var_recursive(name)):
                    state_in.append(name)
                    defined.add(name)
            for name in op.output_arg_names:
                v = block._find_var_recursive(name)
                if threadable(v) and name not in state_out:
                    state_out.append(name)
                defined.add(name)
        # fetching a persistable var that no op writes still needs its value
        for name in self.fetch_names:
            if name not in defined:
                if threadable(block._find_var_recursive(name)):
                    state_in.append(name)
                    defined.add(name)
        self.state_in = state_in
        self.state_out = state_out
        # split read-write from read-only: only RW buffers may be donated,
        # otherwise XLA can alias a read-only input (e.g. the LR scalar) to
        # an output and delete the buffer the scope still references
        self.state_rw = [n for n in state_in if n in set(state_out)]
        self.state_ro = [n for n in state_in if n not in set(state_out)]

        fetch_names_ = self.fetch_names
        state_out_ = state_out
        # filled by _SpmdCompiledBlock before its first trace; consulted by
        # mesh-aware lowerings (ring attention) at trace time
        self._spmd_ref = {'mesh': None, 'batch_axis': None}
        spmd_ref = self._spmd_ref

        def paddle_tpu_step(state_rw, state_ro, feeds, rng):
            # traced under STEP_SCOPE: in a device trace, an operation
            # inside it belongs to the Program's block, one outside it
            # (in a lane's executable) to the lane's own loop and slicing
            with jax.named_scope(registry.STEP_SCOPE):
                return step(state_rw, state_ro, feeds, rng)

        def step(state_rw, state_ro, feeds, rng):
            env = {}
            env.update(state_rw)
            env.update(state_ro)
            env.update(feeds)
            ctx = registry.LoweringContext(block, env, rng_key=rng,
                                           place=place,
                                           mesh=spmd_ref['mesh'],
                                           batch_axis=spmd_ref['batch_axis'])
            mask = feeds.get(registry.SAMPLE_MASK_NAME)
            if mask is not None:
                # ragged-batch provenance roots: the feeds the PADDING
                # treated as batch-led (recorded pre-padding, where an
                # aux feed whose rows merely coincide with the padded
                # size is still distinguishable), falling back to the
                # dim-0 shape match.  run_op propagates from here;
                # state (params) is never batch-led.
                declared = getattr(self, '_batch_feed_names', None)
                if declared is not None:
                    ctx.batch_led = {n for n in feeds if n in declared}
                else:
                    ctx.batch_led = {
                        n for n, v in feeds.items()
                        if getattr(v, 'ndim', 0) >= 1
                        and v.shape[0] == mask.shape[0]}
                ctx.batch_tainted = set(ctx.batch_led)
            for op in ops:
                registry.run_op(ctx, op)
            registry.check_cond_uninit(ctx, fetch_names_, 'fetch')
            # NOTE a persistable var assigned only inside a conditional
            # block cannot reach here cond-uninit: the state scan counts
            # blended control flow's outputs as READS, so the var is
            # state_in — either the scope lacks it (_state_from_scope
            # raises 'not initialized') or its real value is in env and
            # the blend keeps it.  No zeros ever persist.
            new_state = {n: env[n] for n in state_out_ if n in env}
            fetches = [env[n] for n in fetch_names_]
            # trace-time side channel: which fetches are batch-led, so
            # the ragged-batch executors trim ONLY those back to the
            # real row count (a parameter fetch whose dim 0 coincides
            # with the padded batch size must come back whole)
            self._fetch_batch_led = [n in ctx.batch_led
                                     for n in fetch_names_]
            return new_state, fetches

        # the jitted callables carry names of their own (``paddle_tpu_step``
        # here, ``paddle_tpu_<lane>`` for the scans): the name is the XLA
        # module's, by which a trace's reader finds the step program
        self._fn = paddle_tpu_step
        self._fetch_batch_led = None  # set at first trace
        self._lane_jits = {}  # every lane's executables (_lane_jit)
        self._lanes_seen = set()  # every lane's compiles (note_compile)
        self._launched = None     # run_lane's last launch, for the same
        donate = (0, ) if self.state_rw else ()
        self._jit = jax.jit(paddle_tpu_step, donate_argnums=donate)

        # eager-path release plan (memory_optimize transpiler): names the
        # pass marked releasable, positioned at their last use over THIS
        # executable's op list and filtered against what must stay alive
        # to the end
        self._eager_release = {}
        allowed = getattr(program, '_releasable', None)
        if allowed:
            keep = (set(self.fetch_names) | set(state_out) |
                    set(state_in))
            last = {}
            for i, op in enumerate(ops):
                for n in op.input_arg_names:
                    last[n] = i
                for n in op.output_arg_names:
                    last[n] = i
            rel = {}
            for n, i in last.items():
                if n in allowed and n not in keep:
                    rel.setdefault(i, []).append(n)
            self._eager_release = rel

    def _run_eager(self, scope, state_rw, state_ro, feeds, rng):
        """Unfused op-by-op execution for blocks containing host ops
        (save/load/print/readers) — identical semantics, no jit."""
        env = {}
        env.update(state_rw)
        env.update(state_ro)
        env.update(feeds)
        ctx = registry.LoweringContext(
            self.block, env, rng_key=rng, place=self.place)
        ctx.scope = scope
        check_nan = flags.FLAGS.check_nan_inf
        for op_idx, op in enumerate(self.ops):
            host_impl = registry.get_host_op(op.type)
            if host_impl is not None:
                # host ops bypass run_op: apply the may-read-before-
                # write check here (a save/print of a cond-uninit var
                # is exactly the reference's uninitialized-read error)
                registry.check_cond_uninit(ctx, op.input_arg_names,
                                           'host op %r' % op.type)
                host_impl(ctx, op, scope)
                # ...and an unconditional host-op WRITE (load/
                # load_combine) covers the name, same as run_op's rule
                for n in op.output_arg_names:
                    ctx.cond_uninit.discard(n)
            else:
                registry.run_op(ctx, op)
            if check_nan:
                # eager path gets reference-style per-op attribution
                _check_nan_inf(
                    [(n, env[n]) for n in op.output_arg_names if n in env],
                    'output of op %r' % op.type)
            # memory_optimize release plan: drop vars past their last use
            # so the eager env's peak live set matches true liveness
            for n in self._eager_release.get(op_idx, ()):
                env.pop(n, None)
        registry.check_cond_uninit(ctx, self.fetch_names, 'fetch')
        new_state = {n: env[n] for n in self.state_out if n in env}
        fetches = [env[n] for n in self.fetch_names]
        return new_state, fetches

    def _state_from_scope(self, scope, names, to_value, cache_back=False):
        import jax
        state = {}
        for name in names:
            var = scope.find_var(name)
            if var is None or var.value() is None:
                raise RuntimeError(
                    'persistable var %r is not initialized in scope — '
                    'did you run the startup program?' % name)
            raw = var.value()
            val = to_value(raw, self.block._find_var_recursive(name))
            if isinstance(raw, jax.Array):
                if val is not raw and val.devices() == raw.devices():
                    # staging committed an uncommitted array where it
                    # lay (_to_device_value): the scope keeps the
                    # committed alias, RW or RO alike, so that no
                    # second array object outlives the staging and a
                    # read-only variable is not committed again every
                    # dispatch.  Both are one buffer: donating the
                    # alias deletes the array it replaced as well, so
                    # the scope is no worse off if the step raises.
                    var.set_value(val)
            elif cache_back and isinstance(val, jax.Array):
                # host-resident READ-ONLY state (e.g. params
                # load_inference_model just read from disk) stays
                # device-resident after the first staging: run() never
                # writes state_ro back, so without this every inference
                # call would re-upload ~all params, one H2D transfer
                # per array per call.
                # RW state must NOT be cached here: its staged buffer
                # is donated into the jit, and caching it would leave
                # the scope pointing at deleted buffers if the step
                # raises before the post-run write-back.
                lod = raw.lod() if isinstance(raw, core.LoDTensor) else None
                if not lod:
                    var.set_value(val)
            state[name] = val
        return state

    def _materialize_args(self, scope, feed_values, cache_ro=False):
        """Device-stage the jit/eager call's arguments: threaded scope
        state and feeds (shared by run() and Executor.memory_analysis —
        the stats must describe the executable run() executes).
        cache_ro: run()-only — memory_analysis uploads nothing into
        the scope (an uncommitted device array it does replace by its
        committed alias, as every staging does: the same buffer)."""
        device = self.place.jax_device()
        to_value = lambda v, desc: _to_device_value(v, desc, device)
        state_rw = self._state_from_scope(scope, self.state_rw, to_value)
        state_ro = self._state_from_scope(scope, self.state_ro, to_value,
                                          cache_back=cache_ro)
        feeds = {
            n: _to_device_value(v, self.block._find_var_recursive(n), device)
            for n, v in feed_values.items()
        }
        return state_rw, state_ro, feeds

    # shared by every compiled block: entry inserts and cost_entries()
    # snapshots race between the dispatch thread and a metrics/bench
    # caller — one module lock keeps the dict copy coherent (held only
    # around dict ops, never across the AOT analysis compile)
    _COST_LOCK = threading.Lock()

    def _capture_cost(self, kind, key, jitted, args, steps=1):
        """Per-executable cost accounting (ISSUE 6): under
        FLAGS_cost_accounting, AOT-analyze ``jitted`` once per cache
        key (two racing first dispatches may both analyze; the result
        is identical and one wins the insert) and remember XLA's own
        FLOPs/bytes — the MFU/HBM ground truth behind
        Executor.cost_report().  Runs BEFORE the dispatch (the abstract
        twins never touch the soon-to-be-donated buffers, and carry
        their shardings, so the dispatch takes from JAX's caches the
        executable the analysis compiled: fluid.trace.aot_compile); a
        backend without cost analysis caches None and never retries."""
        if not flags.FLAGS.cost_accounting:
            return None
        full_key = (kind, ) + tuple(key)
        with self._COST_LOCK:
            reg = getattr(self, '_cost_entries', None)
            if reg is None:
                reg = self._cost_entries = {}
            if full_key in reg:
                return reg[full_key]
        entry = _trace.analyze_cost(jitted, args, kind=kind, steps=steps,
                                    fetch_names=self.fetch_names)
        with self._COST_LOCK:
            return reg.setdefault(full_key, entry)

    def cost_entries(self):
        """This executable set's captured cost-registry entries."""
        with self._COST_LOCK:
            return dict(getattr(self, '_cost_entries', None) or {})

    def _stage_state(self, scope, feed_values):
        with _trace.span('paddle_tpu/executor/stage_state'):
            return self._materialize_args(scope, feed_values, cache_ro=True)

    def _write_back(self, scope, new_state):
        with _trace.span('paddle_tpu/executor/write_back'):
            for name, val in new_state.items():
                scope.var(name).set_value(val)

    def run(self, scope, feed_values, rng_key, eager=False):
        state_rw, state_ro, feeds = self._stage_state(scope, feed_values)
        if eager:
            new_state, fetches = self._run_eager(scope, state_rw, state_ro,
                                                 feeds, rng_key)
        else:
            self._capture_cost('run', (), self._jit,
                               (state_rw, state_ro, feeds, rng_key))
            with _trace.span('paddle_tpu/executor/launch'):
                new_state, fetches = self._jit(state_rw, state_ro, feeds,
                                               rng_key)
            if flags.FLAGS.check_nan_inf:
                _check_nan_inf(list(new_state.items()), 'state var')
                _check_nan_inf(zip(self.fetch_names, fetches), 'fetch')
        self._write_back(scope, new_state)
        return fetches

    def run_lane(self, lane, scope, feed_values, rng_key, operand,
                 steps=None, spec=None, aux=None):
        """ONE device dispatch of a lane (``_LANES``): K steps of the
        program round which the lane's body loops, or the chunk lane's
        one advance.  Amortizes the per-dispatch host cost (feed
        staging, jit call, scope write-back) over K steps; small steps
        such as the stacked LSTM's are otherwise host-bound.

        feed_values: feeds held constant across iterations.
        operand: for the scanned lanes {name: array with leading K
        axis} — one slice per iteration (a whole epoch shipped in one
        transfer); None or empty, the train lane loops over
        ``feed_values`` alone.  For the carried lanes the engine-facing
        slot view (slots/token/alive/remaining), with ``spec`` the
        normalized decode / chunk spec and ``aux`` the chunk lane's
        per-slot active/finish/budget leaves.
        Persistable RW state threads through every lane and persists
        back to the scope.  Returns the lane's results with NO host
        sync: the scanned lanes' fetches (train: the last step's; eval:
        every step's, stacked on a leading K axis), (carry', tokens
        [K, S], alive_in [K, S]) for decode, (carry', alive') for
        chunk."""
        lane = _LANES[lane]
        if lane.counted and steps < 1:
            raise ValueError('run_%s: steps must be >= 1, got %r'
                             % (lane.kind, steps))
        if any(_is_host_op(op) for op in self.ops):
            raise RuntimeError(
                'run_%s: the program contains host ops and cannot run as '
                'one on-device %s' % (lane.kind, lane.advice))
        state_rw, state_ro, feeds = self._stage_state(scope, feed_values)
        if lane.carried:
            # the traced carry: the engine-facing slot view (slots /
            # token / alive / remaining) with the RW state beside it
            full = dict(operand, slots=dict(operand['slots']), state=state_rw)
            args = (state_ro, feeds, full)
            args += ((aux, ) if lane.name == 'chunk' else ()) + (rng_key, )
        else:
            operand = operand or {}
            args = (state_rw, state_ro, feeds, operand, rng_key)
        n = (int(steps), ) if lane.counted else ()
        args += n
        jitted = self._lane_jit(lane.name, feeds, operand, spec)
        names = _lane_names(lane, feeds, operand)
        # the serving engine reads last_<lane>_cost (eval, decode,
        # chunk) to derive the achieved MFU of the dispatch it drains
        setattr(self, 'last_%s_cost' % lane.name, self._capture_cost(
            lane.kind, names + n, jitted, args,
            steps=steps if lane.counted else 1))
        with _trace.span('paddle_tpu/executor/launch'):
            out = jitted(*args)
        # for note_compile, should it find this signature new
        self._launched = (jitted, args, lane, names, spec)
        if not lane.carried:
            self._write_back(scope, out[0])
            return out[1]
        carry_out = dict(out[0])
        self._write_back(scope, carry_out.pop('state'))
        return (carry_out, ) + tuple(out[1:])

    def _lane_jit(self, lane, feeds, operand, spec=None):
        """The lane's executable for this NAME structure of constant
        feeds and operand (and, for the carried lanes, this spec): one
        cache for every lane.  Shapes are not part of the key — the jit
        retraces per shape itself, and ``note_compile`` foresees it.
        What is dead the moment the lane consumed it is DONATED on
        device: RW state; the scanned K-step feed block (two pipelined
        dispatches then double-buffer it instead of holding 2x K lots —
        XLA can take the offer only when an output has the block's
        shape and dtype to alias it to: in the train scan none does,
        JAX warns "Some donated buffers were not usable", on the chip
        as on CPU, and the offer frees nothing there: PERF.md, open
        questions); the slot carry, which XLA then updates IN PLACE, so
        the resident decode cache never doubles during a dispatch."""
        lane = _LANES[lane]
        names = _lane_names(lane, feeds, operand)
        key = (lane.name, ) + names
        if lane.carried:
            key += tuple(sorted(spec.items()))
        jitted = self._lane_jits.get(key)
        if jitted is None:
            jitted = self._lane_jits[key] = self._build_lane_jit(
                lane, names, spec)
        return jitted

    def _again(self):
        """What builds a block like this one: a callable that refers
        neither to this block nor to anything it compiled (a loaded
        program holds device memory, and dies with its executor's cache
        entry).  _SpmdCompiledBlock gives its mesh."""
        return functools.partial(
            _CompiledBlock, self.program, self.block.idx, self.feed_names,
            self.fetch_names, self.place, None)

    def _build_lane_jit(self, lane, names, spec):
        """A new jit of the lane's body for ``names`` (``_lane_names``)
        and ``spec``: ``_lane_jit``'s on a cache miss, and
        ``_lane_jit_again``'s."""
        import jax
        feeds, operand = names
        if lane.carried:
            fn, donate = lane.make(self, spec), (2, )
            operand = {'slots': operand}
        else:
            fn = lane.make(self)
            donate = ((0, ) if self.state_rw else ()) + (
                (3, ) if operand else ())
        ins, outs = self._lane_shardings(lane, feeds, operand, spec)
        kw = {} if ins is None else {'in_shardings': ins,
                                     'out_shardings': outs}
        # the step count is the body's last argument
        static = (4 if lane.carried else 5, ) if lane.counted else ()
        return jax.jit(fn, static_argnums=static, donate_argnums=donate,
                       **kw)

    def _lane_shardings(self, lane, feeds, operand, spec):
        """(in_shardings, out_shardings) of a lane's jit: none on one
        device.  _SpmdCompiledBlock overrides this — the ONE thing the
        mesh changes about a lane."""
        return None, None

    def scanned_sharding(self, name):
        """Where a scanned feed block is placed: this block's device
        (_SpmdCompiledBlock: the feed's sharding over the mesh)."""
        return self.place.jax_device()

    def note_compile(self, lane, static, sig):
        """True exactly when this lane has not run this (static value,
        shape signature of ``sig``) before — i.e. the dispatch was a
        real XLA retrace (steps, or the chunk width, is a static jit
        argument or a traced shape; each scanned or carried
        structure/shape retraces too).  The compile_count bookkeeping
        of both executors, for every lane: each lane is its own
        executable, so retraces are tracked per lane.  A new signature
        is also where ``fluid.trace.note_executable`` is handed the
        launch ``run_lane`` just made (``_launched``, taken here every
        time so that no dispatch's arguments outlive it; two threads on
        one block may note each other's launch: a record of the same
        lane either way)."""
        key = (lane, int(static),
               feed_signature(sig) if sig is not None else None)
        launched, self._launched = self._launched, None
        if key in self._lanes_seen:
            return False
        self._lanes_seen.add(key)
        if launched is not None:
            # fluid.trace keeps what executable_record would need of the
            # launch just made, and nothing more
            _trace.note_executable(*launched[:2], functools.partial(
                _lane_jit_again, self._again(),
                getattr(self, '_batch_feed_names', None), *launched[2:]))
        return True

    def _make_multi(self):
        """The K-steps-per-dispatch function: K-1 iterations inside
        lax.scan (per-step feeds) or fori_loop (constant feeds), last
        step unrolled so fetches come out.  Shared verbatim by the
        single-device and SPMD executors — only the jit's shardings
        differ."""
        import jax
        fn = self._fn
        rw_keys = list(self.state_rw)

        def paddle_tpu_train_scan(state_rw, state_ro, feeds, scanned, rng,
                                  n):
            if scanned:
                def body(s, sl):
                    i, per_step = sl
                    merged = dict(feeds)
                    merged.update(per_step)
                    new_state, _ = fn(s, state_ro, merged,
                                      jax.random.fold_in(rng, i))
                    return ({k: new_state.get(k, s[k])
                             for k in rw_keys}, None)

                head = {k: v[:-1] for k, v in scanned.items()}
                final, _ = jax.lax.scan(
                    body, state_rw,
                    (jax.numpy.arange(n - 1), head))
                last = dict(feeds)
                last.update({k: v[-1] for k, v in scanned.items()})
            else:
                def body(i, s):
                    new_state, _ = fn(s, state_ro, feeds,
                                      jax.random.fold_in(rng, i))
                    return {k: new_state.get(k, s[k]) for k in rw_keys}

                final = jax.lax.fori_loop(0, n - 1, body, state_rw)
                last = feeds
            # last step outside the loop so fetches come out
            new_state, fetches = fn(final, state_ro, last,
                                    jax.random.fold_in(rng, n - 1))
            return new_state, fetches

        return paddle_tpu_train_scan

    def _make_eval_multi(self):
        """The K-EVAL-batches-per-dispatch function: lax.scan over the
        lots, collecting EVERY iteration's fetches stacked on a leading
        K axis — inference serving wants all K results, unlike
        _make_multi's train loop which only surfaces the last step's.
        State still threads through the carry (an eval program normally
        writes none, but e.g. metric accumulators stay correct)."""
        import jax
        import jax.numpy as jnp
        fn = self._fn
        rw_keys = list(self.state_rw)

        def paddle_tpu_eval_scan(state_rw, state_ro, feeds, scanned, rng,
                                 n):
            def body(s, sl):
                i, per_step = sl
                merged = dict(feeds)
                merged.update(per_step)
                new_state, fetches = fn(s, state_ro, merged,
                                        jax.random.fold_in(rng, i))
                return ({k: new_state.get(k, s[k])
                         for k in rw_keys}, fetches)

            final, stacked = jax.lax.scan(
                body, state_rw, (jnp.arange(n), scanned))
            return final, stacked

        return paddle_tpu_eval_scan

    def _slot_updates(self, spec):
        """(slot feed, index of the fetch that is its next value) for
        each state pair of a decode or chunk spec."""
        return [(feed_n, self.fetch_names.index(fetch_n))
                for feed_n, fetch_n in spec['state']]

    def _make_decode_multi(self, spec):
        """The K-AUTOREGRESSIVE-steps-per-dispatch function (ISSUE 7):
        lax.scan over K greedy-decode steps of the step program, the
        whole slot batch at once.  Unlike _make_eval_multi, each step's
        INPUT comes from the previous step's OUTPUT — the scan carry
        holds the per-slot decoder state (KV/hidden — the ``state``
        pairs), the current token, a per-slot alive mask, and a
        per-slot remaining-step budget.  Stop conditions (EOS emitted /
        budget exhausted) are masked INSIDE the scan: a finished slot's
        state and token FREEZE (jnp.where on the alive mask), so dead
        and free slots ride along at zero semantic cost while live ones
        keep decoding — the in-jit half of continuous batching.
        Emits (carry', tokens [K, S], alive_in [K, S]): a token counts
        for a slot exactly when the slot was alive ENTERING the step
        (the EOS itself is emitted, then the slot goes dead) — the same
        accounting as a host-driven greedy loop that appends argmax
        until it appends end_id or exhausts max_len."""
        import jax
        import jax.numpy as jnp
        fn = self._fn
        rw_keys = list(self.state_rw)
        token_name = spec['token']
        end_id = int(spec['end_id'])
        updates = self._slot_updates(spec)

        def paddle_tpu_decode_scan(state_ro, feeds, carry, rng, n):
            def body(c, i):
                s, slots, token = c['state'], c['slots'], c['token']
                alive, remaining = c['alive'], c['remaining']
                merged = dict(feeds)
                merged.update(slots)
                merged[token_name] = token
                new_state, fetches = fn(s, state_ro, merged,
                                        jax.random.fold_in(rng, i))
                logits = fetches[0]
                nxt = jnp.argmax(
                    logits.reshape((logits.shape[0], -1)),
                    axis=-1).astype(token.dtype)
                emit = jnp.where(alive, nxt,
                                 jnp.asarray(end_id, token.dtype))
                rem = remaining - alive.astype(remaining.dtype)
                live = alive & (emit != end_id) & (rem > 0)
                new_slots = _merge_slots(slots, fetches, updates, alive)
                new_token = jnp.where(alive[:, None], emit[:, None],
                                      token)
                c2 = {'state': {k: new_state.get(k, s[k])
                                for k in rw_keys},
                      'slots': new_slots, 'token': new_token,
                      'alive': live, 'remaining': rem}
                return c2, (emit, alive)

            final, (toks, alive_in) = jax.lax.scan(
                body, carry, jnp.arange(n))
            return final, toks, alive_in

        return paddle_tpu_decode_scan

    def _make_chunk_prefill(self, spec):
        """The C-tokens-per-dispatch PREFILL advance (ISSUE 14): run
        the chunk program over the WHOLE slot batch once — each
        PREFILLING slot consumes its next block of prompt tokens and
        its state slabs advance IN PLACE on the carry (the same donated
        carry the decode scans chain on, so chunk dispatches interleave
        with decode dispatches with no host round trip).  Slots not in
        the chunk (``aux['active']`` False: decoding, free, or already
        past their prompt) keep their slabs bitwise.  Slots whose
        prompt ENDS inside this block (``aux['finish']``) transition to
        decoding on the same dispatch: token <- start_id, alive <-
        True, remaining <- their step budget — the first decode scan
        dispatched after this chunk picks them up at a step boundary.
        Returns (carry', alive') where alive' is a separate small
        output the engine harvests to time the chunk (and surface a
        deferred device error) without touching the chained carry.
        The chunk width is part of the token feed's traced SHAPE, so a
        fixed ``prefill_chunk`` compiles exactly once (the ragged final
        block pads to the same width)."""
        import jax.numpy as jnp
        fn = self._fn
        rw_keys = list(self.state_rw)
        start_id = int(spec['start_id'])
        updates = self._slot_updates(spec)

        def paddle_tpu_chunk_prefill(state_ro, feeds, carry, aux, rng):
            s, slots = carry['state'], carry['slots']
            merged = dict(feeds)
            merged.update(slots)
            new_state, fetches = fn(s, state_ro, merged, rng)
            new_slots = _merge_slots(slots, fetches, updates,
                                     aux['active'])
            fin = aux['finish']
            token = jnp.where(fin[:, None],
                              jnp.asarray(start_id, carry['token'].dtype),
                              carry['token'])
            alive = jnp.logical_or(carry['alive'], fin)
            remaining = jnp.where(fin, aux['budget'].astype(
                carry['remaining'].dtype), carry['remaining'])
            c2 = {'state': {k: new_state.get(k, s[k]) for k in rw_keys},
                  'slots': new_slots, 'token': token, 'alive': alive,
                  'remaining': remaining}
            return c2, alive

        return paddle_tpu_chunk_prefill


# What differs between the lanes round the step program, for the one
# mechanism of _CompiledBlock (run_lane / _lane_jit / note_compile /
# _lane_shardings) and the executors' dispatch halves:
#   name     'train' | 'eval' | 'decode' | 'chunk'
#   make     builds the traced body from the block (and the spec)
#   carried  False: the scanned lanes, body(state_rw, state_ro, feeds,
#            scanned, rng, n) over a [K, ...] feed block; True: the
#            carried lanes, body(state_ro, feeds, carry[, aux], rng[, n])
#            over the slot carry, RW state inside it
#   counted  the body takes a static step count n (all but chunk)
#   kind     the lane's name in cost_report(); 'run_' + kind is the
#            public entry it serves, in messages
#   advice   how the host-op rejection ends
_Lane = collections.namedtuple(
    '_Lane', 'name make carried counted kind advice')
_LANES = {lane.name: lane for lane in (
    _Lane('train', _CompiledBlock._make_multi, False, True,
          'multi', 'loop — use run() per step'),
    _Lane('eval', _CompiledBlock._make_eval_multi, False, True,
          'eval_multi', 'loop — use run() per step'),
    _Lane('decode', _CompiledBlock._make_decode_multi, True, True,
          'decode_multi',
          'loop — decode-step programs must be pure compute'),
    _Lane('chunk', _CompiledBlock._make_chunk_prefill, True, False,
          'chunk_prefill',
          'advance — chunk programs must be pure compute'))}


def _lane_names(lane, feeds, operand):
    """The name structure a lane is called with: its constant feeds and
    its scanned feeds or carried slots."""
    return (tuple(sorted(feeds)),
            tuple(sorted(operand['slots'] if lane.carried else operand)))


def _lane_jit_again(make_block, batch_feed_names, lane, names, spec):
    """A lane's jit from a block built again (``_CompiledBlock._again``):
    what ``fluid.trace.executable_record`` lowers after the executor that
    ran the lane, and with it every program it had loaded, is gone.  The
    body is traced again."""
    block = make_block()
    block._batch_feed_names = batch_feed_names
    return block._build_lane_jit(lane, names, spec)


def _merge_slots(slots, fetches, updates, rows):
    """The slot state after a step of a carried lane: slots under the
    [S] mask ``rows`` take the step's fetch, the others keep theirs."""
    import jax.numpy as jnp
    new_slots = dict(slots)
    for feed_n, fi in updates:
        upd = fetches[fi]
        keep = rows.reshape((-1, ) + (1, ) * (max(upd.ndim, 1) - 1))
        new_slots[feed_n] = jnp.where(keep, upd, slots[feed_n])
    return new_slots


# ---- the lanes' front halves, one body for both executors ---------------
#
# Executor and ParallelExecutor take these as their private
# ``_dispatch_*`` methods: one signature per lane (``program=`` /
# ``scope=`` default to the executor's own; a ParallelExecutor refuses
# any other), so FeedPipeline and the serving engine call either without
# knowing which they hold.  What they ask of an executor is grouped
# below its ``_dispatch_*`` lines.  Every lane counts AFTER its launch,
# so a failed call (steps < 1, a shape error inside jit) can't skew the
# counters.


def _trace_id():
    return getattr(_trace.current(), 'trace_id', None)


def _count_lane(exe, compiled, lane, static, sig, steps):
    if compiled.note_compile(lane, static, sig):
        exe.compile_count += 1
    exe._count_dispatch(int(steps))


def _reader_feed_list(exe, what, program, reader, feed, feed_list, steps,
                      require_steps=False):
    """A scanned lane's ``reader=`` mode: up to ``steps`` DISTINCT fresh
    minibatches drain from the program's py_reader onto the feed_list
    path.  Without ``reader=`` the given feed_list comes back — and a
    reader-fed program is refused: the plain-feed paths would pop ONE
    reader minibatch at the resolve and silently run K steps on it."""
    if reader is None:
        _reject_reader_fed(program, exe._label + what)
        return feed_list
    from .dataflow import check_reader_args, drain_reader_feed_list
    check_reader_args(what, feed, feed_list, steps,
                      require_steps=require_steps)
    return drain_reader_feed_list(program, reader, steps,
                                  getattr(exe, 'place', None))


def stage_embed_caches(caches, scope, what, lots, steps):
    """run_multi's two-tier embedding stores (ISSUE 12): remap each
    cache's id feeds in ``lots`` to slab slots IN PLACE — before a
    signature or any padding sees them (a padded tail then replicates
    remapped rows, so every slot stays valid) — and return the
    [(cache, exchange)] to apply right before the dispatch.  EVERY
    cache's scope binding is checked before ANY cache stages: a
    mis-bound cache must not leave another with a staged exchange (and
    skewed hit-rate metrics) for a block that never dispatches."""
    for cache in (caches or ()):
        cache.check_scope(scope, what)
    return [(cache, cache.stage_feed_list(lots, steps=steps))
            for cache in (caches or ())]


def prepare_scanned_lots(what, feed_list, pad=None, stage=None):
    """Normalize a feed_list for a scanned lane: one prepared feed dict
    per iteration, sequence extents that disagree re-quantized onto the
    seq-len ladder, uniform across steps (checked).  ``pad`` is the
    executor's rule for ragged lots (``_pad_ragged``: to the dp extent;
    1 on one device): lots that are ragged or disagree in rows pad to
    one target with masked samples — a size probe only, no lot is
    padded or pulled off the device unless something is ragged; None
    (Executor.run_multi) leaves them to fail the uniformity check.
    ``stage(per_step, k)`` runs on the prepared lots before any padding
    (stage_embed_caches).  Returns (per_step, reals, target,
    batch_feed_names) as normalize_ragged_feed_list does."""
    if not feed_list:
        raise ValueError('%s: feed_list is empty' % what)
    per_step = [prepare_feed_arrays(dict(f)) for f in feed_list]
    check_feed_list_names(per_step, what)
    if stage is not None:
        stage(per_step, len(per_step))
    normalize_trailing_feed_list(per_step)
    reals = target = batch_feed_names = None
    if pad is not None:
        from .parallel_executor import normalize_ragged_feed_list
        per_step, reals, target, batch_feed_names = \
            normalize_ragged_feed_list(per_step, pad)
    check_feed_list_uniform(per_step)
    return per_step, reals, target, batch_feed_names


def place_scanned(compiled, per_step):
    """The uniform lots as ONE scanned [K, ...] block per feed, on the
    block's device or laid out over its mesh."""
    import jax
    return {n: jax.device_put(stack_steps([fa[n] for fa in per_step]),
                              compiled.scanned_sharding(n))
            for n in per_step[0]}


def _dispatch_multi_scanned(self, fetch_list, sig_feed, scanned, steps,
                            batch_feed_names=None, program=None,
                            scope=None):
    """Async front half of a scanned run_multi dispatch (the
    FeedPipeline drives this): resolve + compile keyed on ``sig_feed``
    (the first prepared per-step feed dict), dispatch ONE pre-staged
    [K, ...] scanned block (dp-sharded under a mesh), and return the raw
    device fetches with NO host sync — so the host can stage block N+1
    (and deliver block N-1) while N still computes.  State write-back
    to the scope happens inside (async device arrays).
    batch_feed_names: the padding pass's pre-pad provenance (which
    feeds are batch-led), recorded into the compile exactly like
    run_multi's feed_list path."""
    kind = type(self).__name__
    with _trace.span('paddle_tpu/executor/dispatch', steps=int(steps),
                     executor=kind):
        program, scope = self._bind(program, scope)
        program, scope, _, compiled = self._resolve_block(
            program, scope, fetch_list, sig_feed, batch_feed_names)
        _trace.flight_recorder.record(
            'multi_dispatch', executor=kind, steps=int(steps),
            fetch_names=list(compiled.fetch_names), trace_id=_trace_id())
        fetches = compiled.run_lane('train', scope, {},
                                    self._next_rng(program), scanned,
                                    steps=int(steps))
    _count_lane(self, compiled, 'train', steps, scanned, steps)
    return fetches, compiled


def _dispatch_eval_multi(self, fetch_list, feed=None, steps=None,
                         feed_list=None, reader=None, program=None,
                         scope=None):
    """Async front half of run_eval_multi: resolve + compile, pad
    ragged lots to one shape bucket (under a mesh: to the dp extent,
    with masked samples exactly as run_multi's), dispatch ONE scanned
    eval, and return ``(stacked_fetches, reals, target, compiled, k)``
    with NO host sync — the serving engine drives this directly so the
    host can feed dispatch N+1 (and trim/deliver N-1) while N still
    computes on device.  ``reals`` is the per-step real row count (None
    when nothing was padded), ``target`` the padded rows.  ``reader=``
    drains up to ``steps`` DISTINCT eval minibatches from the program's
    py_reader queue onto the feed_list path (the eval twin of
    run_multi's reader mode, same drain contract: bucket-boundary split
    pushes the ragged tail back, EOF raises)."""
    program, scope = self._bind(program, scope)
    feed_list = _reader_feed_list(self, 'run_eval_multi', program, reader,
                                  feed, feed_list, steps, require_steps=True)
    per_step = None
    if feed_list is not None:
        if feed is not None:
            raise ValueError('run_eval_multi: pass feed OR feed_list')
        per_step, reals, target, batch_feed_names = prepare_scanned_lots(
            'run_eval_multi', feed_list, self._pad_ragged)
        steps, feed = len(per_step), per_step[0]
    elif steps is None:
        raise ValueError('run_eval_multi: pass steps= with feed=')
    else:
        rpt = {}
        feed, real, target = self._pad_ragged(
            prepare_feed_arrays(dict(feed if feed is not None else {})),
            report=rpt)
        reals = [real] * int(steps) if real != target else None
        batch_feed_names = rpt.get('batch_names')
    steps = int(steps)
    # the reader path already drained its batches above (the resolve
    # pops none), and every other path rejects reader-fed programs
    program, scope, feed_arrays, compiled = self._resolve_block(
        program, scope, fetch_list, feed, batch_feed_names)
    scanned = None
    if per_step is not None:
        scanned = place_scanned(compiled, per_step)
        feed_arrays = {}  # every feed name arrives via the scan
    _trace.flight_recorder.record(
        'eval_dispatch', executor=type(self).__name__, steps=steps,
        fetch_names=list(compiled.fetch_names), trace_id=_trace_id())
    stacked = compiled.run_lane('eval', scope, feed_arrays,
                                self._next_rng(program), scanned,
                                steps=steps)
    _count_lane(self, compiled, 'eval', steps, scanned, steps)
    return stacked, reals, target, compiled, steps


def _canonical_slot_carry(exe, carry, what):
    """The carried lanes' carry on jax's dtype rules, and its slot
    count, which must divide over the executor's dp extent (the slot
    dim is sharded over it; the engine sizes its cache so)."""
    carry = canonical_decode_carry(carry)
    slots = int(np.shape(carry['token'])[0])
    if slots % exe._dp_extent() != 0:
        raise ValueError(
            '%s: %d slots do not divide over the dp extent %d — size '
            'the slot batch to a multiple of the mesh'
            % (what, slots, exe._dp_extent()))
    return carry, slots


def _dispatch_decode_multi(self, feed=None, carry=None, steps=None,
                           decode=None, program=None, scope=None):
    """Async front half of run_decode_multi (ISSUE 9 — the engine's
    PIPELINED decode lane drives this, the decode twin of
    _dispatch_multi_scanned): resolve + compile the K-step decode
    scan and dispatch it against a carry whose leaves may be
    DEVICE-RESIDENT — in particular the untouched (donated) output
    carry of the PREVIOUS decode dispatch, so scan N+1 chains
    straight onto scan N with no token block ever materializing on
    host between them.  Returns (carry', tokens [K, S], alive_in
    [K, S], compiled) with NO host sync: all three values are async
    device arrays the caller harvests when it chooses (the chained
    lane harvests scan N's tokens while N+1 computes).  Device
    leaves pass through signature/canonicalization untouched
    (prepare_feed_arrays / canonical_decode_carry are identity on
    jax.Arrays), so a chained dispatch costs the host only the
    cache lookup."""
    program, scope = self._bind(program, scope)
    _reject_reader_fed(program, self._label + 'run_decode_multi')
    if carry is None or steps is None or decode is None:
        raise ValueError('run_decode_multi: carry=, steps= and '
                         'decode= are required')
    steps = int(steps)
    spec = normalize_decode_spec(decode)
    check_decode_carry(carry, spec, 'run_decode_multi')
    carry, slots = _canonical_slot_carry(self, carry, 'run_decode_multi')
    sig_feed = dict(feed or {})
    sig_feed[spec['token']] = carry['token']
    sig_feed.update(carry['slots'])
    program, scope, feed_arrays, compiled = self._resolve_block(
        program, scope, [spec['logits']] + [f for _, f in spec['state']],
        sig_feed)
    const = {n: v for n, v in feed_arrays.items()
             if n not in carry['slots'] and n != spec['token']}
    _trace.flight_recorder.record(
        'decode_dispatch', executor=type(self).__name__, steps=steps,
        slots=slots, trace_id=_trace_id())
    out = compiled.run_lane('decode', scope, const,
                            self._next_rng(program), carry, steps=steps,
                            spec=spec)
    carry_sig = dict(carry['slots'])
    carry_sig[spec['token']] = carry['token']
    _count_lane(self, compiled, 'decode', steps, carry_sig, steps)
    return out + (compiled, )


def _dispatch_chunk_prefill(self, feed=None, carry=None, aux=None,
                            chunk=None, program=None, scope=None):
    """Async front half of chunked prefill (ISSUE 14 — the engine's
    chunk lane drives this, the chunk twin of _dispatch_decode_multi):
    resolve + compile the C-token prefill advance of a CHUNK program
    and dispatch it against a carry whose leaves may be DEVICE-RESIDENT
    (the chained decode carry), returning (carry', alive', compiled)
    with NO host sync.  ``feed`` carries the [S, C, 1] token block, its
    @SEQLEN companion, and the optional per-slot length feed; ``aux``
    the active/finish/budget slot masks."""
    program, scope = self._bind(program, scope)
    _reject_reader_fed(program, self._label + 'run_chunk_prefill')
    if carry is None or aux is None or chunk is None:
        raise ValueError('run_chunk_prefill: carry=, aux= and '
                         'chunk= are required')
    spec = normalize_chunk_spec(chunk)
    carry, slots = _canonical_slot_carry(self, carry, 'run_chunk_prefill')
    check_chunk_aux(aux, 'run_chunk_prefill', slots=slots)
    sig_feed = dict(feed or {})
    sig_feed.update(carry['slots'])
    program, scope, feed_arrays, compiled = self._resolve_block(
        program, scope, [f for _, f in spec['state']], sig_feed)
    block_feed = {n: v for n, v in feed_arrays.items()
                  if n not in carry['slots']}
    # the chunk width is the lane's static shape knob, like steps for
    # the scans
    width = int(np.shape(feed_arrays[spec['token']])[1])
    _trace.flight_recorder.record(
        'chunk_dispatch', executor=type(self).__name__, width=width,
        slots=slots, trace_id=_trace_id())
    out = compiled.run_lane('chunk', scope, block_feed,
                            self._next_rng(program), carry, spec=spec,
                            aux=aux)
    carry_sig = dict(carry['slots'])
    carry_sig[spec['token']] = feed_arrays[spec['token']]
    _count_lane(self, compiled, 'chunk', width, carry_sig, 0)
    return out + (compiled, )


class Executor(object):
    """Program runner (reference executor.py:256 / executor.cc:125)."""

    _CACHE_MAX = 64  # LRU bound; each entry pins its Program (stable ids)

    def __init__(self, place=None):
        self.place = place if place is not None else core.CPUPlace()
        self._cache = collections.OrderedDict()
        self._rng = None
        self._closed = False
        # the executor's OWN cache misses: one per executable it builds
        # (a _CompiledBlock, or a new (steps, shapes) of a scan lane);
        # tests pin bounds on this.  It foresees retraces, it does not
        # see them: what JAX really traced, lowered, compiled or loaded,
        # on any thread, is trace.compile_log()
        self.compile_count = 0
        _trace.compile_log()  # listeners on before the first compile
        # the compile cache and RNG stream are shared mutable state: the
        # reference predictor's thread contract
        # (paddle_inference_api.h:90 — Clone() + concurrent Run()) means
        # N threads may resolve through ONE executor concurrently, and
        # an unguarded OrderedDict get/move_to_end/popitem interleaving
        # corrupts the LRU (or drops a live entry mid-resolve)
        self._cache_lock = threading.RLock()

    def _next_rng(self, program):
        # Keys are built HOST-side as raw uint32[2] threefry keys — a
        # device-side jax.random.split would dispatch a separate tiny
        # computation every step, serializing ~12ms of runtime round trip
        # against the training step.  A numpy key rides the jit call's own
        # argument transfer instead.
        if flags.FLAGS.cpu_deterministic or flags.FLAGS.cudnn_deterministic:
            # deterministic mode (reference FLAGS_cpu_deterministic,
            # build_strategy.h:41): key depends only on (program seed,
            # per-program step index), so streams are independent of what
            # else this Executor has run.  Weakref keys make entries die
            # with their program — no unbounded growth, no recycled-id
            # aliasing
            import weakref
            with self._cache_lock:
                if not hasattr(self, '_det_steps'):
                    self._det_steps = {}
                key = weakref.ref(program,
                                  lambda r: self._det_steps.pop(r, None))
                step = self._det_steps.get(key, 0)
                self._det_steps[key] = step + 1
            return np.array([(program.random_seed or 0) & 0xffffffff, step],
                            np.uint32)
        with self._cache_lock:
            # concurrent predictors (Clone + threaded Run) share this
            # stream: the counter bump must be atomic or two threads
            # can mint one key twice
            if self._rng is None:
                # mask to the key word width: PRNGKey accepted 64-bit
                # and negative seeds, so keep accepting them
                self._rng_seed = int(program.random_seed or 0) & 0xffffffff
                self._rng = 0
            self._rng += 1
            return np.array([self._rng_seed, self._rng], np.uint32)

    def as_lodtensor(self, data):
        return core.LoDTensor(np.asarray(data))

    def _pin_cache_lifetime(self, obj):
        """Purge this executor's cache entries keyed by id(obj) when obj is
        garbage-collected, so recycled ids can't alias stale compiles."""
        import weakref
        attr = '_ptpu_cache_final_%d' % id(self)
        if getattr(obj, attr, None) is not None:
            return
        cache_ref = weakref.ref(self._cache)
        self_ref = weakref.ref(self)
        oid = id(obj)

        def _purge(cache_ref=cache_ref, self_ref=self_ref, oid=oid):
            cache = cache_ref()
            if cache is not None:
                # GC can fire this on any thread: exclude a concurrent
                # _resolve_and_compile mid-LRU-update (the executor —
                # and with it the lock — outlives its cache entries)
                owner = self_ref()
                lock = owner._cache_lock if owner is not None else None
                import contextlib
                with lock if lock is not None \
                        else contextlib.nullcontext():
                    for k in [k for k in list(cache)
                              if oid in (k[0], k[5])]:
                        cache.pop(k, None)

        try:
            setattr(obj, attr, weakref.finalize(obj, _purge))
        except AttributeError:
            pass  # object without a __dict__; fall back to LRU semantics

    def _resolve_and_compile(self, program, feed, fetch_list, scope,
                             pop_readers=True):
        with _trace.span('paddle_tpu/executor/resolve'):
            return self._lookup_or_build(program, feed, fetch_list, scope,
                                         pop_readers)

    def _lookup_or_build(self, program, feed, fetch_list, scope,
                         pop_readers):
        """Shared front half of run()/memory_analysis(): normalize the
        arguments, prepare/validate feeds, and resolve (or build) the
        cached executable.  ``pop_readers=False`` for analysis paths
        that never execute the program — consuming a py_reader batch
        there would silently drop a minibatch from training."""
        if self._closed:
            raise RuntimeError('Attempted to use a closed Executor')
        program = program if program is not None else \
            default_main_program()
        scope = scope if scope is not None else _current_scope()
        feed = dict(feed if feed is not None else {})
        fetch_list = fetch_list if fetch_list is not None else []
        if isinstance(fetch_list, (Variable, str)):
            fetch_list = [fetch_list]
        fetch_names = [_var_name(f) for f in fetch_list]
        from .layers import io as layers_io
        layers_io.note_executor_place(self.place)
        if pop_readers:
            _pop_readers_into_feed(program, feed, self.place)
        feed_arrays = prepare_feed_arrays(feed)
        validate_feed(program, feed_arrays)
        sig = feed_signature(feed_arrays)
        key = (id(program), program._version, tuple(fetch_names), sig,
               self.place, id(scope), registry.amp_enabled())
        # id()-keyed entries are purged when the keyed object dies, so a
        # recycled id can never alias a stale compile (the LRU alone
        # can't guarantee this: evicting one entry may unpin a program
        # whose id recurs while sibling entries survive)
        self._pin_cache_lifetime(program)
        self._pin_cache_lifetime(scope)
        with self._cache_lock:
            compiled = self._cache.get(key)
            if compiled is None:
                self.compile_count += 1
                compiled = _CompiledBlock(program, 0,
                                          [n for n, _, _ in sig],
                                          fetch_names, self.place, scope)
                self._cache[key] = compiled
                if len(self._cache) > self._CACHE_MAX:
                    self._cache.popitem(last=False)
            else:
                self._cache.move_to_end(key)
        return program, scope, feed_arrays, compiled

    def memory_analysis(self, program=None, feed=None, fetch_list=None,
                        scope=None):
        """XLA buffer-assignment stats for the compiled program (the
        measured counterpart of the reference memory_optimize's print
        log): returns the jax CompiledMemoryStats — in particular
        ``temp_size_in_bytes``, the peak intermediate-buffer footprint
        after XLA's liveness-driven reuse.  Feeds must be shaped like a
        real run's (they key the compile)."""
        import jax
        program = program if program is not None else \
            default_main_program()
        if any(op.type == 'read' for op in program.block(0).ops):
            raise RuntimeError(
                'memory_analysis: the program is reader-fed; popping a '
                'py_reader batch here would silently drop a minibatch '
                'from training — pass representative arrays via feed= '
                'on a reader-free clone instead')
        program, scope, feed_arrays, compiled = self._resolve_and_compile(
            program, feed, fetch_list, scope, pop_readers=False)
        if any(_is_host_op(op) for op in compiled.ops):
            raise RuntimeError(
                'memory_analysis: the program contains host ops '
                '(%s) and runs on the eager path, which has no single '
                'compiled executable — remove them or analyse the '
                'compute-only portion' % sorted(
                    {op.type for op in compiled.ops
                     if _is_host_op(op)}))
        state_rw, state_ro, feeds = compiled._materialize_args(
            scope, feed_arrays)
        return _trace.aot_compile(compiled._jit, (
            state_rw, state_ro, feeds,
            jax.random.PRNGKey(0))).memory_analysis()

    def run(self,
            program=None,
            feed=None,
            fetch_list=None,
            feed_var_name='feed',
            fetch_var_name='fetch',
            scope=None,
            return_numpy=True,
            use_program_cache=False):
        program, scope, feed_arrays, compiled = self._resolve_and_compile(
            program, feed, fetch_list, scope)

        eager = any(_is_host_op(op) for op in compiled.ops)
        rng = self._next_rng(program)
        bench = flags.FLAGS.benchmark
        if bench:
            import time as _time
            t0 = _time.perf_counter()
        # one timeline slice per run (the reference profiler records
        # per-op RecordEvents; whole-block XLA execution makes the run
        # the natural host-side unit — device-side op slices come from
        # the xplane capture)
        with _trace.span(
                'paddle_tpu/executor/run',
                event=None if bench else 'executor_run/block0[%s]' % (
                    ','.join(compiled.fetch_names) or 'nofetch')) as sp:
            fetches = compiled.run(scope, feed_arrays, rng, eager=eager)
            if sp.recording or bench:
                # a recorded slice must cover device time, not just the
                # async dispatch, so sync inside
                _block_until_ready(fetches)
        if bench:
            import logging
            logging.getLogger('paddle_tpu').info(
                'FLAGS_benchmark: run %.3f ms, %d fetches',
                (_time.perf_counter() - t0) * 1e3, len(fetches))
        return self._convert_fetches(fetches, return_numpy)

    def run_multi(self,
                  program=None,
                  feed=None,
                  fetch_list=None,
                  steps=1,
                  scope=None,
                  return_numpy=True,
                  feed_list=None,
                  reader=None,
                  embed_caches=None):
        """Run ``steps`` iterations of the program as ONE device
        dispatch.  Returns the LAST iteration's fetches.  For small
        steps — e.g. the stacked-LSTM benchmark — the per-dispatch host
        cost is paid once per K steps, so the wall clock measures the
        chip.  Training state updates
        persist to the scope exactly as ``steps`` sequential run()
        calls would.

        feed: one batch reused every iteration (fori_loop), OR
        feed_list: a list of per-iteration batches (same shapes/LoD
        bucket) scanned on device — a mini-epoch in one dispatch;
        ``steps`` is then len(feed_list), OR
        reader: the program's py_reader — ``steps`` DISTINCT fresh
        minibatches drain from its queue and scan as one dispatch
        (the reference per-iteration pull, executor.cc:321-339); a
        stream ending mid-block trains on the shorter tail, an
        exhausted reader raises core.EOFException exactly like run().
        Overlapped staging across dispatches is fluid.FeedPipeline.

        embed_caches: two-tier embedding stores (ISSUE 12,
        ``distributed.CachedEmbeddingTable``) whose tables this program
        looks up: each cache's id feeds REMAP to slab slots on host,
        and the block's row exchange (dirty evictions out to the host
        master, fetched misses in) applies right before the dispatch.
        Synchronous form — the overlapped prefetch is
        FeedPipeline(embed_caches=)."""
        program, scope = self._bind(program, scope)
        feed_list = _reader_feed_list(self, 'run_multi', program, reader,
                                      feed, feed_list, steps)
        if feed_list is not None:
            if feed is not None:
                raise ValueError('run_multi: pass feed OR feed_list')
            per_step = prepare_scanned_lots('run_multi', feed_list)[0]
            # batch 0 keys the compile signature (already prepared:
            # the resolve passes arrays through and does not re-pad it)
            steps, feed = len(per_step), per_step[0]
        else:
            # the constant-batch (fori_loop) form: one id set reused
            # every iteration — the caches remap it once
            feed = prepare_feed_arrays(dict(feed if feed is not None
                                            else {}))
        exchanges = stage_embed_caches(
            embed_caches, scope if scope is not None else _current_scope(),
            'run_multi', per_step if feed_list is not None else [feed],
            steps)
        program, scope, feed_arrays, compiled = self._resolve_block(
            program, scope, fetch_list, feed)
        scanned = None
        if feed_list is not None:
            scanned = place_scanned(compiled, per_step)
            feed_arrays = {}  # every feed name arrives via the scan
        rng = self._next_rng(program)
        for cache, ex in exchanges:
            # the block's row exchange lands right before its dispatch
            # (an unfinished host fetch is a counted prefetch_stall)
            cache.apply(ex)
        with _trace.span(
                'paddle_tpu/executor/run_multi', steps=int(steps),
                event='executor_run_multi/block0[x%d]' % int(steps)) as sp:
            fetches = compiled.run_lane('train', scope, feed_arrays, rng,
                                        scanned, steps=steps)
            if sp.recording:
                _block_until_ready(fetches)
        # each distinct `steps` value is its own XLA compile (static
        # arg), and so is each scanned-feed SHAPE signature (the jit
        # retraces per pytree structure): recompile-bound tests observe
        # real XLA retraces, not just distinct step counts
        _count_lane(self, compiled, 'train', steps, scanned, steps)
        return self._convert_fetches(fetches, return_numpy)

    # the lanes' async front halves are the module's, shared with
    # ParallelExecutor; below, what they ask of an executor
    _dispatch_multi_scanned = _dispatch_multi_scanned
    _dispatch_eval_multi = _dispatch_eval_multi
    _dispatch_decode_multi = _dispatch_decode_multi
    _dispatch_chunk_prefill = _dispatch_chunk_prefill
    _label = ''  # before the entry's name in the reader-fed rejection

    def _bind(self, program, scope):
        return (program if program is not None
                else default_main_program()), scope

    def _resolve_block(self, program, scope, fetch_list, feed,
                       batch_feed_names=None):
        """A lane's resolve: no reader is popped (the lanes drain
        theirs themselves, or reject reader-fed programs)."""
        program, scope, feed_arrays, compiled = self._resolve_and_compile(
            program, feed, fetch_list, scope, pop_readers=False)
        if batch_feed_names is not None and \
                getattr(compiled, '_batch_feed_names', None) is None:
            # deterministic in the feed signature (which keys the cache
            # entry), so setting it once at first resolve is consistent
            # for every later hit — same contract as ParallelExecutor
            compiled._batch_feed_names = frozenset(batch_feed_names)
        return program, scope, feed_arrays, compiled

    def _pad_ragged(self, feed_arrays, **kw):
        from .parallel_executor import pad_ragged_batch
        return pad_ragged_batch(feed_arrays, 1, **kw)

    def _dp_extent(self):
        return 1

    def _count_dispatch(self, steps):
        pass  # ParallelExecutor counts dispatches; this class never has

    def run_eval_multi(self,
                       program=None,
                       feed=None,
                       fetch_list=None,
                       steps=None,
                       scope=None,
                       return_numpy=True,
                       feed_list=None,
                       reader=None):
        """Run ``steps`` EVAL iterations of the program as ONE device
        dispatch and return EVERY iteration's fetches — the inference
        analog of run_multi (which surfaces only the last step), closing
        the dispatch-tax ledger's last row.  Returns one entry per
        fetch: a [K, ...]-stacked array, except batch-led fetches over
        ragged lots of UNEQUAL real row counts, which come back as a
        list of K per-step arrays trimmed to each lot's real rows.

        feed: one batch evaluated ``steps`` times (the bench's
        device-true timing form), OR feed_list: per-iteration lots
        scanned on device (the serving engine's form; ``steps`` is then
        len(feed_list)), OR reader: the program's py_reader — up to
        ``steps`` DISTINCT fresh eval minibatches drain from its queue
        and scan as one dispatch (the eval sweep's symmetric mode to
        run_multi's reader=; a stream ending mid-block evaluates the
        shorter tail, a shape-bucket boundary splits the block with the
        tail pushed back, an exhausted reader raises core.EOFException
        exactly like run()).  Ragged lots are padded to one shape
        bucket with masked replicated rows and trimmed on the way out."""
        with _trace.span('paddle_tpu/executor/run_eval_multi',
                         event='executor_run_eval_multi/block0'):
            stacked, reals, target, compiled, k = self._dispatch_eval_multi(
                fetch_list, feed=feed, steps=steps, feed_list=feed_list,
                reader=reader, program=program, scope=scope)
            # np.asarray in the conversion drains the device
            return convert_eval_fetches(stacked, reals, target, compiled,
                                        k, return_numpy)

    def run_decode_multi(self, program=None, feed=None, carry=None,
                         steps=None, decode=None, scope=None):
        """Run ``steps`` AUTOREGRESSIVE greedy-decode iterations of a
        STEP program as ONE device dispatch over a whole slot batch
        (ISSUE 7 — the generation sibling of run_eval_multi, and the
        serving engine's decode-lane primitive).  Each iteration feeds
        the previous iteration's outputs back in: ``decode`` names the
        token feed, the logits fetch (argmax = next token), the
        (state feed, state fetch) pairs threading KV/hidden state
        through the scan carry, optional read-only ``context`` slot
        feeds, and ``end_id``; per-slot stop conditions (EOS emitted /
        ``carry['remaining']`` exhausted) are masked INSIDE the scan —
        finished slots freeze, live ones keep decoding.

        carry: {'slots': {name: [S, ...]}, 'token': [S, 1] int,
        'alive': [S] bool, 'remaining': [S] int32} — the slot-resident
        decode state (on device it is DONATED and updated in place).
        feed: feeds held constant across iterations (rarely needed).
        Returns (carry', tokens [K, S], alive_in [K, S]): tokens[i, s]
        counts for slot s exactly when alive_in[i, s] — token-identical
        to a per-slot host-driven greedy loop over the same program."""
        carry_out, toks, alive_in, _ = self._dispatch_decode_multi(
            feed=feed, carry=carry, steps=steps, decode=decode,
            program=program, scope=scope)
        return carry_out, toks, alive_in

    def _convert_fetches(self, fetches, return_numpy):
        def convert(f):
            from ..ops.sparse import SparseRows
            if isinstance(f, core.SelectedRows):
                return f
            if isinstance(f, SparseRows):
                sr = core.SelectedRows(
                    rows=np.asarray(f.rows).tolist(), height=f.height)
                sr.get_tensor().set(np.asarray(f.values))
                return sr
            return np.asarray(f) if return_numpy else core.LoDTensor(
                np.asarray(f))

        return [convert(f) for f in fetches]

    def cost_report(self):
        """Per-executable cost registry (ISSUE 6): every cached
        executable's XLA cost/memory analysis captured under
        FLAGS_cost_accounting — the ground truth behind achieved-MFU
        serving metrics and bench.py's cost-derived MFU."""
        with self._cache_lock:
            blocks = list(self._cache.values())
        return collect_cost_report(blocks)

    def close(self):
        """Reference Executor.Close() notifies pservers (executor.h:51); here
        it just drops the compile cache."""
        self._cache = {}
        self._closed = True
