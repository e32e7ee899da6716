"""Op lowering registry: OpDesc -> JAX/XLA.

The reference dispatches each op to a hand-written CPU/CUDA kernel at runtime
(paddle/fluid/framework/operator.cc:657-714, registered via
REGISTER_OP_CPU_KERNEL / REGISTER_OP_CUDA_KERNEL, op_registry.h:214-217).
Here every op type instead registers a *lowering*: a function that, while the
enclosing block is being traced for XLA compilation, reads its input values
from the tracing environment and writes its outputs.  The whole block becomes
ONE fused XLA computation (the TPU-first swap for the per-op interpreter hot
loop, executor.cc:332-339).

Gradients: the reference synthesizes grad OpDescs with per-op C++
GradOpDescMakers (framework/grad_op_desc_maker.h:34).  We synthesize the same
grad-op graph structure (backward.py) but lower ``<op>_grad`` generically via
``jax.vjp`` of the forward lowering — XLA's CSE merges the recomputed forward
with the original, so this costs nothing inside one compiled block.  Ops whose
forward draws randomness (dropout) register explicit grad lowerings.
"""

import numpy as np

_LOWERINGS = {}
_GRAD_LOWERINGS = {}
# host ops run outside XLA on concrete values (save/load/print/readers);
# impl signature: fn(ctx, op, scope) with ctx.env holding concrete arrays
_HOST_OPS = {}


def register_host_op(op_type):
    def deco(fn):
        _HOST_OPS[op_type] = fn
        return fn

    return deco


def get_host_op(op_type):
    return _HOST_OPS.get(op_type)


def is_host_op_type(op_type):
    return op_type in _HOST_OPS


def register_lowering(op_type):
    def deco(fn):
        _LOWERINGS[op_type] = fn
        return fn

    return deco


def register_grad_lowering(op_type):
    """Register an explicit lowering for ``<op_type>_grad``."""

    def deco(fn):
        _GRAD_LOWERINGS[op_type] = fn
        return fn

    return deco


def has_lowering(op_type):
    return op_type in _LOWERINGS or (op_type.endswith('_grad') and
                                     op_type[:-5] in _LOWERINGS)


def get_lowering(op_type):
    fn = _LOWERINGS.get(op_type)
    if fn is not None:
        return fn
    if op_type.endswith('_grad'):
        fwd = op_type[:-5]
        if fwd in _GRAD_LOWERINGS:
            return _GRAD_LOWERINGS[fwd]
        if fwd in _LOWERINGS:
            return _make_generic_grad(fwd)
    raise NotImplementedError('no XLA lowering registered for op %r' %
                              op_type)


class LoweringContext(object):
    """Tracing environment handed to every lowering.

    ``env`` maps var name -> traced jax value.  ``block`` gives access to var
    descs (shape/dtype metadata).  RNG keys are derived from a carried key so
    compiled functions stay pure.
    """

    def __init__(self, block, env, rng_key=None, is_test=False, place=None,
                 mesh=None, batch_axis=None, cond_uninit=None,
                 conditional_scope=False):
        self.block = block
        self.env = env
        self._rng = rng_key
        self.is_test = is_test
        self.place = place
        # True exactly when this block is lowered for a CPU place — the
        # only case in which a Pallas kernel may run interpreted.  Read
        # from the place's TYPE, never from jax.default_backend(): a
        # TPU-place lowering cannot reach interpret mode.
        from ..fluid import core
        self.on_cpu = isinstance(place, core.CPUPlace)
        # the SPMD executor's device mesh (None single-device) and the mesh
        # axis the batch dim is sharded over: lowerings with a sharded
        # implementation (ring attention over 'sp') consult these at trace
        # time
        self.mesh = mesh
        self.batch_axis = batch_axis
        # trace-time constant folding for scalar index chains: under
        # whole-block jit every value is a tracer, but tensor-array ops
        # need concrete indices to keep list state (the reference keeps
        # them concrete by interpreting op-by-op).  fill_constant /
        # increment / assign record known scalar values here; run_op
        # invalidates entries any other op overwrites.
        self.concrete = {}
        # per-array log of resolved indices, appended at forward-lowering
        # time and popped (reverse order) by the array ops' backwards —
        # in-place index vars make self.concrete stale by backward time
        self.array_log = {}
        # names whose ONLY assignment so far is inside a single
        # conditional_block: the reference leaves such a var
        # uninitialized when the cond is false and errors on read
        # (conditional_block_op.cc); the blended lowering zero-fills
        # instead, which is unobservable once a second branch (or any
        # unconditional op) writes the name — until then, a read is a
        # may-read-before-write program error and is rejected at
        # lowering time.  The set is SHARED down nested contexts (pass
        # cond_uninit); conditional_scope=True marks a context whose ops
        # execute conditionally (branch/loop bodies) — there, reads are
        # not checked (a same-cond guarded read is legal in the
        # reference) and writes do not clear the flag (the write itself
        # may never execute).
        self.cond_uninit = cond_uninit if cond_uninit is not None else set()
        self.conditional_scope = conditional_scope
        # ragged-batch provenance: env names whose value is derived from
        # batch-led feeds AND still carries the batch on dim 0.  Seeded
        # by the executor from the feed dict when a @SAMPLE_MASK rides
        # along; propagated per op by run_op.  Batch-reduction lowerings
        # apply the mask ONLY to members — a weight-derived tensor whose
        # dim 0 merely coincides with the padded batch size never masks.
        self.batch_led = set()
        # ...and names with batch ANCESTRY regardless of current dim 0
        # (a reshape [B,T,..]->[B*T,..] drops out of batch_led but stays
        # tainted) — lets the masked lowerings WARN when a flattened
        # batch reaches a reduction the mask can no longer protect
        self.batch_tainted = set()

    # ---- value access ----
    def get(self, op, slot, default=None):
        names = op.input(slot)
        if not names:
            return default
        return self.env[names[0]]

    def get_list(self, op, slot):
        return [self.env[n] for n in op.input(slot)]

    def set(self, op, slot, value):
        names = op.output(slot)
        if names:
            self.env[names[0]] = value

    def set_list(self, op, slot, values):
        names = op.output(slot)
        for n, v in zip(names, values):
            self.env[n] = v

    def lookup(self, name):
        return self.env[name]

    def has(self, name):
        return name in self.env

    def store(self, name, value):
        self.env[name] = value

    def var_desc(self, name):
        return self.block._find_var_recursive(name)

    def next_rng(self):
        import jax
        if self._rng is None:
            raise RuntimeError('op requested randomness but no RNG key was '
                               'threaded into this block')
        self._rng, key = jax.random.split(self._rng)
        return key

    def sub_context(self, block=None, env=None):
        sub = LoweringContext(
            block if block is not None else self.block,
            env if env is not None else self.env,
            rng_key=None,
            is_test=self.is_test,
            place=self.place,
            mesh=self.mesh,
            batch_axis=self.batch_axis,
            cond_uninit=self.cond_uninit,
            conditional_scope=self.conditional_scope)
        # trace-time constants survive into re-traces (grad synthesis,
        # sub-blocks): lowerings that need concrete values (lod_reset
        # offsets, tensor-array indices) behave identically there
        sub.concrete = dict(self.concrete)
        # grad replays and sub-blocks reuse the parent's names: a
        # forward value's batch-led provenance must survive into them
        sub.batch_led = set(self.batch_led)
        sub.batch_tainted = set(self.batch_tainted)
        return sub


# op types that maintain ctx.concrete themselves (their lowerings set or
# propagate entries); every other op's outputs invalidate stale entries
_CONCRETE_PRESERVING = {'fill_constant', 'increment', 'assign',
                        'assign_value'}

# reserved feed name for the ragged-batch sample mask (float [B]; 1.0 =
# real row, 0.0 = padding the data-parallel executor appended to make the
# lot divisible by the mesh's dp extent).  Batch-mean lowerings consult it
# so loss/grad means weight by REAL sample count — the DataBalance parity
# answer (details/data_balance_op_handle.cc) under static SPMD shapes.
SAMPLE_MASK_NAME = '@SAMPLE_MASK'

SEQLEN_SUFFIX = '@SEQLEN'
# nested (2-level LoD) tensors additionally carry the OUTER level — the
# number of sub-sequences each top-level sequence owns — as `<name>@ROWS`
# int32[B]; the padded data rows are then grouped per sequence by
# cumulative offsets (SURVEY §5.7 nested case)
ROWS_SUFFIX = '@ROWS'
# ops that consume sequence structure and emit dense outputs — sequence
# lengths must NOT propagate through them
_SEQ_CONSUMERS = {
    'sequence_pool', 'sequence_last_step', 'sequence_first_step',
}


def check_cond_uninit(ctx, names, what):
    """Reject a read of a var whose only assignment sits inside a single
    conditional_block — when the cond is false the var is uninitialized
    and the reference's conditional_block_op.cc enforce errors on the
    read.  One helper for every call site (jit op inputs, host-op
    inputs, fetches) so the rule cannot drift between paths."""
    if not ctx.cond_uninit:
        return
    for n in names:
        if n in ctx.cond_uninit:
            raise RuntimeError(
                '%s reads var %r, whose only assignment is inside a '
                'single conditional_block: when the cond is false the '
                'var is uninitialized (reference conditional_block_op.cc '
                'errors on such a read) — write it unconditionally or '
                'in both branches first' % (what, n))


# The executors trace a Program's block under this jax.named_scope, and
# run_op traces each op under op_scope_name(op) inside it.  Scopes run at
# trace time and change the HLO's metadata only, never the HLO: in a
# device trace every executed operation then carries the Fluid op that
# emitted it (chipbench/scopes.py reads them).  The persistent compile
# cache leaves metadata out of its key, so a change that ONLY renames
# scopes keeps loading executables with the old names until the HLO (or
# the module's name) changes too.
STEP_SCOPE = 'paddle_tpu.step'


def op_scope_name(op):
    """``<op type>.<first output>`` (``mul.fc_12.tmp_0``,
    ``adam.fc_16.w_0``, ``mul_grad.fc_12.tmp_0~GRAD``).  ``/`` separates
    scopes, and XLA cuts an operation's name at the first ``@`` (its
    ``<name>@<op type>`` form), which would take the nested scopes below a
    ``...@GRAD`` with it: names lose both."""
    first = next((n for slot in (op.outputs, op.inputs)
                  for ns in slot.values() for n in ns if n), '_')
    return ('%s.%s' % (op.type, first)).replace('/', '|').replace('@', '~')


def run_op(ctx, op):
    """Lower one op into the trace, propagating sequence-length metadata
    (the static-shape stand-in for LoD, SURVEY §5.7)."""
    import jax
    guarded = ctx.conditional_scope or op.type == 'conditional_block'
    if not guarded:
        check_cond_uninit(
            ctx, (n for names in op.inputs.values() for n in names),
            'op %r' % op.type)
    if op.type not in _CONCRETE_PRESERVING:
        for names in op.outputs.values():
            for n in names:
                ctx.concrete.pop(n, None)
    # the one door for block 0, sub-blocks (recurrent, while, conditional
    # bodies) and the generic _grad lowerings, so scopes nest as ops do
    with jax.named_scope(op_scope_name(op)):
        get_lowering(op.type)(ctx, op)
    if ctx.cond_uninit and not guarded:
        # an unconditional write covers the name; writes inside
        # branch/loop bodies (conditional_scope) may never execute and
        # must NOT clear it
        for names in op.outputs.values():
            for n in names:
                ctx.cond_uninit.discard(n)
    mask = ctx.env.get(SAMPLE_MASK_NAME)
    if mask is not None and not op.type.endswith('_grad'):
        # ragged-batch provenance: an output is batch-led iff any input
        # was AND it still carries the batch on dim 0 (a transposed-away
        # batch conservatively drops out — the masked lowerings then
        # leave that value alone); batch ANCESTRY (tainted) survives any
        # shape change so the lowerings can warn on flattened batches
        led = any(n in ctx.batch_led
                  for names in op.inputs.values() for n in names)
        tainted = led or any(n in ctx.batch_tainted
                             for names in op.inputs.values() for n in names)
        b = mask.shape[0]
        for names in op.outputs.values():
            for n in names:
                v = ctx.env.get(n)
                if led and getattr(v, 'ndim', 0) >= 1 and v.shape[0] == b:
                    ctx.batch_led.add(n)
                else:
                    ctx.batch_led.discard(n)
                if tainted:
                    ctx.batch_tainted.add(n)
                else:
                    ctx.batch_tainted.discard(n)
    if op.type in _SEQ_CONSUMERS or op.type.endswith('_grad'):
        return
    for suffix in (SEQLEN_SUFFIX, ROWS_SUFFIX):
        meta = None
        for names in op.inputs.values():
            for n in names:
                if (n + suffix) in ctx.env:
                    meta = ctx.env[n + suffix]
                    break
            if meta is not None:
                break
        if meta is not None:
            for names in op.outputs.values():
                for n in names:
                    ctx.env.setdefault(n + suffix, meta)


GRAD_SUFFIX = '@GRAD'
# attr keys on grad ops recording the forward op's slot structure
FWD_IN_SLOTS_ATTR = '__fwd_in_slots__'
FWD_OUT_SLOTS_ATTR = '__fwd_out_slots__'


def fwd_structure(grad_op):
    """Recover (fwd_inputs, fwd_outputs, fwd_attrs) slot->names maps from a
    grad OpDesc built by backward.append_backward."""
    in_slots = grad_op.attrs[FWD_IN_SLOTS_ATTR]
    out_slots = grad_op.attrs[FWD_OUT_SLOTS_ATTR]
    fwd_inputs = {s: grad_op.input(s) for s in in_slots}
    fwd_outputs = {s: grad_op.input(s) for s in out_slots}
    fwd_attrs = {
        k: v
        for k, v in grad_op.attrs.items()
        if k not in (FWD_IN_SLOTS_ATTR, FWD_OUT_SLOTS_ATTR)
    }
    return fwd_inputs, fwd_outputs, fwd_attrs


def _device_bytes(ctx, value, spec):
    """Bytes one device holds of ``value`` (an array or a tree of them),
    its leading dimensions split over the mesh axes that ``spec`` names (a
    PartitionSpec's entries) wherever they divide; all of it with no mesh."""
    import jax
    sizes = dict(ctx.mesh.shape) if ctx.mesh is not None else {}
    held = 0
    for v in jax.tree_util.tree_leaves(value):
        split = 1
        for dim, axes in zip(v.shape, spec):
            n = 1
            for a in axes if isinstance(axes, tuple) else (axes, ):
                n *= sizes.get(a, 1)
            if dim % n == 0:
                split *= n
        held += v.size * v.dtype.itemsize // split
    return held


def order_param_updates(ctx, op, diff_names, grads):
    """Order every later in-place update of a parameter after this gradient
    op's reads of it; ``grads`` are the op's gradients for its forward
    inputs ``diff_names``, and come back as they are to be stored.

    The op's other gradients (dX) and its parameters' values (W) pass one
    ``optimization_barrier`` and W's name is rebound to what comes out: the
    optimizer's in-place write of W now depends on dX, so XLA keeps no whole
    copy of W for a dX it scheduled later.  W's own gradient stays outside,
    or its product could not take the update into its fusion (PERF.md
    section 6, PR 32).

    A gradient that passes a barrier cannot fuse into its consumer, so an op
    is tied where its parameters' bytes are at least its other gradients',
    both as one device holds them: the shapes here are global, a parameter
    is split as it is annotated, and any other gradient is an activation's,
    whose rows the executor splits over the batch axis with the feeds.  Ops
    in a conditional scope (loop and branch bodies) are left alone: a name
    rebound in their private ``env`` would not reach the optimizer."""
    import jax
    from ..fluid import trace
    from ..parallel.api import sharding_of
    is_param = [getattr(ctx.var_desc(n), 'persistable', False)
                for n in diff_names]
    params = list(dict.fromkeys(
        n for n, p in zip(diff_names, is_param) if p))
    if not params:
        return grads
    values = [ctx.lookup(n) for n in params]
    others = [g for g, p in zip(grads, is_param) if not p]
    held = sum(_device_bytes(ctx, v, sharding_of(ctx.var_desc(n)) or ())
               for n, v in zip(params, values))
    tied = bool(others) and not ctx.conditional_scope and \
        held >= _device_bytes(ctx, others, (ctx.batch_axis, ))
    trace.note_lowering_choice(
        ctx.block.program, 'param_update_order',
        next(filter(None, op.output_arg_names)),
        'tied' if tied else 'untied', params=len(params), mb=held / 1e6)
    if not tied:
        return grads
    others, values = jax.lax.optimization_barrier((others, values))
    for n, v in zip(params, values):
        ctx.store(n, v)
    others = iter(others)
    return [g if p else next(others) for g, p in zip(grads, is_param)]


def _make_generic_grad(fwd_type):
    """Build a grad lowering from the forward lowering via jax.vjp.

    The grad OpDesc (built by backward.py) carries the forward op's inputs,
    outputs and attrs; declared grad outputs ``<slot>@GRAD`` name which inputs
    need gradients.  Missing output-grads are treated as zeros (the analog of
    fill_zeros_like insertion in the reference backward pass).
    """
    import jax
    import jax.numpy as jnp
    fwd_lower = _LOWERINGS[fwd_type]

    def grad_lowering(ctx, op):
        from ..fluid.framework import Operator
        fwd_inputs, fwd_outputs, fwd_attrs = fwd_structure(op)

        # differentiable primal args: those with a declared <slot>@GRAD output
        diff_specs = []  # (slot, idx, grad_out_name)
        for slot, in_names in fwd_inputs.items():
            gnames = op.output(slot + GRAD_SUFFIX)
            for i, gname in enumerate(gnames):
                if gname and i < len(in_names):
                    diff_specs.append((slot, i, gname))
        if not diff_specs:
            return

        fwd_input_vals = {
            slot: [ctx.lookup(n) for n in names]
            for slot, names in fwd_inputs.items()
        }
        # only outputs the forward pass actually produced (some lowerings
        # write optional outputs conditionally, e.g. sequence_pool MaxIndex)
        # and only float ones: integer/bool outputs carry no gradient and
        # jax.vjp rejects non-float0 cotangents for them (bounded While
        # emits its bool condition and int counters as outputs)
        def _inexact(v):
            if isinstance(v, (list, tuple)):
                return bool(v) and _inexact(v[0])
            return jnp.issubdtype(jnp.result_type(v), jnp.inexact)

        out_names = [(slot, n) for slot in fwd_outputs
                     for n in fwd_outputs[slot]
                     if ctx.has(n) and _inexact(ctx.lookup(n))]
        faux = Operator(
            ctx.block, fwd_type,
            inputs={s: list(n) for s, n in fwd_inputs.items()},
            outputs={s: list(n) for s, n in fwd_outputs.items()},
            attrs=fwd_attrs)
        # sequence-length side-band entries the lowering may consult
        seq_entries = {}
        for names in fwd_inputs.values():
            for n in names:
                for suffix in (SEQLEN_SUFFIX, ROWS_SUFFIX):
                    key = n + suffix
                    if ctx.has(key):
                        seq_entries[key] = ctx.lookup(key)
        # the ragged-batch sample mask is a global side-band: the vjp
        # replay of a batch-mean forward must see the same mask the
        # primal trace saw, or pad rows would re-enter the denominator
        if ctx.has(SAMPLE_MASK_NAME):
            seq_entries[SAMPLE_MASK_NAME] = ctx.lookup(SAMPLE_MASK_NAME)

        def primal(*diff_vals):
            env2 = dict(seq_entries)
            vals = {s: list(v) for s, v in fwd_input_vals.items()}
            for (slot, i, _), v in zip(diff_specs, diff_vals):
                vals[slot][i] = v
            for slot, names in fwd_inputs.items():
                for n, v in zip(names, vals[slot]):
                    env2[n] = v
            sub = ctx.sub_context(env=env2)
            fwd_lower(sub, faux)
            return tuple(env2[n] for _, n in out_names)

        diff_vals = [fwd_input_vals[s][i] for s, i, _ in diff_specs]
        primal_outs, vjp_fn = jax.vjp(primal, *diff_vals)

        def _match_ct(ct, ref):
            # cotangents may be pytrees (tensor-array lists); match leaf
            # dtypes to the primal structure
            if isinstance(ref, (list, tuple)):
                return [_match_ct(c, r) for c, r in zip(ct, ref)]
            ct = jnp.asarray(ct)
            return ct.astype(ref.dtype) if ct.dtype != ref.dtype else ct

        cotangents = []
        for k, (_, n) in enumerate(out_names):
            gname = n + GRAD_SUFFIX
            if ctx.has(gname):
                cotangents.append(_match_ct(ctx.lookup(gname),
                                            primal_outs[k]))
            else:
                cotangents.append(jax.tree_util.tree_map(
                    jnp.zeros_like, primal_outs[k]))
        grads = order_param_updates(
            ctx, op, [fwd_inputs[s][i] for s, i, _ in diff_specs],
            vjp_fn(tuple(cotangents)))
        # when an op writes a var it also reads (loop-carried While state),
        # the input-grad name coincides with the output-cotangent name;
        # that pre-existing value is this op's own cotangent, not a sibling
        # contribution, so it must be overwritten rather than accumulated
        cotangent_names = {n + GRAD_SUFFIX for _, n in out_names}
        for (slot, i, gname), g in zip(diff_specs, grads):
            if ctx.has(gname) and gname not in cotangent_names:
                g = ctx.lookup(gname) + g  # rename pass didn't split it
            ctx.store(gname, g)

    return grad_lowering


# ---- mixed precision (bf16 compute / fp32 master weights) ----
# The reference era's float16 work is an inference-only transpiler
# (paddle/contrib/float16/float16_transpiler.py); on TPU the right shape is
# training-time bf16 matmul/conv inputs with fp32 accumulation on the MXU.
_AMP = {'enabled': False}


def set_amp(enabled):
    _AMP['enabled'] = bool(enabled)


def amp_enabled():
    return _AMP['enabled']


def amp_cast_in(*xs):
    """Cast f32 operands to bf16 for an MXU op when AMP is on; leave
    everything else untouched.  Pair with preferred_element_type=f32 so
    accumulation stays fp32."""
    import jax.numpy as jnp
    if not _AMP['enabled']:
        return xs
    return tuple(
        x.astype(jnp.bfloat16)
        if x is not None and hasattr(x, 'dtype') and x.dtype == jnp.float32
        else x for x in xs)


def amp_cast_out(out):
    """AMP output policy for convolutions: activations LAND in HBM as
    bf16.

    Under AMP every conv call site runs amp_cast_in first, so its bf16
    operands yield a bf16 result directly (the TPU MXU accumulates
    bf16 products in fp32 internally regardless of the output dtype) —
    the materialized [B,C,H,W] tensor is 2 bytes/element, and keeping
    it fp32 would double HBM read+write traffic for every activation,
    the dominant cost of a conv net on TPU.  This hook is the safety
    net for any call site whose result comes back fp32 (e.g. a future
    preferred_element_type).  bf16 activations flow through BN (which
    upcasts in-register for its statistics, ops/nn_ops.py
    _batch_norm), relu, pooling and residual adds; master weights and
    optimizer state stay fp32 throughout."""
    import jax.numpy as jnp
    if _AMP['enabled'] and hasattr(out, 'dtype') and \
            out.dtype == jnp.float32:
        return out.astype(jnp.bfloat16)
    return out


def amp_upcast_f32(x):
    """Precision-sensitive math (softmax/norm statistics, loss
    exp/log paths) computes f32 even when AMP lands activations bf16;
    the upcast fuses into the consuming reduction, so HBM still sees
    bf16.  The ONE home of the upcast policy — lowerings call this
    instead of hand-rolling dtype checks."""
    import jax.numpy as jnp
    if x is not None and hasattr(x, 'dtype') and x.dtype == jnp.bfloat16:
        return x.astype(jnp.float32)
    return x


def amp_harmonize(x, y):
    """Mixed bf16/f32 elementwise operands compute bf16 under AMP: the
    f32 side is a parameter (bias, scale) whose in-register cast fuses,
    and promoting instead would re-widen every biased fc activation back
    to f32 in HBM.  Without AMP, normal promotion applies untouched."""
    import jax.numpy as jnp
    if not _AMP['enabled']:
        return x, y
    dx = getattr(x, 'dtype', None)
    dy = getattr(y, 'dtype', None)
    if dx == jnp.bfloat16 and dy == jnp.float32:
        y = y.astype(jnp.bfloat16)
    elif dy == jnp.bfloat16 and dx == jnp.float32:
        x = x.astype(jnp.bfloat16)
    return x, y


def amp_matmul(x, y):
    """The one home of the AMP matmul policy: bf16 operands, bf16
    result.  The TPU MXU accumulates bf16 products in fp32 internally
    regardless of the requested output dtype, so a bf16 output is
    bit-identical to preferred_element_type=f32 followed by a bf16
    cast — but WITHOUT the f32 intermediate: asking for f32 made every
    cotangent in the backward pass f32, which re-widened all gradient
    matmuls and their HBM traffic (r5 transformer A/B: the pure-JAX
    bound emitting bf16 ran the same matmuls ~45% faster end to end)."""
    import jax.numpy as jnp
    x, y = amp_cast_in(x, y)
    return amp_cast_out(jnp.matmul(x, y))
