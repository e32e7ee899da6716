"""State-space model ops: what a Mamba-2 / attention hybrid block needs and
the 2018 op set lacks.

- ``rms_norm`` / ``gated_rms_norm``: ``x / sqrt(mean(x^2) + eps) * w`` over
  the last axis; the gated form normalises ``x * silu(gate)`` (Mamba-2's
  output norm, the gate before the norm).  With ``groups`` G the last axis
  is G runs of channels, each normalised by its own root-mean-square (the
  weight stays one vector over all channels).
- ``swiglu``: ``silu(g) * u`` over the two halves of the last axis (a gated
  feed-forward's activation).
- ``residual_add``: ``x + scale * y`` in ``x``'s dtype.  ``elementwise_add``
  narrows a mixed bf16/f32 pair to bf16 under AMP, which is right for a bias
  and wrong for a pre-norm residual stream: nothing renormalises that
  stream, so it stays f32 and the bf16 branch is widened into it.
- ``causal_conv1d``: depthwise convolution over time, each channel over its
  own last K positions, zeros before the start, bias, optional SiLU; f32
  arithmetic, the output and dX in X's dtype.
- ``ssd_scan``: Mamba-2's selective state-space recurrence

      S_t = exp(dt_t A) S_{t-1} + dt_t X_t B_t^T,   y_t = S_t C_t + D X_t

  with the step dt_t = softplus(Dt_t + DtBias), in its chunked (SSD) form: inside a chunk of ``chunk`` positions the
  masked products ``(C B^T o L) X`` with ``L[i, j] = exp(sum_{j<k<=i}
  dt_k A)``; a chunk's final state; a short recurrence over chunk states.

Precision: every statistic, and all decay arithmetic (``dt``, its bias and
softplus, ``dt A``, cumulative sums, ``exp``), is f32 whatever AMP says;
the products take bf16 operands under AMP with f32 accumulation, as ``mul``
does; outputs land in the AMP activation dtype.

``ssd_scan``'s gradient is an explicit lowering.  The forward leaves the
states entering each chunk ([B, chunks, H, P, N] f32) in the trace beside
its output, and the gradient makes the within-chunk decay matrix and scores
again from the op's inputs and those states, so no [H, chunk, chunk] tensor
lives from the forward to the backward.

Two implementations, picked where the op is lowered from what can be seen
there (``impl='auto'``, the default; ``'xla'`` / ``'pallas'`` ask for one;
``fluid.trace.lowering_choices('ssd_scan')`` records the choice with the
chunk, the chunks and the kernel's block):

- **'pallas'** (``ops/pallas/ssd_scan.py``): one fused kernel forward and
  one for the gradient.  The decay and score matrices and each chunk's own
  state stay in VMEM, the state rides from chunk to chunk in a VMEM
  scratch, X / B / C / dY are read in the row-major layout they have.
  Taken on an accelerator place without a mesh inside the envelope
  ``_fused_fits``: head width 64, state 128, a chunk of 128 or 256
  positions, the sequence a whole number of chunks, the heads of a group
  a whole number of 8-head blocks.
- **'xla'**: the einsum form below, left to XLA, under ``jax.checkpoint``
  in the gradient.  A CPU place, any mesh (GSPMD does not partition a
  ``pallas_call``), and every shape outside the envelope; also the
  reference the kernel's tests compare with.

What 'auto' rests on: both lowerings on the v5e at the two shapes the cells
train, bf16 under AMP, ms a call of one op on the host's clock round ten
calls chained on the device (``tools/pallas_chip_check.py ssd_scan``, PR
35; PERF.md section 6 has the cells' own traces), kernel (forward +
gradient) / xla:
  1 x 1024 tokens, 64 heads of 64, one group, state 128, chunk 256:
      0.34 (alone: 0.11 + 0.25) / 0.45
  2 x 2048 tokens, 64 heads of 64, eight groups, state 128, chunk 128:
      1.11 (alone: 0.38 + 0.77) / 4.03
The einsum form writes a bf16 [chunk, chunk] weight matrix a head to HBM
in both passes and transposes X, B, C to head-major round its products;
its loss grows with the chunks and the groups.  Other widths, states and
chunks were neither compiled nor timed: 'xla'.

``causal_conv1d`` and its gradient have the same two (``impl`` as above;
``fluid.trace.lowering_choices('causal_conv1d')`` records the choice
with the kernels' block):

- **'pallas'** (``ops/pallas/causal_conv1d.py``): one kernel forward, one
  for the gradient, which makes the forward again from X and takes the
  filter's and the bias's sums in the same pass.  Each reads X (and dY)
  once as it lies and writes each output once.  Taken on an accelerator
  place without a mesh inside ``_conv_fits``: whole 128-lane tiles of
  channels, at most ``MAX_TAPS`` taps, whole 16-position tiles, and 128
  channels of the whole length within the kernel's block.
- **'xla'**: the shifted sums (``causal_conv1d``), whose gradient is their
  ``jax.vjp``: XLA writes an f32 copy of the padded input and, in the
  gradient, one f32 gradient a tap.  A CPU place, any mesh, every
  other shape, and the kernels' reference in the tests.

What 'auto' rests on for the convolution: both lowerings on the v5e at
the two shapes the cells train, bf16, K 4, SiLU, ms a call of the forward
and of the gradient alone on the host's clock round ten calls chained on
the device (``tools/pallas_chip_check.py causal_conv1d``), kernel / xla:
  1 x 1024 x 4352: 0.045 + 0.072 / 0.052 + 0.109
  2 x 2048 x 6144: 0.149 + 0.282 / 0.249 + 2.576
The kernels move their bytes at 65-82% of HBM; XLA's gradient writes an
f32 gradient a tap and reads it back.  Other shapes inside the envelope
were checked only at the tests' sizes, interpreted, and never timed.

``gated_rms_norm`` and its gradient have the same two (``impl`` as above;
``fluid.trace.lowering_choices('gated_rms_norm')`` records the choice
with the kernels' block, ``'rms_norm'`` that of the ungated op, always
'xla'):

- **'pallas'** (``ops/pallas/gated_rms_norm.py``): one kernel forward,
  one for the gradient, which makes the forward's factors again.  A
  program takes a block of rows by all channels; a group is a static
  slice of lanes.  Taken on an accelerator place without a mesh inside
  ``_norm_fits``: a gate, more than one group, groups of whole 128-lane
  tiles, and rows that divide into the kernels' block.
- **'xla'**: ``_rms_norm``, whose gradient is the generic ``jax.vjp``.
  Its [..., G, C / G] view puts the groups on the sublanes, and XLA lays
  each float32 intermediate out anew round it (about 1.5 GB a layer of
  nemotron's [2, 2048, 4096] in eight groups, forward and gradient,
  against 0.27 GB the kernels move).  One group fuses into its
  neighbours, so it stays here, as do a CPU place, any mesh, the ungated
  norm and every other shape; also the kernels' reference in the tests.

What 'auto' rests on for the norm: both lowerings on the v5e at
nemotron's shape, bf16, ms a call of the forward and of the gradient
alone, chained as above (``tools/pallas_chip_check.py gated_rms_norm``),
kernel / xla:
  2 x 2048 x 4096, eight groups: 0.095 + 0.165 / 0.663 + 2.013
Chained, the compiler keeps the kernels' results in VMEM, so those two
read fewer HBM bytes than in a step.  Other shapes inside the envelope
were checked only at the tests' sizes, interpreted, and never timed.
"""

import jax
import jax.numpy as jnp

from . import registry
from .registry import (GRAD_SUFFIX, amp_cast_in, amp_cast_out,
                       register_grad_lowering, register_lowering)

# the states entering each chunk, kept in the trace beside the op's output
# for the op's gradient (a side-band, as flash_attention's @FLASH_LSE is)
_STATES_SUFFIX = '@SSD_STATES'


def _f32(x):
    return x.astype(jnp.float32)


# ---- norms, the gated activation, the residual ---------------------------

def _rms_norm(x, weight, eps, gate=None, groups=1):
    xs = _f32(x)
    if gate is not None:
        xs = xs * jax.nn.silu(_f32(gate))
    shape = xs.shape
    if groups > 1:
        if shape[-1] % groups:
            raise ValueError('rms_norm: %d channels do not divide into %d '
                             'groups' % (shape[-1], groups))
        xs = xs.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = xs * jax.lax.rsqrt(jnp.mean(jnp.square(xs), -1, keepdims=True) + eps)
    return amp_cast_out(y.reshape(shape) * _f32(weight))


@register_lowering('swiglu')
def _swiglu(ctx, op):
    x = ctx.get(op, 'X')
    g, u = jnp.split(x, 2, axis=-1)
    ctx.set(op, 'Out', (jax.nn.silu(_f32(g)) * _f32(u)).astype(x.dtype))


@register_lowering('residual_add')
def _residual_add(ctx, op):
    x, y = ctx.get(op, 'X'), ctx.get(op, 'Y')
    ctx.set(op, 'Out', x + jnp.asarray(op.attrs.get('scale', 1.0), x.dtype)
            * y.astype(x.dtype))


# ---- where a kernel may run -------------------------------------------------

def _one_accelerator(ctx):
    """An accelerator place with no mesh axis larger than 1: GSPMD does
    not partition a ``pallas_call``."""
    from .attention_ops import _mesh_axes
    return not (ctx.on_cpu or _mesh_axes(ctx))


def _pick(op_type, attrs, fits):
    """The op's ``impl`` attr: 'auto' takes the kernel where ``fits()``."""
    impl = attrs.get('impl', 'auto')
    if impl == 'auto':
        return 'pallas' if fits() else 'xla'
    if impl not in ('xla', 'pallas'):
        raise ValueError("%s: impl must be 'auto', 'xla' or 'pallas', got "
                         "%r" % (op_type, impl))
    return impl


def _wanted(op, slots):
    """(slot, gradient name) of each of ``slots`` whose gradient the
    gradient op writes."""
    wanted = [(s, op.output(s + GRAD_SUFFIX)) for s in slots]
    return [(s, names[0]) for s, names in wanted if names and names[0]]


def _store_grads(ctx, op, fwd_inputs, wanted, grads):
    """Store ``grads[slot]`` under each name of ``wanted``, the parameters'
    updates ordered after the op's reads, adding to a gradient already
    there."""
    grads = registry.order_param_updates(
        ctx, op, [fwd_inputs[s][0] for s, _ in wanted],
        [grads[s] for s, _ in wanted])
    for (_, name), g in zip(wanted, grads):
        if ctx.has(name):   # the rename pass did not split it
            g = ctx.lookup(name) + g
        ctx.store(name, g)


# ---- the grouped, gated norm -----------------------------------------------

_NORM_SLOTS = ('X', 'Gate', 'Scale')


def _norm_dtype():
    """Y's dtype in the XLA form: its f32 result through ``amp_cast_out``."""
    return jnp.bfloat16 if registry.amp_enabled() else jnp.float32


def _norm_fits(ctx, x, gate, groups):
    """Whether the norm's kernels can run this op where it is lowered: the
    place, the mesh, a gate, more than one group (one group fuses into its
    neighbours in XLA), groups of whole 128-lane tiles, and rows that
    divide into the kernels' block."""
    from .pallas import gated_rms_norm as pl_norm
    channels = x.shape[-1]
    return (_one_accelerator(ctx) and gate is not None and groups > 1
            and channels % (groups * pl_norm.LANES) == 0
            and pl_norm.block(x.size // channels, channels) is not None)


@register_lowering('rms_norm')
@register_lowering('gated_rms_norm')
def _rms_norm_lowering(ctx, op):
    from ..fluid import trace
    from .pallas import gated_rms_norm as pl_norm
    x, gate, w = (ctx.get(op, s) for s in _NORM_SLOTS)
    eps, groups = op.attrs.get('epsilon', 1e-5), int(op.attrs.get('groups',
                                                                  1))
    impl = _pick(op.type, op.attrs, lambda: _norm_fits(ctx, x, gate, groups))
    out_name, seen = op.output('Y')[0], {}
    if impl == 'pallas':
        channels = x.shape[-1]
        seen['block'] = [pl_norm.block(x.size // channels, channels),
                         channels]
        y = pl_norm.gated_rms_norm(x, gate, w, eps, groups, _norm_dtype(),
                                   interpret=ctx.on_cpu)
    else:
        y = _rms_norm(x, w, eps, gate=gate, groups=groups)
    trace.note_lowering_choice(ctx.block.program, op.type, out_name, impl,
                               **seen)
    ctx.set(op, 'Y', y)


_generic_norm_grad = registry._make_generic_grad('gated_rms_norm')


@register_grad_lowering('gated_rms_norm')
def _gated_rms_norm_grad_lowering(ctx, op):
    """dX, dGate and dScale from the op's inputs and dY.  'pallas': the
    gradient kernel, which makes the forward's factors again.  'xla': the
    generic ``jax.vjp`` of ``_rms_norm``."""
    from .pallas import gated_rms_norm as pl_norm
    fwd_inputs, fwd_outputs, attrs = registry.fwd_structure(op)
    x, gate, w = (ctx.lookup(fwd_inputs[s][0]) if fwd_inputs.get(s)
                  else None for s in _NORM_SLOTS)
    eps, groups = attrs.get('epsilon', 1e-5), int(attrs.get('groups', 1))
    if _pick('gated_rms_norm', attrs,
             lambda: _norm_fits(ctx, x, gate, groups)) != 'pallas':
        return _generic_norm_grad(ctx, op)
    wanted = _wanted(op, _NORM_SLOTS)
    if not wanted:
        return
    out_name = fwd_outputs['Y'][0] + GRAD_SUFFIX
    dy = ctx.lookup(out_name).astype(_norm_dtype()) if ctx.has(out_name) \
        else jnp.zeros(x.shape, _norm_dtype())
    grads = dict(zip(_NORM_SLOTS, pl_norm.gated_rms_norm_grad(
        x, gate, w, dy, eps, groups, interpret=ctx.on_cpu)))
    grads['Scale'] = grads['Scale'].astype(w.dtype)
    _store_grads(ctx, op, fwd_inputs, wanted, grads)


# ---- the convolution over time --------------------------------------------

def causal_conv1d(x, w, bias, act=''):
    """X [B, L, C], Filter [C, K] (tap K-1 weighs the current position),
    Bias [C]: K shifted multiply-adds, f32, no later position read; Y in
    X's dtype."""
    length, taps = x.shape[1], w.shape[1]
    w = _f32(w)
    padded = jnp.pad(_f32(x), ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + length] * w[:, k] for k in range(taps)) \
        + _f32(bias)
    if act == 'silu':
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


_CONV_SLOTS = ('X', 'Filter', 'Bias')


def _conv_fits(ctx, x, taps):
    """Whether the convolution's kernels can run this op where it is
    lowered: the place, the mesh, and the shapes (whole 128-lane tiles of
    channels, at most ``MAX_TAPS`` taps, whole 16-position tiles, a block
    of 128 channels over the whole length within the VMEM it was compiled
    for)."""
    from .pallas import causal_conv1d as pl_conv
    length, channels = x.shape[1:]
    return (_one_accelerator(ctx) and channels % pl_conv.LANES == 0
            and taps <= pl_conv.MAX_TAPS and length % 16 == 0
            and length * pl_conv.LANES <= pl_conv.BLOCK_ELEMS)


@register_lowering('causal_conv1d')
def _causal_conv1d(ctx, op):
    from ..fluid import trace
    from .pallas import causal_conv1d as pl_conv
    x, w, bias = (ctx.get(op, s) for s in _CONV_SLOTS)
    act = op.attrs.get('activation', '')
    impl = _pick('causal_conv1d', op.attrs,
                 lambda: _conv_fits(ctx, x, w.shape[1]))
    out_name, seen = op.output('Out')[0], {}
    if impl == 'pallas':
        seen['block'] = pl_conv.block(*x.shape[1:])
        y = pl_conv.causal_conv1d(x, w, bias, act, interpret=ctx.on_cpu)
    else:
        y = causal_conv1d(x, w, bias, act)
    trace.note_lowering_choice(ctx.block.program, op.type, out_name, impl,
                               **seen)
    ctx.set(op, 'Out', y)


@register_grad_lowering('causal_conv1d')
def _causal_conv1d_grad_lowering(ctx, op):
    """dX, dFilter and dBias from the op's inputs and dY.  'pallas': the
    gradient kernel, which makes the forward again from X.  'xla':
    ``jax.vjp`` of the shifted sums."""
    from .pallas import causal_conv1d as pl_conv
    fwd_inputs, fwd_outputs, attrs = registry.fwd_structure(op)
    wanted = _wanted(op, _CONV_SLOTS)
    if not wanted:
        return
    x, w, bias = (ctx.lookup(fwd_inputs[s][0]) for s in _CONV_SLOTS)
    out_name = fwd_outputs['Out'][0] + GRAD_SUFFIX
    dy = ctx.lookup(out_name) if ctx.has(out_name) else jnp.zeros_like(x)
    act = attrs.get('activation', '')
    if _pick('causal_conv1d', attrs,
             lambda: _conv_fits(ctx, x, w.shape[1])) == 'pallas':
        grads = pl_conv.causal_conv1d_grad(x, w, bias, dy, act,
                                           interpret=ctx.on_cpu)
    else:
        grads = jax.vjp(lambda *a: causal_conv1d(*a, act=act), x, w,
                        bias)[1](dy.astype(x.dtype))
    _store_grads(ctx, op, fwd_inputs, wanted, {
        s: g.astype(p.dtype) for s, g, p in zip(_CONV_SLOTS, grads,
                                                 (x, w, bias))})


# ---- the chunked scan -----------------------------------------------------

def _chunks(t, n, q):
    """[B, n*q, ...] -> [B, n, q, ...]."""
    return t.reshape(t.shape[:1] + (n, q) + t.shape[2:])


def _grouped(t, groups, axis=3):
    """The head axis H as (G, H/G): head h reads group h // (H/G)'s B and
    C (one group in the published models)."""
    return t.reshape(t.shape[:axis] + (groups, t.shape[axis] // groups)
                     + t.shape[axis + 1:])


def _chunk_states(x, dt, a, bm):
    """What each chunk alone adds to the state, and how much of the state
    entering it survives it: ([B, n, H, P, N], [B, n, H]) f32.
    x [B,n,q,H,P], dt [B,n,q,H] f32, a [H] f32, bm [B,n,q,G,N]."""
    da = dt * a
    total = jnp.sum(da, axis=2)
    to_end = jnp.exp(total[:, :, None] - jnp.cumsum(da, axis=2))
    xw, bm = amp_cast_in(_f32(x) * (to_end * dt)[..., None], bm)
    states = jnp.einsum('bnqgrp,bnqgs->bngrps', _grouped(xw, bm.shape[3]),
                        bm, preferred_element_type=jnp.float32)
    return states.reshape(x.shape[:2] + x.shape[3:] + bm.shape[-1:]), \
        jnp.exp(total)


def _entering(states, keep):
    """The recurrence between chunks: the state entering each chunk (zeros
    enter the first), [B, n, H, P, N] f32."""
    def step(s, inp):
        add, k = inp
        return s * k[..., None, None] + add, s

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(keep, 1, 0)))
    return jnp.moveaxis(entering, 0, 1)


def _chunk_outputs(x, dt, a, bm, cm, d, entering):
    """y [B,n,q,H,P] f32 from the chunk's own positions (the masked
    products), the state entering the chunk, and the skip D x."""
    groups, q = bm.shape[3], x.shape[2]
    cum = jnp.cumsum(dt * a, axis=2)                  # [B,n,q,H]
    cum_h = jnp.moveaxis(cum, 3, 2)                   # [B,n,H,q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]   # [B,n,H,i,j]
    lower = jnp.tril(jnp.ones((q, q), bool))
    # the exponent above the diagonal is positive and may overflow: masked
    # before the exp, not after
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb, bb = amp_cast_in(cm, bm)
    scores = jnp.einsum('bnigs,bnjgs->bngij', cb, bb,
                        preferred_element_type=jnp.float32)
    weights = _grouped(decay * jnp.moveaxis(dt, 3, 2)[..., None, :], groups,
                       axis=2) * scores[:, :, :, None]
    wx, xx = amp_cast_in(weights, _grouped(x, groups))
    y = jnp.einsum('bngrij,bnjgrp->bnigrp', wx, xx,
                   preferred_element_type=jnp.float32)
    ce, se = amp_cast_in(cm, _grouped(entering, groups, axis=2))
    carried = jnp.einsum('bnigs,bngrps->bnigrp', ce, se,
                         preferred_element_type=jnp.float32)
    return (y.reshape(x.shape)
            + carried.reshape(x.shape) * jnp.exp(cum)[..., None]
            + _f32(x) * d[:, None])


def _step(dt, dt_bias):
    """The step softplus(dt + dt_bias), f32."""
    return jax.nn.softplus(_f32(dt) + _f32(dt_bias))


def _prepare(x, dt, a, bm, cm, d, dt_bias, chunk):
    """The op's inputs as the chunk functions take them: the step
    softplus(dt + dt_bias) and the other decay terms f32, the sequence
    padded to whole chunks (a step of 0 there: the state passes through,
    nothing is added) and cut into them."""
    dt = _step(dt, dt_bias)
    length = x.shape[1]
    q = min(chunk, length)
    n = -(-length // q)
    pad = n * q - length
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0), ) * (
            t.ndim - 2)) for t in (x, dt, bm, cm))
    return (_chunks(x, n, q), _chunks(dt, n, q), _f32(a),
            _chunks(bm, n, q), _chunks(cm, n, q), _f32(d))


def _unchunked(y, like):
    """[B, n, q, H, P] -> like's [B, L, H, P] and dtype, the padding cut."""
    return y.reshape(like.shape[:1] + (-1, ) + like.shape[2:])[
        :, :like.shape[1]].astype(like.dtype)


def ssd_scan(x, dt, a, bm, cm, d, dt_bias, chunk=256):
    """(y, entering): y [B, L, H, P] (x's dtype) of the recurrence in the
    module's header with the step softplus(dt + dt_bias), and the states
    entering each chunk ([B, chunks, H, P, N] f32).  x [B,L,H,P],
    dt [B,L,H], a, d, dt_bias [H], bm/cm [B,L,G,N]."""
    xc, dtc, a, bc, cc, d = _prepare(x, dt, a, bm, cm, d, dt_bias, chunk)
    entering = _entering(*_chunk_states(xc, dtc, a, bc))
    return _unchunked(_chunk_outputs(xc, dtc, a, bc, cc, d, entering),
                      x), entering


_SLOTS = ('X', 'Dt', 'A', 'B', 'C', 'D', 'DtBias')


def _ssd_inputs(ctx, names):
    return [ctx.lookup(names[s][0]) for s in _SLOTS]


def _ssd_chunk(attrs):
    chunk = int(attrs.get('chunk', 256))
    if chunk < 1:
        raise ValueError('ssd_scan: chunk must be positive, got %d' % chunk)
    return chunk


# the fused kernel's envelope: the widths it was compiled and measured at
# (both cells'), and the chunks whose [chunk, chunk] f32 tiles it was
_FUSED_HEAD_DIM = 64
_FUSED_STATE = 128
_FUSED_CHUNKS = (128, 256)


def _fused_fits(ctx, x, bm, chunk):
    """Whether the fused kernel can run this op where it is lowered: every
    term is the place, the mesh or a shape."""
    from .pallas import ssd_scan as pl_ssd
    if not _one_accelerator(ctx):
        return False
    (length, h, p), (g, n) = x.shape[1:], bm.shape[2:]
    return (p == _FUSED_HEAD_DIM and n == _FUSED_STATE
            and chunk in _FUSED_CHUNKS and length % chunk == 0
            and h % g == 0 and (h // g) % pl_ssd.HEAD_BLOCK == 0)


def _pick_impl(ctx, attrs, x, bm, chunk):
    return _pick('ssd_scan', attrs, lambda: _fused_fits(ctx, x, bm, chunk))


def _fused_forward(ctx, inputs, chunk):
    """(y, entering) from the kernel: the step f32 from here, the
    products' operands in the AMP dtype."""
    from .pallas import ssd_scan as pl_ssd
    x, dt, a, bm, cm, d, dt_bias = inputs
    xc, bc, cc = amp_cast_in(x, bm, cm)
    y, entering = pl_ssd.ssd_scan(xc, _step(dt, dt_bias), a, bc, cc, d,
                                  chunk, interpret=ctx.on_cpu)
    return y.astype(x.dtype), entering


@register_lowering('ssd_scan')
def _ssd_scan_lowering(ctx, op):
    from ..fluid import trace
    from .pallas import ssd_scan as pl_ssd
    inputs, chunk = _ssd_inputs(ctx, op.inputs), _ssd_chunk(op.attrs)
    out_name, length = op.output('Y')[0], inputs[0].shape[1]
    chunk = min(chunk, length)
    impl = _pick_impl(ctx, op.attrs, inputs[0], inputs[3], chunk)
    seen = {'chunk': chunk, 'chunks': -(-length // chunk)}
    if impl == 'pallas':
        # a program of the kernel: positions x lanes of X
        seen['block'] = pl_ssd.block(length, chunk, inputs[0].shape[3])
        y, entering = _fused_forward(ctx, inputs, chunk)
    else:
        y, entering = ssd_scan(*inputs, chunk=chunk)
    trace.note_lowering_choice(ctx.block.program, op.type, out_name, impl,
                               **seen)
    ctx.store(out_name + _STATES_SUFFIX, entering)
    ctx.set(op, 'Y', y)


def _fused_grads(ctx, primals, saved, dy, chunk):
    """The seven gradients from the gradient kernel and the few small
    reductions it leaves: the softplus and the bias."""
    from .pallas import ssd_scan as pl_ssd
    x, dt, a, bm, cm, d, dt_bias = primals
    if saved is None:      # another trace lowered the forward
        saved = _fused_forward(ctx, primals, chunk)[1]
    xc, bc, cc = amp_cast_in(x, bm, cm)
    step, step_vjp = jax.vjp(_step, dt, dt_bias)
    dx, dstep, da, dbm, dcm, dd = pl_ssd.ssd_scan_grad(
        xc, step, a, bc, cc, d, saved, dy, chunk, interpret=ctx.on_cpu)
    ddt, dbias = step_vjp(dstep)
    return dx, ddt, da, dbm, dcm, dd, dbias


def _xla_grads(primals, saved, dy, chunk):
    def scan(x, dt, a, bm, cm, d, dt_bias):
        xc, dtc, a, bc, cc, d = _prepare(x, dt, a, bm, cm, d, dt_bias, chunk)
        states, keep = jax.checkpoint(_chunk_states)(xc, dtc, a, bc)
        entering = _entering_saved(states, keep, saved)
        return _unchunked(jax.checkpoint(_chunk_outputs)(
            xc, dtc, a, bc, cc, d, entering), x)

    return jax.vjp(scan, *primals)[1](dy.astype(primals[0].dtype))


@register_grad_lowering('ssd_scan')
def _ssd_scan_grad_lowering(ctx, op):
    """dX, dDt, dA, dB, dC, dD and dDtBias from the op's inputs, the
    states its forward left in this trace, and dY.  'pallas': the gradient
    kernel.  'xla': three pieces, each the ``jax.vjp`` of a function
    above: the chunks' outputs and the chunks' own states under
    ``jax.checkpoint`` (their decay matrix and scores are made again
    here, behind the barrier that keeps XLA from merging them with the
    forward's), and the recurrence between chunks, whose residual is the
    saved states (made again where another trace lowered the forward)."""
    fwd_inputs, fwd_outputs, attrs = registry.fwd_structure(op)
    out_name = fwd_outputs['Y'][0]
    wanted = [op.output(s + GRAD_SUFFIX) for s in _SLOTS]
    if not any(n and n[0] for n in wanted):
        return
    primals, chunk = _ssd_inputs(ctx, fwd_inputs), _ssd_chunk(attrs)
    x = primals[0]
    dy = (ctx.lookup(out_name + GRAD_SUFFIX)
          if ctx.has(out_name + GRAD_SUFFIX) else jnp.zeros_like(x))
    saved = (ctx.lookup(out_name + _STATES_SUFFIX)
             if ctx.has(out_name + _STATES_SUFFIX) else None)
    chunk = min(chunk, x.shape[1])
    if _pick_impl(ctx, attrs, x, primals[3], chunk) == 'pallas':
        grads = _fused_grads(ctx, primals, saved, dy, chunk)
    else:
        grads = _xla_grads(primals, saved, dy, chunk)
    for names, primal, g in zip(wanted, primals, grads):
        if names and names[0]:
            g = g.astype(primal.dtype)
            if ctx.has(names[0]):   # the rename pass did not split it
                g = ctx.lookup(names[0]) + g
            ctx.store(names[0], g)


@jax.custom_vjp
def _entering_saved(states, keep, saved):
    """``_entering(states, keep)``, given its value ``saved`` by the
    forward: nothing is run forward, and the backward is the recurrence
    reversed over the saved states."""
    return _entering(states, keep) if saved is None else saved


def _entering_fwd(states, keep, saved):
    entering = _entering_saved(states, keep, saved)
    return entering, (keep, entering)


def _entering_bwd(res, g):
    keep, entering = res

    def step(lam, inp):
        g_c, k, s = inp
        # lam: the gradient of the state LEAVING this chunk
        d_keep = jnp.sum(lam * s, axis=(-1, -2))
        return g_c + lam * k[..., None, None], (lam, d_keep)

    _, (d_states, d_keep) = jax.lax.scan(
        step, jnp.zeros_like(g[:, 0]),
        (jnp.moveaxis(g, 1, 0), jnp.moveaxis(keep, 1, 0),
         jnp.moveaxis(entering, 1, 0)), reverse=True)
    return (jnp.moveaxis(d_states, 0, 1),
            jnp.moveaxis(d_keep, 0, 1).astype(keep.dtype), None)


_entering_saved.defvjp(_entering_fwd, _entering_bwd)
