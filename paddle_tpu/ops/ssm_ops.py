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
  own last K positions, zeros before the start, bias, optional SiLU.
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


@register_lowering('rms_norm')
@register_lowering('gated_rms_norm')
def _rms_norm_lowering(ctx, op):
    ctx.set(op, 'Y', _rms_norm(ctx.get(op, 'X'), ctx.get(op, 'Scale'),
                               op.attrs.get('epsilon', 1e-5),
                               gate=ctx.get(op, 'Gate'),
                               groups=int(op.attrs.get('groups', 1))))


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


# ---- the convolution over time --------------------------------------------

@register_lowering('causal_conv1d')
def _causal_conv1d(ctx, op):
    """X [B, L, C], Filter [C, K] (tap K-1 weighs the current position),
    Bias [C]: K shifted multiply-adds, f32, no later position read."""
    x = ctx.get(op, 'X')
    w, bias = _f32(ctx.get(op, 'Filter')), ctx.get(op, 'Bias')
    length, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(_f32(x), ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + length] * w[:, k] for k in range(taps)) \
        + _f32(bias)
    if op.attrs.get('activation', '') == 'silu':
        y = jax.nn.silu(y)
    ctx.set(op, 'Out', y.astype(x.dtype))


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
    from .attention_ops import _mesh_axes
    from .pallas import ssd_scan as pl_ssd
    # GSPMD does not partition the kernel: no mesh axis larger than 1
    if ctx.on_cpu or _mesh_axes(ctx):
        return False
    (length, h, p), (g, n) = x.shape[1:], bm.shape[2:]
    return (p == _FUSED_HEAD_DIM and n == _FUSED_STATE
            and chunk in _FUSED_CHUNKS and length % chunk == 0
            and h % g == 0 and (h // g) % pl_ssd.HEAD_BLOCK == 0)


def _pick_impl(ctx, attrs, x, bm, chunk):
    impl = attrs.get('impl', 'auto')
    if impl == 'auto':
        return 'pallas' if _fused_fits(ctx, x, bm, chunk) else 'xla'
    if impl not in ('xla', 'pallas'):
        raise ValueError("ssd_scan: impl must be 'auto', 'xla' or "
                         "'pallas', got %r" % (impl, ))
    return impl


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
