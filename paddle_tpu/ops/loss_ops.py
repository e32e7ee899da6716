"""Loss op lowerings (reference: paddle/fluid/operators/cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, and the *_loss_op.cc family)."""

import functools

import jax
import jax.numpy as jnp

from .registry import register_lowering, amp_upcast_f32

_EPS = 1e-12


def _index_label(label):
    """(N,1) or (N,) int labels -> (N,) int32."""
    if label.ndim > 1 and label.shape[-1] == 1:
        label = jnp.reshape(label, label.shape[:-1])
    return label.astype(jnp.int32)


def _pick_label(x, idx):
    """x[..., idx] as (N, 1), f32 under AMP.  Picks from ``x`` as it
    stands and widens the N picked values (the same numbers: bf16 -> f32
    is exact).  A gather takes no producer fusion, so picking from an
    upcast or otherwise derived [N, V] tensor makes XLA write that whole
    tensor to HBM for the N values read (ISSUE 27: 1966 MB of f32 logits
    in the NMT step whose loss is fetched)."""
    return amp_upcast_f32(jnp.take_along_axis(x, idx[..., None], axis=-1))


@register_lowering('cross_entropy')
def _cross_entropy(ctx, op):
    # log() of bf16 probabilities loses digits — compute f32
    x = ctx.get(op, 'X')  # probabilities (N, C)
    label = ctx.get(op, 'Label')
    if op.attrs.get('soft_label', False):
        p = jnp.maximum(amp_upcast_f32(x), _EPS)
        loss = -jnp.sum(label * jnp.log(p), axis=-1, keepdims=True)
    else:
        idx = _index_label(label)
        loss = -jnp.log(jnp.maximum(_pick_label(x, idx), _EPS))
        ignore = op.attrs.get('ignore_index', -100)
        loss = jnp.where(idx[..., None] == ignore, jnp.zeros_like(loss),
                         loss)
    ctx.set(op, 'Y', loss)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, ))
def _fused_ce_bf16(logits, idx, ignore):
    return _fused_ce_fwd_math(logits, idx, ignore)[:2]


def _fused_ce_fwd_math(logits, idx, ignore):
    # reductions in f32 (exp/sum over a large vocab drifts in bf16); the
    # upcast fuses into the reduction so no f32 [N, V] tensor crosses HBM
    lf = logits.astype(jnp.float32)
    z = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
    valid = (idx != ignore)
    safe = jnp.where(valid, idx, 0)
    loss = jnp.where(valid[..., None], z - _pick_label(logits, safe), 0.0)
    p = jnp.exp(lf - z).astype(logits.dtype)    # residual stays bf16
    return loss, p, (p, safe, valid)


def _fused_ce_fwd(logits, idx, ignore):
    loss, p, res = _fused_ce_fwd_math(logits, idx, ignore)
    return (loss, p), res


def _fused_ce_bwd(ignore, res, gs):
    g_loss, _g_p = gs       # the Softmax output is not differentiated
    p, safe, valid = res
    onehot = jax.nn.one_hot(safe, p.shape[-1], dtype=jnp.float32)
    scale = jnp.where(valid[..., None], g_loss.astype(jnp.float32), 0.0)
    # dlogits lands bf16 DIRECTLY: its consumer is the bf16 vocab-matmul
    # backward, and emitting f32 here cost a [N, V] f32 round-trip plus
    # a convert (13% of the transformer step, round-4 xplane profile)
    d = ((p.astype(jnp.float32) - onehot) * scale).astype(p.dtype)
    return (d, jnp.zeros(safe.shape, jax.dtypes.float0))


_fused_ce_bf16.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@register_lowering('softmax_with_cross_entropy')
def _softmax_with_cross_entropy(ctx, op):
    raw = ctx.get(op, 'Logits')
    label = ctx.get(op, 'Label')
    if not op.attrs.get('soft_label', False) and raw.dtype == jnp.bfloat16:
        # AMP hard-label fast path: custom VJP keeps every [N, V]
        # HBM-crossing tensor (softmax residual, dlogits) in bf16
        idx = _index_label(label)
        loss, softmax = _fused_ce_bf16(
            raw, idx, op.attrs.get('ignore_index', -100))
        ctx.set(op, 'Softmax', softmax)
        ctx.set(op, 'Loss', loss)
        return
    # f32 path (and soft labels): plain composition, f32 throughout.
    # Softmax is an Intermediate output in the reference op (its grad
    # kernel never consumes a Softmax cotangent) and the bf16 fast path
    # above can't see one either — stop_gradient keeps the two paths'
    # autodiff semantics identical (ADVICE r4 #1)
    logits = amp_upcast_f32(raw)
    # jax.nn.log_softmax's own arithmetic, (x - max) - log(sum(exp(x -
    # max))), spelled out so that the hard label's term is picked from
    # the logits and not from log_p, a new [N, V] tensor
    shift = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    log_z = jnp.log(jnp.sum(jnp.exp(logits - shift), axis=-1,
                            keepdims=True))
    log_p = (logits - shift) - log_z
    softmax = jax.lax.stop_gradient(jnp.exp(log_p))
    if op.attrs.get('soft_label', False):
        loss = -jnp.sum(label * log_p, axis=-1, keepdims=True)
    else:
        idx = _index_label(label)
        loss = -((_pick_label(logits, idx) - shift) - log_z)
        ignore = op.attrs.get('ignore_index', -100)
        loss = jnp.where(idx[..., None] == ignore, jnp.zeros_like(loss),
                         loss)
    ctx.set(op, 'Softmax', softmax)
    ctx.set(op, 'Loss', loss)


@register_lowering('sigmoid_cross_entropy_with_logits')
def _sigmoid_ce(ctx, op):
    x = amp_upcast_f32(ctx.get(op, 'X'))
    label = ctx.get(op, 'Label')
    # max(x,0) - x*z + log(1+exp(-|x|)), numerically stable
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set(op, 'Out', loss)


@register_lowering('huber_loss')
def _huber_loss(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    delta = op.attrs['delta']
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    ctx.set(op, 'Residual', r)
    ctx.set(op, 'Out', loss)


@register_lowering('smooth_l1_loss')
def _smooth_l1(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    sigma = op.attrs.get('sigma', 1.0)
    in_w = ctx.get(op, 'InsideWeight')
    out_w = ctx.get(op, 'OutsideWeight')
    s2 = sigma * sigma
    d = x - y
    if in_w is not None:
        d = d * in_w
    ad = jnp.abs(d)
    l = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    ctx.set(op, 'Diff', d)
    if out_w is not None:
        l = l * out_w
    ctx.set(op, 'Out', jnp.sum(l, axis=tuple(range(1, l.ndim)),
                               keepdims=False)[:, None])


@register_lowering('log_loss')
def _log_loss(ctx, op):
    p = amp_upcast_f32(ctx.get(op, 'Predicted'))
    label = ctx.get(op, 'Labels')
    eps = op.attrs.get('epsilon', 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    ctx.set(op, 'Loss', loss)


@register_lowering('hinge_loss')
def _hinge_loss(ctx, op):
    logits = ctx.get(op, 'Logits')
    labels = ctx.get(op, 'Labels')
    ctx.set(op, 'Loss',
            jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0))


@register_lowering('rank_loss')
def _rank_loss(ctx, op):
    label = ctx.get(op, 'Label')
    left = amp_upcast_f32(ctx.get(op, 'Left'))
    right = amp_upcast_f32(ctx.get(op, 'Right'))
    d = left - right
    ctx.set(op, 'Out', jnp.log1p(jnp.exp(d)) - label * d)


@register_lowering('margin_rank_loss')
def _margin_rank_loss(ctx, op):
    label = ctx.get(op, 'Label')
    x1 = ctx.get(op, 'X1')
    x2 = ctx.get(op, 'X2')
    margin = op.attrs.get('margin', 0.0)
    out = jnp.maximum(-label * (x1 - x2) + margin, 0.0)
    ctx.set(op, 'Activated', (out > 0).astype(x1.dtype))
    ctx.set(op, 'Out', out)


@register_lowering('modified_huber_loss')
def _modified_huber_loss(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    z = (2.0 * y - 1.0) * x
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.where(z < 1.0, jnp.square(1.0 - z),
                               jnp.zeros_like(z)))
    ctx.set(op, 'IntermediateVal', z)
    ctx.set(op, 'Out', loss)


@register_lowering('kldiv_loss')
def _kldiv_loss(ctx, op):
    x = ctx.get(op, 'X')  # log-probabilities
    target = ctx.get(op, 'Target')
    loss = target * (jnp.log(jnp.maximum(target, _EPS)) - x)
    reduction = op.attrs.get('reduction', 'mean')
    if reduction == 'mean':
        loss = jnp.mean(loss)
    elif reduction == 'sum':
        loss = jnp.sum(loss)
    elif reduction == 'batchmean':
        loss = jnp.sum(loss) / x.shape[0]
    ctx.set(op, 'Loss', loss)
