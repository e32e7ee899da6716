"""Activation op lowerings (reference: paddle/fluid/operators/activation_op.cc).

Each is a one-liner into jnp/jax.nn; XLA fuses them into adjacent matmuls so
there is no bandwidth cost on TPU.
"""

import jax
import jax.numpy as jnp

from .registry import register_lowering


def _register_unary(name, fn):
    @register_lowering(name)
    def _lower(ctx, op, fn=fn):
        ctx.set(op, 'Out', fn(ctx.get(op, 'X')))


_register_unary('relu', jax.nn.relu)
_register_unary('sigmoid', jax.nn.sigmoid)
_register_unary('logsigmoid', jax.nn.log_sigmoid)
_register_unary('tanh', jnp.tanh)
_register_unary('tanh_shrink', lambda x: x - jnp.tanh(x))
_register_unary('exp', jnp.exp)
_register_unary('log', jnp.log)
_register_unary('sqrt', jnp.sqrt)
_register_unary('square', jnp.square)
_register_unary('abs', jnp.abs)
_register_unary('ceil', jnp.ceil)
_register_unary('floor', jnp.floor)
_register_unary('round', jnp.round)
_register_unary('reciprocal', jnp.reciprocal)
_register_unary('sin', jnp.sin)
_register_unary('cos', jnp.cos)
_register_unary('softsign', jax.nn.soft_sign)
_register_unary('softplus', jax.nn.softplus)
_register_unary('relu6', lambda x: jnp.clip(x, 0.0, 6.0))


def relu2(x):
    """Squared ReLU."""
    return jnp.square(jax.nn.relu(x))


_register_unary('relu2', relu2)


@register_lowering('leaky_relu')
def _leaky_relu(ctx, op):
    x = ctx.get(op, 'X')
    alpha = op.attrs.get('alpha', 0.02)
    ctx.set(op, 'Out', jnp.where(x >= 0, x, alpha * x))


@register_lowering('elu')
def _elu(ctx, op):
    x = ctx.get(op, 'X')
    alpha = op.attrs.get('alpha', 1.0)
    ctx.set(op, 'Out', jnp.where(x >= 0, x, alpha * (jnp.exp(x) - 1.0)))


@register_lowering('brelu')
def _brelu(ctx, op):
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out',
            jnp.clip(x, op.attrs.get('t_min', 0.0), op.attrs.get('t_max',
                                                                 24.0)))


@register_lowering('soft_relu')
def _soft_relu(ctx, op):
    x = ctx.get(op, 'X')
    t = op.attrs.get('threshold', 40.0)
    ctx.set(op, 'Out', jnp.log1p(jnp.exp(jnp.clip(x, -t, t))))


@register_lowering('hard_sigmoid')
def _hard_sigmoid(ctx, op):
    x = ctx.get(op, 'X')
    slope = op.attrs.get('slope', 0.2)
    offset = op.attrs.get('offset', 0.5)
    ctx.set(op, 'Out', jnp.clip(slope * x + offset, 0.0, 1.0))


@register_lowering('thresholded_relu')
def _thresholded_relu(ctx, op):
    x = ctx.get(op, 'X')
    t = op.attrs.get('threshold', 1.0)
    ctx.set(op, 'Out', jnp.where(x > t, x, jnp.zeros_like(x)))


@register_lowering('hard_shrink')
def _hard_shrink(ctx, op):
    x = ctx.get(op, 'X')
    t = op.attrs.get('threshold', 0.5)
    ctx.set(op, 'Out', jnp.where(jnp.abs(x) > t, x, jnp.zeros_like(x)))


@register_lowering('softshrink')
def _softshrink(ctx, op):
    x = ctx.get(op, 'X')
    lam = op.attrs.get('lambda', 0.5)
    ctx.set(op, 'Out',
            jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam,
                                                  jnp.zeros_like(x))))


@register_lowering('stanh')
def _stanh(ctx, op):
    x = ctx.get(op, 'X')
    a = op.attrs.get('scale_a', 0.67)
    b = op.attrs.get('scale_b', 1.7159)
    ctx.set(op, 'Out', b * jnp.tanh(a * x))


@register_lowering('swish')
def _swish(ctx, op):
    x = ctx.get(op, 'X')
    beta = op.attrs.get('beta', 1.0)
    ctx.set(op, 'Out', x * jax.nn.sigmoid(beta * x))


@register_lowering('softmax')
def _softmax(ctx, op):
    # fluid softmax normalizes the trailing axis (operators/softmax_op.cc);
    # the exp/sum runs f32 even for bf16 inputs (AMP) — over wide axes a
    # bf16 denominator drifts — and the output lands back in input dtype
    from .registry import amp_upcast_f32
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out',
            jax.nn.softmax(amp_upcast_f32(x), axis=-1).astype(x.dtype))


@register_lowering('prelu')
def _prelu(ctx, op):
    x = ctx.get(op, 'X')
    alpha = ctx.get(op, 'Alpha')
    mode = op.attrs.get('mode', 'all')
    if mode == 'all':
        a = jnp.reshape(alpha, ())
    elif mode == 'channel':
        a = jnp.reshape(alpha, (1, -1) + (1, ) * (x.ndim - 2))
    else:  # element
        a = jnp.reshape(alpha, (1, ) + x.shape[1:])
    ctx.set(op, 'Out', jnp.where(x > 0, x, a * x))


@register_lowering('maxout')
def _maxout(ctx, op):
    x = ctx.get(op, 'X')  # NCHW
    groups = op.attrs['groups']
    n, c, h, w = x.shape
    ctx.set(op, 'Out',
            jnp.max(jnp.reshape(x, (n, c // groups, groups, h, w)), axis=2))
