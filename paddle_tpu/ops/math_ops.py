"""Math op lowerings: matmul family, elementwise broadcast family, reductions.

Reference kernels: paddle/fluid/operators/mul_op.cc, matmul_op.cc,
elementwise_*_op.cc (broadcast semantics in elementwise_op_function.h),
reduce_*_op.cc, sum_op.cc, scale_op.cc, clip_op.cc.  On TPU these all lower
to jnp/lax inside one compiled block; matmuls hit the MXU.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_lowering, amp_cast_in, amp_matmul, \
    amp_harmonize, SAMPLE_MASK_NAME


def _flatten_2d(x, num_col_dims):
    """Flatten leading num_col_dims axes into rows, rest into cols
    (mul_op's x_num_col_dims semantics)."""
    rows = int(np.prod(x.shape[:num_col_dims])) if num_col_dims > 0 else 1
    return jnp.reshape(x, (rows, -1))


@jax.custom_vjp
def _mul_rows(x, y):
    """``x [..., K] @ y [K, N] -> [..., N]``, computed as mul's own 2-D
    product of x's rows.  Its gradient contracts the output gradient
    where it lies: flattening it to rows too would make XLA re-lay an
    N-D gradient that it keeps in another layout (NMT's head, PR 37)."""
    out = amp_matmul(jnp.reshape(x, (-1, x.shape[-1])), y)
    return jnp.reshape(out, x.shape[:-1] + out.shape[-1:])


def _mul_rows_fwd(x, y):
    return _mul_rows(x, y), (x, y)


def _mul_rows_bwd(res, g):
    # the generic VJP's arithmetic: products in the forward's compute
    # dtype (bf16 under AMP), each gradient cast back to its operand's
    x, y = res
    xc, yc = amp_cast_in(x, y)
    dt = jnp.result_type(xc, yc)
    g, xc, yc = g.astype(dt), xc.astype(dt), yc.astype(dt)
    lead = tuple(range(x.ndim - 1))
    dx = jax.lax.dot_general(g, yc, (((x.ndim - 1, ), (1, )), ((), ())))
    dy = jax.lax.dot_general(xc, g, ((lead, lead), ((), ())))
    return dx.astype(x.dtype), dy.astype(y.dtype)


_mul_rows.defvjp(_mul_rows_fwd, _mul_rows_bwd)


@register_lowering('mul')
def _mul(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    xn = op.attrs.get('x_num_col_dims', 1)
    yn = op.attrs.get('y_num_col_dims', 1)
    y2 = _flatten_2d(y, yn)
    k = y2.shape[0]
    # choose x's split point from the right so trailing dims contract with k;
    # handles LoD tensors whose padded runtime rank exceeds the desc rank
    # (a (B,T,D) @ (D,M) per-token projection where the graph said (N,D))
    split = x.ndim
    acc = 1
    while split > 0 and acc != k:
        split -= 1
        acc *= x.shape[split]
    if acc != k:
        split = xn  # fall back to declared semantics (will raise clearly)
    out_shape = tuple(x.shape[:split]) + tuple(y.shape[yn:])
    if x.ndim > 2 and split == x.ndim - 1:
        ctx.set(op, 'Out', jnp.reshape(_mul_rows(x, y2), out_shape))
        return
    x2 = jnp.reshape(x, (-1, int(np.prod(x.shape[split:], dtype=np.int64))
                         if split < x.ndim else 1))
    out = amp_matmul(x2, y2)
    ctx.set(op, 'Out', jnp.reshape(out, out_shape))


@register_lowering('matmul')
def _matmul(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    tx = op.attrs.get('transpose_X', False)
    ty = op.attrs.get('transpose_Y', False)
    alpha = op.attrs.get('alpha', 1.0)
    # fluid matmul: 1-D inputs get promoted; batch dims broadcast
    squeeze_front = squeeze_back = False
    if x.ndim == 1:
        x = x[None, :]
        squeeze_front = True
    if y.ndim == 1:
        y = y[:, None]
        squeeze_back = True
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    out = amp_matmul(x, y)
    if alpha != 1.0:
        out = out * jnp.asarray(alpha, out.dtype)
    if squeeze_front:
        out = jnp.squeeze(out, -2)
    if squeeze_back:
        out = jnp.squeeze(out, -1)
    ctx.set(op, 'Out', out)


def _bcast_y(x, y, axis):
    """Reference broadcast: Y's shape aligns into X starting at `axis`
    (elementwise_op_function.h); axis=-1 aligns trailing dims.  If the
    requested axis does not fit (e.g. LoD tensors lowered to padded rank-3
    where the graph assumed rank-2), fall back to trailing alignment."""
    if x.shape == y.shape:
        return y
    # trim trailing 1s of y (fluid allows y shape (C,1,1) matching mid dims)
    yshape = list(y.shape)
    while yshape and yshape[-1] == 1 and len(yshape) > 1:
        yshape = yshape[:-1]

    def _aligned(ax):
        if ax < 0 or ax + len(yshape) > x.ndim:
            return None
        if any(ys not in (1, x.shape[ax + i])
               for i, ys in enumerate(yshape)):
            return None
        return [1] * ax + yshape + [1] * (x.ndim - ax - len(yshape))

    if axis == -1 or axis is None:
        axis = x.ndim - len(yshape)
    new_shape = _aligned(axis)
    if new_shape is None:
        new_shape = _aligned(x.ndim - len(yshape))
    if new_shape is None:
        return y  # let jnp's own broadcasting rules apply (or raise)
    return jnp.reshape(y, new_shape)


def _register_elementwise(name, fn):
    @register_lowering('elementwise_' + name)
    def _lower(ctx, op, fn=fn):
        x = ctx.get(op, 'X')
        y = ctx.get(op, 'Y')
        axis = op.attrs.get('axis', -1)
        # the axis attr was chosen for X's DECLARED rank; when the runtime
        # rank differs (LoD tensor lowered to padded [B,T,...]) the only
        # meaningful alignment is trailing — never trust the stale axis
        xnames = op.input('X')
        if xnames:
            xd = ctx.var_desc(xnames[0])
            if xd is not None and xd.shape and len(xd.shape) != x.ndim:
                axis = -1
        y = _bcast_y(x, y, axis)
        # bf16 activation + f32 parameter (fc bias, scales) computes
        # bf16 under AMP — promotion would re-widen the activation
        x, y = amp_harmonize(x, y)
        ctx.set(op, 'Out', fn(x, y))


_register_elementwise('add', jnp.add)
_register_elementwise('sub', jnp.subtract)
_register_elementwise('mul', jnp.multiply)
_register_elementwise('div', jnp.divide)
_register_elementwise('max', jnp.maximum)
_register_elementwise('min', jnp.minimum)
_register_elementwise('pow', jnp.power)
_register_elementwise('mod', jnp.mod)
_register_elementwise('floordiv', jnp.floor_divide)


@register_lowering('sum')
def _sum(ctx, op):
    from .sparse import sparse_add
    xs = ctx.get_list(op, 'X')
    out = xs[0]
    for x in xs[1:]:
        out = sparse_add(out, x)
    ctx.set(op, 'Out', out)


@register_lowering('scale')
def _scale(ctx, op):
    from .sparse import SparseRows
    x = ctx.get(op, 'X')
    if isinstance(x, SparseRows):
        # SelectedRows scale (math/selected_rows_functor.cc) — loss-grad
        # 1/N scaling reaches sparse grads through this path
        if op.attrs.get('bias', 0.0) != 0.0:
            raise NotImplementedError(
                'scale with bias!=0 on a SelectedRows value')
        ctx.set(op, 'Out', x.scale(op.attrs.get('scale', 1.0)))
        return
    scale = jnp.asarray(op.attrs.get('scale', 1.0), x.dtype)
    bias = jnp.asarray(op.attrs.get('bias', 0.0), x.dtype)
    if op.attrs.get('bias_after_scale', True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.set(op, 'Out', out)


@register_lowering('mean')
def _mean(ctx, op):
    # fluid MeanOp fixes the output dim to {1} (operators/mean_op.cc)
    x = ctx.get(op, 'X')
    mask = _batch_mask_for(ctx, op, x)
    if mask is not None:
        # ragged-batch lot: rows past the real sample count are padding
        # the data-parallel executor appended for dp divisibility.  The
        # mean (and, through jax.vjp, every gradient flowing out of it)
        # must weight by the REAL count: pad rows contribute 0 to the
        # numerator and nothing to the denominator, so the padded step
        # equals the unpadded step bit-for-bit in expectation.
        m = mask.astype(x.dtype).reshape(
            (mask.shape[0], ) + (1, ) * (x.ndim - 1))
        per_row = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
        denom = jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1) * per_row
        ctx.set(op, 'Out', jnp.reshape(jnp.sum(x * m) / denom, (1, )))
        return
    ctx.set(op, 'Out', jnp.reshape(jnp.mean(x), (1, )))


def _reduce_dims(x, op):
    if op.attrs.get('reduce_all', False):
        return None
    dim = op.attrs.get('dim', [0])
    if isinstance(dim, int):
        dim = [dim]
    return tuple(d % x.ndim for d in dim)


def _batch_mask_for(ctx, op, x):
    """The ragged-batch sample mask, iff it applies to this op's input:
    the value must be BATCH-LED (derived from the feeds with the batch
    still on dim 0, per run_op's provenance tracking) — a weight-derived
    tensor (weight decay on a [56, ...] parameter, or mean(square(w)))
    whose dim 0 merely coincides with the padded batch size never
    masks."""
    mask = ctx.env.get(SAMPLE_MASK_NAME)
    if mask is None or x.ndim < 1:
        return None
    name = op.input('X')[0]
    if x.shape[0] == mask.shape[0] and name in ctx.batch_led:
        return mask
    if (name in ctx.batch_tainted and x.shape[0] != mask.shape[0]
            and x.shape[0] % mask.shape[0] == 0):
        # batch ancestry but a [B*k] leading dim: a flattened batch
        # (reshape [B,T,..] -> [B*T,..] before the loss) — the sample
        # mask cannot reach this reduction, so the padding rows WILL
        # contribute.  Trace-time warning (once per compile), loud
        # enough to catch the seq-model CE idiom on ragged lots.
        import warnings
        warnings.warn(
            'ragged-batch mask cannot reach %r over %r: its leading dim '
            '%d looks like a FLATTENED batch (mask covers %d rows) — '
            'padding rows will contribute to this reduction; keep the '
            'batch on dim 0 through the loss, or drop the ragged tail'
            % (op.type, name, x.shape[0], mask.shape[0]))
    return None


def _register_reduce(name, fn):
    @register_lowering('reduce_' + name)
    def _lower(ctx, op, fn=fn):
        x = ctx.get(op, 'X')
        dims = _reduce_dims(x, op)
        keep = op.attrs.get('keep_dim', False)
        # ragged-batch lots: reduce_mean/reduce_sum over the batch dim
        # must not count the padding rows (same contract as the 'mean'
        # op; max/min are naturally immune — the padding replicates a
        # real row — and prod over batch is not masked)
        if name in ('mean', 'sum') and (dims is None or 0 in dims):
            mask = _batch_mask_for(ctx, op, x)
            if mask is not None:
                m = mask.astype(x.dtype).reshape(
                    (mask.shape[0], ) + (1, ) * (x.ndim - 1))
                out = jnp.sum(x * m, axis=dims, keepdims=keep)
                if name == 'mean':
                    axes = tuple(range(x.ndim)) if dims is None else dims
                    other = int(np.prod([x.shape[a] for a in axes
                                         if a != 0])) if axes else 1
                    out = out / (jnp.maximum(
                        jnp.sum(mask.astype(x.dtype)), 1) * other)
                if dims is None and not keep:
                    out = jnp.reshape(out, (1, ))
                ctx.set(op, 'Out', out)
                return
        out = fn(x, axis=dims, keepdims=keep)
        if dims is None and not keep:
            out = jnp.reshape(out, (1, ))  # fluid keeps rank-1 [1] output
        ctx.set(op, 'Out', out)


_register_reduce('sum', jnp.sum)
_register_reduce('mean', jnp.mean)
_register_reduce('max', jnp.max)
_register_reduce('min', jnp.min)
_register_reduce('prod', jnp.prod)


@register_lowering('clip')
def _clip(ctx, op):
    x = ctx.get(op, 'X')
    lo = op.attrs.get('min', float('-inf'))
    hi = op.attrs.get('max', float('inf'))
    ctx.set(op, 'Out', jnp.clip(x, lo, hi))


@register_lowering('clip_by_norm')
def _clip_by_norm(ctx, op):
    x = ctx.get(op, 'X')
    max_norm = op.attrs['max_norm']
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12),
                      jnp.ones((), x.dtype))
    ctx.set(op, 'Out', x * scale)


@register_lowering('squared_l2_norm')
def _squared_l2_norm(ctx, op):
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out', jnp.reshape(jnp.sum(jnp.square(x)), (1, )))


@register_lowering('squared_l2_distance')
def _squared_l2_distance(ctx, op):
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    sub = x - y
    ctx.set(op, 'sub_result', sub)
    ctx.set(op, 'Out', jnp.sum(jnp.square(sub), axis=-1, keepdims=True))


@register_lowering('cumsum')
def _cumsum(ctx, op):
    x = ctx.get(op, 'X')
    axis = op.attrs.get('axis', -1)
    exclusive = op.attrs.get('exclusive', False)
    reverse = op.attrs.get('reverse', False)
    if reverse:
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if exclusive:
        out = out - x
    if reverse:
        out = jnp.flip(out, axis)
    ctx.set(op, 'Out', out)


@register_lowering('pow')
def _pow(ctx, op):
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out', jnp.power(x, op.attrs.get('factor', 1.0)))


@register_lowering('sign')
def _sign(ctx, op):
    ctx.set(op, 'Out', jnp.sign(ctx.get(op, 'X')))


@register_lowering('l1_norm')
def _l1_norm(ctx, op):
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out', jnp.sum(jnp.abs(x)))


@register_lowering('norm')
def _norm(ctx, op):
    x = ctx.get(op, 'X')
    axis = op.attrs.get('axis', -1)
    eps = op.attrs.get('epsilon', 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    ctx.set(op, 'Norm', norm)
    ctx.set(op, 'Out', x / norm)


@register_lowering('cos_sim')
def _cos_sim(ctx, op):
    """Row-wise cosine similarity (reference operators/cos_sim_op.cc);
    Y broadcasts when it has one row."""
    x = ctx.get(op, 'X')
    y = ctx.get(op, 'Y')
    eps = 1e-12
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    dot = jnp.sum(x * y, axis=-1, keepdims=True)  # broadcasts [1,D] y
    ctx.set(op, 'Out', dot / jnp.maximum(xn * yn, eps))
    ctx.set(op, 'XNorm', xn)
    ctx.set(op, 'YNorm', yn)
