"""Mixture-of-Experts op lowering (fluid.layers.moe_ffn).

The GShard DENSE dispatch formulation (parallel/moe.py moe_ffn): every
tensor is static-shaped, the expert dimension is a real array axis, and
parallelism comes from the expert weights' PartitionSpec over the 'ep'
mesh axis — GSPMD partitions the dispatch and combine einsums and
inserts the collectives, exactly the mechanism tensor-parallel fc uses.
(The hand-scheduled all_to_all variant for shard_map users lives in
parallel/moe.py moe_ffn_spmd; this lowering is the Program-IR path and
delegates its math to parallel.moe.moe_ffn so routing has one source of
truth.)
"""

import functools

import jax
import jax.numpy as jnp

from .activation_ops import relu2
from .registry import amp_cast_in, amp_cast_out, register_lowering
from ..parallel import moe as _moe


@register_lowering('moe_ffn')
def _moe_ffn(ctx, op):
    x = ctx.get(op, 'X')
    w1 = ctx.get(op, 'W1')
    w2 = ctx.get(op, 'W2')
    b1 = ctx.get(op, 'B1')
    b2 = ctx.get(op, 'B2')
    params = {
        'gate_w': ctx.get(op, 'GateW'),
        'w1': w1,
        # bias_attr=False omits the bias inputs entirely (no frozen
        # zero parameters); the math sees zeros
        'b1': b1 if b1 is not None else jnp.zeros(
            (w1.shape[0], w1.shape[2]), w1.dtype),
        'w2': w2,
        'b2': b2 if b2 is not None else jnp.zeros(
            (w2.shape[0], w2.shape[2]), w2.dtype),
    }
    cf = op.attrs.get('capacity_factor', 1.25)
    lead = x.shape[:-1]
    tok = x.reshape((-1, x.shape[-1]))
    y = _moe.moe_ffn(params, tok, capacity_factor=cf)
    ctx.set(op, 'Out', y.reshape(lead + (x.shape[-1], )))


# ---- top-k routing over experts of which this chip holds a range --------
#
# ``moe_router``: scores (sigmoid or softmax of a float32 product), the k
# largest of ``score + bias`` (the bias selects and never weighs), weights
# from the scores themselves, normalised over the selected and scaled.
# ``moe_experts``: for the (token, slot) pairs whose expert lies in the
# held range, ``w * W_down,e act(W_up,e x)``, summed a token.  What the
# experts held elsewhere would add is left out: the op is one chip's share
# of an expert-parallel layer, without its exchange.
# ``moe_bias_update``: the family's balancing of the selection bias from a
# pass's loads, for a builder to put in a forward-only program that a set-up
# runs, or AFTER a training program's optimizer ops (forward and backward
# then see one bias).
#
# Shapes are static and loads are not.  The pairs are sorted by expert,
# unheld ones last, into a buffer that holds EVERY pair (tokens x k rows),
# so none is ever dropped; the two products are grouped products over the
# held experts' row counts, whose cost follows the rows held and not the
# buffer: on an accelerator place JAX's Pallas TPU grouped matmul
# (megablox ``gmm`` / ``tgmm``, forward and both gradients), which visits
# only the tiles the groups cover; elsewhere ``jax.lax.ragged_dot`` (on the
# v5e four times slower at a light load: PERF.md section 6, PR 34).
# ``fluid.trace.lowering_choices('moe_experts')`` records the
# implementation, the buffer's rows and the tile.

def route(x, weight, bias, score_func, top_k, normalize, scale):
    """(indices [..., k] int32, weights [..., k] f32) of the k experts a
    token selects.  The product and the scores are float32 at the highest
    matmul precision whatever AMP says: selection is discrete, and a bf16
    score flips near-ties."""
    logits = jnp.matmul(x.astype(jnp.float32), weight.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score_func == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
    elif score_func == 'softmax':
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("moe_router: score_func is 'sigmoid' or 'softmax', "
                         'got %r' % (score_func, ))
    choice = jax.lax.stop_gradient(scores)
    if bias is not None:
        choice = choice + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), picked * scale


@register_lowering('moe_router')
def _moe_router(ctx, op):
    a = op.attrs
    idx, weight = route(
        ctx.get(op, 'X'), ctx.get(op, 'Weight'), ctx.get(op, 'Bias'),
        a.get('score_func', 'sigmoid'), int(a['top_k']),
        bool(a.get('norm_topk_prob', True)),
        float(a.get('routed_scaling_factor', 1.0)))
    ctx.set(op, 'TopkIdx', idx)
    ctx.set(op, 'TopkWeight', weight)


def balanced_bias(bias, idx, rate):
    """The family's auxiliary-loss-free balancing of the selection bias
    (``topk_method: noaux_tc``): after a step, an expert that got fewer
    pairs than the mean has its bias raised by ``rate``, one that got more
    has it lowered; no gradient is involved."""
    load = jnp.sum(idx.reshape(-1, 1) == jnp.arange(bias.shape[0]), axis=0,
                   dtype=jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


@register_lowering('moe_bias_update')
def _moe_bias_update(ctx, op):
    ctx.set(op, 'BiasOut', balanced_bias(
        ctx.get(op, 'Bias'), ctx.get(op, 'TopkIdx'),
        float(op.attrs['rate'])))


def sort_pairs(idx, first, held):
    """The (token, slot) pairs in the order the buffer holds them: those of
    expert ``first`` first, ..., of ``first + held - 1``, the unheld last.
    Returns (order [pairs]: the pair in each buffer row, place [pairs]:
    each pair's row, sizes [held] int32: rows an expert)."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    return order, place, sizes


# Into the buffer and out of it.  ``plan`` = (order, place, live): the
# buffer's row r holds pair order[r], pair p lies in row place[p], the
# first ``live`` rows hold the held experts' pairs, and pair p is token
# p // k's.  A product leaves whatever it finds in the rows past ``live``
# (it never visits them): they are cut where they are read, not by a pass
# over the buffer.  Into the buffer is a gather of token rows; out of it, a
# token's sum over its pairs' rows, walks only the tiles that hold live
# rows and adds each as a one-hot product (on the v5e a fifth of the time
# of gathering every pair's row and summing; PERF.md section 6, PR 34).
# Each is the other's gradient.

def _power_of_two_rows(rows, most):
    return next(t for t in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if t <= most and rows % t == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, ))
def _dispatch(x, plan, k):
    """[tokens, D] -> [rows, D]: each buffer row's token."""
    return x[plan[0] // k]


def _dispatch_fwd(x, plan, k):
    return _dispatch(x, plan, k), (plan, x.shape[0])


def _dispatch_bwd(k, res, g):
    plan, tokens = res
    return _combine(g, plan, k, tokens).astype(g.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _combine(y, plan, k, tokens):
    """[rows, D] -> [tokens, D] float32: the sum of a token's pairs' rows,
    a tile of rows at a time over the tiles that hold live rows."""
    order, _, live = plan
    tile = _power_of_two_rows(y.shape[0], 1024)
    exact = None if y.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def add_tile(i, out):
        rows = i * tile + jnp.arange(tile)
        token = jax.lax.dynamic_slice(order, (i * tile, ), (tile, )) // k
        part = jax.lax.dynamic_slice(y, (i * tile, 0), (tile, y.shape[1]))
        part = jnp.where((rows < live)[:, None], part, 0)
        mine = (jnp.arange(tokens)[:, None] == token[None, :]).astype(y.dtype)
        return out + jnp.dot(mine, part, precision=exact,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, (live + tile - 1) // tile, add_tile,
                             jnp.zeros((tokens, y.shape[1]), jnp.float32))


def _combine_fwd(y, plan, k, tokens):
    # an empty array carries y's dtype to the gradient
    return _combine(y, plan, k, tokens), (plan, y[:0])


def _combine_bwd(k, tokens, res, g):
    plan, like = res
    return _dispatch(g.astype(like.dtype), plan, k), None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _pair_values(v, plan):
    """[pairs] -> [rows]: each buffer row's pair's value."""
    return v[plan[0]]


def _pair_values_fwd(v, plan):
    return _pair_values(v, plan), plan


def _pair_values_bwd(plan, g):
    # each pair's row's gradient; none for a pair whose expert is not held
    _, place, live = plan
    return jnp.where(place < live, g[place], 0), None


_pair_values.defvjp(_pair_values_fwd, _pair_values_bwd)


def gmm_tile(rows, inner, outer, widest=1024):
    """(rows, contracted, columns) a tile of the Pallas grouped product:
    the largest power of two to 512 that divides the buffer's rows;
    ``widest`` of the contracted side and 1024 of the columns, or the whole
    side where it is shorter."""
    return (_power_of_two_rows(rows, 512), min(widest, inner),
            min(1024, outer))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, sizes, transposed, interpret):
    """The grouped product as JAX's Pallas TPU kernel (megablox ``gmm``):
    group offsets and each tile's group are prefetched scalars, and the
    grid's row extent is the number of tiles the groups cover, so tiles
    past the last held row are never visited.  ``transposed``: rhs is
    [G, N, K]."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    inner, outer = rhs.shape[1 + transposed], rhs.shape[2 - transposed]
    return gmm(lhs, rhs, sizes, lhs.dtype,
               gmm_tile(lhs.shape[0], inner, outer),
               transpose_rhs=transposed, interpret=interpret)


def _gmm_fwd(lhs, rhs, sizes, transposed, interpret):
    return _gmm(lhs, rhs, sizes, transposed, interpret), (lhs, rhs, sizes)


def _gmm_bwd(transposed, interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = gmm(g, rhs, sizes, lhs.dtype,
                gmm_tile(g.shape[0], g.shape[1], lhs.shape[1]),
                transpose_rhs=not transposed, interpret=interpret)
    # the weights' gradient, in the weights' own [G, K, N] or [G, N, K];
    # it keeps a float32 [contracted, columns] tile twice beside its
    # inputs': 512 wide, or the v5e's 16 MB of scoped VMEM do not hold it
    # inside the step program
    rows, cols = (g, lhs) if transposed else (lhs, g)
    d_rhs = tgmm(rows.swapaxes(0, 1), cols, sizes, jnp.float32,
                 gmm_tile(g.shape[0], rows.shape[1], cols.shape[1],
                          widest=512), interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_dot(lhs, rhs, sizes, impl, interpret=False, transposed=False):
    """Rows of ``lhs`` [rows, K] in runs of ``sizes`` [G], run g times
    ``rhs[g]`` [K, N] ([N, K] where ``transposed``); the rows past the last
    run are not computed.  f32 sums, AMP's operand and result types.
    ``impl``: 'pallas' | 'xla'."""
    lhs, rhs = amp_cast_in(lhs, rhs)
    if impl == 'pallas':
        return _gmm(lhs, rhs, sizes, transposed, interpret)
    return amp_cast_out(jax.lax.ragged_dot(
        lhs, rhs.swapaxes(1, 2) if transposed else rhs, sizes,
        preferred_element_type=jnp.float32))


def held_experts(x, idx, weight, w_up, w_down, first, impl='xla',
                 interpret=False):
    """One chip's share of the routed experts' output: x [T, D], idx and
    weight [T, k], w_up and w_down [E, F, D] the experts ``first`` to
    ``first + E - 1``, each ``W_down relu(W_up x)^2``.  Returns [T, D] in
    x's dtype.  A pair's weight multiplies its row between the two products
    (F wide, not D)."""
    k = idx.shape[1]
    order, place, sizes = sort_pairs(idx, first, w_up.shape[0])
    plan = (order, place, jnp.sum(sizes))
    hidden = grouped_dot(_dispatch(x, plan, k), w_up, sizes, impl,
                         interpret, transposed=True)
    act = relu2(hidden.astype(jnp.float32)) \
        * _pair_values(weight.reshape(-1), plan)[:, None]
    y = grouped_dot(act.astype(hidden.dtype), w_down, sizes, impl, interpret)
    return _combine(y, plan, k, x.shape[0]).astype(x.dtype)


def _pick_impl(ctx, op):
    """'pallas' on an accelerator place with no mesh axis larger than 1
    (GSPMD does not partition the kernel), else XLA's ``ragged_dot``."""
    impl = op.attrs.get('impl', 'auto')
    if impl not in ('auto', 'pallas', 'xla'):
        raise ValueError("moe_experts: impl is 'auto', 'pallas' or 'xla', "
                         'got %r' % (impl, ))
    if impl != 'auto':
        return impl
    meshed = ctx.mesh is not None and any(
        n > 1 for n in dict(ctx.mesh.shape).values())
    return 'xla' if ctx.on_cpu or meshed else 'pallas'


@register_lowering('moe_experts')
def _moe_experts(ctx, op):
    from ..fluid import trace
    x, idx = ctx.get(op, 'X'), ctx.get(op, 'TopkIdx')
    w_up, w_down = ctx.get(op, 'WUp'), ctx.get(op, 'WDown')
    if op.attrs.get('activation', 'relu2') != 'relu2':
        raise ValueError("moe_experts: the experts' activation is 'relu2', "
                         'got %r' % (op.attrs['activation'], ))
    lead, k = x.shape[:-1], idx.shape[-1]
    tokens = x.reshape((-1, x.shape[-1]))
    impl, rows = _pick_impl(ctx, op), tokens.shape[0] * k
    trace.note_lowering_choice(
        ctx.block.program, op.type, op.output('Out')[0],
        'pallas_gmm' if impl == 'pallas' else 'ragged_dot',
        buffer_rows=rows, held=w_up.shape[0],
        tile=(list(gmm_tile(rows, w_up.shape[2], w_up.shape[1]))
              if impl == 'pallas' else None))
    y = held_experts(
        tokens, idx.reshape((-1, k)),
        ctx.get(op, 'TopkWeight').reshape((-1, k)), w_up, w_down,
        int(op.attrs.get('first_expert', 0)), impl=impl,
        interpret=ctx.on_cpu)
    ctx.set(op, 'Out', y.reshape(lead + (x.shape[-1], )))
