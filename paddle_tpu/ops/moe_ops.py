"""Mixture-of-Experts op lowerings: ``moe_ffn``, and the routed ops
``moe_router``, ``moe_experts`` and ``moe_bias_update``.

``moe_ffn`` (fluid.layers.moe_ffn) is top-1 routing in the GShard DENSE
dispatch formulation (parallel/moe.py moe_ffn), with a capacity factor:
every tensor is static-shaped, the expert dimension is a real array axis,
and parallelism comes from the expert weights' PartitionSpec over the 'ep'
mesh axis — GSPMD partitions the dispatch and combine einsums and inserts
the collectives, exactly the mechanism tensor-parallel fc uses.  (The
hand-scheduled all_to_all variant for shard_map users lives in
parallel/moe.py moe_ffn_spmd; this lowering is the Program-IR path and
delegates its math to parallel.moe.moe_ffn so routing has one source of
truth.)

The routed ops (below ``moe_ffn``) are top-k routing with no capacity:
``moe_router`` selects a token's k experts and their weights,
``moe_experts`` computes one chip's held range of experts over a buffer
that holds every (token, slot) pair, with passes whose cost follows the
pairs held, and ``moe_bias_update`` balances the selection bias.
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


# The passes over the buffer.  ``plan`` = (order, place, live): the
# buffer's row r holds pair order[r], pair p lies in row place[p], the
# first ``live`` rows hold the held experts' pairs, and pair p is token
# p // k's.  No pass visits a row past the tile that holds the last live
# row: the grouped products skip those tiles, and every other pass is a
# ``fori_loop`` over the live tiles alone, so its cost follows the rows held
# as the products' does (four passes over all tokens x k rows took about 6
# ms of the v5e's step at 1536 live rows of 24576: PERF.md section 6, PR
# 38).  What a pass leaves in the rows past ``live`` is cut where it is
# read: by the products' groups, and by ``live`` in the sums and the pair
# weights' gradient.  A buffer that a forward pass fills starts as zeros,
# written whole once: the gradient op computes the forward again, and XLA
# merges that with the forward op only where the two are one computation,
# which two ``jax.lax.empty`` never are (the gradient op would gather, run
# the up product and activate again).  The one buffer that only the
# gradient fills, the output's gradient dispatched, starts as
# ``jax.lax.empty``: on an accelerator nothing writes it whole, and its
# rows past the last live tile hold whatever the memory held, as a
# product's output rows there do.
# - Into the buffer (``_dispatch``: the tokens forward, the output's
#   gradient backward) gathers each live tile's token rows, cast to the
#   products' operand type.
# - Out of it (``_combine``: the output forward, the tokens' gradient
#   backward) adds each live tile to its tokens' sums as a one-hot product
#   (on the v5e a fifth of the time of gathering every pair's row and
#   summing; PERF.md section 6, PR 34).  Each is the other's gradient.
# - Between the products (``_activate``) ``relu(h)^2`` times the row's
#   pair weight is written tile by tile; its gradient overwrites the
#   incoming gradient's live tiles in place.

def _power_of_two_rows(rows, most):
    return next(t for t in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if t <= most and rows % t == 0)


def row_tile(rows):
    """Rows a tile of the buffer's passes and of the Pallas grouped product:
    the largest power of two to 512 that divides the buffer's rows."""
    return _power_of_two_rows(rows, 512)


def _live_tiles(live, tile):
    return (live + tile - 1) // tile


def _live_rows(part, at, live):
    """A tile that starts at row ``at`` with its rows past ``live`` zeroed."""
    rows = at + jnp.arange(part.shape[0])
    return jnp.where((rows < live)[:, None], part, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dispatch(x, plan, k, dtype, alloc):
    """[tokens, D] -> [rows, D] in ``dtype``: each live tile's rows hold
    their pairs' tokens, the rows past the last live tile what
    ``alloc(shape, dtype)`` left there (``jnp.zeros`` or
    ``jax.lax.empty``)."""
    order, _, live = plan
    tile = row_tile(order.shape[0])

    def put_tile(i, buf):
        token = jax.lax.dynamic_slice_in_dim(order, i * tile, tile) // k
        return jax.lax.dynamic_update_slice_in_dim(
            buf, x[token].astype(dtype), i * tile, 0)

    return jax.lax.fori_loop(
        0, _live_tiles(live, tile), put_tile,
        alloc((order.shape[0], x.shape[1]), dtype))


def _dispatch_fwd(x, plan, k, dtype, alloc):
    # an empty array carries the tokens and x's dtype to the gradient
    return _dispatch(x, plan, k, dtype, alloc), (plan, x[:, :0])


def _dispatch_bwd(k, dtype, alloc, res, g):
    plan, like = res
    return _combine(g, plan, k, like.shape[0]).astype(like.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _combine(y, plan, k, tokens):
    """[rows, D] -> [tokens, D] float32: the sum of a token's pairs' rows,
    a tile of rows at a time over the tiles that hold live rows."""
    order, _, live = plan
    tile = _power_of_two_rows(y.shape[0], 1024)
    exact = None if y.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def add_tile(i, out):
        token = jax.lax.dynamic_slice_in_dim(order, i * tile, tile) // k
        part = _live_rows(
            jax.lax.dynamic_slice_in_dim(y, i * tile, tile), i * tile, live)
        mine = (jnp.arange(tokens)[:, None] == token[None, :]).astype(y.dtype)
        return out + jnp.dot(mine, part, precision=exact,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, _live_tiles(live, tile), add_tile,
                             jnp.zeros((tokens, y.shape[1]), jnp.float32))


def _combine_fwd(y, plan, k, tokens):
    # an empty array carries y's dtype to the gradient
    return _combine(y, plan, k, tokens), (plan, y[:0])


def _combine_bwd(k, tokens, res, g):
    plan, like = res
    return _dispatch(g, plan, k, like.dtype, jax.lax.empty), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _weighted_relu2(h, w):
    """A tile's ``relu(h)^2`` times each row's weight, in float32, returned
    in h's dtype."""
    return (relu2(h.astype(jnp.float32)) * w[:, None]).astype(h.dtype)


@jax.custom_vjp
def _activate(hidden, weight, plan):
    """[rows, F], [pairs] -> [rows, F]: each live row's ``relu(h)^2`` times
    its pair's weight; zeros past ``live``."""
    order, _, live = plan
    tile = row_tile(hidden.shape[0])

    def act_tile(i, out):
        at = i * tile
        part = _weighted_relu2(
            jax.lax.dynamic_slice_in_dim(hidden, at, tile),
            weight[jax.lax.dynamic_slice_in_dim(order, at, tile)])
        return jax.lax.dynamic_update_slice_in_dim(
            out, _live_rows(part, at, live), at, 0)

    return jax.lax.fori_loop(0, _live_tiles(live, tile), act_tile,
                             jnp.zeros_like(hidden))


def _activate_fwd(hidden, weight, plan):
    return _activate(hidden, weight, plan), (hidden, weight, plan)


def _activate_bwd(res, g):
    hidden, weight, plan = res
    order, place, live = plan
    tile = row_tile(hidden.shape[0])

    def grad_tile(i, grads):
        d_hidden, d_rows = grads
        at = i * tile
        _, vjp = jax.vjp(
            _weighted_relu2, jax.lax.dynamic_slice_in_dim(hidden, at, tile),
            weight[jax.lax.dynamic_slice_in_dim(order, at, tile)])
        # the tile is read once, before it is overwritten: with two fused
        # readers of the buffer XLA copies the whole buffer each iteration
        dh, dw = vjp(jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(d_hidden, at, tile)))
        return (jax.lax.dynamic_update_slice_in_dim(
                    d_hidden, _live_rows(dh, at, live), at, 0),
                jax.lax.dynamic_update_slice_in_dim(d_rows, dw, at, 0))

    d_hidden, d_rows = jax.lax.fori_loop(
        0, _live_tiles(live, tile), grad_tile,
        (g, jnp.zeros(hidden.shape[:1], weight.dtype)))
    # each pair's row's gradient; none for a pair whose expert is not held
    return d_hidden, jnp.where(place < live, d_rows[place], 0), None


_activate.defvjp(_activate_fwd, _activate_bwd)


def gmm_tile(rows, inner, outer, widest=1024):
    """(rows, contracted, columns) a tile of the Pallas grouped product:
    ``row_tile(rows)``; ``widest`` of the contracted side and 1024 of the
    columns, or the whole side where it is shorter."""
    return (row_tile(rows), min(widest, inner), min(1024, outer))


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
    operand = amp_cast_in(x[:0])[0].dtype     # the products' (AMP's) type
    hidden = grouped_dot(_dispatch(x, plan, k, operand, jnp.zeros), w_up,
                         sizes, impl, interpret, transposed=True)
    act = _activate(hidden, weight.reshape(-1), plan)
    y = grouped_dot(act, w_down, sizes, impl, interpret)
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
        buffer_rows=rows, held=w_up.shape[0], pass_rows=row_tile(rows),
        tile=(list(gmm_tile(rows, w_up.shape[2], w_up.shape[1]))
              if impl == 'pallas' else None))
    y = held_experts(
        tokens, idx.reshape((-1, k)),
        ctx.get(op, 'TopkWeight').reshape((-1, k)), w_up, w_down,
        int(op.attrs.get('first_expert', 0)), impl=impl,
        interpret=ctx.on_cpu)
    ctx.set(op, 'Out', y.reshape(lead + (x.shape[-1], )))
