"""Kimi delta attention's chunked delta rule (``ops/linear_attn_ops.py``:
``kda``) as two Pallas TPU kernels, the forward and its gradient.  No
within-chunk quantity reaches HBM: the decay sums, the split decay
factors, the scores A and P, the inverse of ``I + A``, W, U and the
chunk's map of the state live in VMEM; the forward writes the states
ENTERING each chunk once, for the gradient.

Layout: the op's own.  Q, K, G [B, L, H*d_k] and V, O [B, L, H*d_v] are
the row-major arrays a reshape of [B, L, H, d] gives for free: a block of
positions by 128 lanes is one head (d_k = d_v = 128), so nothing is
transposed before or after.  Beta comes in as [B, L, H] in its own dtype
(a program picks its head's column and widens it), dBeta leaves as a row
a chunk, [B, H, chunks, 1, C] float32.  Beta is not widened outside the
kernel: XLA may compute a float32 copy from the float32 value before the
bf16 rounding (it allows itself the excess precision), and the kernel
would then read a Beta that differs from the one the program holds.

Grid (B, H, blocks of chunks), the last sequential: a program takes one
head over ``m`` chunks (a loop inside it, up to four chunks a step), the
[d_k, d_v] float32 state rides from chunk to chunk in a VMEM scratch.
Per chunk of C positions,
with Gamma the cumulative log-decay from the chunk's start (the module
docstring of ``ops/linear_attn_ops.py`` has the algebra):

* forward: q and k made unit; Gamma by a running sum over sublanes; the
  two score products A and P split at sub-chunks of ``SUB`` positions
  exactly as the XLA form splits them (one [2 SUB, d_k] x [d_k, C]
  product a sub-chunk); ``T = (I + A)^-1`` (``_inverse``); ``[W | U] =
  T [b k e^Gamma | b v]``; ``o = (q e^Gamma) S + P (U - W S)``; ``S <-
  e^Gamma_C S + (k e^(Gamma_C - Gamma))^T (U - W S)``.
* gradient: the chunks in reverse, the gradient of the state LEAVING
  the chunk in the scratch.  Every within-chunk quantity is made again
  from the inputs and the saved entering state; ``T`` serves both
  ``T X`` and ``T^T dY`` (no second solve).  dGamma gathers every term
  and dG is its running sum from the chunk's end; the sub-chunks' split
  points and the chunk's last decay add their own shares.

``_inverse``: the unit lower-triangular ``I + A`` inverted by recursive
doubling of its diagonal blocks, ``T_2b = T_b - T_b E_b T_b`` with
``E_b`` the blocks of A that join two neighbouring b-blocks: blocked
forward substitution, every step a [C, C] product, the diagonal blocks
of 1, 2, 4, 8 (``SUB``) done alike for all blocks at once and then
merged up to C.  No power of A is formed.  Two chunks are inverted as
one block-diagonal [2 C, 2 C] matrix: one chain of ten products at the
highest precision (those products are about 45% of the kernels' time)
then serves both, and a step of the loop takes two pairs, whose chains
interleave (PERF.md section 6 has the times of each form on the v5e).

Precision is the XLA form's: the two score products (and their
gradients) take their operands in the products' dtype (bf16 under AMP)
with float32 sums; the gate, the decay sums, every ``exp``, the solve,
the state and every product with the state are float32 at the highest
precision.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..linear_attn_ops import SUB   # the split of a pair's decay factor

__all__ = ['kda', 'kda_grad', 'block', 'vmem_bytes', 'LANES', 'VMEM_LIMIT']

LANES = 128          # d_k and d_v: one head a block of lanes
_BLOCK_ROWS = 512    # positions of Q a program takes (a loop inside it)
VMEM_LIMIT = 48 << 20          # of the v5e's 128 MiB
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1, ), (1, )), ((), ()))     # A B^T
_TN = (((0, ), (0, )), ((), ()))     # A^T B
_NN = (((1, ), (0, )), ((), ()))     # A B

# chunk, the products' dtype (a name), chunks a step of a program's loop
# (1, 2 or 4), interpret
_Cfg = collections.namedtuple('_Cfg', 'c dtype pack interpret')


def _dot(a, b, dims=_NN, dtype=_F32):
    """A product with float32 sums: operands in ``dtype``, at the highest
    precision where that is float32."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=_HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(x, reverse=False):
    """Sums along the rows (positions) of x [C, d], from the first row
    (``reverse``: from the last), in float32 adds: log2(C) rolls."""
    c = x.shape[0]
    row = _iota(x.shape, 0)
    s = 1
    while s < c:
        if reverse:
            x = x + jnp.where(row < c - s, pltpu.roll(x, c - s, 0), 0.0)
        else:
            x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _doubling_masks(rows, block):
    """The identity and, for each step of ``_inverse``, where a's blocks
    join two neighbouring blocks of 2^level (the first step's 2^0 with the
    identity): loop-invariant, made once a program."""
    r, col = _iota((rows, rows), 0), _iota((rows, rows), 1)
    masks, level = [r == col], 0
    while 1 << level < block:
        masks.append((r >> level + 1 == col >> level + 1)
                     & (r >> level != col >> level))
        level += 1
    return masks


def _inverse(a, masks):
    """``(I + a)^-1`` for ``a`` [R, R] strictly lower and zero outside its
    diagonal blocks (``masks``: ``_doubling_masks``)."""
    t = masks[0].astype(_F32) - jnp.where(masks[1], a, 0.0)
    for joins in masks[2:]:
        t = t - _dot(_dot(t, jnp.where(joins, a, 0.0)), t)
    return t


def _wy(t, x1, x2):
    """[W | U] = T [X1 | X2]: the decayed keys and the values, each times
    b, through the inverse of ``I + A``."""
    return _dot(t, jnp.concatenate([x1, x2], axis=1))


def _unit(x, eps=1e-6):
    """(x over its length, 1 / length), float32, along the lanes."""
    n = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + eps)
    return x * n, n


def _lane_rows(x):
    """The sums over the lanes of x [R, n] as a row [1, R] (the row form a
    store of dBeta wants)."""
    return jnp.sum(jnp.transpose(x), axis=0, keepdims=True)


def _columns_of(x):
    """[1, n] row -> [n, n]: row i holds x[i] in every lane."""
    n = x.shape[1]
    return jnp.transpose(jnp.broadcast_to(x, (n, n)))


class _Chunk(object):
    """Every within-chunk quantity of one head, made from its inputs:
    q, k, v [C, 128] as stored, g [C, 128] float32, beta [C, 1]."""

    def __init__(self, q, k, v, g, beta, cfg):
        c, dt = cfg.c, jnp.dtype(cfg.dtype)
        self.q, self.k = q.astype(_F32), k.astype(_F32)
        self.v, self.beta = v.astype(_F32), beta
        self.qn, self.nq = _unit(self.q)
        self.scale = q.shape[1] ** -0.5
        self.qn = self.qn * self.scale
        self.kn, self.nk = _unit(self.k)
        gam = _running_sum(g)
        self.gam = gam
        # Gamma before each sub-chunk's first position (0 before the
        # chunk's): the split point of a pair's decay factor
        excl = gam - g
        subs = c // SUB
        self.starts = [excl[i * SUB:i * SUB + 1] for i in range(subs)]
        row = _iota(gam.shape, 0)
        start_rows = jnp.zeros_like(gam)
        for i in range(1, subs):
            start_rows = jnp.where(row >= i * SUB, self.starts[i],
                                   start_rows)
        self.e_in = jnp.exp(gam - start_rows)     # to the split point
        self.rk, self.rq = self.kn * self.e_in, self.qn * self.e_in
        self.keys = []     # each sub-chunk's keys decayed from its split
        sk, sq = [], []
        for i in range(subs):
            f = jnp.exp(jnp.where(row >= (i + 1) * SUB, -jnp.inf,
                                  self.starts[i] - gam))
            keys = self.kn * f
            self.keys.append((keys, f))
            rows = slice(i * SUB, (i + 1) * SUB)
            both = _dot(jnp.concatenate([self.rk[rows], self.rq[rows]]),
                        keys, _NT, dt)             # [2 SUB, C]
            sk.append(both[:SUB])
            sq.append(both[SUB:])
        r, s = _iota((c, c), 0), _iota((c, c), 1)
        self.lower, self.upto = s < r, s <= r
        self.sk = jnp.where(self.lower, jnp.concatenate(sk), 0.0)
        self.p = jnp.where(self.upto, jnp.concatenate(sq), 0.0)
        self.a = self.sk * beta
        self.eg = jnp.exp(gam)
        self.x1 = beta * self.kn * self.eg
        self.x2 = beta * self.v
        self.last = jnp.sum(jnp.where(row == c - 1, gam, 0.0), axis=0,
                            keepdims=True)        # Gamma_C, [1, d_k]
        self.kd_e = jnp.exp(self.last - gam)
        self.kd = self.kn * self.kd_e             # keys to the chunk's end
        self.qe = self.qn * self.eg

    def leaving(self, s, d):
        """``S' = e^Gamma_C S + Kd^T D`` for D = U - W S."""
        return _columns_of(jnp.exp(self.last)) * s + _dot(self.kd, d, _TN)


def _solve(chunks, masks):
    """The inverse T of ``I + A`` for each pair of the chunks, side by side
    (their A block-diagonal: one [2 C, 2 C] inverse, whose products fill
    the MXU better than two of [C, C]; the pairs' chains of products are
    independent, and interleave), and each chunk's ``w``, ``u`` and
    ``wu``.  ``masks``: ``_doubling_masks`` of one chunk's and of a
    pair's rows.  [(the pair's first index in ``chunks``, T, the pair)]."""
    c = chunks[0].a.shape[0]
    out = []
    for first in range(0, len(chunks), 2):
        pair = chunks[first:first + 2]
        a = pair[0].a
        if len(pair) > 1:
            zero = jnp.zeros_like(a)
            a = jnp.concatenate([jnp.concatenate([a, zero], axis=1),
                                 jnp.concatenate([zero, pair[1].a], axis=1)])
        t = _inverse(a, masks[len(pair) - 1])
        wu = _wy(t, jnp.concatenate([ch.x1 for ch in pair]),
                 jnp.concatenate([ch.x2 for ch in pair]))
        for j, ch in enumerate(pair):
            ch.wu = wu[j * c:(j + 1) * c]
            ch.w, ch.u = ch.wu[:, :LANES], ch.wu[:, LANES:]
        out.append((first, t, pair))
    return out


def _chunk_rows(ci, c):
    return pl.ds(pl.multiple_of(ci * c, c), c)


def _beta_column(beta_ref, h, at):
    """Head ``h``'s column of rows ``at`` of a [rows, H] block: [C, 1]."""
    b = beta_ref[0, at, :].astype(_F32)
    return jnp.sum(jnp.where(_iota(b.shape, 1) == h, b, 0.0), axis=1,
                   keepdims=True)


# ---- forward -----------------------------------------------------------

def _chunks(refs, beta_ref, h, first, cfg):
    """The ``cfg.pack`` chunks from chunk ``first`` of the block."""
    out = []
    for j in range(cfg.pack):
        at = _chunk_rows(first + j, cfg.c)
        out.append(_Chunk(*(r[0, at, :] for r in refs),
                          _beta_column(beta_ref, h, at), cfg))
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref, s_scr,
                *, cfg):
    h, c = pl.program_id(1), pl.program_id(2)

    masks = [_doubling_masks(n * cfg.c, cfg.c) for n in (1, 2)]

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    def group(gi, carry):
        chunks = _chunks((q_ref, k_ref, v_ref, g_ref), beta_ref, h,
                         gi * cfg.pack, cfg)
        _solve(chunks, masks)
        s = s_scr[...]
        for j, ch in enumerate(chunks):
            ci = gi * cfg.pack + j
            st_ref[0, ci, 0] = s
            # W S and Qe S in one product
            both = _dot(jnp.concatenate([ch.w, ch.qe]), s)
            d = ch.u - both[:cfg.c]
            o_ref[0, _chunk_rows(ci, cfg.c), :] = (
                both[cfg.c:] + _dot(ch.p, d)).astype(o_ref.dtype)
            s = ch.leaving(s, d)
        s_scr[...] = s
        return carry

    jax.lax.fori_loop(0, st_ref.shape[1] // cfg.pack, group, 0)


# ---- gradient ----------------------------------------------------------

def _unit_grad(x, n, dx_unit):
    """d x of ``x * n`` (n = 1 / |x|) for its gradient ``dx_unit``."""
    u = x * n
    return n * (dx_unit - u * jnp.sum(u * dx_unit, axis=1, keepdims=True))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_scr, *, cfg):
    h, c = pl.program_id(1), pl.program_id(2)
    cc, groups = cfg.c, st_ref.shape[1] // cfg.pack
    dt = jnp.dtype(cfg.dtype)

    masks = [_doubling_masks(n * cc, cc) for n in (1, 2)]

    @pl.when(c == 0)         # the last chunks: nothing follows them
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    def group(i, carry):
        first = (groups - 1 - i) * cfg.pack
        chunks = _chunks((q_ref, k_ref, v_ref, g_ref), beta_ref, h, first,
                         cfg)
        solved = _solve(chunks, masks)
        # the state's gradient through the chunks, last first: ds is the
        # gradient of the state LEAVING the chunk at hand
        ds, back = ds_scr[...], [None] * cfg.pack
        for j in reversed(range(cfg.pack)):
            ch = chunks[j]
            s = st_ref[0, first + j, 0]
            do = do_ref[0, _chunk_rows(first + j, cc), :].astype(_F32)
            d = ch.u - _dot(ch.w, s)
            lam = jnp.exp(ch.last)
            # S' = Lambda S + Kd^T D and o = Qe S + P D
            dd = _dot(ch.kd, ds) + _dot(ch.p, do, _TN)
            dkd = _dot(d, ds, _NT)
            dlast = _lane_rows(s * ds) * lam
            dp = jnp.where(ch.upto, _dot(do, d, _NT), 0.0)
            # D = U - W S: dQe and dW in one product, Qe^T dO - W^T dD in
            # another
            both = _dot(jnp.concatenate([do, dd]), s, _NT)
            dqe, dw = both[:cc], -both[cc:]
            ds = _columns_of(lam) * ds + _dot(
                jnp.concatenate([ch.qe, ch.w]), jnp.concatenate([do, -dd]),
                _TN)
            back[j] = (dd, dkd, dlast, dp, dqe, dw)
        ds_scr[...] = ds
        # [W | U] = T [X1 | X2], a pair's chunks at once
        for at, t, pair in solved:
            dx_all = _dot(t, jnp.concatenate([
                jnp.concatenate([back[at + j][5], back[at + j][0]], axis=1)
                for j in range(len(pair))]), _TN)
            for j, ch in enumerate(pair):
                dd, dkd, dlast, dp, dqe, dw = back[at + j]
                _chunk_grads(ch, dx_all[j * cc:(j + 1) * cc], dkd, dlast,
                             dp, dqe, dt, first + at + j, refs=(
                                 dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref))
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


def _chunk_grads(ch, dx, dkd, dlast, dp, dqe, dt, ci, refs):
    """What is left of one chunk's gradient once dX = T^T [dW | dU] is
    known: through A, the score products, the decays and the norms, into
    the chunk's rows of dQ, dK, dV, dG and dBeta."""
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref = refs
    cc = ch.gam.shape[0]
    at = _chunk_rows(ci, cc)
    beta = ch.beta
    da = -jnp.where(ch.lower, _dot(dx, ch.wu, _NT), 0.0)
    dx1, dx2 = dx[:, :LANES], dx[:, LANES:]
    dkn = dx1 * beta * ch.eg
    dgam = dx1 * ch.x1
    dbeta = _lane_rows(dx1 * ch.kn * ch.eg + dx2 * ch.v) \
        + _lane_rows(da * ch.sk)
    dv = dx2 * beta
    dsk = da * beta
    # Qe = qn e^Gamma, Kd = kn e^(Gamma_C - Gamma)
    dqn = dqe * ch.eg
    dgam = dgam + dqe * ch.qe
    dkn = dkn + dkd * ch.kd_e
    j = dkd * ch.kd
    dgam = dgam - j
    dlast = dlast + jnp.sum(j, axis=0, keepdims=True)
    # the score products, a sub-chunk at a time
    row = _iota(ch.gam.shape, 0)
    drk, drq, dstart = [], [], jnp.zeros_like(ch.gam)
    for idx, (keys, f) in enumerate(ch.keys):
        rows = slice(idx * SUB, (idx + 1) * SUB)
        dout = jnp.concatenate([dsk[rows], dp[rows]])     # [2 SUB, C]
        lhs = jnp.concatenate([ch.rk[rows], ch.rq[rows]])
        dlhs = _dot(dout, keys, _NN, dt)
        drk.append(dlhs[:SUB])
        drq.append(dlhs[SUB:])
        dkeys = _dot(dout, lhs, _TN, dt)                    # [C, d_k]
        dkn = dkn + dkeys * f
        gk = dkeys * keys
        dgam = dgam - gk
        dstart = jnp.where(row == idx * SUB,
                           jnp.sum(gk, axis=0, keepdims=True), dstart)
    drk, drq = jnp.concatenate(drk), jnp.concatenate(drq)
    dkn = dkn + drk * ch.e_in
    dqn = dqn + drq * ch.e_in
    hh = drk * ch.rk + drq * ch.rq
    dgam = dgam + hh
    for idx in range(cc // SUB):
        rows = slice(idx * SUB, (idx + 1) * SUB)
        dstart = dstart - jnp.where(
            row == idx * SUB, jnp.sum(hh[rows], axis=0, keepdims=True), 0.0)
    # a split point is Gamma before its sub-chunk: Gamma - g there
    dgam = dgam + dstart + jnp.where(row == cc - 1, dlast, 0.0)
    dg = _running_sum(dgam, reverse=True) - dstart
    dq_ref[0, at, :] = _unit_grad(ch.q, ch.nq, dqn * ch.scale).astype(
        dq_ref.dtype)
    dk_ref[0, at, :] = _unit_grad(ch.k, ch.nk, dkn).astype(dk_ref.dtype)
    dv_ref[0, at, :] = dv.astype(dv_ref.dtype)
    dg_ref[0, at, :] = dg
    dbeta_ref[0, 0, ci] = dbeta


# ---- the calls ---------------------------------------------------------

def chunks_a_program(chunk, n_chunks):
    """Chunks a program takes: the most that divide the sequence's and
    keep a block at ``_BLOCK_ROWS`` positions."""
    most = max(1, _BLOCK_ROWS // chunk)
    return max(m for m in range(1, min(most, n_chunks) + 1)
               if n_chunks % m == 0)


def block(length, chunk):
    """[positions, lanes] of Q a program of either kernel takes."""
    return [chunk * chunks_a_program(chunk, length // chunk), LANES]


def vmem_bytes(length, chunk, heads, itemsize):
    """The gradient kernel's blocks (the larger program), double-buffered,
    its scratch, and a chunk's values (about twenty [C, 128] and eight
    [C, C] float32 arrays live at once): what ``VMEM_LIMIT`` must hold
    beside the compiler's own."""
    rows, _ = block(length, chunk)
    m = rows // chunk
    per = (rows * LANES * (3 * itemsize + 4)          # q k v, g
           + rows * -(-heads // LANES) * LANES * 4    # beta, padded
           + m * LANES * LANES * 4                    # states
           + rows * LANES * itemsize                  # do
           + rows * LANES * (3 * itemsize + 4)        # dq dk dv, dg
           + m * 8 * LANES * 4)                       # dbeta, padded
    values = (20 * LANES + 8 * chunk) * chunk * 4
    return 2 * per + LANES * LANES * 4 + values


def _params(cfg):
    if cfg.interpret:
        return {'interpret': True}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        vmem_limit_bytes=VMEM_LIMIT)}


def _specs(m, c, heads, block_of):
    """The block of each kind of array at grid point (b, h, i);
    ``block_of(i)`` the block of ``m`` chunks a step works on."""
    rows = m * c
    return dict(
        x=pl.BlockSpec((1, rows, LANES),
                       lambda b, h, i: (b, block_of(i), h)),
        beta=pl.BlockSpec((1, rows, heads),
                          lambda b, h, i: (b, block_of(i), 0)),
        state=pl.BlockSpec((1, m, 1, LANES, LANES),
                           lambda b, h, i: (b, block_of(i), h, 0, 0)),
        dbeta=pl.BlockSpec((1, 1, m, 1, c),
                           lambda b, h, i: (b, h, block_of(i), 0, 0)))


def _grid(x, c):
    n_chunks = x.shape[1] // c
    m = chunks_a_program(c, n_chunks)
    return n_chunks, m, (x.shape[0], x.shape[2] // LANES, n_chunks // m)


@functools.partial(jax.jit, static_argnames='cfg')
def _fwd_call(q, k, v, g, beta, cfg):
    n_chunks, m, grid = _grid(q, cfg.c)
    sp = _specs(m, cfg.c, beta.shape[2], lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg), grid=grid,
        in_specs=[sp['x']] * 4 + [sp['beta']],
        out_specs=[sp['x'], sp['state']],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((q.shape[0], n_chunks, grid[1], LANES,
                                  LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((LANES, LANES), _F32)],
        name='paddle_tpu_kda_fwd', **_params(cfg))(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames='cfg')
def _bwd_call(q, k, v, g, beta, states, do, cfg):
    n_chunks, m, grid = _grid(q, cfg.c)
    sp = _specs(m, cfg.c, beta.shape[2], lambda i: grid[2] - 1 - i)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg), grid=grid,
        in_specs=[sp['x']] * 4 + [sp['beta'], sp['state'], sp['x']],
        out_specs=[sp['x']] * 4 + [sp['dbeta']],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct((q.shape[0], grid[1], n_chunks, 1, cfg.c),
                                 _F32)],
        scratch_shapes=[pltpu.VMEM((LANES, LANES), _F32)],
        name='paddle_tpu_kda_bwd', **_params(cfg))(
            q, k, v, g, beta, states, do)


def _flat(x):
    return x.reshape(x.shape[:2] + (-1, ))


def _config(q, chunk, dtype, interpret):
    """The static configuration: the chunks a step of a program's loop
    takes (``pack``), four (two pairs, whose solves interleave) where the
    program's chunks allow, else two (one pair), else one."""
    m = chunks_a_program(chunk, q.shape[1] // chunk)
    pack = max(p for p in (1, 2, 4) if m % p == 0)
    return _Cfg(int(chunk), jnp.dtype(dtype).name, pack, bool(interpret))


def kda(q, k, v, g, beta, chunk, dtype, interpret=False):
    """(o [B, L, H, d_v] in v's dtype, the states entering each chunk [B,
    L / chunk, H, d_k, d_v] float32).  q, k [B, L, H, d_k], v [B, L, H,
    d_v] (d_k = d_v = 128), g [B, L, H, d_k] the log-decay, beta [B, L,
    H]; L a whole number of chunks; ``dtype`` the score products' operand
    type.  interpret: Pallas interpret mode, for a CPU place only."""
    cfg = _config(q, chunk, dtype, interpret)
    o, states = _fwd_call(_flat(q), _flat(k), _flat(v),
                          _flat(g.astype(_F32)), beta, cfg)
    return o.reshape(v.shape), states


def kda_grad(q, k, v, g, beta, states, do, chunk, dtype, interpret=False):
    """(dq, dk, dv in their primals' dtypes, dg, dbeta float32) of
    ``kda``'s output for its gradient ``do``, from the states its forward
    left."""
    cfg = _config(q, chunk, dtype, interpret)
    dq, dk, dv, dg, dbeta = _bwd_call(
        _flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)), beta,
        states, _flat(do), cfg)
    b, length, heads = beta.shape
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape),
            jnp.transpose(dbeta.reshape(b, heads, length), (0, 2, 1)))
