"""Mamba-2's chunked scan (``ops/ssm_ops.py``: ``ssd_scan``) as two Pallas
TPU kernels, the forward and its gradient.  Nothing of a head's size
[chunk, chunk] (decay matrix, scores, their product) or [P, N] (a chunk's
own state) reaches HBM, except the states ENTERING each chunk, which the
forward writes once for the gradient.

Layout: the op's own.  X, Y, dY, dX are the row-major [B, L, H*P], B and C
[B, L, G*N]: a block of positions by 128 lanes of X is 128 / P whole heads
(two of 64), of B or C one group (N = 128); no transpose before or after.
The per-position scalars come in ROW form, [B, chunks, 2, H, q] f32: the
step and the running sum of ``dt A`` over its chunk, a head's values along
the chunk one row of lanes (256 bytes a token, made by XLA beside the
softplus, as the sum is).

Grid (B, blocks of chunks, H / hb), the last two sequential: a program
works on ``hb`` heads of one group in a block of chunks (a loop inside it:
each array's blocks are one DMA a program, which costs what 0.3 us of the
kernel's work cost), 128 lanes of heads at a time.

* The running sums, every ``exp`` and the state are f32.  A row-form
  [hb, q] vector becomes the column form a [q, 128] tile of X needs (each
  head's value down its own P lanes) by one f32 transpose of the row
  broadcast down 128 sublanes.
* forward: scores ``C B^T`` once a group (VMEM scratch), per head the
  masked decay ``exp(cum_i - cum_j)`` (masked BEFORE the exp), its product
  with the scores and ``dt_j``, and with X; the entering state's
  ``C S^T exp(cum)``; the skip ``D x``; the chunk's own state
  ``(x dt exp(total - cum))^T B``.  The state of every head rides in a VMEM
  scratch from chunk to chunk: ``S <- exp(total) S + chunk state``.
* gradient: the chunks in reverse, the state's cotangent in that scratch;
  decay and scores are made again from the inputs and the saved entering
  state.  dB and dC sum over a group's heads in VMEM (f32) and are written
  once a group and chunk.  What comes out a head and position (d/d step,
  d/d running sum) leaves in row form; sums over a head's lanes are taken
  on the MXU against 0/1 matrices (``_lane_sums``: a lane reduction a head
  cost a fifth of the kernel), dD leaves as a row a chunk [B, chunks, 1,
  H*P]; the caller folds them (the running sum's own gradient, the
  softplus, A, D and the bias are a few KB of reductions).

Precision is the XLA lowering's: the products take their operands in X's
dtype (bf16 under AMP) with f32 accumulation, all else is f32.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['ssd_scan', 'ssd_scan_grad', 'block', 'HEAD_BLOCK']

LANES = 128
HEAD_BLOCK = 8       # heads a program: the sublanes of one f32 vreg
_VMEM_LIMIT = 48 << 20          # of the v5e's 128 MiB
_BLOCK_ROWS = 512    # positions of X a program takes
_NT = (((1, ), (1, )), ((), ()))     # A B^T
_TN = (((0, ), (0, )), ((), ()))     # A^T B

# heads, head width, groups, state size, chunk, interpret
_Cfg = collections.namedtuple('_Cfg', 'h p g n q interpret')


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _by_head(v, j, p, shape, axis):
    """[hb, 1] per-head values laid along ``axis`` of ``shape`` (128 long
    there): position l holds head ``j * (128 / p) + l // p``'s."""
    hpl = LANES // p
    at = _iota(shape, axis)
    out = jnp.broadcast_to(v[j * hpl:j * hpl + 1], shape)
    for u in range(1, hpl):
        t = j * hpl + u
        out = jnp.where(at >= u * p, v[t:t + 1], out)
    return out


def _columns(rows, j, p):
    """[hb, q] row-form vectors -> the [q, 128] column form of lane tile
    ``j``: lane l holds head ``j * (128 / p) + l // p``'s value at each
    position."""
    hpl, q = LANES // p, rows.shape[1]
    sub = _iota((LANES, q), 0)
    pre = jnp.broadcast_to(rows[j * hpl:j * hpl + 1], (LANES, q))
    for u in range(1, hpl):
        t = j * hpl + u
        pre = jnp.where(sub >= u * p, rows[t:t + 1], pre)
    return pre.T


def _head_lanes(shape, u, p):
    lane = _iota(shape, 1)
    return jnp.logical_and(lane >= u * p, lane < (u + 1) * p)


def _decay(cum_c, cum_row, u, p, lower):
    """One head's masked decay matrix exp(cum_i - cum_j), j <= i, [q, q]:
    cum_c the tile's column form (head ``u`` of it), cum_row [1, q]."""
    q = cum_row.shape[1]
    col = jnp.broadcast_to(cum_c[:, u * p:u * p + 1], (q, q))
    # the exponent above the diagonal is positive and may overflow: masked
    # before the exp, not after
    return jnp.exp(jnp.where(lower, col - cum_row, -jnp.inf))


def _rows(rows_ref, ci):
    """The step, dt A's running sum over chunk ``ci`` of the block and its
    total, row form: [hb, q], [hb, q], [hb, 1] f32."""
    dt, cum = rows_ref[0, ci, 0], rows_ref[0, ci, 1]
    last = _iota(cum.shape, 1) == cum.shape[1] - 1
    return dt, cum, jnp.sum(jnp.where(last, cum, 0.0), axis=1, keepdims=True)


def _lane_sums(v, onto):
    """``v @ onto`` for a 0/1 matrix ``onto``: sums over chosen lanes of
    f32 ``v`` laid into chosen lanes, on the MXU.  Where the products'
    dtype is bf16, v goes in as two bf16 terms (16 bits of mantissa: the
    sum keeps f32's accuracy to 2^-17, far under its terms' own)."""
    if onto.dtype == jnp.float32:
        return _dot(v, onto)
    hi = v.astype(onto.dtype)
    lo = (v - hi.astype(jnp.float32)).astype(onto.dtype)
    return _dot(hi, onto) + _dot(lo, onto)


def _chunk(ci, q):
    return pl.ds(pl.multiple_of(ci * q, q), q)


# ---- forward -----------------------------------------------------------

def _fwd_kernel(rows_ref, d_ref, x_ref, b_ref, c_ref, y_ref, st_ref,
                s_scr, cb_scr, *, cfg):
    c, k = pl.program_id(1), pl.program_id(2)
    q, p = cfg.q, cfg.p
    hb, hpl = rows_ref.shape[3], LANES // cfg.p
    kpg = cfg.h // cfg.g // hb          # programs a group
    lower = _iota((q, q), 1) <= _iota((q, q), 0)

    @pl.when(c == 0)
    def _():
        s_scr[k] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    def chunk(ci, carry):
        at = _chunk(ci, q)
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]

        @pl.when(k % kpg == 0)
        def _():
            cb_scr[ci] = _dot(cm, bm, _NT)

        dt, cum, tot = _rows(rows_ref, ci)
        keep = jnp.exp(tot)
        cb = cb_scr[ci]
        for j in range(hb // hpl):
            lanes = slice(j * LANES, (j + 1) * LANES)
            s = s_scr[k, lanes, :]
            st_ref[0, ci, lanes, :] = s
            x = x_ref[0, at, lanes]
            xf = x.astype(jnp.float32)
            cum_c, dt_c = _columns(cum, j, p), _columns(dt, j, p)
            to_end = jnp.exp(_by_head(tot, j, p, (1, LANES), 1) - cum_c)
            y = _dot(cm, s.astype(x.dtype), _NT) * jnp.exp(cum_c) \
                + xf * d_ref[k, :, lanes]
            s_scr[k, lanes, :] = s * _by_head(keep, j, p, (LANES, 1), 0) \
                + _dot((xf * (dt_c * to_end)).astype(x.dtype), bm, _TN)
            for u in range(hpl):
                t = j * hpl + u
                weights = cb * (_decay(cum_c, cum[t:t + 1], u, p, lower)
                                * dt[t:t + 1])
                y = y + jnp.where(_head_lanes(y.shape, u, p),
                                  _dot(weights.astype(x.dtype), x), 0.0)
            y_ref[0, at, lanes] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, st_ref.shape[1], chunk, 0)


# ---- gradient ----------------------------------------------------------

def _bwd_kernel(rows_ref, d_ref, x_ref, b_ref, c_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dd_ref,
                g_scr, cb_scr, dcb_scr, db_scr, dc_scr, *, cfg):
    c, k = pl.program_id(1), pl.program_id(2)
    q, p = cfg.q, cfg.p
    hb, hpl = rows_ref.shape[3], LANES // cfg.p
    kpg = cfg.h // cfg.g // hb
    m = st_ref.shape[1]
    lower = _iota((q, q), 1) <= _iota((q, q), 0)
    head_row = _iota((hb, 1), 0)
    # 0/1 matrices for ``_lane_sums``: lane t of the sums <- all lanes of
    # a head's [q, q] tile (``to_lane == t``), or the head's own P lanes of
    # a [q, 128] tile of X (``tile_lane == t - t % hpl + head_of``)
    to_lane, tile_lane = _iota((q, LANES), 1), _iota((LANES, LANES), 1)
    head_of = _iota((LANES, LANES), 0) // p

    @pl.when(c == 0)         # the last chunks: nothing follows them
    def _():
        g_scr[k] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    def chunk(i, carry):
        ci = m - 1 - i
        at = _chunk(ci, q)
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]
        mm = bm.dtype

        @pl.when(k % kpg == 0)
        def _():
            cb_scr[ci] = _dot(cm, bm, _NT)
            dcb_scr[ci] = jnp.zeros(dcb_scr.shape[1:], jnp.float32)
            db_scr[ci] = jnp.zeros(db_scr.shape[1:], jnp.float32)
            dc_scr[ci] = jnp.zeros(dc_scr.shape[1:], jnp.float32)

        dt, cum, tot = _rows(rows_ref, ci)
        keep = jnp.exp(tot)
        cb = cb_scr[ci]
        # what comes out a head and position in column form, summed into
        # a lane a head and turned to rows once: lane t d/d(cum_i) from
        # the rows of the decay matrix and the entering state's term,
        # lane hb + t d/d(dt_j exp(total - cum_j))
        cols = jnp.zeros((q, LANES), jnp.float32)
        ddt = jnp.zeros((hb, q), jnp.float32)
        dcum = jnp.zeros((hb, q), jnp.float32)
        gs = jnp.zeros((hb, 1), jnp.float32)      # sum of dS * S a head
        for j in range(hb // hpl):
            lanes = slice(j * LANES, (j + 1) * LANES)
            s, g = st_ref[0, ci, lanes, :], g_scr[k, lanes, :]
            s_mm, g_mm = s.astype(mm), g.astype(mm)
            x, dy = x_ref[0, at, lanes], dy_ref[0, at, lanes]
            xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
            cum_c, dt_c = _columns(cum, j, p), _columns(dt, j, p)
            dtw_c = dt_c * jnp.exp(
                _by_head(tot, j, p, (1, LANES), 1) - cum_c)
            # y's term from the entering state: C S^T exp(cum)
            z = _dot(cm, s_mm, _NT)
            dz = dyf * jnp.exp(cum_c)
            dz_mm = dz.astype(mm)
            dc_scr[ci] += _dot(dz_mm, s_mm)
            g_scr[k, lanes, :] = g * _by_head(keep, j, p, (LANES, 1), 0) \
                + _dot(dz_mm, cm, _TN)
            # the chunk's own state: (x dt exp(total - cum))^T B
            du = _dot(bm, g_mm, _NT)
            db_scr[ci] += _dot((xf * dtw_c).astype(mm), g_mm)
            dx = dyf * d_ref[k, :, lanes] + du * dtw_c
            dd_ref[0, ci, :, lanes] = jnp.sum(dyf * xf, axis=0,
                                              keepdims=True)
            gsp = g * s
            heads = j * hpl + head_of
            cols = cols + _lane_sums(
                dz * z, (tile_lane == heads).astype(mm)) + _lane_sums(
                    du * xf, (tile_lane == hb + heads).astype(mm))
            for u in range(hpl):
                t = j * hpl + u
                mine = _head_lanes((q, LANES), u, p)
                decay = _decay(cum_c, cum[t:t + 1], u, p, lower)
                scored = cb * decay
                weights = scored * dt[t:t + 1]
                dw = _dot(jnp.where(mine, dy, jnp.zeros_like(dy)), x, _NT)
                dx = dx + jnp.where(
                    mine, _dot(weights.astype(mm), dy, _TN), 0.0)
                dcb_scr[ci] += dw * (decay * dt[t:t + 1])
                dseg = dw * weights
                ddt = jnp.where(head_row == t, jnp.sum(
                    dw * scored, axis=0, keepdims=True), ddt)
                dcum = jnp.where(head_row == t, -jnp.sum(
                    dseg, axis=0, keepdims=True), dcum)
                cols = cols + _lane_sums(dseg, (to_lane == t).astype(mm))
                gs = jnp.where(head_row == t, jnp.sum(jnp.sum(
                    gsp[u * p:(u + 1) * p], axis=0, keepdims=True), axis=1,
                    keepdims=True), gs)
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
        cols = cols.T
        to_end = jnp.exp(tot - cum)
        ddtw = cols[hb:2 * hb]
        dto_end = ddtw * dt * to_end      # d/d(to_end) * to_end
        dtot = jnp.sum(dto_end, axis=1, keepdims=True) + keep * gs
        drows_ref[0, ci, 0] = ddt + ddtw * to_end
        drows_ref[0, ci, 1] = dcum + cols[:hb] - dto_end + jnp.where(
            _iota((hb, q), 1) == q - 1, dtot, 0.0)

        @pl.when(k % kpg == kpg - 1)
        def _():
            dcb = dcb_scr[ci].astype(mm)
            dc_ref[0, at, :] = (dc_scr[ci] + _dot(dcb, bm)).astype(
                dc_ref.dtype)
            db_ref[0, at, :] = (db_scr[ci] + _dot(dcb, cm, _TN)).astype(
                db_ref.dtype)
        return carry

    jax.lax.fori_loop(0, m, chunk, 0)


# ---- the calls ---------------------------------------------------------

def _params(cfg):
    if cfg.interpret:
        return {'interpret': True}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _chunks_a_program(q, n_chunks):
    """Chunks a program takes (a loop inside it): the most that divide the
    sequence's and keep a block of X at ``_BLOCK_ROWS`` positions."""
    most = max(1, _BLOCK_ROWS // q)
    return max(m for m in range(1, min(most, n_chunks) + 1)
               if n_chunks % m == 0)


def block(length, chunk, p):
    """[positions, lanes] of X a program of either kernel takes."""
    return [chunk * _chunks_a_program(chunk, length // chunk),
            HEAD_BLOCK * p]


def _grid(cfg, x):
    """(chunks, chunks a program, the grid) for x [B, L, H*P]."""
    n_chunks = x.shape[1] // cfg.q
    m = _chunks_a_program(cfg.q, n_chunks)
    return n_chunks, m, (x.shape[0], n_chunks // m, cfg.h // HEAD_BLOCK)


def _specs(cfg, m, block_of):
    """The block of each kind of array at grid point (b, c, k);
    ``block_of(c)`` the block of ``m`` chunks a step works on."""
    hb, q = HEAD_BLOCK, cfg.q
    w, kpg = hb * cfg.p, cfg.h // cfg.g // hb
    return dict(
        rows=pl.BlockSpec((1, m, 2, hb, q),
                          lambda b, c, k: (b, block_of(c), 0, k, 0)),
        d=pl.BlockSpec((cfg.h // hb, 1, w), lambda b, c, k: (0, 0, 0)),
        x=pl.BlockSpec((1, m * q, w), lambda b, c, k: (b, block_of(c), k)),
        bc=pl.BlockSpec((1, m * q, cfg.n),
                        lambda b, c, k: (b, block_of(c), k // kpg)),
        state=pl.BlockSpec((1, m, w, cfg.n),
                           lambda b, c, k: (b, block_of(c), k, 0)),
        dd=pl.BlockSpec((1, m, 1, w),
                        lambda b, c, k: (b, block_of(c), 0, k)))


@functools.partial(jax.jit, static_argnames='cfg')
def _fwd_call(rows, d, x, bm, cm, cfg):
    """rows [B, n, 2, H, q] f32 (the step; dt A's running sum), d [H / hb,
    1, hb*P] f32, x [B, L, H*P], bm/cm [B, L, G*N] -> (y, the states
    entering each chunk [B, n, H*P, N])."""
    n_chunks, m, grid = _grid(cfg, x)
    hb, hp = HEAD_BLOCK, x.shape[2]
    sp = _specs(cfg, m, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg), grid=grid,
        in_specs=[sp['rows'], sp['d'], sp['x'], sp['bc'], sp['bc']],
        out_specs=[sp['x'], sp['state']],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((x.shape[0], n_chunks, hp, cfg.n),
                                 jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((cfg.h // hb, hb * cfg.p, cfg.n), jnp.float32),
            pltpu.VMEM((m, cfg.q, cfg.q), jnp.float32)],
        name='paddle_tpu_ssd_scan_fwd', **_params(cfg))(
            rows, d, x, bm, cm)


@functools.partial(jax.jit, static_argnames='cfg')
def _bwd_call(rows, d, x, bm, cm, states, dy, cfg):
    n_chunks, m, grid = _grid(cfg, x)
    hb, hp, f32 = HEAD_BLOCK, x.shape[2], jnp.float32
    sp = _specs(cfg, m, lambda c: grid[1] - 1 - c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg), grid=grid,
        in_specs=[sp['rows'], sp['d'], sp['x'], sp['bc'], sp['bc'],
                  sp['state'], sp['x']],
        out_specs=[sp['x'], sp['bc'], sp['bc'], sp['rows'], sp['dd']],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(bm.shape, bm.dtype),
            jax.ShapeDtypeStruct(cm.shape, cm.dtype),
            jax.ShapeDtypeStruct(rows.shape, f32),
            jax.ShapeDtypeStruct((x.shape[0], n_chunks, 1, hp), f32)],
        scratch_shapes=[
            pltpu.VMEM((cfg.h // hb, hb * cfg.p, cfg.n), f32),
            pltpu.VMEM((m, cfg.q, cfg.q), f32),
            pltpu.VMEM((m, cfg.q, cfg.q), f32),
            pltpu.VMEM((m, cfg.q, cfg.n), f32),
            pltpu.VMEM((m, cfg.q, cfg.n), f32)],
        name='paddle_tpu_ssd_scan_bwd', **_params(cfg))(
            rows, d, x, bm, cm, states, dy)


def _chunked_rows(v, q):
    """[B, L, H] -> the row form [B, L / q, H, q]."""
    b, length, h = v.shape
    return jnp.transpose(v.reshape(b, length // q, q, h), (0, 1, 3, 2))


def _plan(x, dt, a, bm, cm, d, chunk, interpret):
    """The static configuration and the arrays as the kernels take them
    (the first: the step and dt A's running sum in row form)."""
    b, length, h, p = x.shape
    g, n = bm.shape[2:]
    cfg = _Cfg(h, p, g, n, int(chunk), bool(interpret))
    f32 = jnp.float32
    dt = _chunked_rows(dt.astype(f32), cfg.q)
    cum = jnp.cumsum(dt * a.astype(f32)[:, None], axis=3)
    return cfg, (
        jnp.stack([dt, cum], axis=2),
        jnp.repeat(d.astype(f32), p).reshape(h // HEAD_BLOCK, 1, -1),
        x.reshape(b, length, h * p),
        bm.astype(x.dtype).reshape(b, length, g * n),
        cm.astype(x.dtype).reshape(b, length, g * n))


def ssd_scan(x, dt, a, bm, cm, d, chunk, interpret=False):
    """(y [B, L, H, P] in x's dtype, the states entering each chunk
    [B, L / chunk, H, P, N] f32).  x [B, L, H, P] (its dtype is the
    products': bf16 under AMP), dt [B, L, H] the step itself (after the
    softplus), a, d [H], bm/cm [B, L, G, N]; L a whole number of chunks.
    interpret: Pallas interpret mode, for a CPU place only (the caller
    decides from the place it lowers for)."""
    cfg, args = _plan(x, dt, a, bm, cm, d, chunk, interpret)
    y, states = _fwd_call(*args, cfg)
    b, n_chunks = states.shape[:2]
    return y.reshape(x.shape), states.reshape(
        b, n_chunks, cfg.h, cfg.p, cfg.n)


def ssd_scan_grad(x, dt, a, bm, cm, d, states, dy, chunk, interpret=False):
    """(dX, d/d(step), dA, dB, dC, dD) of ``ssd_scan`` from its inputs,
    the states its forward left and dY: the gradient kernel alone, and
    the running sum's own gradient (a sum from the chunk's end back)."""
    cfg, args = _plan(x, dt, a, bm, cm, d, chunk, interpret)
    b, length, h, p = x.shape
    dx, db, dc, drows, dd = _bwd_call(
        *args, states.reshape(states.shape[:2] + (h * p, cfg.n)),
        dy.astype(x.dtype).reshape(b, length, h * p), cfg)
    dda = jax.lax.cumsum(drows[:, :, 1], axis=3, reverse=True)
    ddt = drows[:, :, 0] + dda * a.astype(jnp.float32)[:, None]
    return (dx.reshape(x.shape),
            jnp.transpose(ddt, (0, 1, 3, 2)).reshape(b, length, h),
            jnp.sum(dda * args[0][:, :, 0], axis=(0, 1, 3)),
            db.reshape(bm.shape).astype(bm.dtype),
            dc.reshape(cm.shape).astype(cm.dtype),
            jnp.sum(dd.reshape(-1, h, p), axis=(0, 2)))
