"""Fused attention as a Pallas TPU kernel: the [Lq, Lk] scores never leave
VMEM.  One forward kernel, ONE backward kernel (dQ, dK and dV together).

Numerics are the dense path's (parallel.context_parallel.dense_attention):
the operands go into every product in their own dtype (bf16 under AMP)
with f32 accumulation; the scale, the masks, the softmax's max, exponent
and sum are f32; P narrows to V's dtype before P.V, dS to K's before its
two products.  ``seq_lengths`` masks K/V columns, ``causal`` masks
col > row, and a row with no valid column gives 0.

Layout: [batch, seq, heads, head_dim] END TO END, seen as the row-major
[B, L, H*D]: no [B,L,H,D] -> [B,H,L,D] transpose exists.  A program works
on ``block_b`` batch rows of one LANE GROUP: ``lane_group(H, D)`` columns,
a whole number of vregs (128 lanes: two heads of 64).  A head inside its
group is picked by zeroing the other heads' lanes of Q (and dO), never by
slicing half a vreg: the product then contracts over the group's 128
lanes, which costs the 128-deep MXU what a 64-deep contraction costs, and
products that come out group-wide are laid into their head's lanes by a
select.

Tiling: K and V of a row's lane group sit in VMEM whole; the scores are
made a [block_q, block_k] tile at a time (256 x 256 f32 = 256 KB).

* forward: grid (B/block_b, groups, Lq/block_q).  Where Lk is one block
  the softmax is one-shot; else K blocks fold into an online softmax, and
  under ``causal`` the blocks above the diagonal are not visited.  Beside
  O it leaves the rows' log-sum-exp, laid along the lanes
  ([B, groups, Lq/block_q, 8, block_q], one sublane a head).
* backward: grid (B/block_b, groups), in the TRANSPOSED domain
  (S^T = K Q^T, [block_k, block_q]): the log-sum-exp and delta then
  broadcast along sublanes, dV = P^T dO and dK = dS^T Q are plain
  products, and only dQ = dS K contracts over the first dimension.  Five
  products a tile.  Where Lq and Lk are one block each, delta is the
  column sum of P^T * dP^T inside the kernel; else it comes in as
  rowsum(dO * O), the K blocks loop outside the Q blocks (dK and dV are
  carried, dQ accumulates in a VMEM scratch), and causal tiles above the
  diagonal are not visited.

Envelope: Q, K, V, dO and the three gradients of ``block_b`` rows of one
lane group, double-buffered, and the backward's f32 dQ scratch must fit
VMEM: Lq and Lk up to ``MAX_LEN``.  Longer rows belong to ring attention
over an 'sp' mesh axis.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['flash_attention', 'flash_attention_grad', 'MAX_LEN']

_NEG_INF = -1e30
_TINY = 1e-30
_LANES = 128
_BLOCK = 256         # a [256, 256] f32 score tile is 64 vregs, 256 KB
_STAT_ROWS = 8       # sublanes of the log-sum-exp tile: one a head
# the longest row of Q or of K/V the kernel has compiled and run for on
# the v5e (tools/pallas_chip_check.py): 7 arrays of [2048, 128] bf16,
# double-buffered, and the f32 dQ scratch are 8 MiB of the VMEM limit
# below
MAX_LEN = 2048
_VMEM_LIMIT = 48 << 20          # of the v5e's 128 MiB
_BLOCK_BYTES = 4 << 20          # the blocks of one program, one buffer
# batch rows laid out in straight-line code inside a program's loop: a
# row's two heads are two chains of product -> reduce -> exponent ->
# product, each waiting on the one before, and the scheduler overlaps
# only what one loop body holds.  Measured at the cells' shape (v5e, PR
# 25, forward + backward a call): 1.40 ms at 1, 1.25 at 2, 1.18 at 4,
# 1.13 at 8 (the forward alone 0.60 -> 0.39; the backward gains nothing
# past 4)
_FWD_UNROLL = 8
_BWD_UNROLL = 4
_NT = (((1, ), (1, )), ((), ()))     # A B^T
_TN = (((0, ), (0, )), ((), ()))     # A^T B

_Cfg = collections.namedtuple(
    '_Cfg', 'heads d causal scale bq bk mask_cols zero_rows interpret')


def _folds(scale):
    """Whether ``scale`` is a power of two (D=64: 1/8).  Q * scale is then
    exact in any float type, so scaling Q's [L, 128] tile gives, bit for
    bit, what scaling the [Lq, Lk] scores gives, at a quarter of the
    vector work; dS likewise takes its scale through Q (for dK) and on
    dQ's tile."""
    return math.frexp(scale)[0] == 0.5


def _scaled(x, cfg):
    """Q, times the scale where that is exact (``_folds``)."""
    if not _folds(cfg.scale):
        return x
    return (x.astype(jnp.float32) * cfg.scale).astype(x.dtype)


def lane_group(heads, d):
    """Columns of [B, L, heads*d] one program works on: the fewest whole
    heads that fill whole 128-lane vregs, or the whole row where the
    heads do not tile vregs (correct, with sliced vregs: slow)."""
    w = d * _LANES // math.gcd(d, _LANES)
    return w if (heads * d) % w == 0 else heads * d


def _for_rows(bb, unroll, row):
    """``row(r, 0)`` for each of a program's ``bb`` batch rows, ``unroll``
    of them (or the largest divisor of ``bb`` under it) an iteration."""
    u = math.gcd(bb, unroll)

    def body(i, c):
        for t in range(u):
            row(i * u + t, c)
        return c

    jax.lax.fori_loop(0, bb // u, body, 0)


def _head_lanes(shape, h, d):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return jnp.logical_and(lane >= h * d, lane < (h + 1) * d)


def _one_head(x, h, d):
    """x with the lanes of every head but ``h`` zeroed (the whole of x
    where the group is one head)."""
    if x.shape[-1] == d:
        return x
    return jnp.where(_head_lanes(x.shape, h, d), x, jnp.zeros_like(x))


def _valid(shape, q_axis, q0, k0, length, cfg):
    """Where a [.., ..] score tile may attend, or None for everywhere:
    ``q_axis`` is the tile's Q dimension, q0/k0 its first row/column."""
    if not (cfg.causal or cfg.mask_cols):
        return None
    col = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    ok = None
    if cfg.mask_cols:
        ok = col < length
    if cfg.causal:
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        below = col <= row
        ok = below if ok is None else jnp.logical_and(ok, below)
    return ok


# ---- forward -----------------------------------------------------------

def _fwd_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, cfg):
    bb, bq, w = q_ref.shape
    d, bk = cfg.d, cfg.bk
    nk = k_ref.shape[1] // bk
    b0, iq = pl.program_id(0) * bb, pl.program_id(2)
    # causal: K blocks that lie wholly above the diagonal are not visited
    hi = (jnp.minimum(((iq + 1) * bq + bk - 1) // bk, nk)
          if cfg.causal else nk)
    stat_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, _LANES), 1)

    def scores(qh, kb, j, length):
        s = jax.lax.dot_general(
            qh, kb, _NT, preferred_element_type=jnp.float32)
        if not _folds(cfg.scale):
            s = s * cfg.scale
        ok = _valid(s.shape, 0, iq * bq, j * bk, length, cfg)
        return (s if ok is None else jnp.where(ok, s, _NEG_INF)), ok

    def weights(s, ok, m):
        p = jnp.exp(s - m)
        # a row with no valid column has m = -1e30 and exp(0) everywhere
        return jnp.where(ok, p, 0.0) if cfg.zero_rows else p

    def row(r, _):
        length = lens_ref[b0 + r]
        q = _scaled(q_ref[r], cfg)
        out = jnp.zeros((bq, w), jnp.float32)
        stats = jnp.zeros((bq, _LANES), jnp.float32)
        for h in range(w // d):
            qh = _one_head(q, h, d)
            if nk == 1:     # one-shot softmax
                s, ok = scores(qh, k_ref[r], 0, length)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = weights(s, ok, m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                acc = jnp.dot(p.astype(v_ref.dtype), v_ref[r],
                              preferred_element_type=jnp.float32)
            else:           # K blocks fold into an online softmax

                def fold(j, carry, qh=qh):
                    m, l, acc = carry
                    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
                    s, ok = scores(qh, k_ref[r, rows, :], j, length)
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=-1, keepdims=True))
                    p = weights(s, ok, m_new)
                    alpha = jnp.exp(m - m_new)
                    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                    acc = acc * alpha + jnp.dot(
                        p.astype(v_ref.dtype), v_ref[r, rows, :],
                        preferred_element_type=jnp.float32)
                    return m_new, l, acc

                m, l, acc = jax.lax.fori_loop(0, hi, fold, (
                    jnp.full((bq, 1), _NEG_INF, jnp.float32),
                    jnp.zeros((bq, 1), jnp.float32),
                    jnp.zeros((bq, w), jnp.float32)))
            l = jnp.maximum(l, _TINY)
            acc = acc * (1.0 / l)
            out = acc if w == d else jnp.where(
                _head_lanes(acc.shape, h, d), acc, out)
            stats = jnp.where(stat_lane == h, m + jnp.log(l), stats)
        o_ref[r] = out.astype(o_ref.dtype)
        # the rows' statistics, laid along the lanes for the backward
        lse_ref[r, 0, 0] = stats.T[:lse_ref.shape[3]]
        return 0

    _for_rows(bb, _FWD_UNROLL, row)


# ---- backward ----------------------------------------------------------

def _bwd_tile(qh, doh, kb, vb, lse, delta, ok, cfg):
    """One head's [bk, bq] tile in the transposed domain.  qh, doh: the
    head's Q (``_scaled``) and dO (other lanes zero), kb/vb: K and V
    blocks, lse/delta: [1, bq] rows (delta None: the tile is the whole
    row, so it is the column sum of P^T * dP^T).  Returns dQ (group-wide),
    dK, dV (in the head's lanes), f32."""
    fold = _folds(cfg.scale)
    st = jax.lax.dot_general(
        kb, qh, _NT, preferred_element_type=jnp.float32)
    if not fold:
        st = st * cfg.scale
    if ok is not None:
        st = jnp.where(ok, st, _NEG_INF)
    pt = jnp.exp(st - lse)
    if cfg.zero_rows:
        pt = jnp.where(ok, pt, 0.0)
    dv = jnp.dot(pt.astype(doh.dtype), doh,
                 preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(
        vb, doh, _NT, preferred_element_type=jnp.float32)
    if delta is None:
        delta = jnp.sum(pt * dpt, axis=0, keepdims=True)
    dst = pt * (dpt - delta)
    if not fold:
        dst = dst * cfg.scale
    dst = dst.astype(kb.dtype)
    dk = jnp.dot(dst, qh, preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(
        dst, kb, _TN, preferred_element_type=jnp.float32)
    return (dq * cfg.scale if fold else dq), dk, dv


def _bwd_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, *rest,
                cfg):
    bb, lq, w = q_ref.shape
    d, bq, bk = cfg.d, cfg.bq, cfg.bk
    nq, nk = lq // bq, k_ref.shape[1] // bk
    heads = w // d
    b0 = pl.program_id(0) * bb
    one_tile = nq == 1 and nk == 1
    if one_tile:
        dq_ref, dk_ref, dv_ref = rest
    else:
        delta_ref, dq_ref, dk_ref, dv_ref, dq_acc = rest

    def row(r, _):
        length = lens_ref[b0 + r]
        if one_tile:
            ok = _valid((bk, bq), 1, 0, 0, length, cfg)
            q, do = _scaled(q_ref[r], cfg), do_ref[r]
            kb, vb = k_ref[r], v_ref[r]
            dq = jnp.zeros((bq, w), jnp.float32)
            dk = jnp.zeros((bk, w), jnp.float32)
            dv = jnp.zeros((bk, w), jnp.float32)
            for h in range(heads):
                dq_h, dk_h, dv_h = _bwd_tile(
                    _one_head(q, h, d), _one_head(do, h, d), kb, vb,
                    lse_ref[r, 0, 0, h:h + 1, :], None, ok, cfg)
                dq, dk, dv = dq + _one_head(dq_h, h, d), dk + dk_h, dv + dv_h
            dq_ref[r] = dq.astype(dq_ref.dtype)
            dk_ref[r] = dk.astype(dk_ref.dtype)
            dv_ref[r] = dv.astype(dv_ref.dtype)
            return 0

        dq_acc[...] = jnp.zeros_like(dq_acc)

        def k_block(j, _):
            krows = pl.ds(pl.multiple_of(j * bk, bk), bk)
            kb, vb = k_ref[r, krows, :], v_ref[r, krows, :]
            # causal: Q blocks wholly above the diagonal are not visited
            lo = (j * bk) // bq if cfg.causal else 0
            dk = jnp.zeros((bk, w), jnp.float32)
            dv = jnp.zeros((bk, w), jnp.float32)
            for h in range(heads):

                def q_block(i, carry, h=h):
                    dk_h, dv_h = carry
                    qrows = pl.ds(pl.multiple_of(i * bq, bq), bq)
                    ok = _valid((bk, bq), 1, i * bq, j * bk, length, cfg)
                    dq_t, dk_t, dv_t = _bwd_tile(
                        _one_head(_scaled(q_ref[r, qrows, :], cfg), h, d),
                        _one_head(do_ref[r, qrows, :], h, d), kb, vb,
                        lse_ref[r, 0, i, h:h + 1, :],
                        delta_ref[r, 0, i, h:h + 1, :], ok, cfg)
                    dq_acc[qrows, :] += _one_head(dq_t, h, d)
                    return dk_h + dk_t, dv_h + dv_t

                dk, dv = jax.lax.fori_loop(lo, nq, q_block, (dk, dv))
            dk_ref[r, krows, :] = dk.astype(dk_ref.dtype)
            dv_ref[r, krows, :] = dv.astype(dv_ref.dtype)
            return 0

        jax.lax.fori_loop(0, nk, k_block, 0)
        dq_ref[r] = dq_acc[...].astype(dq_ref.dtype)
        return 0

    _for_rows(bb, _BWD_UNROLL, row)


# ---- the calls ---------------------------------------------------------

def _block_b(b, row_bytes):
    """Batch rows a program takes: the largest divisor of ``b`` whose
    blocks stay under ``_BLOCK_BYTES`` (a grid step costs about 0.35 us,
    one row's work at L=256 little more)."""
    fit = max(1, _BLOCK_BYTES // max(row_bytes, 1))
    return max(n for n in range(1, min(b, fit) + 1) if b % n == 0)


def _params(cfg, n_parallel):
    if cfg.interpret:
        return {'interpret': True}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', ) * n_parallel,
        vmem_limit_bytes=_VMEM_LIMIT)}


def _stat_rows(cfg, w):
    return -(-(w // cfg.d) // _STAT_ROWS) * _STAT_ROWS


@functools.partial(jax.jit, static_argnames='cfg')
def _fwd_call(q, k, v, lens, cfg):
    """q: [B, Lq, H*D], k/v: [B, Lk, H*D], padded to whole blocks;
    lens: [B] int32 -> (o, lse [B, groups, Lq/bq, rows, bq])."""
    b, lq, hd = q.shape
    lk = k.shape[1]
    w = lane_group(cfg.heads, cfg.d)
    rows = _stat_rows(cfg, w)
    bb = _block_b(b, 2 * (cfg.bq + lk) * w * q.dtype.itemsize)
    qspec = pl.BlockSpec((bb, cfg.bq, w), lambda bi, g, i, lens: (bi, i, g))
    kvspec = pl.BlockSpec((bb, lk, w), lambda bi, g, i, lens: (bi, 0, g))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // bb, hd // w, lq // cfg.bq),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=[qspec, pl.BlockSpec(
                (bb, 1, 1, rows, cfg.bq),
                lambda bi, g, i, lens: (bi, g, i, 0, 0))]),
        out_shape=[
            jax.ShapeDtypeStruct((b, lq, hd), q.dtype),
            jax.ShapeDtypeStruct(
                (b, hd // w, lq // cfg.bq, rows, cfg.bq), jnp.float32)],
        name='paddle_tpu_flash_attention_fwd',
        **_params(cfg, 3))(lens, q, k, v)


@functools.partial(jax.jit, static_argnames='cfg')
def _bwd_call(q, k, v, lens, o, lse, do, cfg):
    b, lq, hd = q.shape
    lk = k.shape[1]
    w = lane_group(cfg.heads, cfg.d)
    nq = lq // cfg.bq
    bb = _block_b(b, (4 * lq + 3 * lk) * w * q.dtype.itemsize)
    qspec = pl.BlockSpec((bb, lq, w), lambda bi, g, lens: (bi, 0, g))
    kvspec = pl.BlockSpec((bb, lk, w), lambda bi, g, lens: (bi, 0, g))
    stat = pl.BlockSpec((bb, 1) + lse.shape[2:],
                        lambda bi, g, lens: (bi, g, 0, 0, 0))
    ins, in_specs, scratch = [q, k, v, do, lse], \
        [qspec, kvspec, kvspec, qspec, stat], []
    if nq > 1 or lk > cfg.bk:
        # delta[b, t, h] = sum_d dO * O, laid out as the log-sum-exp is
        heads_in = w // cfg.d
        delta = jnp.sum(
            (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
                b, nq, cfg.bq, hd // w, heads_in, cfg.d), axis=-1)
        delta = jnp.transpose(delta, (0, 3, 1, 4, 2))
        delta = jnp.pad(delta, ((0, 0), ) * 3 + (
            (0, lse.shape[3] - heads_in), (0, 0)))
        ins.append(delta)
        in_specs.append(stat)
        scratch = [pltpu.VMEM((lq, w), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b // bb, hd // w),
            in_specs=in_specs, out_specs=[qspec, kvspec, kvspec],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        name='paddle_tpu_flash_attention_bwd',
        **_params(cfg, 2))(lens, *ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, ))
def _flash(q, k, v, lens, cfg):
    """(o, lse): the one differentiable entry.  The log-sum-exp is a
    residual handed out, not a function to differentiate: its cotangent
    is not read."""
    return tuple(_fwd_call(q, k, v, lens, cfg))


def _flash_fwd(q, k, v, lens, cfg):
    o, lse = _fwd_call(q, k, v, lens, cfg)
    return (o, lse), (q, k, v, lens, o, lse)


def _flash_bwd(cfg, res, cotangents):
    return tuple(_bwd_call(*res, cotangents[0], cfg)) + (None, )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _round_up(n, m):
    return -(-n // m) * m


def _plan(q, k, causal, scale, seq_lengths, block_q, block_k, interpret):
    """The static configuration and the [B] lengths of one call."""
    b, lq, heads, d = q.shape
    lk = k.shape[1]
    # compiled tiles are whole vregs both ways; an explicit block (the
    # interpreted tests') is taken as given
    bq = (min(block_q, _round_up(lq, 8)) if block_q
          else min(_BLOCK, _round_up(lq, _LANES)))
    bk = (min(block_k, _round_up(lk, 8)) if block_k
          else min(_BLOCK, _round_up(lk, _LANES)))
    cfg = _Cfg(heads, d, bool(causal),
               float(scale) if scale is not None else d ** -0.5, bq, bk,
               seq_lengths is not None or lk % bk != 0,
               seq_lengths is not None, bool(interpret))
    if seq_lengths is None:
        lens = jnp.full((b, ), lk, jnp.int32)
    else:
        lens = jnp.asarray(seq_lengths, jnp.int32).reshape(b)
    return cfg, lens


def _flat_pad(x, block):
    """[B, L, H, D] -> [B, L padded to whole blocks, H*D]."""
    b, l = x.shape[:2]
    x = x.reshape(b, l, -1)
    pad = _round_up(l, block) - l
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def flash_attention(q, k, v, causal=False, scale=None, seq_lengths=None,
                    block_q=None, block_k=None, interpret=False,
                    return_residual=False):
    """Fused attention.  q: [B, Lq, H, D], k/v: [B, Lk, H, D] (Lq may
    differ from Lk for cross attention); seq_lengths: [B] valid K/V
    lengths.  Differentiable (``jax.custom_vjp``).
    interpret: run the kernel in Pallas interpret mode -- for CPU only;
    the caller decides from the place it lowers for (never from the
    ambient backend), so the default compiles for the chip.
    return_residual: also return the rows' log-sum-exp as the forward
    left it, for ``flash_attention_grad`` (a caller that keeps its own
    record of the forward, as the op's grad lowering does); the output
    is differentiable either way."""
    cfg, lens = _plan(q, k, causal, scale, seq_lengths, block_q, block_k,
                      interpret)
    o, lse = _flash(_flat_pad(q, cfg.bq), _flat_pad(k, cfg.bk),
                    _flat_pad(v, cfg.bk), lens, cfg)
    o = o[:, :q.shape[1]].reshape(q.shape)
    return (o, lse) if return_residual else o


def flash_attention_grad(q, k, v, out, lse, dout, causal=False, scale=None,
                         seq_lengths=None, block_q=None, block_k=None,
                         interpret=False):
    """(dQ, dK, dV) of ``flash_attention`` from its inputs, its output
    and the residual ``return_residual=True`` gave: the backward kernel
    alone, without the forward run again."""
    cfg, lens = _plan(q, k, causal, scale, seq_lengths, block_q, block_k,
                      interpret)
    dq, dk, dv = _bwd_call(
        _flat_pad(q, cfg.bq), _flat_pad(k, cfg.bk), _flat_pad(v, cfg.bk),
        lens, _flat_pad(out, cfg.bq), lse, _flat_pad(dout, cfg.bq), cfg)
    return (dq[:, :q.shape[1]].reshape(q.shape),
            dk[:, :k.shape[1]].reshape(k.shape),
            dv[:, :v.shape[1]].reshape(v.shape))
