"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

Never materialises the [L, L] score matrix in HBM: Q is blocked over the
grid, K/V stream through VMEM in `block_k` tiles folded into a blockwise
online softmax (running max / running sum).  The backward pass recomputes
probabilities from the saved log-sum-exp (the flash-attention trick) in
two kernels: one accumulating dQ over K blocks, one accumulating dK/dV
over Q blocks.

Layout: [batch, seq, heads, head_dim] END TO END.  The kernels see the
row-major [B, L, H*D] view and loop the heads INSIDE (unrolled — each
head is a static D-column slice), so the [B,L,H,D] -> [B,H,L,D]
transpose the usual formulation forces is never materialised.  In a
6-layer transformer those transposes (4 per attention forward + their
VJPs) were ~23% of the training step on hardware.
Variable-length rows mask K/V columns at ``seq_lengths`` — identical
semantics to parallel.context_parallel.dense_attention.

Scope: K/V for one batch row live in VMEM whole across all heads
(2 * L * H * D * 2 bytes bf16) — fine to L ≈ 4-8k at H*D = 512; longer
sequences belong to ring attention over the 'sp' mesh axis
(parallel/context_parallel.py), which shards L before the kernel runs.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['flash_attention']

_NEG_INF = -1e30


def _fwd_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                causal, block_q, block_k, kv_len, heads, d):
    iq = pl.program_id(1)
    length = lens_ref[pl.program_id(0), 0]
    bq = q_ref.shape[1]
    nk = kv_len // block_k
    if causal:
        # only K blocks intersecting col <= row can contribute
        hi = jnp.minimum(((iq + 1) * block_q + block_k - 1) // block_k, nk)
    else:
        hi = nk
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)

    for h in range(heads):
        q = q_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # [bq, D]

        def body(j, carry, h=h):
            m, l, acc = carry
            kb = k_ref[0, pl.ds(j * block_k, block_k),
                       h * d:(h + 1) * d].astype(jnp.float32)
            vb = v_ref[0, pl.ds(j * block_k, block_k),
                       h * d:(h + 1) * d].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kb, (((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = col < length
            if causal:
                mask = jnp.logical_and(mask, col <= row)
            s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p, vb,
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        acc0 = jnp.zeros((bq, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
        o_ref[0, :, h * d:(h + 1) * d] = (
            acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, :, h] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


def _dq_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, scale, causal, block_q, block_k, kv_len, heads,
               d):
    iq = pl.program_id(1)
    length = lens_ref[pl.program_id(0), 0]
    bq = q_ref.shape[1]
    nk = kv_len // block_k
    hi = (jnp.minimum(((iq + 1) * block_q + block_k - 1) // block_k, nk)
          if causal else nk)
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)

    for h in range(heads):
        q = q_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        do = do_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        lse = lse_ref[0, :, h][:, None]      # [bq, 1]
        delta = delta_ref[0, :, h][:, None]  # [bq, 1]

        def body(j, dq, h=h, q=q, do=do, lse=lse, delta=delta):
            kb = k_ref[0, pl.ds(j * block_k, block_k),
                       h * d:(h + 1) * d].astype(jnp.float32)
            vb = v_ref[0, pl.ds(j * block_k, block_k),
                       h * d:(h + 1) * d].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kb, (((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = col < length
            if causal:
                mask = jnp.logical_and(mask, col <= row)
            p = jnp.exp(jnp.where(mask, s, _NEG_INF) - lse)
            p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(do, vb, (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            return dq + jnp.dot(ds, kb, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, hi, body,
                               jnp.zeros((bq, d), jnp.float32))
        dq_ref[0, :, h * d:(h + 1) * d] = dq.astype(dq_ref.dtype)


def _dkv_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, block_q, block_k, q_len,
                heads, d):
    ik = pl.program_id(1)
    length = lens_ref[pl.program_id(0), 0]
    bk = k_ref.shape[1]
    nq = q_len // block_q
    # with causal masking, Q blocks strictly above the diagonal contribute 0
    lo = (ik * block_k) // block_q if causal else 0
    col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_k, block_q), 0)

    for h in range(heads):
        kb = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # [bk, D]
        vb = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)

        def body(j, carry, h=h, kb=kb, vb=vb):
            dk, dv = carry
            qb = q_ref[0, pl.ds(j * block_q, block_q),
                       h * d:(h + 1) * d].astype(jnp.float32)
            dob = do_ref[0, pl.ds(j * block_q, block_q),
                         h * d:(h + 1) * d].astype(jnp.float32)
            lseb = lse_ref[0, pl.ds(j * block_q, block_q), h][None, :]
            deltab = delta_ref[0, pl.ds(j * block_q, block_q), h][None, :]
            # s_T[bk, bq] = (K Q^T) * scale
            s = jax.lax.dot_general(
                kb, qb, (((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) * scale
            rowq = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            mask = col < length
            if causal:
                mask = jnp.logical_and(mask, col <= rowq)
            p = jnp.exp(jnp.where(mask, s, _NEG_INF) - lseb)
            p = jnp.where(mask, p, 0.0)
            dv = dv + jnp.dot(p, dob, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(vb, dob, (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - deltab) * scale
            dk = dk + jnp.dot(ds, qb, preferred_element_type=jnp.float32)
            return dk, dv

        z = jnp.zeros((bk, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(lo, nq, body, (z, z))
        dk_ref[0, :, h * d:(h + 1) * d] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, h * d:(h + 1) * d] = dv.astype(dv_ref.dtype)


def _pad_len(l, block):
    return ((l + block - 1) // block) * block


def _fwd_impl(q, k, v, lens, causal, scale, block_q, block_k, interpret,
              heads):
    """q,k,v: [B,Lq,H*D] / [B,Lk,H*D]; lens: [B,1] int32 -> (o, lse)."""
    b, lq, hd = q.shape
    lk = k.shape[1]
    d = hd // heads
    grid = (b, lq // block_q)
    qspec = pl.BlockSpec((1, block_q, hd), lambda bi, i: (bi, i, 0))
    kvspec = pl.BlockSpec((1, lk, hd), lambda bi, i: (bi, 0, 0))
    lsespec = pl.BlockSpec((1, block_q, heads), lambda bi, i: (bi, i, 0))
    lspec = pl.BlockSpec((b, 1), lambda bi, i: (0, 0),
                         memory_space=pltpu.SMEM)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=lk,
                          heads=heads, d=d),
        grid=grid,
        in_specs=[lspec, qspec, kvspec, kvspec],
        out_specs=[qspec, lsespec],
        out_shape=[
            jax.ShapeDtypeStruct((b, lq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, lq, heads), jnp.float32),
        ],
        interpret=interpret)(lens, q, k, v)
    return o, lse


def _bwd_impl(q, k, v, lens, o, lse, do, causal, scale, block_q, block_k,
              interpret, heads):
    b, lq, hd = q.shape
    lk = k.shape[1]
    d = hd // heads
    # delta[b, t, h] = sum_d do * o per head
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            b, lq, heads, d), axis=-1)
    qspec = pl.BlockSpec((1, block_q, hd), lambda bi, i: (bi, i, 0))
    qfull = pl.BlockSpec((1, lq, hd), lambda bi, i: (bi, 0, 0))
    kvspec = pl.BlockSpec((1, lk, hd), lambda bi, i: (bi, 0, 0))
    kvblk = pl.BlockSpec((1, block_k, hd), lambda bi, i: (bi, i, 0))
    rowblk = pl.BlockSpec((1, block_q, heads), lambda bi, i: (bi, i, 0))
    rowfull = pl.BlockSpec((1, lq, heads), lambda bi, i: (bi, 0, 0))
    lspec = pl.BlockSpec((b, 1), lambda bi, i: (0, 0),
                         memory_space=pltpu.SMEM)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=lk,
                          heads=heads, d=d),
        grid=(b, lq // block_q),
        in_specs=[lspec, qspec, kvspec, kvspec, qspec, rowblk, rowblk],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, lq, hd), q.dtype),
        interpret=interpret)(lens, q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, q_len=lq,
                          heads=heads, d=d),
        grid=(b, lk // block_k),
        in_specs=[lspec, qfull, kvblk, kvblk, qfull, rowfull, rowfull],
        out_specs=[kvblk, kvblk],
        out_shape=[
            jax.ShapeDtypeStruct((b, lk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, lk, hd), v.dtype),
        ],
        interpret=interpret)(lens, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, lens, causal, scale, block_q, block_k, interpret,
           heads):
    o, _ = _fwd_impl(q, k, v, lens, causal, scale, block_q, block_k,
                     interpret, heads)
    return o


def _flash_fwd(q, k, v, lens, causal, scale, block_q, block_k, interpret,
               heads):
    o, lse = _fwd_impl(q, k, v, lens, causal, scale, block_q, block_k,
                       interpret, heads)
    return o, (q, k, v, lens, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, heads, res, do):
    q, k, v, lens, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, lens, o, lse, do, causal, scale,
                           block_q, block_k, interpret, heads)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, seq_lengths=None,
                    block_q=128, block_k=128, interpret=False):
    """Blocked flash attention.  q,k,v: [B, L, H, D] (Lq may differ from
    Lk for cross attention); seq_lengths: [B] valid K/V lengths.
    interpret: run the kernel in Pallas interpret mode — for CPU only;
    the caller decides from the place it lowers for (never from the
    ambient backend), so the default compiles for the chip."""
    scale = float(scale) if scale is not None else q.shape[-1]**-0.5
    b, lq, heads, d = q.shape
    lk = k.shape[1]
    block_q = min(block_q, _pad_len(lq, 8))
    block_k = min(block_k, _pad_len(lk, 8))
    lq_p = _pad_len(lq, block_q)
    lk_p = _pad_len(lk, block_k)
    if seq_lengths is None:
        lens = jnp.full((b, 1), lk, jnp.int32)
    else:
        lens = jnp.asarray(seq_lengths, jnp.int32).reshape(b, 1)

    def flat_pad(x, lpad):
        x = x.reshape(x.shape[0], x.shape[1], heads * d)
        pad = lpad - x.shape[1]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x

    o = _flash(flat_pad(q, lq_p), flat_pad(k, lk_p), flat_pad(v, lk_p),
               lens, bool(causal), scale, block_q, block_k,
               bool(interpret), heads)
    return o[:, :lq].reshape(b, lq, heads, d)
