"""Fused LSTM recurrence as a Pallas TPU kernel (forward + custom VJP).

The TPU-native answer to the reference's fused cell
(`paddle/fluid/operators/math/lstm_compute.h` +
`math/detail/lstm_cpu_kernel.h` — reference fuses the gate math per
timestep; `sequence2batch.h` handles reordering).  Here the WHOLE
recurrence is one kernel: the grid walks T sequentially, the hidden and
cell state live in VMEM scratch across grid steps, each step does one
[B,D]x[D,4D] MXU matmul plus VPU gate math, and the per-step gate
activations are saved as bf16 residuals for the backward kernel.  The
backward kernel walks the grid REVERSED (via index_map) carrying
dh/dc/dW/db accumulators in VMEM scratch.

Semantics match ops/sequence_ops.py:_lstm exactly (gate order
candidate/input/forget/output, bf16 h + f32 c under AMP, per-step
length masking); peepholes are not fused — the lowering falls back to
the lax.scan path for those.

Layout: x arrives [T, B, 4D] (time-major, as the scan path uses);
D and 4D must be multiples of 128 lanes for clean VMEM tiling.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['lstm_fused', 'lstm_fused_tm']


def _sigmoid(x):
    return jax.nn.sigmoid(x)


def _fwd_kernel(x_ref, w_ref, b_ref, h0_ref, c0_ref, m_ref,
                *refs, d, save_acts):
    if save_acts:
        hs_ref, cs_ref, acts_ref, h_scr, c_scr = refs
    else:
        hs_ref, cs_ref, h_scr, c_scr = refs
    t = pl.program_id(1)  # grid = (batch_blocks, T); T iterates fastest

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...]

    h = h_scr[...]
    c = c_scr[...]
    gates = x_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h, w_ref[...], (((1, ), (0, )), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[0].astype(jnp.float32)
    gc = gates[:, :d]
    gi = gates[:, d:2 * d]
    gf = gates[:, 2 * d:3 * d]
    go = gates[:, 3 * d:]
    i = _sigmoid(gi)
    f = _sigmoid(gf)
    o = _sigmoid(go)
    cand = jnp.tanh(gc)
    c_new = f * c + i * cand
    h_new = o * jnp.tanh(c_new)
    m = m_ref[0, 0][:, None]
    h_out = (m * h_new + (1 - m) * h.astype(jnp.float32)).astype(hs_ref.dtype)
    c_out = m * c_new + (1 - m) * c
    h_scr[...] = h_out
    c_scr[...] = c_out
    hs_ref[0] = h_out
    cs_ref[0] = c_out
    if save_acts:
        acts_ref[0, :, :d] = cand.astype(acts_ref.dtype)
        acts_ref[0, :, d:2 * d] = i.astype(acts_ref.dtype)
        acts_ref[0, :, 2 * d:3 * d] = f.astype(acts_ref.dtype)
        acts_ref[0, :, 3 * d:] = o.astype(acts_ref.dtype)


def _bwd_kernel(w_ref, m_ref, acts_ref, csp_ref, hsp_ref, h0_ref, c0_ref,
                dhs_ref, dcs_ref, dx_ref, dw_ref, db_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, dw_scr, db_scr, *, d, t_total):
    bi = pl.program_id(0)
    t = pl.program_id(1)  # 0..T-1 walking REVERSED logical time, fastest
    # csp/hsp blocks are cs/hs read at logical time-1 (shifted index map,
    # clamped at 0); at the first logical step the real prev state is h0/c0
    first = t == t_total - 1
    c_prev_blk = csp_ref[0]
    h_prev_blk = hsp_ref[0]
    c_prev = jnp.where(first, c0_ref[...], c_prev_blk)
    h_prev = jnp.where(first, h0_ref[...], h_prev_blk)

    @pl.when(t == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    @pl.when(jnp.logical_and(bi == 0, t == 0))
    def _init_wb():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    cand = acts_ref[0, :, :d].astype(jnp.float32)
    i = acts_ref[0, :, d:2 * d].astype(jnp.float32)
    f = acts_ref[0, :, 2 * d:3 * d].astype(jnp.float32)
    o = acts_ref[0, :, 3 * d:].astype(jnp.float32)
    c_new = f * c_prev + i * cand  # pre-mask cell, recomputed
    tanh_c = jnp.tanh(c_new)
    m = m_ref[0, 0][:, None]

    dh_tot = dhs_ref[0].astype(jnp.float32) + dh_scr[...]
    dc_tot = dcs_ref[0] + dc_scr[...]
    dh_new = m * dh_tot
    do = dh_new * tanh_c
    dc_new = m * dc_tot + dh_new * o * (1 - tanh_c * tanh_c)
    di = dc_new * cand
    df = dc_new * c_prev
    dcand = dc_new * i
    dgi = di * i * (1 - i)
    dgf = df * f * (1 - f)
    dgo = do * o * (1 - o)
    dgc = dcand * (1 - cand * cand)
    dgates = jnp.concatenate([dgc, dgi, dgf, dgo], axis=1)
    dx_ref[0] = dgates.astype(dx_ref.dtype)

    dg16 = dgates.astype(w_ref.dtype)
    # dh_prev = (1-m)*dh_tot + dgates @ W^T
    dh_scr[...] = (1 - m) * dh_tot + jax.lax.dot_general(
        dg16, w_ref[...], (((1, ), (1, )), ((), ())),
        preferred_element_type=jnp.float32)
    dc_scr[...] = (1 - m) * dc_tot + dc_new * f
    # dW += h_prev^T @ dgates ; db += sum_b dgates
    dw_scr[...] += jax.lax.dot_general(
        h_prev.astype(dg16.dtype), dg16, (((0, ), (0, )), ((), ())),
        preferred_element_type=jnp.float32)
    db_scr[...] += jnp.sum(dgates, axis=0, keepdims=True)

    @pl.when(t == t_total - 1)
    def _finish():
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[...]

    @pl.when(jnp.logical_and(bi == pl.num_programs(0) - 1,
                             t == t_total - 1))
    def _finish_wb():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)
        db_ref[...] = db_scr[...].astype(db_ref.dtype)


def _batch_block(b, d4):
    """Batch tile dividing b, sized so the backward kernel's VMEM budget
    (dw accumulator + double-buffered per-step blocks) stays under the
    ~16MB scoped limit; measured: bq=256 at 4D=2048 overflows by 0.3MB."""
    cap = 256 if d4 <= 1024 else 128
    if b <= cap:
        return b
    for bq in (cap, 128, 64, 32, 16, 8):
        if bq <= cap and b % bq == 0:
            return bq
    return b


def _fwd_impl(xs, w16, bias, h0, c0, mask, interpret, save_acts=True):
    t, b, d4 = xs.shape
    d = d4 // 4
    bq = _batch_block(b, d4)
    step = pl.BlockSpec((1, bq, d4), lambda bi, i: (i, bi, 0))
    steph = pl.BlockSpec((1, bq, d), lambda bi, i: (i, bi, 0))
    stepm = pl.BlockSpec((1, 1, bq), lambda bi, i: (i, 0, bi))
    blkh = pl.BlockSpec((bq, d), lambda bi, i: (bi, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda bi, i: tuple(
        0 for _ in shape))
    out_specs = [steph, steph]
    out_shape = [
        jax.ShapeDtypeStruct((t, b, d), h0.dtype),
        jax.ShapeDtypeStruct((t, b, d), jnp.float32),
    ]
    if save_acts:
        out_specs.append(step)
        out_shape.append(jax.ShapeDtypeStruct((t, b, d4), w16.dtype))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, save_acts=save_acts),
        grid=(b // bq, t),
        in_specs=[step, full((d, d4)), full((1, d4)), blkh, blkh, stepm],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, d), h0.dtype),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret)(xs, w16, bias, h0, c0, mask)
    if save_acts:
        return outs
    hs, cs = outs
    return hs, cs, None


def _bwd_impl(w16, mask, acts, cs, hs, h0, c0, dhs, dcs, interpret,
              x_dtype):
    t, b, d4 = acts.shape
    d = d4 // 4
    bq = _batch_block(b, d4)
    rev = lambda bi, i: (t - 1 - i, bi, 0)
    revm = lambda bi, i: (t - 1 - i, 0, bi)
    # cs/hs read at logical time-1: array index T-2-i, clamped at 0 (the
    # i == T-1 block is discarded in-kernel in favor of h0/c0) — avoids
    # materializing shifted [T,B,D] copies in HBM
    revp = lambda bi, i: (jnp.maximum(t - 2 - i, 0), bi, 0)
    step = pl.BlockSpec((1, bq, d4), rev)
    steph = pl.BlockSpec((1, bq, d), rev)
    stephp = pl.BlockSpec((1, bq, d), revp)
    stepm = pl.BlockSpec((1, 1, bq), revm)
    blkh = pl.BlockSpec((bq, d), lambda bi, i: (bi, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda bi, i: tuple(
        0 for _ in shape))
    dx, dw, db, dh0, dc0 = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, t_total=t),
        grid=(b // bq, t),
        in_specs=[full((d, d4)), stepm, step, stephp, stephp, blkh, blkh,
                  steph, steph],
        out_specs=[step, full((d, d4)), full((1, d4)), blkh, blkh],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, d4), x_dtype),
            jax.ShapeDtypeStruct((d, d4), jnp.float32),
            jax.ShapeDtypeStruct((1, d4), jnp.float32),
            jax.ShapeDtypeStruct((b, d), h0.dtype),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((d, d4), jnp.float32),
            pltpu.VMEM((1, d4), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret)(w16, mask, acts, cs, hs, h0, c0, dhs, dcs)
    return dx, dw, db, dh0, dc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, ))
def _lstm_core(xs, w16, bias, h0, c0, mask, interpret):
    # primal (no grad requested): skip the [T,B,4D] acts residual write
    hs, cs, _ = _fwd_impl(xs, w16, bias, h0, c0, mask, interpret,
                          save_acts=False)
    return hs, cs


def _lstm_core_fwd(xs, w16, bias, h0, c0, mask, interpret):
    hs, cs, acts = _fwd_impl(xs, w16, bias, h0, c0, mask, interpret)
    return (hs, cs), (w16, mask, acts, cs, hs, h0, c0)


def _lstm_core_bwd(interpret, res, grads):
    w16, mask, acts, cs, hs, h0, c0 = res
    x_dtype = w16.dtype  # w16 was cast to x's dtype in lstm_fused_tm
    dhs, dcs = grads
    dx, dw, db, dh0, dc0 = _bwd_impl(
        w16, mask, acts, cs, hs, h0, c0, dhs,
        dcs.astype(jnp.float32), interpret, x_dtype)
    return (dx, dw.astype(w16.dtype), db.astype(jnp.float32), dh0, dc0,
            None)


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def lstm_fused_tm(xs, w, bias, h0, c0, mask=None, interpret=False):
    """Time-major fused LSTM: xs [T,B,4D] pre-projected gates, w [D,4D],
    bias [1,4D], h0 [B,D] (hidden dtype), c0 [B,D] f32, mask [T,B] or
    None.  Returns (hs [T,B,D] in h0.dtype, cs [T,B,D] f32).
    interpret: Pallas interpret mode, for CPU only — the lowering
    passes ctx.on_cpu; the default compiles for the chip."""
    t, b, d4 = xs.shape
    if mask is None:
        mask = jnp.ones((t, b), jnp.float32)
    mask = mask.reshape(t, 1, b)
    w16 = w.astype(xs.dtype)
    bias = jnp.asarray(bias, jnp.float32).reshape(1, d4)
    return _lstm_core(xs, w16, bias, h0, c0, mask, bool(interpret))


def lstm_fused(x, w, bias, h0, c0, mask=None, interpret=False):
    """Batch-major convenience wrapper: x [B,T,4D] -> hs [B,T,D]."""
    xs = jnp.swapaxes(x, 0, 1)
    m = None if mask is None else jnp.swapaxes(mask, 0, 1)
    hs, _ = lstm_fused_tm(xs, w, bias, h0, c0, m, interpret)
    return jnp.swapaxes(hs, 0, 1)
