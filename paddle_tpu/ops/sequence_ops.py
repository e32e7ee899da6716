"""Sequence op lowerings: LoD semantics on static shapes.

The reference stores variable-length batches concatenated with LoD offset
tables and runs LoD-aware kernels (framework/lod_tensor.h:58,
operators/sequence_*); dynamic RNNs reorder via math/sequence2batch.h.
XLA needs static shapes, so (SURVEY §5.7) LoD feeds are lowered to padded
``[B, T, ...]`` tensors plus an int32 ``lengths[B]`` carried in the env
under ``<name>@SEQLEN`` (propagated by registry.run_op).  Every sequence op
is a masked dense op; RNNs are ``lax.scan`` over the time axis — which is
exactly the TPU-friendly formulation (big batched matmuls per step).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .registry import (register_lowering, register_grad_lowering,
                       fwd_structure, SEQLEN_SUFFIX, ROWS_SUFFIX)


def _seqlen(ctx, op, slot='X'):
    names = op.input(slot)
    if not names:
        return None
    return ctx.env.get(names[0] + SEQLEN_SUFFIX)


def _nested_segments(rows, r):
    """Packed nested layout bookkeeping: per-sample row starts and each
    global row's owning sample (rows [B] may be traced)."""
    cum = jnp.cumsum(rows)
    start = cum - rows
    seg = jnp.clip(jnp.searchsorted(cum, jnp.arange(r), side='right'),
                   0, int(rows.shape[0]) - 1)
    return start, seg


def _mask(x, lengths, dtype=None):
    """[B, T] validity mask broadcastable against x [B, T, ...]."""
    t = x.shape[1]
    m = jnp.arange(t)[None, :] < lengths[:, None]
    if dtype is not None:
        m = m.astype(dtype)
    return m


def _expand_mask(m, x):
    return jnp.reshape(m, m.shape + (1, ) * (x.ndim - 2))


@register_lowering('sequence_pool')
def _sequence_pool(ctx, op):
    x = ctx.get(op, 'X')  # [B, T, ...]
    lengths = _seqlen(ctx, op)
    ptype = op.attrs.get('pooltype', 'AVERAGE').upper()
    if lengths is None:
        lengths = jnp.full((x.shape[0], ), x.shape[1], jnp.int32)
    m = _expand_mask(_mask(x, lengths, x.dtype), x)
    lens = jnp.maximum(lengths, 1).astype(x.dtype)
    lens = jnp.reshape(lens, (x.shape[0], ) + (1, ) * (x.ndim - 2))
    if ptype == 'SUM':
        out = jnp.sum(x * m, axis=1)
    elif ptype == 'AVERAGE':
        out = jnp.sum(x * m, axis=1) / lens
    elif ptype == 'SQRT':
        out = jnp.sum(x * m, axis=1) / jnp.sqrt(lens)
    elif ptype == 'MAX':
        neg = jnp.full_like(x, -jnp.inf)
        out = jnp.max(jnp.where(m > 0, x, neg), axis=1)
        out = jnp.where(jnp.reshape(lengths, lens.shape) > 0, out,
                        jnp.zeros_like(out))
    elif ptype == 'LAST':
        idx = jnp.maximum(lengths - 1, 0)
        out = jnp.take_along_axis(
            x, jnp.reshape(idx, (-1, 1) + (1, ) * (x.ndim - 2)),
            axis=1)[:, 0]
    elif ptype == 'FIRST':
        out = x[:, 0]
    else:
        raise NotImplementedError('sequence_pool type %r' % ptype)
    rows = ctx.env.get(op.input('X')[0] + ROWS_SUFFIX)
    if rows is not None and op.attrs.get('agg_to_no_sequence', False):
        # nested input + AggregateLevel.TO_NO_SEQUENCE (the reference
        # default, layers.py:302): aggregate over ALL timesteps of each
        # TOP-level sequence.  The inner pooling above gives one value
        # per sub-sequence row; reduce those per sample with the same
        # pool semantics (average = total/total-count, not
        # average-of-averages).
        b = int(rows.shape[0])
        r = x.shape[0]
        start, seg = _nested_segments(rows, r)
        row_cnt = lengths.astype(jnp.float32)
        tot_cnt = jax.ops.segment_sum(row_cnt, seg, num_segments=b)
        safe_cnt = jnp.maximum(tot_cnt, 1.0).reshape(
            (b, ) + (1, ) * (out.ndim - 1)).astype(out.dtype)
        if ptype in ('SUM', 'AVERAGE', 'SQRT'):
            # the inner pool already produced the masked time-sum (out
            # IS it for SUM; AVERAGE/SQRT divided it by lens)
            if ptype == 'SUM':
                row_tot = out
            elif ptype == 'AVERAGE':
                row_tot = out * lens
            else:
                row_tot = out * jnp.sqrt(lens)
            tot = jax.ops.segment_sum(row_tot, seg, num_segments=b)
            if ptype == 'SUM':
                out = tot
            elif ptype == 'AVERAGE':
                out = tot / safe_cnt
            else:
                out = tot / jnp.sqrt(safe_cnt)
        elif ptype == 'MAX':
            row_max = jnp.where(
                jnp.reshape(lengths, lens.shape) > 0, out,
                jnp.full_like(out, -jnp.inf))
            out = jax.ops.segment_max(row_max, seg, num_segments=b)
            out = jnp.where(jnp.isfinite(out), out, jnp.zeros_like(out))
        elif ptype in ('LAST', 'FIRST'):
            # the sample's true last/first timestep lives in its last/
            # first NON-EMPTY sub-sequence (an empty trailing/leading
            # row would otherwise contribute its padding); a sample
            # with no non-empty rows pools to zeros
            valid_row = lengths > 0
            idx = jnp.arange(r)
            if ptype == 'LAST':
                pick = jax.ops.segment_max(
                    jnp.where(valid_row, idx, -1), seg, num_segments=b)
            else:
                pick = jax.ops.segment_min(
                    jnp.where(valid_row, idx, r + 1), seg,
                    num_segments=b)
            has_any = (pick >= 0) & (pick <= r - 1)
            out = jnp.take(out, jnp.clip(pick, 0, r - 1), axis=0)
            out = jnp.where(
                has_any.reshape((b, ) + (1, ) * (out.ndim - 1)), out,
                jnp.zeros_like(out))
        ctx.set(op, 'Out', out)
        if ptype == 'MAX':
            ctx.set(op, 'MaxIndex', jnp.zeros(out.shape, jnp.int32))
        return
    if rows is not None:
        # TO_SEQUENCE on a nested input: the per-row pooled values form
        # a plain sequence — REPAD into the canonical [B, T, ...] +
        # @SEQLEN runtime form so downstream sequence ops compose
        # (T bound: no sample can own more than all R rows)
        b = int(rows.shape[0])
        r = out.shape[0]
        start, seg = _nested_segments(rows, r)
        slot = jnp.arange(r) - jnp.take(start, seg)
        padded = jnp.zeros((b, r) + out.shape[1:], out.dtype)
        padded = padded.at[seg, slot].set(out)
        ctx.set(op, 'Out', padded)
        ctx.env[op.output('Out')[0] + SEQLEN_SUFFIX] = \
            rows.astype(jnp.int32)
        if ptype == 'MAX':
            ctx.set(op, 'MaxIndex', jnp.zeros(padded.shape, jnp.int32))
        return
    ctx.set(op, 'Out', out)
    if ptype == 'MAX':
        ctx.set(op, 'MaxIndex',
                jnp.zeros(out.shape, jnp.int32))  # index output (unused)


@register_lowering('sequence_last_step')
def _sequence_last_step(ctx, op):
    op.attrs['pooltype'] = 'LAST'
    _sequence_pool(ctx, op)


@register_lowering('sequence_first_step')
def _sequence_first_step(ctx, op):
    op.attrs['pooltype'] = 'FIRST'
    _sequence_pool(ctx, op)


@register_lowering('sequence_softmax')
def _sequence_softmax(ctx, op):
    x = ctx.get(op, 'X')  # [B, T] or [B, T, 1]
    lengths = _seqlen(ctx, op)
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    v = x[..., 0] if squeeze else x
    if lengths is None:
        out = jax.nn.softmax(v, axis=1)
    else:
        m = _mask(v, lengths)
        out = jax.nn.softmax(jnp.where(m, v, -1e30), axis=1)
        out = jnp.where(m, out, jnp.zeros_like(out))
    ctx.set(op, 'Out', out[..., None] if squeeze else out)


@register_lowering('sequence_reverse')
def _sequence_reverse(ctx, op):
    """Mask-aware per-sequence time reversal: out[b, t] = x[b, L_b-1-t]
    for t < L_b, padding stays zero in place (the reference's
    reverse-recurrence input transform; reverse_op.cc is the dense-axis
    cousin).  Lengths propagate unchanged."""
    x = ctx.get(op, 'X')
    lengths = _seqlen(ctx, op)
    t = x.shape[1]
    if lengths is None:
        ctx.set(op, 'Out', jnp.flip(x, axis=1))
        return
    lengths = lengths.astype(jnp.int32)
    pos = jnp.arange(t)[None, :]
    src = jnp.clip(lengths[:, None] - 1 - pos, 0, t - 1)
    out = jnp.take_along_axis(
        x, src.reshape(src.shape + (1, ) * (x.ndim - 2)), axis=1)
    m = _expand_mask(_mask(x, lengths, x.dtype), x)
    ctx.set(op, 'Out', out * m)


@register_lowering('sequence_expand')
def _sequence_expand(ctx, op):
    """Broadcast each batch row of X across its ref sequence's steps
    (reference sequence_expand_op.cc, level-1 semantics on padded form).

    With attr ``expand_from_sequence`` and a NESTED ref (the legacy
    ExpandLevel.FROM_SEQUENCE, reference layers.py:1838): X is a plain
    sequence whose j-th item of sample b broadcasts across the j-th
    sub-sequence of the nested ref — SEQUENCE expands to SUB_SEQUENCE."""
    x = ctx.get(op, 'X')  # [B, D] or [B, 1, D]
    y = ctx.get(op, 'Y')  # [B, T, ...] provides the target lengths
    ynames = op.input('Y')
    rows = (ctx.env.get(ynames[0] + ROWS_SUFFIX) if ynames else None)
    if op.attrs.get('expand_from_sequence') and rows is None:
        raise ValueError(
            'sequence_expand(FROM_SEQUENCE): the expand_as ref %r is '
            'not a nested (2-level LoD) sequence — the reference '
            'errors on this level mismatch; use FROM_NO_SEQUENCE for '
            'a plain ref' % (ynames[0] if ynames else None))
    if op.attrs.get('expand_from_sequence') and rows is not None:
        # X [B, Tx, D] items -> ref rows [R, T2, ...]
        if x.ndim < 3:
            raise ValueError(
                'sequence_expand(FROM_SEQUENCE): X must be a SEQUENCE '
                '(padded [B, T, D]), got shape %s — FROM_NO_SEQUENCE '
                'is the level for per-sample inputs' % (x.shape, ))
        b = int(rows.shape[0])
        r = y.shape[0]
        start, seg = _nested_segments(rows, r)
        raw_slot = jnp.arange(r) - jnp.take(start, seg)
        slot = jnp.clip(raw_slot, 0, x.shape[1] - 1)
        vals = x[seg, slot]                      # [R, D]
        # a ref sub-sequence beyond X's own item count gets zeros, not
        # clipped garbage (reference errors on the length mismatch;
        # lengths are traced here, so mask instead — caller contract)
        x_lens = ctx.env.get(op.input('X')[0] + SEQLEN_SUFFIX)
        if x_lens is not None:
            ok = raw_slot < jnp.take(x_lens.astype(jnp.int32), seg)
            vals = jnp.where(
                ok.reshape((-1, ) + (1, ) * (vals.ndim - 1)), vals,
                jnp.zeros_like(vals))
        t2 = y.shape[1]
        out = jnp.repeat(vals[:, None], t2, axis=1)  # [R, T2, D]
        inner = ctx.env.get(ynames[0] + SEQLEN_SUFFIX)
        if inner is not None:
            m = _mask(out, inner.astype(jnp.int32), out.dtype)
            out = out * jnp.reshape(
                m, m.shape + (1, ) * (out.ndim - 2))
            ctx.env[op.output('Out')[0] + SEQLEN_SUFFIX] = \
                inner.astype(jnp.int32)
        ctx.env[op.output('Out')[0] + ROWS_SUFFIX] = \
            rows.astype(jnp.int32)
        ctx.set(op, 'Out', out)
        return
    if x.ndim == y.ndim:  # already time-major: tile per-step
        ctx.set(op, 'Out', x)
        return
    t = y.shape[1]
    out = jnp.repeat(x[:, None], t, axis=1)
    ctx.set(op, 'Out', out)
    if ynames and (ynames[0] + SEQLEN_SUFFIX) in ctx.env:
        for n in op.output('Out'):
            ctx.env[n + SEQLEN_SUFFIX] = ctx.env[ynames[0] + SEQLEN_SUFFIX]


@register_lowering('sequence_concat')
def _sequence_concat(ctx, op):
    """Per-instance TIME concatenation with summed lengths (reference
    sequence_concat_op default axis=0 semantics, on padded form)."""
    xs = ctx.get_list(op, 'X')
    names = op.input('X')
    lens = []
    for name, x in zip(names, xs):
        l = ctx.env.get(name + SEQLEN_SUFFIX)
        if l is None:
            l = jnp.full((x.shape[0], ), x.shape[1], jnp.int32)
        lens.append(l)
    total_t = sum(x.shape[1] for x in xs)
    b = xs[0].shape[0]
    out = jnp.zeros((b, total_t) + xs[0].shape[2:], xs[0].dtype)
    pos = jnp.arange(total_t)[None, :]  # [1, total_t]
    offset = jnp.zeros((b, ), jnp.int32)
    for x, l in zip(xs, lens):
        # place x[b, 0:l_b] at out[b, offset_b:offset_b+l_b]
        j = pos - offset[:, None]
        valid = (j >= 0) & (j < l[:, None])
        j_cl = jnp.clip(j, 0, x.shape[1] - 1)
        gathered = jnp.take_along_axis(
            x, jnp.reshape(j_cl, (b, total_t) + (1, ) * (x.ndim - 2)),
            axis=1)
        mask = jnp.reshape(valid, (b, total_t) + (1, ) * (x.ndim - 2))
        out = jnp.where(mask, gathered, out)
        offset = offset + l
    ctx.set(op, 'Out', out)
    for n in op.output('Out'):
        ctx.env[n + SEQLEN_SUFFIX] = offset


@register_lowering('sequence_reshape')
def _sequence_reshape(ctx, op):
    x = ctx.get(op, 'X')  # [B, T, D]
    new_dim = op.attrs['new_dim']
    b, t, d = x.shape
    ctx.set(op, 'Out', jnp.reshape(x, (b, t * d // new_dim, new_dim)))
    # lengths rescale by d/new_dim (reference sequence_reshape_op.cc)
    lengths = _seqlen(ctx, op)
    if lengths is not None:
        for n in op.output('Out'):
            ctx.env[n + SEQLEN_SUFFIX] = lengths * d // new_dim


@register_lowering('sequence_conv')
def _sequence_conv(ctx, op):
    """Context-window projection over time
    (reference operators/sequence_conv_op.cc + math/context_project.h)."""
    x = ctx.get(op, 'X')  # [B, T, D]
    w = ctx.get(op, 'Filter')  # [ctx_len * D, M]
    lengths = _seqlen(ctx, op)
    ctx_len = op.attrs.get('contextLength', 3)
    ctx_start = op.attrs.get('contextStart', -(ctx_len // 2))
    b, t, d = x.shape
    if lengths is not None:
        x = x * _expand_mask(_mask(x, lengths, x.dtype), x)
    # pad time so every window is in-bounds, then gather shifted views
    pad_lo = max(-ctx_start, 0)
    pad_hi = max(ctx_start + ctx_len - 1, 0)
    xp = jnp.pad(x, ((0, 0), (pad_lo, pad_hi), (0, 0)))
    views = [
        xp[:, pad_lo + ctx_start + i:pad_lo + ctx_start + i + t]
        for i in range(ctx_len)
    ]
    ctx_mat = jnp.concatenate(views, axis=-1)  # [B, T, ctx_len*D]
    ctx.set(op, 'Out', jnp.einsum('btc,cm->btm', ctx_mat, w))


@register_lowering('sequence_slice')
def _sequence_slice(ctx, op):
    """Per-sequence window (reference sequence_slice_op.cc: each row i
    keeps [offset_i, offset_i + length_i)).  Static layout: rows are
    gathered to the front of the same padded buffer and the lengths
    side-band becomes length_i — offsets/lengths may be traced per-row
    values or concrete scalars."""
    x = ctx.get(op, 'X')
    offset = jnp.reshape(ctx.get(op, 'Offset'), (-1, )).astype(jnp.int32)
    length = jnp.reshape(ctx.get(op, 'Length'), (-1, )).astype(jnp.int32)
    b, t = x.shape[0], x.shape[1]
    if offset.shape[0] == 1 and b > 1:
        offset = jnp.broadcast_to(offset, (b, ))
    if length.shape[0] == 1 and b > 1:
        length = jnp.broadcast_to(length, (b, ))
    pos = jnp.arange(t)[None, :]  # [1, T]
    idx = jnp.clip(offset[:, None] + pos, 0, t - 1)
    gathered = jnp.take_along_axis(
        x, jnp.reshape(idx, (b, t) + (1, ) * (x.ndim - 2)), axis=1)
    valid = pos < length[:, None]
    out = jnp.where(
        jnp.reshape(valid, (b, t) + (1, ) * (x.ndim - 2)), gathered,
        jnp.zeros_like(gathered))
    ctx.set(op, 'Out', out)
    for n in op.output('Out'):
        ctx.env[n + SEQLEN_SUFFIX] = length


@register_lowering('sequence_enumerate')
def _sequence_enumerate(ctx, op):
    x = ctx.get(op, 'X')  # [B, T] or [B, T, 1] int ids
    win = op.attrs['win_size']
    pad_value = op.attrs.get('pad_value', 0)
    squeeze = x.ndim == 3
    v = x[..., 0] if squeeze else x
    b, t = v.shape
    vp = jnp.pad(v, ((0, 0), (0, win - 1)), constant_values=pad_value)
    out = jnp.stack([vp[:, i:i + t] for i in range(win)], axis=-1)
    ctx.set(op, 'Out', out)


@register_lowering('sequence_erase')
def _sequence_erase(ctx, op):
    """Remove listed tokens (reference sequence_erase_op.cc shrinks the LoD
    rows).  Static shapes forbid true erasure, so kept tokens are compacted
    to the front of the padded buffer and the @SEQLEN side-band shrinks to
    the new per-row counts — downstream sequence ops see the same semantics
    as the reference's re-lodded output."""
    x = ctx.get(op, 'X')
    tokens = op.attrs.get('tokens', [])
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    xv = x[..., 0] if squeeze else x
    if xv.ndim == 1:
        xv = xv[None]
        batchless = True
    else:
        batchless = False
    b, t = xv.shape[0], xv.shape[1]
    lens = _seqlen(ctx, op)
    if lens is None:
        lens = jnp.full((b, ), t, jnp.int32)
    valid = jnp.arange(t)[None, :] < lens[:, None]
    keep = valid
    for tok in tokens:
        keep = keep & (xv != tok)
    dest = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    # route dropped entries to a scratch column, then slice it off
    dest = jnp.where(keep, dest, t)
    out = jnp.zeros((b, t + 1), xv.dtype)
    out = out.at[jnp.arange(b)[:, None], dest].set(xv)[:, :t]
    new_lens = jnp.sum(keep.astype(jnp.int32), axis=1)
    if batchless:
        out = out[0]
    if squeeze:
        out = out[..., None]
    ctx.set(op, 'Out', out)
    for n in op.output('Out'):
        ctx.env[n + SEQLEN_SUFFIX] = new_lens


@register_lowering('sequence_pad')
def _sequence_pad(ctx, op):
    # inputs are already padded in this lowering scheme
    x = ctx.get(op, 'X')
    ctx.set(op, 'Out', x)
    lengths = _seqlen(ctx, op)
    if lengths is not None:
        ctx.set(op, 'Length', lengths.astype(jnp.int64))


@register_lowering('sequence_unpad')
def _sequence_unpad(ctx, op):
    ctx.set(op, 'Out', ctx.get(op, 'X'))


# ----------------------------------------------------------------------------
# Recurrent nets: lax.scan over the time axis
# ----------------------------------------------------------------------------
def _act(name):
    return {
        'sigmoid': jax.nn.sigmoid,
        'tanh': jnp.tanh,
        'relu': jax.nn.relu,
        'identity': lambda v: v,
    }[name or 'tanh']


@register_lowering('lstm')
def _lstm(ctx, op):
    """Dynamic LSTM (reference operators/lstm_op.cc).  Input is the
    pre-projected gate matrix [B, T, 4D]; the op runs the recurrence
    h_t = f(x_t + h_{t-1} W + b) with per-step masking replacing the
    reference's sequence2batch reordering.  Gate order: i, f, c, o."""
    x = ctx.get(op, 'Input')  # [B, T, 4D]
    w = ctx.get(op, 'Weight')  # [D, 4D]
    bias = ctx.get(op, 'Bias')  # [1, 4D] (+ [1, 3D] peephole tail)
    h0 = ctx.get(op, 'H0')
    c0 = ctx.get(op, 'C0')
    lengths = _seqlen(ctx, op, 'Input')
    use_peepholes = op.attrs.get('use_peepholes', False)
    is_reverse = op.attrs.get('is_reverse', False)
    gate_act = _act(op.attrs.get('gate_activation', 'sigmoid'))
    cell_act = _act(op.attrs.get('cell_activation', 'tanh'))
    cand_act = _act(op.attrs.get('candidate_activation', 'tanh'))

    b_sz, t, d4 = x.shape
    d = d4 // 4
    gate_bias = bias[:, :4 * d] if bias is not None else 0.0
    if use_peepholes and bias is not None:
        w_ic = bias[0, 4 * d:5 * d]
        w_fc = bias[0, 5 * d:6 * d]
        w_oc = bias[0, 6 * d:7 * d]
    # dtype flow under AMP: the sequence x and hidden state h stay in
    # x's dtype (bf16 — the recurrent matmul rides the MXU fast path via
    # the bf16-cast weight), while gates and the CELL state compute and
    # carry in f32: c accumulates across T steps, exactly the drift an
    # 8-bit mantissa cannot hold
    cd = x.dtype
    w_r = w.astype(cd)
    h_prev = (h0.astype(cd) if h0 is not None
              else jnp.zeros((b_sz, d), cd))
    c_prev = (c0.astype(jnp.float32) if c0 is not None
              else jnp.zeros((b_sz, d), jnp.float32))

    xs = jnp.swapaxes(x, 0, 1)  # [T, B, 4D]
    if is_reverse:
        xs = jnp.flip(xs, 0)
    if lengths is None:
        step_mask = jnp.ones((t, b_sz), jnp.float32)
    else:
        step_mask = _mask(x, lengths, jnp.float32).T  # [T, B]
        if is_reverse:
            step_mask = jnp.flip(step_mask, 0)

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        gates = (x_t + h @ w_r).astype(jnp.float32) + gate_bias
        # reference gate layout: [candidate(in), input, forget, output]
        # (math/detail/lstm_cpu_kernel.h:44-47)
        gc, gi, gf, go = jnp.split(gates, 4, axis=1)
        if use_peepholes:
            gi = gi + c * w_ic
            gf = gf + c * w_fc
        i = gate_act(gi)
        f = gate_act(gf)
        c_new = f * c + i * cand_act(gc)
        if use_peepholes:
            go = go + c_new * w_oc
        o = gate_act(go)
        h_new = o * cell_act(c_new)
        m = m_t[:, None]
        h_out = (m * h_new + (1 - m) * h.astype(jnp.float32)).astype(cd)
        c_out = m * c_new + (1 - m) * c
        return (h_out, c_out), (h_out, c_out)

    (_, _), (hs, cs) = jax.lax.scan(step, (h_prev, c_prev), (xs, step_mask))
    if is_reverse:
        hs = jnp.flip(hs, 0)
        cs = jnp.flip(cs, 0)
    ctx.set(op, 'Hidden', jnp.swapaxes(hs, 0, 1))
    ctx.set(op, 'Cell', jnp.swapaxes(cs, 0, 1).astype(cd))
    ctx.set(op, 'BatchGate', x)
    ctx.set(op, 'BatchCellPreAct', jnp.swapaxes(cs, 0, 1).astype(cd))


@register_lowering('gru')
def _gru(ctx, op):
    """Dynamic GRU (reference operators/gru_op.cc).  Input [B, T, 3D]
    pre-projected; weight [D, 3D] = [W_update | W_reset | W_candidate]."""
    x = ctx.get(op, 'Input')
    w = ctx.get(op, 'Weight')
    bias = ctx.get(op, 'Bias')
    h0 = ctx.get(op, 'H0')
    lengths = _seqlen(ctx, op, 'Input')
    is_reverse = op.attrs.get('is_reverse', False)
    gate_act = _act(op.attrs.get('gate_activation', 'sigmoid'))
    cand_act = _act(op.attrs.get('activation', 'tanh'))

    b_sz, t, d3 = x.shape
    d = d3 // 3
    # same AMP dtype flow as _lstm: x/h in x's dtype for the MXU, the
    # gate math in f32; the bias adds INSIDE the step so the whole
    # [B, T, 3D] sequence is never widened to f32 in HBM
    cd = x.dtype
    w_g = w[:, :2 * d].astype(cd)  # update+reset recurrent weights
    w_c = w[:, 2 * d:].astype(cd)
    if bias is not None:
        bias_g = bias.reshape(1, -1)[:, :2 * d].astype(jnp.float32)
        bias_c = bias.reshape(1, -1)[:, 2 * d:].astype(jnp.float32)
    else:
        bias_g = bias_c = 0.0
    h_prev = h0.astype(cd) if h0 is not None else jnp.zeros((b_sz, d), cd)

    xs = jnp.swapaxes(x, 0, 1)
    if is_reverse:
        xs = jnp.flip(xs, 0)
    if lengths is None:
        step_mask = jnp.ones((t, b_sz), jnp.float32)
    else:
        step_mask = _mask(x, lengths, jnp.float32).T
        if is_reverse:
            step_mask = jnp.flip(step_mask, 0)

    def step(h, inp):
        x_t, m_t = inp
        gu_gr = gate_act(
            (x_t[:, :2 * d] + h @ w_g).astype(jnp.float32) + bias_g)
        u, r = jnp.split(gu_gr, 2, axis=1)
        c = cand_act((x_t[:, 2 * d:] +
                      (r.astype(cd) * h) @ w_c).astype(jnp.float32) +
                     bias_c)
        # reference: h = (1-u)*h_prev + u*c (math/detail/gru_kernel.h:62)
        h_new = (1 - u) * h.astype(jnp.float32) + u * c
        m = m_t[:, None]
        h_out = (m * h_new + (1 - m) * h.astype(jnp.float32)).astype(cd)
        return h_out, h_out

    _, hs = jax.lax.scan(step, h_prev, (xs, step_mask))
    if is_reverse:
        hs = jnp.flip(hs, 0)
    out = jnp.swapaxes(hs, 0, 1)
    ctx.set(op, 'Hidden', out)
    ctx.set(op, 'BatchGate', x)
    ctx.set(op, 'BatchResetHiddenPrev', out)
    ctx.set(op, 'BatchHidden', out)


@register_lowering('gru_unit')
def _gru_unit(ctx, op):
    """Single GRU step (reference operators/gru_unit_op.cc)."""
    x = ctx.get(op, 'Input')  # [B, 3D]
    h_prev = ctx.get(op, 'HiddenPrev')
    w = ctx.get(op, 'Weight')  # [D, 3D]
    bias = ctx.get(op, 'Bias')
    gate_act = _act({1: 'sigmoid', 0: 'identity', 2: 'tanh',
                     3: 'relu'}.get(op.attrs.get('gate_activation', 1)))
    cand_act = _act({1: 'sigmoid', 0: 'identity', 2: 'tanh',
                     3: 'relu'}.get(op.attrs.get('activation', 2)))
    d = h_prev.shape[1]
    if bias is not None:
        x = x + bias
    w_g = w[:, :2 * d]
    w_c = w[:, 2 * d:]
    g = gate_act(x[:, :2 * d] + h_prev @ w_g)
    u, r = jnp.split(g, 2, axis=1)
    c = cand_act(x[:, 2 * d:] + (r * h_prev) @ w_c)
    # reference: h = u*(c - h_prev) + h_prev (gru_unit_op.h:116)
    h = (1 - u) * h_prev + u * c
    ctx.set(op, 'Gate', jnp.concatenate([g, c], axis=1))
    ctx.set(op, 'ResetHiddenPrev', r * h_prev)
    ctx.set(op, 'Hidden', h)


@register_lowering('row_conv')
def _row_conv(ctx, op):
    """Lookahead row convolution (reference operators/row_conv_op.cc)."""
    x = ctx.get(op, 'X')  # [B, T, D]
    w = ctx.get(op, 'Filter')  # [future_ctx, D]
    k = w.shape[0]
    b, t, d = x.shape
    xp = jnp.pad(x, ((0, 0), (0, k - 1), (0, 0)))
    out = sum(xp[:, i:i + t] * w[i][None, None, :] for i in range(k))
    ctx.set(op, 'Out', out)


@register_lowering('sequence_mask')
def _sequence_mask_op(ctx, op):
    """lengths [B] -> mask [B, maxlen] (reference sequence_mask op /
    math/sequence_padding.h mask generation)."""
    lengths = ctx.get(op, 'X').reshape(-1)
    maxlen = int(op.attrs.get('maxlen', -1))
    if maxlen <= 0:
        raise NotImplementedError(
            'sequence_mask needs a static maxlen attr under XLA '
            '(dynamic maxlen = data-dependent shape)')
    dummy = jnp.zeros((lengths.shape[0], maxlen))
    out_dtype = op.attrs.get('out_dtype', 'int64')
    ctx.set(op, 'Out', _mask(dummy, lengths, dtype=jnp.dtype(out_dtype)))


@register_lowering('lstmp')
def _lstmp(ctx, op):
    """LSTM with recurrent projection (reference operators/lstmp_op.cc):
    the recurrence feeds the projected state r_t = proj_act(h_t @ P) back
    into the gates instead of h_t, shrinking the recurrent matmul for
    large-vocab speech models.  Outputs Projection [B, T, P], Cell."""
    x = ctx.get(op, 'Input')  # [B, T, 4D]
    w = ctx.get(op, 'Weight')  # [P, 4D]
    w_proj = ctx.get(op, 'ProjWeight')  # [D, P]
    bias = ctx.get(op, 'Bias')
    h0 = ctx.get(op, 'H0')  # [B, P] projected initial state
    c0 = ctx.get(op, 'C0')  # [B, D]
    lengths = _seqlen(ctx, op, 'Input')
    use_peepholes = op.attrs.get('use_peepholes', False)
    is_reverse = op.attrs.get('is_reverse', False)
    gate_act = _act(op.attrs.get('gate_activation', 'sigmoid'))
    cell_act = _act(op.attrs.get('cell_activation', 'tanh'))
    cand_act = _act(op.attrs.get('candidate_activation', 'tanh'))
    proj_act = _act(op.attrs.get('proj_activation', 'tanh'))

    b_sz, t, d4 = x.shape
    d = d4 // 4
    p_dim = w_proj.shape[1]
    gate_bias = bias[:, :4 * d] if bias is not None else 0.0
    if use_peepholes and bias is not None:
        w_ic = bias[0, 4 * d:5 * d]
        w_fc = bias[0, 5 * d:6 * d]
        w_oc = bias[0, 6 * d:7 * d]
    r_prev = h0 if h0 is not None else jnp.zeros((b_sz, p_dim), x.dtype)
    c_prev = c0 if c0 is not None else jnp.zeros((b_sz, d), x.dtype)

    xs = jnp.swapaxes(x, 0, 1)
    if is_reverse:
        xs = jnp.flip(xs, 0)
    if lengths is None:
        step_mask = jnp.ones((t, b_sz), x.dtype)
    else:
        step_mask = _mask(x, lengths, x.dtype).T
        if is_reverse:
            step_mask = jnp.flip(step_mask, 0)

    def step(carry, inp):
        r, c = carry
        x_t, m_t = inp
        gates = x_t + r @ w + gate_bias
        gc, gi, gf, go = jnp.split(gates, 4, axis=1)
        if use_peepholes:
            gi = gi + c * w_ic
            gf = gf + c * w_fc
        i = gate_act(gi)
        f = gate_act(gf)
        c_new = f * c + i * cand_act(gc)
        if use_peepholes:
            go = go + c_new * w_oc
        o = gate_act(go)
        h_new = o * cell_act(c_new)
        r_new = proj_act(h_new @ w_proj)
        m = m_t[:, None]
        r_out = m * r_new + (1 - m) * r
        c_out = m * c_new + (1 - m) * c
        return (r_out, c_out), (r_out, c_out)

    (_, _), (rs, cs) = jax.lax.scan(step, (r_prev, c_prev), (xs, step_mask))
    if is_reverse:
        rs = jnp.flip(rs, 0)
        cs = jnp.flip(cs, 0)
    ctx.set(op, 'Projection', jnp.swapaxes(rs, 0, 1))
    ctx.set(op, 'Cell', jnp.swapaxes(cs, 0, 1))
    ctx.set(op, 'BatchGate', x)
    ctx.set(op, 'BatchHidden', jnp.swapaxes(rs, 0, 1))


@register_lowering('lod_rank_table')
def _lod_rank_table(ctx, op):
    """Length-descending stable sort permutation (reference
    framework/lod_rank_table.h built by operators/lod_rank_table_op.cc).
    On the padded layout the 'table' is the [B] int32 row permutation."""
    x = ctx.get(op, 'X')
    lengths = _seqlen(ctx, op)
    b = x.shape[0]
    if lengths is None:
        lengths = jnp.full((b, ), x.shape[1] if x.ndim > 1 else 1,
                           jnp.int32)
    # stable argsort on (-length, row) keeps the reference's tie order
    perm = jnp.argsort(-lengths.astype(jnp.int32), stable=True)
    ctx.set(op, 'Out', perm.astype(jnp.int32))


@register_lowering('reorder_lod_tensor_by_rank')
def _reorder_lod_tensor_by_rank(ctx, op):
    """Gather rows by a rank-table permutation (reference
    operators/reorder_lod_tensor_by_rank_op.cc); the sequence-length
    side-band is permuted alongside the data."""
    x = ctx.get(op, 'X')
    perm = ctx.get(op, 'RankTable')
    out = jnp.take(x, perm, axis=0)
    ctx.set(op, 'Out', out)
    lengths = _seqlen(ctx, op)
    if lengths is not None:
        out_name = op.output('Out')[0]
        ctx.env[out_name + SEQLEN_SUFFIX] = jnp.take(lengths, perm, axis=0)


@register_lowering('context_project')
def _context_project(ctx, op):
    """Parameter-free context-window concatenation (reference
    math/context_project.h, the substrate of context_projection):
    out[:, t] = concat(x[:, t+start], ..., x[:, t+start+L-1]) with zero
    padding outside the time range."""
    x = ctx.get(op, 'X')  # [B, T, D]
    ctx_len = int(op.attrs['context_len'])
    start = int(op.attrs.get('context_start',
                             -((ctx_len - 1) // 2)))
    b, t, d = x.shape
    parts = []
    for j in range(ctx_len):
        off = start + j
        if off == 0:
            parts.append(x)
        elif off > 0:
            pad = jnp.zeros((b, off, d), x.dtype)
            parts.append(jnp.concatenate([x[:, off:], pad], axis=1))
        else:
            pad = jnp.zeros((b, -off, d), x.dtype)
            parts.append(jnp.concatenate([pad, x[:, :off]], axis=1))
    ctx.set(op, 'Out', jnp.concatenate(parts, axis=2))


@register_lowering('sub_nested_seq')
def _sub_nested_seq(ctx, op):
    """Select whole sub-sequences of a nested sequence by per-sequence
    row indices (reference sub_nested_seq_layer;
    legacy/gserver/layers/SubNestedSequenceLayer.cpp).

    Static-shape design: the nested input arrives padded [R, T, ...]
    with inner lengths ``X@SEQLEN`` [R] and the outer level ``X@ROWS``
    [B] (sub-sequences per sequence).  ``SelectedIndices`` is [B, k]
    (-1 padded) of row indices RELATIVE to each sequence's own rows —
    the reference's selected_indices contract.  Output keeps the nested
    form: [B*k, T, ...] rows (invalid selections zeroed, length 0) with
    fresh @SEQLEN/@ROWS sidecars, so downstream sequence ops and a
    second selection round both compose."""
    x = ctx.get(op, 'X')
    sel = ctx.get(op, 'SelectedIndices')
    inner = _seqlen(ctx, op, 'X')
    rows = ctx.env.get(op.input('X')[0] + ROWS_SUFFIX)
    if inner is None:
        inner = jnp.full((x.shape[0], ), x.shape[1], jnp.int32)
    if rows is None:
        raise ValueError(
            'sub_nested_seq: input %r carries no @ROWS outer level — '
            'feed it as a 2-level LoD tensor' % op.input('X')[0])
    if sel.ndim == 3 and sel.shape[-1] == 1:
        sel = sel[..., 0]
    sel = sel.astype(jnp.int32)
    b, k = sel.shape
    row_start = jnp.cumsum(rows) - rows            # [B]
    valid = (sel >= 0) & (sel < rows[:, None])     # [B, k]
    abs_rows = jnp.clip(row_start[:, None] + jnp.clip(sel, 0), 0,
                        x.shape[0] - 1).reshape(-1)  # [B*k]
    flat_valid = valid.reshape(-1)
    # compact valid rows to packed order (rows of sequence b start at
    # cumsum of previous sequences' counts) so the output honors the
    # same nested-layout invariant as the input; invalid selections
    # scatter into a scratch row that is sliced off
    n_out = b * k
    pos = jnp.cumsum(flat_valid) - 1               # rank among valid
    target = jnp.where(flat_valid, pos, n_out)
    gathered = x[abs_rows]
    out = jnp.zeros((n_out + 1, ) + x.shape[1:], x.dtype)
    out = out.at[target].set(gathered)[:n_out]
    out_inner = jnp.zeros((n_out + 1, ), jnp.int32).at[target].set(
        inner[abs_rows].astype(jnp.int32))[:n_out]
    ctx.set(op, 'Out', out)
    ctx.env[op.output('Out')[0] + SEQLEN_SUFFIX] = out_inner
    ctx.env[op.output('Out')[0] + ROWS_SUFFIX] = valid.sum(
        axis=1).astype(jnp.int32)


@register_lowering('kmax_seq_score')
def _kmax_seq_score(ctx, op):
    """Top-k INDICES per sequence (reference KmaxSeqScoreLayer.cpp:52 —
    "output ... is some selected indices of the given sequence", carried
    as real values, -1 beyond min(k, seq_len)).  Scores arrive [B, T] or
    [B, T, 1] padded; padding is masked out of the per-row top_k.  The
    index output is exactly what sub_nested_seq_layer consumes as
    selected_indices in the reference beam-training flow."""
    x = ctx.get(op, 'X')
    k = int(op.attrs.get('beam_size', 1))
    lengths = _seqlen(ctx, op)
    v = x[..., 0] if x.ndim == 3 and x.shape[-1] == 1 else x
    if k > v.shape[1]:
        raise ValueError(
            'kmax_seq_score: beam_size %d exceeds the padded time dim %d'
            % (k, v.shape[1]))
    if lengths is not None:
        m = _mask(v, lengths)
        v = jnp.where(m, v, -jnp.inf)
        n_valid = jnp.minimum(lengths.astype(jnp.int32), k)
    else:
        n_valid = jnp.full((v.shape[0], ), min(v.shape[1], k), jnp.int32)
    _, idx = jax.lax.top_k(v, k)
    slot_ok = jnp.arange(k)[None, :] < n_valid[:, None]
    ctx.set(op, 'Out',
            jnp.where(slot_ok, idx, -1).astype(jnp.float32))
