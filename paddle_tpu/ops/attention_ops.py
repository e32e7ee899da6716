"""Fused scaled-dot-product attention op with context-parallel lowering.

The reference composes attention from primitive ops (matmul + softmax +
dropout, python/paddle/fluid/nets.py scaled_dot_product_attention) and has
no sequence parallelism (SURVEY §5.7).  TPU-natively attention is the hot
op of every transformer, so it gets ONE op whose lowering picks the best
implementation for where it runs:

- SPMD executor with an 'sp' (sequence/context parallel) mesh axis:
  **ring attention** (K/V blocks rotate on ICI neighbor links) or
  **Ulysses** all-to-all head resharding, per the ``impl`` attr;
- single device on TPU: dense XLA attention while the [B,H,Lq,Lk] score
  tensor fits the budget, switching to the Pallas flash kernel
  (VMEM-blocked online softmax, O(L) memory — never materialises the
  [L, L] scores in HBM) beyond it, inside the envelope the kernel has
  compiled for on the chip;
- otherwise: dense XLA attention.

Layout: Q, K, V are [batch, seq, heads, head_dim].  Variable-length
batches feed through the LoD sideband (``@SEQLEN``) and mask K/V columns
past each row's length, matching LoD semantics on static shapes.
"""

from . import registry
from .registry import register_lowering


# 'auto' switches dense -> pallas when the materialised [B,H,Lq,Lk]
# score tensor would exceed this budget: the kernel's job is the O(L)
# memory profile that keeps long contexts compiling at all (its speed
# against dense attention on the v5e is not measured).
_DENSE_SCORE_BYTES_BUDGET = 2 << 30
# ...and only inside the envelope the kernel has COMPILED for on the
# chip (tools/pallas_chip_check.py, PR 21: interpret=False, fwd + bwd,
# L=2048 x 8 heads x 64, bf16): K and V for one batch row sit in VMEM
# whole and double-buffered, 4 * Lk * H*D * itemsize bytes — 8 MiB at
# that shape, half the 16 MiB scoped-VMEM default.  'auto' never picks
# the kernel past what was compiled; longer rows belong to ring
# attention over an 'sp' axis, or need the kernel retiled and rechecked.
_PALLAS_KV_VMEM_BYTES = 8 << 20


def _pick_impl(ctx, op, q, k, v):
    impl = op.attrs.get('impl', 'auto')
    mesh = ctx.mesh
    sp = op.attrs.get('sp_axis', 'sp')
    has_sp = (mesh is not None and sp in getattr(mesh, 'axis_names', ())
              and mesh.shape[sp] > 1)
    if impl == 'auto':
        if has_sp:
            return 'ring'
        # the Pallas kernel tiles ONE head_dim for Q/K/V: mixed Dv != Dq
        # cross-attention stays dense
        if not ctx.on_cpu and v.shape[-1] == q.shape[-1]:
            b, lq = q.shape[0], q.shape[1]
            lk, h = k.shape[1], (q.shape[2] if q.ndim == 4 else 1)
            # dense-path scores carry q's dtype (bf16 under AMP, f32
            # otherwise) — budget by the ACTUAL element size, not 4
            # (ADVICE r2 #4: assuming f32 halved the usable budget and
            # flipped 'auto' to the slower flash kernel too early)
            itemsize = getattr(getattr(q, 'dtype', None), 'itemsize', 4)
            kv_vmem = 4 * lk * h * k.shape[-1] * itemsize
            if b * h * lq * lk * itemsize > _DENSE_SCORE_BYTES_BUDGET \
                    and kv_vmem <= _PALLAS_KV_VMEM_BYTES:
                return 'pallas'
        return 'dense'
    if impl in ('ring', 'ulysses') and not has_sp:
        import warnings
        warnings.warn(
            'flash_attention: impl=%r requested but the executor mesh has '
            'no %r axis (mesh=%s) — falling back to dense XLA attention, '
            'which materialises the full [L, L] score matrix' %
            (impl, sp, None if mesh is None else dict(mesh.shape)))
        return 'dense'
    return impl


@register_lowering('flash_attention')
def flash_attention_lowering(ctx, op):
    from ..parallel import context_parallel as cp
    from .registry import amp_cast_in
    q = ctx.get(op, 'Q')
    k = ctx.get(op, 'K')
    v = ctx.get(op, 'V')
    # under AMP the projections normally arrive bf16 already (amp_matmul
    # lands bf16); this cast is the safety net for fp32 producers (e.g.
    # a biased path before harmonization, or AMP-off callers of a mixed
    # graph) so the kernel never runs a widened layout
    q, k, v = amp_cast_in(q, k, v)
    causal = bool(op.attrs.get('causal', False))
    scale = op.attrs.get('scale', None)
    if scale is not None and scale <= 0:
        scale = None
    # LoD sideband: valid lengths of the K/V sequences.  Only K's own
    # sideband applies — Q's lengths describe the query sequence and must
    # NOT mask encoder memory in cross-attention
    lens = None
    names = op.input('K')
    if names and ctx.has(names[0] + registry.SEQLEN_SUFFIX):
        lens = ctx.lookup(names[0] + registry.SEQLEN_SUFFIX)
    impl = _pick_impl(ctx, op, q, k, v)
    if impl in ('ring', 'ulysses'):
        sp = op.attrs.get('sp_axis', 'sp')
        mesh = ctx.mesh
        batch_axis = ctx.batch_axis
        if batch_axis not in mesh.axis_names or mesh.shape[batch_axis] <= 1:
            batch_axis = None
        fn = cp.ring_attention if impl == 'ring' else cp.ulysses_attention
        out = fn(q, k, v, mesh, axis=sp, causal=causal, scale=scale,
                 seq_lengths=lens, batch_axis=batch_axis)
    elif impl == 'pallas':
        from .pallas import flash_attention as pl_fa
        if v.shape[-1] != q.shape[-1]:
            raise ValueError(
                "flash_attention: impl='pallas' tiles one head_dim for "
                "Q/K/V, got Dq=%d and Dv=%d — use impl='dense' (or "
                "'auto') for mixed-width cross attention"
                % (q.shape[-1], v.shape[-1]))
        # compiled for the chip on every accelerator place; interpreted
        # only when the block is lowered for a CPU place
        out = pl_fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                    seq_lengths=lens, interpret=ctx.on_cpu)
    else:
        out = cp.dense_attention(q, k, v, causal=causal, scale=scale,
                                 seq_lengths=lens)
    ctx.set(op, 'Out', out.astype(q.dtype))
