"""Fused scaled-dot-product attention op: one op, four lowerings.

The reference composes attention from primitive ops (matmul + softmax +
dropout, python/paddle/fluid/nets.py scaled_dot_product_attention) and has
no sequence parallelism (SURVEY §5.7).  TPU-natively attention is the hot
op of every transformer, so it gets ONE op whose lowering picks the
implementation from what it can observe where it is lowered: the place,
the mesh, and the shapes of Q, K and V (``impl='auto'``, the default):

- a mesh with an 'sp' (sequence/context parallel) axis: **ring attention**
  (K/V blocks rotate on ICI neighbor links); **Ulysses** all-to-all head
  resharding by the ``impl`` attr;
- an accelerator place, inside the fused kernel's envelope and at or past
  the crossover measured on the v5e: the **Pallas kernel**
  (ops/pallas/flash_attention.py), forward and backward, which keeps the
  [Lq, Lk] scores in VMEM where dense attention writes them to HBM in
  f32 and reads them back in both passes.  On a mesh whose batch axis is
  larger than 1 the kernel runs under ``shard_map`` over that axis (GSPMD
  does not partition a ``pallas_call``: it would gather the batch);
- otherwise (a CPU place, Dq != Dv, a head width that was not measured
  or does not tile 128 lanes, a mesh axis the kernel cannot be sharded
  over, a row of Q or K past the envelope, a side shorter than one
  128-row tile such as a decoder step's Lq=1, fewer than two keys a
  column of the head): dense XLA attention.

``impl='dense'`` / ``'pallas'`` / ``'ring'`` / ``'ulysses'`` ask for one.
What each op of a program lowered to is kept in
``fluid.trace.lowering_choices('flash_attention')``.

Layout: Q, K, V are [batch, seq, heads, head_dim].  Variable-length
batches feed through the LoD sideband (``@SEQLEN``) and mask K/V columns
past each row's length, matching LoD semantics on static shapes.

Head counts: K and V may carry fewer heads than Q (grouped-query
attention: ``Hq`` a multiple of ``Hkv``, query head i reads key-value head
i // (Hq / Hkv)).  Every implementation is given K and V with each head
repeated to Q's count, and dK, dV are the sums over a group's copies: at
the lengths the fused kernel admits (2048 at most) the repeated K and V
are a few MB an op, against a kernel whose lane groups, log-sum-exp tiles
and one-pass dK/dV would all have to learn a second head index (PERF.md
section 6, PR 26).  The envelope of 'auto' is judged on the repeated
shapes.  ``scale`` is passed through to every implementation as given
(a model's own multiplier, such as 1/64 at D=64, is not 1/sqrt(D));
no implementation adds a positional signal.  The kernel's several-tile
path (L of 512 to 2048) is trained by the cell ``granite_h_train_1chip``
(L=1024, causal: 10 of 16 tiles).
"""

from . import registry
from .registry import register_grad_lowering, register_lowering


# What 'auto' rests on: the kernel against dense attention on the v5e
# (tools/pallas_chip_check.py, PR 25; PERF.md section 5 has the table),
# forward + backward, bf16, 32768 tokens of 512 columns a call, ms on the
# host's clock round ten calls chained on the device, kernel / dense:
#   D=64 (H8):   L=64 1.99 / 2.14 (padded to a 128-row tile: a tie),
#                L=128 1.02 / 1.69, L=256 1.14 / 2.82 (the cells' shape),
#                L=512 4.3 / 5.5, L=1024 7.3 / 10.5, L=2048 13.2 / 20.2
#                (causal: 3.7, 5.2, 8.1); Lq 2048 x Lk 256 1.94 / 2.54;
#                the served decoder's step (Lq=1, Lk=256, forward alone)
#                0.19 / 0.17
#   D=128 (H4):  L=128 0.92 / 0.62 (dense wins), L=256 0.96 / 1.69,
#                L=1024 3.97 / 5.41
#   D=32 (H16):  L=128 1.77 / 2.81, L=256 2.08 / 5.34, L=1024 13.4 / 20.8
# Dense attention pays for a head's [Lq, Lk] f32 scores in HBM against
# its [L, D] operands, so its loss grows with Lk / D whatever the number
# of heads: the kernel wins from Lk = 2 D on, given one whole 128-row
# tile a side; at Lk = D it ties (D=64) or loses (D=128).
_FUSED_HEAD_DIMS = (32, 64, 128)    # measured; all tile 128 lanes
_FUSED_MIN_LEN = 128
_FUSED_MIN_KEYS_PER_DIM = 2
# the forward's rows of log-sum-exp, kept in the trace beside the op's
# output for the op's gradient (a side-band, as @SEQLEN is)
_LSE_SUFFIX = '@FLASH_LSE'


def _mesh_axes(ctx):
    """{axis: size} of the mesh's axes larger than 1."""
    mesh = ctx.mesh
    if mesh is None:
        return {}
    return {a: n for a, n in dict(mesh.shape).items() if n > 1}


def _fused_fits(ctx, q, k, v):
    """Whether the fused kernel can run this op where it is lowered, and
    wins there: every term is a shape, the place or the mesh.  K and V
    are judged as the kernel gets them, repeated to Q's head count
    (``_repeat_kv``): any number of key-value heads that divides the
    query heads is admitted where the repeated shapes are."""
    from .pallas import flash_attention as pl_fa
    if ctx.on_cpu or q.ndim != 4 or v.shape[-1] != q.shape[-1]:
        return False
    # GSPMD does not partition the kernel: only the batch axis, which
    # shard_map takes, may be larger than 1
    axes = _mesh_axes(ctx)
    batch = axes.pop(ctx.batch_axis, 1)
    if axes or q.shape[0] % batch:
        return False
    (lq, h, d), lk = q.shape[1:], k.shape[1]
    # MAX_LEN bounds both sides: the backward holds a row's Q, dO and dQ
    # in VMEM as it holds its K, V, dK and dV
    return (d in _FUSED_HEAD_DIMS and (h * d) % 128 == 0
            and _FUSED_MIN_LEN <= lq <= pl_fa.MAX_LEN
            and max(_FUSED_MIN_LEN, _FUSED_MIN_KEYS_PER_DIM * d)
            <= lk <= pl_fa.MAX_LEN)


def _pick_impl(ctx, op, q, k, v):
    impl = op.attrs.get('impl', 'auto')
    sp = op.attrs.get('sp_axis', 'sp')
    has_sp = sp in _mesh_axes(ctx)
    if impl == 'auto':
        if has_sp:
            return 'ring'
        return 'pallas' if _fused_fits(ctx, q, k, v) else 'dense'
    if impl in ('ring', 'ulysses') and not has_sp:
        import warnings
        mesh = ctx.mesh
        warnings.warn(
            'flash_attention: impl=%r requested but the executor mesh has '
            'no %r axis (mesh=%s) — falling back to dense XLA attention, '
            'which materialises the full [L, L] score matrix' %
            (impl, sp, None if mesh is None else dict(mesh.shape)))
        return 'dense'
    return impl


def _over_batch(ctx, fn, *arrays):
    """``fn(*arrays)``, every array's and result's leading dimension the
    batch: under a mesh whose batch axis is larger than 1, each chip runs
    ``fn`` on its own rows."""
    if ctx.batch_axis not in _mesh_axes(ctx):
        return fn(*arrays)
    import jax
    from jax.sharding import PartitionSpec as P
    # [B, L, H, D] crosses the boundary as the row-major [B, L, H*D] it is
    # in HBM.  A 4-D value there is given a tiled (H, D) layout of its own,
    # and the kernel's reshape inside becomes a copy of Q, K, V and dO
    # (0.1-0.27 ms an op in tbase_train_dp4's trace, PR 25)
    shapes = [a.shape for a in arrays]

    def flat(a):
        return a.reshape(a.shape[:2] + (-1, )) if a.ndim == 4 else a

    def local(*xs):
        return jax.tree_util.tree_map(flat, fn(*(
            x.reshape(x.shape[:1] + shape[1:])
            for x, shape in zip(xs, shapes))))

    spec = P(ctx.batch_axis)
    outs = jax.shard_map(local, mesh=ctx.mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)(*map(flat, arrays))
    heads = shapes[0][2:]
    return jax.tree_util.tree_map(
        lambda o: o.reshape(o.shape[:2] + heads) if o.ndim == 3 else o, outs)


def _repeat_kv(q, k, v):
    """K and V with each head repeated to Q's head count, and the number
    of copies (1: untouched)."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq == hkv:
        return k, v, 1
    if hq % hkv or v.shape[2] != hkv:
        raise ValueError(
            'flash_attention: %d query heads over %d key and %d value '
            'heads: the query heads must be a whole multiple of the '
            'key-value heads' % (hq, hkv, v.shape[2]))
    import jax.numpy as jnp
    rep = hq // hkv
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), rep


def _sum_copies(g, rep):
    """The gradient of a repeated K or V, [B, L, Hkv * rep, D], summed
    over each head's copies (f32: up to ``rep`` bf16 terms)."""
    if rep == 1:
        return g
    import jax.numpy as jnp
    b, l, h, d = g.shape
    return jnp.sum(g.reshape(b, l, h // rep, rep, d).astype(jnp.float32),
                   axis=3).astype(g.dtype)


def _attrs(attrs):
    scale = attrs.get('scale', None)
    return bool(attrs.get('causal', False)), \
        (scale if scale is not None and scale > 0 else None)


def _kv_lengths(ctx, k_name):
    """LoD sideband: valid lengths of the K/V sequences.  Only K's own
    sideband applies — Q's lengths describe the query sequence and must
    NOT mask encoder memory in cross-attention."""
    if k_name and ctx.has(k_name + registry.SEQLEN_SUFFIX):
        return ctx.lookup(k_name + registry.SEQLEN_SUFFIX)
    return None


def _fused(ctx, fn, arrays, lens, causal, scale, **kwargs):
    """The kernel's ``fn`` on ``arrays`` (and K's lengths, if any), each
    chip on its own rows: compiled for the chip on every accelerator
    place, interpreted only when the block is lowered for a CPU place."""
    n = len(arrays)

    def call(*a):
        return fn(*a[:n], seq_lengths=a[n] if len(a) > n else None,
                  causal=causal, scale=scale, interpret=ctx.on_cpu,
                  **kwargs)

    return _over_batch(ctx, call, *arrays,
                       *(() if lens is None else (lens, )))


@register_lowering('flash_attention')
def flash_attention_lowering(ctx, op):
    from ..fluid import trace
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
    kv_heads = k.shape[2]
    k, v, _ = _repeat_kv(q, k, v)
    causal, scale = _attrs(op.attrs)
    lens = _kv_lengths(ctx, (op.input('K') or [None])[0])
    impl = _pick_impl(ctx, op, q, k, v)
    out_name = op.output('Out')[0]
    # by output name, so the generic gradient's replay of a dense, ring
    # or ulysses forward does not count twice
    trace.note_lowering_choice(
        ctx.block.program, op.type, out_name, impl, heads=q.shape[2],
        kv_heads=kv_heads)
    if impl in ('ring', 'ulysses'):
        sp = op.attrs.get('sp_axis', 'sp')
        batch_axis = ctx.batch_axis \
            if ctx.batch_axis in _mesh_axes(ctx) else None
        fn = cp.ring_attention if impl == 'ring' else cp.ulysses_attention
        out = fn(q, k, v, ctx.mesh, axis=sp, causal=causal, scale=scale,
                 seq_lengths=lens, batch_axis=batch_axis)
    elif impl == 'pallas':
        from .pallas import flash_attention as pl_fa
        if v.shape[-1] != q.shape[-1]:
            raise ValueError(
                "flash_attention: impl='pallas' tiles one head_dim for "
                "Q/K/V, got Dq=%d and Dv=%d — use impl='dense' (or "
                "'auto') for mixed-width cross attention"
                % (q.shape[-1], v.shape[-1]))
        out, lse = _fused(ctx, pl_fa.flash_attention, (q, k, v), lens,
                          causal, scale, return_residual=True)
        ctx.store(out_name + _LSE_SUFFIX, lse)
    else:
        out = cp.dense_attention(q, k, v, causal=causal, scale=scale,
                                 seq_lengths=lens)
    ctx.set(op, 'Out', out.astype(q.dtype))


_generic_grad = registry._make_generic_grad('flash_attention')


@register_grad_lowering('flash_attention')
def flash_attention_grad_lowering(ctx, op):
    """The fused kernel's backward, from the log-sum-exp its forward left
    in this trace: the forward kernel is neither traced nor run a second
    time.  Every other implementation (and a fused forward lowered in
    another trace) takes the generic ``jax.vjp`` of the forward lowering."""
    import jax.numpy as jnp
    from .pallas import flash_attention as pl_fa
    from .registry import GRAD_SUFFIX, amp_cast_in
    fwd_inputs, fwd_outputs, attrs = registry.fwd_structure(op)
    out_name = fwd_outputs['Out'][0]
    if not ctx.has(out_name + _LSE_SUFFIX):
        return _generic_grad(ctx, op)
    wanted = [(slot, op.output(slot + GRAD_SUFFIX)) for slot in 'QKV']
    if not any(names and names[0] for _, names in wanted):
        return
    primals = [ctx.lookup(fwd_inputs[slot][0]) for slot in 'QKV']
    q, k, v = amp_cast_in(*primals)
    k, v, rep = _repeat_kv(q, k, v)
    out = ctx.lookup(out_name)
    dout = (ctx.lookup(out_name + GRAD_SUFFIX).astype(out.dtype)
            if ctx.has(out_name + GRAD_SUFFIX) else jnp.zeros_like(out))
    causal, scale = _attrs(attrs)
    lens = _kv_lengths(ctx, fwd_inputs['K'][0])
    grads = _fused(
        ctx, pl_fa.flash_attention_grad,
        (q, k, v, out, ctx.lookup(out_name + _LSE_SUFFIX), dout), lens,
        causal, scale)
    for (slot, names), primal, g in zip(wanted, primals, grads):
        if names and names[0]:
            if slot != 'Q':
                g = _sum_copies(g, rep)
            g = g.astype(primal.dtype)
            if ctx.has(names[0]):   # the rename pass did not split it
                g = ctx.lookup(names[0]) + g
            ctx.store(names[0], g)
