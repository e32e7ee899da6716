"""Linear attention with a delta rule, and the rotary positions of latent
attention: what a Kimi-delta-attention / latent-attention hybrid needs and
the 2018 op set lacks.

- ``kda_gate``: the log-decay of Kimi delta attention (KDA), one per key
  channel, in float32 whatever AMP says:
  ``g = lower * sigmoid(exp(A_log_h) * (F + dt_bias))``, in (lower, 0)
  (the "safe" gate; ``lower`` is ``kda_lower_bound``).
- ``kda``: the gated delta rule with a decay per key channel, per head, on
  a state S of [d_k, d_v] that is zero at the sequence's start:

      S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
      o_t = S_t^T q_t

  with q and k each normalised to unit length inside the op (q then over
  sqrt(d_k)), b = Beta.  Kimi Linear (arXiv:2510.26692) section 3, Gated
  Delta Networks (arXiv:2412.06464).
- ``rotary``: rotary positions over a slice of the last axis, in
  interleaved pairs (channel 2i with 2i + 1), frequencies
  ``theta ** (-2i / width)``, positions the sequence axis's index.

``kda`` is computed in chunks of ``chunk`` positions (the WY / UT form).
Inside a chunk, with Gamma the cumulative log-decay from the chunk's start
and S the state entering it,

    A[t, s] = b_t sum_c k_tc k_sc exp(Gamma_tc - Gamma_sc)      s < t
    P[t, s] = sum_c q_tc k_sc exp(Gamma_tc - Gamma_sc)           s <= t
    (I + A) [W | U] = [b (k * exp(Gamma)) | b v]        (lower triangular)
    o = (q * exp(Gamma)) S + P (U - W S)
    S' = Diag(exp(Gamma_C)) S + (k * exp(Gamma_C - Gamma))^T (U - W S)

A pairwise factor exp(Gamma_t - Gamma_s) is never made from exp(Gamma) and
exp(-Gamma) apart: a product over the key channels splits it at the start
r of the sub-chunk of ``SUB`` positions that holds t, exp(Gamma_t -
Gamma_r) exp(Gamma_r - Gamma_s), whose second factor's exponent is at most
``SUB`` times the gate's lower bound's size for a key of the same
sub-chunk (40 at 8 and -5) and at most 0 for an earlier one; a later key
is masked before the ``exp``.  Not 16: the first factor then reaches
exp(-80), and in the gradient its products with small cotangents leave
float32's normal range, which the devices flush to zero (the decay's
gradient at the bound read 150% off on the CPU, 0.01% at 8).  A length
that is no multiple of the chunk is padded after the sequence with
positions that change nothing before them.

Precision: the gate, the cumulative sums, every ``exp``, the triangular
solve and the state carried from chunk to chunk are float32; the products
inside a chunk take AMP's operand type (bf16 under AMP) with float32
sums; the products with the state are float32 at the highest precision.
The output lands in V's dtype.

The forward leaves the states entering each chunk ([B, chunks, H, d_k,
d_v] float32) in the trace beside its output; the gradient lowering makes
every within-chunk quantity again from the op's inputs and runs the
recurrence between chunks backwards over the saved states (made again
where another trace lowered the forward).

Two implementations, picked where the op is lowered from what can be seen
there (``impl='auto'``, the default; ``'xla'`` / ``'pallas'`` ask for one;
``fluid.trace.lowering_choices('kda')`` records the choice with the chunk,
the chunks and the kernels' block):

- **'pallas'** (``ops/pallas/kda.py``): one kernel forward and one for the
  gradient, each walking a head's chunks in order with the state (its
  gradient) in VMEM; no within-chunk quantity reaches HBM.  'auto' takes
  it inside the envelope ``_kda_fits``: an accelerator place without a
  mesh, heads of 128 for keys and values, a chunk of whole sub-chunks and
  16-position tiles, the gradient kernel's blocks within half its VMEM
  limit.
- **'xla'**: the form below (``kda``, ``kda_grads``; the gradient under
  ``jax.checkpoint``, so XLA does not keep the forward's quantities for
  it).  A CPU place, any mesh (GSPMD does not partition a
  ``pallas_call``), every other shape, and the kernels' reference in the
  tests.

What 'auto' rests on: at the ling cell's shape (1 x 2048, 32 heads of
128, chunk 64, bf16 products) on the v5e the kernels took 1.93 + 2.72 ms
an op, forward + gradient, where the XLA form took 5.06 + 19.86 (ms a
call on the host's clock round ten calls chained on the device), and in
the ling cell the six ops took 26.55 ms a step where the XLA form took
107.03 (PERF.md section 6 names the runs).
"""

import functools

import jax
import jax.numpy as jnp

from . import registry
from .registry import (GRAD_SUFFIX, amp_cast_in, register_grad_lowering,
                       register_lowering)

SUB = 8             # positions a sub-chunk: the split of a decay factor
_STATES_SUFFIX = '@KDA_STATES'
_HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


# ---- the gate ---------------------------------------------------------------

def kda_gate(f, a_log, dt_bias, lower):
    """F [B, L, H, d_k], A_log [H], DtBias [H * d_k] -> log-decay
    [B, L, H, d_k] float32 in (lower, 0)."""
    heads, width = f.shape[-2:]
    step = _f32(f) + _f32(dt_bias).reshape(heads, width)
    return lower * jax.nn.sigmoid(jnp.exp(_f32(a_log))[:, None] * step)


@register_lowering('kda_gate')
def _kda_gate(ctx, op):
    ctx.set(op, 'Out', kda_gate(ctx.get(op, 'F'), ctx.get(op, 'ALog'),
                                ctx.get(op, 'DtBias'),
                                float(op.attrs['lower_bound'])))


# ---- the chunked delta rule -------------------------------------------------

def _unit(x, eps=1e-6):
    """x over its length along the last axis, float32."""
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _decay_sums(g):
    """The cumulative log-decay along a chunk's positions (axis 3)."""
    return jnp.cumsum(g, axis=3)


def _carried(s):
    """The state as it is carried into the next chunk."""
    return s


def _wy(a, kb, vb):
    """(W, U): ``(I + A)^-1`` times the decayed keys and the values, each
    times b; A [.., C, C] strictly lower, its diagonal not read."""
    both = jax.scipy.linalg.solve_triangular(
        a, jnp.concatenate([kb, vb], -1), lower=True, unit_diagonal=True)
    return both[..., :kb.shape[-1]], both[..., kb.shape[-1]:]


def _products(x, keys, dtype):
    """``x [.., S, SUB, d] . keys [.., S, C, d]`` over d -> [.., C, C]:
    each sub-chunk's rows against every key, operands in ``dtype``,
    float32 sums (the highest precision where ``dtype`` is float32)."""
    out = jnp.einsum('...itc,...isc->...its', x.astype(dtype),
                     keys.astype(dtype), preferred_element_type=jnp.float32,
                     precision=None if dtype == jnp.bfloat16 else _HIGHEST)
    return out.reshape(out.shape[:-3] + (-1, out.shape[-1]))


def _chunked(x, chunk):
    """[B, L, H, d] -> [B, N, H, C, d] (head-major inside a chunk)."""
    b, length = x.shape[:2]
    x = x.reshape((b, length // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(x, 3, 2)


def _parts(q, k, v, g, beta, chunk, dtype):
    """Every within-chunk quantity, chunks side by side: q and k made
    unit, the decays, the pairwise scores, the triangular solve, and each
    chunk's map of the state entering it to the state leaving it,
    ``S' = M S + N``."""
    qc = _chunked(_unit(q) * q.shape[-1] ** -0.5, chunk)
    kc = _chunked(_unit(k), chunk)
    vc, gc = _chunked(_f32(v), chunk), _chunked(_f32(g), chunk)
    bc = jnp.moveaxis(_f32(beta).reshape(
        beta.shape[:1] + (-1, chunk) + beta.shape[2:]), 3, 2)[..., None]
    gam = _decay_sums(gc)                            # [B, N, H, C, dk]
    subs = chunk // SUB
    # Gamma at the start of each sub-chunk (0 before the chunk's first)
    start = jnp.concatenate(
        [jnp.zeros_like(gam[..., :1, :]), gam[..., SUB - 1:-1:SUB, :]], -2)
    row = jnp.repeat(start, SUB, axis=-2)            # the start over t
    pos = jnp.arange(chunk)
    ahead = pos[None, :] >= SUB * (jnp.arange(subs)[:, None] + 1)
    expo = start[..., :, None, :] - gam[..., None, :, :]   # [.., S, C, dk]
    keys = kc[..., None, :, :] * jnp.exp(
        jnp.where(ahead[:, :, None], -jnp.inf, expo))

    def rows(x):
        x = x * jnp.exp(gam - row)
        return x.reshape(x.shape[:-2] + (subs, SUB, x.shape[-1]))

    lower = pos[:, None] > pos[None, :]
    a = jnp.where(lower, _products(rows(kc), keys, dtype), 0.0) * bc
    p = jnp.where(lower | (pos[:, None] == pos[None, :]),
                  _products(rows(qc), keys, dtype), 0.0)
    w, u = _wy(a, bc * kc * jnp.exp(gam), bc * vc)
    last = gam[..., -1:, :]
    kd = kc * jnp.exp(last - gam)                    # to the chunk's end
    m = jnp.exp(last)[..., 0, :, None] * jnp.eye(kc.shape[-1]) - jnp.einsum(
        '...sc,...sd->...cd', kd, w, precision=_HIGHEST)
    n = jnp.einsum('...sc,...sd->...cd', kd, u, precision=_HIGHEST)
    return qc * jnp.exp(gam), p, w, u, m, n


def _states(m, n):
    """The state entering each chunk [B, N, H, dk, dv], from zeros:
    ``S_{i+1} = M_i S_i + N_i``."""
    def step(s, mn):
        s = _carried(s)
        return jnp.matmul(mn[0], s, precision=_HIGHEST) + mn[1], s

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(n[:, 0]),
        (jnp.moveaxis(m, 1, 0), jnp.moveaxis(n, 1, 0)))
    return jnp.moveaxis(entering, 0, 1)


def _outputs(qe, p, w, u, entering):
    """o of every chunk, [B, N, H, C, dv] float32."""
    def mm(x, y):
        return jnp.matmul(x, y, precision=_HIGHEST)
    return mm(qe, entering) + mm(p, u - mm(w, entering))


def _pad(arrays, chunk):
    """Each [B, L, ...] array padded after the sequence to whole chunks
    with zeros: a key of zero writes nothing, a decay of zero keeps all."""
    extra = -arrays[0].shape[1] % chunk
    if not extra:
        return arrays
    return [jnp.pad(a, [(0, 0), (0, extra)] + [(0, 0)] * (a.ndim - 2))
            for a in arrays]


def _unchunked(o, length):
    """[B, N, H, C, dv] -> [B, length, H, dv]."""
    o = jnp.moveaxis(o, 2, 3)
    return o.reshape((o.shape[0], -1) + o.shape[3:])[:, :length]


def _operand_type():
    """The products' operand type: bf16 under AMP, else float32."""
    return amp_cast_in(jnp.zeros((), jnp.float32))[0].dtype


def kda(q, k, v, g, beta, chunk=64):
    """o [B, L, H, dv] in v's dtype and the states entering each chunk
    [B, N, H, dk, dv] float32.  q, k [B, L, H, dk]; v [B, L, H, dv]; g
    [B, L, H, dk] the log-decay; beta [B, L, H]."""
    qe, p, w, u, m, n = _parts(*_pad([q, k, v, g, beta], chunk), chunk,
                               _operand_type())
    entering = _states(m, n)
    o = _unchunked(_outputs(qe, p, w, u, entering), q.shape[1])
    return o.astype(v.dtype), entering


def kda_grads(q, k, v, g, beta, saved, do, chunk=64):
    """(dq, dk, dv, dg, dbeta) of ``kda``'s output for its gradient
    ``do``; ``saved``: the states the forward left (None: made again)."""
    dtype = _operand_type()

    def fwd(q, k, v, g, beta):
        qe, p, w, u, m, n = jax.checkpoint(
            functools.partial(_parts, chunk=chunk, dtype=dtype))(
                *_pad([q, k, v, g, beta], chunk))
        entering = _entering_saved(m, n, saved)
        return _unchunked(jax.checkpoint(_outputs)(qe, p, w, u, entering),
                          q.shape[1])

    return jax.vjp(fwd, q, k, v, g, beta)[1](_f32(do))


@jax.custom_vjp
def _entering_saved(m, n, saved):
    """``_states(m, n)``, given its value ``saved`` by the forward: nothing
    is run forward, and the backward is the recurrence reversed over the
    saved states."""
    return _states(m, n) if saved is None else saved


def _entering_fwd(m, n, saved):
    entering = _entering_saved(m, n, saved)
    return entering, (m, entering)


def _entering_bwd(res, grad):
    m, entering = res

    def step(lam, inp):
        g_i, m_i, s_i = inp
        # lam: the gradient of the state LEAVING this chunk
        d_m = jnp.matmul(lam, jnp.swapaxes(s_i, -1, -2), precision=_HIGHEST)
        back = jnp.matmul(jnp.swapaxes(m_i, -1, -2), lam, precision=_HIGHEST)
        return g_i + back, (d_m, lam)

    _, (d_m, d_n) = jax.lax.scan(
        step, jnp.zeros_like(grad[:, 0]),
        (jnp.moveaxis(grad, 1, 0), jnp.moveaxis(m, 1, 0),
         jnp.moveaxis(entering, 1, 0)), reverse=True)
    return jnp.moveaxis(d_m, 0, 1), jnp.moveaxis(d_n, 0, 1), None


_entering_saved.defvjp(_entering_fwd, _entering_bwd)

_SLOTS = ('Q', 'K', 'V', 'G', 'Beta')


def _kda_fits(ctx, q, v, chunk):
    """Whether the kernels can run this op where it is lowered: the place,
    the mesh, 128-lane heads of keys and values, a chunk of whole
    sub-chunks and whole bf16 sublane tiles (16 positions), and the
    gradient kernel's blocks within the VMEM it is compiled for."""
    from . import ssm_ops
    from .pallas import kda as pl_kda
    if not ssm_ops._one_accelerator(ctx):
        return False
    length = -(-q.shape[1] // chunk) * chunk
    return (q.shape[-1] == v.shape[-1] == pl_kda.LANES
            and chunk % SUB == 0 and chunk % 16 == 0
            and pl_kda.vmem_bytes(length, chunk, q.shape[2],
                                  q.dtype.itemsize)
            <= pl_kda.VMEM_LIMIT // 2)


def _pick_impl(ctx, attrs, q, v, chunk):
    """'xla' or 'pallas': ``impl`` where asked for, else what 'auto'
    takes from ``_kda_fits``."""
    from . import ssm_ops
    return ssm_ops._pick('kda', attrs, lambda: _kda_fits(ctx, q, v, chunk))


def _fused_forward(ctx, inputs, chunk):
    """(o, entering) from the forward kernel, the sequence padded to
    whole chunks as the XLA form pads it."""
    from .pallas import kda as pl_kda
    o, entering = pl_kda.kda(*_pad(list(inputs), chunk), chunk=chunk,
                             dtype=_operand_type(), interpret=ctx.on_cpu)
    return o[:, :inputs[0].shape[1]], entering


def _fused_grads(ctx, primals, saved, do, chunk):
    """The five gradients from the gradient kernel, which reads the states
    the forward left (made by the forward kernel where another trace
    lowered the forward)."""
    from .pallas import kda as pl_kda
    if saved is None:
        saved = _fused_forward(ctx, primals, chunk)[1]
    padded = _pad(list(primals) + [do], chunk)
    grads = pl_kda.kda_grad(*padded[:5], saved, padded[5], chunk=chunk,
                            dtype=_operand_type(), interpret=ctx.on_cpu)
    return [g[:, :primals[0].shape[1]] for g in grads]


@register_lowering('kda')
def _kda_lowering(ctx, op):
    from ..fluid import trace
    from .pallas import kda as pl_kda
    chunk = int(op.attrs['chunk'])
    if chunk % SUB:
        raise ValueError('kda: a chunk of %d positions is no whole number '
                         'of %d-position sub-chunks' % (chunk, SUB))
    out_name = op.output('Out')[0]
    inputs = [ctx.get(op, s) for s in _SLOTS]
    impl = _pick_impl(ctx, op.attrs, inputs[0], inputs[2], chunk)
    length = inputs[0].shape[1]
    seen = {'chunk': chunk, 'chunks': -(-length // chunk)}
    if impl == 'pallas':
        # a program of either kernel: positions x lanes of Q
        seen['block'] = pl_kda.block(seen['chunks'] * chunk, chunk)
        o, entering = _fused_forward(ctx, inputs, chunk)
    else:
        o, entering = kda(*inputs, chunk=chunk)
    trace.note_lowering_choice(ctx.block.program, op.type, out_name, impl,
                               **seen)
    ctx.store(out_name + _STATES_SUFFIX, entering)
    ctx.set(op, 'Out', o)


@register_grad_lowering('kda')
def _kda_grad_lowering(ctx, op):
    """dQ, dK, dV, dG and dBeta from the op's inputs, the states its
    forward left in this trace (made again where another trace lowered
    it) and dOut: the gradient kernel ('pallas') or ``kda_grads``
    ('xla')."""
    fwd_inputs, fwd_outputs, attrs = registry.fwd_structure(op)
    out_name = fwd_outputs['Out'][0]
    wanted = [op.output(s + GRAD_SUFFIX) for s in _SLOTS]
    if not any(n and n[0] for n in wanted):
        return
    primals = [ctx.lookup(fwd_inputs[s][0]) for s in _SLOTS]
    do = (ctx.lookup(out_name + GRAD_SUFFIX)
          if ctx.has(out_name + GRAD_SUFFIX) else jnp.zeros_like(primals[2]))
    saved = (ctx.lookup(out_name + _STATES_SUFFIX)
             if ctx.has(out_name + _STATES_SUFFIX) else None)
    chunk = int(attrs['chunk'])
    impl = _pick_impl(ctx, attrs, primals[0], primals[2], chunk)
    if impl == 'pallas':
        grads = _fused_grads(ctx, primals, saved, do, chunk)
    else:
        grads = kda_grads(*primals, saved, do, chunk=chunk)
    for names, primal, grad in zip(wanted, primals, grads):
        if names and names[0]:
            grad = grad.astype(primal.dtype)
            if ctx.has(names[0]):   # the rename pass did not split it
                grad = ctx.lookup(names[0]) + grad
            ctx.store(names[0], grad)


# ---- rotary positions -------------------------------------------------------

def rotate(x, start, width, theta):
    """x [B, L, ..., D] with channels [start, start + width) turned in
    interleaved pairs by position: (x_2i, x_2i+1) by the angle
    ``position * theta ** (-2i / width)``; float32 arithmetic, x's dtype."""
    length = x.shape[1]
    part = _f32(x[..., start:start + width])
    freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    angle = angle.reshape((1, length) + (1, ) * (x.ndim - 3) + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = part[..., 0::2], part[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       -1).reshape(part.shape)
    return jnp.concatenate([x[..., :start], turned.astype(x.dtype),
                            x[..., start + width:]], -1)


@register_lowering('rotary')
def _rotary(ctx, op):
    from ..fluid import trace
    a = op.attrs
    x = ctx.get(op, 'X')
    start, width = int(a['start']), int(a['width'])
    trace.note_lowering_choice(ctx.block.program, op.type,
                               op.output('Out')[0], 'xla', start=start,
                               width=width, interleaved=True)
    ctx.set(op, 'Out', rotate(x, start, width, float(a['theta'])))
