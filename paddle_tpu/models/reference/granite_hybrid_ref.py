"""Plain reference of ``paddle_tpu.models.granite_hybrid``: the decoder of
IBM's granite-4.0-h (``model_type: granitemoehybrid`` with no experts) in
``jax.numpy``, float32, ``jax.default_matmul_precision('highest')``.

Forward, loss and gradients (``jax.grad`` of the plain loss).  The
state-space layer is the recurrence itself, one position at a time
(``lax.scan`` over positions); attention is a plain softmax over key-value
heads repeated to the query heads'; nothing is chunked, fused or cast.

``params`` maps the Program's parameter names (``granite_hybrid.names``) to
arrays; ``cfg`` is the published ``config.json``'s keys.  The equations:

    RMS(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[ids] * embedding_multiplier
    per layer:  h = h + residual_multiplier * mixer(RMS(h; w1))
                h = h + residual_multiplier * mlp(RMS(h; w2))
    logits = RMS(h; w_f) E^T / logits_scaling          (tied embedding)
    loss = mean over positions of the next-token cross-entropy

Departures from the published model, each because the source leaves it
open or the cut of the benchmark needs it:
- the gated norm of the Mamba-2 mixer normalises ``y * silu(z)`` (the gate
  before the norm) over all channels as one group, the family's convention
  (``mamba_n_groups`` 1);
- the vocabulary may be a slice of the published one: ids, logits and loss
  are then over the slice (``cfg['vocab_size']`` rows of E);
- no dropout, no bias anywhere but the convolution's (as published).
"""

import jax
import jax.numpy as jnp

PRECISION = 'highest'


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mlp(p, pre, x):
    g, u = jnp.split(x @ p[pre + 'mlp_in'], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ p[pre + 'mlp_out']


def attention(p, pre, x, cfg):
    """Causal softmax attention, no positional signal
    (``position_embedding_type: nope``); query head i reads key-value head
    i // (heads / kv heads); the scores' multiplier is the model's own."""
    b, l, _ = x.shape
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    d = cfg['hidden_size'] // hq
    q = (x @ p[pre + 'q_proj']).reshape(b, l, hq, d)
    k = (x @ p[pre + 'k_proj']).reshape(b, l, hkv, d)
    v = (x @ p[pre + 'v_proj']).reshape(b, l, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * cfg['attention_multiplier']
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, l, hq * d) @ p[pre + 'o_proj']


def causal_conv(x, w, bias):
    """x [B, L, C]; w [C, K]: y_t = bias + sum_k w[:, k] x_{t-(K-1)+k},
    zeros before the sequence's start."""
    taps, length = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + length] * w[:, k] for k in range(taps))


def ssm_recurrence(x, dt, a, bm, cm, d):
    """The state-space layer as written: per head, S of [P, N],
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T, y_t = S_t c_t + d x_t.
    x [B,L,H,P], dt [B,L,H], a, d [H], bm, cm [B,L,G,N]."""
    heads = x.shape[2]
    bm, cm = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (bm, cm))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp          # [B,H,P] [B,H] [B,H,N] [B,H,N]
        s = s * jnp.exp(dt_t * a)[..., None, None] + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum('bhpn,bhn->bhp', s, c_t) + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + bm.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def mamba(p, pre, x, cfg):
    b, l, _ = x.shape
    h, hd = cfg['mamba_n_heads'], cfg['mamba_d_head']
    g, n = cfg['mamba_n_groups'], cfg['mamba_d_state']
    inner = h * hd
    z, xbc, dt = jnp.split(x @ p[pre + 'in_proj'],
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p[pre + 'conv_w'], p[pre + 'conv_b']))
    xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p[pre + 'dt_bias'])
    y = ssm_recurrence(xs.reshape(b, l, h, hd), dt,
                       -jnp.exp(p[pre + 'A_log']), bm.reshape(b, l, g, n),
                       cm.reshape(b, l, g, n), p[pre + 'D'])
    y = rms(y.reshape(b, l, inner) * jax.nn.silu(z), p[pre + 'gate_norm'],
            cfg['rms_norm_eps'])
    return y @ p[pre + 'out_proj']


def hidden(params, cfg, ids):
    """The residual stream after the last layer, [B, L, hidden]."""
    eps, res = cfg['rms_norm_eps'], cfg['residual_multiplier']
    h = params['granite.embed'][ids] * cfg['embedding_multiplier']
    for i, kind in enumerate(cfg['layer_types']):
        pre = 'granite.l%d.' % i
        mixer = mamba if kind == 'mamba' else attention
        h = h + res * mixer(params, pre, rms(h, params[pre + 'norm1'], eps),
                            cfg)
        h = h + res * mlp(params, pre, rms(h, params[pre + 'norm2'], eps))
    return h


def logits(params, cfg, ids):
    h = rms(hidden(params, cfg, ids), params['granite.final_norm'],
            cfg['rms_norm_eps'])
    return h @ params['granite.embed'].T / cfg['logits_scaling']


def loss(params, cfg, ids, labels):
    """Mean next-token cross-entropy; ids, labels [B, L] integers."""
    with jax.default_matmul_precision(PRECISION):
        logp = jax.nn.log_softmax(logits(params, cfg, ids), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[..., None], axis=-1))


def loss_and_grads(params, cfg, ids, labels):
    """(loss, {name: d loss / d params[name]}) for every parameter.  The
    precision holds for the backward's products too: they are traced after
    ``loss`` has returned."""
    with jax.default_matmul_precision(PRECISION):
        return jax.value_and_grad(loss)(params, cfg, ids, labels)
