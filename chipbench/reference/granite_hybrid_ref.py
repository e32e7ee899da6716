"""The benchmark's own plain reference of granite-4.0-h (a copy of
``paddle_tpu/models/reference/granite_hybrid_ref.py``'s equations; it
imports nothing of the program): ``jax.numpy``, float32,
``jax.default_matmul_precision('highest')``, the state-space layer as the
recurrence itself, one position at a time, attention as a plain softmax over
repeated key-value heads.  No kernel, no chunked scan, no AMP.

    RMS(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[ids] * embedding_multiplier
    per layer:  h = h + residual_multiplier * mixer(RMS(h; w1))
                h = h + residual_multiplier * mlp(RMS(h; w2))
    logits = RMS(h; w_f) E^T / logits_scaling          (tied embedding)
    loss = mean over positions of the next-token cross-entropy
    mamba:  [z, xBC, dt] = x W_in;  xBC = silu(conv1d_causal(xBC) + bias)
            [X, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
            S_t = exp(dt_t A) S_{t-1} + dt_t X_t B_t^T;  y_t = S_t C_t + D X_t
            out = RMS(y * silu(z); w_g) W_out

``adam_steps`` trains it: plain Adam on every parameter, a batch a step.

Computed in blocks so that it holds less of the device than a cell's own
training state does at the timed sizes: a layer at a time (each kind of layer is one jitted function
and one jitted vector-Jacobian product, its weights brought to the device
for the call and dropped after it; the residual stream after every layer is
what is kept), and inside the recurrence a block of positions at a time
(``jax.checkpoint`` round ``BLOCK`` positions: the backward keeps one state
a block, not one a position).  Blocking changes what is kept, not what is
computed.

Departures from the published model: the gate before the gated norm, one
group (the family's convention); a vocabulary that may be a slice (ids,
logits and loss over ``cfg['vocab_size']`` rows); no dropout.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = 'highest'
BLOCK = 64   # positions of the recurrence a checkpointed block


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mlp(p, x):
    g, u = jnp.split(x @ p['mlp_in'], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ p['mlp_out']


def attention(p, x, cfg):
    b, l, _ = x.shape
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    d = cfg['hidden_size'] // hq
    q = (x @ p['q_proj']).reshape(b, l, hq, d)
    k = (x @ p['k_proj']).reshape(b, l, hkv, d)
    v = (x @ p['v_proj']).reshape(b, l, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * cfg['attention_multiplier']
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, l, hq * d) @ p['o_proj']


def causal_conv(x, w, bias):
    taps, length = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + length] * w[:, k] for k in range(taps))


def ssm_recurrence(x, dt, a, bm, cm, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T, y_t = S_t c_t + d x_t,
    position by position.  x [B,L,H,P], dt [B,L,H], a, d [H],
    bm, cm [B,L,G,N]."""
    heads, length = x.shape[2], x.shape[1]
    bm, cm = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (bm, cm))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * a)[..., None, None] + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum('bhpn,bhn->bhp', s, c_t) + d[:, None] * x_t

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    size = next(n for n in range(min(BLOCK, length), 0, -1)
                if length % n == 0)
    seq = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (length // size, size, t.shape[0]) + t.shape[2:])
        for t in (x, dt, bm, cm))
    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + bm.shape[-1:], x.dtype)
    _, y = jax.lax.scan(block, s0, seq)
    return jnp.moveaxis(y.reshape((length, ) + y.shape[2:]), 0, 1)


def mamba(p, x, cfg):
    b, l, _ = x.shape
    h, hd = cfg['mamba_n_heads'], cfg['mamba_d_head']
    g, n = cfg['mamba_n_groups'], cfg['mamba_d_state']
    inner = h * hd
    z, xbc, dt = jnp.split(x @ p['in_proj'],
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p['conv_w'], p['conv_b']))
    xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p['dt_bias'])
    y = ssm_recurrence(xs.reshape(b, l, h, hd), dt, -jnp.exp(p['A_log']),
                       bm.reshape(b, l, g, n), cm.reshape(b, l, g, n),
                       p['D'])
    y = rms(y.reshape(b, l, inner) * jax.nn.silu(z), p['gate_norm'],
            cfg['rms_norm_eps'])
    return y @ p['out_proj']


def layer(kind, cfg, p, h):
    """One decoder layer: ``p`` maps the layer's short parameter names."""
    eps, res = cfg['rms_norm_eps'], cfg['residual_multiplier']
    mixer = mamba if kind == 'mamba' else attention
    h = h + res * mixer(p, rms(h, p['norm1'], eps), cfg)
    return h + res * mlp(p, rms(h, p['norm2'], eps))


def head_loss(cfg, embed, final_norm, h, labels):
    x = rms(h, final_norm, cfg['rms_norm_eps'])
    logp = jax.nn.log_softmax(x @ embed.T / cfg['logits_scaling'], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


# a jitted piece takes the configuration as ``static``: its scalar items,
# sorted (hashable, and the same for the same configuration)

@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_fwd(kind, static, p, h):
    return layer(kind, dict(static), p, h)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_vjp(kind, static, p, h, dh):
    _, vjp = jax.vjp(functools.partial(layer, kind, dict(static)), p, h)
    return vjp(dh)


@functools.partial(jax.jit, static_argnums=(0, ))
def _head(static, embed, final_norm, h, labels):
    return jax.value_and_grad(
        functools.partial(head_loss, dict(static)), argnums=(0, 1, 2))(
            embed, final_norm, h, labels)


LAYER_PARAMS = {
    'mamba': ['norm1', 'in_proj', 'conv_w', 'conv_b', 'dt_bias', 'A_log',
              'D', 'gate_norm', 'out_proj', 'norm2', 'mlp_in', 'mlp_out'],
    'attention': ['norm1', 'q_proj', 'k_proj', 'v_proj', 'o_proj', 'norm2',
                  'mlp_in', 'mlp_out'],
}


def backward(weight, cfg, ids, labels, sink):
    """The loss of one batch; ``sink(name, gradient, value)`` is handed
    every parameter's gradient and the value it was taken at (device
    arrays) as the backward reaches it: the final norm, the layers from
    the last to the first, the tied embedding last.  ``weight(name)`` gives
    a parameter as a float32 array (host or device); names are the
    program's (``granite.embed``, ``granite.l3.in_proj``,
    ``granite.final_norm``)."""
    kinds = list(cfg['layer_types'])
    static = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float))))

    def params(i):
        return {n: jnp.asarray(weight('granite.l%d.%s' % (i, n)),
                               jnp.float32) for n in LAYER_PARAMS[kinds[i]]}

    with jax.default_matmul_precision(PRECISION):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        embed = jnp.asarray(weight('granite.embed'), jnp.float32)
        norm = jnp.asarray(weight('granite.final_norm'), jnp.float32)
        stream = [embed[ids] * cfg['embedding_multiplier']]
        for i, kind in enumerate(kinds):
            stream.append(_layer_fwd(kind, static, params(i), stream[-1]))
        loss, (d_embed, d_norm, dh) = _head(static, embed, norm,
                                            stream.pop(), labels)
        sink('granite.final_norm', d_norm, norm)
        for i in reversed(range(len(kinds))):
            p = params(i)
            dp, dh = _layer_vjp(kinds[i], static, p, stream.pop(), dh)
            for n in LAYER_PARAMS[kinds[i]]:
                sink('granite.l%d.%s' % (i, n), dp.pop(n), p.pop(n))
        # the tied embedding: the head's gradient and the lookup's
        sink('granite.embed', d_embed.at[ids.reshape(-1)].add(
            dh.reshape(-1, dh.shape[-1]) * cfg['embedding_multiplier']),
            embed)
    return float(loss)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(w, m, v, g, step, lr, beta1, beta2, eps):
    """Adam as its paper's section 2 closes it and Fluid's ``adam`` states
    it: the bias corrections folded into the step size, epsilon beside
    the uncorrected second moment."""
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1 - beta2 ** step) / (1 - beta1 ** step)
    return w - lr_t * m / (jnp.sqrt(v) + eps), m, v


def adam_steps(weight, cfg, batches, lr, beta1=0.9, beta2=0.999, eps=1e-8,
               wrt=()):
    """Plain training: one Adam step a batch of ``batches`` ((ids, labels)
    pairs), from the weights ``weight(name)`` and zero moments, every
    parameter trained.  The weights stay on the host between their uses (a
    layer's are brought to the device for its calls, as above) and the two
    moments on the device, 8 bytes a parameter: less than any training
    state of the same model holds there (12), so the peak a cell's device
    reports is its program's and not this reference's.  Returns (the loss
    of every step, before its update; the first step's gradients of the
    names in ``wrt``, on the host; ``final(name)``, a parameter after the
    last step, on the host)."""
    names = ['granite.embed', 'granite.final_norm'] + [
        'granite.l%d.%s' % (i, n) for i, kind in enumerate(cfg['layer_types'])
        for n in LAYER_PARAMS[kind]]
    w = {n: weight(n) for n in names}
    m = {n: jnp.zeros(a.shape, jnp.float32) for n, a in w.items()}
    v = {n: jnp.zeros(a.shape, jnp.float32) for n, a in w.items()}
    losses, first = [], {}

    def update(step, name, g, value):
        if step == 1 and name in wrt:
            first[name] = np.asarray(g)
        new, m[name], v[name] = _adam(value, m[name], v[name], g,
                                      float(step), lr, beta1, beta2, eps)
        w[name] = np.asarray(new)

    for step, (ids, labels) in enumerate(batches, 1):
        losses.append(backward(w.__getitem__, cfg, ids, labels,
                               functools.partial(update, step)))
    return losses, first, w.__getitem__
