"""Plain reference of Nemotron-H with routed experts
(``paddle_tpu.models.nemotron_h``; the benchmark keeps a copy of this file,
``paddle_tpu/models/reference/nemotron_h_ref.py`` loads this one file: it
imports nothing of the program).  ``jax.numpy``, float32,
``jax.default_matmul_precision('highest')``; the state-space layer as the
recurrence itself, one position at a time; attention as a plain softmax over
repeated key-value heads; the experts as dense products over every token,
one held expert after the other, times the token's weight for that expert
(zero where it did not select it).  No sort, no kernel, no chunked scan, no
AMP.

    RMS(x; w) = x / sqrt(mean(x^2) + eps) * w
    h = E[ids]
    per layer:  h = h + mixer(RMS(h; w))     M | * | E by the pattern
    logits = RMS(h; w_f) W_head              (untied head)
    loss = mean over positions of the next-token cross-entropy
    M:  [z, xBC, dt] = x W_in;  xBC = silu(conv1d_causal(xBC) + bias)
        [X, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        per head h of group g = h // (H / G):
        S_t = exp(dt_t A) S_{t-1} + dt_t X_t B_{g,t}^T
        y_t = S_t C_{g,t} + D X_t
        u = y * silu(z);  each of the G groups of channels of u over its
        own root-mean-square, times w_g;  out = u W_out
    *:  causal softmax(q k^T / sqrt(head_dim)) v over repeated key-value
        heads, then W_o; no positional signal
    E:  s = sigmoid(x W_r) (float32);  the k largest of s + b are selected
        (b: the selection bias, no gradient);  w_j = scale * s_j / (sum of
        the k selected s + 1e-20);  expert e(x) = relu(x W_up,e^T)^2 W_down,e
        (both matrices [width, hidden], W_up as a Linear stores it)
        out = sum over selected j with e_j held of w_j e_j(x) + shared(x)

``held``: the experts this chip computes, ``range(first, first + n)``;
what the others would add is left out (the program does the same).  With
every expert held the layer is the published one.  ``adam_steps`` trains
it: plain Adam on every parameter but the selection bias, a batch a step;
after a step the bias of an expert that got fewer pairs than the mean goes
up by ``router_bias_update_rate``, of one that got more down (the family's
balancing without an auxiliary loss, ``balanced_bias``; a set-up may have
moved the bias it is given by the same rule).

Selection is discrete, and a program that reads a bf16 activation settles
a near-tie the other way.  ``forced`` (``backward``, ``adam_steps``' first
step) hands a layer the experts another computation selected, and the
weights, the output and every gradient are then this file's own for THOSE
pairs, so that the two are compared on equal selections; what this file
would have selected itself is still handed out (``selections``).
``router_check`` and ``held_experts_check`` are the router and the held
experts alone, on given inputs.

Computed in blocks so that it holds less of the device than a cell's own
training state does at the timed sizes: a layer at a time (each kind of
layer is one jitted function and one jitted vector-Jacobian product, its
weights brought to the device for the call and dropped after it; the
residual stream after every layer is what is kept), inside the recurrence a
block of positions at a time (``jax.checkpoint`` round ``BLOCK`` positions),
and attention a sequence at a time.  Blocking changes what is kept, not
what is computed.

Departures from the published code, each because the source leaves it
open or the cut of the benchmark needs it:
- a held range of experts and a vocabulary that may be a slice (ids,
  logits and loss over ``cfg['vocab_size']`` rows);
- the published code computes every sum in bfloat16 with a bfloat16
  residual stream (``residual_in_fp32: false``); this is float32 throughout;
- ``n_group`` 1 and ``topk_group`` 1: no group stage in the selection;
- ``rope_theta`` is in the configuration and unused: the family's
  attention applies no positional signal;
- no dropout, no auxiliary loss term, no bias but the convolution's (as
  published).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = 'highest'
BLOCK = 64   # positions of the recurrence a checkpointed block


def rms(x, w, eps, groups=1):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(shape) * w


def attention(p, x, cfg):
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    d = cfg['head_dim']

    def one(row):                                     # [L, hidden]
        length = row.shape[0]
        q = (row @ p['q_proj']).reshape(length, hq, d)
        k = (row @ p['k_proj']).reshape(length, hkv, d)
        v = (row @ p['v_proj']).reshape(length, hkv, d)
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
        s = jnp.einsum('qhd,khd->hqk', q, k) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s,
                      -jnp.inf)
        o = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)
        return o.reshape(length, hq * d) @ p['o_proj']

    return jax.lax.map(one, x)


def causal_conv(x, w, bias):
    taps, length = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + length] * w[:, k] for k in range(taps))


def ssm_recurrence(x, dt, a, bm, cm, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T, y_t = S_t c_t + d x_t,
    position by position.  x [B,L,H,P], dt [B,L,H], a, d [H],
    bm, cm [B,L,G,N]: head h reads group h // (H / G)."""
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
    h, hd = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    g, n = cfg['n_groups'], cfg['ssm_state_size']
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
            cfg['layer_norm_epsilon'], groups=g)
    return y @ p['out_proj']


def select(p, x, cfg, forced=None):
    """(indices [..., k], weights [..., k]) of a token's selected experts:
    the largest of s + b first, the lower index first among equals, one
    after the other; or the indices ``forced``, with this router's weights
    for them."""
    s = jax.nn.sigmoid(x @ p['router'])
    left = jax.lax.stop_gradient(s) + p['router_bias']
    idx = []
    for _ in range(cfg['num_experts_per_tok'] if forced is None else 0):
        idx.append(jnp.argmax(left, axis=-1))
        left = jnp.where(jax.nn.one_hot(idx[-1], s.shape[-1], dtype=bool),
                         -jnp.inf, left)
    idx = jnp.stack(idx, axis=-1) if forced is None else forced
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg['norm_topk_prob']:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg['routed_scaling_factor']


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def held_sum(w_up, w_down, x, idx, w, first):
    """Every token through every held expert (``first`` and the
    ``len(w_up) - 1`` after it), times its weight for it: zero where it did
    not select it."""
    out = jnp.zeros_like(x)
    for j in range(w_up.shape[0]):
        mine = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1,
                       keepdims=True)
        out = out + mine * (relu2(x @ w_up[j].T) @ w_down[j])
    return out


def routed(p, x, cfg, held, forced=None):
    """The held experts' part of the layer's output."""
    idx, w = select(p, x, cfg, forced)
    return held_sum(p['experts.w_up'][:len(held)],
                    p['experts.w_down'][:len(held)], x, idx, w, held[0])


def experts(p, x, cfg, forced=None):
    held = range(cfg['first_expert'],
                 cfg['first_expert'] + cfg['n_routed_experts_held'])
    return routed(p, x, cfg, held, forced) \
        + relu2(x @ p['shared_up']) @ p['shared_down']


MIXERS = {'M': mamba, '*': attention, 'E': experts}


def layer(kind, cfg, p, h, forced=None):
    """One decoder layer: ``p`` maps the layer's short parameter names;
    ``forced``: an ``E`` layer's selections, where they are given."""
    x = rms(h, p['norm'], cfg['layer_norm_epsilon'])
    if forced is not None:
        return h + experts(p, x, cfg, forced)
    return h + MIXERS[kind](p, x, cfg)


def head_loss(cfg, head, final_norm, h, labels):
    x = rms(h, final_norm, cfg['layer_norm_epsilon'])
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


# a jitted piece takes the configuration as ``static``: its scalar items,
# sorted (hashable, and the same for the same configuration)

@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_fwd(kind, static, p, h, forced=None):
    return layer(kind, dict(static), p, h, forced)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_vjp(kind, static, p, h, dh, forced=None):
    _, vjp = jax.vjp(functools.partial(layer, kind, dict(static),
                                       forced=forced), p, h)
    return vjp(dh)


@functools.partial(jax.jit, static_argnums=(0, ))
def _head(static, head, final_norm, h, labels):
    return jax.value_and_grad(
        functools.partial(head_loss, dict(static)), argnums=(0, 1, 2))(
            head, final_norm, h, labels)


@functools.partial(jax.jit, static_argnums=(0, ))
def _selected(static, p, h):
    cfg = dict(static)
    return select(p, rms(h, p['norm'], cfg['layer_norm_epsilon']), cfg)[0]


LAYER_PARAMS = {
    'M': ['norm', 'in_proj', 'conv_w', 'conv_b', 'dt_bias', 'A_log', 'D',
          'gate_norm', 'out_proj'],
    '*': ['norm', 'q_proj', 'k_proj', 'v_proj', 'o_proj'],
    'E': ['norm', 'router', 'router_bias', 'experts.w_up', 'experts.w_down',
          'shared_up', 'shared_down'],
}
UNTRAINED = ('router_bias', )   # buffers: no gradient is applied


def pattern(cfg):
    return cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]


def _static(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (bool, int, float))))


def _layer_weights(weight, cfg, i):
    return {n: jnp.asarray(weight('nemotron.l%d.%s' % (i, n)), jnp.float32)
            for n in LAYER_PARAMS[pattern(cfg)[i]]}


def backward(weight, cfg, ids, labels, sink, selections=None, forced=None):
    """The loss of one batch; ``sink(name, gradient, value)`` is handed
    every parameter's gradient and the value it was taken at (device
    arrays) as the backward reaches it: the head and the final norm, the
    layers from the last to the first, the embedding last.
    ``weight(name)`` gives a parameter as a float32 array (host or device);
    names are the program's (``nemotron.embed``, ``nemotron.l1.router``).
    ``selections``: a dict that is handed each ``E`` layer's own selected
    experts, [B, L, k] by the layer's number.  ``forced``: such a dict of
    the selections to compute with instead."""
    kinds, static = pattern(cfg), _static(cfg)
    forced = {i: jnp.asarray(idx) for i, idx in (forced or {}).items()}
    with jax.default_matmul_precision(PRECISION):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        embed = jnp.asarray(weight('nemotron.embed'), jnp.float32)
        head = jnp.asarray(weight('nemotron.lm_head'), jnp.float32)
        norm = jnp.asarray(weight('nemotron.final_norm'), jnp.float32)
        stream = [embed[ids]]
        for i, kind in enumerate(kinds):
            p = _layer_weights(weight, cfg, i)
            if kind == 'E' and selections is not None:
                selections[i] = np.asarray(_selected(static, p, stream[-1]))
            stream.append(_layer_fwd(kind, static, p, stream[-1],
                                     forced.get(i)))
        loss, (d_head, d_norm, dh) = _head(static, head, norm, stream.pop(),
                                           labels)
        sink('nemotron.lm_head', d_head, head)
        sink('nemotron.final_norm', d_norm, norm)
        for i in reversed(range(len(kinds))):
            p = _layer_weights(weight, cfg, i)
            dp, dh = _layer_vjp(kinds[i], static, p, stream.pop(), dh,
                                forced.get(i))
            for n in LAYER_PARAMS[kinds[i]]:
                sink('nemotron.l%d.%s' % (i, n), dp.pop(n), p.pop(n))
        sink('nemotron.embed', jnp.zeros_like(embed).at[ids.reshape(-1)].add(
            dh.reshape(-1, dh.shape[-1])), embed)
    return float(loss)


@functools.partial(jax.jit, static_argnums=(0, ))
def _router_check(static, p, x, idx):
    cfg = dict(static)
    return select(p, x, cfg)[0], select(p, x, cfg, idx)[1]


def router_check(router, bias, x, idx, cfg):
    """The router alone, on given inputs: (the experts it selects for the
    tokens ``x`` [..., hidden], its weights for the selections ``idx``
    [..., k])."""
    with jax.default_matmul_precision(PRECISION):
        p = {'router': jnp.asarray(router, jnp.float32),
             'router_bias': jnp.asarray(bias, jnp.float32)}
        return _router_check(_static(cfg), p, jnp.asarray(x, jnp.float32),
                             jnp.asarray(idx))


@functools.partial(jax.jit, static_argnums=(0, ))
def _held_check(first, w_up, w_down, x, idx, w, dy):
    out, vjp = jax.vjp(
        lambda up, down: held_sum(up, down, x, idx, w, first), w_up, w_down)
    return (out, ) + vjp(dy)


def held_experts_check(w_up, w_down, x, idx, w, dy, first=0):
    """The held experts alone, on given inputs: (their output for the
    tokens ``x`` [..., hidden] with the selections ``idx`` and weights ``w``
    [..., k]; the gradients of ``w_up`` and ``w_down`` that the output's
    gradient ``dy`` gives)."""
    with jax.default_matmul_precision(PRECISION):
        return _held_check(first, *(jnp.asarray(a, jnp.float32)
                                    for a in (w_up, w_down, x)),
                           jnp.asarray(idx), jnp.asarray(w, jnp.float32),
                           jnp.asarray(dy, jnp.float32))


def names(cfg):
    out = ['nemotron.embed', 'nemotron.final_norm', 'nemotron.lm_head']
    for i, kind in enumerate(pattern(cfg)):
        out += ['nemotron.l%d.%s' % (i, n) for n in LAYER_PARAMS[kind]]
    return out


def loss_and_grads(params, cfg, ids, labels, selections=None, forced=None):
    """(loss, {name: d loss / d params[name]}) for every parameter."""
    grads = {}
    loss = backward(params.__getitem__, cfg, ids, labels,
                    lambda name, g, value: grads.__setitem__(name, g),
                    selections, forced)
    return loss, grads


def expert_load(selected, cfg):
    """Rows each held expert gets of one layer's selections [B, L, k]."""
    first = cfg['first_expert']
    return np.bincount(np.asarray(selected).ravel(),
                       minlength=cfg['n_routed_experts'])[
                           first:first + cfg['n_routed_experts_held']]


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(w, m, v, g, step, lr, beta1, beta2, eps):
    """Adam as its paper's section 2 closes it and Fluid's ``adam`` states
    it: the bias corrections folded into the step size, epsilon beside
    the uncorrected second moment."""
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1 - beta2 ** step) / (1 - beta1 ** step)
    return w - lr_t * m / (jnp.sqrt(v) + eps), m, v


def balanced_bias(bias, selected, rate):
    """The family's balancing rule (``topk_method: noaux_tc``): after a
    pass, the selection bias of an expert that got fewer pairs than the
    mean goes up by ``rate``, of one that got more down."""
    load = np.bincount(np.asarray(selected).ravel(), minlength=bias.size)
    return (bias + rate * np.sign(load.mean() - load)).astype(np.float32)


def adam_steps(weight, cfg, batches, lr, beta1=0.9, beta2=0.999, eps=1e-8,
               wrt=(), selections=None, forced=None):
    """Plain training: one Adam step a batch of ``batches`` ((ids, labels)
    pairs), from the weights ``weight(name)`` and zero moments, every
    parameter trained but the selection bias, which moves after each step
    by ``cfg['router_bias_update_rate']`` under ``balanced_bias`` on the
    selections the step computed with.  The weights stay on the host
    between their uses and the two moments on the device, 8 bytes a
    parameter: less than any training state of the same model holds there
    (12).  Returns (the loss of every step, before its update; the first
    step's gradients of the names in ``wrt``, on the host; ``final(name)``,
    a parameter after the last step, on the host).  ``selections`` and
    ``forced``: ``backward``'s, for the FIRST step; every later step
    selects for itself."""
    w = {n: weight(n) for n in names(cfg)}
    trained = [n for n in w if n.rsplit('.', 1)[-1] not in UNTRAINED]
    m = {n: jnp.zeros(w[n].shape, jnp.float32) for n in trained}
    v = {n: jnp.zeros(w[n].shape, jnp.float32) for n in trained}
    losses, first = [], {}

    def update(step, name, g, value):
        if name not in m:
            return
        if step == 1 and name in wrt:
            first[name] = np.asarray(g)
        new, m[name], v[name] = _adam(value, m[name], v[name], g,
                                      float(step), lr, beta1, beta2, eps)
        w[name] = np.asarray(new)

    rate = cfg['router_bias_update_rate']
    for step, (ids, labels) in enumerate(batches, 1):
        own, given = ({} if selections is None else selections, forced) \
            if step == 1 else ({}, None)
        losses.append(backward(w.__getitem__, cfg, ids, labels,
                               functools.partial(update, step), own, given))
        for i, selected in own.items():
            bias = 'nemotron.l%d.router_bias' % i
            w[bias] = balanced_bias(np.asarray(w[bias]),
                                    (given or {}).get(i, selected), rate)
    return losses, first, w.__getitem__
