"""Independent upper bound for transformer-base training throughput.

A standalone pure-JAX transformer-base train step (no framework) with the
same numeric policy as the framework bench (bf16 matmul inputs, f32 master
weights / layernorm stats / softmax, Adam with f32 moments, fused
softmax-CE over the 30k vocab), benched at bench.py's operating point
(bs128, seq256, 6L, d512, ff2048, h8, vocab 30k) — the r3 ResNet-bound
method (tools/jax_resnet_bound.py) reapplied to the transformer, per
VERDICT r4 next-#1.

Variants, each a flag, so one script maps the design space:
  --attn {dense,flash}   dense bf16 QK^T/softmax/PV vs the framework's
                         Pallas flash kernel (ops/pallas/flash_attention)
  --ce {fused,plain}     custom-VJP CE (bwd = p - onehot, no f32 logits
                         materialisation) vs plain logsumexp autodiff
  --remat                jax.checkpoint around each enc/dec layer
  --batch/--seq/--steps  operating point

Prints one JSON line per run: tokens/sec + analytic MFU (same FLOP model
as bench.py _transformer_flops_per_token; peak from bench.DEVICE_PEAKS,
keyed by device_kind).

Run on the chip:  chiprun -- python tools/jax_transformer_bound.py --attn dense
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import peak_flops  # noqa: E402  (the one device_kind-keyed table)

V, L, NLAYER, NHEAD, D, DFF = 30000, 256, 6, 8, 512, 2048


def _transformer_flops_per_token(n_layer, d, d_ff, seq, vocab):
    """Identical accounting to bench.py (MACs x2, train = 3x fwd)."""
    enc = n_layer * (4 * d * d + 2 * d * d_ff + 2 * seq * d)
    dec = n_layer * (8 * d * d + 2 * d * d_ff + 4 * seq * d)
    return 3.0 * 2.0 * (enc + dec + vocab * d)


def _dense(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_params(key):
    ks = iter(jax.random.split(key, 200))
    p = {
        'src_emb': _dense(next(ks), (V, D), 0.02),
        'trg_emb': _dense(next(ks), (V, D), 0.02),
        'out_w': _dense(next(ks), (D, V), D ** -0.5),
        'out_b': jnp.zeros((V,), jnp.float32),
        'enc': [], 'dec': [],
    }

    def ln():
        return {'g': jnp.ones((D,), jnp.float32),
                'b': jnp.zeros((D,), jnp.float32)}

    def attn():
        return {'wq': _dense(next(ks), (D, D), D ** -0.5),
                'wk': _dense(next(ks), (D, D), D ** -0.5),
                'wv': _dense(next(ks), (D, D), D ** -0.5),
                'wo': _dense(next(ks), (D, D), D ** -0.5)}

    def ffn():
        return {'w1': _dense(next(ks), (D, DFF), D ** -0.5),
                'b1': jnp.zeros((DFF,), jnp.float32),
                'w2': _dense(next(ks), (DFF, D), DFF ** -0.5),
                'b2': jnp.zeros((D,), jnp.float32)}

    for _ in range(NLAYER):
        p['enc'].append({'attn': attn(), 'ln1': ln(),
                         'ffn': ffn(), 'ln2': ln()})
        p['dec'].append({'self': attn(), 'ln1': ln(),
                         'cross': attn(), 'ln2': ln(),
                         'ffn': ffn(), 'ln3': ln()})
    return p


def layer_norm(x, p):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p['g'] + p['b']
    return y.astype(jnp.bfloat16)


def matmul(x, w):
    return x @ w.astype(jnp.bfloat16)


def dense_attention(q, k, v, causal):
    """[B, T, H, Dh] bf16; f32 softmax. The straightforward formulation
    the reference's multi_head_attention composes from matmul+softmax."""
    b, t, h, dh = q.shape
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32)
    s = s * (dh ** -0.5)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        s = jnp.where(col <= row, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def attention(x_q, x_kv, p, causal, attn_impl):
    b, t, _ = x_q.shape
    q = matmul(x_q, p['wq'])
    k = matmul(x_kv, p['wk'])
    v = matmul(x_kv, p['wv'])
    if attn_impl == 'flash':
        from paddle_tpu.ops.pallas import flash_attention as pl_fa
        dh = D // NHEAD
        ctx = pl_fa.flash_attention(
            q.reshape(b, t, NHEAD, dh), k.reshape(b, x_kv.shape[1], NHEAD, dh),
            v.reshape(b, x_kv.shape[1], NHEAD, dh),
            causal=causal, scale=dh ** -0.5)
        ctx = ctx.reshape(b, t, D)
    else:
        dh = D // NHEAD
        ctx = dense_attention(q.reshape(b, t, NHEAD, dh),
                              k.reshape(b, x_kv.shape[1], NHEAD, dh),
                              v.reshape(b, x_kv.shape[1], NHEAD, dh),
                              causal).reshape(b, t, D)
    return matmul(ctx, p['wo'])


def ffn(x, p):
    h = jnp.maximum(matmul(x, p['w1']) + p['b1'].astype(jnp.bfloat16), 0)
    return matmul(h, p['w2']) + p['b2'].astype(jnp.bfloat16)


def embed(ids, table, pos):
    e = table.astype(jnp.bfloat16)[ids] * jnp.bfloat16(D ** 0.5)
    return e + pos.astype(jnp.bfloat16)


@jax.custom_vjp
def fused_ce(logits_in, w, b, labels):
    """Mean CE of (x @ w + b) vs labels without autodiff's extra f32
    logits round-trip: bwd emits (softmax - onehot) directly in bf16
    (the round-4 CE-convert find, ops/loss_ops.py, applied here too)."""
    logits = (logits_in @ w.astype(jnp.bfloat16)).astype(jnp.float32) + b
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, labels[..., None], axis=-1)
    return -jnp.mean(ll)


def _fused_ce_fwd(x, w, b, labels):
    logits = (x @ w.astype(jnp.bfloat16)).astype(jnp.float32) + b
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, labels[..., None], axis=-1)
    p = jnp.exp(logits - lse).astype(jnp.bfloat16)
    return -jnp.mean(ll), (x, w, p, labels)


def _fused_ce_bwd(res, g):
    x, w, p, labels = res
    n = p.shape[0] * p.shape[1]
    onehot = jax.nn.one_hot(labels, p.shape[-1], dtype=jnp.bfloat16)
    glog = (p - onehot) * jnp.bfloat16(g / n)
    gx = glog @ w.astype(jnp.bfloat16).T
    gw = jnp.einsum('btd,btv->dv', x, glog,
                    preferred_element_type=jnp.float32)
    gb = jnp.sum(glog.astype(jnp.float32), axis=(0, 1)) * 1.0
    return gx, gw, gb, None


fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def forward_loss(params, src, trg, lbl, attn_impl, ce_impl, remat, pos):
    enc = embed(src, params['src_emb'], pos)

    def enc_layer(x, lp):
        x = layer_norm(x + attention(x, x, lp['attn'], False, attn_impl),
                       lp['ln1'])
        return layer_norm(x + ffn(x, lp['ffn']), lp['ln2'])

    def dec_layer(x, e, lp):
        x = layer_norm(x + attention(x, x, lp['self'], True, attn_impl),
                       lp['ln1'])
        x = layer_norm(x + attention(x, e, lp['cross'], False, attn_impl),
                       lp['ln2'])
        return layer_norm(x + ffn(x, lp['ffn']), lp['ln3'])

    if remat:
        enc_layer = jax.checkpoint(enc_layer)
        dec_layer = jax.checkpoint(dec_layer)

    for lp in params['enc']:
        enc = enc_layer(enc, lp)
    dec = embed(trg, params['trg_emb'], pos)
    for lp in params['dec']:
        dec = dec_layer(dec, enc, lp)

    if ce_impl == 'fused':
        return fused_ce(dec, params['out_w'], params['out_b'], lbl)
    logits = (dec @ params['out_w'].astype(jnp.bfloat16)
              ).astype(jnp.float32) + params['out_b']
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, lbl[..., None], axis=-1)
    return -jnp.mean(ll)


def adam_update(p, m, v, g, lr=1e-3, b1=0.9, b2=0.997, eps=1e-9):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    return p - lr * m / (jnp.sqrt(v) + eps), m, v


def make_step(attn_impl, ce_impl, remat, pos):
    def train_step(params, m_t, v_t, src, trg, lbl):
        loss, grads = jax.value_and_grad(forward_loss)(
            params, src, trg, lbl, attn_impl, ce_impl, remat, pos)
        flat_p, tree = jax.tree.flatten(params)
        flat_m = jax.tree.leaves(m_t)
        flat_v = jax.tree.leaves(v_t)
        flat_g = jax.tree.leaves(grads)
        new_p, new_m, new_v = [], [], []
        for p, m, v, g in zip(flat_p, flat_m, flat_v, flat_g):
            p2, m2, v2 = adam_update(p, m, v, g.astype(jnp.float32))
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
        unf = jax.tree.unflatten
        return unf(tree, new_p), unf(tree, new_m), unf(tree, new_v), loss

    return jax.jit(train_step, donate_argnums=(0, 1, 2))


def position_table(max_len, d):
    posn = np.arange(max_len)[:, None].astype('float64')
    div = np.power(10000.0, -(np.arange(0, d, 2).astype('float64') / d))
    table = np.zeros((max_len, d))
    table[:, 0::2] = np.sin(posn * div)
    table[:, 1::2] = np.cos(posn * div[:d // 2])
    return jnp.asarray(table[None], jnp.float32)


def build(attn_impl='dense', ce_impl='fused', remat=False, batch=128,
          seq=L):
    """Returns (state_dict, timed_block_fn) for same-process gating."""
    dev = jax.devices()[0]
    params = jax.device_put(make_params(jax.random.PRNGKey(0)), dev)
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = {'p': params, 'm': jax.device_put(zeros, dev),
             'v': jax.device_put(jax.tree.map(jnp.zeros_like, params), dev)}
    pos = jax.device_put(position_table(seq, D), dev)
    rng = np.random.RandomState(0)

    def ids():
        return jax.device_put(
            rng.randint(1, V, size=(batch, seq)).astype(np.int32), dev)

    src, trg, lbl = ids(), ids(), ids()
    step = make_step(attn_impl, ce_impl, remat, pos)
    for _ in range(2):
        state['p'], state['m'], state['v'], loss = step(
            state['p'], state['m'], state['v'], src, trg, lbl)
    jax.block_until_ready(loss)

    def timed_block(steps):
        t0 = time.time()
        for _ in range(steps):
            state['p'], state['m'], state['v'], loss = step(
                state['p'], state['m'], state['v'], src, trg, lbl)
        lv = float(loss)
        el = time.time() - t0
        assert np.isfinite(lv)
        return batch * seq * steps / el

    return state, timed_block


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--attn', default='dense', choices=['dense', 'flash'])
    ap.add_argument('--ce', default='fused', choices=['fused', 'plain'])
    ap.add_argument('--remat', action='store_true')
    ap.add_argument('--batch', type=int, default=128)
    ap.add_argument('--seq', type=int, default=L)
    ap.add_argument('--steps', type=int, default=10)
    ap.add_argument('--blocks', type=int, default=3)
    args = ap.parse_args()

    _, timed_block = build(args.attn, args.ce, args.remat, args.batch,
                           args.seq)
    per = [timed_block(args.steps) for _ in range(args.blocks)]
    tok = max(per)  # best-of-blocks
    fpt = _transformer_flops_per_token(NLAYER, D, DFF, args.seq, V)
    print(json.dumps({
        'bench': 'pure_jax_transformer_bound',
        'attn': args.attn, 'ce': args.ce, 'remat': args.remat,
        'batch': args.batch, 'seq': args.seq,
        'tokens_per_sec': round(tok, 1),
        'tokens_per_sec_blocks': [round(v, 1) for v in per],
        'mfu': round(tok * fpt / peak_flops(), 4),
    }))


if __name__ == '__main__':
    main()
