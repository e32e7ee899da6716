"""Independent upper bound for the WMT seq2seq+attention train step.

A standalone pure-JAX implementation of the bench.py `nmt` config
(machine_translation.py architecture: embedding -> fc(4D, tanh) ->
LSTM encoder; per-step Bahdanau attention + GRU decoder; hoisted vocab
projection + masked CE; Adam) with the framework's numeric policy
(bf16 matmuls, f32 gates/cell/softmax, f32 master weights + Adam
moments), at the bench operating point (bs512, seq32, D=512, dict30k).
The r3 ResNet-bound method reapplied, per VERDICT r4 next-#2.

Variants:
  --unroll K   lax.scan unroll factor for both encoder and decoder scans
  --ce {fused,plain}  custom-VJP CE vs plain logsumexp autodiff
  --batch/--seq/--steps  operating point

Prints one JSON line: tokens/sec + MFU at bench.py's 1.404e8 FLOPs/token
accounting (peak from bench.DEVICE_PEAKS, keyed by device_kind).

Run on the chip:  chiprun -- python tools/jax_nmt_bound.py
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
FLOPS_PER_TOKEN = 1.404e8  # bench.py accounting (XLA cost analysis, r2)

V, D, EMB = 30000, 512, 512


def _dense(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_params(key):
    ks = iter(jax.random.split(key, 32))
    s = D ** -0.5
    return {
        'src_emb': _dense(next(ks), (V, EMB), 0.02),
        'trg_emb': _dense(next(ks), (V, EMB), 0.02),
        'fc1_w': _dense(next(ks), (EMB, 4 * D), s),
        'fc1_b': jnp.zeros((4 * D,), jnp.float32),
        'lstm_wh': _dense(next(ks), (D, 4 * D), s),
        'lstm_b': jnp.zeros((4 * D,), jnp.float32),
        'proj_w': _dense(next(ks), (D, D), s),
        'boot_w': _dense(next(ks), (D, D), s),
        'boot_b': jnp.zeros((D,), jnp.float32),
        'att_sp': _dense(next(ks), (D, D), s),
        'att_v': _dense(next(ks), (D, 1), s),
        'dec_in_w': _dense(next(ks), (D + EMB, 3 * D), (D + EMB) ** -0.5),
        'gru_wg': _dense(next(ks), (D, 2 * D), s),
        'gru_wc': _dense(next(ks), (D, D), s),
        'out_w': _dense(next(ks), (D, V), s),
        'out_b': jnp.zeros((V,), jnp.float32),
    }


def bf16(w):
    return w.astype(jnp.bfloat16)


def lstm_encoder(x4, wh, b, unroll):
    """x4: [B, T, 4D] bf16 pre-projected gates input (the fc1 output).
    Paddle dynamic_lstm recurrence: gates = x_t + h @ Wh (+ b), f32
    cell."""
    xs = jnp.swapaxes(x4, 0, 1)
    bsz = x4.shape[0]
    h0 = jnp.zeros((bsz, D), jnp.bfloat16)
    c0 = jnp.zeros((bsz, D), jnp.float32)
    wh_b = bf16(wh)

    def step(carry, x_t):
        h, c = carry
        gates = (x_t + h @ wh_b).astype(jnp.float32) + b
        gc, gi, gf, go = jnp.split(gates, 4, axis=1)
        i = jax.nn.sigmoid(gi)
        f = jax.nn.sigmoid(gf)
        c2 = f * c + i * jnp.tanh(gc)
        o = jax.nn.sigmoid(go)
        h2 = (o * jnp.tanh(c2)).astype(jnp.bfloat16)
        return (h2, c2), h2

    (hT, _), hs = jax.lax.scan(step, (h0, c0), xs, unroll=unroll)
    return jnp.swapaxes(hs, 0, 1), hT


def decoder(p, enc_out, enc_proj, boot, trg_emb, unroll):
    """Per-step Bahdanau attention + GRU; returns [B, T, D] states."""
    xs = jnp.swapaxes(trg_emb, 0, 1)  # [T, B, E]
    att_sp, att_v = bf16(p['att_sp']), bf16(p['att_v'])
    dec_in_w = bf16(p['dec_in_w'])
    gru_wg, gru_wc = bf16(p['gru_wg']), bf16(p['gru_wc'])

    def step(h, w_t):
        sp = h @ att_sp  # [B, D]
        e = jnp.tanh((enc_proj + sp[:, None, :]).astype(jnp.float32))
        scores = (e.astype(jnp.bfloat16) @ att_v)[..., 0]  # [B, Ts]
        a = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        ctx = jnp.einsum('bt,btd->bd', a.astype(jnp.bfloat16), enc_out)
        di = jnp.concatenate([ctx, w_t], axis=1) @ dec_in_w  # [B, 3D]
        gates = (di[:, :2 * D] + h @ gru_wg).astype(jnp.float32)
        u, r = jnp.split(jax.nn.sigmoid(gates), 2, axis=1)
        cand = jnp.tanh((di[:, 2 * D:]
                         + (r.astype(jnp.bfloat16) * h) @ gru_wc
                         ).astype(jnp.float32))
        h2 = (u * h.astype(jnp.float32) + (1 - u) * cand
              ).astype(jnp.bfloat16)
        return h2, h2

    _, hs = jax.lax.scan(step, boot, xs, unroll=unroll)
    return jnp.swapaxes(hs, 0, 1)


@jax.custom_vjp
def fused_ce(x, w, b, labels):
    """Sentence-sum / batch-mean CE of (x @ w + b); bwd = p - onehot in
    bf16 (no f32 [B,T,V] round trip)."""
    logits = (x @ bf16(w)).astype(jnp.float32) + b
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, labels[..., None], axis=-1)
    return -jnp.mean(jnp.sum(ll[..., 0], axis=1))


def _fused_ce_fwd(x, w, b, labels):
    logits = (x @ bf16(w)).astype(jnp.float32) + b
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, labels[..., None], axis=-1)
    p = jnp.exp(logits - lse).astype(jnp.bfloat16)
    return -jnp.mean(jnp.sum(ll[..., 0], axis=1)), (x, w, p, labels)


def _fused_ce_bwd(res, g):
    x, w, p, labels = res
    bsz = p.shape[0]
    onehot = jax.nn.one_hot(labels, p.shape[-1], dtype=jnp.bfloat16)
    glog = (p - onehot) * jnp.bfloat16(g / bsz)
    gx = glog @ bf16(w).T
    gw = jnp.einsum('btd,btv->dv', x, glog,
                    preferred_element_type=jnp.float32)
    gb = jnp.sum(glog.astype(jnp.float32), axis=(0, 1))
    return gx, gw, gb, None


fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def forward_loss(p, src, trg, lbl, unroll, ce_impl):
    src_e = bf16(p['src_emb'])[src]
    x4 = jnp.tanh((src_e @ bf16(p['fc1_w'])).astype(jnp.float32)
                  + p['fc1_b']).astype(jnp.bfloat16)
    enc_out, _ = lstm_encoder(x4, p['lstm_wh'], p['lstm_b'], unroll)
    enc_proj = enc_out @ bf16(p['proj_w'])
    boot = jnp.tanh((enc_out[:, -1, :] @ bf16(p['boot_w'])
                     ).astype(jnp.float32) + p['boot_b']
                    ).astype(jnp.bfloat16)
    trg_e = bf16(p['trg_emb'])[trg]
    hs = decoder(p, enc_out, enc_proj, boot, trg_e, unroll)
    if ce_impl == 'fused':
        return fused_ce(hs, p['out_w'], p['out_b'], lbl)
    logits = (hs @ bf16(p['out_w'])).astype(jnp.float32) + p['out_b']
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - lse, lbl[..., None], axis=-1)
    return -jnp.mean(jnp.sum(ll[..., 0], axis=1))


def adam_update(p, m, v, g, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    return p - lr * m / (jnp.sqrt(v) + eps), m, v


def make_step(unroll, ce_impl):
    def train_step(params, m_t, v_t, src, trg, lbl):
        loss, grads = jax.value_and_grad(forward_loss)(
            params, src, trg, lbl, unroll, ce_impl)
        upd = jax.tree.map(
            lambda p, m, v, g: adam_update(p, m, v, g.astype(jnp.float32)),
            params, m_t, v_t, grads)
        new_p = jax.tree.map(lambda t: t[0], upd,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree.map(lambda t: t[1], upd,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_v = jax.tree.map(lambda t: t[2], upd,
                             is_leaf=lambda t: isinstance(t, tuple))
        return new_p, new_m, new_v, loss

    return jax.jit(train_step, donate_argnums=(0, 1, 2))


def build(unroll=1, ce_impl='fused', batch=512, seq=32):
    """Returns (state, timed_block_fn) for same-process gating."""
    dev = jax.devices()[0]
    params = jax.device_put(make_params(jax.random.PRNGKey(0)), dev)
    state = {'p': params,
             'm': jax.device_put(jax.tree.map(jnp.zeros_like, params), dev),
             'v': jax.device_put(jax.tree.map(jnp.zeros_like, params), dev)}
    rng = np.random.RandomState(0)

    def ids():
        return jax.device_put(
            rng.randint(3, V, size=(batch, seq)).astype(np.int32), dev)

    src, trg, lbl = ids(), ids(), ids()
    step = make_step(unroll, ce_impl)
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
    ap.add_argument('--unroll', type=int, default=1)
    ap.add_argument('--ce', default='fused', choices=['fused', 'plain'])
    ap.add_argument('--batch', type=int, default=512)
    ap.add_argument('--seq', type=int, default=32)
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--blocks', type=int, default=3)
    args = ap.parse_args()

    _, timed_block = build(args.unroll, args.ce, args.batch, args.seq)
    per = [timed_block(args.steps) for _ in range(args.blocks)]
    tok = max(per)  # best-of-blocks
    print(json.dumps({
        'bench': 'pure_jax_nmt_bound',
        'unroll': args.unroll, 'ce': args.ce,
        'batch': args.batch, 'seq': args.seq,
        'tokens_per_sec': round(tok, 1),
        'tokens_per_sec_blocks': [round(v, 1) for v in per],
        'mfu': round(tok * FLOPS_PER_TOKEN / peak_flops(), 4),
    }))


if __name__ == '__main__':
    main()
