"""LSTM recurrence kernel lab (VERDICT r2 next-#2).

Times the exact recurrence the `lstm` op lowering runs (paddle_tpu/ops/
sequence_ops.py:_lstm — bf16 x/h, f32 gates+cell, mask-free fast case)
forward+backward, under variants:

  scan          lax.scan, the shipped lowering
  unroll<K>     lax.scan(unroll=K) — XLA fuses K cells per iteration
  pallas        fused Pallas cell kernel (if present in ops/pallas)

Configs: the reference stacked-LSTM operating points.
Prints one JSON line per (config, variant): tokens/sec of ONE lstm
layer step (fwd+bwd+sgd-less; grads wrt x, w, and the pre-projection
consumer pattern), plus ms/step.

Run on the chip: chiprun -- python tools/lstm_kernel_lab.py
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def make_step(t, unroll):
    def lstm_layer(x, w, bias, h0, c0):
        cd = x.dtype
        w_r = w.astype(cd)
        xs = jnp.swapaxes(x, 0, 1)  # [T, B, 4D]

        def step(carry, x_t):
            h, c = carry
            gates = (x_t + h @ w_r).astype(jnp.float32) + bias
            gc, gi, gf, go = jnp.split(gates, 4, axis=1)
            i = jax.nn.sigmoid(gi)
            f = jax.nn.sigmoid(gf)
            c_new = f * c + i * jnp.tanh(gc)
            o = jax.nn.sigmoid(go)
            h_new = (o * jnp.tanh(c_new)).astype(cd)
            return (h_new, c_new), h_new

        (_, _), hs = jax.lax.scan(step, (h0, c0), xs, unroll=unroll)
        return jnp.swapaxes(hs, 0, 1)

    def loss_fn(x, w, bias, h0, c0):
        hs = lstm_layer(x, w, bias, h0, c0)
        return jnp.sum(hs.astype(jnp.float32) ** 2)

    @jax.jit
    def train(x, w, bias, h0, c0):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            x, w, bias, h0, c0)
        return loss, grads

    return train


def bench_variant(b, t, d, variant, steps=30):
    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((b, t, 4 * d)) * 0.1, jnp.bfloat16),
        dev)
    w = jax.device_put(
        jnp.asarray(rng.standard_normal((d, 4 * d)) * 0.05, jnp.float32), dev)
    bias = jax.device_put(jnp.zeros((1, 4 * d), jnp.float32), dev)
    h0 = jax.device_put(jnp.zeros((b, d), jnp.bfloat16), dev)
    c0 = jax.device_put(jnp.zeros((b, d), jnp.float32), dev)

    if variant == 'pallas':
        from paddle_tpu.ops.pallas import lstm as plstm

        def loss_fn(x, w, bias, h0, c0):
            hs = plstm.lstm_fused(x, w, bias, h0, c0)
            return jnp.sum(hs.astype(jnp.float32) ** 2)

        train = jax.jit(lambda *a: jax.value_and_grad(
            loss_fn, argnums=(0, 1))(*a))
    else:
        unroll = 1 if variant == 'scan' else int(variant.replace('unroll', ''))
        train = make_step(t, unroll)

    # device-true timing: batch `steps` train steps inside ONE dispatch via
    # fori_loop, so the per-dispatch host cost is paid once per block
    def body(_, carry):
        x, w, loss0 = carry
        loss, (gx, gw) = train(x, w, bias, h0, c0)
        # consume the grads so nothing is dead code; keeps x/w live-varying
        return (x + 0.0 * gx, w - 0.0 * gw, loss)

    @jax.jit
    def run_n(x, w):
        return jax.lax.fori_loop(0, steps, body, (x, w, jnp.float32(0)))

    _, _, loss = run_n(x, w)
    float(loss)
    t0 = time.time()
    _, _, loss = run_n(x, w)
    float(loss)
    el = time.time() - t0
    return {
        'config': 'B%d_T%d_D%d' % (b, t, d),
        'variant': variant,
        'ms_per_step': round(el / steps * 1000, 3),
        'tokens_per_sec': round(b * t * steps / el, 1),
    }


def main():
    variants = ['scan', 'unroll4', 'unroll8', 'unroll16', 'unroll32']
    try:
        from paddle_tpu.ops.pallas import lstm  # noqa: F401
        variants.append('pallas')
    except ImportError:
        pass
    # both regimes: D=128 (reference stacked-LSTM width — pallas loses,
    # the scan wins) and D=512 (NMT encoder width — pallas wins +14-15%)
    for (b, t, d) in [(128, 64, 128), (512, 64, 128),
                      (128, 64, 512), (512, 64, 512)]:
        for v in variants:
            print(json.dumps(bench_variant(b, t, d, v)))


if __name__ == '__main__':
    main()
