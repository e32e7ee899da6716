"""Fused Pallas LSTM cell kernel vs the lax.scan reference, in interpret
mode on the CPU test mesh (asked for explicitly here; through the op it
follows the place the block is lowered for).  The compiled kernel is
checked on the chip by tools/pallas_chip_check.py."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import lstm as plstm

lstm_fused = functools.partial(plstm.lstm_fused, interpret=True)


def _scan_ref(x, w, bias, h0, c0, mask):
    """The exact recurrence ops/sequence_ops.py:_lstm runs."""
    xs = jnp.swapaxes(x, 0, 1)
    ms = jnp.swapaxes(mask, 0, 1)

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        gates = (x_t + h @ w).astype(jnp.float32) + bias
        gc, gi, gf, go = jnp.split(gates, 4, axis=1)
        i = jax.nn.sigmoid(gi)
        f = jax.nn.sigmoid(gf)
        c_new = f * c + i * jnp.tanh(gc)
        o = jax.nn.sigmoid(go)
        h_new = o * jnp.tanh(c_new)
        m = m_t[:, None]
        h_out = m * h_new + (1 - m) * h
        c_out = m * c_new + (1 - m) * c
        return (h_out, c_out), h_out

    (_, _), hs = jax.lax.scan(step, (h0, c0), (xs, ms))
    return jnp.swapaxes(hs, 0, 1)


def _inputs(b, t, d, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.standard_normal((b, t, 4 * d)) * 0.3, jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, 4 * d)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((1, 4 * d)) * 0.1, jnp.float32)
    h0 = jnp.zeros((b, d), jnp.float32)
    c0 = jnp.zeros((b, d), jnp.float32)
    lengths = rng.randint(1, t + 1, size=(b, ))
    mask = jnp.asarray(
        (np.arange(t)[None, :] < lengths[:, None]).astype(np.float32))
    return x, w, bias, h0, c0, mask


@pytest.mark.parametrize('b,t,d', [(8, 12, 128), (16, 5, 256)])
def test_fused_forward_matches_scan(b, t, d):
    x, w, bias, h0, c0, mask = _inputs(b, t, d)
    ref = _scan_ref(x, w, bias, h0, c0, mask)
    out = lstm_fused(x, w, bias, h0, c0, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_gradients_match_scan():
    x, w, bias, h0, c0, mask = _inputs(8, 10, 128, seed=1)

    def loss_ref(x, w, bias, h0, c0):
        return jnp.sum(_scan_ref(x, w, bias, h0, c0, mask)**2)

    def loss_pal(x, w, bias, h0, c0):
        return jnp.sum(lstm_fused(x, w, bias, h0, c0, mask=mask)**2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, w, bias, h0, c0)
    gp = jax.grad(loss_pal, argnums=(0, 1, 2, 3, 4))(x, w, bias, h0, c0)
    for name, a, b in zip(['dx', 'dw', 'db', 'dh0', 'dc0'], gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_batch_blocked_path():
    """b > the VMEM batch tile exercises the 2-D (batch, time) grid."""
    x, w, bias, h0, c0, mask = _inputs(512, 3, 128, seed=2)
    ref = _scan_ref(x, w, bias, h0, c0, mask)
    out = lstm_fused(x, w, bias, h0, c0, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('is_reverse', [False, True])
def test_lowering_fused_matches_scan(is_reverse):
    """The lstm op lowering itself: FLAGS_fused_lstm='always' engages
    the kernel in interpret mode on CPU, so the integration glue (bias
    fallback, is_reverse flip/flip-back, output wiring, masking from the
    LoD side-band) is exercised end-to-end against the scan path."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags

    def run():
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            xin = fluid.layers.data(name='x', shape=[1024],
                                    dtype='float32', lod_level=1)
            proj = fluid.layers.fc(input=xin, size=512)
            h, c = fluid.layers.dynamic_lstm(input=proj, size=512,
                                             use_peepholes=False,
                                             is_reverse=is_reverse)
            out = fluid.layers.mean(h) + fluid.layers.mean(c)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        rng = np.random.RandomState(3)
        rows = [rng.standard_normal((n, 1024)).astype('float32')
                for n in (7, 4, 6, 3)]
        feed = {'x': fluid.create_lod_tensor(
            np.concatenate(rows), [[len(r) for r in rows]])}
        with fluid.scope_guard(scope):
            exe.run(startup)
            return exe.run(main, feed=feed, fetch_list=[out])[0]

    base = run()
    old = flags.FLAGS.fused_lstm
    flags.FLAGS.fused_lstm = 'always'
    try:
        fused = run()
    finally:
        flags.FLAGS.fused_lstm = old
    np.testing.assert_allclose(fused, base, rtol=1e-5, atol=1e-6)


def test_fused_lstm_flag_rejects_typos():
    from paddle_tpu.fluid import flags
    with pytest.raises(ValueError):
        flags.FLAGS.fused_lstm = 'off'
    assert flags.FLAGS.fused_lstm == 'auto'
