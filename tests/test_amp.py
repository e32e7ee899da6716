"""Mixed-precision (bf16 compute / fp32 master weights) tests.

Reference-era analog: paddle/contrib/float16/float16_transpiler.py
(inference-only fp16); here AMP is a trace-time training mode."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid


def _build_convnet():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[3, 16, 16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        c = fluid.layers.conv2d(x, num_filters=8, filter_size=3,
                                act='relu')
        pred = fluid.layers.fc(c, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return prog, startup, loss


def _data():
    rng = np.random.RandomState(0)
    xv = rng.standard_normal((16, 3, 16, 16)).astype('float32')
    yv = (np.arange(16) % 4).astype('int64')[:, None]
    return xv, yv


def test_amp_training_converges():
    prog, startup, loss = _build_convnet()
    xv, yv = _data()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        with fluid.amp_guard():
            losses = []
            for _ in range(20):
                lv, = exe.run(prog, feed={'x': xv, 'y': yv},
                              fetch_list=[loss])
                losses.append(float(np.asarray(lv).flatten()[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5


def test_amp_close_to_fp32_and_guard_restores():
    # forward-only program: same weights in ONE scope, amp off vs on
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[3, 16, 16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        c = fluid.layers.conv2d(x, num_filters=8, filter_size=3,
                                act='relu')
        pred = fluid.layers.fc(c, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
    xv, yv = _data()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        assert not fluid.amp.amp_enabled()
        l_fp32, = exe.run(prog, feed={'x': xv, 'y': yv},
                          fetch_list=[loss])
        with fluid.amp_guard():
            l_amp, = exe.run(prog, feed={'x': xv, 'y': yv},
                             fetch_list=[loss])
        assert not fluid.amp.amp_enabled()  # guard restored
    # identical weights: bf16 rounding shifts the loss by well under 2%
    np.testing.assert_allclose(
        float(np.asarray(l_amp).flatten()[0]),
        float(np.asarray(l_fp32).flatten()[0]), rtol=2e-2)


def test_amp_master_weights_stay_fp32():
    prog, startup, loss = _build_convnet()
    xv, yv = _data()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with fluid.amp_guard():
            exe.run(prog, feed={'x': xv, 'y': yv}, fetch_list=[loss])
        for p in prog.global_block().all_parameters():
            arr = np.asarray(scope.find_var(p.name).value())
            assert arr.dtype == np.float32, (p.name, arr.dtype)


def test_amp_loss_parity_with_fp32_training():
    """VERDICT Weak #9 guard: a full bf16-AMP training run must land at an
    fp32-comparable loss (not just a finite one) — the check that AMP
    throughput didn't buy a silent quality regression."""

    def train(amp):
        import contextlib
        prog, startup, loss = _build_convnet()
        prog.random_seed = 5
        xv, yv = _data()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.core.Scope()):
            exe.run(startup)
            guard = fluid.amp_guard() if amp else contextlib.nullcontext()
            with guard:
                for _ in range(30):
                    lv, = exe.run(prog, feed={'x': xv, 'y': yv},
                                  fetch_list=[loss])
        return float(np.asarray(lv).flatten()[0])

    l_fp32 = train(amp=False)
    l_amp = train(amp=True)
    # both optimized the same schedule; bf16 rounding noise only
    assert l_amp < 1.0, (l_amp, l_fp32)  # genuinely trained (start ~1.39)
    assert abs(l_amp - l_fp32) < 0.15, (l_amp, l_fp32)


def test_amp_lstm_training_loss_parity():
    """The AMP recurrence policy (bf16 sequence/hidden state, f32 gate
    math, f32 LSTM cell carry) must track fp32 training — an all-bf16
    cell accumulator would drift across time steps."""
    import contextlib
    from helpers import lod_feed

    def train(amp):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            words = fluid.layers.data('words', [1], dtype='int64',
                                      lod_level=1)
            label = fluid.layers.data('label', [1], dtype='int64')
            emb = fluid.layers.embedding(input=words, size=[50, 16])
            proj = fluid.layers.fc(input=emb, size=32 * 4)
            h, _ = fluid.layers.dynamic_lstm(input=proj, size=32 * 4)
            last = fluid.layers.sequence_last_step(input=h)
            pred = fluid.layers.fc(input=last, size=2, act='softmax')
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(pred, label))
            fluid.optimizer.Adam(learning_rate=0.02).minimize(loss)
        startup.random_seed = 3
        rng = np.random.RandomState(0)
        rows = [rng.randint(0, 50, (l, 1)).tolist()
                for l in (7, 12, 5, 9, 11, 6, 8, 10)]
        feed = {'words': lod_feed(rows, 'int64'),
                'label': rng.randint(0, 2, (8, 1)).astype('int64')}
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.core.Scope()):
            exe.run(startup)
            guard = fluid.amp_guard() if amp else contextlib.nullcontext()
            with guard:
                for _ in range(20):
                    lv, = exe.run(main, feed=feed, fetch_list=[loss])
        return float(np.asarray(lv).flatten()[0])

    l_fp32 = train(False)
    l_amp = train(True)
    assert l_fp32 < 0.3, l_fp32  # overfits the fixed batch
    assert abs(l_amp - l_fp32) < 0.1, (l_amp, l_fp32)


def test_fused_bf16_ce_matches_f32_path():
    """The AMP hard-label fused CE (custom VJP, ops/loss_ops.py
    _fused_ce_bf16): loss, Softmax output, and parameter gradients must
    match the f32 composition within bf16 tolerance, including
    ignore_index rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import loss_ops

    rng = np.random.RandomState(11)
    n, v = 24, 96
    logits = rng.standard_normal((n, v)).astype('float32') * 3
    idx = rng.randint(0, v, (n, )).astype('int32')
    idx[:4] = -100    # ignored rows

    loss_bf, p_bf = loss_ops._fused_ce_bf16(
        jnp.asarray(logits, jnp.bfloat16), jnp.asarray(idx), -100)
    lf = jnp.asarray(logits, jnp.bfloat16).astype(jnp.float32)
    log_p = jax.nn.log_softmax(lf, axis=-1)
    want_p = jnp.exp(log_p)
    safe = np.where(idx == -100, 0, idx)
    want_loss = -np.take_along_axis(np.asarray(log_p), safe[:, None], 1)
    want_loss[idx == -100] = 0.0
    np.testing.assert_allclose(np.asarray(loss_bf, np.float32),
                               want_loss, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(p_bf, np.float32),
                               np.asarray(want_p), rtol=2e-2, atol=2e-2)

    # gradient: d loss / d logits == (p - onehot) masked, in bf16
    def total(lg):
        l, _ = loss_ops._fused_ce_bf16(lg, jnp.asarray(idx), -100)
        return jnp.sum(l)

    g = jax.grad(total)(jnp.asarray(logits, jnp.bfloat16))
    onehot = np.zeros((n, v), np.float32)
    onehot[np.arange(n), safe] = 1.0
    want_g = (np.asarray(want_p) - onehot)
    want_g[idx == -100] = 0.0
    assert g.dtype == jnp.bfloat16   # lands bf16 for the matmul consumer
    np.testing.assert_allclose(np.asarray(g, np.float32), want_g,
                               rtol=2e-2, atol=2e-2)


class _Slots(object):
    """The two calls a loss lowering makes on its context."""

    def __init__(self, **ins):
        self.ins, self.outs = ins, {}

    def get(self, op, slot):
        return self.ins.get(slot)

    def set(self, op, slot, value):
        self.outs[slot] = value


class _Attrs(object):
    def __init__(self, **attrs):
        self.attrs = attrs


def _ce_pick_cases():
    """Each hard-label path of ops/loss_ops.py beside the formula it
    replaced, which gathered the label's term from a widened or derived
    [N, V] tensor (PR 27): name -> (new, old)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import loss_ops

    def lowered(lowering, x_slot, out_slot, dtype):
        def run(x, idx, ignore):
            ctx = _Slots(**{x_slot: x.astype(dtype), 'Label': idx[:, None]})
            lowering(ctx, _Attrs(ignore_index=ignore))
            return ctx.outs[out_slot]
        return run

    def old_bf16(x, idx, ignore):
        lf = x.astype(jnp.bfloat16).astype(jnp.float32)
        z = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
        safe = jnp.where(idx != ignore, idx, 0)
        picked = jnp.take_along_axis(lf, safe[:, None], axis=-1)
        return jnp.where((idx != ignore)[:, None], z - picked, 0.0)

    def old_f32(x, idx, ignore):
        log_p = jax.nn.log_softmax(x, axis=-1)
        loss = -jnp.take_along_axis(log_p, idx[:, None], axis=-1)
        return jnp.where(idx[:, None] == ignore, 0.0, loss)

    def old_probabilities(x, idx, ignore):
        xf = x.astype(jnp.bfloat16).astype(jnp.float32)
        picked = jnp.take_along_axis(xf, idx[:, None], axis=-1)
        loss = -jnp.log(jnp.maximum(picked, 1e-12))
        return jnp.where(idx[:, None] == ignore, 0.0, loss)

    swce = loss_ops._softmax_with_cross_entropy
    return {
        'bf16_hard_label': (lowered(swce, 'Logits', 'Loss', jnp.bfloat16),
                            old_bf16),
        'f32_hard_label': (lowered(swce, 'Logits', 'Loss', jnp.float32),
                           old_f32),
        'cross_entropy': (lowered(loss_ops._cross_entropy, 'X', 'Y',
                                  jnp.bfloat16), old_probabilities),
    }


@pytest.mark.parametrize('path', ['bf16_hard_label', 'f32_hard_label',
                                  'cross_entropy'])
@pytest.mark.parametrize('jitted', [False, True])
def test_label_pick_equals_the_gather_from_the_widened_tensor(path, jitted):
    """The hard label's term is gathered from the operand as it stands
    and the picked values are widened; before PR 27 it was gathered from
    an f32 copy of the whole [N, V] operand (or from log_p, a new [N, V]
    tensor), which XLA had to write to HBM for the gather.  Same
    numbers, bit for bit, rows with ``ignore_index`` included: bf16 ->
    f32 is exact, and the f32 path sends the picked logits through
    ``log_softmax``'s own arithmetic, ``(x - max) - log(sum)``."""
    import jax
    import jax.numpy as jnp
    new, old = _ce_pick_cases()[path]
    rng = np.random.RandomState(27)
    n, v = 48, 300
    x = rng.standard_normal((n, v)).astype('float32') * 4
    if path == 'cross_entropy':
        x = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
    idx = rng.randint(0, v, (n, )).astype('int32')
    idx[::7] = -100
    if jitted:
        new, old = (jax.jit(f, static_argnums=2) for f in (new, old))
    got = np.asarray(new(jnp.asarray(x), jnp.asarray(idx), -100))
    want = np.asarray(old(jnp.asarray(x), jnp.asarray(idx), -100))
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape == (n, 1)
    assert (got[::7] == 0).all() and (got[1::7] > 0).all()
    np.testing.assert_array_equal(got, want)


def test_fused_bf16_ce_gradient_is_bitwise_the_old_formula():
    """The custom VJP's residuals and backward did not change with the
    pick (PR 27): d loss / d logits is ((p - onehot) * g) in bf16 with p
    the bf16 softmax, bit for bit."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import loss_ops
    rng = np.random.RandomState(28)
    n, v = 48, 300
    logits = jnp.asarray(rng.standard_normal((n, v)) * 4, jnp.bfloat16)
    idx = rng.randint(0, v, (n, )).astype('int32')
    idx[::7] = -100
    weight = jnp.asarray(rng.uniform(0.5, 2.0, (n, 1)), jnp.float32)

    def total(lg):
        loss, _ = loss_ops._fused_ce_bf16(lg, jnp.asarray(idx), -100)
        return jnp.sum(loss * weight)

    got = jax.grad(total)(logits)
    lf = logits.astype(jnp.float32)
    z = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
    p = jnp.exp(lf - z).astype(jnp.bfloat16)
    valid = (idx != -100)[:, None]
    onehot = jax.nn.one_hot(np.where(idx == -100, 0, idx), v,
                            dtype=jnp.float32)
    want = ((p.astype(jnp.float32) - onehot)
            * jnp.where(valid, weight, 0.0)).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
