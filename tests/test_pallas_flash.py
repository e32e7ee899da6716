"""Pallas flash-attention kernel vs the dense reference, in interpret mode
on the CPU test mesh — asked for EXPLICITLY here; through the op it is
chosen from the place the block is lowered for, and only for a CPU
place.  The compiled kernel is checked on the chip by
tools/pallas_chip_check.py."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash_attention as pl_fa
from paddle_tpu.parallel.context_parallel import dense_attention

flash_attention = functools.partial(pl_fa.flash_attention, interpret=True)

B, L, H, D = 2, 48, 4, 16


def _qkv(seed=0, l=L):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.standard_normal((B, l, H, D)).astype('float32')
    return mk(), mk(), mk()


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('with_lens', [False, True])
def test_flash_matches_dense(causal, with_lens):
    q, k, v = _qkv()
    lens = np.array([40, 13], np.int32) if with_lens else None
    ref = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, seq_lengths=lens)
    out = flash_attention(q, k, v, causal=causal, seq_lengths=lens,
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(1)
    lens = np.array([48, 20], np.int32)

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=True, seq_lengths=lens,
                                block_q=16, block_k=16)**2).sum()

    def ld(q, k, v):
        return (dense_attention(q, k, v, causal=True,
                                seq_lengths=lens)**2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g2 = jax.grad(ld, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_cross_attention_and_padding():
    # Lq != Lk and lengths not multiples of the block size (padding path)
    rng = np.random.RandomState(3)
    q = rng.standard_normal((B, 24, H, D)).astype('float32')
    k = rng.standard_normal((B, 50, H, D)).astype('float32')
    v = rng.standard_normal((B, 50, H, D)).astype('float32')
    lens = np.array([50, 17], np.int32)
    ref = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          seq_lengths=lens)
    out = flash_attention(q, k, v, seq_lengths=lens, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_program_level_pallas_impl():
    """flash_attention layer with impl='pallas' runs through the Executor."""
    import paddle_tpu.fluid as fluid
    import paddle_tpu.fluid.layers as layers
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[L, H * D], dtype='float32')
        proj = layers.fc(x, H * D, num_flatten_dims=2)
        out = layers.flash_attention(proj, proj, proj, num_heads=H,
                                     causal=True, impl='pallas')
        loss = layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        vals = []
        for _ in range(2):
            xv = rng.standard_normal((B, L, H * D)).astype('float32')
            lv, = exe.run(main, feed={'x': xv}, fetch_list=[loss])
            vals.append(float(np.asarray(lv).flatten()[0]))
    assert all(np.isfinite(vals)), vals


@pytest.mark.parametrize('impl', ['dense', 'pallas'])
def test_flash_attention_amp_matches_fp32(impl):
    """Under AMP the attention inputs cast to bf16 at the op boundary,
    but softmax statistics stay f32 on every impl — the result must
    track the fp32 path within bf16-matmul tolerance.  The pallas case
    runs the kernel in interpret mode on CPU."""
    import paddle_tpu.fluid as fluid

    rng = np.random.RandomState(0)
    B, L, H, D = 2, 64, 2, 16
    qkv = rng.standard_normal((3, B, L, H * D)).astype('float32')

    def run(amp):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data('q', [L, H * D], dtype='float32')
            k = fluid.layers.data('k', [L, H * D], dtype='float32')
            v = fluid.layers.data('v', [L, H * D], dtype='float32')
            out = fluid.layers.flash_attention(q, k, v, num_heads=H,
                                               causal=True, impl=impl)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(amp):
            exe.run(startup)
            o, = exe.run(main, feed={'q': qkv[0], 'k': qkv[1],
                                     'v': qkv[2]}, fetch_list=[out])
        return np.asarray(o, np.float32)

    full = run(False)
    mixed = run(True)
    # bf16 inputs: ~2-3 decimal digits; f32 stats keep the error bounded
    np.testing.assert_allclose(mixed, full, rtol=5e-2, atol=5e-2)
    assert np.max(np.abs(mixed - full)) < 0.05


class _Ctx(object):
    """The slice of LoweringContext _pick_impl reads."""

    def __init__(self, on_cpu):
        self.on_cpu = on_cpu
        self.mesh = None


class _Op(object):
    def __init__(self, impl):
        self.attrs = {'impl': impl}


def _abstract(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_auto_picks_the_kernel_only_inside_its_compiled_envelope():
    """'auto' selects the Pallas kernel for an accelerator place, past
    the dense score budget, and ONLY up to the row length the kernel
    has compiled for on the chip; never for a CPU place."""
    pick = attention_ops._pick_impl
    big = _abstract((128, 2048, 8, 64))        # 8 GiB of bf16 scores
    small = _abstract((2, 256, 8, 64))
    longer = _abstract((64, 4096, 8, 64))      # K/V rows past the envelope
    assert pick(_Ctx(False), _Op('auto'), big, big, big) == 'pallas'
    assert pick(_Ctx(False), _Op('auto'), small, small, small) == 'dense'
    assert pick(_Ctx(False), _Op('auto'), longer, longer, longer) == 'dense'
    assert pick(_Ctx(True), _Op('auto'), big, big, big) == 'dense'
    wide_v = _abstract((128, 2048, 8, 128))
    assert pick(_Ctx(False), _Op('auto'), big, big, wide_v) == 'dense'


def test_explicit_pallas_that_cannot_be_honoured_raises():
    """impl='pallas' with Dv != Dq used to warn and run dense."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data('q', [L, H, D], dtype='float32')
        v = fluid.layers.data('v', [L, H, 2 * D], dtype='float32')
        out = fluid.layers.flash_attention(q, q, v, impl='pallas')
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(fluid.core.Scope()):
        with pytest.raises(ValueError, match="impl='pallas'"):
            exe.run(main, feed={
                'q': rng.standard_normal((B, L, H, D)).astype('float32'),
                'v': rng.standard_normal((B, L, H, 2 * D)).astype('float32'),
            }, fetch_list=[out])


def test_interpret_mode_follows_the_place_not_the_backend(monkeypatch):
    """The lowering passes interpret=ctx.on_cpu: True for the CPUPlace
    this suite lowers for; a TPUPlace context gives False whatever the
    ambient backend is (here: CPU-only)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import registry
    assert registry.LoweringContext(None, {}, place=fluid.CPUPlace()).on_cpu
    assert not registry.LoweringContext(
        None, {}, place=fluid.TPUPlace()).on_cpu
    seen = []
    real = pl_fa.flash_attention

    def spy(*args, **kwargs):
        seen.append(kwargs['interpret'])
        return real(*args, **kwargs)

    monkeypatch.setattr(pl_fa, 'flash_attention', spy)
    test_program_level_pallas_impl()
    assert seen and all(v is True for v in seen)
