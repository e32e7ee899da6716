"""Pallas fused-attention kernel vs the dense path and a plain f32
softmax attention, in interpret mode on the CPU test mesh — asked for
EXPLICITLY here; through the op it is chosen from the place the block is
lowered for, and only for a CPU place.  The compiled kernel is checked on
the chip by tools/pallas_chip_check.py, and compiled for the v5e without
one by tests/test_pallas_tpu_compile.py."""

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


# ---- parity at the cells' shape class ----------------------------------
#
# Tolerances, as max |got - want| over max |want| (one number a tensor,
# so a gradient's small entries are judged on the tensor's scale):
#
# * against the plain f32 reference fed the same bf16-rounded inputs:
#   2e-2.  Both bf16 paths round P (or e^(s-m)) and dS to bf16 before
#   their products (half an ulp: 2^-9 = 2.0e-3 an element, averaged down
#   by the f32 accumulation over 64..384 terms) and round the result to
#   bf16 once more (2^-9 of its own size, up to 2^-8 of the tensor's
#   largest): 4e-3..8e-3 is what both read here; 2e-2 leaves room for
#   other seeds and is far under a wrong mask or a dropped scale (>1e-1).
# * against ``dense_attention`` (same precision, another order of
#   rounding: it normalises P before narrowing, the kernel after P.V):
#   2e-2, for the same reason.
# * "the same precision as the dense path, no narrower": the kernel's
#   error against f32 may not pass twice the dense path's own, plus 2e-3.
HK, DK = 8, 64
FORMS = {
    # name: (Lq, Lk, causal)
    'self': (256, 256, False),
    'causal_self': (256, 256, True),
    'cross': (128, 384, False),     # two K blocks, Lq != Lk
}
TOL_F32 = TOL_DENSE = 2e-2


def _plain_f32_attention(q, k, v, causal, lens):
    """softmax(Q K^T / sqrt(D) + mask) V in f32 ``jax.numpy``, nothing
    shared with either path under test; a row with no valid column is 0."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(q.shape[-1])
    ok = jnp.ones(s.shape, bool)
    if causal:
        ok = ok & (jnp.arange(q.shape[1])[:, None]
                   >= jnp.arange(k.shape[1])[None, :])
    if lens is not None:
        ok = ok & (jnp.arange(k.shape[1])[None, None, None, :]
                   < jnp.asarray(lens)[:, None, None, None])
    s = jnp.where(ok, s, -1e30)
    e = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize('ragged', [False, True],
                         ids=['full', 'lens_and_masked_row'])
@pytest.mark.parametrize('form', sorted(FORMS))
def test_kernel_matches_dense_and_f32_at_the_cells_shape(form, ragged):
    """bf16 in, H=8 x D=64, L=256: the output and all three gradients
    against ``dense_attention`` and against plain f32 attention; the
    ragged case has a short row and a row with no valid column."""
    lq, lk, causal = FORMS[form]
    b = 3 if ragged else 2
    lens = np.array([lk, 100, 0], np.int32) if ragged else None
    rng = np.random.RandomState(11)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, HK, DK)),
                              jnp.bfloat16) for l in (lq, lk, lk, lq))

    def outputs(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out, ) + grads

    got = outputs(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, seq_lengths=lens))
    dense = outputs(lambda q, k, v: dense_attention(
        q, k, v, causal=causal, seq_lengths=lens))
    want = outputs(lambda q, k, v: _plain_f32_attention(
        q, k, v, causal, lens))
    for name, g, d, r in zip(('out', 'dq', 'dk', 'dv'), got, dense, want):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape, name
        err, dense_err = _rel(g, r), _rel(d, r)
        assert err <= TOL_F32, (name, err)
        assert _rel(g, d) <= TOL_DENSE, (name, _rel(g, d))
        assert err <= 2 * dense_err + 2e-3, (name, err, dense_err)
    if ragged:      # the all-masked row gives zeros, and takes no gradient
        assert not np.asarray(got[0][2], np.float32).any()
        assert not np.asarray(got[1][2], np.float32).any()


def test_causal_blocks_above_the_diagonal_are_skipped_not_wrong():
    """L=512 is two blocks a side: the forward visits 3 of the 4 tiles,
    the backward too; the result is the dense one."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 512, 2, DK)),
                           jnp.float32) for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    got = jax.grad(loss(flash_attention), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_grad_from_the_residual_is_the_custom_vjp():
    """``flash_attention_grad`` (what the op's grad lowering calls with
    the forward's log-sum-exp) gives what ``jax.grad`` gives."""
    rng = np.random.RandomState(2)
    q, k, v, do = (jnp.asarray(rng.standard_normal((2, 48, H, D)),
                               jnp.float32) for _ in range(4))
    kw = dict(causal=True, seq_lengths=np.array([48, 9], np.int32),
              interpret=True)
    out, lse = pl_fa.flash_attention(q, k, v, return_residual=True, **kw)
    got = pl_fa.flash_attention_grad(q, k, v, out, lse, do, **kw)
    _, vjp = jax.vjp(lambda q, k, v: pl_fa.flash_attention(q, k, v, **kw),
                     q, k, v)
    for a, b in zip(got, vjp(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ---- differentiable wherever the op is lowered --------------------------

def _attention_in_a_sub_block(kind, impl):
    """T steps of fc -> flash_attention accumulated through a StaticRNN
    (``recurrent``: jax.checkpoint over a scan) or a bounded While: the
    sub-block's gradient is the generic ``jax.vjp`` of its lowering, so
    the attention op inside is differentiated by JAX, not by the op's
    own grad lowering."""
    import paddle_tpu.fluid as fluid
    layers = fluid.layers
    steps = 3
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data('x', shape=[steps, B, L, H * D], dtype='float32',
                        append_batch_size=False)

        def attend(x_t):
            h = layers.fc(x_t, H * D, num_flatten_dims=2, bias_attr=False,
                          param_attr=fluid.ParamAttr(name='step_w'))
            return layers.flash_attention(h, h, h, num_heads=H,
                                          causal=True, impl=impl)

        if kind == 'static_rnn':
            rnn = layers.StaticRNN()
            with rnn.step():
                x_t = rnn.step_input(x)
                mem = rnn.memory(shape=[L, H * D], batch_ref=x_t,
                                 init_value=0.0, ref_batch_dim_idx=0)
                acc = layers.elementwise_add(mem, attend(x_t))
                rnn.update_memory(mem, acc)
                rnn.output(acc)
            total = layers.slice(rnn(), axes=[0], starts=[steps - 1],
                                 ends=[steps])
        else:
            # the loop's state feeds the next step's attention
            x_0 = layers.reshape(layers.slice(
                x, axes=[0], starts=[0], ends=[1]), [B, L, H * D])
            i = layers.fill_constant(shape=[1], dtype='int64', value=0)
            mems = layers.array_write(x_0, i=i)
            n = layers.fill_constant(shape=[1], dtype='int64', value=steps)
            cond = layers.less_than(x=i, y=n)
            loop = layers.While(cond=cond, max_trip_count=steps)
            with loop.block():
                prev = layers.array_read(array=mems, i=i)
                acc = layers.elementwise_add(
                    prev, attend(layers.elementwise_add(x_0, prev)))
                layers.increment(x=i, value=1.0, in_place=True)
                layers.array_write(acc, i=i, array=mems)
                layers.less_than(x=i, y=n, cond=cond)
            total = layers.array_read(array=mems, i=i)
        loss = layers.mean(layers.elementwise_mul(total, total))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rng = np.random.RandomState(5)
    feed = {'x': rng.standard_normal(
        (steps, B, L, H * D)).astype('float32')}
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.asarray(v) for _ in range(2) for v in exe.run(
            main, feed=feed, fetch_list=[loss, 'step_w@GRAD'])]


@pytest.mark.parametrize('kind', ['static_rnn', 'bounded_while'])
def test_the_kernel_trains_inside_a_sub_block(kind):
    """impl='pallas' inside a ``recurrent`` or a bounded ``while``
    sub-block: ``jax.vjp`` goes through the forward lowering there, which
    has to be the custom_vjp function and not the bare ``pallas_call``
    (no JVP rule with scalar prefetch, no transpose).  Two SGD steps give
    the losses and the weight's gradients that dense attention gives."""
    got = _attention_in_a_sub_block(kind, 'pallas')
    want = _attention_in_a_sub_block(kind, 'dense')
    assert np.abs(want[1]).max() > 0 and want[2] < want[0]
    # f32 through three chained steps, summed in another order: as
    # test_flash_gradients_match_dense, on the value's own scale
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-4 * np.abs(b).max())


def test_generic_grad_of_a_fused_forward_lowered_in_another_trace():
    """The op's grad lowering finds no ``@FLASH_LSE`` in its trace (the
    forward was lowered elsewhere) and takes the generic ``jax.vjp`` of
    the forward lowering: the same gradients as from the residual."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.backward import append_backward
    from paddle_tpu.ops import registry
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q, k, v = (fluid.layers.data(n, [L, H, D], dtype='float32')
                   for n in 'qkv')
        for var in (q, k, v):
            var.stop_gradient = False
        out = fluid.layers.flash_attention(q, k, v, causal=True,
                                           impl='pallas')
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
        append_backward(loss)
    block = main.global_block()
    feed = dict(zip('qkv', map(jnp.asarray, _qkv(seed=4))))
    lse_name = out.name + attention_ops._LSE_SUFFIX

    def grads(keep_residual):
        env = dict(feed)
        ctx = registry.LoweringContext(block, env, place=fluid.CPUPlace())
        for op in block.ops:
            if op.type == 'flash_attention_grad':
                assert lse_name in env
                if not keep_residual:
                    del env[lse_name]
            registry.run_op(ctx, op)
        return [np.asarray(env[n + '@GRAD']) for n in 'qkv']

    want = grads(True)
    assert all(np.abs(g).max() > 0 for g in want)
    for a, b in zip(grads(False), want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ---- what 'auto' picks --------------------------------------------------

class _Mesh(object):
    """The slice of jax.sharding.Mesh the choice reads."""

    def __init__(self, **axes):
        self.shape = axes
        self.axis_names = tuple(axes)


class _Ctx(object):
    """The slice of LoweringContext _pick_impl reads."""

    def __init__(self, on_cpu, mesh=None):
        self.on_cpu = on_cpu
        self.mesh = mesh
        self.batch_axis = 'dp'


class _Op(object):
    def __init__(self, impl):
        self.attrs = {'impl': impl}


def _abstract(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


CELL = _abstract(128, 256, 8, 64)
TPU, CPU = _Ctx(False), _Ctx(True)
# (case, ctx, impl attr, q, k, v, lowered to)
PICKS = [
    ('the cells shape on an accelerator place', TPU, 'auto',
     CELL, CELL, CELL, 'pallas'),
    ('a CPU place', CPU, 'auto', CELL, CELL, CELL, 'dense'),
    ('an sp mesh', _Ctx(False, _Mesh(dp=2, sp=4)), 'auto',
     CELL, CELL, CELL, 'ring'),
    ('Dq != Dv', TPU, 'auto', CELL, CELL, _abstract(128, 256, 8, 128),
     'dense'),
    ('the served decoder step, Lq=1', TPU, 'auto',
     _abstract(128, 1, 8, 64), CELL, CELL, 'dense'),
    ('the old small case, 2 x 256 x 8 x 64: L decides, not B', TPU, 'auto',
     _abstract(2, 256, 8, 64), _abstract(2, 256, 8, 64),
     _abstract(2, 256, 8, 64), 'pallas'),
    ('the shortest measured win, L=128', TPU, 'auto',
     _abstract(256, 128, 8, 64), _abstract(256, 128, 8, 64),
     _abstract(256, 128, 8, 64), 'pallas'),
    ('under one 128-row tile, L=64: a tie on the chip', TPU, 'auto',
     _abstract(512, 64, 8, 64), _abstract(512, 64, 8, 64),
     _abstract(512, 64, 8, 64), 'dense'),
    ('short memory under long queries, Lk=64', TPU, 'auto',
     CELL, _abstract(128, 64, 8, 64), _abstract(128, 64, 8, 64),
     'dense'),
    ('the envelope longest row, L=2048', TPU, 'auto',
     _abstract(16, 2048, 8, 64), _abstract(16, 2048, 8, 64),
     _abstract(16, 2048, 8, 64), 'pallas'),
    ('past the VMEM envelope, L=4096', TPU, 'auto',
     _abstract(8, 4096, 8, 64), _abstract(8, 4096, 8, 64),
     _abstract(8, 4096, 8, 64), 'dense'),
    ('a long query row against one block of memory, Lq 2048 x Lk 256',
     TPU, 'auto', _abstract(16, 2048, 8, 64), _abstract(16, 256, 8, 64),
     _abstract(16, 256, 8, 64), 'pallas'),
    ('a query row past the VMEM envelope, Lq 4096 x Lk 256', TPU, 'auto',
     _abstract(8, 4096, 8, 64), _abstract(8, 256, 8, 64),
     _abstract(8, 256, 8, 64), 'dense'),
    ('D=128 from Lk = 2 D on, L=256', TPU, 'auto',
     *[_abstract(128, 256, 4, 128)] * 3, 'pallas'),
    ('D=128 at Lk = D, L=128: dense won on the chip', TPU, 'auto',
     *[_abstract(256, 128, 4, 128)] * 3, 'dense'),
    ('D=32, four heads a lane group, L=128', TPU, 'auto',
     *[_abstract(256, 128, 16, 32)] * 3, 'pallas'),
    ('a head width that was not measured, D=16', TPU, 'auto',
     *[_abstract(128, 256, 32, 16)] * 3, 'dense'),
    ('a head width that was not measured, D=256', TPU, 'auto',
     *[_abstract(128, 512, 2, 256)] * 3, 'dense'),
    ('heads that do not tile 128 lanes, 3 x 64', TPU, 'auto',
     *[_abstract(128, 256, 3, 64)] * 3, 'dense'),
    ('a dp mesh: shard_map takes the batch axis',
     _Ctx(False, _Mesh(dp=4)), 'auto',
     _abstract(512, 256, 8, 64), _abstract(512, 256, 8, 64),
     _abstract(512, 256, 8, 64), 'pallas'),
    ('a batch the dp axis does not divide', _Ctx(False, _Mesh(dp=4)),
     'auto', _abstract(6, 256, 8, 64), _abstract(6, 256, 8, 64),
     _abstract(6, 256, 8, 64), 'dense'),
    ('a tp axis the kernel is not sharded over',
     _Ctx(False, _Mesh(dp=2, tp=2)), 'auto', CELL, CELL, CELL, 'dense'),
    ('asked for: pallas', CPU, 'pallas', CELL, CELL, CELL, 'pallas'),
    ('asked for: dense', TPU, 'dense', CELL, CELL, CELL, 'dense'),
]


@pytest.mark.parametrize('case', PICKS, ids=[c[0] for c in PICKS])
def test_pick_impl_from_place_mesh_and_shapes(case):
    """Shapes, place and mesh in; the implementation out.  The rule is
    the crossover measured on the v5e (PERF.md), not a memory budget."""
    _, ctx, impl, q, k, v, want = case
    assert attention_ops._pick_impl(ctx, _Op(impl), q, k, v) == want


def test_ring_asked_for_without_an_sp_axis_warns_and_runs_dense():
    with pytest.warns(UserWarning, match='falling back to dense'):
        assert attention_ops._pick_impl(
            TPU, _Op('ring'), CELL, CELL, CELL) == 'dense'


# ---- across chips -------------------------------------------------------

def _attention_model(impl):
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[L, H * D], dtype='float32')
        proj = fluid.layers.fc(
            x, H * D, num_flatten_dims=2,
            param_attr=fluid.ParamAttr(name='proj_w'))
        out = fluid.layers.flash_attention(proj, proj, proj, num_heads=H,
                                           causal=True, impl=impl)
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
        fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    return main, startup, loss


def test_dp_mesh_runs_the_kernel_on_each_chips_rows():
    """impl='pallas' (interpreted) under ParallelExecutor(mesh={'dp': 4})
    trains as one device does, and its compiled step gathers nothing: the
    kernel sits in a shard_map over the batch axis, so Q, K and V stay
    where GSPMD put them."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import prepare_feed_arrays
    from paddle_tpu.parallel import make_mesh
    rng = np.random.RandomState(7)
    xs = [rng.standard_normal((8, L, H * D)).astype('float32')
          for _ in range(3)]

    def train(parallel):
        main, startup, loss = _attention_model('pallas')
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            if not parallel:
                return [float(np.asarray(exe.run(
                    main, feed={'x': x}, fetch_list=[loss])[0]).ravel()[0])
                    for x in xs], None
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, scope=scope,
                mesh=make_mesh({'dp': 4}, jax.devices()[:4]))
            losses = [float(np.asarray(pe.run(
                [loss.name], feed={'x': x})[0]).ravel()[0]) for x in xs]
            compiled, = pe._cache.values()
            args = compiled._stage_state(
                scope, prepare_feed_arrays({'x': xs[0]}))
            hlo = compiled._jit.lower(
                *args, pe._next_rng()).compile().as_text()
            return losses, hlo

    one, _ = train(False)
    four, hlo = train(True)
    np.testing.assert_allclose(four, one, rtol=1e-5, atol=1e-6)
    assert 'all-reduce' in hlo      # the gradient's, as before
    assert 'all-gather' not in hlo


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
