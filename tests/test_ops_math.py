"""Per-op tests for math/elementwise/reduce ops via the OpTest harness
(reference pattern: tests/unittests/test_elementwise_add_op.py etc.)."""

import numpy as np
import pytest

from op_test import OpTest

RNG = np.random.RandomState(7)


def _t(op_type, inputs, outputs, attrs=None):
    t = OpTest()
    t.op_type = op_type
    t.inputs = inputs
    t.outputs = outputs
    t.attrs = attrs or {}
    return t


class TestElementwiseAdd:
    def test_same_shape(self):
        x = RNG.uniform(0.1, 1, (3, 4)).astype('float32')
        y = RNG.uniform(0.1, 1, (3, 4)).astype('float32')
        t = _t('elementwise_add', {'X': x, 'Y': y}, {'Out': x + y})
        t.check_output()
        t.check_grad(['X', 'Y'])

    def test_broadcast_axis(self):
        # reference broadcast: Y's dims align to X starting at `axis`
        x = RNG.uniform(0.1, 1, (2, 3, 4)).astype('float32')
        y = RNG.uniform(0.1, 1, (3, )).astype('float32')
        out = x + y.reshape(1, 3, 1)
        t = _t('elementwise_add', {'X': x, 'Y': y}, {'Out': out},
               {'axis': 1})
        t.check_output()
        t.check_grad(['X', 'Y'])


class TestElementwiseOthers:
    def test_sub(self):
        x = RNG.uniform(0.1, 1, (3, 4)).astype('float32')
        y = RNG.uniform(0.1, 1, (3, 4)).astype('float32')
        _t('elementwise_sub', {'X': x, 'Y': y}, {'Out': x - y}) \
            .check_output()

    def test_mul_broadcast(self):
        x = RNG.uniform(0.1, 1, (2, 3, 4)).astype('float32')
        y = RNG.uniform(0.5, 1, (2, 3)).astype('float32')
        out = x * y.reshape(2, 3, 1)
        t = _t('elementwise_mul', {'X': x, 'Y': y}, {'Out': out},
               {'axis': 0})
        t.check_output()
        t.check_grad(['X', 'Y'])

    def test_div(self):
        x = RNG.uniform(0.5, 1, (3, 4)).astype('float32')
        y = RNG.uniform(0.5, 1, (3, 4)).astype('float32')
        t = _t('elementwise_div', {'X': x, 'Y': y}, {'Out': x / y})
        t.check_output()
        t.check_grad(['X', 'Y'], max_relative_error=2e-2)

    def test_max_min_pow(self):
        x = RNG.uniform(0.5, 1.5, (3, 4)).astype('float32')
        y = RNG.uniform(0.5, 1.5, (3, 4)).astype('float32')
        _t('elementwise_max', {'X': x, 'Y': y},
           {'Out': np.maximum(x, y)}).check_output()
        _t('elementwise_min', {'X': x, 'Y': y},
           {'Out': np.minimum(x, y)}).check_output()
        _t('elementwise_pow', {'X': x, 'Y': y},
           {'Out': np.power(x, y)}).check_output()


class TestMulMatmul:
    def test_mul(self):
        x = RNG.uniform(-1, 1, (4, 5)).astype('float32')
        y = RNG.uniform(-1, 1, (5, 3)).astype('float32')
        t = _t('mul', {'X': x, 'Y': y}, {'Out': x.dot(y)},
               {'x_num_col_dims': 1, 'y_num_col_dims': 1})
        t.check_output()
        t.check_grad(['X', 'Y'])

    def test_matmul_transpose(self):
        x = RNG.uniform(-1, 1, (3, 5)).astype('float32')
        y = RNG.uniform(-1, 1, (4, 5)).astype('float32')
        t = _t('matmul', {'X': x, 'Y': y}, {'Out': x.dot(y.T)},
               {'transpose_X': False, 'transpose_Y': True})
        t.check_output()
        t.check_grad(['X', 'Y'])

    def test_matmul_batched(self):
        x = RNG.uniform(-1, 1, (2, 3, 5)).astype('float32')
        y = RNG.uniform(-1, 1, (2, 5, 4)).astype('float32')
        _t('matmul', {'X': x, 'Y': y}, {'Out': np.matmul(x, y)},
           {'transpose_X': False, 'transpose_Y': False}).check_output()


# mul with an X of more than two axes: (X's shape, its LoD or None, Y's
# shape, x_num_col_dims).  The first three contract X's last axis alone,
# whose gradient contracts the output gradient as it lies (PR 37); the
# LoD input is padded to [B, T, K] at run time, a rank above its desc's;
# 'flatten' contracts two axes of X and keeps the 2-D rows.
MUL_ND_CASES = {
    '3d': ((2, 3, 4), None, (4, 5), 2),
    '4d': ((2, 3, 2, 4), None, (4, 5), 3),
    'lod_padded': ((5, 4), [[0, 2, 5]], (4, 3), 1),
    'flatten': ((2, 3, 4), None, (12, 5), 1),
}


def _packed(value, lod):
    """A fetched LoD value's rows, packed: the executor pads a LoD tensor
    to [sequences, bucket, ...]."""
    value = np.asarray(value)
    if lod is None:
        return value
    offsets = lod[0]
    return np.concatenate([value[i, :offsets[i + 1] - offsets[i]]
                           for i in range(len(offsets) - 1)])


@pytest.mark.parametrize('amp', [False, True], ids=['f32', 'amp'])
@pytest.mark.parametrize('case', list(MUL_ND_CASES))
def test_mul_nd(case, amp):
    """Out, dX and dY of ``sum(W * mul(X, Y))`` against the flatten-reshape
    NumPy product; under AMP the products are bf16 and each gradient comes
    back in its operand's dtype, as the generic VJP gives it."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.backward import append_backward
    x_shape, lod, y_shape, xn = MUL_ND_CASES[case]
    rng = np.random.RandomState(37)
    x = rng.uniform(-1, 1, x_shape).astype('float32')
    y = rng.uniform(-1, 1, y_shape).astype('float32')
    k, n = y_shape
    ref = x.reshape(-1, k).dot(y).reshape(x_shape[:xn] + (n, ))
    w = rng.uniform(0.5, 1.5, ref.shape).astype('float32')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if lod is None:
            xv = fluid.layers.data('X', list(x_shape),
                                   append_batch_size=False)
            wv = fluid.layers.data('W', list(ref.shape),
                                   append_batch_size=False)
        else:
            xv = fluid.layers.data('X', list(x_shape[1:]), lod_level=1)
            wv = fluid.layers.data('W', list(ref.shape[1:]), lod_level=1)
        yv = fluid.layers.data('Y', list(y_shape), append_batch_size=False)
        xv.stop_gradient = yv.stop_gradient = False
        block = main.global_block()
        out = block.create_var(name='Out', shape=(-1, ) + ref.shape[1:],
                               dtype='float32', lod_level=xv.lod_level)
        block.append_op(type='mul', inputs={'X': ['X'], 'Y': ['Y']},
                        outputs={'Out': ['Out']},
                        attrs={'x_num_col_dims': xn, 'y_num_col_dims': 1})
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, wv))
        append_backward(loss)
    feed = {'X': x, 'Y': y, 'W': w}
    if lod is not None:
        for name in ('X', 'W'):
            feed[name] = core.LoDTensor(feed[name])
            feed[name].set_lod(lod)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(core.Scope()), fluid.amp_guard(amp):
        exe.run(startup)
        got_out, got_dx, got_dy = exe.run(
            main, feed=feed, fetch_list=['Out', 'X@GRAD', 'Y@GRAD'])
    # under AMP the operands, and dOut (W times a bf16 Out), are bf16
    tol = dict(rtol=2e-2, atol=5e-2) if amp else dict(rtol=1e-5, atol=1e-5)
    w2 = w.reshape(-1, n)
    np.testing.assert_allclose(
        _packed(got_out, lod).astype(np.float32), ref, **tol)
    np.testing.assert_allclose(
        _packed(got_dx, lod), w2.dot(y.T).reshape(x_shape), **tol)
    np.testing.assert_allclose(
        np.asarray(got_dy), x.reshape(-1, k).T.dot(w2), **tol)
    assert np.asarray(got_dx).dtype == np.float32
    assert np.asarray(got_dy).dtype == np.float32


def test_mul_grad_contracts_the_output_gradient_as_it_lies():
    """The VJP of ``mul`` on a [B, T, H] input reshapes no output gradient:
    both gradient products read it as [B, T, N] (a flatten to rows made XLA
    copy NMT's 983 MB loss gradient into another layout, PR 37)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import registry
    B, T, H, N = 4, 3, 8, 16
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        fluid.layers.data('x', [B, T, H], append_batch_size=False)
        fluid.layers.data('w', [H, N], append_batch_size=False)
        block = main.global_block()
        block.create_var(name='out', shape=(B, T, N), dtype='float32')
        op = block.append_op(type='mul', inputs={'X': ['x'], 'Y': ['w']},
                             outputs={'Out': ['out']},
                             attrs={'x_num_col_dims': 2,
                                    'y_num_col_dims': 1})

    def mul(x, w):
        env = {'x': x, 'w': w}
        registry.get_lowering('mul')(registry.LoweringContext(block, env), op)
        return env['out']

    x = np.ones((B, T, H), np.float32)
    w = np.ones((H, N), np.float32)
    for amp in (False, True):
        with fluid.amp_guard(amp):
            out, vjp = jax.vjp(mul, x, w)
            closed = jax.make_jaxpr(vjp)(np.ones(out.shape, out.dtype))
        g = closed.jaxpr.invars[0]
        assert g.aval.shape == (B, T, N)
        # no reshape: only the two products read it (or a cast to their dtype)
        readers = {e.primitive.name for e in closed.jaxpr.eqns
                   if g in e.invars}
        assert 'dot_general' in readers and readers <= {
            'dot_general', 'convert_element_type'}, closed


class TestReduce:
    def test_reduce_sum_dim(self):
        x = RNG.uniform(-1, 1, (3, 4, 5)).astype('float32')
        t = _t('reduce_sum', {'X': x}, {'Out': x.sum(axis=1)},
               {'dim': [1], 'keep_dim': False, 'reduce_all': False})
        t.check_output()
        t.check_grad(['X'])

    def test_reduce_mean_keepdim(self):
        x = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        t = _t('reduce_mean', {'X': x},
               {'Out': x.mean(axis=0, keepdims=True)},
               {'dim': [0], 'keep_dim': True, 'reduce_all': False})
        t.check_output()
        t.check_grad(['X'])

    def test_reduce_max_all(self):
        x = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        _t('reduce_max', {'X': x}, {'Out': np.asarray(x.max())},
           {'reduce_all': True, 'keep_dim': False}).check_output()

    def test_mean(self):
        x = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        t = _t('mean', {'X': x}, {'Out': np.asarray(x.mean())})
        t.check_output()
        t.check_grad(['X'])

    def test_sum_of_list(self):
        a = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        b = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        c = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        _t('sum', {'X': [('a', a), ('b', b), ('c', c)]},
           {'Out': a + b + c}).check_output()


class TestActivations:
    def _check(self, op_type, fn, lo=-1.0, hi=1.0, grad=True, attrs=None,
               tol=1e-2):
        x = RNG.uniform(lo, hi, (3, 4)).astype('float32')
        t = _t(op_type, {'X': x}, {'Out': fn(x)}, attrs)
        t.check_output()
        if grad:
            t.check_grad(['X'], max_relative_error=tol)

    def test_relu(self):
        self._check('relu', lambda x: np.maximum(x, 0), grad=False)

    def test_sigmoid(self):
        self._check('sigmoid', lambda x: 1 / (1 + np.exp(-x)))

    def test_tanh(self):
        self._check('tanh', np.tanh)

    def test_exp_log_sqrt(self):
        self._check('exp', np.exp)
        self._check('log', np.log, lo=0.2, hi=2.0)
        self._check('sqrt', np.sqrt, lo=0.2, hi=2.0)

    def test_square_abs_reciprocal(self):
        self._check('square', np.square)
        self._check('abs', np.abs, grad=False)
        self._check('reciprocal', lambda x: 1 / x, lo=0.5, hi=1.5)

    def test_softplus_softsign(self):
        self._check('softplus', lambda x: np.log1p(np.exp(x)))
        self._check('softsign', lambda x: x / (1 + np.abs(x)))

    def test_leaky_relu_elu(self):
        self._check('leaky_relu', lambda x: np.where(x > 0, x, 0.02 * x),
                    grad=False, attrs={'alpha': 0.02})
        self._check('elu',
                    lambda x: np.where(x > 0, x, 1.0 * (np.exp(x) - 1)),
                    grad=False, attrs={'alpha': 1.0})

    def test_pow_scale(self):
        self._check('pow', lambda x: np.power(x, 2.0), lo=0.2, hi=1.5,
                    attrs={'factor': 2.0})
        self._check('scale', lambda x: 3.0 * x + 0.0,
                    attrs={'scale': 3.0, 'bias': 0.0,
                           'bias_after_scale': True})


class TestSoftmaxAndLosses:
    def test_softmax(self):
        x = RNG.uniform(-2, 2, (4, 7)).astype('float32')
        e = np.exp(x - x.max(-1, keepdims=True))
        t = _t('softmax', {'X': x}, {'Out': e / e.sum(-1, keepdims=True)})
        t.check_output()
        t.check_grad(['X'])

    def test_softmax_with_cross_entropy(self):
        logits = RNG.uniform(-2, 2, (5, 7)).astype('float32')
        label = RNG.randint(0, 7, (5, 1)).astype('int64')
        e = np.exp(logits - logits.max(-1, keepdims=True))
        softmax = e / e.sum(-1, keepdims=True)
        loss = -np.log(softmax[np.arange(5), label.ravel()])[:, None]
        t = _t('softmax_with_cross_entropy',
               {'Logits': logits, 'Label': label},
               {'Softmax': softmax, 'Loss': loss.astype('float32')})
        t.check_output()
        t.check_grad(['Logits'], output_names=['Loss'])

    def test_cross_entropy(self):
        probs = RNG.uniform(0.05, 1, (4, 6)).astype('float32')
        probs /= probs.sum(-1, keepdims=True)
        label = RNG.randint(0, 6, (4, 1)).astype('int64')
        loss = -np.log(probs[np.arange(4), label.ravel()])[:, None]
        t = _t('cross_entropy', {'X': probs, 'Label': label},
               {'Y': loss.astype('float32')})
        t.check_output()
        t.check_grad(['X'], output_names=['Y'], max_relative_error=2e-2)

    def test_sigmoid_ce_logits(self):
        x = RNG.uniform(-2, 2, (4, 5)).astype('float32')
        lbl = RNG.randint(0, 2, (4, 5)).astype('float32')
        ref = np.maximum(x, 0) - x * lbl + np.log1p(np.exp(-np.abs(x)))
        t = _t('sigmoid_cross_entropy_with_logits',
               {'X': x, 'Label': lbl}, {'Out': ref})
        t.check_output()
        t.check_grad(['X'])

    def test_huber_loss(self):
        x = RNG.uniform(-1, 1, (5, 1)).astype('float32')
        y = RNG.uniform(-1, 1, (5, 1)).astype('float32')
        d = 0.5
        r = y - x
        loss = np.where(np.abs(r) <= d, 0.5 * r * r, d * (np.abs(r) - 0.5 * d))
        t = _t('huber_loss', {'X': x, 'Y': y},
               {'Out': loss.astype('float32'), 'Residual': r},
               {'delta': d})
        t.check_output()

    def test_squared_l2_norm_and_distance(self):
        x = RNG.uniform(-1, 1, (3, 4)).astype('float32')
        _t('squared_l2_norm', {'X': x},
           {'Out': np.asarray((x * x).sum())}).check_output()


class TestClipCast:
    def test_clip(self):
        x = RNG.uniform(-2, 2, (3, 4)).astype('float32')
        t = _t('clip', {'X': x}, {'Out': np.clip(x, -0.5, 0.5)},
               {'min': -0.5, 'max': 0.5})
        t.check_output()

    def test_clip_by_norm(self):
        x = RNG.uniform(-2, 2, (3, 4)).astype('float32')
        norm = np.sqrt((x * x).sum())
        ref = x * (1.0 / max(norm, 1.0)) if norm > 1.0 else x
        _t('clip_by_norm', {'X': x}, {'Out': ref.astype('float32')},
           {'max_norm': 1.0}).check_output()

    def test_cast(self):
        x = RNG.uniform(-2, 2, (3, 4)).astype('float32')
        _t('cast', {'X': x}, {'Out': x.astype('int32')},
           {'in_dtype': 5, 'out_dtype': 2}).check_output()


def test_softmax_with_ce_softmax_output_is_intermediate_both_paths():
    """ADVICE r4 #1: the reference op treats Softmax as an Intermediate
    output (its grad kernel never consumes a Softmax cotangent).  The
    bf16 fast path can't see one by construction; the f32 path must
    stop_gradient it so AMP on/off agree: a loss built on the Softmax
    output contributes NOTHING to dLogits on either path."""
    import paddle_tpu.fluid as fluid

    def logits_grad(amp):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            x = fluid.layers.data('x', [8])
            label = fluid.layers.data('label', [1], dtype='int64')
            logits = fluid.layers.fc(x, 8, bias_attr=False,
                                     param_attr=fluid.ParamAttr(
                                         name='w_ce_int'))
            loss_ce = fluid.layers.softmax_with_cross_entropy(
                logits, label)
            # build an extra loss ON the Softmax output: must be inert
            helper_out = prog.global_block().ops[-1].output('Softmax')[0]
            soft_var = prog.global_block().var(helper_out)
            extra = fluid.layers.mean(soft_var)
            total = fluid.layers.elementwise_add(
                fluid.layers.mean(loss_ce),
                fluid.layers.scale(extra, scale=100.0))
            fluid.backward.append_backward(total)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        rng = np.random.RandomState(0)
        with fluid.scope_guard(scope), fluid.amp_guard(amp):
            exe.run(startup)
            g, = exe.run(prog, feed={
                'x': rng.standard_normal((4, 8)).astype('float32'),
                'label': rng.randint(0, 8, (4, 1)).astype('int64')},
                fetch_list=['w_ce_int@GRAD'])
        return np.asarray(g, dtype=np.float32)

    g_f32 = logits_grad(False)
    g_amp = logits_grad(True)
    # the x100-scaled softmax-mean loss must not leak into the grads on
    # EITHER path; remaining difference is bf16 rounding only
    assert np.abs(g_f32 - g_amp).max() < 0.05, (g_f32, g_amp)
    assert np.abs(g_f32).max() < 5.0  # CE-scale, not 100x-softmax scale
