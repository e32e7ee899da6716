"""The ``causal_conv1d`` kernels (``ops/pallas/causal_conv1d.py``),
interpreted on the CPU through the op (``impl='pallas'``): the output and
the gradients of X, the filter and the bias against the XLA form (the
shifted sums), with and without SiLU, one and two sequences, one and
several channel tiles a program grid, several tiles of positions a
program, K 2 and 4, float32 and AMP; that no position reads a later one
(and no gradient an earlier one) across the kernels' tiles; and the
envelope ``impl='auto'`` picks the kernels in.

Tolerances.  Float32: both sides sum the same f32 products in another
order: 1e-5 of the value's own scale (1e-7 to 1e-6 observed).  Under AMP
X arrives in bf16 from a projection and Y and dX leave in bf16, each side
rounding its own f32 sums: 1e-2 of the value's norm (a bf16 rounding is
at most 0.4% of an element).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import causal_conv1d as pl_conv

F32_TOL = 1e-5
AMP_TOL = 1e-2

# (B, L, C, K): the programs' grid and the tiles of positions each walks
# (``pl_conv.block`` / ``_rows``)
FORMS = {
    # one channel tile, one tile of 48 positions
    'b2_l48_c256_k4': (2, 48, 256, 4),
    # 2 x 3 programs of 512 channels, 16 tiles of 128 positions each
    'b2_l2048_c1536_k2': (2, 2048, 1536, 2),
    # 2 programs of 256 channels, 32 tiles of 128 positions each
    'b1_l4096_c512_k4': (1, 4096, 512, 4),
}


def run_op(form, impl, act, amp=False, seed=0, moved=None, loss_w=None):
    """The op on the CPU place, fed by a projection of a seeded input so
    that under AMP X arrives in bf16; loss = sum(y * w) for a seeded w.
    ``moved``: (position, amount) added to the input there.  Returns
    (y, {'x', 'filter', 'bias', 'proj'}: the gradients, w)."""
    b, length, c, taps = FORMS[form]
    r = np.random.RandomState(seed)
    u = r.standard_normal((b, length, 64)).astype('float32')
    if moved is not None:
        u[:, moved[0]] += moved[1]
    w = r.standard_normal((b, length, c)).astype('float32') \
        if loss_w is None else loss_w
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    layers = fluid.layers
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ud = layers.data('u', [length, 64], dtype='float32')
        x = layers.fc(ud, c, bias_attr=False, num_flatten_dims=2,
                      param_attr=fluid.ParamAttr(name='proj'))
        bound = taps ** -0.5
        y = layers.causal_conv1d(
            x, filter_size=taps, act=act, impl=impl,
            param_attr=fluid.ParamAttr(
                name='cw', initializer=fluid.initializer.Uniform(
                    -bound, bound)),
            bias_attr=fluid.ParamAttr(
                name='cb', initializer=fluid.initializer.Uniform(
                    -bound, bound)))
        wd = layers.data('w', [length, c], dtype='float32')
        append_backward(layers.reduce_sum(layers.elementwise_mul(y, wd)))
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(amp):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={'u': u, 'w': w},
                      fetch_list=[y, x.name + '@GRAD', 'cw@GRAD', 'cb@GRAD',
                                  'proj@GRAD'])
    got = [np.asarray(g, np.float32) for g in got]
    return got[0], dict(zip(('x', 'filter', 'bias', 'proj'), got[1:])), w


def close(got, want, tol, norm=False):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    if norm:
        err, scale = np.linalg.norm(got - want), np.linalg.norm(want)
    else:
        err, scale = np.abs(got - want).max(), np.abs(want).max()
    assert float(err) <= tol * max(float(scale), 1e-30), (
        float(err), float(scale))


@pytest.mark.parametrize('act', [None, 'silu'], ids=['plain', 'silu'])
@pytest.mark.parametrize('form', sorted(FORMS))
def test_kernels_match_the_shifted_sums(form, act):
    """Y and the gradients of X, the filter, the bias (and, through dX,
    the projection's weight): the kernels against the XLA form."""
    out, grads, _ = run_op(form, 'pallas', act)
    want_out, want_grads, _ = run_op(form, 'xla', act)
    close(out, want_out, F32_TOL)
    for name in want_grads:
        close(grads[name], want_grads[name], F32_TOL)


@pytest.mark.parametrize('form', ['b2_l48_c256_k4', 'b1_l4096_c512_k4'])
def test_kernels_match_the_shifted_sums_under_amp(form):
    out, grads, _ = run_op(form, 'pallas', 'silu', amp=True)
    want_out, want_grads, _ = run_op(form, 'xla', 'silu', amp=True)
    close(out, want_out, AMP_TOL, norm=True)
    for name in want_grads:
        close(grads[name], want_grads[name], AMP_TOL, norm=True)


def test_kernels_keep_the_input_dtype():
    """Y and dX in X's dtype (bf16 here), dFilter and dBias f32."""
    x = jnp.ones((1, 32, 128), jnp.bfloat16)
    w, bias = jnp.ones((128, 4), jnp.float32), jnp.ones((128, ), jnp.float32)
    assert pl_conv.causal_conv1d(x, w, bias, 'silu',
                                 interpret=True).dtype == jnp.bfloat16
    dx, dw, db = pl_conv.causal_conv1d_grad(x, w, bias, x, 'silu',
                                            interpret=True)
    assert (dx.dtype, dw.dtype, db.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.float32)
    assert (dw.shape, db.shape) == ((128, 4), (128, ))


def test_kernel_reads_no_later_position():
    """An input moved at position t moves Y at t .. t + K - 1 alone, and
    the loss's weight moved at t moves dX at t - K + 1 .. t alone; t
    starts a tile of 128 positions, so both cross a tile's edge."""
    form, t = 'b2_l2048_c1536_k2', 128
    base, grads, w = run_op(form, 'pallas', 'silu')
    out, _, _ = run_op(form, 'pallas', 'silu', moved=(t, 1.0))
    assert np.array_equal(out[:, :t], base[:, :t])
    assert np.abs(out[:, t:t + 2] - base[:, t:t + 2]).min() > 0
    assert np.array_equal(out[:, t + 2:], base[:, t + 2:])
    w = w.copy()
    w[:, t] += 1.0
    _, moved, _ = run_op(form, 'pallas', 'silu', loss_w=w)
    dx, base_dx = moved['x'], grads['x']
    assert np.array_equal(dx[:, t + 1:], base_dx[:, t + 1:])
    assert np.abs(dx[:, t - 1:t + 1] - base_dx[:, t - 1:t + 1]).min() > 0
    assert np.array_equal(dx[:, :t - 1], base_dx[:, :t - 1])


# ---- what 'auto' picks ---------------------------------------------------

class _Where(object):
    """What a lowering sees of where it runs."""

    def __init__(self, on_cpu=False, mesh=None):
        self.on_cpu, self.mesh = on_cpu, mesh


def _pick(where, shape=(1, 1024, 4352), taps=4):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return ssm_ops._pick('causal_conv1d', {'impl': 'auto'},
                         lambda: ssm_ops._conv_fits(where, x, taps))


def test_auto_takes_the_kernels_at_both_cells_shapes():
    assert _pick(_Where()) == 'pallas'                 # granite_h
    assert _pick(_Where(), (2, 2048, 6144)) == 'pallas'   # nemotron3_nano
    assert pl_conv.block(1024, 4352) == [1024, 256]
    assert pl_conv.block(2048, 6144) == [2048, 512]


@pytest.mark.parametrize('why,kwargs', [
    ('channels_no_whole_lane_tiles', {'shape': (1, 1024, 4350)}),
    ('more_taps_than_the_kernel_takes', {'taps': 9}),
    ('a_length_no_whole_tiles', {'shape': (1, 1000, 4352)}),
    ('a_length_past_the_block', {'shape': (1, 16384, 256)}),
], ids=lambda v: v if isinstance(v, str) else '')
def test_auto_keeps_the_xla_form_outside_the_envelope(why, kwargs):
    assert _pick(_Where(), **kwargs) == 'xla'


def test_auto_keeps_the_xla_form_on_a_cpu_place_and_under_a_mesh():
    assert _pick(_Where(on_cpu=True)) == 'xla'
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ('dp', ))
    assert _pick(_Where(mesh=mesh)) == 'xla'
    # a mesh of one device partitions nothing
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ('dp', ))
    assert _pick(_Where(mesh=one)) == 'pallas'


def test_the_choice_is_recorded_with_its_block():
    form = 'b2_l48_c256_k4'
    run_op(form, 'pallas', 'silu')
    seen = trace.lowering_choices('causal_conv1d', seen=True)[-1]
    assert list(seen.values()) == [{'choice': 'pallas', 'block': [48, 256]}]
    run_op(form, 'auto', 'silu')      # a CPU place
    seen = trace.lowering_choices('causal_conv1d', seen=True)[-1]
    assert list(seen.values()) == [{'choice': 'xla'}]
    assert trace.lowering_choices('causal_conv1d')[-1] == {'xla': 1}
    with pytest.raises(ValueError, match="'auto', 'xla' or 'pallas'"):
        run_op(form, 'fused', 'silu')
