"""The grouped ``gated_rms_norm`` kernels (``ops/pallas/gated_rms_norm.py``),
interpreted on the CPU: against today's XLA form (``ssm_ops._rms_norm``
and its ``jax.vjp``) called directly, and through the op
(``impl='pallas'`` against ``impl='xla'``, the generic gradient), Y, dX,
dGate and dScale, float32 and AMP; nemotron's grouping (8 x 512) at a
small row count and over several programs, 2 x 128 over several tiles
of one program; and the envelope ``impl='auto'`` picks the kernels in.

Tolerances.  Float32: both sides sum the same f32 products in another
order and the kernels' sigmoid is the tanh form: 1e-5 of the value's own
scale.  Bf16 inputs and AMP: each side rounds its own f32 result to bf16
(a rounding is at most 0.4% of an element): 1e-2 of the value's norm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import gated_rms_norm as pl_norm

F32_TOL = 1e-5
AMP_TOL = 1e-2
EPS = 1e-5

# (B, L, C, G): the programs' grid (``pl_norm.block``) and the tiles of
# 16 rows each walks
FORMS = {
    # nemotron's grouping, one program of two tiles
    'b1_l32_c4096_g8': (1, 32, 4096, 8),
    # nemotron's grouping, two programs of 128 rows
    'b2_l128_c4096_g8': (2, 128, 4096, 8),
    # two groups of 128 lanes, one program of six tiles
    'b2_l48_c256_g2': (2, 48, 256, 2),
}


def close(got, want, tol, norm=False):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    if norm:
        err, scale = np.linalg.norm(got - want), np.linalg.norm(want)
    else:
        err, scale = np.abs(got - want).max(), np.abs(want).max()
    assert float(err) <= tol * max(float(scale), 1e-30), (
        float(err), float(scale))


def _arrays(form, dtype, seed=0):
    b, length, c, _ = FORMS[form]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x, z, dy = (jax.random.normal(k, (b, length, c)).astype(dtype)
                for k in keys[:3])
    w = 1.0 + 0.1 * jax.random.normal(keys[3], (c, ))
    return x, z, w, dy


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('form', sorted(FORMS))
def test_kernels_match_rms_norm_and_its_vjp(form, dtype):
    """Y, dX, dGate and dScale of the kernels against ``_rms_norm`` and
    ``jax.vjp`` of it on the same arrays (Y and dY in the inputs' dtype,
    as under AMP)."""
    groups = FORMS[form][3]
    x, z, w, dy = _arrays(form, dtype)
    want, vjp = jax.vjp(lambda x, z, w: ssm_ops._rms_norm(
        x, w, EPS, gate=z, groups=groups).astype(dtype), x, z, w)
    got = pl_norm.gated_rms_norm(x, z, w, EPS, groups, dtype,
                                 interpret=True)
    tol, norm = (F32_TOL, False) if dtype == 'float32' else (AMP_TOL, True)
    close(got, want, tol, norm)
    grads = pl_norm.gated_rms_norm_grad(x, z, w, dy, EPS, groups,
                                        interpret=True)
    for g, ref in zip(grads, vjp(dy)):
        close(g, ref, tol, norm)


def run_op(form, impl, amp=False, seed=0):
    """The gated norm on the CPU place, X and the gate two projections of
    a seeded input (bf16 under AMP); loss = sum(y * w) for a seeded w.
    Returns (y, {'x', 'gate', 'scale', 'proj_x', 'proj_z'}: gradients)."""
    b, length, c, groups = FORMS[form]
    r = np.random.RandomState(seed)
    u = r.standard_normal((b, length, 64)).astype('float32')
    w = r.standard_normal((b, length, c)).astype('float32')
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    layers = fluid.layers
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ud = layers.data('u', [length, 64], dtype='float32')
        x, z = (layers.fc(ud, c, bias_attr=False, num_flatten_dims=2,
                          param_attr=fluid.ParamAttr(name=name))
                for name in ('proj_x', 'proj_z'))
        y = layers.rms_norm(
            x, gate=z, groups=groups, impl=impl,
            param_attr=fluid.ParamAttr(
                name='scale', initializer=fluid.initializer.Uniform(
                    0.5, 1.5)))
        wd = layers.data('w', [length, c], dtype='float32')
        append_backward(layers.reduce_sum(layers.elementwise_mul(y, wd)))
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(amp):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={'u': u, 'w': w},
                      fetch_list=[y, x.name + '@GRAD', z.name + '@GRAD',
                                  'scale@GRAD', 'proj_x@GRAD',
                                  'proj_z@GRAD'])
    got = [np.asarray(g, np.float32) for g in got]
    return got[0], dict(zip(('x', 'gate', 'scale', 'proj_x', 'proj_z'),
                            got[1:]))


@pytest.mark.parametrize('amp', [False, True], ids=['f32', 'amp'])
@pytest.mark.parametrize('form', ['b1_l32_c4096_g8', 'b2_l48_c256_g2'])
def test_op_kernels_match_the_xla_form(form, amp):
    """Through the op and the executor: the kernels' Y and every gradient
    (and, through dX and dGate, the projections' weights) against the XLA
    form and the generic gradient."""
    tol, norm = (AMP_TOL, True) if amp else (F32_TOL, False)
    out, grads = run_op(form, 'pallas', amp)
    want_out, want_grads = run_op(form, 'xla', amp)
    close(out, want_out, tol, norm)
    for name in want_grads:
        close(grads[name], want_grads[name], tol, norm)


def test_kernels_keep_the_dtypes():
    """Y in the dtype asked for, dX and dGate in their inputs' dtypes,
    dScale f32 of the weight's shape."""
    x = jnp.ones((1, 16, 256), jnp.bfloat16)
    z = jnp.ones((1, 16, 256), jnp.float32)
    w = jnp.ones((256, ), jnp.float32)
    assert pl_norm.gated_rms_norm(x, z, w, EPS, 2, jnp.bfloat16,
                                  interpret=True).dtype == jnp.bfloat16
    dx, dz, dw = pl_norm.gated_rms_norm_grad(x, z, w, x, EPS, 2,
                                             interpret=True)
    assert (dx.dtype, dz.dtype, dw.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.float32)
    assert (dx.shape, dz.shape, dw.shape) == (x.shape, z.shape, (256, ))


# ---- what 'auto' picks ---------------------------------------------------

class _Where(object):
    """What a lowering sees of where it runs."""

    def __init__(self, on_cpu=False, mesh=None):
        self.on_cpu, self.mesh = on_cpu, mesh


def _pick(where, shape=(2, 2048, 4096), groups=8, gate=True):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return ssm_ops._pick('gated_rms_norm', {'impl': 'auto'},
                         lambda: ssm_ops._norm_fits(
                             where, x, x if gate else None, groups))


def test_auto_takes_the_kernels_at_nemotrons_shape():
    assert _pick(_Where()) == 'pallas'
    assert pl_norm.block(4096, 4096) == 128


@pytest.mark.parametrize('why,kwargs', [
    ('one_group', {'groups': 1}),
    ('no_gate', {'gate': False}),
    ('groups_no_whole_lane_tiles', {'groups': 64}),
    ('rows_no_whole_tiles', {'shape': (1, 1000, 4096)}),
    ('channels_past_the_block', {'shape': (1, 64, 65536)}),
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
    form = 'b2_l48_c256_g2'
    run_op(form, 'pallas')
    seen = trace.lowering_choices('gated_rms_norm', seen=True)[-1]
    assert list(seen.values()) == [{'choice': 'pallas', 'block': [96, 256]}]
    run_op(form, 'auto')      # a CPU place
    seen = trace.lowering_choices('gated_rms_norm', seen=True)[-1]
    assert list(seen.values()) == [{'choice': 'xla'}]
    assert trace.lowering_choices('gated_rms_norm')[-1] == {'xla': 1}
    with pytest.raises(ValueError, match="'auto', 'xla' or 'pallas'"):
        run_op(form, 'fused')
