"""The fused ``ssd_scan`` kernels (``ops/pallas/ssd_scan.py``), interpreted
on the CPU, at the two cells' head width (64) and state size (128) with
short sequences: the output, the saved states and all seven gradients
against the XLA lowering AND against the step-by-step recurrence; and the
envelope ``impl='auto'`` picks the kernel in.

Tolerances.  Float32: both sides differ in the order of sums and in where
the decay's exponent is taken (a difference of running sums in a chunk, a
running product in the recurrence): 2e-4 of the value's own scale, where
1e-5 to 6e-5 is observed at these widths.  Under AMP the products take
bf16 operands on the program's side: 3% of the value's norm against the
float32 recurrence (0.3-0.8% observed), and against the XLA lowering,
which rounds the same operands.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.models.reference import granite_hybrid_ref as ref
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import ssd_scan as pl_ssd

P, N = 64, 128
F32_TOL = 2e-4
AMP_TOL = 3e-2

# (heads, groups, chunk): one program a chunk and one group (granite's
# form, cut to one 8-head block), eight groups of one block (nemotron's)
FORMS = {'one_group_chunk_256': (8, 1, 256),
         'eight_groups_chunk_128': (64, 8, 128)}
SLOTS = ('x', 'dt', 'a_log', 'bm', 'cm', 'd', 'dt_bias')


def feeds_of(heads, groups, length, batch=1, seed=1):
    r = np.random.RandomState(seed)
    f = lambda *s: r.standard_normal(s).astype('float32')   # noqa: E731
    return {'x': f(batch, length, heads, P),
            'dt': f(batch, length, heads) - 2.0,
            'a_log': np.log(r.uniform(1, 8, heads)).astype('float32'),
            'bm': f(batch, length, groups, N) * 0.3,
            'cm': f(batch, length, groups, N) * 0.3,
            'd': f(heads), 'dt_bias': f(heads)}


def run_op(feeds, chunk, impl, amp=False, seed=0):
    """The op on the CPU place (the kernel interpreted); loss = sum(y * w)
    for a seeded w.  (y, {feed: d loss / d feed}, w)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        block, data = main.global_block(), {}
        for name, value in feeds.items():
            data[name] = block.create_var(name=name, shape=value.shape,
                                          dtype=value.dtype, is_data=True)
            data[name].stop_gradient = False
        layers = fluid.layers
        out = layers.ssd_scan(
            data['x'], data['dt'],
            layers.scale(layers.exp(data['a_log']), scale=-1.0),
            data['bm'], data['cm'], data['d'], data['dt_bias'],
            chunk=chunk, impl=impl)
        w = np.random.RandomState(seed).standard_normal(
            out.shape).astype('float32')
        wv = block.create_var(name='loss_w', shape=w.shape, dtype=w.dtype,
                              is_data=True)
        append_backward(layers.reduce_sum(layers.elementwise_mul(out, wv)))
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(amp):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=dict(feeds, loss_w=w),
                      fetch_list=[out] + [n + '@GRAD' for n in feeds])
    return np.asarray(got[0]), dict(zip(feeds, map(np.asarray, got[1:]))), w


def recurrence(feeds, w):
    def fn(x, dt, a_log, bm, cm, d, dt_bias):
        return ref.ssm_recurrence(x, jax.nn.softplus(dt + dt_bias),
                                  -jnp.exp(a_log), bm, cm, d)

    args = [jnp.asarray(feeds[s]) for s in SLOTS]
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                     argnums=tuple(range(len(args))))(*args)
    return np.asarray(fn(*args)), dict(zip(SLOTS, map(np.asarray, grads)))


def close(got, want, tol, norm=False):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    if norm:
        err, scale = np.linalg.norm(got - want), np.linalg.norm(want)
    else:
        err, scale = np.abs(got - want).max(), np.abs(want).max()
    assert float(err) <= tol * max(float(scale), 1e-30), (
        float(err), float(scale))


@pytest.mark.parametrize('amp', [False, True], ids=['f32', 'amp'])
@pytest.mark.parametrize('chunks', [1, 2], ids=['one_chunk', 'two_chunks'])
@pytest.mark.parametrize('form', sorted(FORMS))
def test_kernel_matches_the_xla_lowering_and_the_recurrence(
        form, chunks, amp):
    """Y and the gradients of X, dt, A (through A_log), B, C, D and
    dt_bias through the op: the kernel against the einsum lowering and
    against the recurrence.  Two chunks: the state rides between them in
    the forward, its cotangent in the gradient."""
    heads, groups, chunk = FORMS[form]
    feeds = feeds_of(heads, groups, chunks * chunk)
    out, grads, w = run_op(feeds, chunk, 'pallas', amp)
    xla_out, xla_grads, _ = run_op(feeds, chunk, 'xla', amp)
    want_out, want_grads = recurrence(feeds, w)
    tol = AMP_TOL if amp else F32_TOL
    for want_o, want_g in ((xla_out, xla_grads), (want_out, want_grads)):
        close(out, want_o, tol, norm=amp)
        assert set(grads) == set(want_g) == set(SLOTS)
        for name in SLOTS:
            close(grads[name], want_g[name], tol, norm=amp)


@pytest.mark.parametrize('form', sorted(FORMS))
def test_kernel_saves_the_states_entering_each_chunk(form):
    """The side-band the gradient reads: [B, chunks, H, P, N] f32, the
    XLA lowering's, zeros entering the first chunk."""
    heads, groups, chunk = FORMS[form]
    f = feeds_of(heads, groups, 3 * chunk, batch=2)
    args = [jnp.asarray(f[s]) for s in ('x', 'dt', 'bm', 'cm', 'd')]
    x, dt, bm, cm, d = args
    a = -jnp.exp(jnp.asarray(f['a_log']))
    y, states = pl_ssd.ssd_scan(
        x, jax.nn.softplus(dt + f['dt_bias']), a, bm, cm, d, chunk,
        interpret=True)
    want_y, want = ssm_ops.ssd_scan(x, dt, a, bm, cm, d, f['dt_bias'],
                                    chunk=chunk)
    assert states.shape == want.shape == (2, 3, heads, P, N)
    assert states.dtype == jnp.float32
    assert not np.asarray(states[:, 0]).any()
    close(states, want, F32_TOL)
    close(y, want_y, F32_TOL)


def test_kernel_reads_no_later_position():
    heads, groups, chunk = 8, 1, 128
    feeds = feeds_of(heads, groups, 2 * chunk)
    base, _, _ = run_op(feeds, chunk, 'pallas')
    t = 170     # inside the second chunk
    moved = dict(feeds)
    for name in ('x', 'dt', 'bm', 'cm'):
        moved[name] = feeds[name].copy()
        moved[name][:, t] += 1.0
    out, _, _ = run_op(moved, chunk, 'pallas')
    assert np.array_equal(out[:, :t], base[:, :t])
    assert np.abs(out[:, t:] - base[:, t:]).max() > 1e-3


# ---- what 'auto' picks ---------------------------------------------------

class _Where(object):
    """What a lowering sees of where it runs."""

    def __init__(self, on_cpu=False, mesh=None):
        self.on_cpu, self.mesh = on_cpu, mesh


def _pick(where, length=1024, heads=64, p=P, groups=1, n=N, chunk=256):
    x = jax.ShapeDtypeStruct((1, length, heads, p), jnp.bfloat16)
    bm = jax.ShapeDtypeStruct((1, length, groups, n), jnp.bfloat16)
    return ssm_ops._pick_impl(where, {'impl': 'auto'}, x, bm,
                              min(chunk, length))


def test_auto_takes_the_kernel_at_both_cells_shapes():
    chip = _Where()
    assert _pick(chip) == 'pallas'       # granite_h_train_1chip
    assert _pick(chip, length=2048, groups=8, chunk=128) == 'pallas'


@pytest.mark.parametrize('why,kwargs', [
    ('a_last_chunk_that_is_padded', {'length': 1000}),
    ('a_chunk_past_the_length', {'length': 192}),
    ('a_head_width_outside', {'p': 32}),
    ('a_state_size_outside', {'n': 64}),
    ('a_chunk_outside', {'chunk': 64}),
    ('a_group_that_is_no_whole_block', {'heads': 64, 'groups': 16}),
], ids=lambda v: v if isinstance(v, str) else '')
def test_auto_keeps_the_xla_lowering_outside_the_envelope(why, kwargs):
    assert _pick(_Where(), **kwargs) == 'xla'


def test_auto_keeps_the_xla_lowering_on_a_cpu_place_and_under_a_mesh():
    assert _pick(_Where(on_cpu=True)) == 'xla'
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ('dp', ))
    assert _pick(_Where(mesh=mesh)) == 'xla'
    # a mesh of one device partitions nothing
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ('dp', ))
    assert _pick(_Where(mesh=one)) == 'pallas'


def test_the_choice_is_recorded_with_its_block():
    feeds = feeds_of(8, 1, 256)
    run_op(feeds, 128, 'pallas')
    seen = trace.lowering_choices('ssd_scan', seen=True)[-1]
    assert list(seen.values()) == [{
        'choice': 'pallas', 'chunk': 128, 'chunks': 2, 'block': [256, 512]}]
    run_op(feeds, 128, 'auto')      # a CPU place
    seen = trace.lowering_choices('ssd_scan', seen=True)[-1]
    assert list(seen.values()) == [
        {'choice': 'xla', 'chunk': 128, 'chunks': 2}]
    with pytest.raises(ValueError, match="'auto', 'xla' or 'pallas'"):
        run_op(feeds, 128, 'fused')
