"""The ``kda`` kernels (``ops/pallas/kda.py``), interpreted on the CPU, at
the ling cell's head width (128) with short sequences: the output, the
saved entering states and all five gradients against the XLA lowering AND
against the recurrence of the plain reference
(``models/reference/ling_hybrid_ref.py``, one position at a time); the
gradient from saved states against the one with the states made again; a
kernel without the delta correction; the state carried across a
program's blocks of chunks; Beta read as the program holds it; and the
envelope ``impl='auto'`` picks the kernels in.

Tolerances: ``tests/test_kda.py``'s.  Float32 on every side, the products
at the highest precision: the chunked forms and the recurrence differ in
the order of their sums, and the solve rounds (the kernels' inverse by
recursive doubling, the XLA form's triangular solve): 2e-5 of a result's
norm, 1e-4 for a gradient (4e-6 to 7e-6 read against the XLA form).
Under AMP the two score products take bf16 operands on the kernels' side
and the XLA form's alike: 3% of the norm against the float32 recurrence
and against the XLA form (0.01-0.3% read, dg 1%).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.models.reference import ling_hybrid_ref as ref
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.pallas import kda as pl_kda

TOL = 2e-5
GRAD_TOL = 1e-4
AMP_TOL = 3e-2
H, D = 2, 128
SLOTS = ('q', 'k', 'v', 'g', 'beta')


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def inputs(length, decay='random', batch=1, seed=0):
    r = np.random.RandomState(seed)
    q, k, v, do = (r.standard_normal((batch, length, H, D)).astype(
        'float32') for _ in range(4))
    if decay == 'lower_bound':
        g = np.full((batch, length, H, D), -4.999, 'float32')
    elif decay == 'slow':
        g = (-0.1 / (1 + np.exp(-r.standard_normal(
            (batch, length, H, D))))).astype('float32')
    elif decay == 'mixed':
        g = np.where(r.rand(batch, length, H, D) < 0.5, -4.999,
                     -0.001).astype('float32')
    else:
        g = (-5.0 / (1 + np.exp(-3 * r.standard_normal(
            (batch, length, H, D))))).astype('float32')
    beta = (1 / (1 + np.exp(-r.standard_normal((batch, length, H))))
            ).astype('float32')
    return dict(q=q, k=k, v=v, g=g, beta=beta), do


def op_and_grads(feeds, do, chunk, impl, amp=False):
    """The Fluid ``kda`` op and its gradient lowering on the CPU place
    (the kernels interpreted): (o, {name: d sum(o * do) / d name})."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {}
        for name, value in dict(feeds, do=do).items():
            data[name] = main.global_block().create_var(
                name=name, shape=value.shape, dtype='float32', is_data=True)
            data[name].stop_gradient = name == 'do'
        out = fluid.layers.kda(*(data[n] for n in SLOTS), chunk=chunk,
                               impl=impl)
        append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, data['do'])))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(amp):
        got = exe.run(main, feed=dict(feeds, do=do),
                      fetch_list=[out] + [n + '@GRAD' for n in feeds])
    return got[0], dict(zip(feeds, got[1:]))


@pytest.mark.parametrize('length,chunk,decay', [
    (64, 64, 'random'), (100, 64, 'random'), (48, 16, 'mixed'),
    (100, 32, 'lower_bound')],
    ids=['one_chunk', 'length_off_the_chunk', 'mixed_decays',
         'lower_bound'])
def test_kernels_match_the_xla_lowering_and_the_recurrence(length, chunk,
                                                           decay):
    """Output and dq, dk, dv, dg, dbeta through the Fluid op: the kernels
    against the XLA form and against ``jax.vjp`` of the recurrence.  At
    the lower bound the sub-chunks' split is what keeps the decay's
    gradient from being flushed."""
    feeds, do = inputs(length, decay)
    o, grads = op_and_grads(feeds, do, chunk, 'pallas')
    xla_o, xla_grads = op_and_grads(feeds, do, chunk, 'xla')
    want = ref.delta_rule_check(*(feeds[n] for n in SLOTS), do)
    for other_o, other_grads in ((xla_o, xla_grads),
                                 (want[0], dict(zip(SLOTS, want[1:])))):
        assert rel(o, other_o) < TOL
        for name in SLOTS:
            assert rel(grads[name], other_grads[name]) < GRAD_TOL, name
    assert np.isfinite(o).all()


def test_under_amp_the_score_products_round_as_the_xla_forms():
    """bf16 operands in the two score products, float32 elsewhere: the
    kernels against the XLA form under AMP and against the float32
    recurrence."""
    feeds, do = inputs(100)
    o, grads = op_and_grads(feeds, do, 32, 'pallas', amp=True)
    xla_o, xla_grads = op_and_grads(feeds, do, 32, 'xla', amp=True)
    want = ref.delta_rule_check(*(feeds[n] for n in SLOTS), do)
    for other_o, other_grads in ((xla_o, xla_grads),
                                 (want[0], dict(zip(SLOTS, want[1:])))):
        assert rel(o, other_o) < AMP_TOL
        for name in SLOTS:
            assert rel(grads[name], other_grads[name]) < AMP_TOL, name
    # the products did round: the float32 kernels read 1e-5 of this
    assert rel(o, want[0]) > 1e-3


def test_saved_states_are_the_xla_forms_and_the_gradient_reads_them():
    """The forward kernel's states entering each chunk ([B, chunks, H,
    d_k, d_v] f32, zero at the start) are the XLA form's; the gradient
    from them equals the gradient with the states made again by the
    forward kernel (the lowering's path where another trace lowered the
    forward)."""
    feeds, do = inputs(96, batch=2)
    args = [jnp.asarray(feeds[n]) for n in SLOTS]
    padded = la._pad(args, 32)
    o, states = pl_kda.kda(*padded, chunk=32, dtype=jnp.float32,
                           interpret=True)
    with jax.default_matmul_precision('highest'):
        want_o, want = la.kda(*args, chunk=32)
    assert states.shape == want.shape == (2, 3, H, D, D)
    assert states.dtype == jnp.float32
    assert not np.asarray(states[:, 0]).any()
    assert rel(states, want) < TOL and rel(o, want_o) < TOL

    class Where(object):
        on_cpu = True

    mine = la._fused_grads(Where(), args, states, jnp.asarray(do), 32)
    again = la._fused_grads(Where(), args, None, jnp.asarray(do), 32)
    for a, b in zip(mine, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture
def fresh_traces():
    """A patched kernel body must not meet a trace cached without it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_without_the_delta_correction_the_comparison_fails(monkeypatch,
                                                           fresh_traces):
    """The kernels with ``(I - b k k^T)`` dropped (W = 0, U = b v: a gated
    linear attention) are far outside the tolerance of every output: the
    comparison is not blind to the solve."""
    feeds, do = inputs(64, 'slow')
    monkeypatch.setattr(pl_kda, '_wy', lambda t, x1, x2: jnp.concatenate(
        [jnp.zeros_like(x1), x2], axis=1))
    o, grads = op_and_grads(feeds, do, 32, 'pallas')
    want = ref.delta_rule_check(*(feeds[n] for n in SLOTS), do)
    assert rel(o, want[0]) > 0.1
    assert min(rel(grads[n], w) for n, w in zip(SLOTS, want[1:])) > 0.05


@pytest.mark.parametrize('rows', [32, 64, 128],
                         ids=['one_chunk_a_step', 'a_pair', 'two_pairs'])
def test_the_state_rides_across_a_programs_blocks(monkeypatch, fresh_traces,
                                                  rows):
    """Programs of fewer positions than the sequence (``_BLOCK_ROWS``):
    eight, four or two blocks of 32-position chunks on the sequential
    grid axis, taken one, two or four chunks a step.  The forward carries
    the state and the gradient the state's gradient in VMEM scratch from
    block to block, the gradient walking the blocks in reverse; three
    heads, so that a program picks its own column of Beta."""
    monkeypatch.setattr(pl_kda, '_BLOCK_ROWS', rows)
    r = np.random.RandomState(1)
    feeds = {n: r.standard_normal((1, 256, 3, D)).astype('float32')
             for n in ('q', 'k', 'v')}
    feeds['g'] = (-5.0 / (1 + np.exp(-3 * r.standard_normal(
        (1, 256, 3, D))))).astype('float32')
    feeds['beta'] = (1 / (1 + np.exp(-r.standard_normal((1, 256, 3))))
                     ).astype('float32')
    do = r.standard_normal((1, 256, 3, D)).astype('float32')
    assert pl_kda.block(256, 32) == [rows, 128]
    o, grads = op_and_grads(feeds, do, 32, 'pallas')
    want = ref.delta_rule_check(*(feeds[n] for n in SLOTS), do)
    assert rel(o, want[0]) < TOL
    for name, w in zip(SLOTS, want[1:]):
        assert rel(grads[name], w) < GRAD_TOL, name


def test_the_kernels_read_beta_as_the_program_holds_it():
    """Beta from a projection and a sigmoid under AMP, held in bf16: the
    op's output inside the step is the kernel's on the values the step
    fetches.  Widened to float32 outside the kernel, XLA on the CPU
    computed that copy from the sigmoid's float32 value before its bf16
    rounding, and the output read 1e-3 off."""
    length, chunk = 96, 32
    r = np.random.RandomState(2)
    feeds = {n: r.standard_normal((1, length, H, D)).astype('float32')
             for n in ('q', 'k', 'v')}
    feeds['g'] = (-5.0 / (1 + np.exp(-3 * r.standard_normal(
        (1, length, H, D))))).astype('float32')
    feeds['x'] = r.standard_normal((1, length, 16)).astype('float32')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {n: main.global_block().create_var(
            name=n, shape=v.shape, dtype='float32', is_data=True)
            for n, v in feeds.items()}
        beta = fluid.layers.sigmoid(fluid.layers.fc(
            data['x'], H, num_flatten_dims=2, bias_attr=False))
        out = fluid.layers.kda(*(data[n] for n in SLOTS[:4]), beta,
                               chunk=chunk, impl='pallas')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
        exe.run(startup)
        got = exe.run(main, feed=feeds, fetch_list=[
            out] + [data[n] for n in SLOTS[:4]] + [beta])
    assert got[5].dtype == jnp.bfloat16
    alone = pl_kda.kda(*(jnp.asarray(a) for a in got[1:]), chunk=chunk,
                       dtype=jnp.bfloat16, interpret=True)[0]
    assert rel(got[0], alone) < 1e-6


# ---- what 'auto' picks ---------------------------------------------------

class _Where(object):
    """What a lowering sees of where it runs."""

    def __init__(self, on_cpu=False, mesh=None):
        self.on_cpu, self.mesh = on_cpu, mesh


def _pick(where, length=2048, heads=32, dk=128, dv=128, chunk=64):
    q = jax.ShapeDtypeStruct((1, length, heads, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, length, heads, dv), jnp.bfloat16)
    return la._pick_impl(where, {'impl': 'auto'}, q, v, chunk)


def test_auto_takes_the_kernels_at_the_cells_shapes():
    assert _pick(_Where()) == 'pallas'      # ling3_flash_train_1chip
    assert _pick(_Where(), length=2000) == 'pallas'   # padded


@pytest.mark.parametrize('why,kwargs', [
    ('a_key_width_outside', {'dk': 64}),
    ('a_value_width_outside', {'dv': 256}),
    ('a_chunk_of_no_whole_bf16_tile', {'chunk': 24}),
    ('a_chunk_too_large_for_vmem', {'chunk': 1024}),
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
    feeds, do = inputs(100)
    op_and_grads(feeds, do, 32, 'pallas')
    seen = trace.lowering_choices('kda', seen=True)[-1]
    assert list(seen.values()) == [{
        'choice': 'pallas', 'chunk': 32, 'chunks': 4, 'block': [128, 128]}]
    op_and_grads(feeds, do, 32, 'auto')      # a CPU place
    seen = trace.lowering_choices('kda', seen=True)[-1]
    assert list(seen.values()) == [
        {'choice': 'xla', 'chunk': 32, 'chunks': 4}]
    with pytest.raises(ValueError, match="'auto', 'xla' or 'pallas'"):
        op_and_grads(feeds, do, 32, 'fused')
