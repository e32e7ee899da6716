"""Nemotron-H with routed experts on the CPU at toy sizes: the ops it brought
(``moe_router`` and ``moe_experts`` in ``ops/moe_ops.py``, ``relu2``, groups
in ``rms_norm``), the model (``models/nemotron_h.py``) and its plain
reference (``models/reference/nemotron_h_ref.py``), on seeded weights.

Tolerances.  Without AMP everything is float32 on both sides and differs
only in the order of sums: 2e-5 of the value's own scale (1e-4 through the
chunked scan, as ``tests/test_granite_hybrid.py`` explains).  Under AMP the
program's matmuls take bf16 inputs against the reference's f32: 3% of a
gradient's norm, ON EQUAL SELECTIONS: a bf16 input flips one of a router's
near-tied selections, and program and reference then compute valid but
different (token, expert) pairs.  The flips are counted, not hidden: the
test fetches the program's selections, holds the share the reference would
not have made to 3%, and hands them to the reference (``forced``), which
then computes its weights, output and gradients for those pairs.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.reference import nemotron_h_ref as ref
from paddle_tpu.ops import moe_ops

F32_TOL = 2e-5
SCAN_TOL = 1e-4
AMP_TOL = 3e-2
CFG = dict(nh.TINY)
TOKENS, D, E, K, F = 24, 16, 16, 3, 12     # a layer's toy sizes


def close(got, want, tol=F32_TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def run_layer(build, feeds, params=(), seed=0, tweak=None):
    """``out = build(**data variables)`` on the CPU place with
    loss = sum(out * w) for a seeded w: (out, the gradients of every feed
    and of the parameters named in ``params``, the parameters' values, w).
    ``tweak(scope)`` runs between the startup program and the step."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    floats = [n for n, v in feeds.items() if v.dtype.kind == 'f']
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {}
        for name, value in feeds.items():
            data[name] = main.global_block().create_var(
                name=name, shape=value.shape, dtype=value.dtype,
                is_data=True)
            data[name].stop_gradient = name not in floats
        out = build(**data)
        w = np.random.RandomState(seed).standard_normal(
            out.shape).astype('float32')
        wv = main.global_block().create_var(
            name='loss_w', shape=w.shape, dtype=w.dtype, is_data=True)
        append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, wv)))
    scope = fluid.core.Scope()
    wrt = floats + list(params)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if tweak:
            tweak(scope)
        values = {n: np.asarray(scope.find_var(n).get_tensor())
                  for n in params}
        got = exe.run(main, feed=dict(feeds, loss_w=w),
                      fetch_list=[out] + [n + '@GRAD' for n in wrt])
    return (np.asarray(got[0]), dict(zip(wrt, map(np.asarray, got[1:]))),
            values, w)


def want_of(fn, args, w):
    """(fn(**args), d sum(fn * w) / d every float argument)."""
    names = [n for n, v in args.items() if np.asarray(v).dtype.kind == 'f']

    def loss(*floats):
        return jnp.sum(fn(**dict(args, **dict(zip(names, floats)))) * w)

    grads = jax.grad(loss, argnums=tuple(range(len(names))))(
        *[jnp.asarray(args[n]) for n in names])
    return np.asarray(fn(**args)), dict(zip(names, map(np.asarray, grads)))


def tokens(seed=1, rows=TOKENS):
    return np.random.RandomState(seed).standard_normal(
        (2, rows // 2, D)).astype('float32')


# ---- relu2 and the norm by groups --------------------------------------

def test_relu2_is_relu_squared():
    x = tokens()
    out, grads, _, w = run_layer(lambda x: fluid.layers.relu2(x), {'x': x})
    want, want_grads = want_of(lambda x: ref.relu2(x), {'x': x}, w)
    close(out, want)
    close(grads['x'], want_grads['x'])
    assert (out[x < 0] == 0).all()


@pytest.mark.parametrize('groups', [2, 4])
@pytest.mark.parametrize('gated', [False, True], ids=['plain', 'gated'])
def test_rms_norm_by_groups_matches_the_reference(groups, gated):
    attr = fluid.ParamAttr(initializer=fluid.initializer.Constant(0.5))
    feeds = {'x': tokens(2)}
    if gated:
        feeds['z'] = tokens(3)

    def build(x, z=None):
        return fluid.layers.rms_norm(x, gate=z, groups=groups,
                                     param_attr=attr)

    def fn(x, z=None):
        x = x if z is None else x * jax.nn.silu(z)
        return ref.rms(x, 0.5, 1e-5, groups=groups)

    out, grads, _, w = run_layer(build, feeds)
    want, want_grads = want_of(fn, feeds, w)
    close(out, want)
    for name in feeds:
        close(grads[name], want_grads[name])
    # each group is normalised alone: scaling one group's inputs leaves
    # the others' outputs as they were
    scaled = {k: v.copy() for k, v in feeds.items()}
    scaled['x'][..., :D // groups] *= 3.0
    moved, _, _, _ = run_layer(build, scaled)
    assert np.array_equal(moved[..., D // groups:], out[..., D // groups:])


@pytest.mark.parametrize('gated', [False, True], ids=['plain', 'gated'])
def test_rms_norm_with_one_group_is_bit_equal_to_the_ungrouped_op(gated):
    """``groups=1`` is the op granite-4.0-h runs: the same bits as the
    lowering gives an op that carries no ``groups`` attr (a program built
    before the attr existed)."""
    feeds = {'x': tokens(2), 'z': tokens(3)} if gated else {'x': tokens(2)}

    def layer(strip):
        def build(x, z=None):
            out = fluid.layers.rms_norm(x, gate=z, groups=1)
            if strip:
                out.block.ops[-1].attrs.pop('groups')
            return out
        return build

    a, ga, _, _ = run_layer(layer(False), feeds)
    b, gb, _, _ = run_layer(layer(True), feeds)
    assert np.array_equal(a, b)
    for name in feeds:
        assert np.array_equal(ga[name], gb[name])
    with pytest.raises(ValueError, match='do not divide'):
        run_layer(lambda x: fluid.layers.rms_norm(x, groups=3),
                  {'x': tokens(2)})


# ---- the router --------------------------------------------------------

def router_layer(score_func, normalize, scale, bias=None):
    def build(x):
        idx, weight = fluid.layers.moe_router(
            x, E, K, score_func=score_func, norm_topk_prob=normalize,
            routed_scaling_factor=scale,
            param_attr=fluid.ParamAttr(
                name='router', initializer=fluid.initializer.Normal(0, 1)),
            bias_attr=fluid.ParamAttr(name='router_bias'))
        build.idx = idx
        return weight

    def tweak(scope):
        if bias is not None:
            scope.find_var('router_bias').set_value(bias)
    return build, tweak


def reference_weights(x, router, bias, normalize=True, scale=2.5):
    cfg = dict(num_experts_per_tok=K, norm_topk_prob=normalize,
               routed_scaling_factor=scale)
    return ref.select({'router': router, 'router_bias': jnp.asarray(bias)},
                      x, cfg)


@pytest.mark.parametrize('normalize,scale', [(True, 2.5), (False, 1.0)],
                         ids=['normalised_scaled', 'raw_scores'])
def test_sigmoid_router_matches_the_reference(normalize, scale):
    """Weights and their gradients to the input and the router's matrix;
    the selection itself through the reference's weights (the k weights a
    token gets are its selected experts' scores, in the order selected)."""
    x = tokens()
    build, tweak = router_layer('sigmoid', normalize, scale)
    out, grads, values, w = run_layer(build, {'x': x}, ['router'],
                                      tweak=tweak)
    bias = np.zeros(E, 'float32')

    def fn(x, router):
        return reference_weights(x, router, bias, normalize, scale)[1]

    want, want_grads = want_of(fn, {'x': x, 'router': values['router']}, w)
    close(out, want)
    for name in ('x', 'router'):
        close(grads[name], want_grads[name])
    if normalize:
        close(out.sum(-1), np.full(out.shape[:-1], scale))


def test_softmax_router_scores_are_one_rule_with_the_sigmoid_ones():
    x = tokens()
    build, tweak = router_layer('softmax', False, 1.0)
    out, _, values, _ = run_layer(build, {'x': x}, ['router'], tweak=tweak)
    probs = jax.nn.softmax(x @ values['router'], axis=-1)
    close(out, jax.lax.top_k(probs, K)[0])
    with pytest.raises(ValueError, match='score_func'):
        run_layer(router_layer('tanh', True, 1.0)[0], {'x': x})


def selections_of(build, tweak, x):
    """The indices the program's router selected (fetched)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.layers.data('x', list(x.shape[1:]), dtype='float32')
        weight = build(xv)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        tweak(scope)
        router = np.asarray(scope.find_var('router').get_tensor())
        idx, weight = exe.run(main, feed={'x': x},
                              fetch_list=[build.idx, weight])
    return np.asarray(idx), np.asarray(weight), router


def test_the_bias_selects_and_never_weighs():
    x = tokens()
    bias = np.zeros(E, 'float32')
    bias[[2, 9]] = 10.0           # always selected
    bias[5] = -10.0               # never
    build, tweak = router_layer('sigmoid', True, 2.5, bias)
    idx, weight, router = selections_of(build, tweak, x)
    assert idx.dtype == np.int32 and idx.shape == x.shape[:-1] + (K, )
    assert ((idx == 2).sum(-1) == 1).all() and ((idx == 9).sum(-1) == 1).all()
    assert not (idx == 5).any()
    want_idx, want = reference_weights(x, router, bias)
    assert np.array_equal(idx, np.asarray(want_idx))
    close(weight, want)
    # the weights are the selected experts' own scores, not score + bias
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    picked = np.take_along_axis(scores, idx, -1)
    close(weight, 2.5 * picked / picked.sum(-1, keepdims=True))


def test_no_gradient_reaches_the_selection_bias():
    build, tweak = router_layer('sigmoid', True, 2.5)
    with pytest.raises(Exception):
        run_layer(build, {'x': tokens()}, ['router_bias'], tweak=tweak)


# ---- the held experts --------------------------------------------------

def experts_layer(first, held, impl, bias=None, act='relu2'):
    """Router and one share of the experts: out = the held experts' part."""
    router, tweak = router_layer('sigmoid', True, 2.5, bias)

    def build(x):
        weight = router(x)
        return fluid.layers.moe_experts(
            x, router.idx, weight, held, F, first_expert=first, act=act,
            impl=impl, param_attr=fluid.ParamAttr(
                name='experts', initializer=fluid.initializer.Normal(0, .3)))
    return build, tweak


def reference_share(first, held, bias):
    cfg = dict(num_experts_per_tok=K, norm_topk_prob=True,
               routed_scaling_factor=2.5)

    def fn(x, router, w_up, w_down):
        p = {'router': router, 'router_bias': jnp.asarray(bias),
             'experts.w_up': w_up, 'experts.w_down': w_down}
        return ref.routed(p, x, cfg, range(first, first + held))
    return fn


def whole_buffer_share(first, held, bias, impl):
    """The same share with every pass over the buffer of pairs made over all
    tokens x k rows: each row gathered, activated and weighed, and the rows
    past the held pairs cut where the gradients and the sum read them."""
    def fn(x, router, w_up, w_down):
        idx, weight = moe_ops.route(x, router, jnp.asarray(bias), 'sigmoid',
                                    K, True, 2.5)
        tok, idx = x.reshape(-1, D), idx.reshape(-1, K)
        order, _, sizes = moe_ops.sort_pairs(idx, first, held)
        live = (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]
        hidden = moe_ops.grouped_dot(
            jnp.where(live, tok[order // K], 0), w_up, sizes, impl,
            interpret=True, transposed=True)
        act = ref.relu2(jnp.where(live, hidden, 0)) \
            * weight.reshape(-1)[order][:, None]
        y = moe_ops.grouped_dot(jnp.where(live, act, 0), w_down, sizes,
                                impl, interpret=True)
        out = jax.ops.segment_sum(jnp.where(live, y, 0), order // K,
                                  num_segments=tok.shape[0])
        return out.reshape(x.shape)
    return fn


PARAMS = ['router', 'experts.w_up', 'experts.w_down']
# the selection bias of the held range that makes a load; its first expert
# always selected puts 28 pairs in the buffer, three tiles of 8 rows and four
LOADS = {'routed': 0.0, 'no_pair_held': -10.0, 'every_pair_held': 10.0,
         'live_rows_off_the_tile': [10.0, 0.0, 0.0, 0.0]}


@pytest.mark.parametrize('impl', ['xla', 'pallas'])
@pytest.mark.parametrize('first,held,load', [
    (0, 4, 'routed'), (8, 4, 'routed'), (0, 16, 'routed'),
    (4, 4, 'no_pair_held'), (4, 4, 'live_rows_off_the_tile'),
    (4, 4, 'every_pair_held')],
    ids=['experts_0_to_3', 'experts_8_to_11', 'all_16', 'no_pair_held',
         'live_rows_off_the_tile', 'every_pair_held'])
def test_held_experts_match_the_reference(first, held, load, impl):
    """Output and the gradients to the input, the router (through the
    weights), and both expert matrices, for a held range at the start, in
    the middle, and for every expert, and at loads of no pair, of a number
    of pairs that ends inside a tile of the passes, and of every pair; XLA's
    ragged product and the Pallas grouped product (interpreted here).  Each
    is also the whole-buffer form's: the passes that visit only the tiles
    holding live rows leave nothing out."""
    x, bias = tokens(), np.zeros(E, 'float32')
    bias[first:first + held] = LOADS[load]
    build, tweak = experts_layer(first, held, impl, bias)
    out, grads, values, w = run_layer(build, {'x': x}, PARAMS, tweak=tweak)
    args = {'x': x, 'router': values['router'],
            'w_up': values['experts.w_up'],
            'w_down': values['experts.w_down']}
    idx, _ = reference_weights(x, values['router'], bias)
    live = int(((idx >= first) & (idx < first + held)).sum())
    assert live == {'no_pair_held': 0, 'every_pair_held': TOKENS * K}.get(
        load, live)
    assert load != 'live_rows_off_the_tile' or \
        live % moe_ops.row_tile(TOKENS * K)
    for fn in (reference_share(first, held, bias),
               whole_buffer_share(first, held, bias, impl)):
        want, want_grads = want_of(fn, args, w)
        close(out, want)
        close(grads['x'], want_grads['x'])
        for name, short in zip(PARAMS, ('router', 'w_up', 'w_down')):
            close(grads[name], want_grads[short])
    seen = trace.lowering_choices('moe_experts', seen=True)[-1]
    assert list(seen.values()) == [{
        'choice': 'pallas_gmm' if impl == 'pallas' else 'ragged_dot',
        'buffer_rows': TOKENS * K, 'held': held, 'pass_rows': 8,
        'tile': [8, D, F] if impl == 'pallas' else None}]


@pytest.mark.parametrize('impl', ['xla', 'pallas'])
@pytest.mark.parametrize('load', ['every_pair_held', 'no_pair_held',
                                  'one_expert_takes_every_token'])
def test_no_pair_is_dropped_at_any_load(load, impl):
    """Weights built so that every token selects only held experts (the
    buffer of tokens x k rows is full: a capacity would drop pairs here),
    so that none does (the buffer holds nothing), and so that one held
    expert gets every token: each matches the reference."""
    x, bias = tokens(), np.zeros(E, 'float32')
    first, held = 4, 4
    if load == 'every_pair_held':
        bias[first:first + held] = 10.0
    elif load == 'no_pair_held':
        bias[first:first + held] = -10.0
    else:
        bias[first + 1] = 10.0
    build, tweak = experts_layer(first, held, impl, bias)
    out, grads, values, w = run_layer(build, {'x': x}, PARAMS, tweak=tweak)
    args = {'x': x, 'router': values['router'],
            'w_up': values['experts.w_up'],
            'w_down': values['experts.w_down']}
    want, want_grads = want_of(reference_share(first, held, bias), args, w)
    idx, _ = reference_weights(x, values['router'], bias)
    pairs = int(((idx >= first) & (idx < first + held)).sum())
    assert pairs == {'every_pair_held': TOKENS * K, 'no_pair_held': 0}.get(
        load, pairs)
    if load == 'one_expert_takes_every_token':
        assert int((idx == first + 1).sum()) == TOKENS
    close(out, want)
    close(grads['x'], want_grads['x'])
    for name, short in zip(PARAMS, ('router', 'w_up', 'w_down')):
        close(grads[name], want_grads[short])
    if load == 'no_pair_held':
        assert not out.any() and not grads['experts.w_up'].any()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in shares of 4: the four shares' routed parts, each from
    its own ``moe_experts`` op over its own slice of the weights, plus the
    shared expert ONCE, are the uncut reference's layer output and
    gradients (every expert held)."""
    x = tokens()
    router, tweak = router_layer('sigmoid', True, 2.5)
    std = fluid.initializer.Normal(0, .3)

    def build(x):
        weight = router(x)
        out = None
        for share in range(4):
            part = fluid.layers.moe_experts(
                x, router.idx, weight, 4, F, first_expert=4 * share,
                param_attr=fluid.ParamAttr(name='share%d' % share,
                                           initializer=std))
            out = part if out is None else fluid.layers.elementwise_add(
                out, part)
        up = fluid.layers.fc(x, 2 * F, num_flatten_dims=2, bias_attr=False,
                             param_attr=fluid.ParamAttr(name='shared_up',
                                                        initializer=std))
        down = fluid.layers.fc(
            fluid.layers.relu2(up), D, num_flatten_dims=2, bias_attr=False,
            param_attr=fluid.ParamAttr(name='shared_down', initializer=std))
        return fluid.layers.elementwise_add(out, down)

    names = ['router', 'shared_up', 'shared_down'] + [
        'share%d.%s' % (s, side) for s in range(4)
        for side in ('w_up', 'w_down')]
    out, grads, values, w = run_layer(build, {'x': x}, names, tweak=tweak)
    cfg = dict(num_experts_per_tok=K, norm_topk_prob=True,
               routed_scaling_factor=2.5, first_expert=0,
               n_routed_experts_held=16)

    def fn(x, router, w_up, w_down, shared_up, shared_down):
        return ref.experts({
            'router': router, 'router_bias': jnp.zeros(E),
            'experts.w_up': w_up, 'experts.w_down': w_down,
            'shared_up': shared_up, 'shared_down': shared_down}, x, cfg)

    args = {'x': x, 'router': values['router'],
            'w_up': np.concatenate(
                [values['share%d.w_up' % s] for s in range(4)]),
            'w_down': np.concatenate(
                [values['share%d.w_down' % s] for s in range(4)]),
            'shared_up': values['shared_up'],
            'shared_down': values['shared_down']}
    want, want_grads = want_of(fn, args, w)
    close(out, want)
    for name in ('x', 'router', 'shared_up', 'shared_down'):
        close(grads[name], want_grads[name])
    for s in range(4):
        for side in ('w_up', 'w_down'):
            close(grads['share%d.%s' % (s, side)],
                  want_grads[side][4 * s:4 * s + 4])


def test_moe_experts_refuses_what_it_does_not_know():
    x = tokens()
    with pytest.raises(ValueError, match='activation'):
        run_layer(experts_layer(0, 4, 'xla', act='gelu')[0], {'x': x})
    with pytest.raises(ValueError, match='impl'):
        run_layer(experts_layer(0, 4, 'dense')[0], {'x': x})
    with pytest.raises(ValueError, match='top_k'):
        fluid.layers.moe_router(fluid.layers.data('x', [4, D]), 4, 5)


# ---- the whole model ---------------------------------------------------

LEN, ROWS = 32, 2


def model_batch(seed=0):
    r = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, 127)
    ids = 2 + r.choice(126, size=(ROWS, LEN), p=p / p.sum())
    ids = ids.astype('int64')
    return {'ids': ids, 'lbl_ids': np.concatenate(
        [ids[:, 1:], np.ones((ROWS, 1), 'int64')], axis=1)}


def routers_of(model):
    """{layer number: the variable holding its router's selections}."""
    return {int(op.input('Weight')[0].split('.')[1][1:]):
            op.output('TopkIdx')[0]
            for op in model['main'].global_block().ops
            if op.type == 'moe_router'}


def trained_once(amp, seed=7, forced=False):
    """The program's loss, gradients and selections on one batch, and the
    reference's on the same weights and batch: on its own selections, or
    (``forced``) on the program's."""
    model = nh.build(max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = seed
    names = nh.names()
    trained = [n for n in names if not n.endswith('router_bias')]
    routers = routers_of(model)
    scope, feed = fluid.core.Scope(), model_batch()
    with fluid.scope_guard(scope), fluid.amp_guard(amp):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        weights = {n: np.asarray(scope.find_var(n).get_tensor())
                   for n in names}
        got = exe.run(model['main'], feed=feed, fetch_list=[
            model['loss']] + [n + '@GRAD' for n in trained]
            + list(routers.values()))
    assert sorted(names) == sorted(
        p.name for p in model['main'].global_block().all_parameters())
    got = [np.asarray(g) for g in got]
    selections = {}
    selected = dict(zip(routers, got[1 + len(trained):]))
    want_loss, want_grads = ref.loss_and_grads(
        weights, CFG, feed['ids'], feed['lbl_ids'], selections,
        selected if forced else None)
    return {'loss': float(got[0].ravel()[0]),
            'grads': dict(zip(trained, got[1:1 + len(trained)])),
            'selected': selected, 'weights': weights,
            'want_loss': want_loss, 'want_grads': want_grads,
            'want_selected': selections}


def test_model_loss_and_every_gradient_match_the_reference():
    t = trained_once(amp=False)
    close(t['loss'], t['want_loss'])
    assert sorted(t['selected']) == sorted(t['want_selected']) == [1, 3]
    for layer, idx in t['selected'].items():
        assert np.array_equal(idx, t['want_selected'][layer])
    for name, g in t['grads'].items():
        close(g, t['want_grads'][name], SCAN_TOL)
    # every held expert's slice of the stacked gradient is its own
    assert t['grads']['nemotron.l1.experts.w_up'].shape == (4, 32, 64)


def test_model_under_amp_stays_within_bf16_of_the_reference():
    """bf16 matmul inputs: 3% of a gradient's norm, every gradient, on
    equal selections.  The router's product and scores are float32 in the
    program too, but their input is a bf16 activation: the share of
    (token, slot) selections that the reference would not have made is
    measured here (at the benchmark's sizes on the chip it is a number of
    the comparison, ``selection_disagree_share``)."""
    t = trained_once(amp=True, forced=True)
    assert abs(t['loss'] - t['want_loss']) < 5e-3
    agree = {layer: float((idx[..., :, None] == t['want_selected'][layer][
        ..., None, :]).any(-1).mean())
        for layer, idx in t['selected'].items()}
    assert min(agree.values()) >= 0.97, agree
    for name, g in t['grads'].items():
        want = np.asarray(t['want_grads'][name])
        assert g.dtype == np.float32      # master gradients
        assert np.linalg.norm(g - want) <= AMP_TOL * np.linalg.norm(want), \
            name


def test_forced_selections_are_computed_with_and_own_ones_still_told():
    """The reference on selections it is handed: its own, handed back,
    change nothing; others change the loss and the experts' gradients, and
    what it would have selected itself is reported either way."""
    t = trained_once(amp=False)
    weights = {n: np.asarray(v) for n, v in t['weights'].items()}
    feed = model_batch()
    own = {}
    loss, grads = ref.loss_and_grads(
        weights, CFG, feed['ids'], feed['lbl_ids'], own, t['want_selected'])
    close(loss, t['want_loss'])
    for name in grads:
        close(grads[name], t['want_grads'][name])
    other = {layer: (idx + 1) % CFG['n_routed_experts']
             for layer, idx in t['want_selected'].items()}
    own = {}
    loss, grads = ref.loss_and_grads(
        weights, CFG, feed['ids'], feed['lbl_ids'], own, other)
    assert abs(loss - t['want_loss']) > 1e-5
    assert np.array_equal(own[1], t['want_selected'][1])
    assert not np.allclose(grads['nemotron.l1.experts.w_up'],
                           t['want_grads']['nemotron.l1.experts.w_up'])


def test_the_held_experts_alone_on_given_inputs():
    """``held_experts_check``: the routed sum and its two weight gradients
    for given tokens, selections, weights and output gradient, against
    ``jax.vjp`` of the layer's own ``routed`` with a router that selects
    the same."""
    r = np.random.RandomState(3)
    x = r.standard_normal((2, 6, D)).astype('float32')
    idx = np.stack([r.permutation(E)[:K] for _ in range(12)]).reshape(
        2, 6, K).astype('int32')
    w = r.uniform(0.2, 1.0, (2, 6, K)).astype('float32')
    w_up, w_down = (r.standard_normal((4, F, D)).astype('float32')
                    for _ in range(2))
    dy = r.standard_normal(x.shape).astype('float32')
    out, d_up, d_down = ref.held_experts_check(w_up, w_down, x, idx, w, dy,
                                               first=8)
    want = sum(
        np.where(idx == 8 + j, w, 0).sum(-1, keepdims=True)
        * (np.square(np.maximum(x @ w_up[j].T, 0)) @ w_down[j])
        for j in range(4))
    close(out, want)
    eps, j = 1e-3, (1, 2, 3)
    moved = w_down.copy()
    moved[j] += eps
    out2, _, _ = ref.held_experts_check(w_up, moved, x, idx, w, dy, first=8)
    assert abs(float(((np.asarray(out2) - np.asarray(out)) * dy).sum()) / eps
               - float(d_down[j])) < 1e-2 * max(1.0, abs(float(d_down[j])))
    assert np.asarray(d_up).shape == w_up.shape and np.asarray(d_up).any()


def test_two_dispatches_through_the_k_step_lane_lower_the_loss():
    """``Executor`` + ``FeedPipeline`` with K=4 under AMP with adam, as the
    cell runs it; the selection bias has moved by the rate a step, up or
    down, and by nothing else."""
    model = nh.build(max_len=LEN, lr=0.003)
    model['main'].random_seed = model['startup'].random_seed = 11
    source = (model_batch(seed=i) for i in range(16))
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.amp_guard(True):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        pipe = fluid.FeedPipeline(exe, [model['loss']], source=source,
                                  steps=4, program=model['main'])
        deliveries = iter(pipe)
        losses = [float(np.asarray(next(deliveries)[0]).ravel()[0])
                  for _ in range(3)]
        deliveries.close()
        bias = np.asarray(
            scope.find_var('nemotron.l1.router_bias').get_tensor())
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05, \
        losses
    steps = bias / CFG['router_bias_update_rate']
    assert bias.shape == (16, ) and bias.any()
    assert np.abs(steps - np.round(steps)).max() < 1e-3
    assert 1 <= np.abs(steps).max() <= 16       # at most the steps taken


def test_the_bias_update_is_the_sign_rule_after_a_step_or_a_pass():
    """An expert under the mean load goes up by the rate, one over it
    down, one at it stays.  The training program holds the op once an E
    layer, after every gradient and optimizer op; the test program does
    not; the model's ``balance`` program holds it after a forward pass and
    nothing else, and a run of it moves every selection bias by the rate
    and no parameter."""
    idx = np.array([[[0, 1, 2], [0, 1, 3]], [[0, 4, 5], [0, 1, 6]]], 'int32')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        sel = fluid.layers.data('sel', [2, 3], dtype='int32')
        bias = fluid.layers.create_parameter(
            [8], 'float32', attr=fluid.ParamAttr(
                name='b', trainable=False,
                initializer=fluid.initializer.Constant(0.5)))
        fluid.layers.moe_bias_update(bias, sel, rate=0.01)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={'sel': idx}, fetch_list=[])
        got = np.asarray(scope.find_var('b').get_tensor())
    # loads 4 3 1 1 1 1 1 0, mean 1.5
    want = 0.5 + 0.01 * np.array([-1, -1, 1, 1, 1, 1, 1, 1], 'float32')
    close(got, want)
    close(ref.balanced_bias(np.full(8, 0.5, 'float32'), idx, 0.01), want)

    model = nh.build(dict(CFG, router_bias_update_rate=0.05), max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = 13
    assert 'moe_bias_update' not in [
        op.type for op in model['test'].global_block().ops]
    for program in ('main', 'balance'):
        ops = model[program].global_block().ops
        types = [op.type for op in ops]
        assert types[-2:] == ['moe_bias_update'] * 2     # one an E layer
        assert 'moe_bias_update' not in types[:-2]
        assert [op.attrs['rate'] for op in ops[-2:]] == [0.05, 0.05]
    assert 'adam' in [op.type for op in model['main'].global_block().ops]
    assert not [op.type for op in model['balance'].global_block().ops
                if op.type == 'adam' or op.type.endswith('_grad')]
    assert sorted(model['routed']) == [1, 3]
    names, feed = nh.names(), model_batch()
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        before = {n: np.array(scope.find_var(n).get_tensor()) for n in names}
        selected = exe.run(model['test'], feed=feed, fetch_list=[
            model['routed'][i]['idx'] for i in (1, 3)])
        exe.run(model['balance'], feed=feed, fetch_list=[])
        after = {n: np.array(scope.find_var(n).get_tensor()) for n in names}
    for name in names:
        if name.endswith('router_bias'):
            layer = int(name.split('.')[1][1:])
            close(after[name], ref.balanced_bias(
                before[name], np.asarray(selected[(1, 3).index(layer)]),
                0.05))
            assert np.abs(after[name]).max() == np.float32(0.05)
        else:
            assert np.array_equal(after[name], before[name]), name


def test_three_steps_train_like_the_reference():
    """Float32, three batches after two set-up passes over the selection
    bias: the program's Adam steps and bias updates against ``adam_steps``
    from the same weights and bias (a step's selections move the bias the
    next step selects with)."""
    cfg = dict(CFG, router_bias_update_rate=0.05)
    model = nh.build(cfg, max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = 13
    names = nh.names()
    feeds = [model_batch(seed=i) for i in range(3)]
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        for f in feeds[:2]:
            exe.run(model['balance'], feed=f, fetch_list=[])
        weights = {n: np.array(scope.find_var(n).get_tensor())
                   for n in names}
        losses = [float(np.asarray(exe.run(
            model['main'], feed=f, fetch_list=[model['loss']])[0]).ravel()[0])
            for f in feeds]
        after = {n: np.asarray(scope.find_var(n).get_tensor())
                 for n in names}
    own = {}
    want_losses, _, final = ref.adam_steps(
        weights.__getitem__, cfg, [(f['ids'], f['lbl_ids']) for f in feeds],
        0.001, selections=own)
    close(losses, want_losses, 1e-4)
    assert sorted(own) == [1, 3] and all(
        ref.expert_load(v, cfg).shape == (4, ) for v in own.values())
    for name in names:
        if name.endswith('router_bias'):    # two passes, three steps
            assert np.abs(weights[name]).max() > 0
            assert np.abs(after[name] - weights[name]).max() > 0
            close(after[name], final(name))
        else:       # three Adam steps of 0.001 an element
            change = after[name] - weights[name]
            assert np.linalg.norm(change - (final(name) - weights[name])) \
                <= 0.02 * np.linalg.norm(change), name


def test_model_reads_no_later_position():
    model = nh.build(max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = 3
    feed = model_batch()
    moved = {k: v.copy() for k, v in feed.items()}
    t = 19
    moved['ids'][:, t] = (feed['ids'][:, t] - 2 + 5) % 126 + 2
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        a, = exe.run(model['test'], feed=feed, fetch_list=[model['logits']])
        b, = exe.run(model['test'], feed=moved,
                     fetch_list=[model['logits']])
    a, b = np.asarray(a), np.asarray(b)
    assert np.array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t:] - b[:, t:]).max() > 1e-4


def test_initial_values_follow_the_family():
    model = nh.build(max_len=LEN)
    model['startup'].random_seed = 5
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(model['startup'])
        get = lambda n: np.asarray(   # noqa: E731
            scope.find_var(n).get_tensor())
        a = np.exp(get('nemotron.l0.A_log'))
        assert (a >= 1).all() and (a < 16).all()
        dt = np.log1p(np.exp(get('nemotron.l0.dt_bias')))     # softplus
        assert (dt > 0.0009).all() and (dt < 0.11).all()
        assert (get('nemotron.l0.D') == 1).all()
        assert (get('nemotron.l1.norm') == 1).all()
        assert not get('nemotron.l1.router_bias').any()   # zeros at first
        assert get('nemotron.l1.router').shape == (64, 16)
        assert abs(get('nemotron.l1.experts.w_up').std() - 0.02) < 0.002
        assert abs(get('nemotron.lm_head').std() - 0.02) < 0.002


def test_the_pattern_is_cut_to_the_depth_and_checked():
    assert nh.pattern(dict(CFG, num_hidden_layers=2)) == 'ME'
    with pytest.raises(ValueError, match='pattern'):
        nh.pattern(dict(CFG, num_hidden_layers=9))
    with pytest.raises(ValueError, match='pattern'):
        nh.pattern(dict(CFG, hybrid_override_pattern='MX*E'))
