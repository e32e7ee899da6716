"""Driver ``train_loop_ref_routed``: ``train_loop_ref``'s run for a model
with routed experts, whose selections are discrete: compared on EQUAL
selections, and its selection bias balanced in set-up.

``train_loop_ref.py`` is loaded by path and runs unchanged but for the two
functions this file hands its private copy: the one fetched step and the
comparison.  The builder's model names its routed layers
(``model['routed']``: by layer the names of the selection bias, of the
held experts' two matrices and of their input, selected experts, weights
and output) and brings a
forward-only program that moves the bias (``model['balance']``).

1. Before the fetched step, ``router_bias_setup_passes`` runs of
   ``model['balance']`` on the first batches of the cell's traffic: forward
   passes that move each layer's selection bias by the family's own rule
   (an expert under the mean load up by ``router_bias_update_rate``, one
   over it down) and train nothing: they level the loads that random
   weights under Zipf ids leave uneven, so that the checked step and the
   window see the loads a deployment's step sees; the training program
   itself applies the same rule after every step, which holds them.  Its
   executor goes before the fetched step's comes (the device holds one
   program's temporaries less).  The reference is handed the bias as
   set-up left it, with the weights, and moves it by the same rule after
   each of its steps.  The mark ``bias_balanced`` closes the phase; it
   falls in ``setup_s``.
2. The fetched step also fetches every routed layer's selected experts,
   and for ``checked_experts`` (one layer) the held experts' input, weights,
   output and the output's gradient.
3. The reference's FIRST step computes with the program's selections
   (``reference_train(..., forced=...)``): a near-tie that a bf16 activation
   settles the other way is then no difference, and the gradients of the
   router and the experts are held to limits like any other matrix's.
   What a lower precision in the router does is to select other experts;
   that is read directly, ``selection_disagree_share.l<i>``: the share of
   the program's (token, slot) pairs that the reference, from the same
   weights and up to there the same selections, would not have selected.
   The lane's later steps cannot fetch their selections (it fetches its
   loss alone), so the parameters' change is compared as before.
4. ``alone.l<i>.*``: that layer's router and held experts ALONE, the
   reference's in float32 on the program's own input (its normed tokens,
   exactly as the program's ops read them), selections, weights and output
   gradient.  ``router_selected``: the share of the program's pairs that
   the reference's router does not select for the same tokens;
   ``router_weight``, ``experts_out``, ``experts_w_up``,
   ``experts_w_down``: the program's weights, routed output and the two
   matrices' gradients against the reference's (norm of the difference
   over the norm of the reference's).  Nothing upstream is in these
   numbers: they read the router's and the three grouped products' own
   arithmetic, so a lower precision in either shows at its own size and
   not beside what nine bf16 layers leave on every gradient.

``correct`` is ``train_loop_ref``'s AND these numbers under the
configuration's ``tolerances`` (``selection_disagree_share``, ``alone``).
One chip only.
"""

import gc
import importlib.util
import json
import os
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _train_loop_ref():
    spec = importlib.util.spec_from_file_location(
        'chipbench_train_loop_ref', os.path.join(HERE, 'train_loop_ref.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _with(namespace, **changed):
    return types.SimpleNamespace(**dict(vars(namespace), **changed))


def balance(ctx, model, scope):
    """The set-up passes over the selection bias; prints what they left."""
    import paddle_tpu.fluid as fluid
    cfg, model_lib = ctx.config, ctx.model_lib
    passes = int(cfg['router_bias_setup_passes'])
    stream = ctx.traffic_lib.token_batches(
        ctx.traffic, model_lib.vocab(cfg), ctx.seed)
    exe = fluid.Executor(fluid.core.place_of(ctx.devices[0]))
    for _ in range(passes):
        exe.run(model['balance'], feed=model_lib.feed(cfg, next(stream)),
                fetch_list=[])
    left = {layer: np.array(scope.find_var(names['bias']).get_tensor())
            for layer, names in model['routed'].items()}
    del exe
    gc.collect()
    print('chipbench: selection bias after %d forward passes at %g: %s'
          % (passes, cfg['router_bias_update_rate'], ' '.join(
              'l%d %+.3f..%+.3f' % (layer, b.min(), b.max())
              for layer, b in sorted(left.items()))), flush=True)


def first_step(ctx, model, scope):
    """``train_loop_ref.first_step`` after the set-up passes, with the
    routed layers' selections and the checked layer's held experts' input,
    weights, output and output gradient fetched from the same step."""
    import paddle_tpu.fluid as fluid
    cfg, model_lib = ctx.config, ctx.model_lib
    if len(ctx.devices) != 1:
        raise ValueError('train_loop_ref_routed: one chip, got %d'
                         % len(ctx.devices))
    balance(ctx, model, scope)
    ctx.mark('bias_balanced')
    weights = {p.name: np.array(scope.find_var(p.name).get_tensor(),
                                np.float32)
               for p in model['main'].global_block().all_parameters()}
    batch = next(ctx.traffic_lib.token_batches(
        ctx.traffic, model_lib.vocab(cfg), ctx.seed))
    names = model_lib.checked_gradients(cfg)
    routed, layer = model['routed'], model_lib.checked_experts(cfg)
    alone = routed[layer]
    exe = fluid.Executor(fluid.core.place_of(ctx.devices[0]))
    got = exe.run(
        model['main'], feed=model_lib.feed(cfg, batch),
        fetch_list=[model['loss']] + [n + '@GRAD' for n in names]
        + [routed[i]['idx'] for i in sorted(routed)]
        + [alone['x'], alone['weight'], alone['out'], alone['out'] + '@GRAD'])
    got = [np.array(g) for g in got]
    grads = [np.array(g, np.float32) for g in got[1:1 + len(names)]]
    selected = got[1 + len(names):-4]
    return {'weights': weights, 'loss': float(got[0].ravel()[0]),
            'grads': dict(zip(names, grads)),
            'selected': dict(zip(sorted(routed), selected)),
            'alone': dict(zip(('x', 'w', 'out', 'dy'), (
                np.array(g, np.float32) for g in got[-4:])), layer=layer,
                w_up=alone['w_up'], w_down=alone['w_down'])}


def rel(got, want):
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def disagree(mine, theirs):
    """The share of the (token, slot) pairs of ``mine`` [..., k] that are
    not among the token's pairs of ``theirs``."""
    return float(1.0 - (mine[..., :, None] == theirs[..., None, :]).any(
        -1).mean())


def compare_routed(ctx, first, own):
    """{name: [value, limit]}: the selections the reference would not have
    made, and the checked layer's router and held experts alone."""
    cfg, limits = ctx.config, ctx.config['tolerances']
    numbers = {}
    for layer, mine in sorted(first['selected'].items()):
        numbers['selection_disagree_share.l%d' % layer] = [
            disagree(mine, own[layer]),
            limits['selection_disagree_share']['limit']]
    alone = first['alone']
    layer = alone['layer']
    want = ctx.model_lib.reference_routed_layer(
        cfg, first['weights'].__getitem__, layer, alone['x'],
        first['selected'][layer], alone['w'], alone['dy'])
    got = {'router_weight': alone['w'], 'experts_out': alone['out'],
           'experts_w_up': first['grads'][alone['w_up']],
           'experts_w_down': first['grads'][alone['w_down']]}
    read = {'router_selected': disagree(first['selected'][layer],
                                        want['router_selected'])}
    read.update((key, rel(mine, want[key])) for key, mine in got.items())
    for key, value in read.items():
        numbers['alone.l%d.%s' % (layer, key)] = [
            value, limits['alone'][key]['limit']]
    return numbers


def run(ctx):
    base = _train_loop_ref()
    compare_ref = base.compare

    def compare(ctx, first, after, lane_losses):
        own = {}

        def reference_train(cfg, weight, feeds, wrt):
            return ctx.model_lib.reference_train(
                cfg, weight, feeds, wrt, forced=first['selected'], own=own)

        result = compare_ref(
            _with(ctx, model_lib=_with(ctx.model_lib,
                                       reference_train=reference_train)),
            first, after, lane_losses)
        numbers = compare_routed(ctx, first, own)
        print('chipbench: routed experts on equal selections: %s'
              % json.dumps({key: [float('%.4g' % v), lim]
                            for key, (v, lim) in numbers.items()}),
              flush=True)
        result['numbers'].update(numbers)
        result['agree'] = bool(result['agree'] and all(
            np.isfinite(v) and v <= lim for v, lim in numbers.values()))
        return result

    base.first_step, base.compare = first_step, compare
    # the expert products' implementation, buffer and tile beside the others
    base.CHOOSERS = base.CHOOSERS + ('moe_experts', )
    return base.run(ctx)
