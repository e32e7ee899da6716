"""Driver ``train_loop_ref``: ``train_loop``'s run, with what the timed lane
itself trained compared with the benchmark's plain float32 reference.

``train_loop.py`` is loaded by path and runs unchanged: its set-up, its
window, its counters and its record are this driver's.  The builder is
wrapped so that the programs it returns are known here, and ``ctx.mark``
so that three things happen round ``train_loop``'s own steps (inside its
scope and AMP guards):

1. at ``startup_ran``, once the startup program has drawn the weights,
   they are read out of the scope (host copies, float32), and ONE step of
   the timed Program runs on the first batch of the cell's traffic at the
   timed sizes (a second ``Executor`` on the same place and scope),
   fetching the loss and the gradients the builder names
   (``checked_gradients``).  It is a real training step: the lane starts
   from weights one Adam step on, and trains on the same first batch again;
2. at ``warmup_1_delivered``, when the first dispatch of the timed lane
   (``FeedPipeline``, K steps an executable, the one the window times) has
   delivered its loss, the checked parameters are read out of the scope
   again, with Adam's ``Beta1Pow``, which says how many steps that state
   has taken (the pipeline has a second dispatch in flight by then);
3. after the window the cell's state is dropped from the device and the
   builder's ``reference_train`` takes as many plain Adam steps from the
   host copies on the same batches, float32, matmul precision highest, on
   the same device (weights on the host, moments on the device: less
   than the cell held, so the device's peak stays the cell's).

Compared under the configuration's ``tolerances``: the first step's loss
(absolute difference) and gradients (norm of the difference over the norm
of the reference's); every loss the lane's dispatches fetched up to the
state that was read (absolute difference); and each checked parameter's
change over those steps (norm of the difference of the two changes over
the norm of the reference's: a state left unchanged reads 1).  ``correct``
is that agreement AND ``train_loop``'s rule over the window.  In
``setup_s`` fall the host copies and the one step (the marks
``startup_ran`` -> ``first_step_ran``) and the second read
(``warmup_1_delivered`` -> ``state_read``); the reference's seconds come
after the window (``reference_compared``).  One chip only.
"""

import gc
import importlib.util
import json
import math
import os
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# the op types whose lowering picks among implementations: what it picked
# for the timed Program is printed beside the comparison
CHOOSERS = ('flash_attention', 'ssd_scan')


def _train_loop():
    spec = importlib.util.spec_from_file_location(
        'chipbench_train_loop', os.path.join(HERE, 'train_loop.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read(scope, name):
    return np.array(scope.find_var(name).get_tensor(), np.float32)


def first_step(ctx, model, scope):
    """The weights as drawn (host copies), and the loss and the checked
    gradients of one step of the timed Program on the first batch."""
    import paddle_tpu.fluid as fluid
    cfg, model_lib = ctx.config, ctx.model_lib
    if len(ctx.devices) != 1:
        raise ValueError('train_loop_ref: one chip (the comparison reads '
                         'and runs on one device), got %d'
                         % len(ctx.devices))
    weights = {p.name: _read(scope, p.name)
               for p in model['main'].global_block().all_parameters()}
    batch = next(ctx.traffic_lib.token_batches(
        ctx.traffic, model_lib.vocab(cfg), ctx.seed))
    names = model_lib.checked_gradients(cfg)
    exe = fluid.Executor(fluid.core.place_of(ctx.devices[0]))
    got = exe.run(model['main'], feed=model_lib.feed(cfg, batch),
                  fetch_list=[model['loss']] + [n + '@GRAD' for n in names])
    got = [np.array(g, np.float32) for g in got]
    return {'weights': weights, 'loss': float(got[0].ravel()[0]),
            'grads': dict(zip(names, got[1:]))}


def state_read(ctx, model, scope):
    """The checked parameters as the scope holds them now, and the number
    of Adam steps that state has taken (``Beta1Pow`` is beta1 to the power
    of the next step's number)."""
    adam = next(op for op in model['main'].global_block().ops
                if op.type == 'adam')
    power = float(_read(scope, adam.input('Beta1Pow')[0]).ravel()[0])
    steps = math.log(power) / math.log(adam.attrs['beta1']) - 1
    if abs(steps - round(steps)) > 0.01:
        raise ValueError('train_loop_ref: Beta1Pow %r is no power of %r'
                         % (power, adam.attrs['beta1']))
    return {'steps': int(round(steps)),
            'params': {n: _read(scope, n) for n in
                       ctx.model_lib.checked_gradients(ctx.config)}}


def compare(ctx, first, after, lane_losses):
    """{'agree': bool, 'numbers': {name: [value, limit]}}: the program's
    first step, its lane's fetched losses and its parameters' change
    against the reference's."""
    cfg, model_lib = ctx.config, ctx.model_lib
    k, steps = int(ctx.cell['steps_per_dispatch']), after['steps']
    if steps < 1 + k:
        raise ValueError('train_loop_ref: the state read has taken %d '
                         'steps: no dispatch of the lane (%d steps) is in it'
                         % (steps, k))
    # the one step, then the lane's: the stream again from its first batch
    stream = ctx.traffic_lib.token_batches(
        ctx.traffic, model_lib.vocab(cfg), ctx.seed)
    batches = [next(stream) for _ in range(steps - 1)]
    feeds = [model_lib.feed(cfg, b) for b in batches[:1] + batches]
    names = list(first['grads'])
    ref_losses, ref_grads, ref_final = model_lib.reference_train(
        cfg, first['weights'].__getitem__, feeds, names)
    limits = cfg['tolerances']

    def rel(got, want):
        return float(np.linalg.norm(got - want)
                     / max(np.linalg.norm(want), 1e-30))

    def limit(group, name):
        kinds = limits[group]
        return kinds.get(name.rsplit('.', 1)[-1], kinds['default'])['limit']

    numbers = {'loss_abs_diff': [abs(first['loss'] - ref_losses[0]),
                                 limits['loss_abs_diff']['limit']]}
    for name in names:
        numbers['grad_rel_err.' + name] = [
            rel(first['grads'][name], ref_grads[name]),
            limit('grad_rel_err', name)]
    # dispatch d's fetched loss is its last step's: the reference's step
    # 1 + (d + 1) k, counted from 1
    fetched = [(lane_losses[d], ref_losses[(d + 1) * k])
               for d in range((steps - 1) // k)]
    numbers['lane_loss_abs_diff'] = [
        max(abs(got - want) for got, want in fetched),
        limits['lane_loss_abs_diff']['limit']]
    for name in names:
        before = first['weights'][name]
        numbers['param_change_rel_err.' + name] = [
            rel(after['params'][name] - before, ref_final(name) - before),
            limit('param_change_rel_err', name)]
    agree = all(np.isfinite(v) and v <= lim for v, lim in numbers.values())
    print('chipbench: reference after %d steps: first loss %.6f (program '
          '%.6f), lane losses %s (program %s) %s'
          % (steps, ref_losses[0], first['loss'],
             ' '.join('%.4f' % want for _, want in fetched),
             ' '.join('%.4f' % got for got, _ in fetched),
             json.dumps({key: [float('%.4g' % v), lim]
                         for key, (v, lim) in numbers.items()})), flush=True)
    return {'agree': agree, 'steps': steps, 'numbers': numbers}


def run(ctx):
    import paddle_tpu.fluid as fluid
    base = _train_loop()
    seen = {}

    def build(cfg, traffic):
        seen['model'] = ctx.model_lib.build(cfg, traffic)
        return seen['model']

    def mark(name):
        now = ctx.mark(name)
        if name == 'startup_ran':
            seen['scope'] = fluid.global_scope()
            seen['first'] = first_step(ctx, seen['model'], seen['scope'])
            now = ctx.mark('first_step_ran')
        elif name == 'warmup_1_delivered':
            seen['after'] = state_read(ctx, seen['model'], seen['scope'])
            now = ctx.mark('state_read')
        return now

    model_lib = types.SimpleNamespace(
        **dict(vars(ctx.model_lib), build=build))
    record = base.run(types.SimpleNamespace(
        **dict(vars(ctx), model_lib=model_lib, mark=mark)))
    stats = ctx.devices[0].memory_stats() or {}
    print('chipbench: lowered %s; device peak before the reference %d bytes'
          % (json.dumps({t: fluid.trace.lowering_choices(t, seen=True)[-1:]
                         for t in CHOOSERS}),
             stats.get('peak_bytes_in_use', 0)
             + stats.get('peak_bytes_reserved', 0)), flush=True)
    # the reference's moments (8 bytes a parameter) do not fit beside the
    # cell's state (12), and stay under it: the cell's goes, and with it
    # (collected now, not when the collector comes round to their cycles)
    # the executors whose programs hold a reservation on the device
    scope = seen.pop('scope')
    scope.erase(scope.local_var_names())
    gc.collect()
    record['reference'] = compare(ctx, seen['first'], seen['after'],
                                  record['losses'])
    ctx.mark('reference_compared')
    record['correct'] = bool(record['correct']
                             and record['reference']['agree'])
    return record
