"""``drivers/train_loop_ref.py``'s comparison, on made-up numbers: what the
timed lane trained agrees with the reference only if the first step, the
lane's fetched losses and the parameters' change all do.  (The cell's own
rehearsal, ``test_chipbench_cells``, runs the driver end to end.)"""

import importlib.util
import os
import types

import numpy as np
import pytest

from chipbench_helpers import BENCH

K, STEPS = 2, 5          # one fetched step, then two dispatches of two
NAMES = ['m.l0.in_proj', 'm.l0.A_log']
LIMITS = {
    'loss_abs_diff': {'limit': 1e-3},
    'grad_rel_err': {'default': {'limit': 0.05}, 'A_log': {'limit': 0.1}},
    'lane_loss_abs_diff': {'limit': 0.05},
    'param_change_rel_err': {'default': {'limit': 0.5}},
}


def driver():
    spec = importlib.util.spec_from_file_location(
        'train_loop_ref_under_test',
        os.path.join(BENCH, 'drivers', 'train_loop_ref.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def numbers(**fault):
    """(ctx, first, after, lane losses) of a program that did what the
    reference did, but for ``fault``."""
    r = np.random.RandomState(0)
    before = {n: r.standard_normal(6).astype('float32') for n in NAMES}
    grads = {n: r.standard_normal(6).astype('float32') for n in NAMES}
    change = {n: 0.01 * r.standard_normal(6).astype('float32')
              for n in NAMES}
    ref_losses = [5.0, 4.8, 4.6, 4.4, 4.2]
    fed = []

    def reference_train(cfg, weight, feeds, wrt):
        fed.extend(feeds)
        assert all(np.array_equal(weight(n), before[n]) for n in NAMES)
        return ref_losses, grads, lambda n: before[n] + change[n]

    ctx = types.SimpleNamespace(
        config={'tolerances': LIMITS}, cell={'steps_per_dispatch': K},
        traffic={}, seed=3,
        traffic_lib=types.SimpleNamespace(
            token_batches=lambda traffic, vocab, seed: iter(range(100))),
        model_lib=types.SimpleNamespace(
            vocab=lambda cfg: 8, feed=lambda cfg, batch: batch,
            reference_train=reference_train))
    first = {'weights': before, 'loss': 5.0 + fault.get('first_loss', 0.0),
             'grads': {n: g * (1 + fault.get('gradient', 0.0))
                       for n, g in grads.items()}}
    kept = fault.get('change_kept', 1.0)
    after = {'steps': STEPS, 'params': {
        n: before[n] + kept * change[n] for n in NAMES}}
    lane = [4.6 + fault.get('lane_loss', 0.0), 4.2, 4.1, 4.0]
    return ctx, first, after, lane, fed


@pytest.mark.parametrize('fault,agree', [
    ({}, True),
    ({'first_loss': 0.01}, False),
    ({'gradient': 0.08}, False),
    ({'lane_loss': 0.2}, False),
    ({'change_kept': 0.0}, False),      # a state left unchanged reads 1
    ({'change_kept': 0.25}, False),     # three of four steps lost
    ({'change_kept': 0.9}, True),
], ids=['sound', 'first_loss_off', 'gradient_off', 'lane_loss_off',
        'state_unchanged', 'steps_lost', 'within_the_limits'])
def test_comparison_holds_every_number_to_its_limit(fault, agree):
    ctx, first, after, lane, fed = numbers(**fault)
    out = driver().compare(ctx, first, after, lane)
    assert out['agree'] is agree
    # the reference is fed the fetched step's batch, then the lane's
    # stream from its first batch again
    assert fed == [0, 0, 1, 2, 3]
    assert set(out['numbers']) == {'loss_abs_diff', 'lane_loss_abs_diff'} \
        | {kind + n for n in NAMES
           for kind in ('grad_rel_err.', 'param_change_rel_err.')}
    if fault.get('change_kept') == 0.0:
        assert all(abs(v - 1) < 1e-6 for key, (v, _) in
                   out['numbers'].items() if key.startswith('param_change'))
    # the gradient of a 64-number vector has its own, wider limit
    assert out['numbers']['grad_rel_err.m.l0.A_log'][1] == 0.1


def test_comparison_needs_a_fetched_loss_of_the_lane():
    """A state read before the lane's first dispatch has ended (one step
    taken) compares no lane loss: not correct."""
    ctx, first, after, lane, _ = numbers()
    after['steps'] = 1
    with pytest.raises(ValueError):
        driver().compare(ctx, first, after, lane)


# ---- the whole driver, on a lane with a contract fault -------------------

def _weight_left_unchanged(sound):
    def adam(ctx, op):
        if op.input('Param')[0] != 'granite.l0.in_proj':
            return sound(ctx, op)
        for out, src in (('ParamOut', 'Param'), ('Moment1Out', 'Moment1'),
                         ('Moment2Out', 'Moment2')):
            ctx.set(op, out, ctx.get(op, src))
    return adam


def _first_moment_not_carried(sound):
    import jax.numpy as jnp

    def adam(ctx, op):
        # Fluid's adam, with Moment1 taken as 0 at every step
        p, g, m2 = (ctx.get(op, slot) for slot in ('Param', 'Grad',
                                                   'Moment2'))
        b1p, b2p, lr = (jnp.reshape(ctx.get(op, slot), ()) for slot in (
            'Beta1Pow', 'Beta2Pow', 'LearningRate'))
        m1 = 0.1 * g
        m2 = 0.999 * m2 + 0.001 * jnp.square(g)
        ctx.set(op, 'ParamOut', p - lr * jnp.sqrt(1 - b2p) / (1 - b1p) * m1
                / (jnp.sqrt(m2) + 1e-8))
        ctx.set(op, 'Moment1Out', m1)
        ctx.set(op, 'Moment2Out', m2)
    return adam


@pytest.mark.parametrize('fault,numbers_over', [
    (None, []),
    (_weight_left_unchanged, ['param_change_rel_err.granite.l0.in_proj']),
    (_first_moment_not_carried, ['param_change_rel_err.granite.embed']),
], ids=['sound', 'weight_left_unchanged', 'first_moment_not_carried'])
def test_a_fault_in_the_lane_that_the_losses_pass_is_not_correct(
        fault, numbers_over, monkeypatch, capsys):
    """The cell's ``--cpu-tiny`` run with Adam's lowering replaced: every
    loss stays finite and falls (``failed`` 0, ``train_loop``'s rule), the
    first step agrees with the reference, and ``correct`` is false by the
    parameters' change."""
    import json
    import runpy
    import sys
    from paddle_tpu.ops import registry
    if fault:
        monkeypatch.setitem(registry._LOWERINGS, 'adam',
                            fault(registry._LOWERINGS['adam']))
    monkeypatch.setattr(sys, 'argv', [
        'run.py', '--workload', 'granite_h_train_1chip', '--seed',
        '2147483659', '--seconds', '0.5', '--trace', '0', '--cpu-tiny'])
    with pytest.raises(SystemExit) as exit_:
        runpy.run_path(os.path.join(BENCH, 'run.py'), run_name='__main__')
    assert exit_.value.code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    compared = json.loads(next(
        line for line in lines if line.startswith(
            'chipbench: reference after'))
        .split(') ', 2)[-1])
    over = [key for key, (value, limit) in compared.items()
            if not value <= limit]
    assert result['failed'] == 0
    assert result['correct'] is (fault is None)
    assert set(numbers_over) <= set(over)
    assert not [key for key in over if not key.startswith('param_change')]
