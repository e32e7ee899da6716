"""A gradient op that reads a parameter beside another differentiable
input ties the parameter's value to the gradients it yields for its other
inputs (``ops/registry.py:order_param_updates``, ISSUE 32), so that every
later in-place update of the parameter is ordered, in the data flow, after
the gradient op's reads of it: XLA then needs no copy of the carried (or
donated) weight.  The tie is an ``optimization_barrier``, an identity: the
same numbers come out, and the parameter's own gradient stays outside it.
"""

import numpy as np
import pytest

import jax
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.ops import registry

from helpers import assert_close_across_executables

B, D, H = 4, 8, 64
FEED = {'x': np.linspace(-1, 1, B * D, dtype='float32').reshape(B, D)}


def _two_layers(optimizer=True):
    """``fc(H)`` whose weight (D x H floats) is larger than its input's
    gradient (B x D), then ``fc(2)`` whose weight (H x 2) is smaller than
    its input's (B x H); ``x`` takes a gradient, as an activation does."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [D])
        x.stop_gradient = False
        hidden = fluid.layers.fc(x, H, act='relu')
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.fc(hidden, 2)))
        if optimizer:
            fluid.optimizer.Adam(0.05).minimize(loss)
        else:
            fluid.backward.append_backward(loss)
    return main, startup, loss


def _started(main, startup):
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return exe, scope


def _state(main, scope):
    return {v.name: np.asarray(scope.find_var(v.name).value())
            for v in main.list_vars()
            if v.persistable and scope.find_var(v.name) is not None}


def _barriers(jaxpr):
    """Every ``optimization_barrier`` equation of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'optimization_barrier':
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_barriers(sub))
    return found


def _untie(monkeypatch):
    monkeypatch.setattr(registry, 'order_param_updates',
                        lambda ctx, op, diff_names, grads: grads)


def test_one_barrier_holds_the_large_weight_and_its_input_gradient():
    main, startup, loss = _two_layers()
    exe, scope = _started(main, startup)
    _, _, feeds, block = exe._resolve_and_compile(
        main, FEED, [loss], scope, pop_readers=False)
    state_rw, state_ro, _ = block._materialize_args(scope, {})
    weight = next(n for n in state_rw if state_rw[n].shape == (D, H))
    closed = jax.make_jaxpr(block._fn)(
        state_rw, state_ro, feeds, exe._next_rng(main))
    names = jax.tree_util.tree_leaves(jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key, (state_rw, state_ro, feeds)))
    weight_var = closed.jaxpr.invars[names.index(weight)]
    barrier, = _barriers(closed.jaxpr)
    # the first mul_grad's: dX and W; no bias, not the second layer's
    # weight, and nothing of a parameter's shape but the parameter itself
    assert sorted(v.aval.shape for v in barrier.invars) == [(B, D), (D, H)]
    assert weight_var in barrier.invars
    # ...and the record says what the jaxpr shows (satellite 5): the two
    # biases and the small weight are left to the scheduler
    seen = trace.lowering_choices('param_update_order', seen=True)[-1]
    assert sorted(op['choice'] for op in seen.values()) == [
        'tied', 'untied', 'untied', 'untied']
    tied, = (op for op in seen.values() if op['choice'] == 'tied')
    assert tied == {'choice': 'tied', 'params': 1, 'mb': D * H * 4 / 1e6}
    assert trace.lowering_choices('param_update_order')[-1] == {
        'tied': 1, 'untied': 3}


def _train(k_lane):
    """Three steps from one start, through ``Executor.run`` or one K=3
    ``run_multi`` dispatch; (losses, the state after them)."""
    main, startup, loss = _two_layers()
    exe, scope = _started(main, startup)
    if k_lane:
        losses = exe.run_multi(main, feed_list=[FEED] * 3,
                               fetch_list=[loss], scope=scope)
    else:
        losses = [exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)[0]
                  for _ in range(3)]
    return np.asarray(losses), _state(main, scope)


@pytest.mark.parametrize('k_lane', [False, True], ids=['run', 'run_multi'])
def test_the_tie_changes_no_number(monkeypatch, k_lane):
    losses, state = _train(k_lane)
    assert trace.lowering_choices('param_update_order')[-1]['tied'] == 1
    with monkeypatch.context() as patch:
        _untie(patch)
        want_losses, want = _train(k_lane)
    assert sorted(state) == sorted(want) and len(state) > 8   # moments too
    assert_close_across_executables(losses, want_losses)
    for name in want:
        assert_close_across_executables(state[name], want[name],
                                        err_msg=name)


def test_gradients_without_an_optimizer_leave_the_parameters(monkeypatch):
    def fetch():
        main, startup, _ = _two_layers(optimizer=False)
        exe, scope = _started(main, startup)
        before = _state(main, scope)
        params = [p.name for p in main.global_block().all_parameters()]
        got = exe.run(main, feed=FEED, scope=scope, fetch_list=[
            n + '@GRAD' for n in params] + params)
        after = _state(main, scope)
        for name in before:
            np.testing.assert_array_equal(after[name], before[name], name)
        for name, fetched in zip(params, got[len(params):]):
            # a parameter fetched by name comes through the tie: the same
            np.testing.assert_array_equal(fetched, before[name], name)
        return got
    got = fetch()
    assert trace.lowering_choices('param_update_order')[-1]['tied'] == 1
    with monkeypatch.context() as patch:
        _untie(patch)
        want = fetch()
    for g, w in zip(got, want):
        assert_close_across_executables(g, w)


def _choices_under(axes, shard_first=False):
    """The step's record when the two-layer program trains over a mesh of
    ``axes``; (choices by the weight's bytes, the losses of three steps)."""
    from paddle_tpu import parallel
    mesh = parallel.make_mesh(
        axes, jax.devices()[:int(np.prod(list(axes.values())))])
    main, startup, loss = _two_layers()
    if shard_first:
        parallel.shard(next(p for p in main.global_block().all_parameters()
                            if p.shape == (D, H)), None, 'tp')
    exe, scope = _started(main, startup)
    runner = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                    scope=scope, mesh=mesh)
    losses = [float(np.asarray(runner.run([loss.name], feed=FEED)[0]).mean())
              for _ in range(3)]
    seen = trace.lowering_choices('param_update_order', seen=True)[-1]
    # the two weights' ops: a bias is at most H floats
    return sorted((op['mb'], op['choice']) for op in seen.values()
                  if op['mb'] > H * 4 / 1e6), losses


def test_under_a_mesh_the_sizes_are_those_one_device_holds():
    """The lowering holds global shapes.  Over ``dp`` = 4 a chip holds a
    quarter of the second layer's input gradient (B x H / 4 floats), now
    smaller than its weight (H x 2): tied, as the one-device program of a
    chip's own rows would be.  A weight split over ``tp`` counts as its
    half.  The numbers are the one-device program's."""
    small, large = H * 2 * 4 / 1e6, D * H * 4 / 1e6
    want, _ = _train(False)
    choices, losses = _choices_under({'dp': 4})
    assert choices == [(small, 'tied'), (large, 'tied')]
    assert_close_across_executables(losses, want.ravel())
    choices, _ = _choices_under({'dp': 1})
    assert choices == [(small, 'untied'), (large, 'tied')]
    choices, _ = _choices_under({'dp': 1, 'tp': 2}, shard_first=True)
    assert choices == [(small, 'untied'), (large / 2, 'tied')]


def test_a_conditional_scope_rebinds_nothing():
    """Loop and branch bodies are lowered with an ``env`` of their own: a
    parameter rebound there would not reach the optimizer."""
    main, startup, loss = _two_layers()
    block = main.global_block()
    grad_op = next(op for op in block.ops if op.type == 'mul_grad'
                   and (D, H) in [block.var(n).shape
                                  for n in op.input_arg_names])
    rng = np.random.RandomState(0)
    env = {n: jax.numpy.asarray(rng.standard_normal(
               [B if d < 0 else d for d in block.var(n).shape]), 'float32')
           for n in grad_op.input_arg_names}
    held = dict(env)
    ctx = registry.LoweringContext(block, env, place=fluid.CPUPlace(),
                                   conditional_scope=True)
    registry.run_op(ctx, grad_op)
    assert all(env[n] is held[n] for n in held)
    seen = trace.lowering_choices('param_update_order', seen=True)[-1]
    assert [op['choice'] for op in seen.values()] == ['untied']
    ctx = registry.LoweringContext(block, dict(held), place=fluid.CPUPlace())
    registry.run_op(ctx, grad_op)
    assert sum(ctx.env[n] is not held[n] for n in held) == 1   # the weight


def test_a_recurrence_with_weights_in_its_body_still_trains():
    """``recurrent_grad`` reads the body's weights beside the sequence's
    gradient, which is the larger here: noted, and left untied."""
    t, width = 6, 4
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [t, B, width], append_batch_size=False)
        x.stop_gradient = False
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            word = rnn.step_input(x)
            prev = rnn.memory(shape=[width], batch_ref=word,
                              ref_batch_dim_idx=0)
            hidden = fluid.layers.fc([word, prev], width, act='tanh')
            rnn.update_memory(prev, hidden)
            rnn.output(hidden)
        loss = fluid.layers.mean(fluid.layers.square(rnn()))
        fluid.optimizer.Adam(0.05).minimize(loss)
    exe, scope = _started(main, startup)
    feed = {'x': np.ones((t, B, width), 'float32')}
    before = _state(main, scope)
    losses = [exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
              for _ in range(4)]
    assert losses[-1] < losses[0]
    weights = [p.name for p in main.global_block().all_parameters()]
    after = _state(main, scope)
    assert weights and all((after[n] != before[n]).any() for n in weights)
    seen = trace.lowering_choices('param_update_order', seen=True)[-1]
    recurrent, = (op for out, op in seen.items() if op['params'] > 1)
    assert recurrent['choice'] == 'untied'
    assert recurrent['params'] == len(weights)


HLO = '''
%fused_computation.7 (param_0.1: f32[512,256]) -> f32[512,256] {
  %param_0.1 = f32[512,256]{1,0:T(8,128)} parameter(0)
  ROOT %copy.1 = f32[512,256]{0,1:T(8,128)} copy(%param_0.1)
}

%region_0.12.sunk (arg_tuple.0: (s32[], f32[8192,1024], f32[4096,1024], bf16[64,1024])) -> (s32[], f32[8192,1024], f32[4096,1024], bf16[64,1024]) {
  %arg_tuple.0 = (s32[]{:T(128)}, f32[8192,1024]{1,0:T(8,128)}, f32[4096,1024]{1,0:T(8,128)}, /*index=3*/bf16[64,1024]{1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.5 = f32[8192,1024]{1,0:T(8,128)} get-tuple-element(%arg_tuple.0), index=1
  %get-tuple-element.6 = f32[4096,1024]{1,0:T(8,128)} get-tuple-element(%arg_tuple.0), index=2
  %get-tuple-element.7 = bf16[64,1024]{1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.0), index=3
  %copy.20 = f32[8192,1024]{1,0:T(8,128)} copy(%get-tuple-element.5), backend_config={"estimated_cycles":"619865"}
  %slice-start.1 = ((f32[4096,1024]{1,0:T(8,128)}), f32[2048,1024]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) slice-start(%get-tuple-element.6), slice={[0:2048], [0:1024]}
  %slice-start.2 = ((f32[4096,1024]{1,0:T(8,128)}), f32[2048,1024]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) slice-start(%get-tuple-element.6), slice={[2048:4096], [0:1024]}
  %slice-done.1 = f32[2048,1024]{1,0:T(8,128)S(1)} slice-done(%slice-start.1)
  %slice-done.2 = f32[2048,1024]{1,0:T(8,128)S(1)} slice-done(%slice-start.2)
  %copy.21 = f32[2048,1024]{1,0:T(8,128)} copy(%slice-done.1)
  %custom-call.3 = f32[4096,1024]{1,0:T(8,128)S(1)} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  %copy.22 = f32[4096,1024]{1,0:T(8,128)} copy(%custom-call.3)
  %fusion.30 = bf16[64,8192]{1,0:T(8,128)(2,1)} fusion(%get-tuple-element.7, %copy.20), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(paddle_tpu_train_scan)/while/body/closed_call/paddle_tpu.step/mul.fc_0.tmp_0/dot_general"}
  %copy.23 = bf16[64,8192]{0,1:T(8,128)(2,1)} copy(%fusion.30)
  %fusion.31 = f32[8192,1024]{1,0:T(8,128)} fusion(%copy.20, %copy.23, %copy.22), kind=kOutput, calls=%fused_computation.7
  ROOT %tuple.9 = (s32[]{:T(128)}, f32[8192,1024]{1,0:T(8,128)}, f32[4096,1024]{1,0:T(8,128)}, /*index=3*/bf16[64,1024]{1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.7, %fusion.31, %copy.22, %get-tuple-element.7)
}

ENTRY %main.40 (state_rw__fc_0_w_0__.1: f32[8192,1024], state_ro__learning_rate_0__.1: f32[1], scanned__x__.1: f32[4096,1024]) -> (f32[8192,1024]) {
  %state_rw__fc_0_w_0__.1 = f32[8192,1024]{1,0:T(8,128)} parameter(0)
  %scanned__x__.1 = f32[4096,1024]{1,0:T(8,128)} parameter(2)
  %copy.30 = f32[8192,1024]{1,0:T(8,128)} copy(%state_rw__fc_0_w_0__.1)
  %copy.31 = f32[4096,1024]{1,0:T(8,128)} copy(%scanned__x__.1)
  %tuple.11 = (s32[]{:T(128)}, f32[8192,1024]{1,0:T(8,128)}, f32[4096,1024]{1,0:T(8,128)}, /*index=3*/bf16[64,1024]{1,0:T(8,128)(2,1)}) tuple(%constant.1, %copy.30, %copy.31, %constant.2)
  %while.2 = (s32[]{:T(128)}, f32[8192,1024]{1,0:T(8,128)}, f32[4096,1024]{1,0:T(8,128)}, /*index=3*/bf16[64,1024]{1,0:T(8,128)(2,1)}) while(%tuple.11), condition=%region_1.13, body=%region_0.12.sunk
  ROOT %get-tuple-element.9 = f32[8192,1024]{1,0:T(8,128)} get-tuple-element(%while.2), index=1
}
'''


def test_the_tool_lists_the_whole_copies_of_state():
    """``tools/compile_for_v5e.py:state_copies`` on a few lines of an
    optimized module: a copy of a carried value, one that memory-space
    assignment routed through slices, a donated argument's; not a slice's,
    a result's, a feed's, nor one inside a fusion."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools'))
    import compile_for_v5e
    body, entry = 'region_0.12.sunk', 'main.40'
    assert compile_for_v5e.state_copies(HLO) == [
        (8192 * 1024 * 4 / 1e6, 'loop body', body, 'copy.20',
         'f32[8192,1024]', 'get-tuple-element.5', 'fusion.30'),
        (8192 * 1024 * 4 / 1e6, 'fetched step', entry, 'copy.30',
         'f32[8192,1024]', 'state_rw__fc_0_w_0__.1', 'tuple.11'),
        (4096 * 1024 * 4 / 1e6, 'loop body', body, 'copy.22',
         'f32[4096,1024]', 'get-tuple-element.6', 'fusion.31')]
    written = compile_for_v5e.large_results(HLO, 1.0)
    assert [row[2] for row in written].count('copy.1') == 0
    assert ('bf16[64,8192]', 'jit(paddle_tpu_train_scan)/while/body/'
            'closed_call/paddle_tpu.step/mul.fc_0.tmp_0/dot_general') in [
                row[3:] for row in written]
