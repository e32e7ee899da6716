"""On one device, persistable state reaches ``jax.jit`` committed to the
executor's device from the first dispatch on (ISSUE 29): a startup
program's outputs are uncommitted arrays (its jit has no committed
input), a step's outputs come back committed, and ``jax.jit`` lowers and
compiles once for each of the two signatures.  Staging commits the
uncommitted array where it lies, so ``Executor.run`` and every lane
present ONE signature and compile once.
"""

import numpy as np
import pytest

import jax
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace

from test_scan_lanes import BUILD, LANES

D = 4


def _run_path():
    x = fluid.layers.data('x', [D])
    loss = fluid.layers.mean(fluid.layers.square(fluid.layers.fc(x, 3)))
    fluid.optimizer.Adam(0.1).minimize(loss)
    feed = {'x': np.ones((8, D), 'float32')}

    def dispatch(exe, k, program, scope):
        exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
    return dispatch


PATHS = dict(BUILD, run=_run_path)
NAMES = dict(LANES, run='paddle_tpu_step')


def _started(build):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dispatch = build()
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return exe, main, scope, dispatch


def _weight(main):
    return [p.name for p in main.global_block().all_parameters()
            if p.name.endswith('.w_0')][0]


def _compiles(fun_name, since):
    return sorted(e['kind'] for e in trace.compile_log()[since:]
                  if e['kind'] in ('lower', 'backend_compile')
                  and e['fun_name'] == 'jit(%s)' % fun_name)


@pytest.mark.parametrize('path', sorted(PATHS))
def test_startup_then_one_dispatch_is_the_only_compile(path):
    exe, main, scope, dispatch = _started(PATHS[path])
    n = len(trace.compile_log())
    dispatch(exe, 2, program=main, scope=scope)
    assert _compiles(NAMES[path], n) == ['backend_compile', 'lower']
    n = len(trace.compile_log())
    dispatch(exe, 2, program=main, scope=scope)
    assert _compiles(NAMES[path], n) == []


def _persistables(main, scope):
    return {v.name: scope.find_var(v.name).value()
            for v in main.list_vars()
            if v.persistable and scope.find_var(v.name) is not None
            and isinstance(scope.find_var(v.name).value(), jax.Array)}


def test_staging_commits_where_the_array_lies_and_the_scope_keeps_it():
    exe, main, scope, _ = _started(_run_path)
    before = _persistables(main, scope)
    assert before and not any(a.committed for a in before.values())
    _, _, feed_arrays, compiled = exe._resolve_and_compile(
        main, {'x': np.ones((8, D), 'float32')}, [], scope,
        pop_readers=False)
    # the learning rate is read-only state, the weights and Adam's
    # moments are donated: both kinds are committed
    assert compiled.state_rw and compiled.state_ro
    state_rw, state_ro, _ = compiled._stage_state(scope, feed_arrays)
    staged = dict(state_rw, **state_ro)
    device = fluid.CPUPlace().jax_device()
    for name, arr in staged.items():
        assert arr.committed and arr.devices() == {device}, name
        assert arr.unsafe_buffer_pointer() == \
            before[name].unsafe_buffer_pointer(), name
        assert scope.find_var(name).value() is arr, name
    # a second staging finds them committed: the same objects again
    again_rw, again_ro, _ = compiled._stage_state(scope, feed_arrays)
    assert all(v is staged[n] for n, v in dict(again_rw, **again_ro).items())


def test_state_on_another_device_is_moved_and_the_scope_keeps_its_own():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = fluid.layers.fc(fluid.layers.data('x', [D]), 3)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    name = _weight(main)
    far = jax.device_put(np.asarray(scope.find_var(name).value()),
                         jax.devices('cpu')[1])
    scope.find_var(name).set_value(far)
    _, _, feed_arrays, compiled = exe._resolve_and_compile(
        main, {'x': np.ones((8, D), 'float32')}, [out], scope,
        pop_readers=False)
    assert name in compiled.state_ro
    _, state_ro, _ = compiled._stage_state(scope, feed_arrays)
    assert state_ro[name].devices() == {fluid.CPUPlace().jax_device()}
    assert scope.find_var(name).value() is far


@pytest.mark.parametrize('path', ['run', 'train'])
def test_a_training_dispatch_still_donates_the_startup_state(path):
    exe, main, scope, dispatch = _started(PATHS[path])
    name = _weight(main)
    startup_array = scope.find_var(name).value()
    dispatch(exe, 2, program=main, scope=scope)
    # the committed alias was donated: the buffer under both is gone,
    # and the step's output took the variable over
    assert startup_array.is_deleted()
    now = scope.find_var(name).value()
    assert now.committed and not now.is_deleted()
