"""The one scan-lane mechanism under both executors (ISSUE 28): every lane
(train, eval, decode, chunk) of `_CompiledBlock` is cached, jitted,
compile-counted and launched by the same code, and `Executor` and
`ParallelExecutor` share one private dispatch signature per lane.

For each lane x executor: a repeated signature compiles nothing — by the
executor's own count and by what JAX reports; a new static value (steps,
chunk width) compiles exactly once; the dispatch takes ``program=`` /
``scope=`` on either executor; and the state the lane's program writes is
in the scope afterwards.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace

D, V, S = 4, 6, 8   # S rows / slots: the CPU mesh's dp extent divides it
LANES = {'train': 'paddle_tpu_train_scan', 'eval': 'paddle_tpu_eval_scan',
         'decode': 'paddle_tpu_decode_scan',
         'chunk': 'paddle_tpu_chunk_prefill'}


def _ticks():
    """A persistable counter the program bumps once a step: the state a
    lane must thread through its loop and write back."""
    t = fluid.layers.create_global_var([1], 0.0, 'float32',
                                       persistable=True, name='ticks')
    fluid.layers.increment(t)


def _slot_carry():
    return {'slots': {'h': np.zeros((S, D), 'float32')},
            'token': np.ones((S, 1), np.int64),
            'alive': np.ones((S, ), bool),
            'remaining': np.full((S, ), 99, np.int32)}


def _train_lane():
    x = fluid.layers.data('x', [D])
    loss = fluid.layers.mean(fluid.layers.square(fluid.layers.fc(x, 3)))
    fluid.optimizer.SGD(0.1).minimize(loss)
    _ticks()
    lot = {'x': np.ones((S, D), 'float32')}

    def dispatch(exe, k, **bound):
        exe._dispatch_multi_scanned(
            [loss], lot, {'x': np.stack([lot['x']] * k)}, k, **bound)
        return k
    return dispatch


def _eval_lane():
    x = fluid.layers.data('x', [D])
    out = fluid.layers.fc(x, 3)
    _ticks()

    def dispatch(exe, k, **bound):
        exe._dispatch_eval_multi(
            [out], feed_list=[{'x': np.ones((S, D), 'float32')}] * k,
            **bound)
        return k
    return dispatch


def _step_state(token_f32):
    h = fluid.layers.data('h', [D])
    return fluid.layers.fc(fluid.layers.concat([token_f32, h], axis=1), D,
                           act='tanh')


def _decode_lane():
    tok = fluid.layers.data('tok', [1], dtype='int64')
    h2 = _step_state(fluid.layers.cast(tok, 'float32'))
    logits = fluid.layers.fc(h2, V)
    _ticks()
    spec = {'token': 'tok', 'logits': logits, 'state': [('h', h2)],
            'end_id': 0}

    def dispatch(exe, k, **bound):
        exe._dispatch_decode_multi(carry=_slot_carry(), steps=k,
                                   decode=spec, **bound)
        return k
    return dispatch


def _chunk_lane():
    ctok = fluid.layers.data('ctok', [-1, 1], dtype='int64')
    h2 = _step_state(fluid.layers.reduce_mean(
        fluid.layers.cast(ctok, 'float32'), dim=1))
    _ticks()
    spec = {'token': 'ctok', 'state': [('h', h2)], 'start_id': 1}
    aux = {'active': np.ones((S, ), bool), 'finish': np.zeros((S, ), bool),
           'budget': np.zeros((S, ), np.int32)}

    def dispatch(exe, width, **bound):
        exe._dispatch_chunk_prefill(
            feed={'ctok': np.ones((S, width, 1), np.int64)},
            carry=_slot_carry(), aux=aux, chunk=spec, **bound)
        return 1   # one advance of the chunk program a dispatch
    return dispatch


BUILD = {'train': _train_lane, 'eval': _eval_lane, 'decode': _decode_lane,
         'chunk': _chunk_lane}


def _backend_compiles(fun_name):
    return sum(1 for e in trace.compile_log()
               if e['kind'] == 'backend_compile'
               and e['fun_name'] == 'jit(%s)' % fun_name)


@pytest.mark.parametrize('executor', ['Executor', 'ParallelExecutor'])
@pytest.mark.parametrize('lane', sorted(LANES))
def test_lane_contract(lane, executor):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dispatch = BUILD[lane]()
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    if executor == 'Executor':
        exe = fluid.Executor(fluid.CPUPlace())
    else:
        exe = fluid.ParallelExecutor(main_program=main, scope=scope)
        assert exe.device_count == 8
        with pytest.raises(ValueError, match='its OWN main_program'):
            dispatch(exe, 2, program=fluid.Program(), scope=scope)
    # the same keywords on either executor: the callers never branch
    bound = {'program': main, 'scope': scope}

    def counts():
        return exe.compile_count, _backend_compiles(LANES[lane])

    # one dispatch compiles, and the contract starts: the start-up
    # state is staged committed (test_executor_state_commit.py)
    ticks = dispatch(exe, 2, **bound)
    first = counts()
    ticks += dispatch(exe, 2, **bound)
    assert counts() == first, 'a repeated signature compiled'
    ticks += dispatch(exe, 3, **bound)
    # steps is a static argument of the lane's executable: one compile.
    # The chunk width is a feed's shape: a block for the new signature
    # (the executor's count only) and that block's one chunk executable
    own = 2 if lane == 'chunk' else 1
    assert counts() == (first[0] + own, first[1] + 1)
    # what the lane's program wrote is in the scope
    assert float(np.asarray(scope.find_var('ticks').value())[0]) == ticks
    if executor == 'ParallelExecutor':
        assert exe.dispatch_count == 3
