"""``fluid.trace``'s record of a lane's own executable (PR 36): the HLO-text
parser on an excerpt of v5e optimized HLO (``testdata/v5e_step_excerpt.hlo.
txt``: the shapes, layouts and metadata of ``tools/compile_for_v5e.py
nmt_train_1chip``'s output, cut to one loop body with a prefetch read by a
scoped fusion, a sliced prefetch read through a bitcast, a fusion that holds
a parameter's update beside its gradient's product, a scopeless copy at a
branch's root and a name two computations share), and the record of a small
program's train lane through both executors: what dispatching costs
(nothing JAX reports), what the first read costs, that a second read costs
nothing, and that the record does not keep a loaded program alive."""

import gc
import importlib.util
import logging
import os
import weakref

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import parallel
from paddle_tpu.fluid import hlo_text, trace
from paddle_tpu.ops import registry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LANE = 'paddle_tpu_train_scan'


@pytest.fixture(scope='module')
def rows():
    with open(os.path.join(HERE, 'testdata',
                           'v5e_step_excerpt.hlo.txt')) as f:
        return hlo_text.op_rows(f.read())


# ---- the parser -----------------------------------------------------------

@pytest.mark.parametrize('name, owner, why', [
    ('fusion.931', 'mul.fc_0.tmp_0', 'its own scope'),
    ('copy-done.35', 'mul.fc_0.tmp_0', 'a wait: the scoped fusion that '
     'reads it first'),
    ('copy-start.35', 'mul.fc_0.tmp_0', 'through its done half'),
    ('slice-done.24', 'mul.fc_1.tmp_0', 'a wait read through a bitcast'),
    ('slice-done.25', 'mul.fc_1.tmp_0', 'a wait printed in the long form '
     '(async-done of a computation that wraps a slice)'),
    ('bitcast.40', 'mul.fc_1.tmp_0', "its first reader's"),
    ('fusion.940', 'softmax_with_cross_entropy.loss',
     'no scope of its own, one scope inside'),
    ('reduce-window.3', 'softmax_with_cross_entropy.loss',
     "its first reader's owner"),
    ('copy-done.36', 'mul_grad.fc_0.tmp_0~GRAD',
     'read by the loop body\'s root alone: what made its operand'),
    ('copy.364', 'scale.beta1_pow_acc_0',
     "a branch's root: what made its operand"),
    ('compare.7', 'conditional_block.pow_0',
     'read by a whole branch with a scope'),
    ('copy.365', None, "a root whose operand is the branch's parameter"),
    ('add.7', None, "the lane's own counter: outside the step's scope"),
    ('while.610', None, 'the lane\'s loop'),
])
def test_owner_rule(rows, name, owner, why):
    assert rows[name]['owner'] == owner, why


def test_rows_are_the_operations_a_trace_prints(rows):
    # ENTRY, the loop's body and condition, both branches
    assert {r['computation'] for r in rows.values()} - {None} == {
        'main.134', 'region_0.77.sunk.clone', 'cond.78', 'branch_true.3',
        'branch_false.4'}
    # nothing of a fused computation, nothing of a reducer
    # nor of what an asynchronous pair wraps
    assert not {'convolution.135', 'multiply.135', 'convert.10',
                'add.1', 'reduce_sum.1', 'slice.33'} & set(rows)
    row = rows['divide_subtract_fusion.54']
    assert (row['opcode'], row['computation']) == (
        'fusion', 'region_0.77.sunk.clone')
    assert row['scope'] == 'mul_grad.fc_0.tmp_0~GRAD'
    assert row['op_name'].endswith(
        'paddle_tpu.step/mul_grad.fc_0.tmp_0~GRAD/transpose(jvp())/'
        'dot_general')
    # the update fused beside the product, and the constant XLA shares
    assert row['inside'] == ['adam.fc_0.w_0', 'adam.fc_1.w_0',
                             'mul_grad.fc_0.tmp_0~GRAD']
    assert abs(row['mb'] - 2 * 512 * 512 * 4 / 1e6) < 1e-9
    assert rows['copy-done.35']['scope'] is None
    assert rows['copy-done.35']['op_name'] is None
    assert abs(rows['slice-done.24']['mb'] - 512 * 512 * 4 / 1e6) < 1e-9
    # a generic async pair is named by the operation it wraps, as the
    # short form is: the chip prints sliced prefetches long
    assert rows['slice-start.25']['opcode'] == 'slice-start'
    assert rows['slice-done.25']['opcode'] == rows['slice-done.24'][
        'opcode'] == 'slice-done'


def test_a_name_two_computations_use_owns_nothing(rows):
    row = rows['bitcast.99']
    assert row['owner'] is None and row['scope'] is None
    assert row['opcode'] == 'bitcast' and row['inside'] == []
    assert [r['computation'] for r in row['rows']] == [
        'branch_true.3', 'branch_false.4']
    assert row['rows'][0]['owner'] == 'scale.beta1_pow_acc_0'


@pytest.mark.parametrize('name, moves', [
    ('copy-done.35', 'state_rw__fc_0_w_0__.1'),    # a carried weight
    ('slice-done.24', 'state_rw__fc_1_w_0__.1'),   # a slice of one
    ('slice-start.24', 'state_rw__fc_1_w_0__.1'),
    ('slice-done.25', 'state_rw__fc_1_w_0__.1'),   # the long form
    ('copy-done.36', 'mul_grad.fc_0.tmp_0~GRAD'),  # what the step made
    ('fusion.931', None),                          # no pair
])
def test_what_a_pair_moves(rows, name, moves):
    assert rows[name]['moves'] == moves


@pytest.mark.parametrize('op_name', [
    'jit(paddle_tpu_train_scan)/while/body/closed_call/paddle_tpu.step/'
    'mul_grad.rms_norm_14.tmp_0~GRAD/transpose(jvp())/dot_general',
    'jit(paddle_tpu_train_scan)/while/body/paddle_tpu.step/recurrent.'
    'gru_unit_0.tmp_2~rnn_out/while/body/closed_call/checkpoint/'
    'mul.fc_4.tmp_0/convert_element_type',
    'jit(paddle_tpu_train_scan)/paddle_tpu.step/transpose(jvp(mul.fc_0'
    '.tmp_0))/dot_general:',
    'jit(paddle_tpu_train_scan)/while/body/add',
    'jit(paddle_tpu_train_scan)/paddle_tpu.step/reshape',
    'mul.fc_0.tmp_0/dot_general',
    '',
])
def test_scope_rule_is_the_trace_readers(op_name):
    """``hlo_text.fluid_scope`` on an ``op_name`` and ``chipbench/scopes
    .py:fluid_scope`` on a trace's ``tf_op`` name the same Fluid op."""
    spec = importlib.util.spec_from_file_location(
        'scopes_for_hlo_text', os.path.join(ROOT, 'chipbench', 'scopes.py'))
    scopes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scopes)
    assert hlo_text.fluid_scope(op_name) == scopes.fluid_scope(
        op_name, scopes.load_classes())[1]


def test_the_tool_has_no_parser_of_its_own():
    spec = importlib.util.spec_from_file_location(
        'compile_for_v5e_under_test',
        os.path.join(ROOT, 'tools', 'compile_for_v5e.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.operations is hlo_text.operations
    assert tool._mb is hlo_text.mb and tool.MOVES is hlo_text.MOVES
    assert tool.op_rows is hlo_text.op_rows
    with open(tool.__file__) as f:
        source = f.read()
    assert '.lower(' not in source and 'aot_compile' in source


# ---- the record -----------------------------------------------------------

def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [16])
        y = fluid.layers.data('y', [1])
        hidden = fluid.layers.fc(x, 32, act='relu')
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(hidden, 1), y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def feed_list(rows_=8, steps=3):
    rng = np.random.RandomState(0)
    return [{'x': rng.rand(rows_, 16).astype('float32'),
             'y': rng.rand(rows_, 1).astype('float32')}
            for _ in range(steps)]


def compiles(since):
    return [(e['kind'], e['fun_name']) for e in trace.compile_log()[since:]
            if e['kind'] in ('lower', 'backend_compile')]


def lane_of(kind, main, startup, loss, scope):
    """run(feed_list) of the train lane on one device or on a dp=4 mesh."""
    import jax
    if kind == 'executor':
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe, lambda lots: exe.run_multi(
            main, feed_list=lots, fetch_list=[loss])
    fluid.Executor(fluid.CPUPlace()).run(startup)
    pe = fluid.ParallelExecutor(
        loss_name=loss.name, main_program=main, scope=scope,
        mesh=parallel.make_mesh({'dp': 4}, jax.devices()[:4]))
    return pe, lambda lots: pe.run_multi([loss], feed_list=lots)


@pytest.mark.parametrize('kind', ['executor', 'parallel_executor'])
def test_record_of_a_lane_and_what_it_costs(kind):
    main, startup, loss = build()
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        runner, run = lane_of(kind, main, startup, loss, scope)
        run(feed_list())
        mark = len(trace.compile_log())
        run(feed_list())
        run(feed_list())
        # dispatching lowers and compiles nothing it did not before
        assert compiles(mark) == []
        made = trace.executable_record(LANE)
        # nor does the first read while the executor lives: JAX's caches
        # hand back the executable the lane runs
        assert made['live'] and compiles(mark) == []
        assert trace.executable_record(LANE) is made
        assert compiles(mark) == []
    assert made['fun_name'] == LANE
    assert made['memory']['temp'] > 0 and made['memory']['argument'] > 0
    assert set(made['seconds']) == {'compile', 'text', 'parse'}
    named = set()
    for row in made['ops'].values():
        named.update(row['inside'] + [row['scope']])
    ops = [registry.op_scope_name(op) for op in main.global_block().ops]
    # every op whose work reaches the device under its own name: the CPU
    # compiler folds a fill_constant and two element-wise gradients away
    kept = [s for s in ops if s.split('.')[0] in (
        'mul', 'mul_grad', 'adam', 'relu', 'relu_grad', 'mean',
        'elementwise_add', 'scale')]
    assert len(kept) >= 14 and set(kept) <= named
    assert len(named & set(ops)) >= 0.8 * len(set(ops))
    if kind == 'parallel_executor':
        assert 'all-reduce' in {r['opcode'] for r in made['ops'].values()}
    del runner, run


def test_a_new_signature_is_a_new_record():
    main, startup, loss = build()
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])
        first = trace.executable_record(LANE)
        exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])
        assert trace.executable_record(LANE) is first
        exe.run_multi(main, feed_list=feed_list(rows_=4), fetch_list=[loss])
        second = trace.executable_record(LANE)
    assert second is not first
    assert second['memory']['argument'] < first['memory']['argument']


def test_record_outlives_the_executor_and_pins_no_loaded_program():
    main, startup, loss = build()
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])
        block = next(b for b in exe._cache.values() if b._lane_jits)
        jitted = weakref.ref(next(iter(block._lane_jits.values())))
        with_amp = trace.aot_compile(jitted(), trace._executables[LANE][
            'args']).memory_analysis().temp_size_in_bytes
    del exe, block
    gc.collect()
    # what the registry keeps holds neither the jitted function nor the
    # block that caches it: the loaded program went with the executor
    assert jitted() is None
    mark = len(trace.compile_log())
    assert not registry.amp_enabled()
    made = trace.executable_record(LANE)
    assert not registry.amp_enabled()
    assert made is not None and not made['live']
    # the body was traced again, under the mixed precision it ran with
    assert ('lower', 'jit(%s)' % LANE) in compiles(mark)
    assert made['memory']['temp'] == with_amp
    mark = len(trace.compile_log())
    assert trace.executable_record(LANE) is made and compiles(mark) == []


def test_no_record_without_a_lane_and_none_on_failure(monkeypatch, caplog):
    assert trace.executable_record('paddle_tpu_no_such_lane') is None
    main, startup, loss = build()
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])

        def no_text(hlo):
            raise RuntimeError('RESOURCE_EXHAUSTED: planted')

        monkeypatch.setattr(hlo_text, 'op_rows', no_text)
        with caplog.at_level(logging.WARNING, logger='paddle_tpu'):
            assert trace.executable_record(LANE) is None
        assert 'RESOURCE_EXHAUSTED: planted' in caplog.text
        # the outcome is kept: four readers do not try four times
        monkeypatch.undo()
        assert trace.executable_record(LANE) is None


def test_cost_accounting_compiles_each_executable_once():
    main, startup, loss = build()
    fluid.FLAGS.cost_accounting = True
    try:
        with fluid.scope_guard(fluid.core.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            mark = len(trace.compile_log())
            exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])
            exe.run_multi(main, feed_list=feed_list(), fetch_list=[loss])
            entry, = [e for e in exe.cost_report()
                      if e['kind'] == 'multi']
            stats = exe.memory_analysis(main, feed=feed_list()[0],
                                        fetch_list=[loss])
    finally:
        fluid.FLAGS.cost_accounting = False
    # the analysis and the dispatch share one lowering and one compile
    assert compiles(mark).count(('lower', 'jit(%s)' % LANE)) == 1
    assert compiles(mark).count(('backend_compile', 'jit(%s)' % LANE)) == 1
    assert entry['kind'] == 'multi' and entry['flops'] > 0
    assert entry['temp_bytes'] > 0 and stats.temp_size_in_bytes > 0
