"""The four readers PR 36 added, which join a traced run's operations
(``chipbench/scopes.py``'s ``worst['ops']``: name -> self time, bucket) with
the program's record of its own step executable
(``fluid.trace.executable_record``) by operation name: on a fabricated table
and a fabricated record whose sums are made by hand, and nothing where there
is no trace, where the program keeps no record, where it has no such leg at
all (the parent of PR 36) and where making the record raises."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark  # noqa: E402

from paddle_tpu.fluid import trace  # noqa: E402

NAMES = ['unscoped_owned_share.train', 'prefetch_wait_device_ms.train',
         'update_fused_device_ms.train', 'step_temp_gb.train']
TRACED = NAMES[:3]
CELL = {'name': 'granite_h_train_1chip', 'config': 'granite-4.0-h-micro',
        'traffic': 'zipf_b1_l1024'}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        'cb_' + name.replace('.', '_'),
        os.path.join(BENCH, 'layer_metrics', name + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def row(opcode, owner=None, inside=(), scope=None):
    return {'opcode': opcode, 'computation': 'body', 'mb': 1.0,
            'op_name': None, 'scope': scope, 'inside': list(inside),
            'owner': owner or scope, 'moves': None}


# seconds over 5 runs of 2 steps: 10 steps in the stretch
OPS = {
    # a wait for a prefetched weight, owned by the product that reads it
    'copy-done.35': (0.020, 'unscoped', row('copy-done', 'mul.fc_1.tmp_0')),
    'slice-done.24': (0.010, 'unscoped', row('slice-done', 'mul.fc_1.tmp_0')),
    # a wait nothing owns (the lane's carried feed block)
    'copy-done.801': (0.002, 'unscoped', row('copy-done')),
    # a layout copy the compiler made, owned; a reshape no rule reaches
    'copy.365': (0.005, 'unscoped', row('copy', 'lstm.lstm_0.tmp_0')),
    'reshape.9': (0.003, 'unscoped', row('reshape')),
    # a scoped wait (XLA kept the scope): a wait all the same
    'copy-done.7': (0.004, 'other', row(
        'copy-done', scope='rms_norm.rms_norm_0.tmp_0')),
    # the product that carries a parameter's update, and one that does not
    'divide_subtract_fusion.442': (0.300, 'matmul', row(
        'fusion', scope='mul_grad.rms_norm_14.tmp_0~GRAD',
        inside=['adam.granite.l7.in_proj',
                'mul_grad.rms_norm_14.tmp_0~GRAD'])),
    'fusion.931': (0.200, 'matmul', row(
        'fusion', scope='mul.fc_0.tmp_0', inside=['mul.fc_0.tmp_0'])),
    # an update that stands alone is the optimizer bucket's already
    'fusion.77': (0.050, 'optimizer', row(
        'fusion', scope='adam.granite.embed',
        inside=['adam.granite.embed'])),
    # the accumulators' scale is of the optimizer class by its variable
    'fusion.78': (0.006, 'other', row(
        'fusion', scope='elementwise_mul.tmp_3',
        inside=['elementwise_mul.tmp_3', 'scale.beta1_pow_acc_0'])),
    # an operation the record does not know (another program's)
    'fusion.1': (0.001, 'unscoped', None),
}
WANT = {
    # owned 0.020 + 0.010 + 0.005 of 0.041 unscoped
    'unscoped_owned_share.train': 100.0 * 0.035 / 0.041,
    # (0.020 + 0.010 + 0.002 + 0.004) s over 10 steps
    'prefetch_wait_device_ms.train': 3.6,
    # (0.300 + 0.006) s over 10 steps
    'update_fused_device_ms.train': 30.6,
    'step_temp_gb.train': 5.45,
}


def fabricated(monkeypatch, made='default'):
    """What run.py hands a reader after a traced run, and the program's
    record behind ``fluid.trace.executable_record``."""
    if made == 'default':
        made = {'fun_name': 'paddle_tpu_train_scan', 'live': False,
                'memory': {'argument': int(8.0e9), 'output': 0, 'alias': 0,
                           'temp': int(5.45e9), 'generated_code': 0},
                'ops': {n: r for n, (_, _, r) in OPS.items() if r},
                'seconds': {'compile': 0.0, 'text': 0.0, 'parse': 0.0}}
    asked = []

    def executable_record(fun_name):
        asked.append(fun_name)
        if isinstance(made, Exception):
            raise made
        return made

    monkeypatch.setattr(trace, 'executable_record', executable_record,
                        raising=False)
    worst = {'scoped': True, 'step_runs': 5, 'busy_s': 0.6,
             'ops': {n: {'self_s': s, 'bucket': b, 'scope': None,
                         'tf_op': None} for n, (s, b, _) in OPS.items()}}
    return {'trace': {'busy_s': 0.6}, 'steps_per_dispatch': 2, 'cell': CELL,
            'scopes': {'worst': worst}}, asked


@pytest.mark.parametrize('name', NAMES)
def test_reader_agrees_with_its_entry(name):
    entries = benchmark()['per_layer']
    entry = next(m for m in entries if m['name'] == name)
    module = reader(name)
    assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
            module.MOVES) == (entry['layer'], entry['unit'],
                              entry['better'], entry['source'],
                              entry['moves'])
    # every cell reports them; appended in this order, after PR 34's
    assert 'workloads' not in entry
    names = [m['name'] for m in entries]
    first = names.index(NAMES[0])
    assert names[first:first + 4] == NAMES
    assert first > names.index('moe_experts_roofline.train')


@pytest.mark.parametrize('name', NAMES)
def test_reader_on_a_fabricated_table_and_record(name, monkeypatch):
    record, asked = fabricated(monkeypatch)
    assert reader(name).read(record) == pytest.approx(WANT[name], rel=1e-12)
    assert set(asked) == {'paddle_tpu_train_scan'}


@pytest.mark.parametrize('name', TRACED)
def test_nothing_without_a_trace(name, monkeypatch):
    record, asked = fabricated(monkeypatch)
    record['trace'] = None
    assert reader(name).read(record) is None
    assert reader(name).read({'trace': None}) is None
    assert asked == []   # and the program is not asked to make a record


@pytest.mark.parametrize('name', NAMES)
@pytest.mark.parametrize('made', [
    None, RuntimeError('RESOURCE_EXHAUSTED: planted'),
    {'fun_name': 'paddle_tpu_train_scan', 'memory': None, 'ops': {}}],
    ids=['no_record', 'program_raises', 'empty_record'])
def test_nothing_without_the_record(name, made, monkeypatch):
    record, _ = fabricated(monkeypatch, made)
    assert reader(name).read(record) is None


@pytest.mark.parametrize('name', NAMES)
def test_nothing_from_a_program_without_the_leg(name, monkeypatch):
    """PR 36's parent: ``fluid.trace`` has no ``executable_record``."""
    record, _ = fabricated(monkeypatch)
    monkeypatch.delattr(trace, 'executable_record')
    assert reader(name).read(record) is None


def test_zero_where_the_step_has_no_such_operation(monkeypatch):
    record, _ = fabricated(monkeypatch)
    ops = record['scopes']['worst']['ops']
    for gone in [n for n in ops if 'done' in n or 'divide' in n
                 or n == 'fusion.78']:
        del ops[gone]
    assert reader('prefetch_wait_device_ms.train').read(record) == 0.0
    assert reader('update_fused_device_ms.train').read(record) == 0.0
    # 0.005 of the 0.009 left unscoped is owned
    assert reader('unscoped_owned_share.train').read(record) == \
        pytest.approx(100.0 * 0.005 / 0.009)
    for name in [n for n, op in ops.items() if op['bucket'] == 'unscoped']:
        del ops[name]
    assert reader('unscoped_owned_share.train').read(record) is None


def test_an_unscoped_program_reads_nothing(monkeypatch):
    record, _ = fabricated(monkeypatch)
    record['scopes']['worst']['scoped'] = False
    for name in TRACED:
        assert reader(name).read(record) is None


def test_the_tool_prints_the_readers_sums(monkeypatch):
    """``tools/step_ops_table.py`` only groups and prints: its sums are the
    readers', from the one join (``chipbench/executable_ops.py``)."""
    record, _ = fabricated(monkeypatch)
    spec = importlib.util.spec_from_file_location(
        'step_ops_table', os.path.join(os.path.dirname(BENCH), 'tools',
                                       'step_ops_table.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ops = tool._executable_ops()
    text = '\n'.join(tool.tables(ops.joined(record), ops.program_record()))
    assert '10 steps in the stretch' in text
    # all but fusion.1's 0.001 of the 0.601 s is under names the record holds
    assert 'the record holds: %.4f%%' % (100.0 * 0.600 / 0.601) in text
    assert "unknown: [('fusion.1', 0.1)]" in text
    assert 'unscoped 4.1000 ms a step' in text
    assert 'prefetch waits %.4f ms a step' % WANT[
        'prefetch_wait_device_ms.train'] in text
    assert 'outside the optimizer bucket %.4f ms a step' % WANT[
        'update_fused_device_ms.train'] in text
    assert "no owner (ms a step): [('reshape.9', 0.3), " \
        "('copy-done.801', 0.2), ('fusion.1', 0.1)]" in text
