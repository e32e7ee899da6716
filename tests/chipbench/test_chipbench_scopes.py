"""chipbench/scopes.py and the thirteen readers over it, against the two
traces recorded on the v5e: ``probe.xplane.pb`` (PR 23; one
``jax.named_scope`` of its own) and ``scoped.xplane.pb.gz`` (PR 24; a toy
transformer through Executor + FeedPipeline, with the program's own scopes
and spans)."""

import gzip
import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark, run_cell  # noqa: E402


def by_path(*parts):
    spec = importlib.util.spec_from_file_location(
        'cb_' + parts[-1].replace('.', '_'), os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


scopes = by_path('scopes.py')
xplane = scopes.xplane
PROBE = os.path.join(BENCH, 'testdata', 'probe.xplane.pb')
# the probe's program is not the repo's: its step is ``jit(small_step)``
# and its one scope has no variable
PROBE_CLASSES = {'step_scope': 'small_step',
                 'scope_pattern': '^(probe_[a-z]+)()$',
                 'classes': {'matmul': ['probe_matmul']}}
NEW = ['matmul_device_ms.train', 'attention_device_ms.train',
       'recurrence_device_ms.train', 'loss_device_ms.train',
       'optimizer_device_ms.train', 'other_ops_device_ms.train',
       'scan_lane_device_ms.train', 'unscoped_device_share.train',
       'host_dispatch_ms.train', 'feed_stage_ms.train',
       'xla_compiles_in_window.train', 'lowering_s.setup',
       'compile_or_load_s.setup']
PARTITION = NEW[:7]


def test_metadata_of_the_recorded_operation():
    dev = scopes.load(PROBE)['devices'][0]
    by_name = {xplane.op_name(m['name']): m['stats']
               for m in dev['metadata'].values()}
    stats = by_name['convolution_tanh_fusion']
    assert stats['tf_op'] == 'jit(small_step)/probe_matmul/dot_general:'
    assert stats['hlo_category'] == 'convolution fusion'
    assert stats['flops'] == 17188257792
    assert stats['bytes_accessed'] == 25165824
    assert scopes.fluid_scope(stats['tf_op'], PROBE_CLASSES) == (
        True, 'probe_matmul')
    assert 'tf_op' not in by_name['copy-done']


def test_busy_time_is_xplanes_and_the_buckets_partition_it():
    ours = scopes.reduce(PROBE, PROBE_CLASSES)['worst']
    theirs = xplane.reduce(PROBE)['worst']
    # whole nanoseconds here, float seconds there
    assert ours['busy_s'] == pytest.approx(theirs['busy_s'], rel=1e-12)
    assert ours['window_s'] == pytest.approx(theirs['window_s'], rel=1e-12)
    assert ours['step_runs'] == theirs['step_runs'] == 6
    assert sum(ours['buckets'].values()) == pytest.approx(
        ours['busy_s'], rel=1e-12)
    # six runs of the scoped product; the unscoped product and the copies
    # inside the step; the other program's reduction outside it
    assert set(ours['buckets']) == {'matmul', 'unscoped', 'scan_lane'}
    assert ours['scopes']['probe_matmul']['flops'] == 6 * 17188257792
    assert ours['scopes']['probe_matmul']['categories'] == {
        'convolution fusion': ours['buckets']['matmul']}
    assert ours['ops']['fusion']['bucket'] == 'unscoped'
    assert ours['ops']['multiply_reduce_fusion']['bucket'] == 'scan_lane'
    assert ours['scoped'] is True
    # the repo's own class file finds no step scope in this program
    assert scopes.reduce(PROBE)['worst']['scoped'] is False


def test_fluid_scope_is_the_innermost_op_under_the_step():
    classes = scopes.load_classes()
    cases = {
        'jit(paddle_tpu_train_scan)/while/body/paddle_tpu.step/'
        'mul.fc_3.tmp_0/dot_general:': (True, 'mul.fc_3.tmp_0'),
        'jit(paddle_tpu_train_scan)/while/body/dynamic_slice:': (False, None),
        'jit(paddle_tpu_step)/paddle_tpu.step/recurrent.out~rnn_out/while/'
        'body/closed_call/checkpoint/gru_unit.h_0/transpose(jvp('
        'mul.fc_21.tmp_0))/dot_general:': (True, 'mul.fc_21.tmp_0'),
        'jit(paddle_tpu_step)/transpose(paddle_tpu.step)/'
        'relu_grad.fc_0.tmp_1~GRAD/jvp()/select_n:':
            (True, 'relu_grad.fc_0.tmp_1~GRAD'),
        'jit(paddle_tpu_step)/paddle_tpu.step/jit(_where)/select_n:':
            (True, None),
        None: (False, None),
    }
    for tf_op, want in cases.items():
        assert scopes.fluid_scope(tf_op, classes) == want, tf_op
    for scope, want in {
            'mul.fc_3.tmp_0': 'matmul', 'mul_grad.x~GRAD': 'matmul',
            'softmax_grad.a': 'attention', 'recurrent.out': 'recurrence',
            'softmax_with_cross_entropy.l': 'loss', 'adam.fc_0.w_0':
            'optimizer', 'scale.beta1_pow_acc_0': 'optimizer',
            'scale.emb_scaled': 'other', 'lookup_table_grad.e': 'other',
            'layer_norm.y': 'other'}.items():
        assert scopes.class_of(scope, classes) == want, scope


def test_gap_takes_the_innermost_span_over_it():
    spans = [(0.0, 10.0, 'paddle_tpu/feed/deliver', 'main'),
             (2.0, 9.0, 'paddle_tpu/executor/dispatch', 'main'),
             (3.0, 5.0, 'paddle_tpu/executor/stage_state', 'main')]
    assert scopes.label_gap((3.5, 4.5), spans) == \
        'paddle_tpu/executor/stage_state'
    assert scopes.label_gap((6.0, 8.0), spans) == \
        'paddle_tpu/executor/dispatch'
    assert scopes.label_gap((11.0, 12.0), spans) == 'unattributed'


@pytest.mark.parametrize('name', NEW)
def test_new_reader_reads_nothing_without_a_trace(name):
    entry = next(m for m in benchmark()['per_layer'] if m['name'] == name)
    module = by_path('layer_metrics', name + '.py')
    assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
        entry['layer'], entry['unit'], entry['source'], entry['moves'])
    # no trace; and run.py is not the running program, so no window
    assert module.read({'trace': None, 'end_to_end': {'setup_s': 1.0},
                        'window': {'seconds': 1.0}}) is None


@pytest.fixture(scope='module')
def scoped_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp('scoped') / 'scoped.xplane.pb')
    with gzip.open(os.path.join(BENCH, 'testdata',
                                'scoped.xplane.pb.gz')) as src, \
            open(path, 'wb') as dst:
        dst.write(src.read())
    return path


@pytest.fixture(scope='module')
def scoped(scoped_path):
    return scopes.reduce(scoped_path)


@pytest.fixture
def scoped_record(scoped_path, scoped):
    # what run.py hands a reader, for the recorded file
    return {'trace': xplane.reduce(scoped_path), 'steps_per_dispatch': 2,
            'cell': {'name': 'scoped'}, 'scopes': scoped}


def test_scoped_trace_partitions_its_busy_time(scoped, scoped_record):
    worst = scoped['worst']
    assert worst['step_module'].startswith('jit_paddle_tpu_train_scan')
    assert worst['step_runs'] == 3 and worst['scoped'] is True
    assert worst['busy_s'] == pytest.approx(
        scoped_record['trace']['worst']['busy_s'], rel=1e-9)
    assert sum(worst['buckets'].values()) == pytest.approx(
        worst['busy_s'], rel=1e-9)
    for bucket in ('matmul', 'attention', 'loss', 'optimizer', 'other',
                   'scan_lane'):
        assert worst['buckets'].get(bucket, 0.0) > 0, bucket
    assert 'recurrence' not in worst['buckets']
    # the scopes reach nearly all of the step
    assert worst['buckets'].get('unscoped', 0.0) < 0.25 * worst['busy_s']
    assert any(s.startswith('mul_grad.') for s in worst['scopes'])
    assert any(s.startswith('adam.') for s in worst['scopes'])


def test_scoped_trace_readers_sum_to_step_device_ms(scoped_record):
    record = scoped_record
    step_ms = by_path('layer_metrics', 'step_device_ms.train.py').read(record)
    parts = {name: by_path('layer_metrics', name + '.py').read(record)
             for name in PARTITION}
    assert parts['recurrence_device_ms.train'] == 0.0
    share = by_path('layer_metrics',
                    'unscoped_device_share.train.py').read(record)
    assert share is not None and 0.0 <= share < 25.0
    total = sum(parts.values()) + share / 100.0 * step_ms
    assert total == pytest.approx(step_ms, rel=0.01)


def test_scoped_trace_spans_are_on_the_trace_clock(scoped, scoped_record):
    spans = scoped['spans']
    dispatch = spans['paddle_tpu/executor/dispatch']
    stage = spans['paddle_tpu/feed/stage']
    assert dispatch['durations_s'] and stage['durations_s']
    assert dispatch['lines'].isdisjoint(stage['lines'])   # two threads
    for child in ('resolve', 'stage_state', 'launch', 'write_back'):
        assert 'paddle_tpu/executor/' + child in spans
    for name in ('host_dispatch_ms.train', 'feed_stage_ms.train'):
        value = by_path('layer_metrics', name + '.py').read(scoped_record)
        assert value is not None and 0.0 < value < 1e3
    assert all(label == 'unattributed' or label.startswith('paddle_tpu/')
               for label, _ in scoped['worst']['gaps'])
    assert scopes.table(scoped, 2, 10)


def test_traced_rehearsal_prints_the_counter_metrics():
    result, _ = run_cell('nmt_train_1chip', trace=1)
    metrics = result['metrics']
    assert metrics['xla_compiles_in_window.train'] == {
        'value': 0, 'unit': 'count'}
    assert metrics['lowering_s.setup']['value'] > 0
    assert metrics['compile_or_load_s.setup']['value'] > 0
    # a CPU trace has no device plane: nothing read from it is printed
    assert not set(PARTITION) & set(metrics)
