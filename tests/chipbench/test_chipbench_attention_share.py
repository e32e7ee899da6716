"""``fluid.trace.lowering_choices('flash_attention')`` (what each
``flash_attention`` op of a program was lowered to) and its reader,
``chipbench/layer_metrics/attention_fused_share.train.py`` (PR 25)."""

import gc
import importlib.util
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark, run_cell  # noqa: E402

NAME = 'attention_fused_share.train'


def choices():
    from paddle_tpu.fluid import trace
    return trace.lowering_choices('flash_attention')
B, L, H, D = 2, 32, 2, 16


def reader():
    spec = importlib.util.spec_from_file_location(
        'cb_attention_fused_share',
        os.path.join(BENCH, 'layer_metrics', NAME + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _train_three_attentions(impl, then_run_a_copy=False):
    """A program of three ``flash_attention`` ops (self, causal, cross),
    lowered for a CPU place and trained one step (and then, if asked, its
    ``clone(for_test=True)`` run once); the Program, kept alive for the
    record."""
    import paddle_tpu.fluid as fluid
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data('x', [L, H * D], dtype='float32')
        y = layers.data('y', [2 * L, H * D], dtype='float32')
        h = layers.fc(x, H * D, num_flatten_dims=2)
        mem = layers.fc(y, H * D, num_flatten_dims=2)
        h = layers.flash_attention(h, h, h, num_heads=H, impl=impl)
        h = layers.flash_attention(h, h, h, num_heads=H, causal=True,
                                   impl=impl)
        h = layers.flash_attention(h, mem, mem, num_heads=H, impl=impl)
        loss = layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'x': rng.standard_normal((B, L, H * D)).astype('float32'),
            'y': rng.standard_normal((B, 2 * L, H * D)).astype('float32')}
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        value, = exe.run(main, feed=feed, fetch_list=[loss])
        if then_run_a_copy:
            copy = main.clone(for_test=True)
            assert copy._serial != main._serial
            exe.run(copy, feed=feed, fetch_list=[loss.name])
    assert np.isfinite(np.asarray(value)).all()
    return main


@pytest.mark.parametrize('impl, lowered_to', [
    ('auto', 'dense'),      # a CPU place keeps 'auto' dense
    ('dense', 'dense'),
    ('pallas', 'pallas'),   # interpreted, on the CPU place
])
def test_three_ops_count_three_of_the_implementation(impl, lowered_to):
    """Forward and gradient are both lowered (the generic gradient
    replays a dense forward): an op still counts once."""
    before = choices()
    program = _train_three_attentions(impl)
    after = choices()
    assert len(after) == len(before) + 1
    assert after[-1] == {lowered_to: 3} and program is not None


def test_the_record_outlives_its_program():
    """``chipbench/run.py`` reads the metric after the driver has
    returned and let its model go."""
    program = _train_three_attentions('dense')
    n = len(choices())
    del program
    gc.collect()
    assert len(choices()) == n


def test_a_copy_of_a_program_keeps_its_own_record():
    """``Program.clone`` is another Program: lowering it adds a record
    and leaves the original's as it was."""
    n = len(choices())
    program = _train_three_attentions('dense', then_run_a_copy=True)
    assert choices()[n:] == [{'dense': 3}, {'dense': 3}]
    assert program is not None


def test_reader_matches_its_benchmark_entry():
    entry = next(m for m in benchmark()['per_layer'] if m['name'] == NAME)
    module = reader()
    assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
            module.MOVES) == (entry['layer'], entry['unit'],
                              entry['better'], entry['source'],
                              entry['moves'])
    assert entry['workloads'] == ['tbase_train_1chip', 'tbase_train_dp4']


@pytest.mark.parametrize('programs, share', [
    ([], None),                                        # no such op lowered
    ([{'dense': 18}], 0.0),
    ([{'pallas': 18}], 100.0),
    ([{'dense': 1, 'pallas': 2, 'ring': 1}, {'pallas': 4}], 75.0),
])
def test_reader_takes_the_share_from_the_counter(monkeypatch, programs,
                                                 share):
    from paddle_tpu.fluid import trace
    monkeypatch.setattr(trace, 'lowering_choices', lambda op_type: programs)
    assert reader().read({}) == share


def test_reader_reads_nothing_from_a_program_without_the_record(
        monkeypatch):
    """The parent commit's ``fluid.trace`` has no ``lowering_choices``:
    the reader returns None and does not raise."""
    from paddle_tpu.fluid import trace
    monkeypatch.delattr(trace, 'lowering_choices')
    assert reader().read({}) is None


def test_traced_rehearsal_prints_the_share():
    """``--cpu-tiny`` lowers for CPU places: every op is dense there."""
    result, _ = run_cell('tbase_train_1chip', trace=1)
    assert result['metrics'][NAME] == {'value': 0.0, 'unit': '%'}
