"""``chipbench/layer_metrics/param_update_tied_share.train.py`` (PR 32):
the share of the parameter bytes read by gradient ops whose in-place update
the lowering ordered after the op's reads, from the program's own record
``fluid.trace.lowering_choices('param_update_order', seen=True)``."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, benchmark, run_cell  # noqa: E402

NAME = 'param_update_tied_share.train'


def reader():
    spec = importlib.util.spec_from_file_location(
        'cb_param_update_tied_share',
        os.path.join(BENCH, 'layer_metrics', NAME + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_reader_matches_its_benchmark_entry():
    entry = benchmark()['per_layer'][-1]
    module = reader()
    assert entry == {
        'name': NAME, 'layer': module.LAYER, 'unit': module.UNIT,
        'better': module.BETTER, 'source': module.SOURCE,
        'moves': module.MOVES}   # no 'workloads': every training cell


def op(choice, mb, params=1):
    return {'choice': choice, 'mb': mb, 'params': params}


@pytest.mark.parametrize('programs, share', [
    ([], None),                                    # no gradient op lowered
    ([{'b@GRAD': op('untied', 0.0)}], None),
    ([{'x@GRAD': op('untied', 4.0), 'b@GRAD': op('untied', 0.5)}], 0.0),
    ([{'x@GRAD': op('tied', 64.0)}], 100.0),
    ([{'x@GRAD': op('tied', 60.0), 'h@GRAD': op('untied', 15.0, 3)},
      {'y@GRAD': op('untied', 5.0)}], 75.0),
])
def test_reader_takes_the_share_of_bytes_from_the_record(monkeypatch,
                                                         programs, share):
    from paddle_tpu.fluid import trace
    monkeypatch.setattr(trace, 'lowering_choices',
                        lambda op_type, seen=False: programs)
    assert reader().read({}) == share


@pytest.mark.parametrize('older', [
    None,                          # no record at all (before PR 25)
    lambda op_type: [],            # a record without what was seen (PR 25)
    lambda op_type, seen=False: [],   # the parent: no such op type noted
])
def test_reader_reads_nothing_from_a_program_without_the_record(
        monkeypatch, older):
    from paddle_tpu.fluid import trace
    if older is None:
        monkeypatch.delattr(trace, 'lowering_choices')
    else:
        monkeypatch.setattr(trace, 'lowering_choices', older)
    assert reader().read({}) is None


def test_traced_rehearsal_prints_the_share():
    """The toy transformer's weights are all smaller than the activations
    beside them but the dictionary-wide head."""
    result, _ = run_cell('tbase_train_1chip', trace=1)
    share = result['metrics'][NAME]
    assert share['unit'] == '%' and 0.0 < share['value'] < 100.0
