"""What PR 34 added to the benchmark: the ``nemotron-3-nano-30b-a3b``
configuration (its widths are the published ones, its cuts the three it
lists), the builder's counted work, the two readers ``moe_device_ms.train``
and ``moe_experts_roofline.train`` (nothing without a trace, 0.0 / nothing
on recorded traces that hold no expert op), the benchmark's copy of the
plain reference, the driver ``train_loop_ref_routed`` (the comparison on
equal selections, the held experts alone, the set-up passes over the
selection bias), and a traced ``--cpu-tiny`` rehearsal of the cell whose log
carries the held experts' load and the expert products' implementation."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_helpers import BENCH, ROOT, benchmark, run_cell  # noqa: E402

NAMES = ['moe_device_ms.train', 'moe_experts_roofline.train']
CELL = {'name': 'nemotron3_nano_train_1chip',
        'config': 'nemotron-3-nano-30b-a3b', 'traffic': 'zipf_b2_l2048'}
# the published widths (config.json of the model): none may be cut
WIDTHS = {
    'hidden_size': 2688, 'head_dim': 128, 'num_attention_heads': 32,
    'num_key_value_heads': 2, 'mamba_num_heads': 64, 'mamba_head_dim': 64,
    'ssm_state_size': 128, 'n_groups': 8, 'conv_kernel': 4,
    'chunk_size': 128, 'expand': 2, 'n_routed_experts': 128,
    'num_experts_per_tok': 6, 'moe_intermediate_size': 1856,
    'moe_shared_expert_intermediate_size': 3712, 'intermediate_size': 1856,
    'n_shared_experts': 1, 'routed_scaling_factor': 2.5,
    'norm_topk_prob': True, 'n_group': 1, 'topk_group': 1,
    'mlp_hidden_act': 'relu2', 'tie_word_embeddings': False,
    'hybrid_override_pattern':
        'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'}


def by_path(*parts):
    spec = importlib.util.spec_from_file_location(
        'cb_' + parts[-1].replace('.', '_'), os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def file(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = file('configs', CELL['config'] + '.json')
TRAFFIC = file('traffic', CELL['traffic'] + '.json')


@pytest.mark.parametrize('key', sorted(WIDTHS))
def test_the_configuration_keeps_the_published_value(key):
    assert CFG[key] == WIDTHS[key]


def test_the_cuts_are_the_three_listed_with_the_published_beside_them():
    assert CFG['reduced'] == ['num_hidden_layers', 'n_routed_experts_held',
                              'vocab_size']
    assert (CFG['num_hidden_layers'], CFG['n_routed_experts_held'],
            CFG['vocab_size']) == (9, 8, 16384)
    assert {k: CFG['published'][k] for k in CFG['reduced']} == {
        'num_hidden_layers': 52, 'n_routed_experts_held': 128,
        'vocab_size': 131072}
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    builder = by_path('models', CFG['builder'] + '.py')
    assert builder.kinds(CFG) == 'MEMEM*EME'
    assert CFG['vocab_size'] * 8 == CFG['published']['vocab_size']
    assert '16 chips' in CFG['deployment']
    for word in ('positions', 'residual stream', 'selection bias', 'A_log',
                 'dt_bias', 'matrices', 'experts', 'optimizer', 'bytes'):
        assert CFG['assumed'][word]
    # the values the builder set itself, each with its reason beside it
    assert (CFG['learning_rate'], CFG['router_bias_update_rate'],
            CFG['router_bias_setup_passes']) == (1e-05, 0.01, 16)
    assert 'BENCHMARK DEVICE' in CFG['assumed']['optimizer']
    assert 'ASSUMED' in CFG['assumed']['selection bias']
    assert '0.00001' in CFG['assumed']['optimizer']
    assert '0.01' in CFG['assumed']['selection bias']
    assert '16' in CFG['assumed']['selection bias']
    assert set(CFG['tolerances']) == {
        'what', 'loss_abs_diff', 'grad_rel_err', 'lane_loss_abs_diff',
        'param_change_rel_err', 'selection_disagree_share', 'alone'}
    assert set(CFG['tolerances']['alone']) == {
        'router_selected', 'router_weight', 'experts_out', 'experts_w_up',
        'experts_w_down'}
    # no limit of tens of percent on a gradient: the comparison is on
    # equal selections
    assert max(kind['limit'] for kind in CFG['tolerances'][
        'grad_rel_err'].values()) <= 0.12
    assert file('workloads', CELL['name'] + '.json')['driver'] \
        == 'train_loop_ref_routed'
    assert (TRAFFIC['batch'], TRAFFIC['length']) == (2, 2048)


def test_the_counted_work_follows_the_shapes():
    builder = by_path('models', CFG['builder'] + '.py')
    flops, nbytes = builder.moe_experts_work(CFG, TRAFFIC)
    # four E layers, three products, one pass over 8 experts' two f32
    # matrices each; no rows, so no operations
    assert flops == 0.0
    assert nbytes == 4 * 3 * 4 * (8 * 2 * 2688 * 1856)
    macs = {
        'M': 2688 * 10304 + 4096 * 2688 + 64 * (1024 + 4096)
        + 2 * 4096 * 128,
        '*': 2688 * 4608 + 4096 * 2688 + 2048 * 4096,
        'E': 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856}
    per_token = builder.train_flops_per_token(CFG, TRAFFIC)
    assert per_token == 6.0 * (4 * macs['M'] + macs['*'] + 4 * macs['E']
                               + 2688 * 16384)
    assert 7.9e12 < per_token * 4096 < 8.4e12


def test_the_checked_gradients_are_parameters_of_the_program():
    sys.path.insert(0, ROOT)
    from paddle_tpu.models import nemotron_h
    builder = by_path('models', CFG['builder'] + '.py')
    tiny = dict(CFG, **CFG['cpu_tiny'])
    names = builder.checked_gradients(tiny)
    assert len(names) == len(set(names)) == 11
    assert set(names) <= set(nemotron_h.names(builder.model_config(tiny)))
    kinds = {n.rsplit('.', 1)[-1] for n in names}
    assert {'router', 'w_up', 'w_down', 'shared_up', 'in_proj', 'A_log',
            'dt_bias', 'k_proj', 'embed', 'lm_head'} == kinds
    assert {n.rsplit('.', 1)[-1] for n in builder.checked_gradients(CFG)} \
        == kinds


def test_the_plain_reference_has_one_source_and_the_benchmark_keeps_it():
    sys.path.insert(0, ROOT)
    from paddle_tpu.models.reference import nemotron_h_ref
    kept = os.path.join(BENCH, 'reference', 'nemotron_h_ref.py')
    assert os.path.samefile(nemotron_h_ref.__file__, kept)
    with open(kept) as f:
        code = f.read().split('"""')[2]
    assert 'paddle_tpu' not in code and 'chipbench' not in code


def test_the_routed_comparison_reads_a_lower_precision_at_its_own_size():
    """``compare_routed`` on toy arrays: sound inputs read (near) nothing;
    a selection the reference's router would not make, a weight or an
    output one percent off, read what they are."""
    import types
    import numpy as np
    sys.path.insert(0, ROOT)
    from paddle_tpu.models.reference import nemotron_h_ref as ref
    driver = by_path('drivers', 'train_loop_ref_routed.py')
    builder = by_path('models', CFG['builder'] + '.py')
    tiny = dict(CFG, **CFG['cpu_tiny'])
    r = np.random.RandomState(0)
    d, e, f = tiny['hidden_size'], tiny['n_routed_experts'], \
        tiny['moe_intermediate_size']
    weights = {'nemotron.l1.router': r.standard_normal((d, e)) * .3,
               'nemotron.l1.router_bias': np.zeros(e),
               'nemotron.l1.experts.w_up': r.standard_normal((4, f, d)) * .3,
               'nemotron.l1.experts.w_down': r.standard_normal((4, f, d)) * .3}
    weights = {k: v.astype('float32') for k, v in weights.items()}
    x = r.standard_normal((2, 8, d)).astype('float32')
    dy = r.standard_normal((2, 8, d)).astype('float32')
    idx, w = ref.router_check(weights['nemotron.l1.router'],
                              weights['nemotron.l1.router_bias'], x,
                              np.zeros((2, 8, 3), 'int32'),
                              builder.model_config(tiny))
    idx = np.asarray(idx)
    _, w = ref.router_check(weights['nemotron.l1.router'],
                            weights['nemotron.l1.router_bias'], x, idx,
                            builder.model_config(tiny))
    out, d_up, d_down = (np.asarray(a) for a in ref.held_experts_check(
        weights['nemotron.l1.experts.w_up'],
        weights['nemotron.l1.experts.w_down'], x, idx, w, dy))
    ctx = types.SimpleNamespace(config=tiny, model_lib=builder)

    def numbers(**changed):
        first = {'weights': weights, 'selected': {1: idx, 3: idx},
                 'grads': {'up': d_up, 'down': d_down},
                 'alone': dict(dict(x=x, w=np.asarray(w), out=out, dy=dy,
                                    layer=1, w_up='up', w_down='down'),
                               **changed)}
        return {k: v for k, (v, _) in driver.compare_routed(
            ctx, first, {1: idx, 3: np.roll(idx, 1, axis=0)}).items()}

    sound = numbers()
    assert max(v for k, v in sound.items() if k != \
               'selection_disagree_share.l3') < 1e-5
    assert sound['selection_disagree_share.l3'] > 0.5
    assert abs(numbers(out=out * 1.01)['alone.l1.experts_out'] - 0.01) < 1e-4
    assert abs(numbers(w=np.asarray(w) * 0.99)['alone.l1.router_weight']
               - 0.01) < 1e-4
    # the program selected, for every token, an expert its router ranks last
    last = np.argsort(x @ weights['nemotron.l1.router'], axis=-1)[..., :1]
    moved = np.concatenate([idx[..., :2], last.astype('int32')], axis=-1)
    first = {'weights': weights, 'selected': {1: moved, 3: idx},
             'grads': {'up': d_up, 'down': d_down},
             'alone': dict(x=x, w=np.asarray(w), out=out, dy=dy, layer=1,
                           w_up='up', w_down='down')}
    read = driver.compare_routed(ctx, first, {1: idx, 3: idx})
    assert abs(read['alone.l1.router_selected'][0] - 1 / 3.0) < 1e-6
    assert read['alone.l1.router_selected'][1] \
        == tiny['tolerances']['alone']['router_selected']['limit']


@pytest.mark.parametrize('name', NAMES)
def test_reader_agrees_with_its_entry_and_reads_nothing_without_a_trace(
        name):
    entry = next(m for m in benchmark()['per_layer'] if m['name'] == name)
    module = by_path('layer_metrics', name + '.py')
    assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
            module.MOVES) == (entry['layer'], entry['unit'],
                              entry['better'], entry['source'],
                              entry['moves'])
    assert entry['workloads'] == [CELL['name']]
    assert module.read({'trace': None, 'cell': CELL, 'peaks': None}) is None


@pytest.mark.parametrize('trace_name', ['granite_scoped.xplane.pb',
                                        'scoped.xplane.pb'])
def test_readers_on_recorded_traces_without_expert_ops(trace_name, tmp_path):
    """A program without the expert ops (the parent's, another cell's):
    0.0 ms of them, and no share."""
    ssm = by_path('..', 'tests', 'chipbench',
                  'test_chipbench_ssm_metrics.py')
    record = dict(ssm.record_of(trace_name, tmp_path), cell=CELL)
    assert by_path('layer_metrics', NAMES[0] + '.py').read(record) == 0.0
    assert by_path('layer_metrics', NAMES[1] + '.py').read(record) is None


def test_traced_rehearsal_carries_the_load_and_leaves_out_device_metrics():
    result, lines = run_cell(CELL['name'], trace=1, seconds=2.0)
    assert result['correct'] is True and result['failed'] == 0
    m = result['metrics']
    assert m['compiles_in_window.train']['value'] == 0
    assert m['train_scan_lowerings.setup']['value'] == 1
    # a CPU trace has no device plane: the trace's readers return nothing
    assert not set(NAMES) & set(m)
    loads = [l for l in lines if l.startswith('chipbench: expert load ')]
    assert [l.split()[4] for l in loads] == ['1:', '3:']
    assert all('largest over mean' in l for l in loads)
    lowered = next(l for l in lines if l.startswith('chipbench: lowered '))
    assert '"flash_attention"' in lowered and '"ssd_scan"' in lowered
    # the expert products' implementation, buffer and tile are in the log
    assert '"moe_experts"' in lowered and '"ragged_dot"' in lowered
    assert '"buffer_rows": 192' in lowered
    balanced = next(l for l in lines if l.startswith(
        'chipbench: selection bias after 2 forward passes at 0.001: '))
    assert 'l1 -0.002..+0.002' in balanced
    routed = json.loads(next(l for l in lines if l.startswith(
        'chipbench: routed experts on equal selections: ')).split(': ', 2)[2])
    assert sorted(routed) == [
        'alone.l1.experts_out', 'alone.l1.experts_w_down',
        'alone.l1.experts_w_up', 'alone.l1.router_selected',
        'alone.l1.router_weight', 'selection_disagree_share.l1',
        'selection_disagree_share.l3']
    assert all(value <= limit for value, limit in routed.values())
    setup = next(l for l in lines if l.startswith('chipbench: setup '))
    marks = [word.split('=')[0] for word in setup.split()[2:]]
    assert marks.index('startup_ran') < marks.index('bias_balanced') \
        < marks.index('first_step_ran') < marks.index('window_opens')
