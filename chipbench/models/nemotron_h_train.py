"""Builder of the ``nemotron-3-nano-30b-a3b`` training programs, through the
repo's normal entry point (``paddle_tpu.models.nemotron_h.build``), with the
functions that count its work from shapes and the hook to the benchmark's
own plain reference (``chipbench/reference/nemotron_h_ref.py``)."""

import functools
import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# the keys of the published config.json the model is built from, and the
# two that say which experts this chip holds
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'hybrid_override_pattern',
    'num_hidden_layers', 'layer_norm_epsilon', 'num_attention_heads',
    'num_key_value_heads', 'head_dim', 'mamba_num_heads', 'mamba_head_dim',
    'ssm_state_size', 'n_groups', 'conv_kernel', 'chunk_size',
    'n_routed_experts', 'n_routed_experts_held', 'first_expert',
    'num_experts_per_tok', 'moe_intermediate_size',
    'moe_shared_expert_intermediate_size', 'norm_topk_prob',
    'routed_scaling_factor', 'router_bias_update_rate')


def model_config(cfg):
    """The model's keys as run (the file keeps the published pattern whole;
    the builder takes its first ``num_hidden_layers`` letters)."""
    return {k: cfg[k] for k in MODEL_KEYS}


def kinds(cfg):
    return cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]


def build(cfg, traffic):
    from paddle_tpu.models import nemotron_h
    return nemotron_h.build(
        model_config(cfg), max_len=int(traffic['length']),
        lr=cfg['learning_rate'], init_std=cfg['initializer_range'])


def vocab(cfg):
    return cfg['vocab_size']


def feed(cfg, batch):
    """The generator's target stream as the document, its shift as the
    labels; the source stream is not used (a decoder-only model)."""
    return {'ids': batch['trg'], 'lbl_ids': batch['next']}


def train_flops_per_token(cfg, traffic):
    """Operations one token's training step needs: forward and backward
    (3 x forward), two per multiply-add; the matrix products, attention at
    half (causal), the scan's chunk products, the routed experts at the
    EXPECTED rows a token this chip's share gets (experts per token x held
    / all: 0.375 here; a seed's load is above or below it); nothing
    recomputed, the embedding's lookup not counted."""
    d, seq = cfg['hidden_size'], int(traffic['length'])
    hq, hkv, hd = cfg['num_attention_heads'], cfg['num_key_value_heads'], \
        cfg['head_dim']
    h, p = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    g, n, q = cfg['n_groups'], cfg['ssm_state_size'], cfg['chunk_size']
    inner = h * p
    scan = (q / 2.0) * (g * n + inner) + 2.0 * inner * n
    per_layer = {
        'M': d * (2 * inner + 2 * g * n + h) + inner * d + scan,
        '*': d * (hq + 2 * hkv) * hd + hq * hd * d + seq * hq * hd,
        'E': d * cfg['n_routed_experts']
        + 2 * d * cfg['moe_shared_expert_intermediate_size']
        + cfg['num_experts_per_tok'] * cfg['n_routed_experts_held']
        / float(cfg['n_routed_experts'])
        * 2 * d * cfg['moe_intermediate_size']}
    layers = sum(per_layer[k] for k in kinds(cfg))
    return 3.0 * 2.0 * (layers + d * cfg['vocab_size'])


def moe_experts_work(cfg, traffic):
    """(operations, bytes) EVERY load needs in a training step's
    ``moe_experts`` ops, forward and both gradients, whatever implements
    them: one pass over each held expert's two float32 matrices in each of
    the three products (read for the output and for the input's gradient,
    written as the weights' gradient).  The rows a seed's load sends the
    held experts are not counted, nor are their operations (a reader cannot
    know them): a lower bound of the work, so the share it gives cannot
    pass 100 on a lightly loaded seed."""
    del traffic
    layers = kinds(cfg).count('E')
    weights = cfg['n_routed_experts_held'] * 2 * cfg['hidden_size'] \
        * cfg['moe_intermediate_size']
    return 0.0, layers * 3 * 4.0 * weights


# ---- the reference ----------------------------------------------------

def checked_gradients(cfg):
    """Parameters whose gradient the first step is compared on: the first
    expert layer's router, held experts and shared expert, the last one's
    held experts' input side, one Mamba-2 mixer's projection, decay and
    step bias, the attention's key projection (the summed gradient of
    sixteen repeated heads), the embedding and the head."""
    k = kinds(cfg)
    e, m, a = k.index('E'), k.index('M'), k.index('*')
    last_e = len(k) - 1 - k[::-1].index('E')
    return ['nemotron.l%d.router' % e, 'nemotron.l%d.experts.w_up' % e,
            'nemotron.l%d.experts.w_down' % e, 'nemotron.l%d.shared_up' % e,
            'nemotron.l%d.experts.w_up' % last_e,
            'nemotron.l%d.in_proj' % m, 'nemotron.l%d.A_log' % m,
            'nemotron.l%d.dt_bias' % m, 'nemotron.l%d.k_proj' % a,
            'nemotron.embed', 'nemotron.lm_head']


@functools.lru_cache(None)
def reference():
    spec = importlib.util.spec_from_file_location(
        'chipbench_nemotron_h_ref', os.path.join(
            os.path.dirname(HERE), 'reference', 'nemotron_h_ref.py'))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def checked_experts(cfg):
    """The ``E`` layer whose held experts are also compared alone, on the
    program's own inputs: the first (both its matrices are among
    ``checked_gradients``)."""
    return kinds(cfg).index('E')


def reference_train(cfg, weight, feeds, wrt, forced=None, own=None):
    """The benchmark's plain float32 reference trained from the program's
    weights (``weight(name)``) by the configuration's Adam, one step a fed
    batch of ``feeds``: (every step's loss, the first step's gradients of
    the names in ``wrt``, ``final(name)`` a parameter after the last).
    ``forced``: by ``E`` layer, the experts the program selected in the
    first step, which the reference's first step then computes with;
    ``own``: a dict that is handed what it would have selected itself.
    Prints the rows the first batch sends each held expert, by the
    reference's own routing: the run's load is in its log."""
    own = {} if own is None else own
    out = reference().adam_steps(
        weight, model_config(cfg),
        [(f['ids'], f['lbl_ids']) for f in feeds], cfg['learning_rate'],
        wrt=set(wrt), selections=own, forced=forced)
    for layer, selected in sorted(own.items()):
        rows = reference().expert_load(selected, model_config(cfg))
        print('chipbench: expert load layer %d: rows %s of %d pairs, '
              'largest over mean %.3f'
              % (layer, ' '.join(map(str, rows)), selected.size,
                 rows.max() / max(rows.mean(), 1e-30)), flush=True)
    return out


def reference_routed_layer(cfg, weight, layer, x, idx, w, dy):
    """The reference's router and held experts of ``layer`` ALONE, on the
    inputs the program's own had (its normed tokens ``x``, its selections
    ``idx``, its weights ``w``, its output's gradient ``dy``):
    ``router_selected`` (what the router selects for ``x``),
    ``router_weight`` (its weights for ``idx``), ``experts_out`` and the
    gradients ``experts_w_up`` and ``experts_w_down``."""
    ref, pre = reference(), 'nemotron.l%d.' % layer
    selected, weights = ref.router_check(
        weight(pre + 'router'), weight(pre + 'router_bias'), x, idx,
        model_config(cfg))
    out, d_up, d_down = ref.held_experts_check(
        weight(pre + 'experts.w_up'), weight(pre + 'experts.w_down'), x, idx,
        w, dy, first=cfg['first_expert'])
    return {key: np.asarray(a) for key, a in (
        ('router_selected', selected), ('router_weight', weights),
        ('experts_out', out), ('experts_w_up', d_up),
        ('experts_w_down', d_down))}
