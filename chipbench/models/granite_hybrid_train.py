"""Builder of the ``granite-4.0-h-micro`` training programs, through the
repo's normal entry point (``paddle_tpu.models.granite_hybrid.build``), with
the functions that count its work from shapes and the hook to the
benchmark's own plain reference (``chipbench/reference/``)."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
# the keys of the published config.json the model is built from
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'intermediate_size', 'layer_types',
    'num_attention_heads', 'num_key_value_heads', 'attention_multiplier',
    'embedding_multiplier', 'residual_multiplier', 'logits_scaling',
    'rms_norm_eps', 'mamba_n_heads', 'mamba_d_head', 'mamba_d_state',
    'mamba_n_groups', 'mamba_d_conv', 'mamba_chunk_size')


def model_config(cfg):
    """The model's keys as run: of the published ``layer_types`` the first
    ``num_hidden_layers`` (the file keeps the list whole; the cut in depth
    is that one number)."""
    out = {k: cfg[k] for k in MODEL_KEYS}
    depth = cfg['num_hidden_layers']
    if len(out['layer_types']) < depth:
        raise ValueError('%s: %d layer_types for num_hidden_layers %d'
                         % (cfg['name'], len(out['layer_types']), depth))
    out['layer_types'] = out['layer_types'][:depth]
    return out


def build(cfg, traffic):
    from paddle_tpu.models import granite_hybrid
    return granite_hybrid.build(
        model_config(cfg), max_len=int(traffic['length']),
        lr=cfg['learning_rate'], init_std=cfg['initializer_range'])


def vocab(cfg):
    return cfg['vocab_size']


def feed(cfg, batch):
    """The generator's target stream as the document, its shift as the
    labels; the source stream is not used (a decoder-only model)."""
    return {'ids': batch['trg'], 'lbl_ids': batch['next']}


def _scan_macs_per_token(cfg):
    """Multiply-adds a position of one ``ssd_scan`` needs, forward: inside
    a chunk of Q the scores C B^T and their product with X over the
    earlier half of the chunk on average (causal), the position's own
    outer product into the chunk's state, and its read of the entering
    state."""
    h, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    g, n, q = cfg['mamba_n_groups'], cfg['mamba_d_state'], \
        cfg['mamba_chunk_size']
    return (q / 2.0) * (g * n + h * p) + 2.0 * h * p * n


def train_flops_per_token(cfg, traffic):
    """Operations one token's training step needs: forward and backward
    (3 x forward), two per multiply-add; the matrix products, attention at
    half (causal), the scan's chunk products; nothing recomputed, the
    embedding's lookup not counted (the tied head's product is)."""
    d, ff, seq = cfg['hidden_size'], cfg['intermediate_size'], \
        int(traffic['length'])
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    hd = d // hq
    inner = cfg['mamba_n_heads'] * cfg['mamba_d_head']
    bc = 2 * cfg['mamba_n_groups'] * cfg['mamba_d_state']
    mlp = d * 2 * ff + ff * d
    mamba = d * (2 * inner + bc + cfg['mamba_n_heads']) + inner * d \
        + _scan_macs_per_token(cfg)
    attention = d * (hq + 2 * hkv) * hd + hq * hd * d + seq * hq * hd
    kinds = model_config(cfg)['layer_types']
    layers = sum(mamba if k == 'mamba' else attention for k in kinds) \
        + len(kinds) * mlp
    return 3.0 * 2.0 * (layers + d * cfg['vocab_size'])


def ssd_scan_work(cfg, traffic):
    """(operations, bytes) a training step needs in all its ``ssd_scan``
    ops, forward and gradient, whatever implements them.  Operations: the
    forward's products, twice as many for their gradients, and the
    gradient's recomputation of the forward's within-chunk products
    counted once (2 per multiply-add).  Bytes: one read of X, dt, B, C and
    one write of Y and of the chunk states (f32) forward; backward one read
    of X, dt, B, C, the states and dY and one write of dX, ddt, dB, dC.
    Activations at 2 bytes under AMP, else 4."""
    tokens = int(traffic['batch']) * int(traffic['length'])
    h, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    g, n, q = cfg['mamba_n_groups'], cfg['mamba_d_state'], \
        cfg['mamba_chunk_size']
    ops = sum(k == 'mamba' for k in model_config(cfg)['layer_types'])
    act = 2 if cfg['amp'] else 4
    flops = 4.0 * 2.0 * _scan_macs_per_token(cfg) * tokens
    per_token = (h * p + h + 2 * g * n) * act       # X, dt, B, C
    states = 4.0 * h * p * n * tokens / q           # f32, one a chunk
    forward = tokens * (per_token + h * p * act) + states
    backward = tokens * (2 * per_token + h * p * act) + states
    return ops * flops, ops * (forward + backward)


# ---- the reference ----------------------------------------------------

def checked_gradients(cfg):
    """Parameters whose gradient the first step is compared on: one of
    each kind the new ops own, the attention's key projection (the summed
    gradient of its repeated heads), a feed-forward and the tied embedding
    (two uses)."""
    kinds = model_config(cfg)['layer_types']
    m, a = kinds.index('mamba'), kinds.index('attention')
    last_m = len(kinds) - 1 - kinds[::-1].index('mamba')
    return ['granite.l%d.in_proj' % m, 'granite.l%d.A_log' % m,
            'granite.l%d.dt_bias' % m, 'granite.l%d.conv_w' % m,
            'granite.l%d.k_proj' % a, 'granite.l%d.mlp_in' % last_m,
            'granite.embed']


def reference_train(cfg, weight, feeds, wrt):
    """The benchmark's plain float32 reference trained from the program's
    weights (``weight(name)``) by the configuration's Adam, one step a fed
    batch of ``feeds``: (every step's loss, the first step's gradients of
    the names in ``wrt``, ``final(name)`` a parameter after the last)."""
    spec = importlib.util.spec_from_file_location(
        'chipbench_granite_hybrid_ref', os.path.join(
            os.path.dirname(HERE), 'reference', 'granite_hybrid_ref.py'))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref.adam_steps(
        weight, model_config(cfg),
        [(f['ids'], f['lbl_ids']) for f in feeds], cfg['learning_rate'],
        wrt=set(wrt))
