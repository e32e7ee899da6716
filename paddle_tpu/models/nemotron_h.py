"""Nemotron-H with routed experts: a decoder-only language model whose layers
are each ONE mixer under a pre-norm and a residual, as NVIDIA publishes it
(``model_type: nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16).  Which
mixer a layer has is a letter of ``hybrid_override_pattern``:

    h = E[ids]
    h = h + mixer(rms_norm(h))        M Mamba-2 | * attention | E experts
    logits = rms_norm(h) W_head                      (untied head)

- ``M``: granite-4.0-h's Mamba-2 mixer (``granite_hybrid._mamba``) with
  ``n_groups`` groups of B and C and the gated norm by those groups;
- ``*``: grouped-head causal attention at 1/sqrt(head_dim), ``head_dim``
  its own key (heads x head_dim is not the hidden size), **no positional
  signal**;
- ``E``: ``moe_router`` (sigmoid scores, the k largest of score + bias,
  weights normalised over the selected and scaled), ``moe_experts`` over
  the ``n_routed_experts_held`` experts this chip holds, numbered from
  ``first_expert``, and a shared expert for every token; experts are
  ungated, ``W_down relu(W_up x)^2``.  The selection bias is a buffer
  that starts as zeros and that no gradient reaches; the family's
  balancing rule moves it (``topk_method: noaux_tc``, ``moe_bias_update``:
  towards equal loads by ``router_bias_update_rate``): after every
  training step, and each time ``balance`` runs, a forward-only program
  over the same parameters, for a set-up to level the loads on a few
  batches before anything trains.

No projection has a bias; the convolution has.  The residual stream is
f32 under AMP (the published ``residual_in_fp32`` is false: a departure).
The plain reference is ``paddle_tpu.models.reference.nemotron_h_ref`` over
the same parameter names (``names``).
"""

import paddle_tpu.fluid as fluid

from .granite_hybrid import _attention, _linear, _mamba, _matrix, _param

__all__ = ['build', 'names', 'TINY']

# a toy of every mechanism, for CPU tests: 16 experts, a share of 4
TINY = dict(
    vocab_size=128, hidden_size=64, hybrid_override_pattern='ME*E',
    num_hidden_layers=4, layer_norm_epsilon=1e-5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8,
    n_routed_experts=16, n_routed_experts_held=4, first_expert=0,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, norm_topk_prob=True,
    routed_scaling_factor=2.5, router_bias_update_rate=0.001)


def pattern(cfg):
    """The layers' letters as run: the first ``num_hidden_layers`` of the
    published pattern."""
    letters = cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]
    if len(letters) < cfg['num_hidden_layers'] or set(letters) - set('M*E'):
        raise ValueError('nemotron_h: pattern %r for %d layers of M, * and E'
                         % (cfg['hybrid_override_pattern'],
                            cfg['num_hidden_layers']))
    return letters


def _mixer_keys(cfg):
    """The configuration under the names granite_hybrid's mixers read."""
    return dict(
        hidden_size=cfg['hidden_size'], rms_norm_eps=cfg['layer_norm_epsilon'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        head_dim=cfg['head_dim'], mamba_n_heads=cfg['mamba_num_heads'],
        mamba_d_head=cfg['mamba_head_dim'],
        mamba_d_state=cfg['ssm_state_size'], mamba_n_groups=cfg['n_groups'],
        mamba_d_conv=cfg['conv_kernel'], mamba_chunk_size=cfg['chunk_size'])


def _experts(h, x, cfg, pre, std):
    """(h + routed(x) + shared(x), the names of the layer's selection bias
    and of what goes into and comes out of its held experts): the two
    branches are widened into the f32 stream one after the other."""
    layers = fluid.layers
    idx, weight = layers.moe_router(
        x, cfg['n_routed_experts'], cfg['num_experts_per_tok'],
        score_func='sigmoid', norm_topk_prob=cfg['norm_topk_prob'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        param_attr=_matrix(pre + 'router', std),
        bias_attr=_param(pre + 'router_bias'))
    routed = layers.moe_experts(
        x, idx, weight, cfg['n_routed_experts_held'],
        cfg['moe_intermediate_size'], first_expert=cfg['first_expert'],
        act='relu2', param_attr=_matrix(pre + 'experts', std))
    names = dict(bias=pre + 'router_bias', w_up=pre + 'experts.w_up',
                 w_down=pre + 'experts.w_down', x=x.name, idx=idx.name,
                 weight=weight.name, out=routed.name)
    shared = _linear(
        layers.relu2(_linear(x, cfg['moe_shared_expert_intermediate_size'],
                             pre + 'shared_up', std)),
        cfg['hidden_size'], pre + 'shared_down', std)
    return layers.residual_add(layers.residual_add(h, routed), shared), names


def build(cfg=None, max_len=32, lr=0.001, init_std=0.02):
    """Training program over [B, max_len] int64 ids.  ``cfg``: the
    published config's keys with ``n_routed_experts_held``,
    ``first_expert`` and ``router_bias_update_rate`` (``TINY`` has them
    all); feeds: ``ids`` and ``lbl_ids`` (the next tokens).  The loss is
    the mean next-token cross-entropy over ``cfg['vocab_size']`` rows.
    Beside the programs: ``routed``, by ``E`` layer's number the names of
    its selection bias (``bias``), of its held experts' matrices (``w_up``,
    ``w_down``) and of their input, selected experts, weights and output
    (``x``, ``idx``, ``weight``, ``out``)."""
    cfg = dict(TINY, **(cfg or {}))
    layers, mixer = fluid.layers, _mixer_keys(cfg)
    eps = cfg['layer_norm_epsilon']
    main, startup, routed = fluid.Program(), fluid.Program(), {}
    with fluid.program_guard(main, startup):
        ids = layers.data(name='ids', shape=[max_len], dtype='int64')
        lbl = layers.data(name='lbl_ids', shape=[max_len], dtype='int64')
        h = layers.embedding(
            input=ids, size=[cfg['vocab_size'], cfg['hidden_size']],
            param_attr=_matrix('nemotron.embed', init_std))
        for i, kind in enumerate(pattern(cfg)):
            pre = 'nemotron.l%d.' % i
            x = layers.rms_norm(h, epsilon=eps,
                                param_attr=_param(pre + 'norm'))
            if kind == 'E':
                h, routed[i] = _experts(h, x, cfg, pre, init_std)
            else:
                branch = _mamba if kind == 'M' else _attention
                h = layers.residual_add(h, branch(x, mixer, pre, init_std))
        x = layers.rms_norm(h, epsilon=eps,
                            param_attr=_param('nemotron.final_norm'))
        logits = _linear(x, cfg['vocab_size'], 'nemotron.lm_head', init_std)
        cost = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(lbl, axes=[2]))
        avg_cost = layers.mean(cost)
        test_program = main.clone(for_test=True)
        balance = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=lr).minimize(avg_cost)
    # after the step's own ops, as the family trains (forward and backward
    # read the bias the selections were made with), and after a forward
    # pass alone
    for program in (main, balance):
        with fluid.program_guard(program, fluid.Program()):
            block = program.global_block()
            for names in routed.values():
                layers.moe_bias_update(block.var(names['bias']),
                                       block.var(names['idx']),
                                       rate=cfg['router_bias_update_rate'])
    return dict(main=main, startup=startup, test=test_program,
                balance=balance, routed=routed, feeds=['ids', 'lbl_ids'],
                logits=logits, loss=avg_cost)


LAYER_PARAMS = {
    'M': ['norm', 'in_proj', 'conv_w', 'conv_b', 'dt_bias', 'A_log', 'D',
          'gate_norm', 'out_proj'],
    '*': ['norm', 'q_proj', 'k_proj', 'v_proj', 'o_proj'],
    'E': ['norm', 'router', 'router_bias', 'experts.w_up', 'experts.w_down',
          'shared_up', 'shared_down'],
}


def names(cfg=None):
    """The parameters' names, in the order the reference walks them (the
    selection bias among them: a buffer no gradient reaches)."""
    cfg = dict(TINY, **(cfg or {}))
    out = ['nemotron.embed']
    for i, kind in enumerate(pattern(cfg)):
        out += ['nemotron.l%d.%s' % (i, n) for n in LAYER_PARAMS[kind]]
    return out + ['nemotron.final_norm', 'nemotron.lm_head']
