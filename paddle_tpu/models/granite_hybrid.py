"""granite-4.0-h: a decoder-only language model of Mamba-2 (state-space)
layers with an attention layer among them, as IBM publishes it
(``model_type: granitemoehybrid``, here without experts;
https://huggingface.co/ibm-granite/granite-4.0-h-micro).

Built from ``fluid.layers`` like every other model: ``build`` returns the
training Program ``transformer.build`` returns.  Per layer, pre-norm, an
f32 residual stream with the model's multipliers:

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * mixer(rms_norm(h))      mamba | attention
    h = h + residual_multiplier * mlp(rms_norm(h))        gated feed-forward
    logits = rms_norm(h) E^T / logits_scaling             tied embedding

- attention: 32 query heads over 8 key-value heads (``flash_attention``
  with ``num_kv_heads``), causal, the scores' multiplier the model's own,
  **no positional signal** (``position_embedding_type: nope``);
- Mamba-2 mixer: one input projection to [z, xBC, dt]; ``causal_conv1d``
  with SiLU over xBC; ``ssd_scan`` over X, B, C with
  dt = softplus(dt + dt_bias) and A = -exp(A_log); ``rms_norm`` gated by z;
  the output projection;
- feed-forward: one projection to [g, u], ``swiglu``, one back.

No projection has a bias; the convolution has.  The plain reference is
``paddle_tpu.models.reference.granite_hybrid_ref`` over the same parameter
names (``names``).
"""

import math

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.initializer import Initializer

__all__ = ['build', 'names', 'TINY']

# a toy of every mechanism, for CPU tests
TINY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    layer_types=['mamba', 'attention'], num_attention_heads=4,
    num_key_value_heads=2, attention_multiplier=0.25,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    rms_norm_eps=1e-5, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=8)


class _Chain(Initializer):
    """A parameter drawn uniformly in [low, high) and passed through
    ``ops`` (Fluid op types with their attrs) in the startup program."""

    def __init__(self, low, high, ops):
        super(_Chain, self).__init__()
        self._low, self._high, self._ops = low, high, ops

    def __call__(self, var, block):
        op = block.append_op(
            type='uniform_random', outputs={'Out': [var.name]},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'min': float(self._low), 'max': float(self._high),
                   'seed': 0})
        for op_type, attrs in self._ops:
            op = block.append_op(type=op_type, inputs={'X': [var.name]},
                                 outputs={'Out': [var.name]}, attrs=attrs)
        return op


def _a_log_init():
    """A_log = log(a), a uniform in [1, 16) (the family's convention)."""
    return _Chain(1.0, 16.0, [('log', {})])


def _dt_bias_init(dt_min=0.001, dt_max=0.1):
    """The inverse softplus of a step dt that is log-uniform in
    [dt_min, dt_max): dt + log(1 - exp(-dt))."""
    lo, hi = math.log(dt_min), math.log(dt_max)
    # u -> dt = exp(u) ; softplus^-1(dt) = log(exp(dt) - 1)
    return _Chain(lo, hi, [
        ('exp', {}), ('exp', {}),
        ('scale', {'scale': 1.0, 'bias': -1.0}), ('log', {})])


def _param(name, init=None):
    return fluid.ParamAttr(name=name, initializer=init)


def _matrix(name, std):
    return _param(name, fluid.initializer.Normal(0.0, std))


def _linear(x, size, name, std):
    return fluid.layers.fc(input=x, size=size, bias_attr=False,
                           num_flatten_dims=2, param_attr=_matrix(name, std))


def _attention(x, cfg, pre, std):
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    d = cfg.get('head_dim') or cfg['hidden_size'] // hq
    q = _linear(x, hq * d, pre + 'q_proj', std)
    k = _linear(x, hkv * d, pre + 'k_proj', std)
    v = _linear(x, hkv * d, pre + 'v_proj', std)
    o = fluid.layers.flash_attention(
        q, k, v, num_heads=hq, num_kv_heads=hkv, causal=True,
        scale=cfg.get('attention_multiplier'))
    return _linear(o, cfg['hidden_size'], pre + 'o_proj', std)


def _mamba(x, cfg, pre, std):
    layers = fluid.layers
    h, hd = cfg['mamba_n_heads'], cfg['mamba_d_head']
    g, n = cfg['mamba_n_groups'], cfg['mamba_d_state']
    inner, taps = h * hd, cfg['mamba_d_conv']
    z, xbc, dt = layers.split(
        _linear(x, 2 * inner + 2 * g * n + h, pre + 'in_proj', std),
        [inner, inner + 2 * g * n, h], dim=2)
    # torch.nn.Conv1d's own initial values: uniform in +-1/sqrt(taps)
    bound = taps ** -0.5
    xbc = layers.causal_conv1d(
        xbc, filter_size=taps, act='silu',
        param_attr=_param(pre + 'conv_w',
                          fluid.initializer.Uniform(-bound, bound)),
        bias_attr=_param(pre + 'conv_b',
                         fluid.initializer.Uniform(-bound, bound)))
    xs, bm, cm = layers.split(xbc, [inner, g * n, g * n], dim=2)
    a_log = layers.create_parameter(
        [h], 'float32', attr=_param(pre + 'A_log', _a_log_init()))
    y = layers.ssd_scan(
        layers.reshape(xs, [0, 0, h, hd]), dt,
        layers.scale(layers.exp(a_log), scale=-1.0),
        layers.reshape(bm, [0, 0, g, n]), layers.reshape(cm, [0, 0, g, n]),
        layers.create_parameter(
            [h], 'float32',
            attr=_param(pre + 'D', fluid.initializer.Constant(1.0))),
        layers.create_parameter(
            [h], 'float32', attr=_param(pre + 'dt_bias', _dt_bias_init())),
        chunk=cfg['mamba_chunk_size'])
    y = layers.rms_norm(layers.reshape(y, [0, 0, inner]), gate=z,
                        epsilon=cfg['rms_norm_eps'], groups=g,
                        param_attr=_param(pre + 'gate_norm'))
    return _linear(y, cfg['hidden_size'], pre + 'out_proj', std)


def _mlp(x, cfg, pre, std):
    up = _linear(x, 2 * cfg['intermediate_size'], pre + 'mlp_in', std)
    return _linear(fluid.layers.swiglu(up), cfg['hidden_size'],
                   pre + 'mlp_out', std)


def build(cfg=None, max_len=32, lr=0.001, init_std=0.02):
    """Training program over [B, max_len] int64 ids.  ``cfg``: the
    published config's keys (``TINY`` has them all); feeds: ``ids`` and
    ``lbl_ids`` (the next tokens).  The loss is the mean next-token
    cross-entropy over the rows of the embedding that ``cfg['vocab_size']``
    holds."""
    cfg = dict(TINY, **(cfg or {}))
    layers = fluid.layers
    eps, res = cfg['rms_norm_eps'], cfg['residual_multiplier']
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data(name='ids', shape=[max_len], dtype='int64')
        lbl = layers.data(name='lbl_ids', shape=[max_len], dtype='int64')
        emb = layers.embedding(
            input=ids, size=[cfg['vocab_size'], cfg['hidden_size']],
            param_attr=_matrix('granite.embed', init_std))
        h = layers.scale(emb, scale=float(cfg['embedding_multiplier']))
        for i, kind in enumerate(cfg['layer_types']):
            pre = 'granite.l%d.' % i
            mixer = _mamba if kind == 'mamba' else _attention
            x = layers.rms_norm(h, epsilon=eps,
                                param_attr=_param(pre + 'norm1'))
            h = layers.residual_add(h, mixer(x, cfg, pre, init_std), res)
            x = layers.rms_norm(h, epsilon=eps,
                                param_attr=_param(pre + 'norm2'))
            h = layers.residual_add(h, _mlp(x, cfg, pre, init_std), res)
        x = layers.rms_norm(h, epsilon=eps,
                            param_attr=_param('granite.final_norm'))
        # the head is the embedding, transposed: one parameter, two uses
        logits = layers.matmul(
            x, main.global_block().var('granite.embed'), transpose_y=True,
            alpha=1.0 / float(cfg['logits_scaling']))
        cost = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(lbl, axes=[2]))
        avg_cost = layers.mean(cost)
        test_program = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=lr).minimize(avg_cost)
    return dict(main=main, startup=startup, test=test_program,
                feeds=['ids', 'lbl_ids'], logits=logits, loss=avg_cost)


def names(cfg=None):
    """The parameters' names, in the order the reference walks them."""
    cfg = dict(TINY, **(cfg or {}))
    out = ['granite.embed']
    for i, kind in enumerate(cfg['layer_types']):
        mixer = (['in_proj', 'conv_w', 'conv_b', 'dt_bias', 'A_log', 'D',
                  'gate_norm', 'out_proj'] if kind == 'mamba' else
                 ['q_proj', 'k_proj', 'v_proj', 'o_proj'])
        out += ['granite.l%d.%s' % (i, n) for n in
                ['norm1'] + mixer + ['norm2', 'mlp_in', 'mlp_out']]
    return out + ['granite.final_norm']
