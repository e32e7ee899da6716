"""Argument-fidelity tests for the legacy config DSL (VERDICT r3
next-#3): every forwarded kwarg must CHANGE the built model, not just be
accepted.  Reference contract: trainer_config_helpers/layers.py:1500
(lstmemory reverse), :349 (ParameterAttribute on every parameterized
layer), and ParameterAttribute semantics from attrs.py (initial_std /
initial_mean / name; bias_attr=False disables the bias parameter).

The deterministic-parameter trick: ParameterAttribute(initial_std=0.0,
initial_mean=c) pins every weight to the constant c, so outputs are
comparable across independently-created topologies and the reversed
recurrence can be checked against its flip-the-input oracle exactly.
"""

import numpy as np
import pytest

import paddle_tpu.v2 as paddle
from paddle_tpu import trainer_config_helpers as tch


def setup_function(_fn):
    tch.reset_config()


def _const_attr(c, name=None):
    return tch.ParamAttr(initial_std=0.0, initial_mean=c, name=name)


def _lstm_chain(reverse, d=6):
    """x -> deterministic fc(4d) -> lstmemory(reverse=...)."""
    x = tch.data_layer(name='x', size=8, seq=True)
    proj = tch.fc_layer(input=x, size=4 * d, act=tch.LinearActivation(),
                        param_attr=_const_attr(0.1), bias_attr=False)
    lstm = tch.lstmemory(input=proj, size=d, reverse=reverse,
                         param_attr=_const_attr(0.2),
                         bias_attr=_const_attr(0.0))
    return lstm


def _infer_seq(out_layer, seq):
    params = paddle.parameters.create(out_layer)
    return paddle.infer(output_layer=out_layer, parameters=params,
                        input=[(seq, )])


def test_lstmemory_reverse_flips_the_recurrence():
    rng = np.random.RandomState(0)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(5)]

    fwd = _infer_seq(_lstm_chain(reverse=False), seq)
    tch.reset_config()
    rev = _infer_seq(_lstm_chain(reverse=True), seq)
    # the flag must change the computation...
    assert not np.allclose(fwd, rev)
    # ...and must equal the flip-input-flip-output oracle exactly on
    # the valid region (outputs are padded past the true length, so the
    # flip runs over the sequence's own 5 steps, not the padded axis)
    tch.reset_config()
    fwd_on_flipped = _infer_seq(_lstm_chain(reverse=False), seq[::-1])
    np.testing.assert_allclose(rev[:, :5], fwd_on_flipped[:, 4::-1],
                               rtol=1e-5, atol=1e-6)


def test_grumemory_reverse_flips_the_recurrence():
    rng = np.random.RandomState(1)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(5)]

    def chain(reverse):
        x = tch.data_layer(name='x', size=8, seq=True)
        return tch.grumemory(input=x, size=6, reverse=reverse,
                             param_attr=_const_attr(0.15),
                             bias_attr=_const_attr(0.0))

    fwd = _infer_seq(chain(False), seq)
    tch.reset_config()
    rev = _infer_seq(chain(True), seq)
    assert not np.allclose(fwd, rev)
    tch.reset_config()
    fwd_on_flipped = _infer_seq(chain(False), seq[::-1])
    np.testing.assert_allclose(rev[:, :5], fwd_on_flipped[:, 4::-1],
                               rtol=1e-5, atol=1e-6)


def test_fc_bias_attr_false_removes_the_bias_parameter():
    x = tch.data_layer(name='x', size=4)
    out = tch.fc_layer(input=x, size=3, bias_attr=False)
    with_out_bias = paddle.parameters.create(out).names()
    assert len(with_out_bias) == 1, with_out_bias

    tch.reset_config()
    x = tch.data_layer(name='x', size=4)
    out = tch.fc_layer(input=x, size=3)
    with_bias = paddle.parameters.create(out).names()
    assert len(with_bias) == 2, with_bias


def test_fc_param_attr_name_and_initializer_are_honored():
    x = tch.data_layer(name='x', size=4)
    out = tch.fc_layer(input=x, size=3, act=tch.LinearActivation(),
                       param_attr=_const_attr(0.25, name='fid_w'),
                       bias_attr=_const_attr(0.5, name='fid_b'))
    params = paddle.parameters.create(out)
    assert 'fid_w' in params.names() and 'fid_b' in params.names()
    np.testing.assert_allclose(params.get('fid_w'), 0.25)
    np.testing.assert_allclose(params.get('fid_b'), 0.5)
    # and the forward actually uses them: y = x @ 0.25 + 0.5
    xv = np.arange(4, dtype='float32')
    got = paddle.infer(output_layer=out, parameters=params,
                       input=[(xv, )])
    np.testing.assert_allclose(got, np.full((1, 3), xv.sum() * 0.25 + 0.5),
                               rtol=1e-5)


def test_embedding_param_attr_initializer_is_honored():
    words = tch.data_layer(name='w', size=11, data_type_kind='index',
                           seq=True)
    emb = tch.embedding_layer(input=words, size=5,
                              param_attr=_const_attr(0.125, name='emb_t'))
    params = paddle.parameters.create(emb)
    assert 'emb_t' in params.names()
    tab = params.get('emb_t')
    assert tab.shape == (11, 5)
    np.testing.assert_allclose(tab, 0.125)


def test_recurrent_group_reverse_is_the_suffix_scan():
    """recurrent_group(reverse=True) scans back-to-front with outputs
    at ORIGINAL positions (reference layers.py:4161): a running-sum
    step turns prefix sums into suffix sums, mask-aware on ragged
    lengths."""
    import paddle_tpu.fluid as fluid
    import paddle_tpu.v2.layer as L
    x = tch.data_layer(name='x', size=1, seq=True)

    def make(rev):
        def step(tok):
            mem = tch.memory(name='acc%d' % rev, size=1)
            return L.addto(input=[tok, mem], name='acc%d' % rev)
        return tch.recurrent_group(step=step, input=[x],
                                   reverse=bool(rev))

    fwd, rev = make(0), make(1)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ctx = {}
        fv, rv = fwd.to_fluid(ctx), rev.to_fluid(ctx)
    lt = fluid.create_lod_tensor(
        np.asarray([[1.], [2.], [3.], [10.], [20.]], 'float32'),
        [[3, 2]])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.core.Scope()):
        exe.run(startup)
        f, r = exe.run(main, feed={'x': lt}, fetch_list=[fv, rv])
    f, r = np.asarray(f), np.asarray(r)
    np.testing.assert_allclose(f[0, :3, 0], [1, 3, 6])
    np.testing.assert_allclose(f[1, :2, 0], [10, 30])
    np.testing.assert_allclose(r[0, :3, 0], [6, 5, 3])
    np.testing.assert_allclose(r[1, :2, 0], [30, 20])


def test_recurrent_layer_reverse_matches_forward_on_flipped_input():
    """recurrent_layer(reverse=True) — previously rejected — now runs
    the reference recurrence backward (flip-input oracle)."""
    rng = np.random.RandomState(3)
    seq = [rng.standard_normal(6).astype('float32') for _ in range(4)]

    def chain(reverse):
        x = tch.data_layer(name='x', size=6, seq=True)
        return tch.recurrent_layer(input=x, size=6, reverse=reverse)

    rev = _infer_seq(chain(True), seq)
    tch.reset_config()
    plain = _infer_seq(chain(False), seq)
    assert not np.allclose(plain, rev)
    # parameter init is deterministic across rebuilds, so the exact
    # flip-input-flip-output oracle pins the semantics (same trick as
    # the lstmemory/grumemory reverse tests)
    tch.reset_config()
    fwd_on_flipped = _infer_seq(chain(False), seq[::-1])
    np.testing.assert_allclose(rev[:, :4], fwd_on_flipped[:, 3::-1],
                               rtol=1e-5, atol=1e-6)


def test_batch_norm_epsilon_and_attrs_forward():
    """batch_norm_layer's epsilon changes the normalization and its
    param/bias attrs reach the scale/shift parameters (previously
    swallowed by **kwargs — tools/dsl_signature_audit.py class)."""
    def build(eps):
        x = tch.data_layer(name='x', size=2 * 4 * 4)
        return tch.batch_norm_layer(
            input=tch.img_conv_layer(
                input=x, filter_size=3, num_filters=2, num_channels=2,
                padding=1, param_attr=_const_attr(0.1), bias_attr=False),
            epsilon=eps,
            param_attr=_const_attr(2.0, name='bn_s%s' % eps),
            bias_attr=_const_attr(0.5))
    rng = np.random.RandomState(0)
    xv = rng.standard_normal(32).astype('float32')
    a = _infer_seq_dense(build(1e-5), xv)
    tch.reset_config()
    b = _infer_seq_dense(build(0.5), xv)
    assert not np.allclose(a, b), 'epsilon had no effect'
    # scale=2/bias=0.5 differ from the default init (1/0): reverting
    # the attr forwarding must change this output
    tch.reset_config()
    x2 = tch.data_layer(name='x', size=2 * 4 * 4)
    plain = tch.batch_norm_layer(
        input=tch.img_conv_layer(
            input=x2, filter_size=3, num_filters=2, num_channels=2,
            padding=1, param_attr=_const_attr(0.1), bias_attr=False),
        epsilon=1e-5)
    c = _infer_seq_dense(plain, xv)
    assert not np.allclose(a, c), 'param/bias attrs had no effect'


def _infer_seq_dense(out_layer, xv):
    params = paddle.parameters.create(out_layer)
    return paddle.infer(output_layer=out_layer, parameters=params,
                        input=[(xv, )])


def test_reference_default_activations():
    """The legacy DSL's wrapped defaults (wrap_act_default): fc=Tanh,
    img_conv/batch_norm=ReLU — omitting act must NOT mean linear
    (reference layers.py:1013,2508,3245)."""
    x = tch.data_layer(name='x', size=4)
    dflt = tch.fc_layer(input=x, size=3,
                        param_attr=_const_attr(0.25, name='da_w'),
                        bias_attr=False)
    xv = np.arange(4, dtype='float32')
    got = _infer_seq_dense(dflt, xv)
    want = np.tanh(np.full((1, 3), xv.sum() * 0.25))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_dsl_signature_audit_has_no_silent_missing():
    """The automated audit (tools/dsl_signature_audit.py): every
    reference builder parameter is either explicit in our signature or
    absorbed by **kwargs — never a silent TypeError surprise."""
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        'tools'))
    import dsl_signature_audit as aud
    if not _os.path.exists(aud.REF):
        pytest.skip('the reference checkout %s is not on this machine'
                    % aud.REF)
    rows = aud.audit()
    missing = [(n, p) for n, p, cls in rows if cls == 'n/a']
    assert not missing, missing
    assert len({n for n, _, _ in rows}) >= 100  # the audit really ran


def test_param_attr_mean_with_unset_std_still_breaks_symmetry():
    """initial_mean with initial_std UNSET must keep the legacy default
    gaussian (std 1/sqrt(fan_in)), NOT collapse to a constant — a
    constant would pin every hidden unit identical forever."""
    x = tch.data_layer(name='x', size=16)
    out = tch.fc_layer(input=x, size=8, act=tch.LinearActivation(),
                       param_attr=tch.ParamAttr(initial_mean=0.05,
                                                name='sym_w'),
                       bias_attr=False)
    params = paddle.parameters.create(out)
    w = params.get('sym_w')
    # centered near the mean, but NOT constant
    assert np.std(w) > 1e-3, 'weights collapsed to a constant'
    assert abs(np.mean(w) - 0.05) < 3 * (1 / 4.0) / np.sqrt(w.size)


def test_layer_attr_drop_rate_wraps_in_dropout():
    x = tch.data_layer(name='x', size=4)
    plain = tch.fc_layer(input=x, size=3)
    assert plain.kind == 'fc'
    dropped = tch.fc_layer(input=x, size=3, name='nm',
                           layer_attr=tch.ExtraAttr(drop_rate=0.5))
    assert dropped.kind == 'dropout'
    assert dropped.parents[0].kind == 'fc'
    # the user-facing NAME resolves to the post-dropout value, so
    # memory(name='nm') links see dropout (legacy config_parser applies
    # drop_rate on the named layer itself)
    assert dropped.name == 'nm'
    assert dropped.parents[0].name != 'nm'


def test_img_conv_bias_attr_false_and_param_name():
    img = tch.data_layer(name='img', size=2 * 8 * 8)
    conv = tch.img_conv_layer(input=img, filter_size=3, num_filters=4,
                              num_channels=2, padding=1,
                              param_attr=_const_attr(0.01, name='cw'),
                              bias_attr=False)
    params = paddle.parameters.create(conv)
    assert params.names() == ['cw'], params.names()


def test_simple_lstm_projection_is_linear_and_biasfree():
    """Composite fidelity (reference networks.py:696): simple_lstm's
    size*4 gate transform is a bias-free LINEAR mixed_layer.  With
    pinned parameters the composite must equal the manual chain built
    with an explicit LinearActivation — if the fc Tanh default leaked
    into the composite, the gate pre-activations would be squashed and
    the outputs diverge."""
    from paddle_tpu.trainer_config_helpers import networks as tchn
    rng = np.random.RandomState(1)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(5)]

    comp = tchn.simple_lstm(
        input=tch.data_layer(name='x', size=8, seq=True), size=6,
        mat_param_attr=_const_attr(0.1),
        inner_param_attr=_const_attr(0.2),
        bias_param_attr=_const_attr(0.0))
    got = _infer_seq(comp, seq)
    tch.reset_config()
    want = _infer_seq(_lstm_chain(reverse=False), seq)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_simple_lstm_reverse_forwards():
    from paddle_tpu.trainer_config_helpers import networks as tchn

    def build(reverse):
        return tchn.simple_lstm(
            input=tch.data_layer(name='x', size=8, seq=True), size=6,
            reverse=reverse, mat_param_attr=_const_attr(0.1),
            inner_param_attr=_const_attr(0.2),
            bias_param_attr=_const_attr(0.0))

    rng = np.random.RandomState(2)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(4)]
    fwd = _infer_seq(build(False), seq)
    tch.reset_config()
    rev = _infer_seq(build(True), seq)
    assert not np.allclose(fwd, rev), 'reverse was swallowed'


def test_img_conv_bn_pool_conv_is_linear():
    """Composite fidelity (reference networks.py:308): the conv under
    batch_norm is explicitly LINEAR; a leaked ReLU default would clip
    the negative conv outputs before normalization and shift the BN
    statistics."""
    from paddle_tpu.trainer_config_helpers import networks as tchn

    def composite():
        x = tch.data_layer(name='img', size=2 * 4 * 4)
        return tchn.img_conv_bn_pool(
            input=x, filter_size=3, num_filters=2, pool_size=2,
            num_channel=2,
            conv_param_attr=_const_attr(0.1), conv_bias_attr=False,
            bn_param_attr=_const_attr(1.0, name='bn_scale'),
            bn_bias_attr=_const_attr(0.0))

    def manual():
        x = tch.data_layer(name='img', size=2 * 4 * 4)
        conv = tch.img_conv_layer(input=x, filter_size=3, num_filters=2,
                                  num_channels=2,
                                  act=tch.LinearActivation(),
                                  param_attr=_const_attr(0.1),
                                  bias_attr=False)
        bn = tch.batch_norm_layer(input=conv,
                                  param_attr=_const_attr(1.0,
                                                         name='bn_s2'),
                                  bias_attr=_const_attr(0.0))
        return tch.img_pool_layer(input=bn, pool_size=2)

    # negative inputs make the linear conv produce negative values, so
    # an erroneous pre-BN ReLU cannot be invisible
    xv = -np.abs(np.random.RandomState(3).standard_normal(32)) \
        .astype('float32')
    got = _infer_seq_dense(composite(), xv)
    tch.reset_config()
    want = _infer_seq_dense(manual(), xv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_layer_state_product_is_linear():
    """recurrent_layer's documented recurrence is
    out_t = act(in_t + out_{t-1} W + b): the state-weight product
    enters the addto LINEARLY.  Verified against a hand-rolled numpy
    recurrence with pinned parameters — a leaked fc Tanh default would
    compute act(in_t + tanh(out_{t-1} W + b)) instead."""
    d = 4
    x = tch.data_layer(name='x', size=d, seq=True)
    out = tch.recurrent_layer(input=x, act=tch.TanhActivation(),
                              param_attr=_const_attr(0.3, name='rw'))
    rng = np.random.RandomState(4)
    seq = [rng.standard_normal(d).astype('float32') for _ in range(5)]
    got = _infer_seq(out, seq)

    w = np.full((d, d), 0.3, dtype='float32')
    h = np.zeros(d, dtype='float32')
    want = []
    for t in range(5):
        h = np.tanh(seq[t] + h @ w)
        want.append(h)
    got = np.asarray(got)
    np.testing.assert_allclose(got.reshape(-1, d)[:5], np.stack(want),
                               rtol=1e-4, atol=1e-5)


def test_batch_norm_explicit_false_wins_over_is_test():
    """fluid contract: batch_norm(is_test=True, use_global_stats=False)
    uses BATCH statistics via the direct path AND the
    clone(for_test=True) path (both routes agree), and neither test
    route drifts the checkpointed moving averages."""
    import paddle_tpu.fluid as fluid

    def build(is_test):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            xv = fluid.layers.data('x', [3], dtype='float32')
            y = fluid.layers.batch_norm(xv, is_test=is_test,
                                        use_global_stats=False)
        return prog, startup, y

    rng = np.random.RandomState(5)
    x = (rng.standard_normal((16, 3)) * 5 + 7).astype('float32')

    exe = fluid.Executor(fluid.CPUPlace())
    outs, moving_means = [], []

    def run(prog, startup, yname):
        # the moving-average slots come from the op's own input list
        # (they are named batch_norm_N.w_K, not *mean*)
        bn_op = [o for o in prog.blocks[0].ops
                 if o.type == 'batch_norm'][0]
        mean_name = bn_op.inputs['Mean'][0]
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            out = exe.run(prog, feed={'x': x}, fetch_list=[yname])[0]
            # a few more eval passes, then read the moving mean
            for _ in range(3):
                exe.run(prog, feed={'x': x}, fetch_list=[yname])
            mv = exe.run(prog, feed={'x': x}, fetch_list=[mean_name])[0]
        return out, np.copy(mv)

    # direct is_test route
    for is_test in (False, True):
        prog, startup, y = build(is_test)
        out, mv = run(prog, startup, y.name)
        outs.append(out)
        if is_test:
            moving_means.append(mv)
    # clone(for_test=True) route
    prog, startup, y = build(False)
    test_prog = prog.clone(for_test=True)
    out, mv = run(test_prog, startup, y.name)
    outs.append(out)
    moving_means.append(mv)

    # batch statistics every time: all three outputs identical, and
    # actually normalized (mean~0) rather than scaled by moving stats
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5, atol=1e-6)
    assert abs(float(np.mean(outs[1]))) < 1e-3
    # the moving mean is untouched by the test-mode passes (init 0.0;
    # the feed mean is ~7, so a single leaked update would move it) -
    # eval batches must not drift the checkpointed averages even though
    # they normalize with batch statistics
    assert moving_means, 'no test-mode moving means were collected'
    for mv in moving_means:
        np.testing.assert_allclose(mv, np.zeros_like(mv), atol=1e-7)


def test_simple_gru2_single_projection():
    """Composite fidelity (reference networks.py:1207): simple_gru2 is
    ONE pinned linear projection + the raw GRU - gru_like must not add
    a second hidden [3S,3S] projection when its input is already
    3S-wide (double projection diverges from the reference and burns an
    extra matmul per step)."""
    from paddle_tpu.trainer_config_helpers import networks as tchn

    def composite():
        x = tch.data_layer(name='x', size=8, seq=True)
        return tchn.simple_gru2(input=x, size=6,
                                mixed_param_attr=_const_attr(0.1),
                                mixed_bias_attr=False,
                                gru_param_attr=_const_attr(0.2),
                                gru_bias_attr=_const_attr(0.0))

    def manual():
        x = tch.data_layer(name='x', size=8, seq=True)
        proj = tch.fc_layer(input=x, size=18, act=tch.LinearActivation(),
                            param_attr=_const_attr(0.1), bias_attr=False)
        return tch.grumemory(input=proj, size=6,
                             param_attr=_const_attr(0.2),
                             bias_attr=_const_attr(0.0))

    rng = np.random.RandomState(6)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(5)]
    got = _infer_seq(composite(), seq)
    tch.reset_config()
    want = _infer_seq(manual(), seq)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_batch_norm_default_program_serializes():
    """The tri-state use_global_stats default must not leak a None attr
    onto the proto wire: a default batch_norm program round-trips
    through serialize/deserialize (reproduces the round-4 review's
    save_inference_model crash)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import proto_serde
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        x = fluid.layers.data('x', [4], dtype='float32')
        fluid.layers.batch_norm(fluid.layers.fc(x, 8))
        # the explicit tri-states serialize as real booleans
        fluid.layers.batch_norm(fluid.layers.fc(x, 8),
                                use_global_stats=False)
        fluid.layers.batch_norm(fluid.layers.fc(x, 8),
                                use_global_stats=True)
    wire = proto_serde.serialize_program(prog)
    back = proto_serde.deserialize_program(wire)
    bns = [o for o in back.blocks[0].ops if o.type == 'batch_norm']
    assert [o.attrs.get('use_global_stats') for o in bns] == \
        [None, False, True]


def test_bidirectional_gru_param_attrs_forward():
    """bidirectional_gru's per-arm mixed/gru attrs (reference
    networks.py:1226) must reach the projections and recurrences: with
    all weights pinned the composite equals the manual two-arm build."""
    from paddle_tpu.trainer_config_helpers import networks as tchn
    rng = np.random.RandomState(12)
    seq = [rng.standard_normal(8).astype('float32') for _ in range(4)]

    def composite():
        x = tch.data_layer(name='x', size=8, seq=True)
        return tchn.bidirectional_gru(
            input=x, size=6, return_seq=True,
            fwd_mixed_param_attr=_const_attr(0.1),
            fwd_mixed_bias_attr=False,
            fwd_gru_param_attr=_const_attr(0.2),
            fwd_gru_bias_attr=_const_attr(0.0),
            bwd_mixed_param_attr=_const_attr(0.15),
            bwd_mixed_bias_attr=False,
            bwd_gru_param_attr=_const_attr(0.25),
            bwd_gru_bias_attr=_const_attr(0.0))

    def manual():
        x = tch.data_layer(name='x', size=8, seq=True)
        fp = tch.fc_layer(input=x, size=18, act=tch.LinearActivation(),
                          param_attr=_const_attr(0.1), bias_attr=False)
        fwd = tch.grumemory(input=fp, size=6,
                            param_attr=_const_attr(0.2),
                            bias_attr=_const_attr(0.0))
        bp = tch.fc_layer(input=x, size=18, act=tch.LinearActivation(),
                          param_attr=_const_attr(0.15), bias_attr=False)
        bwd = tch.grumemory(input=bp, size=6, reverse=True,
                            param_attr=_const_attr(0.25),
                            bias_attr=_const_attr(0.0))
        return tch.concat_layer(input=[fwd, bwd])

    got = _infer_seq(composite(), seq)
    tch.reset_config()
    want = _infer_seq(manual(), seq)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
