"""granite-4.0-h on the CPU at toy sizes: the ops it brought
(``ops/ssm_ops.py``, grouped heads in ``flash_attention``), the model
(``models/granite_hybrid.py``) and its plain reference
(``models/reference/granite_hybrid_ref.py``), on seeded weights.

Tolerances.  Without AMP everything is float32 on both sides and differs
only in the order of sums (a chunk's products against a recurrence, a
fused softmax against a plain one): 2e-5 of the value's own scale, where
observed differences are 1e-7 to 2e-6.  Under AMP the program's matmuls
take bf16 inputs (8 bits of mantissa, 0.4% a rounding) with f32 sums
against the reference's f32: gradients read 0.2-0.8% of their norm here,
and 3% is the limit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models.reference import granite_hybrid_ref as ref

F32_TOL = 2e-5     # of the value's own scale: float32, another order of sums
# the scan's decay is exp of a DIFFERENCE of cumulative sums of dt A in a
# chunk, the recurrence's a running product of exps: with |dt A| up to 10
# and 24 positions the f32 roundings differ by up to 4e-5 of the scale
SCAN_TOL = 1e-4
AMP_TOL = 3e-2     # of the gradient's norm: bf16 matmul inputs against f32


def close(got, want, tol=F32_TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def layer_program(build, feeds):
    """``out = build(**data variables)``, one differentiable data variable
    a feed, in a fresh Program: (main, startup, out)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {}
        for name, value in feeds.items():
            data[name] = main.global_block().create_var(
                name=name, shape=value.shape, dtype=value.dtype,
                is_data=True)
            data[name].stop_gradient = False
        out = build(**data)
    return main, startup, out


def run_layer(build, feeds, seed=0):
    """The layer on the CPU place; loss = sum(out * w) for a seeded w.
    Returns (out, {feed name: d loss / d feed}, w)."""
    main, startup, out = layer_program(build, feeds)
    w = np.random.RandomState(seed).standard_normal(
        out.shape).astype('float32')
    with fluid.program_guard(main, startup):
        wv = main.global_block().create_var(
            name='loss_w', shape=w.shape, dtype=w.dtype, is_data=True)
        append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, wv)))
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=dict(feeds, loss_w=w),
                      fetch_list=[out] + [n + '@GRAD' for n in feeds])
    return np.asarray(got[0]), dict(zip(feeds, map(np.asarray, got[1:]))), w


def want_of(fn, feeds, w):
    """The same from a plain ``jax.numpy`` function of the feeds."""
    args = [jnp.asarray(v) for v in feeds.values()]
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                     argnums=tuple(range(len(args))))(*args)
    return np.asarray(fn(*args)), dict(zip(feeds, map(np.asarray, grads)))


def check_layer(build, fn, feeds, tol=F32_TOL):
    out, grads, w = run_layer(build, feeds)
    want_out, want_grads = want_of(fn, feeds, w)
    close(out, want_out, tol)
    assert set(grads) == set(want_grads)
    for name in grads:
        close(grads[name], want_grads[name], tol)


# ---- the scan ---------------------------------------------------------

B, H, P, G, N = 2, 4, 8, 1, 8


def scan_feeds(length, groups=G, seed=1):
    r = np.random.RandomState(seed)
    f = lambda *s: r.standard_normal(s).astype('float32')   # noqa: E731
    return {'x': f(B, length, H, P), 'dt': f(B, length, H) - 1.0,
            'a_log': np.log(r.uniform(1, 8, H)).astype('float32'),
            'bm': f(B, length, groups, N), 'cm': f(B, length, groups, N),
            'd': f(H), 'dt_bias': f(H)}


def scan_layer(chunk):
    def build(x, dt, a_log, bm, cm, d, dt_bias):
        layers = fluid.layers
        a = layers.scale(layers.exp(a_log), scale=-1.0)
        return layers.ssd_scan(x, dt, a, bm, cm, d, dt_bias, chunk=chunk)
    return build


def scan_recurrence(x, dt, a_log, bm, cm, d, dt_bias):
    return ref.ssm_recurrence(x, jax.nn.softplus(dt + dt_bias),
                              -jnp.exp(a_log), bm, cm, d)


@pytest.mark.parametrize('length,chunk', [
    (24, 4), (24, 8), (24, 24), (22, 8), (24, 256)],
    ids=['6_chunks_of_4', '3_chunks_of_8', 'one_chunk', 'padded_last_chunk',
         'chunk_past_the_length'])
def test_ssd_scan_and_its_gradient_match_the_recurrence(length, chunk):
    """Forward and every input's gradient (X, dt, A through A_log, B, C, D,
    dt_bias) against the step-by-step recurrence: several chunks, so the
    recurrence between chunks and the saved chunk states are in play; one
    chunk; a last chunk that is padded."""
    check_layer(scan_layer(chunk), scan_recurrence, scan_feeds(length),
                tol=SCAN_TOL)


def test_ssd_scan_two_groups():
    """Heads 0-1 read group 0's B and C, heads 2-3 group 1's."""
    check_layer(scan_layer(8), scan_recurrence, scan_feeds(24, groups=2),
                tol=SCAN_TOL)


def test_ssd_scan_gives_the_same_for_every_chunk_size():
    feeds = scan_feeds(24)
    runs = [run_layer(scan_layer(c), feeds) for c in (4, 8, 12, 24)]
    for out, grads, _ in runs[1:]:
        close(out, runs[0][0], SCAN_TOL)
        for name in grads:
            close(grads[name], runs[0][1][name], SCAN_TOL)


def test_ssd_scan_reads_no_later_position():
    feeds = scan_feeds(24)
    base, _, _ = run_layer(scan_layer(8), feeds)
    t = 13     # inside the second chunk
    moved = dict(feeds)
    for name in ('x', 'dt', 'bm', 'cm'):
        moved[name] = feeds[name].copy()
        moved[name][:, t] += 1.0
    out, _, _ = run_layer(scan_layer(8), moved)
    assert np.array_equal(out[:, :t], base[:, :t])
    assert np.abs(out[:, t:] - base[:, t:]).max() > 1e-3


def test_ssd_scan_records_its_choice_and_keeps_its_states_out_of_fetches():
    from paddle_tpu.fluid import trace
    run_layer(scan_layer(8), scan_feeds(24))
    seen = trace.lowering_choices('ssd_scan', seen=True)[-1]
    assert list(seen.values()) == [
        {'choice': 'xla', 'chunk': 8, 'chunks': 3}]
    assert trace.lowering_choices('ssd_scan')[-1] == {'xla': 1}


def test_ssd_scan_gradient_recomputes_behind_a_barrier():
    """The gradient's lowering makes the decay matrix and the scores again
    under ``jax.checkpoint``: its jaxpr holds the rematerialised pieces, so
    XLA cannot merge them with the forward's and keep a [H, chunk, chunk]
    tensor from the forward to the backward (at the cell's size the
    compiled step's temporaries are 1.5 GB for nine scans: PERF.md)."""
    from paddle_tpu.ops import registry
    feeds = scan_feeds(24)
    main, startup, out = layer_program(scan_layer(8), feeds)
    with fluid.program_guard(main, startup):
        append_backward(fluid.layers.reduce_sum(out))
    block = main.global_block()

    def step(env):
        env = dict(env)
        ctx = registry.LoweringContext(block, env, place=fluid.CPUPlace())
        for op in block.ops:
            registry.run_op(ctx, op)
        return env['x@GRAD']

    text = jax.jit(step).lower({k: jnp.asarray(v)
                                for k, v in feeds.items()}).as_text()
    # one round the chunks' outputs, one round the chunks' own states
    assert text.count('optimization_barrier') >= 2


# ---- the convolution, the norms, the gated activation, the residual ----

def conv_feeds(seed=2):
    r = np.random.RandomState(seed)
    return {'x': r.standard_normal((2, 12, 6)).astype('float32')}


def conv_layer(act):
    def build(x):
        return fluid.layers.causal_conv1d(
            x, filter_size=4, act=act,
            param_attr=fluid.ParamAttr(
                name='cw', initializer=fluid.initializer.Uniform(-.5, .5)),
            bias_attr=fluid.ParamAttr(
                name='cb', initializer=fluid.initializer.Uniform(-.5, .5)))
    return build


@pytest.mark.parametrize('act', [None, 'silu'])
def test_causal_conv1d_matches_the_shifted_sums(act):
    """Against the reference's convolution on the Program's own filter and
    bias (read back from the scope)."""
    main, startup = fluid.Program(), fluid.Program()
    feeds = conv_feeds()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [12, 6], dtype='float32')
        x.stop_gradient = False
        out = conv_layer(act)(x)
        append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, out)))
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w, bias = (np.asarray(scope.find_var(n).get_tensor())
                   for n in ('cw', 'cb'))
        got = exe.run(main, feed=feeds,
                      fetch_list=[out, 'x@GRAD', 'cw@GRAD', 'cb@GRAD'])

    def fn(x, w, bias):
        y = ref.causal_conv(x, w, bias)
        return jax.nn.silu(y) if act else y

    args = (jnp.asarray(feeds['x']), jnp.asarray(w), jnp.asarray(bias))
    close(got[0], fn(*args))
    want = jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))(
        *args)
    for g, wg in zip(got[1:], want):
        close(g, wg)


def test_causal_conv1d_reads_no_later_position():
    feeds = conv_feeds()
    base, _, _ = run_layer(conv_layer('silu'), feeds)
    t = 7
    moved = {'x': feeds['x'].copy()}
    moved['x'][:, t] += 1.0
    out, _, _ = run_layer(conv_layer('silu'), moved)
    assert np.array_equal(out[:, :t], base[:, :t])
    # position t and the three after it read it; the fifth does not
    assert np.abs(out[:, t:t + 4] - base[:, t:t + 4]).min() > 0
    assert np.array_equal(out[:, t + 4:], base[:, t + 4:])


def norm_feeds(gate=True):
    r = np.random.RandomState(3)
    feeds = {'x': r.standard_normal((2, 5, 16)).astype('float32'),
             'z': r.standard_normal((2, 5, 16)).astype('float32')}
    return feeds if gate else {'x': feeds['x']}


def test_rms_norm_matches_jax_numpy():
    attr = fluid.ParamAttr(initializer=fluid.initializer.Constant(1.5))
    check_layer(
        lambda x: fluid.layers.rms_norm(x, epsilon=1e-5, param_attr=attr),
        lambda x: ref.rms(x, 1.5, 1e-5), norm_feeds(gate=False))


def test_gated_rms_norm_matches_jax_numpy():
    attr = fluid.ParamAttr(initializer=fluid.initializer.Constant(0.5))
    check_layer(
        lambda x, z: fluid.layers.rms_norm(x, gate=z, param_attr=attr),
        lambda x, z: ref.rms(x * jax.nn.silu(z), 0.5, 1e-5), norm_feeds())


def test_swiglu_matches_jax_numpy():
    def fn(x):
        g, u = jnp.split(x, 2, axis=-1)
        return jax.nn.silu(g) * u
    check_layer(lambda x: fluid.layers.swiglu(x), fn,
                norm_feeds(gate=False))


def test_residual_add_matches_and_keeps_the_stream_f32_under_amp():
    check_layer(lambda x, z: fluid.layers.residual_add(x, z, scale=0.22),
                lambda x, z: x + 0.22 * z, norm_feeds())
    # under AMP a bf16 branch is widened into the f32 stream
    # (elementwise_add would narrow the stream)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [5, 16], dtype='float32')
        branch = fluid.layers.fc(x, 16, num_flatten_dims=2)
        out = fluid.layers.residual_add(x, branch, scale=0.22)
        narrowed = fluid.layers.elementwise_add(x, branch)
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={'x': norm_feeds()['x']},
                      fetch_list=[out, narrowed, branch],
                      return_numpy=False)
    dtypes = [str(jnp.asarray(g).dtype) for g in got]
    assert dtypes == ['float32', 'bfloat16', 'bfloat16'], dtypes


# ---- grouped heads in flash_attention ---------------------------------

AB, AL, AD = 2, 16, 8


def attention_feeds(hq, hkv, seed=4):
    r = np.random.RandomState(seed)
    f = lambda *s: r.standard_normal(s).astype('float32')   # noqa: E731
    return {'q': f(AB, AL, hq * AD), 'k': f(AB, AL, hkv * AD),
            'v': f(AB, AL, hkv * AD)}


def dense_repeated(hq, hkv, scale):
    def fn(q, k, v):
        q = q.reshape(AB, AL, hq, AD)
        k, v = (jnp.repeat(t.reshape(AB, AL, hkv, AD), hq // hkv, axis=2)
                for t in (k, v))
        s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((AL, AL), bool)), s, -jnp.inf)
        o = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), v)
        return o.reshape(AB, AL, hq * AD)
    return fn


@pytest.mark.parametrize('impl', ['dense', 'pallas'])
@pytest.mark.parametrize('hq,hkv', [(4, 2), (4, 1), (4, 4)],
                         ids=['4_over_2', '4_over_1', 'equal_heads'])
def test_flash_attention_grouped_heads_match_dense_with_repeated_heads(
        hq, hkv, impl):
    """Forward, dQ, and dK / dV summed over each key-value head's query
    heads, causal, the scale passed through (0.25, not 1/sqrt(8)); the
    fused kernel (interpreted here) and dense attention; equal heads as
    before."""
    def build(q, k, v):
        return fluid.layers.flash_attention(
            q, k, v, num_heads=hq, num_kv_heads=hkv, causal=True,
            scale=0.25, impl=impl)
    # the kernel sums a row's columns tile by tile: 1e-4 as
    # tests/test_pallas_flash.py's gradients
    check_layer(build, dense_repeated(hq, hkv, 0.25),
                attention_feeds(hq, hkv),
                tol=F32_TOL if impl == 'dense' else 2e-4)


def test_flash_attention_records_the_head_counts_it_saw():
    from paddle_tpu.fluid import trace
    run_layer(lambda q, k, v: fluid.layers.flash_attention(
        q, k, v, num_heads=4, num_kv_heads=2, causal=True),
        attention_feeds(4, 2))
    seen = list(trace.lowering_choices('flash_attention', seen=True)[-1]
                .values())
    assert seen == [{'choice': 'dense', 'heads': 4, 'kv_heads': 2}]
    assert trace.lowering_choices('flash_attention')[-1] == {'dense': 1}


def test_flash_attention_refuses_heads_that_do_not_divide():
    with pytest.raises(ValueError, match='whole multiple'):
        run_layer(lambda q, k, v: fluid.layers.flash_attention(
            q, k, v, num_heads=4, num_kv_heads=3), {
                'q': np.zeros((1, 8, 32), 'float32'),
                'k': np.zeros((1, 8, 24), 'float32'),
                'v': np.zeros((1, 8, 24), 'float32')})


# ---- the whole model ---------------------------------------------------

LEN, ROWS = 32, 2


def model_batch(seed=0):
    r = np.random.RandomState(seed)
    # Zipf-like ids, so that there is a unigram distribution to learn
    p = 1.0 / np.arange(1, 127)
    ids = 2 + r.choice(126, size=(ROWS, LEN), p=p / p.sum())
    ids = ids.astype('int64')
    return {'ids': ids, 'lbl_ids': np.concatenate(
        [ids[:, 1:], np.ones((ROWS, 1), 'int64')], axis=1)}


def trained_once(amp, seed=7):
    """(program's loss and gradients on one batch, the reference's on the
    same weights and batch, the names)."""
    model = gh.build(max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = seed
    names = gh.names()
    scope, feed = fluid.core.Scope(), model_batch()
    with fluid.scope_guard(scope), fluid.amp_guard(amp):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        weights = {n: jnp.asarray(np.asarray(
            scope.find_var(n).get_tensor())) for n in names}
        got = exe.run(model['main'], feed=feed, fetch_list=[
            model['loss']] + [n + '@GRAD' for n in names])
    assert sorted(names) == sorted(
        p.name for p in model['main'].global_block().all_parameters())
    want = ref.loss_and_grads(weights, gh.TINY, jnp.asarray(feed['ids']),
                              jnp.asarray(feed['lbl_ids']))
    return [np.asarray(g) for g in got], want, names


def test_model_loss_and_every_gradient_match_the_reference():
    got, (want_loss, want_grads), names = trained_once(amp=False)
    close(got[0].ravel()[0], want_loss)
    for name, g in zip(names, got[1:]):
        close(g, want_grads[name])


def test_model_under_amp_stays_within_bf16_of_the_reference():
    got, (want_loss, want_grads), names = trained_once(amp=True)
    # the loss is a mean of 64 f32 log-probabilities of bf16 logits
    assert abs(float(got[0].ravel()[0]) - float(want_loss)) < 5e-3
    for name, g in zip(names, got[1:]):
        r = np.asarray(want_grads[name])
        assert g.dtype == np.float32      # master gradients
        assert np.linalg.norm(g - r) <= AMP_TOL * np.linalg.norm(r), name


def test_tied_embedding_receives_both_gradients():
    """d loss / d E is the head's product's gradient plus the lookup's
    scatter: each alone is not the reference's, and a row that no id names
    and no label asks for still has the head's (every row is a logit)."""
    got, (_, want_grads), names = trained_once(amp=False)
    g = got[1 + names.index('granite.embed')]
    want = np.asarray(want_grads['granite.embed'])
    close(g, want)
    feed = model_batch()
    unseen = sorted(set(range(128)) - set(feed['ids'].ravel())
                    - set(feed['lbl_ids'].ravel()))
    seen = sorted(set(feed['ids'].ravel()))
    assert unseen and np.abs(g[unseen]).max() > 0          # the head's
    # the lookup's: rows that are read carry far more than those that
    # are only logits
    assert np.abs(g[seen]).mean() > 3 * np.abs(g[unseen]).mean()


def test_model_reads_no_later_position():
    model = gh.build(max_len=LEN)
    model['main'].random_seed = model['startup'].random_seed = 3
    feed = model_batch()
    moved = {k: v.copy() for k, v in feed.items()}
    t = 19
    moved['ids'][:, t] = (feed['ids'][:, t] - 2 + 5) % 126 + 2
    with fluid.scope_guard(fluid.core.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        a, = exe.run(model['test'], feed=feed, fetch_list=[model['logits']])
        b, = exe.run(model['test'], feed=moved,
                     fetch_list=[model['logits']])
    a, b = np.asarray(a), np.asarray(b)
    assert np.array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t:] - b[:, t:]).max() > 1e-4


def test_two_dispatches_through_the_k_step_lane_lower_the_loss():
    """``Executor`` + ``FeedPipeline`` with K=4 under AMP with adam, as the
    cell runs it."""
    model = gh.build(max_len=LEN, lr=0.003)
    model['main'].random_seed = model['startup'].random_seed = 11
    source = (model_batch(seed=i) for i in range(16))
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(model['startup'])
        pipe = fluid.FeedPipeline(exe, [model['loss']], source=source,
                                  steps=4, program=model['main'])
        deliveries = iter(pipe)
        losses = [float(np.asarray(next(deliveries)[0]).ravel()[0])
                  for _ in range(3)]
        deliveries.close()
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05, \
        losses


def test_initial_values_follow_the_family():
    model = gh.build(max_len=LEN)
    model['startup'].random_seed = 5
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(model['startup'])
        get = lambda n: np.asarray(   # noqa: E731
            scope.find_var(n).get_tensor())
        a = np.exp(get('granite.l0.A_log'))
        assert (a >= 1).all() and (a < 16).all()
        dt = np.log1p(np.exp(get('granite.l0.dt_bias')))     # softplus
        assert (dt > 0.0009).all() and (dt < 0.11).all()
        assert (get('granite.l0.D') == 1).all()
        assert (get('granite.l0.norm1') == 1).all()
        assert abs(get('granite.embed').std() - 0.02) < 0.002
        assert np.abs(get('granite.l0.conv_w')).max() <= 0.5
