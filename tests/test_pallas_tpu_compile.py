"""The fused attention and ``ssd_scan`` kernels, and the held experts'
grouped products with the passes round them, COMPILED for the TPU v5e,
here, without the chip: the TPU's compiler is installed and compiles
for a described topology.  Nothing runs, so this says nothing of results or times; it
refuses what the chip's compiler would refuse (a misaligned slice, too
much VMEM, a kernel GSPMD cannot place) at no chip time.

All such compiles live in THIS file, and the topology is described inside
a fixture: one process loads the TPU's library and keeps it, so under
several test workers only the worker given this file may do so."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

H, D = 8, 64


@pytest.fixture(scope='module')
def topo():
    import os
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)


@pytest.fixture(scope='module')
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', old)
    compilation_cache.reset_cache()


# (B, Lq, Lk, H, D, causal, ragged): the transformer cells' three forms,
# the state-space hybrid cell's one attention shape,
# the envelope's longest row with lengths, two blocks a side, the longest
# Q row 'auto' admits against one block of K (the backward holds Q, dO,
# dQ and an f32 dQ scratch of a row in VMEM), and the other head widths
# 'auto' was measured at (one head, and four heads, a 128-lane group)
SHAPES = {
    'cell_self': (128, 256, 256, 8, 64, False, False),
    'cell_causal': (128, 256, 256, 8, 64, True, False),
    'cell_cross_lk384': (128, 256, 384, 8, 64, False, False),
    'l2048_causal_ragged': (16, 2048, 2048, 8, 64, True, True),
    'l512_causal': (64, 512, 512, 8, 64, True, False),
    'cross_lq2048_lk256': (16, 2048, 256, 8, 64, False, False),
    'd128_causal': (128, 256, 256, 4, 128, True, False),
    'd128_l2048': (16, 2048, 2048, 4, 128, False, False),
    'd32_self': (128, 256, 256, 16, 32, False, False),
    # granite_h_train_1chip's attention layer: one sequence, 32 query
    # heads (its 8 key-value heads arrive repeated), 4 x 4 causal tiles
    'b1_l1024_h32_causal': (1, 1024, 1024, 32, 64, True, False),
}


@pytest.mark.parametrize('name', sorted(SHAPES))
def test_kernel_compiles_for_the_v5e(topo, no_compile_cache, name):
    """Forward and the one backward kernel, bf16: two Mosaic custom calls
    in the compiled module, and no more."""
    from paddle_tpu.ops.pallas import flash_attention as pl_fa
    b, lq, lk, h, d, causal, ragged = SHAPES[name]
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((b, l, h, d), jnp.bfloat16, sharding=one)
            for l in (lq, lk, lk)]
    if ragged:
        args.append(jax.ShapeDtypeStruct((b, ), jnp.int32, sharding=one))

    def step(q, k, v, lens=None):
        def loss(q, k, v):
            return jnp.sum(pl_fa.flash_attention(
                q, k, v, causal=causal,
                seq_lengths=lens).astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    hlo = jax.jit(step).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


def _lower_block(block, env, place, mesh=None):
    """Every op of a Program's block into the current trace, as the
    executors do it."""
    from paddle_tpu.ops import registry
    ctx = registry.LoweringContext(block, env, place=place, mesh=mesh,
                                   batch_axis='dp')
    for op in block.ops:
        registry.run_op(ctx, op)
    return env


def _three_attentions():
    """Encoder self, causal self and cross attention as the transformer
    emits them (Q, K, V each a projection), with their gradients,
    ``impl='auto'``: the Program, its loss, and its parameters."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.backward import append_backward
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()

    def attention(q_in, kv_in, causal):
        q, k, v = (layers.fc(src, H * D, bias_attr=False,
                             num_flatten_dims=2)
                   for src in (q_in, kv_in, kv_in))
        return layers.flash_attention(q, k, v, num_heads=H, causal=causal)

    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data('x', [256, H * D], dtype='float32')
        y = layers.data('y', [256, H * D], dtype='float32')
        h = attention(x, x, False)
        h = attention(h, h, True)
        h = attention(h, y, False)
        loss = layers.mean(h)
        append_backward(loss)
    return main, loss, [p.name for p in main.all_parameters()]


@pytest.mark.parametrize('chips', [1, 4])
def test_auto_lowers_the_step_to_the_kernel_on_a_tpu_place(
        topo, no_compile_cache, chips):
    """A block of three ``flash_attention`` ops and their gradients,
    lowered for a TPU place as the executors lower it, under AMP: 'auto'
    takes the kernel (three forward and three backward custom calls: the
    gradient does not run the forward again) and the record says so.
    On the 2x2 mesh with the batch over 'dp', as ``ParallelExecutor``
    jits it, the compiled step gathers nothing: each chip's kernel takes
    its own 128 rows."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import trace
    main, loss, params = _three_attentions()
    block = main.global_block()
    if chips == 1:
        mesh = None
        whole = rows = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(4), ('dp', ))
        whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P('dp'))
    feeds = {n: jax.ShapeDtypeStruct((128 * chips, 256, H * D),
                                     jnp.float32, sharding=rows)
             for n in ('x', 'y')}
    feeds.update({n: jax.ShapeDtypeStruct((H * D, H * D), jnp.float32,
                                          sharding=whole) for n in params})

    def step(feeds):
        env = _lower_block(block, dict(feeds), fluid.TPUPlace(), mesh)
        return [env[loss.name]] + [env[n + '@GRAD'] for n in params]

    with fluid.amp_guard(True):
        hlo = jax.jit(step).lower(feeds).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 6
    assert 'all-gather' not in hlo
    # Q, K, V cross the shard_map as [B, L, H*D]: a 4-D value there made
    # the kernel's reshape a copy of each (tbase_train_dp4's trace, PR 25)
    assert 'shard_map/reshape' not in hlo
    assert trace.lowering_choices('flash_attention')[-1] == {'pallas': 3}


# (B, L, H, P, G, N, chunk): the scans of granite_h_train_1chip and of
# nemotron3_nano_train_1chip, as their mixers feed them under AMP
SSD_SHAPES = {
    'granite_b1_l1024_one_group_chunk256': (1, 1024, 64, 64, 1, 128, 256),
    'nemotron_b2_l2048_eight_groups_chunk128': (2, 2048, 64, 64, 8, 128,
                                                128),
}


@pytest.mark.parametrize('name', sorted(SSD_SHAPES))
def test_ssd_scan_lowers_to_its_two_kernels_at_the_cells_shapes(
        topo, no_compile_cache, name):
    """One ``ssd_scan`` op and its gradient at a cell's exact shapes,
    lowered for a TPU place as the executors lower it, under AMP: 'auto'
    takes the kernel, the compiled module holds the forward's and the
    gradient's custom call and no other (the gradient does not run the
    forward again), and the record names the block.  A block the chip's
    compiler cannot tile, or more VMEM than a kernel may take, fails
    here."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import trace
    from paddle_tpu.fluid.backward import append_backward
    b, length, h, p, g, n, chunk = SSD_SHAPES[name]
    one = SingleDeviceSharding(topo.devices[0])
    act, f32 = jnp.bfloat16, jnp.float32
    feeds = {'x': ((b, length, h, p), act), 'dt': ((b, length, h), act),
             'a': ((h, ), f32), 'bm': ((b, length, g, n), act),
             'cm': ((b, length, g, n), act), 'd': ((h, ), f32),
             'dt_bias': ((h, ), f32)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {}
        for key, (shape, _) in feeds.items():
            data[key] = main.global_block().create_var(
                name=key, shape=shape, dtype='float32', is_data=True)
            data[key].stop_gradient = False
        out = fluid.layers.ssd_scan(
            *(data[k] for k in ('x', 'dt', 'a', 'bm', 'cm', 'd',
                                'dt_bias')), chunk=chunk)
        append_backward(fluid.layers.mean(out))
    block = main.global_block()

    def step(env):
        env = _lower_block(block, dict(env), fluid.TPUPlace())
        return [env[out.name]] + [env[k + '@GRAD'] for k in feeds]

    with fluid.amp_guard(True):
        hlo = jax.jit(step).lower({
            k: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for k, (shape, dtype) in feeds.items()}).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert list(trace.lowering_choices('ssd_scan', seen=True)[-1].values()) \
        == [{'choice': 'pallas', 'chunk': chunk, 'chunks': length // chunk,
             'block': [512, 512]}]


def test_fetched_loss_writes_no_f32_copy_of_the_logits(topo,
                                                       no_compile_cache):
    """``fc`` to a dictionary + ``softmax_with_cross_entropy`` + ``mean``
    + Adam under AMP, the executor's own K=2 train scan with the loss
    fetched, compiled for the v5e by ``tools/compile_for_v5e.py``: the
    logits reach HBM as bf16 and nothing the program writes is
    ``f32[rows, dictionary]``.  Before PR 27 the step whose loss is
    fetched wrote one (1966 MB a dispatch in ``nmt_train_1chip``): the
    label's logit was gathered from ``logits.astype(f32)``, and a gather
    fuses no producer.  The parent's module has that result at every
    size tried, down to 8 rows x 128 entries; this is the smallest with
    no other [rows, dictionary]-shaped value (rows != width)."""
    import os
    import sys
    import paddle_tpu.fluid as fluid
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import compile_for_v5e
    rows, width, dictionary = 64, 128, 512
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data('x', [width], dtype='float32')
        y = fluid.layers.data('y', [1], dtype='int64')
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(x, dictionary), y))
        fluid.optimizer.Adam(0.001).minimize(loss)
    feed = {'x': np.zeros((rows, width), 'float32'),
            'y': np.zeros((rows, 1), 'int64')}
    hlo = compile_for_v5e.compile_train_scan(
        topo.devices[0], main, startup, loss, [feed] * 2, amp=True).as_text()
    written = {r[3] for r in compile_for_v5e.large_results(hlo, 0)}
    assert 'bf16[%d,%d]' % (rows, dictionary) in written
    assert 'f32[%d,%d]' % (rows, dictionary) not in written


def test_moe_experts_gradient_reuses_the_forward_passes(topo,
                                                        no_compile_cache):
    """``moe_router`` + ``moe_experts`` and their gradients, lowered for a
    TPU place as the executors lower them, under AMP, at a buffer of 3072
    pairs (tiles of 512 rows): the gradient op computes the forward again,
    and XLA merges that with the forward op only where the two are one
    computation.  So the module holds the forward's two grouped products
    and the gradient's two products and two weight gradients, and no third
    up product, and its loops (besides the products' own group metadata)
    are the forward's three passes and the gradient's three.  A forward
    buffer that is not one computation in both ops (``jax.lax.empty``) makes
    the gradient op gather, run the up product and activate again."""
    import re
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import trace
    from paddle_tpu.fluid.backward import append_backward
    tokens, d, f, experts, k, held = 512, 256, 128, 16, 6, 8
    one = SingleDeviceSharding(topo.devices[0])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = main.global_block().create_var(
            name='x', shape=(2, tokens // 2, d), dtype='float32',
            is_data=True)
        x.stop_gradient = False
        idx, weight = fluid.layers.moe_router(
            x, experts, k, param_attr=fluid.ParamAttr(name='router'),
            bias_attr=fluid.ParamAttr(name='router_bias'))
        out = fluid.layers.moe_experts(
            x, idx, weight, held, f,
            param_attr=fluid.ParamAttr(name='experts'))
        append_backward(fluid.layers.mean(out))
    block = main.global_block()
    shapes = {'x': (2, tokens // 2, d), 'router': (d, experts),
              'router_bias': (experts, ), 'experts.w_up': (held, f, d),
              'experts.w_down': (held, f, d)}
    grads = ['x', 'router', 'experts.w_up', 'experts.w_down']

    def step(env):
        env = _lower_block(block, dict(env), fluid.TPUPlace())
        return [env[out.name]] + [env[n + '@GRAD'] for n in grads]

    with fluid.amp_guard(True):
        hlo = jax.jit(step).lower({
            n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
            for n, s in shapes.items()}).compile().as_text()
    rows = tokens * k
    products = re.findall(r'= \S+ custom-call\(.*custom_call_target='
                          r'"tpu_custom_call".*?op_name="([^"]*)"', hlo)
    assert len(products) == 6
    assert sum('tgmm' in name for name in products) == 2
    loops = [name for name in re.findall(r' while\(.*?op_name="([^"]*)"',
                                         hlo) if 'searchsorted' not in name]
    assert sum('moe_experts_grad' not in name for name in loops) == 3
    assert sum('moe_experts_grad' in name and 'transpose(' in name
               for name in loops) == 3 and len(loops) == 6
    assert list(trace.lowering_choices('moe_experts', seen=True)[-1]
                .values()) == [{'choice': 'pallas_gmm', 'buffer_rows': rows,
                                'held': held, 'pass_rows': 512,
                                'tile': [512, d, f]}]
