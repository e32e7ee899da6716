"""Independent upper bound for ResNet-50 training throughput on this chip.

A standalone pure-JAX ResNet-50 train step (no framework) with the same
numeric policy as the framework bench (bf16 conv/matmul inputs, f32 master
weights + BN stats, momentum SGD, fused softmax-CE loss), benched at the
same operating point (bs512, 224x224, 1000 classes).

Variants, each a flag combination, so one script answers VERDICT round-2
"next #1" (a)(b)(c):
  --layout {NCHW,NHWC}   input/conv layout end-to-end
  --remat                jax.checkpoint around every residual block
  --steps/--batch        operating point

Prints one JSON line per run: imgs/sec + analytic MFU (conv+fc FLOPs,
fwd+bwd = 3x fwd, peak from bench.DEVICE_PEAKS keyed by device_kind).

Run on the chip:
  chiprun -- python tools/jax_resnet_bound.py --layout NHWC --remat
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import peak_flops  # noqa: E402  (the one device_kind-keyed table)

# ResNet-50 bottleneck config
STAGES = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def conv_dims(layout):
    if layout == 'NHWC':
        return lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                          ('NHWC', 'HWIO', 'NHWC'))
    return lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                      ('NCHW', 'OIHW', 'NCHW'))


def init_conv(key, cin, cout, k, layout):
    fan = cin * k * k
    w = jax.random.normal(key, (k, k, cin, cout), jnp.float32)
    w = w * np.sqrt(2.0 / fan)
    if layout == 'NCHW':
        w = jnp.transpose(w, (3, 2, 0, 1))  # OIHW
    return w


def make_params(key, layout, class_dim=1000):
    """Flat list-of-dicts parameter tree mirroring the framework model."""
    params = []

    def add_conv_bn(key, cin, cout, k):
        k1, key = jax.random.split(key)
        params.append({
            'w': init_conv(k1, cin, cout, k, layout),
            'scale': jnp.ones((cout,), jnp.float32),
            'bias': jnp.zeros((cout,), jnp.float32),
        })
        return key

    key = add_conv_bn(key, 3, 64, 7)
    cin = 64
    for ch, count, _stride in STAGES:
        for i in range(count):
            if cin != ch * 4:
                key = add_conv_bn(key, cin, ch * 4, 1)  # shortcut proj
            key = add_conv_bn(key, cin, ch, 1)
            key = add_conv_bn(key, ch, ch, 3)
            key = add_conv_bn(key, ch, ch * 4, 1)
            cin = ch * 4
    k1, _ = jax.random.split(key)
    params.append({
        'w': jax.random.normal(k1, (2048, class_dim), jnp.float32) * 0.01,
        'bias': jnp.zeros((class_dim,), jnp.float32),
    })
    return params


BN_DTYPE = jnp.float32  # set to bfloat16 by --bf16-bn to probe the policy cost


def conv_bn(x, p, stride, layout, padding, relu=True):
    dn = conv_dims(layout)
    w = p['w'].astype(jnp.bfloat16)
    y = lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w, (stride, stride), padding,
        dimension_numbers=dn)
    # batch-norm (training mode, batch statistics); stats dtype = BN_DTYPE
    axes = (0, 1, 2) if layout == 'NHWC' else (0, 2, 3)
    yf = y.astype(BN_DTYPE)
    mean = jnp.mean(yf, axes)
    # two-pass variance: non-negative by construction even in bf16
    shape0 = (1, 1, 1, -1) if layout == 'NHWC' else (1, -1, 1, 1)
    var = jnp.mean(jnp.square(yf - mean.reshape(shape0)), axes)
    shape = (1, 1, 1, -1) if layout == 'NHWC' else (1, -1, 1, 1)
    inv = lax.rsqrt(var + 1e-5) * p['scale'].astype(BN_DTYPE)
    y = (yf - mean.reshape(shape)) * inv.reshape(shape) \
        + p['bias'].astype(BN_DTYPE).reshape(shape)
    y = y.astype(jnp.bfloat16)
    if relu:
        y = jnp.maximum(y, 0)
    return y


def forward(params, x, layout, remat):
    it = iter(params)

    def nxt():
        return next(it)

    x = conv_bn(x, nxt(), 2, layout, [(3, 3), (3, 3)])
    # maxpool 3x3 s2 p1
    if layout == 'NHWC':
        window, strides = (1, 3, 3, 1), (1, 2, 2, 1)
        pads = ((0, 0), (1, 1), (1, 1), (0, 0))
    else:
        window, strides = (1, 1, 3, 3), (1, 1, 2, 2)
        pads = ((0, 0), (0, 0), (1, 1), (1, 1))
    x = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)

    cin = 64
    for ch, count, stage_stride in STAGES:
        for i in range(count):
            stride = stage_stride if i == 0 else 1
            blk_params = []
            if cin != ch * 4:
                blk_params.append(nxt())
            blk_params += [nxt(), nxt(), nxt()]

            def block(x, bp, stride=stride, ch=ch, cin=cin):
                j = 0
                if cin != ch * 4:
                    short = conv_bn(x, bp[j], stride, layout, 'VALID',
                                    relu=False)
                    j += 1
                else:
                    short = x
                y = conv_bn(x, bp[j], stride, layout, 'VALID')
                y = conv_bn(y, bp[j + 1], 1, layout, [(1, 1), (1, 1)])
                y = conv_bn(y, bp[j + 2], 1, layout, 'VALID', relu=False)
                return jnp.maximum(short + y, 0)

            if remat:
                block = jax.checkpoint(block,
                                       policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            x = block(x, blk_params)
            cin = ch * 4
    axes = (1, 2) if layout == 'NHWC' else (2, 3)
    x = jnp.mean(x.astype(jnp.float32), axes)  # global avg pool
    fc = next(it)
    logits = x.astype(jnp.bfloat16) @ fc['w'].astype(jnp.bfloat16)
    return logits.astype(jnp.float32) + fc['bias']


def loss_fn(params, x, label, layout, remat):
    logits = forward(params, x, layout, remat)
    logz = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    ll = jnp.take_along_axis(logits - logz, label[:, None], axis=-1)
    return -jnp.mean(ll)


@functools.partial(jax.jit, static_argnames=('layout', 'remat', 'lr'))
def train_step(params, vel, x, label, layout='NCHW', remat=False, lr=0.1):
    return _train_step_impl(params, vel, x, label, layout, remat, lr)


@functools.partial(jax.jit, static_argnames=('layout', 'remat', 'lr'),
                   donate_argnums=(0, 1))
def train_step_donated(params, vel, x, label, layout='NCHW', remat=False,
                       lr=0.1):
    return _train_step_impl(params, vel, x, label, layout, remat, lr)


def _train_step_impl(params, vel, x, label, layout, remat, lr):
    loss, grads = jax.value_and_grad(loss_fn)(params, x, label, layout, remat)
    new_p, new_v = [], []
    for p, v, g in zip(params, vel, grads):
        np_, nv_ = {}, {}
        for k in p:
            nv_[k] = 0.9 * v[k] + g[k]
            np_[k] = p[k] - lr * nv_[k]
        new_p.append(np_)
        new_v.append(nv_)
    return new_p, new_v, loss


def analytic_flops_per_img(layout, class_dim=1000):
    """Conv + fc MACs*2, fwd; training = 3x."""
    flops = 0.0
    h = w = 224

    def conv(cin, cout, k, stride, hin, win):
        ho, wo = hin // stride, win // stride
        return 2.0 * ho * wo * cout * cin * k * k, ho, wo

    f, h, w = conv(3, 64, 7, 2, h, w)
    flops += f
    h, w = h // 2, w // 2  # maxpool
    cin = 64
    for ch, count, stage_stride in STAGES:
        for i in range(count):
            stride = stage_stride if i == 0 else 1
            if cin != ch * 4:
                f, _, _ = conv(cin, ch * 4, 1, stride, h, w)
                flops += f
            f, h2, w2 = conv(cin, ch, 1, stride, h, w)
            flops += f
            f, h2, w2 = conv(ch, ch, 3, 1, h2, w2)
            flops += f
            f, h2, w2 = conv(ch, ch * 4, 1, 1, h2, w2)
            flops += f
            h, w, cin = h2, w2, ch * 4
    flops += 2.0 * 2048 * class_dim
    return 3.0 * flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--layout', default='NCHW', choices=['NCHW', 'NHWC'])
    ap.add_argument('--remat', action='store_true')
    ap.add_argument('--batch', type=int, default=512)
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--bf16-bn', action='store_true',
                    help='batch-norm stats in bf16 (policy probe)')
    ap.add_argument('--bf16-feed', action='store_true',
                    help='feed images as bf16 (halves the input read)')
    ap.add_argument('--donate', action='store_true',
                    help='donate param/velocity buffers into the step')
    args = ap.parse_args()
    if args.bf16_bn:
        global BN_DTYPE
        BN_DTYPE = jnp.bfloat16

    dev = jax.devices()[0]
    key = jax.random.PRNGKey(0)
    params = make_params(key, args.layout)
    vel = [{k: jnp.zeros_like(v) for k, v in p.items()} for p in params]
    params = jax.device_put(params, dev)
    vel = jax.device_put(vel, dev)
    shape = ((args.batch, 224, 224, 3) if args.layout == 'NHWC'
             else (args.batch, 3, 224, 224))
    rng = np.random.RandomState(0)
    feed_dt = jnp.bfloat16 if args.bf16_feed else np.float32
    x = jax.device_put(
        jnp.asarray(rng.standard_normal(shape), dtype=feed_dt), dev)
    label = jax.device_put(
        rng.randint(0, 1000, size=(args.batch,)).astype(np.int32), dev)

    step_fn = train_step_donated if args.donate else train_step
    step = functools.partial(step_fn, layout=args.layout, remat=args.remat)
    for _ in range(2):
        params, vel, loss = step(params, vel, x, label)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(args.steps):
        params, vel, loss = step(params, vel, x, label)
    jax.block_until_ready(loss)
    elapsed = time.time() - t0
    imgs = args.batch * args.steps / elapsed
    mfu = imgs * analytic_flops_per_img(args.layout) / peak_flops()
    print(json.dumps({
        'bench': 'pure_jax_resnet50_bound',
        'layout': args.layout, 'remat': args.remat, 'batch': args.batch,
        'bf16_bn': args.bf16_bn, 'bf16_feed': args.bf16_feed,
        'donate': args.donate,
        'imgs_per_sec': round(imgs, 1),
        'mfu': round(mfu, 4),
        'loss': float(loss),
    }))


if __name__ == '__main__':
    main()
