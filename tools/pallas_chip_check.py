"""Compile each Pallas kernel for the chip and check it against its XLA
reference — THROUGH ITS OP (a fluid Program on ``Executor(TPUPlace())``,
so ``interpret=False`` by the lowering's own rule), forward AND backward,
under AMP as the models that use it run, at stated shapes:

  flash_attention  ``layers.flash_attention(impl='pallas')`` vs
                   ``impl='dense'`` at the transformer cells' shape
                   (B128 x L256 x 8 x 64: encoder self, causal decoder
                   self, cross), at the envelope's longest row (L=2048,
                   causal, with per-row ``seq_lengths``, the LoD
                   sideband; Lq 2048 against Lk 256) and at the other
                   head widths 'auto' admits (4 x 128, 16 x 32); then
                   the kernel's and dense attention's time a call,
                   forward + backward, on the host's clock, at L 64 ..
                   2048, at the served decoder's Lq=1, and at D 128
                   and 32
  ssd_scan         ``layers.ssd_scan(impl='pallas')`` vs ``impl='xla'`` at
                   the two cells' shapes (granite: 1 x 1024, 64 heads of
                   64, one group, state 128, chunk 256; nemotron: 2 x
                   2048, eight groups, chunk 128), fed by four ``fc``
                   projections of one input, so that the weights'
                   gradients carry dX, dDt, dB and dC; then both
                   lowerings' time a call, forward + gradient

Compared: the op's output, the loss, and the gradient of every fc weight
feeding it.  Tolerance (written before the first chip run): the largest
absolute difference, normalized by the reference's largest magnitude,
must be <= 3e-2 — four bf16 ulps (bf16 eps 7.8e-3): both sides round
their matmul inputs to bf16 and accumulate in f32, in a different order.

    chiprun -- python tools/pallas_chip_check.py        # on the chip
    JAX_PLATFORMS=cpu python tools/pallas_chip_check.py --cpu-tiny

Prints one JSON line per case (and one with the table of times) and exits
non-zero if any kernel failed to compile or missed the tolerance.
``--cpu-tiny`` (explicit, never detected) runs small shapes on CPUPlace
in interpret mode, and takes no time.
"""

import argparse
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 3e-2


def _norm_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float('inf')
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _run_program(build, feed, place):
    """Build (under a fresh name scope + fixed seed), run ONE SGD step,
    return {fetch name: value} for the outputs and every weight grad."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fetches = build()
        fluid.optimizer.SGD(learning_rate=0.0).minimize(fetches['loss'])
    names = dict(fetches)
    for p in main.all_parameters():
        if len(p.shape) >= 2:   # weights; biases are checked through them
            names[p.name + '@GRAD'] = p.name + '@GRAD'
    exe = fluid.Executor(place)
    with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
        exe.run(startup)
        t0 = time.time()
        vals = exe.run(main, feed=feed, fetch_list=list(names.values()))
    out = {k: np.asarray(v, np.float32) for k, v in zip(names, vals)}
    out['_wall_s'] = time.time() - t0
    return out


# (name, B, Lq, Lk, H, D, causal, ragged): the three forms the
# transformer cells train (encoder self, causal decoder self, cross) at
# their shape, beside the longest row of the kernel's envelope with the
# LoD sideband, the longest Q row against one block of K, and the other
# head widths 'auto' admits (one head, and four, a 128-lane group)
FLASH_CASES = [
    ('self', 128, 256, 256, 8, 64, False, False),
    ('causal', 128, 256, 256, 8, 64, True, False),
    ('cross', 128, 256, 256, 8, 64, False, False),
    ('causal_l2048_ragged', 2, 2048, 2048, 8, 64, True, True),
    ('cross_lq2048', 16, 2048, 256, 8, 64, False, False),
    ('causal_d128', 128, 256, 256, 4, 128, True, False),
    ('self_d32', 128, 256, 256, 16, 32, False, False),
]
FLASH_TINY = [
    ('self', 2, 64, 64, 2, 16, False, False),
    ('causal', 2, 64, 64, 2, 16, True, False),
    ('cross', 2, 32, 64, 2, 16, False, False),
    ('causal_ragged', 2, 64, 64, 2, 16, True, True),
]


def check_flash(place, tiny):
    """One record per case: the op with impl='pallas' against
    impl='dense', both through the Executor."""
    import paddle_tpu.fluid as fluid
    for name, b, lq, lk, h, d, causal, ragged in (
            FLASH_TINY if tiny else FLASH_CASES):
        rng = np.random.RandomState(0)
        cross = name.startswith('cross')
        if ragged:
            lens = [lq] + [(lq * 5) // 8] * (b - 1)
            rows = [rng.standard_normal((n, h * d)).astype('float32')
                    for n in lens]
            feed = {'x': fluid.create_lod_tensor(np.concatenate(rows),
                                                 [lens])}
        else:
            lens = None
            feed = {'x': rng.standard_normal(
                (b, lq, h * d)).astype('float32')}
            if cross:
                feed['y'] = rng.standard_normal(
                    (b, lk, h * d)).astype('float32')

        def build(impl):
            if ragged:
                x = fluid.layers.data('x', [h * d], dtype='float32',
                                      lod_level=1)
            else:
                x = fluid.layers.data('x', [lq, h * d], dtype='float32')
            y = fluid.layers.data('y', [lk, h * d],
                                  dtype='float32') if cross else x
            # a LoD var is [B, T, H*D] + lengths at run time: split the
            # heads by hand (0 copies a run-time dim)
            q, k, v = (fluid.layers.reshape(
                fluid.layers.fc(src, h * d, bias_attr=False,
                                num_flatten_dims=1 if ragged else 2),
                [0, 0, h, d]) for src in (x, y, y))
            out = fluid.layers.flash_attention(q, k, v, causal=causal,
                                               impl=impl)
            loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
            return {'out': out, 'loss': loss}

        ref = _run_program(lambda: build('dense'), feed, place)
        got = _run_program(lambda: build('pallas'), feed, place)
        yield {'kernel': 'flash_attention', 'case': name,
               'shape': [b, lq, lk, h, d], 'causal': causal,
               'seq_lengths': lens and lens[:2]}, got, ref


# (B, Lq, Lk, H, D, backward too): 32768 tokens of 512 columns a call,
# as the cells' step holds, at each length; the served decoder's step;
# the longest Q row against one block of K; and the other head widths
FLASH_TIMES = [(512, 64, 64, 8, 64, True), (256, 128, 128, 8, 64, True),
               (128, 256, 256, 8, 64, True), (64, 512, 512, 8, 64, True),
               (32, 1024, 1024, 8, 64, True), (16, 2048, 2048, 8, 64, True),
               (128, 1, 256, 8, 64, False), (16, 2048, 256, 8, 64, True)]
FLASH_TIMES += [(32768 // l, l, l, h, d, True)
                for h, d in ((4, 128), (16, 32)) for l in (128, 256, 1024)]


def time_chained(evaluate, x, steps=10, calls=3):
    """ms one ``evaluate(x)`` (a tuple of arrays) holds the device:
    ``steps`` evaluations chained inside ONE jitted scan, each fed the one
    before through an element of x, so the host's dispatch (about a
    kernel's own time at these sizes) is paid once in ``steps``;
    ``optimization_barrier`` keeps every result whole."""
    import jax
    import jax.numpy as jnp

    def step(x, _):
        outs = jax.lax.optimization_barrier(tuple(evaluate(x)))
        seen = sum(o.reshape(-1)[0].astype(jnp.float32) for o in outs) * 0
        return x.at[(0, ) * x.ndim].add(seen.astype(x.dtype)), None

    run = jax.jit(lambda x: jax.lax.scan(step, x, None, length=steps)[0])
    jax.block_until_ready(run(x))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = run(x)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t0) / (calls * steps), 4)


def time_attention(fn, q, k, v, w, bwd):
    """ms one evaluation of ``fn(q, k, v)`` (with its three gradients if
    ``bwd``) holds the device (``time_chained``)."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum((fn(q, k, v) * w).astype(jnp.float32))

    return time_chained(
        lambda q: jax.grad(loss, (0, 1, 2))(q, k, v) if bwd
        else (fn(q, k, v), ), q)


def flash_times(device):
    """ms a call of the kernel and of dense attention, bf16, non-causal
    and causal: forward + backward (forward alone for the decoder's
    Lq=1).  The table 'auto' in ops/attention_ops.py rests on.  The
    clock is the host's, round ``steps`` evaluations chained on the
    device (``time_attention``): no device trace is read."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as pl_fa
    from paddle_tpu.parallel.context_parallel import dense_attention
    rows = []
    for b, lq, lk, h, d, bwd in FLASH_TIMES:
        for causal in (False, True):
            if causal and lq != lk:
                continue
            keys = jax.random.split(jax.random.PRNGKey(0), 4)
            mk = lambda key, l: jax.device_put(jax.random.normal(
                key, (b, l, h, d), jnp.bfloat16), device)
            q, k, v, w = mk(keys[0], lq), mk(keys[1], lk), \
                mk(keys[2], lk), mk(keys[3], lq)
            row = {'B': b, 'Lq': lq, 'Lk': lk, 'H': h, 'D': d,
                   'causal': causal, 'backward': bwd}
            for impl, fn in (('pallas', pl_fa.flash_attention),
                             ('dense', dense_attention)):
                try:
                    row[impl + '_ms'] = time_attention(
                        functools.partial(fn, causal=causal), q, k, v, w,
                        bwd)
                except Exception as e:   # past the device's memory, say
                    row[impl + '_ms'] = None
                    row[impl + '_error'] = str(e)[-300:]
            rows.append(row)
    return rows


# (name, B, L, H, P, G, N, chunk): the two cells' scans
SSD_CASES = [('granite', 1, 1024, 64, 64, 1, 128, 256),
             ('nemotron', 2, 2048, 64, 64, 8, 128, 128)]
SSD_TINY = [('one_group', 1, 256, 8, 64, 1, 128, 128),
            ('two_groups', 2, 512, 16, 64, 2, 128, 256)]


def check_ssd(place, tiny):
    """One record per case: the op with impl='pallas' against impl='xla',
    both through the Executor."""
    import paddle_tpu.fluid as fluid
    for name, b, length, h, p, g, n, chunk in (
            SSD_TINY if tiny else SSD_CASES):
        width = 64 if tiny else 512
        feed = {'u': np.random.RandomState(0).standard_normal(
            (b, length, width)).astype('float32')}

        def build(impl):
            layers = fluid.layers
            u = layers.data('u', [length, width], dtype='float32')
            x, dt, bm, cm = (layers.fc(u, size, bias_attr=False,
                                       num_flatten_dims=2)
                             for size in (h * p, h, g * n, g * n))
            vec = lambda pname, lo, hi: layers.create_parameter(   # noqa
                [h], 'float32', attr=fluid.ParamAttr(
                    name=pname, initializer=fluid.initializer.Uniform(
                        lo, hi)))
            out = layers.ssd_scan(
                layers.reshape(x, [0, 0, h, p]), dt,
                layers.scale(layers.exp(vec('A_log', 0.0, 2.77)),
                             scale=-1.0),
                layers.reshape(bm, [0, 0, g, n]),
                layers.reshape(cm, [0, 0, g, n]), vec('D', 0.5, 1.5),
                vec('dt_bias', -4.0, -1.0), chunk=chunk, impl=impl)
            fetches = {'out': out, 'loss': layers.mean(
                layers.elementwise_mul(out, out))}
            fetches.update((v + '@GRAD', v + '@GRAD')
                           for v in ('A_log', 'D', 'dt_bias'))
            return fetches

        ref = _run_program(lambda: build('xla'), feed, place)
        got = _run_program(lambda: build('pallas'), feed, place)
        yield {'kernel': 'ssd_scan', 'case': name,
               'shape': [b, length, h, p, g, n], 'chunk': chunk}, got, ref


def ssd_times(device):
    """ms a call, forward + gradient, of the kernel and of the XLA
    lowering at the cells' shapes, bf16 under AMP, on the host's clock
    (``time_chained``): what 'auto' in ops/ssm_ops.py was first judged
    on; the cells' own traces are what it rests on."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.ops.pallas import ssd_scan as pl_ssd
    rows = []
    for name, b, length, h, p, g, n, chunk in SSD_CASES:
        keys = jax.random.split(jax.random.PRNGKey(0), 8)
        mk = lambda i, shape, dtype=jnp.bfloat16: jax.device_put(   # noqa
            jax.random.normal(keys[i], shape, jnp.float32).astype(dtype),
            device)
        x, w = mk(0, (b, length, h, p)), mk(1, (b, length, h, p))
        bm, cm = mk(2, (b, length, g, n)), mk(3, (b, length, g, n))
        dt = mk(4, (b, length, h), jnp.float32) - 3.0
        a = -jnp.exp(mk(5, (h, ), jnp.float32))
        d, bias = mk(6, (h, ), jnp.float32), mk(7, (h, ), jnp.float32)

        def xla(x):
            primals = (x, dt, a, bm, cm, d, bias)
            y, states = ssm_ops.ssd_scan(*primals, chunk=chunk)
            return (y, ) + tuple(ssm_ops._xla_grads(
                primals, states, w, chunk))

        def pallas(x):
            step = ssm_ops._step(dt, bias)
            y, states = pl_ssd.ssd_scan(x, step, a, bm, cm, d, chunk)
            return (y, ) + tuple(pl_ssd.ssd_scan_grad(
                x, step, a, bm, cm, d, states, w, chunk))

        row = {'case': name, 'shape': [b, length, h, p, g, n],
               'chunk': chunk}
        with fluid.amp_guard(True):
            for impl, fn in (('pallas', pallas), ('xla', xla)):
                row[impl + '_ms'] = time_chained(fn, x)
        rows.append(row)
    return rows


CHECKS = {'flash_attention': check_flash, 'ssd_scan': check_ssd}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--cpu-tiny', action='store_true')
    ap.add_argument('kernels', nargs='*', default=sorted(CHECKS))
    args = ap.parse_args(argv)
    import paddle_tpu.fluid as fluid
    place = fluid.CPUPlace() if args.cpu_tiny else fluid.TPUPlace()
    dev = place.jax_device()   # typed error off the chip
    failed = False

    def report(rec):
        rec['device'] = fluid.core.device_info([dev])
        print(json.dumps(rec), flush=True)
        return not rec['ok']

    for name in args.kernels:
        try:
            for rec, got, ref in CHECKS[name](place, args.cpu_tiny):
                errs = {k: _norm_err(got[k], ref[k])
                        for k in ref if not k.startswith('_')}
                rec.update(
                    compiled=True, interpret=args.cpu_tiny,
                    fwd_err=max(errs['out'], errs['loss']),
                    bwd_err=max(v for k, v in errs.items()
                                if k.endswith('@GRAD')),
                    tolerance=TOLERANCE,
                    first_run_s={'kernel': round(got['_wall_s'], 1),
                                 'reference': round(ref['_wall_s'], 1)})
                rec['ok'] = max(rec['fwd_err'], rec['bwd_err']) <= TOLERANCE
                failed = report(rec) or failed
        except Exception as e:   # report every kernel, then fail
            traceback.print_exc()
            failed = report({
                'kernel': name, 'compiled': False, 'ok': False,
                'error': '%s: %s' % (type(e).__name__, str(e)[-1500:])})
    if 'flash_attention' in args.kernels and not args.cpu_tiny:
        # a time is a device number: never taken on the CPU
        print(json.dumps({'kernel': 'flash_attention',
                          'times': flash_times(dev)}), flush=True)
    if 'ssd_scan' in args.kernels and not args.cpu_tiny:
        print(json.dumps({'kernel': 'ssd_scan', 'times': ssd_times(dev)}),
              flush=True)
    sys.exit(1 if failed else 0)


if __name__ == '__main__':
    main()
