"""Compile each Pallas kernel for the chip and check it against its XLA
reference — THROUGH ITS OP (a fluid Program on ``Executor(TPUPlace())``,
so ``interpret=False`` by the lowering's own rule), forward AND backward,
under AMP as the models that would use it run, at one stated shape each:

  flash_attention  ``layers.flash_attention(impl='pallas')`` vs
                   ``impl='dense'`` at the transformer-base head layout
                   B x L x 8 x 64, L=2048, causal, with per-row
                   ``seq_lengths`` (the LoD sideband)
  lstm             ``layers.dynamic_lstm`` under FLAGS_fused_lstm='always'
                   vs 'never' (the lax.scan path) at D=512, B=128, T=32,
                   ragged lengths

Compared: the op's output, the loss, and the gradient of every fc weight
feeding it.  Tolerance (written before the first chip run): the largest
absolute difference, normalized by the reference's largest magnitude,
must be <= 3e-2 — four bf16 ulps (bf16 eps 7.8e-3): both sides round
their matmul inputs to bf16 and accumulate in f32, in a different order.

    chiprun -- python tools/pallas_chip_check.py        # on the chip
    JAX_PLATFORMS=cpu python tools/pallas_chip_check.py --cpu-tiny

Prints one JSON line per kernel and exits non-zero if any kernel failed
to compile or missed the tolerance.  ``--cpu-tiny`` (explicit, never
detected) runs small shapes on CPUPlace in interpret mode.
"""

import argparse
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


def check_flash(place, tiny):
    import paddle_tpu.fluid as fluid
    b, l, h, d = (2, 64, 2, 16) if tiny else (2, 2048, 8, 64)
    lens = [l, (l * 5) // 8]
    rng = np.random.RandomState(0)
    rows = [rng.standard_normal((n, h * d)).astype('float32') for n in lens]
    feed = {'x': fluid.create_lod_tensor(np.concatenate(rows), [lens])}

    def build(impl):
        x = fluid.layers.data('x', [h * d], dtype='float32', lod_level=1)
        # a LoD var is [B, T, H*D] + lengths at run time: split the
        # heads by hand (0 copies a run-time dim)
        q, k, v = (fluid.layers.reshape(
            fluid.layers.fc(x, h * d, bias_attr=False), [0, 0, h, d])
            for _ in range(3))
        out = fluid.layers.flash_attention(q, k, v, causal=True, impl=impl)
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
        return {'out': out, 'loss': loss}

    ref = _run_program(lambda: build('dense'), feed, place)
    got = _run_program(lambda: build('pallas'), feed, place)
    return {'kernel': 'flash_attention', 'shape': [b, l, h, d],
            'causal': True, 'seq_lengths': lens}, got, ref


def check_lstm(place, tiny):
    import paddle_tpu.fluid as fluid
    b, t, d = (8, 6, 128) if tiny else (128, 32, 512)
    rng = np.random.RandomState(0)
    lens = [int(n) for n in rng.randint(1, t + 1, size=b)]
    lens[0] = t
    rows = [rng.standard_normal((n, d)).astype('float32') * 0.5
            for n in lens]
    feed = {'x': fluid.create_lod_tensor(np.concatenate(rows), [lens])}

    def build():
        x = fluid.layers.data('x', [d], dtype='float32', lod_level=1)
        proj = fluid.layers.fc(x, 4 * d)
        hid, cell = fluid.layers.dynamic_lstm(proj, size=4 * d,
                                              use_peepholes=False)
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(hid, hid)) \
            + fluid.layers.mean(cell)
        return {'out': hid, 'loss': loss}

    def run(mode):
        old = fluid.FLAGS.fused_lstm
        fluid.FLAGS.fused_lstm = mode
        try:
            return _run_program(build, feed, place)
        finally:
            fluid.FLAGS.fused_lstm = old

    ref = run('never')
    got = run('always')
    return {'kernel': 'lstm', 'shape': {'B': b, 'T': t, 'D': d},
            'ragged': True}, got, ref


CHECKS = {'flash_attention': check_flash, 'lstm': check_lstm}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--cpu-tiny', action='store_true')
    ap.add_argument('kernels', nargs='*', default=sorted(CHECKS))
    args = ap.parse_args(argv)
    import paddle_tpu.fluid as fluid
    place = fluid.CPUPlace() if args.cpu_tiny else fluid.TPUPlace()
    dev = place.jax_device()   # typed error off the chip
    failed = False
    for name in args.kernels:
        rec = {'kernel': name}
        try:
            rec, got, ref = CHECKS[name](place, args.cpu_tiny)
            errs = {k: _norm_err(got[k], ref[k])
                    for k in ref if not k.startswith('_')}
            rec.update(compiled=True, interpret=args.cpu_tiny,
                       fwd_err=max(errs['out'], errs['loss']),
                       bwd_err=max(v for k, v in errs.items()
                                   if k.endswith('@GRAD')),
                       tolerance=TOLERANCE,
                       first_run_s={'kernel': round(got['_wall_s'], 1),
                                    'reference': round(ref['_wall_s'], 1)})
            rec['ok'] = max(rec['fwd_err'], rec['bwd_err']) <= TOLERANCE
        except Exception as e:   # report every kernel, then fail
            rec.update(compiled=False, ok=False,
                       error='%s: %s' % (type(e).__name__, str(e)[-1500:]))
            traceback.print_exc()
        rec['device'] = fluid.core.device_info([dev])
        failed = failed or not rec['ok']
        print(json.dumps(rec), flush=True)
    sys.exit(1 if failed else 0)


if __name__ == '__main__':
    main()
