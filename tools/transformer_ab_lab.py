"""Same-process A/B: framework transformer-base train step vs the pure-JAX
bound (tools/jax_transformer_bound.py), with optional xplane capture of
each side — the instrument for VERDICT r4 next-#1.

Both sides are compiled first, then timed in INTERLEAVED blocks in ONE
process (the chip belongs to one process), so slow drift of the machine
cancels in per-block ratios.

Run:  python tools/transformer_ab_lab.py [--trace /tmp/tfab] [--steps 10]
Prints one JSON line: per-block tokens/sec for both sides + per-block
ratios; with --trace also prints the top device ops per side.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SEQ = 256
# bs64 default: framework + bound (params, Adam state, CE logits) must
# co-reside on the 16GB chip for interleaved blocks; bs128 OOMs.
BATCH = int(__import__('os').environ.get('TFAB_BATCH', '64'))


def build_framework():
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer

    model = transformer.build(src_vocab=30000, trg_vocab=30000,
                              max_len=SEQ, n_layer=6, n_head=8,
                              d_model=512, d_ff=2048)
    place = fluid.TPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    dev = place.jax_device()
    ids = lambda: jax.device_put(
        rng.randint(1, 30000, size=(BATCH, SEQ)).astype('int64'), dev)
    feed = {'src_ids': ids(), 'trg_ids': ids(), 'lbl_ids': ids()}
    with fluid.scope_guard(scope), fluid.amp_guard(True):
        exe.run(model['startup'])
        for _ in range(2):
            exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
            exe.run(model['main'], feed=feed, fetch_list=[])

    def timed_block(steps):
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(model['main'], feed=feed, fetch_list=[])
            loss_v, = exe.run(model['main'], feed=feed,
                              fetch_list=[model['loss']])
            el = time.time() - t0
        assert np.isfinite(np.asarray(loss_v)).all()
        return BATCH * SEQ * steps / el

    return timed_block


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=10)
    ap.add_argument('--blocks', type=int, default=3)
    ap.add_argument('--trace', default=None,
                    help='base dir for xplane captures (fw/, bd/)')
    ap.add_argument('--attn', default='dense', choices=['dense', 'flash'])
    ap.add_argument('--trace-top', type=int, default=25)
    args = ap.parse_args()

    import jax_transformer_bound as bound
    fw_block = build_framework()
    _, bd_block = bound.build(attn_impl=args.attn, batch=BATCH)

    fw, bd = [], []
    for _ in range(args.blocks):
        fw.append(fw_block(args.steps))
        bd.append(bd_block(args.steps))
    ratios = [f / b for f, b in zip(fw, bd)]
    fpt = bound._transformer_flops_per_token(6, 512, 2048, SEQ, 30000)
    print(json.dumps({
        'framework_blocks': [round(v, 1) for v in fw],
        'bound_blocks': [round(v, 1) for v in bd],
        'ratios': [round(r, 4) for r in ratios],
        'best_ratio': round(max(ratios), 4),
        'framework_mfu': round(max(fw) * fpt / bound.peak_flops(), 4),
        'bound_mfu': round(max(bd) * fpt / bound.peak_flops(), 4),
        'attn': args.attn,
    }), flush=True)

    if args.trace:
        import xplane_top as xt
        for name, block in (('fw', fw_block), ('bd', bd_block)):
            d = os.path.join(args.trace, name)
            os.makedirs(d, exist_ok=True)
            with xt.capture(d):
                block(3)
            print('== top device ops: %s ==' % name, flush=True)
            xt.print_top(d, args.trace_top)


if __name__ == '__main__':
    main()
