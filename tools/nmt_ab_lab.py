"""Same-process A/B: NMT train step with FLAGS_fused_lstm never vs always.

Like tools/perf_gate.py, both variants are built, compiled, and timed in
ONE process (the chip belongs to one process) with interleaved timing
blocks, so slow drift of the machine cancels; only the ratio is
meaningful.

Run: python tools/nmt_ab_lab.py
Prints one JSON line: ms/step per variant per block, plus the
fused/scan speedup ratio from the best (min) block of each.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_and_run():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags
    from paddle_tpu.models import seq2seq

    batch, seq_len, dict_dim, dim = 512, 32, 30000, 512
    rng = np.random.RandomState(0)

    def lod(rows):
        return fluid.create_lod_tensor(rows, [[len(r) for r in rows]])

    src = [rng.randint(3, dict_dim, size=(seq_len, 1)).tolist()
           for _ in range(batch)]
    trg = [rng.randint(3, dict_dim, size=(seq_len, 1)).tolist()
           for _ in range(batch)]
    feed = {'src_word_id': lod(src), 'target_language_word': lod(trg),
            'target_language_next_word': lod(trg)}

    variants = {}
    for name, mode in [('scan', 'never'), ('fused', 'always')]:
        flags.FLAGS.fused_lstm = mode
        model = seq2seq.build(src_dict_dim=dict_dim, trg_dict_dim=dict_dim,
                              embedding_dim=dim, encoder_size=dim,
                              decoder_size=dim)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.core.Scope()
        variants[name] = (exe, scope, model)
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            exe.run(model['startup'])
            # compile + warm
            exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
            exe.run(model['main'], feed=feed, fetch_list=[])

    def timed_block(name, steps=12):
        exe, scope, model = variants[name]
        with fluid.scope_guard(scope), fluid.amp_guard(True):
            # sync point so the previous variant's queue drains first
            exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(model['main'], feed=feed, fetch_list=[])
            v = exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
            el = time.time() - t0
        assert np.isfinite(float(np.asarray(v[0]).flatten()[0]))
        return el / steps * 1000.0

    blocks = {'scan': [], 'fused': []}
    for _ in range(3):
        for name in ('scan', 'fused'):
            blocks[name].append(round(timed_block(name), 2))

    best = {k: min(v) for k, v in blocks.items()}
    tok = batch * seq_len
    print(json.dumps({
        'blocks_ms': blocks,
        'best_ms': best,
        'tokens_per_sec': {k: round(tok / (m / 1000.0), 1)
                           for k, m in best.items()},
        'fused_over_scan': round(best['scan'] / best['fused'], 4),
    }), flush=True)


if __name__ == '__main__':
    build_and_run()
