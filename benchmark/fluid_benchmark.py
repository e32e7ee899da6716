"""Benchmark harness over the BASELINE model configs (reference:
benchmark/fluid/fluid_benchmark.py — its --model/--batch_size/--iterations
/--device CLI over the models in benchmark/fluid/models/).

    python benchmark/fluid_benchmark.py --model resnet --batch_size 64 \
        --iterations 10 --device TPU [--amp]

Models: mnist, resnet, vgg, stacked_lstm (IMDB), machine_translation
(WMT14 seq2seq), ctr (sparse).  Prints one JSON line per run with
examples/sec (imgs/sec or tokens/sec to match the reference's reporting).
"""

import argparse
import json
import time

import numpy as np

import paddle_tpu.fluid as fluid
import paddle_tpu.dataset as dataset


def _lod_feed(rows, dtype, dim=1):
    flat = np.concatenate(
        [np.asarray(r, dtype).reshape(-1, dim) for r in rows])
    return fluid.create_lod_tensor(flat, [[len(r) for r in rows]])


def _mnist(args, rng):
    from paddle_tpu.models import mnist
    model = mnist.build(nn_type='conv' if args.use_conv else 'mlp',
                        img_shape=(1, 28, 28) if args.use_conv else (784, ))
    shape = (args.batch_size, 1, 28, 28) if args.use_conv else (
        args.batch_size, 784)
    feed = {
        'img': rng.standard_normal(shape).astype('float32'),
        'label': rng.randint(0, 10, (args.batch_size, 1)).astype('int64'),
    }
    return model, feed, args.batch_size, 'imgs/sec'


def _resnet(args, rng):
    from paddle_tpu.models import resnet
    model = resnet.build(depth=50, class_dim=1000,
                         image_shape=(3, 224, 224), lr=0.1)
    feed = {
        'img': rng.standard_normal(
            (args.batch_size, 3, 224, 224)).astype('float32'),
        'label': rng.randint(0, 1000,
                             (args.batch_size, 1)).astype('int64'),
    }
    return model, feed, args.batch_size, 'imgs/sec'


def _vgg(args, rng):
    from paddle_tpu.models import vgg
    model = vgg.build(class_dim=1000, image_shape=(3, 224, 224))
    feed = {
        'img': rng.standard_normal(
            (args.batch_size, 3, 224, 224)).astype('float32'),
        'label': rng.randint(0, 1000,
                             (args.batch_size, 1)).astype('int64'),
    }
    return model, feed, args.batch_size, 'imgs/sec'


def _stacked_lstm(args, rng):
    from paddle_tpu.models import stacked_lstm
    model = stacked_lstm.build()
    seq_len = args.seq_len
    rows = [rng.randint(0, 5149, size=(seq_len, 1)).tolist()
            for _ in range(args.batch_size)]
    feed = {
        'words': _lod_feed(rows, 'int64'),
        'label': rng.randint(0, 2, (args.batch_size, 1)).astype('int64'),
    }
    return model, feed, args.batch_size * seq_len, 'tokens/sec'


def _machine_translation(args, rng):
    from paddle_tpu.models import seq2seq
    # reference get_model dims (benchmark/fluid/models/machine_translation.py:
    # embedding_dim=512, encoder/decoder_size=512, dict_size=30000)
    model = seq2seq.build(src_dict_dim=30000, trg_dict_dim=30000,
                          embedding_dim=512, encoder_size=512,
                          decoder_size=512)
    seq_len = args.seq_len
    src = [rng.randint(3, 30000, size=(seq_len, 1)).tolist()
           for _ in range(args.batch_size)]
    trg = [rng.randint(3, 30000, size=(seq_len, 1)).tolist()
           for _ in range(args.batch_size)]
    feed = {
        'src_word_id': _lod_feed(src, 'int64'),
        'target_language_word': _lod_feed(trg, 'int64'),
        'target_language_next_word': _lod_feed(trg, 'int64'),
    }
    return model, feed, args.batch_size * seq_len, 'tokens/sec'


def _ctr(args, rng):
    from paddle_tpu.models import ctr
    from paddle_tpu.dataset import ctr as ctr_data
    model = ctr.build()
    feed = {
        'dense': rng.standard_normal(
            (args.batch_size, ctr_data.DENSE_DIM)).astype('float32'),
        'sparse_ids': rng.randint(
            0, ctr_data.SPARSE_DIM,
            (args.batch_size, ctr_data.SPARSE_SLOTS)).astype('int64'),
        'label': rng.randint(0, 2, (args.batch_size, 1)).astype('int64'),
    }
    return model, feed, args.batch_size, 'examples/sec'


def _transformer(args, rng):
    from paddle_tpu.models import transformer
    seq_len = args.seq_len
    model = transformer.build(src_vocab=30000, trg_vocab=30000,
                              max_len=seq_len, n_layer=6, n_head=8,
                              d_model=512, d_ff=2048)
    src = rng.randint(2, 30000, (args.batch_size, seq_len)).astype('int64')
    trg = np.concatenate(
        [np.zeros((args.batch_size, 1), 'int64'), src[:, :-1]], axis=1)
    feed = {'src_ids': src, 'trg_ids': trg, 'lbl_ids': src}
    return model, feed, args.batch_size * seq_len, 'tokens/sec'


MODELS = {
    'mnist': _mnist,
    'resnet': _resnet,
    'vgg': _vgg,
    'stacked_lstm': _stacked_lstm,
    'machine_translation': _machine_translation,
    'transformer': _transformer,
    'ctr': _ctr,
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', choices=sorted(MODELS), default='mnist')
    parser.add_argument('--batch_size', type=int, default=32)
    parser.add_argument('--iterations', type=int, default=10)
    parser.add_argument('--skip_batch_num', type=int, default=2)
    parser.add_argument('--seq_len', type=int, default=32)
    parser.add_argument('--use_conv', action='store_true')
    parser.add_argument('--amp', action='store_true',
                        help='bf16 matmul/conv inputs (TPU MXU format)')
    parser.add_argument('--device', choices=['CPU', 'TPU'], default='TPU')
    args = parser.parse_args()

    rng = np.random.RandomState(0)
    model, feed, examples_per_step, unit = MODELS[args.model](args, rng)
    # --device is honoured or the run fails: TPUPlace raises the typed
    # NoAcceleratorError when JAX has no accelerator
    place = fluid.TPUPlace() if args.device == 'TPU' else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    if args.iterations < 1:
        raise SystemExit('--iterations must be >= 1')
    with fluid.scope_guard(scope), fluid.amp_guard(args.amp):
        exe.run(model['startup'])
        for _ in range(args.skip_batch_num):
            exe.run(model['main'], feed=feed, fetch_list=[model['loss']])
        t0 = time.time()
        for _ in range(args.iterations):
            loss_v = exe.run(model['main'], feed=feed,
                             fetch_list=[model['loss']])
        elapsed = time.time() - t0
    rate = examples_per_step * args.iterations / elapsed
    print(json.dumps({
        'model': args.model,
        'batch_size': args.batch_size,
        'device': fluid.core.device_info([place.jax_device()]),
        'amp': bool(args.amp),
        'rate': round(rate, 2),
        'unit': unit,
        'last_loss': float(np.asarray(loss_v[0]).flatten()[0]),
    }))


if __name__ == '__main__':
    main()
