"""How ``scoped.xplane.pb.gz`` was recorded on the v5e (PR 24), kept so that
the test trace can be made again: a two-layer toy transformer (d_model 32,
AMP) trained through ``fluid.Executor`` + ``fluid.FeedPipeline`` with K=2,
two warm-up dispatches untraced, then five traced ones (the first and the
last run of the step program are never counted, and a dispatch's host spans
lie before its run: three whole runs hold two whole dispatches), so the
trace holds the program's own scopes (``paddle_tpu.step/<op type>.<var>``
in every operation's ``tf_op``) and spans (``paddle_tpu/executor/...``,
``paddle_tpu/feed/...``).  Of the recorded file only the planes the readers
use are kept, byte for byte (``/device:TPU:0``, ``/host:CPU``; the HLO
protos of ``/host:metadata`` are 16 MB), and gzipped: 1800 operations'
metadata are 1.4 MB as recorded.  Run by hand through the chip tool
(``chiprun -- python chipbench/testdata/record_scoped.py``); nothing imports
it."""
import gzip
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'chipbench'))

import jax  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
import scopes  # noqa: E402

K, BATCH, LENGTH, VOCAB = 2, 8, 16, 100
print(jax.devices(), jax.devices()[0].device_kind, flush=True)
model = transformer.build(src_vocab=VOCAB, trg_vocab=VOCAB, max_len=LENGTH,
                          n_layer=2, n_head=2, d_model=32, d_ff=64,
                          dropout=0.0, lr=0.001)
model['main'].random_seed = model['startup'].random_seed = 24


def batches():
    rng = np.random.RandomState(24)
    while True:
        ids = rng.randint(1, VOCAB, (3, BATCH, LENGTH)).astype('int64')
        yield {'src_ids': ids[0], 'trg_ids': ids[1], 'lbl_ids': ids[2]}


out = os.path.join(ROOT, 'chiprun_out', 'scoped_trace')
shutil.rmtree(out, ignore_errors=True)
with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
    exe = fluid.Executor(fluid.default_place())
    exe.run(model['startup'])
    pipe = fluid.FeedPipeline(exe, [model['loss']], source=batches(),
                              steps=K, program=model['main'])
    deliveries = iter(pipe)
    losses = [float(np.ravel(next(deliveries)[0])[0]) for _ in range(2)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # TraceAnnotation spans only
    opts.host_tracer_level = 1   # and not the runtime's own events
    jax.profiler.start_trace(out, profiler_options=opts)
    losses += [float(np.ravel(next(deliveries)[0])[0]) for _ in range(5)]
    jax.profiler.stop_trace()
    deliveries.close()
print('losses', losses)

path = scopes.xplane.find_trace(out)
with open(path, 'rb') as f:
    space = memoryview(f.read())

def varint(n):
    out = bytearray()
    while n > 0x7f:
        out.append(n & 0x7f | 0x80)
        n >>= 7
    return bytes(out + bytes([n]))


kept = bytearray()
for field, _, plane in scopes._fields(space):
    name = next((bytes(v).decode() for f, _, v in scopes._fields(plane)
                 if f == 2), '') if field == 1 else ''
    keep = field == 1 and (scopes.xplane.DEVICE_PLANE.match(name)
                           or name == '/host:CPU')
    print('plane %-40r %8d bytes %s' % (name, len(plane),
                                        'kept' if keep else ''))
    if keep:   # XSpace.planes is field 1, length-delimited: tag 0x0a
        kept += b'\x0a' + varint(len(plane)) + plane
small = os.path.join(ROOT, 'chiprun_out', 'scoped.xplane.pb')
with open(small, 'wb') as f:
    f.write(kept)
with gzip.GzipFile(small + '.gz', 'wb', 9, mtime=0) as f:
    f.write(kept)
print(path, os.path.getsize(path), '->', small, len(kept), '->',
      os.path.getsize(small + '.gz'), 'gzipped')
reduced = scopes.reduce(small)
if reduced:
    print('\n'.join(scopes.table(reduced, K, 25)))
    print('xplane.reduce busy_s', scopes.xplane.reduce(small)['busy_s'],
          'scopes busy_s', reduced['worst']['busy_s'])
