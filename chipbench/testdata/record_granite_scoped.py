"""How ``granite_scoped.xplane.pb.gz`` was recorded on the v5e (PR 26), kept
so that the test trace can be made again: the configuration's ``cpu_tiny``
model (a Mamba-2 layer and an attention layer, hidden 64, AMP) trained
through ``fluid.Executor`` + ``fluid.FeedPipeline`` with K=2, as
``record_scoped.py`` records its toy transformer: two warm-up dispatches
untraced, five traced.  The trace holds the scopes of the state-space ops
(``ssd_scan.*``, ``ssd_scan_grad.*``, ``causal_conv1d.*``,
``gated_rms_norm.*``) that ``layer_metrics/ssm_device_ms.train.py`` and
``ssd_scan_roofline.train.py`` read.  Only the device plane and
``/host:CPU`` are kept, gzipped.  Run by hand through the chip tool
(``chiprun -- python chipbench/testdata/record_granite_scoped.py``);
nothing imports it."""
import gzip
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'chipbench'))

import jax  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
import scopes  # noqa: E402
import traffic as traffic_lib  # noqa: E402
sys.path.insert(0, os.path.join(ROOT, 'chipbench', 'models'))
import granite_hybrid_train as builder  # noqa: E402

K = 2
print(jax.devices(), jax.devices()[0].device_kind, flush=True)


def tiny(name):
    with open(os.path.join(ROOT, 'chipbench', name)) as f:
        params = json.load(f)
    params.update(params.pop('cpu_tiny'))
    return params


cfg, traffic = tiny('configs/granite-4.0-h-micro.json'), \
    tiny('traffic/zipf_b1_l1024.json')
model = builder.build(cfg, traffic)
model['main'].random_seed = model['startup'].random_seed = 26
source = (builder.feed(cfg, b) for b in traffic_lib.token_batches(
    traffic, builder.vocab(cfg), 26))

out = os.path.join(ROOT, 'chiprun_out', 'granite_scoped_trace')
shutil.rmtree(out, ignore_errors=True)
with fluid.scope_guard(fluid.core.Scope()), fluid.amp_guard(True):
    exe = fluid.Executor(fluid.default_place())
    exe.run(model['startup'])
    pipe = fluid.FeedPipeline(exe, [model['loss']], source=source, steps=K,
                              program=model['main'])
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
    if field == 1 and (scopes.xplane.DEVICE_PLANE.match(name)
                       or name == '/host:CPU'):
        # XSpace.planes is field 1, length-delimited: tag 0x0a
        kept += b'\x0a' + varint(len(plane)) + plane
small = os.path.join(ROOT, 'chiprun_out', 'granite_scoped.xplane.pb')
with open(small, 'wb') as f:
    f.write(kept)
with gzip.GzipFile(small + '.gz', 'wb', 9, mtime=0) as f:
    f.write(kept)
print(path, os.path.getsize(path), '->', small, len(kept), '->',
      os.path.getsize(small + '.gz'), 'gzipped')
reduced = scopes.reduce(small)
print('\n'.join(scopes.table(reduced, K, 40)))
