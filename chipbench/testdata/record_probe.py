"""How ``probe.xplane.pb`` was recorded on the v5e (PR 23), kept so that the
test trace can be made again: four rounds of two 2048^2 bf16 matmul programs,
a 20 ms host pause and one reduction, under ``chipbench/...`` spans; then the
trace's planes, lines and first events are printed.  Run by hand through the
chip tool (``chiprun -- python chipbench/testdata/record_probe.py``); nothing
imports it."""
import glob, os, shutil, time, json
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
print(jax.devices(), jax.devices()[0].device_kind, flush=True)
print('mem', jax.devices()[0].memory_stats())

@jax.jit
def small_step(x, w):
    with jax.named_scope('probe_matmul'):
        y = jnp.tanh(x @ w)
    return y @ w.T

@jax.jit
def reduce_step(x):
    return jnp.sum(x * x, axis=1)

x = jnp.ones((2048, 2048), jnp.bfloat16); w = jnp.ones((2048, 2048), jnp.bfloat16)
for _ in range(2):
    small_step(x, w).block_until_ready(); reduce_step(x).block_until_ready()
out = 'chiprun_out/probe_trace'
shutil.rmtree(out, ignore_errors=True)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(out, profiler_options=opts)
t0 = time.perf_counter()
for i in range(4):
    with jax.profiler.TraceAnnotation('chipbench/fetch'):
        y = small_step(x, w); y = small_step(y, w); y.block_until_ready()
    with jax.profiler.TraceAnnotation('chipbench/host_pause'):
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation('chipbench/fetch'):
        reduce_step(y).block_until_ready()
print('traced loop s', time.perf_counter() - t0)
jax.profiler.stop_trace()
p = glob.glob(out + '/plugins/profile/*/*.xplane.pb')[0]
print(p, os.path.getsize(p))
shutil.copy(p, 'chiprun_out/probe.xplane.pb')
d = ProfileData.from_file(p)
for pl in d.planes:
    lines = list(pl.lines)
    print('PLANE', repr(pl.name), len(lines), 'stats', dict(list(pl.stats)[:10]) if hasattr(pl, 'stats') else None)
    for ln in lines:
        evs = list(ln.events)
        print('  LINE', repr(ln.name), len(evs))
        for e in evs[:6]:
            try: st = dict(e.stats)
            except Exception as ex: st = repr(ex)
            print('     ', repr(e.name), e.start_ns, e.duration_ns, {k: (v if not isinstance(v, (bytes, str)) or len(v) < 60 else str(v)[:60]) for k, v in (st.items() if isinstance(st, dict) else [])})
print('mem', jax.devices()[0].memory_stats())
