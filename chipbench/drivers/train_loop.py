"""Driver ``train_loop``: training through ``fluid.FeedPipeline``.

One chip: ``Executor(place of the chip)``; several: ``ParallelExecutor``
on a ``dp`` mesh over them, the traffic's batch split among the chips.
Set-up builds the programs, runs the startup program (weights from the
seed), and delivers the warm-up dispatches, which compile.  The window
then counts whole dispatches: every one ends in a fetched loss, so the
clock stops on finished device work.  It closes at the first delivery at
or after ``--seconds``, and the rate is all its tokens over all its time.

In a traced run the last seconds of the window are traced; the counters'
deltas are taken over the part before, which the profiler does not touch.
"""

import shutil
import time

import numpy as np

TRACE_SECONDS = 3.0
WARMUP_DISPATCHES = 2   # the first compiles, the second fills the pipeline


def run(ctx):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel

    cell, cfg, traffic, model_lib = ctx.cell, ctx.config, ctx.traffic, \
        ctx.model_lib
    devices = ctx.devices
    n, k = len(devices), int(cell['steps_per_dispatch'])
    model = model_lib.build(cfg, traffic)
    ctx.mark('programs_built')
    # the startup program draws the weights: one seed, one set of weights
    model['main'].random_seed = model['startup'].random_seed = \
        ctx.seed % (2 ** 31 - 1)
    batches = ctx.traffic_lib.token_batches(
        traffic, model_lib.vocab(cfg), ctx.seed)
    source = (model_lib.feed(cfg, b) for b in batches)

    scope = fluid.core.Scope()
    losses = []
    with fluid.scope_guard(scope), fluid.amp_guard(bool(cfg['amp'])):
        if n == 1:
            runner = fluid.Executor(fluid.core.place_of(devices[0]))
            runner.run(model['startup'])
        else:
            # the mesh executor lays the host-initialized state out over
            # the mesh at its first dispatch (chip_smoke.py's pattern)
            fluid.Executor(fluid.CPUPlace()).run(model['startup'])
            runner = fluid.ParallelExecutor(
                loss_name=model['loss'].name, main_program=model['main'],
                scope=scope, mesh=parallel.make_mesh({'dp': n}, devices))
        ctx.mark('startup_ran')
        pipe = fluid.FeedPipeline(
            runner, [model['loss']], source=source, steps=k,
            program=model['main'] if n == 1 else None)
        deliveries = iter(pipe)

        def deliver():
            with jax.profiler.TraceAnnotation('chipbench/fetch'):
                out = next(deliveries)
            losses.append(float(np.asarray(out[0]).ravel()[0]))

        try:
            for i in range(WARMUP_DISPATCHES):
                deliver()
                ctx.mark('warmup_%d_delivered' % (i + 1))
            before = _counters(runner, pipe)
            warm = len(losses)
            t0 = ctx.mark('window_opens')   # setup_s ends here
            untraced = ctx.seconds - min(TRACE_SECONDS, ctx.seconds / 2)
            traced_from = None   # (counters, time) when the trace started
            while True:
                deliver()
                now = time.perf_counter()
                if ctx.trace and not traced_from and now - t0 >= untraced:
                    traced_from = (_counters(runner, pipe), now)
                    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
                    _start_trace(jax, ctx.trace_dir)
                if now - t0 >= ctx.seconds:
                    break
            t1 = now
            # counters over the untraced part of the window (all of it,
            # in an untraced run)
            end, t_end = traced_from or (_counters(runner, pipe), t1)
            if traced_from:
                jax.profiler.stop_trace()
        finally:
            deliveries.close()

    dispatches = len(losses) - warm
    tokens_per_step = ctx.traffic_lib.tokens_per_step(traffic)
    window = {'seconds': t1 - t0,
              'dispatches': dispatches, 'steps': dispatches * k,
              'tokens': dispatches * k * tokens_per_step}
    counted = {key: end[key] - before[key] for key in before}
    counted['seconds'] = t_end - t0
    counted['tokens'] = counted['steps'] * tokens_per_step
    finite = bool(np.isfinite(losses).all())
    print('chipbench: losses %s' % ' '.join('%.4f' % l for l in losses),
          flush=True)
    return {
        'attempted': window['steps'],
        'failed': 0 if finite else window['steps'],
        # every fetched loss finite, and the window's last below the
        # run's first
        'correct': finite and losses[-1] < losses[0],
        'end_to_end': {
            'train_tokens_per_s': window['tokens'] / window['seconds']},
        'window': window, 'counted': counted,
        'steps_per_dispatch': k, 'losses': losses,
        'flops_per_token': model_lib.train_flops_per_token(cfg, traffic),
    }


def _counters(runner, pipe):
    m = pipe.metrics()
    return {'compiles': runner.compile_count,
            'feed_stall_s': m['feed_stall_s'],
            'dispatches': m['dispatches'], 'steps': m['steps_dispatched']}


def _start_trace(jax, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # TraceAnnotation spans only
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
