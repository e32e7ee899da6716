"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the main path once, in THIS process (a chip belongs to one process;
no child is started), through the entry points a user calls, at the full
width of the models bench.py measures (depth as published too; weights
random from a seed):

  trainer  transformer-base (6L d512 ff2048 h8 vocab 30000, batch 128 x
           seq 256 per chip, AMP, Adam): startup program, then a few
           K-step ``run_multi`` dispatches fed fresh batches through
           ``fluid.FeedPipeline`` — on ``Executor(TPUPlace())`` with one
           chip, on ``ParallelExecutor(mesh={'dp': 4})`` with four or more.
           Passes when every loss is finite, the last is lower than the
           first, and every parameter, optimizer accumulator and scanned
           feed block is a ``jax.Array`` laid out over exactly the
           devices the leg runs on.
  server   the 512-wide NMT generator (``seq2seq.build_step_decode``)
           behind ``serving.InferenceEngine`` with 4 decode slots and
           4-step decode scans, answering mixed-length ``submit_generate``
           requests from two client threads.  Passes when every future
           resolves to 1..max_len in-range token ids, a second submission
           of the same requests returns the same tokens exactly, the
           decode lane dispatched, and ``engine.metrics()['device']``
           names the platform the smoke runs on.

Exits non-zero — and prints no result line — unless JAX's platform is
``tpu``.  ``--cpu-tiny`` is the one way to run it anywhere else: the same
two phases at a toy width on CPU devices, chosen by that argument and
never by detection (tests/test_chip_smoke.py).  Any phase failure raises,
so it reaches the exit code.  A passing run prints what it observed
(versions, compile-cache hits and misses, both phases' records) on a
``chip_smoke: summary {...}`` line, and then, as the last stdout line, one
JSON object with exactly these keys and nothing else:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.

Run it with JAX_PLATFORMS unset or ``tpu,cpu`` — never ``tpu`` alone
(CPUPlace, which startup programs of mesh runs use, needs the cpu
backend).  The compile cache lives where JAX_COMPILATION_CACHE_DIR says,
else in <checkout>/.jax_cache (fluid.flags.enable_compile_cache).
"""

import argparse
import json
import sys
import threading
import time

import numpy as np

SEED = 0
STEPS_PER_DISPATCH = 4
DISPATCHES = 3
PROMPT_LENS = (3, 6, 9, 4, 8, 5, 7, 2)   # > decode_slots: forces re-admission
# training ids come from the first WORKING_VOCAB words: drawn uniformly
# over all 30000, each class shows up about once a batch and a dozen
# steps teach nothing (first chip run: loss 10.34 -> 10.35)
WORKING_VOCAB = 64
CLIENTS = 2
RESULT_TIMEOUT_S = 600

# bench.py's chip sizes (bench_transformer, bench_nmt's decode block)
FULL = {
    'train': dict(vocab=30000, seq=256, n_layer=6, n_head=8, d_model=512,
                  d_ff=2048, batch_per_chip=128),
    'serve': dict(vocab=30000, dim=512, max_len=16),
}
TINY = {
    'train': dict(vocab=100, seq=16, n_layer=1, n_head=2, d_model=32,
                  d_ff=64, batch_per_chip=8),
    'serve': dict(vocab=100, dim=16, max_len=8),
}


def _check(cond, msg, *args):
    """Phase assertions raise (assert is stripped under -O)."""
    if not cond:
        raise RuntimeError('chip_smoke: ' + (msg % args))


def train_phase(cfg, devices):
    """Transformer training over ``devices`` (1: Executor; >1:
    ParallelExecutor on a dp mesh, per-chip batch unchanged)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel
    from paddle_tpu.models import transformer

    n = len(devices)
    model = transformer.build(
        src_vocab=cfg['vocab'], trg_vocab=cfg['vocab'], max_len=cfg['seq'],
        n_layer=cfg['n_layer'], n_head=cfg['n_head'],
        d_model=cfg['d_model'], d_ff=cfg['d_ff'])
    model['main'].random_seed = model['startup'].random_seed = SEED
    batch = cfg['batch_per_chip'] * n
    rng = np.random.RandomState(SEED)

    def batches():
        # a fresh batch every step; the label is the decoder input
        # itself, so a dozen Adam steps visibly lower the loss
        for _ in range(STEPS_PER_DISPATCH * DISPATCHES):
            ids = lambda: rng.randint(
                1, WORKING_VOCAB, size=(batch, cfg['seq'])).astype('int64')
            trg = ids()
            yield {'src_ids': ids(), 'trg_ids': trg, 'lbl_ids': trg}

    scope = fluid.core.Scope()
    t0 = time.time()
    with fluid.scope_guard(scope), fluid.amp_guard(True):
        if n == 1:
            runner = fluid.Executor(fluid.core.place_of(devices[0]))
            runner.run(model['startup'])
        else:
            # the mesh executor lays the host-initialized state out over
            # the mesh at its first dispatch
            fluid.Executor(fluid.CPUPlace()).run(model['startup'])
            runner = fluid.ParallelExecutor(
                loss_name=model['loss'].name, main_program=model['main'],
                scope=scope, mesh=parallel.make_mesh({'dp': n}, devices))
        pipe = fluid.FeedPipeline(
            runner, [model['loss']], source=batches(),
            steps=STEPS_PER_DISPATCH,
            # a ParallelExecutor runs the program it was built over
            program=model['main'] if n == 1 else None)
        losses = [float(np.asarray(out[0]).ravel()[0]) for out in pipe]
    wall = time.time() - t0

    _check(len(losses) == DISPATCHES, 'trainer delivered %d of %d '
           'dispatches', len(losses), DISPATCHES)
    _check(np.isfinite(losses).all(), 'trainer loss not finite: %s', losses)
    _check(losses[-1] < losses[0], 'trainer loss did not fall: %s', losses)
    want = set(devices)
    # what training updates: parameters and optimizer accumulators
    written = {n for op in model['main'].global_block().ops
               for n in op.output_arg_names}
    state = [v.name for v in model['main'].list_vars()
             if v.persistable and v.name in written]
    for name in state:
        val = scope.find_var(name).value()
        _check(isinstance(val, jax.Array), 'state %r is %s, not a '
               'jax.Array', name, type(val).__name__)
        _check(set(val.devices()) == want, 'state %r lives on %s, the leg '
               'runs on %s', name, sorted(map(str, val.devices())),
               sorted(map(str, want)))
    m = pipe.metrics()
    _check(m['feed_devices'] == n, 'scanned feed block laid out over %d '
           'device(s), expected %d', m['feed_devices'], n)
    in_use = [(d.memory_stats() or {}).get('bytes_in_use') for d in devices]
    if in_use[0] is not None:   # the CPU backend reports no memory stats
        _check(all(b > 0 for b in in_use), 'a device holds no memory: %s',
               in_use)
    return {'executor': type(runner).__name__, 'devices': n,
            'global_batch': batch, 'losses': [round(l, 4) for l in losses],
            'state_arrays': len(state), 'dispatches': m['dispatches'],
            'feed_devices': m['feed_devices'],
            'bytes_in_use': in_use, 'wall_s': round(wall, 1)}


def serve_phase(cfg, device):
    """NMT generation serving on ``device`` through the engine."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import seq2seq

    place = fluid.core.place_of(device)
    model = seq2seq.build_step_decode(
        cfg['vocab'], cfg['vocab'], cfg['dim'], cfg['dim'], cfg['dim'],
        max_len=cfg['max_len'])
    for key in ('prefill', 'prefill_startup', 'step', 'step_startup'):
        model[key].random_seed = SEED
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(model['prefill_startup'])
        exe.run(model['step_startup'])
    rng = np.random.RandomState(SEED + 1)
    prompts = [
        {'src_word_id': fluid.create_lod_tensor(
            rng.randint(3, cfg['vocab'], size=(l, 1)).tolist(), [[l]])}
        for l in PROMPT_LENS]
    eng = serving.InferenceEngine(
        model['prefill'], fetch_list=model['prefill_fetches'], scope=scope,
        executor=exe, place=place,
        config=serving.ServingConfig(
            max_batch_size=len(prompts), max_wait_ms=5, decode_slots=4,
            decode_steps=4),
        generation=serving.GenerationSpec.from_model(model))

    def one_pass():
        """Every prompt once, submitted by CLIENTS concurrent threads
        while the worker is held, so both passes coalesce the same lots
        (same executables: token equality is then exact)."""
        futures = [None] * len(prompts)

        def client(k):
            for i in range(k, len(prompts), CLIENTS):
                futures[i] = eng.submit_generate(prompts[i])

        threads = [threading.Thread(target=client, args=(k, ))
                   for k in range(CLIENTS)]
        with eng.paused():
            for t in threads:
                t.start()
            for t in threads:
                t.join(RESULT_TIMEOUT_S)
        _check(all(f is not None for f in futures),
               'a client thread did not submit')
        return [np.asarray(f.result(RESULT_TIMEOUT_S)) for f in futures]

    t0 = time.time()
    with eng:
        first = one_pass()
        again = one_pass()
        # one request at a time: a DIFFERENT (batch-1) prefill
        # executable, which may differ by an ulp (ROADMAP D2) — recorded,
        # not judged
        alone = [np.asarray(eng.generate(p, timeout=RESULT_TIMEOUT_S))
                 for p in prompts]
        metrics = eng.metrics()
    wall = time.time() - t0

    for toks in first:
        _check(1 <= len(toks) <= cfg['max_len'], 'generated %d tokens, '
               'budget is 1..%d', len(toks), cfg['max_len'])
        _check(((toks >= 0) & (toks < cfg['vocab'])).all(),
               'token id out of range: %s', toks)
    for a, b in zip(first, again):
        _check(np.array_equal(a, b), 'resubmission changed the tokens: %s '
               'vs %s', a, b)
    dec = metrics['decode']
    _check(dec['dispatches'] > 0, 'the decode lane never dispatched: %s',
           dec)
    _check(dec['finished'] == 3 * len(prompts), 'decode lane finished %d '
           'of %d requests', dec['finished'], 3 * len(prompts))
    _check(metrics['device']['platform'] == device.platform,
           'engine reports device %s, smoke runs on %s',
           metrics['device'], device.platform)
    return {'requests': 3 * len(prompts),
            'tokens': int(sum(len(t) for t in first)),
            'decode_dispatches': dec['dispatches'],
            'tokens_per_dispatch': dec['tokens_per_dispatch'],
            'host_syncs_per_token': dec['host_syncs_per_token'],
            'executables': metrics['executor_compile_count'],
            'engine_device': metrics['device'],
            'one_at_a_time_equal': '%d/%d' % (
                sum(np.array_equal(a, b) for a, b in zip(first, alone)),
                len(prompts)),
            'wall_s': round(wall, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--cpu-tiny', action='store_true',
                    help='run both phases at a toy width on CPU devices '
                         '(tier-1 tests); without it the platform must '
                         'be tpu')
    args = ap.parse_args(argv)
    t0 = time.time()

    import importlib.metadata as md
    import jax
    devices = jax.devices('cpu') if args.cpu_tiny else jax.devices()
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    print('chip_smoke: platform=%(platform)s device_kind=%(kind)s '
          'count=%(count)d' % device, flush=True)
    if not args.cpu_tiny and device['platform'] != 'tpu':
        sys.exit('chip_smoke: needs a TPU, JAX found platform %r (%s x%d) '
                 '— run on the chip, or pass --cpu-tiny for the CPU-sized '
                 'check' % (device['platform'], device['kind'],
                            device['count']))

    from paddle_tpu.fluid import flags
    cache = {'dir': flags.enable_compile_cache(), 'hits': 0, 'misses': 0}

    def count(event, **_):
        if event.startswith('/jax/compilation_cache/cache_'):
            cache[event.rsplit('_', 1)[1]] += 1   # ..._hits / ..._misses

    jax.monitoring.register_event_listener(count)
    cfg = TINY if args.cpu_tiny else FULL
    # four or more devices: the trainer takes the mesh leg
    train_devices = devices[:4] if len(devices) >= 4 else devices[:1]
    train = train_phase(cfg['train'], train_devices)
    print('chip_smoke: trainer ok %s' % json.dumps(train), flush=True)
    serve = serve_phase(cfg['serve'], devices[0])
    print('chip_smoke: server ok %s' % json.dumps(serve), flush=True)
    print('chip_smoke: summary %s' % json.dumps({
        'versions': {p: md.version(p) for p in ('jax', 'jaxlib', 'libtpu')},
        'width': 'tiny' if args.cpu_tiny else 'full',
        'compile_cache': cache,
        'trainer': train, 'server': serve,
        'wall_s': round(time.time() - t0, 1)}), flush=True)
    # the driver parses this line: exactly these keys, the device as JAX
    # reports it, nothing after it
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
