"""The driver-bench machinery must be unkillable (VERDICT r3 next-#1).

An early round's record was rc=124 with nothing captured because bench.py
buffered one JSON line until all four configs finished.  These tests pin
the contract since: the parent imports no jax (one process for each chip
— the children need it), each config runs in a subprocess under a hard
budget, a contract-shaped JSON line is flushed after EVERY config, a
hanging config costs only its own budget, and a child that finds no
accelerator fails instead of benchmarking the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, 'bench.py')
sys.path.insert(0, REPO)  # for `from bench import CONFIGS` (no jax)

CONTRACT_KEYS = {'metric', 'value', 'unit', 'vs_baseline'}


def _run_bench(env_extra, timeout, cwd, args=()):
    """bench.py writes BENCH_PARTIAL.json into its working directory:
    run it from a temp dir so the suite leaves the checkout clean."""
    env = dict(os.environ)
    # children must not inherit the suite's 8-device virtual mesh
    env.pop('XLA_FLAGS', None)
    env.pop('BENCH_FORCE_CPU', None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, BENCH] + list(args), env=env, timeout=timeout,
        cwd=str(cwd), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)


def test_child_without_chip_fails_instead_of_benchmarking_cpu(tmp_path):
    """No accelerator and no BENCH_FORCE_CPU=1: the child exits
    non-zero, naming the platform it found, and prints no record —
    toy-size CPU numbers must never appear under device metric names."""
    proc = _run_bench({'JAX_PLATFORMS': 'cpu'}, 120, tmp_path,
                      args=['--config', 'stacked_lstm'])
    assert proc.returncode != 0
    assert b"platform 'cpu'" in proc.stderr, proc.stderr[-400:]
    assert not proc.stdout.strip(), proc.stdout


def test_every_config_flushes_and_timeouts_are_isolated(tmp_path):
    """Tiny budgets -> every child is killed mid-startup, yet the parent
    emits one contract line per config plus the final line, writes the
    partial file, and exits on its own (no external timeout needed).
    The budget must undercut even the interpreter + jax import (~2s):
    the ctr CPU smoke (ISSUE 11) is light enough to FINISH inside the
    old 3s budget on a warm page cache."""
    proc = _run_bench({'BENCH_BUDGET': '1', 'BENCH_FORCE_CPU': '1'}, 120,
                      tmp_path)
    lines = [json.loads(l) for l in proc.stdout.decode().splitlines() if l]
    # N-1 incremental lines + 1 final (the last config's completion IS
    # the final record — no duplicate emission)
    from bench import CONFIGS
    n = len(CONFIGS)
    assert len(lines) == n, proc.stdout
    assert [r['partial'] for r in lines] == [True] * (n - 1) + [False]
    for rec in lines:
        assert CONTRACT_KEYS <= set(rec), rec
        assert 'configs' in rec and 'partial' in rec
    final = lines[-1]
    assert final['partial'] is False
    assert len(final['configs']) == n
    # every config carries an isolated TIMEOUT record, not a crash
    for cfg in final['configs']:
        assert cfg['metric'].endswith('_TIMEOUT'), cfg
        assert 'budget' in cfg['error']
    # ANY config that did not finish -> nonzero exit
    assert proc.returncode != 0
    with open(os.path.join(str(tmp_path), 'BENCH_PARTIAL.json')) as f:
        partial = json.loads(f.read())
    assert partial['configs'] == final['configs']


def test_incremental_lines_are_each_driver_parseable(tmp_path):
    """Kill the parent after the first config completes: the stdout tail
    must already be a valid contract record (the round-3 failure mode)."""
    env = dict(os.environ)
    env.pop('XLA_FLAGS', None)
    env.update({'BENCH_BUDGET': '3', 'BENCH_FORCE_CPU': '1'})
    proc = subprocess.Popen(
        [sys.executable, BENCH], env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        start_new_session=True)
    try:
        first = proc.stdout.readline().decode()
        rec = json.loads(first)
    finally:
        proc.kill()
        proc.wait()
    assert CONTRACT_KEYS <= set(rec)
    assert rec['partial'] is True
    assert len(rec['configs']) == 1


@pytest.mark.slow
def test_single_config_child_runs_cpu():
    # slow-marked (~12 s subprocess soak): the child-isolation
    # contract keeps tier-1 coverage via
    # test_every_config_flushes_and_timeouts_are_isolated
    """The cheapest config end-to-end on CPU through the child entry."""
    env = dict(os.environ)
    env.pop('XLA_FLAGS', None)
    env['BENCH_FORCE_CPU'] = '1'
    proc = subprocess.run(
        [sys.executable, BENCH, '--config', 'stacked_lstm'], env=env,
        timeout=180, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    assert proc.returncode == 0, proc.stderr[-500:]
    rec = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert rec['value'] > 0
    # headline is device-true (run_multi); the one-dispatch-per-step
    # number rides along as a secondary field
    assert rec['device_true'] is True
    assert rec['steps_per_dispatch'] > 1
    assert rec['tokens_per_sec_dispatch_bound'] > 0
    # ISSUE 3: the paired overlapped-input measurement rides along
    _assert_feed_overlap(rec)
    # ISSUE 6: the child enabled FLAGS_cost_accounting, so the timed
    # executable's XLA cost analysis rides the record (mfu itself stays
    # None on CPU — no v5e peak to divide by)
    assert rec['cost'] is not None, rec
    assert rec['cost']['source'] == 'xla_cost_analysis'
    assert rec['cost']['flops_per_step'] > 0
    assert rec['mfu_analytic'] is None  # CPU smoke
    # every record names the device it ran on, as JAX reports it
    assert rec['platform'] == 'cpu' and rec['device_kind'] == 'cpu'
    assert rec['device_count'] >= 1


FEED_OVERLAP_KEYS = {'steps_per_dispatch', 'pipeline_depth', 'dispatches',
                     'ms_per_step_overlapped', 'feed_stall_ms_per_dispatch',
                     'overlap_ratio'}


def _assert_feed_overlap(rec):
    """Every device-true TRAIN record carries the ISSUE 3 feed_overlap
    block: fresh batches staged through the FeedPipeline, with the
    stall/overlap counters that evidence staging N+1 overlapped
    compute N."""
    fo = rec['feed_overlap']
    assert FEED_OVERLAP_KEYS <= set(fo), fo
    assert fo['dispatches'] >= 1
    assert fo['pipeline_depth'] >= 2
    assert 0.0 <= fo['overlap_ratio'] <= 1.0


def test_flagship_configs_wired_through_run_multi():
    """Every flagship config is device-true: TRAIN configs (resnet, nmt,
    transformer, stacked_lstm) time Executor.run_multi dispatches (K
    steps per dispatch), and the inference config times
    Executor.run_eval_multi (K eval steps per dispatch — the last
    dispatch-tax ledger row, ISSUE 2) — all with uniform reporting
    fields.  Source-level pin — the functional path is covered by the
    nmt smoke below and the stacked_lstm child above, all of which
    route through the same _run/_timed_steps_multi helper."""
    import inspect
    import bench
    assert 'run_multi' in inspect.getsource(bench._timed_steps_multi)
    for fn in (bench.bench_resnet, bench.bench_nmt, bench.bench_transformer):
        src = inspect.getsource(fn)
        assert '_run(' in src, fn.__name__
        assert "'device_true': True" in src, fn.__name__
        assert "'steps_per_dispatch': steps" in src, fn.__name__
    # every device-true TRAIN config pairs its number with the
    # overlapped-input measurement (ISSUE 3): a FeedPipeline block over
    # FRESH per-step batches reporting feed_overlap fields
    assert 'FeedPipeline' in inspect.getsource(bench._feed_overlap_block)
    for fn in (bench.bench_resnet, bench.bench_nmt, bench.bench_transformer,
               bench.bench_stacked_lstm):
        src = inspect.getsource(fn)
        assert "'feed_overlap': feed_overlap" in src, fn.__name__
        assert 'batch_fn' in src, fn.__name__
    # the inference config is device-true through the eval scan
    src = inspect.getsource(bench.bench_resnet_infer_bf16)
    assert 'run_eval_multi' in src
    assert "'device_true': True" in src
    assert "'steps_per_dispatch': k" in src
    # ISSUE 4: the inference config pairs its number with the
    # multi-model measurement — both variants registry-hosted under one
    # HBM budget, resident vs evict-reload windows with the arbiter's
    # counters riding along
    assert 'ModelRegistry' in src
    assert "'multi_model': mm" in src
    mm_src = src  # the block builder is nested in the config fn
    for key in ('resident_imgs_per_sec', 'evict_reload_imgs_per_sec',
                'reload_tax', 'evictions', 'reloads',
                'admission_rejects', 'budget_mb'):
        assert "'%s'" % key in mm_src, key


def test_trailing_bucket_blocks_wired():
    """ISSUE 5: the nmt/transformer configs pair their numbers with a
    trailing_bucket block (distinct-length request streams served
    through the trailing-bucketed engine — the helper asserts they
    REALLY coalesce), and tools/perf_gate.py registers the trailing_dim
    paired config with the executable-count/padding-waste deliverables.
    Source-level pin; the functional path is covered by the nmt CPU
    smoke below and tests/test_trailing_buckets.py."""
    import inspect
    import bench
    helper = inspect.getsource(bench._trailing_bucket_block)
    assert 'InferenceEngine' in helper
    assert "m['lots'] < m['requests']" in helper
    for key in ('distinct_lengths', 'executables',
                'trailing_padding_waste', 'trailing_hits'):
        assert "'%s'" % key in helper, key
    for fn in (bench.bench_nmt, bench.bench_transformer):
        src = inspect.getsource(fn)
        assert '_trailing_bucket_block(' in src, fn.__name__
        assert "'trailing_bucket': trailing_bucket" in src, fn.__name__
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    assert 'trailing_dim' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_trailing_dim)
    for key in ('bucketed_vs_exact', 'executables_bucketed',
                'executables_exact', 'executable_ratio',
                'padding_waste'):
        assert "'%s'" % key in src, key


def test_decode_blocks_wired():
    """ISSUE 7: the nmt/transformer configs pair their numbers with a
    functional ``decode`` block (mixed-length prompts through the
    engine's continuous-batching generation lane — the helper asserts
    the lane really fired and every request finished), and
    tools/perf_gate.py registers the decode paired config.  Source-
    level pin; the functional paths are the nmt CPU smoke below,
    tests/test_generation_serving.py, and the perf_gate decode CPU
    smoke in tests/test_perf_gate.py."""
    import inspect
    import bench
    helper = inspect.getsource(bench._decode_block)
    assert 'submit_generate' in helper
    assert 'GenerationSpec' in helper
    assert "d['dispatches'] > 0" in helper
    for key in ('tokens_per_sec', 'steps_per_dispatch',
                'tokens_per_dispatch', 'slot_occupancy',
                'decode_dispatches', 'prefill_lots',
                # ISSUE 9: the pipelined lane's sync accounting
                'host_syncs_per_token', 'decode_pipeline_depth',
                'chain_flushes',
                # ISSUE 14: the chunked-prefill lane's counters — 0
                # chunks on these monolithic blocks, with the stall
                # gauge reporting what the prompt mix imposed
                'prefill_chunks', 'max_decode_stall_cycles'):
        assert "'%s'" % key in helper, key
    for fn, builder in ((bench.bench_nmt, 'seq2seq.build_step_decode'),
                        (bench.bench_transformer,
                         'transformer.build_step_decode')):
        src = inspect.getsource(fn)
        assert '_decode_block(' in src, fn.__name__
        assert builder in src, fn.__name__
        assert "'decode': decode" in src, fn.__name__
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    assert 'decode' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_decode)
    for key in ('dispatch_ratio', 'tokens_per_dispatch',
                'lane_vs_ref', 'slot_occupancy'):
        assert "'%s'" % key in src, key


def test_multi_model_perf_gate_config_registered():
    """tools/perf_gate.py multi_model (ISSUE 4): two models under one
    budget, paired resident-vs-evict-reload windows.  Structural pin —
    the functional path is TPU-only (tests/test_perf_gate.py drives the
    hard gates on hardware); the registry machinery itself is covered
    functionally by tests/test_model_registry.py."""
    import inspect
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    assert 'multi_model' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_multi_model)
    for key in ('resident_imgs_per_sec', 'evict_reload_imgs_per_sec',
                'reload_tax', 'evictions', 'reloads',
                'admission_rejects', 'budget_mb'):
        assert "'%s'" % key in src, key
    assert 'ModelRegistry' in inspect.getsource(
        perf_gate.build_multi_model)


def test_cost_mfu_and_trace_overhead_wired():
    """ISSUE 6: bench.py's MFU is XLA-cost-analysis-derived — every
    child runs under FLAGS_cost_accounting and every device-true config
    reports the timed executable's `cost` block (the analytic counts
    stay as mfu_analytic cross-checks) — and tools/perf_gate.py
    registers the trace_overhead paired config (tracing-on vs
    tracing-off engine over one scope) with the bounded-overhead
    assertion.  Source-level pin; the functional cost-registry path is
    covered by tests/test_trace.py and the stacked_lstm child below."""
    import inspect
    import bench
    helper = inspect.getsource(bench._cost_block)
    assert 'cost_report' in helper
    assert 'xla_cost_analysis' in helper
    assert 'cost_accounting' in inspect.getsource(bench.run_one)
    for fn in (bench.bench_resnet, bench.bench_nmt,
               bench.bench_transformer, bench.bench_stacked_lstm):
        src = inspect.getsource(fn)
        assert "'cost': cost" in src, fn.__name__
        assert "'mfu_analytic': mfu_analytic" in src, fn.__name__
        # mfu prefers the captured cost entry over the analytic count
        assert "cost['mfu']" in src, fn.__name__
    src = inspect.getsource(bench.bench_resnet_infer_bf16)
    assert "'cost': cost" in src
    assert "kind='eval_multi'" in src
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    assert 'trace_overhead' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_trace_overhead)
    for key in ('traced_vs_untraced', 'untraced_rows_per_sec',
                'traced_rows_per_sec', 'spans_last_window',
                'traced_requests', 'stages_ms_mean'):
        assert "'%s'" % key in src, key
    assert 'PERF_GATE_TRACE_MIN' in src
    assert 'tracing()' in inspect.getsource(perf_gate.build_trace_overhead)


@pytest.mark.slow
def test_nmt_cpu_smoke_is_device_true():
    """The cheapest flagship config end-to-end in-process (tiny CPU
    dims): the record must carry the multi-step dispatch contract AND
    the functional feed_overlap block (the pipeline really ran).
    Slow-marked: ~40 s of wall, the single heaviest test in the
    suite — the tier-1 window keeps the subprocess-contract tests
    while this in-process soak rides the slow lane."""
    import bench
    rec = bench.bench_nmt(False)
    assert rec['value'] > 0
    assert rec['device_true'] is True
    assert rec['steps_per_dispatch'] == 2  # the CPU smoke step count
    _assert_feed_overlap(rec)
    assert rec['feed_overlap']['ms_per_step_overlapped'] > 0
    # ISSUE 5: distinct-length request streams really coalesce in the
    # trailing_bucket block (the helper asserts lots < requests)
    tb = rec['trailing_bucket']
    assert tb['distinct_lengths'] >= 4
    assert tb['lots'] < tb['requests']
    assert tb['executables'] <= tb['distinct_lengths']
    assert 0.0 < tb['trailing_padding_waste'] < 1.0
    # ISSUE 7: the decode block really drove the generation lane —
    # mixed-length prompts, K-step scans, every request finished
    dec = rec['decode']
    assert dec['requests'] >= 6
    assert dec['tokens'] > 0 and dec['tokens_per_sec'] > 0
    assert dec['steps_per_dispatch'] > 1
    assert dec['tokens_per_dispatch'] > 1
    assert 0.0 < dec['slot_occupancy'] <= 1.0
    assert dec['decode_dispatches'] > 0
    # ISSUE 9: the pipelined lane's host-sync accounting rode the
    # block — chained by default (depth 2), so syncs per token must
    # come in strictly below one-per-scan
    assert dec['decode_pipeline_depth'] >= 2
    assert dec['host_syncs_per_token'] is not None
    assert dec['host_syncs_per_token'] * dec['tokens'] <= \
        dec['decode_dispatches']
    # ISSUE 14: these blocks run the monolithic lane — zero chunk
    # dispatches, and the stall gauge field is present (>= 0)
    assert dec['prefill_chunks'] == 0
    assert dec['max_decode_stall_cycles'] >= 0.0


def test_ctr_config_wired_sharded_sparse():
    """ISSUE 11 structural pins (no jax in this test): the ctr config
    is registered + budgeted, trains through ParallelExecutor.run_multi
    over a {dp, mp} mesh with the table row-sharded via the
    DistributeTranspiler sparse pass, reports the sparse lane's
    bytes-avoided, and its serving block loads the trained program into
    a ModelRegistry with the per-device embed-table account + the
    sharded-vs-unsharded HBMBudgetError counterfactual."""
    import inspect
    from bench import CONFIGS, BUDGETS, bench_ctr, _ctr_serving_block, \
        _ctr_serving_rec
    assert 'ctr' in CONFIGS and 'ctr' in BUDGETS
    src = inspect.getsource(bench_ctr)
    for pin in ('run_multi', 'DistributeTranspiler', "sparse_shard_axis",
                'is_sparse=True', 'zipf',
                "'sparse_grad_bytes_avoided_per_step'",
                "'embedding_rows_per_sec'", 'is_fully_replicated'):
        assert pin in src, pin
    ssrc = inspect.getsource(_ctr_serving_block) \
        + inspect.getsource(_ctr_serving_rec)
    for pin in ('ModelRegistry', 'EMBED_TABLE_SUFFIX', 'HBMBudgetError',
                "'rows_per_sec'", 'hbm_budget_bytes'):
        assert pin in ssrc, pin
    # the CPU smoke forces the 8-dev virtual mesh before jax loads
    import bench
    assert '--xla_force_host_platform_device_count=8' in \
        inspect.getsource(bench.run_one)


@pytest.mark.slow
def test_ctr_cpu_smoke_trains_and_serves():
    # slow-marked (~11 s in-process soak): the ctr bench contract
    # keeps tier-1 coverage via tests/test_sparse.py's train/serve
    # lanes
    """The ISSUE 11 acceptance, functionally in-process on the suite's
    8-dev virtual mesh: bench_ctr trains device-true with a row-sharded
    table (sparse lane end to end), serves id-batches through the
    registry, carries the per-device table account, and the unsharded
    counterfactual draws the typed HBMBudgetError."""
    import bench
    rec = bench.bench_ctr(on_tpu=False)
    assert rec['value'] > 0 and rec['device_true'] is True
    assert rec['steps_per_dispatch'] >= 2
    assert rec['mesh']['mp'] >= 2 and rec['mesh']['dp'] >= 2
    assert rec['table_row_sharded'] is True
    assert rec['sparse_grad_bytes_avoided_per_step'] > 0
    assert rec['embedding_rows_per_sec'] > 0
    assert rec['cost'] is None or rec['cost']['flops_per_step'] > 0
    srv = rec['serving']
    assert srv['rows'] > 0 and srv['rows_per_sec'] > 0
    assert srv['unsharded_rejected_typed'] is True
    accounts = srv['table_accounts']
    assert accounts, 'the sharded table must carry its own account'
    (acct, ), = [list(accounts)]
    assert ':embed-table:' in acct
    # charged at the PER-DEVICE shard, not the global table
    assert accounts[acct]['bytes'] < srv['table_bytes']
    assert accounts[acct]['resident'] is True
    # ISSUE 12: the two-tier hot-row cache block — overlapped prefetch
    # really fired (> 0 is also asserted inside the block itself), the
    # skewed stream hits, and the host traffic stays a fraction of a
    # full per-step exchange
    cb = rec['cache']
    assert cb['prefetch_overlap_ratio'] > 0
    assert cb['hit_rate'] >= 0.8
    assert cb['exchanges'] >= 2
    assert cb['slab_bytes'] < cb['table_bytes']
    assert cb['rows_per_sec'] > 0


def test_ctr_cache_block_wired():
    """ISSUE 12 structural pins (no jax in this test): the ctr config's
    cache block drives the two-tier store through a FeedPipeline (the
    staging-thread prefetch is what the overlap ratio measures), pins
    overlap > 0 in the block itself, and reports the cache
    deliverables."""
    import inspect
    from bench import bench_ctr, _ctr_cache_block
    assert "'cache'" in inspect.getsource(bench_ctr)
    src = inspect.getsource(_ctr_cache_block)
    for pin in ('CachedEmbeddingTable', 'FeedPipeline', 'embed_caches',
                "'prefetch_overlap_ratio'", "'hit_rate'",
                "'host_bytes_per_step'", 'hot_frac'):
        assert pin in src, pin


def test_no_tmp_sidecars_in_repo_root():
    """ISSUE 9 satellite: the stray ``BENCH_PARTIAL.json.tmp`` kept
    reappearing (an interrupted bench child leaves its atomic-write
    temp behind) — such files are transient by contract, so none may
    ever be TRACKED, and the ignore rule that keeps them out of
    ``git add`` sweeps must stay."""
    import subprocess
    out = subprocess.run(
        ['git', 'ls-files', '*.json.tmp', '**/*.json.tmp'],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    tracked = out.stdout.decode().strip()
    assert not tracked, 'tracked *.json.tmp files: %s' % tracked
    with open(os.path.join(REPO, '.gitignore')) as f:
        assert '*.json.tmp' in f.read()


def test_device_peaks_keyed_by_device_kind():
    """ONE peak table, keyed by jax's device_kind, shared with the
    pure-JAX bound tools; a kind that is not in it is an error, never a
    default (no jax in this test)."""
    import bench
    assert bench.device_peak('TPU v5 lite') == 197e12
    assert bench.device_peak('TPU v5 lite', 'hbm_bytes_per_s') == 819e9
    with pytest.raises(KeyError, match='no peak rates'):
        bench.device_peak('cpu')
    tools = os.path.join(REPO, 'tools')
    for name in ('jax_resnet_bound.py', 'jax_nmt_bound.py',
                 'jax_transformer_bound.py'):
        with open(os.path.join(tools, name)) as f:
            src = f.read()
        assert 'from bench import peak_flops' in src, name
        assert '197e12' not in src, name
