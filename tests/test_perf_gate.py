"""Perf-gate pins that run OFF the chip: every tools/perf_gate.py config
is registered and still measures its deliverable (structural pins), and
the hardware-free paired configs smoke on CPU through their run_*
functions.  The gates themselves are chip-only — ONE process holds the
chip (framework and bound interleaved): ``chiprun -- python
tools/perf_gate.py <config>``, which exits non-zero off-TPU."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_trace_overhead_config_registered():
    """ISSUE 6 structural pin (runs off-TPU): the trace_overhead paired
    config exists, interleaves untraced/traced windows of ONE engine,
    and hard-asserts the bounded-overhead floor.  The functional window
    is TPU-only like the other paired configs; the tracing machinery
    itself is covered functionally by tests/test_trace.py."""
    import inspect
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    assert 'trace_overhead' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_trace_overhead)
    assert "'traced_vs_untraced'" in src
    assert 'PERF_GATE_TRACE_MIN' in src
    build = inspect.getsource(perf_gate.build_trace_overhead)
    assert 'tracing()' in build
    assert 'InferenceEngine' in build


def _import_perf_gate():
    import inspect
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    return perf_gate, inspect


def test_decode_config_registered():
    """ISSUE 7 structural pin (runs off-TPU): the decode paired config
    exists, interleaves lane/per-step-reference windows, asserts
    token-identity, and hard-gates dispatch_ratio +
    tokens_per_dispatch behind their env knobs."""
    perf_gate, inspect = _import_perf_gate()
    assert 'decode' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_decode)
    for pin in ("'dispatch_ratio'", "'tokens_per_dispatch'",
                'PERF_GATE_DECODE_RATIO_MAX', 'PERF_GATE_DECODE_TPD_MIN',
                'token-identical'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_decode)
    assert 'submit_generate' in build
    assert 'GenerationSpec' in build


def test_decode_config_cpu_smoke(monkeypatch):
    """The ISSUE 7 acceptance criterion, functionally on CPU: N >= 6
    mixed-length generation requests through the decode lane are
    token-identical to per-request reference decode at <= 1/3 the
    dispatches (run_decode hard-asserts both gates)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_DEC_REQS', '6')
    monkeypatch.setenv('PERF_GATE_DEC_LEN', '8')
    monkeypatch.setattr(perf_gate, 'BLOCKS', 1)
    rec = perf_gate.run_decode()
    assert rec['requests_per_window'] >= 6
    assert rec['dispatch_ratio'] <= 1.0 / 3.0
    assert rec['tokens_per_dispatch'] >= 4.0
    assert rec['lane_dispatches'] < rec['ref_dispatches']
    assert 0.0 < rec['slot_occupancy'] <= 1.0


def test_decode_overlap_config_registered():
    """ISSUE 9 structural pin (runs off-TPU): the decode_overlap
    paired config exists, pairs a chained (decode_pipeline_depth >= 2)
    engine against the per-scan-sync (depth 1) lane over one shared
    scope/executor, asserts token-identity, and hard-gates the
    host-sync reduction + tokens/s ratio behind their env knobs."""
    perf_gate, inspect = _import_perf_gate()
    assert 'decode_overlap' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_decode_overlap)
    for pin in ("'host_sync_reduction'", "'chained_vs_synced'",
                'PERF_GATE_DECODE_SYNC_RATIO',
                'PERF_GATE_DECODE_TPS_MIN', 'token-identical'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_decode_overlap)
    assert 'decode_pipeline_depth' in build
    assert 'submit_generate' in build
    # the paired engines differ ONLY in pipeline depth: one side is
    # hard-wired to 1 (the per-scan-sync baseline)
    assert 'make_engine(1,' in build


def test_decode_overlap_cpu_smoke(monkeypatch):
    """The ISSUE 9 acceptance criterion, functionally on CPU: the
    chained lane's outputs are bitwise token-identical to the
    per-scan-sync lane's over the same mixed-length stream, with host
    syncs per emitted token reduced >= 2x (run_decode_overlap
    hard-asserts both).  The tokens/s floor is relaxed for this
    CPU-share-capped container (the sync reduction is the structural
    deliverable; throughput parity is jitter-bound here and gated at
    its real floor on hardware)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_DOV_REQS', '6')
    monkeypatch.setenv('PERF_GATE_DOV_LEN', '10')
    monkeypatch.setenv('PERF_GATE_DECODE_TPS_MIN', '0.5')
    # 2 interleaved blocks judged on the best shared window, like the
    # slo smoke: one window's ratio is timing-jittery on this host
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_decode_overlap()
    assert rec['host_sync_reduction'] >= 2.0
    assert rec['sync_per_token_chained'] < rec['sync_per_token_synced']
    assert rec['chained_host_syncs'] < rec['synced_host_syncs']
    assert rec['tokens_per_window'] > 0
    assert rec['decode_pipeline_depth'] >= 2


def test_chunked_prefill_config_registered():
    """ISSUE 14 structural pin (runs off-TPU): the chunked_prefill
    paired config exists, pairs a prefill_chunk=C engine against the
    monolithic lane over one shared scope/executor, asserts token
    identity, and hard-gates the stall reduction, chunk dispatches and
    the bounded-executable structural check behind their env knobs."""
    perf_gate, inspect = _import_perf_gate()
    assert 'chunked_prefill' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_chunked_prefill)
    for pin in ("'stall_reduction'", "'prefill_chunks'",
                'PERF_GATE_CP_STALL_RATIO',
                "'chunked_new_len_compiles'",
                "'mono_new_rung_compiles'", 'token-identical'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_chunked_prefill)
    assert 'prefill_chunk' in build
    assert 'submit_generate' in build
    assert 'chunk=chunk' in build  # the model is built chunk-capable
    # the paired engines differ ONLY in prefill_chunk: one side is
    # hard-wired to the monolithic lane (None)
    assert 'chunk if chunked else None' in build


@pytest.mark.slow
def test_chunked_prefill_cpu_smoke(monkeypatch):
    # slow-marked (~6 s): structural pin stays tier-1; functional
    # chunk-chain coverage rides tests/test_chunked_prefill.py
    """The ISSUE 14 acceptance criterion, functionally on CPU: one
    seeded mixed long-prompt + decode stream through chunked vs
    monolithic engines (shared scope) — outputs token-identical, the
    max decode inter-token stall reduced >= 2x, chunk dispatches
    fired, and the chunked lane recompiles NOTHING for new prompt
    lengths while the monolithic lane mints a fresh-rung executable
    (run_chunked_prefill hard-asserts all four)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_chunked_prefill()
    assert rec['outputs_token_identical']
    assert rec['stall_reduction_s'] >= 2.0
    assert rec['prefill_chunks'] > 0
    assert rec['chunked_new_len_compiles'] == 0
    assert rec['mono_new_rung_compiles'] > 0
    assert rec['mono_prefill_lots'] > 0


def test_slo_profile_shed_check():
    """ISSUE 9's sharpened slo shed contract, deterministically on
    CPU: the per-signature horizon sheds the slow-signature request
    the global min-wall horizon would have admitted (and keeps the
    fast one either way) — plus the structural pin that run_slo folds
    the check into its record."""
    perf_gate, inspect = _import_perf_gate()
    rec = perf_gate.check_profile_shed()
    assert rec == {'profile_shed_slow': True, 'profile_kept_fast': True,
                   'global_horizon_admitted_slow': True}
    src = inspect.getsource(perf_gate.run_slo)
    assert 'check_profile_shed' in src
    assert "'profile_shed_slow'" in src


def test_slo_config_registered():
    """ISSUE 8 structural pin (runs off-TPU): the slo paired config
    exists, drives BOTH engines with the same seeded open-loop stream,
    asserts within-deadline bitwise parity + the typed/staged shed
    contract, and hard-gates the goodput ratio behind its env knob."""
    perf_gate, inspect = _import_perf_gate()
    assert 'slo' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_slo)
    for pin in ("'goodput_ratio'", 'PERF_GATE_SLO_GOODPUT_MIN',
                'DeadlineExceededError', "'shed'", 'bitwise'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_slo)
    assert 'OpenLoopLoadGen' in build
    assert "'fifo'" in build and "'edf'" in build


@pytest.mark.slow
def test_slo_config_cpu_smoke(monkeypatch):
    # slow-marked: under full-suite load the closed-burst capacity
    # calibration can underestimate ~4x (transient CPU weather), the
    # offered rate then never overloads either engine and the
    # goodput ratio degenerates to 1.0 — a harness flake, not an
    # engine bug; the SLO functional contract keeps tier-1 coverage
    # via tests/test_slo_serving.py
    """The ISSUE 8 acceptance criterion, functionally on CPU: under an
    identical overloaded Poisson stream the deadline scheduler's
    goodput beats the FIFO engine's by >= the configured floor
    (run_slo hard-asserts the floor, the bitwise parity of
    within-deadline responses, and the typed shed contract)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_SLO_REQS', '64')
    # 2 interleaved blocks, judged on the best shared window (the
    # gates' pairing rule): one window's ratio is timing-jittery on a
    # CPU-share-capped host, the max of two is decisively > 1.3
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_slo()
    assert rec['goodput_ratio'] >= 1.3
    assert rec['edf_goodput'] > rec['fifo_goodput']
    assert rec['edf_shed'] > 0 and rec['fifo_shed'] == 0
    assert rec['bitwise_checked'] > 0 and rec['shed_checked'] > 0
    assert rec['edf_goodput_req_s'] > rec['fifo_goodput_req_s']


def test_sparse_grad_config_registered():
    """ISSUE 11 structural pin (runs off-TPU): the sparse_grad paired
    config exists, trains sparse-vs-dense CTR lanes over one identical
    seeded zipfian stream through run_multi, asserts final-param
    parity, and hard-gates the step-time ratio + the structural
    no-dense-grad-buffer check behind their env knobs."""
    perf_gate, inspect = _import_perf_gate()
    assert 'sparse_grad' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_sparse_grad)
    for pin in ("'step_time_ratio'", 'PERF_GATE_SPARSE_RATIO_MAX',
                "'sparse_grad_bytes_avoided_per_step'",
                'assert_allclose', 'temp_bytes'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_sparse_grad)
    assert 'is_sparse' in build
    assert 'run_multi' in build
    assert 'zipf' in build


@pytest.mark.slow
def test_sparse_grad_cpu_smoke(monkeypatch):
    # slow-marked (~9 s): structural pin stays tier-1; sparse-lane
    # parity coverage rides tests/test_sparse.py
    """The ISSUE 11 acceptance criterion, functionally on CPU:
    sparse-vs-dense final params allclose over the identical seeded
    skewed stream, bounded step-time ratio on the best shared window,
    and no [V, D]-sized gradient buffer in the sparse lane's cost
    report (its temp bytes stay below one table; the dense lane's meet
    it) — run_sparse_grad hard-asserts all three.  The wall-clock
    floor is relaxed for this CPU-share-capped container (0.79-0.89
    observed solo, but under full-suite load the tiny-shape windows
    are timing luck — the decode_overlap smoke precedent); the strict
    <= 1.0 gate binds at the gate's own default on hardware."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_SP_VOCAB', '8000')
    monkeypatch.setenv('PERF_GATE_SP_STEPS', '4')
    monkeypatch.setenv('PERF_GATE_SPARSE_RATIO_MAX', '1.25')
    # 3 interleaved blocks judged on the best shared window (the
    # gates' pairing rule): single windows are timing-jittery here
    monkeypatch.setattr(perf_gate, 'BLOCKS', 3)
    rec = perf_gate.run_sparse_grad()
    assert rec['step_time_ratio'] <= 1.25
    assert rec['params_checked'] >= 5
    assert rec['sparse_temp_bytes'] < rec['table_bytes']
    assert rec['dense_temp_bytes'] >= rec['table_bytes']
    assert rec['sparse_grad_bytes_avoided_per_step'] > 0
    assert rec['grad_bytes_sparse'] < rec['grad_bytes_dense']


def test_embed_cache_config_registered():
    """ISSUE 12 structural pin (runs off-TPU): the embed_cache paired
    config exists, trains cached-vs-full-table CTR lanes over one
    identical seeded hot-zipfian stream, asserts table parity BITWISE
    (SGD exact), and hard-gates hit rate, the measured
    every-step-exchange host-byte reduction, and the structural
    temp-bytes-below-one-table check behind their env knobs."""
    perf_gate, inspect = _import_perf_gate()
    assert 'embed_cache' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_embed_cache)
    for pin in ("'hit_rate'", 'PERF_GATE_EMBED_HIT_MIN',
                "'host_bytes_reduction'", 'PERF_GATE_EMBED_HOST_RATIO',
                'array_equal', 'invalidate', 'temp_bytes',
                'table_bytes'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_embed_cache)
    assert 'CachedEmbeddingTable' in build
    assert 'embed_caches' in build
    assert 'hot_frac' in build and 'zipf' in build


@pytest.mark.slow
def test_embed_cache_cpu_smoke(monkeypatch):
    # slow-marked (~11 s): the structural pin above stays tier-1, the
    # cache-lane functional contract keeps tier-1 coverage via
    # tests/test_embed_cache.py
    """The ISSUE 12 acceptance criterion, functionally on CPU:
    cached-vs-uncached final params allclose (table BITWISE — SGD
    exact), hit rate >= 0.9 at the smoke's skew, host bytes/step
    >= 4x below the measured every-step-exchange lane, and the
    structural assert that the timed executable's temp bytes stay
    below one full table — run_embed_cache hard-asserts all of it."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_EC_STEPS', '8')
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_embed_cache()
    assert rec['hit_rate'] >= 0.9
    assert rec['host_bytes_reduction'] >= 4.0
    assert rec['prefetch_stalls'] >= 0
    assert rec['slab_bytes'] < rec['table_bytes']
    assert rec['cached_temp_bytes'] < rec['table_bytes']
    assert rec['params_checked'] >= 5


def test_pserver_config_registered():
    """ISSUE 19 structural pin (runs off-TPU): the pserver paired
    config exists, trains the SAME cached CTR lane over a sharded
    parameter-server host tier vs the single-process master on one
    identical seeded zipfian stream, asserts table parity BITWISE,
    holds the hit-rate and host-byte gates UNCHANGED from embed_cache,
    and folds in the seeded shard-kill chaos block (drop_response +
    mid-pass kill-and-restore, zero lost / zero double-applied)."""
    perf_gate, inspect = _import_perf_gate()
    assert 'pserver' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_pserver)
    for pin in ("'hit_rate'", 'PERF_GATE_EMBED_HIT_MIN',
                "'host_bytes_reduction'", 'PERF_GATE_EMBED_HOST_RATIO',
                'array_equal', 'invalidate', 'chaos_bitwise_table',
                'chaos_lost_writes', 'chaos_double_applied_writes',
                'chaos_dedup_replays'):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_pserver)
    assert 'sharded_cache_from_scope' in build
    assert 'CachedEmbeddingTable' in build
    assert 'embed_caches' in build
    assert 'hot_frac' in build and 'zipf' in build
    chaos = inspect.getsource(perf_gate.check_pserver_chaos)
    assert 'drop_response' in chaos
    assert 'kill' in chaos and 'restore' in chaos
    assert 'dedup_replays' in chaos


@pytest.mark.slow
def test_pserver_config_cpu_smoke(monkeypatch, tmp_path):
    # slow-marked (~35 s): the structural pin above stays tier-1, the
    # pserver functional contract keeps tier-1 coverage via
    # tests/test_pserver.py
    """The ISSUE 19 acceptance criterion, functionally on CPU: the
    cached lane over a 4-shard ShardedEmbeddingClient finishes BITWISE
    with the single-process master (table and accumulators), the
    embed_cache gates hold unchanged, and the seeded shard-kill chaos
    block reports zero lost / zero double-applied writes with at least
    one dedup replay — run_pserver hard-asserts all of it."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_PS_STEPS', '8')
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_pserver()
    assert rec['hit_rate'] >= 0.9
    assert rec['host_bytes_reduction'] >= 4.0
    assert rec['shards'] == 4
    assert rec['rpc_calls'] >= 1
    assert rec['params_checked'] >= 5
    assert rec['chaos_bitwise_table'] is True
    assert rec['chaos_lost_writes'] == 0
    assert rec['chaos_double_applied_writes'] == 0
    assert rec['chaos_dedup_replays'] >= 1
    assert rec['chaos_retries'] >= 1
    assert rec['chaos_reconnects'] >= 1
    assert rec['chaos_injected_faults'] >= 1


def test_elastic_config_registered():
    """ISSUE 13 structural pin (runs off-TPU): the elastic paired
    config exists, interleaves bare/async/sync checkpoint windows over
    one warmed executor, hard-gates the async overhead ratio behind
    its env knob, and folds in the kill-resume check (zero replayed
    steps, bitwise params, lease re-dispatch observed)."""
    perf_gate, inspect = _import_perf_gate()
    assert 'elastic' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_elastic)
    for pin in ("'checkpoint_overhead_ratio'",
                'PERF_GATE_ELASTIC_OVERHEAD',
                "'sync_overhead_ratio'", 'check_kill_resume',
                "'resume_replayed_steps'", "'kill_resume_bitwise'"):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_elastic)
    assert 'AsyncShardedCheckpoint' in build
    assert 'run_multi' in build
    kill = inspect.getsource(perf_gate.check_kill_resume)
    assert 'ElasticTrainJob' in kill
    assert 'array_equal' in kill


@pytest.mark.slow
def test_elastic_config_cpu_smoke(monkeypatch):
    # slow-marked (~7 s): structural pin stays tier-1; elastic
    # kill-resume coverage rides tests/test_elastic.py
    """The ISSUE 13 acceptance criterion, functionally on CPU: the
    kill-and-replace run reaches bitwise-identical final params vs an
    uninterrupted run with the dead worker's task lease observed
    timing out and re-dispatching, zero replayed steps, and the async
    checkpoint lane's step-time overhead bounded vs the no-checkpoint
    lane.  The overhead floor is relaxed for this CPU-share-capped
    container (the background writer contends with XLA's own thread
    pool here; the 1.05 default binds at its real floor on hardware —
    the sparse_grad/decode_overlap smoke precedent)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_EL_DISPATCHES', '4')
    # under FULL-SUITE CPU contention the tiny timed windows slow ~2x
    # while the checkpoint's fixed host cost doesn't, so the smoke's
    # relaxed floor needs real headroom (1.30 observed at the margin);
    # the ratio gate's enforcement point is the 1.05 default on
    # hardware — here the structural half (saves committed, bitwise
    # kill-resume, zero replays) is the deliverable
    monkeypatch.setenv('PERF_GATE_ELASTIC_OVERHEAD', '1.6')
    # 3 interleaved blocks judged on the best shared window (the
    # gates' pairing rule): single windows are timing-jittery here
    monkeypatch.setattr(perf_gate, 'BLOCKS', 3)
    rec = perf_gate.run_elastic()
    assert rec['checkpoint_overhead_ratio'] <= 1.6
    assert rec['resume_replayed_steps'] == 0
    assert rec['kill_resume_bitwise'] and rec['lease_redispatched']
    assert rec['async_saves'] > 0 and rec['sync_saves'] > 0
    assert rec['async_bytes_written'] > 0
    assert rec['kill_resume_rows_per_sec'] > 0


def test_resnet_infer_and_feed_pipeline_configs_registered():
    """Back-filled structural pins for the two pre-meta-pin paired
    configs (resnet_infer — ISSUE 2's eval-scan dispatch-tax pair;
    feed_pipeline — ISSUE 3's overlapped-vs-blocked staging pair):
    registered, and their deliverable blocks still measured."""
    perf_gate, inspect = _import_perf_gate()
    assert 'resnet_infer' in perf_gate.CONFIGS
    assert 'run_eval_multi' in inspect.getsource(
        perf_gate.build_resnet_infer)
    assert 'feed_pipeline' in perf_gate.CONFIGS
    assert "'overlapped_vs_blocked'" in inspect.getsource(
        perf_gate.run_feed_pipeline)
    assert 'FeedPipeline' in inspect.getsource(
        perf_gate.build_feed_pipeline)


def test_every_perf_gate_config_has_structural_test():
    """Meta-pin (ISSUE 11 satellite): every perf_gate.CONFIGS entry
    must be exercised by the gate test modules (this file, plus
    test_bench_contract.py where the older paired configs' pins
    historically live) — a dedicated structural/smoke test or the
    TPU-gated parametrize list — so a new paired config cannot land
    ungated."""
    perf_gate, _ = _import_perf_gate()
    src = ''
    for fname in ('test_perf_gate.py', 'test_bench_contract.py'):
        with open(os.path.join(os.path.dirname(
                os.path.abspath(__file__)), fname)) as f:
            src += f.read()
    missing = [name for name in perf_gate.CONFIGS
               if "'%s'" % name not in src and '"%s"' % name not in src]
    assert not missing, (
        'perf_gate configs with no structural test in '
        'test_perf_gate.py/test_bench_contract.py: %s — add a '
        'test_<config>_config_registered (and a CPU smoke where the '
        'config is hardware-free)' % missing)


def test_bound_gate_configs_registered():
    """The three framework-vs-pure-JAX-bound gates (chip-only: they
    need the real operating point) stay registered, each building its
    independent bound from tools/jax_*_bound.py, and the CLI refuses
    to report a pass off the chip."""
    perf_gate, inspect = _import_perf_gate()
    for name, bound in (('resnet', 'jax_resnet_bound'),
                        ('transformer', 'jax_transformer_bound'),
                        ('nmt', 'jax_nmt_bound')):
        assert name in perf_gate.CONFIGS
        assert bound in inspect.getsource(perf_gate.CONFIGS[name][0])
    main_src = inspect.getsource(perf_gate.main)
    assert "backend != 'tpu'" in main_src and 'sys.exit' in main_src


def test_master_chaos_config_registered():
    """ISSUE 15 structural pin (runs off-TPU): the master_chaos
    paired config exists, pairs bare vs resilient ELASTIC windows
    plus the pure-RPC drain diagnostic, hard-gates the retry-layer
    overhead behind its env knob, and folds in the functional chaos
    contract (kill+promotion bitwise run, replayed-task_failed dedup
    pin with its discarding counterfactual)."""
    perf_gate, inspect = _import_perf_gate()
    assert 'master_chaos' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_master_chaos)
    for pin in ("'retry_layer_overhead_ratio'",
                'PERF_GATE_CHAOS_OVERHEAD',
                "'rpc_drain_overhead_ratio'",
                'check_master_chaos', 'check_dedup_replay',
                "'chaos_bitwise_params'", "'chaos_lost'",
                "'chaos_double_processed'", "'chaos_failovers'",
                "'replayed_task_failed_deduped'"):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_master_chaos)
    assert 'ElasticTrainJob' in build
    assert 'ResilientMasterClient' in build
    assert 'MasterClient' in build
    chaos = inspect.getsource(perf_gate.check_master_chaos)
    for pin in ('FaultInjector', 'SnapshotReplica', 'drop_response',
                'heartbeat', 'array_equal', 'failovers'):
        assert pin in chaos, pin
    dedup = inspect.getsource(perf_gate.check_dedup_replay)
    assert 'dedup_execute' in dedup
    assert 'failure_max=2' in dedup


@pytest.mark.slow
def test_master_chaos_config_cpu_smoke(monkeypatch):
    """Slow-marked (~20 s): the structural pin above stays tier-1;
    the functional chaos pass rides the slow lane with the other
    long soaks so the suite holds its wall-clock budget.

    The ISSUE 15 acceptance, functionally on CPU: the seeded chaos
    run (master kill + standby promotion mid-pass, dropped acks,
    delayed heartbeats) finishes with zero lost / zero
    double-processed records and bitwise params vs fault-free; the
    replayed task_failed provably dedups; and the retry layer's
    fault-free overhead stays bounded.  The overhead floors are
    relaxed for this CPU-share-capped container (tiny windows under
    full-suite load are timing luck — the elastic/sparse_grad smoke
    precedent); the 1.05 / 1.6 defaults bind at their real floor on
    quiet hardware."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_CHAOS_OVERHEAD', '1.5')
    monkeypatch.setenv('PERF_GATE_CHAOS_RPC_MAX', '2.5')
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_master_chaos()
    assert rec['chaos_bitwise_params']
    assert rec['chaos_lost'] == 0
    assert rec['chaos_double_processed'] == 0
    assert rec['chaos_deduped_acks'] >= 1
    assert rec['chaos_failovers'] >= 1
    assert rec['replayed_task_failed_deduped']
    assert rec['dedup_counterfactual_discards']
    assert rec['retry_layer_overhead_ratio'] <= 1.5
    assert rec['rpc_drain_overhead_ratio'] <= 2.5
    assert rec['bare_rows_per_sec'] > 0
    assert rec['resilient_rows_per_sec'] > 0


def test_fleet_config_registered():
    """ISSUE 17 structural pin (runs off-TPU): the fleet paired config
    exists, pairs single-registry vs fleet-under-kill windows over the
    identical seeded stream, hard-gates the post-kill goodput ratio
    behind its env knob, and folds in the chaos contract (seeded
    drop_response + pinned-victim kill -> exactly-once, bitwise
    outputs, structural session affinity)."""
    perf_gate, inspect = _import_perf_gate()
    assert 'fleet' in perf_gate.CONFIGS
    src = inspect.getsource(perf_gate.run_fleet)
    for pin in ("'post_kill_goodput_ratio'", 'PERF_GATE_FLEET_GOODPUT',
                "'fleet_lost'", "'fleet_duplicated'",
                "'fleet_bitwise_outputs'", "'fleet_dedup_replays'",
                "'fleet_failovers'", "'fleet_re_prefills'",
                "'fleet_affinity_max_distinct'",
                "'fleet_post_kill_on_survivor'"):
        assert pin in src, pin
    build = inspect.getsource(perf_gate.build_fleet)
    for pin in ('ReplicaServer', 'FleetRouter', 'FaultInjector',
                'drop_response', 'session_dispatches', 'array_equal',
                'submit_generate'):
        assert pin in build, pin


@pytest.mark.slow
def test_fleet_config_cpu_smoke(monkeypatch):
    """Slow-marked (~20 s): the structural pin above stays tier-1,
    and the router/failover functional contract keeps tier-1 coverage
    through tests/test_fleet.py's chaos lane (~4 s); the full
    perf-gate pass rides the slow lane.

    The ISSUE 17 acceptance, functionally on CPU: 2 replicas behind
    the router, a seeded lost response in phase A, the replica holding
    session 0's decode slots killed between rounds — every request of
    the offered stream finishes exactly once, bitwise-identical to the
    fault-free single-registry reference; the retry lands as a dedup
    REPLAY; sessions stay structurally affine (1 replica fault-free,
    <=2 across the kill, all on the survivor after).  The goodput
    floor is relaxed for this CPU-share-capped container (the
    survivor's registry contends with the suite; the 0.25 default
    binds at its real floor on hardware — the master_chaos smoke
    precedent)."""
    perf_gate, _ = _import_perf_gate()
    monkeypatch.setenv('PERF_GATE_FLEET_REQS', '12')
    monkeypatch.setenv('PERF_GATE_FLEET_GOODPUT', '0.15')
    monkeypatch.setattr(perf_gate, 'BLOCKS', 2)
    rec = perf_gate.run_fleet()
    assert rec['fleet_lost'] == 0
    assert rec['fleet_duplicated'] == 0
    assert rec['fleet_bitwise_outputs']
    assert rec['fleet_dedup_replays'] >= 1
    assert rec['fleet_failovers'] >= 1
    assert rec['fleet_replica_deaths'] == 1
    assert rec['fleet_re_prefills'] >= 1
    assert rec['fleet_affinity_pre_kill_max_distinct'] == 1
    assert rec['fleet_affinity_max_distinct'] <= 2
    assert rec['fleet_post_kill_on_survivor']
    assert rec['post_kill_goodput_req_s'] > 0
