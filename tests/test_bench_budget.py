"""bench.py's one-line contract: the byte budget of the emitted line,
the section filter, the device rules (an unknown ``device_kind`` is an
error; no chip, no record), and the exit code.
"""

import json
import sys
import time


import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    sys.path.insert(0, _REPO)
    import bench

    return bench


# Byte budget: the ONE emitted line must stay parseable inside the
# driver's ~2,000-char window. The shrink is exercised on the WORST
# case: every bench section populated.
# ---------------------------------------------------------------------------


def _worst_case_extra(bench, tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_REPO_DIR", str(tmp_path))
    # every section populated: ~90 keys the real worker can emit
    extra = {"device": "TPU_v5e(chip=0)", "model": "gpt2-small-124M"}
    sections = (
        "flash_step_s flash_batch seq_len mfu dense_step_s dense_batch "
        "dense_tokens_per_s flash_vs_dense headline_config ckpt_bytes "
        "flash_ckpt_save_block_s ckpt_save_block_s ckpt_async_stage_block_s "
        "ckpt_save_vs_target restore_s h2d_floor_s restore_overhead_x "
        "goodput_ckpt_every_10_steps durable_save_block_s "
        "durable_restore_s durable_block_vs_flash_x "
        "flash_seq4096_ms flash_seq4096_tflops "
        "flash_seq4096_dispatch_floor_ms generate_tokens_per_s decode_batch "
        "decode_prompt_len decode_new_tokens decode_ms_per_step "
        "decode_tokens_per_s prefill_ms decode_int8_ms_per_step "
        "decode_int8_tokens_per_s decode_int8_vs_bf16 spec_tokens_per_s "
        "spec_acceptance spec_self_acceptance spec_self_acceptance_f32 "
        "spec_vs_plain serving_weight_adopt_s serving_stream_tokens_per_s "
        "serving_homogeneous_tokens_per_s serving_mixed_vs_homogeneous "
        "serving_weight_swap_s serving_batch_slots serving_requests "
        "serving_per_row_tokens_per_s serving_per_row_vs_frontier "
        "serving_sync_tokens_per_s serving_overlap_tokens_per_s "
        "serving_overlap_vs_sync serving_overlap_hidden_ms "
        "serving_overlap_slots serving_auto_chunk_final "
        "serving_auto_chunk_retunes "
        "interposer_plain_step_s flash_base_step_s "
        "serving_spec_tokens_per_s serving_spec_acceptance "
        "serving_spec_vs_per_row serving_int8_2x_slots_tokens_per_s "
        "serving_int8_2x_vs_per_row serving_host_frac "
        "attr_top_residual_frac attr_matmul_frac llama_tokens_per_s "
        "llama_step_s moe_tokens_per_s moe_step_s longseq_train_tokens_per_s "
        "longseq_train_mfu fused_ce_b32_step_s fused_ce_b32_tokens_per_s "
        "fused_ce_b64_step_s fused_ce_b64_tokens_per_s remat_dots_step_s "
        "remat_dots_tokens_per_s no_remat_step_s no_remat_tokens_per_s "
        "batch48_step_s batch48_tokens_per_s batch64_step_s "
        "batch64_tokens_per_s worker_rc"
    ).split()
    for i, k in enumerate(sections):
        extra[k] = round(1234.5678 + i, 4)
    extra["serving_overlap_exact"] = True
    extra["ckpt_note"] = "c" * 220  # the artifact-note string rides extra
    extra["section_retry"] = {
        "sections": ["ckpt", "serving"], "cleared": ["ckpt_error"],
        "retry_on_tpu": True, "elapsed_s": 812.4,
    }
    extra["headline_config"] = "flash+fused_ce+remat_dots+b64"
    extra["tpu_attempt"] = "interposed"
    extra["attr_report"] = "BENCH_attr_1785575775_1234.json"
    extra["attr_ring"] = "BENCH_attr_ring_1785575775_1234.timeline"
    extra["attr_top_residual"] = "optimizer_hbm"
    extra["hbm_live_mb"] = {
        n: 1234.5 for n in (
            "post_dense", "post_ckpt", "post_serving", "post_llama",
            "post_longseq",
        )
    }
    extra["interposed"] = {
        "execute_count": 50000.0, "execute_avg_us": 3300.0,
        "execute_max_us": 410000.0, "h2d_count": 900.0,
        "compile_count": 44.0, "device_completes": 50000.0,
        "stall_verdict": 0.0,
    }
    # slice-storm recovery-SLO matrix (full dict incl. stall forensics
    # rides extra/sidecar; the storm_* scalars must survive in-line)
    extra["goodput_storm"] = {
        "goodput": 0.83, "training_goodput": 0.95, "steps": 520,
        "kills": 4, "elapsed_s": 812.2, "steps_per_second": 0.71,
        "boot_s": 24.3, "mttr_s": 11.4, "slice_mttr_s": 17.9,
        "slice_goodput": 0.88, "slice_relaunches": 3,
        "rdzv_s": 2.1, "restore_s": 0.4, "compile_s": 6.2,
        "first_step_s": 7.0, "recovery_samples": 4,
        # incident-trace phase breakdown (docs/observability.md)
        "mttd_s": 0.8, "detect_s": 0.8, "rendezvous_s": 2.0,
        "reshard_s": 0.5, "recompile_s": 6.1, "trace_mttr_s": 9.4,
        "trace_incidents": 4,
        "stalls": [
            {"at_step": 100 + 30 * i, "gap_s": 12.5, "kill": True,
             "kind": "slice" if i % 2 else "host"}
            for i in range(8)
        ],
    }
    extra["storm_goodput"] = 0.83
    extra["storm_mttr_s"] = 11.4
    extra["storm_slice_mttr_s"] = 17.9
    extra["storm_slice_goodput"] = 0.88
    # MTTR phase breakdown + warm-vs-cold recovery A/B (docs/recovery.md):
    # the full two-leg dict is sidecar-class; the scalars ride the line
    extra["storm_rdzv_s"] = 2.1
    extra["storm_restore_s"] = 0.4
    extra["storm_compile_s"] = 6.2
    extra["storm_first_step_s"] = 7.0
    # trace-derived detection SLOs (docs/observability.md): MTTD + the
    # detect phase share ride the line; the remaining trace phase
    # scalars stay inside the sidecar's goodput_storm dict
    extra["storm_mttd_s"] = 0.8
    extra["storm_detect_s"] = 0.8
    extra["recovery_ab"] = {
        "cold": dict(extra["goodput_storm"], compile_s=12.1),
        "warm": dict(extra["goodput_storm"], compile_s=0.3),
        "mttr_delta_s": 11.8, "cold_compile_s": 12.1,
        "warm_compile_s": 0.3,
    }
    extra["recovery_cold_mttr_s"] = 22.9
    extra["recovery_warm_mttr_s"] = 11.1
    extra["recovery_mttr_delta_s"] = 11.8
    extra["recovery_cold_compile_s"] = 12.1
    extra["recovery_warm_compile_s"] = 0.3
    # master crash tolerance (docs/recovery.md master failover): the
    # MTTR + goodput scalars must survive in-line; the full drill dict
    # (epoch, replay_s, restart audit) is sidecar-class
    extra["master_kill"] = {
        "master_mttr_s": 3.4, "master_kill_goodput": 0.91,
        "steps": 42, "epoch": 2, "worker_restarts": 0,
        "kv_survived": True, "master_replay_s": 0.012,
        "master_boot_samples": 1, "reattach_s": 0.05,
        "rdzv_s": 0.0, "restore_s": 0.0, "compile_s": 0.0,
        "first_step_s": 0.0, "recovery_samples": 0,
    }
    extra["master_mttr_s"] = 3.4
    extra["master_kill_goodput"] = 0.91
    extra["master_kill_worker_restarts"] = 0
    # serving-fleet section (docs/serving_fleet.md): the SLO trio must
    # survive in-line; the supporting scalars may shrink to the sidecar
    extra["fleet_requests_per_s"] = 8.42
    extra["fleet_1rep_requests_per_s"] = 4.91
    extra["fleet_2v1_x"] = 1.715
    extra["fleet_kill_availability"] = 1.0
    extra["fleet_kill_redispatches"] = 3
    extra["fleet_rollout_max_unready"] = 1
    extra["fleet_rollout_aborted"] = False
    extra["fleet_rollout_load_failed"] = 0
    extra["fleet_ready"] = 2
    # paged-KV serving section (docs/serving_fleet.md paged memory):
    # the throughput/p95/hit-rate trio must survive in-line; the dense
    # leg and occupancy scalars may shrink to the sidecar
    extra["fleet_paged_tokens_per_s"] = 1613.5
    extra["fleet_paged_p95_s"] = 0.0559
    extra["prefix_hit_rate"] = 0.792
    extra["fleet_dense_tokens_per_s"] = 390.0
    extra["fleet_dense_p95_s"] = 0.2392
    extra["fleet_paged_vs_dense_x"] = 4.138
    extra["fleet_affinity_hits"] = 9
    extra["fleet_blocks_total"] = 30
    extra["fleet_blocks_free"] = 30
    # chip-pool section (docs/pool.md): the SLO trio must survive
    # in-line; the supporting scalars may shrink to the sidecar
    extra["pool_preempt_to_ready_s"] = 0.54
    extra["pool_spike_availability"] = 1.0
    extra["pool_train_goodput"] = 0.62
    extra["pool_handback"] = True
    extra["pool_requests_ok"] = 212
    extra["pool_revokes"] = 2
    extra["pool_escalations"] = 0
    extra["pool_recovered_vs_baseline"] = 0.98
    extra["pool_window_s"] = 10.4
    # multi-tenant cluster section (docs/cluster.md): the SLO trio must
    # survive in-line; the supporting scalars may shrink to the sidecar
    extra["cluster_inversion_avail"] = 1.0
    extra["cluster_preempt_cascade_s"] = 0.41
    extra["cluster_brain_adopt_s"] = 0.22
    extra["cluster_first_victim"] = "train_lo"
    extra["cluster_adoptions"] = 2
    extra["cluster_revokes"] = 2
    extra["cluster_escalations"] = 0
    extra["cluster_handback"] = True
    extra["cluster_one_trace"] = True
    # elastic hybrid-parallelism section (docs/elastic_parallelism.md):
    # the DP↔PP trade trio must survive in-line; the transition label
    # and the rung's accum may shrink to the sidecar
    extra["dp_pp_trade_mttr_s"] = 0.327
    extra["reshard_s"] = 0.311
    extra["hybrid_vs_accum_goodput_x"] = 1.7778
    extra["elastic_transition"] = "dp8 -> dp2·pp2"
    extra["elastic_rung_accum"] = 4
    return extra


def test_line_budget_worst_case(tmp_path, monkeypatch):
    """Every section populated: the emitted line must stay ≤ 1,800 bytes with the vital
    keys in-line and the complete extra in the sidecar."""
    bench = _bench()
    extra = _worst_case_extra(bench, tmp_path, monkeypatch)
    result = {
        "metric": bench.METRIC, "value": 114100.0, "unit": "tokens/s",
        "vs_baseline": 1.58, "extra": extra,
    }
    assert len(json.dumps(result)) > bench.LINE_BUDGET_BYTES  # truly worst
    line = bench._shrink_to_budget(result)
    s = json.dumps(line)
    assert len(s) <= bench.LINE_BUDGET_BYTES, len(s)
    # the driver's contract fields are intact
    assert line["metric"] == bench.METRIC and line["value"] == 114100.0
    assert line["vs_baseline"] == 1.58
    # the vital keys survived in-line
    slim = line["extra"]
    assert slim["line_truncated"] is True
    assert slim["mfu"] == extra["mfu"]
    assert slim["serving_host_frac"] == extra["serving_host_frac"]
    # the overlap A/B verdict (PR 2 headline rung) must ride the line
    assert slim["serving_overlap_vs_sync"] == (
        extra["serving_overlap_vs_sync"]
    )
    assert slim["serving_overlap_exact"] is True
    # the host-fault recovery headline rides the line as pointer-style
    # scalars (the full storm dict with its stall list stays
    # sidecar-only)
    assert slim["storm_mttr_s"] == extra["storm_mttr_s"]
    assert slim["storm_goodput"] == extra["storm_goodput"]
    # the MTTR phase breakdown, the detect phase share, and the
    # warm-vs-cold A/B verdict pair moved sidecar-only to seat the
    # paged-KV trio (the first three re-derive from the sidecar's
    # goodput_storm dict — same class as storm_restore_s /
    # storm_first_step_s before them — the A/B pair from recovery_ab);
    # the slice row of the matrix (storm_slice_mttr_s /
    # storm_slice_goodput) and the flash_step_s / headline_config pair
    # moved sidecar-only to seat the cluster trio (slice row from
    # goodput_storm, the pair from the SILICON headline dict)
    for key in (
        "storm_rdzv_s", "storm_compile_s", "storm_detect_s",
        "recovery_mttr_delta_s", "recovery_warm_compile_s",
        "storm_slice_mttr_s", "storm_slice_goodput",
        "flash_step_s", "headline_config",
    ):
        assert key not in slim, key
    assert "recovery_ab" not in slim
    # the detection headline still rides the line
    assert slim["storm_mttd_s"] == extra["storm_mttd_s"]
    # the master-kill SLO pair rides the line; the full drill dict is
    # sidecar-only
    assert slim["master_mttr_s"] == extra["master_mttr_s"]
    assert slim["master_kill_goodput"] == extra["master_kill_goodput"]
    assert "master_kill" not in slim
    # the durable-tier SLO pair rides the line; the supporting ratio
    # (durable_block_vs_flash_x) is sidecar-recoverable
    assert slim["durable_save_block_s"] == extra["durable_save_block_s"]
    assert slim["durable_restore_s"] == extra["durable_restore_s"]
    # the fleet SLO trio rides the line (fleet_2v1_x and the per-rep
    # rate are sidecar-recoverable, like the A/B per-leg scalars)
    for key in (
        "fleet_requests_per_s", "fleet_kill_availability",
        "fleet_rollout_max_unready",
    ):
        assert slim[key] == extra[key], key
    # the paged-KV trio rides the line (the dense leg, the speedup
    # ratio, and block occupancy are sidecar-recoverable)
    for key in (
        "fleet_paged_tokens_per_s", "fleet_paged_p95_s",
        "prefix_hit_rate",
    ):
        assert slim[key] == extra[key], key
    # the chip-pool SLO trio rides the line (supporting pool scalars
    # are sidecar-recoverable)
    for key in (
        "pool_preempt_to_ready_s", "pool_spike_availability",
        "pool_train_goodput",
    ):
        assert slim[key] == extra[key], key
    # the multi-tenant cluster SLO trio rides the line (first victim,
    # counters, and the one-trace flag are sidecar-recoverable)
    for key in (
        "cluster_inversion_avail", "cluster_preempt_cascade_s",
        "cluster_brain_adopt_s",
    ):
        assert slim[key] == extra[key], key
    # the elastic DP↔PP trade trio rides the line (the transition label
    # and the rung accum are sidecar-recoverable)
    for key in (
        "dp_pp_trade_mttr_s", "reshard_s", "hybrid_vs_accum_goodput_x",
    ):
        assert slim[key] == extra[key], key
    assert slim["attr_report"] == extra["attr_report"]
    # the COMPLETE extra is recoverable from the sidecar
    sidecar = tmp_path / slim["extra_sidecar"]
    full = json.load(open(sidecar))
    assert set(extra) == set(full)
    assert full["goodput_storm"] == extra["goodput_storm"]


# ---------------------------------------------------------------------------
# Section filter: the BENCH_SECTIONS contract (no jax).
# ---------------------------------------------------------------------------


def test_section_filter_parsing(monkeypatch):
    bench = _bench()
    monkeypatch.delenv("BENCH_SECTIONS", raising=False)
    want, filtered = bench._section_filter()
    assert not filtered and want("ckpt") and want("anything")
    monkeypatch.setenv("BENCH_SECTIONS", "ckpt, serving")
    want, filtered = bench._section_filter()
    assert filtered
    assert want("ckpt") and want("serving")
    assert not want("decode") and not want("ladder")
    # "headline" names no optional section: everything optional skips
    monkeypatch.setenv("BENCH_SECTIONS", "headline")
    want, filtered = bench._section_filter()
    assert filtered and not any(
        want(s) for s in set(bench.SECTION_OF_ERROR.values())
    )


def test_section_of_error_maps_into_headline_errors():
    bench = _bench()
    # every retryable error key is a headline-section error, and the
    # run-scoped markers stay non-retryable
    assert set(bench.SECTION_OF_ERROR) <= bench.HEADLINE_SECTION_ERRORS
    assert "fatal_error" not in bench.SECTION_OF_ERROR


def test_under_budget_line_passes_through_untouched(tmp_path, monkeypatch):
    bench = _bench()
    monkeypatch.setattr(bench, "_REPO_DIR", str(tmp_path))
    result = {
        "metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
        "extra": {"device": "cpu"},
    }
    assert bench._shrink_to_budget(result) is result
    assert not list(tmp_path.glob("BENCH_extra_*"))  # no sidecar spam


# ---------------------------------------------------------------------------
# Device rules: the peak comes from a table keyed by device_kind, the
# platform must be the chip, and a CPU plumbing run writes nothing under
# a device metric's name.
# ---------------------------------------------------------------------------


def test_unknown_device_kind_is_an_error():
    import pytest

    bench = _bench()
    assert bench.peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v9", "cpu", ""):
        with pytest.raises(KeyError, match="no peak FLOP/s on record"):
            bench.peak_flops(kind)


def _run_bench(env_overrides, tmp_path):
    import subprocess

    env = dict(os.environ, BENCH_SECTIONS="headline")
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300,
    )


def test_bench_refuses_to_measure_without_the_chip(tmp_path):
    """No JAX_PLATFORMS from the caller: bench.py pins ``tpu``, so here,
    where no chip is attached, it raises and prints no record."""
    proc = _run_bench({"JAX_PLATFORMS": None, "TPU_LOG_DIR": "disabled"}, tmp_path)
    assert proc.returncode != 0
    assert "metric" not in proc.stdout


def test_cpu_plumbing_run_names_the_platform_everywhere(tmp_path):
    proc = _run_bench({"JAX_PLATFORMS": "cpu"}, tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["platform"] == "cpu" and record["device_count"] >= 1
    # nothing under a device metric's name
    assert record["metric"] == "cpu_plumbing" != _bench().METRIC
    assert record["value"] is None
    assert record["extra"] and all(
        key.startswith("cpu_") for key in record["extra"]
    )
