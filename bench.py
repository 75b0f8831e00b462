"""Benchmark entry: ONE process, ONE JSON line.

Headline: training throughput of the flagship GPT-2-small on the TPU
chip — tokens/s and MFU against the chip's bf16 peak (``PEAK_BF16_FLOPS``,
keyed by ``device_kind``; an unknown kind is an error) — with the Pallas
flash-attention kernel compared against the XLA dense-attention path.
``vs_baseline`` = flash-path tokens/s over the best dense-path tokens/s.

Also carried in ``extra`` (BASELINE.md metric family): blocking-save
seconds, async persist, memory-restore seconds for the full ~1.5 GB
train state, and the implied goodput of checkpointing every 10 steps.

Rules of this file:

- ``python bench.py`` IS the measurement, in this process. It needs the
  chip: the platform is pinned to ``tpu`` (``pin_accelerator``), so a
  failed TPU initialization raises — there is no CPU fallback and no
  probe loop. No chip, no record.
- A caller that sets ``JAX_PLATFORMS=cpu`` itself gets a *plumbing* run
  at toy shapes: every record names the platform, its metric is not the
  device metric's name, and every key of ``extra`` is prefixed
  ``cpu_`` — a CPU number is never written under a device metric's name.
- A section that fails writes its ``*_error`` key, the line is still
  emitted, and the process exits non-zero.
- The sections, the byte budget of the line and the key ladder are
  ROADMAP D1/S1's to replace.
"""

import json
import os
import shutil
import sys
import tempfile
import time

# bf16 peak FLOP/s per chip, keyed by ``jax.devices()[0].device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s). A device that is
# not in the table is an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; "
            f"add it to PEAK_BF16_FLOPS with its source"
        ) from None


TARGET_SAVE_BLOCK_S = 5.0  # BASELINE.json north star

METRIC = "gpt2s_train_tokens_per_s"

# Section-level error keys that mean a run LOST a headline section (vs
# optional probe rungs that degrade into *_error by design — batch walk
# ends on OOM, int8/f32/spec sub-rungs may fail while the section
# headline stands). Owned here, next to the emitters, so a new section
# adds its key in the same diff. Any of them makes the exit code
# non-zero.
HEADLINE_SECTION_ERRORS = frozenset({
    "fatal_error", "dense_error", "ckpt_error",
    "flash_seq4096_error", "decode_error", "spec_error",
    "serving_error", "serving_per_row_error", "llama_family_error",
    "longseq_train_error", "attr_error", "fleet_error",
    "fleet_paged_error", "pool_error", "cluster_error",
})

# Error key -> the BENCH_SECTIONS name that re-runs ONLY that
# section (the section filter below). fatal_error describes the whole
# run and is not section-retryable.
SECTION_OF_ERROR = {
    "ckpt_error": "ckpt",
    "flash_seq4096_error": "flash_seq4096",
    "decode_error": "decode",
    "spec_error": "spec",
    "serving_error": "serving",
    "serving_per_row_error": "serving",
    "attr_error": "attr",
    "fleet_error": "fleet",
    "fleet_paged_error": "fleet",
    "pool_error": "pool",
    "cluster_error": "cluster",
    "llama_family_error": "llama",
    "longseq_train_error": "longseq",
    "dense_error": "dense",
    # storm/recovery_ab/master_kill are NOT here on purpose: a
    # minutes-long storm retry would blow the capture budget; their
    # errors ride the line.
}


class _SectionSkip(Exception):
    """Control-flow sentinel: a section-filtered worker skips a gated
    block from inside its try without writing the block's error key."""


def _section_filter():
    """Parse BENCH_SECTIONS (comma list) into a ``want(name)``
    predicate. Empty/unset -> every section runs (the normal bench).
    With a filter, the headline flash measurement always runs (every
    section builds on its model/params) and only the named optional
    sections join it — the contract behind per-section re-runs."""
    only = {
        s.strip()
        for s in os.environ.get("BENCH_SECTIONS", "").split(",")
        if s.strip()
    }

    def want(name):
        return not only or name in only

    return want, bool(only)

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))


# Hard cap on the ONE emitted JSON line: the driver's parse window is
# ~2,000 chars and has truncated mid-string 3 rounds out of 5. Under
# pressure the FULL extra goes to a run-unique sidecar and the line
# keeps a priority-ordered subset of scalars + the sidecar pointer.
LINE_BUDGET_BYTES = 1800

# In-line survival priority when the full line overflows: errors and
# provenance first (an unparseable failure is the worst artifact), then
# the headline floats, then the attribution/serving rung, then pointers.
_PRIORITY_KEYS = (
    "device", "platform", "device_kind", "fatal_error",
    # EVERY headline-section error marker survives in-line: a truncated
    # line that dropped one would read as a complete run
    *sorted(HEADLINE_SECTION_ERRORS - {"fatal_error"}),
    "model", "mfu", "serving_host_frac",
    "serving_overlap_vs_sync", "serving_overlap_exact",
    "attr_report",
    # Byte offsets for the pool section's SLO trio (same rationale as
    # PR 7's per-leg demotions): the attr supporting floats + ring
    # pointer live in the attr_report artifact and the sidecar;
    # serving_overlap_hidden_ms is the verdict's detail;
    # restore_overhead_x / goodput_ckpt_every_10_steps also ride the
    # SILICON headline dict the last_silicon pointer names. All
    # sidecar-recoverable — only their in-line seats moved.
    # serving-fleet SLO trio (docs/serving_fleet.md): throughput,
    # availability under a replica kill, rollout readiness floor.
    # Byte offsets for it: the overlap A/B per-leg rates
    # (serving_{sync,overlap}_tokens_per_s) and generate_tokens_per_s
    # moved sidecar-only — their verdicts (serving_overlap_vs_sync +
    # exactness flag, decode_tokens_per_s) still ride the line, same
    # rationale as the recovery_ab per-leg scalars above
    "fleet_requests_per_s", "fleet_kill_availability",
    "fleet_rollout_max_unready",
    # paged-KV serving trio (docs/serving_fleet.md): Zipf-trace
    # gateway throughput, client p95, and the prefix-cache hit rate
    # behind them. Supporting scalars (the dense-baseline leg,
    # fleet_paged_vs_dense_x, affinity/block occupancy) are
    # sidecar-recoverable — the verdict ratio re-derives from
    # fleet_paged_tokens_per_s / fleet_dense_tokens_per_s.
    "fleet_paged_tokens_per_s", "fleet_paged_p95_s",
    "prefix_hit_rate",
    # chip-pool arbitration SLO trio (docs/pool.md): preempt latency,
    # availability through the preemption, training goodput over the
    # disruption window (supporting scalars ride the sidecar)
    "pool_preempt_to_ready_s", "pool_spike_availability",
    "pool_train_goodput",
    # multi-tenant cluster SLO trio (docs/cluster.md): availability of
    # the high-priority fleet through the priority-inversion cascade,
    # the breach→surge-READY cascade window, and the brain-target
    # adoption latency. Supporting scalars (first victim, revoke/
    # adoption counts, the one-trace flag) are sidecar-recoverable —
    # the trio IS the verdict the docs table quotes. Byte offsets for
    # it: flash_step_s and headline_config moved sidecar-only (both
    # ride the SILICON headline dict the last_silicon pointer names —
    # the PR 7/8 demotion class), and the slice row of the recovery
    # matrix (storm_slice_mttr_s / storm_slice_goodput) moved
    # sidecar-only too — both re-derive from the sidecar's full
    # goodput_storm dict, the same class as the storm_rdzv_s /
    # storm_compile_s demotions before them; the host-fault recovery
    # headline (storm_mttr_s + storm_goodput) still rides the line.
    "cluster_inversion_avail", "cluster_preempt_cascade_s",
    "cluster_brain_adopt_s",
    # Byte offsets for the detection-SLO pair below:
    # serving_per_row_tokens_per_s and ckpt_async_stage_block_s moved
    # sidecar-only (both ride the SILICON headline dict the
    # last_silicon pointer names, same recoverability class as
    # restore_overhead_x above). Byte offsets for the elastic trio
    # below: decode_tokens_per_s moved sidecar-only too (it also rides
    # the SILICON headline dict), and flash_vs_dense re-derives from
    # the in-line flash_step_s and the sidecar's dense_step_s.
    # recovery-SLO matrix (per-fault-class, pointer-style — the full
    # storm dict with stall forensics goes to the sidecar)
    "storm_goodput", "storm_mttr_s",
    # Byte offsets for the paged-KV trio above: the MTTR phase
    # breakdown (storm_rdzv_s / storm_compile_s), the detect phase
    # share (storm_detect_s), and the warm-vs-cold A/B verdict pair
    # (recovery_mttr_delta_s / recovery_warm_compile_s) moved
    # sidecar-only — the first three re-derive from the sidecar's full
    # goodput_storm dict (the same recoverability class as the
    # storm_restore_s / storm_first_step_s demotions before them), the
    # A/B pair from its recovery_ab dict. The recovery headline
    # (storm_mttr_s + storm_goodput, per fault class) and the
    # detection headline (storm_mttd_s) still ride the line.
    "storm_mttd_s",
    # master crash tolerance (docs/recovery.md master failover): the
    # coordination-outage MTTR and the productive fraction of the kill
    # window; the full drill dict (epoch, replay_s, restart audit) is
    # sidecar-recoverable
    "master_mttr_s", "master_kill_goodput",
    # durable-tier SLO pair (docs/recovery.md durable section): the
    # train-loop hand-off of a durable-enabled save and the
    # whole-pool-loss restore cost. Byte offsets for the pair:
    # flash_batch and seq_len moved sidecar-only above (both ride the
    # SILICON headline dict the last_silicon pointer names — PR 7/8
    # demotion precedent), and the supporting ratio
    # (durable_block_vs_flash_x) stays sidecar-recoverable too: it
    # re-derives from durable_save_block_s / ckpt_async_stage_block_s.
    "durable_save_block_s", "durable_restore_s",
    # elastic hybrid-parallelism trio (docs/elastic_parallelism.md):
    # the dp→pp trade window, its reshard leg, and the cost-model
    # verdict the trade is chosen by. Supporting detail (the
    # transition label and the rung's accum) is sidecar-recoverable.
    "dp_pp_trade_mttr_s", "reshard_s", "hybrid_vs_accum_goodput_x",
    "extra_sidecar", "line_truncated",
)


def _shrink_to_budget(result):
    """Enforce LINE_BUDGET_BYTES on the emitted line. Over budget: the
    complete extra is written to ``BENCH_extra_<ts>_<pid>.json`` and the
    line is rebuilt from _PRIORITY_KEYS, adding each key only while the
    serialized line stays under budget (later, smaller keys still get
    their chance when a big one was skipped)."""
    if len(json.dumps(result)) <= LINE_BUDGET_BYTES:
        return result
    extra = dict(result.get("extra") or {})
    slim = {"line_truncated": True}
    sidecar = os.path.join(
        _REPO_DIR, f"BENCH_extra_{int(time.time())}_{os.getpid()}.json"
    )
    try:
        with open(sidecar, "w") as f:
            json.dump(extra, f, indent=1)
        slim["extra_sidecar"] = os.path.basename(sidecar)
    except OSError:
        pass
    for key in _PRIORITY_KEYS:
        if key not in extra or key in slim:
            continue
        trial = dict(slim)
        trial[key] = extra[key]
        if len(json.dumps(dict(result, extra=trial))) <= LINE_BUDGET_BYTES:
            slim[key] = extra[key]
    return dict(result, extra=slim)


def _emit(result):
    """Print the one JSON line, inside the byte budget."""
    print(json.dumps(_shrink_to_budget(result)))
    sys.stdout.flush()


# ---------------------------------------------------------------------------
# Measurement — jax from here on.
# ---------------------------------------------------------------------------


def _build(cfg_kwargs, batch, seq, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        cross_entropy_loss,
        token_loss_mean,
    )
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )

    cfg = GPTConfig(max_seq_len=seq, **cfg_kwargs)
    model = GPT(cfg)
    tx = default_optimizer()
    tokens = jnp.zeros((batch, seq), jnp.int32)
    state, shardings = init_train_state(model, tokens, mesh, tx)
    loss = token_loss_mean if cfg.ce_chunk > 0 else cross_entropy_loss
    step_fn = build_train_step(model, tx, loss, mesh, shardings)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    y = jnp.roll(x, -1, axis=1)
    return cfg, state, step_fn, x, y


def _time_steps(state, step_fn, x, y, iters=6):
    import jax
    import numpy as np

    state, loss = step_fn(state, x, y)  # compile + warmup
    # Hard sync via a scalar fetch: every timed iteration syncs on the
    # loss value itself, and the measured dispatch+fetch floor is
    # subtracted so step time reflects the device, not the host's
    # round-trip for one scalar.
    if not np.isfinite(float(loss)):
        raise RuntimeError(f"non-finite warmup loss {float(loss)}")
    floor_s = _dispatch_floor(loss)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state, loss = step_fn(state, x, y)
        loss_val = float(loss)
        times.append(time.perf_counter() - t0)
        if not np.isfinite(loss_val):
            raise RuntimeError(f"non-finite loss {loss_val}")
    return max(float(np.median(times)) - floor_s, 1e-9), state


def _dispatch_floor(val, samples: int = 3):
    """Seconds for one tiny dispatch + scalar fetch — the host
    overhead every synced timing pays; subtracted by both the step and
    kernel benches so device time is measured, not the round-trip. Min
    of several samples: one jittered RTT would over-subtract and
    inflate every derived metric."""
    import jax

    sync = jax.jit(lambda v: (v * 0.0).sum())
    _ = float(sync(val))  # compile
    best = float("inf")
    for _i in range(samples):
        t0 = time.perf_counter()
        _ = float(sync(val))
        best = min(best, time.perf_counter() - t0)
    return best


def _mfu(cfg, n_params, batch, seq, step_s):
    """Model FLOP/s utilization against the attached device's peak. A
    caller-pinned CPU plumbing run has no peak: NaN, never a default."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return float("nan")
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.embed_dim * seq
    return (
        flops_per_token * batch * seq / step_s / peak_flops(device.device_kind)
    )


def _bench_long_context(extra):
    """Flash-attention kernel at 4x the training seq (TPU only).

    Timing methodology: a device→host sync after every kernel call
    measures the round-trip, not the kernel. Chain N kernel calls inside
    ONE jitted scan (single dispatch), sync once through a scalar fetch,
    and subtract the measured dispatch+fetch floor.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.flash_attention import flash_attention

    B, H, T, Dh = 4, 12, 4096, 64
    N = 50
    r2 = np.random.default_rng(1)
    mk = lambda: jnp.asarray(  # noqa: E731
        r2.standard_normal((B, T, H, Dh)), jnp.bfloat16
    )
    q, k, v = mk(), mk(), mk()

    att1 = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    if not np.isfinite(float(att1(q, k, v).sum())):
        raise RuntimeError("non-finite flash output")

    def many(q, k, v):
        def body(o, _):
            return flash_attention(o, k, v, causal=True), None

        o, _ = jax.lax.scan(body, q, None, length=N)
        return o.sum()

    floor_s = _dispatch_floor(q)

    att = jax.jit(many)
    _ = float(att(q, k, v))  # compile
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        _ = float(att(q, k, v))
        ts.append((time.perf_counter() - t0 - floor_s) / N)
    att_s = max(float(np.median(ts)), 1e-6)
    # causal fwd flops: 2 matmuls over the lower triangle
    flops = 2 * 2 * B * H * T * T * Dh / 2
    extra.update(
        {
            "flash_seq4096_ms": round(att_s * 1e3, 2),
            "flash_seq4096_tflops": round(flops / att_s / 1e12, 1),
            "flash_seq4096_dispatch_floor_ms": round(floor_s * 1e3, 1),
        }
    )


def _bench_decode(extra, cfg, params, on_tpu):
    """Autoregressive decode throughput through the generation engine
    (models/generation.py) — the rollout half of an RL job. No
    reference counterpart (it delegates to vLLM); reported as its own
    datapoint. One jitted prefill+scan program, synced once via the
    output fetch, dispatch floor subtracted.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.generation import (
        SamplingConfig,
        build_generate_fn,
    )
    from dlrover_tpu.models.gpt import GPT

    model = GPT(cfg)  # same params; flax modules are cheap dataclasses
    if on_tpu:
        B, P, N = 32, 128, 64
    else:
        B, P, N = 2, 16, 8
    toks = jnp.ones((B, P), jnp.int32)
    mask = jnp.ones((B, P), bool)

    def timed(n_new):
        fn = build_generate_fn(
            model,
            SamplingConfig(max_new_tokens=n_new, temperature=1.0, top_k=40),
            prompt_width=P,
        )
        out = fn(params, toks, mask, jax.random.PRNGKey(0))  # compile
        jax.block_until_ready(out)
        floor_s = _dispatch_floor(out[2][:1, :1])
        ts = []
        for i in range(3):
            t0 = time.perf_counter()
            out = fn(params, toks, mask, jax.random.PRNGKey(1 + i))
            _ = float(out[2].sum())  # hard sync on the logprobs
            ts.append(time.perf_counter() - t0 - floor_s)
        return max(float(np.median(ts)), 1e-9)

    # Two-point measurement: one whole-call number (what a rollout
    # role pays) plus t(N) - t(1) over N-1 steps, which cancels the
    # prefill so the per-step figure is pure incremental decode.
    t_full = timed(N)
    t_one = timed(1)
    step_s = max((t_full - t_one) / max(N - 1, 1), 1e-9)
    extra.update(
        {
            "generate_tokens_per_s": round(B * N / t_full, 1),
            "decode_batch": B,
            "decode_prompt_len": P,
            "decode_new_tokens": N,
            "decode_ms_per_step": round(step_s * 1e3, 2),
            "decode_tokens_per_s": round(B / step_s, 1),
            # t(1) runs the prefill + ONE sampling op and zero decode
            # steps (the N-1 scan is empty), so it IS the prefill time
            "prefill_ms": round(t_one * 1e3, 1),
        }
    )

    # int8 KV cache rung: decode is HBM-bound on the cache read, so the
    # half-width cache should shorten the per-step time (same params —
    # only the cache storage changes; fidelity under test in
    # tests/test_generation.py::TestInt8KvCache).
    try:
        import dataclasses

        model = GPT(dataclasses.replace(cfg, kv_cache_int8=True))
        t8_full, t8_one = timed(N), timed(1)
        step8_s = max((t8_full - t8_one) / max(N - 1, 1), 1e-9)
        extra["decode_int8_ms_per_step"] = round(step8_s * 1e3, 2)
        extra["decode_int8_tokens_per_s"] = round(B / step8_s, 1)
        extra["decode_int8_vs_bf16"] = round(step_s / step8_s, 3)
    except Exception as e:  # noqa: BLE001 — keep the bf16 numbers
        extra["decode_int8_error"] = repr(e)[:160]


def _bench_llama(extra, mesh, on_tpu):
    """Second model family (Llama GQA+RoPE+SwiGLU) and its MoE variant
    through the same train-step path — the PARITY silicon claims
    (130k / 136k tokens/s) must be reproducible by THIS file, not an
    ad-hoc script (VERDICT r4 #2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.gpt import cross_entropy_loss
    from dlrover_tpu.models.llama import Llama, LlamaConfig
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )

    if on_tpu:
        base = dict(
            vocab_size=32000, max_seq_len=1024, num_layers=12,
            num_heads=12, num_kv_heads=4, head_dim=64, embed_dim=768,
            mlp_dim=2048, attention_impl="flash", use_remat=True,
        )
        bs, seq = 16, 1024
    else:
        base = dict(
            vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=8, embed_dim=32, mlp_dim=96,
            use_remat=False,
        )
        bs, seq = 2, 128

    variants = (("llama", {}), ("moe", dict(num_experts=4, moe_every=2)))
    for label, over in variants:
        state = step_fn = None  # freed on BOTH paths (OOM mid-variant
        # must not hold the failed attempt's HBM into the next variant)
        try:
            cfg = LlamaConfig(**{**base, **over})
            model = Llama(cfg)
            tx = default_optimizer()
            tokens = jnp.zeros((bs, seq), jnp.int32)
            state, shardings = init_train_state(model, tokens, mesh, tx)
            step_fn = build_train_step(
                model, tx, cross_entropy_loss, mesh, shardings
            )
            r = np.random.default_rng(2)
            x = jnp.asarray(
                r.integers(0, cfg.vocab_size, (bs, seq)), jnp.int32
            )
            y = jnp.roll(x, -1, axis=1)
            n_params = sum(l.size for l in jax.tree.leaves(state.params))
            # rebind state so the finally actually drops the ~GB-scale
            # final train state (a throwaway `_` would pin it in HBM
            # into the next variant)
            step_s, state = _time_steps(state, step_fn, x, y)
            extra[f"{label}_params_m"] = round(n_params / 1e6, 1)
            extra[f"{label}_step_s"] = round(step_s, 4)
            extra[f"{label}_batch"] = bs
            extra[f"{label}_tokens_per_s"] = round(bs * seq / step_s, 1)
            if label == "llama":
                # MFU only for the dense model: the 6N analytic count
                # would charge the MoE's inactive experts as real flops.
                extra["llama_mfu"] = round(
                    _mfu(cfg, n_params, bs, seq, step_s), 4
                )
        except Exception as e:  # noqa: BLE001 — per-variant guard
            extra[f"{label}_error"] = repr(e)[:160]
        finally:
            state = step_fn = None  # noqa: F841 — drop HBM references


def _bench_longseq_train(extra, mesh, on_tpu):
    """End-to-end long-context TRAINING (not just the kernel): GPT-2
    small at 4x the headline seq, flash + remat — the PARITY seq-4096
    MFU 0.461 claim, bench-reproducible."""
    import jax

    if on_tpu:
        kwargs, batch, seq = dict(attention_impl="flash"), 8, 4096
    else:
        kwargs, batch, seq = dict(
            attention_impl="flash", vocab_size=256, num_layers=2,
            num_heads=4, head_dim=8, embed_dim=32, use_remat=False,
        ), 2, 256
    cfg, state, step_fn, x, y = _build(kwargs, batch, seq, mesh)
    n_params = sum(l.size for l in jax.tree.leaves(state.params))
    step_s, _ = _time_steps(state, step_fn, x, y)
    extra.update(
        {
            "longseq_train_seq": seq,
            "longseq_train_batch": batch,
            "longseq_train_step_s": round(step_s, 4),
            "longseq_train_tokens_per_s": round(batch * seq / step_s, 1),
            "longseq_train_mfu": round(
                _mfu(cfg, n_params, batch, seq, step_s), 4
            ),
        }
    )
    del state, step_fn, x, y


def _bench_spec_decode(extra, cfg, params, on_tpu):
    """Speculative decoding vs plain decode at the SAME sampling config
    (greedy — the token-exactness regime): acceptance rate + tokens/s
    (VERDICT r4 #2). Two drafts: a 2-layer random-init draft gives the
    honest acceptance floor on untrained weights; the target drafting
    for itself (acceptance ≡ 1) gives the machinery's speedup ceiling.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.generation import (
        SamplingConfig,
        build_generate_fn,
    )
    from dlrover_tpu.models.gpt import GPT
    from dlrover_tpu.models.speculative import (
        SpecConfig,
        build_speculative_generate_fn,
    )

    model = GPT(cfg)
    B, P, N = (16, 64, 64) if on_tpu else (2, 16, 8)
    k = 4
    sampling = SamplingConfig(max_new_tokens=N, temperature=0.0)
    toks = jnp.ones((B, P), jnp.int32)
    mask = jnp.ones((B, P), bool)

    def timed(fn, *fn_args):
        out = fn(*fn_args, jax.random.PRNGKey(0))  # compile
        jax.block_until_ready(out[:3])
        floor_s = _dispatch_floor(out[2][:1, :1])
        ts = []
        last = out
        for i in range(3):
            t0 = time.perf_counter()
            last = fn(*fn_args, jax.random.PRNGKey(1 + i))
            _ = float(last[2].sum())  # hard sync on the logprobs
            ts.append(time.perf_counter() - t0 - floor_s)
        return max(float(np.median(ts)), 1e-9), last

    plain_fn = build_generate_fn(model, sampling, prompt_width=P)
    t_plain, _ = timed(plain_fn, params, toks, mask)
    plain_tps = B * N / t_plain

    draft = GPT(dataclasses.replace(cfg, num_layers=2))
    d_params = draft.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    results = {"spec": (draft, d_params), "spec_self": (model, params)}
    extra["spec_plain_greedy_tokens_per_s"] = round(plain_tps, 1)
    extra["spec_num_draft"] = k
    for label, (d_model, dp) in results.items():
        try:
            fn = build_speculative_generate_fn(
                model, d_model, sampling, prompt_width=P,
                spec=SpecConfig(num_draft=k),
            )
            t_spec, out = timed(fn, params, dp, toks, mask)
            stats = out[3]
            drafted = float(stats["drafted"])
            acc = float(stats["accepted"]) / max(drafted, 1.0)
            extra[f"{label}_tokens_per_s"] = round(B * N / t_spec, 1)
            extra[f"{label}_acceptance"] = round(acc, 3)
            extra[f"{label}_vs_plain"] = round(t_plain / t_spec, 3)
        except Exception as e:  # noqa: BLE001 — per-variant guard
            extra[f"{label}_error"] = repr(e)[:160]

    if on_tpu:
        # Acceptance sanity in f32: greedy self-draft acceptance is 1.0
        # by construction in exact arithmetic, but the near-random bench
        # weights have razor-thin top-2 logit gaps, and the draft and
        # verify passes are DIFFERENT programs (1-token decode vs k+1
        # batched verify) whose bf16 reduction orders break ties
        # differently — the bf16 self-acceptance above is tie-break
        # noise, not a machinery bug (token-exactness is proven in
        # tests/test_speculative.py). The f32 rung shows the machinery's
        # true acceptance on this hardware.
        try:
            cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
            model32 = GPT(cfg32)
            fn32 = build_speculative_generate_fn(
                model32, model32, sampling, prompt_width=P,
                spec=SpecConfig(num_draft=k),
            )
            out32 = fn32(params, params, toks, mask, jax.random.PRNGKey(0))
            jax.block_until_ready(out32[:3])
            stats32 = out32[3]
            extra["spec_self_acceptance_f32"] = round(
                float(stats32["accepted"])
                / max(float(stats32["drafted"]), 1.0),
                3,
            )
        except Exception as e:  # noqa: BLE001
            extra["spec_self_f32_error"] = repr(e)[:160]


def _timed_stream(model, params, sampling, slots, prompt_width, prompts,
                  layout="frontier", decode_chunk=8, overlap=True):
    """One warmed, timed serving stream; returns (tokens/s, engine).
    The warm/reset convention lives HERE only (both the serving rates
    and the attribution rung's fallback depend on it): warm with the
    FULL stream — greedy + same prompts makes the timed rerun hit
    identical compaction widths, so every jit (prefill, chunk, each
    compaction bucket) is hot when the clock starts — then drop the
    warm run's phase stamps so the engine's host/device split
    describes the same steady-state stream as the rate (compiles land
    in dispatch/prefill and would dominate host_frac)."""
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        model, params, sampling, batch_size=slots,
        prompt_width=prompt_width, decode_chunk=decode_chunk,
        cache_layout=layout, overlap=overlap,
    )
    eng.run(prompts)
    eng.phases.reset()
    t0 = time.perf_counter()
    out = eng.run(prompts)
    dt = time.perf_counter() - t0
    return sum(len(c.tokens) for c in out) / dt, eng


def _bench_serving_overlap_ab(extra, model, params, on_tpu):
    """Overlapped vs synchronous scheduler A/B (the PR 2 headline
    rung): SAME slot count, SAME greedy stream, per-row layout — the
    only variable is the scheduler round. Reports both rates, the
    ratio, whether the emitted streams were bit-identical, and the
    overlapped engine's hidden-host time (``overlap_hidden`` phase).

    Protocol: interleaved best-of-N — each trial times both engines
    back-to-back so machine-state drift hits both sides, and best-of
    converges each side to its noise-free rate (host-timing noise
    only ever slows a run). The CPU config is deliberately
    admission-heavy (short caps, small chunks): that is the regime the
    silicon attribution showed the host dominating, scaled to a
    deterministic smoke box."""
    import time as _time

    import numpy as np

    from dlrover_tpu.models.generation import SamplingConfig

    if on_tpu:
        B, Pw, N, d, n_req, trials = 16, 64, 32, 8, 48, 3
    else:
        B, Pw, N, d, n_req, trials = 8, 16, 8, 2, 48, 8
    sampling = SamplingConfig(max_new_tokens=N, temperature=0.0)
    r = np.random.default_rng(23)
    stream = [
        [int(x) for x in r.integers(1, model.config.vocab_size,
                                    r.integers(4, Pw))]
        for _ in range(n_req)
    ]
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    engines, outs = {}, {}
    for overlap in (False, True):
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=B, prompt_width=Pw,
            decode_chunk=d, cache_layout="per_row", overlap=overlap,
        )
        eng.run(stream)  # compile warm
        outs[overlap] = eng.run(stream)
        engines[overlap] = eng
    exact = all(
        a.tokens == b.tokens and a.uid == b.uid
        for a, b in zip(outs[False], outs[True])
    )
    engines[True].phases.reset()
    best = {False: 0.0, True: 0.0}
    for _ in range(trials):
        for overlap in (False, True):
            t0 = _time.perf_counter()
            out = engines[overlap].run(stream)
            dt = _time.perf_counter() - t0
            best[overlap] = max(
                best[overlap], sum(len(c.tokens) for c in out) / dt
            )
    split = engines[True].phases.split()
    extra.update(
        {
            "serving_sync_tokens_per_s": round(best[False], 1),
            "serving_overlap_tokens_per_s": round(best[True], 1),
            "serving_overlap_vs_sync": round(
                best[True] / max(best[False], 1e-9), 3
            ),
            "serving_overlap_exact": bool(exact),
            # per-STREAM hidden host time: the accumulator spans all
            # trials, so normalize — the number must compare across
            # rounds as one stream's hiding win
            "serving_overlap_hidden_ms": round(
                split.overlap_s * 1e3 / max(trials, 1), 1
            ),
            "serving_overlap_slots": B,
        }
    )


def _bench_serving(extra, cfg, params, on_tpu):
    """Continuous batching (models/serving.py): mixed-length stream
    tokens/s vs the same engine on a homogeneous batch, plus the
    weight hot-swap latency mid-decode (VERDICT r4 #5)."""
    import jax
    import numpy as np

    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT

    model = GPT(cfg)
    if on_tpu:
        B, Pw, N, n_req = 16, 64, 32, 48
    else:
        B, Pw, N, n_req = 2, 16, 8, 6
    sampling = SamplingConfig(max_new_tokens=N, temperature=0.0)
    r = np.random.default_rng(9)

    def stream_rate(prompts, layout="frontier", use_model=None, slots=None):
        return _timed_stream(
            use_model or model, params, sampling, slots or B, Pw,
            prompts, layout=layout,
        )

    mixed = [
        [int(x) for x in r.integers(1, cfg.vocab_size, r.integers(4, Pw))]
        for _ in range(n_req)
    ]
    homog = [[7] * (Pw // 2) for _ in range(n_req)]
    rate_h, _ = stream_rate(homog)
    rate_m, eng = stream_rate(mixed)

    # per-row cache layout: no compaction re-prefills on the same
    # mixed stream — the layouts compete for the serving recommendation
    serving_split = None
    try:
        rate_pr, eng_pr = stream_rate(mixed, layout="per_row")
        extra["serving_per_row_tokens_per_s"] = round(rate_pr, 1)
        extra["serving_per_row_vs_frontier"] = round(rate_pr / rate_m, 3)
        # hand the steady-state phase split to the attribution rung —
        # it describes the SAME timed stream as the per-row rate, and
        # reusing it saves the rung its own engine + recompiles on the
        # budgeted chip window
        serving_split = eng_pr.phases.split()
    except Exception as e:  # noqa: BLE001 — keep the frontier numbers
        extra["serving_per_row_error"] = repr(e)[:160]

    # overlapped-vs-synchronous scheduler A/B (PR 2 tentpole): equal
    # slot count, bit-identical greedy streams, per-row layout — the
    # measured win of the double-buffered round + device-side stop
    try:
        _bench_serving_overlap_ab(extra, model, params, on_tpu)
    except Exception as e:  # noqa: BLE001 — keep the serving rates
        extra["serving_overlap_ab_error"] = repr(e)[:160]

    # decode_chunk auto-tuner rung: serve the mixed stream with
    # auto_chunk and report where the tuner settled + how often it
    # moved (the serving_host_frac-driven feedback loop, live)
    try:
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        eng_at = ContinuousBatchingEngine(
            model, params, sampling, batch_size=B, prompt_width=Pw,
            decode_chunk=4, cache_layout="per_row", auto_chunk=True,
        )
        eng_at.run(mixed)  # warm + lets the tuner observe windows
        eng_at.run(mixed)
        extra["serving_auto_chunk_final"] = eng_at.d
        extra["serving_auto_chunk_retunes"] = eng_at.stats()[
            "auto_chunk_retunes"
        ]
    except Exception as e:  # noqa: BLE001
        extra["serving_auto_chunk_error"] = repr(e)[:160]

    # speculative serving rung: the in-scheduler draft+verify engine on
    # the same mixed stream (self-draft — near-random bench weights
    # give tie-break-limited acceptance in bf16, reported honestly
    # next to the rate; trained weights accept near 1.0, see
    # tests/test_serving.py::TestSpeculativeServing)
    try:
        from dlrover_tpu.models.serving import SpeculativeBatchingEngine

        eng_sp = SpeculativeBatchingEngine(
            model, params, sampling, batch_size=B, prompt_width=Pw,
            num_draft=4,
        )
        eng_sp.run(mixed)  # warm
        t0 = time.perf_counter()
        out_sp = eng_sp.run(mixed)
        dt_sp = time.perf_counter() - t0
        rate_sp = sum(len(c.tokens) for c in out_sp) / dt_sp
        extra["serving_spec_tokens_per_s"] = round(rate_sp, 1)
        extra["serving_spec_acceptance"] = eng_sp.stats()[
            "spec_acceptance"
        ]
        if "serving_per_row_tokens_per_s" in extra:
            extra["serving_spec_vs_per_row"] = round(
                rate_sp / extra["serving_per_row_tokens_per_s"], 3
            )
    except Exception as e:  # noqa: BLE001
        extra["serving_spec_error"] = repr(e)[:160]

    # int8 capacity rung: the int8 cache's headline value is CAPACITY —
    # double the decode slots at the same cache HBM. Serve the same
    # stream through 2x slots on the int8 cache (per-row layout) and
    # report the throughput next to the bf16 engine's.
    try:
        import dataclasses

        model8 = GPT(dataclasses.replace(cfg, kv_cache_int8=True))
        rate8, _ = stream_rate(
            mixed, layout="per_row", use_model=model8, slots=2 * B
        )
        extra["serving_int8_2x_slots_tokens_per_s"] = round(rate8, 1)
        if "serving_per_row_tokens_per_s" in extra:
            extra["serving_int8_2x_vs_per_row"] = round(
                rate8 / extra["serving_per_row_tokens_per_s"], 3
            )
    except Exception as e:  # noqa: BLE001
        extra["serving_int8_error"] = repr(e)[:160]

    extra.update(
        {
            "serving_stream_tokens_per_s": round(rate_m, 1),
            "serving_homogeneous_tokens_per_s": round(rate_h, 1),
            "serving_mixed_vs_homogeneous": round(rate_m / rate_h, 3),
            "serving_batch_slots": B,
            "serving_requests": n_req,
        }
    )
    # A REAL WeightBus-style hot-swap: distinct weights arriving as
    # host arrays (what the bus delivers), adopted mid-decode — the
    # latency includes the full H2D transfer of every leaf. Guarded
    # separately: a failed H2D must not forfeit the rates above or the
    # serving_split handoff to the attribution rung (which would then
    # rebuild an engine and recompile).
    try:
        host_params = jax.tree_util.tree_map(
            lambda x: np.asarray(x) * 1.0001, jax.device_get(params)
        )
        for p in mixed[:B]:
            eng.submit(p)
        rng = jax.random.PRNGKey(1)
        for i in range(3):
            rng, sub = jax.random.split(rng)
            eng.step(sub)  # decode in flight when the push lands
        swap_s = eng.set_params(host_params)
        # Adoption-only swap (already device-resident pytree):
        # separates the engine's own cost from the H2D transfer the
        # host-array swap above includes.
        adopt_s = eng.set_params(eng.params)
        extra["serving_weight_swap_s"] = round(swap_s, 4)
        extra["serving_weight_adopt_s"] = round(adopt_s, 4)
    except Exception as e:  # noqa: BLE001 — rates + split already stand
        extra["serving_swap_error"] = repr(e)[:160]
    return serving_split


def _bench_fleet(extra, cfg, params, on_tpu):
    """Elastic serving fleet rung (dlrover_tpu/fleet/): gateway
    requests/s at 2 replicas vs 1, availability through a mid-load
    replica kill, and max unready replicas through a full staged
    weight rollout. In-process replicas over real HTTP — the gateway,
    supervisor, and rollout paths are the production code; only the
    process boundary is folded (so on a single chip the 2v1 ratio
    reads host-parallelism + batching headroom, not chip count)."""
    import threading
    import urllib.request

    import numpy as np

    from dlrover_tpu.fleet import (
        FleetConfig,
        Gateway,
        InProcessReplica,
        ReplicaSupervisor,
        staged_rollout,
    )
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT

    model = GPT(cfg)
    if on_tpu:
        B, Pw, N, n_req = 8, 64, 32, 32
    else:
        B, Pw, N, n_req = 2, 16, 8, 12
    sampling = SamplingConfig(max_new_tokens=N, temperature=0.0)
    r = np.random.default_rng(11)
    prompts = [
        [int(x) for x in r.integers(1, cfg.vocab_size, r.integers(4, Pw))]
        for _ in range(n_req)
    ]

    def engine_factory():
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        return ContinuousBatchingEngine(
            model, params, sampling, batch_size=B, prompt_width=Pw,
            decode_chunk=4, cache_layout="per_row",
        )

    def make_fleet(n):
        # lenient poll thresholds: jit tracing holds the GIL for
        # seconds, and a false-positive death would relaunch a replica
        # mid-measurement; induced kills are still detected instantly
        # through proc.alive()
        fc = FleetConfig(
            replicas=n, max_replicas=max(n, 2),
            health_interval_s=0.2, health_fails=100,
            health_timeout_s=30.0, relaunch_budget=3,
            start_timeout_s=600.0, queue_limit=256,
        )
        sup = ReplicaSupervisor(
            lambda rid, port: InProcessReplica(
                rid, port, engine_factory=engine_factory,
                reload_fn=lambda: (1, params),
            ),
            fc,
        ).start()
        gw = Gateway(sup, fc)
        if not sup.wait_ready(n, timeout=600.0):
            sup.stop()
            raise RuntimeError(f"fleet never reached {n} READY")
        return sup, gw

    def pump(gw, reqs, on_index=None, pace_s=0.0):
        """Threaded client pump through the gateway; returns
        (ok, failed, wall_s). ``on_index`` maps a request index to a
        callable fired right after that request launches (the kill
        hook); ``pace_s`` spaces the launches so a mid-pump event
        lands among in-flight requests instead of after them."""
        results = {"ok": 0, "failed": 0}
        mu = threading.Lock()

        def hit(p):
            try:
                out = gw.complete({"prompt": list(p)})
                assert out["tokens"]
                with mu:
                    results["ok"] += 1
            except Exception:  # noqa: BLE001 — counted
                with mu:
                    results["failed"] += 1

        t0 = time.perf_counter()
        threads = []
        for i, p in enumerate(reqs):
            t = threading.Thread(target=hit, args=(p,))
            t.start()
            threads.append(t)
            if on_index and i in on_index:
                on_index[i]()
            if pace_s:
                time.sleep(pace_s)
        for t in threads:
            t.join(timeout=600)
        return results["ok"], results["failed"], time.perf_counter() - t0

    def warm_fleet(sup, gw):
        """Warm EVERY replica's engine with the full prompt set (drain
        the others so routing can't skip one) — otherwise the timed
        window pays whichever compiles the warm pump's routing
        happened to miss."""
        for h in sup.replicas():
            for other in sup.replicas():
                if other.rid != h.rid:
                    sup.drain(other.rid)
            pump(gw, prompts)
            for other in sup.replicas():
                if other.rid != h.rid:
                    sup.readmit(other.rid)

    # -- throughput: 1 replica vs 2 (same total request stream) -------
    sup1, gw1 = make_fleet(1)
    try:
        warm_fleet(sup1, gw1)
        ok, failed, wall = pump(gw1, prompts)
        rate1 = ok / wall
    finally:
        sup1.stop()
    sup2, gw2 = make_fleet(2)
    try:
        warm_fleet(sup2, gw2)
        ok, failed, wall = pump(gw2, prompts)
        rate2 = ok / wall
        extra["fleet_requests_per_s"] = round(rate2, 2)
        extra["fleet_1rep_requests_per_s"] = round(rate1, 2)
        extra["fleet_2v1_x"] = round(rate2 / max(rate1, 1e-9), 3)

        # -- availability through a replica kill ----------------------
        kill_reqs = prompts * 2
        ok, failed, _ = pump(
            gw2, kill_reqs,
            on_index={len(kill_reqs) // 3: lambda: sup2.kill_replica(0)},
            pace_s=0.02,
        )
        extra["fleet_kill_availability"] = round(
            ok / max(ok + failed, 1), 4
        )
        extra["fleet_kill_redispatches"] = gw2.redispatches
        sup2.wait_ready(2, timeout=600.0)

        # -- staged rollout under light load --------------------------
        stop_load = threading.Event()
        roll_results = {"ok": 0, "failed": 0}

        roll_mu = threading.Lock()

        def background_load():
            i = 0
            while not stop_load.is_set():
                try:
                    gw2.complete({"prompt": list(prompts[i % n_req])})
                    with roll_mu:
                        roll_results["ok"] += 1
                except Exception:  # noqa: BLE001 — counted
                    with roll_mu:
                        roll_results["failed"] += 1
                i += 1
        loader = threading.Thread(target=background_load)
        loader.start()
        try:
            report = staged_rollout(sup2, gw2)
        finally:
            stop_load.set()
            loader.join(timeout=600)
        extra["fleet_rollout_max_unready"] = report["max_unready"]
        extra["fleet_rollout_aborted"] = report["aborted"]
        extra["fleet_rollout_load_failed"] = roll_results["failed"]
        # fleet status round-trip over real HTTP (the gateway's own
        # endpoint, not the in-process object)
        port = gw2.start_http(0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/status",
                timeout=gw2.cfg.health_timeout_s,
            ) as resp:
                status = json.loads(resp.read())
            extra["fleet_ready"] = status["ready"]
        finally:
            gw2.stop_http()
    finally:
        sup2.stop()


def _bench_paged(extra, cfg, params, on_tpu):
    """Paged-KV serving rung (docs/serving_fleet.md): a multi-tenant
    Zipf-prefix trace through a PAGED 2-replica fleet (block-pool KV,
    copy-on-write prefix sharing, prefix-affinity routing) against the
    dense per_row baseline at equal cache HBM (the default paged pool
    is exactly the dense footprint plus one reserved trash block). The
    dense leg carries each tenant's system prefix INLINE in every
    prompt — what serving without a prefix cache pays — while the
    paged leg registers the prefixes once and lets COW sharing +
    prefix hits skip the repeated prefill. Emits
    ``fleet_paged_tokens_per_s`` (generated tokens/s through the
    gateway), ``fleet_paged_p95_s`` (client-observed request p95), and
    ``prefix_hit_rate`` (engine prefix hits / requests served)."""
    import threading
    import urllib.request  # noqa: F401 — parity with _bench_fleet imports

    import numpy as np

    from dlrover_tpu.fleet import (
        FleetConfig,
        Gateway,
        InProcessReplica,
        ReplicaSupervisor,
    )
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT

    model = GPT(cfg)
    if on_tpu:
        B, Pw, N, n_req, n_tenant, bs = 8, 64, 32, 48, 6, 16
    else:
        B, Pw, N, n_req, n_tenant, bs = 2, 32, 8, 12, 3, 8
    sampling = SamplingConfig(max_new_tokens=N, temperature=0.0)
    r = np.random.default_rng(13)
    # tenant system prefixes: half the prompt window, so the dense
    # leg's inline copies dominate its prefill the way real system
    # prompts do
    plen = Pw // 2
    prefixes = [
        [int(x) for x in r.integers(1, cfg.vocab_size, plen)]
        for _ in range(n_tenant)
    ]
    # Zipf tenant draw (clipped to the tenant count): a couple of hot
    # tenants dominate, the tail stays cold — the distribution that
    # makes prefix warmth worth routing on
    tenants = np.minimum(r.zipf(1.5, n_req), n_tenant) - 1
    suffixes = [
        [int(x) for x in r.integers(1, cfg.vocab_size, r.integers(2, 8))]
        for _ in range(n_req)
    ]

    def make_fleet(layout):
        def engine_factory():
            from dlrover_tpu.models.serving import (
                ContinuousBatchingEngine,
            )

            return ContinuousBatchingEngine(
                model, params, sampling, batch_size=B, prompt_width=Pw,
                decode_chunk=4, cache_layout=layout,
                kv_block_size=bs,
            )

        fc = FleetConfig(
            replicas=2, min_replicas=2, max_replicas=2,
            health_interval_s=0.2, health_fails=100,
            health_timeout_s=30.0, relaunch_budget=3,
            start_timeout_s=600.0, queue_limit=256,
        )
        sup = ReplicaSupervisor(
            lambda rid, port: InProcessReplica(
                rid, port, engine_factory=engine_factory,
            ),
            fc,
        ).start()
        gw = Gateway(sup, fc)
        if not sup.wait_ready(2, timeout=600.0):
            sup.stop()
            raise RuntimeError("paged fleet never reached 2 READY")
        return sup, gw

    def pump(gw, bodies):
        """Threaded trace replay; returns (tokens, latencies, wall_s).
        ``tokens`` counts GENERATED tokens only (the completion body's
        token list), the throughput both layouts are judged on."""
        out = {"tokens": 0, "failed": 0}
        lats = []
        mu = threading.Lock()

        def hit(body):
            t0 = time.perf_counter()
            try:
                res = gw.complete(dict(body))
                dt = time.perf_counter() - t0
                with mu:
                    out["tokens"] += len(res["tokens"])
                    lats.append(dt)
            except Exception:  # noqa: BLE001 — counted
                with mu:
                    out["failed"] += 1

        t0 = time.perf_counter()
        threads = []
        for body in bodies:
            t = threading.Thread(target=hit, args=(body,))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=600)
        if out["failed"]:
            raise RuntimeError(f"{out['failed']} trace requests failed")
        return out["tokens"], lats, time.perf_counter() - t0

    # -- dense baseline: inline prefixes, per_row layout ----------------
    sup_d, gw_d = make_fleet("per_row")
    try:
        dense_trace = [
            {"prompt": (prefixes[t] + suffixes[i])[-Pw:]}
            for i, t in enumerate(tenants)
        ]
        pump(gw_d, dense_trace)  # warm every compile bucket
        toks, lats, wall = pump(gw_d, dense_trace)
        dense_rate = toks / wall
        dense_p95 = float(np.percentile(lats, 95))
    finally:
        sup_d.stop()

    # -- paged leg: registered prefixes + affinity routing --------------
    sup_p, gw_p = make_fleet("paged")
    try:
        pids = [gw_p.register_prefix(p) for p in prefixes]
        paged_trace = [
            {"prompt": suffixes[i], "prefix_id": pids[t]}
            for i, t in enumerate(tenants)
        ]
        pump(gw_p, paged_trace)  # warm compiles + prefix states
        time.sleep(0.5)  # a health poll publishes resident_prefixes
        toks, lats, wall = pump(gw_p, paged_trace)
        paged_rate = toks / wall
        paged_p95 = float(np.percentile(lats, 95))
        time.sleep(0.5)  # let the poll catch the engines' counters
        st = gw_p.status()
        hits = int(st["kv"]["prefix_hits"] or 0)
        extra["fleet_paged_tokens_per_s"] = round(paged_rate, 1)
        extra["fleet_paged_p95_s"] = round(paged_p95, 4)
        extra["fleet_dense_tokens_per_s"] = round(dense_rate, 1)
        extra["fleet_dense_p95_s"] = round(dense_p95, 4)
        extra["fleet_paged_vs_dense_x"] = round(
            paged_rate / max(dense_rate, 1e-9), 3
        )
        # hits accumulate over warm+timed pumps; served counts both
        extra["prefix_hit_rate"] = round(
            hits / max(st["gateway"]["served"], 1), 3
        )
        extra["fleet_affinity_hits"] = st["gateway"]["affinity_hits"]
        extra["fleet_blocks_free"] = st["kv"]["blocks_free"]
        extra["fleet_blocks_total"] = st["kv"]["blocks_total"]
    finally:
        sup_p.stop()


def _bench_pool(extra):
    """Chip-pool arbitration rung (dlrover_tpu/pool/): the full
    traffic-spike drill — serving SLO breach → flash-checkpointed
    training shrink → replica grant to READY → hysteresis handback —
    measured end to end with real engines (the drill's own tiny GPT:
    the pool's verdicts are latencies and availability, not model
    throughput, so the headline model is not re-entered and the rung
    is deliberately device-shape-agnostic). Emits the SLO trio
    (docs/pool.md): ``pool_preempt_to_ready_s``,
    ``pool_spike_availability``, ``pool_train_goodput``."""
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.pool.drill import run_traffic_spike_drill

    try:
        result = run_traffic_spike_drill(
            real_engines=True, timeout_s=300.0
        )
    finally:
        AsyncCheckpointSaver.shutdown()
    if not result.get("ok"):
        raise RuntimeError(
            f"pool drill failed: {result.get('error', result)}"
        )
    extra["pool_preempt_to_ready_s"] = result["preempt_to_ready_s"]
    extra["pool_spike_availability"] = result["availability"]
    extra["pool_train_goodput"] = result["train_goodput"]
    extra["pool_handback"] = result["handback"]
    extra["pool_requests_ok"] = result["requests_ok"]
    extra["pool_revokes"] = result["revokes"]
    extra["pool_escalations"] = result["escalations"]
    extra["pool_recovered_vs_baseline"] = result.get(
        "recovered_vs_baseline"
    )
    extra["pool_window_s"] = result["window_s"]


def _bench_cluster(extra):
    """Multi-tenant cluster scheduler rung (dlrover_tpu/cluster/): the
    4-tenant priority-inversion drill — a traffic spike on the
    highest-priority serving fleet cascades a preemption through the
    priority order (the LOWEST-priority trainer pays first), then the
    brain loop's measured scaling curves re-split the freed budget and
    the grant path stamps adoption latency. Like the pool rung, the
    verdicts are latencies and availability, not model throughput, so
    the section is device-shape-agnostic. Emits the SLO trio
    (docs/cluster.md): ``cluster_inversion_avail``,
    ``cluster_preempt_cascade_s``, ``cluster_brain_adopt_s``."""
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.cluster.drill import run_priority_inversion_drill

    try:
        result = run_priority_inversion_drill(timeout_s=300.0)
    finally:
        AsyncCheckpointSaver.shutdown()
    if not result.get("ok"):
        raise RuntimeError(
            f"cluster drill failed: {result.get('error', result)}"
        )
    extra["cluster_inversion_avail"] = result["availability"]
    extra["cluster_preempt_cascade_s"] = result["preempt_cascade_s"]
    extra["cluster_brain_adopt_s"] = result["brain_adopt_s"]
    extra["cluster_first_victim"] = result["first_victim"]
    extra["cluster_adoptions"] = result["adoptions"]
    extra["cluster_revokes"] = result["revokes"]
    extra["cluster_escalations"] = result["escalations"]
    extra["cluster_handback"] = result["handback"]
    extra["cluster_one_trace"] = result["cascade_one_trace"]


def _bench_elastic(extra):
    """Elastic hybrid-parallelism rung (docs/elastic_parallelism.md):
    the DP→PP trade drill on the live device set. Stage a flash image
    under the full-world mesh, replan half the world under an HBM cap
    sized so the accum-only rung is memory-bound (the regime the rung
    ladder exists for), and execute the trade through RESHARD_RULES
    (``CheckpointEngine.load_resharded``). Emits the SLO trio:
    ``dp_pp_trade_mttr_s`` (plan + reshard, the whole rung-transition
    window), ``reshard_s`` (the load_resharded leg alone — the same
    quantity ``tpurun-trace`` attributes per transition), and
    ``hybrid_vs_accum_goodput_x`` (the cost-model verdict the trade is
    chosen by — > 1.0 or the planner would have stacked accum)."""
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.replan import CostModel, ElasticReplanner, Rung

    n = jax.device_count()
    full = 1 << (max(1, n).bit_length() - 1)  # largest power of 2 <= n
    if full < 4:
        raise RuntimeError(f"elastic rung needs >=4 devices, have {n}")
    mesh_from = build_mesh(MeshConfig(dp=full), devices=jax.devices()[:full])
    dim0 = full * 32
    dp_sh = NamedSharding(mesh_from, PartitionSpec("dp"))
    state = {
        "params": {
            "w": jax.device_put(
                np.arange(dim0 * 64, dtype=np.float32).reshape(dim0, 64),
                dp_sh,
            )
        },
        "opt_state": {
            "mu": {
                "w": jax.device_put(np.zeros((dim0, 64), np.float32), dp_sh)
            }
        },
        "step": jax.device_put(
            np.int64(1), NamedSharding(mesh_from, PartitionSpec())
        ),
    }
    # Accum-only vs trade rung at half the world; the HBM cap sits
    # halfway between their per-device footprints so exactly one side
    # of the trade is memory-feasible (params+moments split over pp,
    # moments further over dp per arXiv:2004.13336).
    shrunk = full // 2
    trade = Rung(dp=max(1, shrunk // 2), pp=2, accum=0)
    base = CostModel(
        param_bytes=1 << 20,
        opt_bytes=2 << 20,
        step_time_s=1.0,
        reference=Rung(dp=full),
        opt_dp_shard=True,
    )
    accum_only = Rung(dp=shrunk, accum=2)
    cap = (
        base.mem_bytes_per_device(trade)
        + base.mem_bytes_per_device(accum_only)
    ) // 2
    planner = ElasticReplanner(
        dataclasses.replace(base, hbm_bytes_per_device=cap),
        full_dp=full,
        current=Rung(dp=full),
        max_pp=2,
    )
    engine = CheckpointEngine(
        tempfile.mkdtemp(prefix="bench_elastic_"), host_rank=0, num_hosts=1
    )
    try:
        if not engine.save_to_memory(1, state):
            raise RuntimeError("flash stage refused the elastic image")
        t0 = time.perf_counter()
        plan = planner.plan(shrunk)
        mesh_to = build_mesh(
            plan.rung.mesh_config(),
            devices=jax.devices()[: plan.rung.devices],
        )
        t1 = time.perf_counter()
        step, placed, _ = engine.load_resharded(mesh_to)
        if step != 1 or not placed:
            raise RuntimeError("reshard lost the staged image")
        jax.block_until_ready(placed)
        t2 = time.perf_counter()
        if not plan.is_trade:
            raise RuntimeError(
                f"planner kept {plan.rung.label()}: no trade to measure"
            )
        extra["dp_pp_trade_mttr_s"] = round(t2 - t0, 6)
        extra["reshard_s"] = round(t2 - t1, 6)
        extra["hybrid_vs_accum_goodput_x"] = round(
            plan.hybrid_vs_accum_goodput_x, 4
        )
        extra["elastic_transition"] = (
            f"{plan.current.label()} -> {plan.rung.label()}"
        )
        extra["elastic_rung_accum"] = plan.rung.accum
    finally:
        engine.close()
        AsyncCheckpointSaver.shutdown()


def _section_gc(extra, name):
    """Between-section HBM hygiene + accounting: drop dead executables
    (jit caches pin their handles), collect cycles, and record the live
    device-array footprint so an OOM cascade (r05 first capture: every
    section after llama died RESOURCE_EXHAUSTED) is attributable to a
    specific section's leak rather than a mystery."""
    import gc

    import jax

    gc.collect()
    try:
        jax.clear_caches()
    except Exception:  # noqa: BLE001 — accounting must never kill bench
        pass
    try:
        live_mb = sum(
            a.size * a.dtype.itemsize for a in jax.live_arrays()
        ) / 1e6
        extra.setdefault("hbm_live_mb", {})[name] = round(live_mb, 1)
    except Exception:  # noqa: BLE001
        pass


def _bench_checkpoint(extra, state, mesh, flash_s):
    """Flash checkpoint on the real train state (~1.5 GB on TPU)."""
    import jax
    import numpy as np

    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    durable_root = os.path.join(ckpt_dir, "durable")
    engine = None
    try:
        engine = CheckpointEngine(
            ckpt_dir,
            mesh=mesh,
            standalone=True,
            durable_dir=durable_root,
            durable_lineage="bench",
        )
        if not engine.save_to_memory(0, state):
            raise RuntimeError("warmup save_to_memory failed")
        runs = []
        for step in range(1, 4):
            t0 = time.perf_counter()
            if not engine.save_to_memory(step, state):
                raise RuntimeError(f"save_to_memory failed at step {step}")
            runs.append(time.perf_counter() - t0)
        save_block_s = min(runs)

        # Async staging (r4): trainer-visible block is one device-side
        # snapshot dispatch; D2H + memcpy happen behind the shard lock.
        # Pre-compile the snapshot executable so the timed saves measure
        # dispatch, not the compile. Each drain pays the TRUE d2h
        # (the blocking saves above ride jax's cached host
        # values — same `state` object re-saved — which real training
        # never does), so keep the timed async saves to two.
        jax.block_until_ready(engine._snapshot(state))
        async_runs = []
        for step in range(4, 6):
            t0 = time.perf_counter()
            if not engine.save_to_memory(step, state, block=False):
                raise RuntimeError(f"async save failed at step {step}")
            async_runs.append(time.perf_counter() - t0)
            if not engine.wait_staged(timeout=600):
                raise RuntimeError(f"async staging failed at step {step}")
        async_block_s = min(async_runs)

        if not engine.save_to_storage(7, state):
            raise RuntimeError("save_to_storage failed")
        if not engine.wait_saving(timeout=600):
            raise RuntimeError("async persist did not complete")
        t0 = time.perf_counter()
        step, restored = engine.load(state)
        restore_s = time.perf_counter() - t0
        if step != 7 or restored is None:
            raise RuntimeError(f"restore failed (step={step})")
        del restored

        # Durable tier (r16): the committed flash image drains to the
        # generation store on the writer's own thread, so the train
        # loop's hand-off for a durable-enabled save must stay at the
        # flash async block (acceptance: within 2x). Timed the same
        # way the async stage block is — non-blocking dispatch, min of
        # the runs — then the drain's commit is awaited off the timer.
        from dlrover_tpu.checkpoint.durable import DurableLayout

        dur_runs = []
        for step in (8, 9):
            t0 = time.perf_counter()
            if not engine.save_to_storage(step, state, block=False):
                raise RuntimeError(f"durable save failed at step {step}")
            dur_runs.append(time.perf_counter() - t0)
            if not engine.wait_saving(timeout=600):
                raise RuntimeError(f"persist failed at step {step}")
        durable_block_s = min(dur_runs)
        layout = DurableLayout(durable_root, "bench")
        deadline = time.monotonic() + 600
        while layout.latest_committed() != 9:
            if time.monotonic() > deadline:
                raise RuntimeError("durable drain did not commit")
            time.sleep(0.05)
        # Whole-pool-loss rung in isolation: read_generation (checksum
        # verify + global assembly) + reshard-on-read placement under
        # the current mesh. shm/flash stay intact — this prices ONLY
        # what a restart pays when both are gone.
        t0 = time.perf_counter()
        loaded = engine._load_from_durable(state)
        durable_restore_s = time.perf_counter() - t0
        if not loaded or loaded[0] != 9:
            raise RuntimeError("durable restore failed")
        del loaded

        nbytes = sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state)
        )
        # Reference H2D floor: ONE fused device_put of the SAME byte
        # count to the same (single-device) placement the restore
        # targets, measured right now — the honest restore figure is
        # the overhead over this floor, not wall time alone. r5 fix: the floor used to transfer nbytes/4 and
        # multiply by 4, which multiplied the per-put fixed cost
        # (connection setup, first-touch alloc) 4x too — overstating
        # the floor enough that restore_overhead_x read 0.77 (< 1) in
        # SILICON_r05_1785592704. A single full-size put has the same
        # fixed cost the restore pays once, so the ratio is >= 1 up to
        # link jitter.
        # Incompressible payload: the transport may compress, and zeros
        # would overstate the floor by an order of magnitude.
        ref_buf = np.random.default_rng(0).standard_normal(
            max(1, int(nbytes // 4)), dtype=np.float32
        )
        ref_sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        t0 = time.perf_counter()
        ref_arr = jax.device_put(ref_buf, ref_sh)
        jax.block_until_ready(ref_arr)
        h2d_ref_s = time.perf_counter() - t0
        del ref_arr, ref_buf

        # Goodput at a 10-step cadence uses the ASYNC block (what the
        # train loop actually pays per cadence save since r4).
        goodput_10 = 10 * flash_s / (10 * flash_s + async_block_s)
        extra.update(
            {
                "ckpt_bytes": int(nbytes),
                # r01 family name, kept stable alongside the short alias
                "flash_ckpt_save_block_s": round(save_block_s, 4),
                "ckpt_save_block_s": round(save_block_s, 4),
                "ckpt_async_stage_block_s": round(async_block_s, 4),
                "ckpt_save_vs_target": round(
                    TARGET_SAVE_BLOCK_S / max(async_block_s, 1e-9), 2
                ),
                "restore_s": round(restore_s, 4),
                "h2d_floor_s": round(h2d_ref_s, 4),
                "restore_overhead_x": round(
                    restore_s / max(h2d_ref_s, 1e-9), 2
                ),
                "goodput_ckpt_every_10_steps": round(goodput_10, 4),
                "durable_save_block_s": round(durable_block_s, 4),
                "durable_restore_s": round(durable_restore_s, 4),
                # the acceptance ratio (<= 2.0): durable hand-off over
                # the flash async stage block
                "durable_block_vs_flash_x": round(
                    durable_block_s / max(async_block_s, 1e-9), 2
                ),
            }
        )
    finally:
        if engine is not None:
            try:
                engine.shm.unlink()
                engine.close()
            except Exception:
                pass
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _interposed_metrics():
    """Driver-boundary numbers from the live interposer (same dlopen
    module jax loaded): corroborates the analytic MFU with measured
    execute completions (VERDICT r3 weak #6)."""
    from dlrover_tpu.profiler import pjrt

    m = pjrt.parse_metrics(pjrt.metrics_text())

    def pick(name, kind=None, agg=None):
        for key, val in m.items():
            if not key.startswith(name):
                continue
            if kind is not None and f'kind="{kind}"' not in key:
                continue
            if agg is not None and f'agg="{agg}"' not in key:
                continue
            return val
        return None

    return {
        "execute_count": pick("tpu_timer_count", kind="execute"),
        "execute_avg_us": pick(
            "tpu_timer_latency_us", kind="execute", agg="win_avg"
        ),
        "execute_max_us": pick(
            "tpu_timer_latency_us", kind="execute", agg="max"
        ),
        "h2d_count": pick("tpu_timer_count", kind="h2d"),
        "compile_count": pick("tpu_timer_count", kind="compile"),
        "device_completes": m.get("tpu_timer_device_completes_total"),
        "stall_verdict": m.get("tpu_timer_stall_verdict"),
    }


def main() -> int:
    extra = {}
    want, filtered = _section_filter()
    if filtered:
        extra["sections_filter"] = os.environ.get(
            "BENCH_SECTIONS", ""
        )
    # pid-unique IPC namespace: the checkpoint section spins up
    # socket-served queues named by the job namespace, and two
    # concurrent bench processes under the same name race for the
    # sockets ("IPC server queue_ckpt_events unavailable"). Override
    # BOTH vars: DLROVER_IPC_NAMESPACE, when inherited from a harness
    # shell, wins over DLROVER_JOB_NAME (multi_process._ipc_namespace).
    os.environ["DLROVER_JOB_NAME"] = f"bench_{os.getpid()}"
    os.environ["DLROVER_IPC_NAMESPACE"] = f"bench_{os.getpid()}"
    # Reclaim segments orphaned by SIGKILLed earlier runs (pid-unique
    # names mean nobody else ever reopens them): any
    # /dev/shm/dlrover_bench_<pid>_* whose pid is dead is ~1.5 GB of
    # tmpfs nobody can free but us.
    import re

    for seg in os.listdir("/dev/shm"):
        m = re.match(r"dlrover_bench_(\d+)_", seg)
        if m and not os.path.exists(f"/proc/{m.group(1)}"):
            try:
                os.unlink(os.path.join("/dev/shm", seg))
            except OSError:
                pass  # another reclaimer won the race
    # The chip, or nothing: unless the CALLER pinned a platform, pin
    # "tpu", so a failed TPU initialization raises below instead of JAX
    # carrying on on the CPU. JAX_PLATFORMS=cpu set by the caller is the
    # plumbing run (toy shapes, cpu_-prefixed keys, see the docstring).
    from dlrover_tpu.common.platform import pin_accelerator

    pin_accelerator()
    # Driver-boundary corroboration when the caller loaded the PJRT
    # interposer as the TPU plugin (profiler/pjrt.py env contract).
    interposed = os.environ.get("TPU_LIBRARY_PATH", "").endswith(
        "libpjrt_interposer.so"
    )

    import jax
    import numpy as np

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    flash_tps = 0.0
    vs_baseline = 0.0
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"bench.py measures the TPU; JAX gave {device.platform!r} "
            f"({device.device_kind}). Set JAX_PLATFORMS=cpu yourself for "
            f"a plumbing run."
        )
    if on_tpu:
        peak_flops(device.device_kind)  # unknown kind: fail before work
    try:
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        extra["device"] = str(device)

        if on_tpu:
            # Flash path: bs=32 fits only because the Pallas kernel never
            # materializes the s^2 probability tensor (dense OOMs at
            # bs=32: 17.4G > 15.75G hbm); dense's best single-chip config
            # is bs=16.
            flash_bs, dense_bs, seq = 32, 16, 1024
        else:
            flash_bs, dense_bs, seq = 2, 2, 128

        tiny = {} if on_tpu else dict(
            vocab_size=256, num_layers=2, num_heads=4, head_dim=8,
            embed_dim=32, use_remat=False,
        )

        cfg, state, step_fn, x, y = _build(
            dict(attention_impl="flash", **tiny), flash_bs, seq, mesh
        )
        n_params = sum(l.size for l in jax.tree.leaves(state.params))
        flash_s, state = _time_steps(state, step_fn, x, y)
        flash_tps = flash_bs * seq / flash_s
        extra.update(
            {
                "model": f"gpt2-small-{n_params/1e6:.0f}M" if on_tpu else "tiny",
                "flash_step_s": round(flash_s, 4),
                # the PLAIN flash config's step, never overwritten by a
                # ladder promotion — the interposer-overhead A/B
                # compares this same config across processes
                "flash_base_step_s": round(flash_s, 4),
                "flash_batch": flash_bs,
                "seq_len": seq,
                "mfu": round(_mfu(cfg, n_params, flash_bs, seq, flash_s), 4),
            }
        )

        dense_tps = 0.0

        def take_headline(config_label, b, step_s):
            """Promote a measured config to the headline: value / mfu /
            step / batch / vs_baseline (and goodput_10 later via
            flash_s) must all describe the SAME config, in every block
            that wins the race."""
            nonlocal flash_tps, flash_s, vs_baseline
            extra["headline_config"] = config_label
            extra["mfu"] = round(_mfu(cfg, n_params, b, seq, step_s), 4)
            extra["flash_step_s"] = round(step_s, 4)
            extra["flash_batch"] = b
            flash_tps = b * seq / step_s
            flash_s = step_s
            if dense_tps:
                vs_baseline = flash_tps / dense_tps
                extra["flash_vs_dense"] = round(vs_baseline, 3)

        if want("dense"):
            try:
                _, dstate, dstep_fn, dx, dy = _build(
                    dict(attention_impl="dense", **tiny), dense_bs, seq,
                    mesh,
                )
                # rebind so the del actually frees the final train state
                # (a `_` binding would pin ~GB of HBM through every later
                # benchmark section)
                dense_s, dstate = _time_steps(dstate, dstep_fn, dx, dy)
                del dstate, dstep_fn, dx, dy
                dense_tps = dense_bs * seq / dense_s
                vs_baseline = flash_tps / dense_tps
                extra.update(
                    {
                        "dense_step_s": round(dense_s, 4),
                        "dense_batch": dense_bs,
                        "dense_tokens_per_s": round(dense_tps, 1),
                        "flash_vs_dense": round(vs_baseline, 3),
                    }
                )
            except Exception as e:  # noqa: BLE001 — keep the flash headline
                extra["dense_error"] = repr(e)[:200]

        # Checkpoint EARLY, on clean HBM, while the full train state
        # (params + optimizer) exists — last position cost the r05
        # first capture its ckpt headline to an OOM cascade. goodput_10
        # is recomputed at the end from the FINAL headline step time.
        _section_gc(extra, "post_dense")
        if want("ckpt"):
            try:
                _bench_checkpoint(extra, state, mesh, flash_s)
            except Exception as e:  # noqa: BLE001
                extra["ckpt_error"] = repr(e)[:200]

        # The remaining generation/serving sections need only params —
        # drop the optimizer state (~1 GB of the ~1.5 GB train state).
        params = state.params
        state = step_fn = x = y = None  # noqa: F841
        _section_gc(extra, "post_ckpt")

        if on_tpu and want("flash_seq4096"):
            try:
                _bench_long_context(extra)
            except Exception as e:  # noqa: BLE001
                extra["flash_seq4096_error"] = repr(e)[:200]

        if want("decode"):
            try:
                _bench_decode(extra, cfg, params, on_tpu)
            except Exception as e:  # noqa: BLE001
                extra["decode_error"] = repr(e)[:200]

        if want("spec"):
            try:
                _bench_spec_decode(extra, cfg, params, on_tpu)
            except Exception as e:  # noqa: BLE001
                extra["spec_error"] = repr(e)[:200]

        serving_split = None
        if want("serving"):
            try:
                serving_split = _bench_serving(
                    extra, cfg, params, on_tpu
                )
            except Exception as e:  # noqa: BLE001
                extra["serving_error"] = repr(e)[:200]

        if want("fleet"):
            try:
                _bench_fleet(extra, cfg, params, on_tpu)
            except Exception as e:  # noqa: BLE001
                extra["fleet_error"] = repr(e)[:200]
            try:
                _bench_paged(extra, cfg, params, on_tpu)
            except Exception as e:  # noqa: BLE001
                extra["fleet_paged_error"] = repr(e)[:200]

        if want("pool"):
            try:
                _bench_pool(extra)
            except Exception as e:  # noqa: BLE001
                extra["pool_error"] = repr(e)[:200]

        if want("cluster"):
            try:
                _bench_cluster(extra)
            except Exception as e:  # noqa: BLE001
                extra["cluster_error"] = repr(e)[:200]

        if want("elastic"):
            try:
                _bench_elastic(extra)
            except Exception as e:  # noqa: BLE001
                extra["elastic_error"] = repr(e)[:200]

        params = None  # the model families below build their own
        _section_gc(extra, "post_serving")

        if want("llama"):
            try:
                # per-variant guards inside
                _bench_llama(extra, mesh, on_tpu)
            except Exception as e:  # noqa: BLE001 — module import failure
                extra["llama_family_error"] = repr(e)[:200]

        _section_gc(extra, "post_llama")
        if want("longseq"):
            try:
                _bench_longseq_train(extra, mesh, on_tpu)
            except Exception as e:  # noqa: BLE001
                extra["longseq_train_error"] = repr(e)[:200]
        _section_gc(extra, "post_longseq")

        # Fused chunked CE (flash + ce_chunk): the fp32 logits are the
        # HBM ceiling of this config — fusing the head+CE frees ~10 GB
        # and should admit batches the plain path cannot fit. Measure
        # at the headline batch first; if parity holds, push the batch
        # and let the BEST measured config take the headline.
        # (gated with the remat/batch ladder below: a section-filtered
        # run wants the PLAIN flash headline, un-promoted)
        try:
            if not want("ladder"):
                raise _SectionSkip()
            # 1.5x sits between the known-good batch and the 2x reach:
            # if 2x OOMs, the freed-logits headroom may still fit 1.5x
            fused_batches = (
                [flash_bs, flash_bs * 2, (flash_bs * 3) // 2]
                if on_tpu
                else [2]
            )
            best_fused = None  # (tokens_per_s, batch, step_s)
            failed_2x = False
            for fb in fused_batches:
                if fb == (flash_bs * 3) // 2 and not failed_2x:
                    break  # 2x worked (or broke parity): no 1.5x rung
                try:
                    _, fstate, fstep, fx, fy = _build(
                        dict(attention_impl="flash", ce_chunk=128, **tiny),
                        fb,
                        seq,
                        mesh,
                    )
                    fs, fstate = _time_steps(fstate, fstep, fx, fy)
                    tps = fb * seq / fs
                    extra[f"fused_ce_b{fb}_step_s"] = round(fs, 4)
                    extra[f"fused_ce_b{fb}_tokens_per_s"] = round(tps, 1)
                    if best_fused is None or tps > best_fused[0]:
                        best_fused = (tps, fb, fs)
                    if tps < flash_tps * 0.98:
                        break  # no parity at this batch; don't escalate
                except Exception as e:  # noqa: BLE001 — e.g. OOM at 2x
                    extra[f"fused_ce_b{fb}_error"] = repr(e)[:160]
                    if fb != flash_bs * 2:
                        break
                    failed_2x = True
                finally:
                    # a failed rung must not pin its HBM into the next
                    fstate = fstep = fx = fy = None  # noqa: F841
            if best_fused is not None and best_fused[0] > flash_tps:
                _, fb, fs = best_fused
                take_headline("flash+fused_ce", fb, fs)
        except _SectionSkip:
            pass
        except Exception as e:  # noqa: BLE001
            extra["fused_ce_error"] = repr(e)[:200]

        # MFU ladder (VERDICT r4 #3): fused-CE freed the logits HBM, so
        # cheaper remat policies may now fit at the headline batch.
        # "dots" saves matmul outputs (backward redoes only VPU work);
        # no-remat redoes nothing. Whichever measures fastest takes the
        # headline — same 6N-FLOP MFU accounting, less recompute.
        try:
            if not want("ladder"):
                raise _SectionSkip()
            hk = dict(attention_impl="flash", **tiny)
            if extra.get("headline_config") == "flash+fused_ce":
                hk["ce_chunk"] = 128
            hb = extra.get("flash_batch", flash_bs)
            ladder = []
            # Rungs only exist when the base config remats (TPU): the
            # CPU tiny config has use_remat=False, so both rungs would
            # re-measure the identical program and report noise as a
            # distinct config (remat_policy itself is covered by
            # tests/test_models.py).
            variants = (
                [
                    ("remat_dots", dict(remat_policy="dots")),
                    ("no_remat", dict(use_remat=False)),
                ]
                if hk.get("use_remat", True)
                else []
            )
            for label, over in variants:
                try:
                    _, vstate, vstep, vx, vy = _build(
                        {**hk, **over}, hb, seq, mesh
                    )
                    vs, vstate = _time_steps(vstate, vstep, vx, vy)
                    tps = hb * seq / vs
                    extra[f"{label}_step_s"] = round(vs, 4)
                    extra[f"{label}_tokens_per_s"] = round(tps, 1)
                    ladder.append((tps, label, vs))
                except Exception as e:  # noqa: BLE001 — e.g. OOM
                    extra[f"{label}_error"] = repr(e)[:160]
                finally:
                    # a failed rung must not pin its HBM into the next
                    vstate = vstep = vx = vy = None  # noqa: F841
            rung_won = False
            if ladder:
                tps, best_label, vs = max(ladder)
                if tps > flash_tps:
                    rung_won = True
                    take_headline(
                        extra.get("headline_config", "flash")
                        + "+" + best_label,
                        hb,
                        vs,
                    )

            # Batch ladder on the WINNING config: throughput/MFU often
            # rises with batch (fixed per-step costs amortize) until
            # HBM runs out — the remat/fused rungs above changed the
            # memory envelope, so the best batch must be re-searched,
            # not assumed to stay at the base config's 32. The ce_chunk
            # fused head keeps the logits out of HBM at any batch.
            if on_tpu:
                # measure at the HEADLINE config exactly: the rung
                # override applies only if that rung actually took the
                # headline, so the "+bNN" label always extends the
                # config the numbers describe
                win = dict(hk)
                if rung_won:
                    win.update(dict(variants)[best_label])
                # No early break on a non-improving rung: the r5 silicon
                # capture showed a NON-monotonic batch response (b48
                # regressed to 104.5k tok/s while b32 held 114.9k —
                # late-bench allocator fragmentation), so breaking at the
                # first loss would hide a b64 win. Only OOM ends the walk.
                # Label from the PRE-walk config: if both b48 and b64
                # win, stacking suffixes off the live headline would
                # yield a self-contradictory "…+b48+b64".
                walk_base_label = extra.get("headline_config", "flash")
                for bb in (hb * 3 // 2, hb * 2):
                    try:
                        _, bstate, bstep, bx, by = _build(
                            win, bb, seq, mesh
                        )
                        bs_s, bstate = _time_steps(bstate, bstep, bx, by)
                        tps = bb * seq / bs_s
                        extra[f"batch{bb}_step_s"] = round(bs_s, 4)
                        extra[f"batch{bb}_tokens_per_s"] = round(tps, 1)
                        if tps > flash_tps:
                            take_headline(
                                walk_base_label + f"+b{bb}", bb, bs_s
                            )
                    except Exception as e:  # noqa: BLE001 — e.g. OOM
                        extra[f"batch{bb}_error"] = repr(e)[:160]
                        break
                    finally:
                        bstate = bstep = bx = by = None  # noqa: F841
        except _SectionSkip:
            pass
        except Exception as e:  # noqa: BLE001
            extra["mfu_ladder_error"] = repr(e)[:200]

        # goodput at a 10-step cadence re-derived from the FINAL
        # headline step time (the ckpt block was measured early; the
        # fused-CE / remat ladder may have changed flash_s since)
        if "ckpt_async_stage_block_s" in extra:
            ab = extra["ckpt_async_stage_block_s"]
            extra["goodput_ckpt_every_10_steps"] = round(
                10 * flash_s / (10 * flash_s + ab), 4
            )

        if interposed:
            try:
                extra["interposed"] = _interposed_metrics()
            except Exception as e:  # noqa: BLE001
                extra["interposed_error"] = repr(e)[:200]

        # Goodput north star, measured (VERDICT r3 #7): the full
        # preemption-storm e2e — real master + agents + trainers,
        # SIGKILLs, PerfMonitor's own number. Now a recovery-SLO
        # MATRIX: 2 host kills plus 2 whole-slice kills (4 hosts,
        # node_unit=2), so MTTR/goodput are reported per fault class
        # (slice-kill next to host-kill). The storm's trainers pin the
        # CPU backend themselves (it measures the control plane), so it
        # runs whatever the platform; the ~8 min cost is opted in with
        # BENCH_STORM=1 (plumbing runs stay fast).
        if os.environ.get("BENCH_STORM", "0") == "1" and want(
            "storm"
        ):
            try:
                from dlrover_tpu.chaos import run_goodput_storm

                storm_dir = tempfile.mkdtemp(prefix="bench_storm_")
                try:
                    # pid-unique job name: a concurrent bench running
                    # its own storm must not cleanup_namespaces() THIS
                    # storm's trainers/shm.
                    storm = run_goodput_storm(
                        storm_dir,
                        num_workers=4,
                        node_unit=2,
                        kills=2,
                        slice_kills=2,
                        kill_interval_steps=100,
                        job_name=f"bench_storm_{os.getpid()}",
                    )
                finally:
                    shutil.rmtree(storm_dir, ignore_errors=True)
                if storm:
                    extra["goodput_storm"] = storm
                    # Pointer-style SLO matrix: these scalars must
                    # survive the 1800-byte line budget (priority keys);
                    # the full storm dict (stall forensics) rides the
                    # sidecar under pressure.
                    extra["storm_goodput"] = storm.get("goodput")
                    extra["storm_mttr_s"] = storm.get("mttr_s")
                    extra["storm_slice_mttr_s"] = storm.get("slice_mttr_s")
                    extra["storm_slice_goodput"] = storm.get(
                        "slice_goodput"
                    )
                    # the MTTR phase breakdown: which serial phase of
                    # recovery the time went to (docs/recovery.md)
                    extra["storm_rdzv_s"] = storm.get("rdzv_s")
                    extra["storm_restore_s"] = storm.get("restore_s")
                    extra["storm_compile_s"] = storm.get("compile_s")
                    extra["storm_first_step_s"] = storm.get("first_step_s")
                    # trace-derived detection SLOs (docs/observability.md):
                    # fault-to-detect latency from the merged incident
                    # trace. The remaining trace phase scalars
                    # (rendezvous/reshard/recompile) stay
                    # sidecar-recoverable inside the storm dict.
                    extra["storm_mttd_s"] = storm.get("mttd_s")
                    extra["storm_detect_s"] = storm.get("detect_s")
                else:
                    extra["goodput_storm_error"] = "harness timed out"
            except Exception as e:  # noqa: BLE001
                extra["goodput_storm_error"] = repr(e)[:200]

        # Master crash tolerance (docs/recovery.md master failover):
        # SIGKILL the coordinating master mid-storm, restart it against
        # its state journal, and measure the coordination outage
        # (master_mttr_s) + the productive step fraction of the kill
        # window (master_kill_goodput) with ZERO worker restarts. Opted
        # in with the storm (same minutes-cost class; the trainers are
        # the storm's CPU-pinned control-plane GPTs).
        if os.environ.get("BENCH_STORM", "0") == "1" and want(
            "master_kill"
        ):
            try:
                from dlrover_tpu.chaos import run_master_kill_storm

                mk_dir = tempfile.mkdtemp(prefix="bench_master_kill_")
                try:
                    mk = run_master_kill_storm(
                        mk_dir,
                        num_workers=2,
                        job_name=f"bench_master_kill_{os.getpid()}",
                    )
                finally:
                    shutil.rmtree(mk_dir, ignore_errors=True)
                if mk:
                    extra["master_kill"] = mk
                    # priority-key scalars (the full dict rides the
                    # sidecar under line pressure)
                    extra["master_mttr_s"] = mk.get("master_mttr_s")
                    extra["master_kill_goodput"] = mk.get(
                        "master_kill_goodput"
                    )
                    extra["master_kill_worker_restarts"] = mk.get(
                        "worker_restarts"
                    )
                else:
                    extra["master_kill_error"] = "drill timed out"
            except Exception as e:  # noqa: BLE001
                extra["master_kill_error"] = repr(e)[:200]

        # Warm-vs-cold recovery A/B (docs/recovery.md): two compressed
        # storms at the IDENTICAL fault plan — the cold leg runs with
        # the cache DISABLED (every incarnation pays the XLA compile
        # inside the measured window), the warm leg with a prewarmed
        # cache (recovery compiles are reads). Proves the warm-restart
        # fast path as a measured MTTR delta (warm compile_s ≈ 0), not
        # a code path. Opted in with the storm (same ~minutes cost
        # class, same CPU-pinned control-plane trainers).
        if os.environ.get("BENCH_STORM", "0") == "1" and want(
            "recovery_ab"
        ):
            try:
                from dlrover_tpu.chaos import run_recovery_ab

                ab_dir = tempfile.mkdtemp(prefix="bench_recovery_ab_")
                try:
                    ab = run_recovery_ab(
                        ab_dir, job_name=f"bench_rec_ab_{os.getpid()}"
                    )
                finally:
                    shutil.rmtree(ab_dir, ignore_errors=True)
                if ab:
                    extra["recovery_ab"] = ab
                    extra["recovery_cold_mttr_s"] = ab["cold"].get("mttr_s")
                    extra["recovery_warm_mttr_s"] = ab["warm"].get("mttr_s")
                    extra["recovery_mttr_delta_s"] = ab.get("mttr_delta_s")
                    extra["recovery_cold_compile_s"] = ab.get(
                        "cold_compile_s"
                    )
                    extra["recovery_warm_compile_s"] = ab.get(
                        "warm_compile_s"
                    )
                else:
                    extra["recovery_ab_error"] = "a leg timed out"
            except Exception as e:  # noqa: BLE001
                extra["recovery_ab_error"] = repr(e)[:200]
    except Exception as e:  # noqa: BLE001 — JSON line on every path
        extra["fatal_error"] = repr(e)[:500]

    failed = sorted(k for k in extra if k in HEADLINE_SECTION_ERRORS)
    record = {
        "metric": METRIC,
        "value": round(flash_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 3),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "extra": extra,
    }
    if not on_tpu:
        # a CPU number is never written under a device metric's name
        record.update(
            metric=f"{device.platform}_plumbing",
            value=None,
            unit=None,
            vs_baseline=None,
            extra={f"{device.platform}_{k}": v for k, v in extra.items()},
        )
    _emit(record)
    if failed:
        print(f"bench: sections failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
