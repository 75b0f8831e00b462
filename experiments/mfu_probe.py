"""MFU attribution probe (r5): decompose the GPT-2 headline step and
re-search the batch/chunk space in a FRESH process.

Why fresh: the driver bench measures the batch ladder late, after the
checkpoint/serving/llama sections have churned HBM — the r5 capture
shows batch48 at 104.5k tok/s (vs 114.9k at b32), a regression that
may be allocator fragmentation rather than a real scaling cliff, and
the ladder's early-break then never tried b64. This probe measures the
same configs with a clean allocator, plus a fwd / fwd+bwd / full-step
decomposition that attributes the non-matmul residual the profiler doc
promises to chase (docs/profiler.md "MFU ceiling analysis").

Run ON the chip (plain env):  python experiments/mfu_probe.py
Emits one JSON line and writes experiments/MFU_PROBE_<ts>.json.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402 — reuse _build/_time_steps/_dispatch_floor/_mfu


def _timed(fn, *args, iters=6, sync=None):
    """Median wall time of fn(*args) minus the dispatch floor, syncing
    on a scalar derived from the output (same methodology as
    bench._time_steps)."""
    import numpy as np

    out = fn(*args)  # compile + warmup
    scalar = sync(out) if sync else out
    _ = float(scalar)
    floor_s = bench._dispatch_floor(scalar)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        scalar = sync(out) if sync else out
        _ = float(scalar)
        times.append(time.perf_counter() - t0)
    return max(float(np.median(times)) - floor_s, 1e-9)


def main():
    smoke = bool(int(os.environ.get("MFU_PROBE_SMOKE", "0")))
    if smoke:
        # smoke mode never touches the chip: pin the host backend
        from dlrover_tpu.common.platform import force_virtual_cpu

        force_virtual_cpu(1)
    import jax
    import jax.numpy as jnp

    res = {"device": str(jax.devices()[0]), "ts": int(time.time())}
    on_tpu = jax.default_backend() == "tpu"
    res["backend"] = jax.default_backend()
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    seq = 128 if smoke else 1024
    base = dict(attention_impl="flash", use_remat=False)
    if smoke:
        base.update(num_layers=2, vocab_size=512)
    b_head = 2 if smoke else 32
    ladder = (2, 3) if smoke else (32, 48, 64)
    chunks = ((2, 64),) if smoke else ((32, 256), (32, 512), (64, 256), (64, 512))

    # --- 1. step decomposition at the headline config (b32) ----------
    n_params = 0
    state = step_fn = x = y = None
    try:
        from dlrover_tpu.models.gpt import cross_entropy_loss

        cfg, state, step_fn, x, y = bench._build(base, b_head, seq, mesh)
        n_params = sum(
            v.size for v in jax.tree_util.tree_leaves(state.params)
        )
        res["n_params_m"] = round(n_params / 1e6, 1)

        from dlrover_tpu.models.gpt import GPT

        model_apply = GPT(cfg).apply

        @jax.jit
        def fwd_only(params, x, y):
            logits = model_apply({"params": params}, x)
            return cross_entropy_loss(logits, y)

        @jax.jit
        def fwd_bwd(params, x, y):
            loss, grads = jax.value_and_grad(
                lambda p: cross_entropy_loss(
                    model_apply({"params": p}, x), y
                )
            )(params)
            # one scalar that depends on every grad leaf: forces the
            # whole backward without fetching the grads to host
            gsum = sum(
                jnp.sum(jnp.abs(g)).astype(jnp.float32)
                for g in jax.tree_util.tree_leaves(grads)
            )
            return loss + 0.0 * gsum

        t_fwd = _timed(fwd_only, state.params, x, y)
        t_fb = _timed(fwd_bwd, state.params, x, y)
        t_step, _st = bench._time_steps(state, step_fn, x, y)
        res[f"b{b_head}_fwd_s"] = round(t_fwd, 4)
        res[f"b{b_head}_fwd_bwd_s"] = round(t_fb, 4)
        res[f"b{b_head}_full_step_s"] = round(t_step, 4)
        res[f"b{b_head}_bwd_s"] = round(t_fb - t_fwd, 4)
        res[f"b{b_head}_opt_overhead_s"] = round(t_step - t_fb, 4)
        res[f"b{b_head}_mfu"] = round(bench._mfu(cfg, n_params, b_head, seq, t_step), 4)
        # fwd MFU on the 2N fwd accounting (2/6 of train FLOPs)
        res[f"b{b_head}_fwd_mfu"] = round(
            bench._mfu(cfg, n_params, b_head, seq, t_fwd) / 3.0, 4
        )
    except Exception as e:  # noqa: BLE001
        res["decomp_error"] = repr(e)[:200]
    finally:
        # release section 1's ~GB of device state even on the failure
        # path — a leaked binding here would fragment HBM into the very
        # ladder this probe exists to measure cleanly
        state = step_fn = x = y = _st = None  # noqa: F841

    # --- 2. fresh-allocator batch ladder -----------------------------
    for b in ladder:
        try:
            cfg, state, step_fn, x, y = bench._build(base, b, seq, mesh)
            if not n_params:  # section 1 failed before counting
                n_params = sum(
                    v.size for v in jax.tree_util.tree_leaves(state.params)
                )
            t, state = bench._time_steps(state, step_fn, x, y)
            res[f"plain_b{b}_step_s"] = round(t, 4)
            res[f"plain_b{b}_tokens_per_s"] = round(b * seq / t, 1)
            res[f"plain_b{b}_mfu"] = round(
                bench._mfu(cfg, n_params, b, seq, t), 4
            )
        except Exception as e:  # noqa: BLE001
            res[f"plain_b{b}_error"] = repr(e)[:160]
        finally:
            state = step_fn = x = y = None  # noqa: F841

    # --- 3. fused-CE chunk sweep (frees logits HBM; may enable b64) --
    for b, chunk in chunks:
        try:
            cfg, state, step_fn, x, y = bench._build(
                dict(base, ce_chunk=chunk), b, seq, mesh
            )
            t, state = bench._time_steps(state, step_fn, x, y)
            key = f"ce{chunk}_b{b}"
            res[f"{key}_step_s"] = round(t, 4)
            res[f"{key}_tokens_per_s"] = round(b * seq / t, 1)
        except Exception as e:  # noqa: BLE001
            res[f"ce{chunk}_b{b}_error"] = repr(e)[:160]
        finally:
            state = step_fn = x = y = None  # noqa: F841

    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"MFU_PROBE_{res['ts']}.json",
    )
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    print("wrote", out, file=sys.stderr)
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
