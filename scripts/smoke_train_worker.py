"""Worker of ``chip_smoke.py``'s train phase, launched through ``tpurun``.

Builds a GPT (GPT-2-small at full width unless ``SMOKE_TINY=1``) through
the normal path — ``elastic_context``, ``init_train_state``,
``build_train_step``, ``CheckpointEngine``, ``ElasticTrainLoop`` — takes
steps on one seeded batch, stages a flash checkpoint every step, and
writes what it saw as JSON lines to ``SMOKE_EVENTS``. The first
incarnation holds after ``SMOKE_HOLD_AT_STEP`` is staged; the parent
kills it there, the agent restarts it and it resumes from that step. Every number here is smoke output, not a benchmark result.
"""

import dataclasses
import json
import os
import shutil
import sys
import time

EVENTS = os.environ["SMOKE_EVENTS"]
REQUIRED = os.environ["SMOKE_REQUIRE_PLATFORM"]
TINY = os.environ.get("SMOKE_TINY") == "1"
SEED = int(os.environ.get("SMOKE_SEED", "0"))
TOTAL_STEPS = int(os.environ["SMOKE_TOTAL_STEPS"])
# the first incarnation holds here, its step staged, until the parent's
# SIGKILL arrives: the staged step at the kill is then exactly this one
HOLD_AT_STEP = int(os.environ["SMOKE_HOLD_AT_STEP"])
CKPT_DIR = os.environ["SMOKE_CKPT_DIR"]


def emit(event: str, **fields) -> None:
    fields.update(event=event, t=time.time(), pid=os.getpid())
    with open(EVENTS, "a") as f:
        f.write(json.dumps(fields) + "\n")


def main() -> int:
    t_boot = time.monotonic()
    from dlrover_tpu.trainer.elastic import elastic_context

    ctx = elastic_context()

    import jax
    import jax.numpy as jnp
    import numpy as np

    cache_events = {"hits": 0, "misses": 0}

    def on_cache_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_cache_event)

    from dlrover_tpu.common.platform import device_summary

    devices = jax.devices()
    emit(
        "device",
        **device_summary(),
        restart_count=ctx.restart_count,
        cache_dir=jax.config.jax_compilation_cache_dir,
        interposed=os.environ.get("TPU_LIBRARY_PATH", ""),
    )
    if devices[0].platform != REQUIRED:
        print(
            f"smoke worker: platform {devices[0].platform!r}, required "
            f"{REQUIRED!r}",
            file=sys.stderr,
        )
        return 4

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.layers import cross_entropy_loss
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    base = GPTConfig.tiny() if TINY else GPTConfig.gpt2_small()
    cfg = dataclasses.replace(base, attention_impl="flash")
    batch = 4 if TINY else 32
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), devices)
    tx = default_optimizer(learning_rate=1e-3, warmup_steps=2)
    tokens = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
    state, shardings = init_train_state(
        model, tokens, mesh, tx, rng=jax.random.PRNGKey(SEED)
    )
    step_fn = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)

    # one seeded batch of low-entropy tokens, every step: the loss falls
    # within a few steps, so a resumed series is told from a fresh one
    rng = np.random.default_rng(SEED)
    x = rng.integers(
        0, min(cfg.vocab_size, 512), (batch, cfg.max_seq_len)
    ).astype(np.int32)
    y = np.roll(x, -1, axis=1)

    # lowering only (no second compile): is the Mosaic kernel in the step
    # this process is about to run, or an interpreted stand-in?
    text = step_fn.lower(state, x, y).as_text()
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state)
    )
    shm_free = shutil.disk_usage("/dev/shm").free
    emit(
        "built",
        model="tiny" if TINY else "gpt2-small",
        n_params=sum(l.size for l in jax.tree.leaves(state.params)),
        batch=batch,
        seq=cfg.max_seq_len,
        tpu_custom_call="tpu_custom_call" in text,
        state_bytes=state_bytes,
        dev_shm_free_bytes=shm_free,
        boot_s=round(time.monotonic() - t_boot, 3),
    )
    if shm_free < state_bytes * 1.05:
        print(
            f"smoke worker: /dev/shm has {shm_free} bytes free, the "
            f"staged state needs {state_bytes}",
            file=sys.stderr,
        )
        return 5

    calls = []

    def timed_step(s, *b):
        t0 = time.monotonic()
        out = step_fn(s, *b)
        jax.block_until_ready(out[1])
        calls.append(time.monotonic() - t0)
        return out

    engine = CheckpointEngine(CKPT_DIR, mesh=mesh)

    def on_step(step, loss):
        if len(calls) == 1:
            emit(
                "restored",
                start_step=loop.start_step,
                resumed_from=loop.start_step - 1,
                restore_s=round(loop.last_restore_s, 3),
            )
        # confirm the stage of THIS step before telling the parent: it
        # kills on this line and checks the resume against it
        staged = engine.wait_staged(timeout=120.0)
        if not staged:
            raise RuntimeError(f"flash stage of step {step} did not land")
        emit(
            "step",
            step=step,
            loss=float(loss),
            step_s=round(calls[-1], 4),
            staged=bool(staged),
            cache_hits=cache_events["hits"],
            cache_misses=cache_events["misses"],
        )
        if ctx.restart_count == 0 and step == HOLD_AT_STEP:
            time.sleep(600)

    loop = ElasticTrainLoop(
        engine,
        timed_step,
        ctx=ctx,
        max_steps=TOTAL_STEPS,
        memory_every=1,
        storage_every=0,
        log_every=1,
        on_step=on_step,
    )

    def data():
        while True:
            yield x, y

    state = loop.run(state, data())
    jax.block_until_ready(state.params)

    metrics = {}
    if os.environ.get("DLROVER_TT_PORT"):
        from dlrover_tpu.profiler import pjrt

        metrics = {
            k: v
            for k, v in pjrt.parse_metrics(pjrt.metrics_text()).items()
            if "launches_total" in k
            or "completes_total" in k
            or 'kind="execute"' in k
        }
    mem = devices[0].memory_stats() or {}
    emit(
        "done",
        final_step=int(state.step),
        first_call_s=round(calls[0], 3) if calls else None,
        steady_step_s=round(float(np.median(calls[2:])), 4)
        if len(calls) > 2
        else None,
        loop_compile_s=loop.last_compile_s,
        cache_hits=cache_events["hits"],
        cache_misses=cache_events["misses"],
        interposer_metrics=metrics,
        peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        bytes_limit=mem.get("bytes_limit"),
    )
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
