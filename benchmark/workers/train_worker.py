"""Worker of the training drivers, launched through ``tpurun``.

The pattern is ``scripts/smoke_train_worker.py``'s (copied, not
imported): build the model through the normal path — ``elastic_context``,
``init_train_state``, ``build_train_step``, ``CheckpointEngine``,
``ElasticTrainLoop`` — and write what happened as JSON lines to the
event file named in the spec. What differs is the timing: the loop runs
in cycles of N steps ended by a device sync, and every stamp is taken
*between* syncs.

A cycle is a *segment* (N steps: from the return of the last save, or
sync, to ``block_until_ready`` on the state after the N-th step) and a
*save* (from that sync to the return of ``engine.save_to_memory``): the
instance's ``save_to_memory`` is wrapped so that it first blocks until
the state is ready, stamps, calls through, stamps again. Where the
traffic has no saves the cycle is the segment alone. The window opens
at the end of the last warm-up cycle and holds whole cycles only.

``BENCH_SPEC`` names a JSON file: config, traffic, seed, seconds, trace,
paths. Every number written here is a stamp or a count; the metrics are
computed by the readers under ``benchmark/end_to_end`` and
``benchmark/layer_metrics``.
"""

import json
import os
import sys
import time

SPEC = json.load(open(os.environ["BENCH_SPEC"]))
EVENTS = SPEC["events"]


def emit(event: str, **fields) -> None:
    fields.update(event=event, t=time.time(), pid=os.getpid())
    with open(EVENTS, "a") as f:
        f.write(json.dumps(fields) + "\n")


class NoSaveEngine:
    """A loop that checkpoints nothing: what ``ElasticTrainLoop`` asks of
    its engine, answered without touching the state."""

    def load_consistent(self, template):
        return -1, None

    def save_to_memory(self, step, pytree, **_):
        return True

    def wait_staged_all(self, timeout: float = 0.0) -> bool:
        return True

    def wait_staged(self, timeout: float = 0.0) -> bool:
        return True

    def wait_saving(self, timeout: float = 0.0) -> bool:
        return True

    def close(self) -> None:
        pass


def main() -> int:
    t_boot = time.time()
    config, traffic = SPEC["config"], SPEC["traffic"]
    params_t = traffic["params"]
    from dlrover_tpu.trainer.elastic import elastic_context

    ctx = elastic_context()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common.platform import device_summary

    devices = jax.devices()
    emit("device", **device_summary(), restart_count=ctx.restart_count,
         t_boot=t_boot, cache_dir=jax.config.jax_compilation_cache_dir)
    if devices[0].platform != SPEC["platform"] or len(devices) < SPEC["chips"]:
        print(f"train worker: {len(devices)} x {devices[0].platform!r}, "
              f"need {SPEC['chips']} x {SPEC['platform']!r}", file=sys.stderr)
        return 4
    devices = devices[: SPEC["chips"]]

    from benchmark import reduce_trace
    from benchmark.device_memory import memory_peak_bytes
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
    from dlrover_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
        choose_mesh_shape,
    )
    from dlrover_tpu.parallel.sharding import DEFAULT_RULES, data_sharding_for
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    cfg = GPTConfig(**{**config["gpt_config"], **params_t.get("gpt_config", {})})
    batch, seq = params_t["batch"], params_t["seq"]
    n_steps = params_t["steps_per_cycle"]
    save_every = params_t["save_every"]  # 0: a sync ends the segment, no save
    model = GPT(cfg)
    if params_t.get("mesh", "dp") == "choose_mesh_shape":
        mesh = build_mesh(choose_mesh_shape(len(devices)), devices)
    else:
        mesh = build_mesh(MeshConfig(dp=-1), devices)
    tx = default_optimizer(
        learning_rate=params_t["learning_rate"],
        warmup_steps=params_t["warmup_steps"],
    )
    tokens = jnp.zeros((batch, seq), jnp.int32)
    state, shardings = init_train_state(
        model, tokens, mesh, tx, rng=jax.random.PRNGKey(config["weights_key"])
    )
    step_fn = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)

    # The first batch is the canary: fixed by the configuration's key, drawn
    # over the whole vocabulary, so that the loss the first step returns
    # (taken at the initial weights) can be held against the reference.
    # Every later batch comes from --seed: a few batches over a narrow
    # alphabet, in a cycle, so that the loss keeps falling.
    sharding = data_sharding_for(np.zeros((batch, seq), np.int32), mesh, DEFAULT_RULES)

    def place(x):
        y = np.roll(x, -1, axis=1)
        return jax.device_put(x, sharding), jax.device_put(y, sharding)

    canary_rng = np.random.default_rng(config["canary_key"])
    canary = place(canary_rng.integers(0, config["vocab_size"], (batch, seq)).astype(np.int32))
    rng = np.random.default_rng(SPEC["seed"])
    batches = [
        place(rng.integers(0, params_t["alphabet"], (batch, seq)).astype(np.int32))
        for _ in range(params_t["distinct_batches"])
    ]

    # Whether the flash kernel is in the step is read off the lowered text,
    # which costs a second trace of the whole step: traced runs only.
    kernel = None
    if SPEC["trace"]:
        kernel = "tpu_custom_call" in step_fn.lower(state, *canary).as_text()
    state_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))
    emit(
        "built",
        n_params=sum(l.size for l in jax.tree.leaves(state.params)),
        batch=batch, seq=seq, mesh={k: int(v) for k, v in mesh.shape.items()},
        tpu_custom_call=kernel,
        state_bytes=state_bytes,
    )

    tracing = {"on": False, "done": not SPEC["trace"], "dir": SPEC["trace_dir"]}
    warmup_cycles = params_t["warmup_cycles"]
    max_cycles = params_t["max_cycles"]
    trace_cycles = params_t.get("trace_cycles", 2)
    seconds = float(SPEC["seconds"])
    cycles = []  # one dict per cycle, warm-up ones included
    mark = {"seg_start": None, "t_open": None, "in_window": 0}
    losses = []

    def end_cycle(t_ready, t_ret, ok, step):
        """Stamps of one finished cycle; opens, extends or closes the window."""
        cycles.append(dict(index=len(cycles), step=step, seg_start=mark["seg_start"],
                           t_ready=t_ready, t_ret=t_ret, ok=bool(ok)))
        if len(cycles) == warmup_cycles:
            mark["t_open"] = t_ret
            if not tracing["done"]:
                reduce_trace.start_trace(tracing["dir"])
                tracing["on"] = True
                tracing["t_start"] = time.time()
        elif len(cycles) > warmup_cycles:
            mark["in_window"] += 1
            if tracing["on"] and mark["in_window"] >= trace_cycles:
                tracing["t_stop"] = time.time()
                reduce_trace.stop_trace()
                tracing["on"], tracing["done"] = False, True
            fastest = min(c["t_ret"] - c["seg_start"] for c in cycles[warmup_cycles:])
            elapsed = time.time() - mark["t_open"]
            if mark["in_window"] >= max_cycles or elapsed + fastest > seconds:
                loop.request_stop()
        mark["seg_start"] = time.time()  # after any profiler work: not in a segment

    def first_sync(step, t_ready, t_ret, ok):
        """The sync after the compiling step: cycle 0, which has no segment."""
        cycles.append(dict(index=0, step=step, seg_start=None,
                           t_ready=t_ready, t_ret=t_ret, ok=bool(ok)))
        mark["seg_start"] = time.time()

    if save_every:
        engine = CheckpointEngine(SPEC["ckpt_dir"], mesh=mesh)
        inner_save = engine.save_to_memory

        def timed_save(step, pytree, *args, **kwargs):
            jax.block_until_ready(pytree)  # the sync that ends the segment
            t_ready = time.time()
            with jax.profiler.TraceAnnotation("save_call"):
                ok = inner_save(step, pytree, *args, **kwargs)
            t_ret = time.time()
            if mark["seg_start"] is not None:
                end_cycle(t_ready, t_ret, ok, step)
            else:  # the save of step 0, right after the compile: no segment yet
                first_sync(step, t_ready, t_ret, ok)
            return ok

        engine.save_to_memory = timed_save
    else:
        engine = NoSaveEngine()

    def traced_step(s, *b):
        with jax.profiler.TraceAnnotation("step_dispatch"):
            return step_fn(s, *b)

    def on_step(step, loss):
        losses.append(loss)
        if save_every:
            return
        if mark["seg_start"] is None:
            jax.block_until_ready(loss)
            now = time.time()
            first_sync(step, now, now, True)
        elif step % n_steps == 0:
            jax.block_until_ready(loss)  # the step's state is ready with its loss
            now = time.time()
            end_cycle(now, now, True, step)

    loop = ElasticTrainLoop(
        engine,
        traced_step,
        ctx=ctx,
        max_steps=(warmup_cycles + max_cycles + 2) * n_steps,
        memory_every=save_every or n_steps,
        storage_every=0,
        log_every=10**9,  # a logged loss is a sync inside a segment
        on_step=on_step,
    )

    def data():
        yield canary
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    t_loop = time.time()
    state = loop.run(state, data())
    jax.block_until_ready(state.params)
    if tracing["on"]:  # the window was shorter than the cycles to trace
        tracing["t_stop"] = time.time()
        reduce_trace.stop_trace()
    values = [float(l) for l in losses]
    emit(
        "window",
        t_loop=t_loop,
        t_open=mark["t_open"],
        warmup_cycles=warmup_cycles,
        steps_per_cycle=n_steps,
        tokens_per_step=batch * seq,
        cycles=cycles,
        losses=values,
        start_step=loop.start_step,
        final_step=int(state.step),
        first_call_s=loop.last_first_step_s,
        trace_t_start=tracing.get("t_start"),
        trace_t_stop=tracing.get("t_stop"),
        memory_peak_bytes=memory_peak_bytes(devices),
        bytes_limit=(devices[0].memory_stats() or {}).get("bytes_limit"),
    )
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
