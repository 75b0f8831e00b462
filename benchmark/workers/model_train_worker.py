"""Worker of the driver ``model_train_cycles``, launched through
``tpurun``: ``train_worker.py``'s cycles and stamps for *any* model the
program can build. The model comes from the configuration's ``model``
entry through ``dlrover_tpu.models.build.build_model``; this file names
no model class, so the next configuration needs no worker.

What differs from ``train_worker.py`` (whose ``emit`` and ``NoSaveEngine``
are imported, not copied):

- the step is built with ``return_metrics``: what the model sows per step
  (routing counters, the second loss) and the gradient's norm come back
  with the loss. The wrapper around the step keeps them aside, still as
  device arrays, and hands ``ElasticTrainLoop`` the loss alone; they are
  read and booked (the model's own ``book_step_counters``, into
  ``observability/spans.py``'s accumulator) at the sync that ends a
  cycle, never inside a segment;
- every batch is drawn over the configuration's whole vocabulary;
- the cells sync, they do not save (``save_every`` must be 0).

The stamps, the ``step_dispatch`` annotation and the events are
``train_worker.py``'s, so the readers of the other training cells read
this one too; the ``window`` event also carries ``first_step`` (the first
step's own numbers, at the initial weights on the canary batch),
``counters`` (the window's totals) and ``counters_traced`` (the totals of
the steps the device trace holds: a kernel's time in the trace is set
against the work of those steps, not of the window's).
"""

import sys
import time

from benchmark.workers.train_worker import SPEC, NoSaveEngine, emit


def main() -> int:
    t_boot = time.time()
    config, traffic = SPEC["config"], SPEC["traffic"]
    params_t = traffic["params"]
    if params_t["save_every"]:
        print("model_train_worker: this worker's cells sync, they do not save", file=sys.stderr)
        return 5
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
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.observability.spans import process_accumulator
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, choose_mesh_shape
    from dlrover_tpu.parallel.sharding import DEFAULT_RULES, data_sharding_for
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    model, loss_fn = build_model(config["model"])
    # the model books its own counters (it knows what it sows)
    book = getattr(model, "book_step_counters", None)
    batch, seq = params_t["batch"], params_t["seq"]
    n_steps = params_t["steps_per_cycle"]
    if params_t.get("mesh", "dp") == "choose_mesh_shape":
        mesh = build_mesh(choose_mesh_shape(len(devices)), devices)
    else:
        mesh = build_mesh(MeshConfig(dp=-1), devices)
    tx = default_optimizer(
        learning_rate=params_t["learning_rate"], warmup_steps=params_t["warmup_steps"])
    tokens = jnp.zeros((batch, seq), jnp.int32)
    state, shardings = init_train_state(
        model, tokens, mesh, tx, rng=jax.random.PRNGKey(config["weights_key"]))
    step_fn = build_train_step(model, tx, loss_fn, mesh, shardings, return_metrics=True)

    sharding = data_sharding_for(np.zeros((batch, seq), np.int32), mesh, DEFAULT_RULES)

    def place(x):
        return jax.device_put(x, sharding), jax.device_put(np.roll(x, -1, axis=1), sharding)

    def draw(rng):
        return place(rng.integers(0, config["vocab_size"], (batch, seq)).astype(np.int32))

    canary = draw(np.random.default_rng(config["canary_key"]))
    rng = np.random.default_rng(SPEC["seed"])
    batches = [draw(rng) for _ in range(params_t["distinct_batches"])]

    # Whether a Pallas kernel is in the step is read off the lowered text,
    # which costs a second trace of the whole step: traced runs only.
    kernel = None
    if SPEC["trace"]:
        kernel = "tpu_custom_call" in step_fn.lower(state, *canary).as_text()
    emit(
        "built",
        n_params=sum(l.size for l in jax.tree.leaves(state.params)),
        batch=batch, seq=seq, mesh={k: int(v) for k, v in mesh.shape.items()},
        tpu_custom_call=kernel,
        state_bytes=sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state)),
        family=config["model"]["family"],
    )

    tracing = {"on": False, "done": not SPEC["trace"], "dir": SPEC["trace_dir"]}
    warmup_cycles = params_t["warmup_cycles"]
    max_cycles = params_t["max_cycles"]
    trace_cycles = params_t.get("trace_cycles", 2)
    seconds = float(SPEC["seconds"])
    cycles = []  # one dict per cycle, warm-up ones included
    mark = {"seg_start": None, "t_open": None, "in_window": 0, "counters_at_open": {}}
    losses, pending, first_step = [], [], {}
    acc = process_accumulator()

    def read_pending():
        """Book the counters of the steps that have ended. Called right
        after a sync, so the arrays are there: no wait happens here."""
        for metrics in jax.device_get(pending):  # one transfer for the cycle's steps
            counters = book(metrics) if book else {}
            if not first_step:
                first_step.update(counters, grad_norm=float(metrics["grad_norm"]))
        pending.clear()

    def since_open():
        at_open = mark["counters_at_open"]
        return {k: v - at_open.get(k, 0) for k, v in acc.counters().items()}

    def stop_tracing():
        """The trace opened with the window, at a sync: what was booked
        since is what its steps did."""
        tracing["t_stop"] = time.time()
        reduce_trace.stop_trace()
        tracing["on"], tracing["done"] = False, True
        tracing["counters"] = since_open()

    def end_cycle(now, step):
        """Stamps of one finished cycle; opens, extends or closes the window."""
        read_pending()
        cycles.append(dict(index=len(cycles), step=step, seg_start=mark["seg_start"],
                           t_ready=now, t_ret=now, ok=True))
        if len(cycles) == warmup_cycles:
            mark["t_open"] = now
            mark["counters_at_open"] = acc.counters()
            if not tracing["done"]:
                reduce_trace.start_trace(tracing["dir"])
                tracing["on"] = True
                tracing["t_start"] = time.time()
        elif len(cycles) > warmup_cycles:
            mark["in_window"] += 1
            if tracing["on"] and mark["in_window"] >= trace_cycles:
                stop_tracing()
            fastest = min(c["t_ret"] - c["seg_start"] for c in cycles[warmup_cycles:])
            elapsed = time.time() - mark["t_open"]
            if mark["in_window"] >= max_cycles or elapsed + fastest > seconds:
                loop.request_stop()
        mark["seg_start"] = time.time()  # after any profiler work: not in a segment

    def traced_step(s, *b):
        with jax.profiler.TraceAnnotation("step_dispatch"):
            s, (loss, metrics) = step_fn(s, *b)
        pending.append(metrics)
        return s, loss

    def on_step(step, loss):
        losses.append(loss)
        if mark["seg_start"] is None:  # the compiling step: cycle 0, no segment
            jax.block_until_ready(loss)
            now = time.time()
            read_pending()
            cycles.append(dict(index=0, step=step, seg_start=None,
                               t_ready=now, t_ret=now, ok=True))
            mark["seg_start"] = time.time()
        elif step % n_steps == 0:
            jax.block_until_ready(loss)  # the step's state is ready with its loss
            end_cycle(time.time(), step)

    loop = ElasticTrainLoop(
        NoSaveEngine(),
        traced_step,
        ctx=ctx,
        max_steps=(warmup_cycles + max_cycles + 2) * n_steps,
        memory_every=n_steps,
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
        stop_tracing()
    # the window's counters: booked at the syncs of the cycles after its opening
    last_counted = cycles[-1]["step"] if cycles else 0
    emit(
        "window",
        t_loop=t_loop,
        t_open=mark["t_open"],
        warmup_cycles=warmup_cycles,
        steps_per_cycle=n_steps,
        tokens_per_step=batch * seq,
        cycles=cycles,
        losses=[float(l) for l in losses],
        start_step=loop.start_step,
        final_step=int(state.step),
        first_call_s=loop.last_first_step_s,
        trace_t_start=tracing.get("t_start"),
        trace_t_stop=tracing.get("t_stop"),
        memory_peak_bytes=memory_peak_bytes(devices),
        bytes_limit=(devices[0].memory_stats() or {}).get("bytes_limit"),
        first_step=first_step,
        counters=since_open(),
        counters_traced=tracing.get("counters"),
        counted_through_step=last_counted,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
