"""What it costs the serving engine to hold its matrices rounded.

    python scripts/serve_params_cast_cost.py [--size xl|small|tiny] [--slots 4]
        [--out FILE]

``ContinuousBatchingEngine`` takes the parameters as a trainer holds them
(float32) and keeps the tree its programs read: each matrix rounded once to
the model's compute dtype (``serve.params_cast``). One process, on whatever
device JAX finds, builds the model at ``--size`` with its own init, and
says in one JSON line:

- ``startup``: the device's bytes in use and its peak before the engine,
  after it (the float32 tree beside its rounded twin) and after the float32
  tree was dropped; the span's own stats and seconds (the enqueue) and the
  seconds until the rounded tree is ready;
- ``serving``: the same two readings after a first stream of requests
  compiled and ran the prefill, admit and chunk programs;
- ``swap_blocking``: ``set_params`` of a float32 host payload (what a
  ``WeightBus`` push delivers) on an idle engine: its latency, the peak;
- ``swap_async``: ``set_params_async`` of the same payload between the
  rounds of a running stream: how long the call held the driver, the
  longest round before, while the swap was pending and after, the rounds it
  was pending for, the adopted swap's latency, the peak, and whether the
  tokens after adoption equal those of the blocking swap (same weights).

The peak is the device's high-water mark since the process began, so each
phase reads the largest of what came before it. The numbers are wanted
from the chip: ``chiprun -- python scripts/serve_params_cast_cost.py``; on
the CPU backend only the plumbing is rehearsed (``--size tiny``).
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def memory(device) -> dict:
    m = device.memory_stats() or {}
    return {k: m.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=["xl", "small", "tiny"], default="xl")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--out", default="chiprun_out/serve_params_cast_cost.json")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine
    from dlrover_tpu.observability import spans

    cfg = {"xl": GPTConfig.gpt2_xl, "small": GPTConfig.gpt2_small, "tiny": GPTConfig.tiny}[ns.size]()
    cfg = dataclasses.replace(cfg, use_remat=False)
    width, new = (512, 128) if ns.size != "tiny" else (16, 12)
    model = GPT(cfg)
    device = jax.devices()[0]
    out = dict(device=dict(platform=device.platform, kind=device.device_kind), size=ns.size, slots=ns.slots)

    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    jax.block_until_ready(params)
    host = jax.tree.map(np.asarray, jax.device_get(params))  # a push's payload
    before = memory(device)

    acc = spans.process_accumulator()
    t0 = time.perf_counter()
    eng = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=new, temperature=0.0),
        batch_size=ns.slots, prompt_width=width, decode_chunk=8,
    )
    t_built = time.perf_counter() - t0
    jax.block_until_ready(eng.params)
    t_ready = time.perf_counter() - t0
    beside = memory(device)
    del params
    stat = acc.stats()["serve.params_cast"]
    cast_s, cast_n = stat.total_s, stat.count
    out["startup"] = dict(
        before=before, float32_beside_rounded=beside, float32_dropped=memory(device),
        engine_built_s=t_built, rounded_ready_s=t_ready,
        span_s=cast_s, span_count=cast_n,
        params_device_bytes=eng.stats()["params_device_bytes"],
        params_casts=eng.stats()["params_casts"],
        float32_leaves_left=sum(
            leaf.dtype == jnp.float32 and leaf.ndim >= 2 for leaf in jax.tree.leaves(eng.params)),
    )

    rng = np.random.default_rng(0)

    def prompts(n):
        return [[int(t) for t in rng.integers(1, cfg.vocab_size, rng.integers(width // 8, width))]
                for _ in range(n)]

    stream = prompts(2 * ns.slots)
    t0 = time.perf_counter()
    first = eng.run(stream)
    out["serving"] = dict(memory=memory(device), first_stream_s=time.perf_counter() - t0,
                          tokens=sum(len(c.tokens) for c in first))

    lat = eng.set_params(host)
    again = eng.run(stream)
    out["swap_blocking"] = dict(
        latency_s=lat, memory=memory(device), params_casts=eng.stats()["params_casts"],
        same_tokens_as_before=[c.tokens for c in again] == [c.tokens for c in first],
        span_s=stat.total_s - cast_s,
    )

    for p in stream:
        eng.submit(p)
    key = jax.random.PRNGKey(1)
    rounds, call_s, pending_rounds, n = dict(before=[], pending=[], after=[]), None, 0, 0
    while eng.pending:
        key, sub = jax.random.split(key)
        if n == 6:
            t0 = time.perf_counter()
            eng.set_params_async(host)
            call_s = time.perf_counter() - t0
        phase = ("before" if call_s is None
                 else "pending" if eng.stats()["swap_pending"] else "after")
        t0 = time.perf_counter()
        eng.step(sub)
        rounds[phase].append(time.perf_counter() - t0)
        pending_rounds += phase == "pending"
        n += 1
    done = eng.drain_completions()
    out["swap_async"] = dict(
        call_held_driver_s=call_s, pending_rounds=pending_rounds,
        longest_round_s={k: max(v) if v else None for k, v in rounds.items()},
        median_round_s={k: sorted(v)[len(v) // 2] if v else None for k, v in rounds.items()},
        latency_s=eng.swap_latency_s, memory=memory(device),
        params_casts=eng.stats()["params_casts"], swap_failures=eng.swap_failures,
        last_swap_error=eng.last_swap_error,
        same_tokens_as_before=[c.tokens for c in done] == [c.tokens for c in first],
    )
    line = json.dumps(out)
    if ns.out:
        os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
        with open(ns.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
