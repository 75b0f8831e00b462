"""Expected values of a ``sdar_moe`` configuration's served cell, and the
readings its limits are set from. One script, run on the chip through the
chip tool (``--rehearsal``: here on the CPU at the configuration's toy size)::

    python3 benchmark/reference/make_expected_sdar_moe.py <config> <traffic>            # the expected file
    ... --matrix-bits 3 | --causal      # the REFERENCE under a control, judged against the expected file
    ... --engine served|mantissa3|mask-causal|scratch-kept   # the ENGINE's own answers, judged likewise

**The expected file** (``expected/<config>[.rehearsal].serve_canary.json``):
``runs``, each a prompt (a prefix of one of a few fixed sequences drawn from
the configuration's ``canary_key`` below the traffic's ``ids_below``) and the
plain reference's answer of ``tokens`` new tokens by whole recomputation
(``reference/sdar_moe.py: generate``, float32 at ``highest``; the sequence
padded behind the block at work to a multiple of 256, which no position
sees): tokens, log-probabilities, passes, and for every token the two gaps a
comparison needs to tell a fault from a near-tie (``top2_gap``,
``select_gap``). ``candidates`` prompts are answered, their lengths
log-uniform over the traffic's prompt lengths and taking every remainder of
the block length in turn, and of each remainder the ``count / block_length``
whose smallest gap is largest are kept: at random weights the two largest of
151,936 logits stand ~0.2 apart at the median, and a run of 12 meets a
near-tie somewhere.

**The readings** (``expected/<config>.readings.json``, one key a control):
what was served or computed for the same prompts, and ``judge_blocks``'s
numbers under limits wide enough to compare everything
(``READING_LIMITS``); ``benchmark/tests/test_metrics_sdar_moe.py`` judges the
same answers again under the traffic file's limits. The engine's controls:
``mantissa3`` (every matrix of the held tree cut to 3 mantissa bits),
``mask-causal`` (``layers._block_mask`` causal inside a block too) and
``scratch-kept`` (``serving._block_final``: a block final at the pass that
decides its last position, its keys and values those of a pass that saw the
mask token there).
"""

import argparse
import json
import math
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "benchmark", "expected")
HERE = os.path.join(ROOT, "benchmark", "reference", "expected")
READING_LIMITS = dict(gap_tolerance=0.15, median_logprob_tolerance=None, later_min_compared=0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--matrix-bits", type=int, default=None)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--engine", default="", choices=["", "served", "mantissa3", "mask-causal", "scratch-kept"])
    ns = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.drivers.model_serve_closed_blocks import judge_blocks
    from benchmark.reference import sdar_moe as ref
    from dlrover_tpu.models.build import build_model, init_params_as_consumed

    t0 = time.time()
    config = harness.load_json(os.path.join(ROOT, "benchmark", "configs", ns.config + ".json"))
    traffic = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json"))
    if ns.rehearsal:
        config = harness.merge(config, config.get("rehearsal", {}))
        traffic = harness.merge(traffic, traffic.get("rehearsal", {}))
    p, hp = traffic["params"], config["model"]["config"]
    ask, Bl = p["canary"]["blocks"], hp["block_length"]
    model, _ = build_model(config["model"])
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    name = ns.config + (".rehearsal" if ns.rehearsal else "")
    where = f"{jax.devices()[0].device_kind}, jax {jax.__version__}"
    out_dir = HERE if ns.rehearsal else OUT
    os.makedirs(out_dir, exist_ok=True)

    def answer(prompt, **control):
        got = ref.generate(params, prompt, ask["tokens"], hp, pad_to=-(-(len(prompt) + 2 * Bl + ask["tokens"]) // 256) * 256,
                           **control)
        return {k: got[k] for k in ("tokens", "logprobs", "passes", "top2_gap", "select_gap")}

    if not ns.engine and ns.matrix_bits is None and not ns.causal:
        rng = random.Random(config["canary_key"])
        below = min(p.get("ids_below", config["vocab_size"]), config["vocab_size"])
        lo, hi = p["prompt_len"]["lo"], p["prompt_len"]["hi"]
        sequences = [[rng.randrange(below) for _ in range(hi)] for _ in range(ask["sequences"])]
        by_rest = {}
        for i in range(ask["candidates"]):
            n = int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
            n = min(max(n - n % Bl + i % Bl, max(lo, 1)), hi)  # every remainder of the block length in turn
            run = dict(prompt=sequences[i % len(sequences)][:n])
            run.update(answer(run["prompt"]))
            run["smallest_gap"] = min(run["top2_gap"] + run["select_gap"])
            by_rest.setdefault(n % Bl, []).append(run)
            print(f"candidate {i}: {n} tokens, smallest gap {run['smallest_gap']:.4f}, {time.time() - t0:.0f} s", flush=True)
        keep = max(1, ask["count"] // len(by_rest))
        runs = [r for rest in sorted(by_rest) for r in sorted(by_rest[rest], key=lambda r: -r["smallest_gap"])[:keep]]
        body = dict(
            made_by=f"benchmark/reference/make_expected_sdar_moe.py {ns.config} {ns.traffic}" + (" --rehearsal" if ns.rehearsal else ""),
            where_it_ran=where, seconds=round(time.time() - t0), block_length=Bl, denoising_steps=hp["denoising_steps"],
            candidates=ask["candidates"], smallest_gaps_kept=sorted(round(r["smallest_gap"], 4) for r in runs),
            smallest_gaps_of_all=sorted(round(r["smallest_gap"], 4) for rs in by_rest.values() for r in rs),
            runs=runs)
        path = os.path.join(out_dir, name + ".serve_canary.json")
        json.dump(body, open(path, "w"))
        print("wrote", path, "runs", len(runs), "prompt lengths", [len(r["prompt"]) for r in runs])
        return 0

    runs = harness.load_json(os.path.join(HERE, name + ".serve_canary.json"))["runs"]
    if ns.engine:
        from dlrover_tpu.models import layers, serving
        from dlrover_tpu.models.generation import SamplingConfig

        control = ns.engine
        if control == "mantissa3":
            params = jax.jit(lambda t: jax.tree_util.tree_map(  # donated: two trees do not fit the chip
                lambda a: ref.round_bits(a, 3) if a.ndim >= 2 else a, t), donate_argnums=0)(params)
        elif control == "mask-causal":
            def causal_inside_a_block(kv_valid, slots_bt, block_length):
                rank = jnp.cumsum(kv_valid, axis=1, dtype=jnp.int32) - 1
                own = jnp.take_along_axis(rank, slots_bt, axis=1)
                return kv_valid[:, None, :] & (rank[:, None, :] <= own[:, :, None])
            layers._block_mask = causal_inside_a_block
        elif control == "scratch-kept":
            serving._block_final = lambda _in, out: ~jnp.any(out, axis=1)
        eng = serving.ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=p["max_new_tokens"], temperature=0.0),
            batch_size=p["batch_size"], prompt_width=p["prompt_width"])
        uids = [eng.submit(r["prompt"], max_new_tokens=len(r["tokens"])) for r in runs]
        done = {c.uid: c for c in eng.run()}
        got = [[done[u].tokens, done[u].logprobs, done[u].passes] for u in uids]
        extra = dict(slots=p["batch_size"], counters={k: v for k, v in eng.phases.split().summary().items()
                                                      if k.startswith(("block.", "moe."))})
    else:
        control = "reference-" + ("causal" if ns.causal else f"mantissa{ns.matrix_bits}")
        got = []
        for i, r in enumerate(runs):
            a = answer(r["prompt"], matrix_bits=ns.matrix_bits, causal=ns.causal)
            got.append([a["tokens"], a["logprobs"], a["passes"]])
            print(f"run {i}: {time.time() - t0:.0f} s", flush=True)
        extra = {}
    ok, judged = judge_blocks(got, runs, READING_LIMITS, Bl)
    path = os.path.join(out_dir, name + ".readings.json")
    seen = os.path.join(HERE, name + ".readings.json")
    readings = harness.load_json(path) if os.path.exists(path) else (
        harness.load_json(seen) if os.path.exists(seen) else {})
    readings[control] = dict(runs=got, judged=judged, blocks_ok=ok, device=where, seconds=round(time.time() - t0), **extra)
    json.dump(readings, open(path, "w"))
    print(control, "ok" if ok else "REFUSED", json.dumps({k: v for k, v in judged.items() if k != "blocks_limits"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
