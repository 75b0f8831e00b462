"""Makes the values ``drivers/model_serve_closed_runs.py`` holds a served
``granite_hybrid`` configuration to, with the plain reference
(``benchmark/reference/granite_hybrid.py``):

    python3 benchmark/reference/make_expected_granite_hybrid.py granite-4.0-h-micro chat-closed-short
    ... --rehearsal                  # the configuration's tiny rehearsal size (here, on the CPU)
    ... --matrix-bits 3              # control: every matrix rounded to 3 mantissa bits first
    ... --state-dtype bfloat16       # control: the state S rounded to bf16 after every token

``teacher`` (the format ``model_serve_closed.check_teacher`` reads): fixed
random sequences and, at each of a list of prompt lengths ``n`` (log-uniform
over the traffic's prompt lengths: every prefill bucket), what the reference
predicts after the first ``n`` tokens; after every ``second_every``-th also
what follows the reference's own first token. ``runs``: for each of
``runs.count`` prompts the reference's greedy continuation of
``runs.tokens`` tokens with, at every one, its log-probability and the gap
between its two largest logits. The prompts are, of ``runs.candidates``
random ones, those whose smallest gap along the run is largest, as many
from each prefill bucket: logits here are divided by 8, so gaps are small,
and a run that stands at a near-tie somewhere compares nothing after it.
The *program* (bf16, its own cached decode) picks them and proposes each
run; the reference, which has no cache, checks a proposed run in one
forward pass over prompt + run (where its own greedy token differs it
takes its own and looks again), so every listed run is the reference's own
greedy continuation.

A control writes, beside the expected file and from it, what the reference
computed in fewer bits says at the same positions
(``...<control>.serve_canary.json``): the driver's comparison has to refuse
it (``benchmark/tests/test_metrics_granite_hybrid.py``).

The weights are the ones the program serves: its own start-up init from
``weights_key`` in the dtypes the server holds (``models/build.py:
init_params_as_consumed``; weights are data). ``old_state_share`` is
measured on the way (``reference/granite_hybrid.py: old_state_share``).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEP = 64  # contexts are right-padded to multiples of this: few shapes to compile


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--matrix-bits", type=int, default=None)
    ap.add_argument("--state-dtype", default=None, choices=["bfloat16"])
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import merge
    from benchmark.reference import granite_hybrid as ref
    from benchmark.reference.make_expected import load_config
    from dlrover_tpu.models.build import build_model, init_params_as_consumed
    from dlrover_tpu.models.generation import decode_apply, prefill_prompt

    config = load_config(ns.config, ns.rehearsal)
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json")))
    if ns.rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    p, canary = traffic["params"], traffic["params"]["canary"]
    hp = config["model"]["config"]
    vocab, lo, hi = config["vocab_size"], p["prompt_len"]["lo"], p["prompt_len"]["hi"]
    model, _ = build_model(config["model"])
    t0 = time.time()
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    print(f"{n_params} parameters made in {time.time() - t0:.0f} s", file=sys.stderr)
    control = dict(state_dtype=jnp.dtype(ns.state_dtype).type if ns.state_dtype else None,
                   matrix_bits=ns.matrix_bits)
    tag = (f"mantissa{ns.matrix_bits}" if ns.matrix_bits else "") + (f"state-{ns.state_dtype}" if ns.state_dtype else "")
    stem = os.path.join(ROOT, "benchmark", "reference", "expected",
                        config["name"] + (".rehearsal" if ns.rehearsal else ""))

    def describe(row):
        """One position's logits -> (the greedy token, the gap to the second, its log-probability)."""
        top = np.argsort(row)[-2:]
        row = row.astype(np.float64)
        return (int(top[1]), float(row[top[1]] - row[top[0]]),
                float(row[top[1]] - row.max() - np.log(np.exp(row - row.max()).sum())))

    def after(contexts, last: int = 1, rows: int = 8):
        """What the reference predicts at the last ``last`` positions of each
        of up to ``rows`` token lists, which may differ in length: padded on
        the right to one width (right-padding cannot reach an earlier
        position through a causal mask, a causal convolution or a
        recurrence). -> [[(token, gap, logprob)] * last] a context."""
        width = -(-max(len(c) for c in contexts) // STEP) * STEP
        padded = [c + [0] * (width - len(c)) for c in contexts] + [[0] * width] * (rows - len(contexts))
        x = ref.hidden(params, jnp.asarray(padded, jnp.int32), hp, **control)
        at = jnp.asarray([[len(c) - last + j for j in range(last)] for c in contexts])
        picked = x[jnp.arange(len(contexts))[:, None], at]  # [contexts, last, d]
        found = np.asarray(ref.head(picked, params["final_norm"]["scale"], params["wte"], hp["rms_norm_eps"],
                                    hp["logits_scaling"], control["matrix_bits"]))
        return [[describe(row) for row in rows_] for rows_ in found]

    def batched(contexts, last=1, rows=8):
        return [d for first in range(0, len(contexts), rows) for d in after(contexts[first:first + rows], last, rows)]

    # -- a control: the same positions, computed in fewer bits -------------------------------------
    if tag:
        expected = json.load(open(stem + ".serve_canary.json"))
        teacher = []
        for seq in expected["teacher"]:
            first = batched([seq["sequence"][:n] for n in seq["prompt_lengths"]])
            second = batched([seq["sequence"][:seq["prompt_lengths"][i]] + [seq["tokens"][i]] for i in seq["second_at"]])
            teacher.append(dict(tokens=[d[0][0] for d in first], logprobs=[d[0][2] for d in first],
                                second_tokens=[d[0][0] for d in second], second_logprobs=[d[0][2] for d in second]))
        runs = []
        for want, said in zip(expected["runs"], batched([r["prompt"] + r["tokens"][:-1] for r in expected["runs"]],
                                                        last=len(expected["runs"][0]["tokens"]))):
            runs.append(dict(tokens=[d[0] for d in said], logprobs=[d[2] for d in said]))
        with open(f"{stem}.{tag}.serve_canary.json", "w") as f:
            json.dump(dict(teacher=teacher, runs=runs, control=tag, config=config["name"],
                           device=jax.devices()[0].device_kind, seconds=round(time.time() - t0)), f)
        print(f"{stem}.{tag}.serve_canary.json")
        return

    # -- the teacher-forced first and second tokens -----------------------------------------------------
    rng = np.random.default_rng(config["canary_key"])
    spec, teacher = canary["teacher"], []
    for _ in range(spec["sequences"]):
        sequence = [int(t) for t in rng.integers(0, vocab, spec["length"])]
        lengths = sorted({int(round(n)) for n in np.exp(rng.uniform(np.log(lo), np.log(spec["length"]), spec["positions"]))})
        rows = np.asarray(ref.logits(params, jnp.asarray([sequence], jnp.int32), hp, at=[n - 1 for n in lengths]))[0]
        tokens, gaps, logprobs = zip(*(describe(row) for row in rows))
        second_at = list(range(0, len(lengths), spec["second_every"]))
        second = [d[0] for d in batched([sequence[:lengths[i]] + [tokens[i]] for i in second_at])]
        teacher.append(dict(sequence=sequence, prompt_lengths=lengths, tokens=list(tokens), top2_gap=list(gaps),
                            logprobs=list(logprobs), second_at=second_at,
                            second_tokens=[d[0] for d in second], second_top2_gap=[d[1] for d in second],
                            second_logprobs=[d[2] for d in second]))
        print(f"sequence of {len(sequence)}: {len(lengths)} positions ({len(second_at)} with a second token), "
              f"min gap {min(gaps):.5f} at {time.time() - t0:.0f} s", file=sys.stderr)

    # -- the runs: the program proposes, the reference disposes ------------------------------------------
    spec = canary["runs"]
    n_new, width = spec["tokens"], p["prompt_width"]

    @jax.jit
    def propose(params, tokens, mask):
        """The program's own greedy run after left-padded prompts (its cached
        decode in the served dtypes) -> (tokens [B, n], top-2 gaps [B, n])."""
        cache, logits, pos, kv_valid = prefill_prompt(model, params, tokens, mask)

        def step(carry, t):
            cache, kv_valid, logits, pos = carry
            top, idx = jax.lax.top_k(logits, 2)
            kv_valid = kv_valid | (jnp.arange(kv_valid.shape[1])[None, :] == width + t)
            out, cache = decode_apply(model, params, cache, idx[:, :1], (pos + 1)[:, None], kv_valid)
            return (cache, kv_valid, out[:, 0].astype(jnp.float32), pos + 1), (idx[:, 0], top[:, 0] - top[:, 1])

        _, (toks, gaps) = jax.lax.scan(step, (cache, kv_valid, logits, pos), jnp.arange(n_new))
        return toks.T, gaps.T

    lengths = [int(round(n)) for n in np.exp(rng.uniform(np.log(lo), np.log(hi), spec["candidates"]))]
    prompts = [[int(t) for t in rng.integers(0, vocab, n)] for n in lengths]
    proposed = []
    for first in range(0, len(prompts), spec["batch"]):
        some = prompts[first:first + spec["batch"]]
        some = some + [some[0]] * (spec["batch"] - len(some))
        toks = np.zeros((len(some), width), np.int32)
        mask = np.zeros((len(some), width), bool)
        for i, prompt in enumerate(some):
            toks[i, width - len(prompt):], mask[i, width - len(prompt):] = prompt, True
        run_tokens, run_gaps = (np.asarray(a) for a in propose(params, jnp.asarray(toks), jnp.asarray(mask)))
        proposed += [(float(g.min()), [int(t) for t in r]) for r, g in zip(run_tokens, run_gaps)][:len(prompts) - first]
    print(f"{len(proposed)} runs proposed at {time.time() - t0:.0f} s; smallest gaps' quartiles "
          f"{np.percentile([g for g, _ in proposed], [25, 50, 75]).round(4).tolist()}", file=sys.stderr)
    buckets = sorted({max(8, width // 4), max(8, width // 2), width})  # the engine's (ContinuousBatchingEngine._bucket_width)
    bucket_of = lambda n: next(b for b in buckets if n <= b)  # noqa: E731
    chosen = []
    for b in buckets:
        here = sorted((i for i in range(len(prompts)) if bucket_of(lengths[i]) == b), key=lambda i: -proposed[i][0])
        chosen += here[:-(-spec["count"] // len(buckets))]
    runs = [dict(prompt=prompts[i], tokens=list(proposed[i][1])) for i in chosen]
    for _ in range(n_new + 1):  # each pass settles at least one more token of every unsettled run
        said = batched([r["prompt"] + r["tokens"][:-1] for r in runs], last=n_new)
        moved = 0
        for r, row in zip(runs, said):
            differs = [j for j in range(n_new) if row[j][0] != r["tokens"][j]]
            if differs:  # the reference's own token there; what follows is looked at again
                r["tokens"][differs[0]], moved = row[differs[0]][0], moved + 1
            else:
                r.update(top2_gap=[d[1] for d in row], logprobs=[d[2] for d in row])
        print(f"{len(runs)} runs checked, {moved} moved, at {time.time() - t0:.0f} s", file=sys.stderr)
        if not moved:
            break
    else:
        raise SystemExit("the runs did not settle")

    # -- what a Mamba layer's output owes to old state (the init's witness) -------------------------
    share = {}
    if not ns.rehearsal:
        tokens = jnp.asarray(rng.integers(0, vocab, (2, 256)), jnp.int32)
        kinds = ref.layer_types(hp)
        for layer in [i for i in (0, 4, 20, 39) if i < len(kinds) and kinds[i] == "mamba"]:
            share[f"layer_{layer}"] = ref.old_state_share(params, tokens, hp, 64, layer)
        print(f"old_state_share {share} at {time.time() - t0:.0f} s", file=sys.stderr)

    with open(stem + ".serve_canary.json", "w") as f:
        json.dump(dict(teacher=teacher, runs=runs, old_state_share_older_than_64=share, config=config["name"],
                       traffic=ns.traffic, n_params=n_params, device=jax.devices()[0].device_kind,
                       seconds=round(time.time() - t0)), f)
    print(stem + ".serve_canary.json")


if __name__ == "__main__":
    main()
