"""Makes the values ``drivers/model_serve_closed.py`` holds a served
``lfm2_moe`` configuration to, with the plain reference
(``benchmark/reference/lfm2_moe.py``):

    JAX_PLATFORMS=cpu python3 benchmark/reference/make_expected_lfm2_moe.py lfm2-24b-a2b-l10 rollout-closed-16-long
    ... --rehearsal                      # the configuration's tiny rehearsal size
    ... --expert-dtype float8_e4m3fn     # the control: experts held in 8 bits (written beside; the
                                         # driver's comparison has to refuse it: benchmark/tests)

``teacher``: fixed sequences of tokens and, at each of a list of prompt
lengths ``n``, what the reference predicts after the first ``n`` tokens
(token, gap between its two largest logits, log-probability): one forward
pass a sequence gives every position, and the driver asks the server for
one token after each prefix, so no position depends on what the server
wrote before it. After every ``second_every``-th prefix also what follows
the reference's own first token (``second_*``: the server's first decode
step, which reads the state and the cache its prefill left), a forward pass
each. The sequences are data, chosen by
``make_teacher_sequences_lfm2_moe.py`` so that no router's choice along
them is a near-tie; the reference's own smallest margin is written beside
what it expects.

The weights are the ones the program serves: its own start-up init from
``weights_key`` in the dtypes the server holds (``models/build.py:
init_params_as_consumed``; weights are data). The reference has no cache and
computes every expert for every token; the configuration's file says where
it ran and how long it took.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--expert-dtype", default=None, choices=["bfloat16", "float8_e4m3fn"])
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import merge
    from benchmark.reference import lfm2_moe as ref
    from benchmark.reference.make_expected import load_config
    from dlrover_tpu.models.build import build_model, init_params_as_consumed

    config = load_config(ns.config, ns.rehearsal)
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json")))
    if ns.rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    canary = traffic["params"]["canary"]
    hp = config["model"]["config"]
    model, _ = build_model(config["model"])
    t0 = time.time()
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    print(f"{n_params} parameters made in {time.time() - t0:.0f} s", file=sys.stderr)
    expert_dtype = jnp.dtype(ns.expert_dtype).type if ns.expert_dtype else None

    def describe(row):
        """One position's logits -> (the greedy token, the gap to the second, its log-probability)."""
        top = np.argsort(row)[-2:]
        row = row.astype(np.float64)
        return (int(top[1]), float(row[top[1]] - row[top[0]]),
                float(row[top[1]] - row.max() - np.log(np.exp(row - row.max()).sum())))

    given = json.load(open(os.path.join(ROOT, "benchmark", "reference", "expected", config["name"] + (
        ".rehearsal" if ns.rehearsal else "") + ".teacher_sequences.json")))["sequences"]

    def after(contexts, rows=8, step=128):
        """What the reference predicts after each of up to ``rows`` token
        lists, which may differ in length: padded to one width, a multiple
        of ``step`` (few shapes to compile; right-padding cannot reach an
        earlier position through the causal mask or the causal convolution)."""
        width = -(-max(len(c) for c in contexts) // step) * step
        padded = [c + [0] * (width - len(c)) for c in contexts] + [[0] * width] * (rows - len(contexts))
        x = ref.hidden(params, jnp.asarray(padded, jnp.int32), hp, expert_dtype)
        last = x[jnp.arange(len(contexts)), jnp.asarray([len(c) - 1 for c in contexts])]
        found = ref.head(last[:, None], params["embedding_norm"]["scale"], params["wte"], hp["norm_eps"])
        return [describe(row) for row in np.asarray(found)[:, 0]]

    def teacher_part():
        teacher = canary["teacher"]
        rng = np.random.default_rng(config["canary_key"])
        lo = traffic["params"]["prompt_len"]["lo"]
        out = []
        for sequence in given[:teacher["sequences"]]:
            lengths = sorted({int(round(n)) for n in
                              np.exp(rng.uniform(np.log(lo), np.log(len(sequence)), teacher["positions"]))})
            margins = []
            rows = np.asarray(ref.logits(params, jnp.asarray([sequence], jnp.int32), hp, expert_dtype,
                                         at=[n - 1 for n in lengths], margins=margins))[0]
            tokens, gaps, logprobs = zip(*(describe(row) for row in rows))
            smallest = np.min(np.stack([np.asarray(m)[0] for m in margins]), axis=0)  # [T]: over the layers
            # every ``second_every``-th prefix goes on for one decode step: the reference's
            # own first token appended, a forward pass each, eight at a time (not for the
            # control: the precision's limit reads the first tokens)
            second_at = [] if expert_dtype else list(range(0, len(lengths), teacher["second_every"]))
            contexts = [sequence[:lengths[i]] + [tokens[i]] for i in second_at]
            second = [d for first in range(0, len(contexts), 8) for d in after(contexts[first:first + 8])]
            out.append(dict(sequence=sequence, prompt_lengths=lengths, tokens=list(tokens),
                            top2_gap=list(gaps), logprobs=list(logprobs), second_at=second_at,
                            second_tokens=[d[0] for d in second], second_top2_gap=[d[1] for d in second],
                            second_logprobs=[d[2] for d in second],
                            router_margin_min=float(smallest.min()),
                            router_margin_quartiles=[float(q) for q in np.percentile(smallest, [25, 50, 75])]))
            print(f"sequence of {len(sequence)}: {len(lengths)} positions ({len(second_at)} with a second "
                  f"token), min gap {min(gaps):.5f}, the smallest router margin {smallest.min():.5f} "
                  f"(median {np.median(smallest):.5f}) at {time.time() - t0:.0f} s", file=sys.stderr)
        return out

    name = config["name"] + (".rehearsal" if ns.rehearsal else "") + (
        f".{ns.expert_dtype}" if ns.expert_dtype else "") + ".serve_canary.json"
    path = os.path.join(ROOT, "benchmark", "reference", "expected", name)
    with open(path, "w") as f:
        json.dump(dict(teacher=teacher_part(), config=config["name"], traffic=ns.traffic, n_params=n_params,
                       device=jax.devices()[0].device_kind, seconds=round(time.time() - t0)), f)
    print(path)


if __name__ == "__main__":
    main()
