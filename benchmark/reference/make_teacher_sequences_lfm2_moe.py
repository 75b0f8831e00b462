"""Makes the token sequences of the teacher-forced comparison
(``drivers/model_serve_closed.py: check_teacher``) for a served ``lfm2_moe``
configuration: sequences at every token of which every router's choice is
decided by a wide margin.

    python3 benchmark/reference/make_teacher_sequences_lfm2_moe.py lfm2-24b-a2b-l10 rollout-closed-16-long
    ... --rehearsal

Why. At random weights a router's top ``num_experts_per_tok`` of 64 scores
is a near-tie at most tokens (the gap between the last chosen and the first
left out is ~0.003 at the median of a layer, ~0.0005 at the smallest of a
token's eight layers), bf16 activations move a score by about as much, and a
token whose choice falls the other way is 8-23% off the float32 reference
from there on, with its neighbours through the convolutions. Over random
prompts that noise is as large as what rounding the experts to 8 bits adds,
and no limit separates the two (PERF.md section 6, PR 32). So the prompts
are *chosen*: token by token, of ``candidates`` random ids the one whose
smallest margin over the expert layers is largest (~0.02), given the tokens
chosen before it. Nothing else about a token is looked at.

How. The program's own model proposes (its decode mode with a cache, one
step a token for all candidates at once, each beside the same prefix; bf16,
so a margin here is an estimate), and the plain float32 reference disposes:
``make_expected_lfm2_moe.py`` reads the sequences as data, computes its own
margins and writes them beside what it expects (``router_margin_min``).
Weights from ``weights_key`` as the server makes them; candidates from
``canary_key``.
"""

import argparse
import functools
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
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import merge
    from benchmark.reference.make_expected import load_config
    from dlrover_tpu.models.build import build_model, init_params_as_consumed
    from dlrover_tpu.models.generation import init_cache

    config = load_config(ns.config, ns.rehearsal)
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json")))
    if ns.rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    teacher = traffic["params"]["canary"]["teacher"]
    length, n_candidates = teacher["length"], teacher["candidates"]
    entry = dict(config["model"], config=dict(config["model"]["config"], max_seq_len=length))
    model, _ = build_model(entry)
    cfg = model.config
    t0 = time.time()
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    expert_layers = [i for i in range(cfg.num_hidden_layers) if cfg.is_expert_block(i)]

    @functools.partial(jax.jit, donate_argnums=1)
    def step(params, cache, key, t):
        """One more token: every row holds the tokens chosen so far; each
        tries another id; the row with the widest smallest margin is kept
        and copied to all rows."""
        tried = jax.random.randint(jax.random.fold_in(key, t), (n_candidates,), 0, cfg.vocab_size)
        _, mut = model.apply(
            {"params": params, "cache": cache}, tried[:, None], decode=True,
            positions=jnp.full((n_candidates, 1), t, jnp.int32),
            kv_valid=jnp.broadcast_to(jnp.arange(length)[None] <= t, (n_candidates, length)),
            mutable=["cache", "intermediates"],
            capture_intermediates=lambda module, _: module.name == "ffn_norm")
        margins = []
        for i in expert_layers:
            h = mut["intermediates"][f"block_{i}"]["ffn_norm"]["__call__"][0][:, 0].astype(jnp.float32)
            moe = params[f"block_{i}"]["moe"]
            with jax.default_matmul_precision("highest"):
                scores = jax.nn.sigmoid(h @ moe["w_router"].astype(jnp.float32))
            if "expert_bias" in moe:
                scores = scores + moe["expert_bias"]
            top, _ = jax.lax.top_k(scores, cfg.num_experts_per_tok + 1)
            margins.append(top[:, -2] - top[:, -1])
        smallest = jnp.min(jnp.stack(margins), axis=0)
        kept = jnp.argmax(smallest)
        cache = jax.tree.map(lambda a: jnp.broadcast_to(a[kept], a.shape) if a.ndim else a, mut["cache"])
        return cache, tried[kept], smallest[kept]

    sequences, margins = [], []
    for s in range(teacher["sequences"]):
        cache, key = init_cache(model, n_candidates), jax.random.PRNGKey(config["canary_key"] + s)
        tokens, smallest = [], []
        for t in range(length):
            cache, token, margin = step(params, cache, key, jnp.int32(t))
            tokens.append(token), smallest.append(margin)
        sequences.append([int(t) for t in jax.device_get(tokens)])
        margins.append(float(np.min(jax.device_get(smallest))))
        print(f"sequence {s}: {length} tokens, the smallest margin (program, bf16) {margins[-1]:.5f}, "
              f"median {float(np.median(jax.device_get(smallest))):.5f} at {time.time() - t0:.0f} s",
              file=sys.stderr)
    name = config["name"] + (".rehearsal" if ns.rehearsal else "") + ".teacher_sequences.json"
    path = os.path.join(ROOT, "benchmark", "reference", "expected", name)
    with open(path, "w") as f:
        json.dump(dict(config=config["name"], traffic=ns.traffic, candidates=n_candidates,
                       device=jax.devices()[0].device_kind, seconds=round(time.time() - t0),
                       program_margin_min=margins, sequences=sequences), f)
    print(path)


if __name__ == "__main__":
    main()
