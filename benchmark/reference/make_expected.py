"""Makes the values the benchmark holds the system to, with the plain
reference, on the host (float32, no accelerator needed):

    JAX_PLATFORMS=cpu python3 benchmark/reference/make_expected.py gpt2-small train b32x1024
    JAX_PLATFORMS=cpu python3 benchmark/reference/make_expected.py gpt2-xl serve

``train``: the mean loss of the configuration's canary batch at its
initial weights, printed and written into the configuration's file by
hand (a value per batch shape). ``serve``: for each canary prompt of the
traffic file, the reference's greedy tokens and, at each position, the
gap between its two largest logits and the chosen token's log-probability; written to
``benchmark/reference/expected/<config>.serve_canary.json``.

The weights are the ones the program serves or trains: its own init from
the configuration's ``weights_key`` (weights are data). ``--rehearsal``
does the same at the configuration's tiny rehearsal size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def load_config(name, rehearsal):
    from benchmark.harness import merge

    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))
    return merge(config, config.get("rehearsal", {})) if rehearsal else config


def init_params(config):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.gpt import GPT, GPTConfig

    model = GPT(GPTConfig(**config["gpt_config"]))
    return jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(config["weights_key"])
    )


def canary_batch(config, batch, seq):
    import numpy as np

    rng = np.random.default_rng(config["canary_key"])
    x = rng.integers(0, config["vocab_size"], (batch, seq)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def train(config, shape):
    from benchmark.reference import gpt2

    batch, seq = (int(v) for v in shape[1:].split("x"))
    x, y = canary_batch(config, batch, seq)
    loss = gpt2.mean_loss(init_params(config), x, y)
    print(json.dumps({"config": config["name"], "shape": shape, "train_canary_loss": loss}))


def serve(config, traffic_name, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import gpt2
    from benchmark.harness import merge

    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", traffic_name + ".json")))
    if rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    canary = traffic["params"]["canary"]
    params = init_params(config)
    # right-padding cannot reach an earlier position through the causal
    # mask, so one padded length (one compile) serves a prompt's every step
    step = jax.jit(lambda p, t, i: gpt2.logits(p, t)[0, i])
    rng = np.random.default_rng(config["canary_key"])
    out = []
    for length in canary["prompt_lengths"]:
        prompt = [int(t) for t in rng.integers(0, config["vocab_size"], length)]
        tokens, gaps, logprobs = [], [], []
        for _ in range(canary["max_tokens"]):
            seq = prompt + tokens
            padded = seq + [0] * (length + canary["max_tokens"] - len(seq))
            row = np.asarray(step(params, jnp.asarray([padded], jnp.int32), len(seq) - 1))
            top = np.argsort(row)[-2:]
            tokens.append(int(top[1]))
            gaps.append(float(row[top[1]] - row[top[0]]))
            row = row.astype(np.float64)
            logprobs.append(float(row[top[1]] - row.max() - np.log(np.exp(row - row.max()).sum())))
        out.append(dict(prompt=prompt, tokens=tokens, top2_gap=gaps, logprobs=logprobs))
        print(f"prompt of {length}: {tokens} min gap {min(gaps):.5f}", file=sys.stderr)
    name = config["name"] + (".rehearsal" if rehearsal else "") + ".serve_canary.json"
    path = os.path.join(ROOT, "benchmark", "reference", "expected", name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(dict(config=config["name"], traffic=traffic_name, canary=out), f)
    print(path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("what", choices=["train", "serve"])
    ap.add_argument("arg", help="train: the batch shape, as b32x1024; serve: the traffic file's name")
    ap.add_argument("--rehearsal", action="store_true")
    ns = ap.parse_args()
    config = load_config(ns.config, ns.rehearsal)
    if ns.what == "train":
        train(config, ns.arg)
    else:
        serve(config, ns.arg, ns.rehearsal)


if __name__ == "__main__":
    main()
