"""Makes the values ``benchmark/configs/<config>.json`` holds a
``model_train_cycles_trunk`` cell to, with the plain reference
(``benchmark/reference/mellum.py``), one batch row at a time:

    python3 benchmark/reference/make_expected_mellum.py mellum2-12b-a2.5b-ep4-l4 b2x8192 --write
    ... --rehearsal b2x32 --write   # the configuration's tiny rehearsal size, on the CPU
    ... --dtype float8_e4m3fn       # control: matrices rounded to 8 bits, bf16 products
    ... --no-window                 # control: every layer causal
    ... --no-yarn                   # control: the full layers on the default RoPE table

At the initial weights (the program's own init from ``weights_key``:
weights are data) on the canary batch: the loss, the global norm of the
gradient and, per layer, the assignments that landed on the experts held.
Printed as one JSON line and kept under ``chiprun_out/benchmark/expected/``;
``--write`` (the float32 reference only) also writes
``expected.first_step.values`` and ``expected.train_canary_loss.values``
into the configuration's file, under its ``rehearsal`` with ``--rehearsal``.
The full size runs on the chip, through the chip tool (float32 at
``highest`` precision there: about a minute with its compile); what it
wrote is then under ``chiprun_out/`` and is written into the file with
``--from <that file> --write``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def compute(config, batch, seq, dtype, window, yarn):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mellum as ref
    from benchmark.reference.make_expected import canary_batch
    from dlrover_tpu.models.build import build_model

    model, _ = build_model(config["model"])
    params = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(config["weights_key"]))
    x, y = canary_batch(config, batch, seq)
    (loss, landed), grads = ref.by_rows(
        params, jnp.asarray(x), jnp.asarray(y), config["model"]["config"],
        jnp.dtype(dtype).type, window=window, yarn=yarn)
    return {
        "device": jax.devices()[0].device_kind,
        "trunk_loss": float(loss), "grad_norm": float(ref.global_norm(grads)),
        "assignments_here_by_layer": [int(n) for n in landed],
        "n_params": sum(int(l.size) for l in jax.tree.leaves(params)),
    }


def write(name, shape, rehearsal, result):
    """The float32 reference's values into the configuration's file."""
    path = os.path.join(ROOT, "benchmark", "configs", name + ".json")
    with open(path) as f:
        config = json.load(f)
    expected = (config["rehearsal"] if rehearsal else config).setdefault("expected", {})
    expected.setdefault("train_canary_loss", {}).setdefault("values", {})[shape] = result["trunk_loss"]
    expected.setdefault("first_step", {}).setdefault("values", {})[shape] = {
        k: result[k] for k in ("trunk_loss", "grad_norm", "assignments_here_by_layer")}
    with open(path, "w") as f:
        json.dump(config, f, indent=1)
        f.write("\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("shape", help="the batch, as b2x8192")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16", "float8_e4m3fn"])
    ap.add_argument("--no-window", action="store_true")
    ap.add_argument("--no-yarn", action="store_true")
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--from", dest="made", help="a result this script kept, instead of computing")
    ns = ap.parse_args()

    from benchmark.reference.make_expected import load_config

    if ns.made:
        with open(ns.made) as f:
            result = json.load(f)
        plain = (result["dtype"], result["window"], result["yarn"]) == ("float32", True, True)
        if (result["config"], result["shape"]) != (ns.config, ns.shape) or not plain:
            raise SystemExit(f"{ns.made} is not the float32 reference of {ns.config} {ns.shape}")
    else:
        config = load_config(ns.config, ns.rehearsal)
        batch, seq = (int(v) for v in ns.shape[1:].split("x"))
        result = dict(config=config["name"], shape=ns.shape, dtype=ns.dtype,
                      window=not ns.no_window, yarn=not ns.no_yarn, rehearsal=ns.rehearsal)
        result.update(compute(config, batch, seq, ns.dtype, not ns.no_window, not ns.no_yarn))
        tag = ".".join([ns.dtype] + ["no-window"] * ns.no_window + ["no-yarn"] * ns.no_yarn
                       + ["rehearsal"] * ns.rehearsal)
        kept = os.path.join(ROOT, "chiprun_out", "benchmark", "expected")
        os.makedirs(kept, exist_ok=True)
        with open(os.path.join(kept, f"{ns.config}.{ns.shape}.{tag}.json"), "w") as f:
            json.dump(result, f)
    print(json.dumps(result), flush=True)
    if ns.write:
        if (result["dtype"], result["window"], result["yarn"]) != ("float32", True, True):
            raise SystemExit("only the float32 reference is written into the configuration")
        write(ns.config, ns.shape, result.get("rehearsal", ns.rehearsal), result)


if __name__ == "__main__":
    main()
