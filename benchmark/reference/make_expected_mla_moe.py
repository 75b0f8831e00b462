"""Makes the values ``benchmark/configs/<config>.json`` holds a
``model_train_cycles`` cell to, with the plain reference
(``benchmark/reference/mla_moe.py``), one batch row at a time:

    JAX_PLATFORMS=cpu python3 benchmark/reference/make_expected_mla_moe.py joyai-llm-flash-ep16 b4x4096
    ... --rehearsal b2x32          # the configuration's tiny rehearsal size
    ... --dtype bfloat16           # a router scored in bf16, like everything else
    ... --dtype float8_e4m3fn      # 8-bit parameters under bf16 products

At the initial weights (the program's own init from ``weights_key``:
weights are data) on the canary batch: the trunk's loss, the MTP loss,
their weighted sum, the global norm of the gradient and, per expert
layer, the assignments that landed on the experts held. Printed as one
JSON line; written into the configuration's file by hand. On the host a
b4x4096 run at float32 takes a few minutes and ~50 GB; on the chip,
through the chip tool, seconds (``highest`` precision there).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("shape", help="the batch, as b4x4096")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16", "float8_e4m3fn"])
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla_moe as ref
    from benchmark.reference.make_expected import canary_batch, load_config
    from dlrover_tpu.models.build import build_model

    config = load_config(ns.config, ns.rehearsal)
    batch, seq = (int(v) for v in ns.shape[1:].split("x"))
    model, _ = build_model(config["model"])
    params = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(config["weights_key"]))
    x, y = canary_batch(config, batch, seq)
    (loss, trunk, mtp, landed), grads = ref.by_rows(
        params, jnp.asarray(x), jnp.asarray(y), config["model"]["config"], jnp.dtype(ns.dtype).type)
    print(json.dumps({
        "config": config["name"], "shape": ns.shape, "dtype": ns.dtype,
        "device": jax.devices()[0].device_kind,
        "loss": float(loss), "trunk_loss": float(trunk), "mtp_loss": float(mtp),
        "grad_norm": float(ref.global_norm(grads)),
        "assignments_here_by_layer": [int(n) for n in landed],
        "n_params": sum(int(l.size) for l in jax.tree.leaves(params)),
    }), flush=True)


if __name__ == "__main__":
    main()
