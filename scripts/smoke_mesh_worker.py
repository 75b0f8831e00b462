"""Worker of ``chip_smoke.py --chips 4``, launched through ``tpurun``:
ONE process over all four devices.

Runs the same GPT, global batch and seed once on a mesh of
``devices[:1]`` and once on the mesh ``choose_mesh_shape(4)`` gives,
compares the losses of the first steps, checks that every parameter,
optimizer leaf and batch has addressable shards on four distinct
devices, then stages the four-device state as a flash checkpoint and
restores it under another mesh (dp2 x tp2) through ``RESHARD_RULES``,
comparing every leaf with a host copy. Writes JSON lines to
``SMOKE_EVENTS``; raises on any disagreement.
"""

import dataclasses
import gc
import json
import os
import sys
import time

EVENTS = os.environ["SMOKE_EVENTS"]
REQUIRED = os.environ["SMOKE_REQUIRE_PLATFORM"]
TINY = os.environ.get("SMOKE_TINY") == "1"
SEED = int(os.environ.get("SMOKE_SEED", "0"))
CKPT_DIR = os.environ["SMOKE_CKPT_DIR"]
STEPS = 4
# Same program, same data, different partitioning: the reductions run in
# another order and the matmuls in bf16, so the losses agree to a band,
# not to the bit. 0.05 nats on a loss near ln(vocab) ~ 10.8 is ~0.5 %.
LOSS_BAND = 0.05


def emit(event: str, **fields) -> None:
    fields.update(event=event, t=time.time(), pid=os.getpid())
    with open(EVENTS, "a") as f:
        f.write(json.dumps(fields) + "\n")


def main() -> int:
    from dlrover_tpu.trainer.elastic import elastic_context

    elastic_context()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common.platform import device_summary

    devices = jax.devices()
    emit("device", **device_summary())
    if devices[0].platform != REQUIRED or len(devices) != 4:
        print(
            f"smoke mesh worker: {len(devices)} x {devices[0].platform!r}, "
            f"required 4 x {REQUIRED!r}",
            file=sys.stderr,
        )
        return 4

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.shm_handler import _path_str
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.layers import cross_entropy_loss
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

    base = GPTConfig.tiny() if TINY else GPTConfig.gpt2_small()
    cfg = dataclasses.replace(base, attention_impl="flash")
    batch = 8 if TINY else 32
    model = GPT(cfg)
    rng = np.random.default_rng(SEED)
    x = rng.integers(
        0, min(cfg.vocab_size, 512), (batch, cfg.max_seq_len)
    ).astype(np.int32)
    y = np.roll(x, -1, axis=1)

    def run(mesh):
        tx = default_optimizer(learning_rate=1e-3, warmup_steps=2)
        tokens = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
        state, shardings = init_train_state(
            model, tokens, mesh, tx, rng=jax.random.PRNGKey(SEED)
        )
        step_fn = build_train_step(
            model, tx, cross_entropy_loss, mesh, shardings
        )
        data_sharding = data_sharding_for(x, mesh, DEFAULT_RULES)
        xd = jax.device_put(x, data_sharding)
        yd = jax.device_put(y, data_sharding)
        text = step_fn.lower(state, xd, yd).as_text()
        losses = []
        for _ in range(STEPS):
            state, loss = step_fn(state, xd, yd)
            losses.append(float(loss))
        return state, (xd, yd), losses, "tpu_custom_call" in text

    mesh_one = build_mesh(MeshConfig(dp=-1), devices[:1])
    state, _, losses_one, kernel_one = run(mesh_one)
    del state
    gc.collect()
    mesh_four = build_mesh(choose_mesh_shape(4), devices)
    state, data, losses_four, kernel_four = run(mesh_four)
    diffs = [abs(a - b) for a, b in zip(losses_one, losses_four)]
    emit(
        "losses",
        one_device=losses_one,
        four_devices=losses_four,
        max_abs_diff=max(diffs),
        band=LOSS_BAND,
        mesh=dict(mesh_four.shape),
        tpu_custom_call=[kernel_one, kernel_four],
    )
    if not all(np.isfinite(losses_one + losses_four)):
        raise RuntimeError("non-finite loss")
    if max(diffs) > LOSS_BAND:
        raise RuntimeError(
            f"one-device and four-device losses differ by {max(diffs)} "
            f"> {LOSS_BAND}"
        )

    # code that has only seen one chip may put everything on the first
    def devices_of(leaf):
        return {s.device for s in leaf.addressable_shards}

    leaves = {
        "params": jax.tree.leaves(state.params),
        "opt_state": [
            l for l in jax.tree.leaves(state.opt_state) if hasattr(l, "addressable_shards")
        ],
        "batch": list(data),
    }
    placement = {}
    for name, group in leaves.items():
        counts = {len(devices_of(leaf)) for leaf in group}
        placement[name] = {"leaves": len(group), "devices": sorted(counts)}
        if counts != {4}:
            raise RuntimeError(f"{name}: leaves on {counts} devices, want 4")
    split = sum(
        1
        for leaf in leaves["params"]
        if not leaf.sharding.is_fully_replicated
    )
    emit("placement", split_params=split, **placement)
    if split == 0:
        raise RuntimeError("no parameter is split over the mesh")

    # flash save under one mesh, restore under another, against a host copy
    host = {
        _path_str(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]
    }
    engine = CheckpointEngine(CKPT_DIR, mesh=mesh_four)
    try:
        if not engine.save_to_memory(STEPS - 1, state):
            raise RuntimeError("flash save skipped")
        del state
        gc.collect()
        mesh_to = build_mesh(MeshConfig(dp=2, tp=2), devices)
        t0 = time.monotonic()
        step, placed, _ = engine.load_resharded(mesh_to)
        jax.block_until_ready(list(placed.values()))
        reshard_s = time.monotonic() - t0
        if step != STEPS - 1 or set(placed) != set(host):
            raise RuntimeError(
                f"reshard restore: step {step}, "
                f"{len(set(placed) ^ set(host))} leaf paths differ"
            )
        unequal = [
            path
            for path, want in host.items()
            if not np.array_equal(np.asarray(placed[path]), want)
        ]
        retiled = sum(
            1
            for arr in placed.values()
            if hasattr(arr, "sharding")
            and "tp" in str(arr.sharding.spec)
        )
        emit(
            "reshard",
            from_mesh=dict(mesh_four.shape),
            to_mesh=dict(mesh_to.shape),
            leaves=len(placed),
            unequal=unequal[:5],
            split_over_tp=retiled,
            reshard_s=round(reshard_s, 3),
        )
        if unequal:
            raise RuntimeError(f"{len(unequal)} leaves differ after reshard")
        if any(
            hasattr(a, "sharding") and a.sharding.mesh.shape != mesh_to.shape
            for a in placed.values()
        ):
            raise RuntimeError("a leaf was not placed on the target mesh")
    finally:
        engine.close()
    emit("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
