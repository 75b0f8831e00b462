"""Llama + mixture-of-experts over an expert-parallel mesh.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/llama_moe.py

Experts shard over the ``ep`` mesh axis (GShard-style einsum dispatch,
compiled to all-to-alls by XLA); everything else rides the same train
step and flash checkpoint path as the GPT family.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models.layers import cross_entropy_loss
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)


def main():
    n = len(jax.devices())
    ep = 4 if n % 4 == 0 else 1
    mesh = build_mesh(MeshConfig(dp=n // ep, fsdp=1, ep=ep))
    print("mesh:", dict(mesh.shape))

    cfg = LlamaConfig.tiny(num_experts=ep * 2, moe_every=2, max_seq_len=128)
    model = Llama(cfg)
    tx = default_optimizer(warmup_steps=5)
    batch = 2 * (n // ep)

    tokens = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
    state, shardings = init_train_state(model, tokens, mesh, tx)
    step_fn = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)

    rng = np.random.default_rng(0)
    for step in range(30):
        x = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len)),
            jnp.int32,
        )
        y = jnp.roll(x, -1, axis=1)
        state, loss = step_fn(state, x, y)
        if step % 5 == 0:
            print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
