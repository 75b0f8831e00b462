"""Pipeline-parallel GPT: the flagship family trains over a real pp axis.

Correctness bar: pp=2 and pp=1 (same params, refolded) produce the SAME
loss — the schedule is an execution reordering of identical math — and
a short training run reduces the loss. Checkpoint/re-mesh of the stacked
stage params is covered in test_pipeline.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.gpt import GPTConfig
from dlrover_tpu.models.layers import cross_entropy_loss
from dlrover_tpu.models.gpt_pipeline import (
    build_gpt_pipeline_train_step,
    gpt_pipeline_forward,
    gpt_pipeline_shardings,
    init_gpt_pipeline_params,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.pipeline import refold_stages, stage_sharding


def _cfg():
    return GPTConfig(
        vocab_size=128,
        max_seq_len=32,
        num_layers=4,
        num_heads=2,
        head_dim=8,
        embed_dim=16,
        use_remat=False,
    )


def _data(cfg, batch=8, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32)
    return x, jnp.roll(x, -1, axis=1)


class TestForwardEquivalence:
    def test_pp2_matches_pp1(self):
        cfg = _cfg()
        mesh1 = build_mesh(MeshConfig(dp=8, fsdp=1, pp=1))
        mesh2 = build_mesh(MeshConfig(dp=4, fsdp=1, pp=2))
        params = init_gpt_pipeline_params(cfg, 2, jax.random.PRNGKey(0))
        x, _ = _data(cfg)

        with mesh2:
            p2 = jax.device_put(params, gpt_pipeline_shardings(params, mesh2))
            # M=2 keeps mb=4 divisible by dp=4 (batch stays dp-sharded)
            logits2 = gpt_pipeline_forward(p2, x, cfg, mesh2, num_microbatches=2)

        # same weights refolded into ONE stage of 4 layers on pp=1
        params1 = dict(params)
        params1["stages"] = refold_stages(params["stages"], 1)
        with mesh1:
            p1 = jax.device_put(
                params1, gpt_pipeline_shardings(params1, mesh1)
            )
            logits1 = gpt_pipeline_forward(p1, x, cfg, mesh1, num_microbatches=4)
        np.testing.assert_allclose(
            np.asarray(logits2, np.float32),
            np.asarray(logits1, np.float32),
            rtol=2e-2,  # bf16 activations
            atol=2e-2,
        )

    def test_layers_must_divide_stages(self):
        with pytest.raises(ValueError):
            init_gpt_pipeline_params(_cfg(), 3, jax.random.PRNGKey(0))


class TestTraining:
    def test_pp2_training_reduces_loss(self):
        cfg = _cfg()
        mesh = build_mesh(MeshConfig(dp=4, fsdp=1, pp=2))
        params = init_gpt_pipeline_params(cfg, 2, jax.random.PRNGKey(0))
        shardings = gpt_pipeline_shardings(params, mesh)
        with mesh:
            params = jax.device_put(params, shardings)
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = build_gpt_pipeline_train_step(
            cfg, mesh, tx, num_microbatches=2, shardings=shardings
        )
        x, y = _data(cfg)
        losses = []
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses

    def test_stage_params_actually_sharded(self):
        cfg = _cfg()
        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, pp=4))
        params = init_gpt_pipeline_params(cfg, 4, jax.random.PRNGKey(0))
        sh = gpt_pipeline_shardings(params, mesh)
        with mesh:
            placed = jax.device_put(params, sh)
        w = placed["stages"]["wqkv"]
        assert w.shape[0] == 4
        # each pp rank's slice holds exactly its own stage
        assert w.addressable_shards[0].data.shape[0] == 1
