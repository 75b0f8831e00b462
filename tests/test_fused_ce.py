"""Fused chunked cross-entropy (head + CE without whole-seq logits).

The fp32 [B,T,V] logits are the HBM ceiling of the flagship bench
config (6.6 GB at bs=32/seq=1024/vocab=50k); GPTConfig.ce_chunk
computes per-token CE inside the model over seq chunks with
jax.checkpoint, so live logits are [B, chunk, V]. These tests pin the
numerics: chunking must be exactly the dense computation, reordered.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.layers import cross_entropy_loss, token_loss_mean
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)


def _data(cfg, batch=4, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(
        r.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32
    )
    return x, jnp.roll(x, -1, axis=1)


class TestFusedCeNumerics:
    @pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
    def test_token_losses_match_dense(self, tied):
        cfg_kw = dict(
            vocab_size=256,
            max_seq_len=128,
            num_layers=2,
            num_heads=4,
            head_dim=8,
            embed_dim=32,
            use_remat=False,
            tie_embeddings=tied,
        )
        dense = GPT(GPTConfig(**cfg_kw))
        fused = GPT(GPTConfig(ce_chunk=32, **cfg_kw))
        x, y = _data(dense.config)
        params = dense.init(jax.random.PRNGKey(0), x)["params"]

        logits = dense.apply({"params": params}, x)
        want = cross_entropy_loss(logits, y)
        token_losses = fused.apply({"params": params}, x, targets=y)
        assert token_losses.shape == x.shape
        got = token_loss_mean(token_losses, y)
        np.testing.assert_allclose(
            float(got), float(want), rtol=1e-5, atol=1e-6
        )

    def test_ignore_index_masked(self):
        cfg = GPTConfig(
            vocab_size=64,
            max_seq_len=64,
            num_layers=1,
            num_heads=2,
            head_dim=8,
            embed_dim=16,
            use_remat=False,
            ce_chunk=16,
        )
        model = GPT(cfg)
        x, y = _data(cfg, batch=2)
        y = y.at[:, ::2].set(-1)  # ignore every other position
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        tls = model.apply({"params": params}, x, targets=y)
        assert float(jnp.abs(tls[:, ::2]).sum()) == 0.0
        assert float(jnp.abs(tls[:, 1::2]).sum()) > 0.0

    def test_rejects_non_divisible_seq(self):
        cfg = GPTConfig(
            vocab_size=64,
            max_seq_len=48,
            num_layers=1,
            num_heads=2,
            head_dim=8,
            embed_dim=16,
            use_remat=False,
            ce_chunk=32,
        )
        model = GPT(cfg)
        x, y = _data(cfg, batch=2)
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        with pytest.raises(ValueError, match="not divisible by ce_chunk"):
            model.apply({"params": params}, x, targets=y)


class TestFusedCeTrainStep:
    def test_step_matches_dense_step(self):
        """One optimizer step through the fused path lands on the same
        loss and parameters as the dense path (same init, same data)."""
        cfg_kw = dict(
            vocab_size=128,
            max_seq_len=64,
            num_layers=2,
            num_heads=4,
            head_dim=8,
            embed_dim=32,
            use_remat=False,
        )
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        results = {}
        for name, extra_cfg, loss in [
            ("dense", {}, cross_entropy_loss),
            ("fused", {"ce_chunk": 16}, token_loss_mean),
        ]:
            model = GPT(GPTConfig(**cfg_kw, **extra_cfg))
            x, y = _data(model.config)
            tx = default_optimizer(learning_rate=1e-2, warmup_steps=1)
            state, shardings = init_train_state(model, x, mesh, tx)
            step = build_train_step(model, tx, loss, mesh, shardings)
            new_state, loss_val = step(state, x, y)
            results[name] = (
                float(loss_val),
                jax.tree.map(np.asarray, new_state.params),
            )
        np.testing.assert_allclose(
            results["dense"][0], results["fused"][0], rtol=1e-4
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=2e-3, atol=1e-5
            ),
            results["dense"][1],
            results["fused"][1],
        )

    def test_sharded_fused_step_runs(self):
        """Fused CE under a dp x tp mesh: the head matmul is tp-sharded
        inside the scan; the step must compile and agree with dense."""
        cfg_kw = dict(
            vocab_size=128,
            max_seq_len=64,
            num_layers=1,
            num_heads=4,
            head_dim=8,
            embed_dim=32,
            use_remat=False,
        )
        mesh = build_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
        losses = {}
        for name, extra_cfg, loss in [
            ("dense", {}, cross_entropy_loss),
            ("fused", {"ce_chunk": 16}, token_loss_mean),
        ]:
            model = GPT(GPTConfig(**cfg_kw, **extra_cfg))
            x, y = _data(model.config, batch=4)
            tx = default_optimizer(learning_rate=1e-2, warmup_steps=1)
            state, shardings = init_train_state(model, x, mesh, tx)
            step = build_train_step(model, tx, loss, mesh, shardings)
            _, loss_val = step(state, x, y)
            losses[name] = float(loss_val)
        np.testing.assert_allclose(
            losses["dense"], losses["fused"], rtol=1e-4
        )


class TestLlamaFusedCe:
    """Same contract on the second model family (untied head + MoE)."""

    def test_llama_token_losses_match_dense(self):
        from dlrover_tpu.models.llama import Llama, LlamaConfig

        dense = Llama(LlamaConfig.tiny())
        fused = Llama(LlamaConfig.tiny(ce_chunk=32))
        r = np.random.default_rng(0)
        x = jnp.asarray(r.integers(0, 256, (2, 128)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        params = dense.init(jax.random.PRNGKey(0), x)["params"]
        want = cross_entropy_loss(dense.apply({"params": params}, x), y)
        tls = fused.apply({"params": params}, x, targets=y)
        got = token_loss_mean(tls, y)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_llama_moe_fused_step(self):
        """ce_chunk composes with MoE blocks (aux losses still sowed)."""
        from dlrover_tpu.models.llama import Llama, LlamaConfig

        model = Llama(
            LlamaConfig.tiny(num_experts=4, moe_every=2, ce_chunk=32)
        )
        r = np.random.default_rng(0)
        x = jnp.asarray(r.integers(0, 256, (4, 128)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        mesh = build_mesh(MeshConfig(dp=2, ep=2), jax.devices()[:4])
        tx = default_optimizer(learning_rate=1e-2, warmup_steps=1)
        state, shardings = init_train_state(model, x, mesh, tx)
        step = build_train_step(model, tx, token_loss_mean, mesh, shardings)
        new_state, loss = step(state, x, y)
        assert np.isfinite(float(loss))
        assert int(new_state.step) == 1


class TestFusedCeEvalStep:
    def test_eval_matches_dense_eval(self):
        """build_eval_step honors the fused contract: a ce_chunk model
        gets targets handed in and the eval loss equals the dense one
        (a non-aware eval would feed logits into token_loss_mean and
        return a silently wrong scalar)."""
        from dlrover_tpu.parallel.train_step import build_eval_step

        cfg_kw = dict(
            vocab_size=128,
            max_seq_len=64,
            num_layers=1,
            num_heads=2,
            head_dim=8,
            embed_dim=16,
            use_remat=False,
        )
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        losses = {}
        for name, extra_cfg, loss in [
            ("dense", {}, cross_entropy_loss),
            ("fused", {"ce_chunk": 16}, token_loss_mean),
        ]:
            model = GPT(GPTConfig(**cfg_kw, **extra_cfg))
            x, y = _data(model.config, batch=2)
            tx = default_optimizer(learning_rate=1e-2, warmup_steps=1)
            state, shardings = init_train_state(model, x, mesh, tx)
            ev = build_eval_step(model, loss, mesh, shardings)
            losses[name] = float(ev(state.params, x, y))
        np.testing.assert_allclose(
            losses["dense"], losses["fused"], rtol=1e-5
        )
