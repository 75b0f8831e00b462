"""Llama family + MoE/expert-parallel tests (8 virtual CPU devices).

Parity note: the reference's examples span multiple model families
(GPT, Llama2 under FSDP — ``examples/pytorch/llama2/``); the runtime
must not be shaped around one architecture. EP itself is beyond the
reference (SURVEY.md §2.17: SP/EP absent there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.layers import apply_rope, cross_entropy_loss, rope_tables
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, choose_mesh_shape
from dlrover_tpu.parallel.sharding import apply_rules
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)


class TestRope:
    def test_rotation_preserves_norm(self):
        cos, sin = rope_tables(16, 8, 10000.0)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 4, 8)))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1),
            rtol=1e-5,
        )

    def test_position_zero_identity(self):
        cos, sin = rope_tables(4, 8, 10000.0)
        x = jnp.ones((1, 4, 1, 8))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(x[0, 0]), rtol=1e-6)


class TestRematPolicy:
    def _cfg(self, **kw):
        from dlrover_tpu.models.gpt import GPTConfig

        return GPTConfig(
            vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2,
            head_dim=8, embed_dim=16, use_remat=True, **kw,
        )

    @pytest.mark.parametrize("policy", ["nothing", "dots"])
    def test_policies_train(self, policy):
        """Both remat policies produce finite grads — and identical
        ones (remat changes WHAT is recomputed, never the math)."""
        from dlrover_tpu.models.gpt import GPT

        def grad_for(policy):
            model = GPT(self._cfg(remat_policy=policy))
            p = model.init(
                jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32)
            )["params"]
            g = jax.grad(
                lambda p, x: model.apply({"params": p}, x)
                .astype(jnp.float32)
                .sum()
            )(p, jnp.ones((2, 16), jnp.int32))
            return g

        g = grad_for(policy)
        assert all(
            bool(jnp.isfinite(leaf).all()) for leaf in jax.tree.leaves(g)
        )
        base = grad_for("nothing")
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(base)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_unknown_policy_raises(self):
        from dlrover_tpu.models.gpt import GPT

        model = GPT(self._cfg(remat_policy="dot"))
        with pytest.raises(ValueError, match="remat_policy"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32))


class TestLlamaDense:
    def test_forward_shapes_and_finite(self):
        cfg = LlamaConfig.tiny()
        model = Llama(cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        with apply_rules():
            variables = model.init(jax.random.PRNGKey(0), tokens)
            logits = model.apply(variables, tokens)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits, np.float32)).all()

    def test_gqa_param_shapes(self):
        cfg = LlamaConfig.tiny()  # 4 heads, 2 kv heads
        model = Llama(cfg)
        tokens = jnp.zeros((1, 8), jnp.int32)
        with apply_rules():
            variables = model.init(jax.random.PRNGKey(0), tokens)
        attn = variables["params"]["block_0"]["LlamaAttention_0"]
        assert attn["wq"].shape == (32, 4, 8)
        assert attn["wk"].shape == (32, 2, 8)  # grouped kv
        assert attn["wv"].shape == (32, 2, 8)

    def test_trains_on_mesh_tp_fsdp(self):
        cfg = LlamaConfig.tiny()
        model = Llama(cfg)
        mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2, sp=2))
        tx = default_optimizer(warmup_steps=1)
        tokens = jnp.zeros((4, 16), jnp.int32)
        state, shardings = init_train_state(model, tokens, mesh, tx)
        step = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)
        r = np.random.default_rng(0)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        losses = []
        for _ in range(5):
            state, loss = step(state, x, y)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]  # it learns


class TestMoE:
    def _moe_cfg(self, **kw):
        base = dict(num_experts=4, moe_every=2, capacity_factor=2.0)
        base.update(kw)
        return LlamaConfig.tiny(**base)

    def test_moe_forward_finite(self):
        cfg = self._moe_cfg()
        model = Llama(cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        with apply_rules():
            variables = model.init(jax.random.PRNGKey(0), tokens)
            logits = model.apply(variables, tokens)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        # layer 1 is the MoE block (moe_every=2 → odd layers)
        moe = variables["params"]["block_1"]["MoeMlp_0"]
        assert moe["w_gate"].shape == (4, 32, 64)  # [E, D, F]

    def test_aux_loss_sown(self):
        cfg = self._moe_cfg()
        model = Llama(cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        with apply_rules():
            variables = model.init(jax.random.PRNGKey(0), tokens)
            _, mutated = model.apply(
                variables, tokens, mutable=["losses"]
            )
        aux = jax.tree.leaves(mutated["losses"])
        assert aux and all(float(a) >= 0 for a in aux)

    def test_expert_parallel_training_on_ep_mesh(self):
        """Experts sharded over a real ep axis; full train step runs and
        the expert weights ARE distributed (sharding spec non-trivial)."""
        cfg = self._moe_cfg()
        model = Llama(cfg)
        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, ep=4, tp=1))
        tx = default_optimizer(warmup_steps=1)
        tokens = jnp.zeros((4, 16), jnp.int32)
        state, shardings = init_train_state(model, tokens, mesh, tx)
        moe_sh = shardings.params["block_1"]["MoeMlp_0"]["w_gate"]
        assert "ep" in (moe_sh.spec[0] or ()), moe_sh.spec
        step = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)
        r = np.random.default_rng(1)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        state, loss = step(state, x, y)
        assert np.isfinite(float(loss))
        # expert weight truly sharded: each addressable shard holds E/ep
        w = state.params["block_1"]["MoeMlp_0"]["w_gate"]
        assert w.addressable_shards[0].data.shape[0] == 1  # 4 experts / ep=4

    def test_moe_every_one_means_every_block(self):
        cfg = self._moe_cfg(moe_every=1)
        assert all(cfg.is_moe_block(i) for i in range(cfg.num_layers))
        cfg2 = self._moe_cfg(moe_every=2)
        assert [cfg2.is_moe_block(i) for i in range(4)] == [
            False, True, False, True,
        ]

    def test_aux_loss_reaches_gradients(self):
        """ADVICE r2: build_train_step must collect the sowed balance
        term — the same batch from the same init must step to different
        params when aux_loss_weight changes, and the reported loss must
        include the aux term."""
        cfg = self._moe_cfg()
        model = Llama(cfg)
        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, ep=4, tp=1))
        tx = default_optimizer(warmup_steps=1)
        tokens = jnp.zeros((2, 16), jnp.int32)
        r = np.random.default_rng(3)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        losses = {}
        gates = {}
        for w in (0.0, 1.0):
            state, shardings = init_train_state(model, tokens, mesh, tx)
            step = build_train_step(
                model, tx, cross_entropy_loss, mesh, shardings,
                aux_loss_weight=w,
            )
            state, loss = step(state, x, y)  # lr still 0 (warmup)
            losses[w] = float(loss)
            state, _ = step(state, x, y)  # lr > 0: grads reach params
            gates[w] = np.asarray(
                state.params["block_1"]["MoeMlp_0"]["w_gate"], np.float32
            )
        assert losses[1.0] > losses[0.0]  # aux term counted in the loss
        assert not np.allclose(gates[0.0], gates[1.0])  # ...and in grads

    def test_capacity_drops_overflow_tokens(self):
        """With capacity_factor tiny, overflowed tokens contribute zero
        output (combine mask empty) — the layer still runs, no NaNs."""
        cfg = self._moe_cfg(capacity_factor=0.1)
        model = Llama(cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        with apply_rules():
            variables = model.init(jax.random.PRNGKey(0), tokens)
            logits = model.apply(variables, tokens)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


class TestMeshEpAxis:
    def test_choose_mesh_shape_with_ep(self):
        cfg = choose_mesh_shape(8, ep=2, tp=2)
        assert cfg.ep == 2 and cfg.tp == 2 and cfg.fsdp == 2
        with pytest.raises(ValueError):
            choose_mesh_shape(6, ep=4)

    def test_six_axis_mesh_builds(self):
        mesh = build_mesh(MeshConfig(dp=1, fsdp=2, ep=2, tp=2, sp=1, pp=1))
        assert dict(mesh.shape) == {
            "dp": 1, "fsdp": 2, "ep": 2, "tp": 2, "sp": 1, "pp": 1,
        }
