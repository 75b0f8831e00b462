"""Mesh, sharding, and train-step tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.layers import cross_entropy_loss
from dlrover_tpu.models.mnist import MlpConfig, MnistMlp, classification_loss
from dlrover_tpu.parallel.mesh import (
    MeshConfig,
    build_mesh,
    choose_mesh_shape,
    local_batch_slice,
)
from dlrover_tpu.parallel.train_step import (
    build_eval_step,
    build_train_step,
    default_optimizer,
    init_train_state,
)


class TestMeshConfig:
    def test_resolve_free_axis(self):
        cfg = MeshConfig(dp=-1, fsdp=1, tp=2)
        assert cfg.resolve(8).as_dict() == {
            "dp": 4, "fsdp": 1, "ep": 1, "tp": 2, "sp": 1, "pp": 1,
        }

    def test_resolve_exact(self):
        cfg = MeshConfig(dp=2, fsdp=2, tp=2)
        assert cfg.resolve(8).sizes == (2, 2, 1, 2, 1, 1)

    def test_resolve_mismatch_raises(self):
        with pytest.raises(ValueError):
            MeshConfig(dp=3, fsdp=1, tp=1).resolve(8)
        with pytest.raises(ValueError):
            MeshConfig(dp=-1, tp=3).resolve(8)

    def test_choose_mesh_shape_elastic(self):
        # Elastic world change: 8 → 6 devices with tp=2 keeps tp, shrinks data
        cfg8 = choose_mesh_shape(8, tp=2)
        cfg6 = choose_mesh_shape(6, tp=2)
        assert cfg8.fsdp == 4 and cfg6.fsdp == 3
        with pytest.raises(ValueError):
            choose_mesh_shape(7, tp=2)

    def test_local_batch_slice(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        assert local_batch_slice(32, mesh) == 8
        with pytest.raises(ValueError):
            local_batch_slice(30, mesh)

    def test_build_mesh_axis_order(self):
        mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=4))
        assert mesh.shape["dp"] == 2
        assert mesh.shape["tp"] == 4


@pytest.fixture(scope="module")
def tiny_gpt_setup():
    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    tx = default_optimizer()
    tokens = jnp.zeros((8, 32), jnp.int32)
    state, shardings = init_train_state(model, tokens, mesh, tx)
    return cfg, model, mesh, tx, state, shardings


class TestGptTrainStep:
    def test_params_are_sharded(self, tiny_gpt_setup):
        _, _, mesh, _, state, _ = tiny_gpt_setup
        wqkv = state.params["block_0"]["CausalSelfAttention_0"]["wqkv"]
        assert "tp" in tuple(wqkv.sharding.spec)
        assert "fsdp" in tuple(wqkv.sharding.spec)
        w1 = state.params["block_0"]["Mlp_0"]["w1"]
        assert tuple(w1.sharding.spec) == ("fsdp", "tp")

    def test_loss_decreases(self, tiny_gpt_setup):
        cfg, model, mesh, tx, state, shardings = tiny_gpt_setup
        # donate=False: the module-scoped fixture state is reused by other
        # tests; donation would delete its buffers.
        step = build_train_step(
            model, tx, cross_entropy_loss, mesh, shardings, donate=False
        )
        r = np.random.default_rng(0)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        state0_loss = None
        for i in range(8):
            state, loss = step(state, x, y)
            state0_loss = state0_loss if state0_loss is not None else float(loss)
        assert float(loss) < state0_loss
        assert int(state.step) == 8

    def test_sharded_matches_single_device(self):
        """The same model/optimizer on a 1-device mesh and an 8-device mesh
        must produce (numerically close) identical losses — sharding is an
        implementation detail, not a semantics change."""
        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        tx = default_optimizer()
        r = np.random.default_rng(1)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        losses = {}
        for name, mcfg, devs in [
            ("single", MeshConfig(dp=1), jax.devices()[:1]),
            ("sharded", MeshConfig(dp=2, fsdp=2, tp=2), jax.devices()),
        ]:
            mesh = build_mesh(mcfg, devs)
            tokens = jnp.zeros((8, 32), jnp.int32)
            state, shardings = init_train_state(
                model, tokens, mesh, tx, rng=jax.random.PRNGKey(7)
            )
            step = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)
            run = []
            for _ in range(3):
                state, loss = step(state, x, y)
                run.append(float(loss))
            losses[name] = run
        np.testing.assert_allclose(losses["single"], losses["sharded"], rtol=2e-2)

    def test_eval_step(self, tiny_gpt_setup):
        cfg, model, mesh, tx, state, shardings = tiny_gpt_setup
        eval_step = build_eval_step(model, cross_entropy_loss, mesh, shardings)
        r = np.random.default_rng(2)
        x = jnp.asarray(r.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
        loss = eval_step(state.params, x, jnp.roll(x, -1, axis=1))
        assert np.isfinite(float(loss))


class TestMnist:
    def test_train_decreases_loss(self):
        model = MnistMlp(MlpConfig(input_dim=64, hidden_dim=32))
        mesh = build_mesh(MeshConfig(dp=4, fsdp=2))
        tx = default_optimizer(learning_rate=1e-2)
        x_example = jnp.zeros((8, 64))
        state, shardings = init_train_state(model, x_example, mesh, tx)
        step = build_train_step(
            model, tx, classification_loss, mesh, shardings, example_data=(x_example, jnp.zeros((8,), jnp.int32))
        )
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=(8, 64)), jnp.float32)
        y = jnp.asarray(r.integers(0, 10, (8,)), jnp.int32)
        first = None
        for _ in range(20):
            state, loss = step(state, x, y)
            first = first if first is not None else float(loss)
        assert float(loss) < first * 0.8


class TestOptDpShard:
    """Cross-replica weight-update sharding (arXiv:2004.13336, the
    RESHARD_RULES ``mirror_dp`` policy): ``state_shardings(
    shard_opt_over_dp=True)`` shards optimizer moments dim 0 over
    ``dp``; GSPMD inserts the gather at ``tx.update`` from the
    annotations alone, so the update math is unchanged."""

    def test_moments_shard_over_dp_and_update_matches(self):
        model = MnistMlp(MlpConfig(input_dim=64, hidden_dim=32))
        mesh = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        tx = default_optimizer(learning_rate=1e-2)
        x_example = jnp.zeros((8, 64))
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=(8, 64)), jnp.float32)
        y = jnp.asarray(r.integers(0, 10, (8,)), jnp.int32)
        runs = {}
        for flag in (False, True):
            state, shardings = init_train_state(
                model, x_example, mesh, tx, shard_opt_over_dp=flag
            )
            step = build_train_step(
                model,
                tx,
                classification_loss,
                mesh,
                shardings,
                example_data=(x_example, jnp.zeros((8,), jnp.int32)),
                donate=False,
            )
            losses = []
            for _ in range(3):
                state, loss = step(state, x, y)
                losses.append(float(loss))
            runs[flag] = (losses, state)
        # Annotations move placement, not math.
        np.testing.assert_allclose(
            runs[True][0], runs[False][0], rtol=1e-4, atol=1e-5
        )
        # dp-divisible moment leaves actually shard: 1/4 per device.
        hits = 0
        for leaf in jax.tree.leaves(runs[True][1].opt_state):
            shape = getattr(leaf, "shape", ())
            if not shape or shape[0] % 4 or not hasattr(leaf, "sharding"):
                continue
            head = (tuple(leaf.sharding.spec) or (None,))[0]
            axes = head if isinstance(head, tuple) else (head,)
            if "dp" in axes:
                hits += 1
                assert (
                    leaf.addressable_shards[0].data.shape[0]
                    == shape[0] // 4
                )
        assert hits > 0, "no moment leaf picked up the dp factor"
        # The un-sharded run's moments never reference dp.
        for leaf in jax.tree.leaves(runs[False][1].opt_state):
            if hasattr(leaf, "sharding"):
                spec = tuple(getattr(leaf.sharding, "spec", ()) or ())
                flat = [
                    a
                    for e in spec
                    for a in (e if isinstance(e, tuple) else (e,))
                ]
                assert "dp" not in flat
