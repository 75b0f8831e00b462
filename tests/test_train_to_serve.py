"""Train → checkpoint → rollout handoff (the serve side of the loop).

A user trains with the elastic runtime, flash-checkpoints, and then
stands up a rollout/serving role from the SAME artifacts: the params
restore from the engine's storage (or the Orbax export) into the
generation engine with zero format conversion. The reference cannot
close this loop in one stack — training checkpoints are torch state
dicts, serving is vLLM's own weight loader. Greedy continuity is the
proof: the restored policy generates exactly what the live policy
generated before the round trip.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.models.generation import (
    SamplingConfig,
    generate,
    left_pad_prompts,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.layers import token_loss_mean
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)


def _train_some(tmp_path, steps=3):
    cfg = GPTConfig(
        vocab_size=128,
        max_seq_len=64,
        num_layers=1,
        num_heads=2,
        head_dim=8,
        embed_dim=16,
        use_remat=False,
        ce_chunk=16,
    )
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = default_optimizer(learning_rate=1e-2, warmup_steps=1)
    x = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    state, shardings = init_train_state(model, x, mesh, tx)
    step = build_train_step(model, tx, token_loss_mean, mesh, shardings)
    r = np.random.default_rng(0)
    for _ in range(steps):
        xb = jnp.asarray(
            r.integers(0, cfg.vocab_size, (2, cfg.max_seq_len)), jnp.int32
        )
        state, _ = step(state, xb, jnp.roll(xb, -1, axis=1))
    return model, mesh, state


class TestTrainToServe:
    def test_engine_checkpoint_feeds_generation(self, tmp_path):
        model, mesh, state = _train_some(tmp_path)
        prompts, mask = left_pad_prompts([[5, 9], [3]], pad_id=0)
        sampling = SamplingConfig(max_new_tokens=5, temperature=0.0)
        live, _, _ = generate(
            model, state.params, prompts, mask, jax.random.PRNGKey(0),
            sampling,
        )

        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine(ckpt_dir, mesh=mesh, standalone=True)
        try:
            assert engine.save_to_storage(int(state.step), state)
            assert engine.wait_saving(timeout=120)
        finally:
            engine.shm.unlink()
            engine.close()

        # fresh "rollout role": restore into a template built from the
        # shared model definition — no trainer objects carried over
        model2, mesh2, template = _train_some(tmp_path, steps=0)
        engine2 = CheckpointEngine(ckpt_dir, mesh=mesh2, standalone=True)
        try:
            step, restored = engine2.load(template)
            assert restored is not None and step == int(state.step)
        finally:
            engine2.shm.unlink()
            engine2.close()
        served, _, _ = generate(
            model2, restored.params, prompts, mask, jax.random.PRNGKey(0),
            sampling,
        )
        np.testing.assert_array_equal(np.asarray(served), np.asarray(live))

        # ... and through the continuous-batching scheduler: the same
        # restored params serve a request stream, and each greedy
        # completion matches the one-shot engine's output row
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(
            model2, restored.params, sampling, batch_size=2,
            prompt_width=8, decode_chunk=4,
        )
        comps = eng.run([[5, 9], [3]])
        assert [c.uid for c in comps] == [0, 1]  # nothing dropped
        live_np = np.asarray(live)
        for i, c in enumerate(comps):
            assert c.tokens == [int(t) for t in live_np[i]], (
                i, c.tokens, live_np[i]
            )
        # the server rounded its own copy of the matrices; what the
        # checkpoint restored (and a trainer would resume from) is the
        # float32 state still
        assert any(
            leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(eng.params)
        )
        for kept, trained in zip(
            jax.tree.leaves(restored.params), jax.tree.leaves(state.params)
        ):
            assert kept.dtype == trained.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(kept), np.asarray(trained))

    def test_restored_float32_params_swap_into_a_running_engine(
        self, tmp_path
    ):
        """``/v1/weights/reload``'s path: the trainer's float32 params
        land in a server that holds another version, rounded as they
        land; it then serves what a server built from them serves, log
        probabilities included."""
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        model, _, state = _train_some(tmp_path)
        _, _, fresh = _train_some(tmp_path, steps=0)
        sampling = SamplingConfig(max_new_tokens=5, temperature=0.0)

        def serve(params, swap_to=None):
            eng = ContinuousBatchingEngine(
                model, params, sampling, batch_size=2, prompt_width=8,
                decode_chunk=4,
            )
            if swap_to is not None:
                eng.set_params(swap_to)
            return [(c.tokens, c.logprobs) for c in eng.run([[5, 9], [3]])], eng

        want, _ = serve(state.params)
        got, eng = serve(fresh.params, swap_to=state.params)
        assert got == want
        assert eng.stats()["params_casts"] == 2
        assert all(
            leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(state.params)
        )

    def test_orbax_export_feeds_generation(self, tmp_path):
        """The Orbax-interop artifact serves too: a consumer with only
        stock orbax (no dlrover_tpu checkpoint engine) restores the
        exported tree and generates identically."""
        import orbax.checkpoint as ocp

        from dlrover_tpu.checkpoint.orbax_interop import export_to_orbax

        model, mesh, state = _train_some(tmp_path)
        prompts, mask = left_pad_prompts([[7, 2, 4]], pad_id=0)
        sampling = SamplingConfig(max_new_tokens=4, temperature=0.0)
        live, _, _ = generate(
            model, state.params, prompts, mask, jax.random.PRNGKey(0),
            sampling,
        )

        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine(ckpt_dir, mesh=mesh, standalone=True)
        try:
            assert engine.save_to_storage(int(state.step), state)
            assert engine.wait_saving(timeout=120)
        finally:
            engine.shm.unlink()
            engine.close()
        orbax_dir = str(tmp_path / "orbax")
        assert export_to_orbax(ckpt_dir, orbax_dir) == int(state.step)

        # external-consumer path: stock orbax restore, params subtree
        tree = ocp.StandardCheckpointer().restore(orbax_dir)
        served, _, _ = generate(
            model,
            tree["params"],
            prompts,
            mask,
            jax.random.PRNGKey(0),
            sampling,
        )
        np.testing.assert_array_equal(np.asarray(served), np.asarray(live))
