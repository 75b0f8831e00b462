"""SPMD generation over a device mesh (rollout at scale).

The sharded rollout capability: ``build_generate_fn(mesh=...)`` runs
prefill + the decode scan over a tp/fsdp/dp mesh with the params held
exactly as the trainer shards them — XLA inserts the decode
collectives. The reference can only do this by deploying a separate
vLLM instance per rollout (SURVEY.md §2.13); here it is the same
compiled path as single-chip generation, so the test's keystone is
bit-identical greedy output between the two.

8 virtual CPU devices (conftest), mirroring the multichip dryrun.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.generation import (
    SamplingConfig,
    build_generate_fn,
    left_pad_prompts,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    default_optimizer,
    init_train_state,
)


def _sharded_params(model, mesh, batch=4, width=8):
    """Params initialized INTO their mesh shards, trainer-style."""
    tokens = jnp.zeros((batch, width), jnp.int32)
    state, shardings = init_train_state(
        model, tokens, mesh, default_optimizer()
    )
    return state.params, shardings.params


class TestShardedGeneration:
    @pytest.mark.parametrize(
        "mesh_cfg",
        [
            MeshConfig(dp=2, fsdp=2, tp=2),
            MeshConfig(dp=4, tp=2),
            MeshConfig(dp=8),
        ],
        ids=["dp2_fsdp2_tp2", "dp4_tp2", "dp8"],
    )
    def test_greedy_matches_single_device(self, mesh_cfg):
        model = Llama(LlamaConfig.tiny())
        mesh = build_mesh(mesh_cfg, jax.devices()[:8])
        params, param_sh = _sharded_params(model, mesh)

        # 8 rows: divisible by the data extent of every mesh case
        toks, mask = left_pad_prompts(
            [
                [3, 7, 11],
                [9],
                [5, 5],
                [1, 2, 3, 4],
                [8],
                [2, 4, 6],
                [10, 11],
                [7, 7, 7, 7],
            ],
            pad_id=0,
        )
        sampling = SamplingConfig(max_new_tokens=4, temperature=0.0)
        fn = build_generate_fn(
            model,
            sampling,
            prompt_width=toks.shape[1],
            mesh=mesh,
            param_shardings=param_sh,
        )
        out_s, mask_s, logp_s = fn(params, toks, mask, jax.random.PRNGKey(0))

        # single-device reference on the SAME parameter values
        host_params = jax.device_get(params)
        fn1 = build_generate_fn(model, sampling, prompt_width=toks.shape[1])
        out_1, mask_1, logp_1 = fn1(
            jax.tree.map(jnp.asarray, host_params),
            toks,
            mask,
            jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_1))
        np.testing.assert_array_equal(np.asarray(mask_s), np.asarray(mask_1))
        np.testing.assert_allclose(
            np.asarray(logp_s), np.asarray(logp_1), rtol=2e-2, atol=2e-2
        )

    def test_gpt_tp_sharded_generation(self):
        model = GPT(GPTConfig.tiny())
        mesh = build_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
        params, param_sh = _sharded_params(model, mesh)
        toks, mask = left_pad_prompts([[3, 7], [9, 1]], pad_id=0)
        fn = build_generate_fn(
            model,
            SamplingConfig(max_new_tokens=3, temperature=0.0),
            prompt_width=2,
            mesh=mesh,
            param_shardings=param_sh,
        )
        out, omask, _ = fn(params, toks, mask, jax.random.PRNGKey(0))
        assert out.shape == (2, 3) and bool(omask.all())
        # teacher-forced check through the sharded TRAINING forward
        from dlrover_tpu.parallel.sharding import apply_rules

        full = jnp.concatenate([toks, out[:, :2]], axis=1)
        with mesh, apply_rules():
            logits = jax.jit(
                lambda p, t: model.apply({"params": p}, t)
            )(params, full)
        pred = jnp.argmax(np.asarray(logits)[:, 1:], axis=-1)
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(out))

    def test_sampled_path_runs_sharded(self):
        """Temperature/top-k/top-p over a tp-sharded vocab compiles and
        executes (the filters argsort the vocab dim — XLA must gather)."""
        model = Llama(LlamaConfig.tiny())
        mesh = build_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
        params, param_sh = _sharded_params(model, mesh)
        toks, mask = left_pad_prompts([[3], [9]], pad_id=0)
        fn = build_generate_fn(
            model,
            SamplingConfig(
                max_new_tokens=3, temperature=0.9, top_k=16, top_p=0.9
            ),
            prompt_width=1,
            mesh=mesh,
            param_shardings=param_sh,
        )
        out, omask, logp = fn(params, toks, mask, jax.random.PRNGKey(1))
        assert out.shape == (2, 3)
        assert np.isfinite(np.asarray(logp)).all()


class TestShardedEngineHoldsRoundedLeaves:
    """The serving engine over a two-device mesh: what it holds (the
    matrices in the model's compute dtype) sits on the shardings the
    trainer gave the float32 leaves, for both served families, and
    serves the float32 tree's own greedy stream."""

    @pytest.mark.parametrize("family", ["gpt", "llama"])
    def test_rounded_leaves_keep_shardings_and_the_stream(self, family):
        import types

        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        model = (
            GPT(GPTConfig.tiny()) if family == "gpt"
            else Llama(LlamaConfig.tiny())
        )
        mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
        params, _ = _sharded_params(model, mesh)
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        prompts = [[3, 7, 11], [9, 1], [4, 4, 4, 2]]

        def run(m):
            eng = ContinuousBatchingEngine(
                m, params, sampling, batch_size=2, prompt_width=8,
                decode_chunk=2, mesh=mesh,
            )
            return [(c.tokens, c.logprobs) for c in eng.run(prompts)], eng

        got, eng = run(model)
        silent = types.SimpleNamespace(
            config=model.config, apply=model.apply, init=model.init
        )
        want, _ = run(silent)  # the float32 tree through the same programs
        assert got == want
        dtypes = jax.tree.leaves(model.consumed_param_dtypes(params))
        rounded_and_split = 0
        for given, held, dt in zip(
            jax.tree.leaves(params), jax.tree.leaves(eng.params), dtypes
        ):
            assert held.dtype == dt
            assert held.sharding.is_equivalent_to(given.sharding, given.ndim)
            rounded_and_split += (
                held.dtype != given.dtype
                and not held.sharding.is_fully_replicated
            )
        assert rounded_and_split
