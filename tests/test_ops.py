"""Pallas flash attention + ring attention kernels.

Kernel logic runs in Pallas interpret mode on the CPU backend (identical
code path to TPU modulo codegen); ring attention runs under shard_map on
the virtual 8-device mesh (SURVEY §4 trick #2).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from dlrover_tpu.ops.ring_attention import ring_attention

from jax import shard_map


def _qkv(b=2, t=32, h=2, d=16, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in keys)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal, None, 16, 16)
        ref = reference_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_non_divisible_seq_padding(self):
        q, k, v = _qkv(t=40)
        out = flash_attention(q, k, v, True, None, 16, 16)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_gradients_match_reference(self):
        q, k, v = _qkv(t=32)

        def loss_fa(q, k, v):
            return (flash_attention(q, k, v, True, None, 16, 16) ** 2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, True) ** 2).sum()

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_bf16_inputs(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, True, None, 16, 16)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), atol=3e-2
        )

    # Latent attention's shapes: q and k carry nope + rope, v is narrower.
    @pytest.mark.parametrize("d_qk,d_v,t", [(24, 16, 32), (24, 16, 40), (16, 24, 32)])
    def test_two_head_sizes_match_reference(self, d_qk, d_v, t):
        q, k, _ = _qkv(t=t, d=d_qk)
        v = _qkv(t=t, d=d_v, seed=1)[2]
        out = flash_attention(q, k, v, True, None, 16, 16)
        assert out.shape == q.shape[:3] + (d_v,)
        np.testing.assert_allclose(
            out, reference_attention(q, k, v, True), atol=2e-5
        )

    @pytest.mark.parametrize("d_qk,d_v", [(24, 16), (16, 24)])
    def test_two_head_sizes_gradients_match_reference(self, d_qk, d_v):
        q, k, _ = _qkv(t=40, d=d_qk)
        v = _qkv(t=40, d=d_v, seed=1)[2]
        w = jax.random.normal(jax.random.PRNGKey(2), q.shape[:3] + (d_v,))

        def loss(attend, q, k, v):
            return (attend(q, k, v) * w).sum()

        fa = functools.partial(
            flash_attention, causal=True, sm_scale=None, block_q=16, block_k=16
        )
        g_fa = jax.grad(functools.partial(loss, fa), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            functools.partial(loss, reference_attention), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g_fa, g_ref):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=5e-5)


class TestRingAttention:
    def _mesh(self, sp):
        devices = np.array(jax.devices()[:sp]).reshape(sp)
        return Mesh(devices, ("sp",))

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("sp", [2, 4, 8])
    def test_matches_full_attention(self, causal, sp):
        t_global = 8 * sp
        q, k, v = _qkv(b=2, t=t_global, h=2, d=8)
        mesh = self._mesh(sp)
        spec = P(None, "sp", None, None)
        fn = shard_map(
            functools.partial(ring_attention, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        out = fn(q, k, v)
        ref = reference_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_gradients_flow_through_ring(self):
        sp = 4
        t_global = 8 * sp
        q, k, v = _qkv(b=1, t=t_global, h=2, d=8)
        mesh = self._mesh(sp)
        spec = P(None, "sp", None, None)
        fn = shard_map(
            functools.partial(ring_attention, causal=True),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )

        def loss_ring(q, k, v):
            return (fn(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, True) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_long_context_memory_shape(self):
        """The per-device intermediate stays O(T/sp): run a sequence that
        would be a (T, T) = (256, 256) logits matrix per head densely,
        sharded 8 ways."""
        sp = 8
        q, k, v = _qkv(b=1, t=256, h=1, d=8)
        mesh = self._mesh(sp)
        spec = P(None, "sp", None, None)
        fn = jax.jit(
            shard_map(
                functools.partial(ring_attention, causal=True),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
        )
        out = fn(q, k, v)
        assert out.shape == q.shape
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5)


class TestRingAttentionInModel:
    def test_sp_train_step_matches_dense(self):
        """A full sharded train step with ring attention (sp=4) produces
        the same loss as the dense-attention step on identical weights."""
        from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.train_step import (
            build_train_step,
            default_optimizer,
            init_train_state,
        )

        def make(attn_impl, mesh_cfg):
            cfg = GPTConfig(
                vocab_size=128,
                max_seq_len=32,
                num_layers=2,
                num_heads=2,
                head_dim=8,
                embed_dim=16,
                use_remat=False,
                attention_impl=attn_impl,
            )
            model = GPT(cfg)
            mesh = build_mesh(mesh_cfg, jax.devices()[:8])
            tx = default_optimizer(learning_rate=1e-3)
            state, shardings = init_train_state(
                model, jnp.zeros((4, 32), jnp.int32), mesh, tx
            )
            step = build_train_step(
                model,
                tx,
                cross_entropy_loss,
                mesh,
                shardings,
                example_data=(
                    jnp.zeros((4, 32), jnp.int32),
                    jnp.zeros((4, 32), jnp.int32),
                ),
                donate=False,
            )
            return step, state

        tokens = jax.random.randint(
            jax.random.PRNGKey(3), (4, 32), 0, 128, jnp.int32
        )
        targets = jnp.roll(tokens, -1, axis=1)

        step_ring, state_ring = make("ring", MeshConfig(dp=2, sp=4))
        step_dense, state_dense = make("dense", MeshConfig(dp=2, sp=4))
        _, loss_ring = step_ring(state_ring, tokens, targets)
        _, loss_dense = step_dense(state_dense, tokens, targets)
        np.testing.assert_allclose(
            np.asarray(loss_ring), np.asarray(loss_dense), rtol=2e-3
        )


class TestCrossLengthCausal:
    def test_kv_cache_decode_shape(self):
        """t_kv > t_q (decode with cache): the causal mask is end-aligned,
        matching the reference oracle."""
        q, _, _ = _qkv(b=1, t=8, h=2, d=16, seed=5)
        _, k, v = _qkv(b=1, t=24, h=2, d=16, seed=6)
        out = flash_attention(q, k, v, True, None, 8, 8)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5)


class TestFlashAttentionSharded:
    """A Mosaic kernel cannot be partitioned by GSPMD; on a mesh the
    kernel runs per shard under shard_map (found compiling the 4-device
    step for a described v5e, tests/test_tpu_compile.py)."""

    def test_matches_reference_on_a_four_device_mesh(self):
        from dlrover_tpu.ops.flash_attention import (
            flash_attention_sharded,
            reference_attention,
        )
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.sharding import apply_rules

        mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2), jax.devices()[:4])
        q, k, v = _qkv(b=4, t=32, h=4, d=16)

        @jax.jit
        def fwd_bwd(q, k, v):
            def loss(q, k, v):
                return flash_attention_sharded(q, k, v, mesh).sum()

            return loss(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        with mesh, apply_rules():
            out = flash_attention_sharded(q, k, v, mesh)
            _, grads = fwd_bwd(q, k, v)
        want = reference_attention(q, k, v)
        want_grads = jax.grad(
            lambda *a: reference_attention(*a).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5)
        for got, ref in zip(grads, want_grads):
            np.testing.assert_allclose(got, ref, atol=2e-4)
        # batch over fsdp, heads over tp: really split, not gathered
        assert len(out.sharding.device_set) == 4

    def test_no_mesh_is_the_plain_kernel(self):
        from dlrover_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_sharded,
        )

        q, k, v = _qkv()
        np.testing.assert_array_equal(
            flash_attention_sharded(q, k, v, None), flash_attention(q, k, v)
        )

    def test_sharded_sequence_is_refused(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_sharded
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.sharding import apply_rules

        mesh = build_mesh(MeshConfig(dp=2, sp=2), jax.devices()[:4])
        q, k, v = _qkv(b=4, t=32)
        with mesh, apply_rules(), pytest.raises(ValueError, match="ring"):
            flash_attention_sharded(q, k, v, mesh)
