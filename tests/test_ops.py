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

from dlrover_tpu.ops import flash_attention as fa
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


def _grads(attend, w):
    return jax.grad(lambda *a: (attend(*a) * w).sum(), argnums=(0, 1, 2))


def _out_and_grads(attend, q, k, v, w):
    """Output and the three gradients of ``sum(attend(q, k, v) * w)``."""
    return (attend(q, k, v),) + _grads(attend, w)(q, k, v)


@pytest.fixture()
def walked(monkeypatch):
    """``walked(fn, *args)``: the kernels of the causal walk among those
    that tracing ``fn`` builds (``fwd`` / ``dkdv`` / ``dq``), sorted."""
    seen = []
    real = fa._built

    def spy(kernel, plan):
        seen.append((kernel, plan["path"]))
        return real(kernel, plan)

    monkeypatch.setattr(fa, "_built", spy)

    def trace(fn, *args):
        del seen[:]
        jax.make_jaxpr(fn)(*args)
        return sorted(k for k, path in seen if path == "causal_tiled")

    return trace


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

    # -- the causal walk: causal self-attention over whole square tiles ----

    # T 256 is one sub-block, 1024 one tile (the one-pass backward), 2048
    # and 4096 have tiles under, on and above the diagonal; 64/64 is GPT-2's
    # head, 192/128 latent attention's (q and k carry nope + rope)
    @pytest.mark.parametrize("d,d_v", [(64, 64), (192, 128)], ids=["d64", "d192_v128"])
    @pytest.mark.parametrize("t", [256, 1024, 2048, 4096])
    def test_walk_matches_reference(self, t, d, d_v, walked):
        keys = jax.random.split(jax.random.PRNGKey(t + d), 4)
        q = jax.random.normal(keys[0], (1, t, 1, d))
        k = jax.random.normal(keys[1], (1, t, 1, d))
        v = jax.random.normal(keys[2], (1, t, 1, d_v))
        w = jax.random.normal(keys[3], (1, t, 1, d_v))
        # the backward walks at every such T, the forward beyond one tile
        # (under grad the forward is traced once more, for its residuals)
        want = ["dkdv", "dq"] + ["fwd"] * (t > 1024)
        assert walked(_grads(flash_attention, w), q, k, v) == want
        got = _out_and_grads(flash_attention, q, k, v, w)
        want = _out_and_grads(reference_attention, q, k, v, w)
        for a, b, atol in zip(got, want, (2e-5, 5e-5, 5e-5, 5e-5)):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=atol)

    @pytest.mark.parametrize("t,block", [(512, 256), (1024, 512), (1024, 256)])
    def test_walk_at_smaller_tiles_matches_reference(self, t, block, walked):
        """2x2 and 4x4 grids at a size the CPU runs in a second; two heads
        and two batch rows, so the head axis of the grid is walked too."""
        q, k, v = _qkv(t=t)
        w = _qkv(t=t, seed=1)[0]
        attend = functools.partial(
            flash_attention, causal=True, sm_scale=None, block_q=block, block_k=block
        )
        assert walked(_grads(attend, w), q, k, v) == ["dkdv", "dq", "fwd"]
        got = _out_and_grads(attend, q, k, v, w)
        want = _out_and_grads(reference_attention, q, k, v, w)
        for a, b, atol in zip(got, want, (2e-5, 5e-5, 5e-5, 5e-5)):
            np.testing.assert_allclose(a, b, atol=atol)

    def test_walk_bf16_inputs(self):
        q, k, v = _qkv(b=1, t=1024, dtype=jnp.bfloat16)
        w = _qkv(b=1, t=1024, seed=1)[0].astype(jnp.bfloat16)
        attend = functools.partial(
            flash_attention, causal=True, sm_scale=None, block_q=512, block_k=512
        )
        got = _out_and_grads(attend, q, k, v, w)
        want = _out_and_grads(reference_attention, q, k, v, w)
        for a, b in zip(got, want):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32), atol=6e-2
            )

    @pytest.mark.parametrize(
        "t_q,t_kv,causal,block_q,block_k,forward",
        [
            (300, 300, True, 1024, 1024, False),  # ragged T: a padded tile
            (1280, 1280, True, 1024, 1024, False),  # whole tiles and a ragged one
            (256, 512, True, 1024, 1024, False),  # t_q != t_kv (ring attention)
            (512, 512, False, 1024, 1024, False),  # no mask
            (512, 512, True, 256, 512, False),  # unequal blocks
            (128, 128, True, 1024, 1024, False),  # under one sub-block
            (1024, 1024, True, 1024, 1024, True),  # the one-tile forward
            (512, 512, True, 1024, 1024, True),  # ... the XL server's width
        ],
        ids=["ragged", "ragged_tail", "tq_ne_tkv", "non_causal", "unequal_blocks",
             "under_one_sub_block", "one_tile_forward", "one_tile_forward_512"],
    )
    def test_other_calls_keep_the_general_kernels(
        self, t_q, t_kv, causal, block_q, block_k, forward, walked
    ):
        blocks = fa._clamp_blocks(jnp.dtype(jnp.bfloat16), t_q, t_kv, block_q, block_k)
        assert fa._sub_block(causal, t_q, t_kv, *blocks, forward) == 0
        plan = fa._kernel_plan(causal, t_q, t_kv, *blocks, 0)
        assert plan["path"] == "general"
        q = jax.ShapeDtypeStruct((1, t_q, 2, 16), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, t_kv, 2, 16), jnp.float32)
        attend = functools.partial(
            flash_attention, causal=causal, sm_scale=None, block_q=block_q, block_k=block_k
        )
        if forward:
            assert walked(attend, q, kv, kv) == []
        else:
            assert walked(_grads(attend, 1.0), q, kv, kv) == []

    @pytest.mark.parametrize("t,block,forward,share", [
        (1024, 1024, False, 0.625), (4096, 1024, False, 0.53125),
        (512, 512, False, 0.75), (256, 256, False, 1.0), (768, 768, False, 2 / 3),
        (4096, 1024, True, 0.53125), (1024, 512, True, 0.625),
        (1536, 768, True, 7 / 12), (512, 256, True, 0.75),
    ])
    def test_walk_covers_the_mask_exactly_once(self, t, block, forward, share):
        """Every (query, key) pair the mask keeps is computed once, by
        either order of the walk; a pair above the diagonal only inside a
        masked square on it; tiles above the diagonal not at all."""
        sub = fa._sub_block(True, t, t, block, block, forward)
        assert sub == fa._SUB_BLOCK == 256
        keep = np.tril(np.ones((t, t), bool))
        squares = np.zeros((t, t), bool)
        for lo in range(0, t, sub):
            squares[lo:lo + sub, lo:lo + sub] = True
        n = t // block
        plan = fa._kernel_plan(True, t, t, block, block, sub)
        for by_keys in (False, True):
            seen = np.zeros((t, t), np.int8)
            for iq in range(n):
                for ik in range(iq):
                    seen[iq * block:(iq + 1) * block, ik * block:(ik + 1) * block] += 1
                at = iq * block
                pieces = fa._diagonal_pieces(block, sub, by_keys)
                assert len(pieces) == block // sub  # one a sub-block
                for r0, nr, k0, nk in pieces:
                    seen[at + r0:at + r0 + nr, at + k0:at + k0 + nk] += 1
                    # what the mask removes of a piece lies in its square on
                    # the diagonal: the last keys of a row block, the first
                    # rows of a key block
                    rest = (np.s_[at + r0 + sub:at + r0 + nr, at + k0:at + k0 + nk]
                            if by_keys else
                            np.s_[at + r0:at + r0 + nr, at + k0:at + k0 + nk - sub])
                    assert keep[rest].all()
            assert (seen[keep] == 1).all()
            assert (seen[~keep] == squares[~keep]).all()
            assert plan["score_share"] == pytest.approx(seen.sum() / t**2)
        assert plan["path"] == "causal_tiled"
        assert plan["score_share"] == pytest.approx(share)
        assert (plan["tiles_visited"], plan["tiles_run"]) == (n * n, n * (n + 1) // 2)

    def test_general_plan_is_the_parents_grid(self):
        # the general kernel at T 1024 and 4096: the whole square, 10 of 16 tiles
        for t, share, run in ((1024, 1.0, 1), (4096, 0.625, 10)):
            plan = fa._kernel_plan(True, t, t, 1024, 1024, 0)
            assert (plan["path"], plan["score_share"], plan["tiles_run"]) == (
                "general", share, run)
        # t_q != t_kv: the mask is aligned at the end, the first key tile runs
        plan = fa._kernel_plan(True, 256, 512, 256, 256, 0)
        assert (plan["tiles_visited"], plan["tiles_run"], plan["score_share"]) == (2, 2, 1.0)

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv"])
    def test_a_tile_above_the_diagonal_is_neither_run_nor_read(self, kernel, walked):
        """T 2048 in tiles of 1024: tile (q 0, keys 1) lies above the
        diagonal. What it would read is filled with NaN (for the forward and
        dq the keys and values of tile 1, for dk/dv the rows of tile 0): the
        results that tile would have touched are finite and the reference's,
        so it was not computed, not even to be masked (0 * NaN is NaN)."""
        t, half, scale = 2048, 1024, 0.125
        keys = jax.random.split(jax.random.PRNGKey(11), 4)
        q, k, v, do = (jax.random.normal(key, (2, t, 64)) for key in keys)
        assert walked(
            lambda *a: fa._flash_fwd(*a, scale, True, half, half), q, k, v
        ) == ["fwd"]
        out, lse = fa._flash_fwd(q, k, v, scale, True, half, half)
        dq, dk, dv = fa._flash_bwd(q, k, v, out, lse, do, scale, True, half, half)
        tail = lambda x: x.at[:, half:].set(jnp.nan)
        head = lambda x: x.at[:, :half].set(jnp.nan)
        if kernel == "fwd":
            got, _ = fa._flash_fwd(q, tail(k), tail(v), scale, True, half, half)
            got, want = got[:, :half], out[:, :half]
        elif kernel == "dq":
            got = fa._flash_bwd(
                q, tail(k), tail(v), out, lse, do, scale, True, half, half
            )[0]
            got, want = got[:, :half], dq[:, :half]
        else:
            got = fa._flash_bwd(
                head(q), k, v, head(out), head(lse), head(do), scale, True, half, half
            )[1:]
            got = jnp.concatenate([g[:, half:] for g in got], axis=-1)
            want = jnp.concatenate([dk[:, half:], dv[:, half:]], axis=-1)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_array_equal(got, want)


class TestFlashAttentionWindow:
    """``flash_attention(..., window=w)``: row ``i`` sees the ``w`` keys that
    end at its own. Walked (the band's tiles only) where the call takes the
    causal walk and the window is whole blocks; the general kernels with
    the window in their mask otherwise."""

    @staticmethod
    def _paths(fn, *args, monkeypatch):
        seen = []
        real = fa._built

        def spy(kernel, plan):
            seen.append((kernel, plan["path"], plan.get("window")))
            return real(kernel, plan)

        monkeypatch.setattr(fa, "_built", spy)
        jax.make_jaxpr(fn)(*args)
        return sorted(set(seen))

    # blocks of 256 (one sub-block a tile) and 512 (a tile walked in 2 x 2
    # sub-squares, so both masked pieces and an unmasked one): T of 2, 4 and
    # 8 blocks, a window of one block and of two
    # ... and T of 3 blocks under a window of 2 (``band`` 2 of ``n`` 3): row
    # tile 0's band hangs two steps over the sequence's edge and row tile 1's
    # one, key tiles 2 and 1 the same at their other end
    @pytest.mark.parametrize("blocks_back", [1, 2])
    @pytest.mark.parametrize("t,block", [(512, 256), (1024, 256), (2048, 256),
                                         (1024, 512), (2048, 512), (4096, 512),
                                         (768, 256)])
    def test_band_matches_reference(self, t, block, blocks_back, monkeypatch):
        window = blocks_back * block
        keys = jax.random.split(jax.random.PRNGKey(t + window), 4)
        q, k, v, w = (jax.random.normal(key, (1, t, 2, 16)) for key in keys)
        attend = functools.partial(
            flash_attention, causal=True, sm_scale=None, block_q=block, block_k=block,
            window=window)
        want_path = "window_tiled" if window < t else "causal_tiled"
        assert self._paths(_grads(attend, w), q, k, v, monkeypatch=monkeypatch) == [
            (kernel, want_path, window if window < t else None) for kernel in ("dkdv", "dq", "fwd")]
        got = _out_and_grads(attend, q, k, v, w)
        want = _out_and_grads(functools.partial(reference_attention, window=window), q, k, v, w)
        for a, b, atol in zip(got, want, (2e-5, 5e-5, 5e-5, 5e-5)):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=atol)

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv"])
    @pytest.mark.parametrize("window,inner", [
        (256, 2), (512, 3),  # a band of 1 and of 2 tiles: ``band + 1`` steps
        (None, 4), (1024, 4),  # no window, and one that cuts nothing off: ``n``
    ])
    def test_the_grid_is_the_band(self, kernel, window, inner):
        """The walked kernels' grids as ``jax.make_jaxpr`` shows them, T 1,024
        in blocks of 256 (``n`` 4) for 2 heads: ``(bh, n, band + 1)`` under a
        band, the parent's ``(bh, n, n)`` where there is none."""
        t, block, d, d_v = 1024, 256, 16, 32
        qk = jax.ShapeDtypeStruct((1, t, 2, d), jnp.float32)
        v = jax.ShapeDtypeStruct((1, t, 2, d_v), jnp.float32)
        attend = lambda q, k, v: flash_attention(q, k, v, True, None, block, block, window).sum()
        jaxpr = jax.make_jaxpr(jax.grad(attend, (0, 1, 2)))(qk, qk, v).jaxpr
        grids = {}
        for eqn in jaxpr.eqns:  # the walk's jitted wrappers are inlined
            if eqn.primitive.name != "pallas_call":
                continue
            outs = [a.shape for a in eqn.params["out_avals"]]
            # dq is one result; dk/dv are two ``[bh, t, .]``; the forward's
            # second is its lse as rows, ``[bh, 8, t]``
            name = "dq" if len(outs) == 1 else "dkdv" if outs[1] == (2, t, d_v) else "fwd"
            assert name not in grids
            grids[name] = eqn.params["grid_mapping"].grid
        assert sorted(grids) == ["dkdv", "dq", "fwd"]
        n = t // block
        assert grids[kernel] == (2, n, inner)
        band = window // block if window and window < t else 0
        plan = fa._kernel_plan(True, t, t, block, block, 256, window if band else None)
        assert plan["tiles_visited"] == n * inner
        # what the grid visits and does not run: the band's steps over the
        # sequence's edge, or with no band the tiles above the diagonal
        assert plan["tiles_visited"] - plan["tiles_run"] == (
            band * (band + 1) // 2 if band else n * (n - 1) // 2)

    @pytest.mark.parametrize("t,block,window", [
        (1000, 512, 300),  # a ragged T
        (768, 256, 300),  # whole tiles, a window that is no whole number of blocks
        (100, 32, 7), (64, 64, 1),
    ])
    def test_other_windows_take_the_general_kernels(self, t, block, window, monkeypatch):
        q, k, v = _qkv(b=1, t=t)
        w = _qkv(b=1, t=t, seed=1)[0]
        attend = functools.partial(
            flash_attention, causal=True, sm_scale=None, block_q=block, block_k=block,
            window=window)
        assert self._paths(_grads(attend, w), q, k, v, monkeypatch=monkeypatch) == [
            (kernel, "general", window) for kernel in ("dkdv", "dq", "fwd")]
        got = _out_and_grads(attend, q, k, v, w)
        want = _out_and_grads(functools.partial(reference_attention, window=window), q, k, v, w)
        for a, b, atol in zip(got, want, (2e-5, 5e-5, 5e-5, 5e-5)):
            np.testing.assert_allclose(a, b, atol=atol)

    def test_end_aligned_window_over_a_longer_kv(self):
        """``t_q != t_kv``: the window ends where the causal mask does."""
        q = _qkv(b=1, t=24)[0]
        _, k, v = _qkv(b=1, t=40, seed=1)
        out = flash_attention(q, k, v, True, None, 16, 16, 5)
        np.testing.assert_allclose(out, reference_attention(q, k, v, True, window=5), atol=2e-5)

    def test_a_window_that_cuts_nothing_is_the_causal_call(self):
        q, k, v = _qkv(t=64)
        np.testing.assert_array_equal(
            flash_attention(q, k, v, True, None, 32, 32, 64), flash_attention(q, k, v, True, None, 32, 32))
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, False, None, 32, 32, 8)

    def test_window_reaches_the_sharded_call(self, monkeypatch):
        from dlrover_tpu.ops.flash_attention import flash_attention_sharded

        q, k, v = _qkv(t=64)
        np.testing.assert_allclose(
            flash_attention_sharded(q, k, v, None, causal=True, window=9),
            reference_attention(q, k, v, True, window=9), atol=2e-5)

    @pytest.mark.parametrize("by_keys", [False, True])
    @pytest.mark.parametrize("t,block,window", [(8192, 1024, 1024), (2048, 512, 1024), (1024, 256, 256)])
    def test_band_covers_the_mask_exactly_once(self, t, block, window, by_keys):
        """Every (query, key) pair the window's mask keeps is computed once,
        by either order of the walk; a pair outside it only inside a masked
        square at one end of the band; tiles outside the band not at all."""
        sub, n, band = 256, t // block, window // block
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        keep = (j <= i) & (i - j < window)
        seen = np.zeros((t, t), np.int8)
        for iq in range(n):
            for ik in range(max(iq - band, 0), iq + 1):
                far, diag = ik == iq - band, ik == iq
                pieces = ([(0, block, 0, block)] if not (far or diag) else
                          fa._diagonal_pieces(block, sub, by_keys, far))
                for r0, nr, k0, nk in pieces:
                    seen[iq * block + r0:iq * block + r0 + nr, ik * block + k0:ik * block + k0 + nk] += 1
        assert (seen[keep] == 1).all() and seen.max() == 1
        plan = fa._kernel_plan(True, t, t, block, block, sub, window)
        assert plan["path"] == "window_tiled" and plan["window"] == window
        assert plan["score_share"] == pytest.approx(seen.sum() / t**2)
        assert plan["tiles_run"] == sum(min(iq, band) + 1 for iq in range(n))
        # what is computed beyond the mask lies in 256-wide squares on the band's two edges
        extra = seen.astype(bool) & ~keep
        edge = ((i // sub == j // sub) | ((i - window) // sub == j // sub))
        assert (extra <= edge).all()

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv"])
    def test_a_tile_before_the_band_is_neither_run_nor_read(self, kernel):
        """T 1024 in tiles of 256 under a window of 256: row tile 3 needs key
        tiles 2 and 3, key tile 0 needs row tiles 0 and 1. What lies outside
        is filled with NaN: the results are finite and the reference's, so
        those tiles were not computed, not even to be masked."""
        t, b, scale = 1024, 256, 0.125
        keys = jax.random.split(jax.random.PRNGKey(12), 4)
        q, k, v, do = (jax.random.normal(key, (2, t, 64)) for key in keys)
        out, lse = fa._flash_fwd(q, k, v, scale, True, b, b, b)
        dq, dk, dv = fa._flash_bwd(q, k, v, out, lse, do, scale, True, b, b, b)
        early = lambda x: x.at[:, :2 * b].set(jnp.nan)  # key tiles 0 and 1
        late = lambda x: x.at[:, 2 * b:].set(jnp.nan)  # row tiles 2 and 3
        if kernel == "fwd":
            got = fa._flash_fwd(q, early(k), early(v), scale, True, b, b, b)[0][:, 3 * b:]
            want = out[:, 3 * b:]
        elif kernel == "dq":
            got = fa._flash_bwd(q, early(k), early(v), out, lse, do, scale, True, b, b, b)[0][:, 3 * b:]
            want = dq[:, 3 * b:]
        else:
            got = fa._flash_bwd(late(q), k, v, late(out), late(lse), late(do), scale, True, b, b, b)[1:]
            got = jnp.concatenate([g[:, :b] for g in got], axis=-1)
            want = jnp.concatenate([dk[:, :b], dv[:, :b]], axis=-1)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_array_equal(got, want)


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
        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.models.layers import cross_entropy_loss
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
