"""GPT-style decoder-only transformer, TPU-first.

Flagship model family for the runtime (the reference's headline workloads
are GPT-2/GLM elastic jobs — e.g. ``examples/pytorch/gpt``). Written for
the MXU: bf16 activations, fp32 params/optimizer, matmul-heavy blocks,
logical-axis annotations everywhere so the same module runs 1-chip or
pjit over any dp/fsdp/tp/sp mesh. No data-dependent Python control flow —
everything traces once.
"""

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    cached_decode_attention, chunked_token_ce, constrain, dtypes_read_by_name, param_with_axes)
from .layers import cross_entropy_loss  # noqa: F401  GPT's loss over its logits: callers take it from here


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    head_dim: int = 64
    embed_dim: int = 768
    mlp_ratio: int = 4
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_remat: bool = True  # jax.checkpoint each block: HBM for FLOPs
    # Remat aggressiveness when use_remat: "nothing" recomputes the
    # whole block in backward (min HBM, ~1 extra fwd of FLOPs); "dots"
    # saves matmul outputs and recomputes only elementwise ops (middle
    # ground — the MXU work is NOT redone, only VPU ops are). With
    # fused-CE freeing the logits HBM, "dots" (or use_remat=False) can
    # buy back most of the remat FLOPs at the headline batch.
    remat_policy: str = "nothing"  # "nothing" | "dots"
    # >0: when targets are passed to __call__, compute per-token CE
    # inside the model over seq chunks of this size — the [B,T,V] fp32
    # logits (the HBM ceiling: 6.6 GB at bs=32/seq=1024/vocab=50k)
    # never materialize whole, and backward recomputes each chunk's
    # logits (jax.checkpoint), unlocking larger batches.
    ce_chunk: int = 0
    use_flash_attention: bool = False  # pallas kernel from dlrover_tpu.ops
    # "dense" | "flash" (pallas kernel, single-device/data-parallel) |
    # "ring" (sp-sharded exact attention via shard_map; needs
    # parallel.mesh.current_mesh to be active)
    attention_impl: str = ""
    tie_embeddings: bool = True
    # int8 decode KV cache: values stored int8 with a per-token
    # per-kv-head scale (amax/127), dequantized in-register on the
    # attention read. Decode attention is HBM-bound — halving the
    # cache bytes is the decode-throughput lever (and doubles the
    # batch a given HBM budget serves). The whole decode-mode path
    # (prefill AND incremental steps) attends over the quantized
    # cache; only the training forward (no cache) is untouched.
    kv_cache_int8: bool = False

    def resolved_attention_impl(self) -> str:
        if self.attention_impl:
            return self.attention_impl
        return "flash" if self.use_flash_attention else "dense"

    @property
    def mlp_dim(self) -> int:
        return self.mlp_ratio * self.embed_dim

    @staticmethod
    def tiny() -> "GPTConfig":
        return GPTConfig(
            vocab_size=256,
            max_seq_len=128,
            num_layers=2,
            num_heads=4,
            head_dim=8,
            embed_dim=32,
            use_remat=False,
        )

    @staticmethod
    def gpt2_small() -> "GPTConfig":
        return GPTConfig(num_layers=12, num_heads=12, head_dim=64, embed_dim=768)

    @staticmethod
    def gpt2_xl() -> "GPTConfig":
        return GPTConfig(num_layers=48, num_heads=25, head_dim=64, embed_dim=1600)


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        x,
        *,
        deterministic: bool = True,
        decode: bool = False,
        kv_valid=None,
        cache_slots=None,
    ):
        cfg = self.config
        B, T, D = x.shape
        H, Hd = cfg.num_heads, cfg.head_dim

        wqkv = param_with_axes(
            "wqkv",
            nn.initializers.normal(0.02),
            (D, 3, H, Hd),
            cfg.param_dtype,
            axes=("embed", None, "heads", "kv"),
        )
        wo = param_with_axes(
            "wo",
            nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.num_layers)),
            (H, Hd, D),
            cfg.param_dtype,
            axes=("heads", "kv", "embed"),
        )
        qkv = jnp.einsum("btd,dchk->cbthk", x, wqkv.astype(cfg.dtype))
        q, k, v = qkv[0], qkv[1], qkv[2]
        q = constrain(q, "batch", "seq", "heads", "kv")
        k = constrain(k, "batch", "seq", "heads", "kv")
        v = constrain(v, "batch", "seq", "heads", "kv")

        if decode:
            return cached_decode_attention(
                self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots,
                wo, cfg,
            )

        impl = cfg.resolved_attention_impl()
        if impl not in ("dense", "flash", "ring"):
            raise ValueError(
                f"unknown attention_impl {impl!r}; expected dense|flash|ring"
            )
        if impl == "ring":
            from ..ops.ring_attention import ring_attention_sharded
            from ..parallel.mesh import get_current_mesh

            mesh = get_current_mesh()
            if mesh is None:
                raise ValueError(
                    "attention_impl='ring' needs parallel.mesh.current_mesh "
                    "active around model application"
                )
            out = ring_attention_sharded(q, k, v, mesh, causal=True)
        elif impl == "flash":
            from ..ops.flash_attention import flash_attention_sharded
            from ..parallel.mesh import get_current_mesh

            out = flash_attention_sharded(
                q, k, v, get_current_mesh(), causal=True
            )
        else:
            scale = 1.0 / jnp.sqrt(Hd).astype(cfg.dtype)
            logits = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            logits = jnp.where(mask[None, None, :, :], logits, -1e9)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
        out = constrain(out, "batch", "seq", "heads", "kv")
        y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
        return constrain(y, "batch", "seq", "embed")


class Mlp(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        D, F = cfg.embed_dim, cfg.mlp_dim
        w1 = param_with_axes(
            "w1",
            nn.initializers.normal(0.02),
            (D, F),
            cfg.param_dtype,
            axes=("embed", "mlp"),
        )
        b1 = param_with_axes(
            "b1", nn.initializers.zeros, (F,), cfg.param_dtype, axes=("mlp",)
        )
        w2 = param_with_axes(
            "w2",
            nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.num_layers)),
            (F, D),
            cfg.param_dtype,
            axes=("mlp", "embed"),
        )
        b2 = param_with_axes(
            "b2", nn.initializers.zeros, (D,), cfg.param_dtype, axes=("embed",)
        )
        h = jnp.dot(x, w1.astype(cfg.dtype)) + b1.astype(cfg.dtype)
        h = constrain(h, "batch", "seq", "mlp")
        h = jax.nn.gelu(h)
        y = jnp.dot(h, w2.astype(cfg.dtype)) + b2.astype(cfg.dtype)
        return constrain(y, "batch", "seq", "embed")


class LayerNorm(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = param_with_axes(
            "scale", nn.initializers.ones, (x.shape[-1],), cfg.param_dtype, axes=("norm",)
        )
        bias = param_with_axes(
            "bias", nn.initializers.zeros, (x.shape[-1],), cfg.param_dtype, axes=("norm",)
        )
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
        return (y * scale + bias).astype(cfg.dtype)


class Block(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        x,
        *,
        deterministic: bool = True,
        decode: bool = False,
        kv_valid=None,
        cache_slots=None,
    ):
        x = x + CausalSelfAttention(self.config)(
            LayerNorm(self.config)(x),
            deterministic=deterministic,
            decode=decode,
            kv_valid=kv_valid,
            cache_slots=cache_slots,
        )
        x = x + Mlp(self.config)(LayerNorm(self.config)(x))
        return x


# Every use of these is ``leaf.astype(cfg.dtype)``. LayerNorm's ``scale``
# and ``bias`` are read in float32 and are not here.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "wpe", "wqkv", "wo", "w1", "b1", "w2", "b2", "lm_head"}
)


class GPT(nn.Module):
    """Decoder-only LM. ``__call__(tokens[B,T]) -> logits[B,T,V]``.

    With ``targets`` given the return value is per-token losses
    ``[B, T]`` (fp32, 0.0 at ``ignore_index`` positions) — pair with
    ``layers.token_loss_mean`` as the train-step loss. ``cfg.ce_chunk``
    > 0 additionally fuses head + CE chunk-by-chunk so the full logits
    tensor never exists (0 = one whole-sequence chunk).
    """

    config: GPTConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        """The dtype ``__call__`` reads each leaf of ``params`` in. A
        holder that rounds a leaf to it once (the serving engine) asks
        the arithmetic of the float32 tree: ``astype`` of a value
        already in that dtype is the identity."""
        return dtypes_read_by_name(
            params, _READ_IN_COMPUTE_DTYPE, self.config.dtype
        )

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        targets=None,
        deterministic: bool = True,
        decode: bool = False,
        positions=None,
        kv_valid=None,
        cache_slots=None,
    ):
        cfg = self.config
        B, T = tokens.shape
        wte = param_with_axes(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.embed_dim),
            cfg.param_dtype,
            axes=("vocab", "embed"),
        )
        wpe = param_with_axes(
            "wpe",
            nn.initializers.normal(0.01),
            (cfg.max_seq_len, cfg.embed_dim),
            cfg.param_dtype,
            axes=(None, "embed"),
        )
        if positions is None and decode:
            raise ValueError("decode=True needs absolute positions")
        with jax.named_scope("gpt.embed"):
            if positions is None:
                pos_emb = wpe.astype(cfg.dtype)[None, :T]
            else:
                pos_emb = wpe.astype(cfg.dtype)[positions]  # [B, T, D]
            x = wte.astype(cfg.dtype)[tokens] + pos_emb
            x = constrain(x, "batch", "seq", "embed")

        # remat trades FLOPs for HBM in training; during incremental
        # decode there is no backward pass and the cache collection must
        # stay plainly mutable, so bypass it. The decode kwargs must not
        # cross nn.remat either — jax.checkpoint would trace the bool.
        if cfg.use_remat and not decode:
            policies = {
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.dots_saveable,
            }
            if cfg.remat_policy not in policies:
                raise ValueError(
                    f"unknown remat_policy {cfg.remat_policy!r}; "
                    f"expected one of {sorted(policies)}"
                )
            policy = policies[cfg.remat_policy]
            block = nn.remat(
                Block,
                prevent_cse=False,
                policy=policy,
            )
            for i in range(cfg.num_layers):
                x = block(cfg, name=f"block_{i}")(
                    x, deterministic=deterministic
                )
        else:
            for i in range(cfg.num_layers):
                x = Block(cfg, name=f"block_{i}")(
                    x,
                    deterministic=deterministic,
                    decode=decode,
                    kv_valid=kv_valid,
                    cache_slots=cache_slots,
                )
        # the last norm, the head and the loss: one device scope (the
        # blocks' parts are named by their flax modules)
        with jax.named_scope("gpt.head"):
            x = LayerNorm(cfg, name="ln_f")(x)

            if cfg.tie_embeddings:
                w_head = wte.astype(cfg.dtype)  # [V, D]
                vocab_first = True
            else:
                w_head = param_with_axes(
                    "lm_head",
                    nn.initializers.normal(0.02),
                    (cfg.embed_dim, cfg.vocab_size),
                    cfg.param_dtype,
                    axes=("embed", "vocab"),
                ).astype(cfg.dtype)  # [D, V]
                vocab_first = False

            if targets is not None:
                # uniform contract: targets given -> per-token losses.
                # ce_chunk=0 degenerates to one whole-sequence chunk (the
                # dense math, just routed through the fused path) so the
                # pairing with token_loss_mean can never be silently wrong.
                return chunked_token_ce(
                    x, w_head, targets, cfg.ce_chunk or T, vocab_first
                )

            if vocab_first:
                logits = jnp.einsum("btd,vd->btv", x, w_head)
            else:
                logits = jnp.dot(x, w_head)
            return constrain(logits, "batch", "seq", "vocab")
