"""Llama-family decoder (RMSNorm / RoPE / GQA / SwiGLU) with optional
GShard-style mixture-of-experts blocks, TPU-first.

Second flagship model family next to :mod:`dlrover_tpu.models.gpt`
(reference parity: the reference's examples span multiple model families
— GPT, Llama fine-tunes under FSDP/DeepSpeed, e.g.
``examples/pytorch/llama2/``; the runtime must not be shaped around one
architecture). Same discipline as gpt.py: bf16 activations, fp32 params,
logical-axis annotations everywhere, no data-dependent Python control
flow, remat per block.

The MoE layer is the einsum (GShard/Mesh-TF) formulation: top-2 gating
with a static per-expert capacity, dispatch/combine as one-hot einsums —
all shapes static, so XLA turns the expert-sharded matmuls into
all-to-alls over the ``ep`` mesh axis instead of host-side routing.
"""

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    RMSNorm, apply_rope, apply_rope_at, cached_decode_attention, chunked_token_ce, constrain,
    cross_entropy_loss, dtypes_read_by_name, param_with_axes, rope_tables)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int = 4  # < num_heads → grouped-query attention
    head_dim: int = 64
    embed_dim: int = 512
    mlp_dim: int = 1408  # ~8/3 * embed, rounded to a multiple of 128
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_remat: bool = True
    # >0: targets passed to __call__ fuse head+CE over seq chunks of
    # this size (the contract: ``models/build.py``)
    ce_chunk: int = 0
    attention_impl: str = ""  # "" → dense; flash|ring as in gpt.py
    # int8 decode KV cache with per-token per-kv-head scales
    # (``layers._update_decode_cache``)
    kv_cache_int8: bool = False
    # MoE: num_experts > 0 replaces every `moe_every`-th block's MLP with
    # a top-2 expert layer (0 = dense model).
    num_experts: int = 0
    moe_every: int = 2
    expert_mlp_dim: int = 0  # 0 → mlp_dim
    capacity_factor: float = 1.25

    @property
    def moe_mlp_dim(self) -> int:
        return self.expert_mlp_dim or self.mlp_dim

    def is_moe_block(self, layer_idx: int) -> bool:
        # Every `moe_every`-th block, LAST of each group: moe_every=1
        # means every block, moe_every=2 means layers 1, 3, 5, ...
        # NOTE: this rule changed from `% moe_every == 1` (which placed
        # no MoE blocks at all for moe_every=1 and layers 1,4,7 for
        # moe_every=3). Checkpoints trained under the old rule with
        # moe_every>2 have MoE params at different layer indices; a
        # restore fails loudly with "checkpoint missing leaf
        # layers_<i>/moe/..." (engine._restore_into_template) rather
        # than mis-restoring, because leaf paths encode the layer index.
        return self.num_experts > 0 and (
            layer_idx % self.moe_every == self.moe_every - 1
        )

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        base = dict(
            vocab_size=256,
            max_seq_len=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=8,
            embed_dim=32,
            mlp_dim=64,
            use_remat=False,
        )
        base.update(overrides)
        return LlamaConfig(**base)


class LlamaAttention(nn.Module):
    """GQA causal attention with rotary embeddings."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, KVH, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if H % KVH:
            raise ValueError(f"num_heads {H} not a multiple of kv heads {KVH}")

        wq = param_with_axes(
            "wq",
            nn.initializers.normal(0.02),
            (D, H, Hd),
            cfg.param_dtype,
            axes=("embed", "heads", "kv"),
        )
        wk = param_with_axes(
            "wk",
            nn.initializers.normal(0.02),
            (D, KVH, Hd),
            cfg.param_dtype,
            axes=("embed", "kv_heads", "kv"),
        )
        wv = param_with_axes(
            "wv",
            nn.initializers.normal(0.02),
            (D, KVH, Hd),
            cfg.param_dtype,
            axes=("embed", "kv_heads", "kv"),
        )
        wo = param_with_axes(
            "wo",
            nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.num_layers)),
            (H, Hd, D),
            cfg.param_dtype,
            axes=("heads", "kv", "embed"),
        )
        q = jnp.einsum("btd,dhk->bthk", x, wq.astype(cfg.dtype))
        k = jnp.einsum("btd,dgk->btgk", x, wk.astype(cfg.dtype))
        v = jnp.einsum("btd,dgk->btgk", x, wv.astype(cfg.dtype))

        if decode:
            # RoPE at the tokens' absolute positions (left-padded prompts
            # carry a per-row position array), then cache the SMALL
            # pre-repeat GQA k/v — the KVH-wide cache is the whole point
            # of grouped-query attention at decode time.
            cos_t, sin_t = rope_tables(
                cfg.max_seq_len, Hd, cfg.rope_theta
            )
            if positions is None:
                raise ValueError("decode=True needs absolute positions")
            q = apply_rope_at(q, cos_t, sin_t, positions)
            k = apply_rope_at(k, cos_t, sin_t, positions)
            # no repeat: the grouped contraction runs q heads
            # against the narrow KVH-wide cache instead of widening it
            # every step (int8 caches take the int8 x int8 path)
            return cached_decode_attention(
                self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots,
                wo, cfg,
            )

        cos, sin = rope_tables(T, Hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        # Expand kv groups to full heads for the shared attention kernels
        # (flash/ring take equal head counts). The repeat is free under
        # XLA when the kv tensor is small (KVH << H is the GQA point).
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
        q = constrain(q, "batch", "seq", "heads", "kv")
        k = constrain(k, "batch", "seq", "heads", "kv")
        v = constrain(v, "batch", "seq", "heads", "kv")

        impl = cfg.attention_impl or "dense"
        if impl == "ring":
            from ..ops.ring_attention import ring_attention_sharded
            from ..parallel.mesh import get_current_mesh

            mesh = get_current_mesh()
            if mesh is None:
                raise ValueError("attention_impl='ring' needs current_mesh")
            out = ring_attention_sharded(q, k, v, mesh, causal=True)
        elif impl == "flash":
            from ..ops.flash_attention import flash_attention_sharded
            from ..parallel.mesh import get_current_mesh

            out = flash_attention_sharded(
                q, k, v, get_current_mesh(), causal=True
            )
        elif impl == "dense":
            scale = 1.0 / jnp.sqrt(Hd).astype(cfg.dtype)
            logits = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            logits = jnp.where(mask[None, None, :, :], logits, -1e9)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
                cfg.dtype
            )
            out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
        else:
            raise ValueError(f"unknown attention_impl {impl!r}")
        out = constrain(out, "batch", "seq", "heads", "kv")
        y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
        return constrain(y, "batch", "seq", "embed")


class SwiGluMlp(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        D, F = cfg.embed_dim, cfg.mlp_dim
        w_gate = param_with_axes(
            "w_gate",
            nn.initializers.normal(0.02),
            (D, F),
            cfg.param_dtype,
            axes=("embed", "mlp"),
        )
        w_up = param_with_axes(
            "w_up",
            nn.initializers.normal(0.02),
            (D, F),
            cfg.param_dtype,
            axes=("embed", "mlp"),
        )
        w_down = param_with_axes(
            "w_down",
            nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.num_layers)),
            (F, D),
            cfg.param_dtype,
            axes=("mlp", "embed"),
        )
        h = jax.nn.silu(jnp.dot(x, w_gate.astype(cfg.dtype))) * jnp.dot(
            x, w_up.astype(cfg.dtype)
        )
        h = constrain(h, "batch", "seq", "mlp")
        y = jnp.dot(h, w_down.astype(cfg.dtype))
        return constrain(y, "batch", "seq", "embed")


class MoeMlp(nn.Module):
    """Top-2 expert-parallel SwiGLU layer (GShard einsum formulation):
    the *capacity-factor* layer. Each expert takes at most
    ``capacity_factor * 2 * S / E`` tokens of a batch row and the rest
    are dropped; the ``[B,S,E,C]`` dispatch mask grows with tokens x
    experts, which rules it out at hundreds of experts. The dropless
    layer (sigmoid scores, top-k of hundreds, sorted grouped products
    over the experts a chip holds) is ``models/moe.py: MoeLayer``.

    Static shapes throughout: gating produces a [B,S,E,C] dispatch mask
    via one-hot position-in-expert bookkeeping; dispatch and combine are
    einsums, so the expert-sharded matmuls compile to a2a + local matmul
    over the ``ep`` axis — no host routing, no dynamic shapes.
    Auxiliary load-balance loss is stored via ``self.sow`` under
    ``("losses", "moe_aux")``.
    """

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, D = x.shape
        E = cfg.num_experts
        F = cfg.moe_mlp_dim
        # capacity: tokens each expert may accept from each batch row
        C = max(1, int(cfg.capacity_factor * 2 * S / E))

        w_router = param_with_axes(
            "w_router",
            nn.initializers.normal(0.02),
            (D, E),
            cfg.param_dtype,
            axes=("embed", None),
        )
        w_gate = param_with_axes(
            "w_gate",
            nn.initializers.normal(0.02),
            (E, D, F),
            cfg.param_dtype,
            axes=("expert", "embed", "expert_mlp"),
        )
        w_up = param_with_axes(
            "w_up",
            nn.initializers.normal(0.02),
            (E, D, F),
            cfg.param_dtype,
            axes=("expert", "embed", "expert_mlp"),
        )
        w_down = param_with_axes(
            "w_down",
            nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.num_layers)),
            (E, F, D),
            cfg.param_dtype,
            axes=("expert", "expert_mlp", "embed"),
        )

        # -- top-2 gating (fp32 for a stable softmax/argmax) --------------
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), w_router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate1 = jnp.argmax(probs, axis=-1)  # [B,S]
        p1 = jnp.take_along_axis(probs, gate1[..., None], axis=-1)[..., 0]
        masked = probs * (1.0 - jax.nn.one_hot(gate1, E))
        gate2 = jnp.argmax(masked, axis=-1)
        p2 = jnp.take_along_axis(masked, gate2[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(p1 + p2, 1e-9)
        p1, p2 = p1 / denom, p2 / denom

        # load-balance aux loss (GShard eq.4): mean gate prob * mean
        # assignment fraction per expert, scaled by E
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jax.nn.one_hot(gate1, E), axis=(0, 1))
        self.sow("losses", "moe_aux", E * jnp.sum(me * ce))

        def dispatch_mask(gate, prio_offset):
            """[B,S,E,C] one-hot of (expert, position-within-capacity)."""
            onehot = jax.nn.one_hot(gate, E)  # [B,S,E]
            pos = jnp.cumsum(onehot, axis=1) - 1 + prio_offset  # [B,S,E]
            keep = (pos < C) & (onehot > 0)
            pos_oh = jax.nn.one_hot(pos, C)  # [B,S,E,C]
            return pos_oh * keep[..., None], pos

        mask1, pos1 = dispatch_mask(gate1, 0.0)
        # second choices queue behind every first-choice token
        count1 = jnp.sum(jax.nn.one_hot(gate1, E), axis=1, keepdims=True)
        mask2, _ = dispatch_mask(gate2, count1)

        combine = (
            mask1 * p1[..., None, None] + mask2 * p2[..., None, None]
        ).astype(cfg.dtype)  # [B,S,E,C]
        dispatch = (mask1 + mask2).astype(cfg.dtype)

        # -- dispatch -> expert compute -> combine ------------------------
        xe = jnp.einsum("bsec,bsd->becd", dispatch, x)  # [B,E,C,D]
        xe = constrain(xe, "batch", "expert", None, "embed")
        h = jax.nn.silu(
            jnp.einsum("becd,edf->becf", xe, w_gate.astype(cfg.dtype))
        ) * jnp.einsum("becd,edf->becf", xe, w_up.astype(cfg.dtype))
        h = constrain(h, "batch", "expert", None, "expert_mlp")
        ye = jnp.einsum("becf,efd->becd", h, w_down.astype(cfg.dtype))
        y = jnp.einsum("bsec,becd->bsd", combine, ye)
        return constrain(y, "batch", "seq", "embed")


class LlamaBlock(nn.Module):
    config: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None, cache_slots=None):
        cfg = self.config
        x = x + LlamaAttention(cfg)(
            RMSNorm(cfg)(x),
            decode=decode,
            positions=positions,
            kv_valid=kv_valid,
            cache_slots=cache_slots,
        )
        mlp = MoeMlp(cfg) if cfg.is_moe_block(self.layer_idx) else SwiGluMlp(cfg)
        x = x + mlp(RMSNorm(cfg)(x))
        return x


# Every use of these is ``leaf.astype(cfg.dtype)``. RMSNorm's ``scale``
# multiplies in float32 and the MoE's ``w_router`` is scored in float32:
# they are not here.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"}
)


class Llama(nn.Module):
    """``__call__(tokens[B,T]) -> logits[B,T,V]``.

    ``targets`` given → per-token losses ``[B, T]`` through the fused
    chunked-CE path (pair with ``layers.token_loss_mean``).
    """

    config: LlamaConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        """The dtype ``__call__`` reads each leaf of ``params`` in (the
        contract: ``models/build.py``)."""
        return dtypes_read_by_name(
            params, _READ_IN_COMPUTE_DTYPE, self.config.dtype
        )

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        targets=None,
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
        with jax.named_scope("llama.embed"):
            x = wte.astype(cfg.dtype)[tokens]
            x = constrain(x, "batch", "seq", "embed")
        # decode bypasses remat: no backward pass, and the decode kwargs
        # must not cross jax.checkpoint (it would trace the bool).
        if cfg.use_remat and not decode:
            block = nn.remat(
                LlamaBlock,
                prevent_cse=False,
                policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=(),
            )
            for i in range(cfg.num_layers):
                x = block(cfg, layer_idx=i, name=f"block_{i}")(x)
        else:
            for i in range(cfg.num_layers):
                x = LlamaBlock(cfg, layer_idx=i, name=f"block_{i}")(
                    x,
                    decode=decode,
                    positions=positions,
                    kv_valid=kv_valid,
                    cache_slots=cache_slots,
                )
        w_lm = param_with_axes(
            "lm_head",
            nn.initializers.normal(0.02),
            (cfg.embed_dim, cfg.vocab_size),
            cfg.param_dtype,
            axes=("embed", "vocab"),
        )
        with jax.named_scope("llama.head"):
            x = RMSNorm(cfg, name="norm_f")(x)
            if targets is not None:
                return chunked_token_ce(
                    x,
                    w_lm.astype(cfg.dtype),
                    targets,
                    cfg.ce_chunk or T,
                    vocab_first=False,
                )
            logits = jnp.dot(x, w_lm.astype(cfg.dtype))
            return constrain(logits, "batch", "seq", "vocab")


def llama_loss(model_vars_or_logits, targets=None, aux_weight: float = 0.01):
    """CE loss; when applied through ``apply(..., mutable=["losses"])`` the
    caller adds the sowed MoE aux terms — this helper covers the plain
    logits path used by the generic train step."""
    return cross_entropy_loss(model_vars_or_logits, targets)
