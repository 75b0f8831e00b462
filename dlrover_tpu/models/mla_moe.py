"""Latent-attention, dropless mixture-of-experts decoder with a
multi-token-prediction module (the DeepSeek-V3 layer equations), as one
chip of an expert-parallel group trains it.

The config's keys are the published ones, by their published names
(``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``first_k_dense_replace``, ``n_routed_experts`` ...), so a model's public
``config.json`` reads straight into :class:`MlaMoeConfig`. The layers:

- **Latent attention** in every block: ``c_q = RMSNorm(x W_DQ)``,
  ``q = c_q W_UQ`` in heads of nope + rope; ``[c_kv ; k_r] = x W_DKV``,
  ``[k_nope ; v] = RMSNorm(c_kv) W_UKV``; ``k_r`` is one rope vector a
  position, shared by all heads; RoPE on ``q_rope`` and ``k_r`` only,
  pairs interleaved; scores over ``[nope ; rope]`` scaled by
  ``1/sqrt(nope + rope)``; v and the output are ``v_head_dim`` wide, so
  the flash kernel runs with two head sizes.
- **SwiGLU** in the first ``first_k_dense_replace`` blocks.
- **Experts** in the others: ``s = sigmoid(x W_r)`` in float32 over all
  ``n_routed_experts``; the top ``num_experts_per_tok`` of ``s + b``
  (``e_score_correction_bias``, which enters the selection only and so
  takes no gradient; ``frozen_leaves`` tells the train step to leave it
  alone); gates are the unbiased ``s`` of the chosen, normalised, times
  ``routed_scaling_factor``; plus the shared expert. **No capacity, no
  dropped token.**
- **MTP** (depth 1): ``h' = W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)]``, one expert block, a final norm of its own, then the
  trunk's head (embedding and head are shared leaves); it predicts
  ``t_{i+2}``.

**The chip's share.** ``experts_held`` / ``expert_offset`` say which of
the ``n_routed_experts`` live here. The router stays full width, every
token still picks ``num_experts_per_tok`` of all of them, and the layer
adds its own experts' part plus the shared expert. Assignments to absent
experts are counted and skipped: what those experts would add is another
chip's to compute, and nothing here stands in for it or for the
exchange. (``experts_held = 0`` holds them all.) The layer is
``moe.MoeLayer``, which says how the experts held are computed.

With ``targets`` the model returns the trunk's per-token losses (the
fused-CE contract: ``models/build.py``) and sows the weighted MTP loss under
``("objective", "mtp")`` and its counters under ``"metrics"``; the train
step adds the one and returns the others.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash_attention import flash_attention_sharded
from ..parallel.mesh import get_current_mesh
from .layers import (
    KEEP_FLASH_RESULTS, RMSNorm, SwiGlu, chunked_token_ce, constrain, param_with_axes,
    token_loss_mean, weight)
from .moe import MoeLayer, MoeSizes, book_step_counters


@dataclass(frozen=True)
class MlaMoeConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    num_nextn_predict_layers: int = 1
    # -- the chip's share of an expert-parallel group -----------------------
    experts_held: int = 0  # 0: all of them
    expert_offset: int = 0  # the first expert held
    # -- what no config states ------------------------------------------------
    mtp_loss_weight: float = 0.3  # lambda of the DeepSeek-V3 report
    init_std: float = 0.02
    bias_init_std: float = 0.01  # so that the bias changes selections
    # -- how it is computed -----------------------------------------------------
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # each block recomputed in the backward pass from its input and its flash
    # kernel's two results, which are kept (``_block``): near the memory limit
    # that is tokens x heads x (2 x v_head_dim + 4) bytes a block
    use_remat: bool = True
    ce_chunk: int = 0  # 0: each head's losses in one chunk

    # The objective has a term the model computes from the targets (MTP):
    # the train step hands them in whatever ce_chunk says.
    takes_targets = True
    # Leaves that take neither gradient nor weight decay.
    frozen_leaves: Tuple[str, ...] = ("e_score_correction_bias",)

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing is not implemented")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func {self.scoring_func!r} is not implemented")
        if not self.rope_interleave:
            raise ValueError("only interleaved RoPE pairs are implemented")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one MTP module at most")
        held = self.experts_here
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError("the experts held do not lie inside the routed ones")

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def rms_eps(self) -> float:  # the name ``layers.RMSNorm`` reads
        return self.rms_norm_eps

    @property
    def moe_sizes(self) -> MoeSizes:
        return MoeSizes(
            n_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, experts_held=self.experts_held,
            expert_offset=self.expert_offset, norm_topk=self.norm_topk_prob,
            scale=self.routed_scaling_factor, n_shared=self.n_shared_experts,
            init_std=self.init_std, bias_init_std=self.bias_init_std,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def is_expert_block(self, layer_idx: int) -> bool:
        return layer_idx >= self.first_k_dense_replace

    @staticmethod
    def tiny(**overrides) -> "MlaMoeConfig":
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=2,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            rope_theta=10000.0, n_routed_experts=8, num_experts_per_tok=2,
            use_remat=False,
        )
        base.update(overrides)
        return MlaMoeConfig(**base)


def rope_interleaved(x, theta: float):
    """RoPE over ``x [B, T, ..., d]`` with the pairs interleaved: channels
    ``2i`` and ``2i + 1`` turn by ``t * theta^(-2i/d)``. Written with lane
    rolls, so that nothing is re-laid out: ``out = x cos + swap(x) sin``
    with ``swap(x)[2i] = -x[2i+1]`` and ``swap(x)[2i+1] = x[2i]``."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.repeat(jnp.outer(jnp.arange(t, dtype=jnp.float32), freqs), 2, axis=-1)
    shape = (1, t) + (1,) * (x.ndim - 3) + (d,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x32 = x.astype(jnp.float32)
    even = (jnp.arange(d) % 2 == 0).reshape((1,) * (x.ndim - 1) + (d,))
    swapped = jnp.where(even, -jnp.roll(x32, -1, axis=-1), jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + swapped * sin).astype(x.dtype)


class LatentAttention(nn.Module):
    config: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, D = x.shape
        H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        w_dq = weight("w_dq", cfg, (D, cfg.q_lora_rank), ("embed", "q_lora"))
        w_uq = weight("w_uq", cfg, (cfg.q_lora_rank, H, nope + rope),
                      ("q_lora", "heads", "kv"))
        w_dkv = weight("w_dkv", cfg, (D, cfg.kv_lora_rank + rope), ("embed", "kv_lora"))
        w_ukv = weight("w_ukv", cfg, (cfg.kv_lora_rank, H, nope + dv),
                       ("kv_lora", "heads", "kv"))
        w_o = weight("w_o", cfg, (H, dv, D), ("heads", "kv", "embed"))

        c_q = RMSNorm(cfg, name="q_norm")(jnp.dot(x, w_dq))
        q = jnp.einsum("btr,rhk->bthk", c_q, w_uq)
        down = jnp.dot(x, w_dkv)
        c_kv = RMSNorm(cfg, name="kv_norm")(down[..., : cfg.kv_lora_rank])
        k_r = rope_interleaved(down[..., cfg.kv_lora_rank:], cfg.rope_theta)
        kv = jnp.einsum("btr,rhk->bthk", c_kv, w_ukv)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], cfg.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, rope))], axis=-1)
        v = kv[..., nope:]
        q = constrain(q, "batch", "seq", "heads", "kv")
        k = constrain(k, "batch", "seq", "heads", "kv")
        v = constrain(v, "batch", "seq", "heads", "kv")
        with jax.named_scope("mla.attend"):
            out = flash_attention_sharded(q, k, v, get_current_mesh(), causal=True)
        out = constrain(out, "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bthk,hkd->btd", out, w_o), "batch", "seq", "embed")


class Block(nn.Module):
    config: MlaMoeConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope("mla.attn"):  # norm, projections, and mla.attend inside
            x = x + LatentAttention(cfg, name="attn")(RMSNorm(cfg, name="norm_attn")(x))
        h = RMSNorm(cfg, name="norm_mlp")(x)
        if cfg.is_expert_block(self.layer_idx):
            y = MoeLayer(cfg.moe_sizes, name="moe")(h)
        else:
            with jax.named_scope("mla.mlp"):
                y = SwiGlu(cfg, cfg.intermediate_size, name="mlp")(h)
        return constrain(x + y, "batch", "seq", "embed")


def _block(cfg: MlaMoeConfig):
    if not cfg.use_remat:
        return Block
    # The backward pass recomputes the block from its input, except what its
    # flash kernel wrote: ``out`` and ``lse`` (named in ``_fa_fwd``) are kept,
    # so the forward kernel runs once a step and not twice. A block holds
    # tokens x heads x (2 x v_head_dim + 4) bytes for them beside its kept
    # input (136 MB beside 67 MB at b4 x 4096, 32 heads of 128).
    return nn.remat(Block, prevent_cse=True, policy=KEEP_FLASH_RESULTS)


class MtpModule(nn.Module):
    """One multi-token-prediction depth: the next token's embedding and
    the trunk's (normed) output, joined, through one expert block."""

    config: MlaMoeConfig

    @nn.compact
    def __call__(self, h, next_emb):
        cfg = self.config
        D = cfg.hidden_size
        w_eh = weight("w_eh", cfg, (2 * D, D), (None, "embed"))
        joined = jnp.concatenate(
            [RMSNorm(cfg, name="norm_e")(next_emb), RMSNorm(cfg, name="norm_h")(h)], axis=-1)
        x = jnp.dot(joined, w_eh)
        x = _block(cfg)(cfg, layer_idx=cfg.first_k_dense_replace, name="block")(x)
        return RMSNorm(cfg, name="norm_f")(x)


class MlaMoeLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]``; with ``targets``
    (``targets[:, i]`` is the token after ``tokens[:, i]``) the trunk's
    per-token losses ``[B, T]``, the MTP loss sown beside them."""

    config: MlaMoeConfig

    @nn.compact
    def __call__(self, tokens, *, targets=None):
        cfg = self.config
        B, T = tokens.shape
        wte = param_with_axes(
            "wte", nn.initializers.normal(cfg.init_std),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype,
            axes=("vocab", "embed")).astype(cfg.dtype)
        w_head = weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size),
                        ("embed", "vocab"))
        with jax.named_scope("mla.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        for i in range(cfg.num_hidden_layers):
            x = _block(cfg)(cfg, layer_idx=i, name=f"block_{i}")(x)
        with jax.named_scope("mla.head"):
            h = RMSNorm(cfg, name="norm_f")(x)
        chunk = cfg.ce_chunk or T

        mtp = cfg.num_nextn_predict_layers > 0
        if mtp and (targets is not None or self.is_initializing()):
            # init traces the module whatever the call, so that its leaves exist
            nxt = targets if targets is not None else tokens
            with jax.named_scope("mla.mtp"):
                h_mtp = MtpModule(cfg, name="mtp_0")(h, wte[jnp.maximum(nxt, 0)])
                if targets is not None:
                    # position i predicts t_{i+2} = targets[i + 1]; the last has none
                    after = jnp.concatenate(
                        [targets[:, 1:], jnp.full((B, 1), -1, targets.dtype)], axis=1)
                    mtp_loss = token_loss_mean(
                        chunked_token_ce(h_mtp, w_head, after, chunk, vocab_first=False), after)
                    self.sow("objective", "mtp", cfg.mtp_loss_weight * mtp_loss)
                    self.sow("metrics", "mtp_loss", mtp_loss)

        with jax.named_scope("mla.head"):
            if targets is None:
                return constrain(jnp.dot(h, w_head), "batch", "seq", "vocab")
            losses = chunked_token_ce(h, w_head, targets, chunk, vocab_first=False)
            self.sow("metrics", "trunk_loss", token_loss_mean(losses, targets))
            return losses

    @staticmethod
    def book_step_counters(metrics: dict) -> dict:
        """What a caller that holds the model does with one step's returned
        ``metrics`` (``moe.book_step_counters``; the contract: ``models/build.py``)."""
        return book_step_counters(metrics)
