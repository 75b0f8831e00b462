"""Grouped-query attention under a mask by blocks, routed experts in every
layer: a decoder that *generates by diffusion over blocks* (the ``sdar_moe``
layer equations), served.

The config's keys are the published ones, by their published names, so a
model's public ``config.json`` reads straight into :class:`SdarMoeConfig`;
what it does not state (the decoding procedure's sizes) is beside them.
Width ``d``; no bias anywhere. Layer ``l``: ``h = x + Attn(RMSNorm(x))``;
``y = h + MoE(RMSNorm(h))``; one RMSNorm after the last layer, then the
head (untied). **The logits at position ``i`` score the token *at*
position ``i``**: no shift (the family's published generation code reads
them so).

- **``Attn``**: grouped-query attention; q and k are RMS-normed per head
  (each with a learned vector of the head's size) BEFORE RoPE (rotate-half
  pairing over the whole head); scores over ``sqrt(head size)``, softmax in
  float32; no window. **The mask**: with ``Bl = block_length`` and positions
  counted from the sequence's start, row ``i`` sees key ``j`` iff
  ``j // Bl <= i // Bl``: causal between blocks, both ways inside one.
- **``MoE``** (every layer: ``decoder_sparse_step`` 1, ``mlp_only_layers``
  empty; ``intermediate_size`` is read by no layer): ``p = softmax(z W_r)``
  in float32 over all experts, the top ``num_experts_per_tok``, gates ``p``
  of the chosen over their sum (``norm_topk_prob``); no shared expert, no
  selection bias. It is ``moe.MoeLayer`` given this model's sizes.

**Decoding** (``decode=True``, the contract ``generation.decode_apply``
spells) is by blocks, and the procedure is the holder's
(``serving.ContinuousBatchingEngine``, told by :meth:`SdarMoeLM.decode_blocks`):
a block of ``block_length`` positions starts undecided, every pass runs the
model over the whole block with ``mask_token_id`` where a position is
undecided and fixes some of them by confidence (``denoising_steps`` passes
at most), and only a block whose every position is decided leaves keys and
values that later blocks see. Two kinds of multi-token decode call reach the
model: **a prefill** (no ``cache_slots``: a prompt's whole blocks at the
shared write offset; it returns the last position's logits alone, which
nobody reads) and **a block pass** (``cache_slots [B]``: row ``b``'s block at
slots ``[s_b, s_b + Bl)``; it returns every position's logits). Both attend
under the same mask, ``layers._update_decode_cache(block_length=)``.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    BlockDecoding, RMSNorm, apply_rope, apply_rope_at, cached_decode_attention, constrain,
    dtypes_read_by_name, rope_tables, weight)
from .moe import MoeLayer, MoeSizes, decode_step_counters


@dataclass(frozen=True)
class SdarMoeConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144  # read by no layer: every layer is routed
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Any = None
    sliding_window: Any = None
    use_sliding_window: bool = False
    max_window_layers: int = 48  # read only under ``use_sliding_window``
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 32768  # RoPE keeps no table: unused
    model_type: str = "sdar_moe"
    # -- the decoding procedure's, which the config does not state ------------
    block_length: int = 4
    denoising_steps: int = 2  # passes that fix tokens, a block: the static low-confidence schedule
    mask_token_id: int = 151669
    # -- the init, which no config states. A layer is a small step of the stream
    # and the routed part is seen (``Qwen3NextConfig``'s draw, for its reasons),
    # with two departures that decoding by blocks forces: every undecided
    # position of a block enters as the SAME mask token, so what tells them
    # apart has to come through attention. With ``wo`` at the residual scaling
    # attention adds a hundredth of what the embedding holds, every undecided
    # position predicts the mask token's own argmax at one confidence, and no
    # comparison of outputs sees the mask or the cache (the benchmark
    # configuration's ``assumed.init`` has the readings on the chip). So ``wo``
    # is drawn like the matrices that read the stream (``init_std``), and the
    # embedding narrower, where the attention's part is of its size.
    init_std: float = 0.02
    embed_init_std: float = 0.3
    expert_init_std: float = 0.04
    residual_init_std: float = 0.02 / 96.0 ** 0.5  # every ``w_down``: 0.02 / sqrt(2 x 48 layers)
    # -- how it is computed -----------------------------------------------------
    max_seq_len: int = 2048  # the decode cache's length
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers))  # a list from JSON
        for name, want in (("attention_bias", False), ("rope_scaling", None), ("use_sliding_window", False),
                           ("decoder_sparse_step", 1), ("mlp_only_layers", ()), ("hidden_act", "silu"),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(f"only {name} = {want!r} is implemented, not {getattr(self, name)!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        if self.block_length < 1 or self.denoising_steps < 1:
            raise ValueError("block_length and denoising_steps are at least 1")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is not in the vocabulary")

    @property
    def rms_eps(self) -> float:  # the name ``layers.RMSNorm`` reads
        return self.rms_norm_eps

    @property
    def moe_sizes(self) -> MoeSizes:
        return MoeSizes(
            n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, norm_topk=self.norm_topk_prob,
            score_fn="softmax", bias_name="", init_std=self.init_std,
            expert_init_std=self.expert_init_std, down_init_std=self.residual_init_std,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    @staticmethod
    def tiny(**overrides) -> "SdarMoeConfig":
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=8, num_experts_per_tok=2, rope_theta=10000.0, mask_token_id=127,
            max_seq_len=64,
        )
        base.update(overrides)
        return SdarMoeConfig(**base)


def block_mask(T: int, block_length: int):
    """``[T, T]``: query ``i`` sees key ``j`` iff ``j``'s block is ``i``'s or an earlier one."""
    block_of = jnp.arange(T) // block_length
    return block_of[None, :] <= block_of[:, None]


class Attention(nn.Module):
    """Grouped-query attention with per-head q/k norms before RoPE, under
    the mask by blocks."""

    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        wq = weight("wq", cfg, (D, H, d), ("embed", "heads", "kv"))
        wk = weight("wk", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wv = weight("wv", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wo = weight("wo", cfg, (H, d, D), ("heads", "kv", "embed"))
        q = RMSNorm(cfg, name="q_norm")(jnp.einsum("btd,dhk->bthk", x, wq))
        k = RMSNorm(cfg, name="k_norm")(jnp.einsum("btd,dgk->btgk", x, wk))
        v = jnp.einsum("btd,dgk->btgk", x, wv)
        if decode:
            if positions is None:
                raise ValueError("decode=True needs absolute positions")
            # a multi-token call over the cache: a prompt's, or a block's
            with jax.named_scope("sdar.attend_prefill" if cache_slots is None else "sdar.attend_block"):
                cos_t, sin_t = rope_tables(cfg.max_seq_len, d, cfg.rope_theta)
                q = apply_rope_at(q, cos_t, sin_t, positions)
                k = apply_rope_at(k, cos_t, sin_t, positions)
                return cached_decode_attention(
                    self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots, wo, cfg,
                    block_length=cfg.block_length)
        with jax.named_scope("sdar.attend"):
            cos, sin = rope_tables(T, d, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            qg = q.reshape(B, T, G, H // G, d)
            scores = jnp.einsum("btgck,bsgk->bgcts", qg, k) / jnp.sqrt(d).astype(cfg.dtype)
            scores = jnp.where(block_mask(T, cfg.block_length)[None, None, None], scores, -1e9)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bgcts,bsgk->btgck", probs, v).reshape(B, T, H, d)
        return constrain(jnp.einsum("bqhk,hkd->bqd", out, wo), "batch", "seq", "embed")


class Block(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        with jax.named_scope("sdar.attn"):  # norm and projections, sdar.attend_* inside
            x = x + Attention(cfg, name="attn")(
                RMSNorm(cfg, name="input_norm")(x), decode=decode, positions=positions,
                kv_valid=kv_valid, cache_slots=cache_slots)
        y = MoeLayer(cfg.moe_sizes, name="moe")(RMSNorm(cfg, name="post_attention_norm")(x))
        return constrain(x + y, "batch", "seq", "embed")


# Every use of these is ``leaf.astype(cfg.dtype)``. The norms' scales (the
# per-head q/k norms' too) and the router are read in float32.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "lm_head", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


class SdarMoeLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]`` (float32), position
    ``i``'s scoring the token at ``i``; with ``decode=True`` through the
    ``"cache"`` collection (a prefill returns ``[B, 1, V]``, a block pass
    ``[B, T, V]``: the module's docstring). The methods beside it are the
    contract's (``models/build.py``)."""

    config: SdarMoeConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        return dtypes_read_by_name(params, _READ_IN_COMPUTE_DTYPE, self.config.dtype)

    @nn.nowrap
    def decode_step_counters(self, metrics):
        return decode_step_counters(metrics)

    @nn.nowrap
    def decode_blocks(self) -> BlockDecoding:
        cfg = self.config
        return BlockDecoding(cfg.block_length, cfg.denoising_steps, cfg.mask_token_id)

    @nn.compact
    def __call__(self, tokens, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"), cfg.embed_init_std)
        head = weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size), ("embed", "vocab"))
        with jax.named_scope("sdar.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        for i in range(cfg.num_hidden_layers):
            x = Block(cfg, name=f"block_{i}")(
                x, decode=decode, positions=positions, kv_valid=kv_valid, cache_slots=cache_slots)
        with jax.named_scope("sdar.head"):
            if decode and cache_slots is None:
                x = x[:, -1:]  # a prefill: no holder reads a prompt position's logits
            h = RMSNorm(cfg, name="final_norm")(x)
            logits = jnp.einsum("btd,dv->btv", h, head, preferred_element_type=jnp.float32)
            return constrain(logits, "batch", "seq", "vocab")
