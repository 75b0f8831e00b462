"""Gated short-convolution / grouped-query-attention decoder with routed
experts (the ``lfm2_moe`` layer equations), trained and served.

The config's keys are the published ones, by their published names
(``layer_types``, ``conv_L_cache``, ``num_dense_layers``, ``num_experts``,
``use_expert_bias`` ...), so a model's public ``config.json`` reads
straight into :class:`Lfm2MoeConfig`. Width ``d``; no bias anywhere.
Layer ``i``: ``h = h + Op_i(RMSNorm(h))``; ``h = h + FF_i(RMSNorm(h))``;
one RMSNorm after the last layer, then the head (tied to the embedding).

- **``Op_i`` where ``layer_types[i] == "conv"``** (gated short
  convolution): ``[B ; C ; x~] = u W_in`` (``d -> 3d``, in that order);
  ``z = B * x~``; ``c_t = sum_j k_j * z_{t-2+j}`` (a depthwise causal
  convolution of ``conv_L_cache`` = 3 taps, one filter a channel, ``z``
  zero before the first token); ``y = (C * c) W_out``. No non-linearity.
- **``Op_i`` where ``layer_types[i] == "full_attention"``**: grouped-query
  attention; q and k are RMS-normed per head (each with a learned vector
  of the head's size) BEFORE RoPE (rotate-half pairing); scores over
  ``sqrt(head size)``, causal softmax in float32.
- **``FF_i``**: a SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers; in the others ``num_experts`` routed
  experts, top ``num_experts_per_tok``, no shared one:
  ``s = sigmoid(x W_r)`` in float32, the top k of ``s + expert_bias``
  (selection only, no gradient), gates the unbiased ``s`` of the chosen
  over (their sum + ``gate_norm_eps``), times ``routed_scaling_factor``.
  It is ``moe.MoeLayer`` (the sorted row buffer and the grouped
  products of ``ops/grouped_matmul.py``), given this model's sizes.

**Decoding** (``decode=True``, the contract ``generation.decode_apply``
spells). An attention layer keeps keys and values through
``layers.cached_decode_attention``, as GPT and Llama do. A convolution layer
keeps a *state with no position axis*: ``conv_state [B, 2, d]``, the ``z``
of the request's last two real tokens. The rule model and engine keep
together: **the convolution at a real token reads the ``z`` of the two
real tokens before it in its own row, whatever padding lies between, and
zeros before the row's first token.** Which of a call's tokens are real is
read from ``kv_valid`` at the slots the call writes (the prompt's left
padding, and the holes between a registered prefix and its left-padded
suffix, are False there); a padded token computes something nobody reads
and leaves the state alone. ``cache_state_leaves`` tells a holder of the
cache which leaves are such states.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    RMSNorm, SwiGlu, apply_rope, apply_rope_at, cached_decode_attention, chunked_token_ce,
    constrain, dtypes_read_by_name, param_with_axes, real_neighbours, rope_tables,
    state_leaves_by_name, token_valid_at, weight)
from .moe import MoeLayer, MoeSizes, decode_step_counters

CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2MoeConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # one entry a layer, the first ``num_hidden_layers`` are used (a cut in
    # depth keeps the published list whole); () is the published pattern
    layer_types: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0  # ``rope_parameters.rope_theta``
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000  # RoPE keeps no table: unused
    # -- what the config does not state ---------------------------------------
    head_dim: int = 0  # 0: hidden_size / num_attention_heads
    tie_word_embeddings: bool = True
    gate_norm_eps: float = 1e-6  # in the gates' normalisation
    init_std: float = 0.02
    # the routed experts' matrices. At 0.02 the eight expert layers are 8% of
    # the residual stream's variance at the published widths (two dense
    # layers 59%, eight convolutions 33%) and a comparison of the output
    # hardly sees them; at 0.03 they are half of it
    expert_init_std: float = 0.03
    bias_init_std: float = 0.01  # so that the bias changes selections
    # a 3-tap filter drawn at 0.02 would leave the operator 1/15 of a
    # torch ``Conv1d``'s default (uniform in +-1/sqrt(3): std 1/3)
    conv_init_std: float = 1.0 / 3.0
    # -- how it is computed -----------------------------------------------------
    max_seq_len: int = 2048  # the decode cache's length
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_remat: bool = True
    ce_chunk: int = 0
    attention_impl: str = "flash"  # the non-decode pass: flash | dense

    # Leaves that take neither gradient nor weight decay.
    frozen_leaves: Tuple[str, ...] = ("expert_bias",)

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            ATTENTION if i % 4 == 2 else CONV for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)  # a list from JSON: hashable now
        if len(types) < self.num_hidden_layers or set(types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {types} for {self.num_hidden_layers} layers")
        if self.conv_L_cache != 3 or self.conv_bias:
            raise ValueError("only the 3-tap convolution without bias is implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        if not self.tie_word_embeddings:
            raise ValueError("only the tied head is implemented")

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def rms_eps(self) -> float:  # the name ``layers.RMSNorm`` reads
        return self.norm_eps

    @property
    def moe_sizes(self) -> MoeSizes:
        return MoeSizes(
            n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, norm_topk=self.norm_topk_prob,
            norm_eps=self.gate_norm_eps, scale=self.routed_scaling_factor,
            n_shared=0, bias_name="expert_bias" if self.use_expert_bias else "",
            init_std=self.init_std, expert_init_std=self.expert_init_std,
            bias_init_std=self.bias_init_std,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def is_expert_block(self, layer_idx: int) -> bool:
        return layer_idx >= self.num_dense_layers

    @staticmethod
    def tiny(**overrides) -> "Lfm2MoeConfig":
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
            num_experts=8, num_experts_per_tok=2, rope_theta=10000.0,
            max_seq_len=64, use_remat=False, attention_impl="dense",
        )
        base.update(overrides)
        return Lfm2MoeConfig(**base)


class ShortConv(nn.Module):
    """The gated short convolution. ``token_valid`` ``[B, T]`` (decode
    only) says which of this call's tokens are real."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u, *, decode: bool = False, token_valid=None):
        cfg = self.config
        B, T, D = u.shape
        w_in = weight("w_in", cfg, (D, 3, D), ("embed", None, "conv_channels"))
        w_out = weight("w_out", cfg, (D, D), ("conv_channels", "embed"))
        taps = param_with_axes(
            "conv_kernel", nn.initializers.normal(cfg.conv_init_std),
            (cfg.conv_L_cache, D), jnp.float32, axes=("conv_taps", "conv_channels"))
        with jax.named_scope("lfm2.conv"):
            bcx = jnp.einsum("btd,dgc->btgc", u, w_in)
            gate_b, gate_c, x = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
            z = gate_b * x  # [B, T, D], in the compute dtype: what the state keeps
            if not decode:
                padded = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))
                z2, z1 = padded[:, :T], padded[:, 1:T + 1]
            else:
                z2, z1 = self._from_state(z, token_valid)
            c = (taps[0] * z2.astype(jnp.float32) + taps[1] * z1.astype(jnp.float32)
                 + taps[2] * z.astype(jnp.float32))
            y = jnp.dot((gate_c.astype(jnp.float32) * c).astype(cfg.dtype), w_out)
        return constrain(y, "batch", "seq", "embed")

    def _from_state(self, z, token_valid):
        """(``z`` of the real token two before, of the one before) for each
        of this call's tokens, and the state moved on: the ``z`` of the
        row's last two real tokens, this call's included."""
        B, T, D = z.shape
        state = self.variable("cache", "conv_state", jnp.zeros, (B, 2, D), z.dtype)
        if token_valid is None:
            token_valid = jnp.ones((B, T), bool)
        (z2, z1), state.value = real_neighbours(state.value, z, token_valid)
        return z2, z1


class Attention(nn.Module):
    """Grouped-query attention with per-head q/k norms before RoPE."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size
        wq = weight("wq", cfg, (D, H, d), ("embed", "heads", "kv"))
        wk = weight("wk", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wv = weight("wv", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wo = weight("wo", cfg, (H, d, D), ("heads", "kv", "embed"))
        q = RMSNorm(cfg, name="q_norm")(jnp.einsum("btd,dhk->bthk", x, wq))
        k = RMSNorm(cfg, name="k_norm")(jnp.einsum("btd,dgk->btgk", x, wk))
        v = jnp.einsum("btd,dgk->btgk", x, wv)
        with jax.named_scope("lfm2.attend"):
            if decode:
                if positions is None:
                    raise ValueError("decode=True needs absolute positions")
                cos_t, sin_t = rope_tables(cfg.max_seq_len, d, cfg.rope_theta)
                q = apply_rope_at(q, cos_t, sin_t, positions)
                k = apply_rope_at(k, cos_t, sin_t, positions)
                # the narrow cache and the grouped contraction are ``layers``'
                return cached_decode_attention(
                    self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots, wo, cfg)
            cos, sin = rope_tables(T, d, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
            q = constrain(q, "batch", "seq", "heads", "kv")
            k = constrain(k, "batch", "seq", "heads", "kv")
            v = constrain(v, "batch", "seq", "heads", "kv")
            if cfg.attention_impl == "flash":
                from ..ops.flash_attention import flash_attention_sharded
                from ..parallel.mesh import get_current_mesh

                out = flash_attention_sharded(q, k, v, get_current_mesh(), causal=True)
            elif cfg.attention_impl == "dense":
                scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(d).astype(cfg.dtype)
                mask = jnp.tril(jnp.ones((T, T), bool))
                scores = jnp.where(mask[None, None], scores, -1e9)
                probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
            else:
                raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")
        out = constrain(out, "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bqhk,hkd->bqd", out, wo), "batch", "seq", "embed")


class Block(nn.Module):
    config: Lfm2MoeConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None, token_valid=None):
        cfg = self.config
        u = RMSNorm(cfg, name="operator_norm")(x)
        if cfg.layer_types[self.layer_idx] == CONV:
            with jax.named_scope("lfm2.conv_mixer"):  # its projections, and lfm2.conv inside
                x = x + ShortConv(cfg, name="conv")(u, decode=decode, token_valid=token_valid)
        else:
            with jax.named_scope("lfm2.attn"):  # its projections, and lfm2.attend inside
                x = x + Attention(cfg, name="attn")(
                    u, decode=decode, positions=positions, kv_valid=kv_valid,
                    cache_slots=cache_slots)
        h = RMSNorm(cfg, name="ffn_norm")(x)
        if cfg.is_expert_block(self.layer_idx):
            y = MoeLayer(cfg.moe_sizes, name="moe")(h)
        else:
            with jax.named_scope("lfm2.mlp"):
                y = SwiGlu(cfg, cfg.intermediate_size, name="mlp")(h)
        return constrain(x + y, "batch", "seq", "embed")


# Every use of these is ``leaf.astype(cfg.dtype)``. The norms' scales, the
# router, its selection bias and the convolution's taps are read in float32.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})
_STATE_LEAVES = frozenset({"conv_state"})


class Lfm2MoeLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]`` (float32); with
    ``targets`` the per-token losses ``[B, T]``; with ``decode=True``
    through the ``"cache"`` collection. The three optional methods are
    the contract's (``models/build.py``)."""

    config: Lfm2MoeConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        return dtypes_read_by_name(params, _READ_IN_COMPUTE_DTYPE, self.config.dtype)

    @nn.nowrap
    def cache_state_leaves(self, cache):
        return state_leaves_by_name(cache, _STATE_LEAVES)

    @nn.nowrap
    def decode_step_counters(self, metrics):
        return decode_step_counters(metrics)

    @nn.compact
    def __call__(self, tokens, *, targets=None, decode: bool = False, positions=None,
                 kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T = tokens.shape
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"))
        with jax.named_scope("lfm2.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        if decode:
            token_valid = token_valid_at(self, B, T, kv_valid, cache_slots)
            for i in range(cfg.num_hidden_layers):
                x = Block(cfg, layer_idx=i, name=f"block_{i}")(
                    x, decode=True, positions=positions, kv_valid=kv_valid,
                    cache_slots=cache_slots, token_valid=token_valid)
        else:
            block = Block
            if cfg.use_remat:
                block = nn.remat(Block, prevent_cse=True,
                                 policy=jax.checkpoint_policies.nothing_saveable)
            for i in range(cfg.num_hidden_layers):
                x = block(cfg, layer_idx=i, name=f"block_{i}")(x)
        with jax.named_scope("lfm2.head"):
            h = RMSNorm(cfg, name="embedding_norm")(x)  # the family's name; applied at the END
            if targets is not None:
                return chunked_token_ce(h, wte, targets, cfg.ce_chunk or T, vocab_first=True)
            logits = jnp.einsum("btd,vd->btv", h, wte, preferred_element_type=jnp.float32)
            return constrain(logits, "batch", "seq", "vocab")
