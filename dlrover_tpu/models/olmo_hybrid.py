"""Gated delta-rule / full-attention hybrid decoder with a dense SwiGLU
(the ``olmo_hybrid`` layer equations), served.

The config's keys are the published ones by their published names
(``layer_types``, ``linear_num_value_heads``, ``linear_allow_neg_eigval``,
``rope_parameters`` ...), so a model's public ``config.json`` reads straight
into :class:`OlmoHybridConfig`. Width ``d``; no bias anywhere. Layer ``i``,
in the OLMo 2 / 3 order, **the norm on the sublayer's output**: ``h = x +
Norm(Mix_i(x))``; ``y = h + Norm(MLP(h))``; one norm after the last layer,
then an untied head. ``Norm`` is the plain RMS norm ``x / rms(x) * w`` in
float32 (``w`` starts at 1); ``MLP`` is ``layers.SwiGlu`` of
``intermediate_size``.

- **``Mix_i`` where ``layer_types[i] == "linear_attention"``**: the gated
  delta rule, ``layers.GatedDeltaMixer`` built from this config's sizes
  (``Hk = Hv`` heads, ``dk != dv``): ``[q ; k ; v ; z] = x W_qkvz``,
  ``[b ; a] = x W_ba``; ``[q ; k ; v] <- silu(conv([q ; k ; v]))``, a
  depthwise causal convolution of ``linear_conv_kernel_dim`` taps without
  bias, zeros before the first token; ``q`` and ``k`` L2-normalised over
  ``dk``, ``q`` times ``dk^-0.5``; ``beta = 2 sigmoid(b)``
  (``linear_allow_neg_eigval``: a write can reflect the state along ``k``,
  eigenvalue ``1 - beta`` in (-1, 1)), ``g = -exp(A_log) softplus(a +
  dt_bias)`` in float32; per head the recurrence of ``ops/gated_delta.py``
  on a float32 state ``[dk, dv]`` (chunks of 64 where ``T > 1``, one step
  where ``T = 1``); ``y = RMSNorm_dv(o) w silu(z)``; ``W_out``.
- **``Mix_i`` where ``layer_types[i] == "full_attention"``**: ``q = x W_q``,
  ``k = x W_k``, ``v = x W_v`` in ``num_attention_heads`` on
  ``num_key_value_heads`` heads of ``d / num_attention_heads``; an RMS norm
  with a weight over the **whole** width of ``q`` and of ``k`` before the
  heads are split; **no rotary embedding** (``rope_parameters.rope_theta``
  is null: order comes from the recurrent layers); causal softmax over
  ``sqrt(head size)``; ``W_o``.

**Decoding** (``decode=True``, the contract ``models/build.py`` spells). An
attention layer keeps keys and values through
``layers.cached_decode_attention`` (an ungrouped cache, folded; a prefill
too wide to hold its scores attends in tiles there). A delta layer keeps
``delta_state`` and ``conv_state``, *states with no position axis*; the
padding rule is the mixer's: **a padded token leaves both states alone and
is invisible to every real token after it** (which tokens of a call are
real: ``layers.token_valid_at``). Where a decode call has more than one
token (a prefill, a prefix's continuation) the head is computed for its last
position only, ``logits [B, 1, V]``: every holder reads ``logits[:, -1]``,
and at a width of 8,192 the rest would be 3.3 GB of float32 nobody reads.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    GatedDeltaMixer, RMSNorm, SwiGlu, cached_decode_attention, constrain, dtypes_read_by_name,
    state_leaves_by_name, token_valid_at, weight)

LAYER_TYPES = ("linear_attention", "full_attention")


@dataclass(frozen=True)
class OlmoHybridConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: Tuple[str, ...] = ("linear_attention",) * 3 + ("full_attention",)  # repeated to the depth
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Any = (("rope_theta", None),)  # the published ``{"rope_theta": null}``: no rotary embedding
    # -- what the config does not state -----------------------------------------
    init_std: float = 0.02
    # the embedding at torch ``nn.Embedding``'s default: every sublayer's output
    # leaves its norm at unit size, so an embedding at 0.02 would be a fiftieth
    # of the stream after the first layer and the tokens hardly tell in the logits
    embed_init_std: float = 1.0
    # ``w_out`` of a delta layer; its output is normalised, so the size is free
    residual_init_std: float = 0.02
    # the weights of the two norms on a block's sublayers' outputs: what a
    # sublayer adds to the stream has this size whatever its matrices hold. At
    # 1 (the family's own start) sixteen sublayers add to an embedding of size
    # 1 and the stream a mixer reads, un-normed, grows fourfold over eight
    # layers; a configuration that wants a layer to be a small step starts
    # them lower (``benchmark/configs/olmo-hybrid-7b-pp4-l8.json`` says why)
    sublayer_norm_init: float = 1.0
    # -- how it is computed -----------------------------------------------------
    max_seq_len: int = 2048  # the decode cache's length
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        pattern = tuple(self.layer_types)
        if len(pattern) < self.num_hidden_layers:  # a period, repeated
            pattern = (pattern * -(-self.num_hidden_layers // len(pattern)))
        object.__setattr__(self, "layer_types", pattern[:self.num_hidden_layers])
        rope = self.rope_parameters
        object.__setattr__(self, "rope_parameters", tuple(sorted(dict(rope or {}).items())))
        unimplemented = dict(attention_bias=False, tie_word_embeddings=False, hidden_act="silu",
                             rope_parameters=(("rope_theta", None),))
        for key, only in unimplemented.items():
            if getattr(self, key) != only:
                raise ValueError(f"only {key}={only!r} is implemented")
        if set(self.layer_types) - set(LAYER_TYPES):
            raise ValueError(f"layer_types holds {sorted(set(self.layer_types) - set(LAYER_TYPES))}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("the width is not a whole number of attention heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("the delta rule's value heads are not a multiple of its key heads")

    @property
    def head_dim(self) -> int:  # not published: the width over the heads
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_eps(self) -> float:  # ``layers.RMSNorm``'s name for it
        return self.rms_norm_eps

    def is_attention(self, layer_idx: int) -> bool:
        return self.layer_types[layer_idx] == "full_attention"

    @staticmethod
    def tiny(**overrides) -> "OlmoHybridConfig":
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=48, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=4, linear_value_head_dim=8,
            max_seq_len=64,
        )
        base.update(overrides)
        return OlmoHybridConfig(**base)


class FullAttention(nn.Module):
    """Ungrouped causal attention with no position embedding, ``q`` and ``k``
    normalised over their whole width before the heads are split."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        wq = weight("wq", cfg, (D, H, d), ("embed", "heads", "kv"))
        wk = weight("wk", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wv = weight("wv", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wo = weight("wo", cfg, (H, d, D), ("heads", "kv", "embed"), cfg.residual_init_std)
        # the family's QK-norm: over all the heads' channels at once, then split
        q = jnp.einsum("btd,dhk->bthk", x, wq).reshape(B, T, H * d)
        k = jnp.einsum("btd,dgk->btgk", x, wk).reshape(B, T, G * d)
        q = RMSNorm(cfg, name="q_norm")(q).reshape(B, T, H, d)
        k = RMSNorm(cfg, name="k_norm")(k).reshape(B, T, G, d)
        v = jnp.einsum("btd,dgk->btgk", x, wv)
        if decode:
            # the cache, its two products and the tiled prefill are ``layers``'
            with jax.named_scope("olmo.attend_decode" if T == 1 else "olmo.attend_prefill"):
                return cached_decode_attention(
                    self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots, wo, cfg)
        with jax.named_scope("olmo.attend"):
            k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
            scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(d).astype(cfg.dtype)
            scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], scores, -1e9)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            out = constrain(jnp.einsum("bhqs,bshk->bqhk", probs, v), "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bqhk,hkd->bqd", out, wo), "batch", "seq", "embed")


class Block(nn.Module):
    config: OlmoHybridConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, *, decode: bool = False, kv_valid=None, cache_slots=None,
                 token_valid=None):
        cfg = self.config
        if cfg.is_attention(self.layer_idx):
            with jax.named_scope("olmo.attn"):  # its projections, olmo.attend_* inside
                mix = FullAttention(cfg, name="attn")(
                    x, decode=decode, kv_valid=kv_valid, cache_slots=cache_slots)
        else:
            mix = GatedDeltaMixer(cfg, name="gdn")(x, decode=decode, token_valid=token_valid)
        h = x + RMSNorm(cfg, cfg.sublayer_norm_init, name="post_mixer_norm")(mix)
        with jax.named_scope("olmo.mlp"):
            y = SwiGlu(cfg, cfg.intermediate_size, name="mlp")(h)
        return constrain(h + RMSNorm(cfg, cfg.sublayer_norm_init, name="post_mlp_norm")(y), "batch", "seq", "embed")


# Every use of these is ``leaf.astype(cfg.dtype)``. The norms' weights, the
# convolution's taps, ``dt_bias`` and ``A_log`` are read in float32.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "lm_head", "w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "wo",
     "w_gate", "w_up", "w_down"})
_STATE_LEAVES = frozenset({"conv_state", "delta_state"})


class OlmoHybridLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]`` (float32); with
    ``decode=True`` through the ``"cache"`` collection, ``[B, 1, V]`` of the
    last position where ``T > 1``. The two optional methods are the
    contract's (``models/build.py``)."""

    config: OlmoHybridConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        return dtypes_read_by_name(params, _READ_IN_COMPUTE_DTYPE, self.config.dtype)

    @nn.nowrap
    def cache_state_leaves(self, cache):
        return state_leaves_by_name(cache, _STATE_LEAVES)

    @nn.compact
    def __call__(self, tokens, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        B, T = tokens.shape
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"), cfg.embed_init_std)
        w_head = weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size), ("embed", "vocab"))
        with jax.named_scope("olmo.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        token_valid = token_valid_at(self, B, T, kv_valid, cache_slots) if decode else None
        for i in range(cfg.num_hidden_layers):
            x = Block(cfg, layer_idx=i, name=f"block_{i}")(
                x, decode=decode, kv_valid=kv_valid, cache_slots=cache_slots,
                token_valid=token_valid)
        with jax.named_scope("olmo.head"):
            if decode and T > 1:
                x = x[:, -1:]
            h = RMSNorm(cfg, name="final_norm")(x)
            logits = jnp.dot(h, w_head, preferred_element_type=jnp.float32)
            return constrain(logits, "batch", "seq", "vocab")
