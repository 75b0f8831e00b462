"""Gated delta-rule / gated-attention hybrid decoder with routed experts
(the ``qwen3_next`` layer equations), served, as one chip of an
expert-parallel group holds it.

The config's keys are the published ones by their published names
(``full_attention_interval``, ``linear_num_value_heads``,
``partial_rotary_factor``, ``shared_expert_intermediate_size`` ...), so a
model's public ``config.json`` reads straight into
:class:`Qwen3NextConfig`. Width ``d``; no bias anywhere. Layer ``i``:
``h = h + Mix_i(Norm(h))``; ``h = h + MoE(Norm(h))``; one norm after the
last layer, then an untied head. ``Norm`` is the **zero-centred** RMS norm
``x / rms(x) * (1 + w)`` in float32 (``w`` starts at 0); only the delta
mixer's gate norm has a plain weight.

- **``Mix_i`` where ``(i + 1) % full_attention_interval != 0``** (gated
  delta rule; ``Hk`` key heads and ``Hv`` value heads of ``dk`` / ``dv``
  channels, value head ``j`` reads key head ``j // (Hv / Hk)``):
  ``[q ; k ; v ; z] = u W_qkvz``, ``[b ; a] = u W_ba``; ``[q ; k ; v] <-
  silu(conv([q ; k ; v]))``, a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps without bias, zeros before the first
  token; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``
  in float32; ``q`` and ``k`` L2-normalised over ``dk``, ``q`` times
  ``dk^-0.5``; per value head, in float32, the recurrence of
  ``ops/gated_delta.py`` (chunks of 64 where ``T > 1``, one step where
  ``T = 1``); ``y = RMSNorm_dv(o) w silu(z)`` (the norm first, then the
  gate, a head at a time); ``W_out``.
- **``Mix_i`` elsewhere** (gated attention): ``[q_h ; gate_h] = u W_q`` a
  head, ``k`` and ``v`` in ``num_key_value_heads``; zero-centred RMS norm
  of ``q_h`` and ``k_h`` over the head; rotate-half RoPE on the first
  ``partial_rotary_factor`` of each head; causal softmax over
  ``sqrt(head size)``; ``(attention * sigmoid(gate)) W_o``.
- **``MoE``**: ``p = softmax(x W_r)`` in float32 over all ``num_experts``;
  the top ``num_experts_per_tok``; gates ``p`` of the chosen over their sum;
  plus ``sigmoid(x w_s)`` times the shared expert. It is
  ``moe.MoeLayer`` given this model's sizes; ``experts_held`` /
  ``expert_offset`` say which experts live here (the chip's share: what
  the absent ones would add is another chip's, counted and left out).

The published model's multi-token-prediction module is not here: nothing
in the server drafts.

**Decoding** (``decode=True``, the contract ``generation.decode_apply``
spells). An attention layer keeps keys and values through
``layers.cached_decode_attention``. A delta layer keeps two *states with no
position axis*: ``delta_state [B, Hv, dk, dv]`` (float32) and
``conv_state [B, K - 1, 2 Hk dk + Hv dv]`` (the ``[q ; k ; v]`` of the
request's last ``K - 1`` real tokens). The rule model and engine keep
together: **a padded token leaves both states alone and is invisible to
every real token after it.** For the recurrence that is ``g = 0`` and
``beta = 0`` at a padded token, exactly (decay ``exp(0) = 1``, nothing
written); for the convolution it is ``layers.real_neighbours``, three
deep. Which tokens of a call are real is read from ``kv_valid`` at the
slots the call writes (``layers.token_valid_at``).
"""

import math
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    GatedDeltaMixer, apply_rope, apply_rope_at, cached_decode_attention, chunked_token_ce,
    constrain, dtypes_read_by_name, param_with_axes, rope_tables, state_leaves_by_name,
    token_valid_at, weight)
from .moe import MoeLayer, MoeSizes, decode_step_counters

# The delta mixer is ``layers.GatedDeltaMixer``, built from this config's
# sizes (``olmo_hybrid.py`` builds it from its own). The init the config does
# not state (``dt_bias``, ``A_log``, the taps) is ``layers.dt_bias_init`` and
# its neighbours, which say why: the reference model's own draw forgets
# within a token or two. This family names the legacy ``inverse="squaring"``
# (its ``beta`` stays under 1, and its served programs are pinned to that
# text: ``tests/test_tpu_compile.py: PARENT_FAMILY_PROGRAMS``); the PR that
# may next move ``qwen3next-serve-rag-16`` re-bases the pins, drops the
# argument here and deletes the squaring from ``ops/gated_delta.py``.


@dataclass(frozen=True)
class Qwen3NextConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    hidden_act: str = "silu"
    # published and unused: the dense SwiGLU's width (every layer is sparse)
    # and the longest context (RoPE keeps no table)
    intermediate_size: int = 5120
    max_position_embeddings: int = 262144
    # -- the chip's share of an expert-parallel group -----------------------
    experts_held: int = 0  # 0: all of them
    expert_offset: int = 0  # the first expert held
    # -- what the config does not state ---------------------------------------
    init_std: float = 0.02
    # the routed experts' matrices. A token's ten gates sum to 1 and a
    # quarter of its experts are held, so at 0.02 the routed part of a layer
    # would be a twentieth of its mixer's output and a comparison of outputs
    # would hardly see it (``Lfm2MoeConfig.expert_init_std``)
    expert_init_std: float = 0.04
    # With every matrix at 0.02 a layer adds as much as the stream holds, and
    # what one layer rounds the next two double: bf16 and float32 stand 7% apart
    # after 12 layers with the routed experts off and 20-36% with them on (a
    # router's tenth choice that falls the other way moves a token by a
    # fifth of the layer's output), so no comparison of outputs is possible
    # (PERF.md, PR 42). Hence the two conventions under which a layer is a
    # small step: the embedding at torch ``nn.Embedding``'s default, and the
    # matrices that write to the residual stream (``w_out``, ``wo``, every
    # ``w_down``) at the GPT-2 / Megatron ``init_std / sqrt(2 x layers)`` of the
    # published 48 layers.
    embed_init_std: float = 1.0
    residual_init_std: float = 0.02 / math.sqrt(96.0)
    # -- how it is computed -----------------------------------------------------
    max_seq_len: int = 2048  # the decode cache's length
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    ce_chunk: int = 0

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers))
        unimplemented = dict(
            decoder_sparse_step=1, mlp_only_layers=(), attention_bias=False,
            tie_word_embeddings=False, use_sliding_window=False, hidden_act="silu")
        for key, only in unimplemented.items():
            if getattr(self, key) != only:
                raise ValueError(f"only {key}={only!r} is implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("the delta rule's value heads are not a multiple of its key heads")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is not a whole number of expert widths wide")
        if self.rotary_dim % 2:
            raise ValueError("the rotated part of a head is not whole pairs")
        held = self.experts_held or self.num_experts
        if not 0 <= self.expert_offset <= self.num_experts - held:
            raise ValueError("the experts held do not lie inside the routed ones")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def delta_key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def delta_value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def delta_conv_width(self) -> int:  # the channels of ``[q ; k ; v]``
        return 2 * self.delta_key_width + self.delta_value_width

    @property
    def moe_sizes(self) -> MoeSizes:
        return MoeSizes(
            n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, experts_held=self.experts_held,
            expert_offset=self.expert_offset, norm_topk=self.norm_topk_prob,
            n_shared=self.shared_expert_intermediate_size // self.moe_intermediate_size,
            shared_gate=True, score_fn="softmax", bias_name="",
            init_std=self.init_std, expert_init_std=self.expert_init_std,
            down_init_std=self.residual_init_std,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def is_attention(self, layer_idx: int) -> bool:
        return (layer_idx + 1) % self.full_attention_interval == 0

    @staticmethod
    def tiny(**overrides) -> "Qwen3NextConfig":
        base = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_key_head_dim=8, linear_value_head_dim=8, linear_num_key_heads=2,
            linear_num_value_heads=4, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            rope_theta=10000.0, max_seq_len=64,
        )
        base.update(overrides)
        return Qwen3NextConfig(**base)


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)`` over the last axis, in float32."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = param_with_axes("scale", nn.initializers.zeros, (x.shape[-1],),
                            cfg.param_dtype, axes=("norm",))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + cfg.rms_norm_eps) * (1.0 + w)).astype(cfg.dtype)


class GatedAttention(nn.Module):
    """Grouped-query attention with per-head q/k norms, RoPE on part of a
    head and a per-head sigmoid gate on the output."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, G, d, rot = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rotary_dim
        wq = weight("wq", cfg, (D, H, 2 * d), ("embed", "heads", "kv"))  # [q_h ; gate_h] a head
        wk = weight("wk", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wv = weight("wv", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wo = weight("wo", cfg, (H, d, D), ("heads", "kv", "embed"), cfg.residual_init_std)
        q_gate = jnp.einsum("btd,dhk->bthk", x, wq)
        q, gate = q_gate[..., :d], q_gate[..., d:]
        q = ZeroCentredRMSNorm(cfg, name="q_norm")(q)
        k = ZeroCentredRMSNorm(cfg, name="k_norm")(jnp.einsum("btd,dgk->btgk", x, wk))
        v = jnp.einsum("btd,dgk->btgk", x, wv)

        def rotated(a, turn):  # the first ``rot`` channels of each head turn, the rest pass
            return jnp.concatenate([turn(a[..., :rot]), a[..., rot:]], axis=-1)

        with jax.named_scope("qwen3next.attend"):
            if decode:
                if positions is None:
                    raise ValueError("decode=True needs absolute positions")
                cos_t, sin_t = rope_tables(cfg.max_seq_len, rot, cfg.rope_theta)
                q = rotated(q, lambda a: apply_rope_at(a, cos_t, sin_t, positions))
                k = rotated(k, lambda a: apply_rope_at(a, cos_t, sin_t, positions))
                # the narrow cache and the grouped contraction are ``layers``';
                # without ``wo`` it returns the heads, for the gate
                out = cached_decode_attention(
                    self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots, None, cfg)
            else:
                cos, sin = rope_tables(T, rot, cfg.rope_theta)
                q = rotated(q, lambda a: apply_rope(a, cos, sin))
                k = rotated(k, lambda a: apply_rope(a, cos, sin))
                k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
                scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(d).astype(cfg.dtype)
                scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], scores, -1e9)
                probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
        out = constrain(out, "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bqhk,hkd->bqd", out, wo), "batch", "seq", "embed")


class Block(nn.Module):
    config: Qwen3NextConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, *, decode: bool = False, positions=None, kv_valid=None,
                 cache_slots=None, token_valid=None):
        cfg = self.config
        u = ZeroCentredRMSNorm(cfg, name="input_norm")(x)
        if cfg.is_attention(self.layer_idx):
            with jax.named_scope("qwen3next.attn"):  # its projections and gate, qwen3next.attend inside
                x = x + GatedAttention(cfg, name="attn")(
                    u, decode=decode, positions=positions, kv_valid=kv_valid,
                    cache_slots=cache_slots)
        else:
            x = x + GatedDeltaMixer(cfg, inverse="squaring", name="gdn")(
                u, decode=decode, token_valid=token_valid)
        y = MoeLayer(cfg.moe_sizes, name="moe")(ZeroCentredRMSNorm(cfg, name="post_norm")(x))
        return constrain(x + y, "batch", "seq", "embed")


# Every use of these is ``leaf.astype(cfg.dtype)``. The norms' weights, the
# router, the shared expert's gate, the convolution's taps, ``dt_bias`` and
# ``A_log`` are read in float32.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "lm_head", "w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "wo",
     "w_gate", "w_up", "w_down"})
_STATE_LEAVES = frozenset({"conv_state", "delta_state"})


class Qwen3NextLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]`` (float32); with
    ``targets`` the per-token losses ``[B, T]``; with ``decode=True``
    through the ``"cache"`` collection. The three optional methods are
    the contract's (``models/build.py``)."""

    config: Qwen3NextConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        return dtypes_read_by_name(params, _READ_IN_COMPUTE_DTYPE, self.config.dtype)

    @nn.nowrap
    def cache_state_leaves(self, cache):
        return state_leaves_by_name(cache, _STATE_LEAVES)

    @nn.nowrap
    def decode_step_counters(self, metrics):  # a share: also what landed here and elsewhere
        return decode_step_counters(metrics, share=True)

    @nn.compact
    def __call__(self, tokens, *, targets=None, decode: bool = False, positions=None,
                 kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T = tokens.shape
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"), cfg.embed_init_std)
        w_head = weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size), ("embed", "vocab"))
        with jax.named_scope("qwen3next.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        token_valid = token_valid_at(self, B, T, kv_valid, cache_slots) if decode else None
        for i in range(cfg.num_hidden_layers):
            x = Block(cfg, layer_idx=i, name=f"block_{i}")(
                x, decode=decode, positions=positions, kv_valid=kv_valid,
                cache_slots=cache_slots, token_valid=token_valid)
        with jax.named_scope("qwen3next.head"):
            h = ZeroCentredRMSNorm(cfg, name="final_norm")(x)
            if targets is not None:
                return chunked_token_ce(h, w_head, targets, cfg.ce_chunk or T, vocab_first=False)
            logits = jnp.dot(h, w_head, preferred_element_type=jnp.float32)
            return constrain(logits, "batch", "seq", "vocab")
