"""Mamba-2 / grouped-query-attention hybrid decoder (the
``granitemoehybrid`` layer equations, dense: no routed experts), trained
and served.

The config's keys are the published ones by their published names
(``layer_types``, ``mamba_n_heads``, ``mamba_d_state``,
``attention_multiplier`` ...), so a model's public ``config.json`` reads
straight into :class:`GraniteHybridConfig`. Width ``d``; RMSNorm in
float32. ``h_0 = embedding_multiplier * wte[tokens]``. Layer ``i``:
``h = h + residual_multiplier * Mix_i(RMSNorm(h))``;
``h = h + residual_multiplier * MLP(RMSNorm(h))``. One RMSNorm after the
last layer, then ``logits = (h wte^T) / logits_scaling`` (the head tied).

- **``Mix_i`` where ``layer_types[i] == "mamba"``** (Mamba-2; ``H`` heads
  of ``P`` channels, inner width ``H P``, state size ``N``, ``G`` groups,
  ``K`` taps): ``[z ; xBC ; dt] = u W_in`` (``d -> H P + (H P + 2 G N) +
  H``); ``xBC <- silu(conv(xBC) + b_c)``, a depthwise causal convolution
  of ``K`` taps with bias, zeros before the first token;
  ``[x ; B ; C] = xBC``; ``delta = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head, in float32,
  ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t`` (``ops/ssd_scan.py``: chunks of
  ``mamba_chunk_size`` where ``T > 1``, one step where ``T = 1``);
  ``y <- RMSNorm(y * silu(z)) w_g`` (the gate first, then the norm over
  all ``H P`` channels); ``W_out``.
- **``Mix_i`` where ``layer_types[i] == "attention"``**: grouped-query
  attention with **no position encoding** (``position_embedding_type:
  "nope"``), scores ``q k^T * attention_multiplier`` (a published float,
  not ``1 / sqrt(head size)``), causal softmax in float32.
- **``MLP``**: ``(silu(x W_gate) * x W_up) W_down`` of
  ``shared_intermediate_size`` (the family's shared MLP; with
  ``num_local_experts`` 0 there is nothing else).

**Decoding** (``decode=True``, the contract ``generation.decode_apply``
spells; ``positions`` is accepted and unused). An attention layer keeps
keys and values through ``layers.cached_decode_attention``. A Mamba layer
keeps two *states with no position axis*: ``ssm_state [B, H, P, N]``
(float32: the recurrence multiplies it by a decay near 1 at every token)
and ``conv_state [B, K - 1, H P + 2 G N]`` (the ``xBC`` of the request's
last ``K - 1`` real tokens). The rule model and engine keep together: **a
padded token leaves both states alone and is invisible to every real
token after it.** For the recurrence that is ``delta = 0`` at a padded
token, exactly (decay ``exp(0) = 1``, input 0), not softplus of
something; for the convolution it is ``layers.real_neighbours``, three
deep. Which tokens of a call are real is read from ``kv_valid`` at the
slots the call writes (``layers.token_valid_at``). A continuation of a
stored prefix starts the scan from the stored row's state.
"""

import math
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssd_scan import ssd_scan, ssd_step
from .layers import (
    CONV_INIT_STD, RMSNorm, SwiGlu, a_log_init, cached_decode_attention, chunked_token_ce,
    constrain, dt_bias_init, dtypes_read_by_name, param_with_axes, real_neighbours,
    state_leaves_by_name, token_valid_at, weight)

MAMBA, ATTENTION = "mamba", "attention"
# The init the config does not state (``dt_bias``, ``A_log``, the taps and
# their bias) is ``layers.dt_bias_init`` and its neighbours, which say why.


@dataclass(frozen=True)
class GraniteHybridConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # one entry a layer; () is the published pattern (attention at 5, 15, 25, 35)
    layer_types: Tuple[str, ...] = ()
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-5
    position_embedding_type: str = "nope"
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    # published and unused: the experts' width (there are none), the rotary
    # base and the longest context (no position is encoded, no table kept)
    intermediate_size: int = 8192
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    # -- what the config does not state ---------------------------------------
    head_dim: int = 0  # 0: hidden_size / num_attention_heads
    init_std: float = 0.02
    # -- how it is computed -----------------------------------------------------
    max_seq_len: int = 2048  # the decode cache's length
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_remat: bool = True
    ce_chunk: int = 0
    attention_impl: str = "flash"  # the non-decode pass: flash | dense

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            ATTENTION if i % 10 == 5 else MAMBA for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)  # a list from JSON: hashable now
        if len(types) < self.num_hidden_layers or set(types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {types} for {self.num_hidden_layers} layers")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("the Mamba heads are not a multiple of the groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        unimplemented = dict(
            attention_bias=False, mamba_proj_bias=False, mamba_conv_bias=True,
            position_embedding_type="nope", num_local_experts=0, tie_word_embeddings=True,
            hidden_act="silu", normalization_function="rmsnorm")
        for key, only in unimplemented.items():
            if getattr(self, key) != only:
                raise ValueError(f"only {key}={only!r} is implemented")

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def rms_eps(self) -> float:  # the name ``layers.RMSNorm`` reads
        return self.rms_norm_eps

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_width(self) -> int:  # the channels of ``xBC``
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**overrides) -> "GraniteHybridConfig":
        base = dict(
            vocab_size=128, hidden_size=32, shared_intermediate_size=64,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA), mamba_n_heads=8, mamba_d_head=8,
            mamba_d_state=16, mamba_chunk_size=8, attention_multiplier=0.25,
            max_seq_len=64, use_remat=False, attention_impl="dense",
        )
        base.update(overrides)
        return GraniteHybridConfig(**base)


class MambaMixer(nn.Module):
    """The Mamba-2 mixer. ``token_valid`` ``[B, T]`` (decode only) says
    which of this call's tokens are real."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, *, decode: bool = False, token_valid=None):
        cfg = self.config
        B, T, D = u.shape
        H, P, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
        inner, width, K = cfg.mamba_inner, cfg.mamba_conv_width, cfg.mamba_d_conv
        w_in = weight("w_in", cfg, (D, inner + width + H), ("embed", "mamba_proj"))
        w_out = weight("w_out", cfg, (inner, D), ("mamba_inner", "embed"))
        f32 = jnp.float32
        taps = param_with_axes("conv_kernel", nn.initializers.normal(CONV_INIT_STD),
                               (K, width), f32, axes=("conv_taps", "mamba_channels"))
        conv_bias = param_with_axes("conv_bias", nn.initializers.normal(CONV_INIT_STD),
                                    (width,), f32, axes=("mamba_channels",))
        dt_bias = param_with_axes("dt_bias", dt_bias_init, (H,), f32, axes=("mamba_heads",))
        a_log = param_with_axes("A_log", a_log_init, (H,), f32, axes=("mamba_heads",))
        skip = param_with_axes("D", nn.initializers.ones, (H,), f32, axes=("mamba_heads",))

        with jax.named_scope("mamba.in_proj"):
            zxd = jnp.dot(u, w_in, preferred_element_type=f32)  # the step sizes stay float32
            z, xbc = zxd[..., :inner].astype(cfg.dtype), zxd[..., inner:inner + width].astype(cfg.dtype)
            dt = zxd[..., inner + width:]
        with jax.named_scope("mamba.conv"):
            if not decode:
                padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
                earlier = [padded[:, k:k + T] for k in range(K - 1)]
            else:
                state = self.variable("cache", "conv_state", jnp.zeros, (B, K - 1, width), xbc.dtype)
                earlier, state.value = real_neighbours(state.value, xbc, token_valid)
            conv = conv_bias + taps[K - 1] * xbc.astype(f32)
            for k in range(K - 1):
                conv = conv + taps[k] * earlier[k].astype(f32)
            xbc = jax.nn.silu(conv).astype(cfg.dtype)
        x = xbc[..., :inner].reshape(B, T, H, P)
        b_in = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
        c_in = xbc[..., inner + G * N:].reshape(B, T, G, N)
        delta = jax.nn.softplus(dt + dt_bias)
        if decode:
            # the padding rule: a padded token's step size is 0, exactly (decay
            # exp(0) = 1, input 0), so it leaves the state alone
            delta = jnp.where(token_valid[:, :, None], delta, 0.0)
        a = -jnp.exp(a_log)
        ssm = self.variable("cache", "ssm_state", jnp.zeros, (B, H, P, N), f32) if decode else None
        if decode and T == 1:
            with jax.named_scope("mamba.step"):
                y, ssm.value = ssd_step(ssm.value, x[:, 0], delta[:, 0], a, b_in[:, 0], c_in[:, 0])
                y = y[:, None]
        else:  # from the row's state, or from zeros where nothing is cached
            with jax.named_scope("mamba.scan"):
                y, last = ssd_scan(x, delta, a, b_in, c_in, cfg.mamba_chunk_size, ssm.value if decode else None)
            if decode:
                ssm.value = last
        y = y + skip[:, None] * x.astype(f32)
        with jax.named_scope("mamba.gate_norm"):
            gated = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
            y = RMSNorm(cfg, name="gate_norm")(gated)
        with jax.named_scope("mamba.out_proj"):
            out = jnp.dot(y, w_out)
        return constrain(out, "batch", "seq", "embed")


class Attention(nn.Module):
    """Grouped-query attention with no position encoding and a published
    softmax scale."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, *, decode: bool = False, kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T, D = x.shape
        H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size
        wq = weight("wq", cfg, (D, H, d), ("embed", "heads", "kv"))
        wk = weight("wk", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wv = weight("wv", cfg, (D, G, d), ("embed", "kv_heads", "kv"))
        wo = weight("wo", cfg, (H, d, D), ("heads", "kv", "embed"))
        q = jnp.einsum("btd,dhk->bthk", x, wq)
        k = jnp.einsum("btd,dgk->btgk", x, wk)
        v = jnp.einsum("btd,dgk->btgk", x, wv)
        with jax.named_scope("granite.attend"):
            if decode:
                # ``cached_decode_attention`` divides by sqrt(head size) and takes
                # no scale: the published one goes onto q first
                q = (q.astype(jnp.float32) * (cfg.attention_multiplier * math.sqrt(d))).astype(q.dtype)
                return cached_decode_attention(
                    self, cfg.max_seq_len, q, k, v, kv_valid, cache_slots, wo, cfg)
            k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
            q = constrain(q, "batch", "seq", "heads", "kv")
            k = constrain(k, "batch", "seq", "heads", "kv")
            v = constrain(v, "batch", "seq", "heads", "kv")
            if cfg.attention_impl == "flash":
                from ..ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, True, cfg.attention_multiplier)
            elif cfg.attention_impl == "dense":
                scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * jnp.asarray(
                    cfg.attention_multiplier, cfg.dtype)
                mask = jnp.tril(jnp.ones((T, T), bool))
                scores = jnp.where(mask[None, None], scores, -1e9)
                probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
            else:
                raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")
        out = constrain(out, "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bqhk,hkd->bqd", out, wo), "batch", "seq", "embed")


class Block(nn.Module):
    config: GraniteHybridConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, *, decode: bool = False, kv_valid=None, cache_slots=None,
                 token_valid=None):
        cfg = self.config

        def scaled(branch):  # the multiplier in float32: 0.22 is no bf16 number
            return (branch.astype(jnp.float32) * cfg.residual_multiplier).astype(cfg.dtype)

        u = RMSNorm(cfg, name="input_norm")(x)
        if cfg.layer_types[self.layer_idx] == MAMBA:
            mix = MambaMixer(cfg, name="mamba")(u, decode=decode, token_valid=token_valid)
        else:
            with jax.named_scope("granite.attn"):  # its projections, and granite.attend inside
                mix = Attention(cfg, name="attn")(
                    u, decode=decode, kv_valid=kv_valid, cache_slots=cache_slots)
        x = x + scaled(mix)
        with jax.named_scope("granite.mlp"):
            y = SwiGlu(cfg, cfg.shared_intermediate_size, name="mlp")(RMSNorm(cfg, name="post_norm")(x))
        return constrain(x + scaled(y), "batch", "seq", "embed")


# Every use of these is ``leaf.astype(cfg.dtype)``. The norms' scales, the
# convolution's taps and bias, ``dt_bias``, ``A_log`` and ``D`` are read in
# float32.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})
_STATE_LEAVES = frozenset({"conv_state", "ssm_state"})


class GraniteHybridLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]`` (float32); with
    ``targets`` the per-token losses ``[B, T]``; with ``decode=True``
    through the ``"cache"`` collection. The two optional methods are the
    contract's (``models/build.py``)."""

    config: GraniteHybridConfig

    @nn.nowrap
    def consumed_param_dtypes(self, params):
        return dtypes_read_by_name(params, _READ_IN_COMPUTE_DTYPE, self.config.dtype)

    @nn.nowrap
    def cache_state_leaves(self, cache):
        return state_leaves_by_name(cache, _STATE_LEAVES)

    @nn.compact
    def __call__(self, tokens, *, targets=None, decode: bool = False, positions=None,
                 kv_valid=None, cache_slots=None):
        cfg = self.config
        B, T = tokens.shape
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"))
        with jax.named_scope("granite.embed"):
            x = (wte[tokens].astype(jnp.float32) * cfg.embedding_multiplier).astype(cfg.dtype)
            x = constrain(x, "batch", "seq", "embed")
        if decode:  # ``positions`` is the contract's; nothing here encodes a position
            token_valid = token_valid_at(self, B, T, kv_valid, cache_slots)
            for i in range(cfg.num_hidden_layers):
                x = Block(cfg, layer_idx=i, name=f"block_{i}")(
                    x, decode=True, kv_valid=kv_valid, cache_slots=cache_slots,
                    token_valid=token_valid)
        else:
            block = Block
            if cfg.use_remat:
                block = nn.remat(Block, prevent_cse=True,
                                 policy=jax.checkpoint_policies.nothing_saveable)
            for i in range(cfg.num_hidden_layers):
                x = block(cfg, layer_idx=i, name=f"block_{i}")(x)
        with jax.named_scope("granite.head"):
            h = RMSNorm(cfg, name="final_norm")(x)
            if targets is not None:
                # the fused loss knows no divisor: it goes onto the hidden state
                h = (h.astype(jnp.float32) / cfg.logits_scaling).astype(h.dtype)
                return chunked_token_ce(h, wte, targets, cfg.ce_chunk or T, vocab_first=True)
            logits = jnp.einsum("btd,vd->btv", h, wte, preferred_element_type=jnp.float32)
            return constrain(logits / cfg.logits_scaling, "batch", "seq", "vocab")
