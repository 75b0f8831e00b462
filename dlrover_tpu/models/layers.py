"""The parts every model family is made of: the norm, the SwiGLU, the
rotate-half RoPE, the decode cache and the attention over it, the padding
rule of a state with no position axis, the losses, and the two helpers a
model's side of the contract is written with (``models/build.py`` states
the contract). A family's file (``gpt.py`` ... ``mellum.py``) imports this
module, ``moe.py``, ``ops/`` and ``parallel/``, and never another family;
this module imports no ``models`` module
(``tests/test_models_layering.py``). A part moves here when a second family
needs it: ``mla_moe.rope_interleaved`` and ``mellum.rope_table`` (YaRN) have
one user each and stay with it; the gated delta-rule mixer
(:class:`GatedDeltaMixer`) came when ``olmo_hybrid.py`` became the second
family to build it.
"""

import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from ..ops.gated_delta import gated_delta_chunked, gated_delta_step
from ..parallel.sharding import with_logical_constraint as constrain

param_with_axes = nn_partitioning.param_with_axes


# -- weights, norm, SwiGLU ----------------------------------------------------

def weight(name, cfg, shape, axes, std: float = 0.0):
    return param_with_axes(
        name, nn.initializers.normal(std or cfg.init_std), shape,
        cfg.param_dtype, axes=axes,
    ).astype(cfg.dtype)


class RMSNorm(nn.Module):
    config: Any  # reads ``rms_eps``, ``param_dtype``, ``dtype``
    init: float = 1.0  # the weight's start: a norm on a sublayer's *output* sets the layer's step by it

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = param_with_axes(
            "scale",
            nn.initializers.ones if self.init == 1.0 else nn.initializers.constant(self.init),
            (x.shape[-1],),
            cfg.param_dtype,
            axes=("norm",),
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.rms_eps)
        return (y * scale).astype(cfg.dtype)


class SwiGlu(nn.Module):
    config: Any  # reads ``init_std``, ``param_dtype``, ``dtype``
    width: int
    down_init_std: float = 0.0  # ``w_down``, which writes to the residual stream; 0: ``init_std``

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        D, F = x.shape[-1], self.width
        w_gate = weight("w_gate", cfg, (D, F), ("embed", "mlp"))
        w_up = weight("w_up", cfg, (D, F), ("embed", "mlp"))
        w_down = weight("w_down", cfg, (F, D), ("mlp", "embed"), self.down_init_std)
        h = jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up)
        return jnp.dot(h, w_down)


# -- rotate-half RoPE --------------------------------------------------------

def rope_tables(seq_len: int, head_dim: int, theta: float):
    """(cos, sin) [T, head_dim//2] in fp32 — computed once per trace."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), freqs)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """Rotate pairs of channels; x is [B, T, H, Hd]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1
    ).astype(x.dtype)


def apply_rope_at(x, cos_table, sin_table, positions):
    """RoPE at per-row absolute positions; x [B,T,H,Hd], positions [B,T].

    The decode path's variant of :func:`apply_rope`: left-padded rows
    sit at different absolute token positions for the same cache slot,
    so the angle tables are gathered per (row, slot) instead of shared
    across the batch.
    """
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos_table[positions][:, :, None, :]  # [B, T, 1, Hd//2]
    sin = sin_table[positions][:, :, None, :]
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1
    ).astype(x.dtype)


# -- the init of a recurrence's step sizes and decays -------------------------
#
# What no config states, for a Mamba-2 mixer (``granite_hybrid.py``) and a
# gated delta rule (``qwen3_next.py``) alike. The step sizes are drawn
# log-uniform in DT_RANGE through ``dt_bias`` (Mamba-2's own rule) and ``A``
# log-spaced over the heads in A_RANGE. Not ``A`` = 1 .. H, nor the delta
# rule's reference draw (``A ~ U(0, 16)``, ``dt_bias = 1``): under either the
# state forgets within tokens and no comparison of outputs sees it; in
# [1/16, 1] the slowest heads remember over a thousand tokens
# (``benchmark/reference/{granite_hybrid,qwen3_next}.py: old_state_share``;
# the configurations' files have the numbers). The 4-tap filters (and their
# bias) at torch ``Conv1d``'s default spread (uniform in +-1/sqrt(4): std
# 0.2887).
DT_RANGE = (0.001, 0.1)
A_RANGE = (0.0625, 1.0)
CONV_INIT_STD = 0.5 / math.sqrt(3.0)


def dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in ``DT_RANGE``."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, *(math.log(v) for v in DT_RANGE)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus's inverse


def a_log_init(key, shape, dtype=jnp.float32):
    return jnp.linspace(*(math.log(v) for v in A_RANGE), shape[0]).astype(dtype)


# What a rematerialised block keeps (``mla_moe._block``, ``mellum._block``):
# its flash kernel's two results, by the names ``_fa_fwd`` gives them. One
# object for every block of every family: JAX caches a policy's partial
# evaluations by its identity, and a policy made anew for each block lowers
# every block's callees again.
KEEP_FLASH_RESULTS = jax.checkpoint_policies.save_only_these_names(
    "flash.out", "flash.lse")


# -- the decode cache and the attention over it -------------------------------

def _quant_kv(x):
    """Per-token per-kv-head symmetric int8: [B, T, KVH, Hd] →
    (int8 values, f32 scales [B, T, KVH])."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant_kv(q, scale, dtype):
    """Inverse of :func:`_quant_kv` — round-trip/debug helper only.

    NOT used by the attention path: dequantizing the cache before the
    einsums materializes the wide bf16 tensor to HBM (XLA does not
    fuse converts into dot operands); what that costs beside the bf16
    cache was not measured on the chip in this round. The production
    path keeps operands int8 end to end — see
    :func:`_masked_attention_int8`."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _fold_heads(x):
    """``[B, T, H, Hd]`` -> ``[B, T, lanes]``: a token's heads side by
    side in the minor dimension, then zeros up to a multiple of 128.

    The TPU tiles an array's two minor dimensions, (16, 128) for bf16.
    A ``[H, Hd]`` pair pads head by head: [25, 64] takes 2.56x its size
    in HBM, and every decode step reads all of it. ``H*Hd`` lanes pad at
    most to the next 128 ([1600] -> [1664], 1.04x). The zeros make that
    padding the array's own, so that no program is handed a
    ``[.., L, 1600]`` array laid out with the positions minor."""
    B, T, H, Hd = x.shape
    return jnp.pad(
        x.reshape(B, T, H * Hd), ((0, 0), (0, 0), (0, -(H * Hd) % 128))
    )


def _update_decode_cache(
    module, max_len, k, v, kv_valid, cache_slots=None, *, fold=False,
    block_length=0,
):
    """Write this call's K/V into the module's decode cache; return the
    full cache plus the attention mask for the queries of this call.

    The positional leaves ``k`` and ``v`` are ``[B, max_len, KVH, Hd]``,
    or with ``fold`` ``[B, max_len, lanes]`` (:func:`_fold_heads`; the
    caller asks for it where queries and keys have the same heads, see
    :func:`cached_decode_attention`; the int8 cache never folds). Axis
    1 is the position either way and both write rules below are the
    same for both.

    Incremental decoding the flax way (``"cache"`` variable collection),
    shared by every family's attention. The DEFAULT path follows the
    engine convention (:mod:`dlrover_tpu.models.generation`): LEFT-
    padded prompts, so every batch row shares one static write offset
    and the cache update is a single ``dynamic_update_slice`` — the
    shape XLA tiles well for multi-token prefill writes. ``kv_valid``
    [B, max_len] marks which cache slots hold real tokens (False =
    left-pad); queries at local position i attend valid slots s with
    s <= offset + i.

    ``cache_slots`` int32 ``[B]`` switches to PER-ROW write slots (the
    continuous-batching engine's per-row cache layout: every request
    advances its own write position, so admissions leave no holes past a
    prompt's bucket): row ``b``'s ``T`` tokens go to slots ``[s_b, s_b +
    T)``, one token a step for an autoregressive model, a block a pass
    for one decoded by blocks. The write is a B-row scatter — tiny next
    to the attention pass that reads the whole cache anyway — and the
    causal mask keys on each query's own slot (returned mask is
    [B, T, max_len]). Requires an explicit ``kv_valid``.

    ``block_length`` > 0 (a model decoded a block at a time:
    ``models/sdar_moe.py``) replaces the causal rule by **blocks of
    positions**, in both write modes: a slot's position is its rank among
    the row's valid slots (prompts are left-padded, so a slot says nothing
    of a position), and the query at position ``i`` sees the valid key at
    position ``j`` iff ``j // block_length <= i // block_length``: causal
    between blocks, both ways inside one. The query's own slot has to be
    valid. It needs an explicit ``kv_valid`` too.

    Reference RL rollouts lean on vLLM for this
    (examples/unified/rl/openrlhf/ppo/main.py:26-60); here generation is
    a first-class jit-compiled path over the training parameters.
    """
    B, T = k.shape[0], k.shape[1]
    int8_cache = bool(getattr(module.config, "kv_cache_int8", False))
    if int8_cache:
        k_store, k_scale = _quant_kv(k)
        v_store, v_scale = _quant_kv(v)
        store_dtype = jnp.int8
    else:
        k_store, v_store = k, v
        k_scale = v_scale = None
        store_dtype = k.dtype
    if fold and not int8_cache:
        k_store, v_store = _fold_heads(k_store), _fold_heads(v_store)
    ck = module.variable(
        "cache", "k", jnp.zeros, (B, max_len) + k_store.shape[2:],
        store_dtype,
    )
    cv = module.variable(
        "cache", "v", jnp.zeros, (B, max_len) + v_store.shape[2:],
        store_dtype,
    )
    if int8_cache:
        csk = module.variable(
            "cache", "k_scale", jnp.zeros, (B, max_len) + k.shape[2:3],
            jnp.float32,
        )
        csv = module.variable(
            "cache", "v_scale", jnp.zeros, (B, max_len) + v.shape[2:3],
            jnp.float32,
        )
    cidx = module.variable(
        "cache", "index", lambda: jnp.zeros((), jnp.int32)
    )

    def _read(mask):
        """bf16 cache → (k, v, mask); int8 cache → the RAW int8
        tensors + scales (k8, ks, v8, vs, mask). Never dequantize here:
        a materialized [B, max_len, KVH, Hd] bf16 tensor costs more
        HBM traffic than the narrow cache saves (by its bytes; not
        measured on the chip in this round) — the int8 attention path
        consumes the int8 operands directly (see
        _masked_attention_int8)."""
        if not int8_cache:
            return ck.value, cv.value, mask
        return ck.value, csk.value, cv.value, csv.value, mask

    if cache_slots is not None:
        if kv_valid is None:
            raise ValueError("cache_slots mode needs explicit kv_valid")
        if cache_slots.shape != (B,):
            raise ValueError(
                f"cache_slots {cache_slots.shape} incompatible with "
                f"tokens [B={B}, T={T}]"
            )
        slots_bt = cache_slots[:, None]  # [B, T]: a row's slots [s_b, s_b + T)
        if T > 1:
            slots_bt = slots_bt + jnp.arange(T, dtype=slots_bt.dtype)[None, :]
        rows = jnp.arange(B)[:, None]
        with jax.named_scope("serve.cache_write"):
            ck.value = ck.value.at[rows, slots_bt].set(k_store)
            cv.value = cv.value.at[rows, slots_bt].set(v_store)
            if int8_cache:
                csk.value = csk.value.at[rows, slots_bt].set(k_scale)
                csv.value = csv.value.at[rows, slots_bt].set(v_scale)
        # cidx (the shared frontier) is meaningless per-row; leave it.
        # causal per (row, query): query written at slot slots_bt[b, t]
        # sees valid slots <= its own
        if block_length:
            return _read(_block_mask(kv_valid, slots_bt, block_length))
        causal = (
            jnp.arange(max_len)[None, None, :] <= slots_bt[:, :, None]
        )  # [B, T, max_len]
        mask = kv_valid[:, None, :] & causal  # [B, T, max_len]
        return _read(mask)
    offset = cidx.value
    at = (0, offset) + (0,) * (k_store.ndim - 2)
    with jax.named_scope("serve.cache_write"):
        ck.value = jax.lax.dynamic_update_slice(ck.value, k_store, at)
        cv.value = jax.lax.dynamic_update_slice(cv.value, v_store, at)
        if int8_cache:
            csk.value = jax.lax.dynamic_update_slice(
                csk.value, k_scale, (0, offset, 0)
            )
            csv.value = jax.lax.dynamic_update_slice(
                csv.value, v_scale, (0, offset, 0)
            )
        cidx.value = offset + T
    if block_length:
        if kv_valid is None:
            raise ValueError("a mask by blocks of positions needs explicit kv_valid")
        slots_bt = jnp.broadcast_to(offset + jnp.arange(T)[None, :], (B, T))
        return _read(_block_mask(kv_valid, slots_bt, block_length))
    if kv_valid is None:
        # all slots up to the write frontier are real tokens
        kv_valid = jnp.arange(max_len)[None, :] < (offset + T)
        kv_valid = jnp.broadcast_to(kv_valid, (B, max_len))
    # causal-by-slot: query at absolute slot offset+i sees slots <= it
    slot_q = offset + jnp.arange(T)  # [T]
    causal = jnp.arange(max_len)[None, :] <= slot_q[:, None]  # [T, max_len]
    mask = kv_valid[:, None, :] & causal[None, :, :]  # [B, T, max_len]
    return _read(mask)


class BlockDecoding(NamedTuple):
    """What a model that is decoded a block at a time tells its holder
    (``model.decode_blocks()``, ``models/build.py``'s contract): a block of
    ``block_length`` positions is fed with ``mask_token_id`` where a position
    is undecided, and ``denoising_steps`` passes fix all of them."""

    block_length: int
    denoising_steps: int
    mask_token_id: int


def _block_mask(kv_valid, slots_bt, block_length):
    """``[B, T, L]``: the query written at slot ``slots_bt[b, t]`` sees the
    valid slots whose position lies in its own block of ``block_length``
    positions or in an earlier one; a slot's position is its rank among
    the row's valid slots (``kv_valid [B, L]``)."""
    block_of = (jnp.cumsum(kv_valid, axis=1, dtype=jnp.int32) - 1) // block_length  # [B, L]
    own = jnp.take_along_axis(block_of, slots_bt, axis=1)  # [B, T]
    return kv_valid[:, None, :] & (block_of[:, None, :] <= own[:, :, None])


# The most positions squared (a call's width x the cache's length) that a
# call's scores are held whole for, a head a row: 32 MiB of float32. Like
# ``flash_attention._sub_block`` the one place that decides, from the call's
# static shapes alone. Every served program up to a 2,048-wide prefill over
# 2,560 positions (5.2 M) stays under it, text for text; an 8,192-wide one
# over 8,704 (71 M: 8.6 GB of scores at 30 heads) does not.
_WHOLE_SCORES_MAX = 8 * 1024 * 1024


def prefill_is_tiled(width: int, max_len: int) -> bool:
    """Whether a ``width``-token call over a cache of ``max_len`` positions
    attends in tiles (:func:`_tiled_prefill_attention`) and not by the
    masked product. A one-token step never does."""
    return width > 1 and width * max_len > _WHOLE_SCORES_MAX


def cached_decode_attention(
    module, max_len, q, k, v, kv_valid, cache_slots, wo, cfg, *,
    block_length=0,
):
    """Update the module's decode cache with this call's K/V, then run
    attention in the cache's STORAGE precision: the bf16 cache feeds
    the plain masked einsum; the int8 cache feeds the int8 x int8 MXU
    path. The single decode-attention entry point of every family
    that decodes.

    Two bf16 leaves, chosen by what the call is given: where queries
    and keys have the same heads (GPT-2, an ungrouped Llama) the cache
    is folded (:func:`_fold_heads`, read by
    :func:`_masked_attention_folded`); a GQA-narrow cache stays
    ``[B, max_len, KVH, Hd]`` under the grouped einsums, and so does
    the int8 cache. Two for now: ``docs/generation.md`` says why.
    ``wo`` None (the bf16 GQA-narrow cache only) returns the heads'
    outputs ``[B, T, H, Hd]`` unprojected, for a model that gates them.

    A multi-token call over a bf16 cache whose scores would be too many
    to hold (:func:`prefill_is_tiled`) writes its keys and values the same
    way and attends through :func:`_tiled_prefill_attention`, which knows
    the causal rule alone: with ``block_length`` (the mask by blocks of
    positions, :func:`_update_decode_cache`) such a call is refused.
    """
    if block_length and cache_slots is None and prefill_is_tiled(q.shape[1], max_len):
        raise ValueError(
            f"a {q.shape[1]}-token call over {max_len} positions attends in tiles, "
            f"and the tiled prefill has no mask by blocks of positions")
    res = _update_decode_cache(
        module, max_len, k, v, kv_valid, cache_slots,
        fold=q.shape[2] == k.shape[2], block_length=block_length,
    )
    if len(res) == 3:
        k_full, v_full, mask = res
        if cache_slots is None and prefill_is_tiled(q.shape[1], max_len):
            offset = module.get_variable("cache", "index") - q.shape[1]
            return _tiled_prefill_attention(
                q, k, v, k_full, v_full, kv_valid, offset, wo, cfg)
        folded = k_full.ndim == 3
        attend = _masked_attention_folded if folded else _masked_attention
        return attend(q, k_full, v_full, mask, wo, cfg)
    k8, ks, v8, vs, mask = res
    return _masked_attention_int8(q, k8, ks, v8, vs, mask, wo, cfg)


def _tiled_prefill_attention(q, k, v, k_full, v_full, kv_valid, offset, wo, cfg):
    """A multi-token call's attention without its ``[T, L]`` scores or its
    ``[B, T, L]`` mask: ``q``, ``k``, ``v`` are this call's own ``[B, T, ..]``,
    ``k_full`` / ``v_full`` the row's leaves with them written at ``offset``
    (a traced scalar: the cache's write index before the call).

    **A fresh row** (``offset == 0``: a prompt's prefill, nothing in the row
    before it) has no key but its own ``T``: the flash forward kernel
    (``ops/flash_attention.py``, causal, unchanged) walks them in tiles. The
    kernel knows no left pad, so each row is turned until its real tokens
    stand first (prompts are LEFT-padded: a row's real tokens are its last
    ``n``): under the causal mask a real token then sees real tokens only,
    the padding behind them sees anything and is seen by nobody, and the
    output is turned back. Whatever depends on position (RoPE) was applied
    before. **A continued row** (a prefix's continuation) attends over the
    whole row as the masked product does, a block of queries at a time."""
    B, T, H, Hd = q.shape
    L, KVH = k_full.shape[1], k.shape[2]
    if kv_valid is None:
        kv_valid = jnp.broadcast_to(jnp.arange(L)[None, :] < offset + T, (B, L))

    def fresh():
        from ..ops.flash_attention import flash_attention

        pad = T - jnp.sum(kv_valid[:, :T], axis=1, dtype=jnp.int32)  # [B]: the call lies at slots [0, T)

        def turned(a, by):
            return jax.vmap(lambda row, n: jnp.roll(row, n, axis=0))(a, by)

        def as_the_kernel_takes(a):  # turned, then a grouped call's keys repeated to the query heads
            a = turned(a, -pad)
            return a if H == KVH else jnp.repeat(a, H // KVH, axis=2)

        out = flash_attention(turned(q, -pad), as_the_kernel_takes(k), as_the_kernel_takes(v), causal=True)
        return turned(out, pad)

    def continued():
        block = T
        while block * L > _WHOLE_SCORES_MAX and block % 2 == 0 and block > 2:
            block //= 2
        attend = _masked_attention_folded if k_full.ndim == 3 else _masked_attention

        def one(i):
            rows = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
            slot = offset + i * block + jnp.arange(block)
            mask = kv_valid[:, None, :] & (
                jnp.arange(L)[None, None, :] <= slot[None, :, None])
            return attend(rows, k_full, v_full, mask, None, cfg)

        out = jax.lax.map(one, jnp.arange(T // block))  # [T / block, B, block, H, Hd]
        return jnp.moveaxis(out, 0, 1).reshape(B, T, H, Hd)

    out = jax.lax.cond(offset == 0, fresh, continued)
    if wo is None:
        return out
    y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
    return constrain(y, "batch", "seq", "embed")


def _masked_attention_int8(q, k8, ks, v8, vs, mask, wo, cfg):
    """Decode attention computed IN int8 over the quantized cache.

    Dequantizing the cache to bf16 before the einsums makes XLA
    materialize the [B, max_len, KVH, Hd] bf16 tensor to HBM, so the
    step pays int8-read + bf16-write + bf16-read (no cell holds an int8
    cache: neither form was measured on the chip in this round). So
    never materialize a wide dequantized tensor: quantize the QUERY
    too and run int8 x int8 MXU dots with the scales factored out of
    the contractions —

    - QK: per-(token, head) q scales and per-(token, kv-head) k scales
      both factor OUT of the dot (they are constant along the
      contracted Hd axis): logits = (q8 . k8)_i32 * qs * ks.
    - PV: the v scale varies along the CONTRACTED slot axis, so it
      cannot factor out; instead fold it into the probs (a [.., S]
      tensor, tiny next to the cache), re-quantize the folded weights
      per row, and run int8 x int8 again.

    HBM traffic per step: the int8 cache + scales, read once, directly
    as dot operands.
    """
    Hd = q.shape[-1]
    H, KVH = q.shape[2], k8.shape[2]
    B, T = q.shape[:2]
    G = H // KVH
    qg = q.reshape(B, T, KVH, G, Hd)
    q8, qs = _quant_kv(qg)  # scales [B, T, KVH, G]
    logits = jnp.einsum(
        "btgck,bsgk->bgcts", q8, k8, preferred_element_type=jnp.int32
    ).astype(jnp.float32)
    logits = logits * jnp.transpose(qs, (0, 2, 3, 1))[..., None]
    logits = logits * jnp.transpose(ks, (0, 2, 1))[:, :, None, None, :]
    logits = logits / jnp.sqrt(jnp.float32(Hd))
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1)  # fp32
    w = probs * jnp.transpose(vs, (0, 2, 1))[:, :, None, None, :]
    wscale = jnp.maximum(jnp.max(jnp.abs(w), axis=-1) / 127.0, 1e-12)
    w8 = jnp.clip(jnp.round(w / wscale[..., None]), -127, 127).astype(
        jnp.int8
    )
    out = jnp.einsum(
        "bgcts,bsgk->btgck", w8, v8, preferred_element_type=jnp.int32
    ).astype(jnp.float32)
    out = out * jnp.transpose(wscale, (0, 3, 1, 2))[..., None]
    out = out.reshape(B, T, H, Hd).astype(cfg.dtype)
    y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
    return constrain(y, "batch", "seq", "embed")


def _masked_attention(q, k, v, mask, wo, cfg):
    """Dense attention over the full decode cache with an explicit mask.

    Decode is HBM-bound gather work, not MXU work — a plain einsum over
    the cache is the right TPU shape (the flash kernel's tiling pays off
    only on long training sequences). When the cache is GQA-narrow
    (k/v head count < q head count) the contraction is grouped instead
    of widening the cache: re-materializing [B, max_len, H, Hd] every
    single-token step would multiply exactly the HBM traffic the narrow
    cache exists to avoid. (``H == KVH`` reaches here from the folded
    body's ``T > 1`` view only.)
    """
    Hd = q.shape[-1]
    H, KVH = q.shape[2], k.shape[2]
    scale = 1.0 / jnp.sqrt(Hd).astype(q.dtype)
    if H != KVH:
        B, T = q.shape[:2]
        G = H // KVH
        qg = q.reshape(B, T, KVH, G, Hd)
        logits = jnp.einsum("btgck,bsgk->bgcts", qg, k) * scale
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e9)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            q.dtype
        )
        out = jnp.einsum("bgcts,bsgk->btgck", probs, v).reshape(B, T, H, Hd)
    else:
        logits = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
        logits = jnp.where(mask[:, None, :, :], logits, -1e9)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            q.dtype
        )
        out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    if wo is None:  # the heads as they are: the caller gates them, then projects
        return out
    y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
    return constrain(y, "batch", "seq", "embed")


def _masked_attention_folded(q, k, v, mask, wo, cfg):
    """:func:`_masked_attention` over a folded cache: ``k`` and ``v`` are
    ``[B, L, lanes]`` with head h in lanes ``[h*Hd, (h+1)*Hd)``
    (:func:`_fold_heads`), ``q`` is ``[B, T, H, Hd]``. The same sums of
    the same terms: bf16 operands, float32 softmax, the same mask.

    A decode step (``T == 1``) costs what it reads of the cache, so it
    leaves the leaf where it lies and contracts the whole lane
    dimension. The query goes in spread block-diagonally
    (``spread[b, h*Hd + d, h] = q[b, h, d]``, zero elsewhere), so
    ``K @ spread`` is one matrix product a row that streams K once,
    lane-dense; ``probs @ V`` gives every head all the lanes, of which
    it keeps its own ``Hd``. The zeros add nothing to a sum; the extra
    arithmetic is ``H`` times a one-token attention's, nothing beside
    the bytes. ``T > 1`` (a prefill into the cache, a prefix's
    continuation) would pay ``H * T`` times, so it views the leaf as
    ``[B, L, H, Hd]`` and contracts head by head: one relayout of a
    row's cache is nothing beside a prefill.
    """
    B, T, H, Hd = q.shape
    if T > 1:
        k4 = k[..., : H * Hd].reshape(B, -1, H, Hd)
        v4 = v[..., : H * Hd].reshape(B, -1, H, Hd)
        return _masked_attention(q, k4, v4, mask, wo, cfg)
    scale = 1.0 / jnp.sqrt(Hd).astype(q.dtype)
    own = jnp.eye(H, dtype=q.dtype)
    spread = jnp.einsum("bhd,hg->bhdg", q[:, 0], own).reshape(B, H * Hd, H)
    spread = jnp.pad(spread, ((0, 0), (0, k.shape[2] - H * Hd), (0, 0)))
    logits = jnp.einsum("bsj,bjh->bhs", k, spread) * scale
    logits = jnp.where(mask, logits, -1e9)  # [B, 1, L] over [B, H, L]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype
    )
    full = jnp.einsum("bhs,bsj->bhj", probs, v)[..., : H * Hd]
    out = jnp.einsum("bhgd,hg->bhd", full.reshape(B, H, H, Hd), own)
    y = jnp.einsum("bqhk,hkd->bqd", out[:, None], wo.astype(cfg.dtype))
    return constrain(y, "batch", "seq", "embed")


# -- a decode state with no position axis -------------------------------------

def _rows(a, idx):
    """``a[b, idx[b, t]]``: whole rows of ``a [B, S, d]`` by ``idx [B, T]``."""
    return jax.vmap(lambda rows, i: rows[i])(a, idx)


def real_neighbours(s, z, token_valid):
    """The padding rule of a causal convolution's decode state, for any
    number of earlier taps: ``s [B, n, D]`` holds the inputs of the row's
    last ``n`` real tokens (oldest first; zeros before the row's first),
    ``z [B, T, D]`` this call's inputs, ``token_valid [B, T]`` which of
    them are real. -> (``n`` arrays ``[B, T, D]``: for each of this call's
    tokens the input of the real token ``n`` before it, ..., of the one
    just before it, whatever padding lies between; the state moved on: the
    inputs of the row's last ``n`` real tokens, this call's included).
    A padded token reads something nobody uses and leaves the state alone."""
    B, T, D = z.shape
    n = s.shape[1]
    if T == 1:  # a decode step: no neighbour to look for
        keep = token_valid[:, :, None]
        moved = jnp.where(keep, jnp.concatenate([s[:, 1:], z], axis=1), s)
        return [s[:, i:i + 1] for i in range(n)], moved
    # the row as [state ; this call], the state's entries always real
    zz = jnp.concatenate([s, z], axis=1)  # [B, T + n, D]
    real = jnp.concatenate([jnp.ones((B, n), bool), token_valid], axis=1)
    at = jnp.arange(T + n, dtype=jnp.int32)[None, :]
    last = jax.lax.cummax(jnp.where(real, at, 0), axis=1)  # the last real one up to here
    prev = [jnp.concatenate([jnp.zeros((B, 1), jnp.int32), last[:, :-1]], axis=1)]  # ... before here
    ends = [last[:, -1:]]
    for _ in range(n - 1):
        prev.append(jnp.take_along_axis(prev[0], prev[-1], axis=1))  # ... and the one before that
        ends.append(jnp.take_along_axis(prev[0], ends[-1], axis=1))
    moved = _rows(zz, jnp.concatenate(ends[::-1], axis=1))
    return [_rows(zz, p[:, n:]) for p in prev[::-1]], moved


def token_valid_at(module, B, T, kv_valid, cache_slots):
    """Which of this call's tokens are real: ``kv_valid`` at the slots
    the call writes, found as :func:`_update_decode_cache` finds them
    (the shared write offset, kept on ``module`` as ``index``; or the
    per-row ``cache_slots``). With no ``kv_valid`` every token is. For
    the top module of a model that keeps a state with no position axis."""
    index = module.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
    if cache_slots is not None:
        if kv_valid is None:
            raise ValueError("cache_slots mode needs explicit kv_valid")
        return jnp.take_along_axis(kv_valid, cache_slots[:, None], axis=1)
    offset = index.value
    index.value = offset + T
    if kv_valid is None:
        return jnp.ones((B, T), bool)
    return jax.lax.dynamic_slice(kv_valid, (0, offset), (B, T))


# -- the gated delta-rule mixer -------------------------------------------------

DELTA_CHUNK = 64  # the chunked form's tile (``ops/gated_delta.py``)


def _l2_normalised(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


class GatedDeltaMixer(nn.Module):
    """The gated delta-rule mixer of ``qwen3_next.py`` and
    ``olmo_hybrid.py`` (their docstrings have the equations), built from
    the config's own sizes by their published names: ``linear_num_key_heads``
    (``Hk``), ``linear_num_value_heads`` (``Hv``, a multiple of ``Hk``),
    ``linear_key_head_dim``, ``linear_value_head_dim``,
    ``linear_conv_kernel_dim``; ``linear_allow_neg_eigval`` (absent: False)
    makes the write strength ``beta = 2 sigmoid(b)``, in (0, 2), so that a
    write can reflect the state along ``k`` (eigenvalue ``1 - beta`` in
    (-1, 1)); beside them ``rms_norm_eps``, ``residual_init_std`` (of
    ``w_out``), ``init_std``, ``dtype``, ``param_dtype``. ``token_valid``
    ``[B, T]`` (decode only) says which of this call's tokens are real:
    at a padded token ``g = 0`` and ``beta = 0`` exactly, and the
    convolution's neighbours are the real ones (:func:`real_neighbours`).
    ``inverse`` is ``ops/gated_delta.py``'s: a family whose programs are
    pinned to the squaring names it. In decode mode it keeps ``delta_state
    [B, Hv, dk, dv]`` (float32) and ``conv_state [B, K - 1, 2 Hk dk + Hv
    dv]`` in the ``"cache"`` collection."""

    config: Any
    inverse: str = "blocks"

    @nn.compact
    def __call__(self, u, *, decode: bool = False, token_valid=None):
        cfg = self.config
        B, T, D = u.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, K = cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim
        keys, values = Hk * dk, Hv * dv
        width = 2 * keys + values  # the channels of ``[q ; k ; v]``
        f32 = jnp.float32
        w_qkvz = weight("w_qkvz", cfg, (D, width + values), ("embed", "delta_proj"))
        w_ba = weight("w_ba", cfg, (D, 2 * Hv), ("embed", "delta_heads"))
        w_out = weight("w_out", cfg, (values, D), ("delta_inner", "embed"), cfg.residual_init_std)
        taps = param_with_axes("conv_kernel", nn.initializers.normal(CONV_INIT_STD),
                               (K, width), f32, axes=("conv_taps", "delta_channels"))
        dt_bias = param_with_axes("dt_bias", dt_bias_init, (Hv,), f32, axes=("delta_heads",))
        a_log = param_with_axes("A_log", a_log_init, (Hv,), f32, axes=("delta_heads",))
        gate_w = param_with_axes("gate_norm", nn.initializers.ones, (dv,), f32, axes=("norm",))

        with jax.named_scope("gdn.in_proj"):
            qkvz = jnp.dot(u, w_qkvz)
            qkv, z = qkvz[..., :width], qkvz[..., width:]
            ba = jnp.dot(u, w_ba, preferred_element_type=f32)  # the decays stay float32
        with jax.named_scope("gdn.conv"):
            if not decode:
                padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
                earlier = [padded[:, j:j + T] for j in range(K - 1)]
            else:
                state = self.variable("cache", "conv_state", jnp.zeros, (B, K - 1, width), qkv.dtype)
                earlier, state.value = real_neighbours(state.value, qkv, token_valid)
            conv = taps[K - 1] * qkv.astype(f32)
            for j in range(K - 1):
                conv = conv + taps[j] * earlier[j].astype(f32)
            qkv = jax.nn.silu(conv)
        q = _l2_normalised(qkv[..., :keys].reshape(B, T, Hk, dk)) * dk ** -0.5
        k = _l2_normalised(qkv[..., keys:2 * keys].reshape(B, T, Hk, dk))
        v = qkv[..., 2 * keys:].reshape(B, T, Hv, dv)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        if getattr(cfg, "linear_allow_neg_eigval", False):
            beta = 2.0 * beta
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
        if decode:
            # the padding rule: at a padded token no decay and nothing written,
            # exactly, so it leaves the state alone
            beta = jnp.where(token_valid[:, :, None], beta, 0.0)
            g = jnp.where(token_valid[:, :, None], g, 0.0)
        held = self.variable("cache", "delta_state", jnp.zeros, (B, Hv, dk, dv), f32) if decode else None
        if decode and T == 1:
            with jax.named_scope("gdn.step"):
                o, held.value = gated_delta_step(held.value, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
        else:  # from the row's state, or from zeros where nothing is cached
            with jax.named_scope("gdn.chunk"):
                o, last = gated_delta_chunked(
                    q, k, v, g, beta, DELTA_CHUNK, held.value if decode else None, inverse=self.inverse)
            if decode:
                held.value = last
        with jax.named_scope("gdn.gate_norm"):
            var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * gate_w
            y = (o.reshape(B, T, values) * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        with jax.named_scope("gdn.out_proj"):
            out = jnp.dot(y, w_out)
        return constrain(out, "batch", "seq", "embed")


# -- a model's side of the contract (``models/build.py``) ---------------------

def dtypes_read_by_name(params, names, dtype):
    """A tree like ``params`` holding, for each leaf, the dtype its model
    reads it in: ``dtype`` for a floating leaf whose own name is in
    ``names`` (every use of it is ``leaf.astype(dtype)``), else the
    leaf's own. What ``consumed_param_dtypes`` of a model returns."""

    def one(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in names and jnp.issubdtype(leaf.dtype, jnp.floating):
            return jnp.dtype(dtype)
        return jnp.dtype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


def state_leaves_by_name(cache, names):
    """A tree like ``cache`` (the ``"cache"`` collection, at any batch
    size) that is True where a leaf is a per-request *state* ``[B, ...]``
    with no position axis, False where it is positional ``[B, L, ...]``
    or a scalar: True for a leaf whose own name is in ``names``. By the
    name, never by the shape: a cache of two positions is as long as a
    convolution's state. What ``cache_state_leaves`` of a model returns."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) in names, cache)


# -- losses -------------------------------------------------------------------

def _token_ce(logits, targets, ignore_index: int = -1):
    """Masked per-token CE in fp32: [..., V] logits -> [...] losses
    (0.0 at ignored positions). Single source of the CE math for both
    the dense loss and the chunked fused path."""
    logits = logits.astype(jnp.float32)
    mask = targets != ignore_index
    safe_targets = jnp.where(mask, targets, 0)
    logps = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(
        logps, safe_targets[..., None], axis=-1
    )[..., 0]
    return jnp.where(mask, token_loss, 0.0)


def cross_entropy_loss(logits, targets, ignore_index: int = -1):
    """Mean next-token CE in fp32 (MXU-friendly: one log_softmax fusion)."""
    return token_loss_mean(
        _token_ce(logits, targets, ignore_index), targets, ignore_index
    )


def chunked_token_ce(
    x, w_head, targets, chunk: int, vocab_first: bool, ignore_index: int = -1
):
    """Per-token CE fused with the LM head, seq-chunked: [B,T,D] -> [B,T].

    The fp32 logits for the full sequence are the HBM ceiling of a
    small-model/large-vocab step (bs=32 x 1024 x 50304 fp32 = 6.6 GB).
    A ``lax.scan`` over T/chunk slices computes each chunk's logits,
    reduces them to token losses, and — with ``jax.checkpoint`` on the
    body — recomputes them in backward instead of storing them, so live
    logits are [B, chunk, V] at any moment. Costs one extra head matmul
    in backward; buys the batch sizes the dense path cannot fit.
    """
    B, T, D = x.shape
    if T % chunk:
        raise ValueError(f"seq len {T} not divisible by ce_chunk {chunk}")
    C = T // chunk

    @jax.checkpoint
    def body(carry, xs):
        xb, tb = xs
        if vocab_first:  # w_head [V, D] (tied embeddings)
            logits = jnp.einsum("bcd,vd->bcv", xb, w_head)
        else:  # w_head [D, V]
            logits = jnp.einsum("bcd,dv->bcv", xb, w_head)
        return carry, _token_ce(logits, tb, ignore_index)

    with jax.named_scope("loss.chunk"):  # the device scope of the head's products and the CE
        xc = jnp.swapaxes(x.reshape(B, C, chunk, D), 0, 1)  # [C, B, c, D]
        tc = jnp.swapaxes(targets.reshape(B, C, chunk), 0, 1)  # [C, B, c]
        _, tls = jax.lax.scan(body, (), (xc, tc))  # [C, B, c]
        return jnp.swapaxes(tls, 0, 1).reshape(B, T)


def token_loss_mean(token_losses, targets, ignore_index: int = -1):
    """Loss head for the fused-CE path: mean of model-computed per-token
    losses over non-ignored positions (the model already zeroed them)."""
    if token_losses.ndim != targets.ndim:
        raise ValueError(
            f"token_loss_mean expects per-token losses shaped like targets "
            f"{targets.shape}, got {token_losses.shape} — a [B,T,V] rank "
            f"means the model ran with ce_chunk=0 (raw logits); pair that "
            f"with cross_entropy_loss instead"
        )
    mask = targets != ignore_index
    return token_losses.sum() / jnp.maximum(mask.sum(), 1)
