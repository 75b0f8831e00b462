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
from flax.linen import partitioning as nn_partitioning

param_with_axes = nn_partitioning.param_with_axes


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


def _constrain(x, *axes):
    from ..parallel.sharding import with_logical_constraint

    return with_logical_constraint(x, *axes)


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
    fuse converts into dot operands), which measured 0.81x the bf16
    cache on silicon. The production path keeps operands int8 end to
    end — see :func:`_masked_attention_int8`."""
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
    module, max_len, k, v, kv_valid, cache_slots=None, *, fold=False
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
    shared by GPT and Llama attention. The DEFAULT path follows the
    engine convention (:mod:`dlrover_tpu.models.generation`): LEFT-
    padded prompts, so every batch row shares one static write offset
    and the cache update is a single ``dynamic_update_slice`` — the
    shape XLA tiles well for multi-token prefill writes. ``kv_valid``
    [B, max_len] marks which cache slots hold real tokens (False =
    left-pad); queries at local position i attend valid slots s with
    s <= offset + i.

    ``cache_slots`` int32 switches to PER-ROW write slots: ``[B]`` for
    single-token decode (the continuous-batching engine's per-row
    cache layout: every request advances its own write position, so
    admissions leave no holes past a prompt's bucket) or ``[B, T]``
    for a T-token window written at per-row slots (no caller at
    present: ROADMAP D13). The write is a
    B(×T)-row scatter — tiny next to the attention pass that reads the
    whole cache anyway — and the causal mask keys on each query's own
    slot (returned mask is [B, T, max_len]). Requires an explicit
    ``kv_valid``.

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
        HBM traffic than the narrow cache saves (measured 0.81x on
        silicon) — the int8 attention path consumes the int8 operands
        directly (see _masked_attention_int8)."""
        if not int8_cache:
            return ck.value, cv.value, mask
        return ck.value, csk.value, cv.value, csv.value, mask

    if cache_slots is not None:
        if kv_valid is None:
            raise ValueError("cache_slots mode needs explicit kv_valid")
        # [B] (single-token decode) or [B, T] (a T-token window written
        # at per-row slots)
        slots_bt = (
            cache_slots[:, None] if cache_slots.ndim == 1 else cache_slots
        )
        if slots_bt.shape != (B, T):
            raise ValueError(
                f"cache_slots {cache_slots.shape} incompatible with "
                f"tokens [B={B}, T={T}]"
            )
        rows = jnp.arange(B)[:, None]
        ck.value = ck.value.at[rows, slots_bt].set(k_store)
        cv.value = cv.value.at[rows, slots_bt].set(v_store)
        if int8_cache:
            csk.value = csk.value.at[rows, slots_bt].set(k_scale)
            csv.value = csv.value.at[rows, slots_bt].set(v_scale)
        # cidx (the shared frontier) is meaningless per-row; leave it.
        # causal per (row, query): query written at slot slots_bt[b, t]
        # sees valid slots <= its own
        causal = (
            jnp.arange(max_len)[None, None, :] <= slots_bt[:, :, None]
        )  # [B, T, max_len]
        mask = kv_valid[:, None, :] & causal  # [B, T, max_len]
        return _read(mask)
    offset = cidx.value
    at = (0, offset) + (0,) * (k_store.ndim - 2)
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
    if kv_valid is None:
        # all slots up to the write frontier are real tokens
        kv_valid = jnp.arange(max_len)[None, :] < (offset + T)
        kv_valid = jnp.broadcast_to(kv_valid, (B, max_len))
    # causal-by-slot: query at absolute slot offset+i sees slots <= it
    slot_q = offset + jnp.arange(T)  # [T]
    causal = jnp.arange(max_len)[None, :] <= slot_q[:, None]  # [T, max_len]
    mask = kv_valid[:, None, :] & causal[None, :, :]  # [B, T, max_len]
    return _read(mask)


def cached_decode_attention(
    module, max_len, q, k, v, kv_valid, cache_slots, wo, cfg
):
    """Update the module's decode cache with this call's K/V, then run
    attention in the cache's STORAGE precision: the bf16 cache feeds
    the plain masked einsum; the int8 cache feeds the int8 x int8 MXU
    path. The single decode-attention entry point for GPT, Llama, LFM2
    and Granite.

    Two bf16 leaves, chosen by what the call is given: where queries
    and keys have the same heads (GPT-2, an ungrouped Llama) the cache
    is folded (:func:`_fold_heads`, read by
    :func:`_masked_attention_folded`); a GQA-narrow cache stays
    ``[B, max_len, KVH, Hd]`` under the grouped einsums, and so does
    the int8 cache. Two for now: ``docs/generation.md`` says why.
    ``wo`` None (the bf16 GQA-narrow cache only) returns the heads'
    outputs ``[B, T, H, Hd]`` unprojected, for a model that gates them.
    """
    res = _update_decode_cache(
        module, max_len, k, v, kv_valid, cache_slots,
        fold=q.shape[2] == k.shape[2],
    )
    if len(res) == 3:
        k_full, v_full, mask = res
        folded = k_full.ndim == 3
        attend = _masked_attention_folded if folded else _masked_attention
        return attend(q, k_full, v_full, mask, wo, cfg)
    k8, ks, v8, vs, mask = res
    return _masked_attention_int8(q, k8, ks, v8, vs, mask, wo, cfg)


def _masked_attention_int8(q, k8, ks, v8, vs, mask, wo, cfg):
    """Decode attention computed IN int8 over the quantized cache.

    The first int8 attempt dequantized the cache to bf16 before the
    einsums; XLA materialized the [B, max_len, KVH, Hd] bf16 tensor to
    HBM, so the step paid int8-read + bf16-write + bf16-read — 24%
    SLOWER than the bf16 cache on silicon (SILICON_r05_1785579811:
    decode_int8_vs_bf16 0.809). The fix is to never materialize a wide
    dequantized tensor: quantize the QUERY too and run int8 x int8
    MXU dots with the scales factored out of the contractions —

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
    return _constrain(y, "batch", "seq", "embed")


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
    return _constrain(y, "batch", "seq", "embed")


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
    return _constrain(y, "batch", "seq", "embed")


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
        q = _constrain(q, "batch", "seq", "heads", "kv")
        k = _constrain(k, "batch", "seq", "heads", "kv")
        v = _constrain(v, "batch", "seq", "heads", "kv")

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
        out = _constrain(out, "batch", "seq", "heads", "kv")
        y = jnp.einsum("bqhk,hkd->bqd", out, wo.astype(cfg.dtype))
        return _constrain(y, "batch", "seq", "embed")


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
        h = _constrain(h, "batch", "seq", "mlp")
        h = jax.nn.gelu(h)
        y = jnp.dot(h, w2.astype(cfg.dtype)) + b2.astype(cfg.dtype)
        return _constrain(y, "batch", "seq", "embed")


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


# Every use of these is ``leaf.astype(cfg.dtype)``. LayerNorm's ``scale``
# and ``bias`` are read in float32 and are not here.
_READ_IN_COMPUTE_DTYPE = frozenset(
    {"wte", "wpe", "wqkv", "wo", "w1", "b1", "w2", "b2", "lm_head"}
)


class GPT(nn.Module):
    """Decoder-only LM. ``__call__(tokens[B,T]) -> logits[B,T,V]``.

    With ``targets`` given the return value is per-token losses
    ``[B, T]`` (fp32, 0.0 at ``ignore_index`` positions) — pair with
    :func:`token_loss_mean` as the train-step loss. ``cfg.ce_chunk``
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
        if positions is None:
            if decode:
                raise ValueError("decode=True needs absolute positions")
            pos_emb = wpe.astype(cfg.dtype)[None, :T]
        else:
            pos_emb = wpe.astype(cfg.dtype)[positions]  # [B, T, D]
        x = wte.astype(cfg.dtype)[tokens] + pos_emb
        x = _constrain(x, "batch", "seq", "embed")

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
            return _chunked_token_ce(
                x, w_head, targets, cfg.ce_chunk or T, vocab_first
            )

        if vocab_first:
            logits = jnp.einsum("btd,vd->btv", x, w_head)
        else:
            logits = jnp.dot(x, w_head)
        return _constrain(logits, "batch", "seq", "vocab")


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


def _chunked_token_ce(
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
    xc = jnp.swapaxes(x.reshape(B, C, chunk, D), 0, 1)  # [C, B, c, D]
    tc = jnp.swapaxes(targets.reshape(B, C, chunk), 0, 1)  # [C, B, c]

    @jax.checkpoint
    def body(carry, xs):
        xb, tb = xs
        if vocab_first:  # w_head [V, D] (tied embeddings)
            logits = jnp.einsum("bcd,vd->bcv", xb, w_head)
        else:  # w_head [D, V]
            logits = jnp.einsum("bcd,dv->bcv", xb, w_head)
        return carry, _token_ce(logits, tb, ignore_index)

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
