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
exchange. (``experts_held = 0`` holds them all.)

**How the experts held are computed.** Assignments are sorted by expert;
those that land here go, a row buffer at a time, through three grouped
products (``ops/grouped_matmul.py``). The buffer holds
``MoeSizes.buffer_over_mean`` times the mean load, which one pass nearly
always fits: 4 where something in the step steers the selection (a trained
router, a frozen bias), 2 where nothing does (a chip's share of a router
whose gates are constants in the backward pass and that has no bias: the
load on the experts held read 0.97-1.07 times the mean on a first step and
passed twice the mean in one layer-step of ~6,900). A step whose
load is past the buffer takes as many further passes over the same buffer
as its load needs, so the layer never drops a token whatever the router
does, and has no second way of computing an expert.

With ``targets`` the model returns the trunk's per-token losses (the
fused-CE contract of ``gpt.py``) and sows the weighted MTP loss under
``("objective", "mtp")`` and its counters under ``"metrics"``; the train
step adds the one and returns the others.
"""

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from ..ops.flash_attention import flash_attention_sharded
from ..ops.grouped_matmul import collect_rows, grouped_matmul, spread_rows
from ..parallel.mesh import get_current_mesh
from .gpt import _chunked_token_ce, token_loss_mean
from .llama import RMSNorm, _constrain  # the same norm and constraint; eps below

param_with_axes = nn_partitioning.param_with_axes

@dataclass(frozen=True)
class MoeSizes:
    """What :class:`MoeLayer` is built from: the sizes of one routed-expert
    layer and of the chip's share of it, whichever model's config they
    come from (``MlaMoeConfig.moe_sizes``, ``Lfm2MoeConfig.moe_sizes``,
    ``Qwen3NextConfig.moe_sizes``, ``MellumConfig.moe_sizes``). The layer
    derives the size of its row buffer from them (``buffer_over_mean``):
    nothing sets it."""

    n_experts: int  # the router's width
    top_k: int
    width: int  # each expert's SwiGLU
    experts_held: int = 0  # 0: all of them
    expert_offset: int = 0  # the first expert held
    norm_topk: bool = True  # gates over the chosen ones' sum ...
    norm_eps: float = 0.0  # ... plus this
    scale: float = 1.0
    n_shared: int = 0  # 0 or 1 shared expert, ``n_shared`` widths wide
    shared_gate: bool = False  # the shared expert behind ``sigmoid(x w_s)``, a float a token
    score_fn: str = "sigmoid"  # of the router's logits, over all experts: sigmoid | softmax
    bias_name: str = "e_score_correction_bias"  # "": no selection bias
    init_std: float = 0.02
    expert_init_std: float = 0.0  # the routed experts' matrices; 0: ``init_std``
    # the matrices that write to the residual stream (``w_down``, routed and
    # shared), where a model draws them narrower; 0: as the others. The routed
    # ones keep their factor over ``init_std``
    down_init_std: float = 0.0
    bias_init_std: float = 0.01
    # False: the gates are constants in the backward pass. For a chip's share
    # of a router that no frozen bias steers: through the gates the task loss
    # reaches the router by the held experts' outputs alone (the group's
    # all-reduce would add the others'), and that partial sum trains the
    # chip's share of the assignments up or down, not the choice among experts
    train_gates: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def buffer_over_mean(self) -> int:
        """The row buffer of the grouped products, as a multiple of the mean
        load (the assignments that land on the experts held when routing is
        even). Moving rows costs by the buffer, whatever the load (every
        gather, gate select, sort permutation and scatter runs over all of
        it; only the grouped products skip its empty tiles), and a load past
        it costs a further pass, so it is sized to hold nearly every step,
        by what can steer the load:

        - **4** where the step trains the router (``train_gates``) or a
          selection bias stands on it (``bias_name``): under training with a
          fixed bias the router sends a layer's tokens to one hot expert for
          steps at a time, and on the v5e 19% of a run's (layer, step) pairs
          passed 2x the mean, 1-5% passed 4x, none 8x (PERF.md, PR 27).
        - **2** where neither does: the selection is the router's init over
          fresh tokens, and the load on the 16 of 64 experts held read
          0.97-1.07x the mean in every layer of a first step, 24.8-26.1% of
          a window's assignments in every seed, and passed 2x in one
          layer-step of ~6,900 while a window memorised its batches (PERF.md,
          PRs 47-48). There 4x the mean would be every assignment, a quarter
          of it filled.
        """
        return 4 if self.train_gates or self.bias_name else 2


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
    def rms_eps(self) -> float:  # the name ``llama.RMSNorm`` reads
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


def _weight(name, cfg, shape, axes, std: float = 0.0):
    return param_with_axes(
        name, nn.initializers.normal(std or cfg.init_std), shape,
        cfg.param_dtype, axes=axes,
    ).astype(cfg.dtype)


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
        w_dq = _weight("w_dq", cfg, (D, cfg.q_lora_rank), ("embed", "q_lora"))
        w_uq = _weight("w_uq", cfg, (cfg.q_lora_rank, H, nope + rope),
                       ("q_lora", "heads", "kv"))
        w_dkv = _weight("w_dkv", cfg, (D, cfg.kv_lora_rank + rope), ("embed", "kv_lora"))
        w_ukv = _weight("w_ukv", cfg, (cfg.kv_lora_rank, H, nope + dv),
                        ("kv_lora", "heads", "kv"))
        w_o = _weight("w_o", cfg, (H, dv, D), ("heads", "kv", "embed"))

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
        q = _constrain(q, "batch", "seq", "heads", "kv")
        k = _constrain(k, "batch", "seq", "heads", "kv")
        v = _constrain(v, "batch", "seq", "heads", "kv")
        with jax.named_scope("mla.attend"):
            out = flash_attention_sharded(q, k, v, get_current_mesh(), causal=True)
        out = _constrain(out, "batch", "seq", "heads", "kv")
        return _constrain(jnp.einsum("bthk,hkd->btd", out, w_o), "batch", "seq", "embed")


class SwiGlu(nn.Module):
    config: Any  # reads ``init_std``, ``param_dtype``, ``dtype``
    width: int
    down_init_std: float = 0.0  # ``w_down``, which writes to the residual stream; 0: ``init_std``

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        D, F = x.shape[-1], self.width
        w_gate = _weight("w_gate", cfg, (D, F), ("embed", "mlp"))
        w_up = _weight("w_up", cfg, (D, F), ("embed", "mlp"))
        w_down = _weight("w_down", cfg, (F, D), ("mlp", "embed"), self.down_init_std)
        h = jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up)
        return jnp.dot(h, w_down)


def route(scores, bias, top_k: int, norm: bool, scale: float, eps: float = 0.0):
    """(expert ids ``[N, k]``, gates ``[N, E]``) from float32 scores
    ``[N, E]``: the top k of ``scores + bias`` are chosen, and a chosen
    expert's gate is its *unbiased* score, over the chosen ones' sum
    (plus ``eps``) with ``norm``, times ``scale``. The gates are given for every expert (a
    token's row of them is read at the experts it chose), so that no
    gather by choice, and no scatter behind it, is needed."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    if norm:
        chosen = jnp.sum(jnp.take_along_axis(scores, idx, axis=-1), axis=-1, keepdims=True)
        scores = scores / (chosen + eps if eps else chosen)
    return idx, scores * scale


_SCORE_FNS = {"sigmoid": jax.nn.sigmoid, "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


class MoeLayer(nn.Module):
    """The routed experts held here plus the shared expert, if there is
    one. Built from :class:`MoeSizes`, so that every model with such a
    layer runs this one: trained over ``[B, T]`` tokens, or inside a
    server's decode chunk over one token a slot (there ``N x K`` rows are
    the whole buffer: one pass, and the overflow branch is never built).
    An expert no row chose has an empty group, which the grouped product
    does not visit: its weights are not read."""

    sizes: MoeSizes

    @nn.compact
    def __call__(self, x):
        cfg = self.sizes
        B, T, D = x.shape
        N, K = B * T, cfg.top_k
        E, Eh, F = cfg.n_experts, cfg.experts_here, cfg.width
        xf = x.reshape(N, D)

        w_router = param_with_axes(
            "w_router", nn.initializers.normal(cfg.init_std), (D, E),
            jnp.float32, axes=("embed", None))
        bias = 0.0
        if cfg.bias_name:
            bias = jax.lax.stop_gradient(param_with_axes(
                cfg.bias_name, nn.initializers.normal(cfg.bias_init_std),
                (E,), jnp.float32, axes=(None,)))
        std = cfg.expert_init_std
        w_gate = _weight("w_gate", cfg, (Eh, D, F), ("expert", "embed", "expert_mlp"), std)
        w_up = _weight("w_up", cfg, (Eh, D, F), ("expert", "embed", "expert_mlp"), std)
        down_std = std
        if cfg.down_init_std:
            down_std = cfg.down_init_std * (std or cfg.init_std) / cfg.init_std
        w_down = _weight("w_down", cfg, (Eh, F, D), ("expert", "expert_mlp", "embed"), down_std)

        with jax.named_scope("moe.route"):
            # float32 all the way: a score rounded to bf16 moves the top k
            logits = jnp.dot(xf.astype(jnp.float32), w_router,
                             precision=jax.lax.Precision.HIGHEST)
            scores = _SCORE_FNS[cfg.score_fn](logits)
            idx, gate_of_expert = route(
                scores, bias, K, cfg.norm_topk, cfg.scale, cfg.norm_eps)
            if not cfg.train_gates:
                gate_of_expert = jax.lax.stop_gradient(gate_of_expert)

        with jax.named_scope("moe.dispatch"):
            local = idx - cfg.expert_offset
            held = (local >= 0) & (local < Eh)
            key = jnp.where(held, local, Eh).reshape(N * K)
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(Eh)[None, :], axis=0, dtype=jnp.int32)
            n_here = jnp.sum(group_sizes)
            order = jnp.argsort(key, stable=True)  # held first, by expert
            ends = jnp.cumsum(group_sizes)  # of each expert's group among the sorted rows
            mean_load = N * K * Eh / E
            rows = min(N * K, -(-int(cfg.buffer_over_mean * mean_load) // 8) * 8)  # whole sublanes
            firsts = range(0, N * K, rows)  # a pass takes the sorted rows [first, first + rows)
            valid = [jnp.clip(n_here - first, 0, rows) for first in firsts]

        def grouped(first, xf, gate_of_expert):
            """One pass: the sorted rows from ``first`` on, a buffer of
            them, through the grouped products."""
            with jax.named_scope("moe.dispatch"):
                taken = order[first:first + rows]
                last = first + taken.shape[0]
                sizes = jnp.clip(ends, first, last) - jnp.clip(ends - group_sizes, first, last)
                n_valid = valid[first // rows]
                token_of = taken // K
                expert_of = key[taken] + cfg.expert_offset
                xs = spread_rows(xf, token_of, n_valid)
                gate_of = jnp.sum(  # each row's gate: its token's, at its expert
                    jnp.where(expert_of[:, None] == jnp.arange(E)[None, :],
                              spread_rows(gate_of_expert, token_of, n_valid), 0.0),
                    axis=1, keepdims=True).astype(cfg.dtype)
            with jax.named_scope("moe.experts"):
                h = jax.nn.silu(grouped_matmul(xs, w_gate, sizes)) * (
                    grouped_matmul(xs, w_up, sizes))
                ys = grouped_matmul(h, w_down, sizes)
            with jax.named_scope("moe.combine"):
                return collect_rows(ys * gate_of, token_of, n_valid, N)

        def nothing(xf, gate_of_expert):
            return jnp.zeros_like(xf)

        @jax.checkpoint  # a rare pass keeps nothing for the backward pass
        def overflow(xf, gate_of_expert):
            """The rows past the first buffer, as many passes as they need."""
            out = grouped(firsts[1], xf, gate_of_expert)
            for i in range(2, len(firsts)):
                out = out + jax.lax.cond(
                    valid[i] > 0, functools.partial(grouped, firsts[i]), nothing, xf, gate_of_expert)
            return out

        routed = grouped(0, xf, gate_of_expert)
        # (initialising wants the parameters, which the branch has none of, and tracing
        # a second pass costs a start ~0.3 s a layer: set-up is a bounded metric)
        if len(firsts) > 1 and not self.is_initializing():
            routed = routed + jax.lax.cond(valid[1] > 0, overflow, nothing, xf, gate_of_expert)

        out = routed
        if cfg.n_shared:
            shared = SwiGlu(cfg, F * cfg.n_shared, cfg.down_init_std, name="shared")(xf)
            if cfg.shared_gate:
                with jax.named_scope("moe.shared_gate"):
                    w_s = param_with_axes(
                        "w_shared_gate", nn.initializers.normal(cfg.init_std), (D, 1),
                        jnp.float32, axes=("embed", None))
                    gate = jax.nn.sigmoid(jnp.dot(xf.astype(jnp.float32), w_s))
                    shared = (gate * shared.astype(jnp.float32)).astype(cfg.dtype)
            out = out + shared
        for name, value in dict(
            assignments_here=n_here,
            assignments_absent=N * K - n_here,
            load_max_over_mean=jnp.max(group_sizes) * Eh / jnp.maximum(n_here, 1).astype(jnp.float32),
            experts_touched=jnp.sum(group_sizes > 0, dtype=jnp.int32),
            dropped=n_here - sum(valid),  # none: the passes take every row
            extra_passes=sum([(v > 0).astype(jnp.int32) for v in valid[1:]], jnp.int32(0)),
        ).items():
            self.sow("metrics", name, value)
        return out.reshape(B, T, D)


class Block(nn.Module):
    config: MlaMoeConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = x + LatentAttention(cfg, name="attn")(RMSNorm(cfg, name="norm_attn")(x))
        h = RMSNorm(cfg, name="norm_mlp")(x)
        if cfg.is_expert_block(self.layer_idx):
            y = MoeLayer(cfg.moe_sizes, name="moe")(h)
        else:
            y = SwiGlu(cfg, cfg.intermediate_size, name="mlp")(h)
        return _constrain(x + y, "batch", "seq", "embed")


# What a rematerialised block keeps (``_block``). One object for every block:
# JAX caches a policy's partial evaluations by its identity, and a policy made
# anew for each block lowers every block's callees again.
_KEEP_FLASH_RESULTS = jax.checkpoint_policies.save_only_these_names(
    "flash.out", "flash.lse")


def _block(cfg: MlaMoeConfig):
    if not cfg.use_remat:
        return Block
    # The backward pass recomputes the block from its input, except what its
    # flash kernel wrote: ``out`` and ``lse`` (named in ``_fa_fwd``) are kept,
    # so the forward kernel runs once a step and not twice. A block holds
    # tokens x heads x (2 x v_head_dim + 4) bytes for them beside its kept
    # input (136 MB beside 67 MB at b4 x 4096, 32 heads of 128).
    return nn.remat(Block, prevent_cse=True, policy=_KEEP_FLASH_RESULTS)


class MtpModule(nn.Module):
    """One multi-token-prediction depth: the next token's embedding and
    the trunk's (normed) output, joined, through one expert block."""

    config: MlaMoeConfig

    @nn.compact
    def __call__(self, h, next_emb):
        cfg = self.config
        D = cfg.hidden_size
        w_eh = _weight("w_eh", cfg, (2 * D, D), (None, "embed"))
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
        w_head = _weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size),
                         ("embed", "vocab"))
        x = _constrain(wte[tokens], "batch", "seq", "embed")
        for i in range(cfg.num_hidden_layers):
            x = _block(cfg)(cfg, layer_idx=i, name=f"block_{i}")(x)
        h = RMSNorm(cfg, name="norm_f")(x)
        chunk = cfg.ce_chunk or T

        mtp = cfg.num_nextn_predict_layers > 0
        if mtp and (targets is not None or self.is_initializing()):
            # init traces the module whatever the call, so that its leaves exist
            nxt = targets if targets is not None else tokens
            with jax.named_scope("mtp"):
                h_mtp = MtpModule(cfg, name="mtp_0")(h, wte[jnp.maximum(nxt, 0)])
                if targets is not None:
                    # position i predicts t_{i+2} = targets[i + 1]; the last has none
                    after = jnp.concatenate(
                        [targets[:, 1:], jnp.full((B, 1), -1, targets.dtype)], axis=1)
                    mtp_loss = token_loss_mean(
                        _chunked_token_ce(h_mtp, w_head, after, chunk, vocab_first=False), after)
                    self.sow("objective", "mtp", cfg.mtp_loss_weight * mtp_loss)
                    self.sow("metrics", "mtp_loss", mtp_loss)

        if targets is None:
            return _constrain(jnp.dot(h, w_head), "batch", "seq", "vocab")
        losses = _chunked_token_ce(h, w_head, targets, chunk, vocab_first=False)
        self.sow("metrics", "trunk_loss", token_loss_mean(losses, targets))
        return losses

    @staticmethod
    def book_step_counters(metrics: dict) -> dict:
        """What a caller that holds the model does with one step's returned
        ``metrics``: see the module's function of this name."""
        return book_step_counters(metrics)


# -- counters ---------------------------------------------------------------

_MOE_SUMS = ("assignments_here", "assignments_absent", "load_max_over_mean",
             "dropped", "extra_passes")


def step_counters(metrics: dict) -> dict:
    """One step's sown ``metrics`` collection (device arrays, already
    computed) as plain numbers by counter name: sums over the expert
    layers (``moe.load_max_over_mean`` is to be divided by
    ``moe.layer_steps``), the two losses, and the assignments that landed
    here layer by layer (trunk blocks in order, then the MTP module's)."""
    layers = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(metrics)[0]:
        keys = [getattr(k, "key", None) for k in path]
        name = next(k for k in reversed(keys) if k is not None)
        if name in _MOE_SUMS:
            layers.setdefault(tuple(keys[: keys.index(name)]), {})[name] = leaf
        elif name in ("mtp_loss", "trunk_loss"):
            layers.setdefault("losses", {})[f"train.{name}"] = float(leaf)
    out = dict(layers.pop("losses", {}))

    def in_order(scope):  # block_3/moe before mtp_0/block/moe
        return (1, 0) if scope[0].startswith("mtp") else (0, int(scope[0].rsplit("_", 1)[1]))

    scopes = sorted(layers, key=in_order)
    for name in _MOE_SUMS:
        kind = float if name == "load_max_over_mean" else int
        out[f"moe.{name}"] = sum(kind(layers[s][name]) for s in scopes)
    out["moe.layer_steps"] = len(scopes)
    out["moe.assignments_here_by_layer"] = [int(layers[s]["assignments_here"]) for s in scopes]
    return out


def decode_step_counters(metrics: dict, share: bool = False) -> dict:
    """The sown ``metrics`` of one decode step as device scalars by counter
    name, summed over the expert layers: what a server's jitted decode
    chunk returns beside its tokens, so that they reach the host in the
    read-back the chunk already has and are booked there
    (``ContinuousBatchingEngine``). Traceable: nothing here reads a value.
    ``share``, for a chip that holds a share of the experts: also the
    assignments that landed here and those routed to absent experts."""
    sums = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(metrics)[0]:
        name = next(k.key for k in reversed(path) if getattr(k, "key", None) is not None)
        sums.setdefault(name, []).append(leaf)
    layers = len(sums.get("assignments_here", ()))
    if not layers:
        return {}
    counters = {
        "moe.assignments": sum(sums["assignments_here"]).astype(jnp.int32),
        "moe.experts_touched": sum(sums["experts_touched"]).astype(jnp.int32),
        "moe.load_max_over_mean": sum(sums["load_max_over_mean"]).astype(jnp.float32),
        "moe.layer_steps": jnp.int32(layers),
    }
    if share:
        counters["moe.assignments_here"] = counters["moe.assignments"]
        counters["moe.assignments_absent"] = sum(sums["assignments_absent"]).astype(jnp.int32)
    return counters


def book_step_counters(metrics: dict) -> dict:
    """Book one step's counters into the process accumulator
    (``observability/spans.py``) and return them. Call it where the step
    is known to have ended (a sync), never between two dispatches: it
    reads device arrays."""
    from ..observability.spans import process_accumulator

    counters = step_counters(metrics)
    acc = process_accumulator()
    for name, value in counters.items():
        if not isinstance(value, list):
            acc.count(name, value)
    acc.count("train.steps_counted", 1)
    return counters
