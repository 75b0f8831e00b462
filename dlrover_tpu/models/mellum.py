"""Window / full attention decoder with routed experts (the ``mellum``
layer equations), as one chip of an expert-parallel group trains it.

The config's keys are the published ones by their published names
(``layer_types``, ``sliding_window``, ``rope_parameters``, ``num_experts``
...), so a model's public ``config.json`` reads straight into
:class:`MellumConfig`. Width ``d``; no bias anywhere. Layer ``l``:
``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; one norm after
the last layer, then an untied head.

- **``Attn_l``**: ``q = x W_q`` in ``num_attention_heads`` heads, ``k`` and
  ``v`` in ``num_key_value_heads`` (query head ``h`` reads key/value head
  ``h // (H / KV)``), RoPE over the whole head in the rotate-half pairing
  (``layers.apply_rope``), scores over ``sqrt(head_dim)``, softmax, ``W_o``.
  What differs by ``layer_types[l]``:

  - ``sliding_attention``: row ``i`` sees key ``j`` iff ``0 <= i - j <
    sliding_window``; RoPE from ``rope_parameters["sliding_attention"]``.
    The flash kernel walks the band only (``ops/flash_attention.py``:
    ``window``), under the scope ``swa.attend_window``.
  - ``full_attention``: causal; RoPE from
    ``rope_parameters["full_attention"]`` (:func:`rope_table`: ``yarn``
    bends the slow channels and scales cos and sin), under
    ``swa.attend_full``.

- **``MoE``**: ``p = softmax(z W_r)`` in float32 over all ``num_experts``,
  the ``num_experts_per_tok`` largest, gates ``p`` of the chosen over their
  sum (``norm_topk_prob``), each expert a SwiGLU; no shared expert, no
  selection bias. It is ``moe.MoeLayer`` given this model's sizes;
  ``experts_held`` / ``expert_offset`` say which experts live here (the
  chip's share: what the absent ones would add is another chip's, counted
  and left out). On a share the gates are constants in the backward pass
  (``MoeSizes.train_gates``): through them the task loss would reach the
  router by the held experts' outputs alone, a partial sum of what the
  group's all-reduce gives it, and a router trained on that sum alone moves
  the chip's share of the assignments (measured: 9% to 33% of them from
  seed to seed at one init, 61-64% at another: PERF.md, PR 47). With no
  gradient and no bias nothing steers the selection, the load on the experts
  held stays near the mean (0.97-1.07 times it in every layer of a first
  step; past twice the mean in one layer-step of ~6,900: PERF.md, PR 48),
  and ``MoeLayer`` moves its rows through a buffer of twice the mean load
  and not of four times (``MoeSizes.buffer_over_mean``: at 16 of 64 held,
  half the assignments and not all of them; a load past it would take the
  layer's overflow pass, as anywhere). With all experts held the gates are
  trained and the buffer is every assignment, as before.

The embedding is drawn at ``EMBED_INIT_STD``, the matrices at ``init_std``:
with both at 0.02 the first attention layer writes about twice what the
embedding holds, nearly one vector for all the positions of a window, every
router reads that one direction and a row's tokens choose the same few
experts (the busiest of 16 held at 5-6 times their mean, whichever way the
router is trained: PERF.md, PR 47), so that what lands on a chip depends on
the batch and not on the tokens.

Grouped-query keys and values are repeated ``H / KV`` times for the kernel,
as ``llama.py`` and ``lfm2_moe.py`` do; a kernel that reads them through
its index map is queued in ROADMAP.md. The published family is described
with an MTP head that no config key sizes: it is not built.

With ``targets`` the model returns per-token losses (the fused-CE contract:
``models/build.py``) and sows its counters under ``"metrics"``, as ``mla_moe``.
"""

import math
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash_attention import flash_attention_sharded
from ..parallel.mesh import get_current_mesh
from .layers import (
    KEEP_FLASH_RESULTS, RMSNorm, apply_rope, chunked_token_ce, constrain, token_loss_mean, weight)
from .moe import MoeLayer, MoeSizes, book_step_counters

LAYER_TYPES = ("sliding_attention", "full_attention")
# ``torch.nn.Embedding``'s own default, N(0, 1), as ``models/qwen3_next.py``'s
# benchmark configuration draws it: a token's own vector decides its experts
EMBED_INIT_STD = 1.0
_PUBLISHED_ROPE = (
    ("full_attention", (
        ("attention_factor", 1.2772588722239782), ("beta_fast", 32), ("beta_slow", 1),
        ("factor", 16), ("original_max_position_embeddings", 8192),
        ("rope_theta", 500000), ("rope_type", "yarn"))),
    ("sliding_attention", (("rope_theta", 500000), ("rope_type", "default"))),
)


def _frozen(groups) -> Tuple:
    """A ``{layer type: {key: value}}`` group as sorted tuples: a config is
    a static argument, and a dict cannot be hashed."""
    if isinstance(groups, dict):
        return tuple(sorted((kind, tuple(sorted(g.items()))) for kind, g in groups.items()))
    return tuple(groups)


@dataclass(frozen=True)
class MellumConfig:
    # -- published keys ---------------------------------------------------
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # one entry a layer, or one period that is repeated: the published
    # model's is three ``sliding_attention`` then one ``full_attention``
    layer_types: Tuple[str, ...] = LAYER_TYPES[:1] * 3 + LAYER_TYPES[1:]
    mlp_layer_types: Tuple[str, ...] = ("sparse",)
    sliding_window: int = 1024
    rope_parameters: Any = _PUBLISHED_ROPE  # by layer type
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    use_sliding_window: bool = True
    hidden_act: str = "silu"
    # published and unused: the dense SwiGLU's width (every layer is sparse),
    # the longest context (RoPE keeps no table) and a key that is 0
    intermediate_size: int = 7168
    max_position_embeddings: int = 131072
    max_window_layers: int = 0
    # -- the chip's share of an expert-parallel group -----------------------
    experts_held: int = 0  # 0: all of them
    expert_offset: int = 0  # the first expert held
    # -- what the config does not state ---------------------------------------
    init_std: float = 0.02
    # -- how it is computed -----------------------------------------------------
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # each block recomputed in the backward pass from its input and its flash
    # kernel's two results, which are kept (``layers.KEEP_FLASH_RESULTS``)
    use_remat: bool = True
    ce_chunk: int = 0  # 0: the head's losses in one chunk

    takes_targets = True  # the counters are sown on the way to the losses

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mlp_layer_types", tuple(self.mlp_layer_types))
        object.__setattr__(self, "rope_parameters", _frozen(self.rope_parameters))
        unimplemented = dict(attention_bias=False, tie_word_embeddings=False, hidden_act="silu")
        for key, only in unimplemented.items():
            if getattr(self, key) != only:
                raise ValueError(f"only {key}={only!r} is implemented")
        if set(self.mlp_layer_types) != {"sparse"}:
            raise ValueError("only layers of routed experts (`sparse`) are implemented")
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)}; have {LAYER_TYPES}")
        if "sliding_attention" in self.layer_types and not (
                self.use_sliding_window and self.sliding_window > 0):
            raise ValueError("a sliding_attention layer needs use_sliding_window and a window")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key/value heads")
        if self.head_dim % 2:
            raise ValueError("a head is not whole pairs")
        for kind in set(self.layer_types):
            rope_table(8, self.head_dim, self.rope_of(kind))  # a key that is missing, now
        held = self.experts_held or self.num_experts
        if not 0 <= self.expert_offset <= self.num_experts - held:
            raise ValueError("the experts held do not lie inside the routed ones")

    @property
    def rms_eps(self) -> float:  # the name ``layers.RMSNorm`` reads
        return self.rms_norm_eps

    def layer_type(self, layer_idx: int) -> str:
        return self.layer_types[layer_idx % len(self.layer_types)]

    def rope_of(self, layer_type: str) -> dict:
        return dict(dict(self.rope_parameters)[layer_type])

    @property
    def moe_sizes(self) -> MoeSizes:
        return MoeSizes(
            n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, experts_held=self.experts_held,
            expert_offset=self.expert_offset, norm_topk=self.norm_topk_prob,
            score_fn="softmax", bias_name="", init_std=self.init_std,
            train_gates=(self.experts_held or self.num_experts) == self.num_experts,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    @staticmethod
    def tiny(**overrides) -> "MellumConfig":
        base = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16, use_remat=False,
        )
        base.update(overrides)
        return MellumConfig(**base)


def rope_inv_freq(head_dim: int, rope: dict):
    """(the ``head_dim / 2`` channel frequencies, the factor on cos and sin)
    of one ``rope_parameters`` group. ``default``: ``f_i = theta^(-2i/d)``.
    ``yarn``: the channels that turn fewer than ``beta_slow`` times over the
    original length are slowed by ``factor``, those that turn more than
    ``beta_fast`` times are left, and a linear ramp over the channel index
    joins the two: with ``c(r) = d ln(L / (2 pi r)) / (2 ln theta)``, ``low
    = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``ramp_i =
    clip((i - low) / (high - low), 0, 1)`` and ``inv_freq_i = (f_i / factor)
    ramp_i + f_i (1 - ramp_i)``; cos and sin times ``attention_factor``
    (``0.1 ln(factor) + 1`` where the group does not state it). Applied at
    every length, as the public implementation of ``rope_type: yarn`` does."""
    d, theta = head_dim, float(rope["rope_theta"])
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not implemented")
    factor, length = float(rope["factor"]), rope["original_max_position_embeddings"]

    def channel(turns):  # the channel that turns ``turns`` times over ``length``
        return d * math.log(length / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(channel(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(channel(rope.get("beta_slow", 1))), d - 1)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return f / factor * ramp + f * (1.0 - ramp), float(scale)


def rope_table(seq_len: int, head_dim: int, rope: dict):
    """(cos, sin) ``[seq_len, head_dim / 2]`` for ``layers.apply_rope``."""
    inv_freq, scale = rope_inv_freq(head_dim, rope)
    angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv_freq)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


class Attention(nn.Module):
    config: MellumConfig
    layer_type: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, D = x.shape
        H, KV, Hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        w_q = weight("w_q", cfg, (D, H, Hd), ("embed", "heads", "kv"))
        w_k = weight("w_k", cfg, (D, KV, Hd), ("embed", "kv_heads", "kv"))
        w_v = weight("w_v", cfg, (D, KV, Hd), ("embed", "kv_heads", "kv"))
        w_o = weight("w_o", cfg, (H, Hd, D), ("heads", "kv", "embed"))

        cos, sin = rope_table(T, Hd, cfg.rope_of(self.layer_type))
        q = apply_rope(jnp.einsum("btd,dhk->bthk", x, w_q), cos, sin)
        k = apply_rope(jnp.einsum("btd,dgk->btgk", x, w_k), cos, sin)
        v = jnp.einsum("btd,dgk->btgk", x, w_v)
        # the kernel takes equal head counts: each key/value head 8 times
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        q = constrain(q, "batch", "seq", "heads", "kv")
        k = constrain(k, "batch", "seq", "heads", "kv")
        v = constrain(v, "batch", "seq", "heads", "kv")
        windowed = self.layer_type == "sliding_attention"
        # a device event says which kind of layer its kernel belongs to
        with jax.named_scope("swa.attend_window" if windowed else "swa.attend_full"):
            out = flash_attention_sharded(
                q, k, v, get_current_mesh(), causal=True,
                window=cfg.sliding_window if windowed else None)
        out = constrain(out, "batch", "seq", "heads", "kv")
        return constrain(jnp.einsum("bthk,hkd->btd", out, w_o), "batch", "seq", "embed")


class Block(nn.Module):
    config: MellumConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        attn = Attention(cfg, cfg.layer_type(self.layer_idx), name="attn")
        with jax.named_scope("swa.attn"):  # norm and projections, swa.attend_* inside
            x = x + attn(RMSNorm(cfg, name="norm_attn")(x))
        y = MoeLayer(cfg.moe_sizes, name="moe")(RMSNorm(cfg, name="norm_mlp")(x))
        return constrain(x + y, "batch", "seq", "embed")


def _block(cfg: MellumConfig):
    if not cfg.use_remat:
        return Block
    # as ``mla_moe._block``: recomputed from its input, except what its flash
    # kernel wrote (tokens x heads x (2 x head_dim + 4) bytes a block)
    return nn.remat(Block, prevent_cse=True, policy=KEEP_FLASH_RESULTS)


class MellumLM(nn.Module):
    """``__call__(tokens[B, T]) -> logits[B, T, V]``; with ``targets``
    (``targets[:, i]`` is the token after ``tokens[:, i]``) the per-token
    losses ``[B, T]``."""

    config: MellumConfig

    @nn.compact
    def __call__(self, tokens, *, targets=None):
        cfg = self.config
        wte = weight("wte", cfg, (cfg.vocab_size, cfg.hidden_size),
                     ("vocab", "embed"), EMBED_INIT_STD)
        w_head = weight("lm_head", cfg, (cfg.hidden_size, cfg.vocab_size),
                        ("embed", "vocab"))
        with jax.named_scope("swa.embed"):
            x = constrain(wte[tokens], "batch", "seq", "embed")
        for i in range(cfg.num_hidden_layers):
            x = _block(cfg)(cfg, layer_idx=i, name=f"block_{i}")(x)
        with jax.named_scope("swa.head"):
            h = RMSNorm(cfg, name="norm_f")(x)
            if targets is None:
                return constrain(jnp.dot(h, w_head), "batch", "seq", "vocab")
            losses = chunked_token_ce(
                h, w_head, targets, cfg.ce_chunk or tokens.shape[1], vocab_first=False)
            self.sow("metrics", "trunk_loss", token_loss_mean(losses, targets))
            return losses

    @staticmethod
    def book_step_counters(metrics: dict) -> dict:
        """``moe.book_step_counters`` (the contract: ``models/build.py``)."""
        return book_step_counters(metrics)
