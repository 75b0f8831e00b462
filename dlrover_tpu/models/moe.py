"""The routed-experts layer every family with experts runs
(:class:`MoeLayer`, built from :class:`MoeSizes`), and the counters that
read what it sows. Imports ``layers`` and ``ops/`` only.

**The chip's share.** ``experts_held`` / ``expert_offset`` say which of
the ``n_experts`` live here. The router stays full width, every token
still picks ``top_k`` of all of them, and the layer adds its own experts'
part plus the shared expert. Assignments to absent experts are counted and
skipped: what those experts would add is another chip's to compute, and
nothing here stands in for it or for the exchange. (``experts_held = 0``
holds them all.)

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

**What runs over the buffer** (PR 57: each is a pass a move needs; the
masks, gate selects and index gathers that computed nothing new are gone).
The router hands back the chosen experts and their gates, ``[N, K]`` each
(:func:`route`). One sort a layer orders the ``N x K`` assignments by expert
and carries each one's gate to its place (``sort_carrying``); a pass takes a
buffer of the sorted rows and sorts their tokens once more for the way back
(``row_order``). Then, over ``[rows, D]``: one gather in (``spread_rows``;
nothing zeroes the rows past the pass's load, which the grouped products
never visit), the three products, and on the way out one multiply by the
gates and one gather with a select (``collect_rows``, which also says in
what precision a row meets its gate: the rows past the load may hold
anything, NaN included, so they are selected away and never multiplied by
zero; the gates' own select is over ``[rows]`` floats and is what keeps a
NaN out of the router's gradient). The backward pass runs the same moves
transposed from the same index vectors.
"""

import functools
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import collect_rows, grouped_matmul, row_order, sort_carrying, spread_rows
from .layers import SwiGlu, param_with_axes, weight


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
        even). Moving rows costs by the buffer, whatever the load (a gather
        in, a multiply by the gates, a gather with its select out, and the
        same again transposed in the backward pass each run over all of it;
        only the grouped products skip its empty tiles), and a load past
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


def route(scores, bias, top_k: int, norm: bool, scale: float, eps: float = 0.0):
    """(expert ids ``[N, k]``, their gates ``[N, k]``) from float32 scores
    ``[N, E]``: the top k of ``scores + bias`` are chosen (``bias`` None:
    of the scores), and a chosen expert's gate is its *unbiased* score,
    over the chosen ones' sum (plus ``eps``) with ``norm``, times
    ``scale``. The chosen scores come from the selection itself: with no
    bias they are the values ``top_k`` already holds; under a bias each is
    read by a one-hot select and a max over ``E``, one term a choice and so
    exact, where ``values - bias[idx]`` would round (a max and not a sum:
    XLA merges a sum over ``E`` with the sum over the chosen behind it and
    adds the k scores in another order). Neither gathers ``[N, k]`` floats
    out of ``[N, E]``, which a TPU does an element at a time (1.34 ms at
    ``[16384, 64]`` on the v5e: PERF.md, PR 57), and the backward of the
    select is a select. That the gates are the former gather's bit for bit is
    shown on the CPU alone (``tests/test_mla_moe.py``): compiled for the
    chip, with a bias or with none, about half of them differ from it in
    the last place of their float32 (the sum and the quotient fuse
    otherwise), and all but 2 of 131,072 agree once rounded to bf16."""
    if bias is None:
        chosen, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + bias, top_k)
        picked = idx[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :]
        chosen = jnp.max(jnp.where(picked, scores[:, None, :], -jnp.inf), axis=-1)
    if norm:
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        chosen = chosen / (total + eps if eps else total)
    return idx, chosen * scale


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
        bias = None
        if cfg.bias_name:
            bias = jax.lax.stop_gradient(param_with_axes(
                cfg.bias_name, nn.initializers.normal(cfg.bias_init_std),
                (E,), jnp.float32, axes=(None,)))
        std = cfg.expert_init_std
        w_gate = weight("w_gate", cfg, (Eh, D, F), ("expert", "embed", "expert_mlp"), std)
        w_up = weight("w_up", cfg, (Eh, D, F), ("expert", "embed", "expert_mlp"), std)
        down_std = std
        if cfg.down_init_std:
            down_std = cfg.down_init_std * (std or cfg.init_std) / cfg.init_std
        w_down = weight("w_down", cfg, (Eh, F, D), ("expert", "expert_mlp", "embed"), down_std)

        with jax.named_scope("moe.route"):
            # float32 all the way: a score rounded to bf16 moves the top k
            logits = jnp.dot(xf.astype(jnp.float32), w_router,
                             precision=jax.lax.Precision.HIGHEST)
            scores = _SCORE_FNS[cfg.score_fn](logits)
            idx, gates = route(scores, bias, K, cfg.norm_topk, cfg.scale, cfg.norm_eps)
            if not cfg.train_gates:
                gates = jax.lax.stop_gradient(gates)

        with jax.named_scope("moe.dispatch"):
            local = idx - cfg.expert_offset
            held = (local >= 0) & (local < Eh)
            key = jnp.where(held, local, Eh).reshape(N * K)
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(Eh)[None, :], axis=0, dtype=jnp.int32)
            n_here = jnp.sum(group_sizes)
            # the layer's one sort by expert: held first, and every
            # assignment's gate carried to its place among the sorted rows
            order, gate_sorted = sort_carrying(key, gates.reshape(N * K))
            ends = jnp.cumsum(group_sizes)  # of each expert's group among the sorted rows
            mean_load = N * K * Eh / E
            rows = min(N * K, -(-int(cfg.buffer_over_mean * mean_load) // 8) * 8)  # whole sublanes
            firsts = range(0, N * K, rows)  # a pass takes the sorted rows [first, first + rows)
            valid = [jnp.clip(n_here - first, 0, rows) for first in firsts]

        def grouped(first, xf, gate_sorted):
            """One pass: the sorted rows from ``first`` on, a buffer of
            them, through the grouped products."""
            with jax.named_scope("moe.dispatch"):
                taken = order[first:first + rows]
                last = first + taken.shape[0]
                sizes = jnp.clip(ends, first, last) - jnp.clip(ends - group_sizes, first, last)
                n_valid = valid[first // rows]
                moves = row_order(taken // K, n_valid, N)
                xs = spread_rows(xf, moves)
            with jax.named_scope("moe.experts"):
                h = jax.nn.silu(grouped_matmul(xs, w_gate, sizes)) * (
                    grouped_matmul(xs, w_up, sizes))
                ys = grouped_matmul(h, w_down, sizes)
            with jax.named_scope("moe.combine"):
                return collect_rows(ys, moves, gate_sorted[first:last])

        def nothing(xf, gate_sorted):
            return jnp.zeros_like(xf)

        @jax.checkpoint  # a rare pass keeps nothing for the backward pass
        def overflow(xf, gate_sorted):
            """The rows past the first buffer, as many passes as they need."""
            out = grouped(firsts[1], xf, gate_sorted)
            for i in range(2, len(firsts)):
                out = out + jax.lax.cond(
                    valid[i] > 0, functools.partial(grouped, firsts[i]), nothing, xf, gate_sorted)
            return out

        routed = grouped(0, xf, gate_sorted)
        # (initialising wants the parameters, which the branch has none of, and tracing
        # a second pass costs a start ~0.3 s a layer: set-up is a bounded metric)
        if len(firsts) > 1 and not self.is_initializing():
            routed = routed + jax.lax.cond(valid[1] > 0, overflow, nothing, xf, gate_sorted)

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
