"""Continuous batching over the generation engine, TPU-first.

The reference serves RL rollouts through vLLM (continuous batching +
paged KV — examples/unified/rl/openrlhf/ppo/main.py:26-60). This
module is that capability over the repo's own engine: a request-queue
scheduler that admits new prompts into freed batch slots while other
rows keep decoding, so a rollout role serving a mixed-length prompt
stream does not pay worst-case padding in every batch
(VERDICT r4 #5).

TPU shape — every device program is static-shape and compiled once:

- **Slot admission.** A new request's prompt is prefilled into a fresh
  single-row cache at slots ``[0, W)`` (W = the smallest width bucket
  that fits it, at most Pw) and the whole row is inserted into the
  batch cache; positions count only valid slots, so RoPE/posembs never
  see pad holes.
- **Decode runs in chunks**: a ``lax.scan`` of ``decode_chunk`` steps
  per scheduler iteration, so the host pays one dispatch + one result
  fetch per chunk, not per token.
- **One write rule, two cache layouts** (``cache_layout=``): every row
  writes at its OWN next slot (a B-row scatter —
  layers._update_decode_cache ``cache_slots`` mode), so there are no holes
  past a prompt's bucket and a freed slot is reused in place. Liveness
  is per-request: ``prompt_width + max_new_tokens <= max_seq_len``.

  - ``"per_row"`` (the default, and ``tpurun-serve``'s): a static
    dense ``[B, L]`` cache.
  - ``"paged"``: the full vLLM-style serving memory (models/
    kv_blocks.py); refused at construction for a model with state
    leaves (below). The cache is a pool of fixed-size token blocks;
    each slot carries a block TABLE, the decode chunk gathers the
    dense view by table, runs the SAME step body (bit-exact
    by construction), and scatters back. Admission is bounded by free
    BLOCKS (a short request reserves its bucket + cap, not a whole
    [L] row), a registered prefix's fully-covered blocks are
    refcounted and shared copy-on-write across every row using it,
    and an out-of-blocks burst queues (bounded) instead of OOMing.

- **Two kinds of cache leaf.** The model's ``"cache"`` collection holds
  positional leaves ``[B, L, ...]`` (keys and values, hidden from
  attention by ``kv_valid`` where a slot is padding) and may hold
  per-request *state* leaves ``[B, ...]`` with no position axis (a short
  convolution's last inputs); ``model.cache_state_leaves`` says which,
  by name. Every program here moves a row's leaves by their first axis
  alone (prefill into a fresh one-row cache, the insert at admission,
  a registered prefix's stored row, the hand-off payload), so a state
  travels with its row: ``admit`` replaces a slot's state whole, a done
  or empty row's state may hold anything. What padding means to a state
  is the model's to keep: prompts are LEFT-padded and a prefix
  continuation leaves pad holes between prefix and suffix, so the model
  reads ``kv_valid`` at the slots a call writes and lets only real
  tokens move its state (``models/lfm2_moe.py``; for a recurrent state
  that every token rewrites whole, ``models/granite_hybrid.py``: a step
  size of exactly 0 at a padded token). ``stats()`` reports
  ``cache_bytes_positional`` and ``cache_bytes_state``, and counts
  ``prefill_tokens_real`` against ``prefill_tokens_padded`` at admission,
  ``prefill_tiled_calls`` beside them (admissions whose call was too wide
  for an attention layer to hold its scores: ``layers.prefill_is_tiled``),
  and at each chunk's read-back ``kv_positions_valid`` (the rows' real
  lengths at the steps that emitted their tokens) against
  ``kv_positions_held`` (``slots x max_seq_len`` a step: what a step's
  attention reads against what it needs).
- **A model decoded a block at a time** (``model.decode_blocks()``:
  generation by diffusion over blocks, ``models/sdar_moe.py``) gets the
  *block chunk* in the decode chunk's place (``make_block_chunk``): a row
  holds a block of ``block_length`` positions, some decided; a pass runs
  the model over the whole block at the row's next ``block_length`` slots
  and fixes the most confident of the undecided positions, or, where none
  is undecided, makes the block final: only then do its slots become
  valid, its tokens leave, the row's budget and write slot move. A pass
  yields several tokens of a row or none, and rows stand at different
  passes of their blocks. ``docs/generation.md`` has the carry and what is
  refused for such a model (the paged layout, prefixes, the hand-off, a
  tiled prefill).
- **Weight hot-swap between chunks**: ``set_params`` replaces the
  parameter argument of the jitted programs (same shapes — no
  recompile), so a WeightBus push lands at the next chunk boundary;
  ``swap_latency_s`` of the last swap is recorded.
- **The programs get the parameters as the model consumes them**: the
  engine rounds each matrix to the model's compute dtype once per
  weight version (construction, each swap: the ``serve.params_cast``
  span, ``params_casts`` / ``params_device_bytes`` in ``stats()``) and
  holds that tree, not the float32 one it was given; no program rounds
  a matrix again, and the arithmetic asked of them is the float32
  tree's (bit for bit under test on the CPU).
- **Overlapped (double-buffered) round** (``overlap=True``, the
  default): each ``step()`` dispatches chunk N+1 *before* it syncs and
  retires chunk N, so the device queue never drains between rounds and
  the host's emission/retirement/admission work runs while the next
  chunk executes. Per-row stop enforcement lives ON THE DEVICE for
  this (cap counters + done-masking inside the jitted chunk fn): a row
  that hits its cap or EOS mid-flight is silenced by the device state
  itself, so the one-chunk lag between device progress and host
  bookkeeping can neither over-emit nor corrupt KV. The host sees a
  one-chunk emission latency; greedy streams are bit-identical with
  the synchronous round (``overlap=False``, the tests' reference).
  Weight swaps adopt only at a drained pipeline (no chunk in flight),
  so a push can never split a round between parameter versions.
  Host time hidden behind in-flight chunks is stamped as the
  ``overlap_hidden`` phase (attribution.phases). Every round names
  its own time through ``self.phases.span``: one call writes the span
  onto the profiler's clock (``serve.round`` > ``serve.admission``,
  ``serve.prefill``, ``serve.decode_dispatch``, ``serve.host_sync``,
  ``serve.retirement``) and books it under its phase for ``/healthz``.
"""

import contextlib
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..attribution.phases import PhaseAccumulator
from ..attribution.recovery import startup_summary
from ..chaos import faults
from ..observability.spans import span
from . import kv_blocks
from .layers import prefill_is_tiled
from .generation import (
    SamplingConfig,
    decode_apply,
    init_cache,
    left_pad_prompts,
    prefill_prompt,
    sample_logits,
    sample_step,
)

__all__ = [
    "Completion",
    "ContinuousBatchingEngine",
]


@dataclass
class Completion:
    uid: int
    tokens: List[int]
    logprobs: List[float]
    # a model decoded by blocks: the pass of its block at which each token
    # was fixed (0 .. denoising_steps - 1); None for every other model
    passes: Optional[List[int]] = None
    # per-request service metrics (host wall-clock)
    queue_s: float = 0.0  # submit → slot admission
    ttft_s: float = 0.0  # admission → first emitted token
    total_s: float = 0.0  # admission → retirement


def _tree_ready(tree) -> bool:
    """Non-blocking: every leaf of ``tree`` has finished computing /
    transferring (``Array.is_ready``). The one readiness poll shared
    by async weight adoption and the pipeline's zero-lag probe."""
    return all(
        leaf.is_ready()
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "is_ready")
    )


def _device_put_like(tree, like):
    """Enqueue ``tree`` to the device preserving ``like``'s per-leaf
    placement: a WeightBus push delivers HOST arrays, and a bare
    ``device_put`` would commit them to one device — collapsing
    tp/fsdp-sharded serving onto a single chip and forcing a
    recompile."""
    try:
        spec = jax.tree_util.tree_map(lambda x: x.sharding, like)
    except AttributeError:  # engine was built with host arrays
        spec = None
    return jax.device_put(tree, spec)


class _Block(NamedTuple):
    """A row's block in the block chunk's carry (``make_block_chunk``):
    ``[B, Bl]`` the tokens, which positions are decided (a flag, never
    ``tok == mask_token_id``: a prompt may hold that id), each decided
    one's log-probability and the pass that fixed it; ``[B]`` the passes
    left to the block and the position its emission starts from (what a
    prompt's tail brought into a first block is not emitted)."""

    tok: Any
    decided: Any
    logp: Any
    at_pass: Any
    left: Any
    emit_from: Any


def _fresh_block(blocks, rows: tuple) -> _Block:
    """Blocks with every position undecided, for ``rows`` (a shape) rows."""
    wide = rows + (blocks.block_length,)
    return _Block(
        jnp.zeros(wide, jnp.int32), jnp.zeros(wide, bool),
        jnp.zeros(wide, jnp.float32), jnp.zeros(wide, jnp.int32),
        jnp.full(rows, blocks.denoising_steps, jnp.int32),
        jnp.zeros(rows, jnp.int32),
    )


def _choose(logits, rng, s: SamplingConfig):
    """A token for every position of ``logits [B, Bl, V]`` (float32, as
    given: a row's disallowed tokens at -inf) and its log-probability under
    them, which is the position's confidence: the argmax at temperature 0,
    else a sample of the filtered logits (``generation.sample_logits``)."""
    B, Bl, V = logits.shape
    choice = sample_logits(
        logits.reshape(B * Bl, V), rng, s.temperature, s.top_k, s.top_p
    ).reshape(B, Bl)
    # (the chosen logit less the log-sum: no [B, Bl, V] of log-probabilities)
    chosen = jnp.take_along_axis(logits, choice[..., None], axis=-1)[..., 0]
    return choice, chosen - jax.nn.logsumexp(logits, axis=-1)


def _rank_by_confidence(logp, among):
    """``[B, Bl]``: each position's rank among the positions ``among`` of its
    row by ``logp``, the most confident 0; of two equally confident ones the
    lower position comes first."""
    at = jnp.arange(logp.shape[1])
    ahead = among[:, None, :] & (
        (logp[:, None, :] > logp[:, :, None])
        | ((logp[:, None, :] == logp[:, :, None])
           & (at[None, None, :] < at[None, :, None]))
    )  # [B, i, j]: j comes before i
    return jnp.sum(ahead, axis=2, dtype=jnp.int32)


def _block_final(undecided_in, undecided_out):
    """``[B]``: the blocks that this pass made final. A block is final at a
    pass that was *given* no undecided position, because only that pass
    wrote every position's keys and values from its final token; the pass
    that decides the last position saw ``mask_token_id`` there (and
    ``undecided_out``, what is left after it, is not what decides). The one
    place that says so: the benchmark's ``scratch-kept`` control swaps it."""
    del undecided_out
    return ~jnp.any(undecided_in, axis=1)


@dataclass
class _Slot:
    uid: int = -1  # -1 = empty
    prompt: List[int] = field(default_factory=list)
    emitted: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    passes: List[int] = field(default_factory=list)  # block decoding only
    finished: bool = False  # EOS seen (device done flag)
    cap: int = 0  # this request's max_new_tokens (<= engine budget)
    submit_t: float = 0.0
    admit_t: float = 0.0
    first_tok_t: float = 0.0


class ContinuousBatchingEngine:
    """Serve a stream of prompts through ``batch_size`` decode slots.

    ``submit(tokens)`` enqueues a request and returns its uid;
    ``run()`` drives the scheduler until queue and slots drain,
    returning ``Completion``s. Greedy output is token-exact with
    :func:`generation.build_generate_fn` on the same prompt — the
    keystone test (admission holes are invisible to the math).
    """

    def __init__(
        self,
        model,
        params,
        sampling: SamplingConfig,
        batch_size: int,
        prompt_width: int,
        decode_chunk: Optional[int] = None,
        mesh=None,
        rules=None,
        cache_layout: str = "per_row",
        overlap: bool = True,
        kv_block_size: int = 16,
        kv_pool_blocks: int = 0,
    ):
        """``params`` is the tree as a trainer or a checkpoint holds it
        (float32). The engine does not keep it: ``engine.params`` is the
        tree its programs get, each leaf in the dtype the model reads it
        in (``model.consumed_param_dtypes``: for ``GPT`` and ``Llama``
        the matrices, embeddings and MLP biases in ``cfg.dtype``, the
        norms and a router in float32), rounded once here and once per
        adopted swap (:meth:`_as_consumed`). The model rounds each of
        those leaves before every use anyway, so the arithmetic is the
        float32 tree's; a caller that wants the memory back drops its
        own reference to ``params``.

        With ``mesh`` (+ optional logical-axis ``rules``) every
        device program runs SPMD over it: pass params already placed in
        their trainer shardings (tp/fsdp) and the whole engine serves a
        model bigger than one chip — same scheduler, XLA inserts the
        decode collectives. The stream state rides the batch axis
        REPLICATED (serve-mesh convention: scale batch by running one
        engine per data shard; the mesh scales the MODEL), so use
        tp/fsdp axes only.

        ``cache_layout``:

        - ``"per_row"`` (default): every row writes at its OWN next
          slot of a dense ``[B, L]`` cache via a B-row scatter
          (``layers._update_decode_cache`` ``cache_slots`` mode). No holes
          past a request's prompt bucket; slots are reused in place, so
          a request's lifetime is bounded by its own prompt+budget, not
          by the stream's. Liveness is simply prompt_width +
          max_new_tokens <= max_seq_len.
        - ``"paged"``: per_row's write discipline over a BLOCK POOL
          (models/kv_blocks.py): ``kv_block_size`` tokens per block,
          ``kv_pool_blocks`` blocks total (0 = the dense equivalent,
          ``batch_size * L/bs + 1``; size it smaller to serve the same
          batch in less HBM). Each slot holds a block table; the chunk
          program gathers the dense view, runs the per_row step body
          unchanged, and scatters back — greedy streams are bit-exact
          with the dense layout. Admission allocates ``ceil((bucket
          + cap)/bs)`` blocks (bounded by free blocks, NOT free
          slots); a registered prefix's fully-covered blocks are
          shared refcounted across rows (copy-on-write: decode writes
          start past the prefix, the partial tail block is the
          per-row copy), and idle prefix blocks evict LRU under pool
          pressure.

        ``overlap`` selects the double-buffered scheduler round (the
        default): chunk N+1 is dispatched before chunk N's results are
        synced, and the host's emission/retirement/admission runs while
        the device executes. ``overlap=False`` keeps the host-serial
        round (the reference the bit-identity tests hold the overlapped
        round to).
        """
        cfg = model.config
        L = cfg.max_seq_len
        if cache_layout not in ("per_row", "paged"):
            raise ValueError(
                f"cache_layout {cache_layout!r}: per_row | paged"
            )
        self.layout = cache_layout
        # which leaves of the model's cache are per-request state with no
        # position axis (the model says: ``cache_state_leaves``; a model
        # that says nothing has none)
        template = init_cache(model, 1)
        kinds_of = getattr(model, "cache_state_leaves", None)
        self._state_leaf = (
            kinds_of(template) if kinds_of is not None
            else jax.tree_util.tree_map(lambda _: False, template)
        )
        if cache_layout == "paged" and any(
            jax.tree_util.tree_leaves(self._state_leaf)
        ):
            raise ValueError(
                "cache_layout 'paged': this model keeps per-request state "
                "with no position axis beside its keys and values "
                "(model.cache_state_leaves), and a pool of token blocks "
                "holds only leaves shaped [row, position, ...]; serve it "
                "with cache_layout 'per_row'"
            )
        # a model decoded a block at a time says so (``decode_blocks``,
        # ``models/build.py``); None for every autoregressive one
        blocks_of = getattr(model, "decode_blocks", None)
        self.blocks = blocks_of() if blocks_of is not None else None
        if self.blocks is not None:
            Bl = self.blocks.block_length
            if cache_layout == "paged":
                raise ValueError(
                    "cache_layout 'paged': this model is decoded a block at "
                    "a time (model.decode_blocks), and the block pool's "
                    "chunk gathers and scatters a row for one token a "
                    "step; serve it with cache_layout 'per_row'"
                )
            # a first block may begin up to Bl - 1 tokens before the
            # prompt's end, and a last one end as many past the cap
            if prompt_width + sampling.max_new_tokens + 2 * (Bl - 1) > L:
                raise ValueError(
                    f"per_row liveness by blocks: prompt_width + "
                    f"max_new_tokens + 2 x (block_length - 1) = "
                    f"{prompt_width + sampling.max_new_tokens + 2 * (Bl - 1)}"
                    f" > max_seq_len {L}"
                )
        # liveness: each request lives in its own slots
        if prompt_width + sampling.max_new_tokens > L:
            raise ValueError(
                f"{cache_layout} liveness: prompt_width + "
                f"max_new_tokens = "
                f"{prompt_width + sampling.max_new_tokens} > "
                f"max_seq_len {L}"
            )
        if decode_chunk is None:
            # the default: 8 steps, and for a model decoded by blocks the
            # next whole number of blocks (S passes and one that makes the
            # block final, so 9 at S = 2). Rows admitted at a chunk's edge
            # then stand at the same pass of their blocks in every pass;
            # the benchmark's cell reads one rate either way and spreads
            # less over seeds at 9 (0.65% and 0.66% against 1.76% and
            # 1.05%: PERF.md section 6, PR 59). A length that is given is
            # taken as given: any is right.
            decode_chunk = 8
            if self.blocks is not None:
                per_block = self.blocks.denoising_steps + 1
                decode_chunk = -(-decode_chunk // per_block) * per_block
        self.model = model
        self.s = sampling
        self.mesh = mesh
        self.rules = rules
        self.B = batch_size
        self.Pw = prompt_width
        self.L = L
        self.d = decode_chunk
        self.overlap = bool(overlap)
        # double-buffer queue: chunks dispatched but not yet synced /
        # emitted. Each entry is (output futures..., done futures, the
        # per-slot uid snapshot AT DISPATCH — emission only credits a
        # slot whose uid still matches, so a cancel + re-admit during
        # the one-chunk lag can never leak another request's tokens).
        self._inflight: List[tuple] = []
        # tokens emitted by drains OUTSIDE a step (swap adoption):
        # folded into the next step()'s return so the per-call count
        # never silently drops a chunk
        self._drained_uncounted = 0
        self.params = self._as_consumed(params)
        # weight versions held so far: this one, then one a swap adopted
        self.params_casts = 1
        self.swap_latency_s: Optional[float] = None
        self._pending_params = None  # in-flight async weight swap
        self._pending_t0 = 0.0
        # A failed swap (device transfer error, poisoned payload) is
        # ABORTED, not served: the engine keeps the old weights, clears
        # the pending state so the pipeline never wedges waiting on a
        # transfer that will not land, and surfaces the failure here.
        self.swap_failures = 0
        self.last_swap_error: Optional[str] = None
        self._uid = 0
        # (uid, tokens, submit_t, cap, prefix_id)
        self._queue: List[tuple] = []
        self._slots = [_Slot() for _ in range(batch_size)]
        self._completions: List[Completion] = []
        # eager admission prefill (overlapped round): queued requests'
        # prompt rows computed WHILE a decode chunk is in flight, so
        # admission later pays only the cheap insert. Keyed by uid;
        # dropped on weight swap (stale-weight KV) and on cancel.
        self._prefilled: Dict[int, tuple] = {}
        # prefix caching: registered token lists + their lazily built
        # device row states (dropped on weight swap — stale KV would
        # silently serve the OLD model's prefix encoding)
        self._prefixes: Dict[int, List[int]] = {}
        self._prefix_states: Dict[int, tuple] = {}
        self._next_prefix_id = 0
        # host/device phase accounting: every scheduler round opens
        # serve.* spans (observability.spans: the profiler's clock) that
        # book under admission / prefill / decode_dispatch / host_sync /
        # retirement / overlap_hidden; attribution.phases reduces them
        # to serving_host_frac. The same accumulator counts requests,
        # their waits, chunks and row-steps where they happen.
        self.phases = PhaseAccumulator()
        # rolling completion-latency window: (retire_t, total_s,
        # emitted tokens) per finished request. Sized to smooth over
        # bursts while still tracking weight-swap / load regime changes
        # within a few hundred requests; feeds the p50/p95 + tokens/s
        # stats the fleet gateway routes on and the autoscaler scales on
        self._lat_window: deque = deque(maxlen=256)
        self.completed_total = 0
        # paged-layout accounting (zeroed-but-present in every layout
        # so stats()/healthz keys stay uniform across a mixed fleet)
        self.kv_block_size = int(kv_block_size)
        self.prefix_hits = 0
        self.alloc_failures = 0
        self.prefix_evictions = 0
        if cache_layout == "paged":
            bs = self.kv_block_size
            if bs < 1 or L % bs != 0:
                raise ValueError(
                    f"kv_block_size {bs} must divide max_seq_len {L}"
                )
            self._nb = L // bs  # block-table width (blocks per row)
            n = int(kv_pool_blocks) or batch_size * self._nb + 1
            worst = kv_blocks.blocks_for(
                self.Pw + sampling.max_new_tokens, bs
            )
            if worst > n - 1:
                raise ValueError(
                    f"kv_pool_blocks {n}: a worst-case request needs "
                    f"{worst} blocks but only {n - 1} are allocatable "
                    f"(block 0 is the trash block)"
                )
            self._pool = kv_blocks.BlockPool(n, bs)
            self._row_blocks: Dict[int, List[int]] = {}
            # pid -> shared block ids, LRU-ordered for idle eviction
            self._prefix_blocks: "OrderedDict[int, List[int]]" = (
                OrderedDict()
            )
        self._build_programs()
        self._reset_device_state()

    # -- device programs (compiled once each; the decode contract and
    # sampling live in generation.py — token-exactness with the
    # one-shot engine depends on sharing them, not mirroring them) ----

    def _build_programs(self):
        s, L = self.s, self.L
        model = self.model

        def prefill_row(params, toks, mask):
            """[1, W] prompt → (row cache, last logits, last pos,
            row kv_valid)."""
            cache, last_logits, last_pos, kv_valid = prefill_prompt(
                model, params, toks, mask
            )
            return cache, last_logits[0], last_pos[0], kv_valid[0]

        def continue_prefill_row(
            params, row_cache, toks, mask, row_kv, last_pos, start
        ):
            """Extend a stored prefix row cache with a LEFT-padded
            [1, W] suffix at slots [start, start+W) — prefix caching's
            device half. ``start`` (static: one compile per bucket
            pair) is the prefix's bucket width = the row cache's write
            index; positions continue the prefix's real-token count.
            The stored prefix cache is immutable — every admission
            derives a fresh row from it."""
            W = toks.shape[1]
            with jax.named_scope("serve.cache_write"):
                positions = last_pos + jnp.cumsum(
                    mask.astype(jnp.int32), axis=1
                )
                kvv = row_kv[None, :].at[:, start:start + W].set(mask)
            logits, cache = decode_apply(
                model, params, row_cache, toks, positions, kvv
            )
            with jax.named_scope("serve.sample"):
                return (
                    cache,
                    logits[0, -1].astype(jnp.float32),
                    positions[0, -1],
                    kvv[0],
                )

        def prefill_block_row(params, toks, mask, tail, n_tail):
            """A block model's admission: the prompt's WHOLE blocks
            ``[1, W]`` (left-padded) through the model under the mask by
            blocks, into a fresh row's slots ``[0, W)``; the ``n_tail``
            tokens that begin a block the prompt does not fill (``tail
            [Bl]``, padded) seat that block's first positions as decided.
            -> (row cache, the row's first block, the position its first
            block begins at, row kv_valid): a row as :func:`admit` takes
            it, the block where an autoregressive row has its last logits."""
            # (the prefill's logits are nobody's: the model returns one
            # position's and the compiler drops what computes them)
            cache, _, _, kv_valid = prefill_prompt(model, params, toks, mask)
            first = _fresh_block(self.blocks, ())._replace(
                tok=tail, decided=jnp.arange(tail.shape[0]) < n_tail,
                emit_from=n_tail,
            )
            return cache, first, jnp.sum(mask, dtype=jnp.int32), kv_valid[0]

        @jax.named_scope("serve.admit")  # the device scope of every copy below
        def admit(state, row_cache, row_logits, row_pos, row_kv,
                  row_allow, slot, next_slot, cap):
            """Insert a prefilled row at ``slot`` (traced — one compile
            covers every slot). The row's KV live at low slots and its
            own write slot restarts at ``next_slot`` = its prompt bucket
            width. ``cap`` arms the row's DEVICE-side emission budget:
            the chunk fn decrements it per emitted token and done-masks
            the row at zero, so cap enforcement cannot lag the device (the
            overlapped round's one-chunk window). ``row_logits`` is what
            the row's decoding starts from: its last logits, or for a
            model decoded by blocks its first block (a tree, leaf by leaf)."""
            (cache, kv_valid, last_logits, cur_pos, allow, budget, done,
             row_f) = state
            cache = ContinuousBatchingEngine._insert_row(
                cache, row_cache, slot
            )
            return (
                cache,
                kv_valid.at[slot].set(row_kv),
                jax.tree_util.tree_map(
                    lambda rows, row: rows.at[slot].set(row),
                    last_logits, row_logits,
                ),
                cur_pos.at[slot].set(row_pos),
                allow.at[slot].set(row_allow),
                budget.at[slot].set(cap),
                done.at[slot].set(False),
                row_f.at[slot].set(next_slot),
            )

        paged = self.layout == "paged"
        counters_of = getattr(model, "decode_step_counters", None)

        def make_decode_chunk(d: int):
            """Build the d-step decode program; returns stacked (toks,
            emits, logps) [d, B], the model's per-step counters [d]
            (``model.decode_step_counters``; ``{}`` without) and the
            advanced state. Each row
            writes at its own next slot (``cache_slots`` scatter);
            done/empty rows keep stepping on pad (static shapes) with
            their write slot parked clamped at L-1 — their kv bit and
            cache row are fully replaced at the next admission, so the
            parked writes are invisible. ``paged`` shares the step body
            with the dense layout (bit-exactness is structural, not
            re-proven): its state's cache element is ``(pool,
            tables)``, and the chunk gathers the dense [B, L] view by
            block table, steps it, and scatters the advanced view back
            — one dispatch per chunk either way. A retired slot's table
            is parked on the trash block, so its clamped writes can
            never touch a re-allocated block.

            Per-row stop enforcement is ON THE DEVICE: each row carries
            a remaining-emission budget (its request cap), decremented
            per emitted token; at zero the row is done-masked exactly
            like EOS. The host never needs to intervene to stop a row,
            which is what makes dispatching chunk N+1 before reading
            chunk N safe — a capped row cannot emit past its cap or
            consume liveness headroom during the lag window."""

            def chunk(params, state, rng):
                if paged:
                    (pool, tables) = state[0]
                    with jax.named_scope("serve.cache_write"):
                        state = (
                            kv_blocks.gather_cache(pool, tables), *state[1:]
                        )

                def step(carry, t):
                    (cache, kv_valid, last_logits, cur_pos, allow,
                     budget, done, row_f, rng) = carry
                    # device scopes (``jax.named_scope``: HLO metadata
                    # only) name the engine's share of a step; the
                    # model's call stays under the model's own
                    with jax.named_scope("serve.sample"):
                        rng, sub = jax.random.split(rng)
                        # per-request constrained decoding (RL action
                        # spaces): sampling AND behavior logprobs come from
                        # the masked distribution — what the policy can
                        # actually emit. An all-True row is a no-op.
                        tok, emit, tok_logp, done = sample_step(
                            jnp.where(allow, last_logits, -jnp.inf), done,
                            sub, s,
                        )
                        # device-side cap: the token that exhausts the
                        # budget is still emitted (host parity: emit while
                        # count < cap), then the row is done
                        emit = emit & (budget > 0)
                        budget = budget - emit.astype(jnp.int32)
                        done = done | (budget <= 0)
                    with jax.named_scope("serve.cache_write"):
                        write_slots = jnp.minimum(row_f, L - 1)
                        slot_hits = (
                            jnp.arange(L)[None, :] == write_slots[:, None]
                        )
                        row_f = row_f + 1
                        kv_valid = kv_valid | slot_hits
                        pos = cur_pos + 1
                    logits, cache, sown = decode_apply(
                        model, params, cache, tok[:, None], pos[:, None],
                        kv_valid, cache_slots=write_slots, metrics=True,
                    )
                    # what the model counted in this step (a routed
                    # layer's load), as device scalars: stacked by the
                    # scan, read back with the tokens, booked on the host
                    with jax.named_scope("serve.counters"):
                        counters = counters_of(sown) if counters_of else {}
                    with jax.named_scope("serve.sample"):  # what the next step samples from
                        next_logits = logits[:, 0].astype(jnp.float32)
                    return (
                        cache,
                        kv_valid,
                        next_logits,
                        pos,
                        allow,
                        budget,
                        done,
                        row_f,
                        rng,
                    ), (tok, emit, tok_logp, counters)

                carry, out = jax.lax.scan(
                    step, (*state, rng), jnp.arange(d)
                )
                new_state = carry[:-1]
                if paged:
                    with jax.named_scope("serve.cache_write"):
                        new_state = (
                            (
                                kv_blocks.scatter_cache(
                                    pool, tables, new_state[0]
                                ),
                                tables,
                            ),
                            *new_state[1:],
                        )
                return new_state, out

            return chunk

        def make_block_chunk(d: int):
            """The chunk of a model decoded a block at a time: ``d``
            passes. The state is the decode chunk's with the row's block
            (:class:`_Block`) where that has ``last_logits``, and
            ``cur_pos`` the position the block begins at. **One pass**:
            every row's block goes through the model at the row's next
            ``Bl`` slots (``cache_slots``: keys and values written at
            ``[row_f, row_f + Bl)``, valid for this row in this pass,
            nothing past them), ``mask_token_id`` where a position is
            undecided. Then by row: **no position was undecided** -> the
            pass wrote the final tokens' keys and values and the block is
            final: its slots stay valid, ``row_f`` and the position move on
            by ``Bl``, its tokens are emitted (a first block: what the
            prompt did not bring; a last one: up to the budget; up to an
            EOS), a fresh block starts. **Else** the ``ceil(undecided /
            passes left)`` most confident of the undecided positions are
            fixed (a tie: the lowest position), each with its
            log-probability and the pass's number; what this pass wrote is
            scratch that the next pass overwrites. So a block takes
            ``denoising_steps`` passes and one more (a final pass carried
            with the next block's first is ``ROADMAP.md``'s). Returns
            stacked (toks, emits, logps) ``[d x Bl, B]`` (a pass's
            positions in order: what the host's emission reads a token a
            step), the counters ``[d]`` (the model's, and ``block.*`` and
            ``kv_positions_valid``, summed over live rows), the passes at
            which the tokens were fixed ``[d x Bl, B]``, and the advanced
            state. Done and empty rows keep stepping with their block
            parked at the row's last slots, as the decode chunk's do."""
            Bl, S, mask_id = self.blocks
            at = jnp.arange(Bl)

            def chunk(params, state, rng):
                def one_pass(carry, _):
                    (cache, kv_valid, blk, base_pos, allow, budget, done,
                     row_f, rng) = carry
                    with jax.named_scope("serve.cache_write"):
                        live = ~done
                        write_slots = jnp.minimum(row_f, L - Bl)
                        own = jnp.arange(L)[None, :] - write_slots[:, None]
                        kv_pass = kv_valid | ((own >= 0) & (own < Bl))
                    logits, cache, sown = decode_apply(
                        model, params, cache,
                        jnp.where(blk.decided, blk.tok, mask_id),
                        base_pos[:, None] + at[None, :], kv_pass,
                        cache_slots=write_slots, metrics=True,
                    )
                    with jax.named_scope("serve.sample"):
                        rng, sub = jax.random.split(rng)
                        choice, choice_logp = _choose(
                            jnp.where(
                                allow[:, None, :], logits.astype(jnp.float32),
                                -jnp.inf,
                            ), sub, s,
                        )
                        undecided = ~blk.decided
                        n_undecided = jnp.sum(undecided, axis=1, dtype=jnp.int32)
                        n_fix = -(-n_undecided // jnp.maximum(blk.left, 1))
                        newly = undecided & (
                            _rank_by_confidence(choice_logp, undecided)
                            < n_fix[:, None]
                        )
                        tok = jnp.where(newly, choice, blk.tok)
                        logp = jnp.where(newly, choice_logp, blk.logp)
                        at_pass = jnp.where(
                            newly, (S - blk.left)[:, None], blk.at_pass
                        )
                        decided = blk.decided | newly
                        final = live & _block_final(undecided, ~decided)
                        # what a final block emits
                        emit = (
                            final[:, None]
                            & (at[None, :] >= blk.emit_from[:, None])
                            & (at[None, :] - blk.emit_from[:, None]
                               < budget[:, None])
                        )
                        if s.eos_id >= 0:  # the EOS is kept, nothing after it
                            eos = emit & (tok == s.eos_id)
                            emit = emit & (
                                jnp.cumsum(eos, axis=1) - eos.astype(jnp.int32)
                                == 0
                            )
                            done = done | jnp.any(emit & eos, axis=1)
                        budget = budget - jnp.sum(emit, axis=1, dtype=jnp.int32)
                        done = done | (final & (budget <= 0))
                    with jax.named_scope("serve.counters"):
                        counters = dict(
                            counters_of(sown) if counters_of else {},
                            **{
                                "block.row_passes": jnp.sum(live, dtype=jnp.int32),
                                "block.commit_row_passes": jnp.sum(
                                    live & (n_undecided == 0), dtype=jnp.int32),
                                "block.tokens_fixed": jnp.sum(
                                    newly & live[:, None], dtype=jnp.int32),
                                "block.blocks_final": jnp.sum(
                                    final, dtype=jnp.int32),
                                "block.positions_undecided_in": jnp.sum(
                                    undecided & live[:, None], dtype=jnp.int32),
                                "kv_positions_valid": jnp.sum(
                                    kv_pass & live[:, None], dtype=jnp.int32),
                            },
                        )
                    with jax.named_scope("serve.cache_write"):
                        fresh = _fresh_block(self.blocks, (tok.shape[0],))
                        blk = jax.tree_util.tree_map(
                            lambda new, old: jnp.where(
                                final.reshape((-1,) + (1,) * (old.ndim - 1)),
                                new, old,
                            ),
                            fresh,
                            _Block(tok, decided, logp, at_pass,
                                   blk.left - (n_undecided > 0), blk.emit_from),
                        )
                        step = jnp.where(final, Bl, 0)
                    return (
                        cache,
                        jnp.where(final[:, None], kv_pass, kv_valid),
                        blk,
                        base_pos + step,
                        allow,
                        budget,
                        done,
                        row_f + step,
                        rng,
                    ), (tok.T, emit.T, logp.T, counters, at_pass.T)

                carry, (toks, emits, logps, counters, passes) = jax.lax.scan(
                    one_pass, (*state, rng), None, length=d
                )
                flat = lambda a: a.reshape((d * Bl,) + a.shape[2:])  # noqa: E731
                return carry[:-1], (
                    flat(toks), flat(emits), flat(logps), counters,
                    flat(passes),
                )

            return chunk

        def admit_many(state, rows, slots, next_slots, caps):
            """Burst admission: K row inserts in ONE dispatch. A wave
            of slots tends to retire together (equal caps), so the
            scheduler frequently admits K rows back-to-back — K
            separate admit calls cost K jit dispatches of the full
            batch state (~1 ms each on CPU), the dominant host-serial
            cost left in the overlapped round. Row shapes are
            width-independent ([1, L] caches), so jax re-traces only
            per distinct K (at most B traces)."""
            for row, slot, nxt, cap in zip(rows, slots, next_slots,
                                           caps):
                state = admit(state, *row, slot, nxt, cap)
            return state

        @jax.named_scope("serve.admit")
        def paged_admit(state, row_cache, row_logits, row_pos, row_kv,
                        row_allow, slot, next_slot, cap, table_row):
            """Paged-layout insert: scatter the prefilled [1, L] row
            into ITS freshly planned blocks (``table_row``, trash-
            padded past its coverage) and point the slot's table at
            them. Shared prefix blocks in the table receive the row's
            prefix values — bitwise identical to every other sharer's
            (all derive from the one stored prefix state), so the
            overwrite is a semantic no-op and COW needs no masking."""
            (pg, kv_valid, last_logits, cur_pos, allow, budget, done,
             row_f) = state
            pool, tables = pg
            pool = kv_blocks.scatter_row(pool, table_row, row_cache)
            tables = tables.at[slot].set(table_row)
            return (
                (pool, tables),
                kv_valid.at[slot].set(row_kv),
                last_logits.at[slot].set(row_logits),
                cur_pos.at[slot].set(row_pos),
                allow.at[slot].set(row_allow),
                budget.at[slot].set(cap),
                done.at[slot].set(False),
                row_f.at[slot].set(next_slot),
            )

        def paged_admit_many(state, rows, slots, next_slots, caps,
                             table_rows):
            for row, slot, nxt, cap, tr in zip(
                rows, slots, next_slots, caps, table_rows
            ):
                state = paged_admit(state, *row, slot, nxt, cap, tr)
            return state

        self._prefill_fn = jax.jit(
            prefill_row if self.blocks is None else prefill_block_row
        )
        self._continue_fn = jax.jit(continue_prefill_row, static_argnums=6)
        if paged:
            self._admit_fn = jax.jit(paged_admit)
            self._admit_many_fn = jax.jit(paged_admit_many)
        else:
            self._admit_fn = jax.jit(admit)
            self._admit_many_fn = jax.jit(admit_many)
        # chunk programs are cached per d: each length is one compile
        self._chunk_src = (
            make_decode_chunk if self.blocks is None else make_block_chunk
        )
        self._chunk_fns: Dict[int, Callable] = {}

    _NULL_CTX = contextlib.nullcontext()

    def _ctx(self):
        """Mesh + logical-rule contexts around every device call in
        SPMD mode (sharding constraints resolve at trace time, the mesh
        must be active at call time); no-op single-device. On the hot
        path twice per round (admission + dispatch) — the no-op case
        must stay allocation-free."""
        if self.mesh is None:
            return self._NULL_CTX
        from ..parallel.mesh import current_mesh
        from ..parallel.sharding import apply_rules

        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(apply_rules(self.rules))
        stack.enter_context(current_mesh(self.mesh))
        return stack

    def _i32(self, v: int):
        """Cached device scalar: ``jnp.int32(v)`` dispatches a
        conversion op per call (~0.2 ms on CPU), and the scheduler
        passes the same few slot/width/cap values every round — host
        time the dispatch path does not need to pay."""
        cache = self.__dict__.setdefault("_i32_cache", {})
        arr = cache.get(v)
        if arr is None:
            arr = cache[v] = jnp.int32(v)
        return arr

    def _chunk_for(self, d: int) -> Callable:
        if d not in self._chunk_fns:
            self._chunk_fns[d] = jax.jit(self._chunk_src(d))
        return self._chunk_fns[d]

    def _reset_device_state(self):
        V = self.model.config.vocab_size
        if self.layout == "paged":
            # fresh pool: each dense cache leaf (B, L, ...) becomes
            # (num_blocks, block_size, ...); the 0-d write-index
            # scalars ride along unread (``cache_slots`` mode never
            # reads them). Host allocator and block tables restart
            # with it.
            bs = self.kv_block_size
            template = init_cache(self.model, 1)
            pool = jax.tree_util.tree_map(
                lambda leaf: (
                    leaf
                    if leaf.ndim == 0
                    else jnp.zeros(
                        (self._pool.num_blocks, bs) + leaf.shape[2:],
                        leaf.dtype,
                    )
                ),
                template,
            )
            tables = jnp.zeros((self.B, self._nb), jnp.int32)
            cache = (pool, tables)
            self._pool = kv_blocks.BlockPool(self._pool.num_blocks, bs)
            self._row_blocks.clear()
            self._prefix_blocks.clear()
        else:
            cache = init_cache(self.model, self.B)
        # bytes of the cache as the device holds it, by kind of leaf (a
        # paged pool has positional leaves only)
        positional = state = 0
        for leaf, is_state in zip(
            jax.tree_util.tree_leaves(cache[0] if self.layout == "paged" else cache),
            jax.tree_util.tree_leaves(self._state_leaf),
        ):
            if is_state:
                state += leaf.nbytes
            else:
                positional += leaf.nbytes
        self._cache_bytes = (positional, state)
        self._state = (
            cache,
            jnp.zeros((self.B, self.L), bool),
            # what a row decodes from: its last logits, or its block
            jnp.full((self.B, V), -1e9, jnp.float32) if self.blocks is None
            else _fresh_block(self.blocks, (self.B,)),
            jnp.zeros((self.B,), jnp.int32),
            jnp.ones((self.B, V), bool),  # per-row allowed-token mask
            jnp.zeros((self.B,), jnp.int32),  # per-row emission budget
            jnp.ones((self.B,), bool),  # empty slots: done (emit pad)
            jnp.zeros((self.B,), jnp.int32),  # per-row next write slot
        )

    # -- host scheduler -------------------------------------------------

    def register_prefix(self, tokens: List[int]) -> int:
        """Register a shared prompt prefix (system prompt). Requests
        submitted with the returned id prefill ONLY their suffix — the
        prefix's KV is computed once per weight version and reused for
        every admission (vLLM's prefix-caching capability). The device
        state is built lazily on first use, so registration is cheap
        and weight swaps just invalidate."""
        self._refuse_for_blocks("register_prefix")
        if not tokens:
            raise ValueError("empty prefix")
        # the STORED state occupies the prefix's bucket width — a
        # prefix whose bucket rounds up to Pw would register fine yet
        # reject every submit
        if self._bucket_width(len(tokens)) >= self.Pw:
            raise ValueError(
                f"prefix bucket width {self._bucket_width(len(tokens))} "
                f"leaves no room for a suffix within prompt_width "
                f"{self.Pw}"
            )
        pid = self._next_prefix_id
        self._next_prefix_id += 1
        self._prefixes[pid] = list(tokens)
        return pid

    def _prefix_state(self, pid: int) -> tuple:
        """(row cache, last logits, last pos, row kv_valid, bucket
        width) for a registered prefix at the CURRENT weights."""
        if pid not in self._prefix_states:
            prefix = self._prefixes[pid]
            self._prefix_states[pid] = self._prefill(prefix)
        return self._prefix_states[pid]

    def unregister_prefix(self, prefix_id: int) -> None:
        """Drop a registered prefix (the gateway's prefix-GC path).
        Refcount-aware: the registry's hold on the prefix's shared
        blocks is released, but blocks still referenced by live rows
        stay allocated until those rows retire. Refuses while QUEUED
        requests still reference the id (their admission would KeyError
        mid-flight); live decoding rows are fine — their KV was built
        at admission and never looks the prefix up again."""
        if prefix_id not in self._prefixes:
            raise KeyError(f"unknown prefix_id {prefix_id}")
        if any(item[4] == prefix_id for item in self._queue):
            raise ValueError(
                f"prefix_id {prefix_id} still referenced by queued "
                f"requests"
            )
        del self._prefixes[prefix_id]
        self._prefix_states.pop(prefix_id, None)
        if self.layout == "paged":
            ids = self._prefix_blocks.pop(prefix_id, None)
            if ids:
                self._pool.free(ids)

    # -- prefill/decode disaggregation ---------------------------------

    def export_prefill(self, tokens: List[int]) -> Dict:
        """PREFILL-role half of disaggregation: run the prompt's
        prefill here and return the row as a JSON-safe hand-off
        payload (see :func:`kv_blocks.pack_row_state`). The decode
        replica admits it via :meth:`submit_prefilled` and pays only
        the insert — long prompts stop stalling its decode rounds. The
        payload carries every leaf of the row's cache, per-request
        state leaves included (they are leaves like the others, checked
        by shape on arrival)."""
        self._refuse_for_blocks("export_prefill")
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) > self.Pw:
            raise ValueError(
                f"prompt length {len(tokens)} > prompt_width {self.Pw}"
            )
        *row, width = self._prefill(tokens)
        return kv_blocks.pack_row_state(*jax.device_get(row), width, tokens)

    def submit_prefilled(
        self,
        payload: Dict,
        max_new_tokens: Optional[int] = None,
        allowed_tokens: Optional[List[int]] = None,
    ) -> int:
        """DECODE-role half of disaggregation: enqueue a request whose
        prefill already ran on a prefill replica. The payload is shape-
        validated against THIS engine's cache template (mismatched
        model config → ValueError, never a corrupt row) and staged in
        ``self._prefilled`` — admission pays only the insert program.
        A weight swap between staging and admission clears the staged
        row and the request gracefully RE-prefills from its prompt
        tokens at the new weights (the payload carries them)."""
        self._refuse_for_blocks("submit_prefilled")
        (row_cache, row_logits, row_pos, row_kv, width, prompt) = (
            kv_blocks.unpack_row_state(
                payload, init_cache(self.model, 1)
            )
        )
        if width > self.Pw or width != self._bucket_width(len(prompt)):
            raise ValueError(
                f"handoff width {width} inconsistent with prompt "
                f"length {len(prompt)} under prompt_width {self.Pw}"
            )
        uid = self.submit(
            prompt, max_new_tokens=max_new_tokens,
            allowed_tokens=allowed_tokens,
        )
        self._prefilled[uid] = (
            row_cache, row_logits, row_pos, row_kv, width
        )
        return uid

    def submit(
        self,
        tokens: List[int],
        max_new_tokens: Optional[int] = None,
        prefix_id: Optional[int] = None,
        allowed_tokens: Optional[List[int]] = None,
    ) -> int:
        """Enqueue a request. ``max_new_tokens`` caps THIS request
        below the engine budget (``sampling.max_new_tokens``, which
        sized the cache) — a capped request retires its slot early.
        With ``prefix_id``, ``tokens`` is the SUFFIX after that
        registered prefix; the combined length must still fit
        ``prompt_width`` (prefix caching saves prefill compute, not
        cache capacity). ``allowed_tokens`` constrains THIS request's
        sampling to the given token ids (RL action spaces / structured
        output): both the sampled tokens and the behavior logprobs
        come from the masked distribution."""
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(f"unknown prefix_id {prefix_id}")
            if not tokens:
                raise ValueError("prefix_id needs a non-empty suffix")
            # admission pads BOTH parts to bucket widths — the check
            # must bound the admitted row width, not the raw lengths
            # (a raw-length check admits rows wider than Pw, and
            # decode writes then silently corrupt the suffix KV)
            total = self._bucket_width(
                len(self._prefixes[prefix_id])
            ) + self._bucket_width(len(tokens))
            if total > self.Pw:
                raise ValueError(
                    f"prefix bucket + suffix bucket = {total} > "
                    f"prompt_width {self.Pw}"
                )
        elif len(tokens) > self.Pw:
            raise ValueError(
                f"prompt length {len(tokens)} > prompt_width {self.Pw}"
            )
        if self.blocks is not None and prefill_is_tiled(
            self._bucket_width(self._prefill_len(tokens)), self.L
        ):
            raise ValueError(
                f"prompt length {len(tokens)}: its prefill would attend "
                f"in tiles (layers.prefill_is_tiled), which know the "
                f"causal mask alone and not this model's mask by blocks"
            )
        cap = self.s.max_new_tokens
        if max_new_tokens is not None:
            if not 1 <= max_new_tokens <= cap:
                raise ValueError(
                    f"max_new_tokens {max_new_tokens} outside [1, {cap}] "
                    f"(the engine's cache budget)"
                )
            cap = max_new_tokens
        if allowed_tokens is not None:
            V = self.model.config.vocab_size
            allowed_tokens = sorted(set(int(t) for t in allowed_tokens))
            if not allowed_tokens:
                raise ValueError("allowed_tokens must not be empty")
            if allowed_tokens[0] < 0 or allowed_tokens[-1] >= V:
                raise ValueError(
                    f"allowed_tokens outside [0, {V})"
                )
        uid = self._uid
        self._uid += 1
        self._queue.append(
            (uid, list(tokens), time.perf_counter(), cap, prefix_id,
             allowed_tokens)
        )
        return uid

    def _refuse_for_blocks(self, what: str) -> None:
        """A stored prefix and a hand-off payload carry an autoregressive
        row's last logits; a row decoded by blocks starts from its first
        block, and its prefix would have to end on a block's edge."""
        if self.blocks is not None:
            raise ValueError(
                f"{what}: this model is decoded a block at a time "
                f"(model.decode_blocks), and a stored or handed-off row "
                f"carries the last logits of an autoregressive one"
            )

    def _prefill_len(self, prompt: List[int]) -> int:
        """How many of a prompt's tokens its prefill covers: all, or for a
        model decoded by blocks its whole blocks (the tail begins the first
        block the chunk decodes)."""
        if self.blocks is None:
            return len(prompt)
        return len(prompt) - len(prompt) % self.blocks.block_length

    def _prefill(self, prompt: List[int]) -> tuple:
        """(row cache, what the row decodes from, position, row kv_valid,
        bucket width) of a prompt: one call of the prefill program."""
        n = self._prefill_len(prompt)
        width = self._bucket_width(n)
        toks, mask = self._pad_rows([prompt[:n]], width)
        extra = ()
        if self.blocks is not None:
            tail = np.zeros(self.blocks.block_length, np.int32)
            tail[:len(prompt) - n] = prompt[n:]
            extra = (jnp.asarray(tail), self._i32(len(prompt) - n))
        with self._ctx():
            return (*self._prefill_fn(self.params, toks, mask, *extra), width)

    def _as_consumed(self, params, like=None):
        """The tree the programs get, from ``params`` as a trainer, a
        checkpoint or a WeightBus push holds them: each leaf in the dtype
        the model says it reads it in (``model.consumed_param_dtypes``:
        a float32 matrix that every use rounds to bf16 is rounded here,
        once per weight version), on ``like``'s leaf's sharding where
        ``like`` is given and on its own otherwise. The programs find the
        rounding they did in every call already done: the arithmetic
        asked of them is the float32 tree's (the CPU tests hold the
        outputs to it bit for bit; a compiler may still sum a product
        of a leaf that arrives rounded in another order). Leaf by leaf:
        the float32 copy of a host leaf goes as soon as its cast is
        enqueued, and nothing here keeps a leaf of ``params`` past the
        return. A leaf that already has its dtype is taken as it is; a
        model with no such method is served from ``params`` as given."""
        dtypes_of = getattr(self.model, "consumed_param_dtypes", None)
        if dtypes_of is None:
            return params if like is None else _device_put_like(params, like)
        cast = dict(leaves=0, bytes_in=0, bytes_out=0)

        def one(leaf, dt, like_leaf=None):
            sharding = getattr(like_leaf, "sharding", None)
            if sharding is not None or not isinstance(leaf, jax.Array):
                leaf = jax.device_put(leaf, sharding)
            if leaf.dtype == dt:
                return leaf
            cast["leaves"] += 1
            cast["bytes_in"] += leaf.nbytes
            cast["bytes_out"] += leaf.size * dt.itemsize
            return leaf.astype(dt)

        with span("serve.params_cast") as sp:
            held = jax.tree_util.tree_map(
                one, params, dtypes_of(params),
                *(() if like is None else (like,)),
            )
            sp.set(**cast)
        return held

    def set_params(self, params) -> float:
        """Hot-swap weights between chunks (same pytree shapes — no
        recompile). Returns the swap latency: the time to make the new
        params device-resident and adopted for the next chunk. Blocks
        the caller for the full H2D transfer — use
        :meth:`set_params_async` to hide the transfer behind ongoing
        decode instead."""
        self.set_params_async(params)
        jax.block_until_ready(self._pending_params)
        self._maybe_adopt_pending()
        return self.swap_latency_s

    def set_params_async(self, params) -> None:
        """Begin a NON-blocking weight swap: ``jax.device_put`` only
        enqueues the H2D transfer, so it proceeds behind ongoing decode
        chunks, and the engine adopts the new weights at the first
        ``step()`` boundary where every leaf has landed — a WeightBus
        push never stalls the rollout loop (blocking for the whole
        transfer mid-decode is the exact stall this avoids; its
        duration on the v5e: PR 31, docs/generation.md). The payload is
        rounded to the held dtypes as it lands (:meth:`_as_consumed`), so
        "landed" means the rounded tree is ready. A second call
        before adoption supersedes the first (latest weights win).

        A transfer that fails to even enqueue (mismatched payload, a
        dead device) ABORTS the swap: the engine keeps serving the old
        weights, ``swap_pending`` clears, and the failure is surfaced
        via :meth:`stats` — a poisoned push must cost one swap, never
        the serving pipeline."""
        self._pending_t0 = time.perf_counter()
        try:
            faults.inject("serving.swap")
            self._pending_params = self._as_consumed(params, self.params)
        except Exception as e:  # noqa: BLE001 — swap aborted, not served
            self._abort_pending_swap(e)

    def _abort_pending_swap(self, err: BaseException) -> None:
        """Drop an in-flight swap and keep the current weights."""
        self._pending_params = None
        self.swap_failures += 1
        self.last_swap_error = repr(err)[:300]
        from ..common.log import logger

        logger.error("weight swap aborted (serving old weights): %r", err)

    def _maybe_adopt_pending(self) -> bool:
        """Adopt a pending async swap if the transfer has completed —
        checked without blocking (``Array.is_ready``). In the
        overlapped scheduler, adoption first DRAINS the pipeline
        (processes any in-flight chunk): the swap lands at a point
        where host bookkeeping matches device state, so no round is
        ever split between parameter versions — the pipeline's drain
        point is the only adoption boundary. An async transfer that
        FAILED in flight (readiness probe raises) aborts the swap: old
        weights stay live, the pipeline keeps stepping."""
        pending = self._pending_params
        if pending is None:
            return False
        try:
            if not _tree_ready(pending):
                return False
        except Exception as e:  # noqa: BLE001 — failed transfer
            self._abort_pending_swap(e)
            return False
        # catch-up tokens are credited to slots/completions; the count
        # is surfaced through the next step()'s return
        self._drained_uncounted += self._drain_inflight()
        self.params = pending
        self._pending_params = None
        self.params_casts += 1
        # stored prefix KV and eager-prefilled rows encode the OLD
        # weights — rebuild lazily / re-prefill at admission
        self._prefix_states.clear()
        self._prefilled.clear()
        if self.layout == "paged":
            # drop the registry's hold on every prefix's shared blocks:
            # the COW invariant (all sharers of a block agree on its
            # content) would break if post-swap admissions rewrote
            # blocks that pre-swap live rows still gather. Fresh blocks
            # are allocated on next use; live rows keep theirs until
            # retirement (refcounts make the order safe).
            for ids in self._prefix_blocks.values():
                if ids:
                    self._pool.free(ids)
            self._prefix_blocks.clear()
        self.swap_latency_s = time.perf_counter() - self._pending_t0
        return True

    def poll_pending_swap(self) -> bool:
        """Public adoption poll for drivers whose engine may sit IDLE:
        ``step()`` adopts pending async swaps at chunk boundaries, but
        an idle server never steps — without this poll an async swap on
        an idle engine would leave ``swap_pending`` true forever."""
        return self._maybe_adopt_pending()

    def _pad_rows(self, rows: List[List[int]], width: int):
        # generation.left_pad_prompts owns the padding convention
        return left_pad_prompts(rows, pad_id=self.s.pad_id, width=width)

    @staticmethod
    def _insert_row(batch, row, slot):
        """Insert a [1, ...] prefilled row pytree into the batch cache
        at ``slot``; 0-d leaves (the write-index scalars, unread in
        ``cache_slots`` mode) stay the batch's."""
        return jax.tree_util.tree_map(
            lambda b, r: (
                b
                if b.ndim == 0
                else jax.lax.dynamic_update_slice(
                    b, r.astype(b.dtype), (slot,) + (0,) * (b.ndim - 1)
                )
            ),
            batch,
            row,
        )

    def _bucket_width(self, n: int) -> int:
        """Bucketed prefill width: a 5-token prompt must not pay a
        [1, Pw] forward on a Pw=256 engine. jit re-specializes per
        shape, so the same program object serves every bucket (at
        most 3 compiles); KV beyond the bucket stays a hole, which
        the decode contract already masks."""
        width = self.Pw
        for b in (max(8, self.Pw // 4), max(8, self.Pw // 2)):
            if n <= b < width:
                width = b
        return width

    def _build_row(
        self, uid: int, prompt: List[int],
        prefix_id: Optional[int] = None,
        allowed_tokens: Optional[List[int]] = None,
    ):
        """Everything an admission needs short of the insert: the
        prefilled row pytree (cache, logits, pos, kv, allow), its
        bucket width, and the full token history (prefix + suffix).
        Shared by the single and the burst insert."""
        V = self.model.config.vocab_size
        if allowed_tokens is None:
            # cached: rebuilding (and re-transferring) an all-True [V]
            # mask per admission was measurable host time on the
            # admission path the overlapped round now hides
            if not hasattr(self, "_allow_all"):
                self._allow_all = jnp.ones((V,), bool)
            row_allow = self._allow_all
        else:
            row_allow = (
                jnp.zeros((V,), bool)
                .at[jnp.asarray(allowed_tokens, jnp.int32)]
                .set(True)
            )
        with self._ctx():
            if prefix_id is not None:
                # prefix caching: derive the row from the stored prefix
                # state (computed once per weight version) + a
                # suffix-only forward. A warm state is a prefix HIT —
                # the prefix's own prefill is skipped entirely (the
                # affinity signal the fleet gateway routes on).
                if prefix_id in self._prefix_states:
                    self.prefix_hits += 1
                (p_cache, p_logits, p_pos, p_kv, p_width) = (
                    self._prefix_state(prefix_id)
                )
                s_width = self._bucket_width(len(prompt))
                toks, mask = self._pad_rows([prompt], s_width)
                row_cache, row_logits, row_pos, row_kv = (
                    self._continue_fn(
                        self.params, p_cache, toks, mask, p_kv, p_pos,
                        p_width,
                    )
                )
                width = p_width + s_width
                full_prompt = self._prefixes[prefix_id] + prompt
            else:
                pre = self._prefilled.pop(uid, None)
                if pre is not None:
                    # eager prefill already ran (hidden behind an
                    # in-flight chunk): admission is only the insert
                    row_cache, row_logits, row_pos, row_kv, width = pre
                else:
                    row_cache, row_logits, row_pos, row_kv, width = (
                        self._prefill(prompt)
                    )
                full_prompt = prompt
        # what this admission's prefill covers: the request's own tokens
        # (a stored prefix's are not computed again) against the width of
        # the bucket they were padded to. A scan, unlike attention, pays
        # for every padded position.
        own_width = self._bucket_width(self._prefill_len(prompt))
        self.phases.count("prefill_tokens_real", self._prefill_len(prompt))
        self.phases.count("prefill_tokens_padded", own_width)
        # ... and whether that call was too wide for an attention layer to
        # hold its scores whole (``layers.prefill_is_tiled``)
        self.phases.count(
            "prefill_tiled_calls", int(prefill_is_tiled(own_width, self.L))
        )
        row = (row_cache, row_logits, row_pos, row_kv, row_allow)
        return row, width, full_prompt

    def _admit_one(
        self, slot: int, uid: int, prompt: List[int], submit_t: float,
        cap: int, prefix_id: Optional[int] = None,
        allowed_tokens: Optional[List[int]] = None,
        table_ids: Optional[List[int]] = None,
    ):
        row, width, full_prompt = self._build_row(
            uid, prompt, prefix_id, allowed_tokens
        )
        with self._ctx():
            if self.layout == "paged":
                tr = jnp.asarray(
                    kv_blocks.build_table_row(table_ids, self._nb)
                )
                self._state = self._admit_fn(
                    self._state, *row, self._i32(slot),
                    self._i32(width), self._i32(cap), tr,
                )
                self._row_blocks[slot] = list(table_ids)
            else:
                self._state = self._admit_fn(
                    self._state, *row, self._i32(slot),
                    self._i32(width), self._i32(cap),
                )
        self._seat(slot, uid, full_prompt, submit_t, cap)

    def _seat(self, slot, uid, prompt, submit_t, cap, now=None):
        """The host half of an admission: the slot's record, and the
        queue wait it ended (submit → admission), counted here."""
        now = time.perf_counter() if now is None else now
        self._slots[slot] = _Slot(
            uid=uid, prompt=prompt, submit_t=submit_t, cap=cap,
            admit_t=now,
        )
        self.phases.count("requests_admitted")
        self.phases.count("queue_wait_s", max(now - submit_t, 0.0))

    def _first_token(self, st: _Slot, now: float) -> None:
        st.first_tok_t = now
        self.phases.count(
            "admit_to_first_token_s", max(now - st.admit_t, 0.0)
        )

    def _book_counters(self, counters: Dict) -> None:
        """One synced chunk's model counters (``[d]`` host arrays by
        name, from the chunk's own read-back) into the accumulator: the
        sum over its steps, for every slot's row, live or not (the
        device computed them all)."""
        for name, per_step in counters.items():
            self.phases.count(name, per_step.sum().item())

    def _count_chunk(self, row_steps: int) -> None:
        """One dispatched chunk: ``row_steps`` = slots x positions it
        decodes, whether or not a live row fills them; each of those
        steps' attention reads its row whole, ``max_seq_len`` positions."""
        self.phases.count("chunks")
        self.phases.count("row_steps", row_steps)
        self.phases.count("kv_positions_held", row_steps * self.L)

    def _count_kv_valid(self, st: _Slot, new: int) -> None:
        """What the decode steps that emitted a row's last ``new`` tokens
        needed of its keys and values: the row's real length at each (the
        prompt, what it had emitted, the step's own token), against the
        ``kv_positions_held`` a step reads. Booked at the chunk's read-back,
        after the tokens are credited."""
        if self.blocks is not None:
            return  # a pass counts its live rows' valid slots on the device
        before = len(st.prompt) + len(st.emitted) - new
        self.phases.count(
            "kv_positions_valid", new * before + new * (new + 1) // 2
        )

    # -- paged block planning (host side of admission) ------------------

    def _planned_width(
        self, uid: int, prompt: List[int], prefix_id: Optional[int]
    ) -> int:
        """The bucket width _build_row WILL use, computed without
        device work — block planning must reserve exactly what the
        insert covers."""
        pre = self._prefilled.get(uid)
        if pre is not None:
            return pre[4]
        if prefix_id is not None:
            return self._bucket_width(
                len(self._prefixes[prefix_id])
            ) + self._bucket_width(len(prompt))
        return self._bucket_width(len(prompt))

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Pool alloc with idle-prefix eviction as the backpressure
        valve: registered prefixes whose shared blocks no live row
        holds are evicted LRU-first until the allocation fits (their
        device state survives — the next use just re-allocates)."""
        ids = self._pool.alloc(n)
        while ids is None and self._evict_idle_prefix():
            ids = self._pool.alloc(n)
        return ids

    def _evict_idle_prefix(self) -> bool:
        for pid, ids in self._prefix_blocks.items():  # LRU order
            if all(self._pool.refcount(b) == 1 for b in ids):
                del self._prefix_blocks[pid]
                if ids:
                    self._pool.free(ids)
                self.prefix_evictions += 1
                return True
        return False

    def _prefix_shared_ids(self, pid: int) -> Optional[List[int]]:
        """The prefix's shareable blocks — the ones FULLY covered by
        its bucket width (the partial tail block is per-row private:
        the copy in copy-on-write). Allocated on first paged use and
        held by the registry at refcount 1 so they stay warm between
        rows; idle sets are LRU-evictable under pool pressure."""
        ids = self._prefix_blocks.get(pid)
        if ids is None:
            n = self._bucket_width(
                len(self._prefixes[pid])
            ) // self.kv_block_size
            ids = self._alloc_blocks(n) if n else []
            if ids is None:
                return None
            self._prefix_blocks[pid] = ids
        self._prefix_blocks.move_to_end(pid)
        return ids

    def _plan_blocks(self, uid, prompt, cap, prefix_id):
        """Plan one admission's block table: shared prefix blocks plus
        fresh private blocks covering positions [0, width + cap).
        Returns the table's block ids, or None when the pool cannot
        cover the request — the caller leaves it QUEUED and retries
        as retiring rows free blocks (admission bounded by blocks,
        never an OOM and never a wedge). The ``kv.alloc`` chaos point
        fires here: an injected error is exactly a failed allocation
        and takes the same bounded path."""
        ncov = kv_blocks.blocks_for(
            self._planned_width(uid, prompt, prefix_id) + cap,
            self.kv_block_size,
        )
        try:
            faults.inject(
                "kv.alloc", need=ncov, free=self._pool.blocks_free
            )
        except faults.FaultInjectedError:
            self.alloc_failures += 1
            return None
        shared: List[int] = []
        if prefix_id is not None:
            shared = self._prefix_shared_ids(prefix_id)
            if shared is None:
                self.alloc_failures += 1
                return None
            shared = shared[:ncov]
        priv = self._alloc_blocks(ncov - len(shared))
        if priv is None:
            self.alloc_failures += 1
            return None
        self._pool.share(shared)
        return shared + priv

    def _release_slot_blocks(self, slot: int) -> None:
        """Free a retired row's blocks (shared prefix blocks decref
        back to the registry's hold) and park the slot's table on the
        trash block, so the done row's clamped writes can never touch
        a re-allocated block. Idempotent — the retirement paths
        overlap (finalize + device retire + cancel)."""
        ids = self._row_blocks.pop(slot, None)
        if ids is None:
            return
        self._pool.free(ids)
        (pool, tables), *rest = self._state
        if not hasattr(self, "_trash_row_arr"):
            self._trash_row_arr = jnp.zeros((self._nb,), jnp.int32)
        self._state = (
            (pool, tables.at[slot].set(self._trash_row_arr)), *rest
        )

    def _finalize_slot(self, slot: int):
        """Completion bookkeeping shared by every mode: record the
        Completion (with service metrics) and free the host slot."""
        st = self._slots[slot]
        if st.uid >= 0:
            now = time.perf_counter()
            total_s = max(now - st.admit_t, 0.0)
            self._completions.append(
                Completion(
                    st.uid, st.emitted, st.logprobs,
                    passes=None if self.blocks is None else st.passes,
                    queue_s=max(st.admit_t - st.submit_t, 0.0),
                    ttft_s=max(
                        (st.first_tok_t or now) - st.admit_t, 0.0
                    ),
                    total_s=total_s,
                )
            )
            self._lat_window.append((now, total_s, len(st.emitted)))
            self.completed_total += 1
        self._slots[slot] = _Slot()
        if self.layout == "paged":
            self._release_slot_blocks(slot)

    def _retire(self, slot: int):
        self._finalize_slot(slot)
        self._retire_device_slot(slot)

    # tpulint: hotpath — admission runs under the in-flight chunk
    def _admit_free_slots(self, hidden: bool = False) -> None:
        """Fill empty slots from the queue.
        The admission device path (prefill + admit programs) runs in
        ``serve.prefill`` spans inside the caller's ``serve.admission``;
        in the overlapped round the whole of it runs while a chunk is
        in flight (``hidden``) and books as ``overlap_hidden``.

        The overlapped round admits a whole burst through ONE
        ``admit_many`` dispatch: a wave of equal-cap slots retires
        together, and per-row insert calls each pay full-state jit
        dispatch — the largest host-serial cost the pipeline had
        left. The synchronous baseline keeps the per-row path it
        always had."""
        # Chaos hook: a delay models a slow admission host path (the
        # overlapped round must hide it); an error surfaces to the
        # driver loop rather than silently corrupting slot state.
        faults.inject("serving.admit", queue_depth=len(self._queue))
        paged = self.layout == "paged"
        burst = self.overlap
        prefill_phase = "overlap_hidden" if hidden else "prefill"
        batch = []
        for slot, st in enumerate(self._slots):
            if st.uid >= 0 or not self._queue:
                continue
            # a freed slot ALWAYS has room (per-request liveness was
            # checked at construction); only the paged pool can refuse
            table_ids = None
            if paged:
                # paged admission is bounded by free BLOCKS: plan the
                # head request's block table before popping it, so a
                # request the pool can't cover right now stays QUEUED
                # (retiring rows return blocks) — never half-admitted,
                # never an OOM, never a wedge (submit() proved it fits
                # an empty pool).
                head = self._queue[0]
                table_ids = self._plan_blocks(
                    head[0], head[1], head[3], head[4]
                )
                if table_ids is None:
                    break  # out of blocks — retry next round
            (uid, prompt, submit_t, cap, prefix_id, allowed) = (
                self._queue.pop(0)
            )
            with self.phases.span(
                "serve.prefill", book=prefill_phase, uid=uid
            ):
                if not burst:
                    self._admit_one(
                        slot, uid, prompt, submit_t, cap, prefix_id,
                        allowed, table_ids=table_ids,
                    )
                else:
                    row, width, full_prompt = self._build_row(
                        uid, prompt, prefix_id, allowed
                    )
                    batch.append(
                        (slot, row, width, cap, uid, full_prompt,
                         submit_t, table_ids)
                    )
        if batch:
            with self.phases.span(
                "serve.prefill", book=prefill_phase, rows=len(batch)
            ), self._ctx():
                if paged:
                    self._state = self._admit_many_fn(
                        self._state,
                        tuple(b[1] for b in batch),
                        tuple(self._i32(b[0]) for b in batch),
                        tuple(self._i32(b[2]) for b in batch),
                        tuple(self._i32(b[3]) for b in batch),
                        tuple(
                            jnp.asarray(kv_blocks.build_table_row(
                                b[7], self._nb
                            ))
                            for b in batch
                        ),
                    )
                else:
                    self._state = self._admit_many_fn(
                        self._state,
                        tuple(b[1] for b in batch),
                        tuple(self._i32(b[0]) for b in batch),
                        tuple(self._i32(b[2]) for b in batch),
                        tuple(self._i32(b[3]) for b in batch),
                    )
            now = time.perf_counter()
            for (slot, _row, _w, cap, uid, full_prompt, submit_t,
                 table_ids) in batch:
                if paged:
                    self._row_blocks[slot] = list(table_ids)
                self._seat(slot, uid, full_prompt, submit_t, cap, now)

    # tpulint: hotpath — dispatch must never read the device back
    def _dispatch_round(self, rng) -> tuple:
        """Enqueue one decode chunk on the device; returns the
        in-flight record (output futures + done futures + the uid
        snapshot) without reading anything back."""
        with self._ctx():
            self._state, (toks, emits, logps, counters, *passes) = (
                self._chunk_for(self.d)(self.params, self._state, rng)
            )
        self._count_chunk(self.B * self.d)
        return (
            toks, emits, logps, self._state[-2],  # -2: the done flags
            counters,
            # block decoding: the pass that fixed each token; else None
            passes[0] if passes else None,
            [st.uid for st in self._slots],
        )

    def _emit_outputs(self, fetched, uids) -> int:
        """Credit one synced chunk's tokens to its slots and retire
        finished rows — one fused readback drove this, not per-token
        host polls. A slot whose uid changed since dispatch (cancel,
        or cancel + re-admit during the lag window) is skipped: the
        old row's emit mask is the device's own guarantee that a
        re-admitted request never sees a predecessor's tokens."""
        toks, emits, logps, done, counters, passes = fetched
        self._book_counters(counters)
        emitted = 0
        now = time.perf_counter()
        for slot, st in enumerate(self._slots):
            if st.uid < 0 or st.uid != uids[slot]:
                continue
            sel = emits[:, slot]
            if sel.any():
                new = toks[sel, slot].tolist()
                room = st.cap - len(st.emitted)
                if room < len(new):  # belt: device budget enforces cap
                    new = new[:max(room, 0)]
                if new:
                    if not st.emitted:
                        self._first_token(st, now)
                    st.emitted.extend(int(t) for t in new)
                    st.logprobs.extend(
                        float(x)
                        for x in logps[sel, slot][: len(new)]
                    )
                    if passes is not None:
                        st.passes.extend(
                            int(x) for x in passes[sel, slot][: len(new)]
                        )
                    self._count_kv_valid(st, len(new))
                    emitted += len(new)
            st.finished = bool(done[slot])
            if st.finished or len(st.emitted) >= st.cap:
                # the device already done-masked this row (budget/EOS),
                # so only the host slot needs freeing
                self._finalize_slot(slot)
        return emitted

    def _process_oldest(self) -> int:
        """Sync + emit + retire the oldest in-flight chunk. When a
        newer chunk is still in flight behind it, the host work here
        is hidden by device execution — stamped ``overlap_hidden``."""
        entry = self._inflight.pop(0)
        with self.phases.span("serve.host_sync", book="host_sync"):
            fetched = jax.device_get(entry[:-1])
        with self.phases.span(
            "serve.retirement",
            book="overlap_hidden" if self._inflight else "retirement",
        ):
            emitted = self._emit_outputs(fetched, entry[-1])
        return emitted

    def _drain_inflight(self) -> int:
        """Process every dispatched-but-unread chunk (the pipeline
        drain point: host bookkeeping catches up with the device)."""
        emitted = 0
        while self._inflight:
            emitted += self._process_oldest()
        return emitted

    # tpulint: hotpath — runs behind the dispatched chunk
    def _eager_prefill(self) -> None:
        """Prefill queue-head prompts WHILE a chunk is in flight (the
        overlapped round calls this right after dispatch): prompt rows
        are computed into ``self._prefilled`` so the later admission
        pays only the insert program. At most B rows are held (each a
        [1, L] cache); prefix-path requests keep the lazy path (their
        row derives from the stored prefix state)."""
        if not self._queue:
            return
        held = 0
        for item in self._queue:
            if held >= self.B:
                break
            held += 1
            uid, prompt, _submit_t, _cap, prefix_id, _allowed = item
            if prefix_id is not None or uid in self._prefilled:
                continue
            self._prefilled[uid] = self._prefill(prompt)

    def _oldest_ready(self) -> bool:
        """Non-blocking: has the oldest in-flight chunk already
        finished on the device? (same readiness poll as the async
        weight swap)."""
        return bool(self._inflight) and _tree_ready(
            self._inflight[0][:-1]
        )

    # tpulint: hotpath — the scheduler round; syncs live in _process_oldest
    def step(self, rng):
        """One scheduler iteration. Returns the number of tokens
        emitted this call. Phase boundaries are stamped into
        ``self.phases`` so ``stats()`` can report the
        host/device/hidden split, through spans that also land on a
        running profiler's trace.

        Synchronous round (``overlap=False``): admit into free slots,
        decode one chunk, block on its results, retire finished rows —
        the device idles while the host schedules.

        Overlapped round (default): admit and dispatch chunk N FIRST
        (the device queue stays non-empty), then sync chunk N-1 —
        whose execution already overlapped the previous call's host
        work — and do emission/retirement while chunk N runs. Rows
        stop themselves on the device (cap budget + EOS done-mask), so
        the one-chunk lag cannot over-emit; emission is one fused
        readback of tokens+emit-mask+logps+done. Streams are
        bit-identical with the synchronous round under greedy
        sampling; with temperature > 0 the admission lag shifts which
        rng a refilled slot consumes (either stream is a valid
        sample)."""
        # the round frames its children on the trace and books nothing:
        # the phases below it partition its time
        with self.phases.span(
            "serve.round", book="",
            live=sum(1 for st in self._slots if st.uid >= 0),
            queued=len(self._queue), inflight=len(self._inflight),
        ):
            emitted = (
                self._step_overlapped(rng) if self.overlap
                else self._step_sync(rng)
            )
        emitted += self._drained_uncounted
        self._drained_uncounted = 0
        if emitted:
            self.phases.count("tokens_emitted", emitted)
        return emitted

    # tpulint: hotpath
    def _step_sync(self, rng):
        """The host-serial round (the reference the bit-identity
        tests hold the overlapped round to): dispatch, block, emit,
        retire."""
        span = self.phases.span
        # a completed async weight swap lands here, between chunks —
        # the non-blocking check costs ~nothing when none is pending
        with span("serve.admission", book="admission"):
            self._maybe_adopt_pending()
        # admission books its self time: the serve.prefill spans
        # inside it (the device path) book as "prefill"
        with span("serve.admission", book="admission"):
            self._admit_free_slots()
        with span("serve.decode_dispatch", book="decode_dispatch"):
            entry = self._dispatch_round(rng)
        with span("serve.host_sync", book="host_sync"):
            # tpulint: ignore[host-sync] the sync round IS the blocking
            # reference the overlapped pipeline is compared against
            fetched = jax.device_get(entry[:-1])
        with span("serve.retirement", book="retirement"):
            emitted = self._emit_outputs_sync(fetched, entry[-1])
        self.phases.rounds += 1
        return emitted

    def _emit_outputs_sync(self, fetched, uids) -> int:
        """The synchronous round's per-token host loop, kept verbatim
        as the reference for the overlapped round's fused emission
        (greedy equality between the two paths is under test)."""
        toks, emits, logps, done, counters, passes = fetched
        self._book_counters(counters)
        emitted = 0
        for slot, st in enumerate(self._slots):
            if st.uid < 0:
                continue
            had = len(st.emitted)
            for t in range(toks.shape[0]):
                if len(st.emitted) >= st.cap:
                    break
                if emits[t, slot]:
                    if not st.emitted:
                        self._first_token(st, time.perf_counter())
                    st.emitted.append(int(toks[t, slot]))
                    st.logprobs.append(float(logps[t, slot]))
                    if passes is not None:
                        st.passes.append(int(passes[t, slot]))
                    emitted += 1
            self._count_kv_valid(st, len(st.emitted) - had)
            st.finished = bool(done[slot])
            if st.finished or len(st.emitted) >= st.cap:
                self._retire(slot)
        return emitted

    # tpulint: hotpath — every host span here runs under a chunk
    def _step_overlapped(self, rng):
        """The double-buffered round: dispatch chunk N before reading
        chunk N-1, so every host span between two dispatches runs
        under an executing chunk."""
        emitted = 0
        # adoption drains the pipeline first (_maybe_adopt_pending):
        # a landed WeightBus push costs one catch-up, never a split
        # round
        self._maybe_adopt_pending()
        # Zero-lag retirement: when the device already finished the
        # oldest chunk (it outran the host — the host-bound regime
        # this pipeline targets), process it BEFORE dispatching, so
        # slots it freed refill in THIS round's admission instead of
        # one chunk later. When the device is still busy, keep the
        # dispatch-first order — the queue must never drain.
        if self._oldest_ready():
            emitted += self._process_oldest()
        # admission overlaps the in-flight chunk: the prefill + admit
        # programs enqueue behind it and the host-side cost is hidden
        hidden = bool(self._inflight)
        span = self.phases.span
        with span(
            "serve.admission",
            book="overlap_hidden" if hidden else "admission",
        ):
            self._admit_free_slots(hidden)

        dispatched = False
        if any(st.uid >= 0 for st in self._slots):
            with span("serve.decode_dispatch", book="decode_dispatch"):
                self._inflight.append(self._dispatch_round(rng))
            dispatched = True
            # queued requests' prompt rows prefill NOW, behind the
            # chunk just dispatched — their admission later is only
            # the insert
            with span("serve.prefill", book="overlap_hidden", eager=1):
                self._eager_prefill()
        # keep pipeline depth at one: process the previous chunk while
        # the new one runs; with nothing dispatched, drain the tail
        if len(self._inflight) > (1 if dispatched else 0):
            emitted += self._process_oldest()
        self.phases.rounds += 1
        return emitted

    @property
    def pending(self) -> bool:
        """True while any request is queued or decoding, or a
        dispatched chunk's results are still unread (the overlapped
        round's tail) — the public drain condition for callers driving
        step() themselves (e.g. to land a weight swap mid-stream)."""
        return (
            bool(self._queue)
            or any(st.uid >= 0 for st in self._slots)
            or bool(self._inflight)
        )

    def _latency_stats(self) -> Dict:
        """p50/p95 completion latency and rolling tokens/s over the
        retirement window — the latency signal the fleet gateway's
        least-loaded routing and the autoscaler consume. Snapshot
        first (one C-level copy): /healthz readers call this from
        handler threads while the driver retires slots."""
        window = list(self._lat_window)
        if not window:
            return {
                "latency_p50_s": None,
                "latency_p95_s": None,
                "tokens_per_s": None,
                "completed_total": self.completed_total,
            }
        lats = sorted(t for _, t, _ in window)
        span = max(
            time.perf_counter() - window[0][0],
            # a single just-retired request: its own service time is
            # the only defensible span (avoids an absurd rate spike)
            lats[-1],
            1e-6,
        )
        return {
            "latency_p50_s": round(lats[len(lats) // 2], 4),
            "latency_p95_s": round(
                lats[min(int(len(lats) * 0.95), len(lats) - 1)], 4
            ),
            "tokens_per_s": round(
                sum(n for _, _, n in window) / span, 2
            ),
            "completed_total": self.completed_total,
        }

    def stats(self) -> Dict:
        """Operational snapshot (served over /healthz by tpurun-serve):
        live occupancy, queue depth, per-request latency percentiles,
        and the cache configuration that determines admission
        behavior."""
        return {
            **self._latency_stats(),
            "cache_layout": self.layout,
            "overlap": self.overlap,
            "inflight_chunks": len(self._inflight),
            "decode_chunk": self.d,
            "busy_slots": sum(1 for st in self._slots if st.uid >= 0),
            "queue_depth": len(self._queue),
            "registered_prefixes": len(self._prefixes),
            "prefix_states_cached": len(self._prefix_states),
            # paged-pool occupancy + prefix locality: the gateway's
            # affinity-routing and the autoscaler's admission signal
            # (None fields when the layout is slot-dense)
            "prefix_hits": self.prefix_hits,
            "resident_prefixes": sorted(self._prefix_states)[:64],
            "kv_block_size": (
                self.kv_block_size if self.layout == "paged" else None
            ),
            "blocks_total": (
                self._pool.blocks_total if self.layout == "paged"
                else None
            ),
            "blocks_free": (
                self._pool.blocks_free if self.layout == "paged"
                else None
            ),
            "alloc_failures": self.alloc_failures,
            "prefix_evictions": self.prefix_evictions,
            "kv_cache_int8": bool(
                getattr(self.model.config, "kv_cache_int8", False)
            ),
            # the cache on the device: leaves with a position axis (keys
            # and values, [slots, length, ...] or the block pool) and
            # per-request state leaves with none ([slots, ...])
            "cache_bytes_positional": self._cache_bytes[0],
            "cache_bytes_state": self._cache_bytes[1],
            # what the engine holds on the device for the programs (the
            # matrices in the model's compute dtype), and how many weight
            # versions it has held: start-up, then one a swap adopted
            "params_device_bytes": sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(self.params)
            ),
            "params_casts": self.params_casts,
            "last_swap_latency_s": self.swap_latency_s,
            "swap_pending": self._pending_params is not None,
            "swap_failures": self.swap_failures,
            "last_swap_error": self.last_swap_error,
            # host/device attribution (attribution.phases): host_frac
            # plus per-phase totals, compact enough for /healthz; beside
            # them this process's start-up phases and compile totals
            # (attribution.recovery), which no round ever books
            "phase_split": {
                **self.phases.split().summary(), **startup_summary()
            },
        }

    def partial(self, uid: int):
        """Tokens emitted so far for a live uid, or None if the uid is
        not currently decoding (queued, finished, or unknown). Safe to
        call from other threads: emission extends the list in one
        GIL-atomic C call, so a torn read only under-reports by at
        most one chunk's tokens, which the caller's next poll
        delivers. In the overlapped round the view additionally lags
        the device by one in-flight chunk. The streaming read API —
        external callers must not reach into slot internals."""
        for st in self._slots:
            if st.uid == uid:
                return list(st.emitted)
        return None

    def cancel(self, uid: int) -> bool:
        """Abort a request (client disconnect / timeout): a queued
        request is dropped; a decoding request's slot is freed for the
        next admission (its device row keeps stepping until then —
        static shapes — but emits to nobody). No Completion is
        recorded. Returns whether the uid was found live."""
        for i, item in enumerate(self._queue):
            if item[0] == uid:
                del self._queue[i]
                self._prefilled.pop(uid, None)
                return True
        for slot, st in enumerate(self._slots):
            if st.uid == uid:
                self._slots[slot] = _Slot()
                self._retire_device_slot(slot)
                return True
        return False

    def _retire_device_slot(self, slot: int) -> None:
        """Silence a freed slot on the device until the next admission
        (the done bit makes it emit pad)."""
        *head, done, row_f = self._state
        self._state = (*head, done.at[slot].set(True), row_f)
        if self.layout == "paged":
            # cancel path reaches here without _finalize_slot; the
            # release is idempotent so the retire paths can overlap
            self._release_slot_blocks(slot)

    def drain_completions(self) -> List[Completion]:
        """Hand over (and clear) finished requests, uid-ordered."""
        out, self._completions = self._completions, []
        return sorted(out, key=lambda c: c.uid)

    def run(self, prompts=None, rng=None) -> List[Completion]:
        """Drive the scheduler until every queued request completes.
        Step keys are pre-split in blocks: one ``jax.random.split``
        dispatch per 64 rounds instead of per round (the per-round
        split was measurable host-serial time on both scheduler
        paths). The keys differ from chained per-round splitting but
        are an equally valid independent stream; greedy output is
        key-independent either way."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        for p in prompts or []:
            self.submit(p)
        keys: List = []
        while self.pending:
            if not keys:
                rng, *block = jax.random.split(rng, 65)
                keys = list(block)
            self.step(keys.pop(0))
        out, self._completions = self._completions, []
        return sorted(out, key=lambda c: c.uid)
