"""Autoregressive generation over the training parameters, TPU-first.

The rollout half of an RL job. The reference delegates generation to
vLLM actors (its PPO example wires `vllm_*` engine args straight into
the rollout role — examples/unified/rl/openrlhf/ppo/main.py:26-60); in
this framework generation is a first-class jit-compiled path over the
same flax parameters the trainer optimizes, so a rollout role needs no
second inference stack, no weight format conversion, and re-syncs
weights by just receiving the new param pytree.

Design (all shapes static, everything under one ``jit``):

- **Left-padded prompts.** Every batch row ends at the same cache slot,
  so the prefill and every decode step write the KV cache with a single
  ``dynamic_update_slice`` — never a per-row scatter. Per-row absolute
  positions (for RoPE / learned positional embeddings) and a per-slot
  validity mask carry the variable prompt lengths instead.
- **Prefill** runs the whole prompt through the model once in decode
  mode (one MXU-friendly pass, T0 wide), filling cache slots [0, T0).
- **Decode** is a ``lax.scan`` over single-token steps: sample, write
  slot T0+t, advance. Rows that hit EOS keep stepping on a pad token
  (static shapes) and are masked out of the result.
- **Sampling**: temperature / top-k / top-p composed in fp32, then
  ``jax.random.categorical``. Chosen-token logprobs (under the raw,
  unfiltered distribution) are returned for RL objectives.
"""

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SamplingConfig",
    "build_generate_fn",
    "decode_apply",
    "filter_logits",
    "generate",
    "init_cache",
    "left_pad_prompts",
    "prefill_prompt",
    "sample_logits",
    "sample_step",
]


@dataclass(frozen=True)
class SamplingConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: int = 0  # 0 = off
    top_p: float = 1.0  # 1.0 = off
    eos_id: int = -1  # -1 = never stop early
    pad_id: int = 0


def left_pad_prompts(prompts: list, pad_id: int = 0, width: int = 0):
    """Pack variable-length token lists into LEFT-padded [B, T0] arrays.

    Returns (tokens, mask) with mask True on real tokens. Left padding
    is the generation-engine convention (see module docstring): all rows
    end at the same slot so the decode loop writes one static slice.
    """
    width = max(width, max(len(p) for p in prompts))
    tokens = np.full((len(prompts), width), pad_id, dtype=np.int32)
    mask = np.zeros((len(prompts), width), dtype=bool)
    for i, p in enumerate(prompts):
        if len(p):
            tokens[i, width - len(p) :] = np.asarray(p, dtype=np.int32)
            mask[i, width - len(p) :] = True
    return jnp.asarray(tokens), jnp.asarray(mask)


def init_cache(model, batch_size: int):
    """Zero decode-cache pytree for ``model`` at the given batch size.

    Shapes come from ``jax.eval_shape`` over ``model.init`` in decode
    mode — nothing is computed, no params are materialized. The cache
    spans ``cfg.max_seq_len`` slots per layer (KVH-wide for GQA models).
    """
    cfg = model.config
    dummy = jnp.zeros((batch_size, 1), jnp.int32)
    pos = jnp.zeros((batch_size, 1), jnp.int32)
    valid = jnp.zeros((batch_size, cfg.max_seq_len), bool)

    def _init():
        return model.init(
            jax.random.PRNGKey(0),
            dummy,
            decode=True,
            positions=pos,
            kv_valid=valid,
        )

    shapes = jax.eval_shape(_init)["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )


def filter_logits(logits, top_k: int = 0, top_p: float = 1.0):
    """Mask [..., V] fp32 logits to the top-k/top-p support (-inf out).

    top-k keeps the k largest; top-p keeps the smallest prefix of the
    sorted distribution whose mass reaches p (always at least the
    argmax). Filters compose: k first, then p, the common serving
    convention.
    """
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose cumulative mass BEFORE them is < top_p
        keep_sorted = (cum - probs) < top_p
        inv = jnp.argsort(sort_idx, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def sample_logits(
    logits,
    rng,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
):
    """Sample token ids from [B, V] logits. Static sampling params.

    temperature==0 is greedy argmax; see :func:`filter_logits` for the
    top-k/top-p semantics.
    """
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = filter_logits(logits / max(temperature, 1e-6), top_k, top_p)
    return jax.random.categorical(rng, logits, axis=-1)


def decode_apply(
    model, params, cache, tokens, positions, kv_valid, cache_slots=None,
    metrics: bool = False,
):
    """One decode-mode model application over an explicit cache pytree.

    Returns (raw logits, updated cache), and with ``metrics`` a third:
    what the model sowed under ``"metrics"`` in this call (``{}`` for a
    model that sows nothing), for a caller that returns counters beside
    its tokens. The cache is whatever the model keeps under ``"cache"``:
    positional leaves ``[B, L, ...]`` (keys and values), per-request
    state leaves ``[B, ...]`` with no position axis (a convolution's last
    inputs; ``model.cache_state_leaves`` says which), and 0-d write
    offsets; every leaf's first axis is the batch row.

    The single place the decode contract (``decode=True, positions,
    kv_valid, mutable=["cache"]``) is spelled, shared by the one-shot engine and the continuous-
    batching scheduler — their token-exactness guarantee depends on
    applying the model identically. ``cache_slots`` [B] selects the
    per-row write-slot mode of a single-token step (continuous
    batching's per-row cache layout); see
    layers._update_decode_cache. The whole contract: ``models/build.py``.
    """
    logits, mut = model.apply(
        {"params": params, "cache": cache},
        tokens,
        decode=True,
        positions=positions,
        kv_valid=kv_valid,
        cache_slots=cache_slots,
        mutable=["cache", "metrics"] if metrics else ["cache"],
    )
    if metrics:
        return logits, mut["cache"], mut.get("metrics", {})
    return logits, mut["cache"]


def sample_step(last_logits, done, rng, s: SamplingConfig):
    """One sampling decision: (token, emit mask, logprob, done').

    ``done`` rows emit pad and are masked; an eos sample is emitted
    (the eos token is kept) and marks the row done afterwards.
    Logprobs are computed from the logits AS GIVEN (RL behavior
    logprobs): the one-shot engine passes raw model logits; the
    continuous engine may pass per-row MASKED logits (allowed_tokens
    constrained decoding), in which case the logprobs are under the
    masked distribution — exactly what the policy could emit. Shared
    by the one-shot and continuous engines.
    """
    tok = sample_logits(last_logits, rng, s.temperature, s.top_k, s.top_p)
    logp = jax.nn.log_softmax(last_logits, axis=-1)
    tok_logp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    tok = jnp.where(done, s.pad_id, tok)
    emit_mask = ~done
    if s.eos_id >= 0:
        done = done | (tok == s.eos_id)
    return tok, emit_mask, tok_logp, done


def prefill_prompt(model, params, tokens, mask):
    """Run a LEFT-padded [B, W] prompt through the model in decode mode
    (one MXU-friendly pass), filling a FRESH cache's slots [0, W).

    Returns ``(cache, last_logits[B,V] fp32, last_pos[B],
    kv_valid[B,L])`` — everything a decode loop needs to start.
    """
    B, W = tokens.shape
    L = model.config.max_seq_len
    with jax.named_scope("serve.cache_write"):  # the fresh row: zeros, its valid bits
        cache = init_cache(model, B)
        positions = jnp.maximum(
            jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0
        )
        kv_valid = jnp.zeros((B, L), bool).at[:, :W].set(mask)
    logits, cache = decode_apply(
        model, params, cache, tokens, positions, kv_valid
    )
    with jax.named_scope("serve.sample"):  # what the first sampling reads
        return (
            cache,
            logits[:, -1].astype(jnp.float32),
            positions[:, -1],
            kv_valid,
        )


def build_generate_fn(
    model,
    sampling: SamplingConfig,
    prompt_width: int,
    mesh=None,
    param_shardings=None,
    rules=None,
) -> Callable:
    """Compile a generation function for fixed (prompt width, sampling).

    Returns ``fn(params, prompt_tokens[B,T0], prompt_mask[B,T0], rng) ->
    (tokens[B,N], mask[B,N], logprobs[B,N])`` — completions, a validity
    mask that cuts off after the first EOS (the EOS token itself is
    kept), and per-token logprobs under the raw model distribution
    (what an RL objective wants as behavior logprobs). Build once per
    rollout role; every call reuses the compiled executable.

    With ``mesh`` (+ optionally the params' ``NamedSharding`` tree and
    logical-axis ``rules``), the whole prefill+decode program runs SPMD
    over the mesh: params stay tp/fsdp-sharded exactly as the trainer
    holds them, prompts shard over the data axes, and XLA inserts the
    decode collectives — a rollout role serves a model bigger than one
    chip with the same compiled path (the reference needs a separate
    vLLM deployment for this; SURVEY.md §2.13).
    """
    cfg = model.config
    s = sampling
    max_len = cfg.max_seq_len
    if prompt_width + s.max_new_tokens > max_len:
        raise ValueError(
            f"prompt width {prompt_width} + max_new {s.max_new_tokens} "
            f"exceeds max_seq_len {max_len}"
        )

    def _generate(params, prompt_tokens, prompt_mask, rng):
        B, T0 = prompt_tokens.shape
        if T0 != prompt_width:
            # the build-time overflow guard validated prompt_width; a
            # wider input would overflow the cache SILENTLY (clamped
            # dynamic_update_slice writes + never-matching kv_valid)
            raise ValueError(
                f"prompt_tokens width {T0} != built prompt_width "
                f"{prompt_width}"
            )
        cache, last_logits, cur_pos, kv_valid = prefill_prompt(
            model, params, prompt_tokens, prompt_mask
        )

        # N tokens need N-1 incremental forwards (the prefill supplied
        # the first logits, the last sampled token is never fed back) —
        # the scan covers tokens 0..N-2, the final sample happens after.
        def step(carry, t):
            cache, kv_valid, last_logits, cur_pos, done, rng = carry
            rng, sub = jax.random.split(rng)
            tok, emit_mask, tok_logp, done = sample_step(
                last_logits, done, sub, s
            )

            slot = T0 + t
            kv_valid = kv_valid | (
                jnp.arange(max_len)[None, :] == slot
            )
            pos = cur_pos + 1
            logits, cache = decode_apply(
                model,
                params,
                cache,
                tok[:, None],
                pos[:, None],
                kv_valid,
            )
            carry = (
                cache,
                kv_valid,
                logits[:, 0].astype(jnp.float32),
                pos,
                done,
                rng,
            )
            return carry, (tok, emit_mask, tok_logp)

        done0 = jnp.zeros((B,), bool)
        carry = (cache, kv_valid, last_logits, cur_pos, done0, rng)
        carry, (toks, masks, logps) = jax.lax.scan(
            step, carry, jnp.arange(s.max_new_tokens - 1)
        )
        _, _, last_logits, _, done, rng = carry
        tok_n, emit_n, logp_n, _ = sample_step(
            last_logits, done, jax.random.split(rng)[1], s
        )
        # scan stacks on axis 0 → [N-1, B]; append the final sample
        toks = jnp.concatenate([toks.T, tok_n[:, None]], axis=1)
        masks = jnp.concatenate([masks.T, emit_n[:, None]], axis=1)
        logps = jnp.concatenate([logps.T, logp_n[:, None]], axis=1)
        return toks, masks, logps

    if mesh is None:
        return jax.jit(_generate)

    from ..parallel.sharding import sharded_generate_jit

    return sharded_generate_jit(
        _generate, mesh, (param_shardings,), n_data_args=2, rules=rules
    )


def generate(
    model,
    params,
    prompt_tokens,
    prompt_mask,
    rng,
    sampling: Optional[SamplingConfig] = None,
):
    """One-shot convenience wrapper around :func:`build_generate_fn`."""
    sampling = sampling or SamplingConfig()
    fn = build_generate_fn(model, sampling, prompt_tokens.shape[1])
    return fn(params, prompt_tokens, prompt_mask, rng)
