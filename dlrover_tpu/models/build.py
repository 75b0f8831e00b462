"""One way from a configuration's ``model`` entry to a model the train
step and the server can build: ``{"family": <name>, "config": {<the config
class's fields>}}``. Callers (the benchmark's workers, a training script,
``tpurun-serve``) name no model class; a new family is one more line in
``FAMILIES``, the one registry trainer and server share, and one file that
imports ``layers``, ``moe``, ``ops/`` and ``parallel/`` and no other family
(``tests/test_models_layering.py``). What two families share lives in
``layers.py``: the gated delta-rule mixer (``layers.GatedDeltaMixer``, built
by ``qwen3_next`` and ``olmo_hybrid`` from their own sizes), the decode cache
with its two masks (causal by slot; by blocks of positions for ``sdar_moe``)
and its three attention products.

**The contract**, written here once: what a family's model may give, and
who reads it. No base class and no protocol type: a holder probes with
``getattr`` and a model that lacks a piece is served or trained without it.

- ``model(tokens[B, T], *, targets=None, decode=False, positions=None,
  kv_valid=None, cache_slots=None)`` -> logits ``[B, T, V]``. With
  ``targets`` (``targets[:, i]`` the token after ``tokens[:, i]``; -1 is
  ignored) the per-token losses ``[B, T]`` in float32, 0.0 where ignored:
  the *fused-CE contract*, paired with ``layers.token_loss_mean``
  (``layers.chunked_token_ce`` computes them a chunk at a time, so the
  ``[B, T, V]`` logits never exist whole). A family that is only trained
  takes ``targets`` alone. With ``decode=True`` the model reads and writes
  the ``"cache"`` collection (``generation.decode_apply`` is the one place
  that call is spelt): ``positions [B, T]`` absolute, ``kv_valid [B, L]``
  the cache slots that hold real tokens, ``cache_slots [B]`` per-row write
  slots: row ``b``'s ``T`` tokens go to slots ``[s_b, s_b + T)`` (without it
  the call's tokens go to the shared write offset). An autoregressive
  model's step is such a call of one token; a multi-token decode call may
  carry ``cache_slots`` too (PR 59: a block's pass,
  ``layers._update_decode_cache``), and then every position's logits are
  its holder's to read. Every holder of a multi-token call *without*
  ``cache_slots`` (a prefill) reads ``logits[:, -1]`` alone, and a model
  may return just that position (``olmo_hybrid``, ``sdar_moe``:
  ``[B, 1, V]``).
- On the config: ``ce_chunk`` (> 0: the train step hands the targets in;
  the size of a loss chunk) and ``takes_targets`` (hand them in whatever
  ``ce_chunk`` says: the model sows terms or counters on the way),
  ``frozen_leaves`` (leaf names that take neither gradient nor weight
  decay): ``parallel/train_step.py: build_train_step`` (``:272-275``),
  ``build_eval_step`` (``:405``) and ``build_model`` below.
  ``kv_cache_int8``: ``layers._update_decode_cache``, and
  ``ContinuousBatchingEngine.stats`` (``serving.py:1697``).
- ``consumed_param_dtypes(params)`` -> a tree of the dtype ``__call__``
  reads each leaf in (``layers.dtypes_read_by_name`` over the names the
  family lists). A holder that rounds a leaf to it once asks the
  arithmetic of the float32 tree, since ``astype`` of a value already in
  that dtype is the identity: ``ContinuousBatchingEngine._as_consumed``
  (``serving.py:875``) and ``init_params_as_consumed`` below. Without it
  the tree is held as given.
- ``cache_state_leaves(cache)`` -> a tree that is True where a leaf of the
  ``"cache"`` collection is a per-request *state* ``[B, ...]`` with no
  position axis (``layers.state_leaves_by_name``): the engine moves such a
  leaf whole with its row and never slices it by position
  (``ContinuousBatchingEngine.__init__``, ``serving.py:255``). Without it
  no leaf is a state.
- ``decode_step_counters(metrics)`` -> named device scalars from what one
  decode step sowed under ``"metrics"`` (``moe.decode_step_counters``):
  traced inside the decode chunk and read back with its tokens
  (``_build_programs``, ``serving.py:438``). Without it the chunk returns
  no counters.
- ``decode_blocks()`` -> ``layers.BlockDecoding(block_length,
  denoising_steps, mask_token_id)``: the model is *decoded a block at a
  time* (generation by diffusion over blocks, ``models/sdar_moe.py``), its
  logits at position ``i`` scoring the token at ``i``. The one probe by
  which ``ContinuousBatchingEngine`` builds the block chunk in the decode
  chunk's place (``serving.py: make_block_chunk``: a row's block goes
  through the model at the row's next ``block_length`` slots with
  ``mask_token_id`` where a position is undecided, and only a block with no
  undecided position leaves valid keys and values), refuses the paged
  layout, stored prefixes and the prefill hand-off, and streams a block a
  line (``launcher/serve.py``). Such a model attends under the mask by
  blocks of positions (``layers.cached_decode_attention(block_length=)``).
  Without it the model is decoded a token a step.
- ``book_step_counters(metrics)`` -> one train step's returned ``metrics``
  booked into the process accumulator (``moe.book_step_counters``), called
  by the holder of the step at a sync
  (``benchmark/workers/model_train_worker.py:76``).
"""

import dataclasses
import importlib
from typing import Any, Callable, Tuple

# family -> (module, model class, config class)
FAMILIES = {
    "gpt": ("gpt", "GPT", "GPTConfig"),
    "llama": ("llama", "Llama", "LlamaConfig"),
    "mla_moe": ("mla_moe", "MlaMoeLM", "MlaMoeConfig"),
    "lfm2_moe": ("lfm2_moe", "Lfm2MoeLM", "Lfm2MoeConfig"),
    "granite_hybrid": ("granite_hybrid", "GraniteHybridLM", "GraniteHybridConfig"),
    "qwen3_next": ("qwen3_next", "Qwen3NextLM", "Qwen3NextConfig"),
    "mellum": ("mellum", "MellumLM", "MellumConfig"),
    "olmo_hybrid": ("olmo_hybrid", "OlmoHybridLM", "OlmoHybridConfig"),
    "sdar_moe": ("sdar_moe", "SdarMoeLM", "SdarMoeConfig"),
}

_DTYPE_FIELDS = ("dtype", "param_dtype")


def build_model(entry: dict) -> Tuple[Any, Callable]:
    """(model, loss_fn) for ``build_train_step``. ``loss_fn`` is the mean of
    the model's own per-token losses where the model takes the targets
    (``ce_chunk > 0`` or ``takes_targets``), else the cross entropy of its
    logits. A key the config class does not have is an error, not a
    default: a misspelt width must not run as another model."""
    import jax.numpy as jnp

    family = entry["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}; have {sorted(FAMILIES)}")
    module, model_cls, config_cls = FAMILIES[family]
    mod = importlib.import_module(f"{__package__}.{module}")
    config_type = getattr(mod, config_cls)
    fields = {f.name for f in dataclasses.fields(config_type)}
    values = dict(entry["config"])
    unknown = sorted(set(values) - fields)
    if unknown:
        raise ValueError(f"{config_cls} has no field {unknown}")
    for name in _DTYPE_FIELDS:
        if isinstance(values.get(name), str):
            values[name] = jnp.dtype(values[name]).type
    config = config_type(**values)
    from .layers import cross_entropy_loss, token_loss_mean

    takes_targets = getattr(config, "ce_chunk", 0) > 0 or getattr(config, "takes_targets", False)
    return getattr(mod, model_cls)(config), token_loss_mean if takes_targets else cross_entropy_loss


def init_params_as_consumed(model, rng):
    """The model's initial parameters in the dtypes it reads them in
    (``model.consumed_param_dtypes``: what a server holds), made by ONE
    jitted program whose outputs are already rounded: each leaf is drawn in
    float32 and rounded inside the program, so the float32 tree never
    exists (at a size where it would not fit the chip it cannot). The
    values are those of ``model.init`` on the same key, rounded."""
    import jax
    import jax.numpy as jnp

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        dtypes_of = getattr(model, "consumed_param_dtypes", None)
        if dtypes_of is None:
            return params
        return jax.tree.map(lambda leaf, dt: leaf.astype(dt), params, dtypes_of(params))

    return jax.jit(init)(rng)
