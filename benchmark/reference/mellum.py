"""Plain reference of the window / full attention decoder with routed
experts (the ``mellum`` layer equations, as Mellum2-12B-A2.5B's config
states them): forward, loss and gradients in straightforward float32
``jax.numpy`` under ``default_matmul_precision("highest")``. No kernel, no
sort, no buffer: the masks are comparisons of positions, attention is
computed a block of query rows at a time against all the keys (so that a
row of 8,192 tokens fits: the arithmetic is that of the whole square), and
the routed experts are a loop over the experts held.

``hp`` is the configuration's ``model.config``: the published keys
(``hidden_size``, ``num_key_value_heads``, ``layer_types``,
``sliding_window``, ``rope_parameters`` by layer type ...) plus the share,
``experts_held`` / ``expert_offset`` (which of the ``num_experts`` this
chip computes; 0 held means all). The reference is told the same share as
the program: what the absent experts would add is left out here too, and
that partial sum goes on to the next layer.

Layer ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; a
final RMSNorm; an untied head. ``Attn_l``: ``q = x W_q`` in H heads, ``k``
and ``v`` in KV heads (query head ``h`` reads key/value head ``h // (H /
KV)``), RoPE over the whole head in the rotate-half pairing (channel ``i``
with ``i + d/2``), scores over ``sqrt(d)``, softmax, ``W_o``.
``sliding_attention``: row ``i`` sees key ``j`` iff ``0 <= i - j <
sliding_window``, RoPE ``default``. ``full_attention``: ``j <= i``, RoPE
``yarn`` (:func:`inv_freq`). ``MoE``: ``p = softmax(z W_r)`` over all the
experts, the ``num_experts_per_tok`` largest, gates ``p`` over the chosen
ones' sum, ``sum_e g_e (silu(z W_g,e) * (z W_u,e)) W_d,e``. On a share
(fewer experts held than routed) the gates are constants in the backward
pass: the task loss would reach the router by the held experts' outputs
alone, a partial sum that only the group's all-reduce completes.

Written from the equations, not from ``dlrover_tpu/models/mellum.py``,
which it does not import. Departures from the published model, each the
configuration's (see its ``assumed``):

- the parameters are taken as the program's init made them (weights are
  data), in its layout: ``w_q [D, H, d]``, ``w_k / w_v [D, KV, d]``, ``w_o
  [H, d, D]``, experts ``w_gate / w_up [E_held, D, F]``, ``w_down [E_held,
  F, D]``, ``w_router [D, E]``, ``wte [V, D]``, ``lm_head [D, V]``;
- no MTP module (no config key sizes one), no per-head norm on q and k, no
  auxiliary balancing loss;
- every position has a target (the data rolls the sequence);
- three switches exist to show what the benchmark's limits must refuse:
  ``compute_dtype`` (``bfloat16`` casts parameters and activations, the
  router's scores included; ``float8_e4m3fn`` rounds every parameter to 8
  bits first and then computes as ``bfloat16`` does), ``window=False``
  (every layer causal) and ``yarn=False`` (the full layers on the default
  table);
- :func:`by_rows` (the benchmark's sizes, a batch row at a time)
  recomputes each block, and each block of attention rows, in the backward
  pass; the arithmetic is the same.
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 512  # query rows (and head rows) computed at a time


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def inv_freq(d: int, rope: dict, yarn: bool = True):
    """(``d / 2`` channel frequencies, the factor on cos and sin). With
    ``base``, ``L`` the original length and ``s`` the factor: ``c(r) = d
    ln(L / (2 pi r)) / (2 ln base)``, ``low = max(floor(c(beta_fast)), 0)``,
    ``high = min(ceil(c(beta_slow)), d - 1)``, ``ramp_i = clip((i - low) /
    (high - low), 0, 1)``, ``f_i = base^(-2i/d)``, ``inv_freq_i = (f_i / s)
    ramp_i + f_i (1 - ramp_i)``, and cos and sin times
    ``attention_factor``."""
    base = float(rope["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / d)
    if rope["rope_type"] != "yarn" or not yarn:
        return f, 1.0
    s, length = float(rope["factor"]), rope["original_max_position_embeddings"]
    c = lambda r: d * math.log(length / (2 * math.pi * r)) / (2 * math.log(base))
    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / s) * ramp + f * (1.0 - ramp), float(rope["attention_factor"])


def rope(x, rope_group: dict, yarn: bool = True):
    """x [B, T, heads, d]: channel ``i`` and ``i + d/2`` at position ``t``
    turn by ``t inv_freq_i``."""
    t, d = x.shape[1], x.shape[-1]
    freqs, factor = inv_freq(d, rope_group, yarn)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(angle) * factor)[None, :, None, :]
    sin = (jnp.sin(angle) * factor)[None, :, None, :]
    a, b = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1).astype(x.dtype)


def _attend_rows(q_rows, k, v, first_row, window):
    """q_rows [B, R, KV, G, d] (the R query rows from ``first_row`` on)
    against all of k, v [B, T, KV, d]: row ``i`` sees key ``j`` iff ``j <=
    i`` and, under a window, ``i - j < window``."""
    d = q_rows.shape[-1]
    scores = jnp.einsum("brgqd,bsgd->bgqrs", q_rows, k).astype(jnp.float32) / math.sqrt(d)
    i = first_row + jnp.arange(q_rows.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window:
        seen &= i - j < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bgqrs,bsgd->brgqd", probs, v)


def _attention(x, p, hp, layer_type, attend_rows, window=True, yarn=True):
    h, kv = hp["num_attention_heads"], hp["num_key_value_heads"]
    group = dict(hp["rope_parameters"])[layer_type]
    q = rope(jnp.einsum("btd,dhk->bthk", x, p["w_q"]), group, yarn)
    k = rope(jnp.einsum("btd,dgk->btgk", x, p["w_k"]), group, yarn)
    v = jnp.einsum("btd,dgk->btgk", x, p["w_v"])
    b, t, _, d = q.shape
    q = q.reshape(b, t, kv, h // kv, d)  # query head g * (H / KV) + j reads kv head g
    span = hp["sliding_window"] if layer_type == "sliding_attention" and window else 0
    rows = min(ROW_BLOCK, t)
    out = jnp.concatenate(
        [attend_rows(q[:, r:r + rows], k, v, r, span) for r in range(0, t, rows)], axis=1)
    return jnp.einsum("bthk,hkd->btd", out.reshape(b, t, h, d), p["w_o"])


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def chosen_and_gates(x, p, hp, score_dtype=jnp.float32):
    """(chosen [.., E] bool, gates [.., E]) over all the routed experts."""
    k = hp["num_experts_per_tok"]
    s = jax.nn.softmax(x.astype(score_dtype) @ p["w_router"].astype(score_dtype), axis=-1)
    s = s.astype(jnp.float32)
    kth = jax.lax.top_k(s, k)[0][..., -1:]  # the k-th largest
    chosen = s >= kth
    gates = jnp.where(chosen, s, 0.0)
    if hp.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return chosen, gates


def _expert_layer(x, p, hp, score_dtype):
    held = hp.get("experts_held") or hp["num_experts"]
    first = hp.get("expert_offset", 0)
    chosen, gates = chosen_and_gates(x, p, hp, score_dtype)
    if held < hp["num_experts"]:  # a share: the task loss does not reach the router
        gates = jax.lax.stop_gradient(gates)
    y = jnp.zeros_like(x)
    for e in range(held):  # the experts that live here; the others' part is not ours
        g = gates[..., first + e, None].astype(x.dtype)
        y = y + g * _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y, jnp.sum(chosen[..., first:first + held])


def _block(x, p, hp, layer_type, score_dtype, attend_rows=_attend_rows, window=True, yarn=True):
    eps = hp["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["norm_attn"]["scale"], eps), p["attn"], hp,
                       layer_type, attend_rows, window, yarn)
    y, landed = _expert_layer(_rms_norm(x, p["norm_mlp"]["scale"], eps), p["moe"], hp, score_dtype)
    return x + y, landed


def _ce_rows(h, w_head, targets):
    """The summed cross entropy of a block of positions."""
    logps = jax.nn.log_softmax((h @ w_head).astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logps, targets[..., None], axis=-1))


def layer_type_of(hp, layer: int) -> str:
    return hp["layer_types"][layer % len(hp["layer_types"])]


def losses(params, tokens, targets, hp, compute_dtype=jnp.float32, window=True, yarn=True,
           block=_block, ce_rows=_ce_rows):
    """(loss, assignments landed here per layer). ``block`` and ``ce_rows``
    are how one block and one block of the head's rows are computed
    (:func:`by_rows` recomputes them in the backward pass)."""
    if compute_dtype == jnp.float8_e4m3fn:  # 8-bit parameters, bf16 products
        params = jax.tree.map(lambda a: jnp.asarray(a, compute_dtype), params)
        compute_dtype = jnp.bfloat16
    params = jax.tree.map(lambda a: jnp.asarray(a, compute_dtype), params)
    precision = "highest" if compute_dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        landed = []
        x = params["wte"][tokens]
        for layer in range(hp["num_hidden_layers"]):
            x, n = block(x, params[f"block_{layer}"], hp, layer_type_of(hp, layer),
                         compute_dtype, window=window, yarn=yarn)
            landed.append(n)
        h = _rms_norm(x, params["norm_f"]["scale"], hp["rms_norm_eps"])
        t = tokens.shape[1]
        rows = min(ROW_BLOCK, t)
        total = sum(ce_rows(h[:, r:r + rows], params["lm_head"], targets[:, r:r + rows])
                    for r in range(0, t, rows))
        return total / targets.size, jnp.stack(landed)


def loss_and_grads(params, tokens, targets, hp, compute_dtype=jnp.float32, **how):
    """((loss, landed), gradients)."""
    return jax.value_and_grad(
        lambda p: losses(p, tokens, targets, hp, compute_dtype, **how), has_aux=True)(params)


def by_rows(params, tokens, targets, hp, compute_dtype=jnp.float32, window=True, yarn=True):
    """:func:`loss_and_grads` one batch row at a time, averaged (the loss is
    a mean over rows of equal length, so the mean of the rows' gradients is
    the batch's), each block, each block of attention rows and each block
    of the head's rows recomputed in the backward pass: what fits."""
    attend_rows = jax.checkpoint(_attend_rows, static_argnums=(3, 4))

    def block(x, p, hp_, layer_type, dtype, **how):
        return jax.checkpoint(
            lambda x, p: _block(x, p, hp_, layer_type, dtype, attend_rows, **how))(x, p)

    fn = jax.jit(lambda p, x, y: loss_and_grads(
        p, x, y, hp, compute_dtype, window=window, yarn=yarn,
        block=block, ce_rows=jax.checkpoint(_ce_rows)))
    loss, counts, grads = 0.0, 0, None
    for r in range(tokens.shape[0]):
        (row_loss, landed), g = fn(params, tokens[r:r + 1], targets[r:r + 1])
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        loss, counts = loss + row_loss.astype(jnp.float32), counts + landed
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.shape[0]
    return (loss / n, counts), jax.tree.map(lambda a: a / n, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32))) for a in jax.tree.leaves(tree)))
