"""Plain reference of the latent-attention / routed-experts / MTP decoder
(the DeepSeek-V3 layer equations, as JoyAI-LLM-Flash's config states
them): forward, both losses and their gradients in straightforward
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``. No
kernel, no sort, no buffer, no remat: dense causal attention, and a loop
over the experts held.

``hp`` is the configuration's dict of published keys (``hidden_size``,
``q_lora_rank``, ``num_experts_per_tok`` ...) plus the share:
``experts_held`` / ``expert_offset`` (which of the ``n_routed_experts``
this chip computes; 0 held means all) and ``mtp_loss_weight``. The
reference is told the same share as the program: what the absent experts
would add is left out here too, and that partial sum goes on to the next
layer.

Written from the equations, not from ``dlrover_tpu/models/mla_moe.py``,
which it does not import. Departures from the published model, each the
configuration's (see its ``assumed``):

- the parameters are taken as the program's init made them (weights are
  data), in its layout: ``w_dq [D, r_q]``, ``w_uq [r_q, H, nope+rope]``,
  ``w_dkv [D, r_kv+rope]``, ``w_ukv [r_kv, H, nope+v]``, ``w_o [H, v, D]``,
  experts ``w_gate / w_up [E_held, D, F]``, ``w_down [E_held, F, D]``,
  ``w_router [D, E]``, ``lm_head [D, V]``, ``mtp_0/w_eh [2D, D]``;
- the MTP module's input is the trunk's output *after* its final norm,
  joined as ``[norm_e(Emb(t_{i+1})) ; norm_h(h_i)]``, and its loss enters
  at ``mtp_loss_weight`` (the DeepSeek-V3 report, section 2.2, and its
  public inference code; the config states neither);
- every position has a target (the data rolls the sequence, so the last
  target is the first token): the trunk's loss is over all T positions,
  the MTP loss over the T - 1 that have a token after the next;
- ``compute_dtype`` other than float32 exists to show what a lower
  precision would read (the benchmark's limits must refuse it).
  ``bfloat16`` casts the parameters and activations, the router's scores
  included (the system scores its router in float32). ``float8_e4m3fn``
  rounds every parameter to 8 bits first and then computes as
  ``bfloat16`` does: the nearest thing below the bf16 products the
  configuration states;
- :func:`by_rows` (the benchmark's sizes, a row at a time) recomputes each
  block in the backward pass so that a row of 4,096 tokens fits one chip;
  the arithmetic is the same.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope_interleaved(x, theta):
    """x [B, T, ..., d]: the pair (x[2i], x[2i+1]) at position t turns by
    the angle t * theta^(-2i/d) (``rope_interleave: true``)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, d/2]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def _latent_attention(x, p, hp):
    nope, rope = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    r_kv, eps, t = hp["kv_lora_rank"], hp["rms_norm_eps"], x.shape[1]
    c_q = _rms_norm(x @ p["w_dq"], p["q_norm"]["scale"], eps)
    q = jnp.einsum("btr,rhk->bthk", c_q, p["w_uq"])
    q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], hp["rope_theta"])
    down = x @ p["w_dkv"]
    c_kv = _rms_norm(down[..., :r_kv], p["kv_norm"]["scale"], eps)
    k_r = rope_interleaved(down[..., r_kv:], hp["rope_theta"])  # [B, T, rope], all heads'
    kv = jnp.einsum("btr,rhk->bthk", c_kv, p["w_ukv"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
              + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_r)) / jnp.sqrt(jnp.float32(nope + rope))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["w_o"])


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def chosen_and_gates(x, p, hp, score_dtype=jnp.float32):
    """(chosen [.., E] bool, gates [.., E]) over all the routed experts."""
    k = hp["num_experts_per_tok"]
    s = jax.nn.sigmoid(x.astype(score_dtype) @ p["w_router"].astype(score_dtype))
    s = s.astype(jnp.float32)
    kth = jax.lax.top_k(s + p["e_score_correction_bias"], k)[0][..., -1:]  # the k-th largest
    chosen = (s + p["e_score_correction_bias"]) >= kth  # the k largest of s + b
    gates = jnp.where(chosen, s, 0.0)  # ... gated by the unbiased s
    if hp.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return chosen, gates * hp["routed_scaling_factor"]


def _expert_layer(x, p, hp, score_dtype):
    held = hp.get("experts_held") or hp["n_routed_experts"]
    first = hp.get("expert_offset", 0)
    chosen, gates = chosen_and_gates(x, p, hp, score_dtype)
    y = _swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"])
    for e in range(held):  # the experts that live here; the others' part is not ours
        g = gates[..., first + e, None].astype(x.dtype)
        y = y + g * _swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    landed_here = jnp.sum(chosen[..., first:first + held])
    return y, landed_here


def _block(x, p, hp, score_dtype):
    eps = hp["rms_norm_eps"]
    x = x + _latent_attention(_rms_norm(x, p["norm_attn"]["scale"], eps), p["attn"], hp)
    h = _rms_norm(x, p["norm_mlp"]["scale"], eps)
    if "moe" in p:
        y, landed = _expert_layer(h, p["moe"], hp, score_dtype)
        return x + y, landed
    m = p["mlp"]
    return x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"]), None


def _mean_ce(h, w_head, targets):
    logps = jax.nn.log_softmax((h @ w_head).astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logps, targets[..., None], axis=-1))


def losses(params, tokens, targets, hp, compute_dtype=jnp.float32, block=_block):
    """(trunk loss, MTP loss, assignments landed here per expert layer:
    the trunk's in order, then the MTP module's). ``block`` is how one block
    is computed (:func:`by_rows` recomputes it in the backward pass)."""
    if compute_dtype == jnp.float8_e4m3fn:  # 8-bit parameters, bf16 products
        params = jax.tree.map(lambda a: jnp.asarray(a, compute_dtype), params)
        compute_dtype = jnp.bfloat16
    params = jax.tree.map(lambda a: jnp.asarray(a, compute_dtype), params)
    precision = "highest" if compute_dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        eps, landed = hp["rms_norm_eps"], []
        x = params["wte"][tokens]
        layer = 0
        while f"block_{layer}" in params:
            x, n = block(x, params[f"block_{layer}"], hp, compute_dtype)
            landed += [] if n is None else [n]
            layer += 1
        h = _rms_norm(x, params["norm_f"]["scale"], eps)
        trunk = _mean_ce(h, params["lm_head"], targets)
        m = params["mtp_0"]
        joined = jnp.concatenate(
            [_rms_norm(params["wte"][targets], m["norm_e"]["scale"], eps),
             _rms_norm(h, m["norm_h"]["scale"], eps)], axis=-1)
        x, n = block(joined @ m["w_eh"], m["block"], hp, compute_dtype)
        landed.append(n)
        h_mtp = _rms_norm(x, m["norm_f"]["scale"], eps)
        # position i predicts the token after the next, targets[i + 1]
        mtp = _mean_ce(h_mtp[:, :-1], params["lm_head"], targets[:, 1:])
        return trunk, mtp, jnp.stack(landed)


def loss_and_grads(params, tokens, targets, hp, compute_dtype=jnp.float32, block=_block):
    """((total, trunk, mtp, landed), gradients): total = trunk + lambda mtp."""
    def total(p):
        trunk, mtp, landed = losses(p, tokens, targets, hp, compute_dtype, block)
        return trunk + hp["mtp_loss_weight"] * mtp, (trunk, mtp, landed)

    (loss, (trunk, mtp, landed)), grads = jax.value_and_grad(total, has_aux=True)(params)
    return (loss, trunk, mtp, landed), grads


def by_rows(params, tokens, targets, hp, compute_dtype=jnp.float32):
    """:func:`loss_and_grads` one batch row at a time, averaged (both
    losses are means over rows of equal length, so the mean of the rows'
    gradients is the batch's), each block recomputed in the backward
    pass: the blocks that fit."""
    def block(x, p, hp_, dtype):
        return jax.checkpoint(lambda x, p: _block(x, p, hp_, dtype))(x, p)

    fn = jax.jit(lambda p, x, y: loss_and_grads(p, x, y, hp, compute_dtype, block))
    sums, grads = None, None
    for r in range(tokens.shape[0]):
        (loss, trunk, mtp, landed), g = fn(params, tokens[r:r + 1], targets[r:r + 1])
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        row = (loss.astype(jnp.float32), trunk.astype(jnp.float32), mtp.astype(jnp.float32))
        if sums is None:
            sums, counts, grads = row, landed, g
        else:
            sums = tuple(a + b for a, b in zip(sums, row))
            counts, grads = counts + landed, jax.tree.map(jnp.add, grads, g)
    n = tokens.shape[0]
    loss, trunk, mtp = (s / n for s in sums)
    return (loss, trunk, mtp, counts), jax.tree.map(lambda a: a / n, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32))) for a in jax.tree.leaves(tree)))
