"""Plain reference of the ``lfm2_moe`` decoder (gated short convolutions,
grouped-query attention with per-head q/k norms, routed experts): the
forward pass in straightforward float32 ``jax.numpy``. No kernel, no cache,
no remat, no batching trick; every product under
``default_matmul_precision("highest")``; every expert computed densely for
every token and weighted by its gate, which is zero where the token did not
choose it.

The equations (``hp`` holds the published keys; ``d`` = ``hidden_size``):
RMSNorm ``x rsqrt(mean(x^2) + norm_eps) w``; layer ``i``:
``h += Op_i(RMSNorm(h))``, ``h += FF_i(RMSNorm(h))``; one RMSNorm after the
last layer (``embedding_norm``), then the head, tied to the embedding.

- ``conv``: ``[B ; C ; x~] = u W_in``; ``z = B x~``;
  ``c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t`` with ``z`` zero before the
  first token; ``y = (C c) W_out``.
- ``full_attention``: q in ``num_attention_heads`` heads, k and v in
  ``num_key_value_heads``; q and k RMS-normed per head, each with its own
  vector, before RoPE (rotate-half: channel ``i`` pairs with ``i + d_h/2``,
  angle ``t theta^(-2i/d_h)``); scores over ``sqrt(d_h)``, causal softmax;
  each kv head serves ``heads / kv heads`` query heads.
- ``FF_i``: SwiGLU ``(silu(x W_1) x W_3) W_2`` of ``intermediate_size`` for
  ``i < num_dense_layers``; else ``s = sigmoid(x W_r)``, the top
  ``num_experts_per_tok`` of ``s + b`` chosen, gates ``s_i / (sum of the
  chosen s + 1e-6)`` times ``routed_scaling_factor``, ``y = sum g_i E_i(x)``.

Departures, all the repo's and all under ``assumed`` in the configuration's
file: the head tied to the embedding; head size ``d / heads``; the 1e-6.

The parameters are taken as the program's init made them (weights are data
here), in its layout, and walked a layer at a time: one layer's leaves are
brought to float32, used and dropped (an expert layer's matrices a few
experts at a time), so that a host or a chip holds the tree as the server
holds it (bf16 matrices) plus one layer's share in float32.
``expert_dtype`` rounds the experts' matrices to a lower precision first:
the control that the benchmark's limits have to refuse.
"""

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, T, H, d_h]; rotate-half pairing."""
    t, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def conv_op(u, p):
    t = u.shape[1]
    bcx = jnp.einsum("btd,dgc->btgc", u, p["w_in"])
    z = bcx[:, :, 0] * bcx[:, :, 2]
    zp = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))
    k = p["conv_kernel"]
    c = k[0] * zp[:, :t] + k[1] * zp[:, 1:t + 1] + k[2] * z
    return (bcx[:, :, 1] * c) @ p["w_out"]


def attention_op(u, p, hp):
    t = u.shape[1]
    q = rms_norm(jnp.einsum("btd,dhk->bthk", u, p["wq"]), p["q_norm"]["scale"], hp["norm_eps"])
    k = rms_norm(jnp.einsum("btd,dgk->btgk", u, p["wk"]), p["k_norm"]["scale"], hp["norm_eps"])
    v = jnp.einsum("btd,dgk->btgk", u, p["wv"])
    q, k = rope(q, hp["rope_theta"]), rope(k, hp["rope_theta"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gates(x, p, hp):
    """[..., E]: each token's gate at every expert, zero where not chosen."""
    s = jax.nn.sigmoid(x @ p["w_router"])
    biased = s + p["expert_bias"] if "expert_bias" in p else s
    _, idx = jax.lax.top_k(biased, hp["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=-2)
    g = s * chosen
    if hp.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return g * hp.get("routed_scaling_factor", 1.0)


def route_margin(x, p, hp):
    """[...]: how far the last chosen expert's biased score lies above the
    first one left out. Where it is small the choice is a near-tie, and a
    computation in fewer bits may fall the other way."""
    s = jax.nn.sigmoid(x @ p["w_router"])
    top, _ = jax.lax.top_k(s + p["expert_bias"] if "expert_bias" in p else s, hp["num_experts_per_tok"] + 1)
    return top[..., -2] - top[..., -1]


def experts_op(x, p, hp, at_once: int = 8):
    """Every expert held for every token, weighted by its gate; ``at_once``
    experts at a time, so that their float32 copies and products fit."""
    g = gates(x, p, hp)
    held = p["w_gate"].shape[0]
    at_once = min(at_once, held)
    if held % at_once:
        raise ValueError(f"{held} experts do not divide into groups of {at_once}")
    groups = held // at_once

    def some(y, group):
        w_gate, w_up, w_down, gate = (a.astype(jnp.float32) for a in group)
        h = jax.nn.silu(jnp.einsum("btd,edf->ebtf", x, w_gate)) * jnp.einsum("btd,edf->ebtf", x, w_up)
        return y + jnp.einsum("ebtf,efd,bte->btd", h, w_down, gate), None

    split = lambda a: a.reshape((groups, at_once) + a.shape[1:])  # noqa: E731
    gate = jnp.moveaxis(g.reshape(g.shape[:-1] + (groups, at_once)), -2, 0)
    y, _ = jax.lax.scan(some, jnp.zeros_like(x), (split(p["w_gate"]), split(p["w_up"]),
                                                  split(p["w_down"]), gate))
    return y


def layer_types(hp):
    n = hp["num_hidden_layers"]
    return list(hp.get("layer_types") or
                ["full_attention" if i % 4 == 2 else "conv" for i in range(n)])[:n]


def _hashable(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in hp.items()
                        if isinstance(v, (int, float, bool, str, list, tuple))))


@functools.partial(jax.jit, static_argnames=("hp_items", "kind", "dense", "expert_dtype"))
def _layer(x, p, hp_items, kind, dense, expert_dtype):
    hp = dict(hp_items)
    if expert_dtype is not None and "moe" in p:  # the control: experts held in fewer bits
        p = dict(p, moe={k: (v.astype(expert_dtype) if v.ndim == 3 else v) for k, v in p["moe"].items()})
    # (the experts' matrices are brought to float32 a few experts at a time, inside)
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: a if a.ndim == 3 and any(getattr(k, "key", None) == "moe" for k in path)
        else a.astype(jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["operator_norm"]["scale"], hp["norm_eps"])
        x = x + (conv_op(u, p["conv"]) if kind == "conv" else attention_op(u, p["attn"], hp))
        h = rms_norm(x, p["ffn_norm"]["scale"], hp["norm_eps"])
        if dense:
            return x + swiglu(h, p["mlp"]), jnp.full(x.shape[:-1], jnp.inf)
        return x + experts_op(h, p["moe"], hp), route_margin(h, p["moe"], hp)


@jax.jit
def head(x, scale, wte, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("btd,vd->btv", rms_norm(x, scale.astype(jnp.float32), eps),
                          wte.astype(jnp.float32))


def hidden(params, tokens, hp, expert_dtype=None, margins=None):
    """tokens [B, T] int -> the last layer's output [B, T, d] float32. A list
    ``margins`` gets each layer's ``route_margin`` ``[B, T]`` (inf for a
    dense layer)."""
    x = params["wte"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_types(hp)):
        x, margin = _layer(x, params[f"block_{i}"], _hashable(hp), kind, i < hp["num_dense_layers"],
                           expert_dtype)
        if margins is not None:
            margins.append(margin)
    return x


def logits(params, tokens, hp, expert_dtype=None, at=None, margins=None):
    """tokens [B, T] int -> logits [B, T, V] float32, or ``[B, len(at), V]``
    at the positions ``at`` (one position or a list), for a vocabulary too
    wide to keep T of."""
    x = hidden(params, tokens, hp, expert_dtype, margins)
    if at is not None:
        x = x[:, jnp.atleast_1d(jnp.asarray(at))]
    return head(x, params["embedding_norm"]["scale"], params["wte"], hp["norm_eps"])
