"""Plain reference of the ``qwen3_next`` decoder (gated delta-rule mixers,
gated grouped-query attention, routed experts with a gated shared one):
the forward pass in straightforward float32 ``jax.numpy``. No kernel, no
cache, no chunked form, no sorted rows, no padding; every product under
``default_matmul_precision("highest")``; **the recurrence token by token**
(a ``lax.scan`` over ``T`` that carries ``S``), so that it shares nothing
with the chunked algorithm of ``dlrover_tpu/ops/gated_delta.py``; every
held expert computed for every token and weighted by its gate (0 where the
token did not choose it).

The equations (``hp`` holds the published keys; ``d`` = ``hidden_size``).
``norm(x, w) = x rsqrt(mean(x^2) + rms_norm_eps) (1 + w)`` (zero-centred).
``h_0 = wte[tokens]``; layer ``i``: ``h += Mix_i(norm(h))``,
``h += MoE(norm(h))``; one norm after the last layer, ``logits = h W_head``.

- delta layers (``(i + 1) % full_attention_interval != 0``; ``Hk`` =
  ``linear_num_key_heads``, ``Hv`` = ``linear_num_value_heads``, ``dk``,
  ``dv`` the head sizes, ``K`` = ``linear_conv_kernel_dim``):
  ``[q ; k ; v ; z] = u W_qkvz`` (in that order, a departure: the published
  checkpoint interleaves them by key head; the weights are random),
  ``[b ; a] = u W_ba``; ``[q ; k ; v] <- silu(sum_j taps_j
  [q ; k ; v]_{t-K+1+j})``, zeros before the first token; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q <- q /
  sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k <- k / sqrt(|k|^2 + 1e-6)``; per
  value head ``j`` with key head ``j // (Hv / Hk)``, from ``S = 0``:
  ``S <- exp(g_t) S``; ``u_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
  u_t^T``; ``o_t = S^T q_t``; ``y = o rsqrt(mean_dv(o^2) + eps) w_g
  silu(z)``; ``W_out``.
- attention layers: ``[q_h ; gate_h] = u W_q`` a head; ``k``, ``v`` in
  ``num_key_value_heads``; ``q_h``, ``k_h`` through the zero-centred norm
  over the head; rotate-half RoPE (``rope_theta``) on the first
  ``head_dim partial_rotary_factor`` channels; causal softmax of ``q k^T /
  sqrt(head_dim)``; ``(attention sigmoid(gate)) W_o``.
- ``MoE``: ``p = softmax(x W_r)`` over ``num_experts``; the top
  ``num_experts_per_tok``; gates ``p_i / sum of the chosen p``;
  ``sum_i gate_i SwiGLU_i(x) + sigmoid(x w_s) SwiGLU_shared(x)``. **The
  share**: only experts ``[expert_offset, expert_offset + experts_held)``
  exist here (``experts_held`` 0: all); what the others would add is left
  out, as in the program, and that partial sum is what goes on.

Not here: the published model's multi-token-prediction module.

The parameters are taken as the program's init made them (weights are data
here), in its layout, and walked a layer at a time: one layer's leaves are
brought to float32, used and dropped. ``state_dtype`` rounds ``S`` to a
lower precision after every token and ``matrix_bits`` the matrices to so
many mantissa bits first: controls that the benchmark's limits are read
against. ``forget_at`` zeroes ``S`` before that token: what a layer's output
owes to older state is the difference.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import _hashable, round_mantissa

MATRICES = ("w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "wte", "lm_head")


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def recurrence(q, k, v, g, beta, state_dtype=None, forget_at=None, initial_state=None):
    """Token by token: ``q`` and ``k`` ``[b, T, Hk, dk]``, ``v [b, T, Hv,
    dv]``, ``g`` and ``beta`` ``[b, T, Hv]`` -> (``o [b, T, Hv, dv]``, the
    last state ``[b, Hv, dk, dv]``)."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    per_value_head = lambda a: jnp.repeat(a, hv // hk, axis=1)  # noqa: E731  [b, Hk, dk] -> [b, Hv, dk]

    def one(s, inputs):
        i, q_t, k_t, v_t, g_t, b_t = inputs
        q_t, k_t = per_value_head(q_t), per_value_head(k_t)
        if forget_at is not None:
            s = jnp.where(i == forget_at, 0.0, s)
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        if state_dtype is not None:  # not astype there and back: XLA may keep the excess precision
            s = jax.lax.reduce_precision(s, jnp.finfo(state_dtype).nexp, jnp.finfo(state_dtype).nmant)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s0 = jnp.zeros((bsz, hv, dk, dv), jnp.float32) if initial_state is None else initial_state
    steps = (jnp.arange(t), *(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    last, o = jax.lax.scan(one, s0, steps)
    return jnp.moveaxis(o, 0, 1), last


def delta_op(u, p, hp, state_dtype=None, forget_at=None):
    bsz, t, _ = u.shape
    hk, hv = hp["linear_num_key_heads"], hp["linear_num_value_heads"]
    dk, dv, taps = hp["linear_key_head_dim"], hp["linear_value_head_dim"], hp["linear_conv_kernel_dim"]
    keys, values = hk * dk, hv * dv
    qkvz, ba = u @ p["w_qkvz"], u @ p["w_ba"]
    qkv, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + t] for j in range(taps)))
    q = qkv[..., :keys].reshape(bsz, t, hk, dk)
    k = qkv[..., keys:2 * keys].reshape(bsz, t, hk, dk)
    v = qkv[..., 2 * keys:].reshape(bsz, t, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o, _ = recurrence(q, k, v, g, beta, state_dtype, forget_at)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + hp["rms_norm_eps"]) * p["gate_norm"]
    return (o.reshape(bsz, t, values) * jax.nn.silu(z)) @ p["w_out"]


def rope(x, theta):
    """Rotate-half RoPE over the whole last axis of ``x [b, T, h, r]``."""
    t, r = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.outer(jnp.arange(t, dtype=jnp.float32), freqs)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                            x1 * jnp.sin(angles) + x2 * jnp.cos(angles)], axis=-1)


def attention_op(u, p, hp):
    t, d = u.shape[1], hp["head_dim"]
    rot = int(d * hp["partial_rotary_factor"])
    q_gate = jnp.einsum("btd,dhk->bthk", u, p["wq"])
    q, gate = q_gate[..., :d], q_gate[..., d:]
    q = norm(q, p["q_norm"]["scale"], hp["rms_norm_eps"])
    k = norm(jnp.einsum("btd,dgk->btgk", u, p["wk"]), p["k_norm"]["scale"], hp["rms_norm_eps"])
    v = jnp.einsum("btd,dgk->btgk", u, p["wv"])
    q = jnp.concatenate([rope(q[..., :rot], hp["rope_theta"]), q[..., rot:]], axis=-1)
    k = jnp.concatenate([rope(k[..., :rot], hp["rope_theta"]), k[..., rot:]], axis=-1)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", out * jax.nn.sigmoid(gate), p["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gates(x, w_router, hp):
    """``x [N, d]`` -> the gate of every expert for every token ``[N, E]``:
    0 where the token did not choose it."""
    probs = jax.nn.softmax(x @ w_router, axis=-1)
    top, idx = jax.lax.top_k(probs, hp["num_experts_per_tok"])
    if hp.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)


def moe_op(x, p, hp, matrix_bits=None, shared: bool = True):
    """The experts held here (``experts_held`` from ``expert_offset``; 0:
    all), each over every token, plus (``shared``) the gated shared one.
    The held experts' matrices come as the program holds them and are
    brought to float32 one expert at a time."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = p["w_gate"].shape[0]
    first = hp.get("expert_offset", 0)
    gate = gates(x, p["w_router"], hp)[:, first:first + held]

    def as_float32(w):
        w = w.astype(jnp.float32)
        return w if matrix_bits is None else round_mantissa(w, matrix_bits)

    def one(total, expert):
        w_gate, w_up, w_down, gate_e = expert
        return total + gate_e[:, None] * swiglu(x, as_float32(w_gate), as_float32(w_up), as_float32(w_down)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (p["w_gate"], p["w_up"], p["w_down"], gate.T))
    if shared:
        s = p["shared"]
        out = out + jax.nn.sigmoid(x @ p["w_shared_gate"]) * swiglu(
            x, as_float32(s["w_gate"]), as_float32(s["w_up"]), as_float32(s["w_down"]))
    return out.reshape(shape)


def is_attention(hp, i: int) -> bool:
    return (i + 1) % hp["full_attention_interval"] == 0


def _as_float32(p, matrix_bits, but=()):
    def one(path, a):
        names = [getattr(k, "key", None) for k in path]
        if names[0] in but:  # left as held: brought to float32 where it is used
            return a
        a = a.astype(jnp.float32)
        if matrix_bits is not None and names[-1] in MATRICES:
            a = round_mantissa(a, matrix_bits)
        return a

    return jax.tree_util.tree_map_with_path(one, p)


@functools.partial(jax.jit, static_argnames=("hp_items", "attention", "state_dtype", "matrix_bits"))
def _layer(x, p, hp_items, attention, state_dtype, matrix_bits, forget_at):
    hp = dict(hp_items)
    p = _as_float32(p, matrix_bits, but=("moe",))
    moe = dict(p["moe"], w_router=p["moe"]["w_router"].astype(jnp.float32),
               w_shared_gate=p["moe"]["w_shared_gate"].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        u = norm(x, p["input_norm"]["scale"], hp["rms_norm_eps"])
        mix = attention_op(u, p["attn"], hp) if attention else delta_op(u, p["gdn"], hp, state_dtype, forget_at)
        x = x + mix
        h = norm(x, p["post_norm"]["scale"], hp["rms_norm_eps"])
        return x + moe_op(h, moe, hp, matrix_bits)


@functools.partial(jax.jit, static_argnames=("matrix_bits",))
def head(x, scale, w_head, eps, matrix_bits=None):
    w_head = w_head.astype(jnp.float32)
    if matrix_bits is not None:
        w_head = round_mantissa(w_head, matrix_bits)
    with jax.default_matmul_precision("highest"):
        return norm(x, scale.astype(jnp.float32), eps) @ w_head


def hidden(params, tokens, hp, state_dtype=None, matrix_bits=None):
    """tokens [B, T] int -> the last layer's output [B, T, d] float32."""
    x = params["wte"][tokens].astype(jnp.float32)
    if matrix_bits is not None:
        x = round_mantissa(x, matrix_bits)
    for i in range(hp["num_hidden_layers"]):
        x = _layer(x, params[f"block_{i}"], _hashable(hp), is_attention(hp, i), state_dtype, matrix_bits, -1)
    return x


def logits(params, tokens, hp, at=None, state_dtype=None, matrix_bits=None):
    """tokens [B, T] int -> logits [B, T, V] float32, or ``[B, len(at), V]``
    at the positions ``at`` (one position or a list), for a vocabulary too
    wide to keep T of."""
    x = hidden(params, tokens, hp, state_dtype, matrix_bits)
    if at is not None:
        x = x[:, jnp.atleast_1d(jnp.asarray(at))]
    return head(x, params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"], matrix_bits)


def layer_witnesses(params, tokens, hp, layer: int, older_than: int = 64):
    """What the init has to show of delta layer ``layer`` (``tokens [B, T]``
    with ``T > older_than``; the layers before it run as they are), at the
    last token: ``old_state_share``, the share of the mixer's output that
    state older than ``older_than`` tokens carries (RMS of the output less
    the output with ``S`` zeroed that many tokens before the end, over the
    RMS of the output); and the RMS of what the layer adds to the residual
    stream by branch: the mixer, the routed experts held here, the gated
    shared expert, beside the RMS of the stream they are added to."""
    x = params["wte"][tokens].astype(jnp.float32)
    for i in range(layer):
        x = _layer(x, params[f"block_{i}"], _hashable(hp), is_attention(hp, i), None, None, -1)
    p = _as_float32(params[f"block_{layer}"], None)
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        u = norm(x, p["input_norm"]["scale"], hp["rms_norm_eps"])
        whole = delta_op(u, p["gdn"], hp)
        recent = delta_op(u, p["gdn"], hp, forget_at=tokens.shape[1] - older_than)
        h = norm(x + whole, p["post_norm"]["scale"], hp["rms_norm_eps"])
        both = moe_op(h, p["moe"], hp)
        routed = moe_op(h, p["moe"], hp, shared=False)
    return dict(old_state_share=rms((whole - recent)[:, -1]) / rms(whole[:, -1]),
                stream_rms=rms(x), mixer_rms=rms(whole), routed_rms=rms(routed), shared_rms=rms(both - routed))
