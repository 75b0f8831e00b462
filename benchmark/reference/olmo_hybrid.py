"""Plain reference of the ``olmo_hybrid`` decoder (gated delta-rule mixers
with write strengths in (0, 2), ungrouped attention with no position
embedding, a dense SwiGLU, the norm on each sublayer's output): the forward
pass in straightforward float32 ``jax.numpy``. No kernel, no cache, no
chunked form, no batching tricks, no padding; every product under
``default_matmul_precision("highest")``; **the recurrence token by token**
(``reference/qwen3_next.py: recurrence``, a ``lax.scan`` over ``T`` that
carries ``S``), so that it shares nothing with the chunked algorithm of
``dlrover_tpu/ops/gated_delta.py``; attention
as the full masked softmax, computed ``ATTENTION_ROWS`` query rows at a time
(every row against all the keys, so each block is the whole softmax of its
rows: 8,192 tokens then fit the chip's memory, which is all the blocks are
for).

The equations (``hp`` holds the published keys; ``d`` = ``hidden_size``),
as ISSUE 56 reads them from the config and the catalog's ``described_as``;
each reading the config does not settle is listed in the configuration's
``assumed``. ``norm(x, w) = x rsqrt(mean(x^2) + rms_norm_eps) w``. ``h_0 =
wte[tokens]``; layer ``i`` (the OLMo 2 / 3 order: the norm on the sublayer's
*output*): ``h += norm(Mix_i(h))``, ``h += norm(MLP(h))``; one norm after
the last layer, ``logits = h W_head``. ``MLP(x) = (silu(x W_gate) (x W_up))
W_down``.

- delta layers (``layer_types[i] == "linear_attention"``; ``Hk`` =
  ``linear_num_key_heads``, ``Hv`` = ``linear_num_value_heads``, ``dk``,
  ``dv`` the head sizes, ``K`` = ``linear_conv_kernel_dim``): ``[q ; k ; v ;
  z] = x W_qkvz`` (in that order, a departure: the published checkpoint
  keeps four matrices and three convolutions; the weights are random, and
  three depthwise convolutions are one over the concatenated channels),
  ``[b ; a] = x W_ba``; ``[q ; k ; v] <- silu(sum_j taps_j [q ; k ;
  v]_{t-K+1+j})``, zeros before the first token; ``beta = 2 sigmoid(b)``
  where ``linear_allow_neg_eigval`` (else ``sigmoid(b)``), ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q <- q / sqrt(|q|^2 + 1e-6) /
  sqrt(dk)``, ``k <- k / sqrt(|k|^2 + 1e-6)``; per value head ``j`` with key
  head ``j // (Hv / Hk)``, from ``S = 0``: ``S <- exp(g_t) S``; ``u_t =
  beta_t (v_t - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``; ``y =
  o rsqrt(mean_dv(o^2) + eps) w_g silu(z)``; ``W_out``.
- attention layers: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; ``q`` and
  ``k`` through ``norm`` over their **whole** width (all heads' channels at
  once), then split into heads of ``d / num_attention_heads``; no rotary
  embedding (``rope_parameters.rope_theta`` is null); causal softmax of ``q
  k^T / sqrt(head size)``; ``W_o``.

The parameters are taken as the program's init made them (weights are data
here), in its layout, and walked a layer at a time: one layer's leaves are
brought to float32, used and dropped. ``matrix_bits`` rounds the matrices to
so many mantissa bits first and ``state_dtype`` rounds ``S`` after every
token: controls that the benchmark's limits are read against. ``forget_at``
zeroes ``S`` before that token: what a layer's output owes to older state is
the difference.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import _hashable, round_mantissa
from benchmark.reference.qwen3_next import recurrence  # the delta rule token by token: the same recurrence

MATRICES = ("w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "wte", "lm_head")
ATTENTION_ROWS = 1024


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def delta_op(x, p, hp, state_dtype=None, forget_at=None):
    bsz, t, _ = x.shape
    hk, hv = hp["linear_num_key_heads"], hp["linear_num_value_heads"]
    dk, dv, taps = hp["linear_key_head_dim"], hp["linear_value_head_dim"], hp["linear_conv_kernel_dim"]
    keys, values = hk * dk, hv * dv
    qkvz, ba = x @ p["w_qkvz"], x @ p["w_ba"]
    qkv, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + t] for j in range(taps)))
    q = qkv[..., :keys].reshape(bsz, t, hk, dk)
    k = qkv[..., keys:2 * keys].reshape(bsz, t, hk, dk)
    v = qkv[..., 2 * keys:].reshape(bsz, t, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :hv]) * (2.0 if hp.get("linear_allow_neg_eigval") else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o, _ = recurrence(q, k, v, g, beta, state_dtype, forget_at)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + hp["rms_norm_eps"]) * p["gate_norm"]
    return (o.reshape(bsz, t, values) * jax.nn.silu(z)) @ p["w_out"]


def attention_op(x, p, hp):
    bsz, t, d_model = x.shape
    heads = hp["num_attention_heads"]
    group = heads // hp["num_key_value_heads"]
    d = d_model // heads
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"]).reshape(bsz, t, -1)
    k = jnp.einsum("btd,dgk->btgk", x, p["wk"]).reshape(bsz, t, -1)
    q = norm(q, p["q_norm"]["scale"], hp["rms_norm_eps"]).reshape(bsz, t, heads, d)
    k = norm(k, p["k_norm"]["scale"], hp["rms_norm_eps"]).reshape(bsz, t, heads // group, d)
    v = jnp.einsum("btd,dgk->btgk", x, p["wv"])
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    rows = min(ATTENTION_ROWS, t)
    while t % rows:  # whole blocks: the largest divisor of ``t`` that fits
        rows -= 1

    def block(first):  # these rows' whole softmax over every key
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, axis=1)
        scores = jnp.einsum("bqhk,bshk->bhqs", q_rows, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(rows)[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(0, t, rows))  # [blocks, b, rows, h, d]
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, heads, d)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def is_attention(hp, i: int) -> bool:
    return hp["layer_types"][i] == "full_attention"


def _as_float32(p, matrix_bits):
    def one(path, a):
        a = a.astype(jnp.float32)
        if matrix_bits is not None and getattr(path[-1], "key", None) in MATRICES:
            a = round_mantissa(a, matrix_bits)
        return a

    return jax.tree_util.tree_map_with_path(one, p)


@functools.partial(jax.jit, static_argnames=("hp_items", "attention", "state_dtype", "matrix_bits"))
def _layer(x, p, hp_items, attention, state_dtype, matrix_bits, forget_at):
    hp = dict(hp_items)
    p = _as_float32(p, matrix_bits)
    with jax.default_matmul_precision("highest"):
        mix = attention_op(x, p["attn"], hp) if attention else delta_op(x, p["gdn"], hp, state_dtype, forget_at)
        h = x + norm(mix, p["post_mixer_norm"]["scale"], hp["rms_norm_eps"])
        return h + norm(swiglu(h, p["mlp"]), p["post_mlp_norm"]["scale"], hp["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("matrix_bits",))
def head(x, scale, w_head, eps, matrix_bits=None):
    w_head = w_head.astype(jnp.float32)
    if matrix_bits is not None:
        w_head = round_mantissa(w_head, matrix_bits)
    with jax.default_matmul_precision("highest"):
        return norm(x, scale.astype(jnp.float32), eps) @ w_head


def hidden(params, tokens, hp, state_dtype=None, matrix_bits=None, layers=None):
    """tokens [B, T] int -> the last layer's output [B, T, d] float32 (or
    the output of the first ``layers`` layers)."""
    x = params["wte"][tokens].astype(jnp.float32)
    if matrix_bits is not None:
        x = round_mantissa(x, matrix_bits)
    for i in range(hp["num_hidden_layers"] if layers is None else layers):
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
    RMS of the output); ``beta_over_one``, the share of write strengths
    past 1 (the eigenvalues below 0 that ``linear_allow_neg_eigval``
    allows); and the RMS of the stream the layer's two unit-size branches
    are added to."""
    x = hidden(params, tokens, hp, layers=layer)
    p = _as_float32(params[f"block_{layer}"], None)
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole = delta_op(x, p["gdn"], hp)
        recent = delta_op(x, p["gdn"], hp, forget_at=tokens.shape[1] - older_than)
        b = (x @ p["gdn"]["w_ba"])[..., :hp["linear_num_value_heads"]]
    return dict(old_state_share=rms((whole - recent)[:, -1]) / rms(whole[:, -1]),
                beta_over_one=float(jnp.mean(b > 0.0)) if hp.get("linear_allow_neg_eigval") else 0.0,
                stream_rms=rms(x), mixer_rms=rms(whole))
