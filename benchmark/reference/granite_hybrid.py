"""Plain reference of the ``granitemoehybrid`` decoder without routed
experts (Mamba-2 mixers, grouped-query attention without positions, a
SwiGLU, the family's four multipliers): the forward pass in straightforward
float32 ``jax.numpy``. No kernel, no cache, no chunked scan, no batching
trick, no padding; every product under
``default_matmul_precision("highest")``; **the recurrence token by token**
(a ``lax.scan`` over ``T`` that carries ``S``), so that it shares nothing
with the chunked algorithm of ``dlrover_tpu/ops/ssd_scan.py``.

The equations (``hp`` holds the published keys; ``d`` = ``hidden_size``):
RMSNorm ``x rsqrt(mean(x^2) + rms_norm_eps) w``;
``h_0 = embedding_multiplier wte[tokens]``; layer ``i``:
``h += residual_multiplier Mix_i(RMSNorm(h))``,
``h += residual_multiplier MLP(RMSNorm(h))``; one RMSNorm after the last
layer, then ``logits = h wte^T / logits_scaling``.

- ``mamba`` (``H`` = ``mamba_n_heads``, ``P`` = ``mamba_d_head``, ``N`` =
  ``mamba_d_state``, ``G`` = ``mamba_n_groups``, ``K`` = ``mamba_d_conv``):
  ``[z ; xBC ; dt] = u W_in``; ``xBC <- silu(sum_k k_k xBC_{t-K+1+k} +
  b_c)`` with ``xBC`` zero before the first token; ``[x ; B ; C] = xBC``;
  ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`` from ``S = 0``,
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y silu(z)) w_g``; ``W_out``.
- ``attention``: q in ``num_attention_heads`` heads, k and v in
  ``num_key_value_heads``, no position encoding; scores
  ``q k^T attention_multiplier``, causal softmax; each kv head serves
  ``heads / kv heads`` query heads.
- ``MLP``: ``(silu(x W_gate) x W_up) W_down`` of
  ``shared_intermediate_size``.

Departures, all the repo's and all under ``assumed`` in the configuration's
file: head size ``d / heads``; the init.

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

MATRICES = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "wte")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def round_mantissa(a, bits: int):
    """``a`` (float32) rounded to ``bits`` explicit mantissa bits, ties to even."""
    i = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    half = jnp.uint32((1 << (drop - 1)) - 1) + ((i >> drop) & 1)
    return jax.lax.bitcast_convert_type(((i + half) >> drop) << drop, jnp.float32)


def recurrence(x, delta, a, b_in, c_in, state_dtype=None, forget_at=None):
    """Token by token: ``x [b, T, H, P]``, ``delta [b, T, H]``, ``a [H]``,
    ``b_in`` and ``c_in`` ``[b, T, G, N]`` -> (``y [b, T, H, P]``, the last
    state ``[b, H, P, N]``)."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    per_head = lambda v: jnp.repeat(v, h // g, axis=1)  # noqa: E731  [b, G, N] -> [b, H, N]

    def one(s, inputs):
        i, x_t, d_t, b_t, c_t = inputs
        if forget_at is not None:
            s = jnp.where(i == forget_at, 0.0, s)
        s = (jnp.exp(d_t * a)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * per_head(b_t)[:, :, None, :])
        if state_dtype is not None:  # not astype there and back: XLA may keep the excess precision
            s = jax.lax.reduce_precision(s, jnp.finfo(state_dtype).nexp, jnp.finfo(state_dtype).nmant)
        return s, jnp.sum(s * per_head(c_t)[:, :, None, :], axis=-1)

    steps = (jnp.arange(t), *(jnp.moveaxis(v, 1, 0) for v in (x, delta, b_in, c_in)))
    last, y = jax.lax.scan(one, jnp.zeros((bsz, h, p, n), jnp.float32), steps)
    return jnp.moveaxis(y, 0, 1), last


def mamba_op(u, p, hp, state_dtype=None, forget_at=None):
    bsz, t, _ = u.shape
    h, hd, n, g = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"], hp["mamba_n_groups"]
    k, inner = hp["mamba_d_conv"], hp["mamba_n_heads"] * hp["mamba_d_head"]
    width = inner + 2 * g * n
    zxd = u @ p["w_in"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + width], zxd[..., inner + width:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + t] for j in range(k)) + p["conv_bias"])
    x = xbc[..., :inner].reshape(bsz, t, h, hd)
    b_in = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
    c_in = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y, _ = recurrence(x, delta, -jnp.exp(p["A_log"]), b_in, c_in, state_dtype, forget_at)
    y = y + p["D"][:, None] * x
    y = rms_norm(y.reshape(bsz, t, inner) * jax.nn.silu(z), p["gate_norm"]["scale"], hp["rms_norm_eps"])
    return y @ p["w_out"]


def attention_op(u, p, hp):
    t = u.shape[1]
    q = jnp.einsum("btd,dhk->bthk", u, p["wq"])
    k = jnp.einsum("btd,dgk->btgk", u, p["wk"])
    v = jnp.einsum("btd,dgk->btgk", u, p["wv"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * hp["attention_multiplier"]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def layer_types(hp):
    n = hp["num_hidden_layers"]
    return list(hp.get("layer_types") or
                ["attention" if i % 10 == 5 else "mamba" for i in range(n)])[:n]


def _hashable(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in hp.items()
                        if isinstance(v, (int, float, bool, str, list, tuple))))


def _as_float32(p, matrix_bits):
    def one(path, a):
        a = a.astype(jnp.float32)
        if matrix_bits is not None and getattr(path[-1], "key", None) in MATRICES:
            a = round_mantissa(a, matrix_bits)
        return a

    return jax.tree_util.tree_map_with_path(one, p)


@functools.partial(jax.jit, static_argnames=("hp_items", "kind", "state_dtype", "matrix_bits"))
def _layer(x, p, hp_items, kind, state_dtype, matrix_bits, forget_at):
    hp = dict(hp_items)
    p = _as_float32(p, matrix_bits)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["input_norm"]["scale"], hp["rms_norm_eps"])
        mix = (mamba_op(u, p["mamba"], hp, state_dtype, forget_at) if kind == "mamba"
               else attention_op(u, p["attn"], hp))
        x = x + hp["residual_multiplier"] * mix
        h = rms_norm(x, p["post_norm"]["scale"], hp["rms_norm_eps"])
        return x + hp["residual_multiplier"] * swiglu(h, p["mlp"])


@functools.partial(jax.jit, static_argnames=("matrix_bits",))
def head(x, scale, wte, eps, logits_scaling, matrix_bits=None):
    wte = wte.astype(jnp.float32)
    if matrix_bits is not None:
        wte = round_mantissa(wte, matrix_bits)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("btd,vd->btv", rms_norm(x, scale.astype(jnp.float32), eps), wte) / logits_scaling


def hidden(params, tokens, hp, state_dtype=None, matrix_bits=None):
    """tokens [B, T] int -> the last layer's output [B, T, d] float32."""
    wte = params["wte"][tokens].astype(jnp.float32)
    if matrix_bits is not None:
        wte = round_mantissa(wte, matrix_bits)
    x = hp["embedding_multiplier"] * wte
    for i, kind in enumerate(layer_types(hp)):
        x = _layer(x, params[f"block_{i}"], _hashable(hp), kind, state_dtype, matrix_bits, -1)
    return x


def logits(params, tokens, hp, at=None, state_dtype=None, matrix_bits=None):
    """tokens [B, T] int -> logits [B, T, V] float32, or ``[B, len(at), V]``
    at the positions ``at`` (one position or a list), for a vocabulary too
    wide to keep T of."""
    x = hidden(params, tokens, hp, state_dtype, matrix_bits)
    if at is not None:
        x = x[:, jnp.atleast_1d(jnp.asarray(at))]
    return head(x, params["final_norm"]["scale"], params["wte"], hp["rms_norm_eps"],
                hp["logits_scaling"], matrix_bits)


def old_state_share(params, tokens, hp, older_than: int = 64, layer: int = 0):
    """What share of Mamba layer ``layer``'s output (the mixer's, before the
    residual multiplier) at the last token comes from state older than
    ``older_than`` tokens: RMS of (the output - the output with ``S`` zeroed
    ``older_than`` tokens before the end) over RMS of the output. ``tokens
    [B, T]`` with ``T > older_than``; the layers before ``layer`` run as
    they are."""
    x = hp["embedding_multiplier"] * params["wte"][tokens].astype(jnp.float32)
    kinds = layer_types(hp)
    for i in range(layer):
        x = _layer(x, params[f"block_{i}"], _hashable(hp), kinds[i], None, None, -1)
    p = _as_float32(params[f"block_{layer}"], None)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["input_norm"]["scale"], hp["rms_norm_eps"])
        whole = mamba_op(u, p["mamba"], hp)[:, -1]
        recent = mamba_op(u, p["mamba"], hp, forget_at=tokens.shape[1] - older_than)[:, -1]
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))  # noqa: E731
    return rms(whole - recent) / rms(whole)
