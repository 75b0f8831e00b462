"""Plain reference of GPT-2: the forward pass and the next-token loss in
straightforward float32 ``jax.numpy``. No kernel, no cache, no remat, no
batching tricks; every matmul under ``default_matmul_precision("highest")``.

It follows Radford et al. 2019 / the Hugging Face ``GPT2LMHeadModel``:
learned position table, pre-LayerNorm blocks (eps 1e-5), causal softmax
attention with 1/sqrt(d_head), a 4x MLP with the tanh GELU (``gelu_new``),
a final LayerNorm and an output head tied to the embedding. Departures,
both the repo's: the projections q, k, v and the attention output carry
no bias (``models/gpt.py`` has none), and the vocabulary has the padded
rows the configuration lists under ``assumed``. The parameters are taken
as the program's init made them (weights are data here), in its layout:
``wqkv`` [D, 3, H, d], ``wo`` [H, d, D].
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p):
    t = x.shape[1]
    qkv = jnp.einsum("btd,dchk->cbthk", x, p["wqkv"])
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def _mlp(x, p):
    return _gelu_new(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def logits(params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][None, :t]
        layer = 0
        while f"block_{layer}" in params:
            p = params[f"block_{layer}"]
            x = x + _attention(_layer_norm(x, p["LayerNorm_0"]), p["CausalSelfAttention_0"])
            x = x + _mlp(_layer_norm(x, p["LayerNorm_1"]), p["Mlp_0"])
            layer += 1
        x = _layer_norm(x, params["ln_f"])
        return jnp.einsum("btd,vd->btv", x, params["wte"])


def token_losses(params, tokens, targets):
    """Per-token cross entropy [B, T] float32."""
    logp = jax.nn.log_softmax(logits(params, tokens), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def mean_loss(params, tokens, targets, rows_at_once: int = 2):
    """Mean next-token loss over the whole batch, a few rows at a time so
    that the [rows, T, V] logits fit a host."""
    f = jax.jit(token_losses)
    total, count = 0.0, 0
    for i in range(0, tokens.shape[0], rows_at_once):
        part = f(params, tokens[i:i + rows_at_once], targets[i:i + rows_at_once])
        total += float(jnp.sum(part))
        count += part.size
    return total / count
