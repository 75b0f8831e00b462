"""Plain reference of the ``sdar_moe`` decoder (grouped-query attention with
per-head q/k norms under a mask by blocks, routed experts in every layer)
and of **generation by diffusion over blocks**: the forward pass in
straightforward float32 ``jax.numpy`` and the decoding procedure by *whole
recomputation*. No kernel, no cache, no batching; every product under
``default_matmul_precision("highest")``; every expert computed densely for
every token and weighted by its gate, which is zero where the token did not
choose it. Imports nothing of ``dlrover_tpu``.

The equations (``hp`` holds the published keys; ``d`` = ``hidden_size``):
RMSNorm ``x rsqrt(mean(x^2) + rms_norm_eps) w``; layer ``l``:
``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; one RMSNorm after
the last layer, then the head (untied). The logits at position ``i`` score
the token AT position ``i`` (no shift).

- ``Attn``: q in ``num_attention_heads`` heads of ``head_dim``, k and v in
  ``num_key_value_heads``; q and k RMS-normed per head, each with its own
  vector, before RoPE (rotate-half: channel ``i`` pairs with ``i + d_h/2``,
  angle ``t theta^(-2i/d_h)``); scores over ``sqrt(d_h)``, softmax; each kv
  head serves ``heads / kv heads`` query heads. **The mask**: with ``Bl =
  block_length``, row ``i`` sees key ``j`` iff ``j // Bl <= i // Bl``.
- ``MoE``: ``p = softmax(z W_r)`` over all experts, the top
  ``num_experts_per_tok``, gates ``p`` of the chosen over their sum
  (``norm_topk_prob``), ``y = sum g_e W_down_e (silu(z W_gate_e) * (z W_up_e))``.

**Generation** (:func:`generate`; ``Bl`` = ``block_length``, ``S`` =
``denoising_steps``, the static low-confidence schedule of the family's
published ``generate.py``), greedy::

    prompt x[0:P], n_new tokens wanted; n_blocks = ceil((P + n_new) / Bl); b0 = P // Bl
    y = x, then undecided up to n_blocks * Bl
    for b = b0 .. n_blocks - 1:
        U = the undecided positions of block b
        for t = 0 .. S - 1, while U is not empty:
            logits = f(y with mask_token_id at every undecided position)[block b]
            for i in U: c_i = argmax logits_i;  p_i = softmax(logits_i)[c_i]
            n_t = ceil(|U| / (S - t)); fix the n_t of U with the largest p_i (a tie: the lowest i):
                  y_i = c_i, logprob_i = log p_i, pass_i = t
    emit y[P : P + n_new], logprob and pass of each

``f`` runs the whole sequence up to the end of block ``b`` anew at every
pass (the blocks after ``b`` are seen by nobody under the mask, so the
sequence may be padded behind block ``b`` to a fixed length: ``pad_to``,
which saves a compile a length and changes no number).

Departures from the published description, all the repo's and all under
``assumed`` in the configuration's file: ``block_length``,
``denoising_steps``, ``mask_token_id`` and the schedule (the config gives
none of them); the per-head q/k norms (the modeling file's, no config key);
no shift of the logits.

The parameters are taken as the program's init made them (weights are data
here), in its layout, and walked a layer at a time: one layer's leaves are
brought to float32, used and dropped (the experts' matrices a few experts
at a time). ``matrix_bits`` rounds every matrix to that many mantissa bits
first, ``causal`` attends causally inside a block too: two controls that the
benchmark's limits have to refuse.
"""

import functools
import math

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


def attention_op(u, p, hp, causal=False):
    t = u.shape[1]
    q = rms_norm(jnp.einsum("btd,dhk->bthk", u, p["wq"]), p["q_norm"]["scale"], hp["rms_norm_eps"])
    k = rms_norm(jnp.einsum("btd,dgk->btgk", u, p["wk"]), p["k_norm"]["scale"], hp["rms_norm_eps"])
    v = jnp.einsum("btd,dgk->btgk", u, p["wv"])
    q, k = rope(q, hp["rope_theta"]), rope(k, hp["rope_theta"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    at = jnp.arange(t) if causal else jnp.arange(t) // hp["block_length"]
    scores = jnp.where((at[None, :] <= at[:, None])[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def gates(x, p, hp):
    """[..., E]: each token's gate at every expert, zero where not chosen."""
    s = jax.nn.softmax(x @ p["w_router"], axis=-1)
    _, idx = jax.lax.top_k(s, hp["num_experts_per_tok"])
    g = s * jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=-2)
    if hp.get("norm_topk_prob", True):
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def experts_op(x, p, hp, at_once: int = 8):
    """Every expert for every token, weighted by its gate; ``at_once``
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


def round_bits(a, bits):
    """``a`` with its mantissa cut to ``bits`` bits (a cast down and up again is elided by XLA)."""
    return jax.lax.reduce_precision(a.astype(jnp.float32), exponent_bits=8, mantissa_bits=bits).astype(a.dtype)


def _hashable(hp):
    return tuple(sorted((k, v) for k, v in hp.items() if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnames=("hp_items", "matrix_bits", "causal"))
def _layer(x, p, hp_items, matrix_bits, causal):
    hp = dict(hp_items)
    if matrix_bits is not None:  # the control: every matrix held in fewer bits
        p = jax.tree_util.tree_map(lambda a: round_bits(a, matrix_bits) if a.ndim >= 2 else a, p)
    # (the experts' matrices are brought to float32 a few experts at a time, inside)
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: a if a.ndim == 3 and any(getattr(k, "key", None) == "moe" for k in path)
        else a.astype(jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        x = x + attention_op(rms_norm(x, p["input_norm"]["scale"], hp["rms_norm_eps"]), p["attn"], hp, causal)
        return x + experts_op(rms_norm(x, p["post_attention_norm"]["scale"], hp["rms_norm_eps"]), p["moe"], hp)


@functools.partial(jax.jit, static_argnames=("matrix_bits",))
def head(x, scale, w, eps, matrix_bits=None):
    if matrix_bits is not None:
        w = round_bits(w, matrix_bits)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("btd,dv->btv", rms_norm(x, scale.astype(jnp.float32), eps), w.astype(jnp.float32))


def logits(params, tokens, hp, at=None, matrix_bits=None, causal=False):
    """tokens [B, T] int -> logits [B, T, V] float32, or ``[B, len(at), V]``
    at the positions ``at``, for a vocabulary too wide to keep T of."""
    wte = params["wte"] if matrix_bits is None else round_bits(params["wte"], matrix_bits)
    x = wte[tokens].astype(jnp.float32)
    for i in range(hp["num_hidden_layers"]):
        x = _layer(x, params[f"block_{i}"], _hashable(hp), matrix_bits, causal)
    if at is not None:
        x = x[:, jnp.asarray(at)]
    return head(x, params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"], matrix_bits)


def generate(params, prompt, n_new, hp, pad_to=0, forced=None, matrix_bits=None, causal=False):
    """The procedure of the module's docstring for one prompt (a list of
    ids), greedy. -> a dict: ``tokens``, ``logprobs``, ``passes`` (of the
    ``n_new`` emitted), and for the comparison that has to tell a fault from
    a near-tie, ``top2_gap`` (of each emitted token: how far the logit of
    the token chosen lay above the next, at the pass that fixed it) and
    ``select_gap`` (how far, in log-probability, the least confident position
    that pass fixed lay above the most confident it left undecided; inf
    where it left none), and ``block_logits``: one ``(block, pass, [Bl, V])``
    a pass, when ``forced`` is given or ``hp`` asks for them
    (``keep_block_logits``). ``forced`` (a list like the result's
    ``decisions``) replays another's decisions instead of taking its own."""
    Bl, S, mask_id = hp["block_length"], hp["denoising_steps"], hp["mask_token_id"]
    P = len(prompt)
    n_blocks = -(-(P + n_new) // Bl)
    total = max(n_blocks * Bl, pad_to)
    y = list(prompt) + [mask_id] * (total - P)
    decided = [True] * P + [False] * (total - P)
    logprob, at_pass, top2, select = {}, {}, {}, {}
    decisions, kept = [], []
    for b in range(P // Bl, n_blocks):
        block = list(range(b * Bl, (b + 1) * Bl))
        for t in range(S):
            U = [i for i in block if not decided[i]]
            if not U:
                break
            fed = jnp.asarray([[tok if ok else mask_id for tok, ok in zip(y, decided)]], jnp.int32)
            z = logits(params, fed, hp, at=block, matrix_bits=matrix_bits, causal=causal)[0]  # [Bl, V]
            if forced is not None or hp.get("keep_block_logits"):
                kept.append((b, t, z))
            lp = jax.nn.log_softmax(z, axis=-1)
            best2, c = jax.lax.top_k(z, 2)
            c, conf, gap = (jax.device_get(a) for a in (c[:, 0], jnp.max(lp, axis=-1), best2[:, 0] - best2[:, 1]))
            n_t = math.ceil(len(U) / (S - t))
            if forced is not None:
                fix = forced[len(decisions)]
                chosen = [(i, tok) for i, tok in zip(fix["positions"], fix["tokens"])]
            else:
                ranked = sorted(U, key=lambda i: (-conf[i - b * Bl], i))
                chosen = [(i, int(c[i - b * Bl])) for i in sorted(ranked[:n_t])]
            left = [i for i in U if i not in dict(chosen)]
            margin = (min(conf[i - b * Bl] for i, _ in chosen) - max(conf[i - b * Bl] for i in left)
                      if left else float("inf"))
            for i, tok in chosen:
                y[i], decided[i] = tok, True
                logprob[i], at_pass[i] = float(lp[i - b * Bl, tok]), t
                top2[i], select[i] = float(gap[i - b * Bl]), float(margin)
            decisions.append(dict(block=b, at_pass=t, positions=[i for i, _ in chosen],
                                  tokens=[tok for _, tok in chosen]))
    out = range(P, P + n_new)
    return dict(tokens=[int(y[i]) for i in out], logprobs=[logprob[i] for i in out],
                passes=[at_pass[i] for i in out], top2_gap=[top2[i] for i in out],
                select_gap=[select[i] for i in out], decisions=decisions, block_logits=kept)
