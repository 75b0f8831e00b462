"""The gated delta-rule recurrence of a linear-attention mixer, two ways.

Per value head (``K`` key channels, ``V`` value channels, a state ``S``
of ``[K, V]``), with ``g_t <= 0`` a log-decay and ``beta_t`` in ``[0, 2]``
a write strength (in ``[0, 1]`` the write moves the state's answer at
``k_t`` towards ``v_t``; past 1 it overshoots, and the state's eigenvalue
along ``k_t``, ``1 - beta_t``, is negative: a reflection):

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)        what the state does not yet say of k_t
    S   <- S + k_t (x) u_t
    o_t  = S^T q_t

``q`` and ``k`` come in ``Hk`` key heads, everything else in ``Hv = r Hk``
value heads; value head ``j`` reads key head ``j // r``. Both functions
hold the state, the decays and the write strengths in float32 and take
``exp`` of differences of cumulated ``g`` only, never a quotient of two.

**``g_t = 0`` and ``beta_t = 0`` make a token invisible**: the decay is
``exp(0) = 1`` and ``u_t = 0``, so the state passes through unchanged.
That is how a caller pads (a padded prompt position, a row that is not
live), and how :func:`gated_delta_chunked` fills ``T`` up to whole chunks.

:func:`gated_delta_chunked` is the chunked ("WY") form. Inside a chunk of
``Q`` tokens entered with ``S_0``, with ``c_i`` the cumulated ``g``:
``(I + A) U = beta (V - exp(c) K S_0)`` where ``A[i, j] = beta_i
exp(c_i - c_j) (k_i . k_j)`` for ``j < i``; ``A`` is strictly lower
triangular, so nilpotent, and ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)
...``: ``log2 Q - 1`` squarings and as many products on the matrix unit
instead of a ``Q``-row substitution (``inverse="squaring"``, the legacy
path, kept only for the caller whose programs are pinned to it). That
product is exact in exact arithmetic and no better than its largest term in
float32: where a chunk's keys share a direction (``k_i . k_j`` about ``c``
for every pair) the entries of ``A^n`` grow like ``(beta c)^n C(Q, n)`` and
cancel to an inverse of size 1, so the rounding of the powers is what is
left (at ``beta c`` 0.3 they reach 1e6, at 0.8 1e15). ``beta`` up to 2
doubles every entry of ``A``. ``inverse="blocks"``, the default, forms no
power: rows of 16 by substitution (each row from the rows before it:
nothing larger than the inverse itself is ever formed), and two blocks'
inverses joined by ``X_21 = -X_22 A_21 X_11``, two levels for 64. Then
``O = exp(c) Q S_0 + (M o Q K^T) U`` with ``M[i, j] = exp(c_i - c_j)`` for ``j <= i``, and ``S_Q =
exp(c_Q) S_0 + (exp(c_Q - c) K)^T U``. Everything that does not read
``S_0`` is computed for all chunks at once; only three products a chunk
are carried sequentially. :func:`gated_delta_step` is one token of the
same recurrence, elementwise over the state.
"""

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_chunked", "gated_delta_step"]

_EXACT = jax.lax.Precision.HIGHEST


def _inverse_of_unit_lower(a):
    """``(I + a)^-1`` for ``a [..., Q, Q]`` strictly lower triangular
    (``a^Q = 0``): the product ``(I - a)(I + a^2)(I + a^4)...``. The
    products keep float32 operands whole (on a TPU the default would round
    them to bf16, and the error of one factor is multiplied by the rest)."""
    q = a.shape[-1]
    eye = jnp.eye(q, dtype=a.dtype)
    inv, power, covered = eye - a, a, 2  # inv = sum of (-a)^n for n < covered
    while covered < q:
        power = jnp.matmul(power, power, precision=_EXACT)
        inv = jnp.matmul(inv, eye + power, precision=_EXACT)
        covered *= 2
    return inv


_SUBSTITUTED_ROWS = 16


def _inverse_of_unit_lower_in_blocks(a):
    """``(I + a)^-1`` for ``a [..., Q, Q]`` strictly lower triangular, with
    no power of ``a``: up to ``_SUBSTITUTED_ROWS`` rows by substitution (row
    ``i`` of the inverse is ``e_i - a[i, :i] X[:i]``), larger ones from their
    two halves' inverses."""
    q = a.shape[-1]
    if q <= _SUBSTITUTED_ROWS:
        x = jnp.broadcast_to(jnp.eye(q, dtype=a.dtype), a.shape)
        for i in range(1, q):  # the rows from ``i`` on are still the identity's, and ``a[i, i:]`` is 0
            row = jnp.einsum("...j,...jk->...k", a[..., i, :], x, precision=_EXACT)
            x = x.at[..., i, :].add(-row)
        return x
    h = q // 2
    x11 = _inverse_of_unit_lower_in_blocks(a[..., :h, :h])
    x22 = _inverse_of_unit_lower_in_blocks(a[..., h:, h:])
    x21 = -jnp.matmul(jnp.matmul(x22, a[..., h:, :h], precision=_EXACT), x11, precision=_EXACT)
    top = jnp.concatenate([x11, jnp.zeros_like(a[..., :h, h:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([x21, x22], axis=-1)], axis=-2)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64, initial_state=None, inverse: str = "blocks"):
    """``q`` and ``k`` ``[b, T, Hk, K]`` (``k`` of unit length where the
    caller wants the delta rule's contraction), ``v [b, T, Hv, V]``, ``g``
    and ``beta`` ``[b, T, Hv]`` (float32; both 0 at a padded token),
    ``initial_state [b, Hv, K, V]`` (zeros if None) -> (``o [b, T, Hv, V]``
    float32, the state after the last token ``[b, Hv, K, V]`` float32).
    ``inverse``: ``"blocks"`` or the legacy ``"squaring"`` (the module's
    docstring says what each loses)."""
    invert = {"squaring": _inverse_of_unit_lower, "blocks": _inverse_of_unit_lower_in_blocks}[inverse]
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    n = min(chunk, t)
    pad = -t % n
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if pad:  # g 0 and beta 0: the state passes through, the outputs are cut off
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (t + pad) // n

    def chunks(a, *heads):  # [b, T, H..., ...] -> [b, c, H..., Q, ...]: heads before the tile
        a = a.reshape((bsz, nc, n) + heads + a.shape[3:])
        return jnp.moveaxis(a, 2, 2 + len(heads))

    qc, kc = chunks(q, hk), chunks(k, hk)  # [b, c, Hk, Q, K]
    vc = chunks(v, hk, r)  # [b, c, Hk, r, Q, V]
    gc, bc = chunks(g, hk, r), chunks(beta, hk, r)  # [b, c, Hk, r, Q]
    cum = jnp.cumsum(gc, axis=-1)  # c_i, inclusive
    total = cum[..., -1]  # c_Q [b, c, Hk, r]
    diff = cum[..., :, None] - cum[..., None, :]  # c_i - c_j
    lower = jnp.tril(jnp.ones((n, n), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # M: 0 above the diagonal

    kk = jnp.einsum("bchik,bchjk->bchij", kc, kc)[:, :, :, None]  # shared by a key head's r value heads
    qk = jnp.einsum("bchik,bchjk->bchij", qc, kc)[:, :, :, None]
    a = jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), bc[..., :, None] * decay * kk, 0.0)
    solve = invert(a) * bc[..., None, :]  # (I + A)^-1 diag(beta)
    u_own = jnp.einsum("bchrij,bchrjv->bchriv", solve, vc)  # U where S_0 = 0
    w = jnp.einsum("bchrij,bchjk->bchrik", solve * jnp.exp(cum)[..., None, :], kc)  # U = u_own - w S_0
    attend = decay * qk  # M o Q K^T
    q_in = qc[:, :, :, None] * jnp.exp(cum)[..., None]  # exp(c) Q  [b, c, Hk, r, Q, K]
    k_out = kc[:, :, :, None] * jnp.exp(total[..., None] - cum)[..., None]  # exp(c_Q - c) K

    s0 = (jnp.zeros((bsz, hk, r, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32).reshape(bsz, hk, r, dk, dv))

    def carry(s, inputs):
        u_own_c, w_c, attend_c, q_in_c, k_out_c, total_c = inputs
        u = u_own_c - jnp.einsum("bhrik,bhrkv->bhriv", w_c, s)
        o = jnp.einsum("bhrik,bhrkv->bhriv", q_in_c, s) + jnp.einsum("bhrij,bhrjv->bhriv", attend_c, u)
        s = jnp.exp(total_c)[..., None, None] * s + jnp.einsum("bhrik,bhriv->bhrkv", k_out_c, u)
        return s, o

    last, o = jax.lax.scan(
        carry, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (u_own, w, attend, q_in, k_out, total)))
    o = jnp.moveaxis(o, 0, 1)  # [b, c, Hk, r, Q, V]
    o = jnp.moveaxis(o, 4, 2).reshape(bsz, nc * n, hv, dv)[:, :t]
    return o, last.reshape(bsz, hv, dk, dv)


def gated_delta_step(state, q, k, v, g, beta):
    """One token: ``state [b, Hv, K, V]`` (float32), ``q`` and ``k``
    ``[b, Hk, K]``, ``v [b, Hv, V]``, ``g`` and ``beta`` ``[b, Hv]`` (both
    0 leave the row's state alone) -> (``o [b, Hv, V]`` float32, the new
    state). Elementwise over the state and sums over ``K``: no product's
    operand is rounded. ``o = S'^T q + (k . q) u`` with ``S' = exp(g) S``,
    so the two reads of the state (at ``k`` and at ``q``) are one pass and
    the write another."""
    f32 = jnp.float32
    hv = state.shape[1]
    per_value_head = lambda a: jnp.repeat(a.astype(f32), hv // a.shape[1], axis=1)  # noqa: E731
    q, k = per_value_head(q), per_value_head(k)  # [b, Hv, K]
    decayed = jnp.exp(g.astype(f32))[..., None, None] * state
    said = jnp.sum(decayed * k[..., None], axis=-2)  # S'^T k  [b, Hv, V]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - said)
    o = jnp.sum(decayed * q[..., None], axis=-2) + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]
