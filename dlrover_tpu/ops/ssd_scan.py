"""The selective state-space recurrence of a Mamba-2 mixer, two ways.

Per head ``h`` (``P`` channels, a state of ``[P, N]``), with ``delta_t``
a step size and ``A < 0`` a float per head:

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t
    y_t = S_t C_t

``B_t`` and ``C_t`` (``[N]``) are shared by the heads of a group. The
skip ``D x_t`` is the caller's. Both functions hold the state, the step
sizes and the decays in float32 and take ``exp`` of differences of
cumulated ``delta A`` only, never a quotient of two ``exp``.

**``delta_t = 0`` makes a token invisible**: the decay is ``exp(0) = 1``
and the input ``0``, so the state passes through unchanged. That is how
a caller pads (a padded prompt position, a row that is not live), and how
:func:`ssd_scan` fills ``T`` up to whole chunks.

:func:`ssd_scan` is the chunked form ("state-space duality"): inside a
chunk of ``Q`` tokens the recurrence is two block products, and only the
``T / Q`` chunk states are carried sequentially. :func:`ssd_step` is one
token of the same recurrence, written so that the state is read once and
written once (an elementwise update and a reduction over ``N`` that XLA
fuses into one pass).
"""

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "ssd_step"]


def _grouped(a, groups: int):
    """``[b, T, H, ...] -> [b, T, G, H / G, ...]``."""
    b, t, h = a.shape[:3]
    return a.reshape((b, t, groups, h // groups) + a.shape[3:])


def ssd_scan(x, delta, a, b_in, c_in, chunk: int, initial_state=None):
    """``x [b, T, H, P]``, ``delta [b, T, H]`` (float32, 0 at a padded
    token), ``a [H]`` (negative), ``b_in`` and ``c_in`` ``[b, T, G, N]``,
    ``initial_state [b, H, P, N]`` (zeros if None) ->
    (``y [b, T, H, P]`` float32, the state after the last token
    ``[b, H, P, N]`` float32).

    Per chunk of ``Q = min(chunk, T)`` tokens, with ``a_t`` the cumulated
    ``delta A`` inside the chunk: ``Y_diag = (L o (C B^T)) (delta x)``
    with ``L[i, j] = exp(a_i - a_j)`` for ``i >= j``; the chunk's own
    state ``sum_j exp(a_Q - a_j) (delta_j x_j) (x) B_j``; the carry
    ``S_c = exp(a_Q) S_{c-1} + that``; ``Y_off = exp(a_i) C_i S_{c-1}``.
    The operands are float32; on a TPU a float32 product at the default
    precision rounds its operands to bf16 and accumulates in float32,
    which is what the public kernels do.
    """
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    q = min(chunk, t)
    pad = -t % q
    f32 = jnp.float32
    x, delta, b_in, c_in = (v.astype(f32) for v in (x, delta, b_in, c_in))
    if pad:  # delta 0: the state passes through, the outputs are cut off
        x, delta, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, delta, b_in, c_in))
    nc = (t + pad) // q

    def chunks(v):  # [b, T, ...] -> [b, c, Q, ...]
        return v.reshape((bsz, nc, q) + v.shape[2:])

    xd = chunks(_grouped(x * delta[..., None], g))  # [b, c, Q, G, Hg, P]
    da = chunks(_grouped(delta * a.astype(f32), g))  # [b, c, Q, G, Hg]
    bc, cc = chunks(b_in), chunks(c_in)  # [b, c, Q, G, N]
    cum = jnp.cumsum(da, axis=2)  # a_i, inclusive
    total = cum[:, :, -1]  # a_Q [b, c, G, Hg]

    # inside the chunk: (L o C B^T) (delta x), heads before the [Q, Q] tile
    cum_h = jnp.moveaxis(cum, 2, -1)  # [b, c, G, Hg, Q]
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # a_i - a_j
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc)
    y = jnp.einsum("bcghij,bcjghp->bcighp", cb[:, :, :, None] * decay, xd)

    # each chunk's own state, then the carry over chunks
    to_end = jnp.exp(total[:, :, None] - cum)  # exp(a_Q - a_j) [b, c, j, G, Hg]
    own = jnp.einsum("bcjgn,bcjghp->bcghpn", bc, xd * to_end[..., None])
    s0 = (jnp.zeros((bsz, g, h // g, p, n), f32) if initial_state is None
          else initial_state.astype(f32).reshape(bsz, g, h // g, p, n))

    def carry(s, inputs):
        own_c, total_c = inputs
        return jnp.exp(total_c)[..., None, None] * s + own_c, s

    last, entering = jax.lax.scan(
        carry, s0, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)  # S_{c-1} [b, c, G, Hg, P, N]
    y = y + jnp.einsum("bcign,bcghpn->bcighp", cc, entering) * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :t]
    return y, last.reshape(bsz, h, p, n)


def ssd_step(state, x, delta, a, b_in, c_in):
    """One token: ``state [b, H, P, N]`` (float32), ``x [b, H, P]``,
    ``delta [b, H]`` (0 leaves the row's state alone), ``a [H]``,
    ``b_in`` and ``c_in`` ``[b, G, N]`` -> (``y [b, H, P]`` float32, the
    new state). Elementwise over the state and a sum over ``N``: no
    product's operand is rounded, and the state moves once in, once out."""
    f32 = jnp.float32
    bsz, h, p, n = state.shape
    g = b_in.shape[1]
    delta = delta.astype(f32)
    per_head = lambda v: jnp.repeat(v.astype(f32), h // g, axis=1)  # noqa: E731  [b, H, N]
    decay = jnp.exp(delta * a.astype(f32))  # [b, H]
    inject = (delta[..., None] * x.astype(f32))[..., None] * per_head(b_in)[:, :, None, :]
    new = decay[..., None, None] * state + inject
    y = jnp.sum(new * per_head(c_in)[:, :, None, :], axis=-1)
    return y, new
