"""Grouped matrix products over the experts a chip holds, and the two
row movements around them.

``grouped_matmul(lhs, rhs, group_sizes)``: ``lhs`` is ``[rows, k]`` with
its rows sorted by group, ``rhs`` is ``[groups, k, n]``, and the first
``group_sizes[0]`` rows meet ``rhs[0]``, the next ``group_sizes[1]`` rows
``rhs[1]``, and so on. Rows past ``sum(group_sizes)`` belong to no group:
what comes back in them is not defined (the Pallas kernel never visits
them), so callers mask them. On the TPU it is the ``megablox.gmm`` Pallas
kernel that ships with JAX, which walks only the row tiles that hold a
group's rows; elsewhere (tests, rehearsals) ``jax.lax.ragged_dot``. On
the v5e the kernel's three products took 3.9 ms forward and backward at
8,247 of 32,768 rows against ``ragged_dot``'s 5.8 ms (PERF.md, PR 27).

``spread_rows`` / ``collect_rows`` move rows between token order and the
sorted buffer; each is the other's transpose. Spreading is a gather.
Collecting is a sum over the rows of each token, which a TPU does badly
as a scatter-add and wastefully as a gather over every (token, choice)
pair when few pairs have a row; here the rows are sorted by token and
summed per tile of 128 tokens by one-hot products on the MXU (the
``megablox.tgmm`` kernel, whose groups are the token tiles). Off the TPU
it is ``jax.ops.segment_sum``.
"""

import functools

import jax
import jax.numpy as jnp

TOKEN_TILE = 128  # tokens a group of the collecting kernel covers


def _largest_dividing(x: int, candidates) -> int:
    return next((c for c in candidates if x % c == 0), x)


def megablox_tiling(m: int, k: int, n: int):
    """(m, k, n) tiles of the Pallas kernels for one product's shapes; the
    kernels call this for the forward and for both backward products.
    512 rows a tile keep an expert's weight block resident over four
    times the rows of the kernel's 128 default; k and n tiles are the
    largest multiples of 128 up to 1024 that divide the dimension."""
    wide = (1024, 768, 512, 384, 256, 128)
    return (
        _largest_dividing(m, (512, 256, 128)),
        _largest_dividing(k, wide),
        _largest_dividing(n, wide),
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_matmul(lhs, rhs, group_sizes):
    """``[rows, k] x [groups, k, n] -> [rows, n]`` in ``lhs``'s dtype."""
    group_sizes = group_sizes.astype(jnp.int32)
    if _on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(lhs, rhs, group_sizes, lhs.dtype, megablox_tiling)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)


def _spread(x, token_of, n_valid):
    valid = jnp.arange(token_of.shape[0]) < n_valid
    return jnp.where(valid[:, None], x[token_of], 0)


def _collect(rows, token_of, n_valid, n_tokens: int):
    n_rows = rows.shape[0]
    valid = jnp.arange(n_rows) < n_valid
    token = jnp.where(valid, token_of, n_tokens)  # rows of no token go last
    if not _on_tpu() or n_tokens % TOKEN_TILE or n_rows % 128:
        rows = jnp.where(valid[:, None], rows, 0)
        return jax.ops.segment_sum(rows, token, num_segments=n_tokens + 1)[:n_tokens]
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    by_token = jnp.argsort(token)
    token = token[by_token]
    has_token = token < n_tokens
    rows = jnp.where(has_token[:, None], rows[by_token], 0)
    tiles = n_tokens // TOKEN_TILE
    tile_sizes = jnp.sum(
        (token // TOKEN_TILE)[:, None] == jnp.arange(tiles)[None, :], axis=0, dtype=jnp.int32)
    # [TOKEN_TILE, rows]: row j adds to the token it belongs to within its tile
    place = ((token % TOKEN_TILE)[None, :] == jnp.arange(TOKEN_TILE)[:, None]) & has_token[None, :]
    out = tgmm(place.astype(rows.dtype), rows, tile_sizes, rows.dtype, megablox_tiling)
    return out.reshape(n_tokens, rows.shape[1])


@jax.custom_vjp
def spread_rows(x, token_of, n_valid):
    """``out[j] = x[token_of[j]]`` for the first ``n_valid`` rows of the
    sorted buffer, zero after."""
    return _spread(x, token_of, n_valid)


def _spread_fwd(x, token_of, n_valid):
    return _spread(x, token_of, n_valid), (token_of, n_valid, x.shape[0])


def _spread_bwd(res, g):
    token_of, n_valid, n_tokens = res
    return _collect(g, token_of, n_valid, n_tokens), None, None


spread_rows.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def collect_rows(rows, token_of, n_valid, n_tokens: int):
    """``out[t] = sum of rows[j] over the j < n_valid with token_of[j] == t``,
    ``[n_tokens, d]``: the transpose of :func:`spread_rows`."""
    return _collect(rows, token_of, n_valid, n_tokens)


def _collect_fwd(rows, token_of, n_valid, n_tokens):
    return _collect(rows, token_of, n_valid, n_tokens), (token_of, n_valid)


def _collect_bwd(n_tokens, res, g):
    token_of, n_valid = res
    return _spread(g, token_of, n_valid), None, None


collect_rows.defvjp(_collect_fwd, _collect_bwd)
