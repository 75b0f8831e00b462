"""Grouped matrix products over the experts a chip holds, and the two
row movements around them.

``grouped_matmul(lhs, rhs, group_sizes)``: ``lhs`` is ``[rows, k]`` with
its rows sorted by group, ``rhs`` is ``[groups, k, n]``, and the first
``group_sizes[0]`` rows meet ``rhs[0]``, the next ``group_sizes[1]`` rows
``rhs[1]``, and so on. Rows past ``sum(group_sizes)`` belong to no group:
what comes back in them is not defined (the Pallas kernel never visits
them, and they may hold NaN), so callers select them away: ``collect_rows``
does. On the TPU it is the ``megablox.gmm`` Pallas
kernel that ships with JAX, which walks only the row tiles that hold a
group's rows; elsewhere (tests, rehearsals) ``jax.lax.ragged_dot``. On
the v5e the kernel's three products took 3.9 ms forward and backward at
8,247 of 32,768 rows against ``ragged_dot``'s 5.8 ms (PERF.md, PR 27).

``spread_rows`` / ``collect_rows`` move rows between token order and the
sorted buffer; each is the other's transpose over the buffer's valid rows,
and both read one pass's index vectors from a :class:`RowOrder` made once
(``row_order``) and kept for the backward pass. Spreading is a gather and
nothing else: the rows past ``n_valid`` are the caller's to ignore, as the
grouped products do. Collecting is a sum over the rows of each token (each
times its gate, where the caller has one: ``_times_gate`` says in what
precision), which a TPU does badly as a scatter-add and wastefully as a
gather over every (token, choice) pair when few pairs have a row; here the
rows are sorted by token and summed per tile of 128 tokens by one-hot
products on the MXU (the ``megablox.tgmm`` kernel, whose groups are the
token tiles), after the one select a row meets on its way through the
buffer. Off the TPU, and on it where tokens or rows make no whole tiles (a
server's decode chunk), it is ``jax.ops.segment_sum``. Index vectors are
never gathered by a permutation: a sort carries them (``row_order``,
``sort_carrying``), because the v5e gathers scalars an element at a time
(65,536 integers 0.31 ms, 131,072 floats out of ``[16384, 64]`` 1.34 ms,
against 0.04-0.10 ms for a sort of either length with its operands: PERF.md,
PR 57).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..observability.spans import span

TOKEN_TILE = 128  # tokens a group of the collecting kernel covers


def _largest_dividing(x: int, candidates) -> int:
    return next((c for c in candidates if x % c == 0), x)


ROW_TILES = (512, 256, 128)  # the row tiles the kernels are given, widest first


def megablox_tiling(m: int, k: int, n: int, groups: int = 1):
    """(m, k, n) tiles of the Pallas kernels for one product's shapes; the
    kernels call this for the forward and for both backward products.

    The row tile follows the rows a group can hold: the largest of
    ``ROW_TILES`` that divides ``m`` and is at most ``max(128, m // groups)``
    (``m`` itself where none divides it). The kernel multiplies one whole
    ``[tm, tk] x [tk, tn]`` tile for every (group, row tile) it visits,
    however few of the tile's rows are the group's. At 2,048 rows a group
    (16 experts over 32,768 rows) 512 rows a tile keep an expert's weight
    block resident over four times the rows of the kernel's 128 default
    (PERF.md, PR 27). At ~6 rows a group (a block pass's 512 rows over 89 of
    128 experts of 2048 x 768) a product took 0.86 ms on the v5e in 512-row
    tiles, 0.49 in 256, 0.43 in 128 and 0.45 in 64, where the weights it
    reads ask 0.34 ms, and every tile gave the same bits: the floor is 128
    (PERF.md, PR 60, which has the served prefills' 40-64 rows a group too).
    ``groups`` 1 (the collecting kernel over token tiles) is the rule by
    ``m`` alone. k and n tiles are the largest multiples of 128 up to 1024
    that divide the dimension."""
    wide = (1024, 768, 512, 384, 256, 128)
    most = max(ROW_TILES[-1], m // groups)
    return (
        _largest_dividing(m, tuple(t for t in ROW_TILES if t <= most)),
        _largest_dividing(k, wide),
        _largest_dividing(n, wide),
    )


@functools.cache
def tiling_for(groups: int):
    """:func:`megablox_tiling` for products over ``groups`` groups, the same
    object for the same ``groups``: the kernels are jitted with their tiling
    static, and a fresh ``partial`` a call would trace every call anew."""
    return functools.partial(megablox_tiling, groups=groups)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_matmul(lhs, rhs, group_sizes):
    """``[rows, k] x [groups, k, n] -> [rows, n]`` in ``lhs``'s dtype."""
    group_sizes = group_sizes.astype(jnp.int32)
    if _on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, groups = lhs.shape[0], rhs.shape[0]
        tiling = tiling_for(groups)
        row_tile = tiling(m, *rhs.shape[1:])[0]
        # where a program is traced, never in a step: what the rule chose, and
        # the most (group, row tile) visits the kernel's grid can make. No
        # metric reads the span: tests are its readers (tests/test_mla_moe.py)
        with span("moe.gmm_built", m=m, groups=groups, row_tile=row_tile,
                  visits_bound=m // row_tile + groups - 1):
            return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("token_of", "n_valid", "by_token", "token_sorted", "tile_sizes"),
    meta_fields=("n_tokens",))
@dataclasses.dataclass(frozen=True)
class RowOrder:
    """Where one pass's buffer rows come from and go back to: made once a
    pass (:func:`row_order`), handed to :func:`spread_rows` and
    :func:`collect_rows`, and kept by both for their backward passes."""

    token_of: jax.Array  # [rows] the token each buffer row is a copy of
    n_valid: jax.Array  # rows from here on belong to no token
    by_token: jax.Array  # [rows] the buffer's rows in token order, those of no token last
    token_sorted: jax.Array  # [rows] ``token_of[by_token]``; ``n_tokens`` for a row of no token
    tile_sizes: jax.Array  # [n_tokens // TOKEN_TILE] rows in each tile of tokens
    n_tokens: int


def row_order(token_of, n_valid, n_tokens: int) -> RowOrder:
    """The index vectors of one pass. One sort carries the rows' numbers
    beside their tokens, so nothing is gathered by a permutation here (on
    the v5e a gather of 65,536 integers took 0.31 ms, the sort 0.04)."""
    n_rows = token_of.shape[0]
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    token = jnp.where(rows < n_valid, token_of.astype(jnp.int32), n_tokens)  # rows of no token go last
    token_sorted, by_token = jax.lax.sort((token, rows), num_keys=1, is_stable=True)
    tile_sizes = jnp.sum(
        (token_sorted // TOKEN_TILE)[:, None] == jnp.arange(n_tokens // TOKEN_TILE)[None, :],
        axis=0, dtype=jnp.int32)
    return RowOrder(token_of, n_valid, by_token, token_sorted, tile_sizes, n_tokens)


def _by_kernel(n_tokens: int, n_rows: int) -> bool:
    """Whether rows go back by the ``tgmm`` kernel (whole tiles of tokens
    and of rows, on the TPU) or by a segment sum."""
    return _on_tpu() and n_tokens % TOKEN_TILE == 0 and n_rows % 128 == 0


def _collect(rows, order: RowOrder):
    n_rows, n_tokens = rows.shape[0], order.n_tokens
    valid = jnp.arange(n_rows) < order.n_valid
    # the one select a row meets on its way through the buffer, and never a
    # multiply: what a row of no token holds is not defined and may be NaN.
    # In buffer order, so that it rides in the fusion that made the rows:
    # behind the gather by ``by_token`` it is a pass of its own (0.9 ms at
    # ``bf16[65536, 2304]`` on the v5e: PERF.md, PR 57)
    rows = jnp.where(valid[:, None], rows, 0)
    if not _by_kernel(n_tokens, n_rows):
        token = jnp.where(valid, order.token_of, n_tokens)  # rows of no token go last
        return jax.ops.segment_sum(rows, token, num_segments=n_tokens + 1)[:n_tokens]
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    token = order.token_sorted
    # [TOKEN_TILE, rows]: row j adds to the token it belongs to within its tile
    place = ((token % TOKEN_TILE)[None, :] == jnp.arange(TOKEN_TILE)[:, None]) & (token < n_tokens)[None, :]
    out = tgmm(
        place.astype(rows.dtype), rows[order.by_token], order.tile_sizes, rows.dtype, megablox_tiling)
    return out.reshape(n_tokens, rows.shape[1])


def _times_gate(rows, gate, order: RowOrder):
    """``rows * gate[:, None]`` in the rows' dtype, rounded once; the gate of
    a row of no token is selected to zero first, so that what such a row
    holds never reaches a gate's cotangent.

    Which gate the rows meet is said here, where the way back is chosen, and
    not left to a fusion. Written ``rows * gate.astype(rows.dtype)``, the
    cast is honoured off the chip and in front of the chip's kernel, where
    the gate is materialised, and inside a segment sum's fusion on the chip
    XLA drops it (``xla_allow_excess_precision``) if the gate is computed
    there, and lifts it out and keeps it if the gate arrives from a sort, as
    it does now. The served cells' expected values stand on the float32
    product of the first, the tests' pinned streams on the rounded one
    (PERF.md section 6, PR 57). So the gate is float32 in a segment sum on
    the TPU and rounded to the rows' dtype everywhere else, by
    ``reduce_precision``, which no fusion drops."""
    n_rows = rows.shape[0]
    gate = jnp.where(jnp.arange(n_rows) < order.n_valid, gate, 0.0)
    if not _on_tpu() or _by_kernel(order.n_tokens, n_rows):
        bits = jnp.finfo(rows.dtype)
        gate = jax.lax.reduce_precision(gate, bits.nexp, bits.nmant)
    return (rows.astype(jnp.float32) * gate[:, None]).astype(rows.dtype)


@jax.custom_vjp
def spread_rows(x, order: RowOrder):
    """``out[j] = x[order.token_of[j]]``, ``[rows, d]``. The rows from
    ``order.n_valid`` on are copies of some token too and are the caller's
    to ignore: the grouped products never visit them and
    :func:`collect_rows` selects them away."""
    return x[order.token_of]


def _spread_fwd(x, order):
    return x[order.token_of], order


def _spread_bwd(order, g):
    return _collect(g, order), None


spread_rows.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _sum_rows(rows, order: RowOrder):
    return _collect(rows, order)


def _sum_rows_fwd(rows, order):
    return _collect(rows, order), order


def _sum_rows_bwd(order, g):
    return g[order.token_of], None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def collect_rows(rows, order: RowOrder, gate=None):
    """``out[t] = sum of gate[j] * rows[j] over the j < order.n_valid with
    order.token_of[j] == t``, ``[order.n_tokens, d]`` (no ``gate``: of
    ``rows[j]``, the transpose of :func:`spread_rows` over the valid rows).
    Whatever the other rows hold, NaN included, adds nothing, to the sum or
    to a gradient. ``gate`` is ``[rows]`` float32: :func:`_times_gate`."""
    if gate is not None:
        rows = _times_gate(rows, gate, order)
    return _sum_rows(rows, order)


@jax.custom_vjp
def sort_carrying(key, values):
    """``(order, values[order])`` for ``order = argsort(key)``, stable: one
    sort gives the permutation and moves the values along it, and the
    backward pass brings their cotangents home by a sort along the
    permutation, where a gather by ``order`` and the scatter behind it
    would each walk the vector an element at a time."""
    order = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, order, values = jax.lax.sort((key, order, values), num_keys=1, is_stable=True)
    return order, values


def _sort_carrying_fwd(key, values):
    order, values = sort_carrying(key, values)
    return (order, values), order


def _sort_carrying_bwd(order, g):
    _, g_values = jax.lax.sort((order, g[1]), num_keys=1)
    return None, g_values


sort_carrying.defvjp(_sort_carrying_fwd, _sort_carrying_bwd)
