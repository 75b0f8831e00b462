"""Paged KV-cache building blocks (vLLM's serving-memory idea).

The slot-dense engine layout (``per_row``) reserves a full
``[max_seq_len]`` cache row per batch slot, so HBM pays worst-case
padding on every admission and a shared prompt prefix is stored once
per row. The ``paged`` layout breaks the cache into fixed-size token
BLOCKS drawn from one pool:

- :class:`BlockPool` — the host-side allocator: a free list plus
  per-block refcounts. Admission is bounded by free *blocks*, not by
  decode slots; a registered prefix's fully-covered blocks are
  refcounted and shared across every row using it (copy-on-write: rows
  never write inside a shared block — decode writes start past the
  prefix — and the partially-filled tail block is the per-row "copy").
- :func:`gather_cache` / :func:`scatter_cache` — the jit-side halves:
  a per-request block table ``[B, L // block_size]`` indexes the pool
  ``(num_blocks, block_size, ...)``; gather materializes the dense
  ``[B, L, ...]`` view the shared decode-chunk body runs on, scatter
  writes it back. Block 0 is the TRASH block: unallocated table
  entries point at it, so a retired row's parked writes (the chunk
  body keeps stepping done rows — static shapes) land somewhere
  harmless, and ``kv_valid`` masks whatever gather reads from it.
- :func:`pack_row_state` / :func:`unpack_row_state` — host-portable
  serialization of one prefilled row (cache + logits + position + kv
  mask), the prefill/decode disaggregation hand-off payload: a
  prefill-role replica fills a prompt's row and ships it to a
  decode-role replica over the gateway's existing HTTP plumbing.

Everything here is deliberately framework-thin: the pool is plain
Python (the scheduler already runs the host side of admission), and
the gather/scatter are pure ``jnp`` tree maps traced INTO the decode
chunk program — one dispatch per chunk, same as the dense layouts.
"""

import base64
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TRASH_BLOCK",
    "BlockPool",
    "blocks_for",
    "build_table_row",
    "gather_cache",
    "scatter_cache",
    "pack_row_state",
    "unpack_row_state",
]

# block id 0 is never allocated: every unpopulated block-table entry
# points here, so stray writes (done rows' clamped write slot, table
# rows parked at retirement) have a harmless destination
TRASH_BLOCK = 0


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks covering ``tokens`` cache positions (ceil division)."""
    return -(-int(tokens) // int(block_size))


class BlockPool:
    """Host-side allocator for the paged KV pool.

    Refcounted: ``alloc`` hands out blocks at refcount 1, ``share``
    bumps the count (a row joining a registered prefix's blocks), and
    ``free`` decrements — a block returns to the free list only when
    its LAST holder releases it, which is what makes prefix sharing
    safe against any retire/unregister order.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks {num_blocks} must be >= 2 (block 0 is "
                f"the reserved trash block)"
            )
        if block_size < 1:
            raise ValueError(f"block_size {block_size} must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # LIFO free list: recently-freed blocks are re-used first
        # (their pool pages are the warmest)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}

    @property
    def blocks_total(self) -> int:
        """Allocatable blocks (the trash block is not one)."""
        return self.num_blocks - 1

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` fresh blocks at refcount 1, or None (and take
        NOTHING) when fewer than ``n`` are free — admission either
        gets its whole table or leaves the pool untouched."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        return ids

    def share(self, ids: List[int]) -> None:
        """Add one holder to each of ``ids`` (must be live)."""
        for b in ids:
            if self._ref.get(b, 0) <= 0:
                raise ValueError(f"share of unallocated block {b}")
            self._ref[b] += 1

    def free(self, ids: List[int]) -> int:
        """Release one holder per id; returns how many blocks actually
        went back to the free list (refcount reached zero)."""
        returned = 0
        for b in ids:
            r = self._ref.get(b, 0)
            if r <= 0:
                raise ValueError(f"double free of block {b}")
            if r == 1:
                del self._ref[b]
                self._free.append(b)
                returned += 1
            else:
                self._ref[b] = r - 1
        return returned


def build_table_row(block_ids: List[int], table_width: int) -> np.ndarray:
    """One request's block table: its blocks in position order, padded
    with the trash block out to the fixed table width (L // bs)."""
    if len(block_ids) > table_width:
        raise ValueError(
            f"{len(block_ids)} blocks > table width {table_width}"
        )
    row = np.full((table_width,), TRASH_BLOCK, np.int32)
    row[: len(block_ids)] = block_ids
    return row


def gather_cache(pool, tables):
    """Dense ``[B, L, ...]`` view of the paged pool: each cache leaf
    ``(num_blocks, bs, ...)`` is gathered by the ``[B, nb]`` block
    table and re-flattened. 0-d leaves (the shared write-index
    scalars) pass through. Traced inside the decode chunk program —
    the shared chunk body then runs UNCHANGED on the view, which is
    what makes the paged layout bit-exact with ``per_row``."""
    B, nb = tables.shape
    return jax.tree_util.tree_map(
        lambda p: p if p.ndim == 0 else (
            p[tables].reshape((B, nb * p.shape[1]) + p.shape[2:])
        ),
        pool,
    )


def scatter_cache(pool, tables, dense):
    """Write an advanced dense view back into the pool by block table.
    Duplicate table entries (the trash block; prefix blocks shared
    across rows) receive an unspecified writer — harmless by
    construction: trash content is never read with kv_valid set, and
    every sharer of a prefix block writes back the identical prefix
    values (decode writes land past the prefix, so the gathered
    prefix region rides through unchanged)."""
    B, nb = tables.shape
    return jax.tree_util.tree_map(
        lambda p, d: p if p.ndim == 0 else p.at[tables].set(
            d.reshape((B, nb, p.shape[1]) + p.shape[2:])
        ),
        pool,
        dense,
    )


def scatter_row(pool, table_row, row):
    """Insert one prefilled ``[1, L, ...]`` row into its blocks
    (``table_row``: ``[nb]`` int32). Trash-padded entries write the
    row's uncovered tail into the trash block — never read valid."""
    nb = table_row.shape[0]
    return jax.tree_util.tree_map(
        lambda p, r: p if p.ndim == 0 else p.at[table_row].set(
            r[0].reshape((nb, p.shape[1]) + r.shape[2:]).astype(p.dtype)
        ),
        pool,
        row,
    )


# -- prefill/decode disaggregation hand-off payload ---------------------


def _enc(arr) -> Dict:
    a = np.asarray(arr)
    return {
        "dtype": str(a.dtype),
        "shape": list(a.shape),
        "b64": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def _dec(d: Dict) -> np.ndarray:
    a = np.frombuffer(
        base64.b64decode(d["b64"]), dtype=np.dtype(d["dtype"])
    )
    return a.reshape(d["shape"])


def pack_row_state(
    row_cache, row_logits, row_pos, row_kv, width: int,
    prompt: List[int],
) -> Dict:
    """Serialize one prefilled row for the prefill→decode hand-off:
    JSON-safe (base64 leaves), host-portable, model-agnostic on the
    wire — the receiver validates shapes against ITS model before
    admitting (a payload from a mismatched config must 400, never
    corrupt a cache row)."""
    leaves = jax.tree_util.tree_leaves(row_cache)
    return {
        "v": 1,
        "width": int(width),
        "prompt": [int(t) for t in prompt],
        "cache_leaves": [_enc(x) for x in leaves],
        "logits": _enc(row_logits),
        "pos": _enc(row_pos),
        "kv": _enc(row_kv),
    }


def unpack_row_state(payload: Dict, like_cache):
    """Rebuild ``(row_cache, row_logits, row_pos, row_kv, width,
    prompt)`` from a hand-off payload. ``like_cache`` is the RECEIVING
    engine's ``init_cache(model, 1)`` — structure and per-leaf shapes
    must match exactly or the payload is rejected."""
    if payload.get("v") != 1:
        raise ValueError(f"unknown handoff payload version {payload.get('v')!r}")
    like_leaves, treedef = jax.tree_util.tree_flatten(like_cache)
    enc = payload["cache_leaves"]
    if len(enc) != len(like_leaves):
        raise ValueError(
            f"handoff cache has {len(enc)} leaves, engine expects "
            f"{len(like_leaves)}"
        )
    leaves = []
    for got, want in zip(enc, like_leaves):
        arr = _dec(got)
        if tuple(arr.shape) != tuple(want.shape):
            raise ValueError(
                f"handoff leaf shape {tuple(arr.shape)} != engine "
                f"{tuple(want.shape)} (mismatched model config)"
            )
        leaves.append(jnp.asarray(arr, want.dtype))
    row_cache = jax.tree_util.tree_unflatten(treedef, leaves)
    return (
        row_cache,
        jnp.asarray(_dec(payload["logits"])),
        jnp.asarray(_dec(payload["pos"])),
        jnp.asarray(_dec(payload["kv"])),
        int(payload["width"]),
        [int(t) for t in payload["prompt"]],
    )
