"""Pallas TPU flash attention (forward + backward).

The hot op of the GPT compute path (SURVEY §2.17: the reference has no
attention kernels at all — its parallelism is integrated, not
implemented — so this is TPU-native net-new work, built to the Pallas
guide's flash-attention/online-softmax pattern).

Algorithm: FlashAttention-2. Forward streams K/V blocks through VMEM
with an online softmax (running max ``m``, normalizer ``l``, f32
accumulator); saves per-row logsumexp for the backward. Backward runs
two passes (dk/dv with q as the streamed axis, dq with k streamed),
recomputing probabilities from the saved logsumexp.

Layout: inputs are ``[batch, seq, heads, head_dim]`` (the model's
``bqhk``); kernels operate on ``[batch*heads, seq, head_dim]``. Blocks
default to 1024×1024, fp32 softmax, inputs in bf16 on TPU.

Two sets of kernels, chosen from the call's static shapes in one place
(:func:`_sub_block`), with no argument or flag:

- **The causal walk** (the training shape: causal self-attention over
  whole square tiles): a 1024×1024 block is what one DMA brings, not what
  one product computes. The diagonal tile is walked in 256-wide
  sub-blocks that compute only what the mask leaves (10 of its 16
  sub-squares) and mask only the squares on the diagonal; a tile under
  the diagonal carries no mask; a tile above it is neither run nor
  fetched; where a head is one tile (T <= 1024) a backward sub-block is
  one pass, with no state between pieces, and the forward keeps the
  general kernel. These kernels hold the scores transposed (keys × query
  rows): the softmax's max and sum then run down sublanes, on the vector
  unit, where reductions along lanes kept the forward waiting on the
  cross-lane unit; ``m``, ``l``, lse and delta are lane-dense rows in
  VMEM; and dk/dv's products take ``p^T`` and ``ds^T`` as they lie, with
  no transpose of a score tile. In HBM the backward kernels take lse
  and delta as the general ones do, ``[.., T, 8]`` (tiled (8, 128): 512
  bytes a query row), and turn a block into a row once a grid step.
- **The general kernels** (``t_q != t_kv``, a ragged T, no mask, unequal
  blocks, T under one sub-block): one masked-everywhere body a tile.

**A window** (``window=w``, causal only: row ``i`` sees key ``j`` iff ``0
<= i - j < w``). Where the call takes the causal walk and ``w`` is a whole
number of blocks, the walked kernels run only the band: the diagonal tile
as above, the tile ``w / block`` tiles before it in the same sub-blocks
with the complementary mask (``key_local > row_local``), the tiles between
the two unmasked, and no tile further back is run or fetched: the grid
itself is the band, ``(heads, tiles, w / block + 1)``, whose inner step
``j`` names the band's ``j``-th tile (:func:`_band_step`). Only where the
band hangs over the sequence's edge (the first ``w / block`` row tiles, the
last ``w / block`` key tiles) does a step name a tile that does not exist:
its index maps repeat the nearest tile that does, which comes next or is
there already, and its body does not run. Any other windowed call takes
the general kernels with the window as one more term of their mask. Without
a window, or with one that cuts nothing off, every program is what it was
before the band existed, text for text: the grid ``(heads, tiles, tiles)``.

Two head sizes: q and k share ``d`` (the score's contraction), v and the
output share ``d_v``, and the two may differ (latent attention:
``d = 192`` for nope + rope, ``d_v = 128``). dq and dk come back ``d``
wide, dv ``d_v`` wide. The default scale is ``1 / sqrt(d)``.

On the CPU backend (tests, rehearsals) the same kernels run in Pallas
interpret mode, so CPU tests cover the kernel logic bit-for-bit. A
worker that asked for the TPU cannot reach that branch: its platform is
pinned (``ElasticLaunchConfig.worker_env``), so a failed TPU
initialization raises instead of falling to the CPU.
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.spans import span

# Tuned on v5e silicon (in-device scan timing, B=32/H=12/T=1024/D=64 and
# B=4/T=4096): 1024×1024 beats 512×1024 by ~27% fwd-only and ~10%
# fwd+bwd — fewer grid steps amortize the per-step DMA and the
# online-softmax rescale (2048-wide tiles fail to compile). Under the
# causal walk the block is the DMA tile only: on the diagonal the
# products, the exp and the mask run on sub-block-wide pieces of it, so
# the largest score tile in VMEM there is 1024×256 (1 MB of f32).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Side of the sub-squares a diagonal tile is walked in (the causal walk):
# a multiple of the 128 lanes and of the 16 sublanes of bf16.
_SUB_BLOCK = 256
# Trailing lanes that carry a per-row scalar (lse, delta) in HBM; the
# walk's forward writes its lse as rows instead, in this many sublanes.
_LSE_LANES = 8
_ROW_SUBLANES = 8
_NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _reaches_window(iq, ik, block_q: int, block_k: int, causal_off: int, window: int):
    """Whether key tile ``ik`` holds a key that some row of q tile ``iq``
    still sees under a window: row ``i`` sees keys ``(i + causal_off -
    window, i + causal_off]``, so the tile's last key has to lie past the
    first row's lower bound (the general kernels' skip; the walk names its
    tiles outright)."""
    return ik * block_k + block_k - 1 > iq * block_q + causal_off - window


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    q_len: int,
    window: Optional[int] = None,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    # End-aligned causal offset (standard KV-cache convention): query row
    # i attends keys [0, i + kv_len - q_len].
    causal_off = kv_len - q_len

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: a K block strictly right of the Q block's last row is fully
    # masked — skip its FLOPs (the grid still visits it).
    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1 + causal_off
    if window is not None:
        run = jnp.logical_and(run, _reaches_window(iq, ik, block_q, block_k, causal_off, window))

    @pl.when(run)
    def _body():
        q = q_ref[0]  # (block_q, d) — keep input dtype: bf16 rides the MXU
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= sm_scale
        q_idx = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_idx < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_idx <= q_idx + causal_off)
        if window is not None:
            mask = jnp.logical_and(mask, k_idx > q_idx + causal_off - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        # lse carries a trailing dim of 8 — the smallest the Mosaic block
        # rules allow (equal to the overall array dim). In HBM the rows
        # are tiled (8, 128) all the same: 8 lanes buy nothing there.
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, _LSE_LANES))


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _clamp_blocks(dtype, t_q, t_kv, block_q, block_k):
    """Clamp block sizes to the sequence length while keeping them a
    multiple of the TPU sublane tile (8 for f32, 16 for bf16/f16) —
    Mosaic rejects ragged second-minor block dims on real hardware even
    though interpret-mode CPU runs accept them."""
    sublane = 16 if dtype.itemsize <= 2 else 8
    block_q = min(block_q, _round_up(max(t_q, sublane), sublane))
    block_k = min(block_k, _round_up(max(t_kv, sublane), sublane))
    return block_q, block_k


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# the causal walk (the training shape)
# ---------------------------------------------------------------------------

_TRANS_A = (((0,), (0,)), ((), ()))  # a^T @ b
_TRANS_B = (((1,), (1,)), ((), ()))  # a @ b^T
_PLAIN = (((1,), (0,)), ((), ()))


def _sub_block(
    causal: bool, t_q: int, t_kv: int, block_q: int, block_k: int, forward: bool,
    window: Optional[int] = None,
) -> int:
    """The side of the sub-squares a diagonal tile is walked in, or 0 for
    the general kernels. The one place that decides, from what the call's
    shapes say: the walk is for causal self-attention over whole square
    tiles (no padding, no offset between rows and keys) that hold at
    least one sub-block; anything else keeps the masked-everywhere body.

    The forward walks only where a head is more than one tile; at one
    tile a head it keeps the general kernel and its outputs' bits
    (ROADMAP.md B9 says why, and when that exception goes). A ``window``
    (:func:`_window_of`: one that cuts something off) is walked where it
    is a whole number of blocks: the band then ends on tile edges."""
    walked = (
        causal
        and t_q == t_kv
        and block_q == block_k
        and t_q % block_q == 0
        and block_q % _SUB_BLOCK == 0
        and (window is None or window % block_q == 0)
    )
    if not walked or (forward and t_q == block_q):
        return 0
    return _SUB_BLOCK


def _window_of(window: Optional[int], causal: bool, t_kv: int) -> Optional[int]:
    """The window as the kernels take it: None where it cuts nothing off
    (row ``i`` sees the ``window`` keys that end at its own, and no row has
    ``t_kv`` or more before it), so that such a call is the causal one."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"a window of {window} keys needs causal=True and window >= 1")
    return window if window < t_kv else None


def _diagonal_pieces(block: int, sub: int, by_keys: bool, far: bool = False):
    """A diagonal tile as ``(row0, rows, key0, keys)`` pieces, one a
    sub-block. Forward and dq accumulate by q rows: rows ``[i*sub,
    (i+1)*sub)`` against keys ``[0, (i+1)*sub)``, whose last ``sub`` keys
    are the square on the diagonal. dk/dv accumulate by key columns
    (``by_keys``): keys ``[j*sub, (j+1)*sub)`` against q rows ``[j*sub,
    block)``, whose first ``sub`` rows are the square. Only the square
    needs the mask.

    ``far``: the tile where a window's band ends, ``window // block``
    tiles before the diagonal's, which keeps the complement, ``key_local >
    row_local``: rows ``[i*sub, (i+1)*sub)`` against keys ``[i*sub,
    block)``, whose *first* keys are the square; by key columns, keys
    ``[j*sub, (j+1)*sub)`` against q rows ``[0, (j+1)*sub)``, whose *last*
    rows are."""
    if far:
        if by_keys:
            return [(0, lo + sub, lo, sub) for lo in range(0, block, sub)]
        return [(lo, sub, lo, block - lo) for lo in range(0, block, sub)]
    if by_keys:
        return [(lo, block - lo, lo, sub) for lo in range(0, block, sub)]
    return [(lo, sub, 0, lo + sub) for lo in range(0, block, sub)]


def _walk_causal(
    body, iq, ik, block: int, sub: int, n_tiles: int, by_keys: bool, band: int = 0
):
    """Run ``body(rows, keys, where)`` over what the causal mask leaves of
    tile (iq, ik): a tile under the diagonal whole and unmasked (``where``
    None), the diagonal tile one sub-block at a time, a tile above it not
    at all (its blocks are not fetched either: the index maps repeat the
    last tile that runs). ``where(x, fill)`` fills what the mask removes
    of a ``[keys, rows]`` piece: only the piece's square on the diagonal
    is selected, the rest of ``x`` passes as it is.

    ``band``: a window of ``band`` whole tiles. Tile ``iq - band`` is the
    band's far end and is walked like the diagonal's with the complementary
    mask (:func:`_diagonal_pieces`); the tiles between the two carry no
    mask; a tile further back is no grid step (:func:`_band_step`)."""

    def masked_tile(far: bool = False):
        key = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        keep = key > row if far else key <= row
        # by key columns the square is a piece's first ``sub`` q rows
        # (lanes), by q rows its last ``sub`` keys (sublanes); at the far
        # end the other way round
        axis, square_first = (1, not far) if by_keys else (0, far)

        def where(x, fill):
            if x.shape == keep.shape:
                return jnp.where(keep, x, fill)
            cut = sub if square_first else x.shape[axis] - sub
            at = (slice(None),) * axis
            first = x[at + (slice(None, cut),)]
            if square_first:
                first = jnp.where(keep, first, fill)
            last = x[at + (slice(cut, None),)]
            if not square_first:
                last = jnp.where(keep, last, fill)
            return jnp.concatenate([first, last], axis=axis)

        for row0, n_rows, key0, n_keys in _diagonal_pieces(block, sub, by_keys, far):
            body(slice(row0, row0 + n_rows), slice(key0, key0 + n_keys), where)

    if n_tiles == 1:  # the one tile is the diagonal's: no branch at all
        masked_tile()
        return
    whole = lambda: body(slice(None), slice(None), None)
    if not band:
        pl.when(ik < iq)(whole)
    else:
        if band > 1:
            pl.when(jnp.logical_and(ik < iq, ik > iq - band))(whole)
        pl.when(ik == iq - band)(lambda: masked_tile(far=True))
    pl.when(ik == iq)(masked_tile)


def _kernel_plan(
    causal: bool, t_q: int, t_kv: int, block_q: int, block_k: int, sub: int,
    window: Optional[int] = None,
):
    """What one head's grid does, for the ``flash.kernel_built`` record:
    grid steps, steps whose body runs, and the scores computed as a share
    of the ``t_q x t_kv`` square (the mask itself keeps 0.5 of it, or with
    a ``window`` (:func:`_window_of`) ``window / t`` less half its square).
    ``tiles_visited`` is the grid as built: under a band ``band + 1`` steps
    a tile, so visited less run is the steps that hang over the edge."""
    n_q = _round_up(t_q, block_q) // block_q
    n_k = _round_up(t_kv, block_k) // block_k
    visited = n_q * n_k
    if sub:
        band = window // block_q if window else n_q  # tiles back to the far one
        if window:
            visited = n_q * (band + 1)
        masked = n_q + max(n_q - band, 0)  # the diagonal's, and the far ones
        tiles_run = sum(min(i, band) + 1 for i in range(n_q))
        scores = (tiles_run - masked) * block_q * block_k + masked * sum(
            rows * keys
            for _, rows, _, keys in _diagonal_pieces(block_q, sub, False)
        )
    else:
        off = t_kv - t_q
        tiles_run = sum(
            (not causal or j * block_k <= i * block_q + block_q - 1 + off)
            and (window is None or _reaches_window(i, j, block_q, block_k, off, window))
            for i in range(n_q)
            for j in range(n_k)
        )
        scores = tiles_run * block_q * block_k
    plan = {
        "path": ("window_tiled" if window else "causal_tiled") if sub else "general",
        "tiles_visited": visited,
        "tiles_run": tiles_run,
        "score_share": scores / (t_q * t_kv),
    }
    if window:
        plan["window"] = window
    return plan


def _built(kernel: str, plan: dict):
    """The span around one kernel's tracing: ``flash.kernel_built`` with
    the plan as stats. No metric reads it: tests are its readers
    (``tests/test_spans.py`` holds the stats of GPT-2-small's step), and the
    kernels' device time is read from the trace."""
    return span("flash.kernel_built", kernel=kernel, **plan)


def _as_row(ref):
    """A block of per-row scalars as it lies in HBM, ``(1, rows, 8)``
    with the value in every lane, as a ``(1, rows)`` row."""
    return ref[0].T[:1]


def _inner_steps(n_tiles: int, band: int) -> int:
    """The inner extent of a walked kernel's grid: the band's steps, or
    every tile where no window cuts the walk short."""
    return band + 1 if band else n_tiles


def _band_step(n_tiles: int, band: int, by_keys: bool):
    """What grid step ``(head, it, inner)`` of a walked kernel is: ``(iq, ik,
    inner, last, live)``, the tile pair it names, the inner step, the inner
    axis' last step, and whether the pair exists (None: every one does).

    Without a band the inner axis is the streamed tiles themselves, ``n_tiles``
    of them. Under a window of ``band`` tiles it is the band, ``band + 1``
    steps: rows streamed over keys (forward, dq) meet key tile ``iq - band +
    inner``, far tile first and diagonal last; keys streamed over rows
    (dk/dv) meet row tile ``ik + inner``, diagonal first and far tile last.
    The first ``band`` row tiles' bands start before key tile 0 and the last
    ``band`` key tiles' end past row tile ``n_tiles - 1``: those steps are
    not ``live``, and :func:`_keys_at` / :func:`_rows_at` clamp them."""
    it, inner = pl.program_id(1), pl.program_id(2)
    if not band:
        streamed, live = inner, None
    elif by_keys:
        streamed = it + inner
        live = streamed <= n_tiles - 1
    else:
        streamed = it - band + inner
        live = streamed >= 0
    iq, ik = (streamed, it) if by_keys else (it, streamed)
    return iq, ik, inner, _inner_steps(n_tiles, band) - 1, live


def _walk_live(live, walk):
    """Run ``walk`` where the step's tile pair exists."""
    if live is None:
        walk()
    else:
        pl.when(live)(walk)


def _walk_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale: float, block: int, sub: int, n_tiles: int, band: int = 0,
):
    iq, ik, inner, last, live = _band_step(n_tiles, band, False)

    @pl.when(inner == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(rows, keys, where):
        """One online-softmax update of q ``rows`` by ``keys`` of the
        tiles in VMEM, the scores held ``[keys, rows]``."""
        q = q_ref[0, rows]  # keep input dtype: bf16 rides the MXU
        k = k_ref[0, keys]
        v = v_ref[0, keys]
        s = jax.lax.dot_general(k, q, _TRANS_B, preferred_element_type=jnp.float32)
        s *= sm_scale
        if where is not None:
            s = where(s, _NEG_INF)
        m_prev = m_ref[:1, rows]  # (1, rows)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:1, rows] = alpha * l_ref[:1, rows] + jnp.sum(
            p, axis=0, keepdims=True
        )
        m_ref[:1, rows] = m_new
        pv = jax.lax.dot_general(  # (p @ v)^T: (d_v, rows)
            v, p.astype(v.dtype), _TRANS_A, preferred_element_type=jnp.float32
        )
        acc_ref[:, rows] = acc_ref[:, rows] * alpha + pv

    _walk_live(live, lambda: _walk_causal(body, iq, ik, block, sub, n_tiles, False, band))

    @pl.when(inner == last)
    def _finish():
        l = l_ref[:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).T.astype(o_ref.dtype)
        lse = m_ref[:1] + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse, (_ROW_SUBLANES, block))


def _walk_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *outs_and_scratch,
    sm_scale: float, block: int, sub: int, n_tiles: int, by_keys: bool, band: int = 0,
):
    """dk/dv (``by_keys``: grid ``(head, key tile, q tile)``, outputs and
    accumulators ``[keys, d]`` and ``[keys, d_v]``) or dq (grid ``(head, q
    tile, key tile)``, its accumulator held transposed, ``[d, rows]``); under
    a band the inner axis is the band's steps (:func:`_band_step`)."""
    n_out = len(outs_and_scratch) // 2
    outs, accs = outs_and_scratch[:n_out], outs_and_scratch[n_out:]
    iq, ik, inner, last, live = _band_step(n_tiles, band, by_keys)
    # One tile a head (T <= the block): each sub-block's result is whole
    # after its one piece and is written where it goes; no accumulator,
    # nothing to start or finish.
    one_pass = n_tiles == 1

    if not one_pass:

        @pl.when(inner == 0)
        def _init():
            for acc in accs:
                acc[:] = jnp.zeros_like(acc)

    lse_row, delta_row = _as_row(lse_ref), _as_row(delta_ref)

    def body(rows, keys, where):
        q = q_ref[0, rows]
        k = k_ref[0, keys]
        v = v_ref[0, keys]
        do = do_ref[0, rows]
        lse = lse_row[:, rows]  # (1, rows)
        delta = delta_row[:, rows]
        s = jax.lax.dot_general(k, q, _TRANS_B, preferred_element_type=jnp.float32)
        s *= sm_scale
        p = jnp.exp(s - lse)  # (keys, rows)
        if where is not None:
            p = where(p, 0.0)
        # dp^T = v @ do^T ; ds^T = p^T * (dp^T - delta) * scale
        dp = jax.lax.dot_general(v, do, _TRANS_B, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        if by_keys:  # dk = ds^T @ q ; dv = p^T @ do: as the tiles lie
            found = (
                jax.lax.dot_general(ds, q, _PLAIN, preferred_element_type=jnp.float32),
                jax.lax.dot_general(
                    p.astype(do.dtype), do, _PLAIN, preferred_element_type=jnp.float32
                ),
            )
            for out, acc, x in zip(outs, accs, found):
                if one_pass:
                    out[0, keys] = x.astype(out.dtype)
                else:
                    acc[keys] += x
        else:  # dq^T = k^T @ ds^T
            dq = jax.lax.dot_general(k, ds, _TRANS_A, preferred_element_type=jnp.float32)
            if one_pass:
                outs[0][0, rows] = dq.T.astype(outs[0].dtype)
            else:
                accs[0][:, rows] += dq

    _walk_live(live, lambda: _walk_causal(body, iq, ik, block, sub, n_tiles, by_keys, band))
    if one_pass:
        return

    @pl.when(inner == last)
    def _finish():
        if by_keys:
            for out, acc in zip(outs, accs):
                out[0] = acc[:].astype(out.dtype)
        else:
            outs[0][0] = accs[0][:].T.astype(outs[0].dtype)


# Each walked kernel is a jitted function of its own, inlined where it is
# called: a model's layers call it with one set of shapes, so the Python
# body (the unrolled walk) is traced once a process and every further layer
# re-binds the traced equations, where the general kernels trace theirs
# anew at each of a step's call sites (XL: 48 layers x 3). Inlined, each
# call is lowered under its caller's scope, so a device trace names the
# kernel after the model's layer as it names the general ones.
# ``interpret`` is an argument because it is part of what was traced.
_walk_jit = functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "block", "sub", "interpret", "band"),
    inline=True,
)


def _keys_at(band: int):
    """The key tile grid step ``(b, i, j)`` names, rows streamed over keys
    (forward, dq). With no band ``j`` is the key tile, and a tile above the
    diagonal names the diagonal's blocks: no copy. Under a window of ``band``
    tiles ``j`` is a step of the band (:func:`_band_step`) and names key tile
    ``i - band + j``; a step before key tile 0 names tile 0, which comes
    next and is fetched once."""
    if not band:
        return lambda b, i, j: (b, jnp.minimum(j, i), 0)
    return lambda b, i, j: (b, jnp.maximum(i - band + j, 0), 0)


def _rows_at(band: int, n_tiles: int):
    """The row tile grid step ``(b, j, i)`` names, keys streamed over rows
    (dk/dv: q, do, lse and delta alike). With no band ``i`` is the row tile,
    and a tile above the diagonal names the diagonal's blocks, which come
    next: no copy. Under a band ``i`` is a step of it and names row tile ``j
    + i``; a step past the last row tile names the last, which is there."""
    if not band:
        return lambda b, j, i: (b, jnp.maximum(i, j), 0)
    return lambda b, j, i: (b, jnp.minimum(j + i, n_tiles - 1), 0)


@_walk_jit
def _walk_fwd(
    q, k, v, *, sm_scale: float, block: int, sub: int, interpret: bool, band: int = 0
):
    """The forward of the causal walk: whole square tiles, no padding."""
    bh, t, d = q.shape
    d_v = v.shape[2]
    n = t // block
    at_k = _keys_at(band)
    out, lse = pl.pallas_call(
        functools.partial(
            _walk_fwd_kernel, sm_scale=sm_scale, block=block, sub=sub, n_tiles=n,
            band=band,
        ),
        grid=(bh, n, _inner_steps(n, band)),
        in_specs=[
            _vmem_spec((1, block, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block, d), at_k),
            _vmem_spec((1, block, d_v), at_k),
        ],
        out_specs=[
            _vmem_spec((1, block, d_v), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, _ROW_SUBLANES, block), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, _ROW_SUBLANES, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d_v, block), jnp.float32),
            pltpu.VMEM((_ROW_SUBLANES, block), jnp.float32),
            pltpu.VMEM((_ROW_SUBLANES, block), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, 0]


@_walk_jit
def _walk_dkdv(q, k, v, do, lse, delta, *, sm_scale, block, sub, interpret, band=0):
    """dk and dv of the causal walk; lse and delta ``(bh, t, 8)``."""
    bh, t, d = q.shape
    d_v = v.shape[2]
    n = t // block
    per_row = (1, block, _LSE_LANES)
    rows_at = _rows_at(band, n)
    return pl.pallas_call(
        functools.partial(
            _walk_bwd_kernel, sm_scale=sm_scale, block=block, sub=sub,
            n_tiles=n, by_keys=True, band=band,
        ),
        grid=(bh, n, _inner_steps(n, band)),
        in_specs=[
            _vmem_spec((1, block, d), rows_at),
            _vmem_spec((1, block, d), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block, d_v), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block, d_v), rows_at),
            _vmem_spec(per_row, rows_at),
            _vmem_spec(per_row, rows_at),
        ],
        out_specs=[
            _vmem_spec((1, block, d), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block, d_v), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, d_v), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


@_walk_jit
def _walk_dq(q, k, v, do, lse, delta, *, sm_scale, block, sub, interpret, band=0):
    """dq of the causal walk; lse and delta ``(bh, t, 8)``."""
    bh, t, d = q.shape
    d_v = v.shape[2]
    n = t // block
    per_row = (1, block, _LSE_LANES)
    keys_at = _keys_at(band)
    return pl.pallas_call(
        functools.partial(
            _walk_bwd_kernel, sm_scale=sm_scale, block=block, sub=sub,
            n_tiles=n, by_keys=False, band=band,
        ),
        grid=(bh, n, _inner_steps(n, band)),
        in_specs=[
            _vmem_spec((1, block, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block, d), keys_at),
            _vmem_spec((1, block, d_v), keys_at),
            _vmem_spec((1, block, d_v), lambda b, i, j: (b, i, 0)),
            _vmem_spec(per_row, lambda b, i, j: (b, i, 0)),
            _vmem_spec(per_row, lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[_vmem_spec((1, block, d), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((d, block), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)[0]


def _flash_fwd(
    q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q,k: (BH, T, D), v: (BH, T, Dv) → (out (BH,T,Dv), lse (BH,T))."""
    bh, t_q, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[2]
    block_q, block_k = _clamp_blocks(q.dtype, t_q, t_kv, block_q, block_k)
    window = _window_of(window, causal, t_kv)
    sub = _sub_block(causal, t_q, t_kv, block_q, block_k, True, window)
    plan = _kernel_plan(causal, t_q, t_kv, block_q, block_k, sub, window)
    if sub:
        with _built("fwd", plan):
            return _walk_fwd(
                q, k, v, sm_scale=sm_scale, block=block_q, sub=sub,
                interpret=_use_interpret(), band=window // block_q if window else 0,
            )

    tq_pad = _round_up(t_q, block_q)
    tk_pad = _round_up(t_kv, block_k)
    qp = _pad_to(q, tq_pad, 1)
    kp = _pad_to(k, tk_pad, 1)
    vp = _pad_to(v, tk_pad, 1)
    grid = (bh, tq_pad // block_q, tk_pad // block_k)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=t_kv,
        q_len=t_q,
        window=window,
    )
    with _built("fwd", plan):
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                _vmem_spec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                _vmem_spec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
                _vmem_spec((1, block_q, _LSE_LANES), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tq_pad, d_v), q.dtype),
                jax.ShapeDtypeStruct((bh, tq_pad, _LSE_LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d_v), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            interpret=_use_interpret(),
        )(qp, kp, vp)
    return out[:, :t_q], lse[:, :t_q, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_acc,
    dv_acc,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    q_len: int,
    window: Optional[int] = None,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1 + (kv_len - q_len)
    if window is not None:
        run = jnp.logical_and(
            run, _reaches_window(iq, ik, block_q, block_k, kv_len - q_len, window))

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # (block_q, 1)
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= sm_scale
        q_idx = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = jnp.logical_and(k_idx < kv_len, q_idx < q_len)
        if causal:
            mask = jnp.logical_and(mask, k_idx <= q_idx + (kv_len - q_len))
        if window is not None:
            mask = jnp.logical_and(mask, k_idx > q_idx + (kv_len - q_len) - window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (block_q, block_k)
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_acc,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    q_len: int,
    window: Optional[int] = None,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1 + (kv_len - q_len)
    if window is not None:
        run = jnp.logical_and(
            run, _reaches_window(iq, ik, block_q, block_k, kv_len - q_len, window))

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= sm_scale
        q_idx = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = jnp.logical_and(k_idx < kv_len, q_idx < q_len)
        if causal:
            mask = jnp.logical_and(mask, k_idx <= q_idx + (kv_len - q_len))
        if window is not None:
            mask = jnp.logical_and(mask, k_idx > q_idx + (kv_len - q_len) - window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(
    q, k, v, out, lse, do, sm_scale, causal, block_q, block_k, window=None
):
    bh, t_q, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[2]
    block_q, block_k = _clamp_blocks(q.dtype, t_q, t_kv, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    window = _window_of(window, causal, t_kv)
    sub = _sub_block(causal, t_q, t_kv, block_q, block_k, False, window)
    plan = _kernel_plan(causal, t_q, t_kv, block_q, block_k, sub, window)
    built = functools.partial(_built, plan=plan)

    tq_pad = _round_up(t_q, block_q)
    tk_pad = _round_up(t_kv, block_k)
    qp = _pad_to(q, tq_pad, 1)
    kp = _pad_to(k, tk_pad, 1)
    vp = _pad_to(v, tk_pad, 1)
    dop = _pad_to(do, tq_pad, 1)
    # lse/delta carry a small trailing lane dim (Mosaic block rules)
    lsep = jnp.broadcast_to(
        _pad_to(lse, tq_pad, 1)[..., None], (bh, tq_pad, _LSE_LANES)
    )
    deltap = jnp.broadcast_to(
        _pad_to(delta, tq_pad, 1)[..., None], (bh, tq_pad, _LSE_LANES)
    )
    operands = (qp, kp, vp, dop, lsep, deltap)

    if sub:  # whole tiles: nothing was padded
        walk = dict(
            sm_scale=sm_scale, block=block_q, sub=sub, interpret=_use_interpret(),
            band=window // block_q if window else 0,
        )
        with built("dkdv"):
            dk, dv = _walk_dkdv(*operands, **walk)
        with built("dq"):
            dq = _walk_dq(*operands, **walk)
        return dq, dk, dv

    common = dict(
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=t_kv,
        q_len=t_q,
        window=window,
    )
    with built("dkdv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, **common),
            grid=(bh, tk_pad // block_k, tq_pad // block_q),
            in_specs=[
                _vmem_spec((1, block_q, d), lambda b, j, i: (b, i, 0)),
                _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                _vmem_spec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),
                _vmem_spec((1, block_q, d_v), lambda b, j, i: (b, i, 0)),
                _vmem_spec((1, block_q, _LSE_LANES), lambda b, j, i: (b, i, 0)),
                _vmem_spec((1, block_q, _LSE_LANES), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                _vmem_spec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tk_pad, d), k.dtype),
                jax.ShapeDtypeStruct((bh, tk_pad, d_v), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d_v), jnp.float32),
            ],
            interpret=_use_interpret(),
        )(*operands)

    with built("dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **common),
            grid=(bh, tq_pad // block_q, tk_pad // block_k),
            in_specs=[
                _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                _vmem_spec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
                _vmem_spec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
                _vmem_spec((1, block_q, _LSE_LANES), lambda b, i, j: (b, i, 0)),
                _vmem_spec((1, block_q, _LSE_LANES), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=[_vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((bh, tq_pad, d), q.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=_use_interpret(),
        )(*operands)[0]
    return dq[:, :t_q], dk[:, :t_kv], dv[:, :t_kv]


# ---------------------------------------------------------------------------
# public API (custom VJP over the [B, T, H, D] layout)
# ---------------------------------------------------------------------------


def _to_bht(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bht(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    window: Optional[int] = None,
):
    """Flash attention over ``[batch, seq, heads, head_dim]`` tensors;
    v's (and the output's) head size may differ from q's and k's.

    ``window`` (static, causal only): row ``i`` sees key ``j`` iff ``0 <= i
    - j < window`` (end-aligned like the causal mask where ``t_q != t_kv``).
    Where the call takes the causal walk and the window is a whole number
    of blocks, the kernels run and fetch only the band's tiles; any other
    windowed call takes the general kernels with the window in their mask."""
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, window)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, window):
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    out3, lse = _flash_fwd(
        _to_bht(q), _to_bht(k), _to_bht(v), scale, causal, block_q, block_k, window
    )
    # The two results the backward kernels need, named for a caller's remat
    # policy (``save_only_these_names``): a name is an identity that lowers
    # to nothing, and a policy that reads no names sees nothing new.
    out = checkpoint_name(_from_bht(out3, b, h), "flash.out")
    lse = checkpoint_name(lse, "flash.lse")
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, window, residuals, g):
    q, k, v, out, lse = residuals
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    dq3, dk3, dv3 = _flash_bwd(
        _to_bht(q),
        _to_bht(k),
        _to_bht(v),
        _to_bht(out),
        lse,
        _to_bht(g),
        scale,
        causal,
        block_q,
        block_k,
        window,
    )
    return _from_bht(dq3, b, h), _from_bht(dk3, b, h), _from_bht(dv3, b, h)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_sharded(
    q, k, v, mesh=None, causal: bool = True, window: Optional[int] = None
):
    """:func:`flash_attention` under the model's layout on ``mesh``.

    A Mosaic kernel cannot be partitioned by GSPMD (lowering a sharded
    step around it raises "wrap the call in a shard_map"), so on a mesh
    of more than one device the kernel runs per shard: batch and heads
    split as the active logical rules say, each device attending over
    its own rows — attention never mixes batch rows or heads, so no
    collective is needed. The sequence must be whole on every device;
    a rule table that shards "seq" wants ``attention_impl="ring"``.
    With no mesh (or one device) this is the plain kernel call.
    """
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal, window=window)
    from flax.linen import partitioning as nn_partitioning
    from flax.linen import spmd as flax_spmd
    from jax.sharding import PartitionSpec

    from ..parallel.sharding import DEFAULT_RULES

    rules = list(nn_partitioning.get_axis_rules()) or DEFAULT_RULES
    logical = flax_spmd.logical_to_mesh_axes(
        ("batch", "seq", "heads", "kv"), rules
    )

    def extent(axis):
        axes = axis if isinstance(axis, tuple) else (axis,)
        return math.prod(mesh.shape[a] for a in axes if a is not None)

    # an axis that does not split the dim evenly (or has extent 1)
    # stays whole on every device, as state_shardings does for params
    spec = PartitionSpec(
        *(
            axis if extent(axis) > 1 and dim % extent(axis) == 0 else None
            for dim, axis in zip(q.shape, logical)
        )
    )
    if spec[1] is not None:
        raise ValueError(
            f"flash attention needs the whole sequence on each device, "
            f"but 'seq' is sharded over {spec[1]!r}; use "
            f"attention_impl='ring'"
        )
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal, window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def reference_attention(q, k, v, causal: bool = True, sm_scale=None, window=None):
    """Naive einsum attention — the correctness oracle for kernel tests
    (v may be narrower or wider than q and k). ``window``: row ``i`` sees
    the ``window`` keys that end at its own."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool), k=t_k - t_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t_q, t_k), dtype=bool), k=t_k - t_q - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(probs.dtype)).astype(
        q.dtype
    )
