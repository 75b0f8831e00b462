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

Two head sizes: q and k share ``d`` (the score's contraction), v and the
output share ``d_v``, and the two may differ (latent attention:
``d = 192`` for nope + rope, ``d_v = 128``). dq and dk come back ``d``
wide, dv ``d_v`` wide. With ``d_v == d`` the kernels are the same
programs as before the split. The default scale is ``1 / sqrt(d)``.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tuned on v5e silicon (in-device scan timing, B=32/H=12/T=1024/D=64 and
# B=4/T=4096): 1024×1024 beats 512×1024 by ~27% fwd-only and ~10%
# fwd+bwd — fewer grid steps amortize the online-softmax rescale and the
# per-block mask/iota work, and the 4 MB f32 probability tile still
# leaves VMEM headroom (2048-wide tiles fail to compile).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Trailing lanes used to materialize per-row scalars (lse/delta) in HBM.
_LSE_LANES = 8
_NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


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
        # rules allow (equal to the overall array dim), 16x leaner than a
        # full 128-lane tile.
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


def _flash_fwd(
    q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int
) -> Tuple[jax.Array, jax.Array]:
    """q,k: (BH, T, D), v: (BH, T, Dv) → (out (BH,T,Dv), lse (BH,T))."""
    bh, t_q, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[2]
    block_q, block_k = _clamp_blocks(q.dtype, t_q, t_kv, block_q, block_k)
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
    )
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
    q, k, v, out, lse, do, sm_scale, causal, block_q, block_k
):
    bh, t_q, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[2]
    block_q, block_k = _clamp_blocks(q.dtype, t_q, t_kv, block_q, block_k)
    tq_pad = _round_up(t_q, block_q)
    tk_pad = _round_up(t_kv, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
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

    common = dict(
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=t_kv,
        q_len=t_q,
    )
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
    )(qp, kp, vp, dop, lsep, deltap)

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
    )(qp, kp, vp, dop, lsep, deltap)[0]
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
):
    """Flash attention over ``[batch, seq, heads, head_dim]`` tensors;
    v's (and the output's) head size may differ from q's and k's."""
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    out3, lse = _flash_fwd(
        _to_bht(q), _to_bht(k), _to_bht(v), scale, causal, block_q, block_k
    )
    out = _from_bht(out3, b, h)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, residuals, g):
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
    )
    return _from_bht(dq3, b, h), _from_bht(dk3, b, h), _from_bht(dv3, b, h)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_sharded(q, k, v, mesh=None, causal: bool = True):
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
        return flash_attention(q, k, v, causal)
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
        functools.partial(flash_attention, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def reference_attention(q, k, v, causal: bool = True, sm_scale=None):
    """Naive einsum attention — the correctness oracle for kernel tests
    (v may be narrower or wider than q and k)."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool), k=t_k - t_q)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(probs.dtype)).astype(
        q.dtype
    )
