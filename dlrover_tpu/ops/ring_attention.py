"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

Long-context support the reference lacks entirely (SURVEY §5: "not
present — reserve a mesh axis"; the mesh reserves ``sp``, this op uses
it). Each device holds a contiguous sequence shard of Q/K/V; K/V rotate
around the ring via ``ppermute`` (ICI neighbor transfers) while every
device accumulates its Q shard's attention with a running online
softmax — compute overlaps the rotation, memory stays O(T/sp), and the
result is *exact* attention over the full sequence.

Causality with contiguous sharding: a K/V chunk that originated at a
higher ring position than this device is entirely in the future → its
contribution is masked; the diagonal chunk gets the intra-chunk causal
mask; earlier chunks attend fully.

Use inside ``shard_map`` with the sequence dimension sharded over
``axis_name`` (see ``tests/test_ops.py`` and
``parallel/train_step.py``'s ring variant).
"""

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _chunk_stats(q, k, v, sm_scale, mask):
    """One Q-shard × KV-chunk pass → (unnormalized out, m, l).

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); mask: (Tq, Tk) bool or None.
    Returns out_unnorm (B, Tq, H, D) = exp(s - m) @ v, m/l: (B, H, Tq).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    # The running max is a numerical shift that cancels in the final
    # normalized output, so it must be fully gradient-stopped — here AND
    # in the cross-chunk merge factors derived from it (a half-stopped
    # max corrupts dq/dk).
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1))  # (B, H, Tq)
    # Masked entries sit at _NEG_INF (finite, to keep arithmetic clean);
    # zero them explicitly so a fully-masked row (m == _NEG_INF, where
    # exp(s - m) would be 1) contributes nothing.
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
    l = jnp.sum(p, axis=-1)  # (B, H, Tq)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    return out, m, l


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Exact attention with K/V ring rotation over ``axis_name``.

    Shapes (per device): q, k, v — ``[B, T_local, H, D]`` where the
    global sequence is ``T_local × axis_size``, sharded contiguously.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    # Keep K/V in their input dtype while they rotate: ppermute bytes are
    # the ICI cost ring attention amortizes (bf16 halves them); scores
    # are computed in f32 inside _chunk_stats.
    q32 = q.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (t_local, t_local), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t_local, t_local), 1)

    def step(carry, _):
        kc, vc, acc, m, l, src = carry
        if causal:
            # chunk-level causality: src > my_idx → future chunk
            diag = src == my_idx
            past = src < my_idx
            # build the per-element mask for the diagonal case; select
            # the right one with where (shapes are static)
            causal_mask = col <= row
            full_mask = jnp.ones_like(causal_mask)
            none_mask = jnp.zeros_like(causal_mask)
            mask = jnp.where(
                diag, causal_mask, jnp.where(past, full_mask, none_mask)
            )
        else:
            mask = None
        out_c, m_c, l_c = _chunk_stats(q32, kc, vc, scale, mask)
        m_new = jnp.maximum(m, m_c)
        # When both sides are still at _NEG_INF the exps evaluate to 1,
        # but their acc/l factors are 0 — harmless.
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        acc = acc * _bhq_to_bqh1(alpha) + out_c * _bhq_to_bqh1(beta)
        l = l * alpha + l_c * beta
        m = m_new
        # rotate kv to the next ring position: device i receives the
        # chunk previously held by i-1, so after s steps we hold chunk
        # (my_idx - s) mod n
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        src = (src - 1) % axis_size
        return (kc, vc, acc, m, l, src), None

    # The accumulators are device-varying state (shard_map type system):
    # derive them from q so they inherit exactly its varying axes (which
    # include every manual mesh axis when called from the full-mesh
    # shard_map, not just the ring axis). XLA folds the zero arithmetic.
    acc0 = jnp.zeros_like(q32)
    zero_bht = jnp.sum(q32, axis=-1).transpose(0, 2, 1) * 0.0  # (b,h,t)
    m0 = zero_bht + _NEG_INF
    l0 = zero_bht
    (k_f, v_f, acc, m, l, _), _ = jax.lax.scan(
        step,
        (k, v, acc0, m0, l0, my_idx),
        None,
        length=axis_size,
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / _bhq_to_bqh1(l_safe)
    return out.astype(q.dtype)


def _bhq_to_bqh1(x):
    """(B, H, Tq) → (B, Tq, H, 1) for broadcasting against (B,Tq,H,D)."""
    return x.transpose(0, 2, 1)[..., None]


def ring_attention_sharded(q, k, v, mesh, causal: bool = True, rules=None):
    """Ring attention on global ``[B, T, H, D]`` arrays inside jit.

    Wraps :func:`ring_attention` in ``shard_map`` over the model's
    layout — the PartitionSpec is derived from the active logical rules
    (batch/seq/heads/kv), so custom rule tables shard here exactly as
    they do in the rest of the model. The sequence axis is processed as
    a ring over whatever mesh axis "seq" maps to while XLA still
    partitions batch and heads.
    """
    from flax.linen import spmd as flax_spmd

    from jax import shard_map

    from ..parallel.sharding import DEFAULT_RULES

    if rules is None:
        # inherit the rule table active around the model application
        from flax.linen import partitioning as nn_partitioning

        rules = list(nn_partitioning.get_axis_rules()) or DEFAULT_RULES
    spec = flax_spmd.logical_to_mesh_axes(
        ("batch", "seq", "heads", "kv"), rules
    )
    seq_axis = spec[1]
    if seq_axis is None:
        raise ValueError(
            "ring attention needs the 'seq' logical axis mapped to a mesh "
            f"axis in the rules; got {rules}"
        )
    fn = shard_map(
        partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
