"""SPMD pipeline parallelism over the ``pp`` mesh axis.

The reference carries PP *awareness only* (Megatron pp_rank in checkpoint
shard math, ``megatron_engine.py:52-62`` — the schedule itself lives in
Megatron).  Here the schedule is native: a GPipe microbatch pipeline
written the TPU way — ``shard_map`` over the ``pp`` axis, one
``lax.scan`` over pipeline ticks, activations rotated stage→stage with
``ppermute`` — so the whole schedule is one XLA program: no host-side
stage loop, static shapes, differentiable end-to-end (``ppermute`` and
``scan`` both have transpose rules, so ``jax.grad`` yields the classic
backward pipeline automatically).

Layout: every stage's params are stacked on a leading axis of extent
``pp`` and sharded over it, so each device slice holds exactly its own
stage's weights; the compute per tick is identical on every stage (SPMD),
inactive ticks compute on garbage that is provably never consumed (the
bubble — ``(S-1)/(M+S-1)`` of the schedule, amortized by more
microbatches).
"""

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def stack_stage_params(per_stage_params) -> Any:
    """[tree_s for s in stages] → one tree with leaves stacked on dim 0
    (extent = #stages). All stages must share one tree structure — put
    heterogeneous pieces (embedding, unembedding) OUTSIDE the pipeline."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def stage_sharding(tree, mesh: Mesh, axis: str = "pp"):
    """NamedSharding tree placing each stage's slice on its pp rank."""
    sharding = NamedSharding(mesh, P(axis))

    def leaf_sharding(leaf):
        return sharding

    return jax.tree.map(leaf_sharding, tree)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
    data_spec: P = P(),
):
    """Run ``stage_fn`` as a GPipe pipeline over the ``axis`` mesh axis.

    Args:
      stage_fn: ``(params_one_stage, x[mb, ...]) -> y[mb, ...]`` — the
        per-stage computation (e.g. ``layers_per_stage`` transformer
        blocks). Input and output shapes must match (residual-stream
        discipline), because activations rotate between identical stages.
      stage_params: pytree with every leaf stacked ``[S, ...]`` and
        sharded over ``axis`` (see :func:`stack_stage_params`).
      microbatches: ``[M, mb, ...]`` — the batch pre-split into M
        microbatches.
      mesh: the global mesh; ``mesh.shape[axis]`` = number of stages.
      data_spec: PartitionSpec of the microbatch tensor over the OTHER
        mesh axes (e.g. ``P(None, ("dp", "fsdp"))`` to keep the batch
        dim data-parallel through the pipeline — the default replicates,
        which makes dp ranks compute redundantly). Must not mention
        ``axis``; shard_map's transpose inserts the grad psum over the
        data axes automatically.

    Returns ``[M, mb, ...]`` — last stage's output per microbatch,
    replicated across the ``axis`` ranks, sharded per ``data_spec``.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    # Validate data_spec regardless of S: an invalid spec must not turn
    # into silent acceptance when an elastic re-mesh lands on pp=1.
    flat_axes = []
    for entry in tuple(data_spec or ()):
        if isinstance(entry, (tuple, list)):
            flat_axes.extend(entry)
        elif entry is not None:
            flat_axes.append(entry)
    if axis in flat_axes:
        raise ValueError(f"data_spec {data_spec} must not mention {axis!r}")
    if S == 1:
        # degenerate pipeline: plain scan over microbatches (data_spec
        # sharding rides the caller's jit/constraints)
        params = jax.tree.map(lambda p: p[0], stage_params)
        return jax.lax.map(lambda mb: stage_fn(params, mb), microbatches)

    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_device(params_local, x_all):
        # params_local leaves arrive as [1, ...]: this rank's stage
        params = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis)
        ticks = M + S - 1

        state = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects the next microbatch; later stages consume
            # what the previous stage pushed last tick
            inject = x_all[jnp.clip(t, 0, M - 1)]
            x_in = jnp.where(idx == 0, inject, state)
            y = stage_fn(params, x_in)
            # last stage banks microbatch t-(S-1) once it is real
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = (idx == S - 1) & (t >= S - 1) & (t - (S - 1) < M)
            outputs = jnp.where(
                write, outputs.at[out_idx].set(y), outputs
            )
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(ticks)
        )
        # Only the last stage holds real outputs; broadcast them so the
        # loss (outside the pipeline) sees a replicated tensor.
        outputs = jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), data_spec),
        out_specs=data_spec,
        check_vma=False,
    )(stage_params, microbatches)


def refold_stages(stage_params: Any, new_num_stages: int) -> Any:
    """Re-stage params for a different pipeline depth (elastic re-mesh
    of the pp axis): ``[S, L, ...]`` per-layer stacks become
    ``[S', (S·L)/S', ...]`` — consecutive stages concatenate in order,
    so the composed function is unchanged (stage fns scan their layer
    axis). The new stage count must divide the total layer count S·L.

    Contract: every leaf is layer-stacked ``[stages, layers, ...]`` (the
    shape :func:`init_pipelined_blocks` produces and a scanning stage fn
    consumes). Per-stage leaves WITHOUT a layer axis cannot be refolded
    — their second dim would be misread as layers — and are rejected by
    the rank check below only when rank < 2; keep all stage params
    layer-stacked."""

    def refold(leaf):
        if leaf.ndim < 2:
            raise ValueError(
                f"refold_stages needs [stages, layers, ...] leaves; got "
                f"shape {leaf.shape}"
            )
        s, l = leaf.shape[0], leaf.shape[1]
        total = s * l
        if total % new_num_stages:
            raise ValueError(
                f"{total} layers not divisible into {new_num_stages} stages"
            )
        return leaf.reshape(
            (new_num_stages, total // new_num_stages) + leaf.shape[2:]
        )

    return jax.tree.map(refold, stage_params)


def stage_param_avals(layer_params: Any, num_stages: int) -> Any:
    """ShapeDtypeStructs for ONE stage's params at ``num_stages`` depth.

    ``layer_params`` leaves are layer-stacked ``[total_layers, ...]``
    (concrete arrays or avals); a stage at depth ``num_stages`` scans
    ``total_layers / num_stages`` of them. This is what lets the
    compile-ahead service lower per-STAGE programs for every pipeline
    depth on the rung ladder without materializing any weights — a
    pp-depth change then recompiles one stage program, not the world.
    """

    def aval(leaf):
        total = leaf.shape[0]
        if total % num_stages:
            raise ValueError(
                f"{total} layers not divisible into {num_stages} stages"
            )
        return jax.ShapeDtypeStruct(
            (total // num_stages,) + tuple(leaf.shape[1:]), leaf.dtype
        )

    return jax.tree.map(aval, layer_params)


def split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] → [M, B/M, ...]."""
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"batch {B} not divisible by {num_microbatches} microbatches"
        )
    return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])


def merge_microbatches(x: jax.Array) -> jax.Array:
    """[M, mb, ...] → [M*mb, ...]."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


# ---------------------------------------------------------------------------
# A minimal pipelined transformer LM built on the primitive: embedding and
# unembedding live outside the pipeline (heterogeneous), the homogeneous
# block stack is pipelined. Serves as the reference usage + test vehicle.
# ---------------------------------------------------------------------------


def init_pipelined_blocks(
    rng: jax.Array,
    num_stages: int,
    layers_per_stage: int,
    embed_dim: int,
    mlp_dim: int,
    param_dtype=jnp.float32,
):
    """Per-stage params for ``transformer_stage_fn``: each stage is
    ``layers_per_stage`` pre-norm MLP blocks (attention-free keeps the
    test vehicle small; any residual-stream block slots in the same
    way). Leaves: [S, L, ...]."""

    def one_stage(key):
        keys = jax.random.split(key, layers_per_stage)

        def one_layer(k):
            k1, k2 = jax.random.split(k)
            return {
                "w1": jax.random.normal(k1, (embed_dim, mlp_dim), param_dtype)
                * 0.02,
                "w2": jax.random.normal(k2, (mlp_dim, embed_dim), param_dtype)
                * 0.02,
                "scale": jnp.ones((embed_dim,), param_dtype),
            }

        return jax.tree.map(
            lambda *ls: jnp.stack(ls), *[one_layer(k) for k in keys]
        )

    stages = [
        one_stage(k) for k in jax.random.split(rng, num_stages)
    ]
    return stack_stage_params(stages)


def transformer_stage_fn(stage_params, x):
    """Residual MLP blocks: x[mb, T, D] -> [mb, T, D]. Layers scanned so
    the per-stage code is one trace regardless of depth."""

    def layer(x, p):
        h32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(h32), axis=-1, keepdims=True)
        h = (h32 * jax.lax.rsqrt(var + 1e-5) * p["scale"]).astype(x.dtype)
        h = jax.nn.gelu(h @ p["w1"].astype(x.dtype))
        return x + (h @ p["w2"].astype(x.dtype)), None

    x, _ = jax.lax.scan(layer, x, stage_params)
    return x
