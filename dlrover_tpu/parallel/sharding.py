"""Logical-axis sharding rules: one table maps model axes → mesh axes.

This is the pjit/GSPMD replacement for everything the reference delegates
to Megatron/DeepSpeed: instead of wiring process groups, we annotate
logical axes on params/activations and let XLA insert the collectives.

Rules follow the standard TPU transformer recipe:
- batch        → (dp, fsdp): data sharded over both data axes
- seq          → sp: sequence/context parallelism for long context
- embed        → fsdp: hidden dim of params sharded ZeRO-style
- heads / mlp  → tp: megatron-style column/row parallel matmuls
- vocab        → tp: sharded embedding/logits
"""

from typing import Any, Dict, List, Optional, Tuple

import jax
from flax.linen import partitioning as nn_partitioning
from flax.linen import spmd as flax_spmd
from jax.sharding import Mesh, NamedSharding, PartitionSpec

LogicalRules = List[Tuple[str, Any]]

DEFAULT_RULES: LogicalRules = [
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv", None),
    ("kv_heads", None),  # GQA kv-head groups: few of them; keep local
    ("q_lora", None),  # latent attention's two ranks: a few hundred wide,
    ("kv_lora", None),  # contracted right after they are made; keep local
    ("mlp", "tp"),
    # a gated short convolution is channel-wise between its two products:
    # the channels split like an MLP's hidden dim, the taps stay whole
    ("conv_channels", "tp"),
    ("conv_taps", None),
    # a Mamba-2 mixer: the fused input projection's columns mix the heads'
    # channels with B and C, which every head reads, and stay whole; the
    # output projection contracts the inner width like an MLP's second matrix
    ("mamba_proj", None),
    ("mamba_channels", None),
    ("mamba_inner", "tp"),
    ("mamba_heads", None),
    # a gated delta-rule mixer, by the same reasoning: the fused projection
    # and the convolution's channels whole, the output projection contracted
    ("delta_proj", None),
    ("delta_channels", None),
    ("delta_inner", "tp"),
    ("delta_heads", None),
    ("vocab", "tp"),
    ("expert", "ep"),  # MoE experts distributed over the ep axis
    ("expert_mlp", "tp"),  # per-expert hidden dim still tensor-parallel
    ("stage", "pp"),
    ("norm", None),
]

# ---------------------------------------------------------------------------
# reshard rule table
# ---------------------------------------------------------------------------
#
# The statically-verified half of "restore INTO a different sharding"
# (ROADMAP items 1/4): before the dynamic reshard path exists, every
# state-tree category the checkpoint engine saves must declare how it
# restores when the elastic world moves along the DP×TP×PP rung ladder.
# The ``reshard-coverage`` tpurun-lint pass (docs/analysis.md)
# cross-checks this table against ``TrainState``'s fields, against the
# mesh axes ``DEFAULT_RULES`` can put on a saved leaf, and against
# dict-literal save sites — a category saved with no rule for a rung
# fails lint instead of failing (or silently replicating) at restore.
# Pure literals only: the lint pass reads this file by AST, never by
# import.

# The world ladder re-extents these mesh axes on a rung change; every
# sharded-policy rule below must cover them. Since the DP↔PP/TP
# replanner (parallel/replan.py) landed, a rung change can move tp/pp
# extents too, not just the data axes.
ELASTIC_AXES = ("dp", "fsdp", "tp", "pp")

RESHARD_POLICIES = (
    # replicate:     scalar/small leaves — restore replicated on any rung
    # respec:        re-derive the PartitionSpec on the target mesh and
    #                reshard the assembled global array via device_put
    # mirror_params: optimizer slots adopt the matching param leaf's rule
    #                (shape-matched; scalar counts replicate)
    # mirror_dp:     mirror_params PLUS cross-replica weight-update
    #                sharding (arXiv:2004.13336): moments additionally
    #                shard dim 0 over ``dp``, gathered at the update by
    #                GSPMD-inserted collectives
    # host_local:    per-host payloads (rng, data cursors, metadata) —
    #                never cross a reshard boundary
    "replicate",
    "respec",
    "mirror_params",
    "mirror_dp",
    "host_local",
)

RESHARD_RULES: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    # category: (policy, mesh axes the category's shardings may reference)
    "step": ("replicate", ()),
    "params": ("respec", ("dp", "fsdp", "ep", "tp", "sp", "pp")),
    "opt_state": ("mirror_dp", ("dp", "fsdp", "ep", "tp", "sp", "pp")),
    # the engine's ``extra=`` side-channel (dataloader cursors, torch
    # host trees): opaque host bytes, restored verbatim per host
    "extra": ("host_local", ()),
}


def logical_to_sharding(
    logical_spec: PartitionSpec, mesh: Mesh, rules: Optional[LogicalRules] = None
) -> NamedSharding:
    spec = flax_spmd.logical_to_mesh_axes(logical_spec, rules or DEFAULT_RULES)
    return NamedSharding(mesh, spec)


def tree_logical_to_sharding(
    logical_specs, mesh: Mesh, rules: Optional[LogicalRules] = None
):
    """Map a pytree of logical PartitionSpecs to NamedShardings."""
    return jax.tree.map(
        lambda s: logical_to_sharding(s, mesh, rules),
        logical_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def batch_sharding(mesh: Mesh, rules: Optional[LogicalRules] = None) -> NamedSharding:
    """Sharding for [batch, seq, ...] input arrays."""
    return logical_to_sharding(PartitionSpec("batch", "seq"), mesh, rules)


def data_sharding_for(
    example, mesh: Mesh, rules: Optional[LogicalRules] = None
) -> NamedSharding:
    """Rank-aware data sharding: dim 0 is batch, dim 1 (if any) is seq."""
    rank = len(getattr(example, "shape", ()))
    if rank == 0:
        return logical_to_sharding(PartitionSpec(), mesh, rules)
    axes = ["batch"] + (["seq"] if rank > 1 else [])
    axes += [None] * (rank - len(axes))
    return logical_to_sharding(PartitionSpec(*axes), mesh, rules)


def with_logical_constraint(x, *logical_axes: Optional[str]):
    """Annotate an activation with logical axes inside a jitted fn
    (``models/layers.py: constrain``). Without a mesh flax returns ``x``
    untouched, so on a one-chip cell the ~50 annotations in ``models/``
    constrain nothing (ROADMAP S1 / D17)."""
    return flax_spmd.with_logical_constraint(
        x, PartitionSpec(*logical_axes), fallback=flax_spmd.RulesFallback.NO_CONSTRAINT
    )


def apply_rules(rules: Optional[LogicalRules] = None):
    """Context manager installing the logical axis rules for flax modules."""
    return nn_partitioning.axis_rules(rules or DEFAULT_RULES)


# ---------------------------------------------------------------------------
# reshard rule drivers (the dynamic consumers of RESHARD_RULES)
# ---------------------------------------------------------------------------
#
# The durable tier's reshard-on-read restore (checkpoint/durable/) reads
# a manifest saved under one mesh and materializes state under the
# current one; these helpers are the policy dispatch it drives. They
# live here so the policy table and its interpreters stay in one file —
# the table itself remains pure literals for the lint pass's AST read.


def category_of_path(path: str) -> str:
    """TrainState category of a "/"-joined pytree leaf path. Unknown
    roots restore under the opaque ``extra`` (host_local) rule."""
    head = path.split("/", 1)[0]
    return head if head in RESHARD_RULES else "extra"


def reshard_rule_for(category: str) -> Tuple[str, Tuple[str, ...]]:
    """(policy, allowed mesh axes) for a category; unknown → extra."""
    return RESHARD_RULES.get(category, RESHARD_RULES["extra"])


def spec_mesh_axes(spec) -> Tuple[str, ...]:
    """Mesh axis names a PartitionSpec (or its jsonable form) references."""
    axes: List[str] = []
    for entry in tuple(spec or ()):
        parts = entry if isinstance(entry, (tuple, list)) else (entry,)
        for ax in parts:
            if isinstance(ax, str):
                axes.append(ax)
    return tuple(axes)


def validate_saved_spec(category: str, spec) -> None:
    """Reject a saved spec referencing axes its category's rule does not
    cover — a manifest written by a build with out-of-table shardings
    must fail loudly at restore, not silently mis-place state."""
    policy, allowed = reshard_rule_for(category)
    stray = [ax for ax in spec_mesh_axes(spec) if ax not in allowed]
    if stray:
        raise ValueError(
            f"saved spec {tuple(spec or ())} for category {category!r} "
            f"references mesh axes {stray} outside its {policy!r} rule "
            f"coverage {allowed}"
        )


def respec_spec(saved_spec, mesh: Mesh, global_shape) -> PartitionSpec:
    """Re-derive a leaf's PartitionSpec on the *target* mesh.

    Per dim, keep each saved mesh axis only if the target mesh has it
    AND the accumulated partitioning still divides the dim — the same
    cleaning the train step applies when specs meet a smaller world.
    Dropped axes mean that dim replicates over them, which is always
    correct (ELASTIC_AXES re-extents are exactly this case).
    """
    shape = tuple(global_shape or ())
    entries: List[Any] = []
    for d, entry in enumerate(tuple(saved_spec or ())):
        parts = entry if isinstance(entry, (tuple, list)) else (entry,)
        dim = shape[d] if d < len(shape) else 0
        kept: List[str] = []
        divisor = 1
        for ax in parts:
            if not isinstance(ax, str) or ax not in mesh.axis_names:
                continue
            size = int(mesh.shape[ax])
            if dim > 0 and dim % (divisor * size) == 0:
                kept.append(ax)
                divisor *= size
        if not kept:
            entries.append(None)
        elif len(kept) == 1:
            entries.append(kept[0])
        else:
            entries.append(tuple(kept))
    return PartitionSpec(*entries)


def respec_sharding(
    category: str, saved_spec, mesh: Mesh, global_shape
) -> Optional[NamedSharding]:
    """Policy dispatch: target-mesh NamedSharding for one restored leaf,
    or None for ``host_local`` payloads (never cross a reshard — the
    caller keeps them on the host, per current rank).

    ``mirror_params``/``mirror_dp`` resolve like ``respec`` here: when
    the caller has a template state its leaf shardings win anyway (the
    template already shape-matched slots to params); templateless
    restores fall back to the slot's own saved spec, which the
    save-side mirroring made identical to its param's (plus the ``dp``
    dim-0 factor for ``mirror_dp`` — ``respec_spec`` keeps or drops it
    by the target mesh's own extents, which is exactly the gather/
    reshard the rung transition needs).
    """
    policy, _ = reshard_rule_for(category)
    if policy == "host_local":
        return None
    validate_saved_spec(category, saved_spec)
    if policy == "replicate":
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh, respec_spec(saved_spec, mesh, global_shape))


def place_arrays_with_rules(
    saved_specs: Dict[str, Any],
    arrays: Dict[str, Any],
    mesh: Mesh,
) -> Dict[str, Any]:
    """The shared reshard engine: place host arrays saved under one mesh
    onto ``mesh`` by category rule + saved spec.

    Used by both reshard-on-read paths — the durable tier's
    templateless restore (``checkpoint/durable/restore.py``) and the
    in-memory flash-image transition the elastic replanner drives
    (``CheckpointEngine.load_resharded``). ``host_local`` leaves stay
    host-side; everything else goes down in ONE batched ``device_put``
    (per-leaf puts serialize transfers and wreck restore MTTR).
    """
    paths, host_arrs, shardings = [], [], []
    placed: Dict[str, Any] = {}
    for path, arr in arrays.items():
        sharding = respec_sharding(
            category_of_path(path),
            saved_specs.get(path, []),
            mesh,
            getattr(arr, "shape", ()),
        )
        if sharding is None:  # host_local — stays on the host
            placed[path] = arr
            continue
        paths.append(path)
        host_arrs.append(arr)
        shardings.append(sharding)
    if paths:
        placed.update(zip(paths, jax.device_put(host_arrs, shardings)))
    return placed


def sharded_generate_jit(
    fn, mesh: Mesh, param_trees, n_data_args: int, rules=None
):
    """jit ``fn(*param_trees, *data_args, rng)`` SPMD over ``mesh``.

    The one copy of the sharded-generation wrapper (used by
    :mod:`models.generation`): data args shard over the batch axes, the
    rng replicates, and each entry of ``param_trees`` is a
    NamedSharding tree — or None, meaning that model's params
    replicate. When EVERY tree is None, in_shardings is omitted
    entirely so already-placed device arrays keep their layout. The
    returned callable enters the mesh + logical-rule contexts around
    every call so module constraints resolve.
    """
    from .mesh import current_mesh

    jit_kwargs = {}
    if any(t is not None for t in param_trees):
        rep = NamedSharding(mesh, PartitionSpec())
        data_sh = logical_to_sharding(
            PartitionSpec("batch", None), mesh, rules
        )
        jit_kwargs["in_shardings"] = (
            *[t if t is not None else rep for t in param_trees],
            *([data_sh] * n_data_args),
            rep,
        )
    jitted = jax.jit(fn, **jit_kwargs)

    def run(*args):
        with mesh, apply_rules(rules), current_mesh(mesh):
            return jitted(*args)

    return run
