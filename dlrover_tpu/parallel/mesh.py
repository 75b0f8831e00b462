"""Global device mesh construction and elastic re-meshing.

TPU-first replacement for the reference's process-group world management
(torch elastic re-creates NCCL groups on membership change; XLA worlds are
static, so *every* membership change is a re-mesh). The mesh has five
logical axes:

  dp    pure data parallel (replicated params) — the elastic axis; on
        multislice jobs this is the across-slice/DCN axis
  fsdp  data parallel with sharded params/optimizer (ZeRO-style)
  ep    expert parallel (MoE experts distributed; gshard-style a2a
        dispatch rides this axis)
  tp    tensor (model) parallel — ICI neighbors
  sp    sequence/context parallel for long-context (ring attention)
  pp    pipeline stages

Axis sizes are chosen per elastic world size by :func:`choose_mesh_shape`,
so a node join/leave maps to "rebuild mesh with new dp extent" while
tp/sp/pp extents (ICI-bound) stay fixed.
"""

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# ---------------------------------------------------------------------------
# mesh-axis registry
# ---------------------------------------------------------------------------
#
# The single source of truth for axis NAMES, the ENV_KNOBS idiom applied
# to SPMD: every PartitionSpec / NamedSharding / shard_map spec literal,
# every ``param_with_axes``/``with_logical_constraint`` annotation and
# every collective axis name across parallel/, models/, ops/ and
# checkpoint/meta.py must name an axis registered here — enforced by the
# ``mesh-axes`` tpurun-lint pass (docs/analysis.md), which also
# cross-checks ``MESH_AXES`` and ``sharding.DEFAULT_RULES`` against this
# table. Keep the values PURE LITERALS: the lint pass reads this file by
# AST (it can never import jax), so computed entries are invisible to it
# and are reported as a registry parse failure.
#
# kind "mesh":    an axis of the physical device mesh (a Mesh
#                 construction axis; collectives ride it).
# kind "logical": a model-side logical axis, mapped onto mesh axes by
#                 ``sharding.DEFAULT_RULES``.
MESH_AXIS_REGISTRY: Dict[str, Tuple[str, str]] = {
    # name: (kind, doc)
    "dp": ("mesh", "pure data parallel (replicated params) — the elastic axis; DCN on multislice"),
    "fsdp": ("mesh", "data parallel with ZeRO-style sharded params/optimizer"),
    "ep": ("mesh", "expert parallel (MoE experts distributed; a2a dispatch)"),
    "tp": ("mesh", "tensor (model) parallel — ICI neighbors"),
    "sp": ("mesh", "sequence/context parallel (ring attention)"),
    "pp": ("mesh", "pipeline stages"),
    "batch": ("logical", "leading data dim of inputs/activations"),
    "seq": ("logical", "sequence dim (context parallelism)"),
    "embed": ("logical", "model hidden dim of params"),
    "heads": ("logical", "attention query heads"),
    "kv": ("logical", "per-head projection dim (kept local)"),
    "kv_heads": ("logical", "GQA kv-head groups (few; kept local)"),
    "q_lora": ("logical", "latent attention's query rank (kept local)"),
    "kv_lora": ("logical", "latent attention's key/value rank, with its rope vector (kept local)"),
    "mlp": ("logical", "feed-forward hidden dim"),
    "conv_channels": ("logical", "a gated short convolution's channels, each with its own filter (split like an MLP's hidden dim)"),
    "conv_taps": ("logical", "a short convolution's filter taps (three; kept local)"),
    "mamba_proj": ("logical", "a Mamba-2 mixer's fused input projection [z ; x B C ; dt]: heads' channels beside the groups' B and C (kept local)"),
    "mamba_channels": ("logical", "the channels [x ; B ; C] of a Mamba-2 mixer's short convolution (kept local)"),
    "mamba_inner": ("logical", "a Mamba-2 mixer's inner width, heads x channels, where its output projection contracts it (split like an MLP's hidden dim)"),
    "mamba_heads": ("logical", "a Mamba-2 mixer's per-head floats: dt_bias, A_log, D (kept local)"),
    "delta_proj": ("logical", "a gated delta-rule mixer's fused input projection [q ; k ; v ; z] (kept local)"),
    "delta_channels": ("logical", "the channels [q ; k ; v] of a gated delta-rule mixer's short convolution (kept local)"),
    "delta_inner": ("logical", "a gated delta-rule mixer's value width, heads x channels, where its output projection contracts it (split like an MLP's hidden dim)"),
    "delta_heads": ("logical", "a gated delta-rule mixer's per-head floats and their projection: beta, a, dt_bias, A_log (kept local)"),
    "vocab": ("logical", "embedding/logits vocabulary dim"),
    "expert": ("logical", "MoE expert index"),
    "expert_mlp": ("logical", "per-expert feed-forward hidden dim"),
    "stage": ("logical", "pipeline stage index"),
    "norm": ("logical", "norm scale vectors (kept local)"),
}

# Physical mesh axes IN RESHAPE ORDER (build_mesh depends on the order:
# tp/sp innermost → ICI neighbors). The mesh-axes lint pass enforces
# that this tuple equals the registry's kind-"mesh" entries exactly, so
# the two can never drift.
MESH_AXES = ("dp", "fsdp", "ep", "tp", "sp", "pp")


@dataclass(frozen=True)
class MeshConfig:
    """Desired parallelism extents. -1 on dp/fsdp means "absorb remaining
    devices" (at most one axis may be -1)."""

    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    def axis_sizes(self) -> Tuple[int, int, int, int, int, int]:
        return (self.dp, self.fsdp, self.ep, self.tp, self.sp, self.pp)

    def fixed_product(self) -> int:
        return math.prod(s for s in self.axis_sizes() if s > 0)

    def resolve(self, n_devices: int) -> "ResolvedMesh":
        sizes = list(self.axis_sizes())
        free = [i for i, s in enumerate(sizes) if s == -1]
        if len(free) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes if s > 0)
        if free:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[free[0]] = n_devices // fixed
        total = math.prod(sizes)
        if total != n_devices:
            raise ValueError(
                f"mesh {dict(zip(MESH_AXES, sizes))} needs {total} devices, "
                f"have {n_devices}"
            )
        return ResolvedMesh(sizes=tuple(sizes))


@dataclass(frozen=True)
class ResolvedMesh:
    sizes: Tuple[int, int, int, int, int, int]

    def as_dict(self) -> Dict[str, int]:
        return dict(zip(MESH_AXES, self.sizes))


def build_mesh(
    config: MeshConfig, devices: Optional[Sequence] = None
) -> Mesh:
    """Build the global mesh over all (or given) devices.

    Device ordering: JAX returns devices grouped host-major on TPU, so
    reshaping [dp, fsdp, tp, sp, pp] keeps tp/sp innermost → they land on
    ICI neighbors within a host/slice, while dp spans hosts/slices (DCN
    for multislice) — the layout the scaling recipe wants.
    """
    devices = list(devices if devices is not None else jax.devices())
    resolved = config.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(resolved.sizes)
    return Mesh(dev_array, MESH_AXES)


def choose_mesh_shape(
    n_devices: int,
    ep: int = 1,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    prefer_fsdp: bool = True,
) -> MeshConfig:
    """Pick dp/fsdp extents for an elastic world of ``n_devices``.

    The ICI-bound extents (ep, tp, sp, pp) are honored as given; the
    remaining factor goes to fsdp (params sharded — memory-optimal) or dp.
    Raises if n_devices is not divisible — the caller (master) must pick a
    world size that is a multiple of the slice unit (= ep*tp*sp*pp).
    """
    inner = ep * tp * sp * pp
    if n_devices % inner != 0:
        raise ValueError(
            f"world size {n_devices} not a multiple of ep*tp*sp*pp={inner}"
        )
    outer = n_devices // inner
    if prefer_fsdp:
        return MeshConfig(dp=1, fsdp=outer, ep=ep, tp=tp, sp=sp, pp=pp)
    return MeshConfig(dp=outer, fsdp=1, ep=ep, tp=tp, sp=sp, pp=pp)


_CURRENT_MESH: List[Optional[Mesh]] = [None]


class current_mesh:
    """Context manager publishing the active mesh to modules that need
    the concrete object (e.g. shard_map-wrapped ring attention); plain
    pjit sharding constraints don't need it."""

    def __init__(self, mesh: Optional[Mesh]):
        self._mesh = mesh

    def __enter__(self):
        self._prev = _CURRENT_MESH[0]
        _CURRENT_MESH[0] = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        _CURRENT_MESH[0] = self._prev
        return False


def get_current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH[0]


# -- multi-slice topology (SURVEY §7: the realistic elastic unit is a
# SLICE — dp rides DCN between slices, everything else must stay on a
# slice's ICI; reference node_unit semantics, rdzv_manager.py:179-181) --


@dataclass(frozen=True)
class SliceTopology:
    """``num_slices`` TPU slices of ``slice_size`` chips each. Chips
    within a slice share ICI; traffic between slices rides DCN. The
    elastic unit is a whole slice: a job grows/shrinks/loses capacity
    slice-at-a-time, never chip-at-a-time."""

    num_slices: int
    slice_size: int

    @property
    def total(self) -> int:
        return self.num_slices * self.slice_size


def choose_multislice_shape(
    topology: SliceTopology, ep: int = 1, tp: int = 1, sp: int = 1,
    pp: int = 1,
) -> MeshConfig:
    """The multislice scaling recipe: dp across slices (DCN carries one
    gradient all-reduce per step — the only inter-slice collective),
    fsdp + the ICI-bound axes (ep/tp/sp/pp) within a slice. Losing a
    slice = same call with ``num_slices - 1``: the per-slice layout is
    unchanged, so re-mesh is a pure dp shrink."""
    inner = ep * tp * sp * pp
    if topology.slice_size % inner != 0:
        raise ValueError(
            f"slice size {topology.slice_size} not divisible by "
            f"ep*tp*sp*pp={inner}: per-step collectives would cross DCN"
        )
    return MeshConfig(
        dp=topology.num_slices,
        fsdp=topology.slice_size // inner,
        ep=ep, tp=tp, sp=sp, pp=pp,
    )


def build_multislice_mesh(
    config: MeshConfig, topology: SliceTopology,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh over a multi-slice world, validating that only the
    dp axis crosses the DCN boundary between slices.

    Devices must be listed slice-major (slice 0's chips first — the
    order ``jax.devices()`` returns on multislice, hosts grouped per
    slice). The [dp, fsdp, ep, tp, sp, pp] reshape puts each fixed-dp
    block on ``inner = fsdp*ep*tp*sp*pp`` contiguous devices; requiring
    ``inner | slice_size`` keeps every such block — and therefore every
    non-dp collective — inside one slice's ICI domain, while dp strides
    across blocks and is the only axis whose collective rides DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) != topology.total:
        raise ValueError(
            f"{len(devices)} devices != {topology.num_slices} slices × "
            f"{topology.slice_size}"
        )
    resolved = config.resolve(len(devices))
    sizes = resolved.as_dict()
    inner = math.prod(v for k, v in sizes.items() if k != "dp")
    if topology.slice_size % inner != 0:
        raise ValueError(
            f"non-dp axes product {inner} does not divide slice size "
            f"{topology.slice_size}: fsdp/ep/tp/sp/pp shards would span "
            f"the DCN boundary and per-step collectives would leave ICI"
        )
    # inner | slice_size (+ the device-count check above) implies
    # dp = num_slices * (slice_size // inner): slice boundaries always
    # fall between fixed-dp blocks, never through a non-dp axis.
    dev_array = np.asarray(devices).reshape(resolved.sizes)
    return Mesh(dev_array, MESH_AXES)


def local_batch_slice(global_batch: int, mesh: Mesh) -> int:
    """Per-data-shard batch size on the current mesh."""
    data_extent = mesh.shape["dp"] * mesh.shape["fsdp"]
    if global_batch % data_extent != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by data extent {data_extent}"
        )
    return global_batch // data_extent
