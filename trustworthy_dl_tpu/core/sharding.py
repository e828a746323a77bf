"""The unified logical-axis sharding registry — the ONE place
PartitionSpecs are spelled.

Every parallel mode used to hand-wire its own specs (`core/mesh.py`,
`parallel/tensor_parallel.py` alone spelled 17) and optimizer/param
state was fully replicated except for the lone ZeRO-1 shim.  This
module adopts the Transformer-Engine pattern (named logical axes + one
rule table per parallelism mode + constraints applied by name): models
and subsystems declare *logical* axes once (``batch``, ``seqlen``,
``head``, ``node``, ``w_tp``, ``w_fsdp``, …) and the registry
translates them to mesh axes for the active mode.  dp/fsdp/tp/pp/sp
become configuration, not code paths.

The contract, enforced by the ``sharding-registry-only`` lint rule
(analysis/rules/locality.py): ``PartitionSpec(...)`` / bare ``P(...)``
construction outside THIS module (plus the explicit whitelist in
analysis/contracts.py) is a finding.  Call sites either resolve
logical names through :class:`ShardingRules` or use the mesh-axis
helpers below (:func:`row_sharding`, :func:`replicated_sharding`,
:func:`place_zero_sharded`, …) — which is what keeps every layout's
placement identical across trainer init, checkpoint restore, elastic
migration and serve-replica builds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from trustworthy_dl_tpu.core.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    STAGE_AXIS,
)

# ---------------------------------------------------------------------------
# Logical axis vocabulary (the names models/subsystems declare once).
# ---------------------------------------------------------------------------

BATCH = "batch"      #: per-step batch rows (data-parallel shards)
SEQLEN = "seqlen"    #: sequence/context positions (activations)
HEAD = "head"        #: attention heads (activations; Ulysses shards these)
HIDDEN = "hidden"    #: embedding/feature dims that stay whole under TP
LAYER = "layer"      #: stacked-layer leading dim of block params
NODE = "node"        #: trust-node rows ([num_nodes, ...] state/plan leaves)
STAGE = "stage"      #: pipeline-stage dim of stage-stacked leaves
EXPERT = "expert"    #: MoE expert dim
W_TP = "w_tp"        #: tensor-parallel weight dim (Megatron col/row split)
W_FSDP = "w_fsdp"    #: FSDP/ZeRO weight+optimizer shard dim

LOGICAL_AXES = frozenset({
    BATCH, SEQLEN, HEAD, HIDDEN, LAYER, NODE, STAGE, EXPERT, W_TP, W_FSDP,
})


def axis_rules(parallelism: str, *,
               fsdp: bool = False) -> Dict[str, Optional[str]]:
    """Logical-axis → mesh-axis table for one parallelism mode.

    Axes not named by a mode map to ``None`` (replicated on that dim).
    ``fsdp=True`` additionally maps :data:`W_FSDP` onto the data axis —
    ZeRO/FSDP sharding is a *rule*, not a code path.  Note the mode-
    dependent renames the table exists for: under pipelining the trust
    node IS the stage; under sequence parallelism the Ulysses exchange
    shards attention *heads* over the same mesh axis that shards
    *positions* elsewhere in the layer.
    """
    base: Dict[str, Optional[str]] = {a: None for a in LOGICAL_AXES}
    base[BATCH] = DATA_AXIS
    base[NODE] = DATA_AXIS
    if parallelism == "model":
        base[NODE] = STAGE_AXIS
        base[STAGE] = STAGE_AXIS
    elif parallelism == "tensor":
        base[W_TP] = MODEL_AXIS
    elif parallelism == "sequence":
        base[SEQLEN] = SEQ_AXIS
        base[HEAD] = SEQ_AXIS
    elif parallelism == "expert":
        base[EXPERT] = EXPERT_AXIS
    elif parallelism == "hybrid":
        # Hybrid meshes carry whatever axes the mesh_shape names; the
        # resolver drops rules whose mesh axis is absent, so one table
        # serves every hybrid composition.
        base[STAGE] = STAGE_AXIS
        base[W_TP] = MODEL_AXIS
        base[SEQLEN] = SEQ_AXIS
        base[HEAD] = SEQ_AXIS
        base[EXPERT] = EXPERT_AXIS
    elif parallelism != "data":
        raise ValueError(f"no sharding rules for parallelism={parallelism!r}")
    if fsdp:
        base[W_FSDP] = DATA_AXIS
    return base


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """One mode's resolved rule table.  The only spec 'constructor' call
    sites are allowed to hold: they name logical axes, this object
    translates — unknown names fail loudly (a typo'd axis silently
    replicating is exactly the drift the registry exists to prevent)."""

    parallelism: str
    table: Mapping[str, Optional[str]]

    def mesh_axis(self, logical: Optional[str]) -> Optional[str]:
        if logical is None:
            return None
        try:
            return self.table[logical]
        except KeyError:
            raise ValueError(
                f"unknown logical axis {logical!r} (known: "
                f"{sorted(LOGICAL_AXES)})") from None

    def partition_spec(self, *axes: Optional[str]) -> PartitionSpec:
        """Mesh-independent resolution (e.g. spec trees built before a
        mesh exists, shard_map in/out specs)."""
        return PartitionSpec(*(self.mesh_axis(a) for a in axes))

    def named_sharding(self, mesh: Mesh, *axes: Optional[str]
                       ) -> NamedSharding:
        """Mesh-aware resolution: rules whose mesh axis is absent from
        ``mesh`` resolve to None instead of failing, so one logical
        declaration serves every mesh the mode can build."""
        resolved = [self.mesh_axis(a) for a in axes]
        resolved = [a if a in mesh.axis_names else None for a in resolved]
        return NamedSharding(mesh, PartitionSpec(*resolved))

    def constrain(self, x: Any, *axes: Optional[str]) -> Any:
        """``with_sharding_constraint`` by logical name (inside jit,
        under a mesh context)."""
        return jax.lax.with_sharding_constraint(
            x, self.partition_spec(*axes))


def rules_for(parallelism: str, *, fsdp: bool = False) -> ShardingRules:
    return ShardingRules(parallelism, axis_rules(parallelism, fsdp=fsdp))


def resolve_tree(axes_tree: Any, rules: ShardingRules) -> Any:
    """Translate a logical-axis declaration tree (leaves are tuples of
    logical names, one per dim) into a PartitionSpec tree."""
    return jax.tree_util.tree_map(
        lambda axes: rules.partition_spec(*axes), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Mesh-axis helpers: the shared spellings every placement site funnels
# through (trainer init/restore, elastic migration, serve builds).
# ---------------------------------------------------------------------------


def replicated_spec() -> PartitionSpec:
    return PartitionSpec()


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def row_spec(mesh_axis: str, ndim: int = 1) -> PartitionSpec:
    """Leading-dim sharding for a per-node/per-stage row array."""
    return PartitionSpec(mesh_axis, *([None] * (max(ndim, 1) - 1)))


def row_sharding(mesh: Mesh, mesh_axis: str, ndim: int = 1) -> NamedSharding:
    return NamedSharding(mesh, row_spec(mesh_axis, ndim))


def axis_size(mesh: Mesh, mesh_axis: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get(mesh_axis, 1))


def row_placer(mesh: Mesh, mesh_axis: str, n: int):
    """The ONE per-node-row placement rule, shared by trainer placement
    and elastic migration: a leaf with leading dim ``n`` shards its rows
    over ``mesh_axis`` when that divides evenly; everything else
    replicates."""
    size = axis_size(mesh, mesh_axis)
    repl = replicated_sharding(mesh)

    def place(leaf):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == n \
                and size > 1 and n % size == 0:
            return jax.device_put(leaf, row_sharding(mesh, mesh_axis,
                                                     leaf.ndim))
        return jax.device_put(leaf, repl)

    return place


# ---------------------------------------------------------------------------
# ZeRO/FSDP placement — the generalized `zero1_place_opt_state`.
# ---------------------------------------------------------------------------


def zero_shard_spec(shape: Sequence[int], n_shards: int,
                    mesh_axis: str) -> PartitionSpec:
    """First evenly-divisible dim shards over ``mesh_axis``; leaves with
    no such dim (scalars, odd shapes) replicate.  This is the ZeRO-1
    moment rule generalized to any tree (params under FSDP use it too)."""
    for i, dim in enumerate(shape):
        if dim >= n_shards and dim % n_shards == 0:
            spec: list = [None] * len(shape)
            spec[i] = mesh_axis
            return PartitionSpec(*spec)
    return PartitionSpec()


def place_zero_sharded(tree: Any, mesh: Mesh,
                       mesh_axis: str = DATA_AXIS) -> Any:
    """ZeRO/FSDP-style placement of a whole pytree: every leaf shards on
    its first evenly-divisible dim over ``mesh_axis`` (annotation-only —
    GSPMD partitions the update and gathers where needed, so an n-way
    mesh keeps ~1/n of the bytes per chip).  Replicates everything when
    the axis is absent or size 1, so the helper is safe at any layout.

    This is THE placement both the trainer (`_place_on_mesh`) and
    elastic migration (`elastic/reassignment.py`) use — one spelling, so
    an evict/readmit cycle reproduces exactly the shardings a fresh
    trainer would choose."""
    n = axis_size(mesh, mesh_axis)
    repl = replicated_sharding(mesh)

    def place(leaf):
        if getattr(leaf, "ndim", 0) >= 1 and n > 1:
            spec = zero_shard_spec(leaf.shape, n, mesh_axis)
            return jax.device_put(leaf, NamedSharding(mesh, spec))
        return jax.device_put(leaf, repl)

    return jax.tree_util.tree_map(place, tree)


def tree_bytes_per_device(tree: Any) -> int:
    """Actual per-device bytes of a placed pytree: each leaf contributes
    its shard size on the busiest device (replicated leaves count fully),
    measured from shardings, not estimated."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = getattr(leaf, "sharding", None)
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        if sh is None or not hasattr(sh, "shard_shape"):
            total += nbytes
            continue
        try:
            shard = sh.shard_shape(leaf.shape)
            size = 1
            for d in shard:
                size *= int(d)
            itemsize = leaf.dtype.itemsize
            total += size * itemsize
        except Exception:
            total += nbytes
    return total


# ---------------------------------------------------------------------------
# Serving: tensor-parallel replica submeshes.
# ---------------------------------------------------------------------------


def serve_tp_mesh(tp_size: int,
                  devices: Optional[Sequence[Any]] = None) -> Mesh:
    """A serve replica's TP submesh: ``tp_size`` devices over the
    'model' axis.  The fleet carves per-replica device slices and passes
    them here; a single-engine caller defaults to the first ``tp_size``
    local devices."""
    if tp_size < 1:
        raise ValueError("tp_size must be >= 1")
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp_size:
        raise ValueError(
            f"serve TP mesh needs {tp_size} devices, have {len(devices)}")
    import numpy as np

    return Mesh(np.array(devices[:tp_size]), (MODEL_AXIS,))


def place_serve_tp(params: Any, mesh: Mesh) -> Any:
    """Place serve params with the model's declared TP layout on a
    replica submesh (no-op when the mesh has no 'model' axis).  Resolves
    through the same registry rules training TP uses — one layout, both
    planes."""
    from trustworthy_dl_tpu.parallel.tensor_parallel import apply_tp_sharding

    return apply_tp_sharding(params, mesh)


def mesh_spec_tree(params: Any) -> Any:
    """Sharding specs of a placed tree (None for uncommitted leaves) —
    the regression surface layout tests pin against."""
    def spec_of(leaf):
        sh = getattr(leaf, "sharding", None)
        return getattr(sh, "spec", None)

    return jax.tree_util.tree_map(spec_of, params)
