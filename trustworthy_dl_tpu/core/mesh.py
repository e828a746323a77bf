"""Device-mesh construction — the L1 communication layer, TPU-native.

The reference's L1 is an NCCL process group that is initialised and destroyed
but never used for a collective (distributed_trainer.py:99-114,523-527; see
SURVEY §2.5).  Here L1 is a real `jax.sharding.Mesh`: collectives are XLA ops
(psum / ppermute / all_gather / all_to_all) compiled into the train step and
riding ICI (intra-slice) or DCN (multi-slice).  There is no rendezvous config
to manage — `jax.distributed.initialize()` handles multi-host.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

logger = logging.getLogger(__name__)

# Canonical axis names (SURVEY §7.1).  The reference's "node" maps onto
# whichever axis the chosen parallelism strategy uses.
DATA_AXIS = "data"     # data parallel shards
STAGE_AXIS = "stage"   # pipeline stages (reference's layer-split "nodes")
MODEL_AXIS = "model"   # tensor parallel (attention heads / mlp hidden)
SEQ_AXIS = "seq"       # sequence/context parallel
EXPERT_AXIS = "expert"  # expert parallel (MoE expert dim)

_PARALLELISM_AXIS = {
    "data": DATA_AXIS,
    "model": STAGE_AXIS,
    "tensor": MODEL_AXIS,
    "sequence": SEQ_AXIS,
    "expert": EXPERT_AXIS,
}


def node_axis_for(parallelism: str) -> str:
    """Mesh axis that plays the role of the reference's node index."""
    try:
        return _PARALLELISM_AXIS[parallelism]
    except KeyError:
        raise ValueError(f"no canonical node axis for parallelism={parallelism!r}")


# Canonical outermost-first axis order: DCN-adjacent axes (data, stage —
# the ones whose collectives tolerate lower bandwidth) come first, per the
# scaling-book recipe; bandwidth-hungry axes (model/seq/expert) innermost
# so their collectives ride ICI.
AXIS_ORDER = (DATA_AXIS, STAGE_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS)


def build_hybrid_mesh(
    ici_mesh_shape: Dict[str, int],
    dcn_mesh_shape: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Multi-slice mesh: per-axis ICI size within a slice and DCN size
    across slices.  Axes with a DCN extent >1 replicate/parallelise across
    slices (typically 'data' and/or 'stage'); everything else stays inside
    one slice so its collectives never touch DCN.

    On real multi-slice TPU hardware the device grid comes from
    ``mesh_utils.create_hybrid_device_mesh`` (which groups by slice
    index); when every DCN extent is 1 — single slice, CPU test meshes —
    the layout degenerates to a plain reshape in AXIS_ORDER.
    """
    dcn_mesh_shape = dcn_mesh_shape or {}
    extra = (set(ici_mesh_shape) | set(dcn_mesh_shape)) - set(AXIS_ORDER)
    if extra:
        raise ValueError(f"unknown mesh axes {extra}")
    order = [a for a in AXIS_ORDER
             if a in ici_mesh_shape or a in dcn_mesh_shape]
    ici = [int(ici_mesh_shape.get(a, 1)) for a in order]
    dcn = [int(dcn_mesh_shape.get(a, 1)) for a in order]
    devices = list(devices if devices is not None else jax.devices())
    total = int(np.prod(ici)) * int(np.prod(dcn))
    if total > len(devices):
        raise ValueError(
            f"hybrid mesh ici={ici_mesh_shape} dcn={dcn_mesh_shape} needs "
            f"{total} devices, have {len(devices)}"
        )
    if any(d > 1 for d in dcn):
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices[:total]
        )
        return Mesh(arr, tuple(order))
    arr = np.array(devices[:total]).reshape(ici)
    return Mesh(arr, tuple(order))


def build_mesh(
    num_nodes: int,
    parallelism: str = "data",
    mesh_shape: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_mesh_shape: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build the mesh for a training run.

    For single-axis strategies the node axis gets ``num_nodes`` entries.
    Tensor/sequence modes fold leftover devices into each node's TP/seq
    group; pipeline ("model") uses exactly one device per stage and
    leaves surplus devices out of the mesh (see the stage branch below
    for why).  For "hybrid", ``mesh_shape`` gives the within-slice
    {axis: size} explicitly and ``dcn_mesh_shape`` the optional
    across-slice extents (see build_hybrid_mesh).
    """
    devices = list(devices if devices is not None else jax.devices())
    n_dev = len(devices)

    if parallelism == "hybrid":
        if not mesh_shape:
            raise ValueError("hybrid parallelism requires mesh_shape")
        return build_hybrid_mesh(mesh_shape, dcn_mesh_shape, devices)
    if dcn_mesh_shape:
        # Silently dropping the DCN extents would lay collectives across
        # slices with no slice-aware grouping — the failure hybrid meshes
        # exist to prevent.
        raise ValueError(
            "dcn_mesh_shape requires parallelism='hybrid' (got "
            f"{parallelism!r}); express the within-slice layout in "
            "mesh_shape and the across-slice extents in dcn_mesh_shape"
        )

    axis = node_axis_for(parallelism)
    if num_nodes > n_dev:
        # Degenerate/dev mode: more logical nodes than devices.  The node
        # axis still exists logically (vmapped); the mesh axis takes the
        # largest divisor of num_nodes that fits so [num_nodes, ...] arrays
        # still shard evenly (worst case 1 → fully replicated execution).
        fit = max(d for d in range(1, n_dev + 1) if num_nodes % d == 0)
        logger.warning(
            "num_nodes=%d exceeds device count %d; using a %d-wide mesh "
            "(logical nodes are vmapped within devices)",
            num_nodes, n_dev, fit,
        )
        num_nodes = fit
    if axis == DATA_AXIS:
        # Pure DP: the data axis IS the node axis — per-node arrays shard
        # one (or an equal group of) logical node(s) per device.
        arr = np.array(devices[:num_nodes])
        return Mesh(arr, (DATA_AXIS,))
    usable = (n_dev // num_nodes) * num_nodes
    group = usable // num_nodes
    if axis == STAGE_AXIS:
        # Pipeline: the stage axis carries the nodes.  On TPU, surplus
        # devices form DP pipeline replica rows — a (group, S) mesh whose
        # data axis shards the microbatches (parallel/pipeline.py), so
        # adding chips beyond S scales batch throughput.  On CPU the mesh
        # stays exactly (1, S): the DP×PP composition races independent
        # subgroup collectives (stage-row psum vs GSPMD-inserted data
        # all-reduces), which nondeterministically aborts XLA:CPU's
        # in-process communicator — a backend bug TPU's compiled
        # collectives don't have.  (Verified r3: the bare pipe matched
        # sequential grads under the (2, 4) mesh; only XLA:CPU crashed.)
        if group >= 2 and devices[0].platform == "tpu":
            arr = np.array(devices[:usable]).reshape(group, num_nodes)
        else:
            arr = np.array(devices[:num_nodes]).reshape(1, num_nodes)
        return Mesh(arr, (DATA_AXIS, axis))
    # Tensor / sequence: trust nodes stay data shards; each node owns a
    # TP / sequence group of the remaining devices (SURVEY §2.4 plan — the
    # detection unit is the DP shard, intra-node sharding is transparent).
    arr = np.array(devices[:usable]).reshape(num_nodes, group)
    return Mesh(arr, (DATA_AXIS, axis))


def bind_mode_mesh(mesh: Mesh, parallelism: str) -> None:
    """Bind the global collectives mesh for the modes whose forwards read
    one (sequence ring/Ulysses, MoE expert dispatch); no-op otherwise.

    The ONE binding ladder — shared by trainer construction, elastic mesh
    rebuilds (eviction/readmission) and checkpoint topology adoption, so
    a new rebuild site (or a new mode) cannot silently miss a binding.
    Imports are lazy to keep core/ free of parallel/models dependencies."""
    if parallelism == "sequence":
        from trustworthy_dl_tpu.parallel.sequence import set_sequence_mesh

        set_sequence_mesh(mesh)
    elif parallelism == "expert":
        from trustworthy_dl_tpu.models.moe import set_expert_mesh

        set_expert_mesh(mesh)


def node_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    """Sharding for a per-node leading-axis array (e.g. [num_nodes, ...]).
    Spec resolution lives in the registry (core/sharding.py — lazy import:
    the registry imports this module's axis names)."""
    from trustworthy_dl_tpu.core import sharding as shreg

    return shreg.row_sharding(mesh, axis)


def replicated(mesh: Mesh) -> NamedSharding:
    from trustworthy_dl_tpu.core import sharding as shreg

    return shreg.replicated_sharding(mesh)


def local_device_count() -> int:
    return jax.local_device_count()


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Multi-host init — TPU replacement for the reference's
    init_process_group (distributed_trainer.py:99-114).  On TPU pods all
    arguments are discovered from the environment."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
    logger.info(
        "Initialized distributed environment: process %d/%d",
        jax.process_index(), jax.process_count(),
    )


def shutdown_multihost() -> None:
    """Teardown parity with dist.destroy_process_group
    (distributed_trainer.py:523-527)."""
    try:
        jax.distributed.shutdown()
    except (RuntimeError, ValueError):
        pass  # never initialised — mirrors the reference's is_initialized() guard
