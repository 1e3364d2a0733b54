"""Multi-host distributed runtime: process wire-up, hybrid DCN x ICI meshes,
and host-local data placement.

The reference has no distributed communication backend at all — one OS
process, CPU tensors, a sequential formation loop (SURVEY.md §2.1, reference
vectorized_env.py:71-81). This module is the TPU-native equivalent designed
fresh: ``jax.distributed`` wires processes into one JAX runtime, meshes are
laid out so the heavy collectives (gradient psum over 'dp', ring halo
ppermute over 'sp') ride ICI *within* a slice while only the slice-level
gradient reduction crosses DCN, and every host materializes only its own
formation shard (``jax.make_array_from_process_local_data``) so no
full-batch array ever exists on one host.

Single-process (the CPU test mesh, one chip, one four-chip host)
everything is a no-op / plain single-slice mesh, so the same training
code runs unchanged from laptop CPU to multi-host pod.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from marl_distributedformation_tpu.parallel.mesh import make_mesh

_initialized = False


def _cluster_hosts() -> int:
    """How many hosts the launch environment describes, from the markers
    of the cluster launchers jax.distributed's auto-detection understands
    (Cloud TPU pod slices and multislice, Slurm, Open MPI); 1 when none.

    The COUNT matters, not the presence: a single-host TPU VM sets
    ``TPU_WORKER_ID=0`` and ``TPU_WORKER_HOSTNAMES=localhost`` too, and
    there is nothing to wire up on one host — calling
    ``jax.distributed.initialize()`` there only invites cluster detection
    to wait on a network a sealed machine does not have."""
    hostnames = [
        h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
        if h.strip()
    ]
    counts = [len(hostnames)]
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        counts.append(int(os.environ.get("MEGASCALE_NUM_SLICES") or 2))
    if os.environ.get("SLURM_JOB_NUM_NODES"):
        counts.append(int(os.environ["SLURM_JOB_NUM_NODES"]))
    if os.environ.get("OMPI_MCA_orte_hnp_uri"):
        counts.append(int(os.environ.get("OMPI_COMM_WORLD_SIZE") or 2))
    return max(1, *counts)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Idempotent ``jax.distributed.initialize`` wrapper.

    Arguments default to the standard env vars (``JAX_COORDINATOR_ADDRESS``
    / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``). Without explicit config,
    a launch environment that describes MORE THAN ONE host (TPU pod,
    multislice, Slurm, OMPI — ``_cluster_hosts``) triggers argument-free
    ``jax.distributed.initialize()`` so jax's own cluster detection wires
    the processes together (merely not calling it would run N independent
    single-host jobs); its failure raises — a multi-host launch that
    cannot wire up is not a single-process run. Returns True if a
    multi-process runtime was (or already is) up, False for plain
    single-process operation — callers never need to branch on the launch
    mode themselves.
    """
    global _initialized
    # Resolve the launch configuration BEFORE touching anything that could
    # initialize the XLA backend: jax.distributed.initialize() must run
    # first or it raises, and even jax.process_count() initializes backends.
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    num_processes = (
        num_processes if num_processes is not None
        else (int(env_np) if env_np else None)
    )
    env_pid = os.environ.get("JAX_PROCESS_ID")
    process_id = (
        process_id if process_id is not None
        else (int(env_pid) if env_pid else None)
    )
    if _initialized:
        return jax.process_count() > 1
    if coordinator_address is None or num_processes in (None, 1):
        if num_processes != 1 and _cluster_hosts() > 1:
            # Cluster launch without explicit wiring: let jax detect it.
            jax.distributed.initialize()
        # else: plain single-process launch — safe to query below.
        _initialized = True
        return jax.process_count() > 1
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return True


def is_coordinator() -> bool:
    """True on the process that owns host-side side effects (checkpoint
    writes, metric emission). Always True single-process."""
    return jax.process_index() == 0


def make_hybrid_mesh(
    axis_sizes: Dict[str, int], dcn_axis: str = "dp"
) -> Mesh:
    """Build a mesh whose ``dcn_axis`` outer factor spans hosts over DCN
    while everything else stays on ICI.

    For a multi-slice/multi-host run the device array comes from
    ``mesh_utils.create_hybrid_device_mesh``: ``dcn_axis`` is factored into
    ``num_slices x per_slice`` so that neighboring mesh coordinates along
    every other axis (and within a slice along ``dcn_axis``) are ICI
    neighbors — the gradient psum then does a fast ICI reduce-scatter per
    slice and only the slice-partial crosses DCN. Single-slice runs fall
    back to :func:`parallel.mesh.make_mesh` unchanged.

    ``axis_sizes`` follows ``make_mesh``'s convention (-1 = remaining
    devices); ``dcn_axis`` must be present and divisible by the number of
    slices.
    """
    devs = jax.devices()
    slice_ids = {getattr(d, "slice_index", None) for d in devs}
    if None not in slice_ids and len(slice_ids) > 1:
        # Real multi-slice TPU: granule = slice (DCN between slices).
        num_slices = len(slice_ids)
        process_is_granule = False
    elif jax.process_count() > 1:
        # Multi-process without slice topology (single-slice pod, GPU/CPU
        # clusters): treat each process as the DCN granule.
        num_slices = jax.process_count()
        process_is_granule = True
    else:
        return make_mesh(axis_sizes)

    from marl_distributedformation_tpu.parallel.mesh import (
        resolve_axis_sizes,
    )

    n_devices = len(devs)
    names, sizes = resolve_axis_sizes(axis_sizes, n_devices)
    assert dcn_axis in names, f"dcn_axis {dcn_axis!r} not in {names}"
    sizes = list(sizes)
    total = int(np.prod(sizes))
    if total != n_devices:
        raise ValueError(
            f"multi-host mesh {dict(zip(names, sizes))} covers {total} of "
            f"{n_devices} global devices. Unlike single-process meshes, a "
            "multi-host mesh must span every device (each process needs "
            "addressable devices in the mesh) — use -1 for one axis to "
            "absorb the remainder, e.g. mesh={dp: -1}"
        )
    dcn_idx = names.index(dcn_axis)
    assert sizes[dcn_idx] % num_slices == 0, (
        f"{dcn_axis}={sizes[dcn_idx]} must be divisible by "
        f"num_slices={num_slices}"
    )
    per_slice = list(sizes)
    per_slice[dcn_idx] //= num_slices
    dcn_shape = [1] * len(sizes)
    dcn_shape[dcn_idx] = num_slices
    devices = mesh_utils.create_hybrid_device_mesh(
        tuple(per_slice),
        tuple(dcn_shape),
        devices=devs,
        process_is_granule=process_is_granule,
    )
    return Mesh(devices, names)


def local_formation_slice(
    num_formations: int, process_index: Optional[int] = None
) -> Tuple[int, int]:
    """``(start, count)`` of this host's contiguous formation shard.

    The formation axis is split evenly across processes (multi-host data
    parallelism); M must divide by the process count so every device gets
    identical static shapes.
    """
    n_proc = jax.process_count()
    assert num_formations % n_proc == 0, (
        f"num_formations={num_formations} must be divisible by "
        f"process_count={n_proc}"
    )
    count = num_formations // n_proc
    pid = jax.process_index() if process_index is None else process_index
    return pid * count, count


def global_from_local(tree: Any, mesh: Mesh, spec: P = P("dp")) -> Any:
    """Assemble a globally-sharded pytree from each host's LOCAL shard.

    Every leaf carries this host's rows of the leading (formation) axis;
    the returned leaves are global ``jax.Array``s sharded by ``spec`` over
    ``mesh`` whose addressable shards are exactly the local data — no
    host ever holds the full batch. Single-process this is equivalent to
    ``device_put`` with the same sharding.
    """
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)
        ),
        tree,
    )


def reset_batch_sharded(
    key: Any, params: Any, num_formations: int, mesh: Mesh
) -> Any:
    """Multi-host-safe ``env.formation.reset_batch``: every host constructs
    ONLY its own formation shard and the result is a globally 'dp'-sharded
    ``FormationState``.

    The per-formation PRNG streams are identical to the single-host
    ``reset_batch`` (keys are split globally, then sliced), so scaling the
    host count never changes the sampled initial states.
    """
    from marl_distributedformation_tpu.env.formation import reset

    start, count = local_formation_slice(num_formations)
    keys = jax.random.split(key, num_formations)[start : start + count]
    local = jax.vmap(reset, in_axes=(0, None))(keys, params)
    return global_from_local(local, mesh)


def hetero_reset_batch_sharded(
    key: Any, params: Any, n_agents: Any, n_obstacles: Any, mesh: Mesh
) -> Any:
    """Multi-host-safe ``env.hetero.hetero_reset_batch``: the curriculum's
    per-formation counts are computed identically on every host (same PRNG
    key), but each host materializes only its formation slice of the padded
    state — mirroring :func:`reset_batch_sharded` for the hetero trainer's
    ``start_stage`` (round-1 ADVICE: building the full batch per host both
    crashed ``device_put`` across processes and violated the per-host-shard
    design). Single-process this equals ``hetero_reset_batch`` placed on the
    mesh.
    """
    from marl_distributedformation_tpu.env.hetero import hetero_reset

    num_formations = int(n_agents.shape[0])
    start, count = local_formation_slice(num_formations)
    keys = jax.random.split(key, num_formations)[start : start + count]
    local = jax.vmap(hetero_reset, in_axes=(0, None, 0, 0))(
        keys,
        params,
        n_agents[start : start + count],
        n_obstacles[start : start + count],
    )
    return global_from_local(local, mesh)
