"""Device-mesh construction and sharding placement.

The reference has no distributed machinery at all (SURVEY.md §2.1: no
NCCL/MPI/multi-process anything — "distributed" in its name means
*decentralized control*). The TPU-native scaling story is therefore designed
fresh: formations are the data axis, sharded over a ``jax.sharding.Mesh``
('dp'); parameters are replicated. What the one jitted iteration then does
on each device: the rollout steps the device's own formations; the rollout
buffer is all-gathered, and every device builds the update's packed row
table and draws each epoch's permutation whole (the same random stream as
on one device); of every minibatch a device looks up and differentiates
``batch_size / dp`` rows (:func:`minibatch_sharding`), and XLA all-reduces
the gradient, the advantage moments and the metrics over ICI. Where 'dp'
does not divide a minibatch's rows every device takes it whole. An optional
'sp' axis shards the *agent* ring dimension for very large swarms (see
``parallel/ring.py``).

Works identically on real TPU meshes and on CPU test meshes created with
``--xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def resolve_axis_sizes(
    axis_sizes: Dict[str, int], n_devices: int
) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """Resolve a ``{name: size}`` spec against the device count: a single
    -1 means "all remaining devices"; the total may not exceed
    ``n_devices``. Shared by :func:`make_mesh` and
    ``distributed.make_hybrid_mesh``."""
    names = tuple(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n_devices // known
    total = int(np.prod(sizes))
    if total > n_devices:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices; "
            f"only {n_devices} available"
        )
    return names, tuple(sizes)


def make_mesh(axis_sizes: Dict[str, int]) -> Mesh:
    """Build a mesh with named axes, e.g. ``{"dp": 4}`` or
    ``{"dp": 4, "sp": 2}``. Total size must divide the device count; use
    size -1 for one axis to mean "all remaining devices"."""
    names, sizes = resolve_axis_sizes(axis_sizes, len(jax.devices()))
    total = int(np.prod(sizes))
    devices = mesh_utils.create_device_mesh(
        tuple(sizes), devices=jax.devices()[:total]
    )
    return Mesh(devices, names)


def formation_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading formation axis M over 'dp'; everything else
    (agents, coordinates) stays local to the chip."""
    return NamedSharding(mesh, P("dp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def minibatch_sharding(
    mesh: Mesh, batch_size: int
) -> Optional[NamedSharding]:
    """The layout of ``ppo_update``'s ``(num_minibatches, batch_size)`` row
    indices that divides every minibatch over 'dp' ('sp', where the mesh
    has it, repeats the share), or ``None`` where 'dp' does not divide
    ``batch_size``: the minibatch then stays whole on every device."""
    if batch_size % mesh.shape["dp"] != 0:
        return None
    return NamedSharding(mesh, P(None, "dp"))


def shard_batch(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree whose leaves all carry a leading formation axis."""
    return jax.device_put(tree, formation_sharding(mesh))


def replicate(tree: Any, mesh: Mesh) -> Any:
    return jax.device_put(tree, replicated(mesh))


def make_dp_step(params: Any, mesh: Mesh) -> Callable:
    """Batched env step explicitly shard_mapped over 'dp': each device steps
    only its local formation block (the step has no cross-formation
    communication, so no collectives are needed).

    Required for knn observations on a mesh: the fused neighbor kernel
    (ops/knn_pallas.py) is a Mosaic custom call the XLA SPMD partitioner
    cannot split, so under plain ``jit`` the ``impl="auto"`` dispatch falls
    back to the XLA search (ops/knn.py ``_spmd_partitioner_controlled``).
    Inside this shard_map the kernel sees a per-device local ``(m_local, N,
    2)`` block — Manual mesh axes — and "auto" selects Pallas again.
    """
    from marl_distributedformation_tpu.env.formation import step_batch

    spec = P("dp")

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec),
        # pallas_call outputs carry no varying-across-mesh metadata, which
        # trips the vma checker; the step is collective-free so the check
        # buys nothing here.
        check_vma=False,
    )
    def dp_step(state, velocity):
        return step_batch(state, velocity, params)

    return dp_step


def make_shard_fn(
    axis_sizes: Optional[Dict[str, int]] = None,
    mesh: Optional[Mesh] = None,
) -> Callable[[Any, Any, Any], Tuple[Any, Any, Any]]:
    """Build the ``shard_fn`` hook ``Trainer`` applies after initialization:
    replicate the train state, shard env state + obs over 'dp'.

    The jitted train iteration then runs SPMD: each device rolls out its
    own formations; the update's row table and shuffle are whole on every
    device, and ``Trainer`` divides each minibatch's rows over 'dp'
    (:func:`minibatch_sharding`), so XLA all-reduces the gradients over ICI.
    """
    the_mesh = mesh or make_mesh(axis_sizes or {"dp": len(jax.devices())})
    extra_axes = set(the_mesh.shape) - {"dp", "sp"}
    if extra_axes:
        raise ValueError(
            f"shard_fn places the 'dp' (formation) and 'sp' (agent) axes; "
            f"mesh has unknown axes {sorted(extra_axes)}"
        )
    has_sp = "sp" in the_mesh.shape

    def shard_fn(train_state, env_state, obs):
        dp = the_mesh.shape["dp"]
        m = obs.shape[0]
        if m % dp != 0:
            raise ValueError(
                f"num_formations={m} not divisible by dp={dp}"
            )
        if has_sp:
            # Agent-axis sharding: agents/obs P('dp','sp'), per-formation
            # leaves P('dp') — the layout parallel/ring.py's halo-exchange
            # step consumes. Trainer pairs this with make_ring_step.
            from marl_distributedformation_tpu.parallel.ring import (
                place_ring_state,
            )

            return (
                replicate(train_state, the_mesh),
                place_ring_state(env_state, the_mesh),
                jax.device_put(
                    obs, NamedSharding(the_mesh, P("dp", "sp"))
                ),
            )
        return (
            replicate(train_state, the_mesh),
            shard_batch(env_state, the_mesh),
            shard_batch(obs, the_mesh),
        )

    shard_fn.mesh = the_mesh
    return shard_fn
