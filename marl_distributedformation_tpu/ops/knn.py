"""k-nearest-neighbor search over agent positions.

BASELINE.json config 4 ("100-agent swarm with k-nearest-neighbor obs graph
+ GNN policy") needs, per formation and per step, each agent's k nearest
neighbors. The reference has nothing like it (its interaction graph is the
static ring, simulate.py:162-167); this op is the new scaling axis for large
swarms.

TPU mapping: the pairwise squared-distance matrix is computed in the direct
broadcast form (x_i - x_j)^2 + (y_i - y_j)^2 — pure VPU elementwise work,
fully fuseable — then ``jax.lax.top_k`` selects the k smallest per row.
Everything is static-shaped and batches cleanly under ``vmap``.

Why NOT the |a|^2 + |b|^2 - 2 a.b matmul expansion: TPU executes f32
matmuls at bf16 input precision by default, and at world-coordinate scale
~400 the expansion subtracts numbers of magnitude ~3e5 to recover
differences of magnitude ~1 — the bf16 rounding of the cross term is
amplified into real errors (measured round 2 on TPU v5e at M=4096, N=100,
k=4: 33.5% wrong neighbor indices, distance errors up to 46 world units vs
float64 ground truth). The direct form subtracts coordinates FIRST, so
there is no cancellation and no matmul precision to worry about; at d=2
the FLOP difference is noise. ``tests/tpu_compiled_parity.py`` pins this
on hardware and ``tests/test_ops_pallas.py::test_xla_knn_precision`` pins
it structurally (no dot_general in the lowering).
"""

from __future__ import annotations

import functools
import sys
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Self-distance mask. Finite (not inf) so top_k never selects NaN garbage
# even when N <= k would force it into the masked diagonal.
_SELF_MASK = 1e12


def pairwise_sq_dists(points: Array) -> Array:
    """Squared euclidean distance matrix ``(N, N)`` for ``points (N, d)``
    in the direct broadcast form (coordinates subtracted BEFORE squaring —
    exact in f32, no bf16-matmul cancellation; see module docstring); the
    diagonal is masked to ``_SELF_MASK``."""
    diff = points[:, None, :] - points[None, :, :]  # (N, N, d)
    d2 = (diff * diff).sum(-1)
    return d2 + _SELF_MASK * jnp.eye(points.shape[0], dtype=points.dtype)


def knn(
    points: Array, k: int, valid: Array = None
) -> Tuple[Array, Array, Array]:
    """Per-point k nearest neighbors (excluding self).

    Args:
      points: ``(N, d)`` positions (single formation; ``vmap`` over M).
      k: neighbor count, ``k < N``.
      valid: optional ``(N,)`` bool mask for padded formations — invalid
        points are never selected as neighbors. When fewer than k valid
        neighbors exist (a formation padded down to <= k agents), the
        surplus slots degrade to harmless self-loops: ``idx = i``,
        ``offset = 0``, ``dist = 0`` — no masked-distance garbage can reach
        observations.

    Returns:
      ``(idx, offsets, dists)``: indices ``(N, k)`` int32 sorted by
      ascending distance, offsets ``(N, k, d)`` with
      ``offsets[i, j] = points[idx[i, j]] - points[i]``, and euclidean
      distances ``(N, k)``.
    """
    n = points.shape[0]
    assert k < n, f"knn needs k < N (k={k}, N={n})"
    if valid is None:
        # The full search IS the local-query search with every point as a
        # query — a single implementation keeps the sharded/unsharded
        # bit-parity invariant true by construction (parallel/ring.py).
        return knn_local(points, points, k, 0)
    d2 = pairwise_sq_dists(points)
    d2 = jnp.where(valid[None, :], d2, _SELF_MASK)
    neg, idx = jax.lax.top_k(-d2, k)
    idx = idx.astype(jnp.int32)
    # Slots that resolved into the masked region (self or invalid
    # columns, all at _SELF_MASK) become explicit self-loops.
    real = -neg < 0.5 * _SELF_MASK
    idx = jnp.where(real, idx, jnp.arange(n, dtype=jnp.int32)[:, None])
    offsets = points[idx] - points[:, None, :]
    dists = jnp.sqrt(jnp.maximum(-neg, 0.0))
    dists = jnp.where(real, dists, 0.0)
    return idx, offsets, dists


def knn_local(
    queries: Array,
    points: Array,
    k: int,
    query_offset,
) -> Tuple[Array, Array, Array]:
    """k nearest neighbors of a LOCAL block of query agents against the
    full point set — the agent-axis-sharded search (parallel/ring.py swarm
    mode): each device holds ``queries (nq, d)`` (its slab of the formation,
    global rows ``query_offset .. query_offset+nq``) and the all-gathered
    ``points (N, d)``.

    Distances are computed in the same direct broadcast form and the same
    column order as :func:`knn`, so the selected indices/distances are
    bit-identical to the corresponding rows of the unsharded search (no
    tie-break divergence between sharded and unsharded trajectories).

    Returns ``(idx (nq, k) int32 GLOBAL indices, offsets (nq, k, d),
    dists (nq, k))`` sorted by ascending distance.
    """
    nq = queries.shape[0]
    n = points.shape[0]
    assert k < n, f"knn_local needs k < N (k={k}, N={n})"
    diff = queries[:, None, :] - points[None, :, :]  # (nq, N, d)
    d2 = (diff * diff).sum(-1)
    # Self-mask by GLOBAL index: local query row j is global row
    # query_offset + j.
    gids = query_offset + jnp.arange(nq, dtype=jnp.int32)
    cols = jnp.arange(n, dtype=jnp.int32)
    d2 = jnp.where(cols[None, :] == gids[:, None], _SELF_MASK, d2)
    neg, idx = jax.lax.top_k(-d2, k)
    idx = idx.astype(jnp.int32)
    offsets = points[idx] - queries[:, None, :]
    dists = jnp.sqrt(jnp.maximum(-neg, 0.0))
    return idx, offsets, dists


def _resolve_auto_impl(points: Array) -> str:
    """The ``impl="auto"`` dispatch predicate, factored out so tests can
    pin the backend: on TPU, the fused kernel when the whole per-formation
    problem fits VMEM (N <= 640), the chunked-streaming kernel beyond that
    (N <= 16384); xla on other backends or when the SPMD partitioner
    controls the batch (a pallas_call is a Mosaic custom call it cannot
    split; shard_map-wrapped callers re-enter with local blocks).
    Interpret mode is never chosen here — it is a CPU-test spelling only
    (``impl="pallas_interpret"``)."""
    return _resolve_auto(points)[0]


def _resolve_auto(points: Array) -> Tuple[str, str]:
    """``(impl, why)`` for ``impl="auto"``; ``why`` is what
    :func:`knn_batch` prints, so no choice made from the platform or the
    placement is silent."""
    from marl_distributedformation_tpu.ops.knn_pallas import (
        fits_big_kernel,
        fits_vmem,
    )

    n = points.shape[1]
    backend = jax.default_backend()
    if backend != "tpu":
        return "xla", f"backend is {backend}, the Pallas kernels are TPU-only"
    if _spmd_partitioner_controlled(points):
        return "xla", (
            "the batch is under SPMD-partitioner control, which cannot "
            "split a Mosaic custom call (wrap the step in shard_map — "
            "parallel.make_dp_step — to get the kernel on local blocks)"
        )
    if fits_vmem(n):
        return "pallas", f"N={n} fits the fused kernel's VMEM budget"
    # The chunked kernel's column loop is a static unroll — auto caps it
    # where compile time stays sane (explicit impl="pallas_big" can go
    # further; see knn_batch_pallas_big).
    if fits_big_kernel(n):
        return "pallas_big", f"N={n} is past the fused kernel's VMEM cliff"
    return "xla", f"N={n} is past the chunked kernel's unroll ceiling"


@functools.lru_cache(maxsize=None)
def _announce(line: str) -> None:
    """Print each distinct resolution once per process (trace time)."""
    print(line, file=sys.stderr)


def _spmd_partitioner_controlled(points: Array) -> bool:
    """True when ``points`` lives on (or is traced under) a multi-device
    mesh whose axes the XLA SPMD partitioner controls.

    Concrete arrays: committed to >1 device means the implicit jit around
    the kernel would need the partitioner -> True. Tracers carry their
    sharding on the aval: a mesh with any Auto/Explicit axis (plain
    ``jit`` over sharded operands, or under ``jax.set_mesh``) -> the
    partitioner will place this op -> True; under ``shard_map`` (all axes
    Manual) or with no mesh (single-device operands — also on a host with
    several chips) -> the kernel sees a per-device local block -> False.
    """
    if not isinstance(points, jax.core.Tracer):
        sharding = getattr(points, "sharding", None)  # numpy: host data
        return sharding is not None and len(sharding.device_set) > 1
    mesh = points.aval.sharding.mesh
    return any(t != jax.sharding.AxisType.Manual for t in mesh.axis_types)


def knn_batch(
    points: Array,
    k: int,
    valid: Array = None,
    impl: str = "auto",
) -> Tuple[Array, Array, Array]:
    """Batched k-NN over ``points (M, N, 2)`` with implementation dispatch.

    ``impl``: ``"xla"`` — ``vmap`` of :func:`knn` (works everywhere);
    ``"pallas"`` — the fused TPU kernel (ops/knn_pallas.py), which never
    materializes the ``(M, N, N)`` distance tensor in HBM;
    ``"pallas_big"`` — the chunked-streaming kernel for swarms past the
    fused kernel's VMEM cliff (N > 640; O(block) VMEM regardless of N);
    ``"pallas_interpret"`` / ``"pallas_big_interpret"`` — the same kernels
    in interpret mode (CPU tests);
    ``"auto"`` — on TPU, pallas when the kernel's intermediates fit VMEM
    (N <= 640: 641 pads to 768 lanes and the ~6 live (1, 768, 768) f32
    intermediates exceed the 12 MiB budget), pallas_big for
    640 < N <= 16384 (the static chunk unroll keeps compile time bounded;
    ``fits_big_kernel``), xla beyond — provided the batch is not under
    SPMD-partitioner control
    (a ``pallas_call`` is a Mosaic custom call the partitioner cannot split,
    so a dp-sharded batch traced under plain ``jit`` falls back to xla;
    inside ``shard_map`` — where the kernel sees its local block — pallas is
    selected again; ``parallel.make_dp_step`` provides that wrapping for
    sharded training).
    """
    if impl == "auto":
        impl, why = _resolve_auto(points)
        _announce(f"[knn] impl=auto -> {impl}: {why}")
    if impl in ("pallas", "pallas_interpret"):
        from marl_distributedformation_tpu.ops.knn_pallas import (
            knn_batch_pallas,
        )

        return knn_batch_pallas(
            points, k, valid, interpret=(impl == "pallas_interpret")
        )
    if impl in ("pallas_big", "pallas_big_interpret"):
        from marl_distributedformation_tpu.ops.knn_pallas import (
            knn_batch_pallas_big,
        )

        return knn_batch_pallas_big(
            points, k, valid, interpret=(impl == "pallas_big_interpret")
        )
    assert impl == "xla", f"unknown knn impl {impl!r}"
    if valid is None:
        return jax.vmap(lambda p: knn(p, k))(points)
    return jax.vmap(lambda p, v: knn(p, k, v))(points, valid)
