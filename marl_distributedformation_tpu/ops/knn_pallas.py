"""Fused batched k-NN as a Pallas TPU kernel.

The XLA path (ops/knn.py) materializes the ``(M, N, N)`` pairwise-distance
tensor in HBM and runs ``jax.lax.top_k`` over it — at the BASELINE.json
config-4 scale (M=4096 formations x N=100 agents, every step) that is
~160 MB of HBM round-trip per rollout step plus a sort-based top-k XLA
can't fuse through. This kernel keeps the whole per-formation problem in
VMEM: distance matrix, iterative k-extraction (k unrolled argmin passes —
the standard small-k trick; each pass is one VPU reduction over lanes),
and the neighbor gather via one-hot select, with only the ``(M, k, N)``
results ever touching HBM.

Layout notes (guide: /opt/skills/guides/pallas_guide.md):
- positions are fed struct-of-arrays (x and y as separate ``(M, 1, N)``
  planes) so the lane dimension is the agent axis padded to 128, instead
  of a 2-wide trailing dimension padded 64x; the singleton middle axis
  keeps every block Mosaic-legal at any ``block_m`` (see ``_pad_planes``);
- outputs are ``(M, k, N)`` (k on the sublane axis) and transposed to the
  public ``(M, N, k)`` layout outside the kernel;
- the grid runs blocks of ``block_m`` formations per program; ``block_m``
  shrinks automatically as N grows so the ``(block_m, Np, Np)``
  intermediates (distance matrix, broadcast planes, selection masks)
  stay within the VMEM budget.

The reference has no neighbor search at all (its interaction graph is the
static ring, reference simulate.py:162-167); this op exists for the new
large-swarm capability and matches ``ops.knn.knn`` bit-for-bit in its
selection and masking semantics (see tests/test_ops_pallas.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from marl_distributedformation_tpu.ops.knn import _SELF_MASK

Array = jax.Array

_LANE = 128
_VMEM_BUDGET = 12 * 1024 * 1024  # bytes; ~6 live (block_m, Np, Np) f32 bufs


def padded_n(n: int) -> int:
    return max(_LANE, ((n + _LANE - 1) // _LANE) * _LANE)


def fits_vmem(n: int) -> bool:
    """True when the fused kernel's intermediates fit the VMEM budget even
    at the minimum block_m=1 — the dispatch condition for ``impl="auto"``."""
    np_ = padded_n(n)
    return 6 * 4 * np_ * np_ <= _VMEM_BUDGET


# Auto-dispatch ceiling for the chunked kernel: its resident cost is three
# full (block_m, n_pad) f32 position/validity planes plus the (R, C) tile
# intermediates, and the column loop is a STATIC unroll of n_pad/chunk_c
# chunks (compile time grows O(N * k^2 / chunk_c)). 16384 points keeps the
# planes at ~200 KB and the unroll at 32 chunks; beyond that "auto" falls
# back to XLA (explicit impl="pallas_big" still allowed for larger N —
# after Mosaic pads the singleton sublane axis to 8 the planes cost
# ~96 B/point, so VMEM holds to ~10^5 points; expect long compiles).
_BIG_KERNEL_AUTO_MAX_N = 16384


_STREAMING_VMEM_LIMIT = 64 * 1024 * 1024  # N=16384 unrolls 32 chunks


def fits_big_kernel(n: int) -> bool:
    return n <= _BIG_KERNEL_AUTO_MAX_N


def _pad_planes(points: Array, valid, m_pad: int, n_pad: int):
    """Struct-of-arrays prologue shared by both kernels: f32 cast, x/y
    plane split, validity plane, zero-padding to the padded grid shape.

    Planes are shaped ``(m_pad, 1, n_pad)`` — NOT ``(m_pad, n_pad)`` — so
    their block shape ``(block_m, 1, n_pad)`` is always Mosaic-legal: the
    TPU lowering requires the last two block dims be divisible by (8, 128)
    or equal the array dims, and a 2-D ``(block_m, n_pad)`` block violates
    the sublane rule whenever the VMEM budget drives ``block_m`` below 8
    (fused kernel at N in [384, 640], chunked kernel always). The singleton
    axis pins the sublane dim to "equal the array dim" for any block_m.
    Interpret mode never enforces this, so CPU tests can't catch it —
    tests/tpu_compiled_parity.py exercises the compiled shapes on hardware.
    """
    m, n = points.shape[:2]
    pts = points.astype(jnp.float32)
    x = jnp.pad(pts[..., 0], ((0, m_pad - m), (0, n_pad - n)))
    y = jnp.pad(pts[..., 1], ((0, m_pad - m), (0, n_pad - n)))
    if valid is None:
        vm = jnp.ones((m, n), jnp.float32)
    else:
        vm = valid.astype(jnp.float32)
    vm = jnp.pad(vm, ((0, m_pad - m), (0, n_pad - n)))
    return x[:, None, :], y[:, None, :], vm[:, None, :]


def _unpack_outputs(idx, offx, offy, dist, m: int, n: int):
    """Epilogue shared by both kernels: strip padding, move k to the
    trailing axis, re-assemble (M, N, k, 2) offsets — the public
    ``ops.knn.knn`` layout."""
    idx = jnp.swapaxes(idx[:m, :, :n], 1, 2)  # (M, N, k)
    offsets = jnp.stack(
        [
            jnp.swapaxes(offx[:m, :, :n], 1, 2),
            jnp.swapaxes(offy[:m, :, :n], 1, 2),
        ],
        axis=-1,
    )
    dists = jnp.swapaxes(dist[:m, :, :n], 1, 2)
    return idx, offsets, dists


def _knn_kernel(k, x_ref, y_ref, vmask_ref, idx_ref, offx_ref, offy_ref,
                dist_ref):
    """One grid step: k-NN for a ``(B, Np)`` block of formations.

    ``vmask`` is 1.0 for live agent columns, 0.0 for padding/invalid; masked
    columns can never be selected. Slots with no real candidate left (all
    remaining distances at ``_SELF_MASK``) degrade to self-loops
    (idx=i, offset=0, dist=0), mirroring ``ops.knn.knn``'s ``valid`` path.
    """
    x = x_ref[:, 0, :]  # (B, Np); refs carry the Mosaic-layout
    y = y_ref[:, 0, :]  # singleton axis (_pad_planes)
    vm = vmask_ref[:, 0, :]
    d2 = (x[:, :, None] - x[:, None, :]) ** 2 + (
        y[:, :, None] - y[:, None, :]
    ) ** 2  # (B, Np, Np)
    rows = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 2)
    blocked = (rows == cols) | (vm[:, None, :] < 0.5)
    d2 = jnp.where(blocked, _SELF_MASK, d2)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)  # (B, Np)
    xb = jnp.broadcast_to(x[:, None, :], d2.shape)
    yb = jnp.broadcast_to(y[:, None, :], d2.shape)
    for j in range(k):  # k is small and static: unrolled argmin passes
        best = jnp.min(d2, axis=2)  # (B, Np)
        amin = jnp.argmin(d2, axis=2).astype(jnp.int32)
        real = best < 0.5 * _SELF_MASK
        onehot = cols == amin[:, :, None]  # exactly one column per row
        nx = jnp.sum(jnp.where(onehot, xb, 0.0), axis=2)
        ny = jnp.sum(jnp.where(onehot, yb, 0.0), axis=2)
        idx_ref[:, j, :] = jnp.where(real, amin, row_ids)
        offx_ref[:, j, :] = jnp.where(real, nx - x, 0.0)
        offy_ref[:, j, :] = jnp.where(real, ny - y, 0.0)
        dist_ref[:, j, :] = jnp.where(
            real, jnp.sqrt(jnp.maximum(best, 0.0)), 0.0
        )
        d2 = jnp.where(onehot, _SELF_MASK, d2)  # exclude from later passes


def _knn_kernel_chunked(
    k, chunk_c, x_rows_ref, y_rows_ref, x_cols_ref, y_cols_ref, vm_ref,
    idx_ref, offx_ref, offy_ref, dist_ref,
):
    """Grid step for the big-N kernel: k-NN for a ``(B, R)`` block of query
    rows against the full ``(B, Np)`` point set, streamed in ``chunk_c``-
    column chunks so VMEM holds ``(B, R, C)`` — never ``(B, Np, Np)``.

    Running best-k state is a bubble-insertion sorted list (k small): each
    chunk contributes its k best via argmin passes, and every candidate is
    inserted with a strict ``<`` compare — equal distances never displace
    an earlier (lower-column) candidate, which reproduces ``lax.top_k``'s
    stable tie-breaking, so results are bit-identical to the XLA path.
    """
    b, _, r_block = x_rows_ref.shape  # refs carry the Mosaic-layout
    n_pad = x_cols_ref.shape[2]  # singleton axis (_pad_planes)
    xr = x_rows_ref[:, 0, :]  # (B, R)
    yr = y_rows_ref[:, 0, :]
    rb = pl.program_id(1)
    row_gids = rb * r_block + jax.lax.broadcasted_iota(
        jnp.int32, (b, r_block), 1
    )

    zero_f = jnp.zeros((b, r_block), jnp.float32)
    best_d = [zero_f + _SELF_MASK for _ in range(k)]
    best_i = [jnp.zeros((b, r_block), jnp.int32) for _ in range(k)]
    best_x = [zero_f for _ in range(k)]
    best_y = [zero_f for _ in range(k)]

    for c in range(n_pad // chunk_c):  # static unroll over column chunks
        sl = slice(c * chunk_c, (c + 1) * chunk_c)
        xc = x_cols_ref[:, 0, sl]  # (B, C)
        yc = y_cols_ref[:, 0, sl]
        vmc = vm_ref[:, 0, sl]
        d2 = (xr[:, :, None] - xc[:, None, :]) ** 2 + (
            yr[:, :, None] - yc[:, None, :]
        ) ** 2  # (B, R, C)
        local_cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 2)
        global_cols = local_cols + c * chunk_c
        blocked = (global_cols == row_gids[:, :, None]) | (
            vmc[:, None, :] < 0.5
        )
        d2 = jnp.where(blocked, _SELF_MASK, d2)
        xcb = jnp.broadcast_to(xc[:, None, :], d2.shape)
        ycb = jnp.broadcast_to(yc[:, None, :], d2.shape)
        for _ in range(k):  # chunk's k best, ascending
            cd = jnp.min(d2, axis=2)
            am = jnp.argmin(d2, axis=2).astype(jnp.int32)
            onehot = local_cols == am[:, :, None]
            ci = c * chunk_c + am
            cx = jnp.sum(jnp.where(onehot, xcb, 0.0), axis=2)
            cy = jnp.sum(jnp.where(onehot, ycb, 0.0), axis=2)
            d2 = jnp.where(onehot, _SELF_MASK, d2)
            for j in range(k):  # bubble-insert into the sorted running k
                # Lexicographic (distance, column) compare: a strict '<'
                # alone would let a displaced lower-column element get
                # stuck behind an equal-distance one, reordering ties vs
                # lax.top_k's stable lower-index preference.
                take = (cd < best_d[j]) | (
                    (cd == best_d[j]) & (ci < best_i[j])
                )
                best_d[j], cd = (
                    jnp.where(take, cd, best_d[j]),
                    jnp.where(take, best_d[j], cd),
                )
                best_i[j], ci = (
                    jnp.where(take, ci, best_i[j]),
                    jnp.where(take, best_i[j], ci),
                )
                best_x[j], cx = (
                    jnp.where(take, cx, best_x[j]),
                    jnp.where(take, best_x[j], cx),
                )
                best_y[j], cy = (
                    jnp.where(take, cy, best_y[j]),
                    jnp.where(take, best_y[j], cy),
                )

    for j in range(k):
        real = best_d[j] < 0.5 * _SELF_MASK
        idx_ref[:, j, :] = jnp.where(real, best_i[j], row_gids)
        offx_ref[:, j, :] = jnp.where(real, best_x[j] - xr, 0.0)
        offy_ref[:, j, :] = jnp.where(real, best_y[j] - yr, 0.0)
        dist_ref[:, j, :] = jnp.where(
            real, jnp.sqrt(jnp.maximum(best_d[j], 0.0)), 0.0
        )


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_r", "chunk_c", "block_m", "interpret"),
)
def knn_batch_pallas_big(
    points: Array,
    k: int,
    valid: Optional[Array] = None,
    block_r: Optional[int] = None,
    chunk_c: Optional[int] = None,
    block_m: int = 1,
    interpret: bool = False,
) -> Tuple[Array, Array, Array]:
    """Batched k-NN for swarms past the fused kernel's VMEM cliff
    (``fits_vmem`` fails for N > 640): streams the distance matrix in
    ``(block_r, chunk_c)`` tiles with a running top-k. The ``(M, N, N)``
    tensor never exists anywhere — not in HBM either, unlike the XLA
    fallback. VMEM holds the tile intermediates plus three full
    ``(block_m, 1, n_pad)`` position/validity planes (~96 B/point: Mosaic
    pads the singleton sublane axis to 8, so each f32 plane costs
    32 B/point — fine to ~10^5 points), and the chunk loop is a static
    unroll of
    ``n_pad/chunk_c`` iterations, so compile time grows with N;
    ``impl="auto"`` caps this path at N <= 16384 (``fits_big_kernel``).
    Output layout and selection semantics are identical to
    ``knn_batch_pallas`` / ``ops.knn.knn`` (ties break toward the lower
    index).

    ``block_r``/``chunk_c`` must be lane-aligned (multiples of 128); N pads
    to their lcm. The defaults, 256 x 512 tiles, stream ~3 MB of VMEM
    intermediates per program up to N=2048; past it the tiles are 128 rows
    by a quarter of N, which is what keeps the compile short (for a
    described v5e the kernel alone at N=8192: 68 s with 256 x 512 tiles, 16
    chunks unrolled; 22 s with 256 x 2048; 6 s with 128 x 2048).
    """
    m, n, d = points.shape
    if block_r is None:
        block_r = 256 if n <= 2048 else 128
    if chunk_c is None:
        chunk_c = max(512, 128 * -(-n // (4 * 128)))
    assert d == 2, f"knn_batch_pallas_big is 2-D only, got d={d}"
    assert k < n, f"knn needs k < N (k={k}, N={n})"
    assert block_r % 128 == 0 and chunk_c % 128 == 0, (
        f"block_r/chunk_c must be multiples of 128, got {block_r}/{chunk_c}"
    )
    import math

    step = math.lcm(block_r, chunk_c)
    n_pad = ((n + step - 1) // step) * step
    m_pad = ((m + block_m - 1) // block_m) * block_m
    x, y, vm = _pad_planes(points, valid, m_pad, n_pad)

    rows_plane = pl.BlockSpec(
        (block_m, 1, block_r), lambda i, r: (i, 0, r), memory_space=pltpu.VMEM
    )
    cols_plane = pl.BlockSpec(
        (block_m, 1, n_pad), lambda i, r: (i, 0, 0), memory_space=pltpu.VMEM
    )
    out_plane = pl.BlockSpec(
        (block_m, k, block_r),
        lambda i, r: (i, 0, r),
        memory_space=pltpu.VMEM,
    )
    out_f32 = jax.ShapeDtypeStruct((m_pad, k, n_pad), jnp.float32)
    idx, offx, offy, dist = pl.pallas_call(
        functools.partial(_knn_kernel_chunked, k, chunk_c),
        grid=(m_pad // block_m, n_pad // block_r),
        in_specs=[rows_plane, rows_plane, cols_plane, cols_plane, cols_plane],
        out_specs=[out_plane] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, k, n_pad), jnp.int32),
            out_f32,
            out_f32,
            out_f32,
        ],
        interpret=interpret,
        name="knn_streaming",
        # the chunk loop is a static unroll and the compiler keeps every
        # chunk's tile intermediates on the stack: 16.2 MB at N=8192, past
        # the 16 MB a kernel gets unasked (a v5e core has 128 MB)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_STREAMING_VMEM_LIMIT
        ),
    )(x, y, x, y, vm)
    return _unpack_outputs(idx, offx, offy, dist, m, n)


@functools.partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def knn_batch_pallas(
    points: Array,
    k: int,
    valid: Optional[Array] = None,
    block_m: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[Array, Array, Array]:
    """Batched k nearest neighbors, fused on-chip.

    Args:
      points: ``(M, N, 2)`` positions for M independent formations.
      k: neighbor count, ``k < N``.
      valid: optional ``(M, N)`` bool mask; invalid points are never
        selected and short rows degrade to self-loops (same contract as
        ``ops.knn.knn``).
      block_m: formations per kernel program. Default: scaled so the
        ~6 live ``(block_m, Np, Np)`` f32 intermediates stay under ~12 MB
        of VMEM (8 formations/program at Np=128, 1 at Np >= 512).
      interpret: run in Pallas interpret mode (CPU tests).

    Returns:
      ``(idx (M, N, k) int32, offsets (M, N, k, 2), dists (M, N, k))``,
      sorted by ascending distance — the ``ops.knn.knn`` layout.
    """
    m, n, d = points.shape
    assert d == 2, f"knn_batch_pallas is 2-D only, got d={d}"
    assert k < n, f"knn needs k < N (k={k}, N={n})"
    n_pad = padded_n(n)
    if not fits_vmem(n):
        raise ValueError(
            f"knn_batch_pallas: N={n} (padded {n_pad}) needs "
            f"~{6 * 4 * n_pad * n_pad >> 20} MB of VMEM intermediates even "
            f"at block_m=1 (budget {_VMEM_BUDGET >> 20} MB); use the XLA "
            "path (knn_batch(..., impl='xla') / EnvParams.knn_impl='xla')"
        )
    if block_m is None:
        # ~6 live (block_m, Np, Np) f32 intermediates (d2, xb, yb, masks)
        # under the VMEM budget.
        block_m = max(1, min(8, _VMEM_BUDGET // (6 * 4) // (n_pad * n_pad)))
    m_pad = ((m + block_m - 1) // block_m) * block_m
    x, y, vm = _pad_planes(points, valid, m_pad, n_pad)

    plane = pl.BlockSpec(
        (block_m, 1, n_pad), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    out_plane = pl.BlockSpec(
        (block_m, k, n_pad), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    out_f32 = jax.ShapeDtypeStruct((m_pad, k, n_pad), jnp.float32)
    idx, offx, offy, dist = pl.pallas_call(
        functools.partial(_knn_kernel, k),
        grid=(m_pad // block_m,),
        in_specs=[plane, plane, plane],
        out_specs=[out_plane] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, k, n_pad), jnp.int32),
            out_f32,
            out_f32,
            out_f32,
        ],
        interpret=interpret,
        name="knn_fused",
    )(x, y, vm)
    return _unpack_outputs(idx, offx, offy, dist, m, n)
