#!/usr/bin/env python
"""Compiled-mode Pallas k-NN parity check.

The pytest suite pins JAX to CPU (conftest.py), where the kernel only runs
in interpret mode — Mosaic lowering is never exercised there. This module
holds the single copy of the compiled-parity assertion:

- ``python chip_smoke.py`` runs all three legs on the chip (leg 3);
- on hardware, run it directly: ``python tests/tpu_compiled_parity.py``
  (prints one PARITY_OK / PARITY_FAIL line per leg; exits non-zero on a
  failure and when there is no TPU), or run the whole suite with
  ``MDF_TPU_TESTS=1 pytest tests/`` (conftest leaves the real backend on and
  ``test_ops_pallas.py::test_compiled_pallas_parity_on_tpu`` runs all
  three legs);
- the benchmark's ``gnn100-train-m8k`` cell also exercises the compiled
  kernel on TPU (``impl="auto"`` selects it inside the jitted scan).
"""

import sys
from pathlib import Path

# Standalone-invocation bootstrap: `python tests/tpu_compiled_parity.py`
# puts tests/ (not the repo root) on sys.path, and the package may not be
# pip-installed on a fresh machine — resolve the repo root explicitly so
# the documented command works from anywhere.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _assert_matches_xla(pallas_out, xla_out) -> None:
    """The shared leg assertion: exact index agreement, f32-tolerance
    distance/offset agreement, pallas vs the XLA search."""
    import numpy as np

    idx_p, off_p, d_p = pallas_out
    idx_x, off_x, d_x = xla_out
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_x))
    np.testing.assert_allclose(
        np.asarray(d_p), np.asarray(d_x), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(off_p), np.asarray(off_x), rtol=1e-4, atol=1e-4
    )


def _mode(interpret: bool) -> str:
    return "interpreted" if interpret else "compiled"


def run_parity(
    m: int = 4096, n: int = 100, k: int = 4, interpret: bool = False
) -> str:
    """Assert compiled-pallas == xla == host-float64 ground truth at the
    north-star swarm shape; returns a human-readable OK message, raises
    AssertionError on mismatch. ``interpret`` (here and in the other
    legs) is the CPU spelling for tests of the callers — a parity claim
    comes only from the compiled kernel.

    The float64 leg is the absolute-correctness anchor (added round 3):
    round 2's matmul-expansion XLA path agreed with nothing — 33.5% of its
    neighbor indices were wrong on TPU (bf16 matmul cancellation at world
    scale) while the Pallas kernel was exact, so device-vs-device agreement
    alone is not sufficient evidence.
    """
    import jax
    import numpy as np

    from marl_distributedformation_tpu.ops import knn_batch
    from marl_distributedformation_tpu.ops.knn_pallas import knn_batch_pallas

    pts = jax.random.uniform(jax.random.PRNGKey(0), (m, n, 2)) * 400.0
    xla_out = knn_batch(pts, k, impl="xla")
    idx_x, _, d_x = xla_out
    _assert_matches_xla(
        jax.block_until_ready(knn_batch_pallas(pts, k, interpret=interpret)),
        xla_out,
    )

    # Host float64 ground truth (vectorized; ~0.5 GB peak at the default
    # shape, fine for a hardware acceptance script).
    p64 = np.asarray(pts, np.float64)
    diff = p64[:, :, None, :] - p64[:, None, :, :]  # (M, N, N, 2)
    d2 = (diff * diff).sum(-1)
    mi = np.arange(n)
    d2[:, mi, mi] = np.inf
    idx_t = np.argsort(d2, axis=-1, kind="stable")[..., :k]
    d_t = np.sqrt(np.take_along_axis(d2, idx_t, axis=-1))
    frac_idx_wrong = (np.asarray(idx_x) != idx_t).mean()
    max_d_err = np.abs(np.asarray(d_x, np.float64) - d_t).max()
    # Ties at f32 granularity can legitimately flip an index; distances
    # must still match to f32 rounding. > 0.1% differing indices or any
    # distance off by > 1e-2 world units means a real precision defect.
    assert frac_idx_wrong < 1e-3, (
        f"device knn diverges from float64 truth: {frac_idx_wrong:.2%} "
        f"indices wrong, max |d| err {max_d_err:.3g}"
    )
    assert max_d_err < 1e-2, (
        f"device knn distances off by {max_d_err:.3g} world units vs "
        "float64 truth"
    )
    return (
        f"{_mode(interpret)} pallas == xla == float64 truth on "
        f"{jax.devices()[0].device_kind} (M={m}, N={n}, k={k}; "
        f"idx mismatch vs f64 {frac_idx_wrong:.2e}, "
        f"max dist err {max_d_err:.2e})"
    )


def run_parity_mid(
    m: int = 256, n: int = 512, k: int = 4, interpret: bool = False
) -> str:
    """Compiled FUSED kernel at mid N (512 pads to 512 lanes, VMEM drives
    block_m to 2) vs the XLA search, on hardware. Pins the Mosaic sublane
    rule for sub-8 block_m blocks: a 2-D ``(block_m, n_pad)`` plane is not
    lowerable when block_m < 8, which interpret-mode CPU tests never see
    (the singleton-axis layout in ops/knn_pallas.py:_pad_planes is the
    fix; this leg is its hardware regression gate)."""
    import jax

    from marl_distributedformation_tpu.ops import knn_batch
    from marl_distributedformation_tpu.ops.knn_pallas import knn_batch_pallas

    pts = jax.random.uniform(jax.random.PRNGKey(2), (m, n, 2)) * 400.0
    _assert_matches_xla(
        jax.block_until_ready(knn_batch_pallas(pts, k, interpret=interpret)),
        knn_batch(pts, k, impl="xla"),
    )
    return (
        f"{_mode(interpret)} pallas (block_m=2 sublane regime) == xla on "
        f"{jax.devices()[0].device_kind} (M={m}, N={n}, k={k})"
    )


def run_parity_big(
    m: int = 256, n: int = 1024, k: int = 4, interpret: bool = False
) -> str:
    """Compiled chunked-streaming kernel (ops/knn_pallas.py
    knn_batch_pallas_big — the path for swarms past the fused kernel's
    N <= 640 VMEM cliff) vs the XLA search, on hardware."""
    import jax

    from marl_distributedformation_tpu.ops import knn_batch
    from marl_distributedformation_tpu.ops.knn_pallas import (
        knn_batch_pallas_big,
    )

    pts = jax.random.uniform(jax.random.PRNGKey(1), (m, n, 2)) * 400.0
    _assert_matches_xla(
        jax.block_until_ready(
            knn_batch_pallas_big(pts, k, interpret=interpret)
        ),
        knn_batch(pts, k, impl="xla"),
    )
    return (
        f"{_mode(interpret)} pallas_big == xla on "
        f"{jax.devices()[0].device_kind} "
        f"(M={m}, N={n}, k={k})"
    )


def main() -> None:
    import jax

    if jax.default_backend() != "tpu":
        print(
            f"PARITY_FAIL: backend is {jax.default_backend()!r} — the "
            "compiled kernels exist only on a TPU",
            flush=True,
        )
        sys.exit(2)
    # Catch Exception, not just AssertionError: the failure class this
    # gate exists for (Mosaic lowering rejections, e.g. the sublane rule)
    # surfaces as XlaRuntimeError/ValueError — those must still print a
    # greppable PARITY_FAIL line, not only a traceback.
    for leg, label in (
        (run_parity, ""),
        (run_parity_mid, "(mid)"),
        (run_parity_big, "(big)"),
    ):
        try:
            msg = leg()
        except Exception as e:  # noqa: BLE001 — report, then exit non-zero
            err = f"{type(e).__name__}: {e}" if not isinstance(
                e, AssertionError
            ) else str(e)
            print(f"PARITY_FAIL{label}: {err}"[:2000], flush=True)
            sys.exit(1)
        print(f"PARITY_OK: {msg}", flush=True)


if __name__ == "__main__":
    main()
