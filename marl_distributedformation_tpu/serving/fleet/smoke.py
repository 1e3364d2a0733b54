"""Fleet smoke storm: mixed-size request traffic across every replica,
with the acceptance evidence in one flat report.

The single-engine smoke (serving/smoke.py) proves coalescing + padding +
compile-once on ONE engine; this storm drives the same mixed-size
request stream through the ROUTER so the fleet-only behaviors are what
gets exercised: routing across replicas, fleet backpressure, failover,
and — because every client records ``(completion order, model_step)``
into one shared log — the global step-monotonicity contract of the
coordinated hot swap.

The report is one flat dict, printed as one JSON line:

- ``requests_per_sec_fleet`` / merged latency percentiles — the fleet
  throughput headline.
- ``max_compiles_per_rung`` + per-replica ``replica{i}_compiles_bucket_{b}``
  — the RetraceGuard receipts: a storm of arbitrary sizes over N
  replicas must cost at most one compile per rung per replica, ever.
- ``step_monotonic_violations`` — count of responses whose
  ``model_step`` was lower than one already completed anywhere in the
  fleet. Zero is the coordinated-reload contract (reload.py).
- routed / rejected / failed-over / healthy-replica counters from
  ``FleetMetrics``.

``mid_storm`` is the chaos hook: a callable invoked once at
``mid_storm_at_s`` on its own thread — tests and the CLI use it to kill
a replica or land a coordinated swap while traffic flows.
"""

from __future__ import annotations

import threading
import time

# py3.10: concurrent.futures.TimeoutError is a distinct class from the
# builtin (merged in 3.11) — a wedged-worker wait must count as a
# timeout, not a failure.
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from marl_distributedformation_tpu.serving.fleet.router import FleetRouter
from marl_distributedformation_tpu.serving.scheduler import (
    BackpressureError,
    RequestTimeout,
)
from marl_distributedformation_tpu.serving.smoke import DEFAULT_SIZES


def warmup_fleet(
    router: FleetRouter, row_shape: Tuple[int, ...]
) -> None:
    """Compile every rung on every replica once, before the clock runs.

    Uses each replica's REGISTRY params (device-committed), the same
    buffers the scheduler dispatches with — warming with the policy's
    host-resident params would compile against a different placement and
    the real dispatch would trip the budget-1 RetraceGuard."""
    for r in router.replicas:
        params, _ = r.registry.active()
        for bucket in r.engine.buckets:
            r.engine.act(
                np.zeros((bucket, *row_shape), np.float32),
                deterministic=True,
                nn_params=params,
            )


def run_fleet_smoke(
    router: FleetRouter,
    row_shape: Tuple[int, ...],
    sizes: Sequence[int] = DEFAULT_SIZES,
    duration_s: float = 2.0,
    num_clients: int = 4,
    deterministic: bool = True,
    seed: int = 0,
    coordinator: Optional[object] = None,
    mid_storm: Optional[Callable[[], None]] = None,
    mid_storm_at_s: float = 0.5,
    warmup: bool = True,
) -> Dict[str, float]:
    """Drive ``num_clients`` request loops through the router for
    ``duration_s`` seconds; returns the merged fleet report. Rejections
    and timeouts are measured, not raised. ``warmup`` pre-compiles every
    rung on every replica so the storm measures serving, not XLA."""
    if warmup:
        warmup_fleet(router, row_shape)
    counts = {"ok": 0, "rejected": 0, "timed_out": 0, "failed": 0}
    lock = threading.Lock()
    # One global completion log of model_steps in response completion
    # order — the monotonicity witness. Recorded via the router's
    # ``on_result`` hook, which runs INSIDE the serving replica's
    # batch-barrier region: the append provably precedes any later
    # coordinated swap, so the log cannot be reordered by a client
    # thread preempted between resolution and its own bookkeeping.
    completion_steps: list = []

    def record(result) -> None:
        with lock:
            completion_steps.append(int(result.model_step))

    stop_at = time.perf_counter() + duration_s

    def loop(idx: int) -> None:
        rng = np.random.default_rng(seed + idx)
        i = idx  # offset the size cycle per client
        while time.perf_counter() < stop_at:
            n = int(sizes[i % len(sizes)])
            i += 1
            obs = rng.standard_normal((n, *row_shape), dtype=np.float32)
            try:
                future = router.submit(
                    obs, deterministic=deterministic, on_result=record
                )
                result = future.result(
                    timeout=router.default_timeout_s + 5.0
                )
            except BackpressureError as e:
                with lock:
                    counts["rejected"] += 1
                time.sleep(min(0.05, e.retry_after_s))
                continue
            except (RequestTimeout, TimeoutError, FutureTimeoutError):
                with lock:
                    counts["timed_out"] += 1
                continue
            except Exception:  # noqa: BLE001 — incl. NoHealthyReplicas
                # Measured, not raised: a storm's job is to report what
                # the fleet did under fire, including the failures.
                with lock:
                    counts["failed"] += 1
                continue
            assert result.actions.shape[0] == n
            with lock:
                counts["ok"] += 1

    threads = [
        threading.Thread(target=loop, args=(i,), daemon=True)
        for i in range(num_clients)
    ]
    chaos = None
    if mid_storm is not None:

        def _chaos() -> None:
            time.sleep(mid_storm_at_s)
            mid_storm()

        chaos = threading.Thread(target=_chaos, daemon=True)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    if chaos is not None:
        chaos.start()
    for t in threads:
        t.join(timeout=duration_s + 30.0)
    if chaos is not None:
        chaos.join(timeout=30.0)
    elapsed = time.perf_counter() - t0

    report = dict(router.snapshot())
    report["duration_s"] = round(elapsed, 3)
    report["client_requests_ok"] = float(counts["ok"])
    report["client_rejected"] = float(counts["rejected"])
    report["client_timed_out"] = float(counts["timed_out"])
    report["client_failed"] = float(counts["failed"])
    report["requests_per_sec_fleet"] = (
        counts["ok"] / elapsed if elapsed > 0 else 0.0
    )
    # Step monotonicity over the global completion order: a violation is
    # any response carrying a step older than one already returned.
    violations = 0
    high = None
    for step in completion_steps:
        if high is not None and step < high:
            violations += 1
        high = step if high is None else max(high, step)
    report["step_monotonic_violations"] = float(violations)
    if completion_steps:
        report["model_step_min"] = float(min(completion_steps))
        report["model_step_max"] = float(max(completion_steps))
    max_compiles = 0
    for r in router.replicas:
        for bucket, count in r.engine.compile_counts().items():
            report[f"replica{r.index}_compiles_bucket_{bucket}"] = float(
                count
            )
            max_compiles = max(max_compiles, count)
    report["max_compiles_per_rung"] = float(max_compiles)
    if coordinator is not None:
        report["fleet_swap_count"] = float(coordinator.swap_count)
        report["fleet_step"] = float(coordinator.fleet_step)
    return report
