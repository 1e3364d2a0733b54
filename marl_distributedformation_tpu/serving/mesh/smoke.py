"""Mesh smoke: the loopback 2-host acceptance storm.

One call measures four headline numbers:

- ``mesh_req_per_sec`` — client threads hammering the MetaRouter over
  both hosts for ``duration_s``;
- ``mesh_global_swap_latency_s_p50`` / ``_p95`` — wall time of
  coordinator-driven global reloads (prepare + commit across every
  host) under that load, measured over ``swaps`` ascending checkpoints;
- ``mesh_failover_lost_requests`` — accepted requests that never
  resolved (result or typed error) across a REAL ``kill -9`` of one
  host mid-load; the no-accepted-request-lost invariant demands 0;
- ``mesh_host_compile_receipts_max`` — the budget-1 receipt, per host,
  scraped from each surviving host's ``/v1/metrics``.

Also asserts the global monotonicity witness over every completed
response (mesh_step_violations must be 0 — the same checker the chaos
storm runs).
"""

from __future__ import annotations

import shutil
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from marl_distributedformation_tpu.serving.mesh.loopback import (
    spawn_local_mesh,
)
from marl_distributedformation_tpu.utils.checkpoint import (
    checkpoint_path,
    checkpoint_step,
    latest_checkpoint,
)


def make_checkpoint_series(
    log_dir: str | Path,
    promoted_dir: str | Path,
    num_agents: int = 3,
    num_formations: int = 4,
    iterations: int = 2,
) -> Tuple[Path, int]:
    """Train a tiny policy and publish its newest checkpoint into
    ``promoted_dir`` — the minimum a mesh needs to boot. Returns the
    promoted path and its step."""
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    log_dir = Path(log_dir)
    promoted_dir = Path(promoted_dir)
    promoted_dir.mkdir(parents=True, exist_ok=True)
    env = EnvParams(num_agents=num_agents, max_steps=20)
    per_iter = num_formations * num_agents * 5
    Trainer(
        env,
        ppo=PPOConfig(n_steps=5, n_epochs=1, batch_size=32),
        config=TrainConfig(
            num_formations=num_formations,
            total_timesteps=iterations * per_iter,
            save_freq=1,
            name="mesh_smoke",
            log_dir=str(log_dir),
            seed=0,
        ),
    ).train()
    src = latest_checkpoint(log_dir)
    if src is None:
        raise RuntimeError(f"trainer left no checkpoint under {log_dir}")
    dst = promoted_dir / src.name
    shutil.copyfile(src, dst)
    return dst, checkpoint_step(dst)


def publish_next(
    promoted_dir: Path, src: Path, step: int
) -> Tuple[Path, int]:
    """Byte-copy ``src`` to an advanced step under the atomic-rename
    discipline — the storm's synthetic-candidate trick (exactly what a
    still-running trainer would provide)."""
    dst = checkpoint_path(promoted_dir, step)
    tmp = dst.with_name(f".{dst.name}.tmp")
    shutil.copyfile(src, tmp)
    tmp.replace(dst)
    return dst, step


class StepWitness:
    """Response-completion-order monotonicity recorder shared by the
    smoke's client threads (the chaos prober's ``steps`` shape)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.steps: List[Tuple[float, int]] = []
        self.ok = 0
        self.typed_errors = 0
        self.lost = 0

    def record(self, step: int) -> None:
        with self.lock:
            self.ok += 1
            self.steps.append((time.perf_counter(), int(step)))

    def violations(self) -> int:
        from marl_distributedformation_tpu.chaos import (
            check_step_monotonic,
        )

        with self.lock:
            return len(check_step_monotonic(self.steps))


def run_mesh_smoke(
    workdir: str | Path,
    hosts: int = 2,
    duration_s: float = 6.0,
    swaps: int = 3,
    clients: int = 4,
    num_agents: int = 3,
    buckets: Tuple[int, ...] = (1, 8),
    kill_host: bool = True,
    per_iter: int = 60,
    ready_timeout_s: float = 120.0,
) -> Dict[str, Any]:
    """The whole acceptance storm; returns the flat field dict."""
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.serving.scheduler import (
        BackpressureError,
        RequestTimeout,
    )

    import numpy as np

    workdir = Path(workdir)
    promoted = workdir / "promoted"
    src, step0 = make_checkpoint_series(
        workdir / "train", promoted, num_agents=num_agents
    )
    env = EnvParams(num_agents=num_agents, max_steps=20)
    mesh = spawn_local_mesh(
        promoted,
        hosts=hosts,
        buckets=buckets,
        num_agents=num_agents,
        ready_timeout_s=ready_timeout_s,
        probe_interval_s=0.5,
    )
    witness = StepWitness()
    stop = threading.Event()
    obs = np.zeros((1, env.obs_dim), np.float32)

    def client_loop() -> None:
        from marl_distributedformation_tpu.serving.mesh.router import (
            NoHealthyHosts,
        )

        while not stop.is_set():
            try:
                result = mesh.router.predict(obs, timeout_s=5.0)
            except (
                BackpressureError,
                RequestTimeout,
                NoHealthyHosts,
                RuntimeError,
                OSError,
            ):
                with witness.lock:
                    witness.typed_errors += 1
                time.sleep(0.01)
                continue
            except BaseException:
                with witness.lock:
                    witness.lost += 1  # untyped = a lost request
                continue
            witness.record(result.model_step)

    threads = [
        threading.Thread(target=client_loop, daemon=True)
        for _ in range(clients)
    ]
    swap_latencies: List[float] = []
    killed: Optional[str] = None
    try:
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        # Load-phase swaps: ascending synthetic candidates committed
        # through the coordinator barrier while clients hammer.
        step = step0
        swap_every = duration_s / (swaps + 1)
        next_swap = t0 + swap_every
        kill_at = t0 + duration_s * 0.5
        while time.perf_counter() - t0 < duration_s:
            now = time.perf_counter()
            if kill_host and killed is None and now >= kill_at:
                killed = mesh.kill_host(0)
            if len(swap_latencies) < swaps and now >= next_swap:
                step += per_iter
                path, _ = publish_next(promoted, src, step)
                t_swap = time.perf_counter()
                if mesh.coordinator.global_reload(path):
                    swap_latencies.append(
                        time.perf_counter() - t_swap
                    )
                next_swap = now + swap_every
            time.sleep(0.02)
        elapsed = time.perf_counter() - t0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15.0)
        receipts = mesh.router.host_compile_counts()
        mesh.stop()
    for t in threads:
        if t.is_alive():
            witness.lost += 1  # a thread wedged inside a request
    swap_latencies.sort()

    def pct(q: float) -> Optional[float]:
        if not swap_latencies:
            return None
        idx = min(len(swap_latencies) - 1, int(q * len(swap_latencies)))
        return round(swap_latencies[idx], 4)

    max_receipt = max(
        (c for per in receipts.values() for c in per.values()),
        default=0.0,
    )
    return {
        "mesh_hosts": hosts,
        "mesh_req_per_sec": round(witness.ok / max(elapsed, 1e-9), 1),
        "mesh_requests_ok": witness.ok,
        "mesh_typed_errors": witness.typed_errors,
        "mesh_failover_lost_requests": witness.lost,
        "mesh_step_violations": witness.violations(),
        "mesh_global_swaps": len(swap_latencies),
        "mesh_global_swap_latency_s_p50": pct(0.50),
        "mesh_global_swap_latency_s_p95": pct(0.95),
        "mesh_host_killed": killed,
        "mesh_commit_rounds": mesh.coordinator.commit_round,
        "mesh_final_step": mesh.coordinator.fleet_step,
        "mesh_host_compile_receipts_max": max_receipt,
        "mesh_host_compile_receipts": receipts,
    }
