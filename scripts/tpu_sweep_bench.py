#!/usr/bin/env python
"""On-chip population-training throughput: K fused PPO runs vs K x one.

Times one full training iteration of (a) a single Trainer at M formations
and (b) a SweepTrainer with K members at the same per-member M — both at
the TPU-tuned hyperparameters — and reports the population amortization:
how close the fused sweep gets to K-for-free. Run on the real chip:

    python scripts/tpu_sweep_bench.py [K=8] [M=512]

Prints a markdown row + one JSON line (mirror into docs/acceptance/ when
recording).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def timed_iteration(trainer, iters: int = 10) -> float:
    import jax

    metrics = trainer.run_iteration()  # compile + warmup
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        metrics = trainer.run_iteration()
    jax.block_until_ready(metrics["loss"])
    return (time.perf_counter() - t0) / iters


def main() -> None:
    args = [a for a in sys.argv[1:] if a != "cpu"]
    k = int(args[0]) if args else 8
    m = int(args[1]) if len(args) > 1 else 512

    import jax

    if "cpu" in sys.argv[1:]:  # smoke-testing off-chip (env vars are too
        jax.config.update("jax_platforms", "cpu")  # late; see cfg platform)

    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import (
        SweepTrainer,
        TrainConfig,
        Trainer,
    )

    from marl_distributedformation_tpu.utils.config import PRESETS

    device = jax.devices()[0].device_kind
    # The REAL preset=tpu batch (docs/profiling.md), not a drifting copy.
    ppo = PPOConfig(batch_size=PRESETS["tpu"]["batch_size"])
    env = EnvParams(num_agents=5)

    def cfg(name: str) -> TrainConfig:
        return TrainConfig(
            num_formations=m, checkpoint=False, name=name,
            log_dir=f"/tmp/sweep-bench-{name}",
        )

    single_s = timed_iteration(Trainer(env, ppo=ppo, config=cfg("single")))
    sweep_s = timed_iteration(
        SweepTrainer(env, ppo=ppo, config=cfg("pop"), num_seeds=k)
    )

    n_steps = ppo.n_steps
    single_rate = n_steps * m / single_s
    sweep_rate = n_steps * m * k / sweep_s
    amortization = sweep_rate / (single_rate * k)  # 1.0 = K for free

    print(
        f"| {device} | M={m}/member | single {single_s * 1e3:.1f} ms/iter "
        f"({single_rate:,.0f} fs/s) | K={k} sweep {sweep_s * 1e3:.1f} "
        f"ms/iter ({sweep_rate:,.0f} fs/s aggregate) | "
        f"{amortization:.0%} of K-for-free |"
    )
    print(json.dumps({
        "metric": "sweep_population_throughput",
        "device": device,
        "k": k,
        "m_per_member": m,
        "single_iter_ms": round(single_s * 1e3, 1),
        "sweep_iter_ms": round(sweep_s * 1e3, 1),
        "single_formation_steps_per_sec": round(single_rate, 1),
        "sweep_formation_steps_per_sec": round(sweep_rate, 1),
        "amortization_vs_k_singles": round(amortization, 3),
        "batch_size": ppo.batch_size,
    }))


if __name__ == "__main__":
    main()
