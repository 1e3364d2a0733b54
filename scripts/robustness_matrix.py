#!/usr/bin/env python
"""Robustness eval matrix: scenarios x severities x checkpoints, one JSON.

The quantitative stress test of the paper's locality claim: sweep a run's
checkpoint series over every registered disturbance scenario at several
severities, on identical initial states, in ONE compiled eval program
(model params and scenario params are traced inputs; the zero-recompile
contract is enforced with a budget-1 RetraceGuard and the compile count
is recorded in the report).

This CLI is a thin wrapper: the compiled program lives in
``scenarios.matrix`` (``run_matrix`` for a one-shot checkpoint sweep,
``MatrixProgram`` for a long-lived reusable instance) — the
always-learning promotion gate (``pipeline/gate.py``) holds ONE
MatrixProgram for an entire run instead of shelling out here or
re-jitting per candidate.

Usage (same key=value CLI as every entry point):
    python scripts/robustness_matrix.py name=myrun
    python scripts/robustness_matrix.py name=myrun scenarios=[wind,storm] \
        severities=[0,0.5,1] matrix_checkpoints=3 eval_formations=256
    python scripts/robustness_matrix.py checkpoint=logs/x/rl_model_200_steps.ckpt

By default the matrix covers ALL registered scenarios at severities
0 / 0.5 / 1.0 for the run's last 2 checkpoints (training progress vs
robustness), and writes ``logs/{name}/robustness_matrix.json`` plus the
same report as one JSON line on stdout. Unknown scenario names and
mistyped config keys fail fast naming the valid entries.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from marl_distributedformation_tpu.utils import (  # noqa: E402
    device_stamp,
    env_params_from_config,
    load_config,
    repo_root,
    setup_platform,
    validate_override_keys,
)

MATRIX_KEYS = (
    "checkpoint",
    "eval_formations",
    "eval_seed",
    "eval_deterministic",
    "severities",
    "matrix_checkpoints",
    "out",
)


def _checkpoints(cfg) -> list:
    """Resolve the checkpoint list: explicit ``checkpoint=`` (one path or
    a YAML list), else the last ``matrix_checkpoints`` (default 2) of the
    named run."""
    from marl_distributedformation_tpu.utils.checkpoint import (
        checkpoint_step,
    )

    explicit = cfg.get("checkpoint")
    if explicit:
        paths = explicit if isinstance(explicit, list) else [explicit]
        return [str(p) for p in paths]
    log_dir = repo_root() / "logs" / str(cfg.name)
    ckpts = sorted(
        log_dir.glob("rl_model_*_steps.*"), key=checkpoint_step
    )
    if not ckpts:
        raise SystemExit(
            f"no checkpoints under {log_dir}; pass checkpoint=... or "
            "name=<trained run>"
        )
    keep = max(1, int(cfg.get("matrix_checkpoints", 2)))
    return [str(p) for p in ckpts[-keep:]]


def _scenarios(cfg) -> list:
    from marl_distributedformation_tpu.scenarios import (
        get_scenario,
        registered_scenarios,
    )

    raw = cfg.get("scenarios")
    if not raw:
        return list(registered_scenarios())
    names = raw if isinstance(raw, list) else [raw]
    try:
        return [get_scenario(str(n)).name for n in names]
    except ValueError as e:  # unknown name -> clean CLI error w/ registry
        raise SystemExit(str(e)) from e


def main(argv=None) -> dict:
    overrides = sys.argv[1:] if argv is None else argv
    validate_override_keys(overrides, extra_keys=MATRIX_KEYS)
    cfg = load_config(overrides)
    setup_platform(cfg.get("platform"))
    from marl_distributedformation_tpu.scenarios import run_matrix

    params = env_params_from_config(cfg)
    severities = [
        float(s) for s in (cfg.get("severities") or (0.0, 0.5, 1.0))
    ]
    report = run_matrix(
        _checkpoints(cfg),
        params,
        scenarios=_scenarios(cfg),
        severities=severities,
        num_formations=int(cfg.get("eval_formations", 256)),
        seed=int(cfg.get("eval_seed", 1234)),
        deterministic=bool(cfg.get("eval_deterministic", True)),
    )
    report["name"] = str(cfg.name)
    report.update(device_stamp())

    # Human-readable slice: per checkpoint x scenario, return at the
    # highest severity vs clean (degradation is the robustness headline).
    key = "episode_return_per_agent"
    hi = f"{max(severities):g}"
    print(
        f"[matrix] {len(report['checkpoints'])} checkpoints x "
        f"{len(report['scenarios'])} scenarios x {len(severities)} "
        f"severities, M={report['eval_formations']}, "
        f"compiles={report['eval_compiles']}"
    )
    for ckpt, per_scenario in report["matrix"].items():
        print(f"[matrix] {Path(ckpt).name}:")
        for scenario, per_sev in per_scenario.items():
            vals = " ".join(
                f"s={sev}:{metrics[key]:,.0f}"
                for sev, metrics in per_sev.items()
            )
            print(f"  {scenario:<16} {vals}")

    out = cfg.get("out") or str(
        repo_root() / "logs" / str(cfg.name) / "robustness_matrix.json"
    )
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    report["out"] = str(out)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
