#!/usr/bin/env python
"""Worst-case severity search: minimal-severity falsifiers, one JSON.

Attack a run's checkpoints with the grid-refine falsifier search
(``scenarios/adversary.py``, docs/adversarial.md): per scenario family,
find the SMALLEST severity at which the policy's return drops more than
``drop_tolerance`` (relative) below its own clean cell. Every search
generation is ONE vmapped compiled eval over the whole candidate
population — model params and scenario knobs both traced, so the
program compiles exactly once across every generation AND every
checkpoint (budget-1 RetraceGuard receipt recorded in the report).

Usage (same key=value CLI as every entry point):
    python scripts/adversarial_search.py name=myrun
    python scripts/adversarial_search.py name=myrun \\
        scenarios=[wind,storm] drop_tolerance=0.15 max_severity=2 \\
        search_grid=6 search_generations=5 eval_formations=64
    python scripts/adversarial_search.py checkpoint=logs/x/rl_model_200_steps.msgpack

Writes ``logs/{name}/falsifiers.json`` (per-checkpoint falsifier
reports, schema-stamped) plus the same report as one JSON line on
stdout. The falsifier records feed straight into
``scenarios.from_falsifiers`` (an auto-curriculum training stage) and
match what the promotion gate's adversarial rung logs to
``promotions.jsonl``. Unknown scenario names and mistyped config keys
fail fast naming the valid entries.
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

SEARCH_KEYS = (
    "checkpoint",
    "search_checkpoints",
    "drop_tolerance",
    "max_severity",
    "search_grid",
    "search_generations",
    "search_resolution",
    "eval_formations",
    "eval_seed",
    "eval_deterministic",
    "out",
)


def _checkpoints(cfg) -> list:
    """Explicit ``checkpoint=`` (one path or a YAML list), else the last
    ``search_checkpoints`` (default 1) of the named run."""
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
    keep = max(1, int(cfg.get("search_checkpoints", 1)))
    return [str(p) for p in ckpts[-keep:]]


def _scenarios(cfg) -> tuple:
    from marl_distributedformation_tpu.scenarios import get_scenario

    raw = cfg.get("scenarios")
    if not raw:
        return ()  # AdversaryConfig default: every family except clean
    names = raw if isinstance(raw, list) else [raw]
    try:
        return tuple(get_scenario(str(n)).name for n in names)
    except ValueError as e:  # unknown name -> clean CLI error w/ registry
        raise SystemExit(str(e)) from e


def main(argv=None) -> dict:
    overrides = sys.argv[1:] if argv is None else argv
    validate_override_keys(overrides, extra_keys=SEARCH_KEYS)
    cfg = load_config(overrides)
    setup_platform(cfg.get("platform"))

    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.scenarios import (
        AdversaryConfig,
        AdversarySearch,
    )
    from marl_distributedformation_tpu.scenarios.adversary import (
        FALSIFIERS_SCHEMA,
    )

    params = env_params_from_config(cfg)
    checkpoints = _checkpoints(cfg)
    search_cfg = AdversaryConfig(
        scenarios=_scenarios(cfg),
        drop_tolerance=float(cfg.get("drop_tolerance", 0.2)),
        max_severity=float(cfg.get("max_severity", 1.5)),
        grid=int(cfg.get("search_grid", 6)),
        generations=int(cfg.get("search_generations", 4)),
        resolution=float(cfg.get("search_resolution", 0.02)),
        num_formations=int(cfg.get("eval_formations", 64)),
        seed=int(cfg.get("eval_seed", 1234)),
        deterministic=bool(cfg.get("eval_deterministic", True)),
    )

    policies = [
        LoadedPolicy.from_checkpoint(
            str(p), act_dim=params.act_dim, env_params=params
        )
        for p in checkpoints
    ]
    search = AdversarySearch(policies[0].model, params, search_cfg)
    # Validate EVERY architecture before the first eval, so a mismatched
    # file fails the run up front, by name (the matrix CLI's rule).
    for path, pol in zip(checkpoints, policies):
        search.check_params(pol.params, origin=str(path))

    searches = {}
    for path, pol in zip(checkpoints, policies):
        searches[str(path)] = search.search(pol.params, origin=str(path))

    report = {
        "schema": FALSIFIERS_SCHEMA,
        "name": str(cfg.name),
        "checkpoints": checkpoints,
        "scenarios": list(search.specs and [s.name for s in search.specs]),
        "drop_tolerance": search_cfg.drop_tolerance,
        "max_severity": search_cfg.max_severity,
        "num_agents": params.num_agents,
        "eval_formations": search_cfg.num_formations,
        "seed": search_cfg.seed,
        "searches": searches,
        "eval_compiles": search.compile_count,
        "candidates_per_sec": round(search.candidates_per_sec(), 1),
    }
    report.update(device_stamp())

    # Human-readable slice: the minimal break point per checkpoint.
    print(
        f"[adversary] {len(checkpoints)} checkpoints x "
        f"{len(search.specs)} scenario families, "
        f"M={search_cfg.num_formations}, "
        f"compiles={report['eval_compiles']}, "
        f"{report['candidates_per_sec']:,.0f} candidates/s"
    )
    for ckpt, rep in searches.items():
        fals = {
            f["scenario"]: f["severity"] for f in rep["falsifiers"]
        }
        print(
            f"[adversary] {Path(ckpt).name}: falsified "
            f"{json.dumps(fals)} robust {rep['robust']} "
            f"({rep['generations']} generations)"
        )

    out = cfg.get("out") or str(
        repo_root() / "logs" / str(cfg.name) / "falsifiers.json"
    )
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    report["out"] = str(out)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
