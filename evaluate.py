#!/usr/bin/env python
"""Quantitative policy evaluation — the capability the reference lacks
entirely (its only evaluation is watching animations, SURVEY.md §4).

Rolls full episodes for M formations in one jitted scan and prints a
comparison table: trained policy vs the scripted potential-field baseline
(env/baseline.py = reference simulate.py:256-319) vs zero actions, on
identical initial states. Emits one JSON line for machine consumption.

Usage:
    python evaluate.py name=myrun                  # latest checkpoint of run
    python evaluate.py checkpoint=logs/x/rl_model_200_steps.ckpt
    python evaluate.py name=myrun eval_formations=1024 eval_seed=7
    python evaluate.py name=myrun scenario=wind scenario_severity=0.5
                                                   # robustness: evaluate
                                                   # under a disturbance
                                                   # scenario (scenarios/)

Unknown override keys and unknown scenario names fail fast with the valid
entries — a typo must never silently evaluate the clean default.
"""

from __future__ import annotations

import json
import re
import sys

from marl_distributedformation_tpu.eval import (
    baseline_act_fn,
    evaluate,
    evaluate_checkpoint,
    zero_act_fn,
)
from marl_distributedformation_tpu.utils import (
    announce_device,
    env_params_from_config,
    latest_checkpoint,
    load_config,
    run_dir,
    setup_platform,
    validate_override_keys,
)

# Keys meaningful to this entry point beyond the YAML config defaults.
EVAL_KEYS = (
    "checkpoint",
    "eval_formations",
    "eval_seed",
    "eval_deterministic",
    "scenario",
)


def _scenario_params(cfg, overrides):
    """Resolve ``scenario=NAME`` (+ ``scenario_severity``) to traced
    ScenarioParams — unknown names exit naming the registry entries.

    Two near-miss spellings that would otherwise pass key validation
    (both are real YAML keys) and silently evaluate the CLEAN env are
    rejected explicitly: the plural training key ``scenarios=``, and a
    ``scenario_severity=`` override with no ``scenario=`` to apply it to.
    """
    name = cfg.get("scenario")
    override_keys = {
        o.split("=", 1)[0] for o in overrides if "=" in o
    }
    if "scenarios" in override_keys:
        raise SystemExit(
            "evaluate.py takes the SINGULAR scenario=<name> (scenarios= "
            "is the train.py domain-randomization key and would be "
            "ignored here); e.g. scenario=wind scenario_severity=0.5"
        )
    if not name:
        if "scenario_severity" in override_keys:
            raise SystemExit(
                "scenario_severity=... was given without scenario=<name> "
                "— it would silently apply to nothing; add scenario=<name>"
            )
        return None, None, None
    from marl_distributedformation_tpu.scenarios import scenario_params_for

    severity = float(cfg.get("scenario_severity", 0.5) or 0.0)
    try:
        return scenario_params_for(str(name), severity), str(name), severity
    except ValueError as e:
        raise SystemExit(str(e)) from e


def main(argv=None) -> dict:
    overrides = sys.argv[1:] if argv is None else argv
    # Fail fast on mistyped keys: this entry point has no config snapshot
    # to surface a typo, and an ignored key means evaluating the wrong
    # thing (e.g. the clean env instead of the requested scenario).
    validate_override_keys(overrides, extra_keys=EVAL_KEYS)
    cfg = load_config(overrides)
    setup_platform(cfg.get("platform"))
    stamp = announce_device("eval")
    params = env_params_from_config(cfg)
    m = int(cfg.get("eval_formations", 1024))
    seed = int(cfg.get("eval_seed", 1234))
    sp, scenario_name, severity = _scenario_params(cfg, overrides)

    # eval_deterministic=false evaluates the policy as it behaves during
    # training (actions sampled from its Gaussian) — SB3's
    # evaluate_policy(deterministic=...) knob. Policies trained with a
    # high entropy bonus can rely on their action noise; the mode action
    # alone can misrepresent them (see docs/acceptance/hetero5/). Values
    # arrive YAML-parsed, so plain truthiness is the repo convention.
    det = bool(cfg.get("eval_deterministic", True))

    ckpt = cfg.get("checkpoint")
    if not ckpt:
        log_dir = run_dir(cfg)
        # Strictly seed<N> DIRECTORIES: stray files or backups like
        # seed0.bak must neither crash the sort nor flip a single run
        # into sweep mode.
        member_dirs = sorted(
            (
                p for p in log_dir.glob("seed*")
                if p.is_dir() and re.fullmatch(r"seed\d+", p.name)
            ),
            key=lambda p: int(p.name.removeprefix("seed")),
        )
        if member_dirs:
            # Sweep run (train/sweep.py): rank EVERY member by held-out
            # evaluation on identical initial states — more principled
            # than sweep_summary.json's training-reward ranking.
            return eval_sweep(
                member_dirs, params, m, seed, stamp, det,
                scenario_params=sp, scenario=scenario_name,
                severity=severity,
            )
        ckpt = latest_checkpoint(log_dir)
        if ckpt is None:
            raise SystemExit(
                f"no checkpoint under {log_dir}; pass checkpoint=... or "
                "name=<trained run>"
            )

    rows = {
        "policy": evaluate_checkpoint(
            str(ckpt), params, m, seed, det, scenario_params=sp
        ),
        "baseline": evaluate(
            baseline_act_fn(params), params, m, seed, scenario_params=sp
        ),
        "zero": evaluate(
            zero_act_fn(), params, m, seed, scenario_params=sp
        ),
    }

    cols = [
        "episode_return_per_agent",
        "final_avg_dist_to_goal",
        "last100_avg_dist_to_goal",
        "final_ave_dist_to_neighbor",
    ]
    name_w = max(len(k) for k in rows)
    print(f"[eval] checkpoint: {ckpt}")
    print(f"[eval] M={m} formations x N={params.num_agents} agents, "
          f"seed={seed}, full episodes")
    if scenario_name:
        print(f"[eval] scenario={scenario_name} severity={severity:g}")
    header = " | ".join(f"{c:>26}" for c in cols)
    print(f"{'':<{name_w}} | {header}")
    for name, r in rows.items():
        vals = " | ".join(f"{r[c]:>26.2f}" for c in cols)
        print(f"{name:<{name_w}} | {vals}")

    result = {
        "checkpoint": str(ckpt),
        "eval_formations": m,
        "num_agents": params.num_agents,
        "seed": seed,
        "eval_deterministic": det,
        **(
            {"scenario": scenario_name, "scenario_severity": severity}
            if scenario_name
            else {}
        ),
        **{f"{name}_{c}": r[c] for name, r in rows.items() for c in cols},
        "beats_baseline": bool(
            rows["policy"]["episode_return_per_agent"]
            > rows["baseline"]["episode_return_per_agent"]
        ),
        **stamp,
    }
    print(json.dumps(result))
    return result


def eval_sweep(
    member_dirs, params, m: int, seed: int, stamp: dict,
    deterministic: bool = True,
    scenario_params=None, scenario=None, severity=None,
) -> dict:
    """Evaluate every sweep member's latest checkpoint plus the baseline
    and zero policies, all on the same initial states; print a ranked
    table and emit one JSON line."""
    rows = {}
    for d in member_dirs:
        ckpt = latest_checkpoint(d)
        if ckpt is None:
            print(f"[eval] {d.name}: no checkpoint, skipping")
            continue
        rows[d.name] = evaluate_checkpoint(
            str(ckpt), params, m, seed, deterministic,
            scenario_params=scenario_params,
        )
    if not rows:
        raise SystemExit("no member checkpoints found under seed*/")
    rows["baseline"] = evaluate(
        baseline_act_fn(params), params, m, seed,
        scenario_params=scenario_params,
    )
    rows["zero"] = evaluate(
        zero_act_fn(), params, m, seed, scenario_params=scenario_params
    )

    key = "episode_return_per_agent"
    ranked = sorted(rows, key=lambda n: rows[n][key], reverse=True)
    members = [n for n in ranked if n.startswith("seed")]
    best = members[0]
    print(f"[eval] sweep: {len(members)} members, M={m} formations x "
          f"N={params.num_agents} agents, seed={seed}, full episodes")
    name_w = max(len(n) for n in rows)
    print(f"{'':<{name_w}} | {key:>26} | final_avg_dist_to_goal")
    for n in ranked:
        marker = " <- best member" if n == best else ""
        print(f"{n:<{name_w}} | {rows[n][key]:>26.2f} | "
              f"{rows[n]['final_avg_dist_to_goal']:>22.2f}{marker}")

    result = {
        "sweep_members": len(members),
        "eval_formations": m,
        "num_agents": params.num_agents,
        "seed": seed,
        "eval_deterministic": deterministic,
        **(
            {"scenario": scenario, "scenario_severity": severity}
            if scenario
            else {}
        ),
        "member_returns": {n: rows[n][key] for n in members},
        "best_member": best,
        "best_return": rows[best][key],
        "baseline_return": rows["baseline"][key],
        "beats_baseline": bool(rows[best][key] > rows["baseline"][key]),
        **stamp,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
