"""Scenario engine contracts (scenarios/, docs/scenarios.md).

The two load-bearing invariants:

1. **Severity-0 identity, bitwise**: every registered scenario at
   severity 0 reproduces the clean ``FormationEnv`` trajectory exactly
   (agents, goal, obs, rewards, dones) at identical seeds — the
   disturbance stack may add math to the program but never drift the
   clean path (layers are ``jnp.where``-guarded, not ``+ 0.0``).
2. **Compile-once**: scenario identity and severity are traced data, so
   ONE jitted train step serves a whole severity schedule with zero
   recompiles, and ONE jitted eval step serves every scenario x severity
   x same-architecture checkpoint (budget-1 RetraceGuard on both).
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.formation import (
    reset_batch,
    step_batch,
)
from marl_distributedformation_tpu.scenarios import (
    ScenarioSchedule,
    ScenarioSpec,
    ScenarioStage,
    broadcast_params,
    get_scenario,
    register_scenario,
    registered_scenarios,
    sample_scenario_batch,
    scenario_step_batch,
    schedule_from_cfg,
)

M, N, STEPS = 3, 4, 8
PARAMS = EnvParams(num_agents=N, max_steps=6)


_ROW_FIELDS = ("agents", "goal", "obstacles", "obs", "reward", "done")


def _rollout(params, step_fn, num_steps=STEPS, m=M, seed=0):
    """Drive ``step_fn(state, velocity)`` with a shared random action
    stream; returns per-step ``_ROW_FIELDS`` tuples (obstacles included
    so the moving-obstacle layer has a recorded discriminator)."""
    state = reset_batch(jax.random.PRNGKey(seed), params, m)
    key = jax.random.PRNGKey(7)
    rows = []
    for _ in range(num_steps):
        key, k_act = jax.random.split(key)
        vel = params.max_speed * jax.random.uniform(
            k_act, (m, params.num_agents, 2), minval=-1.0, maxval=1.0
        )
        state, tr = step_fn(state, vel)
        rows.append(
            jax.device_get(
                (
                    state.agents, state.goal, state.obstacles,
                    tr.obs, tr.reward, tr.done,
                )
            )
        )
    return rows


def _scenario_step_fn(params, name, severity, m=M):
    sp = broadcast_params(
        get_scenario(name).build(jnp.float32(severity)), m
    )
    return lambda state, vel: scenario_step_batch(state, vel, sp, params)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_has_a_real_scenario_suite():
    names = registered_scenarios()
    assert len(names) >= 5
    assert "clean" in names
    # The ISSUE's named capabilities all have a registered carrier.
    for required in (
        "actuator_fault", "sensor_noise", "wind", "moving_goal",
        "goal_switch", "comm_dropout",
    ):
        assert required in names


def test_unknown_scenario_fails_fast_naming_registry():
    with pytest.raises(ValueError) as e:
        get_scenario("windd")
    msg = str(e.value)
    assert "did you mean 'wind'" in msg
    for name in registered_scenarios():
        assert name in msg, "the error must list every valid entry"


def test_register_scenario_refuses_silent_overwrite():
    with pytest.raises(ValueError):
        register_scenario(ScenarioSpec(name="clean"))


# ---------------------------------------------------------------------------
# Severity-0 identity (bitwise) + severity>0 actually perturbs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", registered_scenarios())
def test_severity_zero_is_bitwise_clean_trajectory(name):
    clean = _rollout(PARAMS, lambda s, v: step_batch(s, v, PARAMS))
    scen = _rollout(PARAMS, _scenario_step_fn(PARAMS, name, 0.0))
    for t, (c_row, s_row) in enumerate(zip(clean, scen)):
        for c, s, what in zip(c_row, s_row, _ROW_FIELDS):
            assert np.array_equal(np.asarray(c), np.asarray(s)), (
                f"{name} severity=0 diverged from clean at step {t} "
                f"({what}) — must be bitwise identical"
            )


@pytest.mark.parametrize(
    "name", [n for n in registered_scenarios() if n != "clean"]
)
def test_severity_one_perturbs_the_trajectory(name):
    # The obstacle layers are (documented) identities on an env with no
    # obstacles — give them something to move / occlude behind.
    params = (
        dataclasses.replace(PARAMS, num_obstacles=4)
        if name in ("obstacle_field", "moving_obstacles")
        else PARAMS
    )
    clean = _rollout(params, lambda s, v: step_batch(s, v, params))
    scen = _rollout(params, _scenario_step_fn(params, name, 1.0))
    assert any(
        not np.array_equal(np.asarray(c), np.asarray(s))
        for c_row, s_row in zip(clean, scen)
        for c, s in zip(c_row, s_row)
    ), f"{name} at severity 1 must change the trajectory"


def test_severity_zero_identity_knn_obs_mode():
    """The knn batched-obs routing (with_obs=False + batch-wide search)
    must preserve the identity too — it is a separate code path."""
    params = EnvParams(num_agents=5, max_steps=6, obs_mode="knn", knn_k=2)
    clean = _rollout(params, lambda s, v: step_batch(s, v, params))
    scen = _rollout(params, _scenario_step_fn(params, "storm", 0.0))
    for c_row, s_row in zip(clean, scen):
        for c, s in zip(c_row, s_row):
            assert np.array_equal(np.asarray(c), np.asarray(s))


def test_comm_dropout_masks_only_neighbor_columns():
    """At drop prob 1.0 every neighbor-derived column is zero while own
    position (and the relative goal) stay untouched."""
    from marl_distributedformation_tpu.scenarios import (
        neighbor_obs_columns,
    )

    sp = broadcast_params(
        get_scenario("comm_dropout").build(jnp.float32(2.0)), M
    )  # 0.5 * 2.0 -> clipped to prob 1.0
    assert float(sp.comm_drop_prob[0]) == 1.0
    state = reset_batch(jax.random.PRNGKey(0), PARAMS, M)
    vel = jnp.zeros((M, N, 2), jnp.float32)
    _, tr_clean = step_batch(state, vel, PARAMS)
    _, tr = scenario_step_batch(state, vel, sp, PARAMS)
    cols = neighbor_obs_columns(PARAMS)
    obs = np.asarray(tr.obs)
    assert np.all(obs[..., cols] == 0.0)
    assert np.array_equal(
        obs[..., ~cols], np.asarray(tr_clean.obs)[..., ~cols]
    )


# ---------------------------------------------------------------------------
# Domain-randomized batches
# ---------------------------------------------------------------------------


def test_mixed_scenario_batch_steps():
    specs = tuple(
        get_scenario(n) for n in ("clean", "wind", "sensor_noise")
    )
    probs = jnp.full((3,), 1.0 / 3.0, jnp.float32)
    sp = sample_scenario_batch(
        jax.random.PRNGKey(3), jnp.float32(0.7), probs, specs, M
    )
    assert sp.fault_prob.shape == (M,) and sp.wind.shape == (M, 2)
    state = reset_batch(jax.random.PRNGKey(0), PARAMS, M)
    vel = jnp.ones((M, N, 2), jnp.float32)
    _, tr = scenario_step_batch(state, vel, sp, PARAMS)
    assert np.isfinite(np.asarray(tr.obs)).all()


# ---------------------------------------------------------------------------
# Compile-once contracts
# ---------------------------------------------------------------------------


def test_scenario_train_step_compiles_exactly_once_across_schedule():
    """5 dispatches spanning a stage boundary and a severity ramp (and a
    scenario-mix change) = ONE compile of the jitted train iteration."""
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    schedule = ScenarioSchedule(
        stages=(
            ScenarioStage(rollouts=2, scenarios=("clean",), severity=0.0),
            ScenarioStage(
                rollouts=3,
                scenarios=(
                    "wind", "sensor_noise", "actuator_fault", "storm",
                ),
                severity=1.0,
            ),
        )
    )
    trainer = Trainer(
        EnvParams(num_agents=3, max_steps=5),
        ppo=PPOConfig(n_steps=2, batch_size=8, n_epochs=1),
        config=TrainConfig(
            num_formations=4, checkpoint=False, name="scenario_compile",
            guard_retraces=1,
        ),
        scenario_schedule=schedule,
    )
    severities = []
    for _ in range(5):
        metrics = trainer.run_iteration()
        severities.append(trainer.scenario_severity)
    assert trainer.retrace_guard.count == 1, (
        "severity/stage changes must never recompile the train step"
    )
    assert severities[-1] == 1.0, "the ramp must reach the stage target"
    assert np.isfinite(float(metrics["loss"]))


def test_matrix_eval_compiles_once_for_scenarios_x_severities_x_params():
    """One jitted eval step serves >=5 scenarios x >=3 severities x 2
    parameter sets (checkpoints of one architecture): budget-1 guard."""
    from marl_distributedformation_tpu.models import MLPActorCritic
    from marl_distributedformation_tpu.scenarios import make_matrix_runner

    params = EnvParams(num_agents=3, max_steps=5)
    model = MLPActorCritic(act_dim=2)
    dummy = jnp.zeros((1, params.obs_dim), jnp.float32)
    param_sets = [
        model.init(jax.random.PRNGKey(i), dummy) for i in range(2)
    ]
    run, guard = make_matrix_runner(model, params, num_formations=4)
    key = jax.random.PRNGKey(11)
    names = ("clean", "wind", "sensor_noise", "actuator_fault", "storm")
    for model_params in param_sets:
        for name in names:
            for severity in (0.0, 0.5, 1.0):
                out = run(
                    key, model_params,
                    get_scenario(name).build(jnp.float32(severity)),
                )
    assert guard.count == 1
    assert np.isfinite(float(out["episode_return_per_agent"]))


# ---------------------------------------------------------------------------
# Schedule parsing
# ---------------------------------------------------------------------------


def test_schedule_from_names_list():
    schedule = schedule_from_cfg(["wind", "storm"], default_severity=0.3)
    assert schedule.names == ("wind", "storm")
    assert schedule.severity_at(0) == pytest.approx(0.3)
    assert schedule.severity_at(99) == pytest.approx(0.3)


def test_schedule_from_stage_dicts_ramps_and_holds():
    schedule = schedule_from_cfg(
        "[{rollouts: 2, scenarios: [clean]},"
        " {rollouts: 3, scenarios: [wind], severity: 1.0}]",
        default_severity=0.5,
    )
    assert schedule.total_rollouts == 5
    assert schedule.names == ("clean", "wind")
    # Stage 2 ramps from stage 1's end (0.5) to 1.0 over 3 rollouts.
    assert schedule.severity_at(2) == pytest.approx(0.5)
    assert schedule.severity_at(4) == pytest.approx(1.0)
    assert schedule.severity_at(50) == pytest.approx(1.0)  # holds
    probs = schedule.probs_at(3)
    assert probs.tolist() == [0.0, 1.0]


def test_schedule_rejects_unknown_scenarios_and_keys():
    with pytest.raises(ValueError, match="registered scenarios"):
        schedule_from_cfg(["warp_drive"])
    with pytest.raises(ValueError, match="unknown scenario-stage keys"):
        schedule_from_cfg([{"rollouts": 1, "scenario": ["wind"]}])


# ---------------------------------------------------------------------------
# Robustness matrix CLI + evaluate.py fail-fast
# ---------------------------------------------------------------------------


def _train_tiny_run(tmp_path, name="matrixrun"):
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    trainer = Trainer(
        EnvParams(num_agents=3, max_steps=5),
        ppo=PPOConfig(n_steps=2, batch_size=8, n_epochs=1),
        config=TrainConfig(
            num_formations=4, checkpoint=True, name=name,
            log_dir=str(tmp_path / "logs" / name),
        ),
    )
    trainer.run_iteration()
    trainer.save()
    trainer.run_iteration()
    trainer.save()
    return trainer


def test_robustness_matrix_cli_emits_json(tmp_path, monkeypatch, capsys):
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))
    monkeypatch.setattr(
        "marl_distributedformation_tpu.utils.repo_root", lambda: tmp_path
    )
    monkeypatch.setattr(
        "marl_distributedformation_tpu.utils.config.repo_root",
        lambda: tmp_path,
    )
    import shutil

    (tmp_path / "cfg").mkdir()
    shutil.copy(
        Path(__file__).resolve().parent.parent / "cfg" / "config.yaml",
        tmp_path / "cfg" / "config.yaml",
    )
    _train_tiny_run(tmp_path)

    import robustness_matrix as rm

    monkeypatch.setattr(rm, "repo_root", lambda: tmp_path)
    report = rm.main(
        [
            "name=matrixrun",
            "num_agents_per_formation=3",
            "max_steps=5",
            "eval_formations=4",
        ]
    )
    # Acceptance shape: >= 5 scenarios x 2 checkpoints, one compile.
    assert len(report["scenarios"]) >= 5
    assert len(report["checkpoints"]) == 2
    assert len(report["severities"]) >= 3
    assert report["eval_compiles"] == 1
    on_disk = json.loads(Path(report["out"]).read_text())
    assert set(on_disk["matrix"]) == set(report["checkpoints"])
    cell = next(iter(next(iter(on_disk["matrix"].values())).values()))
    assert "episode_return_per_agent" in next(iter(cell.values()))
    # The stdout JSON line parses.
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["eval_compiles"] == 1

    with pytest.raises(SystemExit, match="registered scenarios"):
        rm.main(["name=matrixrun", "scenarios=[windd]"])


def test_evaluate_cli_fails_fast_on_unknown_scenario_and_key():
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import evaluate as evaluate_cli

    with pytest.raises(SystemExit, match="registered scenarios"):
        evaluate_cli.main(["name=x", "scenario=warp_drive"])
    with pytest.raises(SystemExit, match="eval_formations"):
        evaluate_cli.main(["name=x", "eval_formatoins=8"])
    # Near-misses that ARE valid YAML keys but would silently evaluate
    # the clean env: the plural training key, and a severity without a
    # scenario to apply it to.
    with pytest.raises(SystemExit, match="SINGULAR scenario="):
        evaluate_cli.main(["name=x", "scenarios=wind"])
    with pytest.raises(SystemExit, match="without scenario="):
        evaluate_cli.main(["name=x", "scenario_severity=1.0"])


def test_scenario_schedule_survives_resume(tmp_path):
    """resume=true must re-enter the schedule at the restored rollout
    index — not replay the severity ramp from stage 0."""
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    schedule = ScenarioSchedule(
        stages=(
            ScenarioStage(rollouts=2, scenarios=("clean",), severity=0.0),
            ScenarioStage(rollouts=4, scenarios=("storm",), severity=1.0),
        )
    )

    def make(resume):
        return Trainer(
            EnvParams(num_agents=3, max_steps=5),
            ppo=PPOConfig(n_steps=2, batch_size=8, n_epochs=1),
            config=TrainConfig(
                num_formations=4, checkpoint=True, name="scenario_resume",
                log_dir=str(tmp_path / "logs" / "scenario_resume"),
                resume=resume,
            ),
            scenario_schedule=schedule,
        )

    trainer = make(resume=False)
    for _ in range(4):  # land mid-way through the storm stage's ramp
        trainer.run_iteration()
    trainer.save()
    resumed = make(resume=True)
    assert resumed._scenario_rollouts == 4
    assert resumed.scenario_severity == pytest.approx(
        schedule.severity_at(4)
    )
    assert resumed.scenario_severity > 0.0, "must not restart at stage 0"
    # The sampling stream is a pure function of (seed, rollout index):
    # the resumed draw equals the uninterrupted run's draw for rollout 4
    # (not a replay of rollout 0's).
    for resumed_leaf, live_leaf in zip(
        jax.tree_util.tree_leaves(resumed.scenario_params),
        jax.tree_util.tree_leaves(trainer.scenario_params),
    ):
        assert np.array_equal(
            np.asarray(resumed_leaf), np.asarray(live_leaf)
        )


def test_schedule_rejects_zero_rollout_stage():
    with pytest.raises(ValueError, match="rollouts must be positive"):
        schedule_from_cfg([{"rollouts": 0, "scenarios": ["wind"]}])


def test_evaluate_scenario_shifts_baseline_returns():
    """The public eval entry under a scenario: same seed, same act_fn —
    wind at severity 1 must change the baseline controller's return."""
    from marl_distributedformation_tpu.eval import (
        baseline_act_fn,
        evaluate,
        evaluate_scenario,
    )

    clean = evaluate(
        baseline_act_fn(PARAMS), PARAMS, num_formations=4, seed=5
    )
    windy = evaluate_scenario(
        baseline_act_fn(PARAMS), PARAMS, "wind", 1.0,
        num_formations=4, seed=5,
    )
    zero = evaluate_scenario(
        baseline_act_fn(PARAMS), PARAMS, "wind", 0.0,
        num_formations=4, seed=5,
    )
    assert zero == clean, "severity 0 must reproduce the clean eval"
    assert windy["episode_return_per_agent"] != clean[
        "episode_return_per_agent"
    ]


def test_serving_smoke_rejects_unknown_scenario():
    """The smoke's scenario hook resolves the registry BEFORE touching
    the scheduler — a typo fails fast, never a clean-noise run."""
    from marl_distributedformation_tpu.serving.smoke import (
        run_smoke_benchmark,
    )

    with pytest.raises(ValueError, match="registered scenarios"):
        run_smoke_benchmark(None, row_shape=(8,), scenario="windd")
