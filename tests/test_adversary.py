"""Adversarial scenario engine contracts (scenarios/adversary.py,
docs/adversarial.md).

The acceptance pins from the adversarial ISSUE:

- **severity 0 can never be a falsifier**: every registered scenario at
  severity 0 is BITWISE the clean cell through the vmapped population
  program (the search's comparison point), so its relative drop is
  exactly 0 — pinned over the whole registry;
- **search determinism** at a fixed seed: identical falsifier reports
  from independent searcher instances;
- **budget-1 compile receipt** across >= 3 generations x >= 2
  checkpoints: model params and scenario knobs are both traced, so the
  population program compiles exactly once, ever;
- ``ScenarioSpec.build`` / ``sample_scenario_batch`` fail fast on
  concrete negative / non-finite severities, naming the scenario;
- ``from_falsifiers`` registers stable ``adv:`` specs and builds a
  trainable stage; the Trainer applies a requested schedule at the next
  dispatch boundary with ZERO recompiles of the train program;
- END TO END: a gate with the adversarial rung rejects a weak
  checkpoint, the verdict carries the falsifier's concrete params
  (promotions.jsonl schema 3), and the supervisor feeds them back into
  the trainer's schedule — the train -> gate -> train loop closes.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.pipeline import (
    AlwaysLearningPipeline,
    GateConfig,
    PromotionLog,
    judge_falsifiers,
)
from marl_distributedformation_tpu.scenarios import (
    AdversaryConfig,
    AdversarySearch,
    ScenarioSchedule,
    ScenarioStage,
    from_falsifiers,
    get_scenario,
    registered_scenarios,
    sample_scenario_batch,
)
from marl_distributedformation_tpu.scenarios.adversary import (
    _stack_rows,
    make_population_runner,
)
from marl_distributedformation_tpu.train import TrainConfig, Trainer

ENV = EnvParams(num_agents=3, max_steps=20)


def _tiny_policy(seed=0):
    model = MLPActorCritic(act_dim=ENV.act_dim)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, ENV.obs_dim), jnp.float32)
    )
    return model, params


def _clean_schedule():
    return ScenarioSchedule(stages=(ScenarioStage(
        rollouts=1, scenarios=("clean",), severity=0.0, severity_start=0.0,
    ),))


def _tiny_trainer(log_dir, name="adv", scenario_schedule="clean", **cfg):
    if scenario_schedule == "clean":
        scenario_schedule = _clean_schedule()
    defaults = dict(
        num_formations=4, checkpoint=False, name=name,
        log_dir=str(log_dir),
    )
    defaults.update(cfg)
    return Trainer(
        ENV,
        ppo=PPOConfig(n_steps=5, n_epochs=1, batch_size=32),
        config=TrainConfig(**defaults),
        scenario_schedule=scenario_schedule,
    )


# ---------------------------------------------------------------------------
# The population program + the search
# ---------------------------------------------------------------------------


def test_severity_zero_is_never_a_falsifier_any_scenario():
    """Bitwise pin over the WHOLE registry: a severity-0 row of any
    scenario reproduces the clean row exactly through the vmapped
    population program, so its relative drop vs clean is identically 0
    — severity 0 cannot falsify, by construction not by tolerance."""
    model, params = _tiny_policy()
    run, guard = make_population_runner(model, ENV, num_formations=3)
    names = registered_scenarios()
    rows = [(get_scenario("clean"), 0.0)] + [
        (get_scenario(name), 0.0) for name in names
    ]
    out = run(jax.random.PRNGKey(0), params, _stack_rows(rows))
    assert guard.count == 1
    host = jax.device_get(out)
    for metric, values in host.items():
        values = np.asarray(values)
        for i, name in enumerate(names):
            assert values[i + 1].tobytes() == values[0].tobytes(), (
                f"scenario {name} at severity 0 drifted the clean "
                f"{metric} — severity 0 would become a spurious falsifier"
            )


def test_search_finds_falsifier_with_positive_severity():
    model, params = _tiny_policy()
    search = AdversarySearch(model, ENV, AdversaryConfig(
        scenarios=("wind",), grid=3, generations=3, num_formations=4,
        drop_tolerance=0.02, resolution=0.001,
    ))
    report = search.search(params, origin="init")
    assert report["falsifiers"], "an untrained policy must break under wind"
    falsifier = report["falsifiers"][0]
    assert falsifier["scenario"] == "wind"
    assert 0.0 < falsifier["severity"] <= search.config.max_severity
    assert falsifier["drop"] > search.config.drop_tolerance
    # The falsifier carries the concrete knobs (the portable payload
    # from_falsifiers and the gate verdicts consume).
    assert falsifier["params"]["wind"][0] > 0.0
    assert report["eval_compiles"] == 1


def test_search_is_deterministic_at_fixed_seed():
    model, params = _tiny_policy()
    cfg = AdversaryConfig(
        scenarios=("wind", "sensor_noise"), grid=3, generations=3,
        num_formations=4, drop_tolerance=0.02,
    )
    reports = [
        AdversarySearch(model, ENV, cfg).search(params, origin="x")
        for _ in range(2)
    ]
    for rep in reports:
        rep.pop("search_seconds")
    assert json.dumps(reports[0], sort_keys=True) == json.dumps(
        reports[1], sort_keys=True
    )


def test_search_compiles_once_across_generations_and_checkpoints():
    """The budget-1 receipt the gate and the bench record: >= 3
    generations x >= 2 same-architecture checkpoints through ONE
    compiled population program (resolution 0 keeps refining, so the
    generation budget is fully spent)."""
    model, params_a = _tiny_policy(seed=0)
    _, params_b = _tiny_policy(seed=1)
    search = AdversarySearch(model, ENV, AdversaryConfig(
        scenarios=("wind",), grid=3, generations=3, num_formations=4,
        drop_tolerance=0.02, resolution=0.0,
    ))
    rep_a = search.search(params_a, origin="ckpt_a")
    rep_b = search.search(params_b, origin="ckpt_b")
    assert rep_a["generations"] >= 3 and rep_b["generations"] >= 3
    assert search.compile_count == 1
    assert search.candidates_per_sec() > 0.0
    # A different architecture is a clean error, not a surprise retrace.
    wide_model = MLPActorCritic(act_dim=ENV.act_dim, hidden=(8,))
    wide = wide_model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, ENV.obs_dim), jnp.float32)
    )
    with pytest.raises(ValueError, match="different parameter"):
        search.search(wide, origin="ckpt_wide")


# ---------------------------------------------------------------------------
# Severity validation (fail fast, naming the scenario)
# ---------------------------------------------------------------------------


def test_build_rejects_negative_and_nonfinite_severity():
    spec = get_scenario("wind")
    with pytest.raises(ValueError, match="'wind'.*>= 0"):
        spec.build(-0.5)
    with pytest.raises(ValueError, match="'wind'.*finite"):
        spec.build(float("nan"))
    with pytest.raises(ValueError, match="'wind'.*finite"):
        spec.build(float("inf"))
    # The traced path is untouched: a jitted builder traces and runs.
    jitted = jax.jit(spec.build)
    params = jitted(jnp.float32(0.5))
    assert float(params.wind[0]) == pytest.approx(2.0)


def test_sample_scenario_batch_rejects_bad_severity():
    specs = (get_scenario("wind"), get_scenario("sensor_noise"))
    key = jax.random.PRNGKey(0)
    probs = jnp.asarray([0.5, 0.5], jnp.float32)
    with pytest.raises(ValueError, match="wind.*sensor_noise"):
        sample_scenario_batch(key, -1.0, probs, specs, 4)
    with pytest.raises(ValueError, match="finite"):
        sample_scenario_batch(key, float("nan"), probs, specs, 4)


# ---------------------------------------------------------------------------
# from_falsifiers -> trainer (the curriculum half of the loop)
# ---------------------------------------------------------------------------


def test_from_falsifiers_registers_stable_specs_and_stage():
    schedule = from_falsifiers(
        [{"scenario": "wind", "severity": 0.8},
         {"scenario": "sensor_noise", "severity": 0.4}],
        rollouts=12,
    )
    assert schedule.names == ("adv:wind", "adv:sensor_noise", "clean")
    stage = schedule.stages[0]
    assert stage.rollouts == 12 and stage.severity == 1.0
    # Derived magnitudes = base x falsifier severity, trained at 1.0.
    adv = get_scenario("adv:wind")
    assert adv.wind_x == pytest.approx(get_scenario("wind").wind_x * 0.8)
    # Re-feeding the same family overwrites IN PLACE: the name union
    # (and with it the trainer's sampler axis) never grows.
    again = from_falsifiers(
        [{"scenario": "wind", "severity": 0.3}], rollouts=5,
    )
    assert again.names == ("adv:wind", "clean")
    assert get_scenario("adv:wind").wind_x == pytest.approx(
        get_scenario("wind").wind_x * 0.3
    )
    with pytest.raises(ValueError, match="positive"):
        from_falsifiers([{"scenario": "wind", "severity": 0.0}])
    with pytest.raises(ValueError, match="positive"):
        from_falsifiers([{"scenario": "wind", "severity": float("nan")}])
    with pytest.raises(ValueError, match="unknown scenario"):
        from_falsifiers([{"scenario": "no_such", "severity": 0.5}])


def test_trainer_applies_requested_schedule_with_zero_recompiles(tmp_path):
    """The zero-recompile contract of the auto-curriculum seam: swapping
    the schedule mid-run (changed spec union included) rebuilds only the
    tiny sampler — the compiled train step is untouched (budget-1
    RetraceGuard across the swap)."""
    trainer = _tiny_trainer(tmp_path, scenario_schedule=_clean_schedule())
    trainer.run_iteration()
    trainer.run_iteration()
    assert trainer.retrace_guard.count == 1
    trainer.request_scenario_schedule(from_falsifiers(
        [{"scenario": "wind", "severity": 0.7}], rollouts=4,
    ))
    # Not applied yet — the training thread owns schedule state and
    # applies at its next dispatch boundary.
    assert trainer._scenario_schedule.names == ("clean",)
    trainer.run_iteration()
    assert trainer._scenario_schedule.names == ("adv:wind", "clean")
    assert trainer.scenario_severity == 1.0
    trainer.run_iteration()
    assert trainer.retrace_guard.count == 1, (
        "a curriculum swap must never recompile the train program"
    )


def test_schedule_swap_never_replays_sampling_draws(tmp_path):
    """A curriculum swap resets the SCHEDULE position but not the
    sampling-key stream: the draw counter keeps climbing, so the first
    post-swap scenario mix cannot bitwise-replay the run's first draw
    (the key-replay bug a plain rollout-counter reset would cause)."""
    schedule = ScenarioSchedule(stages=(ScenarioStage(
        rollouts=1, scenarios=("wind", "sensor_noise"),
        severity=0.5, severity_start=0.5,
    ),))
    trainer = _tiny_trainer(
        tmp_path, name="adv_draws", scenario_schedule=schedule,
        num_formations=16,
    )
    first_draw = jax.device_get(trainer.scenario_params)
    trainer.run_iteration()
    trainer.run_iteration()
    # Same schedule VALUE re-installed: severity and probs match the
    # first draw exactly, so only the sampling key can differ.
    trainer.update_scenario_schedule(ScenarioSchedule(stages=(
        ScenarioStage(rollouts=1, scenarios=("wind", "sensor_noise"),
                      severity=0.5, severity_start=0.5),
    )))
    assert trainer._scenario_rollouts == 0
    assert trainer._scenario_draws == 2, "draw counter must never reset"
    post_swap = jax.device_get(trainer.scenario_params)
    leaves_a = jax.tree_util.tree_leaves(first_draw)
    leaves_b = jax.tree_util.tree_leaves(post_swap)
    assert any(
        a.tobytes() != b.tobytes() for a, b in zip(leaves_a, leaves_b)
    ), "post-swap mix replayed the run's first sampling draw"


def test_fused_trainer_applies_schedule_between_chunks(tmp_path):
    trainer = _tiny_trainer(
        tmp_path, name="adv_fused", fused_chunk=2,
        scenario_schedule=_clean_schedule(),
    )
    jax.block_until_ready(trainer.run_chunk()["reward"])
    trainer.request_scenario_schedule(from_falsifiers(
        [{"scenario": "sensor_noise", "severity": 0.5}], rollouts=4,
    ))
    jax.block_until_ready(trainer.run_chunk()["reward"])
    assert trainer._scenario_schedule.names == ("adv:sensor_noise", "clean")
    assert trainer.retrace_guard.count == 1


def test_update_schedule_without_scenario_seam_fails_fast(tmp_path):
    trainer = _tiny_trainer(
        tmp_path, name="adv_noseam", scenario_schedule=None,
    )
    schedule = from_falsifiers(
        [{"scenario": "wind", "severity": 0.5}], rollouts=2,
    )
    with pytest.raises(ValueError, match="scenarios=\\['clean'\\]"):
        trainer.update_scenario_schedule(schedule)
    with pytest.raises(ValueError, match="scenarios=\\['clean'\\]"):
        trainer.request_scenario_schedule(schedule)


# ---------------------------------------------------------------------------
# The gate rung + the closed loop
# ---------------------------------------------------------------------------


def test_judge_falsifiers_rejects_only_below_floor():
    falsifiers = [
        {"scenario": "wind", "severity": 0.3, "drop": 0.5},
        {"scenario": "storm", "severity": 1.2, "drop": 0.4},
    ]
    reasons = judge_falsifiers(falsifiers, 0.5, "episode_return_per_agent")
    assert len(reasons) == 1 and "wind@0.3" in reasons[0]
    assert judge_falsifiers(falsifiers, 0.1, "m") == []
    # A falsifier with a broken severity is a rejection, not a pass.
    assert judge_falsifiers(
        [{"scenario": "wind", "severity": float("nan"), "drop": 1.0}],
        0.5, "m",
    )


def test_gate_rejection_feeds_trainer_schedule_end_to_end(tmp_path):
    """THE loop: trainer checkpoint -> adversarial gate rejection whose
    verdict carries the falsifier params (promotions.jsonl schema 3) ->
    supervisor feeds them to the trainer -> the next dispatch trains on
    the falsifier stage — with budget-1 receipts for the gate's search
    across candidates AND the train program across the swap."""
    log_dir = tmp_path / "run"
    trainer = _tiny_trainer(
        log_dir, name="adv_e2e", scenario_schedule=_clean_schedule(),
        checkpoint=True, save_freq=5, total_timesteps=5 * 4 * 3,
    )
    trainer.run_iteration()
    trainer.save()
    pipeline = AlwaysLearningPipeline(
        log_dir,
        ENV,
        gate_config=GateConfig(
            scenarios=("wind",), severities=(1.0,), eval_formations=4,
            adversarial=True, adversarial_min_severity=10.0,
            adversarial_grid=3, adversarial_generations=2,
            adversarial_formations=4, adversarial_drop_tolerance=0.02,
        ),
        poll_interval_s=0.01,
        feedback_rollouts=9,
    )
    pipeline.attach_trainer(trainer)
    assert pipeline.poll_once() == 1
    assert len(pipeline.rejections) == 1
    verdict = pipeline.rejections[0]
    assert verdict.falsifiers, "the rejection must carry its falsifiers"
    assert any("adversarial falsifier" in r for r in verdict.reasons)
    assert verdict.adversary_compiles == 1
    assert pipeline.curriculum_updates == 1

    records = PromotionLog.read(log_dir / "promotions.jsonl")
    events = [r["event"] for r in records]
    assert events == ["rejected", "curriculum_updated"]
    rejected = records[0]
    from marl_distributedformation_tpu.pipeline.promote import (
        PROMOTIONS_SCHEMA,
    )

    assert rejected["schema"] == PROMOTIONS_SCHEMA
    assert rejected["falsifiers"][0]["scenario"] == "wind"
    assert rejected["falsifiers"][0]["params"]["wind"][0] > 0.0
    updated = records[1]
    assert updated["feedback_rollouts"] == 9
    assert "adv:wind" in updated["scenarios"]

    # The training thread picks the stage up at its next dispatch, with
    # zero recompiles of the train program.
    trainer.run_iteration()
    assert "adv:wind" in trainer._scenario_schedule.names
    assert trainer.retrace_guard.count == 1

    # A second candidate reuses BOTH compiled gate programs (matrix +
    # adversary): budget-1 across the candidate series.
    trainer.run_iteration()
    trainer.save()
    pipeline.poll_once()
    assert len(pipeline.rejections) == 2
    assert pipeline.gate.adversary.compile_count == 1
    assert pipeline.gate.program.compile_count == 1
    # summary() surfaces the feedback loop for the CLI's JSON line.
    assert pipeline.summary()["curriculum_updates"] == 2
