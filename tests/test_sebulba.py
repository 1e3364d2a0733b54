"""Sebulba lane contract (tier-1): the split acting/learning
architecture (train/sebulba, docs/sebulba.md).

The acceptance pins from the sebulba ISSUE:

- depth-1 lockstep Sebulba is BITWISE-identical to the Anakin host loop
  at the same seed/config — params AND per-iteration metrics — on a
  clean config; a ramped-severity scenario run keeps the env trajectory
  bitwise while reward-derived metrics sit within ~1 ulp (Anakin's
  single program fuses intermediates Sebulba materializes at the
  rollout/update program boundary — docs/sebulba.md, parity modes);
- each slice program compiles exactly once (budget-1 receipts on
  ``actor_guard`` / ``learner_guard``) and the base class's Anakin
  program NEVER compiles (its RetraceGuard stays 0);
- Anakin's dispatch surfaces and Anakin-only constructor options are
  fenced off with actionable errors;
- pipelined ``train()`` checkpoints at chunk boundaries and a fresh
  driver on the same log_dir resumes the counters exactly;
- the continuous-falsifier lane attacks the live checkpoint stream and
  its ``from_falsifiers`` feedback schedule lands through
  ``request_scenario_schedule`` with ZERO train-program recompiles;
- the three chaos seams degrade instead of corrupting: an enqueue drop
  is a seq GAP (never a duplicate), a dequeue redelivery is absorbed by
  the seq guard (no trajectory consumed twice), a dropped publish keeps
  actors on the previous params version (latest wins, versions never
  regress).
"""

import jax
import numpy as np
import pytest

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.chaos import (
    FaultPlane,
    FaultSchedule,
    FaultSpec,
    check_no_duplicate_consume,
    check_params_version_monotone,
    set_fault_plane,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.scenarios import (
    AdversaryConfig,
    ContinuousAdversary,
    ScenarioSchedule,
    ScenarioStage,
    from_falsifiers,
)
from marl_distributedformation_tpu.train import TrainConfig, Trainer
from marl_distributedformation_tpu.train.sebulba import (
    ParamBus,
    SebulbaDriver,
    TransferQueue,
)
from marl_distributedformation_tpu.utils import latest_checkpoint

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)
ENV = EnvParams(num_agents=3, max_steps=20)


@pytest.fixture
def plane():
    """A test-private FaultPlane installed as the process-global one;
    the shipped default (disabled) is restored afterwards."""
    fresh = FaultPlane(enabled=True)
    previous = set_fault_plane(fresh)
    yield fresh
    set_fault_plane(previous)


def _config(tmp_path, **overrides):
    defaults = dict(
        num_formations=4,
        checkpoint=False,
        seed=0,
        name="sebulba",
        log_dir=str(tmp_path / "logs"),
        log_interval=1,
    )
    defaults.update(overrides)
    return TrainConfig(**defaults)


def make_anakin(tmp_path, scenario=None, **overrides):
    return Trainer(
        ENV,
        ppo=PPO,
        config=_config(tmp_path, name="anakin", **overrides),
        scenario_schedule=scenario,
    )


def make_sebulba(tmp_path, scenario=None, **overrides):
    return SebulbaDriver(
        ENV,
        ppo=PPO,
        config=_config(tmp_path, architecture="sebulba", **overrides),
        scenario_schedule=scenario,
    )


def two_stage_schedule():
    """Severity ramp + scenario-mix change (the fused-scan tests' shape)."""
    return ScenarioSchedule(
        stages=(
            ScenarioStage(rollouts=2, scenarios=("wind",), severity=0.8),
            ScenarioStage(
                rollouts=2, scenarios=("wind", "sensor_noise"), severity=0.3
            ),
        )
    )


def clean_schedule():
    """The scenarios=['clean'] seam reservation (trainer.py's spelling)."""
    return ScenarioSchedule(
        stages=(
            ScenarioStage(
                rollouts=1,
                scenarios=("clean",),
                severity=0.0,
                severity_start=0.0,
            ),
        )
    )


def _param_leaves(trainer):
    return [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(
            jax.device_get(trainer.train_state.params)
        )
    ]


# ---------------------------------------------------------------------------
# Lockstep parity: Sebulba == Anakin (the acceptance pin)
# ---------------------------------------------------------------------------


def test_lockstep_bitwise_matches_anakin_host_loop(tmp_path):
    """Depth-1 lockstep drives the REAL transfer plumbing (queue seq
    stamps, bus versions) yet reproduces Anakin's host loop bit for bit:
    same key threading, same op sequence, cut across two programs."""
    anakin = make_anakin(tmp_path / "anakin")
    sebulba = make_sebulba(tmp_path / "sebulba")
    for i in range(3):
        a = jax.device_get(anakin.run_iteration())
        s = jax.device_get(sebulba.run_lockstep_iteration())
        assert set(a) == set(s)
        for name in a:
            np.testing.assert_array_equal(
                np.asarray(s[name]),
                np.asarray(a[name]),
                err_msg=f"metric {name!r} diverges at iteration {i}",
            )
    assert anakin.num_timesteps == sebulba.num_timesteps
    for a, s in zip(_param_leaves(anakin), _param_leaves(sebulba)):
        np.testing.assert_array_equal(a, s)
    # The plumbing really ran: three enqueues, three consumes, three
    # publishes past the initial version 0.
    assert list(sebulba.transfer_queue.consumed_seqs) == [0, 1, 2]
    assert sebulba.param_bus.version == 3
    assert sebulba.consumed_versions == [0, 1, 2]


def test_lockstep_scenario_run_first_rollout_bitwise_rest_tight(tmp_path):
    """Ramped-severity scenario parity: the FIRST rollout (identical
    initial params) keeps the env trajectory bitwise — the rollout
    program is the same computation — and divergence enters only
    through the first update's reward-derived path (~1 ulp: Anakin's
    single fused program keeps intermediates Sebulba materializes at
    its program boundary). From iteration 2 on that ulp rides the
    params into actions, so the whole run — env trajectory, metrics,
    params — is pinned at tight tolerance instead (docs/sebulba.md,
    parity modes)."""
    anakin = make_anakin(tmp_path / "anakin", scenario=two_stage_schedule())
    sebulba = make_sebulba(
        tmp_path / "sebulba", scenario=two_stage_schedule()
    )
    for i in range(4):
        a = jax.device_get(anakin.run_iteration())
        s = jax.device_get(sebulba.run_lockstep_iteration())
        env_cmp = (
            np.testing.assert_array_equal
            if i == 0
            else lambda x, y, err_msg="": np.testing.assert_allclose(
                x, y, rtol=1e-4, atol=1e-4, err_msg=err_msg
            )
        )
        for ea, es in zip(
            jax.tree_util.tree_leaves(jax.device_get(anakin.env_state)),
            jax.tree_util.tree_leaves(jax.device_get(sebulba.env_state)),
        ):
            env_cmp(
                np.asarray(ea),
                np.asarray(es),
                err_msg=f"env trajectory diverges at iteration {i}",
            )
        env_cmp(
            np.asarray(jax.device_get(anakin.obs)),
            np.asarray(jax.device_get(sebulba.obs)),
        )
        for name in a:
            np.testing.assert_allclose(
                np.asarray(s[name]),
                np.asarray(a[name]),
                rtol=1e-4,
                atol=1e-5,
                err_msg=f"metric {name!r} diverges at iteration {i}",
            )
    assert anakin._scenario_rollouts == sebulba._scenario_rollouts == 4
    for a, s in zip(_param_leaves(anakin), _param_leaves(sebulba)):
        np.testing.assert_allclose(a, s, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Budget-1 receipts per slice; Anakin surfaces fenced off
# ---------------------------------------------------------------------------


def test_each_slice_program_compiles_exactly_once(tmp_path):
    sebulba = make_sebulba(tmp_path)
    for _ in range(4):
        sebulba.run_lockstep_iteration()
    assert sebulba.actor_guard.count == 1
    assert sebulba.learner_guard.count == 1
    # The base class's fused Anakin program was never dispatched.
    assert sebulba.retrace_guard.count == 0


def test_anakin_dispatch_surfaces_and_options_are_fenced(tmp_path):
    sebulba = make_sebulba(tmp_path)
    with pytest.raises(SystemExit, match="run_lockstep_iteration"):
        sebulba.run_iteration()
    with pytest.raises(SystemExit, match="drain width"):
        sebulba.run_chunk()
    with pytest.raises(SystemExit, match="recovery"):
        make_sebulba(tmp_path / "rec", recovery=True)


# ---------------------------------------------------------------------------
# Pipelined train(): checkpoint at chunk boundaries, exact resume
# ---------------------------------------------------------------------------


def test_pipelined_train_checkpoints_and_resumes_exactly(tmp_path):
    per_iter = PPO.n_steps * 4 * ENV.num_agents  # n_steps * M * agents
    first = make_sebulba(
        tmp_path,
        checkpoint=True,
        save_freq=8,
        fused_chunk=2,
        total_timesteps=6 * per_iter,
    )
    record = first.train()
    assert record, "pipelined train produced no metrics record"
    assert first.num_timesteps >= 6 * per_iter
    assert latest_checkpoint(first.log_dir) is not None
    # Chunked consume: every consumed seq strictly increasing, every
    # consumed params version monotone (the campaign invariants hold on
    # a clean run too).
    assert not check_no_duplicate_consume(
        list(first.transfer_queue.consumed_seqs)
    )
    assert not check_params_version_monotone(first.consumed_versions)
    assert first.actor_guard.count == 1
    assert first.learner_guard.count == 1

    resumed = make_sebulba(
        tmp_path,
        checkpoint=True,
        resume=True,
        save_freq=8,
        fused_chunk=2,
        total_timesteps=6 * per_iter,
    )
    assert resumed.num_timesteps == first.num_timesteps
    for a, b in zip(_param_leaves(first), _param_leaves(resumed)):
        np.testing.assert_array_equal(a, b)
    # The resumed driver's bus serves the RESUMED params as version 0.
    version, params = resumed.param_bus.latest()
    assert version == 0
    before = resumed.num_timesteps
    assert resumed.run_lockstep_iteration()
    assert resumed.num_timesteps == before + per_iter


# ---------------------------------------------------------------------------
# Continuous falsifier lane -> curriculum feedback, zero recompiles
# ---------------------------------------------------------------------------


def test_continuous_adversary_feeds_schedule_with_zero_recompiles(tmp_path):
    """The train -> falsify -> train loop against a live sebulba run:
    the lane attacks the newest checkpoint, pushes a ``from_falsifiers``
    stage through ``request_scenario_schedule``, and the next actor
    dispatch trains the new mix WITHOUT recompiling either slice
    (severity and knobs are traced inputs; the spec-union sampler is the
    only thing rebuilt)."""
    sebulba = make_sebulba(tmp_path, scenario=clean_schedule())
    sebulba.run_lockstep_iteration()
    sebulba.run_lockstep_iteration()
    assert sebulba.actor_guard.count == 1
    assert sebulba.save() is not None

    pushed = []

    def on_schedule(schedule):
        pushed.append(schedule)
        sebulba.request_scenario_schedule(schedule)

    lane = ContinuousAdversary(
        sebulba.log_dir,
        ENV,
        config=AdversaryConfig(
            scenarios=("wind",),
            grid=3,
            generations=3,
            num_formations=4,
            drop_tolerance=0.02,
            resolution=0.001,
        ),
        on_schedule=on_schedule,
        feedback_rollouts=4,
    )
    report = lane.poll_once()
    assert report is not None, "the lane missed the live checkpoint"
    assert not lane.errors
    assert report["falsifiers"], (
        "an untrained policy must break under wind"
    )
    assert pushed, "falsifiers found but no feedback schedule pushed"
    assert lane.summary()["adversary_schedules_pushed"] == 1
    # Nothing re-attacked until a NEWER checkpoint lands.
    assert lane.poll_once() is None

    # Not applied yet: the training thread owns schedule state.
    assert sebulba._scenario_schedule.names == ("clean",)
    sebulba.run_lockstep_iteration()
    assert "adv:wind" in sebulba._scenario_schedule.names
    sebulba.run_lockstep_iteration()
    assert sebulba.actor_guard.count == 1, (
        "a curriculum swap must never recompile the actor program"
    )
    assert sebulba.learner_guard.count == 1, (
        "a curriculum swap must never recompile the learner program"
    )


def test_schedule_feedback_without_scenario_seam_fails_fast(tmp_path):
    sebulba = make_sebulba(tmp_path)
    with pytest.raises(ValueError, match="scenarios=\\['clean'\\]"):
        sebulba.request_scenario_schedule(
            from_falsifiers(
                [{"scenario": "wind", "severity": 0.5}], rollouts=2
            )
        )


# ---------------------------------------------------------------------------
# Chaos seams: drop / duplicate / stale degrade, never corrupt
# ---------------------------------------------------------------------------


def test_enqueue_drop_is_a_seq_gap_never_a_duplicate(plane):
    queue = TransferQueue(depth=2)
    plane.arm(FaultSchedule([FaultSpec("sebulba.enqueue", "raise", 1)]))
    assert queue.put({"x": 1}, params_version=0) is None
    assert queue.dropped_total == 1
    assert queue.put({"x": 2}, params_version=0) == 1  # seq 0 was spent
    item = queue.get(timeout_s=1.0)
    assert item.seq == 1
    assert list(queue.consumed_seqs) == [1]
    # A gap is fine; a duplicate would be a violation.
    assert not check_no_duplicate_consume(list(queue.consumed_seqs))


def test_dequeue_redelivery_absorbed_by_seq_guard(plane):
    queue = TransferQueue(depth=4)
    plane.arm(FaultSchedule([FaultSpec("sebulba.dequeue", "raise", 1)]))
    queue.put({"x": 1}, params_version=0)
    queue.put({"x": 2}, params_version=0)
    first = queue.get(timeout_s=1.0)  # delivered AND re-queued at head
    assert first.seq == 0
    second = queue.get(timeout_s=1.0)  # replay absorbed, next delivered
    assert second.seq == 1
    assert queue.duplicates_absorbed == 1
    assert list(queue.consumed_seqs) == [0, 1]
    assert not check_no_duplicate_consume(list(queue.consumed_seqs))


def test_dropped_publish_keeps_previous_version_latest_wins(plane):
    # Arm before ANY publish: the seam's hit counter ticks whenever the
    # plane is enabled, armed or not.
    plane.arm(
        FaultSchedule([FaultSpec("sebulba.param_publish", "raise", 2)])
    )
    bus = ParamBus()
    assert bus.publish({"w": 0.0}, 0)  # hit 1: clean
    assert not bus.publish({"w": 1.0}, 1)  # hit 2: dropped
    assert bus.publishes_dropped == 1
    version, params = bus.latest()
    assert version == 0 and params == {"w": 0.0}
    assert bus.publish({"w": 2.0}, 2)  # next version lands
    assert bus.version == 2
    # Latest wins: a regressed version can never take the slot.
    assert not bus.publish({"w": 1.0}, 1)
    assert bus.version == 2
    assert not check_params_version_monotone(bus.versions_published)


def test_lockstep_enqueue_drop_is_a_skipped_update(plane, tmp_path):
    """Under an armed drop the rollout happened but nothing was learned:
    lockstep returns an empty dict, the timestep counter advances by the
    ROLLOUT, and the next iteration learns normally off the next seq."""
    sebulba = make_sebulba(tmp_path)
    per_iter = PPO.n_steps * 4 * ENV.num_agents
    plane.arm(FaultSchedule([FaultSpec("sebulba.enqueue", "raise", 1)]))
    assert sebulba.run_lockstep_iteration() == {}
    assert sebulba.num_timesteps == per_iter
    assert sebulba.transfer_queue.dropped_total == 1
    assert sebulba.consumed_versions == []
    metrics = sebulba.run_lockstep_iteration()
    assert metrics
    assert list(sebulba.transfer_queue.consumed_seqs) == [1]
    assert sebulba.consumed_versions == [0]
