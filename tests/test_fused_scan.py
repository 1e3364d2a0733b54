"""Anakin-mode fused-scan training (TrainConfig.fused_chunk).

The contract (ISSUE 5 acceptance): K fused-scan iterations are
BITWISE-identical to K host-loop iterations at the same seed/config —
params AND per-iteration metrics — for the plain trainer, a
scenario-schedule trainer (stage change INSIDE the chunk), and the
dp-mesh trainer; the fused program compiles exactly once (budget-1
RetraceGuard); and the background checkpoint pipeline can never leave a
torn or visible half-checkpoint, even when a write crashes mid-flight.
"""

import json
import pathlib

import jax
import numpy as np
import pytest

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.scenarios.schedule import (
    ScenarioSchedule,
    ScenarioStage,
)
from marl_distributedformation_tpu.train import TrainConfig, Trainer
from marl_distributedformation_tpu.utils import (
    AsyncCheckpointWriter,
    checkpoint_path,
    latest_checkpoint,
)

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)


def make_trainer(tmp_path, scenario=None, shard_fn=None, **overrides):
    defaults = dict(
        num_formations=4,
        checkpoint=False,
        seed=0,
        name="fused",
        log_dir=str(tmp_path / "logs"),
        log_interval=1,
    )
    defaults.update(overrides)
    return Trainer(
        EnvParams(num_agents=3),
        ppo=PPO,
        config=TrainConfig(**defaults),
        shard_fn=shard_fn,
        scenario_schedule=scenario,
    )


def two_stage_schedule():
    """Severity ramp + scenario-mix change that land INSIDE a chunk of 4."""
    return ScenarioSchedule(
        stages=(
            ScenarioStage(rollouts=2, scenarios=("wind",), severity=0.8),
            ScenarioStage(
                rollouts=2, scenarios=("wind", "sensor_noise"), severity=0.3
            ),
        )
    )


def assert_bitwise_parity(host, fused, k):
    """Run k host-loop iterations vs ONE fused chunk of k; params and
    every per-iteration metric must match bit for bit."""
    per_iter = [jax.device_get(host.run_iteration()) for _ in range(k)]
    stacked = jax.device_get(fused.run_chunk())
    assert host.num_timesteps == fused.num_timesteps
    for name, values in stacked.items():
        for i in range(k):
            np.testing.assert_array_equal(
                np.asarray(values[i]),
                np.asarray(per_iter[i][name]),
                err_msg=f"metric {name!r} diverges at fused iteration {i}",
            )
    for a, b in zip(
        jax.tree_util.tree_leaves(host.train_state.params),
        jax.tree_util.tree_leaves(fused.train_state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Bitwise parity: fused scan == host loop (the acceptance pin)
# ---------------------------------------------------------------------------


def test_fused_scan_bitwise_matches_host_loop_plain(tmp_path):
    host = make_trainer(tmp_path / "host")
    fused = make_trainer(tmp_path / "fused", fused_chunk=3)
    assert_bitwise_parity(host, fused, 3)


def test_fused_scan_bitwise_matches_host_loop_scenario_schedule(tmp_path):
    """The chunk's scanned ScenarioParams xs reproduce the host loop's
    per-dispatch draws exactly — including a stage transition and a
    severity-ramp step in the MIDDLE of the fused chunk."""
    host = make_trainer(tmp_path / "host", scenario=two_stage_schedule())
    fused = make_trainer(
        tmp_path / "fused", scenario=two_stage_schedule(), fused_chunk=4
    )
    assert_bitwise_parity(host, fused, 4)
    assert host._scenario_rollouts == fused._scenario_rollouts == 4


def test_fused_chunk_of_one_with_scenarios_matches_host_loop(tmp_path):
    """The degenerate K=1 chunk still takes scenario xs with a leading
    (1,) axis (a length-1 scan is NOT the unscanned program) — the edge
    the rollouts>1 gate used to miss."""
    host = make_trainer(tmp_path / "host", scenario=two_stage_schedule())
    fused = make_trainer(
        tmp_path / "fused", scenario=two_stage_schedule(), fused_chunk=1
    )
    assert_bitwise_parity(host, fused, 1)


def test_fused_scan_bitwise_matches_host_loop_dp_mesh(tmp_path):
    from marl_distributedformation_tpu.parallel import make_shard_fn

    host = make_trainer(tmp_path / "host", shard_fn=make_shard_fn({"dp": 4}))
    fused = make_trainer(
        tmp_path / "fused", shard_fn=make_shard_fn({"dp": 4}), fused_chunk=2
    )
    assert_bitwise_parity(host, fused, 2)


# ---------------------------------------------------------------------------
# Compile-once (budget-1 RetraceGuard)
# ---------------------------------------------------------------------------


def test_fused_program_compiles_exactly_once_across_chunks_and_stages(
    tmp_path,
):
    """Three chunks crossing a scenario stage change + severity ramp =
    ONE compile of the fused program (guard_retraces=1 would raise on
    the retrace; the count is the receipt the benchmark's
    ``compiles_in_window`` reads)."""
    trainer = make_trainer(
        tmp_path, scenario=two_stage_schedule(), fused_chunk=2,
        guard_retraces=1,
    )
    for _ in range(3):
        trainer.run_chunk()
    assert trainer.retrace_guard.count == 1, (
        "the fused-scan program must compile exactly once per config"
    )


def test_run_iteration_refuses_fused_mode(tmp_path):
    trainer = make_trainer(tmp_path, fused_chunk=2)
    with pytest.raises(AssertionError, match="run_chunk"):
        trainer.run_iteration()
    host = make_trainer(tmp_path / "h")
    with pytest.raises(AssertionError, match="fused_chunk"):
        host.run_chunk()


# ---------------------------------------------------------------------------
# End-to-end: train() with double-buffered drain + async checkpoints
# ---------------------------------------------------------------------------


def test_fused_train_end_to_end_and_resume(tmp_path):
    """4 iterations in 2 fused chunks: per-iteration metrics records land
    in metrics.jsonl (same cadence as the host loop), the background
    writer produces discoverable checkpoints at chunk boundaries, and
    resume restores exactly — including re-entering the scenario
    schedule mid-ramp."""
    total = 4 * 3 * 4 * 4  # 4 iterations of M=4 x N=3 x n_steps=4

    def fused(**kw):
        return make_trainer(
            tmp_path,
            scenario=two_stage_schedule(),
            checkpoint=True,
            save_freq=8,
            total_timesteps=total,
            fused_chunk=2,
            guard_retraces=1,
            **kw,
        )

    trainer = fused()
    final = trainer.train()
    assert trainer.num_timesteps == total
    assert np.isfinite(final["loss"])
    assert trainer.retrace_guard.count == 1
    records = [
        json.loads(line)
        for line in (tmp_path / "logs" / "metrics.jsonl")
        .read_text()
        .splitlines()
    ]
    # Per-iteration records despite 2-iteration chunks, at host-loop
    # step stamps, each carrying its OWN schedule point's severity.
    assert [r["step"] for r in records] == [48, 96, 144, 192]
    sched = two_stage_schedule()
    np.testing.assert_allclose(
        [r["scenario_severity"] for r in records],
        [sched.severity_at(i) for i in range(4)],
    )
    path = latest_checkpoint(tmp_path / "logs")
    assert path is not None and "rl_model_192" in path.name

    resumed = fused(resume=True)
    assert resumed.num_timesteps == total
    assert resumed._scenario_rollouts == 4  # mid-schedule re-entry
    for a, b in zip(
        jax.tree_util.tree_leaves(trainer.train_state.params),
        jax.tree_util.tree_leaves(resumed.train_state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_matches_sync_save_bytes(tmp_path):
    """save_async writes the same checkpoint the synchronous save would
    (device snapshot + writer thread change WHEN the bytes are produced,
    never WHAT they contain)."""
    a = make_trainer(tmp_path / "a", fused_chunk=2)
    b = make_trainer(tmp_path / "b", fused_chunk=2)
    a.run_chunk()
    b.run_chunk()
    sync_path = a.save()
    writer = AsyncCheckpointWriter()
    async_path = b.save_async(writer)
    writer.close()
    assert (
        pathlib.Path(sync_path).read_bytes()
        == pathlib.Path(async_path).read_bytes()
    )


# ---------------------------------------------------------------------------
# Async checkpoint pipeline: crash-safety + error surfacing
# ---------------------------------------------------------------------------


def test_async_writer_crash_mid_write_leaves_nothing_visible(
    tmp_path, monkeypatch
):
    """A persistent IO failure between the tmp write and the atomic
    rename (the worst possible moment) leaves no discoverable
    checkpoint — the dot-prefixed .tmp is invisible to
    latest_checkpoint (the _write_atomic invariant, now load-bearing
    from a background thread) — and, since the chaos hardening
    (docs/chaos.md), is retried then SKIPPED with audit instead of
    killing the training run: close() does not raise, the skip is
    counted, and the next write lands normally."""
    real_replace = pathlib.Path.replace

    def exploding_replace(self, target):
        if str(target).endswith(".msgpack"):
            raise OSError("disk gone mid-rename")
        return real_replace(self, target)

    monkeypatch.setattr(pathlib.Path, "replace", exploding_replace)
    writer = AsyncCheckpointWriter(io_retries=1, io_backoff_s=0.001)
    writer.submit(
        checkpoint_path(tmp_path, 5),
        {"params": np.zeros(3, np.float32), "num_timesteps": 5},
    )
    writer.close()  # degraded, not dead: no surfaced error
    assert writer.writes_skipped == 1
    assert latest_checkpoint(tmp_path) is None, (
        "a torn async write must never be discoverable"
    )
    monkeypatch.undo()
    # The writer recovers: a clean submit after the failure works.
    writer.submit(
        checkpoint_path(tmp_path, 6),
        {"params": np.zeros(3, np.float32), "num_timesteps": 6},
    )
    writer.close()
    assert latest_checkpoint(tmp_path).name == "rl_model_6_steps.msgpack"


def test_async_writer_error_surfaces_on_next_submit(tmp_path, monkeypatch):
    """PROGRAM errors (a serialization bug, a bad snapshot tree) still
    surface on the next submit — only IO weather degrades to
    skip-with-audit (tests/test_chaos.py pins that side)."""
    from marl_distributedformation_tpu.utils import checkpoint as ckpt_mod

    def boom(path, target):
        raise TypeError("unserializable leaf in snapshot tree")

    monkeypatch.setattr(ckpt_mod, "_write_atomic", boom)
    writer = AsyncCheckpointWriter()
    writer.submit(checkpoint_path(tmp_path, 1), {"x": np.zeros(2)})
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="async checkpoint"):
        writer.submit(checkpoint_path(tmp_path, 2), {"x": np.zeros(2)})


def test_async_writer_single_flight_is_ordered(tmp_path):
    """submit joins the previous write first: steps land on disk in
    submit order, so max-step discovery always sees a monotone set."""
    writer = AsyncCheckpointWriter()
    for step in (1, 2, 3):
        writer.submit(
            checkpoint_path(tmp_path, step),
            {"params": np.full(4, step, np.float32), "num_timesteps": step},
        )
    writer.close()
    assert latest_checkpoint(tmp_path).name == "rl_model_3_steps.msgpack"


# ---------------------------------------------------------------------------
# Fail-fasts: where fusion can't compose it must say so
# ---------------------------------------------------------------------------


def test_fused_chunk_fail_fasts(tmp_path):
    """The remaining non-composing combos stay loud. profile=true and
    the population sweeps COMPOSE now (tests/test_fused_sweep.py and
    test_profile_composes_with_fused_trainer below)."""
    from marl_distributedformation_tpu.train import HeteroTrainer

    with pytest.raises(SystemExit, match="fused_chunk"):
        # The single-run curriculum trainer keeps its host-driven stage
        # loop (the POPULATION curriculum shell is the one that fuses).
        HeteroTrainer(
            env_params=EnvParams(num_agents=3),
            ppo=PPO,
            config=TrainConfig(
                num_formations=4, name="h", checkpoint=False,
                log_dir=str(tmp_path / "h"), fused_chunk=2,
            ),
        )


def test_profile_composes_with_fused_trainer(tmp_path):
    """profile=true + fused_chunk: chunk-granular trace captured into
    {log_dir}/profile/ with ZERO extra compiles (the combination used
    to fail-fast)."""
    trainer = make_trainer(
        tmp_path,
        fused_chunk=2,
        total_timesteps=4 * 3 * 4 * 4,  # 4 iterations = 2 chunks
        profile=True,
        profile_iterations=1,
        guard_retraces=1,
    )
    trainer.train()
    profile_dir = pathlib.Path(trainer.log_dir) / "profile"
    assert any(p.is_file() for p in profile_dir.rglob("*")), (
        f"no profiler trace captured under {profile_dir}"
    )
    assert trainer.retrace_guard.count == 1, (
        "tracing must not retrace the fused program"
    )
