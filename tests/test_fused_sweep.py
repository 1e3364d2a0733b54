"""Population-scale Anakin: fused-scan sweeps (ISSUE 6 acceptance).

The contract: a ``fused_chunk`` population sweep is BITWISE-identical to
the host-loop sweep at the same seed/config — params AND every
per-member per-iteration metric — for the plain seed sweep, the
lr-hyperparameter sweep, and the hetero curriculum sweep (including
chunks clipped at a stage change); the fused program compiles exactly
once per config (budget-1 RetraceGuard); resume from a chunk-boundary
``sweep_state`` matches an uninterrupted run bit-exactly; the async
population checkpoint writes the same bytes the synchronous save would;
and ``profile=true`` composes with fused mode (trace captured, zero
extra compiles) instead of fail-fasting.
"""

import json
import pathlib

import jax
import numpy as np
import pytest

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.train import (
    Curriculum,
    CurriculumStage,
    HeteroSweepTrainer,
    SweepTrainer,
    TrainConfig,
)
from marl_distributedformation_tpu.utils import AsyncCheckpointWriter

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)
HPPO = PPOConfig(n_steps=4, batch_size=16, n_epochs=2)
CURR = Curriculum(
    stages=(
        CurriculumStage(rollouts=2, agent_counts=(3,)),
        CurriculumStage(rollouts=3, agent_counts=(3, 5), num_obstacles=1),
    )
)
PER_ITER = PPO.n_steps * 4 * 3  # n_steps * M * N agent-transitions


def make_sweep(log_dir, **overrides):
    defaults = dict(
        num_formations=4,
        seed=0,
        checkpoint=False,
        name="fsweep",
        log_dir=str(log_dir),
    )
    lrs = overrides.pop("learning_rates", None)
    num_seeds = overrides.pop("num_seeds", 2)
    defaults.update(overrides)
    return SweepTrainer(
        EnvParams(num_agents=3),
        ppo=PPO,
        config=TrainConfig(**defaults),
        num_seeds=num_seeds,
        learning_rates=lrs,
    )


def make_hetero(log_dir, **overrides):
    defaults = dict(
        num_formations=4,
        seed=0,
        checkpoint=False,
        name="hfsweep",
        log_dir=str(log_dir),
    )
    defaults.update(overrides)
    return HeteroSweepTrainer(
        curriculum=CURR,
        env_params=EnvParams(num_agents=3),
        ppo=HPPO,
        config=TrainConfig(**defaults),
        num_seeds=2,
    )


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Bitwise parity: fused population scan == host-loop sweep
# ---------------------------------------------------------------------------


def test_fused_sweep_bitwise_matches_host_loop(tmp_path):
    """Two fused chunks of 2 == four host-loop sweep iterations: params
    and every per-member per-iteration metric, bit for bit."""
    host = make_sweep(tmp_path / "host")
    fused = make_sweep(tmp_path / "fused", fused_chunk=2)
    per_iter = [jax.device_get(host.run_iteration()) for _ in range(4)]
    for chunk in range(2):
        stacked = jax.device_get(fused.run_chunk())
        for name, values in stacked.items():
            for i in range(2):
                np.testing.assert_array_equal(
                    np.asarray(values[i]),
                    np.asarray(per_iter[2 * chunk + i][name]),
                    err_msg=(
                        f"metric {name!r} diverges at chunk {chunk} "
                        f"iteration {i}"
                    ),
                )
    assert host.num_timesteps == fused.num_timesteps
    _leaves_equal(host.train_state.params, fused.train_state.params)
    _leaves_equal(host.key, fused.key)


def test_fused_lr_sweep_bitwise_matches_host_loop(tmp_path):
    """Per-member injected learning rates ride the scan carry (optimizer
    STATE) — the lr sweep fuses bitwise too."""
    lrs = [1e-3, 3e-3]
    host = make_sweep(tmp_path / "host", learning_rates=lrs)
    fused = make_sweep(
        tmp_path / "fused", learning_rates=lrs, fused_chunk=2
    )
    for _ in range(2):
        host.run_iteration()
    fused.run_chunk()
    _leaves_equal(host.train_state.params, fused.train_state.params)
    _leaves_equal(host.train_state.opt_state, fused.train_state.opt_state)


def test_fused_sweep_compiles_exactly_once_across_chunks(tmp_path):
    """Three chunks = ONE compile of the fused population program
    (guard_retraces=1 would raise on a retrace)."""
    fused = make_sweep(tmp_path, fused_chunk=2, guard_retraces=1)
    for _ in range(3):
        fused.run_chunk()
    assert fused.retrace_guard.count == 1


def test_run_iteration_refuses_fused_mode(tmp_path):
    fused = make_sweep(tmp_path / "f", fused_chunk=2)
    with pytest.raises(AssertionError, match="run_chunk"):
        fused.run_iteration()
    host = make_sweep(tmp_path / "h")
    with pytest.raises(AssertionError, match="fused_chunk"):
        host.run_chunk()


# ---------------------------------------------------------------------------
# End-to-end: train() with async population checkpoints + resume
# ---------------------------------------------------------------------------


def test_fused_sweep_train_end_to_end_and_resume(tmp_path):
    """4 iterations in 2 fused chunks: per-iteration aggregate records
    land in metrics.jsonl at host-loop step stamps, the background
    writer lands per-member checkpoints + the sweep_state anchor at the
    chunk boundary, and a resume from that boundary ends bit-identical
    to an uninterrupted run (the chunk-aware resume cadence: chunk
    boundary == bit-exact resume boundary)."""
    kw = dict(checkpoint=True, save_freq=10**9, fused_chunk=2)

    full = make_sweep(
        tmp_path / "full", total_timesteps=4 * PER_ITER, **kw
    )
    record = full.train()
    assert full.num_timesteps == 4 * PER_ITER
    assert np.isfinite(record["loss"])
    assert "reward_best" in record and "best_seed" in record
    assert full.retrace_guard.count == 1
    records = [
        json.loads(line)
        for line in (tmp_path / "full" / "metrics.jsonl")
        .read_text()
        .splitlines()
    ]
    assert [r["step"] for r in records] == [
        PER_ITER, 2 * PER_ITER, 3 * PER_ITER, 4 * PER_ITER,
    ]
    # The async writer landed the full artifact set: member checkpoints
    # discoverable by the standard tooling + the population anchor.
    for i in range(2):
        assert list(
            (tmp_path / "full" / f"seed{i}").glob("rl_model_*_steps.msgpack")
        )
    assert (
        tmp_path / "full" / f"sweep_state_{4 * PER_ITER}_steps.msgpack"
    ).exists()
    summary = json.loads(
        (tmp_path / "full" / "sweep_summary.json").read_text()
    )
    assert len(summary["final_reward"]) == 2

    half = make_sweep(
        tmp_path / "part", total_timesteps=2 * PER_ITER, **kw
    )
    half.train()
    resumed = make_sweep(
        tmp_path / "part", total_timesteps=4 * PER_ITER, resume=True, **kw
    )
    assert resumed.num_timesteps == 2 * PER_ITER
    resumed.train()
    for getter in (
        lambda t: t.train_state.params,
        lambda t: t.train_state.opt_state,
        lambda t: t.key,
        lambda t: t.env_state,
        lambda t: t.obs,
    ):
        _leaves_equal(getter(resumed), getter(full))
    s_res = json.loads(
        (tmp_path / "part" / "sweep_summary.json").read_text()
    )
    assert s_res["best_seed"] == summary["best_seed"]
    np.testing.assert_array_equal(
        s_res["final_reward"], summary["final_reward"]
    )


def test_fused_sweep_async_save_matches_sync_save_bytes(tmp_path):
    """save_async writes byte-identical files to the synchronous save —
    member checkpoints AND the sweep_state anchor (the device snapshot +
    writer thread change WHEN the bytes are produced, never WHAT)."""
    a = make_sweep(tmp_path / "a", fused_chunk=2, checkpoint=True)
    b = make_sweep(tmp_path / "b", fused_chunk=2, checkpoint=True)
    a.run_chunk()
    b.run_chunk()
    a.save()
    writer = AsyncCheckpointWriter()
    b.save_async(writer)
    writer.close()
    names = [
        f"sweep_state_{a.num_timesteps}_steps.msgpack",
        f"seed0/rl_model_{a.num_timesteps}_steps.msgpack",
        f"seed1/rl_model_{a.num_timesteps}_steps.msgpack",
    ]
    for name in names:
        sync_bytes = (pathlib.Path(a.log_dir) / name).read_bytes()
        async_bytes = (pathlib.Path(b.log_dir) / name).read_bytes()
        assert sync_bytes == async_bytes, f"{name} drifted sync vs async"


# ---------------------------------------------------------------------------
# Hetero curriculum sweep: fused chunks clip at stage boundaries
# ---------------------------------------------------------------------------


def test_hetero_fused_matches_host_loop_across_stage_change(tmp_path):
    """The 2+3-rollout curriculum under chunk=2 dispatches chunks
    [2][2][1] — a stage change between chunks AND a clipped tail inside
    stage 2. Params, member counters, and the curriculum cursor must
    match the host loop bitwise; the clipped tail costs exactly one
    extra compile (2 distinct scan lengths -> 2 compiles, ever)."""
    host = make_hetero(tmp_path / "host")
    fused = make_hetero(tmp_path / "fused", fused_chunk=2)
    host.train()
    fused.train()
    assert host.completed_rollouts == fused.completed_rollouts == 5
    _leaves_equal(host.train_state.params, fused.train_state.params)
    _leaves_equal(host.key, fused.key)
    np.testing.assert_array_equal(
        host.num_timesteps_members, fused.num_timesteps_members
    )
    assert fused.retrace_guard.count == 2, (
        "chunk lengths {2, 1} must compile once each, never per dispatch"
    )


def test_hetero_fused_resume_from_chunk_boundary(tmp_path):
    """An interrupted fused curriculum block resumed from its
    chunk-boundary sweep_state ends bit-identical to an uninterrupted
    fused run — including a boundary that is also a STAGE boundary (the
    checkpoint must hold the pre-reset key so resume replays the stage
    reset exactly once)."""
    kw = dict(checkpoint=True, save_freq=10**9, fused_chunk=2)
    per_iter_max = HPPO.n_steps * 4 * 3

    full = make_hetero(tmp_path / "full", **kw)
    full.train()

    part = make_hetero(
        tmp_path / "part", total_timesteps=2 * per_iter_max, **kw
    )
    part.train()  # cap lands at rollout 2 == the stage-0/1 boundary
    assert part.completed_rollouts == 2

    resumed = make_hetero(tmp_path / "part", resume=True, **kw)
    assert resumed.completed_rollouts == 2
    resumed.train()
    assert resumed.completed_rollouts == full.completed_rollouts
    for getter in (
        lambda t: t.train_state.params,
        lambda t: t.train_state.opt_state,
        lambda t: t.key,
        lambda t: t.env_state,
        lambda t: t.obs,
    ):
        _leaves_equal(getter(resumed), getter(full))
    np.testing.assert_array_equal(
        resumed.num_timesteps_members, full.num_timesteps_members
    )


# ---------------------------------------------------------------------------
# profile=true composes with fused sweeps (trace captured, no retrace)
# ---------------------------------------------------------------------------


def test_profile_composes_with_fused_sweep(tmp_path):
    """profile=true on a fused sweep captures a chunk-granular trace
    (files land under {log_dir}/profile/) with ZERO extra compiles —
    the combination used to fail-fast."""
    sweep = make_sweep(
        tmp_path,
        fused_chunk=2,
        total_timesteps=4 * PER_ITER,
        profile=True,
        profile_iterations=1,
        guard_retraces=1,
    )
    sweep.train()
    trace_files = list((tmp_path / "profile").rglob("*"))
    assert any(p.is_file() for p in trace_files), (
        f"no profiler trace captured under {tmp_path / 'profile'}"
    )
    assert sweep.retrace_guard.count == 1, (
        "tracing must not retrace the fused program"
    )
