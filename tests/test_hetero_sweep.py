"""Candidate-seed hetero-curriculum populations (train/hetero_sweep.py)."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.train import (
    Curriculum,
    CurriculumStage,
    HeteroSweepTrainer,
    HeteroTrainer,
    TrainConfig,
)
from marl_distributedformation_tpu.parallel import make_mesh

PPO = PPOConfig(n_steps=4, batch_size=16, n_epochs=2)
CURR = Curriculum(
    stages=(
        CurriculumStage(rollouts=2, agent_counts=(3,)),
        CurriculumStage(rollouts=2, agent_counts=(3, 5), num_obstacles=1),
    )
)


def _cfg(tmp_path, **kw):
    base = dict(
        num_formations=4,
        seed=0,
        checkpoint=False,
        name="hsweep",
        log_dir=str(tmp_path / "logs"),
    )
    base.update(kw)
    return TrainConfig(**base)


def _leaves_allclose(a, b, rtol=1e-5, atol=1e-6):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


def _walk(trainer):
    """Drive the curriculum stage loop manually (both trainer shells
    expose start_stage/run_iteration)."""
    metrics = None
    for stage in trainer.curriculum.stages:
        trainer.start_stage(stage)
        for _ in range(stage.rollouts):
            metrics = trainer.run_iteration()
    return metrics


def test_member_matches_hetero_trainer(tmp_path):
    """Member i of a K=2 candidate population == HeteroTrainer(seed=i)
    through the FULL curriculum — same params, same metrics — so a
    population is exactly K reference single runs, fused."""
    sweep = HeteroSweepTrainer(
        curriculum=CURR,
        env_params=EnvParams(num_agents=3),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=2,
    )
    singles = [
        HeteroTrainer(
            curriculum=CURR,
            env_params=EnvParams(num_agents=3),
            ppo=PPO,
            config=_cfg(tmp_path, seed=i),
        )
        for i in range(2)
    ]
    sweep_metrics = _walk(sweep)
    single_metrics = [_walk(t) for t in singles]
    for i, t in enumerate(singles):
        _leaves_allclose(
            jax.tree_util.tree_map(
                lambda x: x[i], sweep.train_state.params
            ),
            t.train_state.params,
        )
        np.testing.assert_allclose(
            float(sweep_metrics["reward"][i]),
            float(single_metrics[i]["reward"]),
            rtol=1e-5,
        )
        assert (
            int(sweep.num_timesteps_members[i]) == t.num_timesteps
        ), "active-transition accounting diverged from the single run"
    # Distinct candidates actually diverge.
    assert not np.allclose(
        np.asarray(sweep_metrics["reward"][0]),
        np.asarray(sweep_metrics["reward"][1]),
    )


@pytest.mark.slow
def test_member_axis_sharding_matches_unsharded(tmp_path):
    """mesh={dp: 4} shards the candidate axis with no effect beyond fp
    reduction-order noise, gated by the explicit Adam-amplification
    budget (tests/adam_budget.py: ~3e-8 lowering noise amplified to
    O(lr) per optimizer step — see test_sweep's twin gate)."""
    from adam_budget import adam_parity_atol, trajectory_rtol, updates_per_run

    plain = HeteroSweepTrainer(
        curriculum=CURR,
        env_params=EnvParams(num_agents=3),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=4,
    )
    sharded = HeteroSweepTrainer(
        curriculum=CURR,
        env_params=EnvParams(num_agents=3),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=4,
        mesh=make_mesh({"dp": 4}),
    )
    m_plain = _walk(plain)
    m_shard = _walk(sharded)
    # Per-member rows per iteration: n_steps * M * padded-N of the stage
    # (stage 2 pads its (3, 5) mix to N=5).
    updates = sum(
        updates_per_run(
            PPO,
            PPO.n_steps * 4 * max(stage.agent_counts),
            stage.rollouts,
        )
        for stage in CURR.stages
    )
    _leaves_allclose(
        plain.train_state.params,
        sharded.train_state.params,
        rtol=0,
        atol=adam_parity_atol(PPO.learning_rate, updates),
    )
    np.testing.assert_allclose(
        np.asarray(m_plain["reward"]),
        np.asarray(m_shard["reward"]),
        rtol=trajectory_rtol(PPO.learning_rate, updates),
    )


def test_checkpoints_and_summary_follow_sweep_contract(tmp_path):
    """train() lands per-member seed{i}/ checkpoints + sweep_summary.json
    — the artifact layout evaluate.py's member ranking and
    visualize_policy.py's best-member descent already consume."""
    config = _cfg(tmp_path, checkpoint=True, save_freq=4)
    sweep = HeteroSweepTrainer(
        curriculum=CURR,
        env_params=EnvParams(num_agents=3),
        ppo=PPO,
        config=config,
        num_seeds=2,
    )
    sweep.train()
    log_dir = Path(config.log_dir)
    for i in range(2):
        ckpts = list((log_dir / f"seed{i}").glob("rl_model_*_steps.msgpack"))
        assert ckpts, f"no member checkpoints under seed{i}/"
    summary = json.loads((log_dir / "sweep_summary.json").read_text())
    assert summary["seeds"] == [0, 1]
    assert summary["best_dir"] in ("seed0", "seed1")
    assert len(summary["final_reward"]) == 2


def test_resume_bit_exact_mid_stage(tmp_path):
    """An interrupted candidate block resumed from its sweep_state
    checkpoint ends bit-identical to an uninterrupted run — including a
    MID-stage interruption, where the partially-walked stage must NOT be
    resampled on resume."""
    env = EnvParams(num_agents=3)
    # 3 rollouts of stage 1 = the cap lands mid-stage-1 (stage 0 is 2).
    per_iter_max = PPO.n_steps * 4 * 3  # n_steps * M * N upper bound
    kw = dict(checkpoint=True, save_freq=10**9)

    full = HeteroSweepTrainer(
        curriculum=CURR, env_params=env, ppo=PPO, num_seeds=2,
        config=_cfg(tmp_path, name="full",
                    log_dir=str(tmp_path / "full"), **kw),
    )
    full.train()

    part = HeteroSweepTrainer(
        curriculum=CURR, env_params=env, ppo=PPO, num_seeds=2,
        config=_cfg(tmp_path, name="part",
                    log_dir=str(tmp_path / "part"),
                    total_timesteps=3 * per_iter_max, **kw),
    )
    part.train()  # budget cap stops mid-curriculum; final save() lands
    assert 0 < part.completed_rollouts < CURR.total_rollouts
    interrupted_at = part.completed_rollouts

    resumed = HeteroSweepTrainer(
        curriculum=CURR, env_params=env, ppo=PPO, num_seeds=2,
        config=_cfg(tmp_path, name="part",
                    log_dir=str(tmp_path / "part"), resume=True, **kw),
    )
    assert resumed.completed_rollouts == interrupted_at
    resumed.train()

    assert resumed.completed_rollouts == full.completed_rollouts
    for getter in (
        lambda t: t.train_state.params,
        lambda t: t.train_state.opt_state,
        lambda t: t.key,
        lambda t: t.env_state,
        lambda t: t.obs,
    ):
        la = jax.tree_util.tree_leaves(getter(resumed))
        lb = jax.tree_util.tree_leaves(getter(full))
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(
        resumed.num_timesteps_members, full.num_timesteps_members
    )


def test_resume_rejects_identity_mismatch(tmp_path):
    env = EnvParams(num_agents=3)
    kw = dict(checkpoint=True, save_freq=10**9)
    t = HeteroSweepTrainer(
        curriculum=CURR, env_params=env, ppo=PPO, num_seeds=2,
        config=_cfg(tmp_path, name="a", log_dir=str(tmp_path / "a"), **kw),
    )
    t.train()
    with pytest.raises(SystemExit, match="num_seeds"):
        HeteroSweepTrainer(
            curriculum=CURR, env_params=env, ppo=PPO, num_seeds=1,
            config=_cfg(tmp_path, name="a", log_dir=str(tmp_path / "a"),
                        resume=True, **kw),
        )


def test_rejections(tmp_path):
    with pytest.raises(AssertionError, match="divisible"):
        HeteroSweepTrainer(
            curriculum=CURR,
            env_params=EnvParams(num_agents=3),
            ppo=PPO,
            config=_cfg(tmp_path),
            num_seeds=3,
            mesh=make_mesh({"dp": 4}),
        )


def test_cli_dispatch(tmp_path, monkeypatch):
    """train.py routes curriculum + num_seeds>1 to HeteroSweepTrainer and
    rejects the learning_rates combination."""
    import train as train_cli
    from marl_distributedformation_tpu.utils import load_config

    curr = (
        "curriculum=[{rollouts: 2, agent_counts: [3]}, "
        "{rollouts: 2, agent_counts: [3, 5]}]"
    )
    cfg = load_config(
        [
            "name=hsweep_cli", "num_seeds=2", "num_formation=4",
            "num_agents_per_formation=3", "n_steps=4", "batch_size=16",
            "n_epochs=2", "checkpoint=false", curr,
        ]
    )
    trainer = train_cli.build_trainer(cfg)
    assert isinstance(trainer, HeteroSweepTrainer)
    assert trainer.num_seeds == 2
    cfg_bad = load_config(
        [
            "name=x", "num_seeds=2", "learning_rates=[1e-3,1e-4]", curr,
        ]
    )
    with pytest.raises(SystemExit, match="learning_rates"):
        train_cli.build_trainer(cfg_bad)
