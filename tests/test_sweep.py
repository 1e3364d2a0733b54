"""Seed-sweep population training (train/sweep.py).

The load-bearing invariant: sweep member i is bit-compatible with a
single Trainer constructed at seed+i — a sweep IS K reference-parity
runs, fused into one program. Plus: seed-axis mesh sharding changes
nothing numerically, and per-member checkpoints flow through the
standard playback/resume tooling.
"""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from marl_distributedformation_tpu.algo import PPOConfig  # noqa: E402
from marl_distributedformation_tpu.env import EnvParams  # noqa: E402
from marl_distributedformation_tpu.parallel import make_mesh  # noqa: E402
from marl_distributedformation_tpu.train import (  # noqa: E402
    SweepTrainer,
    TrainConfig,
    Trainer,
)

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)


def _cfg(tmp_path, **kw):
    base = dict(
        num_formations=4,
        seed=0,
        checkpoint=False,
        name="sweep",
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


@pytest.mark.slow
def test_member_matches_single_trainer(tmp_path):
    """Member i of a K=2 sweep == Trainer(seed=i), params and metrics."""
    params = EnvParams(num_agents=3)
    sweep = SweepTrainer(
        params, ppo=PPO, config=_cfg(tmp_path), num_seeds=2
    )
    singles = [
        Trainer(params, ppo=PPO, config=_cfg(tmp_path, seed=i))
        for i in range(2)
    ]
    for _ in range(2):
        sweep_metrics = sweep.run_iteration()
        single_metrics = [t.run_iteration() for t in singles]
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
    # Distinct seeds actually diverge.
    assert not np.allclose(
        np.asarray(sweep_metrics["reward"][0]),
        np.asarray(sweep_metrics["reward"][1]),
    )


@pytest.mark.slow
def test_seed_axis_sharding_matches_unsharded(tmp_path):
    """mesh={dp: 4} shards the population with no effect beyond fp
    reduction-order noise.

    Tolerances are the explicit Adam-amplification budget
    (tests/adam_budget.py): the one-device and dp-sharded XLA lowerings
    reduce in different orders (~3e-8 per minibatch gradient), and
    Adam's normalized update amplifies any tie-break to O(lr) per
    optimizer step — a flat rtol can never gate this correctly."""
    from adam_budget import adam_parity_atol, trajectory_rtol, updates_per_run

    params = EnvParams(num_agents=3)
    plain = SweepTrainer(params, ppo=PPO, config=_cfg(tmp_path), num_seeds=4)
    sharded = SweepTrainer(
        params,
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=4,
        mesh=make_mesh({"dp": 4}),
    )
    iterations = 2
    for _ in range(iterations):
        m_plain = plain.run_iteration()
        m_shard = sharded.run_iteration()
    # Per-member rollout rows: n_steps * num_formations * num_agents.
    updates = updates_per_run(PPO, PPO.n_steps * 4 * 3, iterations)
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


def test_sweep_rejects_bad_population_split(tmp_path):
    with pytest.raises(AssertionError, match="divisible"):
        SweepTrainer(
            EnvParams(num_agents=3),
            ppo=PPO,
            config=_cfg(tmp_path),
            num_seeds=3,
            mesh=make_mesh({"dp": 4}),
        )
    with pytest.raises(AssertionError, match="'dp'"):
        SweepTrainer(
            EnvParams(num_agents=3),
            ppo=PPO,
            config=_cfg(tmp_path),
            num_seeds=4,
            mesh=make_mesh({"dp": 2, "sp": 2}),
        )


@pytest.mark.slow
def test_lr_sweep_on_mesh(tmp_path):
    """Per-member rates (inject_hyperparams state) under the seed-axis
    shard_map: the rate array shards with the rest of the population."""
    sweep = SweepTrainer(
        EnvParams(num_agents=3),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=4,
        mesh=make_mesh({"dp": 4}),
        learning_rates=[1e-4, 1e-3, 3e-3, 1e-2],
    )
    metrics = sweep.run_iteration()
    assert np.isfinite(np.asarray(metrics["loss"])).all()


@pytest.mark.slow
def test_knn_sweep_on_mesh(tmp_path):
    """knn observations inside a seed-sharded sweep: the shard_map wrap
    keeps the per-device neighbor search local (the SPMD partitioner never
    sees it), so this must compile and run."""
    sweep = SweepTrainer(
        EnvParams(num_agents=6, obs_mode="knn", knn_k=2),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=4,
        mesh=make_mesh({"dp": 4}),
    )
    metrics = sweep.run_iteration()
    assert np.isfinite(np.asarray(metrics["reward"])).all()


@pytest.mark.slow
def test_lr_sweep_members_train_at_their_own_rate(tmp_path):
    """Per-member learning rates: lr=0 freezes that member, a nonzero-lr
    member matches a single Trainer run at that rate (the inject_hyperparams
    wrapper must be numerically equivalent to plain adam)."""
    import dataclasses

    params = EnvParams(num_agents=3)
    sweep = SweepTrainer(
        params,
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=2,
        learning_rates=[0.0, PPO.learning_rate],
    )
    frozen_before = jax.tree_util.tree_map(
        lambda x: np.asarray(x[0]).copy(), sweep.train_state.params
    )
    sweep.run_iteration()
    _leaves_allclose(
        jax.tree_util.tree_map(lambda x: x[0], sweep.train_state.params),
        frozen_before,
        rtol=0,
        atol=0,
    )

    single = Trainer(params, ppo=PPO, config=_cfg(tmp_path, seed=1))
    single.run_iteration()
    _leaves_allclose(
        jax.tree_util.tree_map(lambda x: x[1], sweep.train_state.params),
        single.train_state.params,
    )

    # Distinct nonzero rates diverge.
    sweep2 = SweepTrainer(
        params,
        ppo=dataclasses.replace(PPO),
        config=_cfg(tmp_path),
        num_seeds=2,
        learning_rates=[1e-4, 1e-2],
    )
    for _ in range(2):
        m = sweep2.run_iteration()
    assert not np.allclose(
        np.asarray(m["loss"][0]), np.asarray(m["loss"][1])
    )

    with pytest.raises(AssertionError, match="one entry per member"):
        SweepTrainer(
            params, ppo=PPO, config=_cfg(tmp_path), num_seeds=2,
            learning_rates=[1e-3],
        )


@pytest.mark.slow
def test_lr_sweep_member_checkpoint_resumes_params_only(tmp_path):
    """lr-sweep member checkpoints omit the inject-wrapped opt_state and
    still warm-start a single Trainer (fresh Adam moments)."""
    params = EnvParams(num_agents=3)
    cfg = _cfg(
        tmp_path,
        checkpoint=True,
        total_timesteps=PPO.n_steps * 4 * 3,  # 1 iteration
    )
    sweep = SweepTrainer(
        params, ppo=PPO, config=cfg, num_seeds=2,
        learning_rates=[1e-3, 1e-2],
    )
    sweep.train()
    summary = json.loads(
        (Path(sweep.log_dir) / "sweep_summary.json").read_text()
    )
    np.testing.assert_allclose(
        summary["learning_rates"], [1e-3, 1e-2], rtol=1e-6
    )

    member_dir = Path(sweep.log_dir) / "seed0"
    resumed = Trainer(
        params,
        ppo=PPO,
        config=_cfg(
            tmp_path, log_dir=str(member_dir), resume=True, checkpoint=False
        ),
    )
    assert resumed.num_timesteps == sweep.num_timesteps
    _leaves_allclose(
        resumed.train_state.params,
        jax.tree_util.tree_map(lambda x: x[0], sweep.train_state.params),
    )


@pytest.mark.slow
def test_resume_warns_on_learning_rate_mismatch(tmp_path, capsys):
    """A member trained at a non-default rate must warn when resumed at
    a different one (the rate is recorded in the checkpoint)."""
    params = EnvParams(num_agents=3)
    cfg = _cfg(
        tmp_path,
        checkpoint=True,
        total_timesteps=PPO.n_steps * 4 * 3,  # 1 iteration
    )
    sweep = SweepTrainer(
        params, ppo=PPO, config=cfg, num_seeds=2,
        learning_rates=[1e-3, 1e-2],
    )
    sweep.train()
    capsys.readouterr()
    Trainer(
        params,
        ppo=PPO,  # learning_rate=1e-3 != seed1's 1e-2
        config=_cfg(
            tmp_path,
            log_dir=str(Path(sweep.log_dir) / "seed1"),
            resume=True,
            checkpoint=False,
        ),
    )
    assert "learning_rate=0.01" in capsys.readouterr().out


@pytest.mark.slow
def test_summary_fresh_despite_sparse_logging(tmp_path):
    """A run whose iteration count log_interval never divides must still
    write sweep_summary.json, ranked on the FINAL iteration's rewards."""
    cfg = _cfg(
        tmp_path,
        checkpoint=True,
        log_interval=10,
        total_timesteps=3 * PPO.n_steps * 4 * 3,  # 3 iterations
    )
    sweep = SweepTrainer(
        EnvParams(num_agents=3), ppo=PPO, config=cfg, num_seeds=2
    )
    record = sweep.train()
    assert "reward_best" in record
    summary = json.loads(
        (Path(sweep.log_dir) / "sweep_summary.json").read_text()
    )
    assert len(summary["final_reward"]) == 2


@pytest.mark.slow
def test_periodic_saves_honor_save_freq(tmp_path):
    """save_freq vec-steps between member checkpoints, like Trainer."""
    cfg = _cfg(
        tmp_path,
        checkpoint=True,
        save_freq=PPO.n_steps,  # every iteration
        total_timesteps=2 * PPO.n_steps * 4 * 3,  # 2 iterations
    )
    sweep = SweepTrainer(
        EnvParams(num_agents=3), ppo=PPO, config=cfg, num_seeds=2
    )
    sweep.train()
    ckpts = sorted(
        p.name for p in (Path(sweep.log_dir) / "seed1").glob("*.msgpack")
    )
    assert len(ckpts) == 2, f"expected a checkpoint per iteration: {ckpts}"


@pytest.mark.slow
def test_member_checkpoints_play_back_and_resume(tmp_path):
    """train() writes per-member checkpoints + ranking summary; a member
    checkpoint loads through LoadedPolicy and resumes a single Trainer."""
    from marl_distributedformation_tpu.compat import LoadedPolicy

    params = EnvParams(num_agents=3)
    cfg = _cfg(
        tmp_path,
        checkpoint=True,
        total_timesteps=2 * PPO.n_steps * 4 * 3,  # 2 iterations
    )
    sweep = SweepTrainer(params, ppo=PPO, config=cfg, num_seeds=2)
    record = sweep.train()
    assert "reward_best" in record and "best_seed" in record

    summary = json.loads(
        (Path(sweep.log_dir) / "sweep_summary.json").read_text()
    )
    assert summary["best_dir"] in ("seed0", "seed1")
    assert len(summary["final_reward"]) == 2

    member_dir = Path(sweep.log_dir) / "seed0"
    ckpts = list(member_dir.glob("rl_model_*_steps.msgpack"))
    assert ckpts, f"no member checkpoint in {member_dir}"

    policy = LoadedPolicy.from_checkpoint(ckpts[0], act_dim=2)
    obs = np.zeros((6, params.obs_dim), np.float32)
    actions, _ = policy.predict(obs)
    assert actions.shape == (6, 2)

    resumed = Trainer(
        params,
        ppo=PPO,
        config=_cfg(
            tmp_path, log_dir=str(member_dir), resume=True, checkpoint=False
        ),
    )
    assert resumed.num_timesteps == sweep.num_timesteps
    _leaves_allclose(
        resumed.train_state.params,
        jax.tree_util.tree_map(lambda x: x[0], sweep.train_state.params),
    )


@pytest.mark.slow
def test_sweep_composes_with_ctde_and_gnn(tmp_path):
    """Population training is policy-agnostic: the per-formation CTDE
    critic and the knn-graph GNN both train under the seed vmap."""
    from marl_distributedformation_tpu.models import (
        CTDEActorCritic,
        GNNActorCritic,
    )

    ctde = SweepTrainer(
        EnvParams(num_agents=3),
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=2,
        model=CTDEActorCritic(act_dim=2),
    )
    m = ctde.run_iteration()
    assert np.isfinite(np.asarray(m["loss"])).all()

    kp = EnvParams(num_agents=6, obs_mode="knn", knn_k=2)
    gnn = SweepTrainer(
        kp,
        ppo=PPO,
        config=_cfg(tmp_path),
        num_seeds=2,
        model=GNNActorCritic(k=2, act_dim=2, goal_in_obs=kp.goal_in_obs),
    )
    m = gnn.run_iteration()
    assert np.isfinite(np.asarray(m["loss"])).all()


def test_hetero_rejects_fused_chunk(tmp_path):
    """fused_chunk is the population fusion spelling
    (tests/test_fused_sweep.py pins its bitwise parity); the single-run
    curriculum trainer rejects it (host-driven stages)."""
    from marl_distributedformation_tpu.train import HeteroTrainer

    with pytest.raises(SystemExit, match="fused_chunk"):
        HeteroTrainer(
            env_params=EnvParams(num_agents=3),
            ppo=PPO,
            config=_cfg(tmp_path, fused_chunk=2),
        )


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.slow
@pytest.mark.parametrize("lr_sweep", [False, True])
def test_sweep_resume_bit_exact(tmp_path, lr_sweep):
    """An interrupted sweep resumed from its sweep_state checkpoint ends
    bit-identical to an uninterrupted run — params, optimizer state
    (incl. per-member injected rates), member keys, env state, and
    progress (VERDICT r3 #3)."""
    params = EnvParams(num_agents=3)
    lrs = [1e-3, 3e-3] if lr_sweep else None
    per_iter = PPO.n_steps * 4 * 3  # n_steps * M * N agent-transitions
    kw = dict(checkpoint=True, save_freq=10**9)

    full = SweepTrainer(
        params, ppo=PPO, num_seeds=2, learning_rates=lrs,
        config=_cfg(tmp_path, name="full", log_dir=str(tmp_path / "full"),
                    total_timesteps=2 * per_iter, **kw),
    )
    full.train()

    half = SweepTrainer(
        params, ppo=PPO, num_seeds=2, learning_rates=lrs,
        config=_cfg(tmp_path, name="part", log_dir=str(tmp_path / "part"),
                    total_timesteps=per_iter, **kw),
    )
    half.train()  # final save() writes sweep_state_{per_iter}_steps
    assert (tmp_path / "part" /
            f"sweep_state_{per_iter}_steps.msgpack").exists()

    resumed = SweepTrainer(
        params, ppo=PPO, num_seeds=2, learning_rates=lrs,
        config=_cfg(tmp_path, name="part", log_dir=str(tmp_path / "part"),
                    total_timesteps=2 * per_iter, resume=True, **kw),
    )
    assert resumed.num_timesteps == per_iter
    resumed.train()

    assert resumed.num_timesteps == full.num_timesteps
    _leaves_equal(resumed.train_state.params, full.train_state.params)
    _leaves_equal(resumed.train_state.opt_state, full.train_state.opt_state)
    _leaves_equal(resumed.key, full.key)
    _leaves_equal(resumed.env_state, full.env_state)
    _leaves_equal(resumed.obs, full.obs)
    # The resumed run's final ranking agrees with the uninterrupted one.
    s_full = json.loads(
        (tmp_path / "full" / "sweep_summary.json").read_text()
    )
    s_res = json.loads(
        (tmp_path / "part" / "sweep_summary.json").read_text()
    )
    assert s_res["best_seed"] == s_full["best_seed"]
    np.testing.assert_array_equal(
        s_res["final_reward"], s_full["final_reward"]
    )


@pytest.mark.slow
def test_sweep_resume_rejects_mismatches(tmp_path):
    """Identity mismatches (population size, lr-sweep mode) must fail
    loudly, not silently re-seed members."""
    params = EnvParams(num_agents=3)
    per_iter = PPO.n_steps * 4 * 3
    cfg = _cfg(
        tmp_path, name="pop", log_dir=str(tmp_path / "pop"),
        checkpoint=True, save_freq=10**9, total_timesteps=per_iter,
    )
    SweepTrainer(params, ppo=PPO, num_seeds=2, config=cfg).train()

    resume_cfg = _cfg(
        tmp_path, name="pop", log_dir=str(tmp_path / "pop"),
        checkpoint=True, save_freq=10**9, total_timesteps=2 * per_iter,
        resume=True,
    )
    with pytest.raises(SystemExit, match="num_seeds"):
        SweepTrainer(params, ppo=PPO, num_seeds=4, config=resume_cfg)
    with pytest.raises(SystemExit, match="learning_rates"):
        SweepTrainer(
            params, ppo=PPO, num_seeds=2, config=resume_cfg,
            learning_rates=[1e-3, 3e-3],
        )

    # Member checkpoints without a population file (pre-feature run):
    # fresh start with a loud note, not a crash.
    import os

    os.remove(
        tmp_path / "pop" / f"sweep_state_{per_iter}_steps.msgpack"
    )
    fresh = SweepTrainer(params, ppo=PPO, num_seeds=2, config=resume_cfg)
    assert fresh.num_timesteps == 0


@pytest.mark.slow
def test_visualize_policy_auto_selects_best_member(
    tmp_path, monkeypatch, capsys
):
    """`visualize_policy.py name=pop` on a sweep run descends into
    sweep_summary.json's best member."""
    import visualize_policy

    cfg = _cfg(
        tmp_path,
        name="popviz",
        log_dir=str(tmp_path / "logs" / "popviz"),
        checkpoint=True,
        total_timesteps=PPO.n_steps * 4 * 3,  # 1 iteration
    )
    sweep = SweepTrainer(
        EnvParams(num_agents=3), ppo=PPO, config=cfg, num_seeds=2
    )
    sweep.train()
    monkeypatch.setattr(
        "marl_distributedformation_tpu.utils.repo_root", lambda: tmp_path
    )
    args = ["name=popviz", "platform=cpu", "headless=true", "steps=2",
            "num_agents_per_formation=3"]
    visualize_policy.main(args)
    out = capsys.readouterr().out
    best = json.loads(
        (Path(sweep.log_dir) / "sweep_summary.json").read_text()
    )["best_dir"]
    assert f"playing best member {best}" in out  # THE ranked member
    assert f"/{best}/rl_model_" in out  # and its checkpoint is loaded

    # Summary exists but its best_dir checkpoint was deleted by hand —
    # fall through to the members scan, not "no checkpoint" (ADVICE r3).
    for p in (Path(sweep.log_dir) / best).glob("rl_model_*_steps*"):
        p.unlink()
    visualize_policy.main(args)
    out = capsys.readouterr().out
    assert "best member missing" in out
    assert "furthest-trained member seed" in out

    # Interrupted sweep: members exist, summary doesn't — fall back to
    # the furthest-trained member instead of claiming nothing exists.
    (Path(sweep.log_dir) / "sweep_summary.json").unlink()
    visualize_policy.main(args)
    assert "furthest-trained member seed" in capsys.readouterr().out


def test_cli_dispatch(tmp_path, monkeypatch):
    import train as train_cli
    from marl_distributedformation_tpu.utils import load_config

    cfg = load_config(
        ["name=sweeptest", "num_seeds=2", "num_formation=4",
         "num_agents_per_formation=3", "platform=cpu"]
    )
    trainer = train_cli.build_trainer(cfg)
    assert isinstance(trainer, SweepTrainer)

    # num_seeds now COMPOSES with curriculum (round 5): the candidate
    # population trainer — its own dispatch/rejection matrix is pinned
    # in tests/test_hetero_sweep.py::test_cli_dispatch.
    from marl_distributedformation_tpu.train import HeteroSweepTrainer

    cfg2 = load_config(
        ["name=x", "num_seeds=2", "platform=cpu", "num_formation=4",
         "num_agents_per_formation=3",
         "curriculum=[{rollouts: 2, agent_counts: [3]}]"]
    )
    assert isinstance(train_cli.build_trainer(cfg2), HeteroSweepTrainer)

    # resume=true now composes with sweeps (population resume): with no
    # prior sweep_state it just builds a fresh population.
    cfg3 = load_config(
        ["name=x", "num_seeds=2", "resume=true", "platform=cpu",
         "num_formation=4", "num_agents_per_formation=3",
         f"log_dir={tmp_path / 'x'}"]
    )
    trainer3 = train_cli.build_trainer(cfg3)
    assert isinstance(trainer3, SweepTrainer)
    assert trainer3.num_timesteps == 0
