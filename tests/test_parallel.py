"""Mesh-sharding tests on the 8-virtual-device CPU mesh (conftest.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.training.train_state import TrainState
from jax.sharding import PartitionSpec as P

from marl_distributedformation_tpu.algo import (
    MinibatchData,
    PPOConfig,
    ppo_update,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.models import (
    MLPActorCritic,
    distributions,
)
from marl_distributedformation_tpu.parallel import (
    make_mesh,
    make_shard_fn,
    minibatch_sharding,
    replicate,
)
from marl_distributedformation_tpu.train import TrainConfig, Trainer


def test_virtual_device_count():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"


def test_make_mesh_shapes():
    mesh = make_mesh({"dp": 8})
    assert mesh.shape == {"dp": 8}
    mesh2 = make_mesh({"dp": 4, "sp": 2})
    assert mesh2.shape == {"dp": 4, "sp": 2}
    mesh3 = make_mesh({"dp": -1})
    assert mesh3.shape == {"dp": 8}
    with pytest.raises(ValueError):
        make_mesh({"dp": 16})


def _trainer(tmp_path, shard_fn=None, num_formations=8, batch_size=24):
    return Trainer(
        EnvParams(num_agents=3),
        ppo=PPOConfig(n_steps=4, batch_size=batch_size, n_epochs=2),
        config=TrainConfig(
            num_formations=num_formations,
            seed=0,
            checkpoint=False,
            name="mesh",
            log_dir=str(tmp_path / "logs"),
        ),
        shard_fn=shard_fn,
    )


@pytest.mark.slow
def test_sharded_training_matches_single_device(tmp_path):
    """dp-sharded training is numerically the same program: metrics and
    updated params must match the unsharded run to fp32 tolerance."""
    t_single = _trainer(tmp_path / "single")
    t_sharded = _trainer(tmp_path / "sharded", shard_fn=make_shard_fn({"dp": 8}))

    for _ in range(2):
        m_single = t_single.run_iteration()
        m_sharded = t_sharded.run_iteration()
        np.testing.assert_allclose(
            float(m_single["reward"]), float(m_sharded["reward"]), rtol=1e-5
        )
        np.testing.assert_allclose(
            float(m_single["loss"]), float(m_sharded["loss"]), rtol=1e-3
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(t_single.train_state.params),
        jax.tree_util.tree_leaves(t_sharded.train_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_sharded_training_matches_single_device_quick(tmp_path):
    """The slow test's twin at tier-1's price: on dp=4 the dispatch divides
    every minibatch (6 of 24 rows a device) and still trains the program
    the single device trains."""
    t_single = _trainer(tmp_path / "single")
    t_sharded = _trainer(tmp_path / "sharded", shard_fn=make_shard_fn({"dp": 4}))
    assert t_single._minibatch_sharding() is None
    assert t_sharded._minibatch_sharding().spec == P(None, "dp")
    for _ in range(2):
        m_single = t_single.run_iteration()
        m_sharded = t_sharded.run_iteration()
        for name, rtol in (("reward", 1e-5), ("loss", 1e-3), ("grad_norm", 1e-3)):
            np.testing.assert_allclose(
                float(m_single[name]), float(m_sharded[name]), rtol=rtol
            )
    _assert_same_train_state(t_sharded.train_state, t_single.train_state)


def test_sharded_env_state_placement(tmp_path):
    shard_fn = make_shard_fn({"dp": 8})
    trainer = _trainer(tmp_path, shard_fn=shard_fn, num_formations=16)
    sharding = trainer.env_state.agents.sharding
    assert sharding.is_equivalent_to(
        jax.sharding.NamedSharding(
            shard_fn.mesh, jax.sharding.PartitionSpec("dp")
        ),
        trainer.env_state.agents.ndim,
    )
    # Sharding survives a training iteration (no silent gather to one device).
    trainer.run_iteration()
    assert not trainer.env_state.agents.sharding.is_fully_replicated


def test_indivisible_formations_rejected(tmp_path):
    with pytest.raises(ValueError, match="not divisible"):
        _trainer(tmp_path, shard_fn=make_shard_fn({"dp": 8}), num_formations=12)


# ---------------------------------------------------------------------------
# The minibatch divided over 'dp' (parallel.minibatch_sharding, ppo_update)
# ---------------------------------------------------------------------------


def _assert_same_train_state(got, want):
    """Parameters and Adam's moments agree to float32's tolerance: the
    divided minibatch changes the order of one sum, nothing else."""
    assert int(got.step) == int(want.step)
    for a, b in zip(
        jax.tree_util.tree_leaves((got.params, got.opt_state)),
        jax.tree_util.tree_leaves((want.params, want.opt_state)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def _update_case(obs_dim):
    """A train state and 480 rows of rollout data: 5 minibatches of 96."""
    config = PPOConfig(batch_size=96, n_epochs=2)
    model = MLPActorCritic(act_dim=2)
    k_init, k_obs, k_act, k_adv = jax.random.split(jax.random.PRNGKey(0), 4)
    obs = jax.random.normal(k_obs, (480, obs_dim))
    ts = TrainState.create(
        apply_fn=model.apply,
        params=model.init(k_init, obs[:1]),
        tx=config.make_optimizer(),
    )
    mean, log_std, values = ts.apply_fn(ts.params, obs)
    actions = distributions.sample(k_act, mean, log_std)
    advantages = jax.random.normal(k_adv, (480,))
    data = MinibatchData(
        obs=obs,
        actions=actions,
        old_log_probs=distributions.log_prob(actions, mean, log_std),
        advantages=advantages,
        returns=values + advantages,
    )
    return ts, data, config


@pytest.mark.parametrize("obs_dim", [8, 160], ids=["packed", "a-leaf"])
@pytest.mark.parametrize("dp", [2, 4, 8])
def test_divided_minibatch_equals_whole(dp, obs_dim):
    """``ppo_update`` with its rows laid out over 'dp' is the update without
    the layout: same permutation, same rows a minibatch, the sums taken in
    ``dp`` parts (rows packed into one table, and too wide to pack)."""
    ts, data, config = _update_case(obs_dim)
    key = jax.random.PRNGKey(5)
    mesh = make_mesh({"dp": dp})
    rows = minibatch_sharding(mesh, config.batch_size)
    update = jax.jit(lambda *a: ppo_update(*a, config))
    whole = update(ts, data, key)
    divided = update(
        *replicate((ts, data.replace(rows_sharding=rows), key), mesh)
    )
    _assert_same_train_state(divided[0], whole[0])
    assert divided[1].keys() == whole[1].keys()
    for name, value in whole[1].items():
        np.testing.assert_allclose(
            float(divided[1][name]), float(value), rtol=1e-4, atol=1e-6
        )


def _collectives(text, op):
    """(result shape, op_name) of each ``op`` in a compiled module's text."""
    return re.findall(
        rf"= (\S+) {op}(?:-start)?\(.*op_name=\"([^\"]*)\"", text
    )


def test_divided_minibatch_in_the_compiled_dispatch(tmp_path):
    """On dp=4 the trainer's program looks up 24 / 4 rows a device, meets
    the gradient in an all-reduce under ``loss_and_grad``, and gathers
    across devices only what it gathered before: the rollout buffer, for
    the table every device builds whole (``row_pack``)."""
    t = _trainer(tmp_path, shard_fn=make_shard_fn({"dp": 4}))
    text = (
        t._iteration.lower(t.train_state, t.env_state, t.obs, t.key)
        .compile()
        .as_text()
    )
    lookups = [
        shape
        for shape, name in _collectives(text, "gather")
        if "minibatch_gather" in name
    ]
    assert lookups and all(s.startswith("f32[6,") for s in lookups), lookups
    assert any(
        "loss_and_grad" in name for _, name in _collectives(text, "all-reduce")
    )
    for _, name in _collectives(text, "all-gather"):
        assert "ppo_update" not in name or "row_pack" in name, name
        assert "loss_and_grad" not in name and "subrow_pick" not in name, name


@pytest.mark.parametrize(
    "axes,batch_size,says",
    [
        (None, 24, ""),
        ({"dp": 1}, 24, ""),
        ({"dp": 8}, 20, "20 rows: whole on every device (dp=8 does not"),
        ({"dp": 8}, 24, "24 rows: 3 a device over dp=8"),
    ],
    ids=["no-mesh", "dp1", "indivisible", "divisible"],
)
def test_layout_is_chosen_from_the_mesh_and_the_row_count(
    tmp_path, monkeypatch, capsys, axes, batch_size, says
):
    """No mesh, one device along 'dp', or a minibatch 'dp' does not divide
    lower to the program without the layout, letter for letter; a minibatch
    it divides does not. The build log says once which it took."""

    def lowered(tag):
        t = _trainer(
            tmp_path / tag,
            shard_fn=make_shard_fn(axes) if axes else None,
            batch_size=batch_size,
        )
        return t._iteration.lower(
            t.train_state, t.env_state, t.obs, t.key
        ).as_text()

    chosen = lowered("chosen")
    said = capsys.readouterr().out
    assert said.count("[trainer] minibatches of") == bool(says)
    assert says in said
    monkeypatch.setattr(Trainer, "_minibatch_sharding", lambda self: None)
    without = lowered("without")
    if "a device" in says:
        assert chosen != without
        assert chosen.count("sharding_constraint") > without.count(
            "sharding_constraint"
        )
    else:
        assert chosen == without


# ---------------------------------------------------------------------------
# Ring halo exchange: agent-axis ('sp') sharding (parallel/ring.py)
# ---------------------------------------------------------------------------

from marl_distributedformation_tpu.env.formation import reset_batch, step_batch
from marl_distributedformation_tpu.parallel import make_ring_step, place_ring_state


@pytest.mark.parametrize("dp,sp", [(1, 8), (2, 4), (4, 2), (8, 1)])
@pytest.mark.slow
def test_ring_step_matches_unsharded(dp, sp):
    """Agent-axis sharding is semantics-free: ring-step trajectories equal
    the unsharded vmap step exactly (same reset draws, same rewards/obs)."""
    params = EnvParams(num_agents=8, max_steps=3)  # resets inside the run
    M = 4 * dp if dp > 1 else 4
    mesh = make_mesh({"dp": dp, "sp": sp})
    ring_step = make_ring_step(params, mesh)

    state_ref = reset_batch(jax.random.PRNGKey(0), params, M)
    state_ring = place_ring_state(state_ref, mesh)

    rng = np.random.default_rng(1)
    for t in range(8):  # crosses the strict-parity reset at step 5
        vel = jnp.asarray(
            rng.uniform(-10, 10, (M, 8, 2)).astype(np.float32)
        )
        state_ref, tr_ref = step_batch(state_ref, vel, params)
        state_ring, tr_ring = ring_step(state_ring, vel)
        np.testing.assert_allclose(
            np.asarray(tr_ring.obs), np.asarray(tr_ref.obs),
            rtol=1e-5, atol=1e-6, err_msg=f"obs t={t}",
        )
        np.testing.assert_allclose(
            np.asarray(tr_ring.reward), np.asarray(tr_ref.reward),
            rtol=1e-4, atol=1e-4, err_msg=f"reward t={t}",
        )
        np.testing.assert_array_equal(
            np.asarray(tr_ring.done), np.asarray(tr_ref.done)
        )
        np.testing.assert_allclose(
            np.asarray(state_ring.agents), np.asarray(state_ref.agents),
            rtol=1e-5, atol=1e-5,
        )
        for k in tr_ref.metrics:
            np.testing.assert_allclose(
                np.asarray(tr_ring.metrics[k]),
                np.asarray(tr_ref.metrics[k]),
                rtol=1e-4, atol=1e-4, err_msg=f"metric {k} t={t}",
            )


def test_ring_step_sharding_layout():
    params = EnvParams(num_agents=8)
    mesh = make_mesh({"dp": 2, "sp": 4})
    ring_step = make_ring_step(params, mesh)
    state = place_ring_state(
        reset_batch(jax.random.PRNGKey(0), params, 4), mesh
    )
    vel = jnp.zeros((4, 8, 2))
    state2, tr = ring_step(state, vel)
    # Agent axis stays sharded over 'sp' after the step.
    assert not state2.agents.sharding.is_fully_replicated
    spec = state2.agents.sharding.spec
    assert tuple(spec)[:2] == ("dp", "sp")


def test_ring_step_rejects_indivisible_agents():
    mesh = make_mesh({"dp": 2, "sp": 4})
    with pytest.raises(ValueError, match="not divisible"):
        make_ring_step(EnvParams(num_agents=6), mesh)


# ---------------------------------------------------------------------------
# 'sp' sharding wired end-to-end through the Trainer (VERDICT.md round-1 #2)
# ---------------------------------------------------------------------------


def _sp_trainer(tmp_path, shard_fn=None):
    return Trainer(
        EnvParams(num_agents=8),
        ppo=PPOConfig(n_steps=4, batch_size=32, n_epochs=2),
        config=TrainConfig(
            num_formations=4,
            seed=0,
            checkpoint=False,
            name="sp",
            log_dir=str(tmp_path / "logs"),
        ),
        shard_fn=shard_fn,
    )


@pytest.mark.slow
def test_sp_sharded_training_matches_single_device(tmp_path):
    """Full train iterations on a {dp:2, sp:2} mesh: the halo-exchange env
    step + sharded PPO update must reproduce the unsharded trajectory (env
    states equal, params equal to fp32 reduction tolerance)."""
    t_single = _sp_trainer(tmp_path / "single")
    t_sp = _sp_trainer(
        tmp_path / "sp", shard_fn=make_shard_fn({"dp": 2, "sp": 2})
    )
    assert t_sp._env_step_fn is not None, "sp mesh must select the ring step"

    for i in range(2):
        m_single = t_single.run_iteration()
        m_sp = t_sp.run_iteration()
        np.testing.assert_allclose(
            float(m_single["reward"]), float(m_sp["reward"]),
            rtol=1e-4, err_msg=f"iter {i}",
        )
        np.testing.assert_allclose(
            float(m_single["loss"]), float(m_sp["loss"]), rtol=1e-3
        )
        # Same env trajectory step for step (resets included).
        np.testing.assert_allclose(
            np.asarray(t_single.env_state.agents),
            np.asarray(t_sp.env_state.agents),
            rtol=1e-4, atol=1e-3,
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(t_single.train_state.params),
        jax.tree_util.tree_leaves(t_sp.train_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )


def test_sp_shard_fn_layout(tmp_path):
    trainer = _sp_trainer(
        tmp_path, shard_fn=make_shard_fn({"dp": 2, "sp": 2})
    )
    spec = trainer.env_state.agents.sharding.spec
    assert tuple(spec)[:2] == ("dp", "sp")
    trainer.run_iteration()
    assert not trainer.env_state.agents.sharding.is_fully_replicated
    spec_after = trainer.env_state.agents.sharding.spec
    assert tuple(spec_after)[:2] == ("dp", "sp")


def test_sp_shard_fn_accepts_knn_obs(tmp_path):
    """Round 3: knn swarms shard on 'sp' too (all-gather + local-query
    search). The Trainer selects the sharded step and one iteration runs;
    an indivisible agent count is still rejected."""
    trainer = Trainer(
        EnvParams(num_agents=8, obs_mode="knn", knn_k=2, knn_impl="xla"),
        config=TrainConfig(
            num_formations=4, checkpoint=False,
            log_dir=str(tmp_path / "logs"),
        ),
        shard_fn=make_shard_fn({"dp": 2, "sp": 2}),
    )
    assert trainer._env_step_fn is not None
    assert np.isfinite(trainer.run_iteration()["loss"])
    with pytest.raises(ValueError, match="divisible"):
        Trainer(
            EnvParams(num_agents=7, obs_mode="knn", knn_k=2),
            config=TrainConfig(
                num_formations=4, checkpoint=False,
                log_dir=str(tmp_path / "logs2"),
            ),
            shard_fn=make_shard_fn({"dp": 2, "sp": 2}),
        )


# ---------------------------------------------------------------------------
# Agent-axis sharding of knn swarms: all-gather + local-query search
# ---------------------------------------------------------------------------


def test_knn_local_matches_full_search():
    """knn_local on a slab returns exactly the corresponding rows of the
    full search (global indices, same tie-breaks — both use the identical
    distance expression and column order)."""
    from marl_distributedformation_tpu.ops import knn, knn_local

    pts = jnp.asarray(
        np.random.default_rng(3).uniform(0, 400, (12, 2)), jnp.float32
    )
    idx_full, off_full, d_full = knn(pts, 3)
    for offset, nq in ((0, 4), (4, 4), (8, 4), (3, 6)):
        idx, off, d = knn_local(pts[offset : offset + nq], pts, 3, offset)
        np.testing.assert_array_equal(
            np.asarray(idx), np.asarray(idx_full[offset : offset + nq])
        )
        np.testing.assert_allclose(
            np.asarray(off), np.asarray(off_full[offset : offset + nq]),
            rtol=1e-6, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(d), np.asarray(d_full[offset : offset + nq]),
            rtol=1e-6, atol=1e-6,
        )


@pytest.mark.parametrize("dp,sp", [(2, 4), (1, 8)])
@pytest.mark.slow
def test_knn_ring_step_matches_unsharded(dp, sp):
    """The sp-sharded knn swarm step (all-gather positions + knn_local per
    slab + halo-exchange reward mixing) reproduces the unsharded
    trajectory exactly — including the global neighbor indices carried in
    the observations."""
    params = EnvParams(
        num_agents=16, max_steps=3, obs_mode="knn", knn_k=3,
        knn_impl="xla",
    )
    M = 4 * dp if dp > 1 else 4
    mesh = make_mesh({"dp": dp, "sp": sp})
    ring_step = make_ring_step(params, mesh)

    state_ref = reset_batch(jax.random.PRNGKey(7), params, M)
    state_ring = place_ring_state(state_ref, mesh)

    rng = np.random.default_rng(11)
    for t in range(8):  # crosses the strict-parity auto-reset
        vel = jnp.asarray(
            rng.uniform(-10, 10, (M, 16, 2)).astype(np.float32)
        )
        state_ref, tr_ref = step_batch(state_ref, vel, params)
        state_ring, tr_ring = ring_step(state_ring, vel)
        np.testing.assert_allclose(
            np.asarray(tr_ring.obs), np.asarray(tr_ref.obs),
            rtol=1e-5, atol=1e-6, err_msg=f"obs t={t}",
        )
        np.testing.assert_allclose(
            np.asarray(tr_ring.reward), np.asarray(tr_ref.reward),
            rtol=1e-4, atol=1e-4, err_msg=f"reward t={t}",
        )
        np.testing.assert_array_equal(
            np.asarray(tr_ring.done), np.asarray(tr_ref.done)
        )
        np.testing.assert_allclose(
            np.asarray(state_ring.agents), np.asarray(state_ref.agents),
            rtol=1e-5, atol=1e-5,
        )


@pytest.mark.slow
def test_gnn_trains_on_sp_mesh(tmp_path):
    """A formation-level model (GNN) composes with agent-axis sharding:
    the env step runs the sharded all-gather + local-query search, and the
    SPMD partitioner re-gathers the agent axis where the per-formation
    forward needs it. One full iteration, finite loss."""
    from marl_distributedformation_tpu.models import GNNActorCritic

    params = EnvParams(num_agents=8, obs_mode="knn", knn_k=2, knn_impl="xla")
    trainer = Trainer(
        params,
        ppo=PPOConfig(n_steps=2, batch_size=64, n_epochs=1),
        config=TrainConfig(
            num_formations=4, checkpoint=False,
            log_dir=str(tmp_path / "logs"),
        ),
        model=GNNActorCritic(k=2, act_dim=2, goal_in_obs=params.goal_in_obs),
        shard_fn=make_shard_fn({"dp": 2, "sp": 2}),
    )
    assert trainer._env_step_fn is not None
    assert np.isfinite(trainer.run_iteration()["loss"])


@pytest.mark.slow
def test_weak_scaling_script_smoke(tmp_path, monkeypatch):
    """scripts/weak_scaling.py end-to-end at tiny sizes: every phase
    emits a row per device count and the doc table is written."""
    import json
    import os
    import subprocess
    import sys as _sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(
        os.environ,
        WS_DEVICES="1,2",
        WS_M_TOTAL="8",
        WS_M_TRAIN="8",
        WS_M_MEMBER="4",
        WS_ENV_CHUNK="4",
        WS_MIN_TIMED_S="0.1",
        WS_DOC=str(tmp_path / "weak_scaling.md"),
    )
    out = subprocess.run(
        [_sys.executable, str(repo / "scripts" / "weak_scaling.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
        cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout)
    got = {(r["phase"], r["devices"]) for r in rows}
    assert got == {
        (p, d) for p in ("dp_env", "dp_train", "sweep") for d in (1, 2)
    }
    assert all(r["steps_per_sec"] > 0 for r in rows)
    doc = (tmp_path / "weak_scaling.md").read_text()
    assert "| 2 |" in doc and "sweep" in doc
