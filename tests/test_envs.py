"""The envs/ subsystem contract (tier-1, CPU).

The acceptance pins from the envs ISSUE:

- the registry fails fast on unknown names (did-you-mean + full listing),
  refuses silent overwrites, and keeps ``spec_for_params`` unambiguous
  (one params class per env, MRO dispatch for subclasses);
- the formation env behind the registry is the legacy ``env/formation.py``
  BITWISE — the spec's functions ARE the legacy functions, a registry-
  routed rollout reproduces the direct one exactly, and the declared
  layout matches the hard-coded column knowledge scenarios/ used to carry;
- pursuit-evasion trains end to end (Anakin fused AND Sebulba lockstep,
  fused == host loop bitwise), evaluates/gates through the budget-1
  MatrixProgram, and serves through the bucketed rung ladder with one
  compile per (env, rung);
- every registered scenario layer at severity 0 is bitwise identity on
  BOTH envs, and the obstacle layers really occlude / really move.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from marl_distributedformation_tpu import envs
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env import formation as legacy
from marl_distributedformation_tpu.envs import (
    FORMATION_SPEC,
    PURSUIT_SPEC,
    EnvSpec,
    ObsLayout,
    PursuitParams,
    formation_obs_layout,
    get_env,
    register_env,
    registered_envs,
    spec_for_params,
)
from marl_distributedformation_tpu.envs.pursuit import (
    pursuer_update,
    pursuit_reward,
)
from marl_distributedformation_tpu.scenarios import (
    broadcast_params,
    get_scenario,
    registered_scenarios,
    scenario_step_batch,
)
from marl_distributedformation_tpu.train import TrainConfig, Trainer
from marl_distributedformation_tpu.utils.checkpoint import checkpoint_step

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)
PURSUIT = PursuitParams(num_agents=3, max_steps=20)
M = 3


@dataclasses.dataclass(frozen=True)
class _DerivedPursuit(PursuitParams):
    """A params subclass with NO registration of its own — must resolve
    to its nearest registered ancestor (pursuit_evasion), not formation."""


# ---------------------------------------------------------------------------
# Registry: fail-fast taxonomy
# ---------------------------------------------------------------------------


def test_registry_lists_both_envs_in_registration_order():
    assert registered_envs() == ("formation", "pursuit_evasion")
    assert envs.get is get_env  # the canonical spelling


def test_unknown_env_fails_fast_with_did_you_mean_and_listing():
    with pytest.raises(ValueError) as e:
        get_env("pursuit_evsion")
    msg = str(e.value)
    assert "did you mean 'pursuit_evasion'" in msg
    for name in registered_envs():
        assert name in msg, "the error must list every valid entry"
    # A name nothing close to: no hint, but still the full listing.
    with pytest.raises(ValueError, match="registered environments"):
        get_env("atari")


def test_register_refuses_silent_name_overwrite():
    with pytest.raises(ValueError, match="already registered"):
        register_env(FORMATION_SPEC)
    # Opt-in overwrite with the same spec is a no-op (and restores the
    # registry to exactly the shipped state for the rest of the session).
    register_env(FORMATION_SPEC, overwrite=True)
    assert get_env("formation") is FORMATION_SPEC
    assert spec_for_params(EnvParams(num_agents=3)) is FORMATION_SPEC


def test_register_refuses_ambiguous_params_class_claim():
    """Two envs sharing one params type would make spec_for_params
    ambiguous — the registry rejects the claim at registration time."""
    pretender = dataclasses.replace(FORMATION_SPEC, name="formation_two")
    with pytest.raises(ValueError, match="already claimed"):
        register_env(pretender)
    assert "formation_two" not in registered_envs()


def test_spec_for_params_dispatches_on_most_derived_type():
    assert spec_for_params(EnvParams(num_agents=3)) is FORMATION_SPEC
    assert spec_for_params(PURSUIT) is PURSUIT_SPEC
    # MRO walk: an unregistered subclass resolves to its registered base.
    assert spec_for_params(_DerivedPursuit(num_agents=3)) is PURSUIT_SPEC


def test_spec_for_params_unregistered_type_fails_naming_pairs():
    with pytest.raises(ValueError) as e:
        spec_for_params(object())
    msg = str(e.value)
    assert "no registered environment" in msg
    assert "formation (EnvParams)" in msg
    assert "pursuit_evasion (PursuitParams)" in msg


# ---------------------------------------------------------------------------
# ObsLayout: declared blocks + fail-fast require
# ---------------------------------------------------------------------------


def test_formation_layout_matches_the_obs_row_geometry():
    params = EnvParams(num_agents=3)
    layout = formation_obs_layout(params)
    assert layout.dim == params.obs_dim
    assert layout.topology == "ring"
    assert layout.names() == ("self", "neighbor", "goal")
    # The mask covers the whole row exactly once (blocks partition it).
    assert layout.columns(*layout.names()).all()
    # goal_in_obs=False drops the goal block, not just its columns.
    bare = formation_obs_layout(EnvParams(num_agents=3, goal_in_obs=False))
    assert bare.block("goal") is None


def test_knn_neighbor_block_is_disjoint_ranges():
    params = EnvParams(num_agents=5, obs_mode="knn", knn_k=2)
    layout = formation_obs_layout(params)
    assert layout.topology == "knn"
    ranges = layout.require("neighbor")
    assert len(ranges) == 2, "offsets+distances block AND the index block"
    from marl_distributedformation_tpu.scenarios import neighbor_obs_columns

    np.testing.assert_array_equal(
        layout.columns("neighbor"), neighbor_obs_columns(params)
    )


def test_pursuit_layout_renames_goal_to_pursuer_and_require_fails_fast():
    layout = PURSUIT_SPEC.obs_layout(PURSUIT)
    assert layout.names() == ("self", "neighbor", "pursuer")
    # Same column geometry as formation — only the block NAME differs,
    # so a layer wanting "goal" fails fast instead of silently masking.
    assert layout.require("pursuer") == formation_obs_layout(
        EnvParams(num_agents=3)
    ).require("goal")
    with pytest.raises(ValueError) as e:
        layout.require("goal", needed_by="moving-goal layer")
    msg = str(e.value)
    assert "moving-goal layer" in msg and "pursuer" in msg


def test_obs_layout_rejects_out_of_range_blocks():
    with pytest.raises(AssertionError):
        ObsLayout(dim=4, topology="ring", blocks=(("self", ((0, 5),)),))
    with pytest.raises(AssertionError):
        ObsLayout(dim=4, topology="grid", blocks=())


# ---------------------------------------------------------------------------
# Formation behind the registry == legacy env/formation.py, bitwise
# ---------------------------------------------------------------------------


def test_formation_spec_functions_are_the_legacy_functions():
    """The strongest possible identity: not equal trajectories — the SAME
    function objects, so the formation path cannot drift by construction."""
    assert FORMATION_SPEC.params_cls is EnvParams
    assert FORMATION_SPEC.reset is legacy.reset
    assert FORMATION_SPEC.step is legacy.step
    assert FORMATION_SPEC.reset_batch is legacy.reset_batch
    assert FORMATION_SPEC.step_batch is legacy.step_batch


def _drive(params, reset_batch, step_batch, num_steps=6, m=M, seed=0):
    state = reset_batch(jax.random.PRNGKey(seed), params, m)
    key = jax.random.PRNGKey(7)
    rows = []
    for _ in range(num_steps):
        key, k_act = jax.random.split(key)
        vel = params.max_speed * jax.random.uniform(
            k_act, (m, params.num_agents, 2), minval=-1.0, maxval=1.0
        )
        state, tr = step_batch(state, vel, params)
        rows.append(
            jax.device_get(
                (
                    state.agents, state.goal, state.obstacles,
                    tr.obs, tr.reward, tr.done,
                )
            )
        )
    return rows


@pytest.mark.parametrize(
    "params",
    [
        EnvParams(num_agents=4, max_steps=5, num_obstacles=2),
        EnvParams(num_agents=5, max_steps=5, obs_mode="knn", knn_k=2),
    ],
    ids=["ring", "knn"],
)
def test_formation_via_registry_rollout_is_bitwise_legacy(params):
    spec = get_env("formation")
    direct = _drive(params, legacy.reset_batch, legacy.step_batch)
    routed = _drive(params, spec.reset_batch, spec.step_batch)
    for d_row, r_row in zip(direct, routed):
        for d, r in zip(d_row, r_row):
            assert np.array_equal(np.asarray(d), np.asarray(r))


def test_gym_flavored_protocol_view_matches_primitives():
    params = EnvParams(num_agents=3)
    state, obs = FORMATION_SPEC.reset_env(jax.random.PRNGKey(0), params)
    np.testing.assert_array_equal(
        np.asarray(obs), np.asarray(FORMATION_SPEC.obs(state, params))
    )
    vel = jnp.zeros((params.num_agents, 2), jnp.float32)
    nxt, obs2, reward, done, info = FORMATION_SPEC.step_env(
        state, vel, params
    )
    assert obs2.shape == obs.shape
    assert reward.shape == (params.num_agents,)
    assert "avg_dist_to_goal" in info
    assert FORMATION_SPEC.default_params(num_agents=7).num_agents == 7


# ---------------------------------------------------------------------------
# Pursuit-evasion: scripted pursuer physics
# ---------------------------------------------------------------------------


def test_pursuer_chases_nearest_evader_without_overshoot():
    params = PursuitParams(num_agents=3, pursuer_speed=7.0)
    agents = jnp.array(
        [[100.0, 100.0], [400.0, 400.0], [500.0, 100.0]], jnp.float32
    )
    # Far gap: moves exactly pursuer_speed toward the NEAREST evader.
    moved = pursuer_update(agents, jnp.array([100.0, 50.0]), params)
    np.testing.assert_allclose(
        np.asarray(moved), [100.0, 57.0], atol=1e-5
    )
    # Gap below pursuer_speed: lands ON the evader, never past it.
    close = pursuer_update(agents, jnp.array([100.0, 98.0]), params)
    np.testing.assert_allclose(np.asarray(close), [100.0, 100.0], atol=1e-5)


def test_capture_penalty_applies_inside_capture_radius_only():
    params = PursuitParams(num_agents=3)
    pursuer = jnp.array([100.0, 100.0], jnp.float32)
    agents = jnp.array(
        [[100.0, 110.0], [400.0, 400.0], [600.0, 300.0]], jnp.float32
    )  # agent 0 within capture_radius=30, the others far
    zeros = jnp.zeros((3,), jnp.float32)
    _, terms = pursuit_reward(agents, pursuer, zeros, zeros, params)
    penalty = np.asarray(terms["capture_penalty"])
    assert penalty[0] == -params.capture_penalty
    assert penalty[1] == penalty[2] == 0.0
    # Fleeing pays: the far agents earn strictly more evade reward.
    evade = np.asarray(terms["evade_reward"])
    assert evade[1] > evade[0] and evade[2] > evade[0]


def test_pursuit_metrics_keys_match_formation():
    """The gate, sweeps, and bench consume metric names — both envs must
    emit the same dictionary shape (avg_dist_to_goal is distance to the
    pursuer here)."""
    from marl_distributedformation_tpu.eval import evaluate, zero_act_fn

    form = evaluate(zero_act_fn(), EnvParams(num_agents=3, max_steps=5),
                    num_formations=2)
    purs = evaluate(zero_act_fn(), PursuitParams(num_agents=3, max_steps=5),
                    num_formations=2)
    assert set(form) == set(purs)
    shared = {"episode_return_per_agent", "final_avg_dist_to_goal",
              "final_ave_dist_to_neighbor"}
    assert shared <= set(purs)
    assert all(np.isfinite(v) for v in purs.values())


# ---------------------------------------------------------------------------
# Scenario layers on BOTH envs: severity-0 bitwise identity
# ---------------------------------------------------------------------------

PURSUIT_SCEN = PursuitParams(num_agents=4, max_steps=5, num_obstacles=4)


def _scenario_step_fn(params, name, severity, m=M):
    sp = broadcast_params(get_scenario(name).build(jnp.float32(severity)), m)
    return lambda state, vel: scenario_step_batch(state, vel, sp, params)


@pytest.mark.parametrize("name", registered_scenarios())
def test_pursuit_severity_zero_is_bitwise_clean(name):
    spec = spec_for_params(PURSUIT_SCEN)
    clean = _drive(PURSUIT_SCEN, spec.reset_batch, spec.step_batch)
    scen = _drive(
        PURSUIT_SCEN,
        spec.reset_batch,
        lambda state, vel, p: _scenario_step_fn(p, name, 0.0)(state, vel),
    )
    for t, (c_row, s_row) in enumerate(zip(clean, scen)):
        for c, s in zip(c_row, s_row):
            assert np.array_equal(np.asarray(c), np.asarray(s)), (
                f"{name} severity=0 diverged from clean pursuit at step {t}"
            )


@pytest.mark.parametrize(
    "name", [n for n in registered_scenarios() if n != "clean"]
)
def test_pursuit_severity_one_perturbs(name):
    spec = spec_for_params(PURSUIT_SCEN)
    clean = _drive(PURSUIT_SCEN, spec.reset_batch, spec.step_batch)
    scen = _drive(
        PURSUIT_SCEN,
        spec.reset_batch,
        lambda state, vel, p: _scenario_step_fn(p, name, 1.0)(state, vel),
    )
    assert any(
        not np.array_equal(np.asarray(c), np.asarray(s))
        for c_row, s_row in zip(clean, scen)
        for c, s in zip(c_row, s_row)
    ), f"{name} at severity 1 must change the pursuit trajectory"


# ---------------------------------------------------------------------------
# Obstacle layers: occlusion masks declared columns, obstacles really move
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "params",
    [
        EnvParams(num_agents=4, max_steps=5, num_obstacles=6),
        PursuitParams(num_agents=4, max_steps=5, num_obstacles=6),
    ],
    ids=["formation", "pursuit"],
)
def test_obstacle_field_occludes_only_declared_neighbor_columns(params):
    spec = spec_for_params(params)
    layout = spec.obs_layout(params)
    cols = layout.columns("neighbor", needed_by="test")
    state = spec.reset_batch(jax.random.PRNGKey(0), params, 8)
    vel = jnp.zeros((8, params.num_agents, 2), jnp.float32)
    _, tr_clean = spec.step_batch(state, vel, params)
    sp = broadcast_params(
        get_scenario("obstacle_field").build(jnp.float32(1.0)), 8
    )
    assert float(np.asarray(sp.obstacle_occlusion)[0]) > 0
    _, tr = scenario_step_batch(state, vel, sp, params)
    clean_obs, obs = np.asarray(tr_clean.obs), np.asarray(tr.obs)
    # Non-neighbor columns are untouched; occluded entries are ZEROED
    # neighbor columns; and with 6 obstacles someone IS occluded.
    np.testing.assert_array_equal(obs[..., ~cols], clean_obs[..., ~cols])
    changed = obs != clean_obs
    assert changed.any(), "severity-1 occlusion never fired"
    assert np.all(obs[changed] == 0.0)
    # Physics is untouched — sensors lie, the world doesn't.
    np.testing.assert_array_equal(
        np.asarray(tr.reward), np.asarray(tr_clean.reward)
    )


def test_moving_obstacles_drift_within_speed_and_world_box():
    params = EnvParams(num_agents=4, max_steps=50, num_obstacles=4)
    spec = spec_for_params(params)
    sp = broadcast_params(
        get_scenario("moving_obstacles").build(jnp.float32(1.0)), M
    )
    speed = float(np.asarray(sp.obstacle_speed)[0])
    assert speed > 0
    state = spec.reset_batch(jax.random.PRNGKey(0), params, M)
    vel = jnp.zeros((M, params.num_agents, 2), jnp.float32)
    prev = np.asarray(state.obstacles)
    for _ in range(3):
        state, _ = scenario_step_batch(state, vel, sp, params)
        cur = np.asarray(state.obstacles)
        moved = np.linalg.norm(cur - prev, axis=-1)
        assert moved.max() > 0.0, "obstacles never moved"
        assert moved.max() <= speed + 1e-4, "moved farther than the speed"
        assert cur.min() >= 0.0
        assert cur[..., 0].max() <= params.width
        assert cur[..., 1].max() <= params.height
        prev = cur


# ---------------------------------------------------------------------------
# Pursuit trains end to end: fused == host loop, Sebulba lockstep, then
# gate + serve with budget-1 receipts (the full promotion loop)
# ---------------------------------------------------------------------------


def _pursuit_trainer(tmp_path, cls=Trainer, **overrides):
    defaults = dict(
        num_formations=4,
        checkpoint=False,
        seed=0,
        name="pursuit",
        log_dir=str(tmp_path / "logs"),
        log_interval=1,
    )
    defaults.update(overrides)
    return cls(PURSUIT, ppo=PPO, config=TrainConfig(**defaults))


def test_pursuit_fused_chunk_bitwise_matches_host_loop(tmp_path):
    """The new env inherits the fused-scan guarantee: one scanned chunk
    of K reproduces K host-loop iterations bit for bit."""
    host = _pursuit_trainer(tmp_path / "host")
    fused = _pursuit_trainer(tmp_path / "fused", fused_chunk=3)
    per_iter = [jax.device_get(host.run_iteration()) for _ in range(3)]
    stacked = jax.device_get(fused.run_chunk())
    assert host.num_timesteps == fused.num_timesteps
    for name, values in stacked.items():
        for i in range(3):
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
    assert fused.retrace_guard.count == 1  # budget-1 fused program


def test_pursuit_sebulba_lockstep_matches_anakin(tmp_path):
    """Depth-1 lockstep on the NEW env drives the real transfer plumbing
    and reproduces Anakin within float tolerance. (Not bitwise like the
    formation pin: pursuit's extra reductions — argmin / vector norms in
    the scripted pursuer — fuse differently across the acting/learning
    program cut. The bitwise guarantee for pursuit lives in the fused-
    vs-host test above, where both sides run the same program shape.)"""
    from marl_distributedformation_tpu.train.sebulba import SebulbaDriver

    anakin = _pursuit_trainer(tmp_path / "anakin")
    sebulba = _pursuit_trainer(
        tmp_path / "sebulba", cls=SebulbaDriver, architecture="sebulba"
    )
    for i in range(2):
        a = jax.device_get(anakin.run_iteration())
        s = jax.device_get(sebulba.run_lockstep_iteration())
        assert set(a) == set(s)
        for name in a:
            np.testing.assert_allclose(
                np.asarray(s[name]),
                np.asarray(a[name]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"metric {name!r} diverges at iteration {i}",
            )
    assert anakin.num_timesteps == sebulba.num_timesteps
    for a, s in zip(
        jax.tree_util.tree_leaves(
            jax.device_get(anakin.train_state.params)
        ),
        jax.tree_util.tree_leaves(
            jax.device_get(sebulba.train_state.params)
        ),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(s), rtol=1e-5, atol=1e-7
        )


def test_pursuit_full_loop_train_eval_gate_serve(tmp_path):
    """The ISSUE's end-to-end pin: fused pursuit training writes real
    checkpoints; eval restores and scores them; the PromotionGate's
    MatrixProgram judges them with ONE compile across candidates; the
    serving rung ladder compiles once per bucket (RetraceGuard budget 1
    — a second trace would raise, not just fail a count check)."""
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.eval import evaluate_checkpoint
    from marl_distributedformation_tpu.pipeline import (
        GateConfig,
        PromotionGate,
    )
    from marl_distributedformation_tpu.serving import BucketedPolicyEngine

    log_dir = tmp_path / "run"
    per_iter = 4 * PURSUIT.num_agents * PPO.n_steps
    trainer = _pursuit_trainer(
        log_dir,
        checkpoint=True,
        fused_chunk=2,
        total_timesteps=4 * per_iter,
        save_freq=5,
    )
    trainer.train()
    assert trainer.retrace_guard.count == 1  # one fused program, ever
    ckpts = sorted(
        (log_dir / "logs").glob("**/rl_model_*_steps.msgpack"),
        key=checkpoint_step,
    )
    assert len(ckpts) >= 2

    # Eval restores the checkpoint against PURSUIT params (env-generic
    # dispatch inside run_episode_metrics) and scores finitely.
    scores = evaluate_checkpoint(str(ckpts[-1]), PURSUIT, num_formations=8)
    assert all(np.isfinite(v) for v in scores.values())
    assert "episode_return_per_agent" in scores

    # The gate: bootstrap candidate passes, and the SECOND candidate
    # reuses the compiled MatrixProgram (budget-1 across candidates).
    gate = PromotionGate(
        PURSUIT,
        GateConfig(
            scenarios=("wind",),
            severities=(1.0,),
            eval_formations=8,
            clean_tolerance=10.0,
            rung_tolerance=10.0,
        ),
    )
    verdict = gate.evaluate(ckpts[0])
    assert verdict.passed, verdict.reasons
    assert verdict.eval_compiles == 1
    verdict2 = gate.evaluate(ckpts[-1])
    assert verdict2.passed, verdict2.reasons
    assert gate.program.compile_count == 1

    # Serving: the promoted pursuit policy rides the bucketed ladder —
    # obs-row in, actions out, one compile per rung across a mixed
    # stream (including the above-top-rung split path).
    pol = LoadedPolicy.from_checkpoint(
        ckpts[-1], act_dim=PURSUIT.act_dim, env_params=PURSUIT
    )
    engine = BucketedPolicyEngine(
        pol, buckets=(1, 8), max_traces_per_bucket=1
    )
    rng = np.random.default_rng(0)
    for n in (1, 3, 8, 9, 1, 8):
        obs = rng.standard_normal((n, PURSUIT.obs_dim)).astype(np.float32)
        actions = engine.act(obs, deterministic=True)
        assert actions.shape == (n, PURSUIT.act_dim)
        assert np.abs(actions).max() <= 1.0 + 1e-6
    assert engine.compile_counts() == {1: 1, 8: 1}


# ---------------------------------------------------------------------------
# Config plumbing: env= selects the registered env everywhere
# ---------------------------------------------------------------------------


def test_env_key_selects_registered_params_class():
    from marl_distributedformation_tpu.utils import (
        env_params_from_config,
        load_config,
    )

    cfg = load_config([])
    assert type(env_params_from_config(cfg)) is EnvParams  # default
    cfg = load_config(["env=pursuit_evasion", "pursuer_speed=9.0"])
    params = env_params_from_config(cfg)
    assert type(params) is PursuitParams
    assert params.pursuer_speed == pytest.approx(9.0)


def test_override_validation_is_env_aware():
    from marl_distributedformation_tpu.utils.config import (
        validate_override_keys,
    )

    # Env-specific knobs validate only under the env that declares them.
    validate_override_keys(["env=pursuit_evasion", "capture_radius=25"])
    with pytest.raises(SystemExit, match="capture_radius"):
        validate_override_keys(["capture_radius=25"])
    # A mistyped env name fails with the registry's did-you-mean.
    with pytest.raises(SystemExit, match="pursuit_evasion"):
        validate_override_keys(["env=pursuit_evsion"])
