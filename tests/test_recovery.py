"""Self-healing train lane (train/recovery.py, docs/recovery.md).

The acceptance pins (ISSUE 15): healthy runs are BITWISE identical
health ON vs OFF (host-loop and fused) with budget-1 compile receipts
holding; the in-program skip guard contains a single poisoned iteration
mid-chunk; a NaN bomb mid-fused-run is detected within one chunk drain,
rolls back to last-good, and finishes with finite params while no
non-finite checkpoint ever becomes visible to discovery; the
post-rollback retry stream is a bit-exact pure function of (checkpoint,
recovery index); recovery.jsonl round-trips its schema; and both sweep
drivers carry the health flags through their drain seams.
"""

import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.chaos import (
    FaultSchedule,
    FaultSpec,
    check_finite_checkpoints,
    check_recovery_log,
    get_fault_plane,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.train import (
    HealthConfig,
    RecoveryConfig,
    RecoveryLadder,
    SweepTrainer,
    TrainConfig,
    Trainer,
    fold_recovery_key,
    make_fused_chunk,
    make_health_iteration,
    read_recovery_log,
)
from marl_distributedformation_tpu.train.recovery import (
    HEALTH_ALL,
    scale_injected_lr,
)
from marl_distributedformation_tpu.utils import (
    msgpack_restore_file,
    prune_checkpoints,
)

PPO = PPOConfig(n_steps=4, batch_size=24, n_epochs=2)


def make_trainer(tmp_path, name="run", **overrides):
    defaults = dict(
        num_formations=4,
        checkpoint=False,
        seed=0,
        name=name,
        log_dir=str(tmp_path / name),
        log_interval=1,
    )
    defaults.update(overrides)
    return Trainer(
        EnvParams(num_agents=3), ppo=PPO, config=TrainConfig(**defaults)
    )


def assert_params_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def assert_params_finite(params):
    for leaf in jax.tree_util.tree_leaves(jax.device_get(params)):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.isfinite(arr).all()


@pytest.fixture(autouse=True)
def _clean_plane():
    plane = get_fault_plane()
    plane.reset()
    plane.enabled = False
    yield
    plane.reset()
    plane.enabled = False


# ---------------------------------------------------------------------------
# Bitwise health ON == OFF on healthy runs (the acceptance pin)
# ---------------------------------------------------------------------------


def test_health_on_bitwise_matches_off_host_loop(tmp_path):
    off = make_trainer(tmp_path, "off")
    on = make_trainer(tmp_path, "on", health=True)
    for _ in range(3):
        m_off = jax.device_get(off.run_iteration())
        m_on = jax.device_get(on.run_iteration())
        # Shared metrics bitwise equal too — the word is a side
        # computation, never a perturbation.
        for name, v in m_off.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(m_on[name])
            )
        assert float(m_on["health_ok"]) == 1.0
        assert float(m_on["health_word"]) == HEALTH_ALL
    assert_params_equal(off.train_state.params, on.train_state.params)


def test_health_on_bitwise_matches_off_fused_budget_one(tmp_path):
    off = make_trainer(tmp_path, "off", fused_chunk=3)
    on = make_trainer(tmp_path, "on", fused_chunk=3, health=True)
    s_off = jax.device_get(off.run_chunk())
    s_on = jax.device_get(on.run_chunk())
    for name, v in s_off.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(s_on[name]))
    np.testing.assert_array_equal(s_on["health_ok"], np.ones(3, np.float32))
    assert_params_equal(off.train_state.params, on.train_state.params)
    # Budget-1 compile receipt with health ON: the word adds reductions
    # and selects to the ONE program, never a program of its own.
    assert on.retrace_guard.count == 1
    jax.device_get(on.run_chunk())
    assert on.retrace_guard.count == 1


# ---------------------------------------------------------------------------
# The in-program skip guard (unit, on a toy iteration)
# ---------------------------------------------------------------------------


def _toy_state(value=1.0):
    return TrainState.create(
        apply_fn=lambda *a: None,
        params={"w": jnp.full((3,), value, jnp.float32)},
        tx=optax.sgd(0.0),
    )


def test_skip_guard_contains_single_poisoned_iteration_mid_chunk():
    """Iteration x==2 of a 5-chunk returns NaN params; the guard must
    carry the pre-iteration state through it and the other four
    iterations must land exactly — final w == 1 + 4, flags 1,1,0,1,1."""

    def toy_iteration(ts, env, obs, key, x):
        poisoned = x == 2
        w = ts.params["w"]
        new_w = jnp.where(poisoned, w * jnp.float32(float("nan")), w + 1.0)
        new_ts = ts.replace(params={"w": new_w}, step=ts.step + 1)
        key = jax.random.fold_in(key, 1)
        metrics = {
            "loss": new_w.sum(),
            "grad_norm": jnp.float32(1.0),
        }
        return new_ts, env + 1, obs, key, metrics

    fused = make_fused_chunk(
        make_health_iteration(toy_iteration, HealthConfig()), 5
    )
    ts, env, obs, key = (
        _toy_state(),
        jnp.int32(0),
        jnp.zeros((2,)),
        jax.random.PRNGKey(0),
    )
    out_ts, out_env, _, _, stacked = jax.jit(fused)(
        ts, env, obs, key, jnp.arange(5)
    )
    np.testing.assert_array_equal(
        np.asarray(stacked["health_ok"]),
        np.asarray([1.0, 1.0, 0.0, 1.0, 1.0], np.float32),
    )
    # 4 healthy +1 steps; the poisoned one applied the identity update.
    np.testing.assert_array_equal(
        np.asarray(out_ts.params["w"]), np.full((3,), 5.0, np.float32)
    )
    # The whole carry reverts on a flagged iteration (env counter too),
    # and TrainState.step only advances on committed updates.
    assert int(out_env) == 4
    assert int(out_ts.step) == 4


def test_health_word_decodes_failure_modes():
    """Each failure mode clears exactly its bits: NaN loss, finite-but-
    unbounded grad norm, param-drift blowup."""

    def make_toy(loss_value, grad_value, scale):
        def toy(ts, env, obs, key):
            new_w = ts.params["w"] * jnp.float32(scale)
            new_ts = ts.replace(params={"w": new_w})
            metrics = {
                "loss": jnp.float32(loss_value),
                "grad_norm": jnp.float32(grad_value),
            }
            return new_ts, env, obs, key, metrics

        return toy

    def run(toy):
        wrapped = make_health_iteration(toy, HealthConfig())
        _, _, _, _, m = jax.jit(wrapped)(
            _toy_state(),
            jnp.int32(0),
            jnp.zeros((2,)),
            jax.random.PRNGKey(0),
        )
        return int(m["health_word"]), float(m["health_ok"])

    assert run(make_toy(1.0, 1.0, 1.0)) == (15, 1.0)
    # NaN loss: loss bit clear (grad/drift fine).
    assert run(make_toy(float("nan"), 1.0, 1.0)) == (14, 0.0)
    # Finite-but-unbounded grad norm: only the bounded bit clears.
    assert run(make_toy(1.0, 1.0e9, 1.0)) == (11, 0.0)
    # Param blowup: drift bit clears.
    assert run(make_toy(1.0, 1.0, 1.0e9)) == (7, 0.0)
    # NaN params: drift clears via isfinite(p_new).
    assert run(make_toy(1.0, 1.0, float("nan"))) == (7, 0.0)


# ---------------------------------------------------------------------------
# The e2e: NaN bomb -> detect within one drain -> rollback -> finite finish
# ---------------------------------------------------------------------------

PER_ITER = 4 * 4 * 3  # n_steps * M * N


def _bomb_run(tmp_path, name, at_hit=4, iterations=12, **overrides):
    cfg = dict(
        checkpoint=True,
        save_freq=4,  # two chunks' vec-steps >= save_freq: save per chunk
        fused_chunk=2,
        total_timesteps=iterations * PER_ITER,
        health=True,
        recovery=True,
        recovery_breach_iters=2,
        log_interval=1000,  # quiet
    )
    cfg.update(overrides)
    trainer = make_trainer(tmp_path, name, **cfg)
    plane = get_fault_plane()
    plane.arm(
        FaultSchedule([FaultSpec("train.carry_poison", "raise", at_hit)])
    )
    plane.enabled = True
    trainer.train()
    plane.enabled = False
    return trainer


def test_nan_bomb_rollback_finite_finish_e2e(tmp_path):
    trainer = _bomb_run(tmp_path, "bomb")
    log_dir = tmp_path / "bomb"
    assert not trainer.halted
    assert trainer.num_timesteps == 12 * PER_ITER  # full budget trained
    assert_params_finite(trainer.train_state.params)
    ladder = trainer.recovery_ladder
    assert ladder.recoveries == 1
    assert ladder.breaches == 1
    # Budget-1 receipts held through poison + rollback.
    assert trainer.retrace_guard.count == 1
    events = read_recovery_log(log_dir / "recovery.jsonl")
    kinds = [e["event"] for e in events]
    assert kinds == ["skip", "rollback"]
    skip, rollback = events
    # Detection within ONE chunk drain: the bomb poisons dispatch 4
    # (iterations 6-7 with chunk=2); its drain logs the skip at
    # first_iteration 6 and the rollback lands while the NEXT chunk is
    # in flight.
    assert skip["iteration"] == 6
    assert skip["skipped"] == 2
    assert rollback["iteration"] - skip["iteration"] == 2
    assert rollback["mttr_s"] > 0.0
    # Zero non-finite checkpoints ever visible to discovery.
    assert check_finite_checkpoints(log_dir) == []
    assert check_recovery_log(
        log_dir / "recovery.jsonl", max_rollbacks=3, mttr_bound_s=60.0
    ) == []
    # The poisoned chunk's save was gated/skipped, never published.
    for p in log_dir.glob("rl_model_*.msgpack"):
        tree = msgpack_restore_file(p)
        for leaf in jax.tree_util.tree_leaves(tree["params"]):
            assert np.isfinite(np.asarray(leaf)).all(), p


def test_rollback_retry_is_bit_exact_resume(tmp_path):
    """The post-rollback stream is a pure function of (last-good
    checkpoint, recovery index): a fresh trainer resumed from that
    checkpoint with the same folded key reproduces run A's post-bomb
    trajectory bitwise."""
    a = _bomb_run(tmp_path, "a")
    events = read_recovery_log(tmp_path / "a" / "recovery.jsonl")
    rollback = [e for e in events if e["event"] == "rollback"][0]
    assert rollback["checkpoint"] is not None
    # Run B: a COPY of only the rollback target, resumed cold.
    b_dir = tmp_path / "b"
    b_dir.mkdir()
    src = rollback["checkpoint"]
    shutil.copyfile(src, b_dir / src.split("/")[-1])
    b = make_trainer(
        tmp_path,
        "b",
        checkpoint=False,
        resume=True,
        fused_chunk=2,
        total_timesteps=12 * PER_ITER,
        health=True,
        log_interval=1000,
    )
    assert b.num_timesteps == rollback["to_step"]
    # The manual spelling of what the ladder did: recovery #1's fold.
    b.key = fold_recovery_key(b.key, 1)
    b.train()
    assert b.num_timesteps == a.num_timesteps
    assert_params_equal(a.train_state.params, b.train_state.params)


def test_grad_bomb_quarantines_poisoned_rollback_target(tmp_path):
    """A FINITE 1e18 bomb beats the non-finite write gate into one
    checkpoint (detection lags a chunk); the ladder must quarantine
    that file when the first rollback re-diverges, walk further back,
    and still finish finite without burning the budget."""
    trainer = make_trainer(
        tmp_path,
        "gb",
        checkpoint=True,
        save_freq=4,
        fused_chunk=2,
        total_timesteps=14 * PER_ITER,
        health=True,
        recovery=True,
        recovery_breach_iters=2,
        recovery_max_rollbacks=6,
        log_interval=1000,
    )
    plane = get_fault_plane()
    plane.arm(FaultSchedule([FaultSpec("train.grad_bomb", "raise", 4)]))
    plane.enabled = True
    trainer.train()
    plane.enabled = False
    assert not trainer.halted
    assert_params_finite(trainer.train_state.params)
    ladder = trainer.recovery_ladder
    # Rollback 1 restores the poisoned-but-finite file; rollback 2
    # quarantines it and lands on a clean one; probation keeps the
    # suspect window from minting fresh poisoned checkpoints.
    assert ladder.recoveries == 2
    quarantined = list((tmp_path / "gb").glob("*.quarantined"))
    assert len(quarantined) == 1
    assert check_finite_checkpoints(tmp_path / "gb") == []


def test_host_loop_bomb_rollback_finite_finish(tmp_path):
    """The HOST-LOOP driver's ladder integration: flags observed at the
    log sync, rollback restores, run finishes finite."""
    trainer = make_trainer(
        tmp_path,
        "hl",
        checkpoint=True,
        save_freq=4,
        total_timesteps=12 * PER_ITER,
        health=True,
        recovery=True,
        recovery_breach_iters=2,
        log_interval=1,
    )
    plane = get_fault_plane()
    plane.arm(
        FaultSchedule([FaultSpec("train.carry_poison", "raise", 4)])
    )
    plane.enabled = True
    trainer.train()
    plane.enabled = False
    assert not trainer.halted
    assert trainer.num_timesteps == 12 * PER_ITER
    assert_params_finite(trainer.train_state.params)
    assert trainer.recovery_ladder.recoveries == 1
    assert check_finite_checkpoints(tmp_path / "hl") == []


def test_host_loop_unobserved_tail_poison_still_ends_finite(tmp_path):
    """A bomb the host loop never OBSERVES (log_interval past the run,
    save cadence never reached) must still end on finite params — the
    run-end guarantee, host-loop flavor — and the suspect final save
    must not publish the poison."""
    trainer = make_trainer(
        tmp_path,
        "tail",
        checkpoint=True,
        save_freq=10_000,  # no mid-run saves, no save-cadence observe
        total_timesteps=8 * PER_ITER,
        health=True,
        recovery=True,
        recovery_breach_iters=2,
        log_interval=1000,  # no log-cadence observe either
    )
    plane = get_fault_plane()
    plane.arm(
        FaultSchedule([FaultSpec("train.carry_poison", "raise", 3)])
    )
    plane.enabled = True
    trainer.train()
    plane.enabled = False
    assert_params_finite(trainer.train_state.params)
    # The terminal restore counts as a rollback (the guarantee may
    # exceed the retry budget by one) and no poisoned file is visible.
    assert trainer.recovery_ladder.recoveries == 1
    assert check_finite_checkpoints(tmp_path / "tail") == []


def test_recovery_log_rotates_per_process(tmp_path):
    first = RecoveryLadder(RecoveryConfig(), tmp_path)
    first.observe([0.0] * 3, None, 0)
    assert len(read_recovery_log(tmp_path / "recovery.jsonl")) == 1
    # A second ladder (a resumed run) starts a FRESH file; the old
    # history rotates aside so the per-run validator semantics hold.
    second = RecoveryLadder(RecoveryConfig(), tmp_path)
    assert read_recovery_log(tmp_path / "recovery.jsonl") == []
    assert list(tmp_path.glob("recovery.jsonl.*"))
    second.observe([0.0] * 3, None, 0)
    assert check_recovery_log(tmp_path / "recovery.jsonl") == []


def test_halt_after_rollback_budget_exhausted(tmp_path):
    trainer = _bomb_run(
        tmp_path, "halt", recovery_max_rollbacks=0, iterations=12
    )
    assert trainer.halted
    assert trainer.recovery_ladder.halted
    # Halted short of the budget, ON finite params (restored).
    assert trainer.num_timesteps < 12 * PER_ITER
    assert_params_finite(trainer.train_state.params)
    events = read_recovery_log(tmp_path / "halt" / "recovery.jsonl")
    assert events[-1]["event"] == "halt"
    assert check_recovery_log(tmp_path / "halt" / "recovery.jsonl") == []


def test_lr_backoff_applies_to_injected_rate(tmp_path):
    trainer = _bomb_run(
        tmp_path, "lr", recovery_lr_backoff=0.5, iterations=12
    )
    assert trainer.recovery_ladder.recoveries == 1

    rates = []

    def visit(path, leaf):
        if any(
            getattr(e, "key", getattr(e, "name", None)) == "learning_rate"
            for e in path
        ):
            rates.append(np.asarray(leaf))
        return leaf

    jax.tree_util.tree_map_with_path(visit, trainer.train_state.opt_state)
    assert rates, "recovery_lr_backoff != 1.0 must inject the rate"
    np.testing.assert_allclose(
        float(rates[0]), 0.5 * PPO.learning_rate, rtol=1e-6
    )
    events = read_recovery_log(tmp_path / "lr" / "recovery.jsonl")
    rollback = [e for e in events if e["event"] == "rollback"][0]
    assert rollback["lr_scale"] == 0.5


def test_scale_injected_lr_unit():
    injected = PPO.make_optimizer(inject_lr=True)
    state = injected.init({"w": jnp.ones(3)})
    scaled = scale_injected_lr(state, 0.25)
    assert scaled is not None
    found = []
    jax.tree_util.tree_map_with_path(
        lambda p, leaf: found.append(np.asarray(leaf))
        if any(
            getattr(e, "key", getattr(e, "name", None)) == "learning_rate"
            for e in p
        )
        else None,
        scaled,
    )
    np.testing.assert_allclose(
        float(found[0]), 0.25 * PPO.learning_rate, rtol=1e-6
    )
    # A plain (baked-in lr) opt state has nothing to scale.
    plain = PPO.make_optimizer().init({"w": jnp.ones(3)})
    assert scale_injected_lr(plain, 0.25) is None


def test_fold_recovery_key_streams_are_distinct():
    key = jax.random.PRNGKey(7)
    streams = {
        tuple(np.asarray(jax.random.key_data(k)).tolist())
        for k in (
            key,
            fold_recovery_key(key, 1),
            fold_recovery_key(key, 2),
            fold_recovery_key(key, 3),
        )
    }
    assert len(streams) == 4


# ---------------------------------------------------------------------------
# recovery.jsonl schema round-trip
# ---------------------------------------------------------------------------


def test_recovery_jsonl_schema_round_trip(tmp_path):
    ladder = RecoveryLadder(
        RecoveryConfig(breach_iters=2, max_rollbacks=1), tmp_path
    )
    assert ladder.observe([1.0, 1.0], [15.0, 15.0], 0) == "ok"
    assert ladder.observe([1.0, 0.0], [15.0, 6.0], 2) == "ok"  # 1 skip
    assert ladder.observe([0.0, 0.0], [0.0, 0.0], 4) == "rollback"
    ladder.note_rollback(
        to_step=120, path=str(tmp_path / "x.msgpack"), mttr_s=0.05,
        iteration=6,
    )
    assert ladder.suspect  # probation until a healthy chunk
    assert ladder.observe([1.0, 1.0], [15.0, 15.0], 6) == "ok"
    assert not ladder.suspect
    assert ladder.observe([0.0, 0.0], [0.0, 0.0], 8) == "halt"
    ladder.note_halt(10, "budget exhausted")
    assert ladder.observe([0.0, 0.0], None, 12) == "halt"  # latched
    events = read_recovery_log(tmp_path / "recovery.jsonl")
    assert [e["event"] for e in events] == [
        "skip", "skip", "rollback", "skip", "halt",
    ]
    assert events[1]["health_word_min"] == 0
    assert events[2]["recoveries"] == 1
    # 1 + 2 + 2 skips counted; the post-halt observation is latched
    # out (the ladder is terminal, nothing more accumulates).
    assert ladder.skipped_total == 5
    assert check_recovery_log(tmp_path / "recovery.jsonl") == []
    # The reader REJECTS schema drift, line-addressed.
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"time": 1.0, "event": "rollback", "iteration": 0}\n')
    with pytest.raises(ValueError, match="missing required"):
        read_recovery_log(bad)
    bad.write_text('{"time": 1.0, "event": "explode"}\n')
    with pytest.raises(ValueError, match="unknown recovery event"):
        read_recovery_log(bad)
    bad.write_text("not json\n")
    with pytest.raises(ValueError, match="unparseable"):
        read_recovery_log(bad)


# ---------------------------------------------------------------------------
# The non-finite write gate + retention ring
# ---------------------------------------------------------------------------


def test_nonfinite_checkpoint_write_gate(tmp_path):
    from marl_distributedformation_tpu.utils import AsyncCheckpointWriter

    trainer = make_trainer(tmp_path, "gate", checkpoint=True)
    trainer._poison_carry(float("nan"))
    assert trainer.save() is None  # gate refused; audited, not raised
    assert list((tmp_path / "gate").glob("rl_model_*.msgpack")) == []
    # Async path: skip-with-audit, never a dead run.
    writer = AsyncCheckpointWriter()
    trainer.save_async(writer)
    writer.close()  # must NOT raise
    assert writer.writes_skipped == 1
    assert list((tmp_path / "gate").glob("rl_model_*.msgpack")) == []
    from marl_distributedformation_tpu.obs import get_registry

    assert (
        get_registry().snapshot().get("checkpoint_nonfinite_skipped_total", 0)
        >= 2
    )


def test_retention_ring_prunes_and_protects(tmp_path):
    d = tmp_path / "ring"
    d.mkdir()
    for step in (100, 200, 300, 400, 500):
        (d / f"rl_model_{step}_steps.msgpack").write_bytes(b"x")
    (d / "rl_model_50_steps.msgpack.quarantined").write_bytes(b"x")
    (d / "sweep_state_100_steps.msgpack").write_bytes(b"x")
    (d / "recovery.jsonl").write_text("")
    pruned = prune_checkpoints(
        d, 2, protect=[d / "rl_model_100_steps.msgpack"]
    )
    assert sorted(p.name for p in pruned) == [
        "rl_model_200_steps.msgpack",
        "rl_model_300_steps.msgpack",
    ]
    remaining = sorted(p.name for p in d.iterdir())
    # Newest 2 kept, the protected last-good target survives despite
    # being the OLDEST, quarantine evidence + sweep anchors + audit
    # logs untouched.
    assert set(remaining) == {
        "recovery.jsonl",
        "rl_model_100_steps.msgpack",
        "rl_model_400_steps.msgpack",
        "rl_model_500_steps.msgpack",
        "rl_model_50_steps.msgpack.quarantined",
        "sweep_state_100_steps.msgpack",
    }
    assert prune_checkpoints(d, 0) == []  # 0 = unbounded, no-op


def test_trainer_retention_ring_end_to_end(tmp_path):
    trainer = make_trainer(
        tmp_path,
        "ring",
        checkpoint=True,
        save_freq=4,
        fused_chunk=2,
        total_timesteps=12 * PER_ITER,
        keep_last_n=3,
        log_interval=1000,
    )
    trainer.train()
    ckpts = sorted((tmp_path / "ring").glob("rl_model_*.msgpack"))
    assert len(ckpts) == 3
    # The newest survived (the final save).
    steps = sorted(
        int(p.name.split("_")[2]) for p in ckpts
    )
    assert steps[-1] == trainer.num_timesteps


# ---------------------------------------------------------------------------
# Sweep-driver drain-seam pins
# ---------------------------------------------------------------------------


def test_sweep_drain_seam_health_pins(tmp_path):
    def sweep(name, health):
        return SweepTrainer(
            EnvParams(num_agents=3),
            ppo=PPO,
            config=TrainConfig(
                num_formations=4,
                checkpoint=False,
                seed=0,
                name=name,
                log_dir=str(tmp_path / name),
                fused_chunk=2,
                health=health,
            ),
            num_seeds=2,
        )

    off = sweep("s_off", False)
    on = sweep("s_on", True)
    s_off = jax.device_get(off.run_chunk())
    s_on = jax.device_get(on.run_chunk())
    # Per-member flags stacked (chunk, members) ride the drain.
    assert s_on["health_ok"].shape == (2, 2)
    np.testing.assert_array_equal(
        s_on["health_ok"], np.ones((2, 2), np.float32)
    )
    for name, v in s_off.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(s_on[name]))
    assert_params_equal(off.train_state.params, on.train_state.params)
    # The drain seam consumes them without touching the aggregate
    # contract (population_aggregate means the flags like any metric).
    from marl_distributedformation_tpu.obs import get_registry

    before = get_registry().snapshot().get(
        "train_skipped_updates_total", 0
    )
    on._drain_chunk(_NullLogger(), _NullMeter(), on.run_chunk(), 2, 0)
    after = get_registry().snapshot().get("train_skipped_updates_total", 0)
    assert after == before  # healthy chunk: zero skips recorded


def test_hetero_sweep_health_flags(tmp_path):
    from marl_distributedformation_tpu.train import (
        Curriculum,
        CurriculumStage,
        HeteroSweepTrainer,
    )

    def hs(name, health):
        t = HeteroSweepTrainer(
            curriculum=Curriculum(
                stages=(CurriculumStage(rollouts=2, agent_counts=(3,)),)
            ),
            env_params=EnvParams(num_agents=3),
            ppo=PPO,
            config=TrainConfig(
                num_formations=4,
                checkpoint=False,
                seed=0,
                name=name,
                log_dir=str(tmp_path / name),
                fused_chunk=2,
                health=health,
            ),
            num_seeds=2,
        )
        t.start_stage(t.curriculum.stages[0])
        return t

    off = hs("h_off", False)
    on = hs("h_on", True)
    s_off = jax.device_get(off.run_chunk())
    s_on = jax.device_get(on.run_chunk())
    assert s_on["health_ok"].shape == (2, 2)
    np.testing.assert_array_equal(
        s_on["health_ok"], np.ones((2, 2), np.float32)
    )
    for name, v in s_off.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(s_on[name]))
    assert_params_equal(off.train_state.params, on.train_state.params)


class _NullLogger:
    def log(self, *a, **k):
        pass

    def close(self):
        pass


class _NullMeter:
    def tick(self, *a):
        pass

    def rate(self):
        return 0.0
