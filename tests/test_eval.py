"""Evaluation harness (eval.py / evaluate.py): episode accounting and the
policy-vs-baseline comparison contract."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.eval import (
    baseline_act_fn,
    episode_length,
    evaluate,
    policy_act_fn,
    zero_act_fn,
)


def short_params(**kw):
    return EnvParams(num_agents=4, max_steps=30, **kw)


def test_episode_length_parity_modes():
    assert episode_length(short_params()) == 32  # Q1 off-by-one
    assert episode_length(short_params(strict_parity=False)) == 30


@pytest.mark.parametrize("strict", [True, False])
def test_exactly_one_episode_and_pre_reset_final_metrics(strict):
    """Every formation finishes exactly one episode, and the reported
    final metrics come from the last pre-reset step (the done row's
    metrics describe a fresh formation — reference step order,
    simulate.py:113-117)."""
    params = short_params(strict_parity=strict)
    out = evaluate(zero_act_fn(), params, num_formations=8, seed=5)
    assert out["episodes"] == 8.0
    # Zero actions: agents spawn in the bottom strip, goal is far — the
    # pre-reset distance must reflect that scattered start, not a
    # post-reset re-randomization that could accidentally be closer.
    assert out["final_avg_dist_to_goal"] > 100.0


def test_baseline_beats_zero_actions():
    # N=10, the reference's own demo size (simulate.py:324). At very small
    # N the scripted controller's radius-40 spacing (Q11) lands deep in the
    # reward's quadratic too-close penalty and actually scores WORSE than
    # zero actions — e.g. N=4: spacing 31.4 vs desired 84.9 is ~-57/step.
    params = EnvParams(num_agents=10, max_steps=300)
    base = evaluate(baseline_act_fn(params), params, num_formations=8)
    zero = evaluate(zero_act_fn(), params, num_formations=8)
    assert (
        base["episode_return_per_agent"] > zero["episode_return_per_agent"]
    )
    assert base["final_avg_dist_to_goal"] < zero["final_avg_dist_to_goal"]


def test_policy_act_fn_scales_and_clips():
    """The policy ActFn applies the L1 adapter semantics: mode action
    clipped to [-1, 1] then scaled by max_speed (vectorized_env.py:69-70)."""

    class HugeMean:
        per_formation = False

        def apply(self, params, obs):
            mean = jnp.full((obs.shape[0], 2), 7.0)
            return mean, jnp.zeros(2), jnp.zeros(obs.shape[0])

    params = short_params()
    act = policy_act_fn(HugeMean(), {}, params)
    obs = jnp.zeros((3, params.num_agents, params.obs_dim))
    vel = act(None, None, None, obs, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(vel), params.max_speed)


def test_policy_act_fn_stochastic_samples():
    """deterministic=False samples mean + exp(log_std)·eps (SB3's
    evaluate_policy knob); the sample is key-driven and clipped before
    max_speed scaling."""

    class ZeroMeanWideStd:
        per_formation = False

        def apply(self, params, obs):
            mean = jnp.zeros((obs.shape[0], 2))
            return mean, jnp.full(2, -1.0), jnp.zeros(obs.shape[0])

    params = short_params()
    act = policy_act_fn(ZeroMeanWideStd(), {}, params, deterministic=False)
    obs = jnp.zeros((3, params.num_agents, params.obs_dim))
    v1 = act(None, None, None, obs, jax.random.PRNGKey(0))
    v2 = act(None, None, None, obs, jax.random.PRNGKey(0))
    v3 = act(None, None, None, obs, jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))  # key-driven
    assert np.abs(np.asarray(v1) - np.asarray(v3)).max() > 0  # varies by key
    assert np.abs(np.asarray(v1)).max() <= params.max_speed  # clipped
    # std = e^-1 ~ 0.37: samples are non-degenerate around the zero mean
    assert np.abs(np.asarray(v1)).max() > 0


def test_evaluate_cli_roundtrip(tmp_path, capsys):
    """evaluate.py discovers the latest checkpoint of a run directory
    (``log_dir=``, the key train.py wrote it under — never ``logs/`` in
    the checkout) and emits the machine-readable JSON line with the
    comparison fields and the device it ran on."""
    import sys

    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import evaluate as evaluate_cli
    import train as train_cli

    run = tmp_path / "evalrun"
    trained = train_cli.main(
        [
            "name=evalrun",
            f"log_dir={run}",
            "num_formation=4",
            "total_timesteps=800",
            "max_steps=20",
            "strict_parity=false",
        ]
    )
    assert trained["log_dir"] == str(run)
    assert (run / "config.json").exists()
    result = evaluate_cli.main(
        [
            "name=evalrun",
            f"log_dir={run}",
            "eval_formations=4",
            "max_steps=20",
            "strict_parity=false",
        ]
    )
    out = capsys.readouterr().out
    # Both entry points name their device first...
    assert out.splitlines()[0].startswith("[train] device: platform=cpu")
    assert "[eval] device: platform=cpu" in out
    last_json = json.loads(out.strip().splitlines()[-1])
    # ... and in their result.
    for rec in (trained, last_json):
        assert rec["platform"] == "cpu"
        assert rec["device_kind"] and rec["device_count"] == 8
    for key in (
        "policy_episode_return_per_agent",
        "baseline_episode_return_per_agent",
        "zero_episode_return_per_agent",
        "beats_baseline",
    ):
        assert key in last_json, key
    assert result["eval_formations"] == 4


@pytest.mark.slow
def test_evaluate_cli_sweep_mode(tmp_path, capsys):
    """name= pointing at a sweep run evaluates every member and ranks by
    held-out return."""
    import sys

    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import evaluate as evaluate_cli
    import train as train_cli

    train_cli.main(
        [
            "name=evalsweep",
            f"log_dir={tmp_path / 'evalsweep'}",
            "num_seeds=2",
            "num_formation=4",
            "total_timesteps=720",
            "n_steps=4",
            "batch_size=24",
            "n_epochs=2",
            "max_steps=20",
            "num_agents_per_formation=3",
            "strict_parity=false",
        ]
    )
    result = evaluate_cli.main(
        [
            "name=evalsweep",
            f"log_dir={tmp_path / 'evalsweep'}",
            "eval_formations=4",
            "max_steps=20",
            "num_agents_per_formation=3",
            "strict_parity=false",
        ]
    )
    assert result["sweep_members"] == 2
    assert set(result["member_returns"]) == {"seed0", "seed1"}
    assert result["best_member"] in ("seed0", "seed1")
    assert "baseline_return" in result
