"""The trunk policy (``models/trunk.py``, ``policy=trunk trunk=<name>``)
held to its plain reference (``benchmarks/reference/policy_trunk.py``) on
seeded weights at the size of ``cfg/trunk/tiny.yaml``: hidden 64, 4 query
and 2 key heads of 16, an indexer of 2 heads x 8 with ``topk`` 8, 8 experts
top-2 of which 2 are held, 2 layers, swarms of 32.

Tolerances: both sides compute in float32 on the CPU and differ by the
order of their sums alone. Outputs are compared to 1e-5 of the largest
entry (observed 1e-7), gradients to 1e-4 of each leaf's largest entry
(observed 1e-6: a gradient sums over 96 tokens x 2 layers). Selections are
compared exactly: a score would have to tie to 1e-7 for a rounding to
re-rank it, and the seeds here have no such pair.
"""

import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks import harness
from benchmarks.reference import policy_trunk as reference
from benchmarks.reference import ppo as reference_ppo
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.algo.ppo import MinibatchData, ppo_loss
from marl_distributedformation_tpu.models import trunk
from marl_distributedformation_tpu.models.trunk import TrunkActorCritic, TrunkArch
from marl_distributedformation_tpu.utils.config import _parse_value

ROOT = Path(__file__).resolve().parents[1]
TINY = yaml.safe_load((ROOT / "cfg" / "trunk" / "tiny.yaml").read_text())
POLICY = {"kind": "trunk", "trunk": "tiny", "log_std_init": 0.0, **TINY}
S, K = 32, 4
ENV = {"knn_k": K, "goal_in_obs": True, "num_agents_per_formation": S}
OBS_DIM = 2 + 4 * K + 2
INDEXER_LEAVES = ("idx_wq", "idx_wk", "idx_w", "idx_k_scale", "idx_k_bias")


def _arch(**changes):
    data = {**TINY, **changes}
    if "topk" in changes:
        data["sa_config"] = {**TINY["sa_config"], "topk": changes["topk"]}
    return TrunkArch.from_dict("tiny", data)


def _model(**changes):
    return TrunkActorCritic(arch=_arch(**changes), k=K)


@pytest.fixture(scope="module")
def params():
    return reference.init(jax.random.PRNGKey(0), POLICY, ENV)


@pytest.fixture(scope="module")
def obs():
    return jax.random.uniform(jax.random.PRNGKey(1), (3, S, OBS_DIM))


def _close(a, b, rel):
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * scale)


def test_the_program_reads_the_tree_the_reference_makes(params, obs):
    made = jax.eval_shape(lambda: _model().init(jax.random.PRNGKey(2), obs[:1]))
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(params)
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(params)
    ):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype


def test_forward_and_both_selections_match_the_reference(params, obs):
    """(a) mean and value, and per layer the keys every query selected and
    the experts every token was routed to."""
    mean, log_std, value = jax.jit(_model().apply)(params, obs)
    r_mean, r_log_std, r_value, selections = reference.apply(
        params, POLICY, ENV, obs, collect=True
    )
    _close(mean, r_mean, 1e-5)
    _close(value, r_value, 1e-5)
    np.testing.assert_array_equal(log_std, r_log_std)

    p = params["params"]
    x = obs[..., : 2 + 3 * K + 2] @ p["embed"]["kernel"] + p["embed"]["bias"]
    for i, (r_keys, r_experts) in enumerate(selections):
        lp = jax.tree_util.tree_map(lambda a: a[i], p["layers"])
        x, found = jax.lax.map(
            lambda one: trunk.trunk_layer(one, lp, _arch(), collect=True), x
        )
        np.testing.assert_array_equal(found["selected_keys"], r_keys)
        np.testing.assert_array_equal(found["selected_experts"], r_experts)
        # a query selects min(t + 1, topk) of the keys it can see
        np.testing.assert_array_equal(
            r_keys.sum(-1), np.broadcast_to(np.minimum(np.arange(S) + 1, 8), (3, S))
        )


def test_ppo_loss_gradient_matches_leaf_by_leaf(params, obs):
    """(b) the program's ``ppo_loss`` through its policy against the
    reference's ``loss_fn`` through its own; the indexer's leaves get
    exactly zero on both sides."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    mb = {
        "obs": obs,
        "actions": jax.random.normal(keys[0], (3, S, 2)),
        "log_probs": -2.0 + 0.1 * jax.random.normal(keys[1], (3, S)),
        "advantages": jax.random.normal(keys[2], (3, S)),
        "returns": jax.random.normal(keys[3], (3, S)),
    }
    config = {"policy": POLICY, "env": ENV, "ppo": {
        "normalize_advantage": True, "clip_range": 0.2, "ent_coef": 0.01,
        "vf_coef": 0.5,
    }}
    r_apply = lambda p, x: reference.apply(p, POLICY, ENV, x)  # noqa: E731
    r_loss, r_grads = jax.jit(
        jax.value_and_grad(lambda p: reference_ppo.loss_fn(p, config, r_apply, mb))
    )(params)
    data = MinibatchData(
        obs=mb["obs"], actions=mb["actions"], old_log_probs=mb["log_probs"],
        advantages=mb["advantages"], returns=mb["returns"],
    )
    model = _model()
    (loss, _), grads = jax.jit(
        jax.value_and_grad(
            lambda p: ppo_loss(p, model.apply, data, PPOConfig()), has_aux=True
        )
    )(params)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), theirs in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        name = jax.tree_util.keystr(path)
        if any(leaf in name for leaf in INDEXER_LEAVES):
            assert not np.asarray(ours).any() and not np.asarray(theirs).any(), name
        else:
            assert np.asarray(theirs).any(), name
            _close(ours, theirs, 1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """(c) four chips that hold two of the eight experts each: the parts
    their expert layers give add up to what the reference gives for the
    whole layer."""
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    h2 = jax.random.normal(keys[0], (S, 64))
    router = 0.5 * jax.random.normal(keys[1], (64, 8))
    w_gate = 0.1 * jax.random.normal(keys[2], (8, 64, 32))
    w_up = 0.1 * jax.random.normal(keys[3], (8, 64, 32))
    w_down = 0.1 * jax.random.normal(keys[4], (8, 32, 64))
    e_top, c = reference.route(h2, router, 2, True)
    whole = reference.expert_layer(h2, e_top, c, w_gate, w_up, w_down, range(8))

    total, held_shares = 0.0, []
    for share in range(4):
        held = slice(2 * share, 2 * share + 2)
        ours_top, ours_c = trunk.route(h2, router, 2, True)
        part, counters = trunk.expert_layer(
            h2, ours_top, ours_c, w_gate[held], w_up[held], w_down[held], (share, 4)
        )
        _close(
            part,
            reference.expert_layer(
                h2, e_top, c, w_gate[held], w_up[held], w_down[held], range(8)[held]
            ),
            1e-5,
        )
        total = total + part
        held_shares.append(float(counters["moe_held_share"]))
    _close(total, whole, 1e-5)
    assert sum(held_shares) == pytest.approx(1.0)  # every assignment, once


def test_topk_past_the_swarm_is_dense_causal_attention(params, obs):
    """(d) where every query sees at most ``topk`` keys the selection is
    the causal mask: the indexer's weights change nothing, and the output
    is the reference's with every visible key selected."""
    model = _model(topk=S)
    mean, _, value = jax.jit(model.apply)(params, obs)
    wide = {**POLICY, "sa_config": {**POLICY["sa_config"], "topk": S}}
    r_mean, _, r_value, selections = reference.apply(params, wide, ENV, obs, collect=True)
    _close(mean, r_mean, 1e-5)
    _close(value, r_value, 1e-5)
    causal = np.tril(np.ones((S, S), bool))
    for keys, _ in selections:
        np.testing.assert_array_equal(keys, np.broadcast_to(causal, keys.shape))
    layers = dict(params["params"]["layers"])
    for leaf in INDEXER_LEAVES:
        layers[leaf] = jax.random.normal(jax.random.PRNGKey(5), layers[leaf].shape)
    scrambled = {"params": {**params["params"], "layers": layers}}
    s_mean, _, s_value = jax.jit(model.apply)(scrambled, obs)
    np.testing.assert_array_equal(s_mean, mean)
    np.testing.assert_array_equal(s_value, value)
    # and with topk 8 the indexer does decide
    t_mean, _, _ = jax.jit(_model().apply)(scrambled, obs)
    assert np.abs(np.asarray(t_mean) - np.asarray(mean)).max() > 1e-6


@pytest.mark.parametrize("scores", ["zeros", "few_levels", "random"])
def test_tied_index_scores_select_the_lower_index(scores):
    """(e) the program's bisection and the reference's sorted threshold,
    each with a running count of ties, select what ``jax.lax.top_k``
    selects, tie for tie."""
    key = jax.random.PRNGKey(6)
    index = {
        "zeros": jnp.zeros((2, 16, 48)),
        "few_levels": jax.random.randint(key, (2, 16, 48), -2, 3).astype(jnp.float32),
        "random": jax.random.normal(key, (2, 16, 48)),
    }[scores]
    visible = jnp.arange(48)[None, :] <= (32 + jnp.arange(16))[:, None]
    ours = jax.vmap(lambda i: trunk.select_keys(i, visible, 8))(index)
    theirs = jax.vmap(lambda i: reference.selected_keys(i, visible, 8))(index)
    # what top_k itself picks, its indices scattered into a mask
    _, picked = jax.lax.top_k(jnp.where(visible, index, -jnp.inf), 8)
    top_k = np.zeros((2, 16, 48), bool)
    np.put_along_axis(top_k, np.asarray(picked), True, axis=-1)
    np.testing.assert_array_equal(theirs, top_k)
    np.testing.assert_array_equal(ours, top_k)
    np.testing.assert_array_equal(ours.sum(-1), 8)
    if scores == "zeros":  # all tied: the eight lowest indices
        np.testing.assert_array_equal(ours[..., :8], True)


def test_tied_router_probabilities_select_the_lower_index():
    """(e) experts 1, 4 and 6 tie for the top on every token."""
    h2 = jax.random.normal(jax.random.PRNGKey(7), (S, 64))
    column = jax.random.normal(jax.random.PRNGKey(8), (64,))
    router = jnp.zeros((64, 8)).at[:, jnp.array([1, 4, 6])].set(column[:, None])
    ours_top, ours_c = trunk.route(h2, router, 2, True)
    theirs_top, theirs_c = reference.route(h2, router, 2, True)
    np.testing.assert_array_equal(ours_top, theirs_top)
    _close(ours_c, theirs_c, 1e-6)
    # the others' logits are 0: the tied three lead where theirs is positive
    leads = np.asarray(h2 @ column) > 0
    assert leads.any() and not leads.all()
    assert (np.asarray(ours_top)[leads] == [1, 4]).all()
    assert (np.asarray(ours_top)[~leads] == [0, 2]).all()


def _tiny_cell(tmp_path):
    """The committed cell with the tiny architecture in its place: two
    swarms of 32, a minibatch of two swarm-steps."""
    committed = harness.load_cell("keye-vl2-a3b-ep8-s8k-train-m2", ROOT)
    env = {**committed.config["env"], "num_agents_per_formation": S}
    swap = {"num_agents_per_formation": S, "trunk": "tiny"}
    overrides = [
        f"{key}={swap[key]}" if (key := o.split("=", 1)[0]) in swap else o
        for o in committed.config["overrides"]
    ]
    config = {**committed.config, "env": env, "policy": POLICY, "overrides": overrides}
    job = {**committed.job, "batch_size": 2 * S}
    return dataclasses.replace(
        committed, name="trunk-tiny", config=config, job=job, bench_dir=tmp_path / "b"
    )


def test_a_training_chunk_is_correct_by_the_harness(tmp_path):
    """(f) one ``Trainer.run_chunk()`` of the trainer ``build_trainer``
    makes for ``policy=trunk``, against ``reference.ppo.iteration``
    through ``harness.compare`` and the committed cell's limits."""
    lines = []
    result = harness.run_cell(
        _tiny_cell(tmp_path), seed=2**31 + 27, seconds=0.2, trace=False,
        started=time.perf_counter(), require_chip=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    assert result["compared"]["compiles_in_window"] == [0, 0]
    # on the CPU both sides are float32: far inside the chip's limits
    assert result["compared"]["loss_gap_first"][0] < 1e-5
    assert result["compared"]["param_change_gap"][0] < 1e-3


def _failed(rows):
    return [row["name"] for row in rows if not row["ok"]]


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_the_control_in_bfloat16_is_not_correct(tmp_path, seed):
    """``correct`` can come out false: the reference computed in bfloat16,
    put in the program's place, fails the committed cell's limits, and the
    reference itself passes them."""
    cell = _tiny_cell(tmp_path)
    ref = harness.follow_reference(cell, seed, 1)
    control = harness.follow_reference(cell, seed, 1, dtype="bfloat16")
    assert _failed(harness.judge(harness.compare(control, ref), cell.limits))
    again = harness.follow_reference(cell, seed, 1)
    assert not _failed(harness.judge(harness.compare(again, ref), cell.limits))


def _run_broken(cell, build):
    return harness.run_cell(
        cell, seed=11, seconds=0.2, trace=False, started=time.perf_counter(),
        require_chip=False, build=build, log=lambda line: None,
    )


def test_a_state_handed_back_unchanged_is_not_correct(tmp_path):
    def build(cell, seed):
        trainer = harness.build_program(cell, seed)
        run_chunk = trainer.run_chunk

        def stuck():
            kept = jax.tree_util.tree_map(jnp.copy, trainer.train_state)
            stacked = run_chunk()
            trainer.train_state = kept
            return stacked

        trainer.run_chunk = stuck
        return trainer

    result = _run_broken(_tiny_cell(tmp_path), build)
    assert result["correct"] is False
    value, limit = result["compared"]["param_change_gap"]
    assert value == pytest.approx(1.0) and value > limit


def test_half_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from marl_distributedformation_tpu.train import trainer as program

    whole = program.ppo_update

    def half(train_state, data, key, config):
        kept = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], data)
        return whole(
            train_state, kept, key,
            dataclasses.replace(config, batch_size=config.batch_size // 2),
        )

    monkeypatch.setattr(program, "ppo_update", half)
    result = _run_broken(_tiny_cell(tmp_path), harness.build_program)
    assert result["correct"] is False


def test_forward_counters_account_for_one_pass(params, obs):
    """Read on demand, off a pass of their own: the training iteration
    does not pay for them."""
    counters = jax.device_get(_model().forward_counters(params, obs[:1]))
    assert set(counters) == {  # a sparse_gqa trunk's own
        "moe_held_share", "moe_load_max_over_mean", "indexer_selected_mean"
    }
    assert set(counters) < set(trunk.COUNTERS)
    assert counters["indexer_selected_mean"] == pytest.approx(7.125)
    assert 0.0 < counters["moe_held_share"] < 1.0
    assert 0.0 <= counters["moe_load_max_over_mean"] <= 2.0  # of 2 held; 0: none loaded


def test_train_checkpoint_evaluate_round_trip(tmp_path, capsys):
    """(g) ``train.py policy=trunk trunk=tiny`` trains and checkpoints,
    ``evaluate.py`` rebuilds the policy from the checkpoint and runs it,
    and a resumed trainer holds the saved parameters."""
    sys.path.insert(0, str(ROOT))
    import evaluate as evaluate_cli
    import train as train_cli
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.utils import (
        env_params_from_config,
        latest_checkpoint,
        load_config,
    )

    run = tmp_path / "trunkrun"
    common = [
        "name=trunkrun", f"log_dir={run}", "policy=trunk", "trunk=tiny",
        "obs_mode=knn", "knn_k=4", "knn_impl=xla", f"num_agents_per_formation={S}",
        "max_steps=20", "strict_parity=false",
    ]
    job = ["num_formation=2", "n_steps=4", "n_epochs=1", f"batch_size={2 * S}"]
    trained = train_cli.main(common + job + [f"total_timesteps={2 * S * 4 * 2}"])
    assert trained["num_timesteps"] == 2 * S * 4 * 2
    checkpoint = latest_checkpoint(run)
    assert checkpoint is not None

    cfg = load_config(common + job)
    policy = LoadedPolicy.from_checkpoint(
        checkpoint, env_params=env_params_from_config(cfg)
    )
    assert isinstance(policy.model, TrunkActorCritic)
    assert policy.model.arch == trunk.load_trunk_arch("tiny")
    resumed = train_cli.build_trainer(load_config(common + job + ["resume=true"]))
    for saved, held in zip(
        jax.tree_util.tree_leaves(policy.params),
        jax.tree_util.tree_leaves(resumed.train_state.params),
    ):
        np.testing.assert_array_equal(saved, held)
    assert resumed.num_timesteps == trained["num_timesteps"]

    evaluate_cli.main(common + ["eval_formations=2"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert np.isfinite(last["policy_episode_return_per_agent"])


def test_an_unknown_or_inconsistent_architecture_is_refused():
    with pytest.raises(ValueError, match="no trunk architecture"):
        trunk.load_trunk_arch("no-such-model")
    with pytest.raises(ValueError, match="expert_share"):
        _arch(experts_held=3)
    with pytest.raises(ValueError, match="hidden_act"):
        _arch(hidden_act="gelu")


def test_the_architecture_files_agree():
    """The program's architecture file against the benchmark
    configuration: its top level (the published keys as they are run, the
    three that are cut listed in ``reduced``) and its ``policy`` group (the
    keys the reference computes from, which the harness hands it alone)."""
    program = yaml.safe_load((ROOT / "cfg/trunk/keye-vl2-a3b-ep8.yaml").read_text())
    config = json.loads(
        (ROOT / "benchmarks/configs/keye-vl2-a3b-ep8-s8k.json").read_text()
    )
    for key, value in config["policy"].items():
        if key not in ("kind", "trunk", "log_std_init"):
            assert program[key] == value, key
    assert config["policy"]["trunk"] == "keye-vl2-a3b-ep8"
    held = {"num_hidden_layers": "layers_held", "num_experts": "experts_held"}
    for key, value in program.items():
        if key in held:
            assert config[key] == program[held[key]]
            assert config["published"][key] == value
        elif key == "vocab_size":
            assert config[key] == 0 and config["published"][key] == value
        elif key not in held.values() and key != "expert_share":
            assert config[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "keye-vl2-a3b-ep8-s8k")
    assert set(entry["reduced"]) == set(config["reduced"])
    given = dict(o.split("=", 1) for o in config["overrides"])
    for key, value in {**config["env"], **config["ppo"]}.items():
        if key in given:
            assert _parse_value(given[key]) == pytest.approx(value), key


def test_forward_flops_are_the_issues_count():
    config = json.loads(
        (ROOT / "benchmarks/configs/keye-vl2-a3b-ep8-s8k.json").read_text()
    )
    flops = reference.forward_flops_per_agent(config["policy"], config["env"])
    layer = (
        2 * 18_874_368 + 2 * 2_260_992 + 4096.5 * 2080 + 1792.125 * 16384
        + 2 * 262_144 + 8 * 16 / 128 * 2 * 4_718_592
    )
    heads = 2 * 16 * 2048 + 2 * 2048 * 2 + 2 * 4096
    assert flops == pytest.approx(4 * layer + heads)
    assert layer == pytest.approx(90.1e6, rel=1e-3)
