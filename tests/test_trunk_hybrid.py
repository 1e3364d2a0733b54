"""A trunk whose layers differ and whose heads are shared out
(``policy=trunk trunk=solar-open2-250b-ep40-tp8``; ``models/trunk.py``,
``models/kda.py``) held to its plain reference
(``benchmarks/reference/policy_trunk_hybrid.py``) on seeded weights at the
size of ``cfg/trunk/tiny-hybrid.yaml``: hidden 64, two periods of one gated
GQA layer and three KDA layers, 4 query / 2 key / 4 KDA heads of 16 of which
share 1 of 2 is held, 8 experts top-2 of which 2 are held, a shared expert,
chunks of 8 tokens and blocks of 8 queries, swarms of 29 or 32.

Tolerances: both sides compute in float32 on the CPU and differ by the
order of their sums (the reference runs the delta rule token by token, the
program in chunks through a triangular solve). Outputs are compared to 1e-5
of the largest entry (observed 5e-7), gradients to 1e-4 of each leaf's
largest entry (observed 7e-7: a gradient sums over 87 tokens x 8 layers).
The control, the reference computed in bfloat16, is 1e-2 from it and fails
both (``test_the_bfloat16_control_fails_these_tolerances``).
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
from benchmarks.reference import policy_trunk_hybrid as reference
from benchmarks.reference import ppo as reference_ppo
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.algo.ppo import MinibatchData, ppo_loss
from marl_distributedformation_tpu.models import kda, trunk
from marl_distributedformation_tpu.models.trunk import TrunkActorCritic, TrunkArch
from marl_distributedformation_tpu.utils.config import _parse_value

ROOT = Path(__file__).resolve().parents[1]
NAME = "solar-open2-250b-ep40-tp8"
CELL = f"{NAME}-s8k-train-m1"
TINY = yaml.safe_load((ROOT / "cfg" / "trunk" / "tiny-hybrid.yaml").read_text())
POLICY = {"kind": "trunk_hybrid", "trunk": "tiny-hybrid", "log_std_init": 0.0, **TINY}
# every head and every expert on one chip: what the shares add up to
UNCUT = {**POLICY, "head_share": [0, 1], "experts_held": 8, "expert_share": [0, 1]}
S, K = 29, 4  # 29: neither the chunk nor the query block divides it
ENV = {"knn_k": K, "goal_in_obs": True, "num_agents_per_formation": S}
OBS_DIM = 2 + 4 * K + 2
OUT, GRAD = 1e-5, 1e-4


def _arch(**changes):
    return TrunkArch.from_dict("tiny-hybrid", {**TINY, **changes})


@pytest.fixture(scope="module")
def params():
    return reference.init(jax.random.PRNGKey(0), POLICY, ENV)


@pytest.fixture(scope="module")
def obs():
    return jax.random.uniform(jax.random.PRNGKey(1), (3, S, OBS_DIM))


def _close(a, b, rel):
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * scale)


def _layer(params, name, period=0):
    """One layer out of the tree: ``name`` of the period's layers
    (``0_gated_gqa``, ``1_kda`` ...), of period ``period``."""
    return jax.tree_util.tree_map(
        lambda a: a[period], params["params"]["layers"][name]
    )


def test_the_program_reads_the_tree_the_reference_makes(params, obs):
    arch = _arch()
    assert arch.period == ("gated_gqa", "kda", "kda", "kda")
    assert arch.layer_kinds == arch.period * 2
    assert reference.runs(POLICY) == (
        2, [("gated_gqa", ["0_gated_gqa"]), ("kda", ["1_kda", "2_kda", "3_kda"])]
    )
    model = TrunkActorCritic(arch=arch, k=K)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(2), obs[:1]))
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(params)
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(params)
    ):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
    # the program's own draw of the decay's parameters is the stated one
    drawn = model.init(jax.random.PRNGKey(2), obs[:1])["params"]["layers"]["2_kda"]
    assert drawn["w_in"].shape == (2, 64, 3 * 32 + 2 * 16)  # two periods
    rate = np.exp(np.asarray(drawn["A_log"]))
    step = np.asarray(jax.nn.softplus(drawn["dt_bias"]))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 0.001 <= step.min() and step.max() <= 0.1 + 1e-6


@pytest.mark.parametrize("kind", ["kda", "gated_gqa"])
def test_a_mixer_alone_matches_the_reference_forward_and_gradient(params, kind):
    """One layer's mixer on one swarm: what it adds, and the gradient of a
    scalar of it with respect to the input and every weight of the mixer."""
    lp = _layer(params, {"kda": "2_kda", "gated_gqa": "0_gated_gqa"}[kind], 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (S, 64))
    weight = jax.random.normal(jax.random.PRNGKey(4), (S, 64))
    ours = lambda x, lp: trunk.MIXERS[kind].mix(x, lp, _arch(), False)[0]  # noqa: E731
    theirs = lambda x, lp: reference.MIXERS[kind](x, lp, POLICY)  # noqa: E731
    _close(jax.jit(ours)(x, lp), theirs(x, lp), OUT)
    scalar = lambda f: (lambda x, lp: (weight * f(x, lp)).sum())  # noqa: E731
    g_ours = jax.jit(jax.grad(scalar(ours), argnums=(0, 1)))(x, lp)
    g_theirs = jax.jit(jax.grad(scalar(theirs), argnums=(0, 1)))(x, lp)
    moe_only = set(trunk._moe_shapes(_arch(), None))
    flat, _ = jax.tree_util.tree_flatten_with_path(g_ours)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(g_theirs)):
        name = jax.tree_util.keystr(path)
        if any(leaf in name for leaf in moe_only):
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
        else:
            assert np.asarray(b).any(), name
            _close(a, b, GRAD)


@pytest.mark.parametrize("tile_blocks", [1, 2])
def test_gated_attention_puts_a_blocks_key_tiles_together(params, tile_blocks, monkeypatch):
    """A swarm of 32 in blocks of 8 queries against tiles of 8 or 16 keys:
    10 or 6 (block, tile) pairs under the diagonal, up to four tiles a block
    put together by their largest scores, against the reference's softmax
    over all the keys at once; forward and gradients."""
    monkeypatch.setattr(trunk, "_GQA_TILE_BLOCKS", tile_blocks)
    chunk, tile, pairs = trunk._causal_tiles(32, 8)
    assert (chunk, tile, len(pairs)) == (8, 8 * tile_blocks, {1: 10, 2: 6}[tile_blocks])
    lp = _layer(params, "0_gated_gqa", 1)
    x = jax.random.normal(jax.random.PRNGKey(21), (32, 64))
    weight = jax.random.normal(jax.random.PRNGKey(22), (32, 64))
    ours = lambda x, lp: (  # noqa: E731
        weight * trunk.MIXERS["gated_gqa"].mix(x, lp, _arch(), False)[0]
    ).sum()
    theirs = lambda x, lp: (weight * reference.MIXERS["gated_gqa"](x, lp, POLICY)).sum()  # noqa: E731
    mixer = set(trunk.MIXERS["gated_gqa"].shapes(_arch()))
    v_ours, g_ours = jax.value_and_grad(ours, argnums=(0, 1))(x, lp)
    v_theirs, g_theirs = jax.value_and_grad(theirs, argnums=(0, 1))(x, lp)
    np.testing.assert_allclose(v_ours, v_theirs, rtol=1e-5)
    _close(g_ours[0], g_theirs[0], GRAD)
    for name in mixer:
        _close(g_ours[1][name], g_theirs[1][name], GRAD)


@pytest.mark.parametrize("name", ["0_gated_gqa", "2_kda"])
def test_the_reference_by_token_blocks_is_the_reference_whole(params, name, monkeypatch):
    """``TOKEN_BLOCK`` is there for the compiler: a layer worked 8 tokens at
    a time (four blocks of a swarm of 32) is the layer worked whole, output
    and gradients (float32 sums in another order: 1e-6 of the largest)."""
    lp = _layer(params, name, 1)
    x = jax.random.normal(jax.random.PRNGKey(20), (32, 64))
    kind = name.split("_", 1)[1]

    def scalar(x, lp):
        out = reference.layer(x, lp, kind, POLICY)
        return (out * out).sum(), out

    whole = jax.grad(scalar, argnums=(0, 1), has_aux=True)(x, lp)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 8)
    blocks = jax.grad(scalar, argnums=(0, 1), has_aux=True)(x, lp)
    for a, b in zip(jax.tree_util.tree_leaves(blocks), jax.tree_util.tree_leaves(whole)):
        if np.asarray(b).any():
            _close(a, b, 1e-6)
        else:
            assert not np.asarray(a).any()


def test_the_whole_policy_matches_forward_and_ppo_gradient(params, obs):
    """Mean and value, then the program's ``ppo_loss`` through its policy
    against the reference's ``loss_fn`` through its own, leaf by leaf."""
    model = TrunkActorCritic(arch=_arch(), k=K)
    mean, log_std, value = jax.jit(model.apply)(params, obs)
    r_apply = lambda p, x: reference.apply(p, POLICY, ENV, x)  # noqa: E731
    r_mean, r_log_std, r_value = jax.jit(r_apply)(params, obs)
    _close(mean, r_mean, OUT)
    _close(value, r_value, OUT)
    np.testing.assert_array_equal(log_std, r_log_std)

    keys = jax.random.split(jax.random.PRNGKey(5), 4)
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
    r_loss, r_grads = jax.jit(
        jax.value_and_grad(lambda p: reference_ppo.loss_fn(p, config, r_apply, mb))
    )(params)
    data = MinibatchData(
        obs=mb["obs"], actions=mb["actions"], old_log_probs=mb["log_probs"],
        advantages=mb["advantages"], returns=mb["returns"],
    )
    (loss, _), grads = jax.jit(
        jax.value_and_grad(
            lambda p: ppo_loss(p, model.apply, data, PPOConfig()), has_aux=True
        )
    )(params)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), theirs in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        assert np.asarray(theirs).any(), jax.tree_util.keystr(path)  # all trained
        _close(ours, theirs, GRAD)


def test_the_bfloat16_control_fails_these_tolerances(params, obs):
    """The reference computed in bfloat16 is not within ``OUT`` of itself
    in float32, by a factor of hundreds (observed 950 and 1,300): the
    tolerances above can tell a lower precision."""
    r_mean, _, r_value = reference.apply(params, POLICY, ENV, obs)
    c_mean, _, c_value = reference.apply(params, POLICY, ENV, obs, jnp.bfloat16)
    for control, sound in ((c_mean, r_mean), (c_value, r_value)):
        gap = np.abs(np.asarray(control) - np.asarray(sound)).max()
        assert gap > 300 * OUT * np.abs(np.asarray(sound)).max()


def _recurrence_inputs(s, heads, d, log_decay):
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (s, heads, d))) * d**-0.5
    k = unit(jax.random.normal(keys[1], (s, heads, d)))
    v = jax.random.normal(keys[2], (s, heads, d))
    g = log_decay * jax.random.uniform(keys[3], (s, heads, d), minval=0.9)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (s, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("log_decay", [-0.05, -1.6, -4.0])
def test_the_chunked_recurrence_is_the_token_by_token_one(log_decay):
    """A swarm of 150 in chunks of 64 (the last one 22 tokens and padding),
    at decays from mild to -4 a step: a chunk cumulates down to -256, and a
    form that divides a chunk's decay out (``e^G_t`` times ``e^-G_i``)
    overflows float32 from -1.6 on, which the last assertion shows. Forward
    to ``OUT``, gradients of all five inputs to ``GRAD``."""
    q, k, v, g, beta = _recurrence_inputs(150, 2, 16, log_decay)
    chunked = lambda *a: kda.chunked_delta_rule(*a, chunk=64)  # noqa: E731
    plain = lambda q, k, v, g, beta: reference.delta_rule(q, k, v, jnp.exp(g), beta)  # noqa: E731
    out = jax.jit(chunked)(q, k, v, g, beta)
    assert np.isfinite(np.asarray(out)).all()
    _close(out, plain(q, k, v, g, beta), OUT)
    weight = jax.random.normal(jax.random.PRNGKey(7), out.shape)
    grad = lambda f: jax.jit(  # noqa: E731
        jax.grad(lambda *a: (weight * f(*a)).sum(), argnums=(0, 1, 2, 3, 4))
    )
    for ours, theirs in zip(grad(chunked)(q, k, v, g, beta), grad(plain)(q, k, v, g, beta)):
        assert np.isfinite(np.asarray(ours)).all()
        _close(ours, theirs, GRAD)
    divided_out = jnp.exp(-jnp.cumsum(g[:64], axis=0))
    assert bool(jnp.isinf(divided_out).any()) == (log_decay <= -1.6)


def test_the_short_convolution_is_causal_and_matches():
    x = jax.random.normal(jax.random.PRNGKey(8), (S, 6))
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 6))
    ours = kda.short_conv(x, w)
    _close(ours, reference.short_conv(x, w), 1e-6)
    by_hand = sum(w[j] * x[10 - 3 + j] for j in range(4))  # token 10 from 7..10
    np.testing.assert_allclose(ours[10], by_hand, rtol=1e-5, atol=1e-6)
    later = x.at[11:].set(0.0)  # a token does not see the ones after it
    np.testing.assert_array_equal(kda.short_conv(later, w)[:11], ours[:11])


def _head_slice(lp, kind, share, count, arch):
    """Share ``share`` of ``count`` of an uncut layer's mixer weights: its
    heads' columns of every matrix in the fused leaves (rows of ``wo``);
    what is not per head (the norms, the gates' first halves) whole."""
    cols = lambda a: jnp.split(a, count, axis=-1)[share]  # noqa: E731
    if kind == "kda":
        width = lp["wo"].shape[0]
        q, k, v, gates = jnp.split(lp["w_in"], (width, 2 * width, 3 * width), -1)
        fused = {
            "w_in": jnp.concatenate([cols(q), cols(k), cols(v), gates], -1),
            "conv": jnp.concatenate([cols(a) for a in jnp.split(lp["conv"], 3, -1)], -1),
        }
        per_head = ("f_b", "dt_bias", "A_log", "g_b", "w_beta")
    else:
        nq = arch.num_attention_heads * arch.head_dim
        nkv = arch.num_key_value_heads * arch.head_dim
        parts = jnp.split(lp["w_in"], (nq, nq + nkv, nq + 2 * nkv), -1)
        fused = {"w_in": jnp.concatenate([cols(a) for a in parts], -1)}
        per_head = ()
    cut = {name: cols(lp[name]) if name in per_head else lp[name] for name in lp}
    cut.update(fused)
    cut["wo"] = jnp.split(lp["wo"], count, axis=0)[share]
    return cut


@pytest.mark.parametrize("kind", ["kda", "gated_gqa"])
def test_head_shares_add_up_to_the_uncut_mixer(kind):
    """The share test, heads: the two chips that hold half of the heads
    each give partial outputs ``o @ wo`` that add up to what the reference
    gives for the layer's mixer with every head."""
    uncut = reference.init(jax.random.PRNGKey(10), UNCUT, ENV)
    lp = _layer(uncut, {"kda": "1_kda", "gated_gqa": "0_gated_gqa"}[kind])
    x = jax.random.normal(jax.random.PRNGKey(11), (S, 64))
    whole = reference.MIXERS[kind](x, lp, UNCUT)
    total = 0.0
    for share in range(2):
        arch = _arch(head_share=[share, 2])
        held = _head_slice(lp, kind, share, 2, arch)
        for name, (_, shape) in trunk.MIXERS[kind].shapes(arch).items():
            assert held[name].shape == shape, name  # the widths follow the heads held
        part = trunk.MIXERS[kind].mix(x, held, arch, False)[0]
        _close(part, reference.MIXERS[kind](x, held, {**POLICY, "head_share": [share, 2]}), OUT)
        assert float(jnp.abs(part - whole).max()) > 0.1 * float(jnp.abs(whole).max())
        total = total + part
    _close(total, whole, OUT)


def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test, experts: four chips that hold two of the eight
    experts each; their routed parts, with the shared expert (which every
    chip computes alike) counted once, add up to the uncut reference's
    expert layer."""
    uncut = reference.init(jax.random.PRNGKey(12), UNCUT, ENV)
    lp = _layer(uncut, "3_kda")
    lp = {**lp, "router": 25.0 * lp["router"]}  # spread the routing out
    x = jax.random.normal(jax.random.PRNGKey(13), (S, 64))
    routed, shared = reference.expert_part(x, lp, UNCUT)
    h2 = trunk._rms(x, lp["moe_norm"], TINY["rms_norm_eps"])
    e_top, c = trunk.route(h2, lp["router"], 2, True)
    total, held_shares = 0.0, []
    for share in range(4):
        held = slice(2 * share, 2 * share + 2)
        part, counters = trunk.expert_layer(
            h2, e_top, c, lp["w_gate"][held], lp["w_up"][held], lp["w_down"][held],
            (share, 4),
        )
        total = total + part
        held_shares.append(float(counters["moe_held_share"]))
    ours_shared = trunk.shared_expert(h2, lp["s_in"], lp["s_down"])
    _close(ours_shared, shared, OUT)
    _close(total, routed, OUT)
    _close(total + ours_shared, routed + shared, OUT)
    assert sum(held_shares) == pytest.approx(1.0)  # every assignment, once
    assert min(held_shares) > 0.0


def test_forward_counters_are_this_trunks_own(params, obs):
    model = TrunkActorCritic(arch=_arch(), k=K)
    counters = jax.device_get(model.forward_counters(params, obs[:1]))
    assert set(counters) == {
        "moe_held_share", "moe_load_max_over_mean", "kda_log_decay_mean", "kda_beta_mean"
    }
    assert set(counters) < set(trunk.COUNTERS)
    # the stated draw: rate U(1, 16) times step U(0.001, 0.1), moved a little
    # by the gate's input; beta = 2 sigmoid(small)
    assert -1.6 < counters["kda_log_decay_mean"] < -0.05
    assert counters["kda_beta_mean"] == pytest.approx(1.0, abs=0.05)
    assert 0.0 < counters["moe_held_share"] < 1.0


def _tiny_cell(tmp_path):
    """The committed cell with the tiny architecture in its place: two
    swarms of 32, a minibatch of two swarm-steps."""
    committed = harness.load_cell(CELL, ROOT)
    s = 32
    env = {**committed.config["env"], "num_agents_per_formation": s}
    swap = {"num_agents_per_formation": s, "trunk": "tiny-hybrid"}
    overrides = [
        f"{key}={swap[key]}" if (key := o.split("=", 1)[0]) in swap else o
        for o in committed.config["overrides"]
    ]
    config = {**committed.config, "env": env, "policy": POLICY, "overrides": overrides}
    job = {**committed.job, "num_formation": 2, "batch_size": 2 * s}
    return dataclasses.replace(
        committed, name="trunk-tiny-hybrid", config=config, job=job,
        bench_dir=tmp_path / "b",
    )


def test_a_training_chunk_is_correct_by_the_harness(tmp_path):
    """One ``Trainer.run_chunk()`` of the trainer ``build_trainer`` makes
    for ``policy=trunk trunk=tiny-hybrid``, against ``reference.ppo.iteration``
    through ``harness.compare`` and the committed cell's limits."""
    lines = []
    result = harness.run_cell(
        _tiny_cell(tmp_path), seed=2**31 + 32, seconds=0.2, trace=False,
        started=time.perf_counter(), require_chip=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    assert result["compared"]["compiles_in_window"] == [0, 0]
    # on the CPU both sides are float32: far inside the chip's limits
    assert result["compared"]["loss_gap_first"][0] < 1e-5
    assert result["compared"]["param_change_gap"][0] < 1e-3


@pytest.mark.parametrize("seed", [5, 2**31 + 6])
def test_the_control_in_bfloat16_is_not_correct(tmp_path, seed):
    """``correct`` can come out false: the reference computed in bfloat16,
    put in the program's place, fails the committed cell's limits, and the
    reference itself passes them."""
    cell = _tiny_cell(tmp_path)
    failed = lambda rows: [row["name"] for row in rows if not row["ok"]]  # noqa: E731
    ref = harness.follow_reference(cell, seed, 1)
    control = harness.follow_reference(cell, seed, 1, dtype="bfloat16")
    assert failed(harness.judge(harness.compare(control, ref), cell.limits))
    again = harness.follow_reference(cell, seed, 1)
    assert not failed(harness.judge(harness.compare(again, ref), cell.limits))


def test_train_checkpoint_and_load_the_trunk_key(tmp_path):
    """``train.py policy=trunk trunk=tiny-hybrid`` trains and checkpoints;
    the checkpoint's ``trunk`` key rebuilds the policy, which then holds the
    saved parameters and acts; a resumed trainer holds them too."""
    sys.path.insert(0, str(ROOT))
    import train as train_cli
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.utils import (
        env_params_from_config,
        latest_checkpoint,
        load_config,
    )

    run, s = tmp_path / "hybridrun", 16
    common = [
        "name=hybridrun", f"log_dir={run}", "policy=trunk", "trunk=tiny-hybrid",
        "obs_mode=knn", "knn_k=4", "knn_impl=xla", f"num_agents_per_formation={s}",
        "max_steps=20", "strict_parity=false",
    ]
    job = ["num_formation=2", "n_steps=2", "n_epochs=1", f"batch_size={2 * s}"]
    trained = train_cli.main(common + job + [f"total_timesteps={2 * s * 2 * 2}"])
    assert trained["num_timesteps"] == 2 * s * 2 * 2
    checkpoint = latest_checkpoint(run)
    assert checkpoint is not None

    cfg = load_config(common + job)
    policy = LoadedPolicy.from_checkpoint(
        checkpoint, env_params=env_params_from_config(cfg)
    )
    assert isinstance(policy.model, TrunkActorCritic)
    assert policy.model.arch == trunk.load_trunk_arch("tiny-hybrid")
    assert set(policy.params["params"]["layers"]) == {
        "0_gated_gqa", "1_kda", "2_kda", "3_kda"
    }
    resumed = train_cli.build_trainer(load_config(common + job + ["resume=true"]))
    for saved, held in zip(
        jax.tree_util.tree_leaves(policy.params),
        jax.tree_util.tree_leaves(resumed.train_state.params),
    ):
        np.testing.assert_array_equal(saved, held)
    mean, _, value = policy.model.apply(policy.params, jnp.zeros((1, s, OBS_DIM)))
    assert np.isfinite(np.asarray(mean)).all() and np.isfinite(np.asarray(value)).all()


def test_what_the_new_mixers_do_not_compute_is_refused():
    for key, value in [
        ("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
        ("kda_allow_neg_eigval", False), ("first_k_dense_replace", 1),
        ("routed_scaling_factor", 2.5),
    ]:
        with pytest.raises(ValueError, match=key):
            _arch(**{key: value})
    with pytest.raises(ValueError, match="head_share"):
        _arch(head_share=[0, 4])  # 2 key heads do not divide over 4
    with pytest.raises(ValueError, match="head_share"):
        _arch(head_share=[2, 2])
    with pytest.raises(ValueError, match="expert_share"):
        _arch(experts_held=3)
    with pytest.raises(ValueError, match="linear_attn_config"):
        _arch(kda_chunk_size=0)
    # a file of neither shape
    neither = {k: v for k, v in TINY.items() if k != "gqa_layers"}
    with pytest.raises((ValueError, KeyError)):
        TrunkArch.from_dict("neither", neither)


def test_the_architecture_files_agree():
    """The program's architecture file against the catalog's row as the
    benchmark configuration holds it: its top level (the published keys as
    they are run, the six that are cut listed in ``reduced``) and its
    ``policy`` group (what the reference computes from)."""
    program = yaml.safe_load((ROOT / f"cfg/trunk/{NAME}.yaml").read_text())
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    for key, value in config["policy"].items():
        if key not in ("kind", "trunk", "log_std_init"):
            assert program[key] == value, key
    assert config["policy"]["trunk"] == NAME
    heads = program["head_share"][1]
    run = {
        "num_hidden_layers": program["layers_held"],
        "n_routed_experts": program["experts_held"],
        "num_attention_heads": program["num_attention_heads"] // heads,
        "num_key_value_heads": program["num_key_value_heads"] // heads,
        "linear_attn_config": {
            **program["linear_attn_config"],
            "num_heads": program["linear_attn_config"]["num_heads"] // heads,
        },
        "vocab_size": 0,
    }
    ours = ("layers_held", "experts_held", "expert_share", "head_share",
            "q_chunk_size", "kda_chunk_size")
    for key, value in program.items():
        if key in run:
            assert config[key] == run[key], key
            assert config["published"][key] == value, key
        elif key not in ours:
            assert config[key] == value, key
    assert set(config["reduced"]) == set(run)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == f"{NAME}-s8k")
    assert set(entry["reduced"]) == set(config["reduced"])
    # Keye's env and PPO numbers, letter for letter, but for the second and
    # third fallbacks (n_steps 2 and n_epochs 1 where Keye's file has 4 and 2)
    keye = json.loads((ROOT / "benchmarks/configs/keye-vl2-a3b-ep8-s8k.json").read_text())
    assert config["env"] == keye["env"]
    assert config["ppo"] == {**keye["ppo"], "n_steps": 2, "n_epochs": 1}
    given = dict(o.split("=", 1) for o in config["overrides"])
    for key, value in {**config["env"], **config["ppo"]}.items():
        if key in given:
            assert _parse_value(given[key]) == pytest.approx(value), key
    # the two trunk cells' jobs: Keye's but for the first fallback,
    # one swarm-step a minibatch where Keye's has two
    job = json.loads((ROOT / "benchmarks/workloads/train-m1.json").read_text())
    keye_job = json.loads((ROOT / "benchmarks/workloads/train-m2.json").read_text())
    assert "fallback" in job and job.pop("fallback")
    assert job == {**keye_job, "batch_size": keye_job["batch_size"] // 2}


def test_the_parameter_count_and_the_flops_are_the_issues():
    """639.5 M parameters to within 0.5% (ISSUE 33's arithmetic), and the
    work a token requires by the reference's count."""
    model = TrunkActorCritic(arch=trunk.load_trunk_arch(NAME), k=4)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 64, 22), jnp.float32)
    )
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 639_630_749
    assert count == pytest.approx(639.5e6, rel=5e-3)
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    flops = reference.forward_flops_per_agent(config["policy"], config["env"])
    kda_layer = (
        2 * 4 * 4_194_304 + 4 * (524_288 + 131_072) + 2 * 32_768 + 6 * 4 * 1024
        + 7 * 8 * 128 * 128
    )
    gqa_layer = 2 * (3 * 4_194_304 + 2 * 524_288) + 4096.5 * 4 * 1024
    experts = 2 * 1_310_720 + 8 * 8 / 320 * 6 * 5_242_880 + 6 * 5_242_880
    heads = 2 * 16 * 4096 + 2 * 4096 * 2 + 2 * 8192
    assert flops == pytest.approx(3 * kda_layer + gqa_layer + 4 * experts + heads)
    assert kda_layer == pytest.approx(37.2e6, rel=1e-2)
    assert gqa_layer == pytest.approx(44.0e6, rel=1e-2)
