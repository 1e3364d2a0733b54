"""A trunk of gated short convolutions (``policy=trunk
trunk=lfm2-24b-a2b-ep8``; ``models/trunk.py``: the ``conv`` and ``gqa``
mixers, a leading dense layer whose mixer is not the period's first, sigmoid
routing with a selection bias and the renormalisation's ``1e-6``) held to its
plain reference (``benchmarks/reference/policy_trunk_lfm2.py``) on seeded
weights at the size of ``cfg/trunk/tiny-lfm2.yaml``: hidden 64, convolutions
of 3 taps, GQA with 4 query and 2 key heads of 16 under RoPE, held layers 1-9
of 12 (one dense conv layer of 96, then two periods of an attention and three
conv layers), 8 experts top-2 of which 2 are held, blocks of 8 queries, swarms
of 29 (the query block does not divide it), 32 or 64 (several key tiles).

Tolerances: both sides compute in float32 on the CPU and differ by the order
of their sums (the program puts a block's key tiles together by their
largest scores, the reference takes a softmax over all keys; the program
blocks nothing of a token's products, the reference 256 tokens at a time).
Outputs are compared to 1e-5 of the largest entry, gradients to 1e-4 of each
leaf's largest entry (observed: both under a tenth of that). The control, the
reference computed in bfloat16, lies hundreds of times further out, and so
does a convolution with its taps reversed or a gate dropped, and attention
without RoPE (``test_a_mistaken_mixer_fails_these_tolerances``).
"""

import dataclasses
import functools
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
from benchmarks.reference import policy_trunk_lfm2 as reference
from benchmarks.reference import ppo as reference_ppo
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.algo.ppo import MinibatchData, ppo_loss
from marl_distributedformation_tpu.models import trunk
from marl_distributedformation_tpu.models.kda import short_conv
from marl_distributedformation_tpu.models.trunk import TrunkActorCritic, TrunkArch
from marl_distributedformation_tpu.ops.sparse_attention import sparse_flash_attention
from marl_distributedformation_tpu.utils.config import _parse_value

ROOT = Path(__file__).resolve().parents[1]
NAME = "lfm2-24b-a2b-ep8"
CELL = f"{NAME}-s8k-train-m2"
TINY = yaml.safe_load((ROOT / "cfg" / "trunk" / "tiny-lfm2.yaml").read_text())
POLICY = {"kind": "trunk_lfm2", "trunk": "tiny-lfm2", "log_std_init": 0.0, **TINY}
UNCUT = {**POLICY, "experts_held": 8, "expert_share": [0, 1]}  # every expert
S, K, H = 29, 4, 64
ENV = {"knn_k": K, "goal_in_obs": True, "num_agents_per_formation": S}
OBS_DIM = 2 + 4 * K + 2
OUT, GRAD = 1e-5, 1e-4
EPS = TINY["norm_eps"]
# LFM2-24B-A2B's published config.json, the keys that say something of its shape
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
    + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def _arch(**changes):
    return TrunkArch.from_dict("tiny-lfm2", {**TINY, **changes})


@pytest.fixture(scope="module")
def params():
    return reference.init(jax.random.PRNGKey(0), POLICY, ENV)


@pytest.fixture(scope="module")
def obs():
    return jax.random.uniform(jax.random.PRNGKey(1), (3, S, OBS_DIM))


def _close(a, b, rel):
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * scale)


def _gap(a, b):
    """The largest difference over the largest entry of ``b``."""
    return float(jnp.abs(a - b).max()) / float(jnp.abs(b).max())


def _layer(params, name, at=0):
    """A held layer out of the tree: the dense layer, or period ``at``'s."""
    leaves = params["params"]["layers"][name]
    return leaves if name.startswith("dense") else {k: v[at] for k, v in leaves.items()}


def _x(s=S):
    return jax.random.normal(jax.random.PRNGKey(3), (s, H))


def test_the_program_reads_the_tree_the_reference_makes(params, obs):
    arch = _arch()
    assert arch.layer_kinds == ("conv",) + ("gqa", "conv", "conv", "conv") * 2
    assert arch.period == ("gqa", "conv", "conv", "conv")
    assert (arch.dense_layers, arch.residual, arch.head_dim) == (1, "plain", 16)
    assert (arch.scoring_func, arch.router_bias, arch.topk_norm_eps) == ("sigmoid", True, 1e-6)
    assert reference.dense_names(POLICY) == [("dense0_conv", "conv")]
    assert reference.runs(POLICY) == (
        2, [("gqa", ["0_gqa"]), ("conv", ["1_conv", "2_conv", "3_conv"])]
    )
    model = TrunkActorCritic(arch=arch, k=K)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(2), obs[:1]))
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(params)
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(params)
    ):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
    layers = params["params"]["layers"]
    assert set(layers) == {"dense0_conv", "0_gqa", "1_conv", "2_conv", "3_conv"}
    assert layers["dense0_conv"]["d_in"].shape == (64, 2 * 96)
    assert layers["dense0_conv"]["conv"].shape == (3, 64)  # the dense layer convolves
    assert layers["1_conv"]["w_in"].shape == (2, 64, 3 * 64)  # two periods
    assert layers["0_gqa"]["w_in"].shape == (2, 64, (4 + 2 * 2) * 16)
    assert layers["0_gqa"]["w_gate"].shape == (2, 2, 64, 32)
    assert "router" not in layers["dense0_conv"]
    drawn = model.init(jax.random.PRNGKey(2), obs[:1])["params"]["layers"]
    assert not np.asarray(drawn["1_conv"]["router_bias"]).any()  # zero at the draw
    assert 0.015 < float(jnp.std(drawn["1_conv"]["conv"])) < 0.025  # taps like a matrix


def _conv_pair(lp, arch, policy, s=S):
    ours = lambda x, lp: trunk.MIXERS["conv"].mix(x, lp, arch, False)[0]  # noqa: E731
    theirs = lambda x, lp: reference.conv_mixer(x, lp, policy)  # noqa: E731
    return ours, theirs, _x(s), lp


def _gqa_pair(lp, arch, policy, s=S):
    ours = lambda x, lp: trunk.MIXERS["gqa"].mix(x, lp, arch, False)[0]  # noqa: E731
    theirs = lambda x, lp: reference.gqa_mixer(x, lp, policy)  # noqa: E731
    return ours, theirs, _x(s), lp


def _dense_pair(lp, arch, policy, s=S):
    def ours(x, lp):
        h2 = trunk._rms(x, lp["dense_norm"], arch.rms_norm_eps)
        return trunk.dense_ffn(h2, lp["d_in"], lp["d_down"])

    theirs = lambda x, lp: reference.dense_ffn(x, lp, policy)  # noqa: E731
    return ours, theirs, _x(s), lp


def _router_pair(lp, arch, policy, s=S):
    """The weights the chosen experts combine with, scattered over all the
    experts: the selection and the weights in one array. The bias is moved
    off zero, so that it selects."""
    lp = {
        "router": 25.0 * lp["router"],
        "router_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(14), (8,)),
    }

    def spread(e_top, c):
        return (jax.nn.one_hot(e_top, 8) * c[..., None]).sum(1)

    def ours(x, lp):
        return spread(*trunk.route(
            x, lp["router"], arch.num_experts_per_tok, arch.norm_topk_prob,
            arch.scoring_func, lp["router_bias"], arch.routed_scaling_factor,
            arch.topk_norm_eps,
        ))

    theirs = lambda x, lp: spread(*reference.route(x, lp["router"], lp["router_bias"], policy))  # noqa: E731
    return ours, theirs, _x(s), lp


# part -> (its pair, the layer it reads, the swarm's length)
PARTS = {
    "conv": (_conv_pair, "2_conv", S),
    "dense_conv": (_conv_pair, "dense0_conv", S),
    "gqa": (_gqa_pair, "0_gqa", S),
    "gqa_tiles": (_gqa_pair, "0_gqa", 64),  # blocks of 8 queries, tiles of 32 keys
    "dense": (_dense_pair, "dense0_conv", S),
    "router": (_router_pair, "3_conv", S),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_a_part_alone_matches_the_reference_forward_and_gradient(params, part):
    """A gated convolution, the dense layer's convolution, the attention
    (one key tile, and several), the dense feed-forward and the sigmoid
    router, each on one swarm: what it gives, and the gradient of a scalar
    of it with respect to the input and every weight it reads."""
    pair, name, s = PARTS[part]
    ours, theirs, x, lp = pair(_layer(params, name, at=1), _arch(), POLICY, s)
    out = jax.jit(ours)(x, lp)
    _close(out, theirs(x, lp), OUT)
    weight = jax.random.normal(jax.random.PRNGKey(4), out.shape)
    scalar = lambda f: (lambda x, lp: (weight * f(x, lp)).sum())  # noqa: E731
    g_ours = jax.jit(jax.grad(scalar(ours), argnums=(0, 1)))(x, lp)
    g_theirs = jax.jit(jax.grad(scalar(theirs), argnums=(0, 1)))(x, lp)
    read = {
        "conv": set(trunk.MIXERS["conv"].shapes(_arch())),
        "gqa": set(trunk.MIXERS["gqa"].shapes(_arch())),
        "dense": {"dense_norm", "d_in", "d_down"},
        "router": {"router"},  # the bias selects and does not weigh: no gradient
    }[part.split("_")[-1] if part != "gqa_tiles" else "gqa"]
    _close(g_ours[0], g_theirs[0], GRAD)
    for key in lp:
        a, b = np.asarray(g_ours[1][key]), np.asarray(g_theirs[1][key])
        if key in read:
            assert b.any(), key
            _close(a, b, GRAD)
        else:
            assert not a.any() and not b.any(), key
    if part == "router":  # two experts a token, their weights summing to 1 less the eps
        chosen = np.asarray(out) > 0
        assert (chosen.sum(-1) == 2).all()
        total = np.asarray(out).sum(-1)
        assert (total < 1.0).all() and np.allclose(total, 1.0, atol=2e-6)
        plain = theirs(x, {**lp, "router_bias": jnp.zeros(8)})
        assert ((np.asarray(plain) > 0) != chosen).any()  # the bias selected


def _conv_with(x, lp, fault):
    """The program's gated convolution with one mistake planted: the taps
    reversed, or the B or the C gate left out (taken as 1)."""
    h = trunk._rms(x, lp["attn_norm"], EPS)
    b, c, u = jnp.split(h @ lp["w_in"], 3, 1)
    b = jnp.ones_like(b) if fault == "no_b_gate" else b
    c = jnp.ones_like(c) if fault == "no_c_gate" else c
    taps = lp["conv"][::-1] if fault == "taps_reversed" else lp["conv"]
    return (c * short_conv(b * u, taps)) @ lp["wo"]


@pytest.mark.parametrize("fault", ["taps_reversed", "no_b_gate", "no_c_gate", "no_rope"])
def test_a_mistaken_mixer_fails_these_tolerances(params, monkeypatch, fault):
    """Each mistake a gated convolution or a RoPE attention could be made
    with gives a mixer hundreds of times further from the reference than
    ``OUT``: the tolerances can tell it from the model."""
    arch, x = _arch(), _x()
    if fault == "no_rope":
        lp = _layer(params, "0_gqa", at=0)
        sound = reference.gqa_mixer(x, lp, POLICY)
        monkeypatch.setattr(trunk, "_rope", lambda x, theta: x)
        mistaken = trunk.MIXERS["gqa"].mix(x, lp, arch, False)[0]
    else:
        lp = _layer(params, "1_conv", at=0)
        sound = reference.conv_mixer(x, lp, POLICY)
        # the sketch is the program when nothing is planted
        _close(_conv_with(x, lp, None), trunk.MIXERS["conv"].mix(x, lp, arch, False)[0], OUT)
        mistaken = _conv_with(x, lp, fault)
    assert _gap(mistaken, sound) > 300 * OUT


def _minibatch(obs):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    return {
        "obs": obs,
        "actions": jax.random.normal(keys[0], (3, S, 2)),
        "log_probs": -2.0 + 0.1 * jax.random.normal(keys[1], (3, S)),
        "advantages": jax.random.normal(keys[2], (3, S)),
        "returns": jax.random.normal(keys[3], (3, S)),
    }


def _data(mb):
    return MinibatchData(
        obs=mb["obs"], actions=mb["actions"], old_log_probs=mb["log_probs"],
        advantages=mb["advantages"], returns=mb["returns"],
    )


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

    mb = _minibatch(obs)
    config = {"policy": POLICY, "env": ENV, "ppo": {
        "normalize_advantage": True, "clip_range": 0.2, "ent_coef": 0.01,
        "vf_coef": 0.5,
    }}
    r_loss, r_grads = jax.jit(
        jax.value_and_grad(lambda p: reference_ppo.loss_fn(p, config, r_apply, mb))
    )(params)
    data = _data(mb)
    (loss, _), grads = jax.jit(
        jax.value_and_grad(
            lambda p: ppo_loss(p, model.apply, data, PPOConfig()), has_aux=True
        )
    )(params)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), theirs in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, does not weigh
            assert not np.asarray(ours).any() and not np.asarray(theirs).any()
            continue
        if not np.asarray(theirs).any():  # a held expert no token chose
            assert "w_" in name and not np.asarray(ours).any(), name
            continue
        _close(ours, theirs, GRAD)


def test_the_flash_kernel_gives_the_pairs_paths_policy(params, obs, monkeypatch):
    """The chip's path for ``gqa``, the Pallas kernel with no mask (in its
    interpreter here, the swarm padded to its tile), against the pairs loop
    this CPU runs: the policy's mean and value, the PPO loss and its
    gradient in every leaf. The kernel multiplies in three bf16 passes:
    ``OUT`` and ``GRAD`` as above."""
    model = TrunkActorCritic(arch=_arch(), k=K)
    data = _data(_minibatch(obs))

    def run():  # new functions each call: traced anew under the path in force
        out = jax.jit(lambda p, x: model.apply(p, x))(params, obs)
        loss = lambda p: ppo_loss(p, model.apply, data, PPOConfig())[0]  # noqa: E731
        return out, jax.jit(jax.value_and_grad(loss))(params)

    (mean, _, value), (loss, grads) = run()
    taken = []

    def flash():
        taken.append(True)
        return functools.partial(sparse_flash_attention, interpret=True)

    monkeypatch.setattr(trunk, "_flash", flash)
    jax.clear_caches()  # the layers' scan keeps what it traced
    (k_mean, _, k_value), (k_loss, k_grads) = run()
    assert taken
    _close(k_mean, mean, OUT)
    _close(k_value, value, OUT)
    np.testing.assert_allclose(k_loss, loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(k_grads)
    for (path, ours), theirs in zip(flat, jax.tree_util.tree_leaves(grads)):
        if np.asarray(theirs).any():
            _close(ours, theirs, GRAD)
        else:  # the router's bias, a held expert no token chose
            assert not np.asarray(ours).any(), jax.tree_util.keystr(path)


def test_the_causal_softmax_takes_the_kernel_where_there_is_one(monkeypatch):
    """``_causal_softmax`` hands ``q, k, v`` as they are, with no mask, to the
    kernel ``_flash()`` returns, and to the pairs loop where it returns None."""
    q, k, v = jnp.ones((S, 2, 2, 16)), jnp.ones((S, 2, 16)), jnp.ones((S, 2, 8))
    calls = []
    monkeypatch.setattr(
        trunk, "_causal_pairs", lambda *a: calls.append(("pairs", a)) or "pairs"
    )
    monkeypatch.setattr(
        trunk, "_flash", lambda: lambda *a, **kw: calls.append(("kernel", a, kw)) or "kernel"
    )
    assert trunk._causal_softmax(q, k, v, 8, whole_pairs=True) == "kernel"
    (name, args, kwargs), = calls
    assert name == "kernel" and kwargs == {}
    assert all(a is b for a, b in zip(args, (q, k, v))) and len(args) == 3
    calls.clear()
    monkeypatch.setattr(trunk, "_flash", lambda: None)
    assert trunk._causal_softmax(q, k, v, 8, whole_pairs=True) == "pairs"
    assert calls == [("pairs", (q, k, v, 8, True))]

def test_the_bfloat16_control_fails_these_tolerances(params, obs):
    """The reference computed in bfloat16 is not within ``OUT`` of itself
    in float32, by a factor of hundreds: the tolerances above can tell a
    lower precision."""
    r_mean, _, r_value = reference.apply(params, POLICY, ENV, obs)
    c_mean, _, c_value = reference.apply(params, POLICY, ENV, obs, jnp.bfloat16)
    for control, sound in ((c_mean, r_mean), (c_value, r_value)):
        assert _gap(jnp.asarray(control, jnp.float32), sound) > 300 * OUT


def test_expert_shares_add_up_to_the_uncut_layer():
    """The share test: four chips that hold two of the eight experts each;
    their parts add up to the uncut reference's expert layer, and every
    assignment falls on exactly one of them."""
    uncut = reference.init(jax.random.PRNGKey(12), UNCUT, ENV)
    lp = _layer(uncut, "2_conv", at=1)
    lp = {**lp, "router": 25.0 * lp["router"]}  # spread the routing out
    x = jax.random.normal(jax.random.PRNGKey(13), (S, H))
    whole = reference.expert_part(x, lp, UNCUT)
    arch = _arch()
    h2 = trunk._rms(x, lp["moe_norm"], EPS)
    e_top, c = trunk.route(
        h2, lp["router"], 2, True, arch.scoring_func, lp["router_bias"],
        arch.routed_scaling_factor, arch.topk_norm_eps,
    )
    total, held_shares = 0.0, []
    for share in range(4):
        held = slice(2 * share, 2 * share + 2)
        part, counters = trunk.expert_layer(
            h2, e_top, c, lp["w_gate"][held], lp["w_up"][held], lp["w_down"][held],
            (share, 4),
        )
        mine = {**lp, **{w: lp[w][held] for w in ("w_gate", "w_up", "w_down")}}
        _close(part, reference.expert_part(x, mine, UNCUT, range(2 * share, 2 * share + 2)), OUT)
        total = total + part
        held_shares.append(float(counters["moe_held_share"]))
    _close(total, whole, OUT)
    assert sum(held_shares) == pytest.approx(1.0)  # every assignment, once
    assert min(held_shares) > 0.0


def test_forward_counters_are_this_trunks_own(params, obs):
    model = TrunkActorCritic(arch=_arch(), k=K)
    counters = jax.device_get(model.forward_counters(params, obs[:1]))
    assert set(counters) == {"moe_held_share", "moe_load_max_over_mean"}
    assert 0.0 <= counters["moe_held_share"] <= 1.0


def _tiny_cell(tmp_path):
    """The committed cell with the tiny architecture in its place: two
    swarms of 32, a minibatch of two swarm-steps."""
    committed = harness.load_cell(CELL, ROOT)
    s = 32
    env = {**committed.config["env"], "num_agents_per_formation": s}
    swap = {"num_agents_per_formation": s, "trunk": "tiny-lfm2"}
    overrides = [
        f"{key}={swap[key]}" if (key := o.split("=", 1)[0]) in swap else o
        for o in committed.config["overrides"]
    ]
    config = {**committed.config, "env": env, "policy": POLICY, "overrides": overrides}
    job = {**committed.job, "num_formation": 2, "batch_size": 2 * s}
    return dataclasses.replace(
        committed, name="trunk-tiny-lfm2", config=config, job=job,
        bench_dir=tmp_path / "b",
    )


def test_a_training_chunk_is_correct_by_the_harness(tmp_path):
    """One ``Trainer.run_chunk()`` of the trainer ``build_trainer`` makes
    for ``policy=trunk trunk=tiny-lfm2``, against ``reference.ppo.iteration``
    through ``harness.compare`` and the committed cell's limits."""
    lines = []
    result = harness.run_cell(
        _tiny_cell(tmp_path), seed=2**31 + 39, seconds=0.2, trace=False,
        started=time.perf_counter(), require_chip=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    assert result["compared"]["compiles_in_window"] == [0, 0]
    # on the CPU both sides are float32: far inside the chip's limits
    assert result["compared"]["loss_gap_first"][0] < 1e-5
    assert result["compared"]["param_change_gap"][0] < 1e-3


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
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
    """``train.py policy=trunk trunk=tiny-lfm2`` trains and checkpoints; the
    checkpoint's ``trunk`` key rebuilds the policy, which then holds the
    saved parameters and acts; a resumed trainer holds them too."""
    sys.path.insert(0, str(ROOT))
    import train as train_cli
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.utils import (
        env_params_from_config,
        latest_checkpoint,
        load_config,
    )

    run, s = tmp_path / "lfmrun", 16
    common = [
        "name=lfmrun", f"log_dir={run}", "policy=trunk", "trunk=tiny-lfm2",
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
    assert policy.model.arch == trunk.load_trunk_arch("tiny-lfm2")
    layers = policy.params["params"]["layers"]
    assert set(layers) == {"dense0_conv", "0_gqa", "1_conv", "2_conv", "3_conv"}
    resumed = train_cli.build_trainer(load_config(common + job + ["resume=true"]))
    for saved, held in zip(
        jax.tree_util.tree_leaves(policy.params),
        jax.tree_util.tree_leaves(resumed.train_state.params),
    ):
        np.testing.assert_array_equal(saved, held)
    mean, _, value = policy.model.apply(policy.params, jnp.zeros((1, s, OBS_DIM)))
    assert np.isfinite(np.asarray(mean)).all() and np.isfinite(np.asarray(value)).all()


@pytest.mark.parametrize(
    "key,value",
    [
        ("conv_bias", True),
        ("layer_types", TINY["layer_types"][:-1] + ["sliding_attention"]),
        ("layer_types", TINY["layer_types"][:-1]),  # not one a layer
        ("use_expert_bias", False),
        ("rope_parameters", {"rope_theta": 1000000, "rope_type": "yarn"}),
        ("num_dense_layers", 10),  # past the held layers 1-9
        ("num_attention_heads", 5),  # 64 does not divide into 5 heads
        ("head_dim", 32),
        ("hidden_act", "gelu"),
        ("attention_bias", True),
    ],
)
def test_what_is_not_computed_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        _arch(**{key: value})


def test_the_dense_layers_follow_the_held_window():
    # a held window past the leading dense layers holds none; one from layer 0 both
    assert _arch(first_layer_held=2, layers_held=8).dense_layers == 0
    assert _arch(first_layer_held=0, layers_held=10).dense_layers == 2
    assert _arch(first_layer_held=0, layers_held=10).layer_kinds[:3] == ("conv", "conv", "gqa")


def test_the_architecture_files_agree():
    """The program's architecture file against the catalog's row as the
    benchmark configuration holds it: its top level (the published keys as
    they are run, the three that are cut listed in ``reduced``) and its
    ``policy`` group (what the reference computes from)."""
    program = yaml.safe_load((ROOT / f"cfg/trunk/{NAME}.yaml").read_text())
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    for key, value in config["policy"].items():
        if key not in ("kind", "trunk", "log_std_init"):
            assert program[key] == value, key
    assert config["policy"]["trunk"] == NAME
    assert config["policy"]["kind"] == "trunk_lfm2"
    run = {
        "num_hidden_layers": program["layers_held"],
        "num_experts": program["experts_held"],
        "vocab_size": 0,
    }
    ours = ("first_layer_held", "layers_held", "experts_held", "expert_share", "q_chunk_size")
    for key, value in program.items():
        if key in run:
            assert config[key] == run[key], key
        elif key not in ours:
            assert config[key] == value, key
    assert config["published"] == {"num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536}
    for key in run:
        assert program[key] == config["published"][key], key
    assert set(config["reduced"]) == set(run)
    # the published widths, every one
    assert (program["hidden_size"], program["num_attention_heads"], program["num_key_value_heads"],
            program["conv_L_cache"], program["intermediate_size"],
            program["moe_intermediate_size"], program["num_experts_per_tok"]) == (
        2048, 32, 8, 3, 11776, 1536, 4)
    # the published config.json: every key under its name, but for what is cut
    for key, value in PUBLISHED.items():
        assert config[key] == (run[key] if key in run else value), key
    assert config["source"].startswith(SOURCE)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == f"{NAME}-s8k")
    assert set(entry["reduced"]) == set(config["reduced"])
    # Keye's env and PPO numbers and its job, letter for letter
    keye = json.loads((ROOT / "benchmarks/configs/keye-vl2-a3b-ep8-s8k.json").read_text())
    assert config["env"] == keye["env"] and config["ppo"] == keye["ppo"]
    assert [o for o in config["overrides"] if not o.startswith("trunk=")] == [
        o for o in keye["overrides"] if not o.startswith("trunk=")
    ]
    given = dict(o.split("=", 1) for o in config["overrides"])
    assert given["trunk"] == NAME
    for key, value in {**config["env"], **config["ppo"]}.items():
        if key in given:
            assert _parse_value(given[key]) == pytest.approx(value), key
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == cells["keye-vl2-a3b-ep8-s8k-train-m2"]["traffic"]
    assert cells[CELL]["chips"] == 1


def test_the_parameter_count_and_the_flops():
    """452.55 M parameters: a conv mixer 16.79 M, the GQA mixer 10.49 M, a
    held expert layer 75.63 M (8 x 9.437 M and the router), the dense SwiGLU
    72.35 M; and the work a token requires by the reference's count."""
    model = TrunkActorCritic(arch=trunk.load_trunk_arch(NAME), k=4)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 64, 22), jnp.float32)
    )
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    h = 2048
    conv = h + h * 3 * h + 3 * h + h * h  # norm, W_in, taps, W_out
    gqa = h + h * (32 + 2 * 8) * 64 + 64 + 64 + 32 * 64 * h  # norm, W_in, q/k norms, Wo
    experts = h + h * 64 + 64 + 8 * 3 * h * 1536  # norm, router, bias, 8 experts
    dense = h + 3 * h * 11776
    assert (conv, gqa, experts, dense) == (16_785_408, 10_487_936, 75_630_656, 72_353_792)
    heads = 16 * h + h + h + (2 * h + 2) + (2 * h + 1) + 2
    total = (conv + dense) + (gqa + experts) + 3 * (conv + experts) + heads
    assert count == total == 452_551_045
    assert 16 * count / 2**30 == pytest.approx(6.74, abs=5e-3)
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    flops = reference.forward_flops_per_agent(config["policy"], config["env"])
    conv_f = 2 * 4 * h * h + (2 * 3 + 2) * h
    gqa_f = 2 * (h * 48 * 64 + 32 * 64 * h) + 4096.5 * 4 * 32 * 64
    dense_f = 6 * h * 11776
    experts_f = 2 * h * 64 + 4 * 8 / 64 * 6 * h * 1536
    assert flops == pytest.approx(
        4 * conv_f + gqa_f + dense_f + 4 * experts_f + 2 * 16 * h + 2 * h * 2 + 4 * h
    )
    assert flops == pytest.approx(3.724e8, rel=1e-3)
