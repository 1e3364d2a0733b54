"""A trunk whose residual path is not a sum (``policy=trunk
trunk=xing4-29b-a4b-ep8``; ``models/trunk.py``: the ``mla`` mixer, the
``hyper`` residual, a leading dense layer, the sigmoid router) held to its
plain reference (``benchmarks/reference/policy_trunk_mla_hc.py``) on seeded
weights at the size of ``cfg/trunk/tiny-mla-hc.yaml``: hidden 64, latent
attention with 4 heads of 16 + 8 / 16 through ranks 32 and 24, four
hyper-connected streams under a 20-step Sinkhorn, held layers 1-5 of 8 (one
dense layer of 96, four expert layers), 8 experts top-2 scaled by 2 of which
2 are held, a shared expert, of the heads share 1 of 2, blocks of 8 queries,
swarms of 29 or 32.

Tolerances: both sides compute in float32 on the CPU and differ by the
order of their sums (the program takes ``u phi`` on the streams as they are
and scales afterwards, puts a block's key tiles together by their largest
scores, and carries the streams as ``(n, S, hidden)``). Outputs are compared
to 1e-5 of the largest entry (observed 3e-7), gradients to 1e-4 of each
leaf's largest entry (observed 2e-6). The control, the reference computed in
bfloat16, is 2e-2 from it and fails both, and so does a Sinkhorn of no step
or of one (``test_a_short_sinkhorn_fails_these_tolerances``).
"""

import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks import harness
from benchmarks.reference import policy_trunk_mla_hc as reference
from benchmarks.reference import ppo as reference_ppo
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.algo.ppo import MinibatchData, ppo_loss
from marl_distributedformation_tpu.models import trunk
from marl_distributedformation_tpu.models.trunk import TrunkActorCritic, TrunkArch
from marl_distributedformation_tpu.utils.config import _parse_value

ROOT = Path(__file__).resolve().parents[1]
NAME = "xing4-29b-a4b-ep8"
CELL = f"{NAME}-s8k-train-m1"
TINY = yaml.safe_load((ROOT / "cfg" / "trunk" / "tiny-mla-hc.yaml").read_text())
POLICY = {"kind": "trunk_mla_hc", "trunk": "tiny-mla-hc", "log_std_init": 0.0, **TINY}
# every head and every expert on one chip: what the shares add up to
UNCUT = {**POLICY, "head_share": [0, 1], "experts_held": 8, "expert_share": [0, 1]}
S, K, H, N = 29, 4, 64, 4  # 29: the query block does not divide it
ENV = {"knn_k": K, "goal_in_obs": True, "num_agents_per_formation": S}
OBS_DIM = 2 + 4 * K + 2
OUT, GRAD = 1e-5, 1e-4


def _arch(**changes):
    return TrunkArch.from_dict("tiny-mla-hc", {**TINY, **changes})


@pytest.fixture(scope="module")
def params():
    return reference.init(jax.random.PRNGKey(0), POLICY, ENV)


@pytest.fixture(scope="module")
def obs():
    return jax.random.uniform(jax.random.PRNGKey(1), (3, S, OBS_DIM))


def _close(a, b, rel):
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * scale)


def _layer(params, name=None, at=0):
    """A held layer out of the tree: the dense layer ``name``, or the
    ``at``-th expert layer."""
    layers = params["params"]["layers"]
    if name is not None:
        return layers[name]
    return {k: v[at] for k, v in layers.items() if not isinstance(v, dict)}


def _side_by_side(xs):
    """The program's streams, a tuple of ``(S, hidden)``, as the reference
    keeps them: ``vec(X[t]) (S, n hidden)``."""
    return jnp.concatenate(xs, -1)


def _streams():
    return tuple(jax.random.normal(jax.random.PRNGKey(3), (N, S, H)))


def test_the_program_reads_the_tree_the_reference_makes(params, obs):
    arch = _arch()
    assert arch.layer_kinds == ("mla",) * 5 and arch.period == ("mla",)
    assert (arch.dense_layers, arch.residual) == (1, "hyper")
    assert reference.dense_names(POLICY) == ["dense0_mla"]
    model = TrunkActorCritic(arch=arch, k=K)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(2), obs[:1]))
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(params)
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(params)
    ):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
    layers = params["params"]["layers"]
    assert layers["dense0_mla"]["d_in"].shape == (64, 2 * 96)
    assert layers["w_gate"].shape == (4, 2, 64, 32)  # four expert layers
    assert "router" not in layers["dense0_mla"] and "d_in" not in layers
    # the program's own draw of the hyper-connections' parameters is the stated one
    drawn = model.init(jax.random.PRNGKey(2), obs[:1])["params"]["layers"]
    for ours, theirs in ((drawn, layers), (drawn["dense0_mla"], layers["dense0_mla"])):
        np.testing.assert_array_equal(ours["hc_ffn_alpha"], theirs["hc_ffn_alpha"])
        for b in (np.asarray(ours["hc_attn_b"]), np.asarray(theirs["hc_attn_b"])):
            res = b.reshape(-1, N * (N + 2))[:, 2 * N :].reshape(-1, N, N)
            on = res[:, np.arange(N), np.arange(N)].mean()
            assert 1.0 < on < 3.0 and abs((res.sum() - on * res.shape[0] * N)) / (
                res.size - res.shape[0] * N
            ) < 0.75
    assert not np.asarray(drawn["router_bias"]).any()  # zero at the draw


def _mla_pair(lp, arch, policy):
    ours = lambda x, lp: trunk.MIXERS["mla"].mix(x, lp, arch, False)[0]  # noqa: E731
    theirs = lambda x, lp: reference.mla_mixer(x, lp, policy)  # noqa: E731
    return ours, theirs, jax.random.normal(jax.random.PRNGKey(3), (S, H)), lp


def _hyper_pair(lp, arch, policy):
    """A sublayer's read and write around a stand-in ``F`` that mixes tokens."""
    f = lambda x: jnp.tanh(x) + jnp.cumsum(x, 0) / S  # noqa: E731

    def ours(xs, lp):
        mixed, base, how, _ = trunk.RESIDUALS["hyper"].read(xs, lp, "ffn", arch)
        return _side_by_side(trunk.RESIDUALS["hyper"].add(base, f(mixed), how))

    def theirs(xs, lp):
        return reference.hyper_sublayer(_side_by_side(xs), lp, "ffn", f, policy)

    return ours, theirs, _streams(), lp


def _dense_pair(lp, arch, policy):
    def ours(x, lp):
        h2 = trunk._rms(x, lp["dense_norm"], arch.rms_norm_eps)
        return trunk.dense_ffn(h2, lp["d_in"], lp["d_down"])

    theirs = lambda x, lp: reference.dense_ffn(x, lp, policy)  # noqa: E731
    return ours, theirs, jax.random.normal(jax.random.PRNGKey(3), (S, H)), lp


def _router_pair(lp, arch, policy):
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
        ))

    theirs = lambda x, lp: spread(*reference.route(x, lp["router"], lp["router_bias"], policy))  # noqa: E731
    return ours, theirs, jax.random.normal(jax.random.PRNGKey(3), (S, H)), lp


@pytest.mark.parametrize("part", ["mla", "hyper", "dense", "router"])
def test_a_part_alone_matches_the_reference_forward_and_gradient(params, part):
    """The latent attention, a hyper-connected sublayer, the dense
    feed-forward and the sigmoid router, each on one swarm: what it gives,
    and the gradient of a scalar of it with respect to the input and every
    weight it reads."""
    lp = _layer(params, "dense0_mla") if part == "dense" else _layer(params, at=2)
    pair = {"mla": _mla_pair, "hyper": _hyper_pair, "dense": _dense_pair, "router": _router_pair}
    ours, theirs, x, lp = pair[part](lp, _arch(), POLICY)
    out = jax.jit(ours)(x, lp)
    _close(out, theirs(x, lp), OUT)
    weight = jax.random.normal(jax.random.PRNGKey(4), out.shape)
    scalar = lambda f: (lambda x, lp: (weight * f(x, lp)).sum())  # noqa: E731
    g_ours = jax.jit(jax.grad(scalar(ours), argnums=(0, 1)))(x, lp)
    g_theirs = jax.jit(jax.grad(scalar(theirs), argnums=(0, 1)))(x, lp)
    read = {
        "mla": set(trunk.MIXERS["mla"].shapes(_arch())),
        "hyper": {"hc_ffn_phi", "hc_ffn_alpha", "hc_ffn_b"},
        "dense": {"dense_norm", "d_in", "d_down"},
        "router": {"router"},  # the bias selects and does not weigh: no gradient
    }[part]
    jax.tree_util.tree_map(lambda a, b: _close(a, b, GRAD), g_ours[0], g_theirs[0])
    for name in lp:
        a, b = np.asarray(g_ours[1][name]), np.asarray(g_theirs[1][name])
        if name in read:
            assert b.any(), name
            _close(a, b, GRAD)
        else:
            assert not a.any() and not b.any(), name
    if part == "router":  # two experts a token, their weights summing to the scale
        chosen = np.asarray(out) > 0
        assert (chosen.sum(-1) == 2).all()
        np.testing.assert_allclose(np.asarray(out).sum(-1), 2.0, rtol=1e-5)
        plain = theirs(x, {**lp, "router_bias": jnp.zeros(8)})
        assert ((np.asarray(plain) > 0) != chosen).any()  # the bias selected


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
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, does not weigh
            assert not np.asarray(ours).any() and not np.asarray(theirs).any()
            continue
        assert np.asarray(theirs).any(), name  # everything else is trained
        _close(ours, theirs, GRAD)


def test_the_bfloat16_control_fails_these_tolerances(params, obs):
    """The reference computed in bfloat16 is not within ``OUT`` of itself
    in float32, by a factor of hundreds: the tolerances above can tell a
    lower precision."""
    r_mean, _, r_value = reference.apply(params, POLICY, ENV, obs)
    c_mean, _, c_value = reference.apply(params, POLICY, ENV, obs, jnp.bfloat16)
    for control, sound in ((c_mean, r_mean), (c_value, r_value)):
        gap = np.abs(np.asarray(control) - np.asarray(sound)).max()
        assert gap > 300 * OUT * np.abs(np.asarray(sound)).max()


def _h_res_of_the_program(lp, arch):
    """``H_res (S, row, column)`` read off the program: with ``phi`` at zero
    the coefficients are the biases' alone, and ``H_res X`` for the four unit
    streams is ``H_res``."""
    units = tuple(jnp.broadcast_to(row, (S, N)) for row in jnp.eye(N))
    static = {**lp, "hc_attn_phi": jnp.zeros((N * N, N * (N + 2)))}
    _, rows, _, counters = trunk.RESIDUALS["hyper"].read(
        units, static, "attn", dataclasses.replace(arch, hidden_size=N)
    )
    return jnp.stack(rows, 1), counters


def test_sinkhorn_makes_rows_and_columns_sum_to_one(params):
    """Twenty steps on the seeded biases: the program's ``H_res`` and the
    reference's agree, both sum to 1 along rows and columns to rounding, and
    lie visibly off the identity (the off-diagonal mass the biases' draw is
    there for); after one step the columns do not sum to 1."""
    lp = _layer(params, at=1)
    h_res, counters = _h_res_of_the_program(lp, _arch())
    static = {**lp, "hc_attn_phi": jnp.zeros_like(lp["hc_attn_phi"])}
    xs = _side_by_side(_streams())
    _, _, theirs = reference.hyper_coefficients(xs, static, "attn", POLICY)
    _close(h_res, theirs, OUT)
    for h in (np.asarray(h_res), np.asarray(theirs)):
        np.testing.assert_allclose(h.sum(-1), 1.0, atol=5e-6)
        np.testing.assert_allclose(h.sum(-2), 1.0, atol=5e-5)
    assert float(counters["hc_sinkhorn_row_err"]) < 5e-6
    assert 0.1 < float(counters["hc_res_offdiag_mean"]) < 0.7
    one_step = {**POLICY, "hc_sinkhorn_iters": 1}
    _, _, once = reference.hyper_coefficients(xs, lp, "attn", one_step)
    assert np.abs(np.asarray(once).sum(-2) - 1.0).max() > 0.02


@pytest.mark.parametrize("iters", [0, 1])
def test_a_short_sinkhorn_fails_these_tolerances(params, obs, iters):
    """``M_0`` used as it is, or one step of the 20, in the program's place:
    the policy's outputs leave the reference's by far more than ``OUT``, so a
    Sinkhorn that is skipped or cut short cannot pass for the model."""
    r_mean, _, r_value = reference.apply(params, POLICY, ENV, obs)
    short = TrunkActorCritic(
        arch=dataclasses.replace(_arch(), hc_sinkhorn_iters=iters), k=K
    )
    mean, _, value = jax.jit(short.apply)(params, obs)
    for ours, sound in ((mean, r_mean), (value, r_value)):
        gap = np.abs(np.asarray(ours) - np.asarray(sound)).max()
        assert gap > 300 * OUT * np.abs(np.asarray(sound)).max()


def _head_slice(lp, share, count):
    """Share ``share`` of ``count`` of an uncut layer's latent attention: its
    heads' columns of the second halves (rows of ``wo``); the low-rank first
    halves and the norms whole."""
    cols = lambda a: jnp.split(a, count, axis=-1)[share]  # noqa: E731
    return {
        **lp, "wq_b": cols(lp["wq_b"]), "wkv_b": cols(lp["wkv_b"]),
        "wo": jnp.split(lp["wo"], count, axis=0)[share],
    }


def test_head_shares_add_up_to_the_uncut_mixer():
    """The share test, heads: the two chips that hold half of the heads each
    give partial outputs ``o @ wo`` that add up to what the reference gives
    for the latent attention with every head."""
    uncut = reference.init(jax.random.PRNGKey(10), UNCUT, ENV)
    lp = _layer(uncut, at=0)
    x = jax.random.normal(jax.random.PRNGKey(11), (S, H))
    whole = reference.mla_mixer(x, lp, UNCUT)
    total = 0.0
    for share in range(2):
        arch = _arch(head_share=[share, 2])
        held = _head_slice(lp, share, 2)
        for name, (_, shape) in trunk.MIXERS["mla"].shapes(arch).items():
            assert held[name].shape == shape, name  # the widths follow the heads held
        part = trunk.MIXERS["mla"].mix(x, held, arch, False)[0]
        _close(part, reference.mla_mixer(x, held, {**POLICY, "head_share": [share, 2]}), OUT)
        assert float(jnp.abs(part - whole).max()) > 0.1 * float(jnp.abs(whole).max())
        total = total + part
    _close(total, whole, OUT)


def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test, experts: four chips that hold two of the eight
    experts each; their routed parts, with the shared expert (which every
    chip computes alike) counted once, add up to the uncut reference's
    expert layer."""
    uncut = reference.init(jax.random.PRNGKey(12), UNCUT, ENV)
    lp = _layer(uncut, at=3)
    lp = {**lp, "router": 25.0 * lp["router"]}  # spread the routing out
    x = jax.random.normal(jax.random.PRNGKey(13), (S, H))
    routed, shared = reference.expert_part(x, lp, UNCUT)
    arch = _arch()
    h2 = trunk._rms(x, lp["moe_norm"], TINY["rms_norm_eps"])
    e_top, c = trunk.route(
        h2, lp["router"], 2, True, arch.scoring_func, lp["router_bias"],
        arch.routed_scaling_factor,
    )
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


@pytest.mark.parametrize("pair", [0, 10, 23, 31])
def test_yarn_frequencies_are_the_hand_computed_ones(pair):
    """At the published numbers (64 rotary dimensions, theta 10,000, factor
    64 over 4,096 positions, beta 32 and 1): ``low`` is 10 and ``high`` 23;
    pairs up to ``low`` keep ``theta^(-2i/64)``, pairs from ``high`` on turn
    64 times slower. Program and reference, each by its own code."""
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    assert reference.yarn_correction_range(config["policy"]) == (10, 23)
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(1e4))) == 23
    plain = 1e4 ** (-2 * pair / 64)
    by_hand = {0: 1.0, 10: plain, 23: plain / 64, 31: plain / 64}[pair]
    assert by_hand == pytest.approx(
        {0: 1.0, 10: 5.6234e-2, 23: 2.0836e-5, 31: 2.0836e-6}[pair], rel=1e-3
    )
    ours, mscale2 = trunk._yarn(trunk.load_trunk_arch(NAME))
    theirs = reference.yarn_inv_freq(config["policy"])
    assert float(ours[pair]) == pytest.approx(by_hand, rel=1e-5)
    assert float(theirs[pair]) == pytest.approx(by_hand, rel=1e-5)
    # between them a ramp, falling
    assert np.all(np.diff(np.asarray(ours)) < 0)
    assert mscale2 * 192**-0.5 == pytest.approx(reference.softmax_scale(config["policy"]))
    assert mscale2 == pytest.approx((0.1 * math.log(64) + 1) ** 2)


def test_forward_counters_are_this_trunks_own(params, obs):
    model = TrunkActorCritic(arch=_arch(), k=K)
    counters = jax.device_get(model.forward_counters(params, obs[:1]))
    assert set(counters) == {
        "moe_held_share", "moe_load_max_over_mean", "hc_res_offdiag_mean",
        "hc_sinkhorn_row_err",
    }
    assert set(counters) < set(trunk.COUNTERS)
    assert 0.1 < counters["hc_res_offdiag_mean"] < 0.6  # off the identity
    assert counters["hc_sinkhorn_row_err"] < 5e-6  # rows are normalised last
    assert 0.0 < counters["moe_held_share"] < 1.0


def _tiny_cell(tmp_path):
    """The committed cell with the tiny architecture in its place: two
    swarms of 32, a minibatch of two swarm-steps."""
    committed = harness.load_cell(CELL, ROOT)
    s = 32
    env = {**committed.config["env"], "num_agents_per_formation": s}
    swap = {"num_agents_per_formation": s, "trunk": "tiny-mla-hc"}
    overrides = [
        f"{key}={swap[key]}" if (key := o.split("=", 1)[0]) in swap else o
        for o in committed.config["overrides"]
    ]
    config = {**committed.config, "env": env, "policy": POLICY, "overrides": overrides}
    job = {**committed.job, "num_formation": 2, "batch_size": 2 * s}
    return dataclasses.replace(
        committed, name="trunk-tiny-mla-hc", config=config, job=job,
        bench_dir=tmp_path / "b",
    )


def test_a_training_chunk_is_correct_by_the_harness(tmp_path):
    """One ``Trainer.run_chunk()`` of the trainer ``build_trainer`` makes
    for ``policy=trunk trunk=tiny-mla-hc``, against ``reference.ppo.iteration``
    through ``harness.compare`` and the committed cell's limits."""
    lines = []
    result = harness.run_cell(
        _tiny_cell(tmp_path), seed=2**31 + 35, seconds=0.2, trace=False,
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
    """``train.py policy=trunk trunk=tiny-mla-hc`` trains and checkpoints;
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

    run, s = tmp_path / "mlarun", 16
    common = [
        "name=mlarun", f"log_dir={run}", "policy=trunk", "trunk=tiny-mla-hc",
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
    assert policy.model.arch == trunk.load_trunk_arch("tiny-mla-hc")
    layers = policy.params["params"]["layers"]
    assert {"dense0_mla", "wkv_a", "hc_attn_phi", "router_bias"} <= set(layers)
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
        ("n_group", 2), ("topk_group", 2), ("num_nextn_predict_layers", 1),
        ("rope_scaling", {**TINY["rope_scaling"], "type": "linear"}),
        ("rope_scaling", {**TINY["rope_scaling"], "mscale": 0.707}),
        ("rope_scaling", None), ("scoring_func", "tanh"), ("topk_method", "group_limited_greedy"),
        ("moe_layer_freq", 2), ("num_key_value_heads", 2), ("hc_sinkhorn_iters", 0),
        ("hidden_act", "gelu"), ("attention_bias", True),
    ],
)
def test_what_is_not_computed_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match="num_attention_heads" if key == "num_key_value_heads" else key):
        _arch(**{key: value})


@pytest.mark.parametrize(
    "name,changes",
    [
        ("head_share", {"head_share": [0, 3]}),  # 4 heads do not divide over 3
        ("head_share", {"head_share": [2, 2]}),
        ("expert_share", {"experts_held": 3}),
        ("layers_held", {"first_layer_held": 4}),  # layers 4-8 of 8
        ("layers_held", {"layers_held": 0}),
    ],
)
def test_a_share_that_does_not_fit_is_refused(name, changes):
    with pytest.raises(ValueError, match=name):
        _arch(**changes)
    # and what the file's shape decides: a held window that starts after the
    # leading dense layers has none, one that starts at layer 0 has both
    assert _arch(first_layer_held=3).dense_layers == 0
    assert _arch(first_layer_held=0).dense_layers == 2


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
    assert config["policy"]["kind"] == "trunk_mla_hc"
    heads = program["head_share"][1]
    run = {
        "num_hidden_layers": program["layers_held"],
        "n_routed_experts": program["experts_held"],
        "num_attention_heads": program["num_attention_heads"] // heads,
        "num_key_value_heads": program["num_key_value_heads"] // heads,
        "num_nextn_predict_layers": 0,
        "vocab_size": 0,
    }
    ours = ("first_layer_held", "layers_held", "experts_held", "expert_share",
            "head_share", "q_chunk_size")
    for key, value in program.items():
        if key in run:
            assert config[key] == run[key], key
        elif key not in ours:
            assert config[key] == value, key
    # the published values beside the cut ones; the program's file keeps
    # them but for the prediction layer, which it states as held (none)
    assert config["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 64, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "vocab_size": 131072,
    }
    for key in set(run) - {"num_nextn_predict_layers"}:
        assert program[key] == config["published"][key], key
    assert program["num_nextn_predict_layers"] == 0
    assert program["head_share"] == [0, 2]  # ISSUE 35's first fallback: 16 heads
    assert set(config["reduced"]) == set(run)
    # the catalog's row: every number under its key, but for what is reduced
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(
            r for r in map(json.loads, catalog.read_text().splitlines())
            if r["name"] == "Xing4.0-29B-A4B"
        )
        for key, value in row["config"].items():
            assert config[key] == (run[key] if key in run else value), key
        assert config["source"].startswith(row["source_url"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == f"{NAME}-s8k")
    assert set(entry["reduced"]) == set(config["reduced"])
    # Solar's env and PPO numbers and its job, letter for letter
    solar = json.loads(
        (ROOT / "benchmarks/configs/solar-open2-250b-ep40-tp8-s8k.json").read_text()
    )
    assert config["env"] == solar["env"] and config["ppo"] == solar["ppo"]
    assert [o for o in config["overrides"] if not o.startswith("trunk=")] == [
        o for o in solar["overrides"] if not o.startswith("trunk=")
    ]
    given = dict(o.split("=", 1) for o in config["overrides"])
    assert given["trunk"] == NAME
    for key, value in {**config["env"], **config["ppo"]}.items():
        if key in given:
            assert _parse_value(given[key]) == pytest.approx(value), key
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == cells["solar-open2-250b-ep40-tp8-s8k-train-m1"]["traffic"]
    assert cells[CELL]["chips"] == 1


def test_the_parameter_count_and_the_flops_are_the_issues():
    """583.0 M parameters with 16 of the 32 heads (ISSUE 35's first fallback;
    its arithmetic for all 32: latent attention 28,411,136 a layer with its
    two inner norms, 641.9 M in all), 8 experts 88,080,384, the shared expert
    11,010,048, router and bias 229,440, two sublayers' phi 688,128, the
    dense SwiGLU 99,090,432; and the work a token requires by the reference's
    count."""
    model = TrunkActorCritic(arch=trunk.load_trunk_arch(NAME), k=4)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 64, 22), jnp.float32)
    )
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))

    def mla(heads):  # Wqa, Wqb, Wkva, Wkvb, Wo, the two inner norms
        return (
            3584 * 768 + 768 * heads * 192 + 3584 * 576 + 512 * heads * 256
            + heads * 128 * 3584 + 768 + 512
        )

    assert mla(32) == 28_411_136 and mla(16) == 16_614_656
    hyper = 2 * (4 * 3584 * 24 + 3 + 24)  # phi 688,128, and alpha and b
    assert (3584 * 64 + 64, 8 * 3 * 3584 * 1024, 3 * 3584 * 1024, 3 * 3584 * 9216) == (
        229_440, 88_080_384, 11_010_048, 99_090_432
    )
    shared = 2 * 3584 + hyper  # the two sublayers' norms
    heads = 16 * 3584 + 3584 + 3584 + (3584 * 2 + 2) + (2 * 3584 + 1) + 2

    def total(attention):
        expert_layer = attention + shared + 229_440 + 88_080_384 + 11_010_048
        dense_layer = attention + shared + 99_090_432
        return 4 * expert_layer + dense_layer + heads

    assert count == total(mla(16)) == 582_998_803
    assert total(mla(16)) == pytest.approx(583e6, rel=1e-3)
    assert total(mla(32)) == pytest.approx(641.9e6, rel=1e-3)  # the issue's, uncut
    config = json.loads((ROOT / f"benchmarks/configs/{NAME}-s8k.json").read_text())
    flops = reference.forward_flops_per_agent(config["policy"], config["env"])
    attention = 2 * (mla(16) - 768 - 512) + 4096.5 * 2 * 16 * (192 + 128)
    residual = 2 * (2 * 4 * 3584 * 24 + 4 * 16 * 20 + 2 * 3584 * (4 + 16 + 4))
    experts = 2 * 3584 * 64 + 4 * 8 / 64 * 6 * 3584 * 1024 + 6 * 3584 * 1024
    dense = 6 * 3584 * 9216
    assert flops == pytest.approx(
        5 * (attention + residual) + 4 * experts + dense
        + 2 * 16 * 3584 + 2 * 3584 * 2 + 2 * 7168
    )
    assert attention == pytest.approx(75.2e6, rel=1e-2)
    assert flops == pytest.approx(7.168e8, rel=1e-3)
