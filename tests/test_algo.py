"""Tests for GAE, PPO loss, and the minibatch update."""

import dataclasses

import chex
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.algo import (
    MinibatchData,
    PPOConfig,
    compute_gae,
    ppo_loss,
    ppo_update,
)
from marl_distributedformation_tpu.algo import ppo as ppo_module
from marl_distributedformation_tpu.models import (
    GNNActorCritic,
    MLPActorCritic,
    distributions,
)
from flax.training.train_state import TrainState


def naive_gae(rewards, values, dones, last_value, gamma, lam):
    T = rewards.shape[0]
    adv = np.zeros_like(rewards)
    next_adv = np.zeros_like(last_value)
    for t in reversed(range(T)):
        next_v = values[t + 1] if t + 1 < T else last_value
        nt = 1.0 - dones[t]
        delta = rewards[t] + gamma * next_v * nt - values[t]
        next_adv = delta + gamma * lam * nt * next_adv
        adv[t] = next_adv
    return adv, adv + values


def test_gae_matches_naive_loop():
    rng = np.random.default_rng(0)
    T, B = 12, 7
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    values = rng.normal(size=(T, B)).astype(np.float32)
    dones = (rng.random((T, B)) < 0.2).astype(np.float32)
    last_value = rng.normal(size=(B,)).astype(np.float32)
    adv, ret = compute_gae(
        jnp.asarray(rewards),
        jnp.asarray(values),
        jnp.asarray(dones),
        jnp.asarray(last_value),
        0.99,
        0.95,
    )
    exp_adv, exp_ret = naive_gae(rewards, values, dones, last_value, 0.99, 0.95)
    np.testing.assert_allclose(np.asarray(adv), exp_adv, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ret), exp_ret, rtol=1e-4, atol=1e-5)


def test_gae_no_bootstrap_through_done():
    """A done at t cuts both the value bootstrap and advantage recursion."""
    rewards = jnp.array([[1.0], [1.0], [1.0]])
    values = jnp.zeros((3, 1))
    dones = jnp.array([[0.0], [1.0], [0.0]])
    last_value = jnp.array([100.0])
    adv, _ = compute_gae(rewards, values, dones, last_value, 1.0, 1.0)
    # t=1 terminal: adv = r only. t=0 chains through t=1.
    np.testing.assert_allclose(np.asarray(adv[1]), [1.0])
    np.testing.assert_allclose(np.asarray(adv[0]), [2.0])
    # t=2 bootstraps from last_value (no done).
    np.testing.assert_allclose(np.asarray(adv[2]), [101.0])


def _make_train_state(seed=0, obs_dim=8):
    config = PPOConfig(batch_size=16, n_epochs=2)
    model = MLPActorCritic(act_dim=2)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, obs_dim)))
    ts = TrainState.create(
        apply_fn=model.apply, params=params, tx=config.make_optimizer()
    )
    return ts, config


def _make_batch(ts, key, n=64, obs_dim=8):
    k1, k2 = jax.random.split(key)
    obs = jax.random.normal(k1, (n, obs_dim))
    mean, log_std, values = ts.apply_fn(ts.params, obs)
    actions = distributions.sample(k2, mean, log_std)
    logp = distributions.log_prob(actions, mean, log_std)
    advantages = jax.random.normal(jax.random.PRNGKey(3), (n,))
    return MinibatchData(
        obs=obs,
        actions=actions,
        old_log_probs=logp,
        advantages=advantages,
        returns=values + advantages,
    )


def test_ppo_loss_at_old_policy():
    """With new == old policy, ratio == 1: policy loss is -mean(norm_adv)
    (~0 after normalization) and approx_kl is 0."""
    ts, config = _make_train_state()
    mb = _make_batch(ts, jax.random.PRNGKey(1))
    loss, metrics = ppo_loss(ts.params, ts.apply_fn, mb, config)
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(metrics["approx_kl"]), 0.0, atol=1e-5)
    np.testing.assert_allclose(float(metrics["clip_fraction"]), 0.0, atol=1e-6)
    # Normalized advantages have ~zero mean -> tiny policy loss.
    assert abs(float(metrics["policy_loss"])) < 1e-5
    # Value loss is mse(returns, values) = mean(adv^2) here.
    np.testing.assert_allclose(
        float(metrics["value_loss"]),
        float((mb.advantages**2).mean()),
        rtol=1e-4,
    )


def test_ppo_loss_clipping_engages():
    ts, config = _make_train_state()
    mb = _make_batch(ts, jax.random.PRNGKey(2))
    # Shift old log probs to fake a big ratio.
    mb_shifted = MinibatchData(
        obs=mb.obs,
        actions=mb.actions,
        old_log_probs=mb.old_log_probs - 1.0,
        advantages=mb.advantages,
        returns=mb.returns,
    )
    _, metrics = ppo_loss(ts.params, ts.apply_fn, mb_shifted, config)
    assert float(metrics["clip_fraction"]) > 0.9


def test_value_clipping_semantics():
    """clip_range_vf (SB3's optional value clipping): None reproduces the
    unclipped loss exactly, a huge range is a no-op, and range 0 pins the
    value loss at MSE(returns, old_values) with ZERO critic gradient —
    old_values recovered from the GAE identity returns - advantages."""
    ts, config = _make_train_state()
    mb = _make_batch(ts, jax.random.PRNGKey(5))

    import dataclasses

    loss_none, m_none = ppo_loss(ts.params, ts.apply_fn, mb, config)
    loss_huge, _ = ppo_loss(
        ts.params, ts.apply_fn, mb,
        dataclasses.replace(config, clip_range_vf=1e9),
    )
    np.testing.assert_allclose(
        float(loss_none), float(loss_huge), rtol=1e-6
    )

    # Evaluate at PERTURBED params: the fixture builds returns from ts's
    # own values, so at ts the prediction sits exactly on the clip
    # boundary (values == old_values), where clip's subgradient passes
    # through — only away from the boundary does clipping bite.
    ts2, _ = _make_train_state(seed=1)
    cfg0 = dataclasses.replace(config, clip_range_vf=0.0)
    _, m0 = ppo_loss(ts2.params, ts2.apply_fn, mb, cfg0)
    old_values = np.asarray(mb.returns - mb.advantages)
    np.testing.assert_allclose(
        float(m0["value_loss"]),
        float(((np.asarray(mb.returns) - old_values) ** 2).mean()),
        rtol=1e-5,
    )
    grads = jax.grad(lambda p: ppo_loss(p, ts2.apply_fn, mb, cfg0)[0])(
        ts2.params
    )
    vf_grad = np.abs(
        np.asarray(grads["params"]["vf_head"]["kernel"])
    ).max()
    assert vf_grad == 0.0, f"critic grad must vanish at clip 0: {vf_grad}"

    # Mid-range: hand-computed clipped MSE.
    cfg_mid = dataclasses.replace(config, clip_range_vf=0.05)
    _, m_mid = ppo_loss(ts2.params, ts2.apply_fn, mb, cfg_mid)
    _, _, values = ts2.apply_fn(ts2.params, mb.obs)
    clipped = old_values + np.clip(
        np.asarray(values) - old_values, -0.05, 0.05
    )
    np.testing.assert_allclose(
        float(m_mid["value_loss"]),
        float(((np.asarray(mb.returns) - clipped) ** 2).mean()),
        rtol=1e-5,
    )


def test_ppo_update_improves_loss_and_changes_params():
    ts, config = _make_train_state()
    data = _make_batch(ts, jax.random.PRNGKey(4), n=256)
    ts2, metrics = ppo_update(ts, data, jax.random.PRNGKey(5), config)
    assert np.isfinite(float(metrics["loss"]))
    # Parameters moved.
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), ts.params, ts2.params
    )
    assert max(jax.tree_util.tree_leaves(diff)) > 0
    # Value loss should drop when re-evaluated on the same data.
    _, m0 = ppo_loss(ts.params, ts.apply_fn, data, config)
    _, m1 = ppo_loss(ts2.params, ts.apply_fn, data, config)
    assert float(m1["value_loss"]) < float(m0["value_loss"])


def test_ent_coef_decay_matches_constant_when_degenerate():
    """ent_coef_final == ent_coef must be BIT-IDENTICAL to no schedule:
    the decay plumbing may not perturb unscheduled numerics."""
    ts, config = _make_train_state()
    data = _make_batch(ts, jax.random.PRNGKey(4), n=64)
    plain, m_plain = ppo_update(ts, data, jax.random.PRNGKey(5), config)
    degen = dataclasses.replace(
        config, ent_coef_final=config.ent_coef, total_iterations=3
    )
    sched, m_sched = ppo_update(ts, data, jax.random.PRNGKey(5), degen)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(sched.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "ent_coef" not in m_plain
    np.testing.assert_allclose(float(m_sched["ent_coef"]), config.ent_coef)


def test_ent_coef_decay_anneals_with_optimizer_step():
    """The coefficient interpolates ent_coef -> ent_coef_final on
    TrainState.step: consecutive updates report strictly decreasing
    means, reaching ~ent_coef_final by the horizon."""
    ts, config = _make_train_state()
    config = dataclasses.replace(
        config, ent_coef_final=0.0, total_iterations=2
    )
    data = _make_batch(ts, jax.random.PRNGKey(4), n=64)
    ts, m1 = ppo_update(ts, data, jax.random.PRNGKey(5), config)
    ts, m2 = ppo_update(ts, data, jax.random.PRNGKey(6), config)
    ts, m3 = ppo_update(ts, data, jax.random.PRNGKey(7), config)
    c1, c2, c3 = (float(m["ent_coef"]) for m in (m1, m2, m3))
    assert config.ent_coef >= c1 > c2 > c3 >= 0.0
    # Past the horizon the schedule clamps at the final value.
    ts, m4 = ppo_update(ts, data, jax.random.PRNGKey(8), config)
    np.testing.assert_allclose(float(m4["ent_coef"]), 0.0, atol=1e-7)


def test_ent_coef_decay_requires_horizon():
    ts, config = _make_train_state()
    config = dataclasses.replace(config, ent_coef_final=0.0)
    data = _make_batch(ts, jax.random.PRNGKey(4), n=256)
    with pytest.raises(AssertionError, match="total_iterations"):
        ppo_update(ts, data, jax.random.PRNGKey(5), config)


def test_log_std_decay_projects_parameter_to_ceiling():
    """log_std_final clamps the LEARNED log_std parameter under a
    linearly-decaying ceiling: by the horizon the parameter itself sits
    at/below the final value — so the checkpointed policy IS the
    narrow-noise policy and deterministic eval stops misrepresenting
    it. (A loss-term pull could not do this: clipped-Adam steps are
    ~learning_rate-sized, far too slow to traverse nats in-run.)"""
    ts, config = _make_train_state()
    config = dataclasses.replace(
        config, log_std_final=-2.0, total_iterations=4
    )
    data = _make_batch(ts, jax.random.PRNGKey(4), n=64)
    start = float(np.asarray(ts.params["params"]["log_std"]).max())
    for k in range(8):
        ts, m = ppo_update(ts, data, jax.random.PRNGKey(10 + k), config)
    end = float(np.asarray(ts.params["params"]["log_std"]).max())
    assert start == 0.0  # parity init
    assert end <= -2.0 + 1e-6, f"log_std above final ceiling: {end}"
    # Past the horizon the ceiling clamps at the final value.
    np.testing.assert_allclose(
        float(m["log_std_ceiling"]), -2.0, atol=1e-6
    )
    # The entropy schedule was NOT engaged (independent knobs).
    assert "ent_coef" not in m


def test_log_std_decay_touches_only_log_std():
    """The projection is path-keyed: a single-minibatch update with the
    schedule must leave every non-log_std parameter BIT-IDENTICAL to the
    plain run (the schedule adds no loss term and no gradient), and clamp
    log_std to the ceiling."""
    ts, config = _make_train_state()
    config = dataclasses.replace(config, n_epochs=1, batch_size=256)
    data = _make_batch(ts, jax.random.PRNGKey(4), n=256)
    plain, _ = ppo_update(ts, data, jax.random.PRNGKey(5), config)
    sched_cfg = dataclasses.replace(
        config, log_std_final=-2.0, total_iterations=3
    )
    sched, m_sched = ppo_update(ts, data, jax.random.PRNGKey(5), sched_cfg)
    flat_plain = jax.tree_util.tree_flatten_with_path(plain.params)[0]
    flat_sched = jax.tree_util.tree_flatten_with_path(sched.params)[0]
    for (path, a), (_, b) in zip(flat_plain, flat_sched):
        name = getattr(path[-1], "key", None)
        if name == "log_std":
            np.testing.assert_array_equal(
                np.asarray(b),
                np.minimum(np.asarray(a), float(m_sched["log_std_ceiling"])),
            )
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_log_std_decay_requires_horizon():
    ts, config = _make_train_state()
    config = dataclasses.replace(config, log_std_final=-2.0)
    data = _make_batch(ts, jax.random.PRNGKey(4), n=256)
    with pytest.raises(AssertionError, match="total_iterations"):
        ppo_update(ts, data, jax.random.PRNGKey(5), config)


def test_ppo_update_batch_remainder_dropped():
    """total=100, batch=64 -> one minibatch of 64 per epoch, no crash."""
    ts, config = _make_train_state()
    config = PPOConfig(batch_size=64, n_epochs=1)
    data = _make_batch(ts, jax.random.PRNGKey(6), n=100)
    ts2, metrics = ppo_update(ts, data, jax.random.PRNGKey(7), config)
    assert np.isfinite(float(metrics["loss"]))


# ----------------------------------------------------------------------
# Row packing: one gather a minibatch, the same rows bit for bit
# ----------------------------------------------------------------------


def _unpacked(monkeypatch, update, *args):
    """``update(*args)`` with every leaf gathered on its own, as before rows
    were packed: the reference the packed path has to equal bit for bit."""
    with monkeypatch.context() as m:
        m.setattr(ppo_module, "_pack_rows", lambda data: None)
        return update(*args)


def _assert_same_update(got, want):
    (ts_got, metrics_got), (ts_want, metrics_want) = got, want
    chex.assert_trees_all_equal(
        (ts_got.params, ts_got.opt_state, ts_got.step, metrics_got),
        (ts_want.params, ts_want.opt_state, ts_want.step, metrics_want),
    )


def _traces_row_pack(ts, data, config):
    text = jax.jit(
        lambda ts, data, key: ppo_update(ts, data, key, config)
    ).lower(ts, data, jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "minibatch_gather/" in text
    return "minibatch_gather/row_pack/" in text


def _per_formation_batch(key, rows, n, obs_dim, with_mask):
    ks = jax.random.split(key, 6)
    active = (jax.random.uniform(ks[5], (rows, n)) > 0.2).astype(jnp.float32)
    active = active.at[:, 0].set(1.0)
    return MinibatchData(
        obs=jax.random.normal(ks[0], (rows, n, obs_dim)),
        actions=jax.random.normal(ks[1], (rows, n, 2)),
        old_log_probs=-jnp.abs(jax.random.normal(ks[2], (rows, n))),
        advantages=jax.random.normal(ks[3], (rows, n)),
        returns=jax.random.normal(ks[4], (rows, n)),
        weights=active if with_mask else None,
        mask=active if with_mask else None,
    )


@pytest.mark.parametrize("rows", [256, 100], ids=["whole", "remainder"])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
def test_packed_rows_equal_a_gather_a_leaf_bitwise(monkeypatch, weighted, rows):
    ts, config = _make_train_state()
    config = dataclasses.replace(config, batch_size=48)
    data = _make_batch(ts, jax.random.PRNGKey(4), n=rows)
    if weighted:
        w = jax.random.uniform(jax.random.PRNGKey(8), (rows,)) > 0.25
        data = data.replace(weights=w.astype(jnp.float32))
    assert _traces_row_pack(ts, data, config)
    args = (ts, data, jax.random.PRNGKey(5), config)
    _assert_same_update(ppo_update(*args), _unpacked(monkeypatch, ppo_update, *args))


def test_packed_rows_equal_under_vmap_over_two_members(monkeypatch):
    ts, _ = _make_train_state()
    config = PPOConfig(batch_size=48, n_epochs=2)
    other = MLPActorCritic(act_dim=2).init(jax.random.PRNGKey(1), jnp.zeros((1, 8)))
    states = [ts, ts.replace(params=other)]
    datas = [
        _make_batch(ts, jax.random.PRNGKey(4 + i), n=100)
        for i, ts in enumerate(states)
    ]
    stack = lambda *xs: jnp.stack(xs)  # noqa: E731
    members = (
        jax.tree_util.tree_map(stack, *states),
        jax.tree_util.tree_map(stack, *datas),
        jax.random.split(jax.random.PRNGKey(5), 2),
    )
    update = jax.vmap(lambda ts, data, key: ppo_update(ts, data, key, config))
    got = update(*members)
    _assert_same_update(got, _unpacked(monkeypatch, update, *members))
    # and each member is the run it would have been alone
    alone = ppo_update(states[1], datas[1], members[2][1], config)
    np.testing.assert_allclose(
        np.asarray(got[1]["loss"][1]), np.asarray(alone[1]["loss"]), rtol=1e-5
    )


@pytest.mark.parametrize("with_mask", [False, True], ids=["wide", "mask"])
def test_per_formation_rows_do_not_pack(monkeypatch, with_mask):
    """Rows of a whole formation (9 agents x (16 + 2 + 3) floats, over one
    vreg's 128 lanes) keep the gather a leaf: no ``row_pack`` scope, the
    same result."""
    n, obs_dim = 9, 16
    model = GNNActorCritic(k=3, rounds=1)
    config = PPOConfig(batch_size=8, n_epochs=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, n, obs_dim)))
    ts = TrainState.create(
        apply_fn=model.apply, params=params, tx=config.make_optimizer()
    )
    data = _per_formation_batch(jax.random.PRNGKey(1), 20, n, obs_dim, with_mask)
    assert not _traces_row_pack(ts, data, config)
    args = (ts, data, jax.random.PRNGKey(2), config)
    got = ppo_update(*args)
    _assert_same_update(got, _unpacked(monkeypatch, ppo_update, *args))
    assert np.isfinite(float(got[1]["loss"]))
    assert float(got[1]["grad_norm"]) > 0


# ----------------------------------------------------------------------
# The packed table 128 lanes wide: one physical row an index, the sub-row
# picked out of it
# ----------------------------------------------------------------------

_LANES = ppo_module._PACK_MAX_WIDTH


def _groups(width):
    """Logical rows a physical row of the packed table holds."""
    return _LANES // (1 << (width - 1).bit_length())


def _rows_of_width(key, total, width, weighted=False):
    """``total`` rows whose packed width is ``width`` floats: the five
    rollout leaves (and ``weights``) where the width leaves ``obs`` a float,
    ``obs`` alone (two axes a row, so the reshape is held too) below it."""
    ks = jax.random.split(key, 6)
    if width < 7:
        shape = (total, width // 2, 2) if width % 2 == 0 else (total, width)
        return MinibatchData(
            obs=jax.random.normal(ks[0], shape), actions=None,
            old_log_probs=None, advantages=None, returns=None,
        )
    return MinibatchData(
        obs=jax.random.normal(ks[0], (total, width - 5 - weighted)),
        actions=jax.random.normal(ks[1], (total, 2)),
        old_log_probs=-jnp.abs(jax.random.normal(ks[2], (total,))),
        advantages=jax.random.normal(ks[3], (total,)),
        returns=jax.random.normal(ks[4], (total,)),
        weights=jax.random.uniform(ks[5], (total,)) if weighted else None,
    )


def _lookup(data, idx):
    lookup, unpack = ppo_module._pack_rows(data)
    return unpack(lookup(idx))


def _assert_same_bits(got, want):
    chex.assert_trees_all_equal_shapes_and_dtypes(got, want)
    bits = lambda x: np.asarray(x).view(np.uint32)  # noqa: E731
    chex.assert_trees_all_equal(
        jax.tree_util.tree_map(bits, got), jax.tree_util.tree_map(bits, want)
    )


_TOTALS = {
    "ragged": lambda g: 7 * g + 3,  # g does not divide it (where g > 1)
    "under_g": lambda g: max(g - 1, 1),  # one physical row, not full
    "whole": lambda g: 8 * g,
}


@pytest.mark.parametrize(
    "width,total_kind,weighted",
    [
        (width, kind, weighted)
        for width in (1, 2, 13, 16, 17, 64, 65, 128)
        for kind in _TOTALS
        for weighted in ((False, True) if width >= 7 else (False,))
    ],
)
def test_lookup_equals_a_gather_a_leaf_bitwise(width, total_kind, weighted):
    """``unpack(lookup(idx))`` against ``x[idx]`` on every leaf, at every
    index: among them 0, ``P - 1``, ``P`` (the first row of the second
    group of lanes) and ``total - 1``, and each more than once."""
    groups = _groups(width)
    total = _TOTALS[total_kind](groups)
    data = _rows_of_width(jax.random.PRNGKey(width), total, width, weighted)
    assert sum(
        x[0].size for x in jax.tree_util.tree_leaves(data)
    ) == width
    phys = -(-total // groups)
    edges = jnp.asarray([0, phys - 1, min(phys, total - 1), total - 1])
    idx = jnp.concatenate(
        [edges, jax.random.permutation(jax.random.PRNGKey(1), total), edges]
    )
    want = jax.tree_util.tree_map(lambda x: x[idx], data)
    _assert_same_bits(_lookup(data, idx), want)
    _assert_same_bits(jax.jit(_lookup)(data, idx), want)


@pytest.mark.parametrize("width", [13, 65])
def test_lookup_under_vmap_over_two_members(width):
    """The table gains a member axis and the gather a batch dimension; each
    member gets its own rows at its own indices."""
    total = 7 * _groups(width) + 3
    datas = [
        _rows_of_width(jax.random.PRNGKey(i), total, width) for i in range(2)
    ]
    idx = jnp.stack(
        [jax.random.permutation(jax.random.PRNGKey(7 + i), total) for i in range(2)]
    )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *datas)
    got = jax.vmap(_lookup)(stacked, idx)
    want = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[jax.tree_util.tree_map(lambda x: x[i], d) for d, i in zip(datas, idx)],
    )
    _assert_same_bits(got, want)


@pytest.mark.parametrize("width", [13, 16, 65], ids=["pick", "pick_full", "no_pick"])
def test_lookup_special_values_as_the_docstring_states(width):
    """Where a sub-row is picked (``g > 1``) ``-0.0`` comes back ``+0.0``
    and a non-finite float makes its own logical row non-finite, and no
    row beside it in the same physical row; where the gathered row is the
    row (``g == 1``) every bit comes back."""
    groups = _groups(width)
    total = 7 * groups + 3
    phys = -(-total // groups)
    data = _rows_of_width(jax.random.PRNGKey(3), total, width)
    sick = {2: jnp.inf, 3: -jnp.inf, 4: jnp.nan}
    obs = data.obs.at[1, 0].set(-0.0)
    for row, value in sick.items():
        obs = obs.at[row, 1].set(value)
    data = data.replace(obs=obs)
    idx = jnp.arange(total)
    got = _lookup(data, idx)
    want = jax.tree_util.tree_map(lambda x: x[idx], data)
    healthy = np.setdiff1d(np.arange(total), [1, *sick])
    if groups > 1:
        # rows 2 + phys, ... share physical rows with the sick ones
        assert set(np.asarray(list(sick)) + phys) <= set(healthy)
    _assert_same_bits(
        jax.tree_util.tree_map(lambda x: x[healthy], got),
        jax.tree_util.tree_map(lambda x: x[healthy], want),
    )
    if groups == 1:
        _assert_same_bits(got, want)
        return
    zero = np.asarray(got.obs)[1, 0]
    assert zero == 0.0 and not np.signbit(zero)
    np.testing.assert_array_equal(
        np.asarray(got.obs)[1, 1:], np.asarray(want.obs)[1, 1:]
    )
    for row in sick:
        for leaf in jax.tree_util.tree_leaves(got):
            assert not np.isfinite(np.asarray(leaf)[row]).any(), (row, leaf[row])
