"""k-NN observation graph + GNN policy tests (BASELINE.json config 4)."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.formation import (
    compute_obs,
    reset_batch,
    step_batch,
)
from marl_distributedformation_tpu.models import GNNActorCritic
from marl_distributedformation_tpu.models.gnn import (
    ONEHOT_MAX_NODES,
    gather_nodes,
    neighbor_onehot,
    parse_knn_obs,
)
from marl_distributedformation_tpu.ops import knn
from marl_distributedformation_tpu.train import TrainConfig, Trainer


def _brute_force_knn(points: np.ndarray, k: int):
    n = points.shape[0]
    d = np.linalg.norm(points[:, None] - points[None, :], axis=-1)
    np.fill_diagonal(d, np.inf)
    idx = np.argsort(d, axis=1)[:, :k]
    return idx, d[np.arange(n)[:, None], idx]


def test_knn_matches_brute_force():
    pts = np.asarray(
        jax.random.uniform(jax.random.PRNGKey(3), (50, 2)) * 400.0
    )
    idx, offsets, dists = jax.jit(knn, static_argnums=1)(jnp.asarray(pts), 5)
    ref_idx, ref_d = _brute_force_knn(pts, 5)
    np.testing.assert_array_equal(np.asarray(idx), ref_idx)
    # fp32 |a|^2+|b|^2-2ab expansion loses ~2^-13 relative at coordinate
    # scale 400 — compare with an absolute tolerance in world units.
    np.testing.assert_allclose(np.asarray(dists), ref_d, atol=0.05)
    np.testing.assert_allclose(
        np.asarray(offsets),
        pts[ref_idx] - pts[:, None, :],
        rtol=1e-4,
        atol=1e-4,
    )


def test_knn_valid_mask_excludes_points():
    pts = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    valid = jnp.array([True, True, True, True, False, False])
    idx, _, _ = knn(pts, 3, valid=valid)
    assert not np.isin(np.asarray(idx), [4, 5]).any()


def test_knn_fewer_valid_than_k_degrades_to_self_loops():
    # Only 3 valid points but k=3: each has 2 real neighbors; the surplus
    # slot must be a harmless self-loop, never an invalid index or a
    # masked-distance blowup.
    pts = jnp.array(
        [[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [99.0, 99.0], [98.0, 98.0]]
    )
    valid = jnp.array([True, True, True, False, False])
    idx, offsets, dists = knn(pts, 3, valid=valid)
    idx, offsets, dists = (np.asarray(idx), np.asarray(offsets), np.asarray(dists))
    for i in range(3):
        assert not np.isin(idx[i], [3, 4]).any()
        assert idx[i, 2] == i  # surplus slot -> self
        np.testing.assert_array_equal(offsets[i, 2], 0.0)
        assert dists[i, 2] == 0.0
    assert dists[:3].max() < 100.0  # no 1e6 garbage anywhere


def test_knn_obs_layout():
    params = EnvParams(num_agents=10, obs_mode="knn", knn_k=3)
    assert params.obs_dim == 2 + 6 + 3 + 2 + 3
    state = reset_batch(jax.random.PRNGKey(0), params, 2)
    obs = jax.vmap(compute_obs, in_axes=(0, 0, None))(
        state.agents, state.goal, params
    )
    assert obs.shape == (2, 10, params.obs_dim)

    # Own normalized position block.
    wh = np.array([params.width, params.height])
    np.testing.assert_allclose(
        np.asarray(obs[0, :, :2]), np.asarray(state.agents[0]) / wh, rtol=1e-5
    )
    # Index block: valid agent ids, never self.
    idx = np.asarray(obs[0, :, -3:]).astype(int)
    assert ((idx >= 0) & (idx < 10)).all()
    assert (idx != np.arange(10)[:, None]).all()
    # Offset block consistent with the indices it names.
    agents = np.asarray(state.agents[0])
    offsets = np.asarray(obs[0, :, 2:8]).reshape(10, 3, 2) * wh
    np.testing.assert_allclose(
        offsets, agents[idx] - agents[:, None, :], rtol=1e-4, atol=1e-3
    )


def test_knn_env_steps_at_100_agents():
    params = EnvParams(num_agents=100, obs_mode="knn", knn_k=8)
    state = reset_batch(jax.random.PRNGKey(1), params, 4)
    vel = jnp.zeros((4, 100, 2))
    state, tr = jax.jit(step_batch, static_argnums=2)(state, vel, params)
    assert tr.obs.shape == (4, 100, params.obs_dim)
    assert np.isfinite(np.asarray(tr.obs)).all()
    assert np.isfinite(np.asarray(tr.reward)).all()


def test_gnn_shapes_and_locality():
    k, n = 3, 12
    params = EnvParams(num_agents=n, obs_mode="knn", knn_k=k)
    state = reset_batch(jax.random.PRNGKey(2), params, 1)
    obs = jax.vmap(compute_obs, in_axes=(0, 0, None))(
        state.agents, state.goal, params
    )
    model = GNNActorCritic(k=k, rounds=1)
    nn_params = model.init(jax.random.PRNGKey(0), obs)
    mean, log_std, value = model.apply(nn_params, obs)
    assert mean.shape == (1, n, 2)
    assert value.shape == (1, n)

    # With rounds=1, agent i's action depends only on {i} U knn(i): perturb
    # the obs row of an agent outside agent 0's neighborhood.
    _, _, idx = parse_knn_obs(obs, k)
    neighborhood = set(np.asarray(idx[0, 0]).tolist()) | {0}
    outsider = next(j for j in range(n) if j not in neighborhood)
    # Ensure agent 0 is also not in the outsider's... irrelevant: messages
    # flow from gathered rows only, so row-perturbation is sufficient.
    perturbed = obs.at[0, outsider, :2].add(0.25)
    mean2, _, value2 = model.apply(nn_params, perturbed)
    np.testing.assert_allclose(
        np.asarray(mean[0, 0]), np.asarray(mean2[0, 0]), rtol=1e-6
    )
    # The centralized critic DOES see the perturbation.
    assert abs(float(value2[0, 0] - value[0, 0])) > 1e-7


@pytest.mark.slow
def test_gnn_mask_blocks_padded_neighbors():
    k, n = 2, 6
    obs_dim = EnvParams(num_agents=n, obs_mode="knn", knn_k=k).obs_dim
    obs = jax.random.normal(jax.random.PRNGKey(4), (2, n, obs_dim))
    # Force the index block to point everyone at agents 4 and 5.
    obs = obs.at[..., -k:].set(jnp.array([4.0, 5.0]))
    mask = jnp.ones((2, n)).at[:, 4:].set(0.0)
    model = GNNActorCritic(k=k, rounds=2)
    nn_params = model.init(jax.random.PRNGKey(0), obs)
    _, _, value = model.apply(nn_params, obs, mask)
    assert (np.asarray(value[:, 4:]) == 0.0).all()
    # Padded agents' embeddings must not leak through messages: perturbing
    # agent 4's obs row changes nothing for active agents.
    perturbed = obs.at[:, 4, :2].add(3.0)
    mean1, _, v1 = model.apply(nn_params, obs, mask)
    mean2, _, v2 = model.apply(nn_params, perturbed, mask)
    np.testing.assert_allclose(
        np.asarray(mean1[:, :4]), np.asarray(mean2[:, :4]), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(v1[:, :4]), np.asarray(v2[:, :4]), rtol=1e-6
    )


def _take_along_axis_nodes(h, idx, xp=jnp):
    """The gather ``gather_nodes`` was until PR 28, kept as the reference
    (``xp=np``: on the host, nothing compiled)."""
    n, k = idx.shape[-2], idx.shape[-1]
    flat = xp.take_along_axis(
        h, idx.reshape(*idx.shape[:-2], n * k, 1), axis=-2
    )
    return flat.reshape(*idx.shape[:-2], n, k, h.shape[-1])


def _wide_values(rng, shape):
    """float32 with all 24 mantissa bits in play, both signs, magnitudes
    from 1e-30 to 1e30."""
    mant = 1.0 + (rng.integers(0, 2**23, shape) | 1) * 2.0**-23  # low bit set
    sign = rng.choice([-1.0, 1.0], shape)
    return (sign * mant * 2.0 ** rng.integers(-99, 100, shape)).astype(np.float32)


def _graph(rng, lead, n, k):
    """Random neighbours with repeats, every node's first slot a self-loop
    and node 0 everybody's last."""
    idx = rng.integers(0, n, (*lead, n, k)).astype(np.int32)
    idx[..., 0] = np.arange(n)
    idx[..., 1:, -1] = 0
    return idx


@pytest.mark.parametrize("e", [1, 64])
@pytest.mark.parametrize("lead", [(), (3,), (2, 3)])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("n", [5, 100, 128, 129, 640])
def test_gather_nodes(n, k, lead, e):
    """Both paths of ``gather_nodes`` (the one-hot product where N <= 128,
    ``take_along_axis`` past it) against a plain ``take_along_axis``: the
    forward pass bit for bit, the gradient against ``segment_sum`` to
    float32 rounding (the product adds in another order), and nothing
    with respect to ``idx``. Indices are in ``[0, N)`` by construction
    (``compute_obs_knn``; self-loops for padded agents); outside it the
    product gives zeros where ``take_along_axis`` wraps a negative index
    and fills NaN past N, and neither is held to anything here."""
    rng = np.random.default_rng(1000 * n + 10 * k + e)
    h = _wide_values(rng, (*lead, n, e))
    idx = _graph(rng, lead, n, k)
    assert ONEHOT_MAX_NODES == 128
    assert (neighbor_onehot(jnp.asarray(idx)) is None) == (n > 128)

    out = jax.jit(gather_nodes)(h, idx)
    ref = _take_along_axis_nodes(h, idx, xp=np)
    assert out.shape == ref.shape and out.dtype == h.dtype
    np.testing.assert_array_equal(np.asarray(out).view(np.int32), ref.view(np.int32))

    # Cotangents of both signs, and of sizes a float32 sum holds together.
    g = (rng.standard_normal(ref.shape) * 2.0 ** rng.integers(-6, 7, ref.shape))
    g = g.astype(np.float32)
    d_h, d_idx = jax.jit(
        jax.grad(lambda x, i: jnp.sum(gather_nodes(x, i) * g), (0, 1), allow_int=True)
    )(h, idx)
    # idx is an integer: the only cotangent it can take is float0's nothing
    assert d_idx.dtype == jax.dtypes.float0
    formations = int(np.prod(lead, dtype=int))
    segments = (idx.reshape(formations, n * k) + n * np.arange(formations)[:, None]).ravel()
    seg, seg_abs = (
        np.asarray(jax.ops.segment_sum(x.reshape(-1, e), segments, formations * n))
        for x in (g, np.abs(g))
    )
    # to 1e-6 of what each sum added up
    gap = np.abs(np.asarray(d_h).reshape(-1, e) - seg) / np.maximum(seg_abs, 1e-30)
    assert gap.max() < 1e-6


def test_gnn_is_its_old_self_at_100_agents(monkeypatch):
    """gnn100's module (N=100, k=4) through the product against the same
    module with the old ``take_along_axis`` monkeypatched in: the same
    parameter tree, names and shapes (an older checkpoint loads), and
    outputs equal bit for bit."""
    from marl_distributedformation_tpu.models import gnn as gnn_module

    n, k = 100, 4
    params = EnvParams(num_agents=n, obs_mode="knn", knn_k=k)
    state = reset_batch(jax.random.PRNGKey(5), params, 3)
    obs = jax.vmap(compute_obs, in_axes=(0, 0, None))(
        state.agents, state.goal, params
    )
    model = GNNActorCritic(k=k, rounds=2)

    def init_and_apply():  # a function of its own each time: traced anew
        variables = model.init(jax.random.PRNGKey(0), obs)
        return variables, jax.jit(lambda v, o: model.apply(v, o))(variables, obs)

    def shapes(variables):
        return {
            "/".join(part.key for part in path): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(variables)
        }

    variables, new = init_and_apply()
    old_calls = []

    def old_gather(h, idx, onehot=None):
        old_calls.append(h.shape)
        return _take_along_axis_nodes(h, idx)

    monkeypatch.setattr(gnn_module, "gather_nodes", old_gather)
    old_variables, old = init_and_apply()
    assert old_calls == [(3, n, 64)] * 4  # two rounds, init and apply

    assert shapes(variables) == shapes(old_variables)
    assert shapes(variables)["params/msg_0/kernel"] == (2 * 64 + 3, 64)
    assert set(variables["params"]) == {
        "embed", "msg_0", "upd_0", "msg_1", "upd_1", "actor", "critic", "log_std"}
    for a, b in zip(jax.tree_util.tree_leaves((variables, new)),
                    jax.tree_util.tree_leaves((old_variables, old))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_trainer_gnn_smoke():
    env_params = EnvParams(num_agents=16, obs_mode="knn", knn_k=4)
    model = GNNActorCritic(k=4, rounds=2)
    trainer = Trainer(
        env_params,
        ppo=PPOConfig(n_steps=4, n_epochs=2, batch_size=64),
        config=TrainConfig(num_formations=2, checkpoint=False),
        model=model,
    )
    assert trainer.per_formation
    metrics = trainer.run_iteration()
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["reward"]))


# ---------------------------------------------------------------------------
# knn under SPMD sharding (round-1 ADVICE high finding): "auto" must never
# hand a dp-sharded batch to the Pallas kernel under plain jit, and the
# shard_map-wrapped dp step must run the kernel on local blocks correctly.
# ---------------------------------------------------------------------------


def test_spmd_detection_contexts():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from marl_distributedformation_tpu.ops.knn import (
        _spmd_partitioner_controlled as ctl,
    )
    from marl_distributedformation_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 8})
    x = jnp.zeros((16, 8, 2))
    x_dp = jax.device_put(x, NamedSharding(mesh, P("dp")))
    assert not ctl(x)
    assert ctl(x_dp)
    seen = []
    jax.jit(lambda y: seen.append(ctl(y)) or y)(x_dp)
    assert seen[-1], "tracer under jit+mesh must report partitioner control"
    jax.jit(
        jax.shard_map(
            lambda y: seen.append(ctl(y)) or y,
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
    )(x_dp)
    assert not seen[-1], "inside shard_map the kernel sees a local block"


def test_knn_batch_auto_on_sharded_input_runs():
    """impl='auto' on a dp-sharded batch under jit must compile and match
    the unsharded XLA result (it silently falls back to xla)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from marl_distributedformation_tpu.ops import knn_batch
    from marl_distributedformation_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 8})
    pts = jax.random.uniform(jax.random.PRNGKey(0), (16, 12, 2)) * 100
    pts_dp = jax.device_put(pts, NamedSharding(mesh, P("dp")))
    idx_ref, off_ref, d_ref = knn_batch(pts, 3, impl="xla")
    f = jax.jit(lambda p: knn_batch(p, 3, impl="auto"))
    idx, off, d = f(pts_dp)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(  # eager vs jit fuse sqrt differently
        np.asarray(d), np.asarray(d_ref), rtol=1e-4, atol=1e-4
    )


@pytest.mark.slow
def test_dp_step_shard_map_runs_kernel_on_local_blocks(tmp_path):
    """Trainer with a dp mesh + knn obs uses the shard_map-wrapped env step;
    forcing the (interpret-mode) Pallas kernel inside it must reproduce the
    unsharded XLA trainer's trajectory and update."""
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.parallel import make_shard_fn
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    def mk(sub, impl, shard_fn):
        return Trainer(
            EnvParams(
                num_agents=8, obs_mode="knn", knn_k=2, knn_impl=impl
            ),
            ppo=PPOConfig(n_steps=2, batch_size=16, n_epochs=1),
            config=TrainConfig(
                num_formations=8, seed=0, checkpoint=False,
                name="knn-dp", log_dir=str(tmp_path / sub),
            ),
            shard_fn=shard_fn,
        )

    t_ref = mk("ref", "xla", None)
    t_dp = mk("dp", "pallas_interpret", make_shard_fn({"dp": 8}))
    assert t_dp._env_step_fn is not None, "knn+mesh must use make_dp_step"
    for _ in range(2):
        m_ref = t_ref.run_iteration()
        m_dp = t_dp.run_iteration()
        np.testing.assert_allclose(
            float(m_ref["reward"]), float(m_dp["reward"]), rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(t_ref.env_state.agents),
            np.asarray(t_dp.env_state.agents),
            rtol=1e-4, atol=1e-3,
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(t_ref.train_state.params),
        jax.tree_util.tree_leaves(t_dp.train_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )
