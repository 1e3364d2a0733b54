"""Plain reference of the k-NN graph actor-critic: embed each agent, two
rounds of messages over its k nearest neighbours (mean-aggregated,
residual), an actor head on the node embedding and a critic head on the
embedding joined with the formation's mean embedding. Parameters sit in
the tree the program's policy reads."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .policy_mlp import _affine, _dense

PER_FORMATION = True  # a minibatch row is a whole formation (N agents)


def _node_dim(env):
    return 4 if env["goal_in_obs"] else 2


def init(key, policy, env, act_dim=2):
    e, m = policy["embed_dim"], policy["msg_dim"]
    node = _node_dim(env)
    hidden = list(policy["hidden"])
    gain = 2.0**0.5
    keys = iter(jax.random.split(key, 3 + 2 * policy["rounds"] + 2 * len(hidden)))
    layers = {"embed": _dense(next(keys), node, e, gain)}
    for r in range(policy["rounds"]):
        layers[f"msg_{r}"] = _dense(next(keys), 2 * e + 3, m, gain)
        layers[f"upd_{r}"] = _dense(next(keys), e + m + node, e, gain)
    actor, critic = {}, {}
    fan_pi, fan_vf = e, 2 * e
    for i, width in enumerate(hidden):
        actor[f"pi_{i}"] = _dense(next(keys), fan_pi, width, gain)
        critic[f"vf_{i}"] = _dense(next(keys), fan_vf, width, gain)
        fan_pi = fan_vf = width
    actor["pi_head"] = _dense(next(keys), fan_pi, act_dim, 0.01)
    critic["vf_head"] = _dense(next(keys), fan_vf, 1, 1.0)
    layers.update(actor=actor, critic=critic)
    layers["log_std"] = jnp.full((act_dim,), policy["log_std_init"], jnp.float32)
    return {"params": layers}


def apply(params, policy, env, obs, dtype=jnp.float32):
    """``obs (..., N, 2 + 3k [+2] + k)`` in the k-NN layout: own position,
    k offsets, k distances, relative goal, k neighbour indices."""
    p = params["params"]
    k = env["knn_k"]
    own = obs[..., :2]
    offsets = obs[..., 2 : 2 + 2 * k]
    dists = obs[..., 2 + 2 * k : 2 + 3 * k]
    node = own
    if env["goal_in_obs"]:
        node = jnp.concatenate([own, obs[..., 2 + 3 * k : 4 + 3 * k]], axis=-1)
    idx = obs[..., -k:].astype(jnp.int32)
    n = idx.shape[-2]
    edge = jnp.concatenate(
        [offsets.reshape(*offsets.shape[:-1], k, 2), dists[..., None]], axis=-1
    ).astype(dtype)
    node = node.astype(dtype)

    h = jnp.tanh(_affine(p["embed"], node))
    for r in range(policy["rounds"]):
        flat = idx.reshape(*idx.shape[:-2], n * k, 1)
        h_nb = jnp.take_along_axis(h, flat, axis=-2).reshape(
            *idx.shape, h.shape[-1]
        )
        h_self = jnp.broadcast_to(h[..., :, None, :], h_nb.shape)
        msg = jnp.tanh(
            _affine(p[f"msg_{r}"], jnp.concatenate([h_self, h_nb, edge], -1))
        )
        agg = msg.mean(axis=-2)
        h = h + jnp.tanh(
            _affine(p[f"upd_{r}"], jnp.concatenate([h, agg, node], -1))
        )

    x = h
    for i in range(len(policy["hidden"])):
        x = jnp.tanh(_affine(p["actor"][f"pi_{i}"], x))
    mean = _affine(p["actor"]["pi_head"], x).astype(jnp.float32)

    pooled = jnp.broadcast_to(h.mean(axis=-2, keepdims=True), h.shape)
    x = jnp.concatenate([h, pooled], axis=-1)
    for i in range(len(policy["hidden"])):
        x = jnp.tanh(_affine(p["critic"][f"vf_{i}"], x))
    value = _affine(p["critic"]["vf_head"], x).astype(jnp.float32)[..., 0]
    return mean, p["log_std"], value


def forward_flops_per_agent(policy, env, act_dim=2):
    e, m, k = policy["embed_dim"], policy["msg_dim"], env["knn_k"]
    node = _node_dim(env)
    total = 2 * node * e
    total += policy["rounds"] * (k * 2 * (2 * e + 3) * m + 2 * (e + m + node) * e)
    fan_pi, fan_vf = e, 2 * e
    for width in policy["hidden"]:
        total += 2 * fan_pi * width + 2 * fan_vf * width
        fan_pi = fan_vf = width
    return total + 2 * fan_pi * act_dim + 2 * fan_vf
