"""Plain float32 reference of the formation environment.

Written from the task's definition (asanati/MARL-DistributedFormation
``simulate.py``: single-integrator agents on a 400x600 field, a ring
formation around a goal) and imports nothing from the program under test.
One formation at a time; the caller ``vmap``s over formations. The k-NN
view is the direct broadcast search (no kernel).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SELF_MASK = 1e12  # finite, so top_k never returns an inf


def _wh(env):
    return jnp.array([env["width"], env["height"]], jnp.float32)


def obs_dim(env) -> int:
    goal = 2 if env["goal_in_obs"] else 0
    if env["obs_mode"] == "knn":
        return 2 + 4 * env["knn_k"] + goal
    return 6 + goal


def reset(key, env):
    """Fresh formation: agents in the bottom band, goal a radius from
    every wall. The key-split order is part of the task's seeded stream."""
    if env["num_obstacles"]:
        raise ValueError("the reference covers obstacle-free fields only")
    key, _k_obstacles, k_agents, k_goal = jax.random.split(key, 4)
    n = env["num_agents_per_formation"]
    agents = jax.random.uniform(k_agents, (n, 2), jnp.float32) * jnp.array(
        [env["width"], env["agent_spawn_band"]], jnp.float32
    )
    r = env["desired_radius"]
    goal = jax.random.uniform(k_goal, (2,), jnp.float32) * jnp.array(
        [env["width"] - 2.0 * r, env["height"] - 2.0 * r], jnp.float32
    ) + r
    return {
        "agents": agents,
        "goal": goal,
        "steps": jnp.zeros((), jnp.int32),
        "key": key,
    }


def knn(agents, k):
    """Each agent's k nearest others: indices, offsets, distances."""
    n = agents.shape[0]
    diff = agents[:, None, :] - agents[None, :, :]
    d2 = (diff * diff).sum(-1)
    d2 = jnp.where(jnp.eye(n, dtype=bool), SELF_MASK, d2)
    neg, idx = jax.lax.top_k(-d2, k)
    offsets = agents[idx] - agents[:, None, :]
    return idx, offsets, jnp.sqrt(jnp.maximum(-neg, 0.0))


def observe(agents, goal, env):
    wh = _wh(env)
    own = agents / wh
    rel_goal = [(goal[None, :] - agents) / wh] if env["goal_in_obs"] else []
    if env["obs_mode"] == "knn":
        k = env["knn_k"]
        idx, offsets, dists = knn(agents, k)
        diag = math.hypot(env["width"], env["height"])
        return jnp.concatenate(
            [own, (offsets / wh).reshape(-1, 2 * k), dists / diag]
            + rel_goal
            + [idx.astype(jnp.float32)],
            axis=-1,
        )
    prev_pos, next_pos = jnp.roll(agents, 1, 0), jnp.roll(agents, -1, 0)
    return jnp.concatenate(
        [own, prev_pos / wh - own, next_pos / wh - own] + rel_goal, axis=-1
    )


def reward(agents, goal, out_of_bounds, env):
    dist_goal = jnp.linalg.norm(agents - goal[None, :], axis=-1)
    individual = (
        -env["reward_dist_scale"] * dist_goal
        + env["close_goal_bonus"] * (dist_goal < env["close_goal_dist"])
        - env["oob_penalty"] * out_of_bounds
    )
    n = env["num_agents_per_formation"]
    target = 2.0 * env["desired_radius"] * math.sin(math.pi / n)
    for shift in (-1, 1):  # next, previous ring neighbour
        diff = (
            jnp.linalg.norm(agents - jnp.roll(agents, shift, 0), axis=-1)
            - target
        )
        individual = individual - env["neighbor_penalty_scale"] * jnp.where(
            diff < 0, diff**2, diff
        )
    rho = env["share_reward_ratio"]
    return (1.0 - 2.0 * rho) * individual + rho * (
        jnp.roll(individual, 1) + jnp.roll(individual, -1)
    )


def step(state, velocity, env):
    """One step of one formation: ``(next_state, obs, reward, done)``.
    A finished episode (the reference's quirk: the pre-increment counter
    past ``max_steps``) hands back the first observation of the next."""
    if not env["strict_parity"]:
        raise ValueError("the reference covers strict_parity=true only")
    agents = state["agents"] + velocity
    out_of_bounds = (
        (agents[:, 0] <= 0.0)
        | (agents[:, 1] <= 0.0)
        | (agents[:, 0] >= env["width"])
        | (agents[:, 1] >= env["height"])
    )
    agents = jnp.clip(agents, 0.0, _wh(env))
    rew = reward(agents, state["goal"], out_of_bounds, env)
    done = state["steps"] > env["max_steps"]
    stepped = dict(state, agents=agents, steps=state["steps"] + 1)
    fresh = reset(state["key"], env)
    nxt = jax.tree_util.tree_map(
        lambda a, b: jnp.where(done, a, b), fresh, stepped
    )
    return nxt, observe(nxt["agents"], nxt["goal"], env), rew, done
