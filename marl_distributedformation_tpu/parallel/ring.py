"""Agent-axis ('sp') sharding with ring halo exchange over ICI.

The environment's interaction graph is a ring: every agent reads only its two
ring neighbors, for observations (reference simulate.py:162-167) and reward
mixing (simulate.py:222-229). That locality maps exactly onto a ring of TPU
devices — the same communication shape as ring attention for long sequences:
shard the agent axis N across the 'sp' mesh axis and exchange a ONE-AGENT
halo with each ring-neighbor device via ``lax.ppermute``, instead of
all-gathering the formation. Per step each device exchanges three halos
(pre-reset positions, per-agent rewards, post-reset positions) of
``m_local`` rows each, independent of N — swarm size scales linearly with
devices at constant ICI traffic per device.

``obs_mode="knn"`` swarms shard on 'sp' too (round 3): reward mixing and
metrics keep the constant-traffic ring halos, while the observation's
global neighbor search all-gathers positions over 'sp' (the all-to-all
analog of sequence parallelism — positions are 8N bytes/formation, tiny
next to the O(N·k) obs the search produces, which stay local) and each
device runs the LOCAL-QUERY search ``ops.knn.knn_local`` for its slab.
Sharded and unsharded trajectories coincide bit-for-bit
(tests/test_parallel.py).

The env math itself is NOT reimplemented here: ``env.formation``'s
``compute_obs`` / ``compute_reward`` / ``integrate`` are shape-generic and
parameterized over a ``neighbors_fn``; this module supplies the halo-exchange
variant. Episode resets draw from the same per-formation key on every 'sp'
device (the full formation is sampled and the local slice taken), so sharded
and unsharded trajectories coincide exactly (tested in test_parallel.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from marl_distributedformation_tpu.env import EnvParams, FormationState, Transition
from marl_distributedformation_tpu.env.formation import (
    _in_obstacle,
    compute_obs,
    compute_obs_knn_sharded,
    compute_reward,
    integrate,
    reset,
)

Array = jax.Array


def halo_neighbors(
    block: Array, axis: int, sp_size: int, axis_name: str = "sp"
) -> Tuple[Array, Array]:
    """Sharded equivalent of ``formation.ring_neighbors``: per-agent
    ``(prev, next)`` along the sharded agent axis of a local slab
    ``(m, n_local, ...)``, via one ppermute pair around the device ring.

    With ``sp_size == 1`` the ppermutes are self-sends and this reduces to
    plain wrap-around (``jnp.roll``) semantics.
    """
    axis = axis % block.ndim
    assert axis == 1, f"sharded agent axis must be axis 1, got {axis}"
    last = block[:, -1:]
    first = block[:, :1]
    to_next = [(d, (d + 1) % sp_size) for d in range(sp_size)]
    to_prev = [(d, (d - 1) % sp_size) for d in range(sp_size)]
    from_prev = lax.ppermute(last, axis_name, to_next)
    from_next = lax.ppermute(first, axis_name, to_prev)
    prev = jnp.concatenate([from_prev, block[:, :-1]], axis=1)
    nxt = jnp.concatenate([block[:, 1:], from_next], axis=1)
    return prev, nxt


def make_ring_step(params: EnvParams, mesh: Mesh):
    """Build a jitted batched env step with the agent axis sharded over 'sp'
    (and formations over 'dp').

    Input/output shardings: ``agents/velocity (M, N, 2)`` as P('dp','sp');
    ``goal/obstacles/steps/key`` P('dp') (replicated over 'sp'); per-agent
    outputs P('dp','sp'); per-formation outputs P('dp').
    """
    sp_size = mesh.shape["sp"]
    if params.obs_mode not in ("ring", "knn"):
        raise ValueError(
            f"agent-axis ('sp') sharding supports obs_mode 'ring' (halo "
            f"exchange) and 'knn' (all-gather + local-query search); got "
            f"{params.obs_mode!r}"
        )
    if params.num_agents % sp_size != 0:
        raise ValueError(
            f"num_agents={params.num_agents} not divisible by sp={sp_size}"
        )
    n_local = params.num_agents // sp_size
    n_agents = float(params.num_agents)

    def neighbors_fn(x: Array, axis: int) -> Tuple[Array, Array]:
        return halo_neighbors(x, axis, sp_size)

    def psum_mean(x: Array) -> Array:
        """Global mean over the sharded agent axis, per formation."""
        return lax.psum(x.sum(axis=-1), "sp") / n_agents

    def block_step(
        agents: Array,  # (m, n_local, 2)
        goal: Array,  # (m, 2)
        obstacles: Array,  # (m, K, 2)
        steps: Array,  # (m,)
        key: Array,  # (m, 2) uint32 — identical on every 'sp' device
        velocity: Array,  # (m, n_local, 2)
    ):
        sp_idx = lax.axis_index("sp")

        agents, out_of_bounds = integrate(agents, velocity, params)
        in_obstacle = jax.vmap(_in_obstacle, in_axes=(0, 0, None))(
            agents, obstacles, params
        )

        # Shared reward math with halo-exchange neighbors (exchange #1 on
        # positions, #2 on per-agent rewards for the mixing term).
        mixed, terms = compute_reward(
            agents, goal, out_of_bounds, in_obstacle, params,
            neighbors_fn=neighbors_fn,
        )

        if params.strict_parity:
            done = steps > params.max_steps  # Q1 pre-increment check
        else:
            done = steps + 1 >= params.max_steps
            if params.goal_termination:
                dist_to_goal = jnp.linalg.norm(
                    agents - goal[:, None, :], axis=-1
                )
                close = dist_to_goal < params.close_goal_dist
                done = done | (
                    lax.psum(close.sum(axis=-1), "sp") == params.num_agents
                )

        # Auto-reset: every 'sp' device redraws the FULL formation from the
        # shared per-formation key and slices its slab, so sharded and
        # unsharded trajectories are identical (simulate.py:113-116).
        fresh = jax.vmap(reset, in_axes=(0, None))(key, params)
        fresh_local = lax.dynamic_slice_in_dim(
            fresh.agents, sp_idx * n_local, n_local, axis=1
        )
        new_agents = jnp.where(done[:, None, None], fresh_local, agents)
        new_goal = jnp.where(done[:, None], fresh.goal, goal)
        new_obstacles = (
            jnp.where(done[:, None, None], fresh.obstacles, obstacles)
            if params.num_obstacles > 0
            else obstacles
        )
        new_steps = jnp.where(done, fresh.steps, steps + 1)
        new_key = jnp.where(done[:, None], fresh.key, key)

        # Exchange #3: post-reset positions, reused by both the observation
        # (ring mode) and the neighbor-distance metrics (both modes).
        post_neighbors = neighbors_fn(new_agents, 1)
        if params.obs_mode == "knn":
            # All-to-all analog: gather the full formation's positions over
            # the 'sp' ring (8N bytes/formation — the cheap side of the
            # problem), search locally for this device's slab. Indices in
            # the obs stay global, so rows match the unsharded obs exactly.
            all_pos = lax.all_gather(
                new_agents, "sp", axis=1, tiled=True
            )  # (m, N, 2)
            obs = compute_obs_knn_sharded(
                new_agents, all_pos, new_goal, params, sp_idx * n_local
            )
        else:
            obs = compute_obs(
                new_agents, new_goal, params, pos_neighbors=post_neighbors
            )

        # Metrics (simulate.py:238-254) with global psum reductions; the
        # variance uses the numerically-stable centered form (two passes)
        # to match the unsharded std(ddof=1).
        m_dist_goal = jnp.linalg.norm(new_agents - new_goal[:, None, :], axis=-1)
        m_dist_right = jnp.linalg.norm(new_agents - post_neighbors[1], axis=-1)
        mean_right = psum_mean(m_dist_right)
        centered_sq = (m_dist_right - mean_right[:, None]) ** 2
        var = lax.psum(centered_sq.sum(axis=-1), "sp") / (n_agents - 1.0)
        metrics = {
            "avg_dist_to_goal": psum_mean(m_dist_goal),
            "ave_dist_to_neighbor": mean_right,
            "std_dist_to_neighbor": jnp.sqrt(var),
            "reward": psum_mean(mixed),
        }
        metrics.update({k: psum_mean(v) for k, v in terms.items()})
        return (
            new_agents,
            new_goal,
            new_obstacles,
            new_steps,
            new_key,
            obs,
            mixed,
            done,
            metrics,
        )

    agent_spec = P("dp", "sp")
    formation_spec = P("dp")
    in_specs = (
        agent_spec,  # agents
        formation_spec,  # goal
        formation_spec,  # obstacles
        formation_spec,  # steps
        formation_spec,  # key
        agent_spec,  # velocity
    )
    out_specs = (
        agent_spec,  # agents
        formation_spec,  # goal
        formation_spec,  # obstacles
        formation_spec,  # steps
        formation_spec,  # key
        agent_spec,  # obs
        agent_spec,  # reward
        formation_spec,  # done
        formation_spec,  # metrics (dict of (m,) arrays)
    )
    sharded = jax.shard_map(
        block_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )

    @jax.jit
    def ring_step(
        state: FormationState, velocity: Array
    ) -> Tuple[FormationState, Transition]:
        (
            agents,
            goal,
            obstacles,
            steps,
            key,
            obs,
            reward,
            done,
            metrics,
        ) = sharded(
            state.agents,
            state.goal,
            state.obstacles,
            state.steps,
            state.key,
            velocity,
        )
        next_state = FormationState(
            agents=agents,
            goal=goal,
            obstacles=obstacles,
            steps=steps,
            key=key,
        )
        return next_state, Transition(
            obs=obs, reward=reward, done=done, metrics=metrics
        )

    return ring_step


def place_ring_state(
    state: FormationState, mesh: Mesh
) -> FormationState:
    """Place a batched ``FormationState`` for ring stepping: agents sharded
    ('dp','sp'), per-formation leaves sharded ('dp') and replicated over 'sp'."""
    agent_sharding = NamedSharding(mesh, P("dp", "sp"))
    formation_sharding = NamedSharding(mesh, P("dp"))
    return FormationState(
        agents=jax.device_put(state.agents, agent_sharding),
        goal=jax.device_put(state.goal, formation_sharding),
        obstacles=jax.device_put(state.obstacles, formation_sharding),
        steps=jax.device_put(state.steps, formation_sharding),
        key=jax.device_put(state.key, formation_sharding),
    )
