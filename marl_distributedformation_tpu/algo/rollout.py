"""On-policy rollout collection as a ``lax.scan`` over environment steps.

The TPU-native replacement for SB3's ``collect_rollouts`` host loop (consumed
by the reference at vectorized_env.py:134; SURVEY.md §3.1): the policy
forward pass, action sampling, env step, and buffer write all live inside one
jitted scan — no host round-trips per step.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from marl_distributedformation_tpu.env import EnvParams, FormationState
from marl_distributedformation_tpu.envs import spec_for_params
from marl_distributedformation_tpu.models import distributions

Array = jax.Array


@struct.dataclass
class RolloutBatch:
    """Time-major rollout storage, shapes ``(T, M, N, ...)``.

    ``dones`` is broadcast from per-formation to per-agent, the same flattening
    the reference's adapter performs (vectorized_env.py:79).
    """

    obs: Array  # (T, M, N, obs_dim)
    actions: Array  # (T, M, N, act_dim) — unclipped samples, as SB3 stores
    log_probs: Array  # (T, M, N)
    values: Array  # (T, M, N)
    rewards: Array  # (T, M, N)
    dones: Array  # (T, M, N)
    metrics: Dict[str, Array]  # per-step env metrics, each (T, M)


def collect_rollout(
    apply_fn: Callable[..., Tuple[Array, Array, Array]],
    nn_params: Any,
    env_state: FormationState,
    obs: Array,
    key: Array,
    env_params: EnvParams,
    n_steps: int,
    env_step_fn: Optional[Callable] = None,
    mask: Optional[Array] = None,
) -> Tuple[FormationState, Array, RolloutBatch, Array]:
    """Roll ``n_steps`` vectorized env steps under the current policy.

    Actions are sampled from the Gaussian head, clipped to the [-1, 1] action
    space for the env (SB3's convention: the *unclipped* sample and its log
    prob go into the buffer), then scaled by ``max_speed`` exactly where the
    reference's adapter does it (vectorized_env.py:69-70).

    ``env_step_fn(state, velocity) -> (state, transition)`` defaults to the
    REGISTERED env's vmapped single-chip step, resolved from the params type
    (``envs.spec_for_params`` — formation params resolve to the legacy
    ``step_batch`` verbatim, so that path is bitwise unchanged); pass a ring
    step (``parallel.make_ring_step``) to roll with the agent axis sharded
    over 'sp'.

    ``mask`` is an optional ``(M, N)`` agent-validity mask forwarded to
    per-formation models (CTDE/GNN) for padded heterogeneous batches; it is
    constant across the rollout because ``n_agents`` is preserved through
    auto-resets (env/hetero.py).

    Returns ``(env_state, last_obs, batch, last_value)``.
    """
    if env_step_fn is None:
        env_spec = spec_for_params(env_params)

        def env_step_fn(state, velocity):
            return env_spec.step_batch(state, velocity, env_params)

    def policy(obs):
        if mask is not None:
            return apply_fn(nn_params, obs, mask)
        return apply_fn(nn_params, obs)

    def body(carry, step_key):
        env_state, obs = carry
        with jax.named_scope("policy"):
            mean, log_std, value = policy(obs)
            action = distributions.sample(step_key, mean, log_std)
            log_p = distributions.log_prob(action, mean, log_std)
        clipped = jnp.clip(action, -1.0, 1.0)
        with jax.named_scope("env_step"):
            env_state, tr = env_step_fn(
                env_state, env_params.max_speed * clipped
            )
        done_agents = jnp.broadcast_to(
            tr.done[:, None], tr.reward.shape
        ).astype(jnp.float32)
        out = RolloutBatch(
            obs=obs,
            actions=action,
            log_probs=log_p,
            values=value,
            rewards=tr.reward,
            dones=done_agents,
            metrics=tr.metrics,
        )
        return (env_state, tr.obs), out

    step_keys = jax.random.split(key, n_steps)
    (env_state, last_obs), batch = jax.lax.scan(
        body, (env_state, obs), step_keys
    )
    with jax.named_scope("policy"):  # the bootstrap value's forward pass
        _, _, last_value = policy(last_obs)
    return env_state, last_obs, batch, last_value
