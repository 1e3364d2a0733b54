"""Shared building blocks for actor-critic models.

The per-agent policy tower and the CTDE pooled value head are used by both
``CTDEActorCritic`` (raw local obs) and ``GNNActorCritic`` (message-passed
embeddings); keeping them here keeps the two in lockstep.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

Array = jax.Array

# Host-side sqrt: jnp.sqrt here would run a device computation at import
# time, initializing the JAX backend before entry points can pick a platform
# (utils/config.py setup_platform) — and taking the chip just to import
# the package.
hidden_init = nn.initializers.orthogonal(2.0**0.5)


def masked_mean_pool(x: Array, mask: Optional[Array]) -> Array:
    """Mean over the agent axis (-2), ignoring masked agents; keepdims.
    ``x (..., N, E)``, ``mask (..., N)`` or None -> ``(..., 1, E)``."""
    if mask is None:
        return x.mean(axis=-2, keepdims=True)
    m = mask.astype(x.dtype)[..., None]
    return (x * m).sum(axis=-2, keepdims=True) / jnp.maximum(
        m.sum(axis=-2, keepdims=True), 1.0
    )


class PolicyHead(nn.Module):
    """Per-agent action-mean tower: tanh MLP + orthogonal(0.01) head, the
    SB3 ``'MlpPolicy'`` actor shape (reference vectorized_env.py:126)."""

    act_dim: int
    hidden: Sequence[int]

    @nn.compact
    def __call__(self, x: Array) -> Array:
        for i, width in enumerate(self.hidden):
            x = nn.tanh(
                nn.Dense(width, kernel_init=hidden_init, name=f"pi_{i}")(x)
            )
        return nn.Dense(
            self.act_dim,
            kernel_init=nn.initializers.orthogonal(0.01),
            name="pi_head",
        )(x)


class PooledValueHead(nn.Module):
    """Centralized (CTDE) per-agent value head: concat each agent's features
    with the masked formation-mean pool, run a tanh tower, and zero values of
    masked agents."""

    hidden: Sequence[int]

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        pooled = masked_mean_pool(x, mask)
        vf = jnp.concatenate([x, jnp.broadcast_to(pooled, x.shape)], axis=-1)
        for i, width in enumerate(self.hidden):
            vf = nn.tanh(
                nn.Dense(width, kernel_init=hidden_init, name=f"vf_{i}")(vf)
            )
        value = nn.Dense(
            1, kernel_init=nn.initializers.orthogonal(1.0), name="vf_head"
        )(vf).squeeze(-1)
        if mask is not None:
            value = value * mask.astype(value.dtype)
        return value
