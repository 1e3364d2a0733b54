"""Checkpoint-backed policy for playback — the ``PPO.load`` / ``predict``
capability the reference gets from SB3 (visualize_policy.py:35,16).
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from marl_distributedformation_tpu.models import (
    CTDEActorCritic,
    GNNActorCritic,
    MLPActorCritic,
    TrunkActorCritic,
    distributions,
)
from marl_distributedformation_tpu.models.trunk import load_trunk_arch

# Checkpoints record the policy architecture by class name (trainer
# ``_checkpoint_target``); this registry maps it back for playback.
POLICY_REGISTRY = {
    "MLPActorCritic": MLPActorCritic,
    "CTDEActorCritic": CTDEActorCritic,
    "GNNActorCritic": GNNActorCritic,
    "TrunkActorCritic": TrunkActorCritic,
}


def model_kwargs_for(policy: str, env_params=None, trunk=None) -> dict:
    """Extra constructor arguments a policy needs beyond ``act_dim``,
    derived from the environment configuration (the checkpoint records the
    architecture's class name and, for the trunk, its file's name)."""
    if policy in ("GNNActorCritic", "TrunkActorCritic"):
        if env_params is None:
            raise ValueError(
                f"{policy} playback needs env_params (for knn_k / "
                "goal_in_obs); pass env_params to from_checkpoint"
            )
        kwargs = {"k": env_params.knn_k, "goal_in_obs": env_params.goal_in_obs}
        if policy == "TrunkActorCritic":
            kwargs["arch"] = load_trunk_arch(str(trunk))
        return kwargs
    return {}


def infer_hidden(params: dict, policy: str) -> Optional[tuple]:
    """Infer the policy-tower widths from checkpoint parameters, so
    checkpoints trained with non-default ``hidden_sizes`` (the SB3
    policy_kwargs/net_arch analog, cfg ``hidden_sizes``) restore without
    the caller re-supplying the architecture. The tower layers are named
    ``pi_{i}`` — at the top level for the plain MLP, under ``actor`` for
    the PolicyHead-based CTDE/GNN models. Returns None when no tower is
    found (leave the model's default)."""
    p = params
    if policy in ("CTDEActorCritic", "GNNActorCritic"):
        p = params.get("actor", {})
    widths = []
    i = 0
    while f"pi_{i}" in p:
        kernel = p[f"pi_{i}"].get("kernel")
        if kernel is None:
            return None
        widths.append(int(np.shape(kernel)[-1]))
        i += 1
    return tuple(widths) or None


def load_checkpoint_raw(path: str | Path) -> dict:
    """Restore a checkpoint file into nested dicts without a template.
    Validates the checksum footer: corrupt/truncated files are
    quarantined and raise ``CorruptCheckpointError`` (utils.checkpoint)
    instead of feeding damaged params to a gate or a fleet."""
    from marl_distributedformation_tpu.utils.checkpoint import (
        msgpack_restore_file,
    )

    return msgpack_restore_file(path)


class LoadedPolicy:
    """``predict(obs, deterministic)`` over restored parameters."""

    def __init__(
        self,
        params,
        act_dim: int = 2,
        seed: int = 0,
        policy: str = "MLPActorCritic",
        num_agents: int | None = None,
        model_kwargs: dict | None = None,
    ) -> None:
        if policy not in POLICY_REGISTRY:
            raise ValueError(
                f"unknown policy {policy!r} in checkpoint; known: "
                f"{sorted(POLICY_REGISTRY)}"
            )
        self.model = POLICY_REGISTRY[policy](
            act_dim=act_dim, **(model_kwargs or {})
        )
        self.params = params
        # Formation-level models need the agent axis second-to-last; predict
        # reshapes flat SB3-style (M*N, obs_dim) inputs using num_agents.
        self.per_formation = getattr(self.model, "per_formation", False)
        self.num_agents = num_agents
        self._key = jax.random.PRNGKey(seed)
        self._apply = jax.jit(self.model.apply)

    @classmethod
    def from_checkpoint(
        cls,
        path: str | Path,
        act_dim: int = 2,
        num_agents: int | None = None,
        env_params=None,
    ) -> "LoadedPolicy":
        raw = load_checkpoint_raw(path)
        if "params" not in raw:
            raise ValueError(
                f"{path} does not look like a trainer checkpoint "
                f"(keys: {sorted(raw)})"
            )
        policy = raw.get("policy", "MLPActorCritic")
        if num_agents is None and env_params is not None:
            num_agents = env_params.num_agents
        kwargs = model_kwargs_for(policy, env_params, raw.get("trunk"))
        hidden = infer_hidden(raw["params"]["params"], policy)
        if hidden:
            kwargs["hidden"] = hidden
        return cls(
            {"params": raw["params"]["params"]},
            act_dim=act_dim,
            policy=policy,
            num_agents=num_agents,
            model_kwargs=kwargs,
        )

    def predict(
        self, obs: np.ndarray, deterministic: bool = True
    ) -> Tuple[np.ndarray, Optional[tuple]]:
        """SB3 ``predict`` contract: returns ``(actions, state)`` with
        actions clipped to the [-1, 1] action space."""
        obs = jnp.asarray(obs)
        flat_in = None
        if self.per_formation and self.num_agents and obs.ndim == 2:
            # Flat SB3-style (M*N, obs_dim) rows -> (M, N, obs_dim) formations.
            flat_in = obs.shape
            obs = obs.reshape(-1, self.num_agents, obs.shape[-1])
        mean, log_std, _ = self._apply(self.params, obs)
        if flat_in is not None:
            mean = mean.reshape(flat_in[0], -1)
        if deterministic:
            actions = distributions.mode(mean)
        else:
            self._key, k = jax.random.split(self._key)
            actions = distributions.sample(k, mean, log_std)
        return np.asarray(jnp.clip(actions, -1.0, 1.0)), None
