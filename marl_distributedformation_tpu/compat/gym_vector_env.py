"""Batched ``gymnasium.vector.VectorEnv`` adapter — ecosystem interop.

``compat.gym_env`` exposes ONE formation through ``gymnasium.Env``;
this is the batched half: M formations stepping as one device program
behind the standard ``VectorEnv`` API, so vector-native libraries
(gymnasium wrappers, CleanRL-style loops) drive the jitted JAX env
without ever seeing a Python per-env loop — each "sub-env" is a whole
formation under centralized control, exactly the ``FormationGymEnv``
view.

Autoreset: declared ``SAME_STEP`` (``metadata["autoreset_mode"]``) —
the underlying step auto-resets finished formations and returns the
NEXT episode's first observation with the terminal reward, the SB3
VecEnv convention the reference trains under (reference
simulate.py:113-116). The true final observation is discarded by that
convention (SURVEY.md Q4), so ``infos`` carries NO ``final_obs`` — a
consumer that needs it should bootstrap the way the reference does
(accepting the same bias) or use the single-env adapter with an outer
wrapper. ``infos["steps"]`` has each formation's episode step counter.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from marl_distributedformation_tpu.env import EnvParams, make_vec_env

try:
    import gymnasium as gym
    from gymnasium.vector.utils import batch_space
except ImportError as e:  # pragma: no cover - optional extra
    raise ImportError(
        "compat.gym_vector_env needs gymnasium (pip install "
        "'marl-distributedformation-tpu[gym]')"
    ) from e


class FormationVectorEnv(gym.vector.VectorEnv):
    """M formations as a ``gymnasium.vector.VectorEnv`` (one jitted
    device program per step — no per-env Python loop)."""

    metadata = {
        "autoreset_mode": gym.vector.AutoresetMode.SAME_STEP,
        "render_modes": [],
    }

    def __init__(
        self,
        params: Optional[EnvParams] = None,
        num_envs: int = 16,
    ) -> None:
        self.params = params or EnvParams()
        self.num_envs = int(num_envs)
        n, d = self.params.num_agents, self.params.obs_dim
        high = (
            float(max(1, n - 1)) if self.params.obs_mode == "knn" else 1.0
        )  # knn obs carry raw neighbor indices (see compat.gym_env)
        self.single_observation_space = gym.spaces.Box(
            low=-1.0, high=high, shape=(n, d), dtype=np.float32
        )
        self.single_action_space = gym.spaces.Box(
            low=-1.0, high=1.0, shape=(n, 2), dtype=np.float32
        )
        self.observation_space = batch_space(
            self.single_observation_space, self.num_envs
        )
        self.action_space = batch_space(
            self.single_action_space, self.num_envs
        )
        self._reset_fn, self._step_fn = make_vec_env(
            self.params, self.num_envs
        )
        self._key = jax.random.PRNGKey(0)
        self._state = None
        self._steps = np.zeros(self.num_envs, np.int64)

    # -- gymnasium.vector API -----------------------------------------

    def reset(
        self,
        *,
        seed: Optional[int] = None,
        options: Optional[dict] = None,
    ) -> Tuple[np.ndarray, Dict[str, Any]]:
        if seed is not None:
            self._key = jax.random.PRNGKey(seed)
        self._key, k = jax.random.split(self._key)
        self._state, obs = self._reset_fn(k)
        self._steps[:] = 0
        return np.asarray(obs, np.float32), {}

    def step(
        self, actions: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, dict]:
        assert self._state is not None, "call reset() first"
        act = np.asarray(actions, np.float32).reshape(
            self.num_envs, self.params.num_agents, 2
        )
        self._state, tr = self._step_fn(self._state, jax.numpy.asarray(act))
        # ONE device fetch per step (see compat.gym_env on per-field host syncs).
        tr = jax.device_get(tr)
        self._steps += 1
        done = np.asarray(tr.done, bool)
        # Timeout-only episodes are truncation (SURVEY.md Q3); a real
        # goal termination exists only off-parity and never at the step
        # limit (formation.py ORs the conditions — compat.gym_env).
        timeout = self._steps >= self.params.max_steps
        terminated = (
            done
            & ~timeout
            & (not self.params.strict_parity)
            & self.params.goal_termination
        )
        truncated = done & ~terminated
        infos: Dict[str, Any] = {
            "steps": self._steps.copy(),
            **{
                k: np.asarray(v, np.float32)
                for k, v in tr.metrics.items()
            },
        }
        self._steps[done] = 0  # those formations auto-reset (module doc)
        return (
            np.asarray(tr.obs, np.float32),
            np.asarray(tr.reward, np.float32).mean(axis=-1),
            terminated,
            truncated,
            infos,
        )

    def close_extras(self, **kwargs: Any) -> None:
        pass
