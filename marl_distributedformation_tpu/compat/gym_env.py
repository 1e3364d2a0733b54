"""Single-formation ``gymnasium.Env`` adapter — ecosystem interop.

The reference couples its env to SB3's VecEnv ABC (reference
vectorized_env.py:16-109); ``compat.vec_env`` mirrors that contract. This
module is the other half of interop: ONE formation exposed through the
standard ``gymnasium.Env`` API, so the functional JAX env plugs into any
RL library (and gymnasium tooling like wrappers and the env checker),
treating the whole formation as a single centralized-control agent:

- observation: ``(N, obs_dim)`` Box — every agent's local view;
- action: ``(N, 2)`` Box in [-1, 1], scaled by ``max_speed`` inside
  (the reference adapter's convention, vectorized_env.py:69-70);
- reward: the MEAN per-agent reward (scalar, as gymnasium requires);
- episodes end by truncation at the step limit (the reference's
  timeout-only termination, SURVEY.md Q3); ``terminated`` fires only
  when ``goal_termination`` is enabled with ``strict_parity=False``.

Parity caveat, inherited deliberately: the underlying step auto-resets on
episode end and returns the NEXT episode's first observation with the
terminal reward (the SB3 VecEnv convention the reference trains under,
reference simulate.py:113-116). A gymnasium consumer that bootstraps
from the final observation on truncation sees the same bias the
reference does (Q4); ``info["steps"]`` carries the episode step counter
so callers can tell.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from marl_distributedformation_tpu.env import EnvParams, make_vec_env

try:
    import gymnasium as gym
except ImportError as e:  # pragma: no cover - optional extra
    raise ImportError(
        "compat.gym_env needs gymnasium (pip install "
        "'marl-distributedformation-tpu[gym]')"
    ) from e


class FormationGymEnv(gym.Env):
    """One formation as a ``gymnasium.Env`` (centralized control view)."""

    metadata = {"render_modes": ["human", "rgb_array"], "render_fps": 10}

    def __init__(
        self,
        params: Optional[EnvParams] = None,
        render_mode: Optional[str] = None,
    ) -> None:
        self.params = params or EnvParams()
        n, d = self.params.num_agents, self.params.obs_dim
        # Component ranges: own pos in [0,1], offsets/goal in [-1,1]
        # (SURVEY.md Q10); knn observations additionally carry RAW
        # neighbor indices up to N-1, so their envelope widens — the
        # declared bounds must actually contain observations here
        # (check_env enforces it; the reference's are declarative only).
        high = float(max(1, n - 1)) if self.params.obs_mode == "knn" else 1.0
        self.observation_space = gym.spaces.Box(
            low=-1.0, high=high, shape=(n, d), dtype=np.float32
        )
        self.action_space = gym.spaces.Box(
            low=-1.0, high=1.0, shape=(n, 2), dtype=np.float32
        )
        assert render_mode is None or render_mode in self.metadata[
            "render_modes"
        ], render_mode
        self.render_mode = render_mode
        self._renderer = None
        self._reset_fn, self._step_fn = make_vec_env(self.params, 1)
        self._key = jax.random.PRNGKey(0)
        self._state = None
        self._steps = 0

    # -- gymnasium API ------------------------------------------------

    def reset(
        self,
        *,
        seed: Optional[int] = None,
        options: Optional[dict] = None,
    ) -> Tuple[np.ndarray, Dict[str, Any]]:
        super().reset(seed=seed)
        if seed is not None:
            self._key = jax.random.PRNGKey(seed)
        self._key, k = jax.random.split(self._key)
        self._state, obs = self._reset_fn(k)
        self._steps = 0
        return np.asarray(obs[0], np.float32), {}

    def step(
        self, action: np.ndarray
    ) -> Tuple[np.ndarray, float, bool, bool, Dict[str, Any]]:
        assert self._state is not None, "call reset() first"
        act = np.asarray(action, np.float32).reshape(
            1, self.params.num_agents, 2
        )
        self._state, tr = self._step_fn(self._state, jax.numpy.asarray(act))
        self._steps += 1
        # ONE device fetch for the whole transition: per-field np.asarray
        # would pay ~a dozen blocking round trips per step (obs, reward,
        # done, each metric) — ruinous for exactly the per-step external
        # training loops this adapter serves.
        tr = jax.device_get(tr)
        done = bool(tr.done[0])
        # Timeout-only episodes (Q3) are truncation in gymnasium terms. A
        # true goal termination exists only off-parity — and even there a
        # done at the step limit is still the timeout (formation.py ORs
        # the two conditions), so distinguish by the step counter: the
        # non-strict limit fires at exactly max_steps steps.
        timeout = self._steps >= self.params.max_steps
        terminated = bool(
            done
            and not self.params.strict_parity
            and self.params.goal_termination
            and not timeout
        )
        truncated = done and not terminated
        info: Dict[str, Any] = {
            "steps": self._steps,
            **{k: float(v[0]) for k, v in tr.metrics.items()},
        }
        if done:
            self._steps = 0  # the underlying env auto-reset (see module doc)
        if self.render_mode == "human":
            self.render()
        return (
            np.asarray(tr.obs[0], np.float32),
            float(tr.reward[0].mean()),
            terminated,
            truncated,
            info,
        )

    def render(self):
        if self.render_mode is None:
            return None
        assert self._state is not None, "call reset() before render()"
        if self._renderer is None:
            if self.render_mode == "rgb_array":
                import matplotlib

                matplotlib.use("Agg")
            from marl_distributedformation_tpu.compat.render import (
                FormationRenderer,
            )

            self._renderer = FormationRenderer(
                self.params, title="FormationGymEnv"
            )
        s = self._state
        self._renderer.update(
            np.asarray(s.agents[0]),
            np.asarray(s.goal[0]),
            np.asarray(s.obstacles[0]),
        )
        if self.render_mode == "rgb_array":
            fig = self._renderer.fig
            fig.canvas.draw()
            buf = np.asarray(fig.canvas.buffer_rgba())
            return buf[..., :3].copy()
        # human: update() only moves artists — flush them to the screen
        # (plt.pause runs the GUI event loop one tick, the standard
        # incremental-display idiom).
        import matplotlib.pyplot as plt

        self._renderer.fig.canvas.draw_idle()
        plt.pause(0.001)
        return None

    def close(self) -> None:
        if self._renderer is not None:
            import matplotlib.pyplot as plt

            plt.close(self._renderer.fig)
            self._renderer = None
