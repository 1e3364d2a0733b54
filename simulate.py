#!/usr/bin/env python
"""Baseline potential-field controller demo — the reference's
``python simulate.py`` workflow (simulate.py:321-329): N=10 agents driven by
the scripted formation controller for 1000 frames with live rendering.

Extras over the reference: ``key=value`` overrides (``num_agents=6``,
``steps=200``), ``headless=true`` to run without a display and print
metrics (useful over SSH; the reference hard-requires a GUI),
``platform=cpu`` to keep the demo off the TPU, and a *working* obstacle
demo — ``python simulate.py num_obstacles=4 obstacle_mode=fixed`` exercises
the controller's obstacle repulsion against the consistent box geometry and
the renderer's red-on-collision feedback (the reference ships obstacle code
but guards it off with ``assert num_obstacles == 0``, SURVEY.md Q2).
"""

from __future__ import annotations

import sys

import numpy as np


def main(argv=None) -> None:
    from marl_distributedformation_tpu.utils import Config, apply_overrides

    cfg = Config(
        num_agents=10,
        steps=1000,
        headless=False,
        seed=0,
        platform=None,
        num_obstacles=0,
        obstacle_mode="fixed",
    )
    apply_overrides(cfg, sys.argv[1:] if argv is None else argv)
    num_agents = int(cfg.num_agents)
    steps = int(cfg.steps)
    headless = bool(cfg.headless)
    seed = int(cfg.seed)

    from marl_distributedformation_tpu.utils import setup_platform

    setup_platform(cfg.platform)

    import jax

    from marl_distributedformation_tpu.compat.vec_env import FormationVecEnv
    from marl_distributedformation_tpu.env import EnvParams, control

    params = EnvParams(
        num_agents=num_agents,
        num_obstacles=int(cfg.num_obstacles),
        obstacle_mode=str(cfg.obstacle_mode),
    )
    env = FormationVecEnv(params, num_formations=1, seed=seed)
    env.reset()
    vctrl = jax.jit(
        lambda agents, goal, obstacles: control(agents, goal, obstacles, params)
    )

    def controller_step():
        state = env.state
        vel = np.asarray(
            vctrl(state.agents[0], state.goal[0], state.obstacles[0])
        )
        _, rewards, _, _ = env.step_velocities(vel[None])
        return rewards

    if headless:
        from marl_distributedformation_tpu.compat.render import obstacle_hits

        for t in range(steps):
            rewards = controller_step()
            if t % 100 == 0 or t == steps - 1:
                m = env.last_metrics
                if params.num_obstacles > 0:
                    # Sampled at print time only — a per-step host pull of
                    # agents/obstacles would make the demo host-sync-bound.
                    hits = int(
                        obstacle_hits(
                            env.agents_np(), env.obstacles_np(), params
                        ).sum()
                    )
                    extra = f" obstacle_hits={hits}"
                else:
                    extra = ""
                print(
                    f"step {t:4d} reward={rewards.mean():8.3f} "
                    f"avg_dist_to_goal={m['avg_dist_to_goal']:7.2f} "
                    f"std_neighbor={m['std_dist_to_neighbor']:6.2f}"
                    + extra
                )
        return

    import matplotlib.animation as animation
    import matplotlib.pyplot as plt

    from marl_distributedformation_tpu.compat.render import FormationRenderer

    renderer = FormationRenderer(params, title="baseline controller")

    def frame(i):
        controller_step()
        renderer.update(env.agents_np(), env.goal_np(), env.obstacles_np())

    ani = animation.FuncAnimation(  # noqa: F841 (kept alive for the show loop)
        renderer.fig, frame, frames=range(steps), interval=1
    )
    plt.show()


if __name__ == "__main__":
    main()
