#!/usr/bin/env python
"""Drive the pure-functional environment in your own loop.

Everything in this framework builds on one pattern: env state is a pytree,
stepping is a pure function, and batching is `vmap` — so M formations step
in ONE compiled XLA program (the reference iterates M Python objects
sequentially, vectorized_env.py:71-81). If you want a custom training
loop, a different RL algorithm, or to embed the env in another system,
this is the whole API surface you need:

    reset_fn(key)            -> (state, obs)      # M formations at once
    step_fn(state, actions)  -> (state, transition)

Actions are policy-space ([-1, 1], scaled by max_speed inside — the L1
adapter semantics); `transition` carries obs/reward/done/metrics, with
auto-reset already applied (SB3 VecEnv convention: the obs returned on a
done row is the NEXT episode's first observation).

Run from the repo root (~20 seconds on CPU):

    python examples/functional_env.py
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp


def main() -> None:
    import marl_distributedformation_tpu as mdf
    from marl_distributedformation_tpu.env import control
    from marl_distributedformation_tpu.utils import setup_platform

    setup_platform("cpu")  # the example targets a laptop; drop for TPU

    params = mdf.EnvParams(num_agents=10)
    M = 256
    reset_fn, step_fn = mdf.make_vec_env(params, num_formations=M)
    state, obs = reset_fn(jax.random.PRNGKey(0))

    # Any controller works here: a policy network, a scripted rule, your
    # own code. The baseline potential-field controller is a pure jittable
    # function, so the whole control+step composition compiles to one
    # XLA program.
    vctrl = jax.jit(
        jax.vmap(control, in_axes=(0, 0, 0, None)), static_argnums=3
    )

    # Warm up: the first call compiles; time steady-state execution only.
    vel = vctrl(state.agents, state.goal, state.obstacles, params)
    warm_state, _ = step_fn(state, vel / params.max_speed)
    jax.block_until_ready(warm_state.agents)

    t0 = time.perf_counter()
    for t in range(300):
        vel = vctrl(state.agents, state.goal, state.obstacles, params)
        # step_fn takes policy-space actions; the scripted controller
        # emits raw velocities (the L0 contract, SURVEY.md Q8) — divide
        # by max_speed to cross between the two conventions.
        state, tr = step_fn(state, vel / params.max_speed)
        if (t + 1) % 100 == 0:
            d = float(tr.metrics["avg_dist_to_goal"].mean())
            s = float(tr.metrics["ave_dist_to_neighbor"].mean())
            print(
                f"t={t+1:3d}  avg_dist_to_goal={d:7.2f}  "
                f"ave_dist_to_neighbor={s:6.2f}"
            )
    jax.block_until_ready(state.agents)
    dt = time.perf_counter() - t0
    print(
        f"{300 * M / dt:,.0f} formation-steps/s "
        f"({M} formations x 10 agents, scripted control, one CPU)"
    )
    final = float(tr.metrics["avg_dist_to_goal"].mean())
    assert final < 100, f"formation failed to converge: {final}"
    print("converged: the ring formed around the goal")


if __name__ == "__main__":
    main()
