"""Plain reference of the per-agent MLP actor-critic (the SB3
``MlpPolicy`` shape the source trains): separate tanh towers for the
action mean and the value, orthogonal init, a state-independent
``log_std``. Parameters sit in the tree the program's policy reads
(``{"params": {layer: {"kernel", "bias"}, ..., "log_std"}}``), so one set
made from the seed serves both sides."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import formation

PER_FORMATION = False  # rows of a minibatch are single agent-transitions


def _dense(key, fan_in, fan_out, gain):
    return {
        "kernel": jax.nn.initializers.orthogonal(gain)(
            key, (fan_in, fan_out), jnp.float32
        ),
        "bias": jnp.zeros((fan_out,), jnp.float32),
    }


def init(key, policy, env, act_dim=2):
    obs_dim = formation.obs_dim(env)
    widths = list(policy["hidden"])
    layers = {}
    keys = iter(jax.random.split(key, 2 * (len(widths) + 1)))
    for tower, out, head_gain in (("pi", act_dim, 0.01), ("vf", 1, 1.0)):
        fan_in = obs_dim
        for i, width in enumerate(widths):
            layers[f"{tower}_{i}"] = _dense(next(keys), fan_in, width, 2.0**0.5)
            fan_in = width
        layers[f"{tower}_head"] = _dense(next(keys), fan_in, out, head_gain)
    layers["log_std"] = jnp.full((act_dim,), policy["log_std_init"], jnp.float32)
    return {"params": layers}


def _affine(layer, x):
    return x @ layer["kernel"].astype(x.dtype) + layer["bias"].astype(x.dtype)


def apply(params, policy, env, obs, dtype=jnp.float32):
    """``(mean, log_std, value)`` for ``obs (..., obs_dim)``; ``dtype`` is
    the precision the towers compute in (the control lowers it)."""
    p = params["params"]
    outs = {}
    for tower in ("pi", "vf"):
        x = obs.astype(dtype)
        for i in range(len(policy["hidden"])):
            x = jnp.tanh(_affine(p[f"{tower}_{i}"], x))
        outs[tower] = _affine(p[f"{tower}_head"], x).astype(jnp.float32)
    return outs["pi"], p["log_std"], outs["vf"][..., 0]


def forward_flops_per_agent(policy, env, act_dim=2):
    """Multiply-adds x2 of one forward pass for one agent."""
    obs_dim = formation.obs_dim(env)
    total = 0
    for out in (act_dim, 1):
        fan_in = obs_dim
        for width in policy["hidden"]:
            total += 2 * fan_in * width
            fan_in = width
        total += 2 * fan_in * out
    return total
