"""Plain float32 reference of one training iteration: roll the policy
through the environment for ``n_steps``, estimate advantages (GAE), then
``n_epochs`` shuffled passes of clipped-surrogate minibatch steps under
global-norm clipping and Adam, as the source's SB3 ``PPO`` defines them.

Independent of the program under test: it imports only jax and the
reference's own environment and policies. It draws its random numbers from
the same seeded stream a job with this key is defined to use (one split
per iteration into rollout and update keys, one key per env step, one
permutation per epoch), so the program and the reference see the same
noise and the same shuffles and differ by rounding alone.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp

from . import formation

LOG_2PI = math.log(2.0 * math.pi)
ADAM_B1, ADAM_B2 = 0.9, 0.999


def policy_module(config):
    return importlib.import_module(
        f"{__package__}.policy_{config['policy']['kind']}"
    )


def make_state(key, config, num_formation):
    """Everything a job starts from, made from one key in one call:
    parameters, zeroed Adam moments, M reset formations and the loop key."""
    env = config["env"]
    k_loop, k_init, k_env = jax.random.split(key, 3)
    params = policy_module(config).init(k_init, config["policy"], env)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    env_state = jax.vmap(lambda k: formation.reset(k, env))(
        jax.random.split(k_env, num_formation)
    )
    return {
        "params": params,
        "mu": zeros,
        "nu": zeros,
        "count": jnp.zeros((), jnp.int32),
        "env": env_state,
        "key": k_loop,
    }


def log_prob(actions, mean, log_std):
    z = (actions - mean) * jnp.exp(-log_std)
    return (-0.5 * (z * z + LOG_2PI) - log_std).sum(-1)


def gae(rewards, values, dones, last_value, gamma, lam):
    adv = jnp.zeros_like(last_value)
    next_value = last_value
    out = []
    for t in reversed(range(rewards.shape[0])):
        live = 1.0 - dones[t]
        delta = rewards[t] + gamma * next_value * live - values[t]
        adv = delta + gamma * lam * live * adv
        next_value = values[t]
        out.append(adv)
    advantages = jnp.stack(out[::-1])
    return advantages, advantages + values


def loss_fn(params, config, apply, mb):
    ppo = config["ppo"]
    mean, log_std, values = apply(params, mb["obs"])
    logp = log_prob(mb["actions"], mean, log_std)
    adv = mb["advantages"]
    if ppo["normalize_advantage"]:
        adv = (adv - adv.mean()) / (adv.std(ddof=1) + 1e-8)
    ratio = jnp.exp(logp - mb["log_probs"])
    clip = ppo["clip_range"]
    surrogate = jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - clip, 1 + clip))
    entropy = (log_std + 0.5 * (1.0 + LOG_2PI)).sum()
    value_loss = ((mb["returns"] - values) ** 2).mean()
    return (
        -surrogate.mean() - ppo["ent_coef"] * entropy + ppo["vf_coef"] * value_loss
    )


def adam_step(params, mu, nu, count, grads, ppo):
    """Clip the gradient to ``max_grad_norm`` by its global norm, then one
    Adam step. Returns the pre-clip norm beside the new state."""
    norm = jnp.sqrt(
        sum((g * g).sum() for g in jax.tree_util.tree_leaves(grads))
    )
    scale = jnp.where(norm < ppo["max_grad_norm"], 1.0, ppo["max_grad_norm"] / norm)
    count = count + 1
    c = count.astype(jnp.float32)
    tm = jax.tree_util.tree_map
    grads = tm(lambda g: g * scale, grads)
    mu = tm(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = tm(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    params = tm(
        lambda p, m, v: p
        - ppo["learning_rate"]
        * (m / (1 - ADAM_B1**c))
        / (jnp.sqrt(v / (1 - ADAM_B2**c)) + ppo["adam_eps"]),
        params, mu, nu,
    )
    return params, mu, nu, count, norm


def iteration(state, obs, config, batch_size, dtype=jnp.float32, half_batch=False,
              own_shard=0):
    """One rollout + update. ``obs`` is the observation of ``state['env']``
    (made by whoever owns the state, so the program's own observation path
    is what the first step sees on its side). ``dtype`` below float32 is
    the control; ``half_batch`` plants the fault of a minibatch that leaves
    half its rows out and takes its means over the rest; ``own_shard=c``
    plants the fault of ``c`` chips that exchange nothing, each updating
    from its own formations alone (what the first chip then holds)."""
    env, ppo = config["env"], config["ppo"]
    policy = policy_module(config)

    def apply(params, x):
        return policy.apply(params, config["policy"], env, x, dtype)

    key, k_roll, k_update = jax.random.split(state["key"], 3)
    params = state["params"]

    def env_step(carry, step_key):
        env_state, obs = carry
        mean, log_std, value = apply(params, obs)
        action = mean + jnp.exp(log_std) * jax.random.normal(
            step_key, mean.shape, mean.dtype
        )
        velocity = env["max_speed"] * jnp.clip(action, -1.0, 1.0)
        env_state, next_obs, reward, done = jax.vmap(
            lambda s, v: formation.step(s, v, env)
        )(env_state, velocity)
        row = {
            "obs": obs,
            "actions": action,
            "log_probs": log_prob(action, mean, log_std),
            "values": value,
            "rewards": reward,
            "dones": jnp.broadcast_to(done[:, None], reward.shape).astype(jnp.float32),
        }
        return (env_state, next_obs), row

    (env_state, last_obs), roll = jax.lax.scan(
        env_step, (state["env"], obs), jax.random.split(k_roll, ppo["n_steps"])
    )
    last_value = apply(params, last_obs)[2]
    advantages, returns = gae(
        roll["rewards"], roll["values"], roll["dones"], last_value,
        ppo["gamma"], ppo["gae_lambda"],
    )

    n = env["num_agents_per_formation"]
    row = (n,) if policy.PER_FORMATION else ()
    buffer = {
        "obs": roll["obs"], "actions": roll["actions"],
        "log_probs": roll["log_probs"], "advantages": advantages,
        "returns": returns,
    }
    if own_shard:
        buffer = {k: x[:, : x.shape[1] // own_shard] for k, x in buffer.items()}
        batch_size = batch_size // own_shard
    data = {
        k: x.reshape(-1, *row, *x.shape[3:]) for k, x in buffer.items()
    }
    total = data["obs"].shape[0]
    rows = min(max(1, batch_size // n) if policy.PER_FORMATION else batch_size, total)
    minibatches = total // rows
    grad = jax.value_and_grad(loss_fn)

    def minibatch_step(carry, idx):
        params, mu, nu, count = carry
        if half_batch:
            idx = idx[: rows // 2]
        mb = jax.tree_util.tree_map(lambda x: x[idx], data)
        loss, grads = grad(params, config, apply, mb)
        params, mu, nu, count, norm = adam_step(params, mu, nu, count, grads, ppo)
        return (params, mu, nu, count), (loss, norm)

    def epoch(carry, epoch_key):
        perm = jax.random.permutation(epoch_key, total)[: minibatches * rows]
        return jax.lax.scan(minibatch_step, carry, perm.reshape(minibatches, rows))

    (params, mu, nu, count), (losses, norms) = jax.lax.scan(
        epoch,
        (params, state["mu"], state["nu"], state["count"]),
        jax.random.split(k_update, ppo["n_epochs"]),
    )
    new_state = {
        "params": params, "mu": mu, "nu": nu, "count": count,
        "env": env_state, "key": key,
    }
    metrics = {
        "loss": losses.mean(),
        "grad_norm": norms.mean(),
        "reward": roll["rewards"].mean(),
    }
    return new_state, last_obs, metrics


def observe(env_state, config):
    env = config["env"]
    return jax.vmap(lambda a, g: formation.observe(a, g, env))(
        env_state["agents"], env_state["goal"]
    )
