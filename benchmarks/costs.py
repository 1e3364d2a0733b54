"""Operations and bytes the algorithm needs, from the configuration's
shapes alone. Kept with the benchmark so that no change to the program can
move them."""

from __future__ import annotations

from .reference import ppo as reference


def job_shape(config: dict, job: dict) -> dict:
    """Rows and minibatches of one iteration of this job."""
    n = config["env"]["num_agents_per_formation"]
    ppo = config["ppo"]
    agent_steps = job["num_formation"] * n * ppo["n_steps"]
    batch = min(job["batch_size"], agent_steps)
    if reference.policy_module(config).PER_FORMATION:
        rows = max(1, batch // n)
        minibatches = (agent_steps // n) // rows
        used = minibatches * rows * n
    else:
        minibatches = agent_steps // batch
        used = minibatches * batch
    return {
        "agent_steps": agent_steps,
        "minibatches": minibatches,
        "used": used,
    }


def train_flops_per_iteration(config: dict, job: dict) -> float:
    """Model FLOP one iteration requires: a forward pass for every
    agent-step of the rollout (and the bootstrap value), then forward plus
    backward (3x a forward) for every used row in each epoch. The env, the
    k-NN search, GAE and Adam are not counted: they are not the model."""
    forward = reference.policy_module(config).forward_flops_per_agent(
        config["policy"], config["env"]
    )
    shape = job_shape(config, job)
    n = config["env"]["num_agents_per_formation"]
    rollout = shape["agent_steps"] + job["num_formation"] * n
    return float(
        forward * (rollout + 3 * config["ppo"]["n_epochs"] * shape["used"])
    )


def knn_call_cost(num_formation: int, n: int, k: int) -> dict:
    """One k-NN search over ``(M, N, 2)`` positions. Operations: per
    ordered pair two subtractions, two multiplications and an addition (5),
    then k selection passes of a compare and a select over the row (2k).
    Bytes: the positions read, and indices, offsets and distances written
    (k * (1 + 2 + 1) words a query) - the pairwise matrix never leaves
    fast memory."""
    pairs = num_formation * n * n
    return {
        "ops": float(pairs * (5 + 2 * k)),
        "bytes": float(num_formation * n * (2 + 4 * k) * 4),
    }
