"""The work the trunk's sparse attention requires, from the configuration's
shapes alone: the same count whatever computes it. Kept with the benchmark
so that no change to the program can move it."""

from __future__ import annotations

from . import costs
from .reference import ppo as reference


def selected_attention_flops_per_iteration(config: dict, job: dict) -> float | None:
    """FLOP of attention over the selected keys in one iteration: per token
    and layer ``mean_selected(S, topk) x 4 x heads x head_dim`` (scores and
    their product with the values, multiply-adds x2, as the reference
    policy module's ``forward_flops_per_agent`` counts them), for a forward
    pass over every agent-step of the rollout and the bootstrap value, then
    forward and backward (3x) over every used row in each epoch, as
    ``costs.train_flops_per_iteration`` counts the model. Recomputation is
    not counted. None for a policy without the indexer's selection."""
    policy, env = config["policy"], config["env"]
    if "sa_config" not in policy:
        return None
    s = env["num_agents_per_formation"]
    selected = reference.policy_module(config).mean_selected(s, policy["sa_config"]["topk"])
    per_token = selected * 4 * policy["num_attention_heads"] * policy["head_dim"]
    shape = costs.job_shape(config, job)
    tokens = (
        shape["agent_steps"] + job["num_formation"] * s
        + 3 * config["ppo"]["n_epochs"] * shape["used"]
    )
    return float(policy["layers_held"] * per_token * tokens)
