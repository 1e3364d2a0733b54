"""The whole step's share of the chips' peak: the model FLOP an iteration
requires (``costs.train_flops_per_iteration``: rollout forward, then
forward and backward over the used rows in each epoch; recomputation, env,
k-NN, GAE and Adam not counted) times the iterations the traced window
completed, over its wall time and the chips' bf16 peak."""

from benchmarks import costs


def read(context):
    cell = context["cell"]
    flops = costs.train_flops_per_iteration(cell.config, cell.job)
    achieved = flops * context["iterations"] / context["elapsed_s"]
    return 100.0 * achieved / (cell.chips * context["peaks"]["bf16_flops_per_s"])
