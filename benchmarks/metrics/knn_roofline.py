"""The Pallas k-NN kernel's share of its roofline: the least time the
chip could take for the calls the trace shows (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from ``costs.knn_call_cost``)
over the device time of the kernel's events. The kernel is found by its
Mosaic custom call under the ``env_step`` scope. With 13 operations a pair
against 72 bytes a query the memory roof binds at N=100."""

from benchmarks import costs


def read(context):
    cell, peaks = context["cell"], context["peaks"]
    env = cell.config["env"]
    if env["obs_mode"] != "knn":
        return None
    calls, seconds = 0, 0.0
    for key, (count, total) in context["trace"].get("kernel_s", {}).items():
        if "knn" in key:
            calls += count
            seconds += total
    if not calls or not seconds:
        return None
    per_device = cell.job["num_formation"] // cell.chips
    cost = costs.knn_call_cost(
        per_device, env["num_agents_per_formation"], env["knn_k"]
    )
    least = max(
        cost["ops"] / peaks["bf16_flops_per_s"],
        cost["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * calls * least / seconds
