"""What ``ppo_update`` spends outside its four stage scopes: the device
time under ``ppo_update`` minus that under ``epoch_shuffle``,
``minibatch_gather``, ``loss_and_grad`` and ``optimizer_step``, per
iteration of the traced window. It holds the instructions the compiler left
without metadata (they take the scope of the loop they run in) and the
loops' own bookkeeping, so the five ``update_*`` metrics sum to
``ppo_update_ms``."""

STAGES = ("epoch_shuffle", "minibatch_gather", "loss_and_grad", "optimizer_step")


def read(context):
    scope_s = context["trace"]["scope_s"]
    whole = scope_s.get("ppo_update")
    if not whole or not any(scope_s.get(stage) for stage in STAGES):
        return None  # no update, or an update that is not split: no residual
    rest = whole - sum(scope_s.get(stage, 0.0) for stage in STAGES)
    return 1e3 * rest / context["iterations"]
