"""Device time under the ``routed_experts`` scope inside ``trunk_moe``
(``models/trunk.py``: each held expert's products over the swarm under the
routing's mask, dense and not dispatched), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("routed_experts")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
