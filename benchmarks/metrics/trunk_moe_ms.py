"""Device time under the trunk's ``trunk_moe`` scope
(``models/trunk.py``: router, the held experts' products over the swarm, the
routing's mask and combine), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_moe")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
