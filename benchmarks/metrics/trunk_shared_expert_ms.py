"""Device time under the ``shared_expert`` scope inside ``trunk_moe``
(``models/trunk.py``: the shared expert's three products for every token), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("shared_expert")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
