"""Device time under the trunk's ``trunk_kda`` scope
(``models/trunk.py``: a Kimi-Delta layer's mixer whole: the q/k/v/o products, the
short convolutions, both low-rank gates, the chunked delta rule, the gated head
norm), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_kda")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
