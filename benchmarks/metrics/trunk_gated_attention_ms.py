"""Device time under the trunk's ``trunk_gated_attention`` scope
(``models/trunk.py``: the gated NoPE GQA layer's mixer: the q/k/v/gate/o
products, the blocked causal softmax, the sigmoid gate), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_gated_attention")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
