"""Device time under the trunk's ``trunk_attention`` scope
(``models/trunk.py``: the q/k/v/o products, head norms, RoPE and the
blocked softmax over the selected keys), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_attention")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
