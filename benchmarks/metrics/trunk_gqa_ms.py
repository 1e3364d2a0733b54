"""Device time under the ``trunk_gqa`` scope (``models/trunk.py`` ``_gqa``: a
dense causal GQA layer's mixer whole: its norm, the ``[Wq | Wk | Wv]``
product, the q/k head norms and RoPE, ``_causal_softmax``'s (query block, key
tile) pairs over heads of 64, the o product and the write of its part), in
the rollout's forward passes and in the update's forward, recomputed and
backward passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_gqa")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
