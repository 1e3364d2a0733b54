"""Device time under the ``trunk_mla`` scope (``models/trunk.py`` ``_mla``: a
latent-attention layer's mixer whole: its norm, the low-rank query and
key-value products and their norms, YaRN's rotation, the causal softmax
``mla_softmax``, the o product and the write of its part), in the
rollout's forward passes and in the update's forward, recomputed and backward
passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_mla")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
