"""Device time under the ``trunk_conv`` scope (``models/trunk.py`` ``_conv``: a
gated short convolution layer's mixer whole: its norm, the ``[B | C | x]``
product, ``conv_taps``, the ``W_out`` product and the write of its part), in
the rollout's forward passes and in the update's forward, recomputed and
backward passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_conv")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
