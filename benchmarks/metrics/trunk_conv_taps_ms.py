"""Device time under the ``conv_taps`` scope inside ``trunk_conv``
(``models/trunk.py`` ``_conv``: the ``B x`` gate, the three taps of the
depthwise causal convolution over the agents and the ``C`` gate, all float32
elementwise: what a kernel would take over), in the rollout's forward passes
and in the update's forward, recomputed and backward passes, per iteration of
the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("conv_taps")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
