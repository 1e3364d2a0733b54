"""Device time under the ``mla_softmax`` scope inside ``trunk_mla``
(``models/trunk.py`` ``_causal_softmax`` as ``_mla`` calls it: the (query block,
key tile) pairs' scores over 192, their softmax and product with the values of
128, and the sums that put a block's tiles together: what a kernel would take
over), in the rollout's forward passes and in the update's forward, recomputed
and backward passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("mla_softmax")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
