"""Device time under the ``router`` scope inside ``trunk_moe``
(``models/trunk.py``: the expert layer's norm, the router's logits over all
the routed experts at ``highest``, their softmax, the top-k and its
renormalisation), in the rollout's forward passes and in the update's
forward, recomputed and backward passes, per iteration of the traced
window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("router")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
