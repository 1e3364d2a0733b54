"""Device time under the ``hc_sinkhorn`` scope inside ``trunk_residual``
(``models/trunk.py`` ``_hyper_read``: the clamp, the exponential and the 20
column and row normalisations of a token's 4 x 4 matrix, and their backward
pass through all 20 steps: what a kernel would keep in fast memory), in the
rollout's forward passes and in the update's forward, recomputed and backward
passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("hc_sinkhorn")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
