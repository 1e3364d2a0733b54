"""Device time under the ``trunk_residual`` scope (``models/trunk.py``
``RESIDUALS['hyper']``: the hyper-connections of both sublayers: the
coefficient product on the four streams, ``hc_sinkhorn``, the pre-mix ``h_pre .
X``, ``H_res X`` and the write-back ``h_post (outer) y``), in the
rollout's forward passes and in the update's forward, recomputed and backward
passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_residual")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
