"""Device time under the ``dense_ffn`` scope (``models/trunk.py``
``trunk_layer``: a leading dense layer's norm, its SwiGLU of
``intermediate_size`` and the write of its part), in the
rollout's forward passes and in the update's forward, recomputed and backward
passes, per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("dense_ffn")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
