"""Device time of the streaming Pallas k-NN kernel (the ``pallas_call``
named ``knn_streaming``: swarms past the fused kernel's VMEM, once in every
env step), per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("knn_streaming")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
