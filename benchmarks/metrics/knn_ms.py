"""Device time of the fused Pallas k-NN kernel (the ``pallas_call`` named
``knn_fused``: N <= 512, once in every env step), per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("knn_fused")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
