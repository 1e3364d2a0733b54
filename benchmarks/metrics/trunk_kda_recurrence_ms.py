"""Device time under the ``kda_recurrence`` scope inside ``trunk_kda``
(``models/kda.py``: the chunked delta rule alone: the pairwise decays within a
chunk, the triangular solve, the scan that carries the state across chunks;
what a kernel would take over), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("kda_recurrence")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
