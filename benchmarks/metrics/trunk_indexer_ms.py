"""Device time under the trunk's ``trunk_indexer`` scope
(``models/trunk.py``: the indexer's three products, the index scores and
the selection of each query's keys), in the rollout's forward passes and in the
update's forward, recomputed and backward passes, per iteration of the
traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("trunk_indexer")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
