"""Device time under the GNN's ``neighbor_gather`` scope: the gathers of
neighbour embeddings in the rollout's forward pass and in the update's, and
what of their transpose (the scatter-add) keeps the scope, per iteration of
the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("neighbor_gather")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
