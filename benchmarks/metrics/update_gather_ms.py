"""Device time under the program's ``minibatch_gather`` scope inside
``ppo_update`` (``x[idx]`` on every rollout array, once a minibatch), per
iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("minibatch_gather")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
