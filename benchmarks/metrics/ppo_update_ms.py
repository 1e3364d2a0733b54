"""Device time of the ops under the program's ``ppo_update`` scope
(shuffle, minibatch gathers, loss and gradients, Adam), per iteration."""


def read(context):
    seconds = context["trace"]["scope_s"].get("ppo_update")
    if not seconds:
        return None
    return 1e3 * seconds / context["iterations"]
