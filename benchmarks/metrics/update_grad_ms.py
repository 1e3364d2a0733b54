"""Device time under the program's ``loss_and_grad`` scope inside
``ppo_update`` (the policy's forward and backward pass on a minibatch, the
clipped loss and the gradient's global norm), per iteration of the traced
window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("loss_and_grad")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
