"""Device time under the program's ``epoch_shuffle`` scope inside
``ppo_update`` (each epoch's ``jax.random.permutation`` of the rollout's rows
and its reshape into minibatches), per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("epoch_shuffle")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
