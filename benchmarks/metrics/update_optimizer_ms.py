"""Device time under the program's ``optimizer_step`` scope inside
``ppo_update`` (global-norm clip, Adam and the ``log_std`` clamp), per
iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("optimizer_step")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
