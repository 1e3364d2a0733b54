"""Device time under the program's ``env_step`` scope inside ``rollout``
(the formation dynamics, rewards, resets and the next observation, k-NN
kernel included), per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("env_step")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
