"""Device time under the program's ``policy`` scope inside ``rollout``
(the policy's forward pass, sampling and log-probability at every rollout
step; no gradients), per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("policy")
    if not seconds:
        return None  # the program opens no such scope: nothing to read
    return 1e3 * seconds / context["iterations"]
