"""Device time of the ops under the program's ``rollout`` scope (policy
forward, sampling, env step), per iteration of the traced window."""


def read(context):
    seconds = context["trace"]["scope_s"].get("rollout")
    if not seconds:
        return None
    return 1e3 * seconds / context["iterations"]
