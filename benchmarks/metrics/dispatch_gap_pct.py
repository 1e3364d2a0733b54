"""Share of the traced window in which the device waited between one
chunk's program and the next (``XLA Modules`` line of the device trace)."""


def read(context):
    trace = context["trace"]
    if trace["module_runs"] < 2:
        return None  # one program run has no gap to another
    return 100.0 * trace["dispatch_gap_s"] / trace["window_s"]
