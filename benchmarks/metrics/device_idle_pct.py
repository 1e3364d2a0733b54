"""Share of the traced window in which no operation ran on the device
(1 - union of the device-op intervals over the window, mean of the chips)."""


def read(context):
    trace = context["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
