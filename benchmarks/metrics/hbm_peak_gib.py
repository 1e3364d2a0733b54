"""Peak device memory on the fullest chip after the window, live arrays
plus what the loaded program reserves for its temporaries."""


def read(context):
    peak = context["memory_peak_bytes"]
    return None if peak is None else peak / 2**30
