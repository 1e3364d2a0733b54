"""Programs jax compiled or loaded during set-up: the ``programs`` counter
(one a backend event) summed like ``setup_trace_lower_s``."""

from benchmarks.program_spans import counted


def read(context):
    return counted("programs")
