"""Seconds jax spent in its backend compile during set-up, a build or a
load from the persistent compilation cache: the ``compile_s`` counter
summed like ``setup_trace_lower_s``."""

from benchmarks.program_spans import counted


def read(context):
    return counted("compile_s")
