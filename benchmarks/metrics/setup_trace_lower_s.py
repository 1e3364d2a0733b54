"""Seconds jax spent tracing and lowering during set-up: the ``trace_s``
and ``lower_s`` counters (``jax.monitoring``'s trace and MLIR events)
summed over ``setup_build_trainer``, its two children and the first
dispatch, each event counted on the innermost span it fired in."""

from benchmarks.program_spans import counted


def read(context):
    return counted("trace_s", "lower_s")
