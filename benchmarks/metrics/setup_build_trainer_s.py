"""Seconds of ``train.build_trainer``, whatever trainer it builds: the
program's ``setup_build_trainer`` span (``utils.profiling.host_span``), on
the host's clock, in the tracer's ring of the harness's own process."""

from benchmarks.program_spans import duration


def read(context):
    return duration("setup_build_trainer")
