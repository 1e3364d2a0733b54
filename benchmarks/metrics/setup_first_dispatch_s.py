"""Seconds of the first ``train_dispatch`` (``step_num`` 0): tracing,
lowering and compiling or loading the iteration's program, and its
enqueue; not its execution, which the harness waits for. Also writes the
ring-to-trace clock offset to standard error, where the run traced its
window."""

import sys

from benchmarks.program_spans import duration, log_clock_offset


def read(context):
    seconds = duration("train_dispatch")
    if seconds is not None:
        try:
            log_clock_offset(context["cell"])
        except Exception as e:  # noqa: BLE001 - a log line, not the metric
            print(f"[trace] no ring-to-trace offset: {e!r}", file=sys.stderr)
    return seconds
