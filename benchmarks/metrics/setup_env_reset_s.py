"""Seconds of ``Trainer.__init__``'s environment reset: ``reset_batch``,
the first observation and the placement through ``shard_fn`` (the
``setup_env_reset`` span, a child of ``setup_build_trainer``)."""

from benchmarks.program_spans import duration


def read(context):
    return duration("setup_env_reset")
