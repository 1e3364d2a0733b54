"""Seconds of ``Trainer.__init__``'s parameter draw: the jitted
``model.init`` and ``TrainState.create`` (the ``setup_param_init`` span, a
child of ``setup_build_trainer``). The harness replaces these parameters
with its seeded ones."""

from benchmarks.program_spans import duration


def read(context):
    return duration("setup_param_init")
