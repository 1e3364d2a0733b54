"""Host time the trainer spends handing one chunk's program to the device:
the mean duration of the ``train_dispatch`` annotations
(``Trainer._dispatch``, on the profiler's clock) that lie in the traced
window. The device runs the previous chunk meanwhile, so this is exposed
only where a chunk is shorter than its own enqueue."""

from benchmarks.host_spans import host_spans


def read(context):
    spans = host_spans(context["cell"]).get("train_dispatch")
    if not spans:
        return None  # a program without the annotation: nothing to read
    return 1e-6 * sum(end - start for start, end, _ in spans) / len(spans)
