"""The program's own host spans, from the trace a ``--trace 1`` run wrote.

``trace.reduce_events`` hands the per-layer readers device time only, and
``trace.read_xplane`` keeps no host event under 0.1 ms. The trainer marks
its host seams with ``jax.profiler`` annotations on the profiler's clock
(``utils.profiling.HOST_SPANS``: ``train_dispatch`` around the enqueue of a
chunk, ``train_drain`` around the fetch of its metrics); this reads them
back from the ``/host:CPU`` plane of the run's ``.xplane.pb``, whatever
their length. A program from before the annotations has no such names, and
every reader of this then finds nothing to read.
"""

from __future__ import annotations

import functools
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

from benchmarks import trace

HOST_PLANE = "/host:CPU"
Span = Tuple[float, float, dict]  # start_ns, end_ns, the annotation's arguments


@functools.lru_cache(maxsize=1)
def read_host_spans(xplane: Path, names: Tuple[str, ...]) -> Dict[str, List[Span]]:
    import jax

    started = time.perf_counter()
    spans: Dict[str, List[Span]] = {}
    for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    )
    for found in spans.values():
        found.sort(key=lambda span: span[0])
    print(
        f"[trace] host spans {({k: len(v) for k, v in spans.items()})} read "
        f"from {xplane.name} in {time.perf_counter() - started:.2f}s",
        file=sys.stderr, flush=True,
    )
    return spans


def host_spans(cell) -> Dict[str, List[Span]]:
    """``{name: [(start_ns, end_ns, arguments)]}`` in start order, for the
    names in the program's ``HOST_SPANS`` and no other host event. Empty
    where the program names no span or the run left no trace."""
    from marl_distributedformation_tpu.utils import profiling

    names = tuple(getattr(profiling, "HOST_SPANS", ()))
    if not names:
        return {}
    # where ``harness.run_cell`` traces the cell's window into
    trace_dir = cell.bench_dir.parent / ".bench_out" / "trace" / cell.name
    try:
        xplane = trace.find_xplane(trace_dir)
    except RuntimeError:
        return {}
    return read_host_spans(xplane, names)
