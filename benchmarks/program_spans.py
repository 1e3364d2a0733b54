"""The program's own set-up spans, from the tracer's ring in this process.

The harness times set-up as one interval and traces only the window. The
program records set-up itself (``utils.profiling.host_span``): the span
``setup_build_trainer`` around ``train.build_trainer``, its children
``setup_param_init`` and ``setup_env_reset`` inside ``Trainer.__init__``,
and the first ``train_dispatch`` (``step_num`` 0), which compiles or loads
the iteration's program. Each span carries the compile events that fired
while it was the innermost one open (``trace_s``, ``lower_s``,
``compile_s``, ``cache_load_s``, ``programs``, ``cache_hits``), so sums
over these spans count each event once. The ring
(``obs.get_tracer().snapshot()``) outlives the trainer the harness frees
before the readers run. A program from before these spans has none of
them, and every reader of this then finds nothing to read.

``clock_offset`` places the ring's records on a ``jax.profiler``
capture's clock, from the spans found in both.
"""

from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Optional

SETUP_CHILDREN = ("setup_param_init", "setup_env_reset")
FIRST_DISPATCH = "train_dispatch"


def _spans() -> List[dict]:
    from marl_distributedformation_tpu.obs import get_tracer

    return [r for r in get_tracer().snapshot() if r.get("kind") == "span"]


def setup_spans() -> Dict[str, dict]:
    """``{name: snapshot record}`` of the newest ``setup_build_trainer``,
    its two children and the first dispatch after it; empty where the
    program recorded no such build."""
    spans = _spans()
    builds = [r for r in spans if r["name"] == "setup_build_trainer"]
    if not builds:
        return {}
    build = builds[-1]
    found = {build["name"]: build}
    for r in spans:
        if r["name"] in SETUP_CHILDREN and r.get("parent") == build.get("id"):
            found.setdefault(r["name"], r)
        elif (
            r["name"] == FIRST_DISPATCH
            and (r.get("attrs") or {}).get("step_num") == 0
            and r["t0"] >= build["t1"]
        ):
            found.setdefault(r["name"], r)
    return found


def duration(name: str) -> Optional[float]:
    """Seconds of the set-up span ``name``, or None where there is none."""
    span = setup_spans().get(name)
    return None if span is None else span["duration_s"]


def counted(*keys: str) -> Optional[float]:
    """The counters ``keys`` summed over the set-up spans and the first
    dispatch (an event lands on one span only), or None where the program
    recorded no set-up span."""
    spans = setup_spans()
    if not spans:
        return None
    return float(
        sum((r.get("counters") or {}).get(k, 0) for r in spans.values() for k in keys)
    )


def clock_offset(records: List[dict], host: Dict[str, list]) -> Optional[dict]:
    """How far a ``jax.profiler`` capture's host clock lies from the
    ring's epoch seconds: ``trace start - ring start`` over the spans in
    both (``host`` as ``benchmarks.host_spans.host_spans`` gives it, each
    span paired with the next unpaired event of its name whose arguments
    hold the span's ``attrs``). The median is the offset, in seconds;
    ``spread_s`` the widest minus the narrowest pair."""
    unpaired = {name: list(events) for name, events in host.items()}
    offsets = []
    for r in records:
        if r.get("kind") != "span":
            continue
        attrs = r.get("attrs") or {}
        events = unpaired.get(r["name"], [])
        for i, (start_ns, _, args) in enumerate(events):
            if all(args.get(k) == v for k, v in attrs.items()):
                offsets.append(start_ns * 1e-9 - r["t0"])
                del events[i]
                break
    if not offsets:
        return None
    return {
        "offset_s": statistics.median(offsets),
        "spread_s": max(offsets) - min(offsets),
        "pairs": len(offsets),
    }


def log_clock_offset(cell) -> Optional[dict]:
    """``clock_offset`` of the ring against the run's trace, written to
    standard error; None where either has no span of the other."""
    from benchmarks.host_spans import host_spans

    found = clock_offset(_spans(), host_spans(cell))
    if found is not None:
        print(
            f"[trace] ring-to-trace offset {found['offset_s']!r} s over "
            f"{found['pairs']} spans, spread {1e6 * found['spread_s']:.1f} us",
            file=sys.stderr, flush=True,
        )
    return found
