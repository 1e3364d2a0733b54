"""From the profiler's trace to numbers: the one reduction every PR's
per-layer metrics come from.

``read_xplane`` turns an ``.xplane.pb`` into plain event tuples (it is the
only part that needs jax); everything after works on those tuples, so the
tests drive it with synthetic events. A TPU's device plane carries a line
``XLA Modules`` (one event for each run of a compiled program) and a line
``XLA Ops`` (one event for each HLO instruction that ran, a ``while`` and
the instructions of its body nested inside it). An op's name is its HLO
text, whose ``op_name="..."`` metadata holds the path of
``jax.named_scope`` names the program gave it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_NAME = re.compile(r"^%?([\w.\-]+)")
COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


@dataclass(frozen=True)
class Event:
    name: str  # the instruction's own name, e.g. ``fusion.12``
    scope: str  # the named-scope path from the HLO metadata, or ""
    opcode: str  # ``fusion``, ``while``, ``custom-call``, ... or ""
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


def _opcode(text: str) -> str:
    """The opcode of an HLO instruction's text: what stands between its
    result type (which may nest brackets of all three kinds) and the
    operands' opening bracket."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1 :].partition("(")[0].strip()
    return ""


def parse_op(text: str) -> Tuple[str, str, str]:
    """``(name, scope, opcode)`` of an op event from its HLO text."""
    name = _HLO_NAME.match(text)
    scope = _OP_NAME.search(text)
    return (
        name.group(1) if name else text[:40],
        scope.group(1) if scope else "",
        _opcode(text),
    )


def read_xplane(path: Path, scopes: Optional[Dict[str, str]] = None) -> Dict[str, dict]:
    """``{"devices": {ordinal: {"ops": [Event], "modules": [Event]}},
    "host": [Event]}`` from one ``.xplane.pb``. ``scopes`` maps an
    instruction's name to its named-scope path where the trace's own text
    does not carry it (a TPU's does not)."""
    import jax

    scopes = scopes or {}

    data = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[int, dict] = {}
    host: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            entry = devices.setdefault(int(match.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        name, scope, opcode = parse_op(ev.name)
                        entry["ops"].append(
                            Event(name, scope or scopes.get(name, ""), opcode, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                        )
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        entry["modules"].append(
                            Event(ev.name, "", "module", ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                        )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= 1e5:  # 0.1 ms: what can fill a gap
                        host.append(
                            Event(ev.name, line.name, "host", ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                        )
    return {"devices": devices, "host": host}


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def nest(ops: Sequence[Event]) -> List[Tuple[Event, float, int]]:
    """Each op, in start order, with its self time (the part of its
    interval that no op nested in it covers: a ``while`` keeps only what
    its body leaves) and the index of the op it is nested in, or -1."""
    ordered = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    out: List[List] = []
    stack: List[int] = []
    for ev in ordered:
        while stack and out[stack[-1]][0].end_ns <= ev.start_ns:
            stack.pop()
        parent = stack[-1] if stack else -1
        if stack:
            above = out[parent]
            above[1] -= min(ev.end_ns, above[0].end_ns) - ev.start_ns
        out.append([ev, ev.duration_ns, parent])
        stack.append(len(out) - 1)
    return [(ev, max(self_ns, 0.0), parent) for ev, self_ns, parent in out]


def is_collective(ev: Event) -> bool:
    base = ev.opcode or ev.name
    return any(base.startswith(c) for c in COLLECTIVES)


def _gap_reason(host: Sequence[Event], start: float, end: float) -> str:
    best, best_overlap = "nothing", 0.0
    for ev in host:
        overlap = min(ev.end_ns, end) - max(ev.start_ns, start)
        if overlap > best_overlap and not ev.name.startswith("$profiler"):
            best, best_overlap = ev.name, overlap
    return best


def _short(scope: str) -> str:
    """A scope path without the jit wrappers at its head and the loop
    bodies in between: what a reader needs to place the op."""
    parts = [
        p for p in scope.split("/")
        if p and not p.startswith(("jit(", "pjit")) and p not in ("while", "body", "closed_call")
    ]
    return "/".join(parts)[-120:]


def reduce_events(read: Dict[str, dict], chips: int) -> dict:
    """Everything the per-layer readers take, averaged over the chips
    used. Times are seconds unless the key says otherwise."""
    devices = {
        k: v for k, v in sorted(read["devices"].items()) if v["ops"]
    }
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    used = list(devices)[:chips]
    starts = [min(e.start_ns for e in devices[d]["ops"]) for d in used]
    ends = [max(e.end_ns for e in devices[d]["ops"]) for d in used]
    for d in used:
        if devices[d]["modules"]:
            starts.append(min(e.start_ns for e in devices[d]["modules"]))
            ends.append(max(e.end_ns for e in devices[d]["modules"]))
    window_ns = max(ends) - min(starts)

    busy, gaps_ns, exposed, scopes, by_name = [], [], [], {}, {}
    kernels: Dict[str, List[float]] = {}
    module_runs = 0
    all_gaps: List[Tuple[float, float, float]] = []
    for d in used:
        ops = devices[d]["ops"]
        busy.append(union_ns((e.start_ns, e.end_ns) for e in ops))
        modules = sorted(devices[d]["modules"], key=lambda e: e.start_ns)
        module_runs = max(module_runs, len(modules))
        gap = 0.0
        for prev, nxt in zip(modules, modules[1:]):
            if nxt.start_ns > prev.end_ns:
                gap += nxt.start_ns - prev.end_ns
                all_gaps.append((nxt.start_ns - prev.end_ns, prev.end_ns, nxt.start_ns))
        gaps_ns.append(gap)
        exposed_ns = 0.0
        resolved: List[str] = []
        for ev, self_ns, parent in nest(ops):
            # An instruction the compiler made without metadata (the
            # scatter of a gather's backward pass, a copy) takes the scope
            # of the loop it runs in.
            scope = ev.scope or (resolved[parent] if parent >= 0 else "")
            resolved.append(scope)
            if ev.opcode == "while":
                continue  # a loop's own time is its body's, counted there
            for part in set(scope.split("/")):
                scopes[part] = scopes.get(part, 0.0) + self_ns / len(used)
            key = (ev.name, scope, ev.opcode)
            by_name[key] = by_name.get(key, 0.0) + self_ns / len(used)
            if is_collective(ev):
                exposed_ns += self_ns
            if ev.opcode == "custom-call" and "pallas_call" in scope:
                entry = kernels.setdefault(f"{_short(scope)}::{ev.name}", [0.0, 0.0])
                entry[0] += 1.0 / len(used)
                entry[1] += self_ns / 1e9 / len(used)
        exposed.append(exposed_ns)

    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(all_gaps, reverse=True)[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": mean(busy) / 1e9,
        "dispatch_gap_s": mean(gaps_ns) / 1e9,
        "collective_exposed_s": mean(exposed) / 1e9,
        "module_runs": module_runs,
        "scope_s": {k: v / 1e9 for k, v in scopes.items() if k},
        "kernel_s": {k: (v[0], v[1]) for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [
                [f"{_short(scope) or opcode}::{name}", ns / 1e9]
                for (name, scope, opcode), ns in top
            ],
            "idle_gaps": [
                [_gap_reason(read["host"], start, end), dur / 1e9]
                for dur, start, end in longest
            ],
        },
    }


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace_dir(
    trace_dir: Path, chips: int, scopes: Optional[Dict[str, str]] = None
) -> dict:
    return reduce_events(read_xplane(find_xplane(trace_dir), scopes), chips)
