"""RegressionSentinel: the committed bench record as a live tripwire.

The repo's ``BENCH_r*.json`` records are the performance ground truth —
but until now they were consulted by humans on bench day only. The
sentinel closes that loop: it loads the newest committed record,
compares the live :class:`~.metrics.MetricsRegistry` gauges against the
recorded fields with a tolerance band and ``trip_after``-style
hysteresis (the ``RollbackMonitor`` discipline: one noisy sample must
never page anyone), and on SUSTAINED degradation

- records a ``perf_regression`` incident through the tracer — which
  dumps a ``flightrec-perf_regression-*.json`` flight record with the
  metrics snapshot and the recent span history while the slow period is
  still in the rings, and
- appends an audit line to ``perf_incidents.jsonl`` —

making "slower than the record" an observable incident instead of a
bench-day surprise.

Taxonomy (``missing``) is explicit: a watch whose bench field is absent
from the record, explicitly ``"skipped"`` (a ``BENCH_SKIP_*`` phase),
or non-numeric is recorded as unmeasurable — never a breach, never
silently dropped. A live gauge that has not been recorded yet simply
leaves the streak untouched (a cold process is not evidence of
anything).

This module never imports jax.
"""

from __future__ import annotations

import dataclasses
import json
import re
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from marl_distributedformation_tpu.obs.metrics import (
    MetricsRegistry,
    get_registry,
)
from marl_distributedformation_tpu.obs.tracer import Tracer, get_tracer

# The bench record's explicit not-run marker.
SKIPPED = "skipped"

_BENCH_RE = re.compile(r"^BENCH_r(\d+)\.json$")


def load_bench_record(
    path: Optional[str | Path] = None, root: Optional[str | Path] = None
) -> Tuple[Dict[str, Any], Optional[Path]]:
    """The newest committed bench record as a flat dict.

    ``path`` pins an explicit file; otherwise the highest-numbered
    ``BENCH_r*.json`` under ``root`` (default: the repo root) wins —
    numeric order, so r10 beats r9. Both the driver wrapper shape
    (``{"parsed": {...}}``) and a bare bench JSON line are accepted.
    Returns ``({}, None)`` when nothing is loadable — the sentinel then
    reports every watch as unmeasurable instead of crashing the process
    it guards."""
    if path is not None:
        candidates = [Path(path)]
    else:
        if root is None:
            root = Path(__file__).resolve().parents[2]
        found = [
            p for p in Path(root).glob("BENCH_r*.json") if _BENCH_RE.match(p.name)
        ]
        candidates = sorted(
            found,
            key=lambda p: int(_BENCH_RE.match(p.name).group(1)),
            reverse=True,
        )
    for candidate in candidates:
        try:
            record = json.loads(Path(candidate).read_text())
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(record, dict) and isinstance(
            record.get("parsed"), dict
        ):
            record = record["parsed"]
        if isinstance(record, dict):
            return record, Path(candidate)
    return {}, None


@dataclasses.dataclass(frozen=True)
class Watch:
    """One live-gauge-vs-recorded-field comparison.

    ``direction="min"`` guards throughput (breach when the live value
    falls below ``(1 - tolerance) * recorded``); ``direction="max"``
    guards latency (breach above ``(1 + tolerance) * recorded``).
    ``bench_fields`` is a preference list — the first field present and
    numeric in the record is the reference (the bench's field
    generations: fused_scan beats tuned beats plain)."""

    gauge: str
    bench_fields: Tuple[str, ...]
    direction: str = "min"
    tolerance: float = 0.5

    def __post_init__(self) -> None:
        if self.direction not in ("min", "max"):
            raise ValueError(
                f"direction must be 'min' or 'max', got {self.direction!r}"
            )
        if not self.bench_fields:
            raise ValueError(f"watch {self.gauge!r} names no bench fields")
        if self.tolerance <= 0.0:
            raise ValueError(f"tolerance must be > 0, got {self.tolerance}")


def ledger_watches(tolerance: float = 0.5) -> Tuple[Watch, ...]:
    """Program-ledger guards (obs/ledger.py) against the committed
    record's bench phase-13 fields. Both gauges are deliberately
    RECOVERABLE — the Watch machinery latches while breached and
    re-arms in band, which a lifetime-cumulative value can never do:

    - ``ledger_compile_seconds_max`` (not the total, which
      legitimately grows with every curriculum-swap sampler rebuild
      over a long run): past the record means SOME program got
      materially more expensive to build — an XLA upgrade, an
      accidental program split.
    - ``device_memory_bytes_in_use`` (the instantaneous gauge, judged
      against the committed watermark): sustained residency past the
      recorded peak means the executables + live state no longer fit
      the budget the autoscaler packed against; a transient swap spike
      recovers in band instead of tripping forever.

    Same trip machinery as every other watch: flightrec + audit line."""
    return (
        Watch(
            gauge="ledger_compile_seconds_max",
            bench_fields=("ledger_compile_seconds_max",),
            direction="max",
            tolerance=tolerance,
        ),
        Watch(
            gauge="device_memory_bytes_in_use",
            bench_fields=("device_memory_watermark_bytes",),
            direction="max",
            tolerance=tolerance,
        ),
    )


def recovery_watches(tolerance: float = 1.0) -> Tuple[Watch, ...]:
    """Train-lane recovery guards (train/recovery.py) against the
    committed bench phase-15 field: the live rollback MTTR tail
    (``train_recovery_mttr_seconds_p95`` — the registry histogram's
    percentile gauge, recoverable by construction: a one-off slow
    restore re-arms once faster ones dominate the reservoir) judged
    against the recorded ``recovery_mttr_s``. A sustained breach means
    rollback restores got materially slower than the record — a grown
    checkpoint, a slow disk, a quarantine walk that keeps walking —
    exactly the degradation that turns "self-healing" back into
    downtime. Wide default band: recovery is rare, so samples are few.
    Same flightrec + audit trip machinery as every other watch."""
    return (
        Watch(
            gauge="train_recovery_mttr_seconds_p95",
            bench_fields=("recovery_mttr_s",),
            direction="max",
            tolerance=tolerance,
        ),
    )


def default_watches(tolerance: float = 0.5) -> Tuple[Watch, ...]:
    """The stock lane guards: trainer throughput, gate eval throughput,
    fleet tail latency. Generous default band — committed records are
    often measured on different hardware than the live run; tighten per
    deployment."""
    return (
        Watch(
            gauge="train_env_steps_per_sec",
            bench_fields=(
                "train_env_steps_per_sec_fused_scan",
                "train_env_steps_per_sec_tuned",
                "train_env_steps_per_sec",
            ),
            direction="min",
            tolerance=tolerance,
        ),
        Watch(
            gauge="gate_eval_steps_per_sec",
            bench_fields=("gate_eval_steps_per_sec",),
            direction="min",
            tolerance=tolerance,
        ),
        Watch(
            gauge="latency_p95_ms",
            bench_fields=("serving_fleet_p95_ms",),
            direction="max",
            tolerance=tolerance,
        ),
    )


class _WatchState:
    __slots__ = ("streak", "tripped")

    def __init__(self) -> None:
        self.streak = 0
        self.tripped = False


class RegressionSentinel:
    """Compare live registry gauges against the committed bench record.

    Args:
      watches: the comparisons to run each check.
      record: an explicit bench record dict (tests); otherwise loaded
        from ``record_path`` / the newest committed ``BENCH_r*.json``.
      trip_after: consecutive breaching checks before a watch trips
        (hysteresis — the RollbackMonitor shape).
      audit_dir: directory for ``perf_incidents.jsonl`` (None: no audit
        file, incidents still fire through the tracer).
      registry / tracer: explicit instances (tests); default to the
        process globals, resolved at check time.
    """

    AUDIT_NAME = "perf_incidents.jsonl"

    def __init__(
        self,
        watches: Sequence[Watch] = (),
        record: Optional[Dict[str, Any]] = None,
        record_path: Optional[str | Path] = None,
        bench_root: Optional[str | Path] = None,
        trip_after: int = 3,
        audit_dir: Optional[str | Path] = None,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
    ) -> None:
        self.watches = tuple(watches) or default_watches()
        if record is not None:
            self.record, self.record_source = dict(record), None
        else:
            self.record, self.record_source = load_bench_record(
                record_path, root=bench_root
            )
        self.trip_after = max(1, int(trip_after))
        self.audit_path = (
            Path(audit_dir) / self.AUDIT_NAME
            if audit_dir is not None
            else None
        )
        self._registry = registry
        self._tracer = tracer
        self._state: Dict[str, _WatchState] = {
            w.gauge: _WatchState() for w in self.watches
        }
        self.checks_total = 0
        self.trips: List[dict] = []
        # gauge -> reason, for watches that can never breach: the
        # missing-bench-field taxonomy (explicit, not silent).
        self.missing: Dict[str, str] = {}
        # Watches whose live gauge has appeared in at least one checked
        # snapshot — a watch that never shows up here is blind (nothing
        # feeds its gauge), which summary() surfaces explicitly.
        self._observed: set = set()

    # -- reference arithmetic --------------------------------------------

    def reference(self, watch: Watch) -> Optional[Tuple[str, float]]:
        """``(field, recorded_value)`` for the first usable bench field,
        recording the taxonomy for unusable ones."""
        reasons = []
        for field in watch.bench_fields:
            value = self.record.get(field)
            if value is None:
                reasons.append(f"{field}: absent")
                continue
            if value == SKIPPED:
                reasons.append(f"{field}: explicitly skipped (BENCH_SKIP_*)")
                continue
            try:
                v = float(value)
            except (TypeError, ValueError):
                reasons.append(f"{field}: non-numeric ({value!r})")
                continue
            self.missing.pop(watch.gauge, None)
            return field, v
        self.missing[watch.gauge] = "; ".join(reasons) or "no bench fields"
        return None

    @staticmethod
    def _band(watch: Watch, recorded: float) -> float:
        if watch.direction == "min":
            return recorded * (1.0 - watch.tolerance)
        return recorded * (1.0 + watch.tolerance)

    def limit(self, watch: Watch) -> Optional[float]:
        ref = self.reference(watch)
        if ref is None:
            return None
        return self._band(watch, ref[1])

    # -- the check --------------------------------------------------------

    def check(
        self, snapshot: Optional[Dict[str, float]] = None
    ) -> List[dict]:
        """One comparison pass over every watch; returns the incidents
        that TRIPPED on this check (usually empty). A tripped watch
        stays latched (no repeat dumps while the degradation persists)
        and re-arms once it recovers inside the band."""
        registry = self._registry or get_registry()
        if snapshot is None:
            # The default snapshot carries the program ledger's
            # aggregate gauges too, so ledger_watches() work without
            # every caller hand-merging namespaces (an explicit
            # snapshot argument is taken verbatim — tests).
            from marl_distributedformation_tpu.obs.ledger import (
                merge_ledger_snapshot,
            )

            snapshot = merge_ledger_snapshot(registry.snapshot())
        self.checks_total += 1
        tripped_now: List[dict] = []
        for watch in self.watches:
            ref = self.reference(watch)
            if ref is None:
                continue
            live = snapshot.get(watch.gauge)
            if live is None:
                continue  # not yet recorded: no evidence either way
            self._observed.add(watch.gauge)
            field, recorded = ref
            live = float(live)
            limit = self._band(watch, recorded)
            breached = (
                live < limit if watch.direction == "min" else live > limit
            )
            state = self._state[watch.gauge]
            if not breached:
                state.streak = 0
                state.tripped = False  # recovered: re-arm
                continue
            state.streak += 1
            if state.streak < self.trip_after or state.tripped:
                continue
            state.tripped = True
            incident = {
                "gauge": watch.gauge,
                "live": live,
                "bench_field": field,
                "recorded": recorded,
                "limit": limit,
                "direction": watch.direction,
                "tolerance": watch.tolerance,
                "streak": state.streak,
                "bench_record": (
                    str(self.record_source) if self.record_source else None
                ),
            }
            self._trip(incident, snapshot)
            tripped_now.append(incident)
        return tripped_now

    def _trip(self, incident: dict, snapshot: Dict[str, float]) -> None:
        """A sustained regression: flight-record the evidence and write
        the audit line. Never raises — the sentinel observes the system,
        it must not become its failure mode."""
        self.trips.append(incident)
        registry = self._registry or get_registry()
        registry.counter("sentinel_trips_total").inc()
        tracer = self._tracer or get_tracer()
        dump = tracer.incident(
            "perf_regression", metrics_snapshot=dict(snapshot), **incident
        )
        if self.audit_path is None:
            return
        line = dict(incident)
        line["event"] = "perf_regression"
        line["time"] = time.time()
        line["flightrec"] = str(dump) if dump is not None else None
        try:
            self.audit_path.parent.mkdir(parents=True, exist_ok=True)
            with open(self.audit_path, "a") as f:
                f.write(json.dumps(line) + "\n")
        except OSError:
            pass

    # -- reporting --------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        return {
            "sentinel_checks": self.checks_total,
            "sentinel_trips": len(self.trips),
            "sentinel_missing": dict(self.missing),
            # Watches whose live gauge never appeared in any checked
            # snapshot: measurable against the record, but nothing in
            # this process feeds the gauge — a blind watch is reported,
            # never silent.
            "sentinel_never_observed": sorted(
                w.gauge
                for w in self.watches
                if w.gauge not in self._observed
                and w.gauge not in self.missing
            ),
            "sentinel_bench_record": (
                str(self.record_source) if self.record_source else None
            ),
        }
