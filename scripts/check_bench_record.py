#!/usr/bin/env python
"""Validate a bench JSON line as committable chip evidence.

A bench record counts only when it is hardware evidence: a TPU platform,
no error, no degraded ("skipped"/"failed") phases — plus a per-use list
of required rate fields. This is that gate in ONE place:

    python scripts/check_bench_record.py bench.json \
        --require train_env_steps_per_sec knn_env_steps_per_sec \
        --expect knn_impl=pallas

Exit 0 iff the record passes. ``--require F`` asserts float(rec[F]) > 0;
``--expect K=V`` asserts str(rec[K]) == V. The input is bench.py's stdout
(ONE JSON line, possibly preceded by other output).

Census mode — the acceptance gate for the program ledger
(obs/ledger.py):

    python scripts/check_bench_record.py COMMITTED_census.json \
        --census logs/run/program_ledger.json [--census-tolerance 0.25]

diffs a COMMITTED census (the positional file) against the LIVE one a
fresh run just wrote: programs that vanished or appeared, and
flops/bytes/memory-footprint drift past the tolerance, are rejections —
a chip re-measure must attribute every cost change, not discover it in
a throughput regression later.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path


def load_record(src: Path) -> dict:
    """The bench record in ``src``: the last JSON line carrying a
    ``metric`` field (bench.py prints exactly one, after its log
    lines)."""
    for line in reversed(src.read_text().strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
            if "metric" in rec:
                return rec
    raise SystemExit(f"no bench JSON record found in {src}")


# bench.py writes this sentinel into the rate fields of phases disabled
# by BENCH_SKIP_* env vars — "explicitly not run", distinct from both a
# healthy number and a silently-absent field. Structural validators
# treat sentinel fields as absent; --require rejects them with a message
# that says WHY the field is empty.
SKIPPED = "skipped"


def _present(rec: dict, key: str):
    """Field value, with None for both absent and explicitly-skipped."""
    v = rec.get(key)
    return None if v == SKIPPED else v


def _pipeline_problems(rec: dict) -> list[str]:
    """Structural validation of the always-learning pipeline fields
    (bench phase 7): whenever a record carries them, they must be
    internally consistent — a latency percentile pair that is not a
    percentile pair, or a gate that compiled more than once, is a
    malformed record regardless of which stage required the fields."""
    problems = []
    p50 = _present(rec, "promotion_latency_s_p50")
    p95 = _present(rec, "promotion_latency_s_p95")
    if (p50 is None) != (p95 is None):
        problems.append(
            "promotion_latency_s_p50/p95 must be recorded together"
        )
    if p50 is not None and p95 is not None:
        try:
            p50, p95 = float(p50), float(p95)
            if not 0.0 < p50 <= p95:
                problems.append(
                    f"promotion latency percentiles malformed: "
                    f"p50={p50} p95={p95} (need 0 < p50 <= p95)"
                )
        except (TypeError, ValueError):
            problems.append("promotion latency fields are not numbers")
        gate = rec.get("gate_eval_steps_per_sec")
        try:
            gate_ok = gate is not None and float(gate) > 0.0
        except (TypeError, ValueError):
            gate_ok = False
        if not gate_ok:
            problems.append(
                f"gate_eval_steps_per_sec missing/zero/non-numeric "
                f"beside promotion latency: {gate!r}"
            )
        compiles = rec.get("pipeline_gate_compiles")
        if compiles != 1:
            problems.append(
                f"pipeline_gate_compiles={compiles!r} — the gate's eval "
                "program must compile exactly once across all candidates"
            )
        rung = rec.get("pipeline_serving_max_compiles_per_rung")
        try:
            rung_ok = rung is None or int(rung) <= 1
        except (TypeError, ValueError):
            rung_ok = False
        if not rung_ok:
            problems.append(
                f"pipeline_serving_max_compiles_per_rung={rung!r} "
                "(need an int <= 1)"
            )
    return problems


def _obs_problems(rec: dict) -> list[str]:
    """Structural validation of the obs tracing fields (bench phase 8):
    a tracing overhead that is not a finite number, or a promotion span
    breakdown whose stages overshoot the latency they decompose, is a
    malformed record."""
    problems = []
    pct = _present(rec, "tracing_overhead_pct")
    if pct is not None:
        try:
            if not math.isfinite(float(pct)):
                problems.append(
                    f"tracing_overhead_pct not finite: {pct!r}"
                )
        except (TypeError, ValueError):
            problems.append(
                f"tracing_overhead_pct is not a number: {pct!r}"
            )
    breakdown = rec.get("promotion_span_breakdown")
    if breakdown is not None:
        if not isinstance(breakdown, dict) or not breakdown:
            problems.append(
                f"promotion_span_breakdown must be a non-empty dict of "
                f"stage->seconds: {breakdown!r}"
            )
            return problems
        try:
            stages = {str(k): float(v) for k, v in breakdown.items()}
        except (TypeError, ValueError):
            problems.append(
                f"promotion_span_breakdown has non-numeric stages: "
                f"{breakdown!r}"
            )
            return problems
        bad = {k: v for k, v in stages.items() if v < 0.0}
        if bad:
            problems.append(
                f"promotion_span_breakdown stages negative: {bad!r}"
            )
        # The stage p50s decompose the promotion latency: their sum may
        # not exceed the recorded p95 by more than clock-noise tolerance
        # (stages summing PAST the latency they claim to explain means
        # the decomposition double-counts). deferred_wait_s is excluded:
        # it exists only on deferred promotions, so its p50 conditions
        # on a different promotion subset than the latency percentile —
        # a handful of long defers among many fast promotions would push
        # the sum past a p95 that legitimately never saw them.
        p95 = rec.get("promotion_latency_s_p95")
        try:
            p95 = float(p95) if p95 is not None else None
        except (TypeError, ValueError):
            p95 = None  # already reported by _pipeline_problems
        if p95 is not None:
            total = sum(
                v for k, v in stages.items() if k != "deferred_wait_s"
            )
            tolerance = max(0.5, 0.1 * p95)
            if total > p95 + tolerance:
                problems.append(
                    f"promotion_span_breakdown sums to {total:.3f}s, "
                    f"exceeding promotion_latency_s_p95={p95:.3f}s "
                    f"+ tolerance {tolerance:.3f}s"
                )
    return problems


def _telemetry_problems(rec: dict) -> list[str]:
    """Structural validation of the live-metrics-plane fields (bench
    phase 11): a telemetry overhead that is not a finite number, or a
    sentinel poll rate that is zero/negative, is a malformed record
    whenever present."""
    problems = []
    pct = _present(rec, "telemetry_overhead_pct")
    if pct is not None:
        try:
            if not math.isfinite(float(pct)):
                problems.append(
                    f"telemetry_overhead_pct not finite: {pct!r}"
                )
        except (TypeError, ValueError):
            problems.append(
                f"telemetry_overhead_pct is not a number: {pct!r}"
            )
    rate = _present(rec, "sentinel_checks_per_sec")
    if rate is not None:
        try:
            if not float(rate) > 0.0:
                problems.append(
                    f"sentinel_checks_per_sec={rate!r} (need > 0)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"sentinel_checks_per_sec is not a number: {rate!r}"
            )
    return problems


def _serving_slo_problems(rec: dict) -> list[str]:
    """Structural validation of the SLO serving fields (bench phase 9):
    whenever a record carries the req/s-at-SLO headline, the load-gen
    rate and both 512-rung percentiles must be positive numbers, the
    bf16 delta a finite number, and the compile receipts budget-1."""
    problems = []
    rate = _present(rec, "serving_req_per_sec_at_p95_slo")
    if rate is None:
        return problems
    try:
        if not float(rate) > 0.0:
            problems.append(
                f"serving_req_per_sec_at_p95_slo={rate!r} (need > 0: a "
                "0 rate means even the lowest probe violated the SLO)"
            )
    except (TypeError, ValueError):
        problems.append(
            f"serving_req_per_sec_at_p95_slo is not a number: {rate!r}"
        )
    for key in (
        "serving_sharded_512_p95_ms",
        "serving_replicated_512_p95_ms",
    ):
        v = _present(rec, key)
        try:
            ok = v is not None and float(v) > 0.0
        except (TypeError, ValueError):
            ok = False
        if not ok:
            problems.append(
                f"{key}={v!r} beside the SLO rate (need a positive p95)"
            )
    bf16 = _present(rec, "serving_bf16_speedup_pct")
    try:
        bf16_ok = bf16 is not None and math.isfinite(float(bf16))
    except (TypeError, ValueError):
        bf16_ok = False
    if not bf16_ok:
        problems.append(
            f"serving_bf16_speedup_pct={bf16!r} (need a finite number; "
            "negative is legitimate on CPU)"
        )
    receipts = _present(rec, "serving_slo_max_compiles_per_rung")
    if receipts != 1:
        problems.append(
            f"serving_slo_max_compiles_per_rung={receipts!r} — every "
            "rung (sharded and bf16 included) must compile exactly once"
        )
    return problems


def _adversarial_problems(rec: dict) -> list[str]:
    """Structural validation of the adversarial-robustness fields (bench
    phase 10): whenever a record carries the search throughput, the
    compile receipt must be budget-1 and the worst-case gap a finite
    number (NEGATIVE is legitimate — at bench-sized training budgets the
    curriculum payoff is directional, and an honest record keeps the
    sign it measured)."""
    problems = []
    rate = _present(rec, "adversarial_candidates_per_sec")
    if rate is not None:
        try:
            if not float(rate) > 0.0:
                problems.append(
                    f"adversarial_candidates_per_sec={rate!r} (need > 0)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"adversarial_candidates_per_sec is not a number: {rate!r}"
            )
        compiles = _present(rec, "adversarial_search_compiles")
        if compiles != 1:
            problems.append(
                f"adversarial_search_compiles={compiles!r} — the "
                "falsifier search's population program must compile "
                "exactly once across every generation and checkpoint"
            )
    gap = _present(rec, "worst_case_return_gap_pct")
    if gap is not None:
        try:
            if not math.isfinite(float(gap)):
                problems.append(
                    f"worst_case_return_gap_pct not finite: {gap!r}"
                )
        except (TypeError, ValueError):
            problems.append(
                f"worst_case_return_gap_pct is not a number: {gap!r}"
            )
    return problems


def _chaos_problems(rec: dict) -> list[str]:
    """Structural validation of the chaos-plane fields (bench phase
    12): whenever present, invariant violations must be exactly 0 (a
    nonzero count is a broken recovery story, not a slow one), MTTR a
    finite positive number, and the disabled-plane overhead a finite
    number under the 5% bar (the plane is one attribute read when
    disabled — anything near the bar means injection leaked into a hot
    path). ``"skipped"`` sentinels are honored as structurally
    absent."""
    problems = []
    violations = _present(rec, "chaos_invariant_violations")
    if violations is not None:
        try:
            if int(violations) != 0:
                problems.append(
                    f"chaos_invariant_violations={violations!r} — a "
                    "campaign with ANY invariant violation is a broken "
                    "recovery path, not evidence"
                )
        except (TypeError, ValueError):
            problems.append(
                f"chaos_invariant_violations is not an int: {violations!r}"
            )
    mttr = _present(rec, "chaos_mttr_s")
    if mttr is not None:
        try:
            v = float(mttr)
            if not math.isfinite(v) or v <= 0.0:
                problems.append(
                    f"chaos_mttr_s={mttr!r} (need a finite number > 0: "
                    "zero means no disruptive fault was actually "
                    "recovered from)"
                )
        except (TypeError, ValueError):
            problems.append(f"chaos_mttr_s is not a number: {mttr!r}")
    overhead = _present(rec, "fault_plane_overhead_pct")
    if overhead is not None:
        try:
            v = float(overhead)
            if not math.isfinite(v):
                problems.append(
                    f"fault_plane_overhead_pct not finite: {overhead!r}"
                )
            elif v >= 5.0:
                problems.append(
                    f"fault_plane_overhead_pct={v} breaches the 5% bar "
                    "— the disabled plane must cost one attribute read"
                )
        except (TypeError, ValueError):
            problems.append(
                f"fault_plane_overhead_pct is not a number: {overhead!r}"
            )
    return problems


def _recovery_problems(rec: dict) -> list[str]:
    """Structural validation of the train-lane recovery fields (bench
    phase 15), whenever present: the in-program health word's overhead
    must be a finite number under the 5% bar (it is a handful of
    reductions + selects fused into a program that already runs a full
    PPO update), recovery MTTR a finite positive number (zero means no
    divergence was actually recovered from), and the drill's divergence
    count >= 1 (the bench INJECTS a bomb — a zero count is a broken
    detector, not a clean run). ``"skipped"`` sentinels are honored as
    structurally absent."""
    problems = []
    overhead = _present(rec, "health_overhead_pct")
    if overhead is not None:
        try:
            v = float(overhead)
            if not math.isfinite(v):
                problems.append(
                    f"health_overhead_pct not finite: {overhead!r}"
                )
            elif v >= 5.0:
                problems.append(
                    f"health_overhead_pct={v} breaches the 5% bar — "
                    "the health word must stay a few fused reductions "
                    "and selects, not a program of its own"
                )
        except (TypeError, ValueError):
            problems.append(
                f"health_overhead_pct is not a number: {overhead!r}"
            )
    mttr = _present(rec, "recovery_mttr_s")
    if mttr is not None:
        try:
            v = float(mttr)
            if not math.isfinite(v) or v <= 0.0:
                problems.append(
                    f"recovery_mttr_s={mttr!r} (need a finite number "
                    "> 0: zero means the drill's bomb was never "
                    "recovered from)"
                )
        except (TypeError, ValueError):
            problems.append(f"recovery_mttr_s is not a number: {mttr!r}")
    events = _present(rec, "train_divergence_events")
    if events is not None:
        try:
            if int(events) < 1:
                problems.append(
                    f"train_divergence_events={events!r} — the drill "
                    "injects a bomb, so a measured run must detect at "
                    "least one sustained breach"
                )
        except (TypeError, ValueError):
            problems.append(
                f"train_divergence_events is not an int: {events!r}"
            )
    return problems


LINT_WALL_CEILING_S = 120.0


def _lint_problems(rec: dict) -> list[str]:
    """Structural validation of the graftlint field (bench phase 16),
    whenever present: one cold-process ``--check`` pass over the
    package must be a finite positive wall under the ceiling. The
    engine's whole-repo analyses (lock-edge DFS, guarded-write reach)
    are package-global — this is the tripwire that keeps them from
    quietly going super-linear as the repo grows (measured wall is a
    few seconds; the ceiling leaves ~25x headroom for slow CI hosts).
    ``"skipped"`` sentinels are honored as structurally absent."""
    problems = []
    wall = _present(rec, "graftlint_wall_s")
    if wall is not None:
        try:
            v = float(wall)
            if not math.isfinite(v) or v <= 0.0:
                problems.append(
                    f"graftlint_wall_s={wall!r} (need a finite number "
                    "> 0)"
                )
            elif v > LINT_WALL_CEILING_S:
                problems.append(
                    f"graftlint_wall_s={v} breaches the "
                    f"{LINT_WALL_CEILING_S:.0f}s ceiling — a package-"
                    "global analysis in the call-graph engine has "
                    "gone super-linear"
                )
        except (TypeError, ValueError):
            problems.append(
                f"graftlint_wall_s is not a number: {wall!r}"
            )
    return problems


def _ledger_problems(rec: dict) -> list[str]:
    """Structural validation of the program-ledger fields (bench phase
    13), whenever present: the enabled-ledger overhead must be a finite
    number under the 5% bar (dispatch recording is a perf_counter pair
    plus a shard append), the census must carry at least one program (a
    zero count means registration silently broke at every compile
    site), and the total compile seconds must be a finite non-negative
    number. ``"skipped"`` sentinels are honored as structurally
    absent."""
    problems = []
    overhead = _present(rec, "ledger_overhead_pct")
    if overhead is not None:
        try:
            v = float(overhead)
            if not math.isfinite(v):
                problems.append(
                    f"ledger_overhead_pct not finite: {overhead!r}"
                )
            elif v >= 5.0:
                problems.append(
                    f"ledger_overhead_pct={v} breaches the 5% bar — "
                    "dispatch recording must stay a perf_counter pair "
                    "plus a per-thread shard append"
                )
        except (TypeError, ValueError):
            problems.append(
                f"ledger_overhead_pct is not a number: {overhead!r}"
            )
    count = _present(rec, "ledger_program_count")
    if count is not None:
        try:
            if int(count) <= 0:
                problems.append(
                    f"ledger_program_count={count!r} — a measured run "
                    "with zero registered programs means the compile-"
                    "seam registration is broken, not that nothing "
                    "compiled"
                )
        except (TypeError, ValueError):
            problems.append(
                f"ledger_program_count is not an int: {count!r}"
            )
    compile_s = _present(rec, "ledger_compile_seconds_total")
    if compile_s is not None:
        try:
            v = float(compile_s)
            if not math.isfinite(v) or v < 0.0:
                problems.append(
                    f"ledger_compile_seconds_total={compile_s!r} "
                    "(need a finite number >= 0)"
                )
        except (TypeError, ValueError):
            problems.append(
                "ledger_compile_seconds_total is not a number: "
                f"{compile_s!r}"
            )
    return problems


# -- census diff mode (the program-ledger acceptance gate) ---------------

# Structural cost/memory facts whose drift the census gate bounds.
# Build timings are deliberately excluded: compile wall is environment-
# dependent and the RegressionSentinel already watches it live.
CENSUS_DRIFT_FIELDS = (
    "flops",
    "bytes_accessed",
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
)


def _census_index(census: dict) -> dict:
    """Programs grouped by dispatch key (stable across replica-suffixed
    entry keys): dispatch_key -> {count, max-per-field}."""
    index: dict = {}
    for prog in census.get("programs") or []:
        key = prog.get("dispatch_key") or prog.get("key")
        if key is None:
            continue
        slot = index.setdefault(key, {"count": 0})
        slot["count"] += 1
        for field in CENSUS_DRIFT_FIELDS:
            try:
                v = float(prog.get(field))
            except (TypeError, ValueError):
                continue
            if field not in slot or v > slot[field]:
                slot[field] = v
    return index


def census_diff(
    committed: dict, live: dict, tolerance: float = 0.25
) -> list[str]:
    """Violations of the live census against the committed one: new or
    vanished programs, and per-field relative drift past ``tolerance``.
    Empty list == the run's compiled-program population still matches
    the committed cost story."""
    problems = []
    committed_idx = _census_index(committed)
    live_idx = _census_index(live)
    for key in sorted(set(committed_idx) - set(live_idx)):
        problems.append(
            f"program vanished from the live census: {key} (committed "
            "record has it — a compile site stopped registering or a "
            "subsystem stopped compiling)"
        )
    for key in sorted(set(live_idx) - set(committed_idx)):
        problems.append(
            f"new program not in the committed census: {key} (commit "
            "an updated census if the addition is intentional)"
        )
    for key in sorted(set(committed_idx) & set(live_idx)):
        ref, cur = committed_idx[key], live_idx[key]
        if ref["count"] != cur["count"]:
            problems.append(
                f"{key}: program count changed ({ref['count']} "
                f"committed -> {cur['count']} live) — a replica or "
                "compile site stopped (or started) registering under "
                "this dispatch key"
            )
        for field in CENSUS_DRIFT_FIELDS:
            a, b = ref.get(field), cur.get(field)
            if a is None or b is None or a <= 0.0:
                continue
            drift = abs(b - a) / a
            if drift > tolerance:
                problems.append(
                    f"{key}: {field} drifted {drift * 100.0:.0f}% "
                    f"({a:,.0f} committed -> {b:,.0f} live; tolerance "
                    f"{tolerance * 100.0:.0f}%)"
                )
    return problems


def _mesh_problems(rec: dict) -> list[str]:
    """Structural validation of the mesh-tier fields (bench phase 14),
    whenever present: throughput a finite positive number; global-swap
    latency percentiles finite, positive, and ordered (p50 <= p95);
    ``mesh_failover_lost_requests`` EXACTLY 0 (losing an accepted
    request across a host kill is a broken failover story, not a slow
    one); and every per-host compile receipt at most 1 (the budget-1
    invariant restated per host). ``"skipped"`` sentinels are honored
    as structurally absent."""
    problems = []
    rate = _present(rec, "mesh_req_per_sec")
    if rate is not None:
        try:
            v = float(rate)
            if not math.isfinite(v) or v <= 0.0:
                problems.append(
                    f"mesh_req_per_sec={rate!r} (need a finite number "
                    "> 0 — a zero-throughput mesh measured nothing)"
                )
        except (TypeError, ValueError):
            problems.append(f"mesh_req_per_sec is not a number: {rate!r}")
    p50 = _present(rec, "mesh_global_swap_latency_s_p50")
    p95 = _present(rec, "mesh_global_swap_latency_s_p95")
    for name, value in (
        ("mesh_global_swap_latency_s_p50", p50),
        ("mesh_global_swap_latency_s_p95", p95),
    ):
        if value is None:
            continue
        try:
            v = float(value)
            if not math.isfinite(v) or v <= 0.0:
                problems.append(
                    f"{name}={value!r} (need a finite number > 0: a "
                    "global swap crosses at least one RPC round trip)"
                )
        except (TypeError, ValueError):
            problems.append(f"{name} is not a number: {value!r}")
    if p50 is not None and p95 is not None:
        try:
            if float(p50) > float(p95):
                problems.append(
                    f"mesh swap p50 {p50!r} > p95 {p95!r} — percentile "
                    "order violated"
                )
        except (TypeError, ValueError):
            pass  # already reported above
    lost = _present(rec, "mesh_failover_lost_requests")
    if lost is not None:
        try:
            if int(lost) != 0:
                problems.append(
                    f"mesh_failover_lost_requests={lost!r} — an "
                    "accepted request lost across a host kill is a "
                    "broken no-request-lost invariant, not a slow one"
                )
        except (TypeError, ValueError):
            problems.append(
                f"mesh_failover_lost_requests is not an int: {lost!r}"
            )
    step_violations = _present(rec, "mesh_step_violations")
    if step_violations is not None:
        try:
            if int(step_violations) != 0:
                problems.append(
                    f"mesh_step_violations={step_violations!r} — "
                    "model_step went backward in response completion "
                    "order across hosts; the global barrier is broken"
                )
        except (TypeError, ValueError):
            problems.append(
                f"mesh_step_violations is not an int: {step_violations!r}"
            )
    receipts = _present(rec, "mesh_host_compile_receipts_max")
    if receipts is not None:
        try:
            if float(receipts) > 1.0:
                problems.append(
                    f"mesh_host_compile_receipts_max={receipts!r} "
                    "breaches the per-host budget-1 receipt"
                )
        except (TypeError, ValueError):
            problems.append(
                "mesh_host_compile_receipts_max is not a number: "
                f"{receipts!r}"
            )
    return problems


def _sebulba_problems(rec: dict) -> list[str]:
    """Structural validation of the sebulba-lane fields (bench phase
    17), whenever present: both throughput headlines finite positive
    numbers; queue occupancy p95 a number in [0, depth] (> 0 would be
    vacuous, but negative or non-numeric is malformed); staleness p95 a
    finite non-negative number; BOTH per-slice compile receipts exactly
    1 (the actor rollout and the learner chunk are one program each,
    whatever the transfer weather did); and the gate's under-load eval
    p50 a finite positive number whenever recorded beside them.
    ``"skipped"`` sentinels are honored as structurally absent."""
    problems = []
    for key in (
        "sebulba_env_steps_per_sec",
        "sebulba_learner_steps_per_sec",
    ):
        v = _present(rec, key)
        if v is None:
            continue
        try:
            f = float(v)
            if not math.isfinite(f) or f <= 0.0:
                problems.append(
                    f"{key}={v!r} (need a finite number > 0 — a zero "
                    "rate means that slice never ran)"
                )
        except (TypeError, ValueError):
            problems.append(f"{key} is not a number: {v!r}")
    occupancy = _present(rec, "transfer_queue_occupancy_p95")
    if occupancy is not None:
        try:
            f = float(occupancy)
            if not math.isfinite(f) or f < 0.0:
                problems.append(
                    f"transfer_queue_occupancy_p95={occupancy!r} "
                    "(need a finite number >= 0)"
                )
        except (TypeError, ValueError):
            problems.append(
                "transfer_queue_occupancy_p95 is not a number: "
                f"{occupancy!r}"
            )
    staleness = _present(rec, "param_staleness_p95_updates")
    if staleness is not None:
        try:
            f = float(staleness)
            if not math.isfinite(f) or f < 0.0:
                problems.append(
                    f"param_staleness_p95_updates={staleness!r} "
                    "(need a finite number >= 0)"
                )
        except (TypeError, ValueError):
            problems.append(
                "param_staleness_p95_updates is not a number: "
                f"{staleness!r}"
            )
    for key in ("sebulba_actor_compiles", "sebulba_learner_compiles"):
        receipts = _present(rec, key)
        if receipts is None:
            continue
        if receipts != 1:
            problems.append(
                f"{key}={receipts!r} — each slice's program must "
                "compile exactly once across the whole pipelined run "
                "(the per-slice budget-1 receipt)"
            )
    gate_p50 = _present(rec, "gate_eval_p50_under_load_s")
    if gate_p50 is not None:
        try:
            f = float(gate_p50)
            if not math.isfinite(f) or f <= 0.0:
                problems.append(
                    f"gate_eval_p50_under_load_s={gate_p50!r} (need a "
                    "finite number > 0: the gate evaluates a real "
                    "candidate while the learner is saturated)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"gate_eval_p50_under_load_s is not a number: {gate_p50!r}"
            )
        gate_compiles = _present(rec, "sebulba_gate_compiles")
        if gate_compiles is not None and gate_compiles != 1:
            problems.append(
                f"sebulba_gate_compiles={gate_compiles!r} — the gate's "
                "matrix program on its own slice must compile exactly "
                "once across the warm eval and every under-load eval"
            )
    return problems


def _envs_problems(rec: dict) -> list[str]:
    """Structural validation of the registered-env ladder fields (bench
    phase 1d), whenever present: every per-env rate a finite positive
    number (a zero rate means that env never stepped); the per-env pair
    recorded together (the phase times every registered env, so one rate
    without the other means the loop died mid-ladder); and
    obstacle_overhead_pct a finite number in [0, 100] (the occlusion
    layer can only cost, never accelerate, and cannot eat more than the
    whole rate). ``"skipped"`` sentinels honored as structurally
    absent."""
    problems = []
    env_keys = (
        "env_steps_per_sec_formation",
        "env_steps_per_sec_pursuit_evasion",
    )
    present = {}
    for key in env_keys:
        v = _present(rec, key)
        if v is None:
            continue
        present[key] = v
        try:
            f = float(v)
            if not math.isfinite(f) or f <= 0.0:
                problems.append(
                    f"{key}={v!r} (need a finite number > 0 — a zero "
                    "rate means that env never stepped)"
                )
        except (TypeError, ValueError):
            problems.append(f"{key} is not a number: {v!r}")
    if len(present) == 1:
        problems.append(
            "registered-env ladder incomplete: got only "
            f"{sorted(present)} — the phase times every registered env, "
            "so a lone rate means the ladder died mid-loop"
        )
    overhead = _present(rec, "obstacle_overhead_pct")
    if overhead is not None:
        try:
            f = float(overhead)
            if not math.isfinite(f) or not 0.0 <= f <= 100.0:
                problems.append(
                    f"obstacle_overhead_pct={overhead!r} (need a finite "
                    "number in [0, 100]: the occlusion layer can only "
                    "cost, never accelerate)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"obstacle_overhead_pct is not a number: {overhead!r}"
            )
    return problems


def _tenancy_problems(rec: dict) -> list[str]:
    """Structural validation of the multi-tenant serving fields
    (serving/tenancy, bench tenant smoke), whenever present:

    - ``tenant_isolation_p95_ratio`` a finite number >= 1 wherever a
      quiet lane's storm-phase p95 is floored at its own baseline (a
      sub-1 or non-finite ratio means the two phases were not actually
      measured), recorded beside at least one per-tenant rate;
    - every ``model_{id}__requests_per_sec`` a finite number > 0 — a
      lane with zero throughput during the storm never actually served;
    - ``shared_rung_compiles`` a non-empty ``{"{arch}:rung{B}": n}``
      dict with every count EXACTLY 1: same-arch lanes must share one
      compile per (arch, rung) and each distinct arch must pay exactly
      its own budget-1 compile — 0 means the rung was never warmed,
      2+ means a lane retraced;
    - per-lane ``model_{id}__step_monotonic_violations`` exactly 0.

    ``"skipped"`` sentinels are honored as structurally absent."""
    problems = []
    ratio = _present(rec, "tenant_isolation_p95_ratio")
    if ratio is not None:
        try:
            v = float(ratio)
            if not math.isfinite(v) or v < 1.0:
                problems.append(
                    f"tenant_isolation_p95_ratio={ratio!r} (need a "
                    "finite number >= 1: the quiet lane's storm-phase "
                    "p95 is floored at its own baseline)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"tenant_isolation_p95_ratio is not a number: {ratio!r}"
            )
        rate_keys = [
            k for k in rec
            if k.startswith("model_") and k.endswith("__requests_per_sec")
        ]
        if not rate_keys:
            problems.append(
                "tenant_isolation_p95_ratio recorded without any "
                "model_{id}__requests_per_sec lane rates beside it"
            )
    for key in sorted(rec):
        if not key.startswith("model_"):
            continue
        v = _present(rec, key)
        if v is None:
            continue
        if key.endswith("__requests_per_sec"):
            try:
                f = float(v)
                if not math.isfinite(f) or f <= 0.0:
                    problems.append(
                        f"{key}={v!r} (need a finite number > 0 — a "
                        "zero-rate lane never actually served)"
                    )
            except (TypeError, ValueError):
                problems.append(f"{key} is not a number: {v!r}")
        elif key.endswith("__step_monotonic_violations"):
            try:
                if int(float(v)) != 0:
                    problems.append(
                        f"{key}={v!r} — a lane's model_step went "
                        "backward in response completion order; "
                        "per-model monotonicity is broken"
                    )
            except (TypeError, ValueError):
                problems.append(f"{key} is not an int: {v!r}")
    shared = _present(rec, "shared_rung_compiles")
    if shared is not None:
        if not isinstance(shared, dict) or not shared:
            problems.append(
                "shared_rung_compiles must be a non-empty dict of "
                f"'{{arch}}:rung{{B}}' -> compile count: {shared!r}"
            )
        else:
            for rung_key in sorted(shared):
                count = shared[rung_key]
                try:
                    bad = int(count) != 1
                except (TypeError, ValueError):
                    bad = True
                if bad:
                    problems.append(
                        f"shared_rung_compiles[{rung_key!r}]={count!r} "
                        "— every (arch, rung) must compile exactly "
                        "once (0 = never warmed, 2+ = a lane retraced "
                        "instead of sharing the executable)"
                    )
    return problems


def _elastic_problems(rec: dict) -> list[str]:
    """Structural validation of the elastic-capacity fields
    (serving/elastic, bench phase "elastic"), whenever present:

    - ``serving_req_per_sec_at_p95_slo_elastic`` and ``..._static``
      both finite numbers > 0 — the comparison is only evidence when
      BOTH fleets actually sustained a rate at the p95 target on the
      storm half;
    - ``elastic_resplit_pause_ms`` a finite number in (0, 250]: the
      barrier-commit pause is the WHOLE serving interruption a
      re-split costs, and an unbounded (or zero — unmeasured) pause
      means prewarm work leaked inside the gates;
    - ``elastic_prewarm_compiles`` an int >= 1 (a re-split that
      compiled nothing never built new rungs) recorded beside
      ``elastic_storm_new_programs`` == 0 — the ledger census diff
      proving every post-warm compile is attributed to prewarm, never
      the measured request path;
    - ``elastic_max_compiles_per_rung`` <= 1 (budget-1 receipts per
      (arch, rung) after warm-up) and ``elastic_resplits_committed``
      an int >= 1 wherever a pause was recorded.

    ``"skipped"`` sentinels are honored as structurally absent."""
    problems = []
    for key in (
        "serving_req_per_sec_at_p95_slo_elastic",
        "serving_req_per_sec_at_p95_slo_static",
    ):
        v = _present(rec, key)
        if v is None:
            continue
        try:
            f = float(v)
            if not math.isfinite(f) or f <= 0.0:
                problems.append(
                    f"{key}={v!r} (need a finite number > 0 — a fleet "
                    "that sustained no rate at the p95 target was "
                    "never actually measured on the storm half)"
                )
        except (TypeError, ValueError):
            problems.append(f"{key} is not a number: {v!r}")
    pause = _present(rec, "elastic_resplit_pause_ms")
    if pause is not None:
        try:
            f = float(pause)
            if not math.isfinite(f) or f <= 0.0 or f > 250.0:
                problems.append(
                    f"elastic_resplit_pause_ms={pause!r} (need a "
                    "finite number in (0, 250]: the barrier-commit "
                    "pause is the whole serving interruption — zero "
                    "means unmeasured, above 250ms means prewarm or "
                    "drain work leaked inside the closed gates)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"elastic_resplit_pause_ms is not a number: {pause!r}"
            )
        committed = _present(rec, "elastic_resplits_committed")
        try:
            if committed is None or int(float(committed)) < 1:
                problems.append(
                    "elastic_resplit_pause_ms recorded without "
                    "elastic_resplits_committed >= 1 beside it (a "
                    "pause nothing committed measured nothing)"
                )
        except (TypeError, ValueError):
            problems.append(
                "elastic_resplits_committed is not an int: "
                f"{committed!r}"
            )
    compiles = _present(rec, "elastic_prewarm_compiles")
    if compiles is not None:
        try:
            if int(float(compiles)) < 1:
                problems.append(
                    f"elastic_prewarm_compiles={compiles!r} (a "
                    "re-split that compiled nothing never built new "
                    "rungs — the prewarm receipt is missing)"
                )
        except (TypeError, ValueError):
            problems.append(
                f"elastic_prewarm_compiles is not an int: {compiles!r}"
            )
        storm_new = _present(rec, "elastic_storm_new_programs")
        try:
            if storm_new is None or int(float(storm_new)) != 0:
                problems.append(
                    f"elastic_storm_new_programs={storm_new!r} (need "
                    "exactly 0 beside elastic_prewarm_compiles: the "
                    "census diff must prove no program registered "
                    "during the measured storm — every compile "
                    "attributed to prewarm, never the request path)"
                )
        except (TypeError, ValueError):
            problems.append(
                "elastic_storm_new_programs is not an int: "
                f"{storm_new!r}"
            )
    max_compiles = _present(rec, "elastic_max_compiles_per_rung")
    if max_compiles is not None:
        try:
            if int(float(max_compiles)) > 1:
                problems.append(
                    f"elastic_max_compiles_per_rung={max_compiles!r} "
                    "— a rung retraced after warm-up; budget-1 "
                    "receipts are broken"
                )
        except (TypeError, ValueError):
            problems.append(
                "elastic_max_compiles_per_rung is not an int: "
                f"{max_compiles!r}"
            )
    return problems


def check(rec: dict, require: list[str], expect: list[str]) -> list[str]:
    """Return the list of violations (empty = evidence-grade record)."""
    problems = []
    if rec.get("platform") != "tpu":
        problems.append(
            f"platform is {rec.get('platform')!r} — not hardware evidence"
        )
    if "error" in rec:
        problems.append(f"error field present: {rec['error']!r}")
    notes = str(rec.get("notes", ""))
    if "skipped" in notes or "failed" in notes:
        problems.append(f"degraded phases in notes: {notes!r}")
    problems.extend(_pipeline_problems(rec))
    problems.extend(_obs_problems(rec))
    problems.extend(_telemetry_problems(rec))
    problems.extend(_serving_slo_problems(rec))
    problems.extend(_adversarial_problems(rec))
    problems.extend(_chaos_problems(rec))
    problems.extend(_recovery_problems(rec))
    problems.extend(_ledger_problems(rec))
    problems.extend(_mesh_problems(rec))
    problems.extend(_lint_problems(rec))
    problems.extend(_sebulba_problems(rec))
    problems.extend(_envs_problems(rec))
    problems.extend(_tenancy_problems(rec))
    problems.extend(_elastic_problems(rec))
    for field in require:
        if rec.get(field) == SKIPPED:
            problems.append(
                f"required field explicitly skipped (phase disabled "
                f"via BENCH_SKIP_*): {field}"
            )
            continue
        try:
            ok = float(rec.get(field, 0.0)) > 0.0
        except (TypeError, ValueError):
            ok = False
        if not ok:
            problems.append(f"required field missing/zero: {field}")
    for pair in expect:
        key, _, want = pair.partition("=")
        got = rec.get(key)
        if str(got) != want:
            problems.append(f"{key}={got!r}, expected {want!r}")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("file", type=Path)
    ap.add_argument("--require", nargs="*", default=[], metavar="FIELD")
    ap.add_argument("--expect", nargs="*", default=[], metavar="KEY=VALUE")
    ap.add_argument(
        "--census", type=Path, default=None, metavar="LIVE_CENSUS",
        help="census mode: diff the committed census (the positional "
        "file) against this live program_ledger.json",
    )
    ap.add_argument("--census-tolerance", type=float, default=0.25)
    args = ap.parse_args()
    if args.census is not None:
        repo = str(Path(__file__).resolve().parents[1])
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from marl_distributedformation_tpu.obs.ledger import load_census

        try:
            committed = load_census(args.file)
            live = load_census(args.census)
        except (OSError, ValueError) as e:
            print(f"[check_bench_record] REJECT: {e}", file=sys.stderr)
            sys.exit(1)
        problems = census_diff(
            committed, live, tolerance=args.census_tolerance
        )
    else:
        problems = check(load_record(args.file), args.require, args.expect)
    for p in problems:
        print(f"[check_bench_record] REJECT: {p}", file=sys.stderr)
    if problems:
        sys.exit(1)
    print(f"[check_bench_record] OK: {args.file}")


if __name__ == "__main__":
    main()
