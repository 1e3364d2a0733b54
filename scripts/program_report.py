#!/usr/bin/env python
"""Render a program-ledger census (obs/ledger.py) as a cost report.

    python scripts/program_report.py logs/run/program_ledger.json
    python scripts/program_report.py logs/run/program_ledger.json --json
    python scripts/program_report.py --log-dir logs/run --top 5

The census is the per-executable record every compile site registers
into the ProgramLedger (cost_analysis flops/bytes, memory footprint,
build timings, dispatch-latency summaries); entry points dump it to
``logs/{name}/program_ledger.json``. This report answers the operator
questions directly: which programs dominate flops, bytes, compile wall,
and dispatch tail latency — text tables by default, one JSON object
with ``--json`` (stable keys: ``totals``, ``top``, ``programs``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from marl_distributedformation_tpu.obs.ledger import (  # noqa: E402
    load_census,
)

# (column header, census field, unit divisor, unit suffix)
RANKINGS = (
    ("flops", "flops", 1e6, "Mflop"),
    ("bytes", "bytes_accessed", 1e6, "MB"),
    ("compile", "compile_seconds", 1.0, "s"),
    ("dispatch_p95", "dispatch_seconds_p95", 1e-3, "ms"),
)


def _num(value) -> float:
    try:
        v = float(value)
    except (TypeError, ValueError):
        return float("-inf")
    return v


def rank(programs: list, field: str, top: int) -> list:
    """Programs carrying ``field``, largest first (absent fields sort
    out, never crash — CPU records legitimately lack memory facts)."""
    present = [p for p in programs if _num(p.get(field)) > float("-inf")]
    present.sort(key=lambda p: _num(p.get(field)), reverse=True)
    return present[:top]


def summarize(census: dict, top: int) -> dict:
    programs = list(census.get("programs") or [])
    out = {
        "schema": census.get("schema"),
        "totals": dict(census.get("totals") or {}),
        "program_count": len(programs),
        "top": {
            name: [
                {"key": p.get("key"), name: p.get(field)}
                for p in rank(programs, field, top)
            ]
            for name, field, _, _ in RANKINGS
        },
        "programs": programs,
    }
    return out


def render_text(census: dict, top: int) -> str:
    programs = list(census.get("programs") or [])
    totals = census.get("totals") or {}
    lines = [
        f"program ledger census — {len(programs)} programs, "
        f"{totals.get('traces', '?')} compiles, "
        f"{_fmt(totals.get('compile_seconds'), 1.0, 's')} total compile",
    ]
    wm = totals.get("watermark_bytes")
    if wm is not None:
        lines.append(
            f"device-memory watermark: {_fmt(wm, 1e6, 'MB')}"
        )
    for name, field, div, unit in RANKINGS:
        ranked = rank(programs, field, top)
        if not ranked:
            lines.append(f"\ntop by {name}: (no {field} recorded)")
            continue
        lines.append(f"\ntop by {name}:")
        width = max(len(str(p.get("key"))) for p in ranked)
        for p in ranked:
            src = p.get("analysis_source", "?")
            lines.append(
                f"  {str(p.get('key')).ljust(width)}  "
                f"{_fmt(p.get(field), div, unit).rjust(12)}  "
                f"[{p.get('subsystem', '?')}, {src}]"
            )
    unavailable = [
        p["key"]
        for p in programs
        if p.get("analysis_source") == "unavailable"
    ]
    if unavailable:
        lines.append(
            "\ncost/memory analysis unavailable for: "
            + ", ".join(str(k) for k in unavailable)
        )
    return "\n".join(lines)


def _fmt(value, div: float, unit: str) -> str:
    try:
        return f"{float(value) / div:,.2f} {unit}"
    except (TypeError, ValueError):
        return "n/a"


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "census", nargs="?", type=Path,
        help="path to a program_ledger.json census",
    )
    ap.add_argument(
        "--log-dir", type=Path, default=None,
        help="read {log-dir}/program_ledger.json instead",
    )
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument(
        "--json", action="store_true",
        help="emit the structured summary as one JSON object",
    )
    args = ap.parse_args()
    if args.census is None and args.log_dir is None:
        ap.error("give a census path or --log-dir")
    path = args.census or (args.log_dir / "program_ledger.json")
    try:
        census = load_census(path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"[program_report] cannot read {path}: {e}", file=sys.stderr)
        sys.exit(1)
    if args.json:
        print(json.dumps(summarize(census, args.top)))
    else:
        print(render_text(census, args.top))


if __name__ == "__main__":
    main()
