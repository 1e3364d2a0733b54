"""The table of chip peaks, keyed by ``device_kind``. A kind that is not
in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path


def load_peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add it to "
            f"benchmarks/peaks.json with its source (known: {sorted(table)})"
        )
    return table[device_kind]
