"""Readings a cell's limits are set from, taken on the chip at the cell's
own size. For each seed the reference runs as it is, then again as the
control (computed in bfloat16, the precision below the float32 the
configurations state) and with the faults planted (half of a minibatch
left out; for a cell on a mesh, chips that exchange nothing), each put in
the program's place and compared with the plain reference. One chip is
enough: the reference runs on one.

    python benchmarks/calibrate.py --workload <cell> --seeds 11 12 13

The program's own readings (the lower ends) come from ``run.py``'s runs,
which print every number compared.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--skip", nargs="*", default=[])
    args = parser.parse_args(argv)

    from benchmarks import harness

    harness.setup_cache()
    cell = harness.load_cell(args.workload, ROOT)
    harness.device_gate(1)
    followed = cell.limits["follow_chunks"] * cell.job["fused_chunk"]
    for seed in args.seeds:
        t = time.perf_counter()
        ref = harness.follow_reference(cell, seed, followed)
        row = {"seed": seed, "reference_s": time.perf_counter() - t,
               "reference_metrics": {k: [float(x) for x in v] for k, v in ref["metrics"].items()}}
        if "control" not in args.skip:
            control = harness.follow_reference(cell, seed, followed, dtype="bfloat16")
            row["control_bfloat16"] = harness.compare(control, ref)
        if "half_batch" not in args.skip:
            half = harness.follow_reference(cell, seed, followed, half_batch=True)
            row["fault_half_batch"] = harness.compare(half, ref)
        if cell.chips > 1 and "own_shard" not in args.skip:
            alone = harness.follow_reference(cell, seed, followed, own_shard=cell.chips)
            row["fault_no_exchange"] = harness.compare(alone, ref)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
