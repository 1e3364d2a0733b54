"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip(s). The last line of standard output is
the result's JSON object; everything else goes to standard error.
"""

from __future__ import annotations

import time

_STARTED = time.perf_counter()  # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload, ROOT)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), started=_STARTED
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
