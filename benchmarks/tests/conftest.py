"""Tests of the benchmark's own yardstick. They run on the CPU at tiny
sizes (``python -m pytest benchmarks/tests -q``) and never stand for a
measurement."""

import json
import os
import shutil
import sys
from pathlib import Path

# four virtual devices for the dp=4 cell's rehearsal; read when jax starts
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_JOBS = {
    "mlp5": {"kind": "anakin_train", "num_formation": 64, "batch_size": 256,
             "fused_chunk": 2, "mesh": None},
    "gnn100": {"kind": "anakin_train", "num_formation": 8, "batch_size": 600,
               "fused_chunk": 2, "mesh": None},
    "mlp5-dp4": {"kind": "anakin_train", "num_formation": 64, "batch_size": 256,
                 "fused_chunk": 2, "mesh": {"dp": 4}},
}


@pytest.fixture()
def tiny_root(tmp_path):
    """A throw-away benchmark made of data files alone: the committed
    configurations and readers, and tiny cells and a metric of its own."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    src = ROOT / bench["paths"][0]
    dst = tmp_path / bench["paths"][0]
    for sub in ("configs", "metrics"):
        shutil.copytree(src / sub, dst / sub)
    (dst / "workloads").mkdir()
    (dst / "limits").mkdir()
    committed, bench["workloads"] = bench["workloads"], []
    for name, job in TINY_JOBS.items():
        config = name.split("-")[0]
        cell = f"{config}-tiny-{name}"
        (dst / "workloads" / f"tiny-{name}.json").write_text(json.dumps(job))
        real = next(w["name"] for w in committed if w["config"] == config)
        limits = json.loads((src / "limits" / f"{real}.json").read_text())
        (dst / "limits" / f"{cell}.json").write_text(json.dumps(limits))
        bench["workloads"].append(
            {"name": cell, "config": config, "traffic": f"tiny-{name}",
             "chips": 4 if job["mesh"] else 1, "why": "rehearsal"}
        )
    (dst / "metrics" / "throwaway_count.py").write_text(
        "def read(context):\n    return float(context['iterations'])\n"
    )
    bench["per_layer"].append(
        {"name": "throwaway_count", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "host loop",
         "moves": "agent_steps_per_s"}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
