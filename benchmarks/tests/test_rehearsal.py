"""Every cell's run, end to end, at a tiny size on the CPU: the shape of
the result, that no device metric is ever printed from a CPU run, that a
cell, a configuration's cell and a metric are added by files alone, and
that the command itself refuses to run without a chip."""

import json
import subprocess
import sys
import time

import jax
import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT, TINY_JOBS

CELLS = [f"{name.split('-')[0]}-tiny-{name}" for name in TINY_JOBS]


@pytest.mark.parametrize("cell_name", CELLS)
def test_tiny_cell_runs_and_reports_no_metric_on_the_cpu(tiny_root, cell_name):
    cell = harness.load_cell(cell_name, tiny_root)
    if cell.chips > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    lines = []
    result = harness.run_cell(
        cell, seed=2**31 + 7, seconds=0.5, trace=False,
        started=time.perf_counter(), require_chip=False, log=lines.append,
    )
    assert list(result) == [
        "correct", "attempted", "failed", "metrics", "device", "compared",
    ]
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # a CPU run never names a device metric
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["compiles_in_window"] == [0, 0]
    assert any("compiles in the window: 0" in line for line in lines)
    assert [line for line in lines if line.startswith("[correct]")]
    json.dumps(result)


def test_a_metric_is_added_by_a_file_and_an_entry(tiny_root):
    cell = harness.load_cell(CELLS[0], tiny_root)
    assert "throwaway_count" in [m["name"] for m in cell.per_layer]
    context = {"iterations": 6, "memory_peak_bytes": 3 * 2**30,
               "trace": {"module_runs": 1, "scope_s": {}, "window_s": 1.0,
                         "busy_s": 0.5, "collective_exposed_s": 0.0}}
    metrics = harness.read_per_layer(
        cell, {**context, "cell": cell, "elapsed_s": 1.0,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    )
    assert metrics["throwaway_count"] == {"value": 6.0, "unit": "1"}
    assert metrics["hbm_peak_gib"]["value"] == 3.0
    assert metrics["device_idle_pct"]["value"] == 50.0
    # readers that find nothing to read return nothing: no 0 for a share
    for absent in ("rollout_ms", "ppo_update_ms", "dispatch_gap_pct",
                   "knn_roofline", "collective_exposed_pct"):
        assert absent not in metrics


def test_every_committed_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for metric in bench["per_layer"]:
        assert callable(harness.load_reader(ROOT / "benchmarks", metric["name"]))
    for workload in bench["workloads"]:
        cell = harness.load_cell(workload["name"], ROOT)
        assert set(cell.limits["limits"]) <= {
            "loss_gap_first", "loss_gap", "grad_norm_gap_first", "grad_norm_gap",
            "reward_gap_first", "reward_gap", "adam_mu_gap", "param_change_gap",
        }


def test_the_command_refuses_the_cpu_and_prints_no_result():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    done = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(ROOT)},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_seed_keys_differ_past_32_bits():
    a, b = harness.seed_key(5), harness.seed_key(2**32 + 5)
    assert a.tolist() == [0, 5] and b.tolist() == [1, 5]
