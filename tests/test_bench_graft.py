"""Smoke tests for the driver entry points (bench.py, __graft_entry__.py)."""

import pytest
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench as bench_mod
import __graft_entry__ as graft


def test_bench_runner_compiles_and_steps():
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.env.formation import reset_batch

    params = EnvParams(num_agents=bench_mod.N)
    state = reset_batch(jax.random.PRNGKey(0), params, 8)
    run_chunk = bench_mod.make_runner(params, m=8, chunk=4)
    state2, key, r = run_chunk(state, jax.random.PRNGKey(1))
    assert np.isfinite(float(r))
    assert not np.allclose(
        np.asarray(state2.agents), np.asarray(state.agents)
    )


@pytest.mark.slow
def test_bench_emits_parseable_json_on_cpu(monkeypatch, capsys):
    """The one-JSON-line contract, with the CPU asked for by name
    (BENCH_FORCE_CPU=1) and tiny shapes."""
    import json

    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench_mod, "M", 8)
    monkeypatch.setattr(bench_mod, "CHUNK", 4)
    monkeypatch.setattr(bench_mod, "MIN_TIMED_S", 0.05)
    monkeypatch.setenv("BENCH_TRAIN_M", "4")
    monkeypatch.setenv("BENCH_KNN_M", "4")
    monkeypatch.setenv("BENCH_KNN_BIG_M", "2")
    monkeypatch.setenv("BENCH_KNN_BIG_N", "300")
    monkeypatch.setenv("BENCH_FUSED_CHUNKS", "1,2")  # tiny ladder for CI
    bench_mod.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert {"metric", "value", "unit", "vs_baseline"} <= rec.keys()
    assert rec["value"] > 0
    assert rec["train_env_steps_per_sec"] > 0
    assert rec["knn_env_steps_per_sec"] > 0
    assert rec["knn_big_env_steps_per_sec"] > 0  # phase 4 emits too
    # Scenario-engine phase (scenarios/): the 3-layer storm stack rate
    # rides the same JSON so the perf trajectory captures the wrapper
    # overhead.
    assert rec["scenario_env_steps_per_sec"] > 0
    assert rec["scenario_stack"] == "storm@1.0"
    # Anakin fused-scan phase: best-of-ladder rate, per-chunk rates, and
    # the compile-once RetraceGuard receipt (every fused program must
    # have compiled exactly once).
    assert rec["train_env_steps_per_sec_fused_scan"] > 0
    assert rec["train_fused_scan_chunk"] >= 1
    assert set(rec["train_fused_scan_compiles"].values()) == {1}
    assert rec["dispatch_overhead_pct"] >= 0.0
    assert "error" not in rec and "notes" not in rec
    assert "phases_failed" not in rec
    # The record names the device it ran on, as jax reports it.
    assert rec["platform"] == "cpu"
    assert rec["device_kind"] and rec["device_count"] >= 1


def test_bench_without_a_tpu_exits_nonzero_and_measures_nothing(
    monkeypatch, capsys
):
    """No probe, no fallback: on a machine without a TPU (and without
    BENCH_FORCE_CPU=1 asking for the CPU by name) the bench names the
    device it found, prints no record, and exits non-zero."""
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench_mod.main()
    assert exc.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out.strip() == ""  # no JSON record
    assert "platform=cpu" in captured.err
    assert "no TPU" in captured.err


def test_bench_failed_phase_makes_exit_code_nonzero(monkeypatch, capsys):
    """A phase that raises is named in ``phases_failed`` and fails the
    run AFTER the record prints — never a caught exception and exit 0."""
    import json

    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench_mod, "M", 8)
    monkeypatch.setattr(bench_mod, "CHUNK", 4)
    monkeypatch.setattr(bench_mod, "MIN_TIMED_S", 0.05)
    for phase in (
        "TRAIN", "KNN", "KNN_BIG", "ENVS", "SERVING", "PIPELINE",
        "ADVERSARIAL", "CHAOS", "MESH", "LINT", "SEBULBA", "SWEEP",
    ):
        monkeypatch.setenv(f"BENCH_SKIP_{phase}", "1")

    def boom(*args, **kwargs):
        raise RuntimeError("scenario runner exploded")

    monkeypatch.setattr(bench_mod, "make_scenario_runner", boom)
    with pytest.raises(SystemExit) as exc:
        bench_mod.main()
    assert exc.value.code == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phases_failed"] == ["scenario"]
    assert "scenario phase failed" in rec["notes"]
    assert rec["value"] > 0  # the phases before it still measured


def test_graft_entry_compiles():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    mean, log_std, value = out
    assert mean.shape == (4096 * 5, 2)
    assert value.shape == (4096 * 5,)
    assert np.isfinite(np.asarray(mean)).all()


@pytest.mark.slow
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_odd():
    graft.dryrun_multichip(1)
