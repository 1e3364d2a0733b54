"""chip_smoke.py, tested as a script: the gate refuses a CPU it was not
asked for, the script alone fails, and — with the CPU asked for by name —
every leg passes at tiny sizes with interpret-mode kernels."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    env = {
        "PATH": os.environ["PATH"],
        # What this sandbox (and the driver's) has in its environment: it
        # must NOT be enough to get past the gate.
        "JAX_PLATFORMS": "cpu",
    }
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = os.environ[
            "JAX_COMPILATION_CACHE_DIR"
        ]
    return subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=timeout,
    )


def test_gate_refuses_a_cpu_it_was_not_asked_for(tmp_path):
    res = _run(["--out", str(tmp_path / "out")])
    assert res.returncode == 2, res.stdout + res.stderr
    assert "platform=cpu" in res.stdout  # leg 0 says what it found
    assert "versions: {'jax': '0.9.0'" in res.stdout
    assert "not a TPU. Nothing was run." in res.stderr
    assert '"ok"' not in res.stdout  # and prints no result
    assert not (tmp_path / "out").exists()


def test_the_script_alone_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    res = _run([], cwd=tmp_path, script=alone)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no checkout" in res.stderr


def test_every_leg_passes_at_tiny_sizes_on_a_cpu_asked_for_by_name(tmp_path):
    out = tmp_path / "out"
    res = _run(["--cpu-tiny", "--out", str(out)])
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    summary = json.loads((out / "chip_smoke.json").read_text())
    assert summary["mode"] == "cpu-tiny" and summary["ok"] is True
    legs = summary["legs"]
    assert {name: leg["status"] for name, leg in legs.items()} == {
        name: "passed" for name in (
            "1_train", "2_serve", "3_kernels", "4a_train_dp4", "4b_fleet",
            "4c_fleet_sharded", "4d_sebulba", "4e_sharded_vs_replicated",
        )
    }
    # Leg 1: both dispatch modes, one compile each, a restorable checkpoint.
    for mode in ("host_loop", "fused_scan"):
        assert legs["1_train"][mode]["train_compiles"] == 1.0
        assert legs["1_train"][mode]["restored"] == "MLPActorCritic"
    # Leg 2 served leg 1's step, through every rung and one row past the top.
    assert legs["2_serve"]["model_step"] == legs["1_train"]["fused_scan"]["step"]
    assert legs["2_serve"]["rung_sweep_sizes"] == "1,8,64,512,513"
    assert set(legs["2_serve"]["compiles_per_rung"].values()) == {1.0}
    # Leg 3 ran the interpret spelling it asked for by name — and says so.
    assert legs["3_kernels"]["gnn100"]["ran_impl"] == "pallas_interpret"
    assert legs["3_kernels"]["gnn1024"]["ran_impl"] == "pallas_big_interpret"
    assert legs["3_kernels"]["gnn100"]["mosaic_call_in_train_program"] is False
    # Leg 4: the work is where it should be.
    assert all(v > 0 for v in legs["4a_train_dp4"]["bytes_added"].values())
    assert all(v > 0 for v in legs["4b_fleet"]["bytes_added"].values())
    seb = legs["4d_sebulba"]["bytes_added"]
    assert seb["0"] > 0 and seb["1"] > 0 and seb["2"] == 0 == seb["3"]
    # Run directories live under --out, not <repo>/logs.
    assert (out / "train_fused" / "config.json").exists()


def _main_with_legs(monkeypatch, tmp_path, capsys, legs):
    sys.path.insert(0, str(REPO))
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "LEGS", legs)
    code = chip_smoke.main(["--cpu-tiny", "--out", str(tmp_path / "out")])
    summary = json.loads((tmp_path / "out" / "chip_smoke.json").read_text())
    return code, summary, capsys.readouterr().out


def test_a_leg_short_of_devices_says_not_run_never_ok(
    monkeypatch, tmp_path, capsys
):
    code, summary, out = _main_with_legs(monkeypatch, tmp_path, capsys, (
        ("1_fine", lambda ctx: {"fact": 1}, 1),
        ("4_wide", lambda ctx: {"fact": 2}, 64),
    ))
    assert code == 0
    assert summary["legs"]["1_fine"]["status"] == "passed"
    assert summary["legs"]["4_wide"] == {"status": "not run: 8 device(s)"}
    assert "[chip_smoke] leg 4_wide: not run: 8 device(s)" in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True


def test_a_failed_leg_fails_the_smoke_but_not_the_legs_after_it(
    monkeypatch, tmp_path, capsys
):
    def broken(ctx):
        raise RuntimeError("Mosaic said no")

    code, summary, out = _main_with_legs(monkeypatch, tmp_path, capsys, (
        ("1_broken", broken, 1),
        ("2_after", lambda ctx: {"ran": True}, 1),
    ))
    assert code == 1
    assert summary["ok"] is False
    assert summary["legs"]["1_broken"]["status"] == "FAILED"
    assert "Mosaic said no" in summary["legs"]["1_broken"]["error"]
    assert summary["legs"]["2_after"]["status"] == "passed"
    # No result line: the last stdout line is the summary, ok false.
    assert json.loads(out.strip().splitlines()[-1])["ok"] is False
    assert '{"ok": true' not in out
