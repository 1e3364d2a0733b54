#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the repo's main path once — train a policy, write a checkpoint,
serve it — through the entry points a user calls (``train.main``,
``scripts/serve_policy.py``'s ``main``), at the full width of the
configurations the repo supports, and checks what comes out:

0. device gate: what jax resolved, versions, compile cache. No TPU ->
   exit 2, nothing else runs, no result is printed.
1. train config 2 (M=4096 x N=5, preset=tpu, the 2x64 MLP): a few
   iterations on the host loop, then three ``fused_chunk=8`` chunks.
   Finite loss every iteration, a checkpoint that lands and restores, one
   compile per program, run directory and program ledger stamped with
   the device.
2. serve leg 1's checkpoint: every rung answers and agrees with
   ``LoadedPolicy.predict`` (also one row past the top rung), then the
   stock ``--smoke`` load: requests ok, none rejected or timed out,
   the served step is leg 1's, one compile per rung.
3. kernels, config 4: GNN over the k-NN graph at N=100 (fused Pallas
   kernel) and N=1024 (streaming kernel) through ``train.main``, with the
   Mosaic custom call found in the training program that ran; then the
   three compiled-vs-xla parity legs of ``tests/tpu_compiled_parity.py``.
4. with >= 4 devices (4a-4e): ``mesh={dp: 4}`` training, a
   one-replica-per-device fleet, the fleet with the dp=4 sharded slice,
   the actor/learner split, and sharded-vs-replicated actions at every
   rung — each with the bytes it put on every device. With fewer:
   "not run: N device(s)", never "ok".

One process does everything (one process per chip). Run directories go
under ``--out`` (default ``chiprun_out/chip_smoke``), never ``logs/``.
Exit code 0 only if every leg that ran passed; the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--cpu-tiny`` asks for the CPU by name: tiny sizes, interpret-mode
kernels, four virtual devices — a test of this script, not of the
system. Without it, ``JAX_PLATFORMS=cpu`` in the environment does NOT
make the gate pass.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import sys
import time
import traceback
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# Real sizes are the configurations as BASELINE.json / docs/acceptance
# name them; the tiny ones exist so the script itself can be tested on
# the CPU (tests/test_chip_smoke.py).
SIZES = {
    "chip": {
        "mlp_m": 4096,
        "host_iters": 3,
        "fused_chunk": 8,
        "serve_s": 3.0,
        "gnn100": (1024, 100),
        "gnn1024": (8, 1024),
        "gnn_iters": 3,
        "parity": ((4096, 100, 4), (256, 512, 4), (256, 1024, 4)),
        "n_steps": 10,  # cfg/config.yaml's own
    },
    "cpu-tiny": {
        "mlp_m": 8,
        "host_iters": 2,
        "fused_chunk": 2,
        "serve_s": 0.5,
        "gnn100": (4, 12),
        "gnn1024": (2, 20),
        "gnn_iters": 2,
        "parity": ((8, 20, 3), (2, 130, 3), (2, 300, 3)),
        "n_steps": 2,
    },
}
FUSED_CHUNKS = 3  # the second chunk proves donation + the double-buffered drain
FLEET_FACTS = (
    "replicas", "client_requests_ok", "client_rejected", "client_timed_out",
    "max_compiles_per_rung",
)


class CacheCounter:
    """Compile requests that consulted the persistent cache, and hits,
    as jax's own monitoring events report them."""

    def __init__(self) -> None:
        self.requests = 0
        self.hits = 0

    def __call__(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def train_argv(name: str, out: Path, extra: list) -> list:
    # keep_last_n=1: checkpoints carry the env state (9.6 MB each at
    # M=1024 x N=100), and what the chip tool brings back is capped.
    return [f"name={name}", f"log_dir={out / name}", "keep_last_n=1", *extra]


def read_jsonl(path: Path) -> list:
    return [json.loads(line) for line in path.read_text().splitlines() if line]


def check_train_run(
    result: dict,
    platform: str,
    iterations: int = None,
    compiles: int = 1,
    env_params=None,
) -> dict:
    """What every training leg must leave behind. ``iterations=None``
    (the actor/learner split, whose learner may drop stale batches)
    wants at least one record instead of an exact count."""
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.utils import (
        checkpoint_step,
        latest_checkpoint,
    )

    run = Path(result["log_dir"])
    records = read_jsonl(run / "metrics.jsonl")
    check(
        len(records) == iterations if iterations else bool(records),
        f"{run.name}: {len(records)} metric records, wanted {iterations}",
    )
    losses = [r["loss"] for r in records]
    check(
        all(math.isfinite(v) for v in losses),
        f"{run.name}: non-finite loss in {losses}",
    )
    check(
        result["train_compiles"] == compiles,
        f"{run.name}: train_compiles={result['train_compiles']}, "
        f"wanted {compiles}",
    )
    ckpt = latest_checkpoint(run)
    check(ckpt is not None, f"{run.name}: no checkpoint landed")
    step = checkpoint_step(ckpt)
    check(
        step == result["num_timesteps"],
        f"{run.name}: newest checkpoint is step {step}, the run ended at "
        f"{result['num_timesteps']}",
    )
    restored = LoadedPolicy.from_checkpoint(ckpt, env_params=env_params)
    snap = json.loads((run / "config.json").read_text())
    check(
        snap.get("resolved_platform") == platform,
        f"{run.name}: config.json resolved_platform="
        f"{snap.get('resolved_platform')!r}, wanted {platform!r}",
    )
    census = json.loads((run / "program_ledger.json").read_text())
    check(bool(census["programs"]), f"{run.name}: empty program ledger")
    # The ledger is the process's, so later legs see earlier programs too:
    # every entry must name the device, and the sources are counted.
    sources = {}
    for prog in census["programs"]:
        check(
            prog["backend"] == platform,
            f"{run.name}: ledger entry {prog['key']} says backend="
            f"{prog['backend']!r}",
        )
        source = prog["analysis_source"]
        sources[source] = sources.get(source, 0) + 1
    return {
        "iterations": len(records),
        "step": step,
        "checkpoint": str(ckpt),
        "restored": type(restored.model).__name__,
        "loss_first_last": [losses[0], losses[-1]],
        "train_compiles": result["train_compiles"],
        "ledger_analysis_sources": sources,
        "residency_bytes": result["residency_bytes"],
    }


def mlp_argv(ctx: dict) -> tuple:
    """Config 2's overrides and its agent-transitions per iteration."""
    size = ctx["size"]
    m = size["mlp_m"]
    return [
        f"num_formation={m}", "num_agents_per_formation=5", "preset=tpu",
        f"n_steps={size['n_steps']}",
    ], size["n_steps"] * m * 5


def leg1_train(ctx: dict) -> dict:
    """Config 2 at full width: host loop, then the fused scan."""
    import train

    size, out = ctx["size"], ctx["out"]
    base, per_iter = mlp_argv(ctx)
    host = train.main(train_argv("train_host", out, base + [
        f"total_timesteps={size['host_iters'] * per_iter}",
    ]))
    facts = {"host_loop": check_train_run(
        host, ctx["platform"], size["host_iters"]
    )}
    k = size["fused_chunk"]
    fused = train.main(train_argv("train_fused", out, base + [
        f"fused_chunk={k}",
        f"total_timesteps={FUSED_CHUNKS * k * per_iter}",
    ]))
    facts["fused_scan"] = check_train_run(
        fused, ctx["platform"], FUSED_CHUNKS * k
    )
    ctx["served_run"] = fused["log_dir"]
    ctx["served_step"] = fused["num_timesteps"]
    return facts


def run_serve(argv: list) -> dict:
    """``serve_policy.main(argv)`` in this process; its one stdout line."""
    import serve_policy

    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        code = serve_policy.main(argv)
    text = captured.getvalue()
    sys.stdout.write(text)
    check(code == 0, f"serve_policy.main({argv}) returned {code}")
    return json.loads(text.strip().splitlines()[-1])


def check_served(report: dict, ctx: dict) -> None:
    check(report["client_requests_ok"] > 0, "served 0 requests")
    for key in ("client_rejected", "client_timed_out"):
        check(report[key] == 0, f"{key}={report[key]}")
    check(
        report["platform"] == ctx["platform"],
        f"served on {report['platform']!r}",
    )


def leg2_serve(ctx: dict) -> dict:
    """Single engine, one chip, the checkpoint leg 1 just wrote."""
    from marl_distributedformation_tpu.serving import RUNG_SWEEP_TOL

    check("served_run" in ctx, "leg 1 left no run to serve")
    report = run_serve([
        ctx["served_run"], "--smoke", "--duration", str(ctx["size"]["serve_s"]),
    ])
    check_served(report, ctx)
    check(
        report["model_step"] == ctx["served_step"],
        f"served step {report['model_step']}, leg 1 wrote "
        f"{ctx['served_step']}",
    )
    compiles = {
        b: report[f"compiles_bucket_{b}"] for b in (1, 8, 64, 512)
    }
    check(
        all(c == 1 for c in compiles.values()),
        f"every rung must compile exactly once: {compiles}",
    )
    check(
        report["rung_sweep_other_step"] == 0
        and report["rung_sweep_max_abs_err"] <= RUNG_SWEEP_TOL,
        f"rung sweep vs LoadedPolicy.predict: {report}",
    )
    return {
        key: report[key]
        for key in (
            "client_requests_ok", "client_rejected", "client_timed_out",
            "model_step", "rung_sweep_sizes", "rung_sweep_max_abs_err",
            "latency_p50_ms", "latency_p95_ms",
        )
    } | {"compiles_per_rung": compiles}


def train_program_has_mosaic_call(dump: Path) -> bool:
    """The training program that RAN (jax's own IR dump of it), searched
    for the Mosaic custom call."""
    modules = sorted(dump.glob("*jit_train_iteration*"))
    check(bool(modules), f"no train_iteration module dumped under {dump}")
    return any("tpu_custom_call" in p.read_text() for p in modules)


def leg3_kernels(ctx: dict) -> dict:
    """Config 4: both Pallas kernels inside rollout + GAE + update, then
    compiled-vs-xla parity."""
    import jax
    import jax.numpy as jnp

    import tpu_compiled_parity as parity
    import train
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.ops.knn import _resolve_auto_impl

    size, out, tiny = ctx["size"], ctx["out"], ctx["tiny"]
    facts = {}
    for name, want in (("gnn100", "pallas"), ("gnn1024", "pallas_big")):
        m, n = size[name]
        resolved = _resolve_auto_impl(jnp.zeros((m, n, 2)))
        # On the chip nothing is overridden: the default ("auto") must
        # pick the Mosaic kernel. On the CPU the interpret spelling is
        # asked for by name.
        impl = f"{want}_interpret" if tiny else "auto"
        override = [f"knn_impl={impl}"] if tiny else []
        check(
            tiny or resolved == want,
            f"{name}: impl=auto resolves to {resolved!r}, wanted {want!r}",
        )
        dump = out / f"ir_{name}"
        jax.config.update("jax_dump_ir_to", str(dump))
        try:
            result = train.main(train_argv(name, out, [
                "policy=gnn", "obs_mode=knn", "knn_k=4", *override,
                f"num_agents_per_formation={n}", f"num_formation={m}",
                "preset=tpu", f"n_steps={size['n_steps']}",
                "total_timesteps="
                f"{size['gnn_iters'] * size['n_steps'] * m * n}",
            ]))
        finally:
            jax.config.update("jax_dump_ir_to", None)
        facts[name] = check_train_run(
            result, ctx["platform"], size["gnn_iters"],
            env_params=EnvParams(num_agents=n, obs_mode="knn", knn_k=4),
        )
        mosaic = train_program_has_mosaic_call(dump)
        check(
            mosaic == (not tiny),
            f"{name}: Mosaic custom call in the training program: {mosaic}",
        )
        facts[name] |= {
            "auto_resolves_to": resolved, "ran_impl": impl,
            "mosaic_call_in_train_program": mosaic,
        }
    legs = (parity.run_parity, parity.run_parity_mid, parity.run_parity_big)
    facts["parity"] = [
        leg(*shape, interpret=tiny)
        for leg, shape in zip(legs, size["parity"])
    ]
    for line in facts["parity"]:
        print(f"[chip_smoke] PARITY_OK: {line}")
    return facts


def held_now() -> dict:
    """Bytes per device once everything unreachable is gone — the
    baseline a sub-leg's own residency is read against."""
    import gc

    from marl_distributedformation_tpu.utils import device_residency

    gc.collect()
    return device_residency()


def spread(before: dict, after: dict, at_least: int, what: str) -> dict:
    """Fail when work that should be spread sits on too few devices:
    ``after`` (taken by the entry point while its arrays were alive) must
    exceed ``before`` on ``at_least`` devices."""
    added = {d: after[d] - before[d] for d in after}
    print(f"[chip_smoke] {what}: bytes added per device {added}")
    holding = [d for d, nbytes in added.items() if nbytes > 0]
    check(
        len(holding) >= at_least,
        f"{what}: only device(s) {holding} gained anything, wanted "
        f">= {at_least} of {len(added)}",
    )
    return added


# Leg 4 — the multi-chip paths, each its own leg so that one call on four
# chips shows every failure, not the first.


def leg4_train_dp4(ctx: dict) -> dict:
    """Config 2 with formations sharded over ``mesh={dp: 4}``."""
    import train

    base, per_iter = mlp_argv(ctx)
    before = held_now()
    result = train.main(train_argv("train_dp4", ctx["out"], base + [
        "mesh.dp=4",
        f"total_timesteps={ctx['size']['host_iters'] * per_iter}",
    ]))
    # Two compiles, not one: the first dispatch sees the state as
    # device_put placed it, its donated outputs come back under the
    # program's own output shardings, so the second dispatch compiles
    # once more — and the third does not (a finding, CHANGES.md PR 21).
    facts = check_train_run(
        result, ctx["platform"], ctx["size"]["host_iters"], compiles=2
    )
    facts["bytes_added"] = spread(
        before, result["residency_bytes"], 4, "train mesh={dp: 4}"
    )
    return facts


def fleet_leg(ctx: dict, flags: list, what: str) -> dict:
    check("served_run" in ctx, "leg 1 left no run to serve")
    before = held_now()
    report = run_serve([
        ctx["served_run"], "--fleet", *flags, "--smoke",
        "--duration", str(ctx["size"]["serve_s"]),
    ])
    check_served(report, ctx)
    check(  # the sharded slice counts as one more replica
        report["replicas"] >= ctx["count"],
        f"{what}: {report['replicas']} replicas on {ctx['count']} devices",
    )
    check(
        report["max_compiles_per_rung"] <= 1,
        f"{what}: max_compiles_per_rung={report['max_compiles_per_rung']}",
    )
    return {k: report[k] for k in FLEET_FACTS} | {
        "bytes_added": spread(before, report["residency_bytes"], 4, what)
    }


def leg4_fleet(ctx: dict) -> dict:
    """``--fleet`` as shipped: one replica per local device."""
    return fleet_leg(ctx, [], "fleet, one replica per device")


def leg4_fleet_sharded(ctx: dict) -> dict:
    """The fleet plus the dp=4 mesh slice for the big rungs."""
    return fleet_leg(
        ctx, ["--sharded", "--mesh-devices", "4"],
        "fleet + dp=4 sharded slice",
    )


def leg4_sebulba(ctx: dict) -> dict:
    """The actor/learner device split."""
    import train

    base, per_iter = mlp_argv(ctx)
    k = ctx["size"]["fused_chunk"]
    before = held_now()
    result = train.main(train_argv("train_sebulba", ctx["out"], base + [
        "architecture=sebulba", "actor_devices=1", f"fused_chunk={k}",
        f"total_timesteps={2 * k * per_iter}",
    ]))
    # Actor program + learner program: one compile each.
    facts = check_train_run(result, ctx["platform"], compiles=2)
    # The actor acts on device 0 and the learner dispatches on the FIRST
    # device of its slice, so two devices is all that can be asked for:
    # the rest of a three-device learner slice holds nothing.
    facts["bytes_added"] = spread(
        before, result["residency_bytes"], 2, "sebulba actor_devices=1"
    )
    return facts


def leg4_sharded_vs_replicated(ctx: dict) -> dict:
    """ROADMAP D6 / docs/serving.md: the sharded engine against the
    replicated one on the same rows, every rung, dp=4."""
    import numpy as np

    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.parallel import make_mesh
    from marl_distributedformation_tpu.serving import (
        RUNG_SWEEP_TOL,
        BucketedPolicyEngine,
        ShardedPolicyEngine,
    )
    from marl_distributedformation_tpu.utils import latest_checkpoint

    check("served_run" in ctx, "leg 1 left no run to serve")
    policy = LoadedPolicy.from_checkpoint(
        latest_checkpoint(Path(ctx["served_run"]))
    )
    buckets = (8, 64, 512)
    replicated = BucketedPolicyEngine(policy, buckets=buckets)
    sharded = ShardedPolicyEngine(
        policy, make_mesh({"dp": 4}), buckets=buckets
    )
    diffs = {}
    for rung in buckets:
        obs = np.random.default_rng(rung).standard_normal(
            (rung, 8), dtype=np.float32
        )
        a = replicated.act(obs, deterministic=True)
        b = sharded.act(obs, deterministic=True)
        diffs[str(rung)] = {
            "max_abs_diff": float(np.abs(a - b).max()),
            "differing_values": int((a != b).sum()),
        }
    print(f"[chip_smoke] sharded(dp=4) vs replicated, per rung: {diffs}")
    # What is true per backend (tests/test_sharded.py has the CPU story):
    # the TPU v5e answers bitwise at every rung (measured PR 21); XLA:CPU
    # only from 4 rows per device — below that its dot sums in another
    # order and the last bit may differ.
    for rung, d in diffs.items():
        bitwise = ctx["platform"] == "tpu" or int(rung) // 4 >= 4
        check(
            d["differing_values"] == 0 if bitwise
            else d["max_abs_diff"] <= RUNG_SWEEP_TOL,
            f"sharded vs replicated at rung {rung}: {d}",
        )
    return diffs


# (name, leg, devices it needs)
LEGS = (
    ("1_train", leg1_train, 1),
    ("2_serve", leg2_serve, 1),
    ("3_kernels", leg3_kernels, 1),
    ("4a_train_dp4", leg4_train_dp4, 4),
    ("4b_fleet", leg4_fleet, 4),
    ("4c_fleet_sharded", leg4_fleet_sharded, 4),
    ("4d_sebulba", leg4_sebulba, 4),
    ("4e_sharded_vs_replicated", leg4_sharded_vs_replicated, 4),
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--cpu-tiny", action="store_true",
        help="ask for the CPU by name: tiny sizes, interpret-mode kernels "
        "(tests this script; proves nothing about the chip)",
    )
    parser.add_argument(
        "--out", type=Path, default=ROOT / "chiprun_out" / "chip_smoke",
        help="where run directories and chip_smoke.json go",
    )
    args = parser.parse_args(argv)

    if not (ROOT / "marl_distributedformation_tpu").is_dir():
        print(
            "chip_smoke.py drives the repo's own entry points; there is no "
            f"checkout around {ROOT}",
            file=sys.stderr,
        )
        return 2
    for path in (ROOT / "tests", ROOT / "scripts", ROOT):
        sys.path.insert(0, str(path))
    import jax

    from marl_distributedformation_tpu.utils import (
        announce_device,
        widen_cpu_pool,
    )

    # Leg 0 — the device gate.
    if args.cpu_tiny:
        jax.config.update("jax_platforms", "cpu")
        widen_cpu_pool(4)  # so leg 4's code paths are tested too
    stamp = announce_device("chip_smoke")
    versions = {
        pkg: metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")
    }
    print(f"[chip_smoke] versions: {versions}")
    if stamp["platform"] != "tpu" and not args.cpu_tiny:
        print(
            f"[chip_smoke] leg 0 FAILED: jax resolved "
            f"{stamp['platform']!r}, not a TPU. Nothing was run.",
            file=sys.stderr,
        )
        return 2
    cache = CacheCounter()
    jax.monitoring.register_event_listener(cache)

    out = args.out.resolve()
    if out.exists():
        # Leg 2 must serve the checkpoint leg 1 just wrote, never one a
        # previous run left behind.
        import shutil

        shutil.rmtree(out)
    out.mkdir(parents=True)
    ctx = {
        "size": SIZES["cpu-tiny" if args.cpu_tiny else "chip"],
        "tiny": args.cpu_tiny,
        "out": out,
        "platform": stamp["platform"],
        "count": stamp["device_count"],
    }
    summary = {
        "device": stamp, "versions": versions,
        "mode": "cpu-tiny" if args.cpu_tiny else "chip", "legs": {},
    }
    failed = []
    for name, leg, needs in LEGS:
        if stamp["device_count"] < needs:
            note = f"not run: {stamp['device_count']} device(s)"
            print(f"[chip_smoke] leg {name}: {note}")
            summary["legs"][name] = {"status": note}
            continue
        print(f"[chip_smoke] leg {name}: start")
        before = (cache.requests, cache.hits)
        t0 = time.perf_counter()
        try:
            facts = leg(ctx)
            status = "passed"
        except Exception as e:  # noqa: BLE001 — a failed leg fails the smoke
            traceback.print_exc()
            facts, status = {"error": repr(e)[:2000]}, "FAILED"
            failed.append(name)
        wall = time.perf_counter() - t0
        summary["legs"][name] = {
            "status": status,
            "wall_s": round(wall, 2),
            "cache_requests": cache.requests - before[0],
            "cache_hits": cache.hits - before[1],
            **facts,
        }
        print(
            f"[chip_smoke] leg {name}: {status} in {wall:.1f}s "
            f"(compile-cache hits {cache.hits - before[1]} of "
            f"{cache.requests - before[0]} requests)"
        )
    summary["ok"] = not failed
    (out / "chip_smoke.json").write_text(
        json.dumps(summary, indent=2, default=str)
    )
    print(f"[chip_smoke] summary -> {out / 'chip_smoke.json'}")
    print(json.dumps(summary, default=str))
    if failed:
        print(f"[chip_smoke] FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": stamp["platform"],
            "kind": stamp["device_kind"],
            "count": stamp["device_count"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
