"""The benchmark's harness: one cell, one seed, one measured window.

Data decides what runs. ``BENCHMARK.json`` names a cell's configuration and
traffic; ``configs/<config>.json`` holds the policy, the environment and
the PPO settings with the overrides the program's entry takes;
``workloads/<traffic>.json`` holds the job (formations, batch, fused chunk,
mesh); ``limits/<cell>.json`` holds the limits ``correct`` is held to; and
``metrics/<metric>.py`` is one small reader per per-layer metric. A later
PR adds files and entries and edits none.

From the program the harness takes the system under test
(``train.build_trainer`` and ``Trainer.run_chunk``), its retrace receipts
and its scope and kernel names. The state a job starts from, the window's
loop, the reduction of the trace, the costs, the peaks and the comparison
that decides ``correct`` are the benchmark's own.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import re
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
TRACE_SECONDS = 8.0  # a traced run measures this long at most: traces are
#   large and reading one counts against the run's 360 seconds


@dataclass
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict
    job: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path = field(default=ROOT / "benchmarks")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bench_dir = root / bench["paths"][0]
    return Cell(
        name=name,
        config_name=entry["config"],
        traffic=entry["traffic"],
        chips=int(entry["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        job=json.loads(
            (bench_dir / "workloads" / f"{entry['traffic']}.json").read_text()
        ),
        limits=json.loads((bench_dir / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )


# ----------------------------------------------------------------------
# The device
# ----------------------------------------------------------------------


def setup_cache():
    """The program's own compile cache, with every program in it, also the
    small ones: a second run in the same checkout compiles nothing."""
    import jax

    from marl_distributedformation_tpu.utils import setup_compile_cache

    cache_dir = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def device_gate(chips: int, require_chip: bool = True) -> dict:
    """Name the device, or fail: no accelerator, or fewer chips than the
    cell asks for, ends the run with no result."""
    import jax

    devices = jax.devices()
    stamp = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if require_chip and stamp["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax resolved the CPU, and the benchmark never "
            "measures there"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips and jax found {len(devices)}"
        )
    return stamp


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            # A TPU keeps a program's temporaries apart from its buffers:
            # ``peak_bytes_in_use`` counts live arrays only, and what the
            # loaded executable holds for its temporaries is *reserved*.
            # The chip's memory is full by the sum of the two.
            peaks.append(
                int(stats["peak_bytes_in_use"])
                + int(stats.get("peak_bytes_reserved", 0))
            )
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------
# The system under test, and the state a job starts from
# ----------------------------------------------------------------------


def program_overrides(cell: Cell, seed: int) -> List[str]:
    job = cell.job
    if job.get("kind") != "anakin_train":
        raise SystemExit(f"unknown traffic kind {job.get('kind')!r}")
    overrides = list(cell.config["overrides"]) + [
        f"num_formation={job['num_formation']}",
        f"batch_size={job['batch_size']}",
        f"fused_chunk={job['fused_chunk']}",
        f"seed={seed % (2**31 - 1)}",
        f"name=bench-{cell.name}",
        # run_chunk() writes nothing; the directory is only named
        f"log_dir={cell.bench_dir.parent / '.bench_out' / 'logs' / cell.name}",
    ]
    for axis, size in (job.get("mesh") or {}).items():
        overrides.append(f"mesh.{axis}={size}")
    return overrides


def build_program(cell: Cell, seed: int):
    """The program's own entry: ``train.build_trainer`` on its own config
    loader, with the cell's overrides."""
    import train as train_entry
    from marl_distributedformation_tpu.utils import load_config

    return train_entry.build_trainer(load_config(program_overrides(cell, seed)))


def seed_key(seed: int):
    """A raw threefry key from a seed of up to 64 bits (the driver's seeds
    pass 2**31, which a 32-bit ``PRNGKey`` would not take)."""
    import jax.numpy as jnp

    seed = int(seed)
    return jnp.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=jnp.uint32
    )


def make_initial_state(cell: Cell, seed: int):
    """Parameters, Adam moments, M reset formations and the loop key, on
    the device, in one jitted call from the seed."""
    import jax

    from .reference import ppo as reference

    config, m = cell.config, cell.job["num_formation"]
    return jax.jit(lambda k: reference.make_state(k, config, m))(seed_key(seed))


def place_state(trainer, state) -> None:
    """Hand the seeded state to the trainer, each leaf where the trainer
    keeps its own (replicated parameters, formations sharded over the
    mesh), and let the program make its own first observation of it."""
    import jax

    anchor = jax.tree_util.tree_leaves(trainer.train_state.params)[0].sharding

    def like(new, old):
        # a leaf that is still a Python number (the step) lies with the
        # parameters: replicated on a mesh
        return jax.device_put(new, getattr(old, "sharding", anchor))

    tm = jax.tree_util.tree_map
    # Everything is committed where the trainer's own state lies, the
    # untouched leaves (step, Adam's zeros) too: the program the first
    # chunk compiles is then the one every later chunk runs.
    train_state = trainer.train_state.replace(params=state["params"])
    trainer.train_state = tm(like, train_state, trainer.train_state)
    env = trainer.env_state
    trainer.env_state = env.replace(
        agents=like(state["env"]["agents"], env.agents),
        goal=like(state["env"]["goal"], env.goal),
        obstacles=like(env.obstacles, env.obstacles),
        steps=like(state["env"]["steps"], env.steps),
        key=like(state["env"]["key"], env.key),
    )
    trainer.obs = like(
        trainer.env_spec.obs(trainer.env_state, trainer.env_params), trainer.obs
    )
    trainer.key = jax.device_put(state["key"], anchor)


def adam_moments(opt_state):
    """The Adam node of the optimizer's state, whatever it is chained in."""
    import jax

    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    )
    found = [n for n in nodes if hasattr(n, "mu") and hasattr(n, "nu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def first_steps_record(trainer, chunks_metrics, params_before) -> dict:
    """What the program's first chunks produced, copied to the host: each
    iteration's loss, gradient norm and reward, and the parameters and
    Adam moments the last of them left."""
    import jax
    import numpy as np

    adam = adam_moments(trainer.train_state.opt_state)
    record = jax.device_get(
        {
            "chunks": [
                {k: m[k] for k in ("loss", "grad_norm", "reward")}
                for m in chunks_metrics
            ],
            "params": trainer.train_state.params,
            "mu": adam.mu,
        }
    )
    chunks = record.pop("chunks")
    record["metrics"] = {
        k: np.concatenate([np.atleast_1d(c[k]) for c in chunks]) for k in chunks[0]
    }
    record["params_before"] = params_before
    return record


def program_scopes() -> Dict[str, str]:
    """HLO instruction name -> the ``jax.named_scope`` path the program
    gave it, read from the text of the executables the process holds (the
    device trace names instructions, not scopes)."""
    import jax

    line = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"')
    scopes: Dict[str, str] = {}
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            if "train_iteration" not in module.name:
                continue
            for text in module.to_string().splitlines():
                found = line.match(text)
                if found:
                    scopes.setdefault(found.group(1), found.group(2))
    return scopes


# ----------------------------------------------------------------------
# The window
# ----------------------------------------------------------------------


class CompileCounter:
    """Counts, through ``jax.monitoring``, every executable jax had to get
    because it did not hold it: built by the backend, or fetched from the
    persistent cache (which also stalls a window). Whatever asks, program
    or harness, is seen."""

    _BUILT = "/jax/core/compile/backend_compile_duration"
    _ASKED = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        import jax

        self.built = 0
        self.asked = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self._BUILT:
            self.built += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == self._ASKED:
            self.asked += 1

    @property
    def count(self) -> int:
        return max(self.built, self.asked)


def run_window(trainer, seconds: float) -> dict:
    """The loop ``Trainer._train_fused`` runs, without its logging and
    checkpoints: dispatch chunk N+1, then fetch chunk N's stacked metrics.
    No chunk is dispatched once ``seconds`` have passed; the clock stops
    when the last chunk's metrics are on the host, and the rate is taken
    over the time that really elapsed."""
    import jax

    chunks = 0
    pending = None
    last_host: Dict[str, Any] = {}
    start = time.perf_counter()
    while True:
        stacked = trainer.run_chunk()
        chunks += 1
        if pending is not None:
            last_host = jax.device_get(pending)
        pending = stacked
        if time.perf_counter() - start >= seconds:
            break
    last_host = jax.device_get(jax.block_until_ready(pending))
    elapsed = time.perf_counter() - start
    return {"chunks": chunks, "elapsed_s": elapsed, "last_metrics": last_host}


# ----------------------------------------------------------------------
# Correct: the program's first chunk against the plain reference
# ----------------------------------------------------------------------


def follow_reference(
    cell: Cell,
    seed: int,
    iterations: int,
    dtype: str = "float32",
    half_batch: bool = False,
    own_shard: int = 0,
) -> dict:
    """Run the reference from the seed through ``iterations`` and return
    the record ``first_steps_record`` returns for the program. ``dtype``
    below float32 is the control; ``half_batch`` and ``own_shard`` plant
    faults."""
    import jax
    import jax.numpy as jnp

    from .reference import ppo as reference

    config, batch = cell.config, cell.job["batch_size"]
    state = make_initial_state(cell, seed)
    params_before = jax.device_get(state["params"])
    obs = jax.jit(lambda env: reference.observe(env, config))(state["env"])
    step = jax.jit(
        lambda s, o: reference.iteration(
            s, o, config, batch, dtype=jnp.dtype(dtype), half_batch=half_batch,
            own_shard=own_shard,
        ),
        donate_argnums=(0, 1),
    )
    per_iteration = []
    with jax.default_matmul_precision("highest"):
        for _ in range(iterations):
            state, obs, metrics = step(state, obs)
            per_iteration.append(metrics)
    record = jax.device_get(
        {
            "metrics": {
                k: jnp.stack([m[k] for m in per_iteration])
                for k in per_iteration[0]
            },
            "params": state["params"],
            "mu": state["mu"],
        }
    )
    record["params_before"] = params_before
    return record


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import numpy as np

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        jax.tree_util.keystr(path): float(
            np.linalg.norm(np.asarray(leaf, np.float64))
        )
        for path, leaf in flat
    }


def _worst_leaf_gap(program: Dict[str, float], ref: Dict[str, float], keep) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(ref[k] for k in keep)
    return max(abs(program[k] - ref[k]) / max(ref[k], median) for k in keep)


def compare(program: dict, ref: dict) -> Dict[str, float]:
    """The numbers ``correct`` compares. Every one is a gap relative to
    the reference, so 0 is agreement and 1 is a quantity that is absent."""
    import jax
    import numpy as np

    numbers = {}
    for name in ("loss", "grad_norm", "reward"):
        p = np.asarray(program["metrics"][name], np.float64)
        r = np.asarray(ref["metrics"][name], np.float64)
        n = min(len(p), len(r))
        gaps = np.abs(p[:n] - r[:n]) / np.abs(r[:n])
        # The first iteration starts from one state on both sides, so its
        # gap is rounding alone; later ones add what rounding grew into.
        numbers[f"{name}_gap_first"] = float(gaps[0])
        numbers[f"{name}_gap"] = float(gaps.max())
    tm = jax.tree_util.tree_map
    ref_mu = _leaf_norms(ref["mu"])
    # A leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: it is left out by a rule on the
    # reference's gradient, never by name.
    floor = 1e-3 * statistics.median(ref_mu.values())
    keep = [k for k, v in ref_mu.items() if v >= floor]
    numbers["adam_mu_gap"] = _worst_leaf_gap(_leaf_norms(program["mu"]), ref_mu, keep)
    change = lambda rec: _leaf_norms(  # noqa: E731
        tm(lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
           rec["params"], rec["params_before"])
    )
    numbers["param_change_gap"] = _worst_leaf_gap(change(program), change(ref), keep)
    numbers["leaves_left_out"] = float(len(ref_mu) - len(keep))
    return numbers


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each number compared beside its limit; a number that is not finite
    fails."""
    rows = []
    for name, limit in limits["limits"].items():
        value = numbers.get(name, float("nan"))
        rows.append(
            {
                "name": name,
                "value": value,
                "limit": limit,
                "ok": bool(math.isfinite(value) and value <= limit),
            }
        )
    return rows


# ----------------------------------------------------------------------
# Per-layer metrics: one small reader each
# ----------------------------------------------------------------------


def load_reader(bench_dir: Path, metric: str) -> Callable[[dict], Optional[float]]:
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None or not path.exists():
        raise SystemExit(f"per-layer metric {metric!r} has no reader at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_per_layer(cell: Cell, context: dict) -> Dict[str, dict]:
    out = {}
    for metric in cell.per_layer:
        value = load_reader(cell.bench_dir, metric["name"])(context)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


# ----------------------------------------------------------------------
# One run
# ----------------------------------------------------------------------


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    started: float,
    require_chip: bool = True,
    build: Callable[[Cell, int], Any] = build_program,
    log=lambda line: print(line, file=sys.stderr, flush=True),
) -> dict:
    """Set up, warm, measure, free, check. Returns the result line's
    object. ``require_chip=False`` and ``build`` are for the rehearsal and
    the fault tests: a run on the CPU reports no metric at all."""
    import jax

    cache_dir = setup_cache()
    device = device_gate(cell.chips, require_chip)
    on_chip = device["platform"] != "cpu"
    log(f"[bench] {cell.name} seed={seed} device={device} cache={cache_dir}")
    compiles = CompileCounter()

    trainer = build(cell, seed)
    state = make_initial_state(cell, seed)
    params_before = jax.device_get(state["params"])
    place_state(trainer, state)
    del state
    # The first chunk goes through the window's own call: it compiles (or
    # loads) the one program the window drives, and what it produced is
    # what the reference is held against.
    followed_chunks = int(cell.limits["follow_chunks"])
    recorded = [
        jax.block_until_ready(trainer.run_chunk()) for _ in range(followed_chunks)
    ]
    first = first_steps_record(trainer, recorded, params_before)
    del recorded
    setup_s = time.perf_counter() - started
    compiles_in_setup = compiles.count
    receipts_before = trainer.retrace_guard.count

    trace_dir = cell.bench_dir.parent / ".bench_out" / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(trace_dir))
    window = run_window(trainer, min(seconds, TRACE_SECONDS) if trace else seconds)
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - compiles_in_setup
    receipts_in_window = trainer.retrace_guard.count - receipts_before
    log(
        f"[bench] set-up {setup_s:.2f}s ({compiles_in_setup} compiles); window "
        f"{window['elapsed_s']:.3f}s, {window['chunks']} chunks; compiles in "
        f"the window: {compiles_in_window} (retrace receipts: "
        f"{receipts_in_window})"
    )
    peak = memory_peak_bytes(cell.chips)

    from . import costs

    shape = costs.job_shape(cell.config, cell.job)
    iterations = window["chunks"] * cell.job["fused_chunk"]
    rate = iterations * shape["agent_steps"] / window["elapsed_s"]

    scopes = program_scopes() if trace and on_chip else {}
    # The program's state is freed before the reference takes the chip.
    del trainer
    gc.collect()

    metrics: Dict[str, dict] = {}
    breakdown = None
    if on_chip and not trace:
        values = {"agent_steps_per_s": rate, "setup_s": setup_s}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    elif on_chip:
        from . import trace as trace_reduction

        reduced = trace_reduction.reduce_trace_dir(trace_dir, cell.chips, scopes)
        from .peaks import load_peaks

        context = {
            "cell": cell,
            "device": device,
            "peaks": load_peaks(device["kind"]),
            "trace": reduced,
            "iterations": iterations,
            "elapsed_s": window["elapsed_s"],
            "agent_steps_per_s": rate,
            "memory_peak_bytes": peak,
        }
        metrics = read_per_layer(cell, context)
        log("[trace] scopes (s): " + json.dumps(
            {k: round(v, 4) for k, v in sorted(
                reduced["scope_s"].items(), key=lambda kv: -kv[1])[:12]}))
        log("[trace] kernels (calls, s): " + json.dumps(reduced["kernel_s"]))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
    if peak is not None:
        device["memory_peak_bytes"] = peak

    followed = followed_chunks * cell.job["fused_chunk"]
    t_ref = time.perf_counter()
    ref = follow_reference(cell, seed, followed)
    numbers = compare(first, ref)
    rows = judge(numbers, cell.limits)
    log(f"[bench] reference followed {followed} iterations in "
        f"{time.perf_counter() - t_ref:.1f}s")
    correct = all(r["ok"] for r in rows) and compiles_in_window == 0
    for r in rows:
        log(f"[correct] {r['name']} {r['value']:.6g} limit {r['limit']:.6g} "
            f"{'ok' if r['ok'] else 'FAIL'}")
    log(f"[correct] compiles_in_window {compiles_in_window} limit 0 "
        f"{'ok' if compiles_in_window == 0 else 'FAIL'}")

    result = {
        "correct": bool(correct),
        "attempted": iterations,
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {
        **{r["name"]: [r["value"], r["limit"]] for r in rows},
        "compiles_in_window": [compiles_in_window, 0],
    }
    return result
