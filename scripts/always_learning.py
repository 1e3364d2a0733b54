#!/usr/bin/env python
"""One process, the whole product: trainer -> gate -> fleet, always learning.

Runs the supervised continuous-learning loop (``pipeline/``,
docs/pipeline.md) end to end: a Trainer streams checkpoints into
``logs/{name}/``, every candidate is judged by the PromotionGate (the
compiled robustness matrix + clean-return regression vs the served
baseline — ONE jitted eval program across all candidates, budget-1
RetraceGuard receipt), passing candidates are published to
``logs/{name}/promoted/`` and hot-swapped into a multi-replica serving
fleet at the batch barrier (globally step-monotonic ``model_step``),
and an optional RollbackMonitor demotes to last-good on a served-metric
regression. Verdicts land in ``logs/{name}/promotions.jsonl``.

Usage (same key=value CLI as every entry point; trainer keys ride
through to ``train.build_trainer``):

    python scripts/always_learning.py name=always num_formation=64 \\
        total_timesteps=64000 max_steps=100 pipeline_replicas=2

    # a tiny run on a forced 2-device CPU:
    JAX_PLATFORMS=cpu python scripts/always_learning.py name=bench_pipeline \\
        num_formation=16 total_timesteps=4800 max_steps=60 \\
        gate_formations=32 pipeline_replicas=2

Prints exactly one JSON line: promotions / rejections / rollbacks,
``promotion_latency_s_p50``/``p95`` (train-step -> served model_step
wall time), ``gate_eval_steps_per_sec``, the compile-once receipts, and
the final served step.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from marl_distributedformation_tpu.utils import (  # noqa: E402
    announce_device,
    env_params_from_config,
    load_config,
    setup_platform,
    validate_override_keys,
    widen_cpu_pool,
)

PIPELINE_KEYS = (
    # gate
    "gate_scenarios",
    "gate_severities",
    "gate_formations",
    "gate_seed",
    "gate_clean_tolerance",
    "gate_rung_tolerance",
    # adversarial rung + auto-curriculum feedback (docs/adversarial.md)
    "gate_adversarial",
    "gate_adversarial_scenarios",
    "gate_adversarial_min_severity",
    "gate_adversarial_drop_tolerance",
    "gate_adversarial_max_severity",
    "gate_adversarial_grid",
    "gate_adversarial_generations",
    "gate_adversarial_formations",
    "feedback_rollouts",
    # gate-eval deadline (chaos hardening, docs/chaos.md)
    "gate_timeout_s",
    # self-healing supervision (chaos/watchdog.py, docs/chaos.md)
    "watchdog",
    "watchdog_wedge_timeout_s",
    "watchdog_backoff_s",
    "watchdog_backoff_cap_s",
    # chaos plane (chaos/, docs/chaos.md): arm a seeded fault campaign
    # against THIS live run — dev/staging resilience drills.
    "chaos",
    "chaos_seed",
    "chaos_faults",
    # fleet
    "pipeline_replicas",
    "pipeline_buckets",
    "pipeline_port",
    "pipeline_poll_s",
    "pipeline_budget_s",
    "pipeline_verify_requests",
    # mesh tier (serving/mesh/, docs/mesh.md): serve through a loopback
    # multi-host mesh — host subprocesses behind the MetaRouter, the
    # MeshCoordinator driving every promotion as a global barrier commit.
    "mesh_serve",
    "mesh_hosts",
    "mesh_heartbeat_s",
    "mesh_lease_s",
    "mesh_dead_after_s",
    "mesh_prepare_timeout_s",
    "mesh_port",
    # rollback
    "rollback_metric",
    "rollback_threshold",
    "rollback_ratio",
    "rollback_direction",
    "rollback_trip_after",
    "rollback_baseline_samples",
    # observability spine (obs/, docs/observability.md)
    "obs_trace",
    "obs_ring_size",
    "obs_flightrec",
    # live-metrics plane (obs/metrics.py, docs/observability.md)
    "telemetry",
    "telemetry_port",
    "telemetry_reservoir",
    # program ledger (obs/ledger.py, docs/observability.md)
    "ledger",
    "ledger_reservoir",
    # perf-regression sentinel (obs/sentinel.py)
    "sentinel",
    "sentinel_tolerance",
    "sentinel_trip_after",
    "sentinel_bench",
    "out",
)
# Trainer knobs are the normal YAML config surface (train.py is
# struct-less); this entry point validates only because a mistyped
# pipeline key would otherwise silently run the defaults.
TRAIN_EXTRA_KEYS = (
    "save_freq", "policy", "hidden_sizes", "mesh", "num_seeds",
    "curriculum", "learning_rates", "platform", "preset", "fused_chunk",
    "guard_retraces", "guard_transfers", "guard_nans", "profile",
    "profile_iterations",
    # sebulba lane (train/sebulba/, docs/sebulba.md): the split
    # acting/learning architecture; the gate then runs on its OWN
    # device slice instead of time-sharing the trainer's.
    "architecture", "actor_devices", "transfer_queue_depth",
    "max_param_staleness",
)


def _gate_config(cfg):
    from marl_distributedformation_tpu.pipeline import GateConfig

    scenarios = cfg.get("gate_scenarios") or ["wind", "sensor_noise"]
    if not isinstance(scenarios, list):
        scenarios = [scenarios]
    severities = cfg.get("gate_severities") or [0.5, 1.0]
    if not isinstance(severities, list):
        severities = [severities]
    adv_scenarios = cfg.get("gate_adversarial_scenarios") or []
    if not isinstance(adv_scenarios, list):
        adv_scenarios = [adv_scenarios]
    return GateConfig(
        scenarios=tuple(str(s) for s in scenarios),
        severities=tuple(float(s) for s in severities),
        eval_formations=int(cfg.get("gate_formations", 64)),
        eval_seed=int(cfg.get("gate_seed", 1234)),
        clean_tolerance=float(cfg.get("gate_clean_tolerance", 0.05)),
        rung_tolerance=float(cfg.get("gate_rung_tolerance", 0.10)),
        adversarial=bool(cfg.get("gate_adversarial", False)),
        adversarial_scenarios=tuple(str(s) for s in adv_scenarios),
        adversarial_min_severity=float(
            cfg.get("gate_adversarial_min_severity", 0.5)
        ),
        adversarial_drop_tolerance=float(
            cfg.get("gate_adversarial_drop_tolerance", 0.2)
        ),
        adversarial_max_severity=float(
            cfg.get("gate_adversarial_max_severity", 1.5)
        ),
        adversarial_grid=int(cfg.get("gate_adversarial_grid", 4)),
        adversarial_generations=int(
            cfg.get("gate_adversarial_generations", 3)
        ),
        adversarial_formations=int(
            cfg.get("gate_adversarial_formations", 64)
        ),
        # The eval deadline: size past the cold compile (the FIRST eval
        # includes it) or leave None; a wedged candidate then yields a
        # ``gate_timeout`` verdict instead of stalling the loop.
        gate_timeout_s=(
            float(cfg["gate_timeout_s"])
            if cfg.get("gate_timeout_s") is not None
            else None
        ),
    )


def _monitor(cfg, router):
    metric = cfg.get("rollback_metric")
    if not metric:
        return None
    from marl_distributedformation_tpu.obs import get_registry
    from marl_distributedformation_tpu.pipeline import RollbackMonitor

    direction = str(cfg.get("rollback_direction") or "above")
    # Mesh mode: the fleet families live in the HOST subprocesses and
    # reach this process only as gossip (MeshHost.metrics). A
    # fleet-snapshot metric name is resolved as the WORST value across
    # routable hosts — max for an "above"-breaching metric (latency,
    # queue depth), min for "below" (served return) — so the tripwire
    # fires when ANY host regresses, never silently reads None.
    coordinator = getattr(router, "coordinator", None)

    def sample():
        # One sampling code path fleet-wide (obs/metrics.py): the
        # router snapshot refreshes the fleet gauges in the process
        # registry (FleetMetrics.snapshot publishes as a side effect),
        # then the monitor reads the MERGED registry namespace — the
        # same numbers GET /metrics serves, and any trainer/pipeline
        # gauge is now watchable too, not just fleet keys. The fresh
        # fleet snapshot overlays the registry copy so the monitored
        # metric can never be a stale gauge; with telemetry disabled
        # the registry is empty and the monitor falls back to exactly
        # the fleet snapshot — the telemetry off-switch must never
        # blind the rollback tripwire.
        snap = router.snapshot()
        merged = get_registry().snapshot()
        merged.update(snap)
        if coordinator is not None and metric not in merged:
            values = []
            for h in coordinator.routable_hosts():
                v = (h.metrics or {}).get(metric)
                if isinstance(v, (int, float)):
                    values.append(float(v))
            if values:
                merged[metric] = (
                    max(values) if direction == "above" else min(values)
                )
        return merged

    return RollbackMonitor(
        sample,
        metric=str(metric),
        threshold=cfg.get("rollback_threshold"),
        ratio=cfg.get("rollback_ratio"),
        direction=str(cfg.get("rollback_direction") or "above"),
        baseline_samples=int(cfg.get("rollback_baseline_samples", 3)),
        trip_after=int(cfg.get("rollback_trip_after", 2)),
    )


def main(argv=None) -> dict:
    overrides = sys.argv[1:] if argv is None else argv
    validate_override_keys(
        overrides, extra_keys=PIPELINE_KEYS + TRAIN_EXTRA_KEYS
    )
    cfg = load_config(overrides)
    setup_platform(cfg.get("platform"))

    replicas = int(cfg.get("pipeline_replicas", 2))
    sebulba = str(cfg.get("architecture") or "anakin") == "sebulba"
    actor_devices = int(cfg.get("actor_devices", 1))
    # Sebulba wants real slices: actor_devices acting + 1 learning + 1
    # for the gate's own assignment (docs/sebulba.md). Anakin only needs
    # a device per serving replica.
    want_devices = max(replicas, actor_devices + 2) if sebulba else replicas
    # With the CPU asked for by name (the dev shape) each serving replica
    # and slice gets its own virtual device; on an accelerator they share
    # the devices the hardware has.
    widen_cpu_pool(want_devices)
    stamp = announce_device("pipeline")

    import train as train_entry
    from marl_distributedformation_tpu.pipeline import (
        AlwaysLearningPipeline,
    )
    from marl_distributedformation_tpu.train import Trainer

    env_params = env_params_from_config(cfg)
    if bool(cfg.get("gate_adversarial", False)) and not cfg.get("scenarios"):
        # The adversarial rung feeds rejected candidates' falsifiers back
        # into the trainer's schedule — that needs the traced scenario
        # seam compiled into the train step. Reserve it with the identity
        # scenario; the feedback stages replace it live.
        cfg["scenarios"] = ["clean"]
    trainer = train_entry.build_trainer(cfg)
    if not isinstance(trainer, Trainer):
        raise SystemExit(
            "the always-learning pipeline drives the single-run Trainer; "
            "population sweeps / curriculum trainers checkpoint a "
            "different layout (drop num_seeds / curriculum)"
        )

    # Observability spine (obs/): the tracer records promotion spans +
    # serving batch spans into per-thread rings, and the flight recorder
    # snapshots them next to the checkpoints on incidents (circuit
    # break, rollback trip, wedged barrier). Knobs in cfg/config.yaml.
    from marl_distributedformation_tpu import obs as obs_spine

    obs_enabled = bool(cfg.get("obs_trace", True))
    obs_spine.configure(
        enabled=obs_enabled,
        ring_size=int(cfg.get("obs_ring_size", 4096)),
        flightrec_dir=(
            str(trainer.log_dir)
            if cfg.get("obs_flightrec", True)
            else ""
        ),
    )
    # Live-metrics plane (obs/metrics.py): the trainer's dispatch loop,
    # the gate, and the fleet all record into the process registry;
    # telemetry_port serves the merged namespace as Prometheus text
    # (GET /metrics) so a pipeline run exports everything ROADMAP item
    # 3's autoscaler needs without a fleet frontend.
    obs_spine.configure_metrics(
        enabled=bool(cfg.get("telemetry", True)),
        reservoir=int(cfg.get("telemetry_reservoir", 512)),
    )
    # Program ledger (obs/ledger.py): every compile site in the loop —
    # trainer dispatch, gate MatrixProgram, adversary rung, serving
    # rungs — registers its executable automatically at the
    # RetraceGuard seam; the census lands beside promotions.jsonl at
    # exit and the report carries entry-count == receipt-count.
    obs_spine.configure_ledger(
        enabled=bool(cfg.get("ledger", True)),
        reservoir=int(cfg.get("ledger_reservoir", 256)),
    )
    telemetry = None
    telemetry_port = cfg.get("telemetry_port")
    if telemetry_port is not None:
        telemetry = obs_spine.TelemetryServer(
            port=int(telemetry_port)
        ).start()
        report_telemetry_url = telemetry.url
        print(f"[always] telemetry: {telemetry.url}", file=sys.stderr)
    else:
        report_telemetry_url = None

    # Perf-regression sentinel (obs/sentinel.py): live gauges vs the
    # newest committed BENCH record; a sustained regression dumps a
    # flightrec-perf_regression-*.json and an audit line beside the
    # checkpoints.
    sentinel = None
    if bool(cfg.get("sentinel", False)):
        if not bool(cfg.get("telemetry", True)):
            # The sentinel compares LIVE registry gauges; with the
            # registry disabled every snapshot is empty and the
            # tripwire is silently blind — refuse loudly instead.
            raise SystemExit(
                "sentinel=true needs telemetry=true (the sentinel "
                "watches the live MetricsRegistry gauges; a disabled "
                "registry records nothing, so no regression could "
                "ever trip)"
            )
        sentinel_tol = float(cfg.get("sentinel_tolerance", 0.5))
        sentinel = obs_spine.RegressionSentinel(
            obs_spine.default_watches(tolerance=sentinel_tol)
            # Ledger aggregates guard against compile-time / memory-
            # footprint regressions vs the committed record; an older
            # record without the fields reports as sentinel_missing,
            # never a breach.
            + obs_spine.ledger_watches(tolerance=sentinel_tol)
            # Recovery-MTTR guard (train/recovery.py): live rollback
            # restore wall vs the committed drill; wide band (recovery
            # is rare, samples are few). Missing field = unmeasurable,
            # never a breach.
            + obs_spine.recovery_watches(),
            record_path=cfg.get("sentinel_bench"),
            trip_after=int(cfg.get("sentinel_trip_after", 3)),
            audit_dir=trainer.log_dir,
        )

    budget_s = float(cfg.get("pipeline_budget_s", 600.0))
    deadline = time.time() + budget_s
    gate_device = None
    if sebulba:
        # The gate's own slice under the sebulba partition — candidate
        # evals stop contending with the learner's update stream, and
        # the promotion span breakdown records which device served.
        from marl_distributedformation_tpu.train import assign_gate_device

        gate_device = assign_gate_device(actor_devices)
        print(
            f"[always] sebulba: actor slice {trainer.actor_slice}, "
            f"learner slice {trainer.learner_slice}, gate on "
            f"{gate_device}",
            file=sys.stderr,
        )
    pipeline = AlwaysLearningPipeline(
        trainer.log_dir,
        env_params,
        gate_config=_gate_config(cfg),
        poll_interval_s=float(cfg.get("pipeline_poll_s", 0.25)),
        feedback_rollouts=int(cfg.get("feedback_rollouts", 50)),
        gate_device=gate_device,
    )
    pipeline.attach_trainer(trainer)

    train_error: list = []

    def run_training() -> None:
        try:
            trainer.train()
        except BaseException as e:  # noqa: BLE001 — surfaced in the report
            train_error.append(repr(e))

    train_thread = threading.Thread(
        target=run_training, name="always-learning-trainer", daemon=True
    )
    print(
        f"[always] {cfg.name}: training M={cfg.num_formation} to "
        f"{trainer.total_timesteps} agent-transitions; gate "
        f"{pipeline.gate.config.scenarios} x "
        f"{pipeline.gate.config.severities}; fleet {replicas} replicas",
        file=sys.stderr,
    )
    train_thread.start()

    report: dict = {"name": str(cfg.name)}
    router = None
    frontend = None
    watchdog = None
    mesh = None
    try:
        if not pipeline.wait_first_promotion(
            timeout_s=max(deadline - time.time(), 1.0)
        ):
            raise SystemExit(
                "no candidate passed the gate within pipeline_budget_s "
                f"({budget_s:g}s) — see logs/{cfg.name}/promotions.jsonl"
            )

        buckets = cfg.get("pipeline_buckets") or [1, 8]
        mesh_serve = bool(cfg.get("mesh_serve", False))
        mesh = None
        if mesh_serve:
            # The cross-host shape (serving/mesh/, docs/mesh.md): host
            # SUBPROCESSES serve the promoted directory behind the
            # MetaRouter; the MeshCoordinator drives every promotion
            # as a coordinator-barriered global commit, and the
            # supervisor is none the wiser (duck-typed attach_fleet).
            from marl_distributedformation_tpu.serving.mesh import (
                spawn_local_mesh,
            )

            mesh_port = cfg.get("mesh_port")
            mesh = spawn_local_mesh(
                pipeline.promoted_dir,
                hosts=int(cfg.get("mesh_hosts", 2)),
                replicas_per_host=replicas,
                buckets=tuple(int(b) for b in buckets),
                num_agents=env_params.num_agents,
                heartbeat_s=float(cfg.get("mesh_heartbeat_s", 0.25)),
                lease_s=float(cfg.get("mesh_lease_s", 1.0)),
                dead_after_s=float(cfg.get("mesh_dead_after_s", 1.0)),
                prepare_timeout_s=float(
                    cfg.get("mesh_prepare_timeout_s", 30.0)
                ),
                frontend_port=(
                    int(mesh_port) if mesh_port is not None else None
                ),
                ready_timeout_s=max(deadline - time.time(), 30.0),
            )
            router, coordinator = mesh.router, mesh.coordinator
            if mesh.frontend is not None:
                report["frontend_url"] = mesh.frontend.url
                print(
                    f"[always] mesh frontend: {mesh.frontend.url}",
                    file=sys.stderr,
                )
            print(
                f"[always] mesh: {len(mesh.hosts)} host subprocesses, "
                f"coordinator {coordinator.url}",
                file=sys.stderr,
            )
        else:
            from marl_distributedformation_tpu.serving.fleet import (
                fleet_from_checkpoint_dir,
                warmup_fleet,
            )

            router, coordinator = fleet_from_checkpoint_dir(
                pipeline.promoted_dir,
                env_params=env_params,
                act_dim=env_params.act_dim,
                num_replicas=replicas,
                buckets=tuple(int(b) for b in buckets),
            )
            router.start()
            warmup_fleet(router, (env_params.obs_dim,))
            port = cfg.get("pipeline_port")
            if port is not None:
                from marl_distributedformation_tpu.serving.fleet import (
                    FleetFrontend,
                )

                frontend = FleetFrontend(router, port=int(port)).start()
                report["frontend_url"] = frontend.url
                print(
                    f"[always] frontend: {frontend.url}", file=sys.stderr
                )
        pipeline.attach_fleet(router, coordinator)
        monitor = _monitor(cfg, router)
        if monitor is not None:
            pipeline.attach_monitor(monitor)

        # Self-healing supervision (chaos/watchdog.py): the watchdog
        # restarts a crashed replica worker and the router's half-open
        # probe readmits it — the fleet regrows to full width instead
        # of bleeding replicas. (The pipeline lane here IS this main
        # thread, so only the fleet lanes are watchdogged; the
        # background-loop mode — pipeline.run() — also gets the
        # pipeline lane via watchdog.watch_pipeline.)
        if bool(cfg.get("watchdog", True)) and not mesh_serve:
            # Mesh mode has no in-process fleet lanes to watch — each
            # host subprocess supervises its own schedulers, and host
            # DEATH is the coordinator's lease taxonomy's job.
            from marl_distributedformation_tpu.chaos import LaneWatchdog

            watchdog = LaneWatchdog(
                wedge_timeout_s=float(
                    cfg.get("watchdog_wedge_timeout_s", 30.0)
                ),
                backoff_base_s=float(cfg.get("watchdog_backoff_s", 0.5)),
                backoff_cap_s=float(
                    cfg.get("watchdog_backoff_cap_s", 30.0)
                ),
            )
            watchdog.watch_fleet(router)
            if sebulba:
                # Both training lanes under the same supervision: a dead
                # actor thread restarts, a wedged learner is surfaced.
                trainer.attach_watchdog(watchdog)
            watchdog.start()

        # Chaos drill (chaos/, docs/chaos.md): arm a seeded fault
        # campaign against THIS live run. The schedule is a pure
        # function of chaos_seed, so a drill that trips an invariant
        # replays bit-identically (scripts/chaos_storm.py is the
        # self-contained harness; this knob storms the real run).
        if bool(cfg.get("chaos", False)):
            from marl_distributedformation_tpu.chaos import (
                FaultSchedule,
                get_fault_plane,
            )

            plane = get_fault_plane()
            plane.arm(
                FaultSchedule.from_seed(
                    int(cfg.get("chaos_seed", 0)),
                    faults=int(cfg.get("chaos_faults", 25)),
                )
            )
            plane.enabled = True
            print(
                f"[always] chaos armed: {plane.pending()} faults, "
                f"seed {int(cfg.get('chaos_seed', 0))}",
                file=sys.stderr,
            )

        # Supervision loop: drain candidates while the trainer runs,
        # then drain the tail after it finishes. The loop heartbeats so
        # `pipeline_loop_heartbeat_age_s` is scrapeable liveness.
        while time.time() < deadline:
            pipeline.heartbeat.beat()
            processed = pipeline.poll_once()
            if sentinel is not None:
                # Refresh the fleet families first (FleetMetrics
                # publishes on every snapshot read) so the latency
                # watch sees live numbers even when no monitor or
                # external scraper is driving reads.
                router.snapshot()
                sentinel.check()
            if not train_thread.is_alive() and processed == 0:
                # The trainer may have written its final checkpoint
                # between our poll and the liveness check (train()
                # returning guarantees the async writer drained) — one
                # post-death drain closes the race.
                if pipeline.poll_once() == 0:
                    break
                continue
            if processed == 0:
                time.sleep(0.05)
        train_thread.join(timeout=max(deadline - time.time(), 0.0))

        # Verification traffic: the served step must be the promoted one.
        import numpy as np

        n_verify = int(cfg.get("pipeline_verify_requests", 4))
        served_steps = []
        rng = np.random.default_rng(0)
        for _ in range(n_verify):
            obs = rng.standard_normal(
                (2, env_params.obs_dim), dtype=np.float32
            )
            res = router.submit(obs).result(timeout=30.0)
            served_steps.append(int(res.model_step))

        report.update(pipeline.summary())
        if sentinel is not None:
            report.update(sentinel.summary())
        if report_telemetry_url is not None:
            report["telemetry_url"] = report_telemetry_url
        report["pipeline_replicas"] = replicas
        if sebulba:
            # The transfer-plane health counters next to the promotion
            # stats: one JSON line answers "did the split lanes keep up".
            report["architecture"] = "sebulba"
            report["transfer_queue_occupancy_p95"] = round(
                trainer.occupancy_p95(), 2
            )
            report["param_staleness_p95_updates"] = round(
                trainer.staleness_p95(), 2
            )
            report["sebulba_stale_dropped"] = trainer.stale_dropped
            report["sebulba_actor_compiles"] = trainer.actor_guard.count
            report["sebulba_learner_compiles"] = trainer.learner_guard.count
        report["fleet_swap_count"] = coordinator.swap_count
        if watchdog is not None:
            report["lane_restarts"] = watchdog.restarts_total()
        from marl_distributedformation_tpu.chaos import get_fault_plane
        from marl_distributedformation_tpu.obs import get_registry

        if get_fault_plane().fired:
            report["chaos_faults_fired"] = len(
                get_fault_plane().fired_record()
            )
        live = get_registry().snapshot()
        for key in (
            "checkpoint_writes_skipped_total",
            "checkpoint_quarantined_total",
            "checkpoint_nonfinite_skipped_total",
            "checkpoint_pruned_total",
            "pipeline_gate_timeouts_total",
        ):
            if live.get(key):
                report[key] = int(live[key])
        # Self-healing train lane (train/recovery.py): surface the
        # ladder's history in the run report — a supervised loop whose
        # trainer quietly rolled back should SAY so.
        if trainer.recovery_ladder is not None:
            ladder = trainer.recovery_ladder
            report["train_recoveries"] = ladder.recoveries
            report["train_divergence_events"] = ladder.breaches
            report["train_skipped_updates"] = ladder.skipped_total
            report["train_halted"] = bool(trainer.halted)
        report["verified_served_steps"] = served_steps
        report["train_alive"] = train_thread.is_alive()
        if train_error:
            report["train_error"] = train_error[0][:300]
        if mesh_serve:
            # Per-host receipts scraped over HTTP (the compiled
            # programs live in the host subprocesses); the ledger
            # receipt equality below only covers THIS process.
            receipt_sets = router.host_compile_counts()
            report["mesh_hosts"] = len(mesh.hosts)
            report["mesh_commit_rounds"] = coordinator.commit_round
            report["mesh_host_states"] = {
                h["host_id"]: h["state"] for h in coordinator.hosts()
            }
            compile_receipts = {}
        else:
            compile_receipts = router.compile_counts()
            receipt_sets = compile_receipts
        report["serving_max_compiles_per_rung"] = max(
            (c for per in receipt_sets.values() for c in per.values()),
            default=0,
        )
        # Program ledger: every budget-1 compile site appears in the
        # census exactly once per compile — entry count must equal the
        # sum of the RetraceGuard receipts across the loop's programs
        # (trainer dispatch + scenario samplers + gate eval + adversary
        # rung + serving rungs). A mismatch means a compile escaped
        # attribution; the report carries both sides so the e2e can pin
        # the equality.
        ledger = obs_spine.get_ledger()
        if ledger.enabled:
            receipts = trainer.retrace_guard.count
            sampler_guard = getattr(trainer, "_sampler_guard", None)
            if sampler_guard is not None:
                receipts += sampler_guard.count
            if sebulba:
                # The slice programs carry their own budget-1 guards
                # (the Anakin guard above stays 0 — never dispatched).
                receipts += trainer.actor_guard.count
                receipts += trainer.learner_guard.count
            receipts += pipeline.gate.program.guard.count
            if pipeline.gate.adversary is not None:
                receipts += pipeline.gate.adversary.guard.count
            receipts += sum(
                c
                for per in compile_receipts.values()
                for c in per.values()
            )
            report["ledger_programs"] = len(ledger.entries())
            report["ledger_receipts"] = receipts
            report["ledger_compile_seconds_total"] = round(
                ledger.compile_seconds_total(), 3
            )
            try:
                report["ledger_census"] = str(
                    ledger.write_census(
                        Path(trainer.log_dir) / "program_ledger.json"
                    )
                )
            except OSError:
                pass
    finally:
        from marl_distributedformation_tpu.chaos import get_fault_plane

        get_fault_plane().enabled = False
        if watchdog is not None:
            watchdog.stop()
        if telemetry is not None:
            telemetry.stop()
        if frontend is not None:
            frontend.stop()
        if mesh is not None:
            mesh.stop()  # hosts + coordinator + mesh frontend
        elif router is not None:
            router.stop()
        pipeline.stop()

    if obs_enabled:
        # Leave the whole run's spans beside promotions.jsonl —
        # scripts/trace_report.py renders them Perfetto-loadable.
        try:
            report["trace_dump"] = str(
                obs_spine.get_tracer().dump(
                    Path(trainer.log_dir) / "trace_spans.json"
                )
            )
        except OSError:
            pass

    report.update(stamp)
    out = cfg.get("out")
    if out:
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
