#!/usr/bin/env python
"""Serve a trained policy from its checkpoint directory.

Usage:
    # one-shot smoke benchmark against the newest checkpoint (1 JSON line)
    python scripts/serve_policy.py logs/run1 --smoke

    # long-running server: hot-reloads new checkpoints as training writes
    # them, emits serving metrics to {log_dir}/serving/metrics.jsonl
    python scripts/serve_policy.py logs/run1 --watch

    # no checkpoint yet? serve a freshly initialized policy
    python scripts/serve_policy.py --init-policy MLPActorCritic --obs-dim 8 --smoke

    # multi-replica fleet: one engine per local device, coordinated
    # hot reload, HTTP frontend on --port (0 = ephemeral, printed)
    python scripts/serve_policy.py logs/run1 --fleet --port 8100
    python scripts/serve_policy.py logs/run1 --fleet --replicas 2 --smoke

    # 2-replica fleet smoke on the CPU, asked for by name (the virtual
    # device pool is widened to one device per replica)
    JAX_PLATFORMS=cpu python scripts/serve_policy.py \\
        --init-policy MLPActorCritic --obs-dim 8 --fleet --replicas 2 --smoke

    # multi-tenant: named model lanes over ONE fleet, each lane hot-
    # reloading from its own promoted/ dir; the smoke drives every lane
    # and reports per-tenant throughput + step monotonicity
    python scripts/serve_policy.py --fleet \\
        --tenants formation-a=logs/a/promoted,formation-b=logs/b/promoted \\
        --smoke

The server is the in-process stack from
``marl_distributedformation_tpu.serving`` (bucketed compiled engine,
micro-batching scheduler, hot-reload registry — docs/serving.md); this
CLI wires it to a checkpoint directory and drives it with a synthetic
mixed-size load (``--smoke``) or leaves it serving + watching
(``--watch``, the mode a real frontend would embed). ``--fleet``
replaces the single engine with ``serving.fleet`` (router + coordinated
reload + optional HTTP frontend, docs/serving.md "Fleet");
``--tenants`` replaces the single model with named lanes over that one
fleet (``serving.tenancy``, docs/serving.md "Multi-tenant lanes").
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


def _infer_row_shape(policy) -> tuple:
    """Feature shape of one request row. Per-formation policies
    (CTDE/GNN) take whole ``(num_agents, obs_dim)`` formations as rows
    and their tower widths are post-embedding — inference from the
    kernel is wrong there, so both dims must be passed explicitly. For
    flat per-agent policies the first tower layer's kernel records the
    obs width (the same inference compat.policy.infer_hidden does for
    tower widths)."""
    if getattr(policy.model, "per_formation", False):
        raise SystemExit(
            f"policy {type(policy.model).__name__} serves whole "
            "formations: pass --obs-dim AND --agents to size a request "
            "row (row shape = (agents, obs_dim))"
        )
    inner = policy.params.get("params", {})
    kernel = inner.get("pi_0", {}).get("kernel")
    if kernel is None:
        raise SystemExit(
            "cannot infer --obs-dim from this checkpoint "
            f"(policy {type(policy.model).__name__}); pass --obs-dim"
        )
    import numpy as np

    return (int(np.shape(kernel)[0]),)


def _emit(report: dict, args) -> None:
    """The one JSON line on stdout, carrying the device it ran on and
    where the serving stack's arrays sit (called while it is still up)."""
    from marl_distributedformation_tpu.utils import device_residency

    report.update(args.device)
    report["residency_bytes"] = device_residency()
    print(json.dumps(report), flush=True)


def _build_init_policy(args):
    """A freshly initialized policy for --init-policy runs (shared by
    the single-engine and --fleet paths — one construction recipe, so
    the two can never drift)."""
    if args.obs_dim is None:
        raise SystemExit("--init-policy requires --obs-dim")
    import jax
    import jax.numpy as jnp

    from marl_distributedformation_tpu.compat.policy import (
        POLICY_REGISTRY,
        LoadedPolicy,
    )

    if args.init_policy not in POLICY_REGISTRY:
        raise SystemExit(
            f"unknown policy {args.init_policy!r}; known: "
            f"{sorted(POLICY_REGISTRY)}"
        )
    kwargs = {}
    if getattr(args, "hidden", None):
        hidden = tuple(int(w) for w in args.hidden.split(","))
        kwargs["hidden"] = hidden
    model = POLICY_REGISTRY[args.init_policy](act_dim=2, **kwargs)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, args.obs_dim))
    )
    return LoadedPolicy(
        dict(variables), policy=args.init_policy, model_kwargs=kwargs
    )


def _run_slo_bench(args) -> int:
    """The SLO-driven serving bench (--slo-bench), one JSON line.

    Three fleets on the same forced multi-device CPU (or real mesh),
    driven by the SAME open-loop request trace (serving/loadgen.py):

    1. replicated-only baseline (the PR-4 fleet shape);
    2. + f32 sharded big-rung slice (serving/sharded.py);
    3. + bf16 sharded slice — the "sharding and bf16 on" config, which
       also runs the bisection for ``req_per_sec_at_p95_slo``.

    Three design rules keep the comparison honest on a small shared
    box (each was a measured failure mode of the naive version):

    - **Thread-matched topologies.** A sharded config spends one unit
      of its worker budget on the mesh slice (``replicas - 1``
      single-device replicas + the slice), so every fleet runs the
      same number of scheduler threads — the naive "replicas + slice"
      shape oversubscribes the cores and books the scheduling penalty
      to sharding.
    - **Dedicated big-rung lane.** The slice serves ONLY the big rung
      (``min_rows = big``): big requests never queue behind the small
      stream, small requests never contend the mesh. This is the
      earned-ladder shape the autotuner picks, and the serving-layer
      claim the p95 split measures. The per-dispatch side rides the
      sharded engine's AOT executables (serving/sharded.py ``_run``),
      which on the dp=2 CPU mesh are ~13% faster than the replicated
      pjit dispatch — the compute split itself only materializes on
      real multi-chip hardware.
    - **Interleaved best-of-N.** Each config is replayed ``--slo-passes``
      times in rotated order against long-lived pre-warmed fleets, and
      the reported p95 is each config's best pass — back-to-back
      single passes book container load drift to whichever config hits
      the bad window (the PR-6 bench discipline).

    The autotuner runs on the same trace, so the report carries the
    earned ladder beside the measured one.
    """
    import numpy as np

    from marl_distributedformation_tpu.serving import (
        ShardedSpec,
        max_rate_at_slo,
        run_load,
        synthetic_trace,
    )
    from marl_distributedformation_tpu.serving.autotune import (
        autotune_ladder,
    )
    from marl_distributedformation_tpu.serving.fleet import (
        FleetRouter,
        warmup_fleet,
    )

    replicas = args.replicas or 2
    mesh_devices = args.mesh_devices or replicas
    policy = _build_init_policy(args) if args.init_policy else None
    if policy is None:
        from marl_distributedformation_tpu.compat.policy import (
            LoadedPolicy,
        )
        from marl_distributedformation_tpu.utils.checkpoint import (
            latest_checkpoint,
        )

        path = latest_checkpoint(Path(args.log_dir))
        if path is None:
            raise SystemExit(f"no checkpoint under {args.log_dir}")
        policy = LoadedPolicy.from_checkpoint(path)
    row_shape = (
        (args.agents, args.obs_dim)
        if args.obs_dim and args.agents
        else (args.obs_dim,)
        if args.obs_dim
        else _infer_row_shape(policy)
    )
    buckets = tuple(int(b) for b in args.buckets.split(","))
    big = args.big_rung
    if big not in buckets:
        raise SystemExit(
            f"--big-rung {big} must be one of the ladder rungs {buckets}"
        )
    # The slice serves the big rung only — the earned-ladder lane shape
    # (see docstring). Big rungs are ~20% of requests so the mixed
    # stream queues the replicated lanes; rate sized so the small model
    # keeps up on CPU.
    sharded_buckets = (big,)
    size_mix = ((1, 0.4), (8, 0.2), (64, 0.2), (big, 0.2))
    trace = synthetic_trace(
        args.duration, args.load_rps, seed=7, size_mix=size_mix
    )

    def _fleet(sharded):
        # Thread-matched: the slice replaces one replicated replica, so
        # every config runs `replicas` scheduler workers total.
        n = replicas if sharded is None else max(1, replicas - 1)
        return FleetRouter(
            policy,
            num_replicas=n,
            buckets=buckets,
            window_ms=args.window_ms,
            max_queue=args.queue,
            sharded=sharded,
        )

    def _spec(dtype=None):
        # window_ms=0: the dedicated lane's requests fill the rung on
        # arrival, so there is nothing to coalesce (the autotuner emits
        # exactly this as LadderPlan.sharded_window_ms for this trace).
        return ShardedSpec(
            axis_sizes={"dp": mesh_devices},
            buckets=sharded_buckets,
            min_rows=big,
            dtype=dtype,
            window_ms=0.0,
        )

    report = {
        "slo_p95_target_ms": float(args.slo_p95_ms),
        "replicas": replicas,
        "mesh_devices": mesh_devices,
        "buckets": ",".join(str(b) for b in buckets),
        "big_rung": big,
        "passes": args.slo_passes,
    }
    max_compiles = 0

    def _best(label, key, value):
        """Fold one pass's p95 into the config's best (ignoring empty
        passes — a pass with no completions at a size reports 0.0)."""
        if value <= 0:
            return
        prev = report.get(key)
        report[key] = value if prev is None or prev <= 0 else min(
            prev, value
        )

    configs = [
        ("replicated", None),
        ("sharded", _spec()),
        ("bf16", _spec("bfloat16")),
    ]
    settle = synthetic_trace(
        min(1.0, args.duration), args.load_rps, seed=11, size_mix=size_mix
    )
    with contextlib.ExitStack() as stack:
        routers = {}
        for label, spec in configs:
            router = stack.enter_context(_fleet(spec))
            warmup_fleet(router, row_shape)
            routers[label] = router
        # One unrecorded settle replay per fleet: the first open-loop
        # minutes of a fresh process run 2-4x over the steady-state
        # floor (allocator/thread-pool/frequency ramp), and booking that
        # decay to whichever config is measured first was the dominant
        # noise term in earlier versions of this bench.
        for label, _ in configs:
            run_load(routers[label], settle, row_shape, seed=11)
        # Fixed passes, then adaptive extension: while any config's best
        # p95 still improved >10% in the last round, the process hasn't
        # found its quiet-window floor yet (a noisy container minute at
        # the start must not decide the comparison) — keep going, up to
        # 4 extra rounds.
        rounds = 0
        while rounds < max(1, args.slo_passes) + 4:
            i = rounds
            before = {
                label: report.get(f"{label}_{big}_p95_ms", 0.0)
                for label, _ in configs
            }
            for label, _ in configs[i % 3:] + configs[: i % 3]:
                rep = run_load(routers[label], trace, row_shape, seed=7)
                _best(
                    label,
                    f"{label}_{big}_p95_ms",
                    rep.per_size_p95_ms.get(big, 0.0),
                )
                _best(label, f"{label}_p95_ms", rep.p95_ms)
            rounds += 1
            if rounds >= max(1, args.slo_passes):
                settled = all(
                    before[label] > 0
                    and report[f"{label}_{big}_p95_ms"]
                    > 0.9 * before[label]
                    for label, _ in configs
                )
                if settled:
                    break
        report["passes"] = rounds
        for key in list(report):
            if key.endswith("_p95_ms") and not isinstance(
                report[key], float
            ):
                report[key] = float(report[key])
        report.setdefault(f"replicated_{big}_p95_ms", 0.0)
        report.setdefault(f"sharded_{big}_p95_ms", 0.0)
        report.setdefault(f"bf16_{big}_p95_ms", 0.0)
        f32_p95 = report[f"sharded_{big}_p95_ms"]
        bf16_p95 = report[f"bf16_{big}_p95_ms"]
        report["bf16_speedup_pct"] = (
            100.0 * (f32_p95 / bf16_p95 - 1.0) if bf16_p95 > 0 else 0.0
        )

        # The capacity number: max sustained open-loop rate holding the
        # p95 target, on the full config (sharded slice + bf16 rungs
        # ON) — the same long-lived fleet the comparison measured.
        best, probes = max_rate_at_slo(
            routers["bf16"],
            row_shape,
            p95_target_ms=args.slo_p95_ms,
            lo_rps=args.load_rps / 2,
            hi_rps=args.load_rps * 8,
            probe_duration_s=min(1.0, args.duration),
            iterations=args.slo_iterations,
            seed=7,
            size_mix=size_mix,
            batch_fraction=0.1,
            probe_retries=2,
        )
        preempted = sum(
            r.scheduler.metrics.preempted_total
            for r in routers["bf16"].replicas
        )
        for router in routers.values():
            for counts in router.compile_counts().values():
                max_compiles = max(max_compiles, *counts.values())
    report["req_per_sec_at_p95_slo"] = best
    report["slo_probes"] = len(probes)
    report["max_compiles_per_rung"] = max_compiles
    report["batch_preempted_total"] = preempted

    plan = autotune_ladder(
        trace,
        p95_target_ms=args.slo_p95_ms,
        mesh_divisor=mesh_devices,
        sharded_min_rows=min(sharded_buckets),
    )
    report["autotuned"] = plan.to_dict()
    _emit(report, args)
    if report[f"sharded_{big}_p95_ms"] <= 0:
        print(
            "[serve] slo bench measured no big-rung completions — failing",
            file=sys.stderr,
        )
        return 1
    return 0


def _run_elastic_bench(args) -> int:
    """The --elastic-bench comparison: a
    shifting-mix day — interactive-heavy first half, big-rung storm
    second half — against two fleets on the same forced multi-device
    CPU mesh:

    - **static**: split + ladder autotuned on the FIRST half and then
      frozen — the fleet a pre-traffic tuner ships. The storm's
      64–256-row requests chunk through its small top rung.
    - **elastic**: boots identically, but a ``CapacityController``
      watches the live ``TraceRecorder`` and re-splits at the fleet
      batch barrier when the mix shifts (prewarm-then-commit; the
      serving interruption is ``elastic_resplit_pause_ms``, the
      barrier pause alone).

    Both fleets are measured on the storm half with the same rate
    bisection (``max_rate_at_slo``); budget-1 compile receipts and a
    ledger census diff (no program registered during the measured
    storm — every compile attributed to prewarm) ride the report.
    One JSON line to stdout.
    """
    import numpy as np  # noqa: F401 — row dtype parity with _run_slo_bench

    from marl_distributedformation_tpu.obs.ledger import get_ledger
    from marl_distributedformation_tpu.serving import (
        CapacityController,
        TraceRecorder,
        max_rate_at_slo,
        run_load,
        synthetic_trace,
    )
    from marl_distributedformation_tpu.serving.autotune import (
        autotune_ladder,
    )
    from marl_distributedformation_tpu.serving.fleet import (
        FleetReloadCoordinator,
        FleetRouter,
        warmup_fleet,
    )

    replicas = args.replicas or 2
    if not args.init_policy:
        raise SystemExit("--elastic-bench wants --init-policy + --obs-dim")
    policy = _build_init_policy(args)
    row_shape = (args.obs_dim,)
    duration = args.duration
    interactive_mix = ((1, 0.5), (2, 0.2), (4, 0.2), (8, 0.1))
    storm_mix = ((64, 0.35), (128, 0.3), (256, 0.35))
    storm_rps = max(4.0, args.load_rps / 6.0)
    interactive = synthetic_trace(
        duration, args.load_rps, seed=7, size_mix=interactive_mix
    )
    storm = synthetic_trace(
        duration, storm_rps, seed=9, size_mix=storm_mix
    )

    # The split a pre-traffic tuner ships: autotuned on the first half,
    # then frozen. The storm never informs it.
    first_half_plan = autotune_ladder(
        interactive, p95_target_ms=args.slo_p95_ms
    )
    boot_buckets = first_half_plan.buckets
    report = {
        "replicas": replicas,
        "slo_p95_target_ms": float(args.slo_p95_ms),
        "boot_buckets": ",".join(str(b) for b in boot_buckets),
        "interactive_rps": float(args.load_rps),
        "storm_rps": float(storm_rps),
    }

    def _measure_storm(router, seed):
        rep = run_load(router, storm, row_shape, seed=seed)
        best, probes = max_rate_at_slo(
            router,
            row_shape,
            p95_target_ms=args.slo_p95_ms,
            lo_rps=storm_rps / 2,
            hi_rps=storm_rps * 8,
            probe_duration_s=min(1.0, duration),
            iterations=args.slo_iterations,
            seed=seed,
            size_mix=storm_mix,
            probe_retries=2,
        )
        return rep.p95_ms, best

    with contextlib.ExitStack() as stack:
        static = stack.enter_context(
            FleetRouter(
                policy,
                num_replicas=replicas,
                buckets=boot_buckets,
                window_ms=first_half_plan.window_ms,
                max_queue=args.queue,
            )
        )
        recorder = TraceRecorder()
        elastic = stack.enter_context(
            FleetRouter(
                policy,
                num_replicas=replicas,
                buckets=boot_buckets,
                window_ms=first_half_plan.window_ms,
                max_queue=args.queue,
                trace_recorder=recorder,
            )
        )
        warmup_fleet(static, row_shape)
        warmup_fleet(elastic, row_shape)
        with tempfile.TemporaryDirectory() as empty_dir:
            coordinator = FleetReloadCoordinator(empty_dir, elastic)
            controller = CapacityController(
                elastic,
                coordinator,
                row_shape=row_shape,
                p95_target_ms=args.slo_p95_ms,
                min_requests=32,
            )
            # First half: both fleets serve the interactive mix (also
            # the fresh-process settle replay, PR-6 bench discipline).
            run_load(static, interactive, row_shape, seed=11)
            rep_i = run_load(elastic, interactive, row_shape, seed=11)
            report["elastic_interactive_p95_ms"] = rep_i.p95_ms
            controller.step()  # may retune windows; interactive-earned
            # The mix shifts: storm traffic reaches the elastic fleet,
            # the controller re-splits, prewarm-then-commit. The static
            # fleet serves the same storm on its frozen split.
            run_load(elastic, storm, row_shape, seed=13)
            resplit = controller.step()
            if resplit is None or not resplit.get("committed"):
                print(
                    f"[serve] elastic bench: storm re-split did not "
                    f"commit ({resplit}) — failing",
                    file=sys.stderr,
                )
                return 1
            # Measured storm: census diff proves no compile rides it.
            programs_before = len(get_ledger().entries())
            static_p95, static_rate = _measure_storm(static, seed=13)
            elastic_p95, elastic_rate = _measure_storm(elastic, seed=13)
            report["elastic_storm_new_programs"] = (
                len(get_ledger().entries()) - programs_before
            )
            snap = controller.snapshot()
            report["static_storm_p95_ms"] = static_p95
            report["elastic_storm_p95_ms"] = elastic_p95
            report["req_per_sec_at_p95_slo_static"] = static_rate
            report["req_per_sec_at_p95_slo_elastic"] = elastic_rate
            report["elastic_resplit_pause_ms"] = snap[
                "elastic_last_pause_ms"
            ]
            report["elastic_resplits_committed"] = snap[
                "elastic_resplits_committed"
            ]
            report["elastic_prewarm_compiles"] = snap[
                "elastic_prewarm_compiles_total"
            ]
            report["elastic_buckets"] = ",".join(
                str(b) for b in resplit["decision"]["replicated_buckets"]
                + resplit["decision"]["sharded_buckets"]
            )
            max_compiles = 0
            for router in (static, elastic):
                for counts in router.compile_counts().values():
                    if counts:
                        max_compiles = max(
                            max_compiles, *counts.values()
                        )
            report["max_compiles_per_rung"] = max_compiles
    _emit(report, args)
    if report["req_per_sec_at_p95_slo_elastic"] <= 0:
        print(
            "[serve] elastic bench: elastic fleet sustained no rate at "
            "the p95 target — failing",
            file=sys.stderr,
        )
        return 1
    return 0


def _run_fleet(args) -> int:
    """The --fleet serving path: router + coordinated reload +
    optional HTTP frontend (serving/fleet/, docs/serving.md "Fleet")."""
    from marl_distributedformation_tpu.serving.fleet import (
        FleetFrontend,
        FleetRouter,
        fleet_from_checkpoint_dir,
        run_fleet_smoke,
    )

    buckets = tuple(int(b) for b in args.buckets.split(","))
    sharded = None
    if args.sharded:
        from marl_distributedformation_tpu.serving import ShardedSpec

        sharded = ShardedSpec(
            axis_sizes=(
                {"dp": args.mesh_devices} if args.mesh_devices else None
            ),
            dtype="bfloat16" if args.bf16 else None,
        )
    recorder = None
    if args.record_trace:
        from marl_distributedformation_tpu.serving import TraceRecorder

        recorder = TraceRecorder()
    logger = None
    coordinator = None
    if args.init_policy:
        policy = _build_init_policy(args)
        router = FleetRouter(
            policy,
            num_replicas=args.replicas,
            buckets=buckets,
            window_ms=args.window_ms,
            max_queue=args.queue,
            sharded=sharded,
            trace_recorder=recorder,
        )
    elif args.log_dir:
        from marl_distributedformation_tpu.utils.logging import MetricsLogger

        logger = MetricsLogger(
            Path(args.log_dir) / "serving", run_name="fleet"
        )
        router, coordinator = fleet_from_checkpoint_dir(
            args.log_dir,
            num_replicas=args.replicas,
            buckets=buckets,
            window_ms=args.window_ms,
            max_queue=args.queue,
            poll_interval_s=args.poll_s,
            logger=logger,
            sharded=sharded,
            trace_recorder=recorder,
        )
        policy = router.policy
        print(
            f"[serve] fleet serving {type(policy.model).__name__} from "
            f"{args.log_dir} at step {coordinator.fleet_step}",
            file=sys.stderr,
        )
    else:
        raise SystemExit("need a log_dir or --init-policy (see --help)")

    if args.obs_dim:
        row_shape = (
            (args.agents, args.obs_dim) if args.agents else (args.obs_dim,)
        )
    else:
        row_shape = _infer_row_shape(policy)
    devices = {str(r.device) for r in router.replicas}
    print(
        f"[serve] fleet: {len(router.replicas)} replicas over "
        f"{len(devices)} devices, buckets {args.buckets}",
        file=sys.stderr,
    )

    frontend = None
    try:
        router.start()
        if coordinator is not None:
            coordinator.start()
        if args.port is not None:
            frontend = FleetFrontend(router, port=args.port).start()
            print(
                f"[serve] fleet frontend listening on {frontend.url}",
                file=sys.stderr,
            )
        if args.smoke or (args.port is None and not args.watch):
            report = run_fleet_smoke(
                router,
                row_shape=row_shape,
                duration_s=args.duration,
                num_clients=args.clients,
                deterministic=not args.stochastic,
                coordinator=coordinator,
            )
            report["buckets"] = ",".join(str(b) for b in buckets)
            report["replicas"] = float(len(router.replicas))
            _emit(report, args)
            if report["client_requests_ok"] == 0:
                print(
                    "[serve] fleet smoke served 0 requests — failing",
                    file=sys.stderr,
                )
                return 1
        else:
            print(
                "[serve] fleet serving; Ctrl-C to stop", file=sys.stderr
            )
            while True:
                time.sleep(10.0)
                snap = router.snapshot()
                print(
                    f"[serve] step={snap['model_step']:.0f} "
                    f"healthy={snap['fleet_healthy_replicas']:.0f}/"
                    f"{len(router.replicas)} "
                    f"routed={snap['fleet_routed_total']:.0f} "
                    f"p95={snap['latency_p95_ms']:.1f}ms",
                    file=sys.stderr,
                )
    except KeyboardInterrupt:
        print("[serve] interrupted; shutting down", file=sys.stderr)
    finally:
        if frontend is not None:
            frontend.stop()
        if coordinator is not None:
            coordinator.stop()
        router.stop()
        if logger is not None:
            logger.close()
        if recorder is not None:
            # Replayable loadgen JSONL (serving.loadgen.load_trace):
            # feed it back through run_load or autotune_ladder.
            if recorder.save(args.record_trace):
                print(
                    f"[serve] recorded {recorder.recorded_total} "
                    f"arrivals -> {args.record_trace}",
                    file=sys.stderr,
                )
            else:
                print(
                    "[serve] --record-trace saw <2 arrivals; nothing "
                    "to save",
                    file=sys.stderr,
                )
    return 0


def _parse_tenants(chunks) -> list:
    """``NAME=DIR`` pairs from repeated/comma-joined --tenants values."""
    lanes = []
    seen = set()
    for chunk in chunks:
        for item in chunk.split(","):
            item = item.strip()
            if not item:
                continue
            name, sep, directory = item.partition("=")
            if not sep or not name or not directory:
                raise SystemExit(
                    f"--tenants wants NAME=DIR pairs, got {item!r}"
                )
            if name in seen:
                raise SystemExit(f"--tenants declares {name!r} twice")
            seen.add(name)
            lanes.append((name, directory))
    if not lanes:
        raise SystemExit("--tenants got no NAME=DIR pairs")
    return lanes


def _run_tenants(args) -> int:
    """The --tenants serving path: named model lanes over ONE fleet
    (serving/tenancy/, docs/serving.md "Multi-tenant lanes"). Each
    lane's architecture is read from its own newest checkpoint, so
    same-arch lanes land in one router group (shared compiled rungs)
    and distinct archs get their own — the smoke's
    ``shared_rung_compiles`` census is the receipt."""
    from marl_distributedformation_tpu.compat.policy import (
        infer_hidden,
        load_checkpoint_raw,
    )
    from marl_distributedformation_tpu.serving.tenancy import (
        TenantDirectory,
        TenantSpec,
        run_tenant_smoke,
        tenant_fleet_from_directory,
    )
    from marl_distributedformation_tpu.utils.checkpoint import (
        latest_checkpoint,
    )

    pairs = _parse_tenants(args.tenants)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    directory = TenantDirectory()
    for name, lane_dir in pairs:
        path = latest_checkpoint(Path(lane_dir))
        if path is None:
            raise SystemExit(
                f"--tenants {name}={lane_dir}: no rl_model_*_steps"
                ".msgpack checkpoint there to serve"
            )
        raw = load_checkpoint_raw(path)
        policy_cls = raw.get("policy", "MLPActorCritic")
        hidden = infer_hidden(raw["params"]["params"], policy_cls)
        try:
            directory.add(
                TenantSpec(
                    model_id=name,
                    policy=policy_cls,
                    hidden=tuple(hidden) if hidden else (64, 64),
                    promoted_dir=str(lane_dir),
                    num_agents=args.agents,
                )
            )
        except ValueError as e:
            raise SystemExit(f"--tenants {name}: {e}") from e

    fleet = tenant_fleet_from_directory(
        directory,
        poll_interval_s=args.poll_s,
        num_replicas=args.replicas,
        buckets=buckets,
        window_ms=args.window_ms,
        max_queue=args.queue,
        watch=True,
    )
    groups = directory.arch_groups()
    print(
        f"[serve] tenant fleet: {len(directory)} lanes in "
        f"{len(groups)} arch group(s) — "
        + "; ".join(
            f"{arch}: {', '.join(s.model_id for s in specs)}"
            for arch, specs in groups.items()
        ),
        file=sys.stderr,
    )
    frontend = None
    try:
        fleet.start()
        if args.port is not None:
            # FleetFrontend duck-types over the TenantFleet: submits
            # carry model_id, /v1/metrics reports per-lane gauges.
            from marl_distributedformation_tpu.serving.fleet import (
                FleetFrontend,
            )

            frontend = FleetFrontend(fleet, port=args.port).start()
            print(
                f"[serve] tenant frontend listening on {frontend.url}",
                file=sys.stderr,
            )
        if args.smoke or (args.port is None and not args.watch):
            report = run_tenant_smoke(
                fleet,
                duration_s=args.duration,
                clients_per_lane=max(1, args.clients // len(pairs)),
                deterministic=not args.stochastic,
            )
            report["buckets"] = ",".join(str(b) for b in buckets)
            _emit(report, args)
            starved = [
                name
                for name, _ in pairs
                if report[f"model_{name}__requests_ok"] == 0
            ]
            wiggled = [
                name
                for name, _ in pairs
                if report[f"model_{name}__step_monotonic_violations"] > 0
            ]
            if starved or wiggled:
                print(
                    f"[serve] tenant smoke failing — lanes served 0: "
                    f"{starved}; lanes non-monotonic: {wiggled}",
                    file=sys.stderr,
                )
                return 1
        else:
            print(
                "[serve] tenant fleet serving; Ctrl-C to stop",
                file=sys.stderr,
            )
            while True:
                time.sleep(10.0)
                steps = fleet.lane_steps()
                print(
                    "[serve] "
                    + " ".join(
                        f"{mid}@{step}" for mid, step in sorted(steps.items())
                    )
                    + f" healthy={fleet.healthy_replicas}/"
                    f"{len(fleet.replicas)}",
                    file=sys.stderr,
                )
    except KeyboardInterrupt:
        print("[serve] interrupted; shutting down", file=sys.stderr)
    finally:
        if frontend is not None:
            frontend.stop()
        fleet.stop()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "log_dir",
        nargs="?",
        help="checkpoint directory (logs/{name}) to serve and watch",
    )
    parser.add_argument(
        "--init-policy",
        help="serve a freshly initialized policy of this class instead of "
        "a checkpoint (requires --obs-dim)",
    )
    parser.add_argument("--obs-dim", type=int, help="request row width")
    parser.add_argument(
        "--hidden",
        help="with --init-policy: comma-separated tower widths "
        "(default the model's own, 64,64) — the SLO bench widens the "
        "net so big-rung compute is non-trivial",
    )
    parser.add_argument(
        "--agents",
        type=int,
        help="agents per formation — required for per-formation policies "
        "(CTDE/GNN), whose request rows are (agents, obs_dim)",
    )
    parser.add_argument(
        "--buckets",
        default="1,8,64,512",
        help="comma-separated batch-shape ladder (default 1,8,64,512)",
    )
    parser.add_argument(
        "--window-ms", type=float, default=2.0, help="coalescing window"
    )
    parser.add_argument(
        "--queue", type=int, default=256, help="request queue bound"
    )
    parser.add_argument(
        "--poll-s", type=float, default=2.0, help="checkpoint poll cadence"
    )
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="run the mixed-size smoke benchmark and print one JSON line",
    )
    parser.add_argument(
        "--duration", type=float, default=3.0, help="smoke duration (s)"
    )
    parser.add_argument(
        "--clients", type=int, default=4, help="smoke client threads"
    )
    parser.add_argument(
        "--stochastic",
        action="store_true",
        help="sample actions instead of the deterministic mode",
    )
    parser.add_argument(
        "--scenario",
        help="perturb smoke request observations with this registered "
        "scenario's sensor-noise magnitudes (scenarios/registry.py)",
    )
    parser.add_argument(
        "--scenario-severity",
        type=float,
        default=1.0,
        help="severity scale for --scenario (default 1.0)",
    )
    parser.add_argument(
        "--watch",
        action="store_true",
        help="keep serving + hot-reloading until interrupted",
    )
    parser.add_argument(
        "--fleet",
        action="store_true",
        help="serve a multi-replica fleet (serving.fleet): one "
        "engine+scheduler per local device behind a load-aware router "
        "with coordinated hot reload",
    )
    parser.add_argument(
        "--replicas",
        type=int,
        help="fleet replica count (default: one per local device); "
        "with the CPU asked for by name (JAX_PLATFORMS=cpu) the virtual "
        "device pool is widened to match",
    )
    parser.add_argument(
        "--tenants",
        action="append",
        metavar="NAME=DIR",
        help="with --fleet: serve named model lanes over ONE fleet, "
        "each NAME hot-reloading from its own promoted checkpoint DIR "
        "(repeat the flag or comma-join pairs); the smoke drives every "
        "lane and reports per-tenant req/s + step monotonicity",
    )
    parser.add_argument(
        "--port",
        type=int,
        help="with --fleet: expose the stdlib HTTP frontend on this "
        "port (0 = ephemeral; the bound port is printed to stderr)",
    )
    parser.add_argument(
        "--sharded",
        action="store_true",
        help="with --fleet: add the mesh-backed big-rung replica "
        "(serving.sharded — partition-rule params over a dp slice of "
        "the local devices; big requests route there)",
    )
    parser.add_argument(
        "--bf16",
        action="store_true",
        help="with --sharded: serve the sharded rungs in bfloat16 "
        "(opt-in; divergence bounded by tests/bf16_budget.py)",
    )
    parser.add_argument(
        "--mesh-devices",
        type=int,
        help="dp width of the sharded mesh slice (default: the fleet "
        "replica count)",
    )
    parser.add_argument(
        "--record-trace",
        metavar="PATH",
        help="with --fleet: record every offered request arrival "
        "(rows + SLO class + inter-arrival gap, captured before "
        "admission control) and dump replayable loadgen JSONL here on "
        "shutdown — the same format synthetic_trace saves, so the "
        "recorded day replays through run_load / autotune_ladder",
    )
    parser.add_argument(
        "--elastic-bench",
        action="store_true",
        help="run the elastic-vs-static capacity bench: "
        "a shifting-mix trace against a frozen "
        "first-half-tuned fleet and a CapacityController-managed one, "
        "both measured on the storm half; one JSON line",
    )
    parser.add_argument(
        "--slo-bench",
        action="store_true",
        help="run the SLO-driven serving bench: "
        "replicated vs sharded vs bf16 under the same open-loop load "
        "trace, then bisect for req/s at the p95 target; one JSON line",
    )
    parser.add_argument(
        "--slo-p95-ms",
        type=float,
        default=50.0,
        help="p95 latency target for --slo-bench (default 50 ms)",
    )
    parser.add_argument(
        "--slo-iterations",
        type=int,
        default=5,
        help="rate-bisection steps for --slo-bench (default 5)",
    )
    parser.add_argument(
        "--slo-passes",
        type=int,
        default=4,
        help="interleaved replay passes per config for --slo-bench; "
        "each config reports its best pass (default 4, extended "
        "adaptively while any config's floor still improves)",
    )
    parser.add_argument(
        "--load-rps",
        type=float,
        default=300.0,
        help="base offered rate for the --slo-bench comparison trace",
    )
    parser.add_argument(
        "--big-rung",
        type=int,
        default=512,
        help="the rung the sharded-vs-replicated p95 comparison tracks",
    )
    parser.add_argument(
        "--obs-trace",
        choices=("on", "off"),
        default="on",
        help="obs/ tracing spine: batch spans + trace-ID propagation "
        "(default on)",
    )
    args = parser.parse_args(argv)

    from marl_distributedformation_tpu.utils import (
        announce_device,
        widen_cpu_pool,
    )

    # One device per replica / mesh row where the CPU was asked for by
    # name (before the backend starts); on an accelerator the devices
    # are what the hardware has and replicas past the count share them
    # round-robin — the fleet line says how many it got.
    bench_mode = args.slo_bench or args.elastic_bench
    widen_cpu_pool(
        max(args.replicas or (2 if bench_mode else 1), args.mesh_devices or 1)
    )
    args.device = announce_device("serve", file=sys.stderr)

    from marl_distributedformation_tpu import obs

    obs.configure(enabled=args.obs_trace == "on")

    if args.slo_bench:
        return _run_slo_bench(args)
    if args.elastic_bench:
        return _run_elastic_bench(args)

    if (args.port is not None or args.replicas is not None) and not args.fleet:
        raise SystemExit("--port/--replicas require --fleet")
    if args.record_trace and not args.fleet:
        raise SystemExit("--record-trace requires --fleet")
    if args.record_trace and args.tenants:
        raise SystemExit(
            "--record-trace records one fleet's offered stream; it "
            "does not combine with --tenants yet"
        )
    if (args.sharded or args.bf16) and not args.fleet:
        raise SystemExit("--sharded/--bf16 require --fleet")
    if args.bf16 and not args.sharded:
        raise SystemExit("--bf16 requires --sharded")
    if args.tenants:
        if not args.fleet:
            raise SystemExit("--tenants requires --fleet")
        if args.log_dir or args.init_policy:
            raise SystemExit(
                "--tenants names each lane's checkpoint dir itself; "
                "drop the positional log_dir / --init-policy"
            )
        if args.sharded or args.scenario:
            raise SystemExit(
                "--tenants does not combine with --sharded/--scenario "
                "yet (lanes + sharded big-rung is an open item)"
            )
        return _run_tenants(args)

    if args.scenario:
        # Resolve against the registry BEFORE the expensive part
        # (checkpoint load + engine warmup): a typo'd name exits cleanly
        # naming the valid entries, like every other entry point.
        from marl_distributedformation_tpu.scenarios import get_scenario

        try:
            get_scenario(args.scenario)
        except ValueError as e:
            raise SystemExit(str(e)) from e

    if args.fleet:
        if args.scenario:
            raise SystemExit(
                "--scenario perturbs the single-engine smoke only; "
                "run it without --fleet"
            )
        return _run_fleet(args)

    from marl_distributedformation_tpu.serving import (
        RUNG_SWEEP_TOL,
        BucketedPolicyEngine,
        MicroBatchScheduler,
        ModelRegistry,
        run_rung_sweep,
        run_smoke_benchmark,
    )

    registry = None
    loaded_step = 0  # checkpoint step `policy` was loaded at
    if args.init_policy:
        policy = _build_init_policy(args)
    elif args.log_dir:
        registry = ModelRegistry(
            args.log_dir, poll_interval_s=args.poll_s
        )
        policy = registry.policy
        loaded_step = registry.active_step
        print(
            f"[serve] serving {type(policy.model).__name__} from "
            f"{args.log_dir} at step {registry.active_step}",
            file=sys.stderr,
        )
    else:
        raise SystemExit("need a log_dir or --init-policy (see --help)")

    if args.obs_dim:
        row_shape = (
            (args.agents, args.obs_dim) if args.agents else (args.obs_dim,)
        )
    else:
        row_shape = _infer_row_shape(policy)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    engine = BucketedPolicyEngine(policy, buckets=buckets)

    logger = None
    if args.log_dir:
        from marl_distributedformation_tpu.utils.logging import MetricsLogger

        logger = MetricsLogger(
            Path(args.log_dir) / "serving", run_name="serving"
        )

    scheduler = MicroBatchScheduler(
        engine,
        registry=registry,
        max_queue=args.queue,
        window_ms=args.window_ms,
        logger=logger,
    )
    if registry is not None:
        registry.start()
    try:
        with scheduler:
            if args.smoke or not args.watch:
                # Every rung once, checked against LoadedPolicy.predict,
                # before the load starts (the storm below coalesces and
                # may never touch the small rungs).
                sweep = run_rung_sweep(
                    scheduler,
                    row_shape,
                    policy,
                    expect_step=loaded_step,
                )
                report = run_smoke_benchmark(
                    scheduler,
                    row_shape=row_shape,
                    duration_s=args.duration,
                    num_clients=args.clients,
                    deterministic=not args.stochastic,
                    registry=registry,
                    scenario=args.scenario,
                    scenario_severity=args.scenario_severity,
                )
                report.update(sweep)
                report["buckets"] = ",".join(str(b) for b in buckets)
                _emit(report, args)
                if report["client_requests_ok"] == 0:
                    # A smoke run that served nothing is a failure, not
                    # a report (e.g. a row shape the model rejects).
                    print(
                        "[serve] smoke served 0 requests — failing",
                        file=sys.stderr,
                    )
                    return 1
                if sweep["rung_sweep_max_abs_err"] > RUNG_SWEEP_TOL:
                    print(
                        "[serve] rung sweep disagrees with "
                        "LoadedPolicy.predict by "
                        f"{sweep['rung_sweep_max_abs_err']:.3g} "
                        f"(> {RUNG_SWEEP_TOL:.3g}) — failing",
                        file=sys.stderr,
                    )
                    return 1
            else:
                print(
                    "[serve] watching for checkpoints; Ctrl-C to stop",
                    file=sys.stderr,
                )
                while True:
                    time.sleep(10.0)
                    snap = scheduler.metrics.snapshot()
                    print(
                        f"[serve] step={registry.active_step if registry else 0} "
                        f"requests={snap['requests']:.0f} "
                        f"occupancy={snap['batch_occupancy_pct']:.1f}% "
                        f"p95={snap['latency_p95_ms']:.1f}ms",
                        file=sys.stderr,
                    )
    except KeyboardInterrupt:
        print("[serve] interrupted; shutting down", file=sys.stderr)
    finally:
        if registry is not None:
            registry.stop()
        if logger is not None:
            logger.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
