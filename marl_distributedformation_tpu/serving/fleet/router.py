"""FleetRouter: N compiled engines behind one submit surface.

Podracer (arXiv:2104.06272) scales TPU-native RL by replicating ONE
compiled program across devices behind a thin host-side dispatch layer;
this module is that layer for serving. Each replica is the whole proven
single-engine stack — ``BucketedPolicyEngine`` compiled against one
device plus its own ``MicroBatchScheduler`` worker thread — and the
router only does the three things a replica cannot do for itself:

- **Route.** Every request goes to the healthy replica with the lowest
  estimated drain time (queue depth x recent mean batch wall-clock —
  the quantity ``retry_after_s`` is already priced in). Joining the
  shortest *time* queue, not the shortest *length* queue, is what keeps
  a replica with a slow device from accumulating a latency tail.
- **Degrade.** A replica whose worker dies or whose budget-1
  RetraceGuard trips is circuit-broken: marked unhealthy, its queued
  requests transparently failed over to surviving replicas (bounded by
  ``max_failovers`` hops and the request's own deadline), and
  periodically re-probed (half-open: one routed request is the probe; a
  still-broken replica fails it over again and re-breaks). The fleet
  keeps serving at reduced width instead of dying.
- **Reject honestly.** Only when EVERY healthy replica rejects does the
  router raise fleet-level :class:`BackpressureError`, carrying the
  smallest ``retry_after_s`` any replica quoted — same contract as the
  single scheduler, so ``ServingClient`` works unchanged over a fleet.

Device placement is by params residency: each replica's weights are
``device_put`` onto its device and jit places each replica's compiled
programs there — no per-call device juggling, no sharding machinery in
the request path. The compiled path itself is untouched: the router is
strictly host-side, exactly the layer TF-Agents (arXiv:1709.02878)
identifies as where batched-inference throughput is won.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from marl_distributedformation_tpu.analysis.guards import RetraceError
from marl_distributedformation_tpu.obs import get_tracer
from marl_distributedformation_tpu.serving.engine import (
    DEFAULT_BUCKETS,
    BucketedPolicyEngine,
)
from marl_distributedformation_tpu.serving.fleet.metrics import FleetMetrics
from marl_distributedformation_tpu.serving.fleet.reload import ReplicaRegistry
from marl_distributedformation_tpu.serving.scheduler import (
    BackpressureError,
    MicroBatchScheduler,
    SchedulerStopped,
)


class NoHealthyReplicas(RuntimeError):
    """Every replica is circuit-broken: the fleet is down, not busy."""


# Exceptions that indict the REPLICA, not the request: the router breaks
# the circuit and fails the request over. Everything else (RequestTimeout,
# a ValueError for malformed rows) is the caller's own outcome and
# propagates untouched — failing over a malformed request would just
# poison a second replica's dispatch.
_REPLICA_FAULTS = (SchedulerStopped, RetraceError)


@dataclasses.dataclass
class Replica:
    """One device's serving stack plus its circuit-breaker state.

    ``kind`` is "replicated" (one full-ladder engine on one device) or
    "sharded" (the mesh-backed big-rung engine, serving/sharded.py —
    ``device`` is then the engine's param-sharding tree, which is
    exactly what the reload coordinator ``device_put``s the restored
    tree against at commit, so a swap re-places the params under the
    partition rules once, fleet-wide, at the same barrier)."""

    index: int
    device: Any
    engine: BucketedPolicyEngine
    scheduler: MicroBatchScheduler
    registry: ReplicaRegistry
    # Circuit-breaker state is owned by the router's health lock: break,
    # readmit, and re-arm all mutate under ``FleetRouter._health_lock``.
    healthy: bool = True  # graftlock: guarded-by=_health_lock
    broken_at: float = 0.0  # graftlock: guarded-by=_health_lock
    break_reason: str = ""  # graftlock: guarded-by=_health_lock
    kind: str = "replicated"
    # Tenant lanes (serving/tenancy): one ``(params, step)`` cell PER
    # model lane, each with its own batch barrier. ``registry`` then
    # aliases the first lane's cell (legacy single-model readers); the
    # lane-keyed reload coordinator commits into these directly.
    registries: Optional[Dict[str, ReplicaRegistry]] = None


class FleetRouter:
    """Queue-depth routing + circuit breaking over per-device replicas.

    Args:
      policy: a ``compat.policy.LoadedPolicy`` (shared model definition;
        each replica gets its own device-resident copy of the params).
      devices: devices to replicate over; default ``jax.local_devices()``.
      num_replicas: replica count; default one per device. More replicas
        than devices cycle over them (useful for tests; on hardware one
        replica per device is the shape that makes sense).
      max_failovers: how many times one accepted request may be re-routed
        off a broken replica before its failure surfaces to the caller.
      probe_interval_s: how long a broken replica stays out of rotation
        before a half-open probe readmits it.
      initial_step: ``model_step`` the seeded params report (the fleet
        builder passes the checkpoint's step).
      logger: optional ``MetricsLogger``; the aggregated fleet snapshot
        is emitted every ``emit_every`` routed requests.
      sharded: optional ``serving.sharded.ShardedSpec`` — adds ONE
        mesh-backed big-rung replica (partition-rule params over a dp
        mesh slice, serving/sharded.py). Requests with at least
        ``sharded.route_min_rows`` rows route there first; small
        requests never do (the small rungs stay on the cheap
        single-device replicas). A broken sharded replica fails its
        big requests over to the replicated ladder like any other
        circuit break.
      trace_recorder: optional ``loadgen.TraceRecorder`` shared by every
        replica's scheduler — the interleaved record across schedulers
        IS the fleet-wide arrival process the elastic retuner replays
        (serving/elastic) and ``--record-trace`` dumps.
      lanes: optional ``model_id`` → ``(params, step)`` mapping — turns
        every replica multi-tenant (serving/tenancy): each lane gets
        its own device-resident ``ReplicaRegistry`` cell (own batch
        barrier, own monotonic step) per replica, the scheduler runs in
        tenant mode (per-lane admission queues + per-lane dispatch
        barriers), and ``submit`` requires a ``model_id``. All lanes
        share the ONE engine per replica — the params are traced
        inputs, so same-architecture lanes reuse the same compiled rung
        executables (``policy`` supplies the shared architecture; every
        lane's params must match its tree). Not combinable with
        ``sharded`` yet (docs/serving.md "Limits / next").
      tenant_max_queue: per-lane admission bound in lanes mode
        (default ``max_queue``, applied per lane).
    """

    def __init__(
        self,
        policy: Any,
        devices: Optional[Sequence[Any]] = None,
        num_replicas: Optional[int] = None,
        buckets: Tuple[int, ...] = DEFAULT_BUCKETS,
        window_ms: float = 2.0,
        max_queue: int = 256,
        default_timeout_s: float = 10.0,
        seed: int = 0,
        max_failovers: int = 1,
        probe_interval_s: float = 1.0,
        initial_step: int = 0,
        metrics: Optional[FleetMetrics] = None,
        logger: Any = None,
        emit_every: int = 200,
        sharded: Any = None,
        lanes: Any = None,
        tenant_max_queue: Optional[int] = None,
        trace_recorder: Any = None,
    ) -> None:
        import jax

        devs = list(devices) if devices is not None else jax.local_devices()
        if not devs:
            raise ValueError("need at least one device to build a fleet")
        n = len(devs) if num_replicas is None else int(num_replicas)
        if n < 1:
            raise ValueError(f"need at least one replica, got {n}")
        if lanes is not None and sharded is not None:
            raise ValueError(
                "tenant lanes over the sharded big-rung slice are not "
                "supported yet (docs/serving.md 'Limits / next')"
            )
        if lanes is not None and not lanes:
            raise ValueError("lanes must declare at least one model lane")
        self.policy = policy
        self.lane_ids: Tuple[str, ...] = (
            tuple(lanes) if lanes is not None else ()
        )
        self.default_timeout_s = default_timeout_s
        self.max_failovers = max_failovers
        self.probe_interval_s = probe_interval_s
        self.metrics = metrics or FleetMetrics()
        self.logger = logger
        self.emit_every = emit_every
        self.trace_recorder = trace_recorder
        # Construction knobs kept for the elastic rebuild path
        # (build_replica / build_sharded_replica): a re-split builds
        # replicas the same way the constructor did, just later.
        self._devices = devs
        self._buckets = tuple(buckets)
        self._window_ms = float(window_ms)
        self._max_queue = int(max_queue)
        self._seed = int(seed)
        self._health_lock = threading.Lock()
        self._stopping = False
        self.replicas: List[Replica] = []
        for i in range(n):
            dev = devs[i % len(devs)]
            engine = BucketedPolicyEngine(
                policy, buckets=buckets, seed=seed + i, device=dev
            )
            if lanes is not None:
                # One (params, step) cell per lane, all device-resident
                # on THIS replica's device; the ONE engine serves every
                # lane (params are traced inputs — same-arch lanes share
                # its compiled rungs).
                registries = {
                    mid: ReplicaRegistry(
                        jax.device_put(lane_params, dev),
                        step=lane_step,
                        device=dev,
                    )
                    for mid, (lane_params, lane_step) in lanes.items()
                }
                registry = registries[next(iter(registries))]
                scheduler = MicroBatchScheduler(
                    engine,
                    registries=registries,
                    max_queue=max_queue,
                    tenant_max_queue=tenant_max_queue,
                    window_ms=window_ms,
                    default_timeout_s=default_timeout_s,
                    trace_recorder=trace_recorder,
                )
            else:
                registries = None
                registry = ReplicaRegistry(
                    jax.device_put(policy.params, dev),
                    step=initial_step,
                    device=dev,
                )
                scheduler = MicroBatchScheduler(
                    engine,
                    registry=registry,
                    max_queue=max_queue,
                    window_ms=window_ms,
                    default_timeout_s=default_timeout_s,
                    trace_recorder=trace_recorder,
                )
            self.replicas.append(
                Replica(
                    index=i,
                    device=dev,
                    engine=engine,
                    scheduler=scheduler,
                    registry=registry,
                    registries=registries,
                )
            )
        self.sharded_replica: Optional[Replica] = None
        self._sharded_min_rows = 0
        if sharded is not None:
            from marl_distributedformation_tpu.parallel.mesh import (
                make_mesh,
            )
            from marl_distributedformation_tpu.serving.sharded import (
                ShardedPolicyEngine,
            )

            mesh = make_mesh(
                dict(sharded.axis_sizes or {"dp": len(devs)})
            )
            sh_engine = ShardedPolicyEngine(
                policy,
                mesh,
                buckets=sharded.buckets,
                rules=sharded.rules,
                seed=seed + n,
                dtype=sharded.dtype,
            )
            # The registry cell holds a mesh-placed copy and — the key
            # move — records the param-sharding TREE as its "device":
            # the reload coordinator's per-replica
            # ``device_put(restored, registry.device)`` then re-places
            # every swap under the partition rules, once, at the same
            # fleet batch barrier as everyone else.
            # The engine already placed its own copy at construction —
            # seed the registry with THAT tree instead of sharding a
            # second mesh-resident copy (double param memory on the
            # slice is exactly what sharded serving exists to avoid;
            # both readers are read-only and a swap replaces only the
            # registry's pointer).
            sh_registry = ReplicaRegistry(
                sh_engine._params_on_mesh,
                step=initial_step,
                device=sh_engine.param_shardings,
            )
            sh_scheduler = MicroBatchScheduler(
                sh_engine,
                registry=sh_registry,
                max_queue=max_queue,
                window_ms=(
                    window_ms
                    if sharded.window_ms is None
                    else sharded.window_ms
                ),
                default_timeout_s=default_timeout_s,
                trace_recorder=trace_recorder,
            )
            self.sharded_replica = Replica(
                index=n,
                device=mesh,
                engine=sh_engine,
                scheduler=sh_scheduler,
                registry=sh_registry,
                kind="sharded",
            )
            self.replicas.append(self.sharded_replica)
            self._sharded_min_rows = sharded.route_min_rows
        # Replica indices are never reused across re-splits: metric and
        # report keys (``replica{i}_*``) stay unambiguous for the whole
        # process lifetime.
        self._next_index = len(self.replicas)  # graftlock: guarded-by=_health_lock

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "FleetRouter":
        self._stopping = False
        for r in self.replicas:
            r.scheduler.start()
        return self

    def stop(self) -> None:
        # Flag first: the drain of each scheduler fails its queued
        # futures with SchedulerStopped, and the failover callbacks must
        # not bounce those between replicas that are also shutting down.
        self._stopping = True
        for r in self.replicas:
            r.scheduler.stop()

    def __enter__(self) -> "FleetRouter":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    # -- client side -----------------------------------------------------

    def submit(
        self,
        obs: np.ndarray,
        deterministic: bool = True,
        timeout_s: Optional[float] = None,
        on_result: Optional[Any] = None,
        trace_id: Optional[str] = None,
        slo_class: str = "interactive",
        model_id: Optional[str] = None,
    ) -> Future:
        """Route one request; returns a future resolving to
        ``ServedResult`` (with ``.replica`` set). Raises
        :class:`BackpressureError` when every healthy replica is full,
        :class:`NoHealthyReplicas` when the whole fleet is broken.
        ``model_id`` names the tenant lane (required in lanes mode —
        the schedulers validate it against the declared lanes).

        ``on_result(result)``, if given, runs at resolution time INSIDE
        the serving replica's batch-barrier region — i.e. strictly
        before the reload coordinator can commit a swap. That makes it
        the race-free place to observe fleet-wide response completion
        order (the smoke storm's step-monotonicity witness); an
        observer that waits on the returned future instead can be
        preempted between resolution and its own bookkeeping. Keep it
        cheap: it runs on the dispatch path."""
        timeout = (
            self.default_timeout_s if timeout_s is None else timeout_s
        )
        deadline = time.perf_counter() + timeout
        outer: Future = Future()
        replica, inner = self._route(
            obs, deterministic, timeout_s, set(), trace_id, slo_class,
            model_id,
        )
        self._chain(
            replica, inner, outer, obs, deterministic, timeout_s,
            hops=0, tried={replica.index}, deadline=deadline,
            on_result=on_result, trace_id=trace_id, slo_class=slo_class,
            model_id=model_id,
        )
        return outer

    # -- routing ---------------------------------------------------------

    def _route(
        self,
        obs: np.ndarray,
        deterministic: bool,
        timeout_s: Optional[float],
        tried: Set[int],
        trace_id: Optional[str] = None,
        slo_class: str = "interactive",
        model_id: Optional[str] = None,
    ) -> Tuple[Replica, Future]:
        """Submit to the best healthy replica not in ``tried``; walk down
        the drain-time ordering past individually-full replicas.

        Big-rung preference: a request of at least ``sharded.min_rows``
        rows tries the mesh-backed sharded replica FIRST (that is what
        the slice exists for), then falls through to the replicated
        ladder on backpressure or a break. Small requests route to the
        sharded replica only as a LAST resort (its ladder starts at the
        big rungs, so a 1-row request there pads 64x — but serving it
        wastefully still beats a 503 when every replicated replica is
        broken or full)."""
        self._probe_broken()
        rows = int(obs.shape[0]) if hasattr(obs, "shape") else 0
        big = (
            self.sharded_replica is not None
            and rows >= self._sharded_min_rows
        )

        def _pref(r: Replica) -> int:
            if r.kind == "sharded":
                return 0 if big else 2
            return 1

        candidates = sorted(
            (
                r
                for r in self.replicas
                if r.healthy and r.index not in tried
            ),
            key=lambda r: (
                _pref(r),
                r.scheduler.estimated_drain_s(model_id),
            ),
        )
        rejections: List[BackpressureError] = []
        for r in candidates:
            if not r.scheduler.alive:
                self._break(r, "worker thread dead at routing time")
                continue
            try:
                inner = r.scheduler.submit(
                    obs, deterministic=deterministic, timeout_s=timeout_s,
                    trace_id=trace_id, slo_class=slo_class,
                    model_id=model_id,
                )
                return r, inner
            except BackpressureError as e:
                rejections.append(e)
            except ValueError:
                raise  # malformed request: the caller's problem, as-is
            except RuntimeError as e:
                # "scheduler not started" / racing a concurrent stop().
                self._break(r, f"submit failed: {e!r}")
        if rejections:
            self.metrics.record_rejected()
            raise BackpressureError(
                min(e.retry_after_s for e in rejections)
            )
        raise NoHealthyReplicas(
            f"all {len(self.replicas)} replicas are circuit-broken: "
            + "; ".join(
                f"replica{r.index}: {r.break_reason or 'unknown'}"
                for r in self.replicas
                if not r.healthy
            )
        )

    def _chain(
        self,
        replica: Replica,
        inner: Future,
        outer: Future,
        obs: np.ndarray,
        deterministic: bool,
        timeout_s: Optional[float],
        hops: int,
        tried: Set[int],
        deadline: float,
        on_result: Optional[Any] = None,
        trace_id: Optional[str] = None,
        slo_class: str = "interactive",
        model_id: Optional[str] = None,
    ) -> None:
        """Resolve ``outer`` from ``inner``, failing over replica faults
        onto a fresh replica while the hop budget and deadline allow."""

        def _done(fut: Future) -> None:
            exc = fut.exception()
            if exc is None:
                result = dataclasses.replace(
                    fut.result(), replica=replica.index
                )
                count = self.metrics.record_routed(replica.index)
                if on_result is not None:
                    on_result(result)
                outer.set_result(result)
                if (
                    self.logger is not None
                    and count % self.emit_every == 0
                ):
                    # Off the dispatch path: this callback runs inside
                    # the replica's batch-barrier region, and snapshot()
                    # walks every replica's latency window — doing that
                    # under the lock would stretch every batch AND the
                    # coordinator's commit wait.
                    threading.Thread(
                        target=self._emit_snapshot,
                        args=(count,),
                        name="fleet-metrics-emit",
                        daemon=True,
                    ).start()
                return
            if isinstance(exc, _REPLICA_FAULTS) and not self._stopping:
                self._break(replica, repr(exc))
                if (
                    hops < self.max_failovers
                    and time.perf_counter() < deadline
                ):
                    try:
                        nxt, nfut = self._route(
                            obs, deterministic, timeout_s, tried,
                            trace_id, slo_class, model_id,
                        )
                    except Exception as routing_exc:  # noqa: BLE001
                        outer.set_exception(routing_exc)
                        return
                    self.metrics.record_failover()
                    self._chain(
                        nxt, nfut, outer, obs, deterministic, timeout_s,
                        hops + 1, tried | {nxt.index}, deadline,
                        on_result=on_result, trace_id=trace_id,
                        slo_class=slo_class, model_id=model_id,
                    )
                    return
            outer.set_exception(exc)

        inner.add_done_callback(_done)

    def _emit_snapshot(self, count: int) -> None:
        try:
            self.logger.log(self.snapshot(), step=count)
        except Exception:  # noqa: BLE001 — observability never kills serving
            pass

    # -- health ----------------------------------------------------------

    def _break(self, replica: Replica, reason: str) -> None:
        with self._health_lock:
            if not replica.healthy:
                return
            replica.healthy = False
            replica.broken_at = time.monotonic()
            replica.break_reason = reason
        self.metrics.record_break()
        if not replica.scheduler.alive:
            # A DEAD worker's queued futures would wedge their callers
            # forever (nothing will ever dispatch them). Fail them with
            # SchedulerStopped now — the failover callbacks re-route
            # them to surviving replicas like any replica fault. Guarded
            # on liveness: a live worker (RetraceError break) still owns
            # and drains its own queue.
            replica.scheduler.fail_queued()
        # Circuit break = an incident: snapshot the trace ring while the
        # pre-break dispatch history is still in it (flight recorder,
        # when configured) — outside the health lock, it does file IO.
        get_tracer().incident(
            "circuit_break",
            replica=replica.index,
            reason=reason,
            healthy_replicas=self.healthy_replicas,
        )

    def _probe_broken(self) -> None:
        """Half-open probing on the routing path: a broken replica whose
        probe interval elapsed and whose worker is alive is readmitted;
        its next routed request is the real probe (failure re-breaks
        it). A dead worker can never be readmitted."""
        now = time.monotonic()
        for r in self.replicas:
            if r.healthy or now - r.broken_at < self.probe_interval_s:
                continue
            self.metrics.record_probe()
            if r.scheduler.alive:
                with self._health_lock:
                    if not r.healthy:
                        r.healthy = True
                        r.break_reason = ""
            else:
                # Re-arm under the same lock every other breaker-state
                # write holds — two routing threads probing the same
                # dead replica must not interleave with a concurrent
                # break/readmit.
                with self._health_lock:
                    r.broken_at = now  # still dead; re-check next interval

    def kill_replica(self, index: int, reason: str = "killed") -> None:
        """Stop one replica's worker (chaos hook, used by tests and the
        smoke storm). Its queued requests fail with ``SchedulerStopped``
        and the failover path re-routes them to surviving replicas."""
        # Lookup by Replica.index, not list position: after an elastic
        # re-split the two diverge (indices are never reused).
        replica = next(
            (r for r in self.replicas if r.index == index), None
        )
        if replica is None:
            raise KeyError(f"no replica with index {index}")
        self._break(replica, reason)
        replica.scheduler.stop()

    @property
    def healthy_replicas(self) -> int:
        return sum(1 for r in self.replicas if r.healthy)

    # -- elasticity (serving/elastic) ------------------------------------

    def fleet_params(self) -> Tuple[Any, int]:
        """The ``(params, step)`` the fleet currently serves — a
        replicated replica's cell when one exists (host-transferable
        single-device tree), else the sharded cell. The coordinator
        commits every cell identically, so any cell is authoritative."""
        for r in self.replicas:
            if r.kind == "replicated":
                return r.registry.active()
        return self.replicas[0].registry.active()

    def _alloc_index(self) -> int:
        with self._health_lock:
            index = self._next_index
            self._next_index += 1
            return index

    def build_replica(
        self,
        device: Any = None,
        buckets: Optional[Tuple[int, ...]] = None,
        window_ms: Optional[float] = None,
    ) -> Replica:
        """Build one UNROUTED replicated replica at the fleet's current
        ``(params, step)`` — the elastic prewarm path. The scheduler is
        constructed but NOT started and nothing routes here until the
        replica lands via ``FleetReloadCoordinator.commit_resplit``;
        the caller warms every rung (with the registry's params, the
        ``warmup_fleet`` contract) off the serving path first."""
        import jax

        if self.lane_ids:
            raise ValueError(
                "elastic re-split over tenant lanes is not supported "
                "yet (docs/serving.md 'Limits / next')"
            )
        index = self._alloc_index()
        dev = (
            device
            if device is not None
            else self._devices[index % len(self._devices)]
        )
        params, step = self.fleet_params()
        engine = BucketedPolicyEngine(
            self.policy,
            buckets=tuple(buckets) if buckets is not None else self._buckets,
            seed=self._seed + index,
            device=dev,
        )
        registry = ReplicaRegistry(
            jax.device_put(params, dev), step=step, device=dev
        )
        scheduler = MicroBatchScheduler(
            engine,
            registry=registry,
            max_queue=self._max_queue,
            window_ms=(
                self._window_ms if window_ms is None else float(window_ms)
            ),
            default_timeout_s=self.default_timeout_s,
            trace_recorder=self.trace_recorder,
        )
        return Replica(
            index=index,
            device=dev,
            engine=engine,
            scheduler=scheduler,
            registry=registry,
        )

    def build_sharded_replica(self, spec: Any) -> Replica:
        """Build one UNROUTED mesh-backed big-rung replica from a
        ``serving.sharded.ShardedSpec`` at the fleet's current
        ``(params, step)`` — same construction as the boot path, but
        the slice adopts the params the fleet serves NOW (the boot copy
        from ``policy.params`` would resurrect a stale step after any
        reload). Routing of big requests flips to the new slice only
        when ``commit_resplit`` lands it."""
        from marl_distributedformation_tpu.parallel.mesh import make_mesh
        from marl_distributedformation_tpu.serving.sharded import (
            ShardedPolicyEngine,
        )

        if self.lane_ids:
            raise ValueError(
                "elastic re-split over tenant lanes is not supported "
                "yet (docs/serving.md 'Limits / next')"
            )
        index = self._alloc_index()
        mesh = make_mesh(
            dict(spec.axis_sizes or {"dp": len(self._devices)})
        )
        engine = ShardedPolicyEngine(
            self.policy,
            mesh,
            buckets=spec.buckets,
            rules=spec.rules,
            seed=self._seed + index,
            dtype=spec.dtype,
        )
        params, step = self.fleet_params()
        # Adopt the CURRENT fleet params onto the slice (replacing the
        # boot copy — no double residency) and seed the registry from
        # the same tree, exactly like the constructor's sharded path.
        engine.adopt_params(params)
        registry = ReplicaRegistry(
            engine._params_on_mesh,
            step=step,
            device=engine.param_shardings,
        )
        scheduler = MicroBatchScheduler(
            engine,
            registry=registry,
            max_queue=self._max_queue,
            window_ms=(
                self._window_ms
                if spec.window_ms is None
                else spec.window_ms
            ),
            default_timeout_s=self.default_timeout_s,
            trace_recorder=self.trace_recorder,
        )
        return Replica(
            index=index,
            device=mesh,
            engine=engine,
            scheduler=scheduler,
            registry=registry,
            kind="sharded",
        )

    # graftlock: holds=batch_lock
    def _commit_resplit(
        self,
        add: Sequence[Replica],
        retire: Set[int],
        sharded_min_rows: Optional[int] = None,
    ) -> None:
        """Swap routing membership — coordinator-only, called from
        ``FleetReloadCoordinator.commit_resplit`` at the fleet batch
        barrier with every CURRENT replica's lock held (zero batches in
        flight anywhere). One list assignment under the health lock:
        requests racing the commit see either the old set or the new
        set, never a torn one."""
        with self._health_lock:
            kept = [r for r in self.replicas if r.index not in retire]
            self.replicas = kept + list(add)
            shards = [r for r in self.replicas if r.kind == "sharded"]
            self.sharded_replica = shards[-1] if shards else None
            if self.sharded_replica is None:
                self._sharded_min_rows = 0
            elif sharded_min_rows is not None:
                self._sharded_min_rows = int(sharded_min_rows)

    def drain_replica(
        self, replica: Replica, timeout_s: float = 10.0
    ) -> bool:
        """Drain-before-retire: wait for a DE-ROUTED replica (already
        swapped out by ``commit_resplit`` — no new submits can reach
        it) to finish its queued work and go idle, then stop its
        worker. Returns True on a clean drain; on timeout the worker
        is stopped anyway and its still-queued requests fail with
        ``SchedulerStopped``, which the normal failover path re-routes
        onto the live replicas."""
        deadline = time.perf_counter() + timeout_s
        drained = False
        while time.perf_counter() < deadline:
            sched = replica.scheduler
            if sched.queue_depth == 0 and not sched._busy:
                drained = True
                break
            time.sleep(0.002)
        replica.scheduler.stop()
        return drained

    # -- observability ---------------------------------------------------

    def snapshot(self) -> Dict[str, float]:
        """Aggregated fleet metrics (fleet/metrics.py) plus the newest
        step any replica serves (in lanes mode: the newest step any
        LANE serves, with per-lane ``model_{id}__step`` keys riding
        along — obs/export.py folds them into one ``model``-labeled
        family)."""
        snap = self.metrics.snapshot(self.replicas)
        if self.lane_ids:
            steps = self.lane_steps()
            for mid, step in steps.items():
                snap[f"model_{mid}__step"] = float(step)
                snap[f"model_{mid}__queue_depth"] = float(
                    sum(
                        r.scheduler.lane_queue_depth(mid)
                        for r in self.replicas
                        if r.registries is not None
                    )
                )
            snap["model_step"] = float(max(steps.values()))
        else:
            snap["model_step"] = float(
                max(r.registry.active_step for r in self.replicas)
            )
        return snap

    def lane_steps(self) -> Dict[str, int]:
        """Per-lane served step (lanes mode): the newest step any
        replica's cell for that lane holds — each lane is monotonic
        independently (per-model step monotonicity)."""
        return {
            mid: max(
                r.registries[mid].active_step
                for r in self.replicas
                if r.registries is not None
            )
            for mid in self.lane_ids
        }

    def compile_counts(self) -> Dict[int, Dict[int, int]]:
        """Per-replica per-rung trace counts — the fleet-wide
        compile-once receipt (every value must be <= 1)."""
        return {
            r.index: r.engine.compile_counts() for r in self.replicas
        }
