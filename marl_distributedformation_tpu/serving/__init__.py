"""TPU-native policy inference serving (the north-star's missing layer).

Training produces checkpoints; until now the only inference paths were
the offline ``eval.py`` rollout harness and the per-call, unbatched
``compat.policy.LoadedPolicy.predict``. This package serves those
checkpoints to concurrent callers the way Podracer (arXiv:2104.06272)
serves actors — large fixed-shape batched inference that keeps the
accelerator saturated — with the host-side request path JaxMARL
(arXiv:2311.10090) shows becomes the bottleneck once the policy itself
is compiled:

- :class:`~.engine.BucketedPolicyEngine` — donated, jit-compiled act
  functions over a small ladder of bucketed batch shapes; arbitrary
  request sizes pad to the next bucket so each bucket compiles exactly
  once (pinned by ``analysis.guards.RetraceGuard``).
- :class:`~.scheduler.MicroBatchScheduler` — bounded request queue that
  coalesces concurrent requests within a deadline window, with
  backpressure (reject-with-retry-after) and per-request timeouts.
- :class:`~.registry.ModelRegistry` — watches a ``logs/{name}/``
  directory via ``utils.checkpoint.latest_checkpoint`` and hot-swaps new
  checkpoints atomically between batches; in-flight requests finish on
  the params they were dispatched with.
- :class:`~.metrics.ServingMetrics` — queue depth, batch occupancy,
  latency percentiles, swap count; emitted through
  ``utils.logging.MetricsLogger``.
- :class:`~.client.ServingClient` — the in-process client (used by tests
  and the ``scripts/serve_policy.py`` smoke benchmark), duck-typed over
  one scheduler or a whole fleet router.
- ``serving.fleet`` — the multi-replica layer: ``FleetRouter`` (one
  replica per local device, queue-depth routing, circuit breaking +
  failover), ``FleetReloadCoordinator`` (poll-once batch-barrier swap,
  globally step-monotonic), ``FleetFrontend`` (stdlib HTTP/JSON),
  ``FleetMetrics``, ``run_fleet_smoke``.
- :class:`~.sharded.ShardedPolicyEngine` — the big rungs over a device
  mesh slice instead of per-device replicas: partition-rule-driven
  param placement (``match_partition_rules`` /
  ``make_shard_and_gather_fns``), batch-axis request sharding, optional
  bf16 rungs. ``ShardedSpec`` plugs it into a ``FleetRouter``.
- ``serving.tenancy`` — named model lanes over one fleet:
  ``TenantDirectory`` declares lanes (env, architecture, SLO class,
  promoted dir), ``TenantFleet`` serves them — same-arch lanes share
  compiled rung executables, per-lane admission queues, per-lane
  reload coordinators with per-model step monotonicity,
  ``run_tenant_smoke`` for the isolation evidence.
- ``serving.loadgen`` / ``serving.autotune`` — the earned ladder:
  open-loop traffic replay measuring req/s AT a p95 target
  (``max_rate_at_slo``), and a deterministic ladder autotuner deriving
  rungs + coalescing window from the observed distribution
  (``autotune_ladder``). SLO classes ride admission control —
  batch-eval traffic yields to interactive under backpressure
  (``MicroBatchScheduler.submit(slo_class=...)``).
- ``serving.elastic`` — the live capacity loop: ``TraceRecorder``
  captures offered arrivals at the schedulers, ``CapacityController``
  replays the window through the same autotune DP and re-splits the
  fleet (new ladder, new replicated/sharded device split) with
  prewarm-then-commit at the fleet batch barrier.

Architecture, bucket-ladder sizing, backpressure semantics, and the
hot-reload contract are documented in ``docs/serving.md``.
"""

from marl_distributedformation_tpu.serving.autotune import (
    LadderPlan,
    autotune_ladder,
    plans_equivalent,
    replay_recorder,
)
from marl_distributedformation_tpu.serving.client import (
    ServingClient,
    backoff_s,
)
from marl_distributedformation_tpu.serving.engine import (
    DEFAULT_BUCKETS,
    BucketedPolicyEngine,
)
from marl_distributedformation_tpu.serving.elastic import (
    CapacityController,
    CapacityDecision,
)
from marl_distributedformation_tpu.serving.loadgen import (
    RequestTrace,
    TraceRecorder,
    max_rate_at_slo,
    run_load,
    synthetic_trace,
)
from marl_distributedformation_tpu.serving.metrics import ServingMetrics
from marl_distributedformation_tpu.serving.registry import ModelRegistry
from marl_distributedformation_tpu.serving.scheduler import (
    SLO_BATCH,
    SLO_INTERACTIVE,
    BackpressureError,
    MicroBatchScheduler,
    RequestTimeout,
    ServedResult,
)
from marl_distributedformation_tpu.serving.sharded import (
    ShardedPolicyEngine,
    ShardedSpec,
)
from marl_distributedformation_tpu.serving.smoke import (
    RUNG_SWEEP_TOL,
    run_rung_sweep,
    run_smoke_benchmark,
)

__all__ = [
    "BackpressureError",
    "BucketedPolicyEngine",
    "CapacityController",
    "CapacityDecision",
    "DEFAULT_BUCKETS",
    "LadderPlan",
    "MicroBatchScheduler",
    "ModelRegistry",
    "RequestTimeout",
    "RequestTrace",
    "SLO_BATCH",
    "SLO_INTERACTIVE",
    "ServedResult",
    "ServingClient",
    "ServingMetrics",
    "ShardedPolicyEngine",
    "ShardedSpec",
    "TraceRecorder",
    "autotune_ladder",
    "backoff_s",
    "max_rate_at_slo",
    "plans_equivalent",
    "replay_recorder",
    "run_load",
    "RUNG_SWEEP_TOL",
    "run_rung_sweep",
    "run_smoke_benchmark",
    "synthetic_trace",
]
