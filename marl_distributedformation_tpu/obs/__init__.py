"""Unified tracing spine: trace-ID propagation, spans, flight recorder.

One observability substrate shared by every subsystem (docs/
observability.md has the span taxonomy and the propagation diagram):

- :class:`~.tracer.Tracer` — lock-cheap per-thread ring buffers of
  spans/events on a monotonic clock, with a process-global registry
  (:func:`get_tracer` / :func:`configure`). Recording sits strictly on
  host-side seams; graftlint rule 15 (``span-in-traced-scope``) rejects
  any span/event call reachable inside a compiled scope, so tracing can
  never perturb the budget-1 compile receipts.
- **Trace-context propagation** — an ``X-Trace-Id`` header accepted and
  echoed by the fleet frontend, carried through
  ``FleetRouter.submit -> MicroBatchScheduler -> engine dispatch``
  (batch spans link the coalesced request IDs), and a pipeline trace ID
  minted per candidate checkpoint that follows it through stream ->
  gate -> publish -> barrier commit -> first served response, so ONE
  trace reconstructs a promotion end to end (``promotions.jsonl``
  schema 2 carries ``trace_id`` + the span decomposition).
- **Exporters** (:mod:`~.export`) — Chrome trace-event JSON
  (Perfetto-loadable, ``scripts/trace_report.py``) and Prometheus text
  exposition (content-negotiated on the fleet's ``GET /v1/metrics``).
- :class:`~.flightrec.FlightRecorder` — incident-triggered last-N
  snapshots (circuit break, rollback trip, wedged-barrier abort,
  scheduler worker death, perf-regression trip) to ``flightrec-*.json``,
  so postmortems don't depend on having had logging enabled.
- :class:`~.metrics.MetricsRegistry` — the live-metrics plane: process-
  global counters/gauges/bounded-reservoir histograms recorded from
  every lane (trainer dispatch loop, pipeline gate, serving fleet) on
  lock-cheap per-thread shards, exposed as one merged Prometheus
  namespace via :class:`~.metrics.TelemetryServer` (``GET /metrics``)
  and the fleet's ``GET /v1/metrics``. graftlint rule 18
  (``metrics-in-traced-scope``) keeps recording off the compiled path.
- :class:`~.sentinel.RegressionSentinel` — compares live registry
  gauges against the newest committed ``BENCH_r*.json`` with a
  tolerance band and trip hysteresis; sustained degradation dumps a
  ``flightrec-perf_regression-*.json`` and an audit line.

This package never imports jax — it is pure host-side bookkeeping and
stays importable from the lint CLI and any frontend process.
"""

from marl_distributedformation_tpu.obs.export import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    chrome_trace,
    escape_label_value,
    prometheus_exposition,
    wants_prometheus,
)
from marl_distributedformation_tpu.obs.flightrec import (  # noqa: F401
    FlightRecorder,
)
from marl_distributedformation_tpu.obs.ledger import (  # noqa: F401
    ProgramLedger,
    ProgramRecord,
    configure_ledger,
    get_ledger,
    load_census,
    set_ledger,
)
from marl_distributedformation_tpu.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    TelemetryServer,
    configure_metrics,
    get_registry,
    set_registry,
)
from marl_distributedformation_tpu.obs.sentinel import (  # noqa: F401
    RegressionSentinel,
    Watch,
    default_watches,
    ledger_watches,
    load_bench_record,
    recovery_watches,
)
from marl_distributedformation_tpu.obs.tracer import (  # noqa: F401
    TRACE_HEADER,
    Event,
    Span,
    Tracer,
    configure,
    get_tracer,
    new_trace_id,
    sanitize_trace_id,
    self_seconds,
    set_tracer,
)

__all__ = [
    "Event",
    "FlightRecorder",
    "MetricsRegistry",
    "PROMETHEUS_CONTENT_TYPE",
    "ProgramLedger",
    "ProgramRecord",
    "RegressionSentinel",
    "Span",
    "TRACE_HEADER",
    "TelemetryServer",
    "Tracer",
    "Watch",
    "chrome_trace",
    "configure",
    "configure_ledger",
    "configure_metrics",
    "default_watches",
    "escape_label_value",
    "get_ledger",
    "get_registry",
    "get_tracer",
    "ledger_watches",
    "recovery_watches",
    "load_bench_record",
    "load_census",
    "new_trace_id",
    "set_ledger",
    "prometheus_exposition",
    "sanitize_trace_id",
    "self_seconds",
    "set_registry",
    "set_tracer",
    "wants_prometheus",
]
