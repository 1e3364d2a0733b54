"""Live-metrics plane contract (obs/metrics.py + obs/sentinel.py):
registry concurrency, merged-namespace exposition, the telemetry
endpoint, and the perf-regression sentinel.

The registry is pure host-side bookkeeping (no jax import in obs/), so
most of these are fast unit tests; the sentinel e2e at the bottom runs
a real fused-scan trainer twice at the same seed — once healthy, once
deliberately throttled — and pins that the sentinel trips ONLY on the
throttled run, dumps the flight record, and never costs a compile
(budget-1 RetraceGuard receipt with telemetry on).
"""

import json
import re
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from marl_distributedformation_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    RegressionSentinel,
    TelemetryServer,
    Tracer,
    Watch,
    default_watches,
    get_registry,
    load_bench_record,
    prometheus_exposition,
    set_registry,
    set_tracer,
)

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Registry: recording, merging, bounds
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("reqs_total").inc()
    reg.counter("reqs_total").inc(2.0)
    reg.gauge("depth").set(3)
    for v in (1.0, 2.0, 3.0, 10.0):
        reg.histogram("lat_seconds").observe(v)
    snap = reg.snapshot()
    assert snap["reqs_total"] == 3.0
    assert snap["depth"] == 3.0
    assert snap["lat_seconds_count"] == 4.0
    assert snap["lat_seconds_sum"] == 16.0
    assert snap["lat_seconds_p50"] == 3.0  # nearest-rank on the window
    assert snap["lat_seconds_p99"] == 10.0
    assert snap["lat_seconds_p50"] <= snap["lat_seconds_p95"]


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    reg.counter("c_total").inc()
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(1.0)
    reg.record_gauges({"x": 1.0})
    assert reg.snapshot() == {}
    # Re-enabled, the same handles record again.
    reg.enabled = True
    reg.counter("c_total").inc()
    assert reg.snapshot() == {"c_total": 1.0}


def test_multithread_counts_are_exact_and_snapshots_consistent():
    """Sustained recording from 5 threads while the main thread
    snapshots concurrently: no count is ever lost, and every
    mid-flight snapshot is internally consistent (counters monotone,
    histogram count never exceeds the true total)."""
    reg = MetricsRegistry(reservoir=64)
    per_thread, n_threads = 2000, 5
    stop = threading.Event()

    def hammer(i):
        for k in range(per_thread):
            reg.counter("work_total").inc()
            reg.histogram("work_seconds").observe(float(k % 7))
            reg.gauge(f"worker{i}_progress").set(k)

    threads = [
        threading.Thread(target=hammer, args=(i,)) for i in range(n_threads)
    ]
    seen = []

    def watcher():
        while not stop.is_set():
            seen.append(reg.snapshot().get("work_total", 0.0))

    w = threading.Thread(target=watcher)
    w.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    w.join()
    total = float(per_thread * n_threads)
    snap = reg.snapshot()
    assert snap["work_total"] == total
    assert snap["work_seconds_count"] == total
    # Mid-flight observations never exceeded the true total and are
    # monotone nondecreasing (sums of per-thread monotone shards).
    assert all(v <= total for v in seen)
    assert all(b >= a for a, b in zip(seen, seen[1:]))


def test_gauge_last_write_wins_across_threads():
    reg = MetricsRegistry()
    reg.gauge("g").set(1.0)

    def late_writer():
        reg.gauge("g").set(42.0)

    t = threading.Thread(target=late_writer)
    t.start()
    t.join()
    assert reg.snapshot()["g"] == 42.0
    reg.gauge("g").set(7.0)  # main thread writes after: it wins now
    assert reg.snapshot()["g"] == 7.0


def test_many_short_lived_threads_never_lose_counts():
    """The AsyncCheckpointWriter pattern: one fresh thread per write,
    dying immediately. Dead shards fold into retired accumulators, so
    counter totals stay exact and histogram percentiles stay visible
    across far more dead threads than any bounded shard queue would
    hold — and the live shard map does not grow one entry per corpse."""
    reg = MetricsRegistry(reservoir=32)
    n_threads = 64

    def one_write(i):
        reg.counter("writes_total").inc()
        reg.histogram("write_seconds").observe(float(i))

    for i in range(n_threads):
        t = threading.Thread(target=one_write, args=(i,))
        t.start()
        t.join()
    snap = reg.snapshot()
    assert snap["writes_total"] == float(n_threads)
    assert snap["write_seconds_count"] == float(n_threads)
    assert snap["write_seconds_sum"] == float(sum(range(n_threads)))
    # Percentiles come from the bounded retired-sample pool (every
    # recording thread is dead by now).
    assert snap["write_seconds_p50"] > 0.0
    # Dead idents were swept or recycled — the shard map is bounded by
    # LIVE threads, not by the total ever seen.
    assert len(reg._shards) <= threading.active_count() + 1


def test_reservoir_resize_keeps_counter_totals():
    reg = MetricsRegistry(reservoir=8)
    reg.counter("c_total").inc(5)
    reg.reservoir = 16  # configure_metrics path: shard is retired, not lost
    reg.counter("c_total").inc(3)
    assert reg.snapshot()["c_total"] == 8.0


def test_record_gauges_folds_flat_snapshots_and_skips_annotations():
    reg = MetricsRegistry()
    reg.record_gauges(
        {"fleet_routed_total": 12, "latency_p95_ms": 3.5, "note": "text"}
    )
    snap = reg.snapshot()
    assert snap["fleet_routed_total"] == 12.0
    assert snap["latency_p95_ms"] == 3.5
    assert "note" not in snap


# ---------------------------------------------------------------------------
# Exposition: the merged namespace's line grammar
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (NaN|[+-]?Inf|[-+0-9.e]+)$"
)


def test_exposition_over_merged_namespace():
    """Registry metrics (counters, gauges, histogram percentiles) and
    serving-family keys render together: every sample parses, counters
    type as counters, percentile triples fold into ONE summary family
    with quantile labels, rung keys keep their labeled families."""
    reg = MetricsRegistry()
    reg.counter("train_iterations_total").inc(9)
    reg.gauge("train_env_steps_per_sec").set(1234.5)
    for v in (0.01, 0.02, 0.03):
        reg.histogram("train_chunk_drain_seconds").observe(v)
    snap = reg.snapshot()
    # The serving families arrive through the same flat-dict shape.
    snap.update(
        {
            "latency_p50_ms": 1.5,
            "latency_p95_ms": 2.5,
            "latency_p99_ms": 3.5,
            "rung512_f32_sharded": 1.0,
            "rung512_f32_sharded_compiles": 1.0,
            "replica0_queue_depth": 0.0,
        }
    )
    text = prometheus_exposition(snap)
    lines = text.strip().splitlines()
    samples = [ln for ln in lines if not ln.startswith("#")]
    for line in samples:
        assert _PROM_LINE.match(line), f"unparseable sample: {line!r}"
    types = {
        ln.split()[2]: ln.split()[3] for ln in lines if ln.startswith("# TYPE")
    }
    assert types["marl_train_iterations_total"] == "counter"
    assert types["marl_train_env_steps_per_sec"] == "gauge"
    # Histogram percentiles fold into one summary family.
    assert types["marl_train_chunk_drain_seconds"] == "summary"
    drain = [
        ln for ln in samples
        if ln.startswith("marl_train_chunk_drain_seconds{")
    ]
    assert {'quantile="0.5"', 'quantile="0.95"', 'quantile="0.99"'} == {
        ln[ln.index("{") + 1 : ln.index("}")] for ln in drain
    }
    # Fleet latency keys fold the same way (naming-drift fix discipline).
    assert types["marl_latency_ms"] == "summary"
    # Rung gauges keep their labeled families (pinned since PR 9).
    assert any(
        ln.startswith("marl_rung_sharded{")
        and 'rung="512"' in ln
        and 'dtype="f32"' in ln
        for ln in samples
    )
    assert any(
        ln.startswith("marl_rung_compiles{") and 'kind="sharded"' in ln
        for ln in samples
    )
    assert any(ln.startswith("marl_queue_depth{replica=") for ln in samples)


def test_exposition_folds_tenant_model_labels():
    """Per-tenant ``model_{id}__{metric}`` keys (serving/tenancy) fold
    into ONE family per metric with a ``model`` label — N lanes are one
    label dimension, not N metric names — and every rendered sample
    still parses under the exposition line grammar. Lane names carry
    the full allowed alphabet (dots, dashes, single underscores); the
    double-underscore delimiter keeps the split unambiguous."""
    snap = {
        "model_formation-a__step": 200.0,
        "model_formation-a__requests_total": 7.0,
        "model_form_b.v2__step": 100.0,
        "model_form_b.v2__requests_total": 3.0,
        "model_pursuit__queue_depth": 0.0,
        # A per-lane percentile composes BOTH folds: model + quantile
        # labels on one summary family.
        "model_pursuit__latency_p95_ms": 2.5,
        "model_step": 200.0,  # no double underscore: stays a plain gauge
    }
    text = prometheus_exposition(snap)
    lines = text.strip().splitlines()
    samples = [ln for ln in lines if not ln.startswith("#")]
    for line in samples:
        assert _PROM_LINE.match(line), f"unparseable sample: {line!r}"
    types = {
        ln.split()[2]: ln.split()[3] for ln in lines if ln.startswith("# TYPE")
    }
    # One family per metric, model-labeled; counters stay counters.
    assert types["marl_model_step"] == "gauge"
    assert types["marl_model_requests_total"] == "counter"
    assert types["marl_model_latency_ms"] == "summary"
    steps = [ln for ln in samples if ln.startswith("marl_model_step{")]
    assert {'model="formation-a"', 'model="form_b.v2"'} == {
        ln[ln.index("{") + 1 : ln.index("}")] for ln in steps
    }
    assert any(
        ln.startswith("marl_model_latency_ms{")
        and 'model="pursuit"' in ln
        and 'quantile="0.95"' in ln
        for ln in samples
    )
    # The fleet-wide max rides the same family name UNlabeled (no
    # double underscore to fold on).
    assert "marl_model_step 200.0" in samples


# ---------------------------------------------------------------------------
# TelemetryServer
# ---------------------------------------------------------------------------


def test_telemetry_server_serves_prometheus_and_json():
    reg = MetricsRegistry()
    reg.counter("ticks_total").inc(4)
    reg.gauge("train_env_steps_per_sec").set(100.0)
    srv = TelemetryServer(
        port=0, registry=reg, extra_snapshot=lambda: {"extra_gauge": 1.0}
    ).start()
    try:
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        for line in body.strip().splitlines():
            if not line.startswith("#"):
                assert _PROM_LINE.match(line), line
        assert "marl_ticks_total 4.0" in body
        assert "marl_extra_gauge 1.0" in body
        with urllib.request.urlopen(
            srv.url.replace("/metrics", "/metrics.json"), timeout=5
        ) as resp:
            snap = json.loads(resp.read())
        assert snap["ticks_total"] == 4.0
        # Unknown path is a 404, not a crash.
        try:
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/nope"), timeout=5
            )
            assert False, "expected HTTP 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()


def test_telemetry_server_survives_broken_extra_snapshot():
    reg = MetricsRegistry()
    reg.gauge("g").set(1.0)

    def broken():
        raise RuntimeError("boom")

    srv = TelemetryServer(port=0, registry=reg, extra_snapshot=broken).start()
    try:
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert b"marl_g 1.0" in resp.read()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# RegressionSentinel: bench loading, taxonomy, hysteresis
# ---------------------------------------------------------------------------


def test_load_bench_record_prefers_newest_round_and_unwraps(tmp_path):
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"parsed": {"train_env_steps_per_sec": 2.0}, "n": 2})
    )
    (tmp_path / "BENCH_r10.json").write_text(  # numeric: r10 beats r2
        json.dumps({"train_env_steps_per_sec": 10.0})
    )
    rec, src = load_bench_record(root=tmp_path)
    assert src.name == "BENCH_r10.json"
    assert rec["train_env_steps_per_sec"] == 10.0
    rec2, src2 = load_bench_record(path=tmp_path / "BENCH_r02.json")
    assert rec2["train_env_steps_per_sec"] == 2.0  # wrapper unwrapped
    assert load_bench_record(root=tmp_path / "empty") == ({}, None)


def _sentinel(record, trip_after=2, tolerance=0.5, **kwargs):
    return RegressionSentinel(
        [
            Watch(
                gauge="rate",
                bench_fields=("recorded_rate",),
                direction="min",
                tolerance=tolerance,
            )
        ],
        record=record,
        trip_after=trip_after,
        registry=MetricsRegistry(),
        tracer=Tracer(),
        **kwargs,
    )


def test_sentinel_trips_only_after_consecutive_breaches():
    s = _sentinel({"recorded_rate": 100.0}, trip_after=3)
    # limit = 50: 10 breaches, 80 does not.
    assert s.check({"rate": 10.0}) == []
    assert s.check({"rate": 10.0}) == []
    assert s.check({"rate": 80.0}) == []  # streak resets — hysteresis
    assert s.check({"rate": 10.0}) == []
    assert s.check({"rate": 10.0}) == []
    trips = s.check({"rate": 10.0})
    assert len(trips) == 1 and trips[0]["gauge"] == "rate"
    assert trips[0]["limit"] == 50.0 and trips[0]["recorded"] == 100.0
    # Latched: continued degradation does not re-dump...
    assert s.check({"rate": 10.0}) == []
    # ...until recovery re-arms the watch.
    assert s.check({"rate": 90.0}) == []
    for _ in range(2):
        s.check({"rate": 10.0})
    assert len(s.check({"rate": 10.0})) == 1
    assert len(s.trips) == 2


def test_sentinel_direction_max_guards_latency():
    s = RegressionSentinel(
        [
            Watch(
                gauge="latency_p95_ms",
                bench_fields=("serving_fleet_p95_ms",),
                direction="max",
                tolerance=0.5,
            )
        ],
        record={"serving_fleet_p95_ms": 10.0},
        trip_after=1,
        registry=MetricsRegistry(),
        tracer=Tracer(),
    )
    assert s.check({"latency_p95_ms": 14.0}) == []  # limit is 15
    assert len(s.check({"latency_p95_ms": 20.0})) == 1


def test_sentinel_missing_bench_field_taxonomy_never_trips():
    s = RegressionSentinel(
        [
            Watch("a", ("absent_field",), "min", 0.5),
            Watch("b", ("skipped_field",), "min", 0.5),
            Watch("c", ("text_field",), "min", 0.5),
        ],
        record={"skipped_field": "skipped", "text_field": "notanumber"},
        trip_after=1,
        registry=MetricsRegistry(),
        tracer=Tracer(),
    )
    for _ in range(3):
        assert s.check({"a": 0.0, "b": 0.0, "c": 0.0}) == []
    assert s.trips == []
    assert "absent" in s.missing["a"]
    assert "skipped" in s.missing["b"]
    assert "non-numeric" in s.missing["c"]
    assert s.summary()["sentinel_missing"]  # surfaced, not silent


def test_sentinel_missing_live_gauge_is_not_evidence():
    s = _sentinel({"recorded_rate": 100.0}, trip_after=2)
    assert s.check({"rate": 10.0}) == []
    for _ in range(5):
        assert s.check({}) == []  # cold gauge: streak untouched, no trip
    assert len(s.check({"rate": 10.0})) == 1  # streak was preserved


def test_sentinel_trip_dumps_flightrec_and_audit_line(tmp_path):
    tracer = Tracer(flightrec=FlightRecorder(tmp_path, last_n=64))
    tracer.event("pre-incident", detail=1)
    s = RegressionSentinel(
        [Watch("rate", ("recorded_rate",), "min", 0.5)],
        record={"recorded_rate": 100.0},
        trip_after=1,
        audit_dir=tmp_path,
        registry=MetricsRegistry(),
        tracer=tracer,
    )
    assert len(s.check({"rate": 1.0})) == 1
    dumps = list(tmp_path.glob("flightrec-perf_regression-*.json"))
    assert len(dumps) == 1
    payload = json.loads(dumps[0].read_text())
    assert payload["trigger"] == "perf_regression"
    assert payload["context"]["gauge"] == "rate"
    # The metrics snapshot rides in the dump as structured data.
    assert payload["context"]["metrics_snapshot"]["rate"] == 1.0
    # The pre-incident span history is in the record.
    assert any(r["name"] == "pre-incident" for r in payload["records"])
    audit = (tmp_path / "perf_incidents.jsonl").read_text().splitlines()
    assert len(audit) == 1
    line = json.loads(audit[0])
    assert line["event"] == "perf_regression"
    assert line["flightrec"] == str(dumps[0])
    assert line["limit"] == 50.0


def test_sentinel_reports_never_observed_watches():
    """A watch that is measurable against the record but whose live
    gauge nothing feeds must be surfaced as blind, not silently armed
    forever."""
    s = RegressionSentinel(
        [
            Watch("fed", ("f1",), "min", 0.5),
            Watch("starved", ("f2",), "min", 0.5),
        ],
        record={"f1": 100.0, "f2": 100.0},
        trip_after=2,
        registry=MetricsRegistry(),
        tracer=Tracer(),
    )
    s.check({"fed": 90.0})
    summary = s.summary()
    assert summary["sentinel_never_observed"] == ["starved"]
    assert "fed" not in summary["sentinel_never_observed"]
    s.check({"fed": 90.0, "starved": 90.0})
    assert s.summary()["sentinel_never_observed"] == []


def test_default_watches_cover_the_three_lanes():
    gauges = {w.gauge for w in default_watches()}
    assert gauges == {
        "train_env_steps_per_sec",
        "gate_eval_steps_per_sec",
        "latency_p95_ms",
    }
    with pytest.raises(ValueError):
        Watch("g", ("f",), direction="sideways")
    with pytest.raises(ValueError):
        Watch("g", (), direction="min")


# ---------------------------------------------------------------------------
# RollbackMonitor over the registry: one sampling path fleet-wide
# ---------------------------------------------------------------------------


def test_rollback_monitor_samples_the_registry_namespace():
    from marl_distributedformation_tpu.pipeline import RollbackMonitor

    reg = MetricsRegistry()
    reg.gauge("latency_p95_ms").set(5.0)
    monitor = RollbackMonitor(
        reg.snapshot, metric="latency_p95_ms", threshold=10.0,
        direction="above", trip_after=2,
    )
    assert not monitor.observe()
    reg.gauge("latency_p95_ms").set(50.0)
    assert not monitor.observe()  # first breach
    assert monitor.observe()  # second: trips — semantics unchanged
    # Any registry key is watchable now, not just fleet snapshot keys.
    reg.gauge("train_env_steps_per_sec").set(1.0)
    m2 = RollbackMonitor(
        reg.snapshot, metric="train_env_steps_per_sec", threshold=10.0,
        direction="below", trip_after=1,
    )
    assert m2.observe()


# ---------------------------------------------------------------------------
# Trainer instrumentation + the sentinel e2e (healthy vs throttled)
# ---------------------------------------------------------------------------


def _tiny_trainer(tmp_path, name, trainer_cls=None):
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    cls = trainer_cls or Trainer
    return cls(
        EnvParams(num_agents=3, max_steps=20),
        ppo=PPOConfig(n_steps=4, n_epochs=1, batch_size=24),
        config=TrainConfig(
            num_formations=4,
            # 6 chunks of 4 iterations: per iteration the budget burns
            # n_steps(4) * num_formations(4) * num_agents(3) transitions.
            total_timesteps=6 * 4 * 4 * 4 * 3,
            seed=0,
            fused_chunk=4,
            name=name,
            log_dir=str(tmp_path / name),
            save_freq=1000,
        ),
    )


def test_trainer_records_lane_metrics_into_registry(tmp_path):
    prev = set_registry(MetricsRegistry())
    try:
        trainer = _tiny_trainer(tmp_path, "metrics_plain")
        trainer.train()
        snap = get_registry().snapshot()
        assert snap["train_iterations_total"] == 24.0
        assert snap["train_chunks_total"] == 6.0
        assert snap["train_env_steps_per_sec"] > 0.0
        assert snap["train_steps_per_sec"] > 0.0
        assert snap["train_chunk_drain_seconds_count"] == 6.0
        assert snap["train_chunk_drain_seconds_p50"] >= 0.0
        # The live compile counter is the budget-1 receipt.
        assert snap["train_compiles"] == 1.0
        # Async checkpoint writer health (save_freq forced one final
        # save): queue drained, write latency observed.
        assert snap["checkpoint_writes_total"] >= 1.0
        assert snap["checkpoint_queue_depth"] == 0.0
        assert snap["checkpoint_write_seconds_count"] >= 1.0
    finally:
        set_registry(prev)


class _ThrottledTrainerMixin:
    """A deliberately slowed dispatch loop — the contended-host /
    degraded-device failure mode the sentinel exists to catch. The
    compiled program is untouched (same compile receipt); only the
    host loop drags. THROTTLE_S is set per test run, scaled off the
    measured healthy chunk time so the regression margin survives a
    loaded CI machine."""

    THROTTLE_S = 0.12

    def run_chunk(self):
        time.sleep(self.THROTTLE_S)
        return super().run_chunk()


def test_sentinel_e2e_trips_on_throttled_run_never_on_healthy(tmp_path):
    """The acceptance e2e: same-seed run pair through the REAL fused
    trainer. The healthy run's throughput sets the committed-record
    reference; the sentinel never trips on it, trips (with a flight
    record and audit line) on the throttled twin, and the budget-1
    compile receipt holds through both with telemetry ON."""
    from marl_distributedformation_tpu.train import Trainer

    # -- healthy run: establishes the recorded reference ----------------
    prev_reg = set_registry(MetricsRegistry())
    prev_tracer = set_tracer(Tracer())
    try:
        healthy = _tiny_trainer(tmp_path, "sentinel_healthy")
        healthy.train()
        healthy_snap = get_registry().snapshot()
        healthy_rate = healthy_snap["train_env_steps_per_sec"]
        assert healthy_rate > 0.0
        assert healthy.retrace_guard.count == 1
        bench_record = {"train_env_steps_per_sec_fused_scan": healthy_rate}
        sentinel = RegressionSentinel(
            default_watches(tolerance=0.5),
            record=bench_record,
            trip_after=2,
            audit_dir=tmp_path / "healthy_audit",
        )
        for _ in range(5):
            assert sentinel.check() == [], (
                "sentinel tripped on a healthy same-seed run"
            )
        assert sentinel.trips == []
    finally:
        set_registry(prev_reg)
        set_tracer(prev_tracer)

    # -- throttled run: same seed/config, dragged host loop -------------
    class ThrottledTrainer(_ThrottledTrainerMixin, Trainer):
        # 10x the healthy chunk's wall time (floor 0.12s): the throttled
        # rate lands near healthy/10, far below the 0.5*recorded limit
        # even when a loaded machine slowed the healthy run itself.
        THROTTLE_S = max(0.12, 10 * 64.0 / healthy_rate)

    flight_dir = tmp_path / "throttled_flight"
    prev_reg = set_registry(MetricsRegistry())
    prev_tracer = set_tracer(
        Tracer(flightrec=FlightRecorder(flight_dir, last_n=128))
    )
    try:
        throttled = _tiny_trainer(
            tmp_path, "sentinel_throttled", trainer_cls=ThrottledTrainer
        )
        sentinel = RegressionSentinel(
            default_watches(tolerance=0.5),
            record=bench_record,
            trip_after=2,
            audit_dir=flight_dir,
        )
        throttled.train()
        # The throttle dominates the tiny chunk: the live rate sits far
        # below half the healthy rate, so two checks trip the watch.
        live = get_registry().snapshot()["train_env_steps_per_sec"]
        assert live < 0.5 * healthy_rate, (
            f"throttle too weak to regress: {live} vs {healthy_rate}"
        )
        sentinel.check()
        trips = sentinel.check()
        assert len(trips) == 1
        assert trips[0]["gauge"] == "train_env_steps_per_sec"
        assert trips[0]["bench_field"] == "train_env_steps_per_sec_fused_scan"
        # Flight record + audit line landed.
        dumps = list(flight_dir.glob("flightrec-perf_regression-*.json"))
        assert len(dumps) == 1
        payload = json.loads(dumps[0].read_text())
        assert (
            payload["context"]["metrics_snapshot"]["train_env_steps_per_sec"]
            == live
        )
        assert (flight_dir / "perf_incidents.jsonl").exists()
        # Telemetry + throttling never cost a compile: budget-1 holds.
        assert throttled.retrace_guard.count == 1
    finally:
        set_registry(prev_reg)
        set_tracer(prev_tracer)
