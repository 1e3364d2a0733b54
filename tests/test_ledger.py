"""Program ledger (obs/ledger.py + the analysis/guards.py seam).

The contract under test: every compile site registers exactly one
census entry per compilation (entry count == budget-1 receipt count),
cost/memory facts are present-or-explicitly-unavailable with the source
recorded, the disabled ledger is inert, dispatch histograms survive
writer-thread churn, the census renders as ``program{...}``-labeled
Prometheus families and round-trips through ``program_report.py``, the
census diff gate catches new/vanished/drifted programs, and the
RegressionSentinel's ledger watches trip the flightrec+audit machinery
on an inflated compile-time reading.
"""

import json
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.analysis.guards import (
    RetraceError,
    RetraceGuard,
    ledgered_jit,
    register_aot_program,
    sample_device_watermark,
)
from marl_distributedformation_tpu.obs.export import prometheus_exposition
from marl_distributedformation_tpu.obs.ledger import (
    ANALYSIS_SOURCES,
    CENSUS_SCHEMA,
    ProgramLedger,
    get_ledger,
    load_census,
    sanitize_key,
    set_ledger,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def private_ledger():
    """A fresh process-global ledger per test, restored afterwards."""
    previous = set_ledger(ProgramLedger(enabled=True, reservoir=64))
    try:
        yield get_ledger()
    finally:
        set_ledger(previous)


def _record_invariants(rec):
    """Present-or-explicitly-unavailable: the record always says which
    analysis path produced (or failed to produce) its facts."""
    assert rec.analysis_source in ANALYSIS_SOURCES
    if rec.analysis_source in ("executable", "aot"):
        # Full facts: the compiled executable answered.
        assert rec.facts.get("argument_bytes") is not None
        assert rec.facts.get("temp_bytes") is not None
    elif rec.analysis_source == "lowered":
        # Pre-compile estimates: cost yes, memory footprint no.
        assert rec.facts.get("flops") is not None
    else:
        assert rec.analysis_error, (
            "an unavailable record must say why"
        )


# ---------------------------------------------------------------------------
# Core seam semantics
# ---------------------------------------------------------------------------


def test_sanitize_key():
    assert sanitize_key("Trainer.Train Iteration") == "trainer_train_iteration"
    assert sanitize_key("__x__") == "x"
    assert sanitize_key("???") == "program"


def test_disabled_ledger_is_inert(private_ledger):
    private_ledger.enabled = False
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: x * 2.0, guard, subsystem="test", program="inert"
    )
    out = fn(jnp.ones((4,)))
    np.testing.assert_array_equal(np.asarray(out), 2.0 * np.ones(4))
    assert private_ledger.entries() == []
    assert private_ledger.snapshot() == {}
    assert (
        private_ledger.register(name="x", subsystem="y") is None
    )
    private_ledger.dispatch("x", 0.1)  # no-op, no crash
    private_ledger.record_watermark(123.0)
    assert private_ledger.snapshot() == {}
    assert sample_device_watermark(force=True) is None


def test_one_entry_per_compile_and_dispatch_histograms(private_ledger):
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: jnp.tanh(x @ x).sum(),
        guard,
        subsystem="test",
        program="one_compile",
    )
    for _ in range(5):
        fn(jnp.ones((8, 8)))
    entries = private_ledger.entries()
    assert len(entries) == 1 == guard.count
    rec = entries[0]
    assert rec.key == "test_one_compile"
    assert rec.subsystem == "test"
    assert "float32[8,8]" in rec.fingerprint
    _record_invariants(rec)
    snap = private_ledger.snapshot()
    assert snap["ledger_programs_total"] == 1.0
    # Steady-state dispatches only: the compiling call is a build
    # event (first_dispatch_seconds), never a latency sample.
    assert snap["program_test_one_compile_dispatches_total"] == 4.0
    assert snap["program_test_one_compile_dispatch_seconds_count"] == 4.0
    assert snap["program_test_one_compile_dispatch_seconds_p50"] > 0.0
    assert snap["ledger_compile_seconds_total"] > 0.0
    # Build timings landed (monitoring attribution or first-call wall).
    assert rec.timings["first_dispatch_seconds"] > 0.0


def test_two_signatures_two_entries(private_ledger):
    guard = RetraceGuard("t")  # count-only
    fn = ledgered_jit(
        lambda x: x.sum(), guard, subsystem="test", program="poly"
    )
    fn(jnp.ones((4,)))
    fn(jnp.ones((16,)))
    fn(jnp.ones((16,)))
    entries = private_ledger.entries()
    assert len(entries) == 2 == guard.count
    assert {e.key for e in entries} == {"test_poly", "test_poly_2"}
    # One shared dispatch histogram under the stable wrapper key
    # (compiling calls excluded: 3 calls, 2 compiles, 1 dispatch).
    snap = private_ledger.snapshot()
    assert snap["program_test_poly_dispatches_total"] == 1.0


def test_results_bitwise_identical_ledger_on_off(private_ledger):
    def f(x):
        return jnp.sin(x @ x) + 0.5

    x = jnp.linspace(0.0, 1.0, 64, dtype=jnp.float32).reshape(8, 8)
    on = ledgered_jit(
        f, RetraceGuard("on"), subsystem="test", program="parity_on"
    )(x)
    private_ledger.enabled = False
    off = ledgered_jit(
        f, RetraceGuard("off"), subsystem="test", program="parity_off"
    )(x)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))


def test_budget_still_enforced_and_failed_trace_unregistered(
    private_ledger,
):
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: x * 3.0, guard, subsystem="test", program="budget"
    )
    fn(jnp.ones((4,)))
    with pytest.raises(RetraceError):
        fn(jnp.ones((5,)))  # shape drift: the budget must still fire
    # The over-budget ATTEMPT is counted (existing guard semantics)
    # but produced no program — the census stays at one entry.
    assert len(private_ledger.entries()) == 1


def test_donation_map_recorded(private_ledger):
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda s, x: (s + x, x),
        guard,
        subsystem="test",
        program="donated",
        donate_argnums=(0,),
    )
    fn(jnp.zeros((4,)), jnp.ones((4,)))
    (rec,) = private_ledger.entries()
    assert rec.donate_argnums == (0,)


def test_dispatch_concurrency_and_dead_thread_fold(private_ledger):
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: x + 1.0, guard, subsystem="test", program="threads"
    )
    fn(jnp.ones((4,)))  # compile once on the main thread
    per_thread, n_threads = 40, 5

    def worker():
        for _ in range(per_thread):
            fn(jnp.ones((4,)))

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Dead writer threads' shards fold into retired accumulators:
    # totals stay exact after every writer is gone.
    snap = private_ledger.snapshot()
    assert snap["program_test_threads_dispatches_total"] == float(
        per_thread * n_threads
    )
    assert snap["program_test_threads_dispatch_seconds_count"] == float(
        per_thread * n_threads
    )
    assert guard.count == 1 and len(private_ledger.entries()) == 1


def test_watermark_gauges(private_ledger):
    private_ledger.record_watermark(100.0)
    private_ledger.record_watermark(500.0)
    private_ledger.record_watermark(200.0)
    snap = private_ledger.snapshot()
    assert snap["device_memory_bytes_in_use"] == 200.0
    assert snap["device_memory_watermark_bytes"] == 500.0
    # The jax-side sampler answers on this backend and only raises the
    # watermark. Keep a device array alive so the CPU fallback (summed
    # live buffers) has something to count.
    keep = jnp.ones((128,))
    live = sample_device_watermark(force=True)
    del keep
    assert live is not None and live > 0.0
    assert (
        private_ledger.snapshot()["device_memory_watermark_bytes"]
        >= 500.0
    )


def test_aot_registration(private_ledger):
    def f(x):
        return (x * 2.0).sum()

    lowered = jax.jit(f).lower(jnp.ones((8,)))
    compiled = lowered.compile()
    key = register_aot_program(
        name="aot_prog",
        subsystem="test",
        compiled=compiled,
        fingerprint="f32[8]",
        timings={"lower_seconds": 0.01, "compile_seconds": 0.5},
    )
    assert key == "test_aot_prog"
    (rec,) = private_ledger.entries()
    assert rec.analysis_source == "aot"
    _record_invariants(rec)
    assert rec.timings["compile_seconds"] == 0.5
    private_ledger.dispatch(key, 0.002)
    snap = private_ledger.snapshot()
    assert snap["program_test_aot_prog_dispatches_total"] == 1.0


# ---------------------------------------------------------------------------
# Compile-site coverage: serving rungs + trainer/samplers
# ---------------------------------------------------------------------------


def test_serving_rungs_register(private_ledger):
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.models import MLPActorCritic
    from marl_distributedformation_tpu.serving import BucketedPolicyEngine

    model = MLPActorCritic(act_dim=2, hidden=(16,))
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 6)))
    policy = LoadedPolicy(dict(variables), model_kwargs={"hidden": (16,)})
    engine = BucketedPolicyEngine(policy, buckets=(1, 4))
    obs = np.zeros((3, 6), np.float32)  # pads to rung 4
    engine.act(obs)
    engine.act(obs)  # steady-state dispatch on the warm rung
    engine.act(np.zeros((1, 6), np.float32))  # rung 1
    receipts = sum(engine.compile_counts().values())
    entries = private_ledger.entries()
    assert len(entries) == receipts == 2
    keys = {e.key for e in entries}
    assert keys == {"serving_act_rung1_f32", "serving_act_rung4_f32"}
    for rec in entries:
        _record_invariants(rec)
    snap = private_ledger.snapshot()
    assert snap["program_serving_act_rung4_f32_dispatches_total"] >= 1.0


def test_trainer_and_samplers_register(private_ledger, tmp_path):
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.scenarios import (
        ScenarioSchedule,
        ScenarioStage,
    )
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    trainer = Trainer(
        EnvParams(num_agents=3),
        ppo=PPOConfig(n_steps=8, batch_size=8, n_epochs=1),
        config=TrainConfig(
            num_formations=4,
            checkpoint=False,
            use_wandb=False,
            name="ledger_t",
            log_dir=str(tmp_path),
            guard_retraces=1,
        ),
        scenario_schedule=ScenarioSchedule(
            stages=(
                ScenarioStage(
                    rollouts=8, scenarios=("clean",), severity=0.0
                ),
            )
        ),
    )
    for _ in range(2):
        trainer.run_iteration()
    receipts = trainer.retrace_guard.count + trainer._sampler_guard.count
    entries = private_ledger.entries()
    assert len(entries) == receipts
    by_subsystem = {e.subsystem for e in entries}
    assert by_subsystem == {"trainer", "scenarios"}
    train_rec = next(e for e in entries if e.subsystem == "trainer")
    assert train_rec.donate_argnums == (0, 1)
    _record_invariants(train_rec)
    # The budget-1 receipt holds with the ledger ON.
    assert trainer.retrace_guard.count == 1
    snap = private_ledger.snapshot()
    assert (
        snap["program_trainer_train_iteration_dispatches_total"] == 1.0
    )


# ---------------------------------------------------------------------------
# TraceWindow capture audit
# ---------------------------------------------------------------------------


def test_trace_window_emits_capture_audit_line(private_ledger, tmp_path):
    from marl_distributedformation_tpu.utils.profiling import TraceWindow

    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: (x * 2.0).sum(),
        guard,
        subsystem="test",
        program="profiled",
    )
    window = TraceWindow(str(tmp_path), enabled=True, count=2, skip=1)
    for _ in range(4):
        window.before_dispatch()
        out = fn(jnp.ones((8,)))
        window.after_dispatch(out)
    assert window.captured
    audit = tmp_path / "profile" / TraceWindow.AUDIT_NAME
    assert audit.exists()
    (line,) = [
        json.loads(ln) for ln in audit.read_text().splitlines() if ln
    ]
    assert line["event"] == "profile_capture"
    assert line["completed"] is True
    assert line["dispatches_traced"] == 2
    assert line["trace_dir"].endswith("profile")
    # The window's program attribution: exactly the dispatches that ran
    # while the trace was open.
    assert line["programs"] == {"test_profiled": 2}


# ---------------------------------------------------------------------------
# Prometheus family grammar
# ---------------------------------------------------------------------------


def test_program_prometheus_families(private_ledger):
    guard = RetraceGuard("t", max_traces=1)
    fn = ledgered_jit(
        lambda x: (x @ x).sum(),
        guard,
        subsystem="gramm",
        program="prog",
    )
    for _ in range(3):
        fn(jnp.ones((8, 8)))
    private_ledger.record_watermark(4096.0)
    text = prometheus_exposition(private_ledger.snapshot())
    # Per-program facts fold into ONE labeled family per field.
    assert "# TYPE marl_program_flops gauge" in text
    assert 'marl_program_flops{program="gramm_prog"} ' in text
    # Dispatch percentiles fold into a summary family with BOTH labels.
    assert "# TYPE marl_program_dispatch_seconds summary" in text
    assert (
        'marl_program_dispatch_seconds{program="gramm_prog",'
        'quantile="0.5"} ' in text
    )
    # Counters keep counter typing under the fold.
    assert "# TYPE marl_program_dispatches_total counter" in text
    assert (
        'marl_program_dispatches_total{program="gramm_prog"} 2.0'
        in text
    )
    # Aggregates ride beside them.
    assert "marl_ledger_programs_total 1.0" in text
    assert "marl_device_memory_watermark_bytes 4096.0" in text
    # Every line parses under the exposition grammar.
    import re

    line_re = re.compile(
        r"^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
        r"(?:counter|gauge|summary|histogram))$"
        r"|^[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})? "
        r"(?:[-+]?(?:\d+\.?\d*(?:e[-+]?\d+)?|Inf|NaN))$",
        re.IGNORECASE,
    )
    for line in text.strip().splitlines():
        assert line_re.match(line), f"unparseable line: {line!r}"


def test_merged_namespaces_carry_ledger(private_ledger):
    """TelemetryServer and the sentinel's default snapshot both see the
    ledger families without explicit wiring."""
    from marl_distributedformation_tpu.obs.metrics import (
        MetricsRegistry,
        TelemetryServer,
    )

    private_ledger.register(
        name="p", subsystem="s", facts={"flops": 42.0}
    )
    server = TelemetryServer(registry=MetricsRegistry())
    snap = server._snapshot()
    assert snap["program_s_p_flops"] == 42.0
    assert snap["ledger_programs_total"] == 1.0


# ---------------------------------------------------------------------------
# Census: report round-trip + diff gate
# ---------------------------------------------------------------------------


def _census_with(ledger):
    ledger.register(
        name="big", subsystem="train",
        facts={"flops": 1e9, "bytes_accessed": 1e8, "temp_bytes": 1e6,
               "argument_bytes": 5e5, "output_bytes": 1e5},
        timings={"compile_seconds": 3.0},
        analysis_source="executable",
    )
    ledger.register(
        name="small", subsystem="serve",
        facts={"flops": 1e6, "bytes_accessed": 1e5},
        timings={"compile_seconds": 0.2},
        analysis_source="lowered",
    )
    ledger.dispatch("train_big", 0.01)
    return ledger


def test_census_write_load_and_report_round_trip(
    private_ledger, tmp_path
):
    _census_with(private_ledger)
    path = private_ledger.write_census(tmp_path / "program_ledger.json")
    census = load_census(path)
    assert census["schema"] == CENSUS_SCHEMA
    assert census["totals"]["programs"] == 2
    assert census["totals"]["compile_seconds"] == pytest.approx(3.2)
    keys = [p["key"] for p in census["programs"]]
    assert keys == ["train_big", "serve_small"]
    big = census["programs"][0]
    assert big["dispatches_total"] == 1.0
    # The report renders and ranks it.
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import program_report
    finally:
        sys.path.pop(0)
    summary = program_report.summarize(census, top=5)
    assert summary["program_count"] == 2
    assert [
        p["key"] for p in summary["top"]["flops"]
    ] == ["train_big", "serve_small"]
    # dispatch_p95 ranking only includes programs that dispatched.
    assert [
        p["key"] for p in summary["top"]["dispatch_p95"]
    ] == ["train_big"]
    text = program_report.render_text(census, top=5)
    assert "train_big" in text and "top by compile" in text
    # A truncated file is a clean error, not a crash.
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    with pytest.raises(ValueError):
        load_census(bad)


# ---------------------------------------------------------------------------
# Sentinel: ledger watches trip the same machinery
# ---------------------------------------------------------------------------


def test_sentinel_trips_on_inflated_compile_seconds(
    private_ledger, tmp_path
):
    from marl_distributedformation_tpu.obs.metrics import MetricsRegistry
    from marl_distributedformation_tpu.obs.sentinel import (
        RegressionSentinel,
        ledger_watches,
    )
    from marl_distributedformation_tpu.obs.flightrec import FlightRecorder
    from marl_distributedformation_tpu.obs.tracer import Tracer

    tracer = Tracer(flightrec=FlightRecorder(tmp_path, last_n=64))
    sentinel = RegressionSentinel(
        ledger_watches(tolerance=0.5),
        record={
            "ledger_compile_seconds_max": 10.0,
            "device_memory_watermark_bytes": 1e6,
        },
        trip_after=2,
        audit_dir=tmp_path,
        registry=MetricsRegistry(),
        tracer=tracer,
    )
    healthy = {
        "ledger_compile_seconds_max": 11.0,
        "device_memory_bytes_in_use": 9e5,
    }
    assert sentinel.check(healthy) == []
    assert sentinel.check(healthy) == []
    inflated = {
        "ledger_compile_seconds_max": 40.0,  # > 10 * 1.5
        "device_memory_bytes_in_use": 9e5,
    }
    assert sentinel.check(inflated) == []  # streak 1 of 2
    trips = sentinel.check(inflated)
    assert len(trips) == 1
    assert trips[0]["gauge"] == "ledger_compile_seconds_max"
    # The trip wrote the audit line + flight record.
    audit = tmp_path / RegressionSentinel.AUDIT_NAME
    assert audit.exists()
    (line,) = [
        json.loads(ln) for ln in audit.read_text().splitlines() if ln
    ]
    assert line["event"] == "perf_regression"
    assert line["bench_field"] == "ledger_compile_seconds_max"
    dumps = list(tmp_path.glob("flightrec-perf_regression-*.json"))
    assert dumps, "the trip must dump a flight record"
    # A recovered sample re-arms the watch — the reason the gauge is
    # the per-program MAX, not a lifetime-cumulative total.
    assert sentinel.check(healthy) == []
    assert not sentinel._state["ledger_compile_seconds_max"].tripped


def test_sentinel_default_snapshot_merges_ledger(private_ledger):
    from marl_distributedformation_tpu.obs.metrics import MetricsRegistry
    from marl_distributedformation_tpu.obs.sentinel import (
        RegressionSentinel,
        ledger_watches,
    )
    from marl_distributedformation_tpu.obs.tracer import Tracer

    private_ledger.register(
        name="p", subsystem="s", timings={"compile_seconds": 2.0}
    )
    sentinel = RegressionSentinel(
        ledger_watches(),
        record={"ledger_compile_seconds_max": 2.0},
        registry=MetricsRegistry(),  # empty: the ledger is the source
        tracer=Tracer(enabled=False),
    )
    sentinel.check()  # no explicit snapshot: must merge the ledger
    summary = sentinel.summary()
    assert (
        "ledger_compile_seconds_max"
        not in summary["sentinel_never_observed"]
    )
