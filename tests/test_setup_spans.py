"""Set-up as a layer: ``utils.profiling.host_span`` records spans with
parents in the obs tracer's ring, the compile listener counts jax's compile
events on the innermost open span, ``train.build_trainer`` and the first
dispatch are spanned, and the benchmark's seven ``setup_*`` readers and
``program_spans.clock_offset`` read them."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import train as train_entry
from benchmarks import harness, host_spans, program_spans, trace
from marl_distributedformation_tpu.obs import (
    ProgramLedger,
    Span,
    Tracer,
    self_seconds,
    set_ledger,
    set_tracer,
)
from marl_distributedformation_tpu.utils import load_config
from marl_distributedformation_tpu.utils.profiling import (
    HOST_SPANS,
    RetraceGuard,
    host_span,
    ledgered_jit,
)

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@pytest.fixture
def tracer():
    """A private tracer in the process-global slot, restored after."""
    mine = Tracer()
    previous = set_tracer(mine)
    try:
        yield mine
    finally:
        set_tracer(previous)


@pytest.fixture
def private_ledger():
    """A fresh process-global ledger, restored after."""
    mine = ProgramLedger(enabled=True, reservoir=64)
    previous = set_ledger(mine)
    try:
        yield mine
    finally:
        set_ledger(previous)


def _spans(tracer):
    return [r for r in tracer.snapshot() if r["kind"] == "span"]


def test_nested_host_spans_record_parents_and_self_time(tracer):
    with host_span("outer"):
        with host_span("middle", step_num=3):
            with host_span("inner", chunk=1):
                pass
        with host_span("second"):
            pass
    spans = {r["name"]: r for r in _spans(tracer)}
    assert spans["outer"]["parent"] is None
    assert spans["middle"]["parent"] == spans["outer"]["id"]
    assert spans["inner"]["parent"] == spans["middle"]["id"]
    assert spans["second"]["parent"] == spans["outer"]["id"]
    assert spans["middle"]["attrs"] == {"step_num": 3}
    assert spans["inner"]["attrs"] == {"chunk": 1}
    own = self_seconds(tracer.snapshot())
    children = spans["middle"]["duration_s"] + spans["second"]["duration_s"]
    assert own[spans["outer"]["id"]] == pytest.approx(
        spans["outer"]["duration_s"] - children
    )
    assert own[spans["inner"]["id"]] == spans["inner"]["duration_s"]
    assert all(v >= 0 for v in own.values())


def test_nested_intervals_count_each_instant_once(tracer):
    """Traces arrive inner first and nest in traces and lowerings: an
    enclosing interval takes over the time of those it holds."""
    with tracer.span("dispatch"):
        tracer.tally_interval("trace_s", 1.0, 1.5)
        tracer.tally_interval("trace_s", 1.0, 2.0)  # encloses the first
        tracer.tally_interval("trace_s", 3.0, 3.25)  # inside the lowering
        tracer.tally_interval("lower_s", 2.5, 4.0)
        tracer.tally("compile_s", 2.0)
    (span,) = _spans(tracer)
    assert span["counters"] == pytest.approx(
        {"trace_s": 1.0, "lower_s": 1.5, "compile_s": 2.0}
    )
    with tracer.span("nothing"):
        pass
    tracer.tally("compile_s", 1.0)  # no span open: counted nowhere
    assert "counters" not in _spans(tracer)[-1]


@pytest.fixture
def no_compile_cache():
    """The persistent cache off: a program an earlier run wrote there
    would load for real, and add a hit and take away the build that the
    fed events are counted against."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_a_cache_load_is_counted_as_a_hit_not_a_build(
    tracer, private_ledger, no_compile_cache
):
    """Fed through ``jax.monitoring`` from inside a ledgered program's
    trace: the span counts the load as ``cache_hits`` and ``cache_load_s``
    and the program's real build as one of ``programs``; the ledger keeps
    the same trace, lower and compile seconds as jax's own events and
    gains ``cache_load_seconds``."""
    durations = []

    def heard(event, duration, **_):
        durations.append((event, duration))

    def body(x):
        jax.monitoring.record_event_duration_secs(CACHE_LOAD, 0.25)
        jax.monitoring.record_event(CACHE_HIT)
        return jnp.sin(x) * 2.0

    fn = ledgered_jit(body, RetraceGuard("load"), subsystem="test", program="load")
    x = jnp.ones((4,))
    jax.monitoring.register_event_duration_secs_listener(heard)
    try:
        with host_span("fed"):
            fn(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(heard)
    (span,) = _spans(tracer)
    counters = span["counters"]
    assert counters["cache_hits"] == 1
    assert counters["cache_load_s"] == 0.25
    assert counters["programs"] == sum(e == BACKEND for e, _ in durations) >= 1
    assert counters["compile_s"] == pytest.approx(
        sum(d for e, d in durations if e == BACKEND)
    )
    (record,) = private_ledger.entries()
    assert record.timings["cache_load_seconds"] == 0.25
    for field, event in (
        ("trace_seconds", "/jax/core/compile/jaxpr_trace_duration"),
        ("lower_seconds", "/jax/core/compile/jaxpr_to_mlir_module_duration"),
        ("compile_seconds", BACKEND),
    ):
        assert record.timings[field] == pytest.approx(
            sum(d for e, d in durations if e == event)
        )

    with host_span("loaded"):  # events alone: a load, and no build
        jax.monitoring.record_event_duration_secs(CACHE_LOAD, 0.5)
        jax.monitoring.record_event(CACHE_HIT)
    assert _spans(tracer)[-1]["counters"] == {"cache_load_s": 0.5, "cache_hits": 1}


def _tiny_cfg(tmp_path):
    return load_config([
        "num_formation=4", "num_agents_per_formation=3", "n_steps=4",
        "batch_size=24", "n_epochs=2", "fused_chunk=1", "name=setup",
        f"log_dir={tmp_path / 'logs'}",
    ])


def test_build_trainer_and_first_dispatch_are_spanned(tracer, tmp_path):
    builds = []

    def heard(event, start, end, **_):
        if event == BACKEND:
            builds.append((start, end))

    trainer = train_entry.build_trainer(_tiny_cfg(tmp_path))
    jax.monitoring.register_event_time_span_listener(heard)
    try:
        for _ in range(2):
            jax.block_until_ready(trainer.run_chunk())
    finally:
        jax.monitoring.unregister_event_time_span_listener(heard)
    spans = _spans(tracer)
    assert [r["name"] for r in spans] == [  # in start order
        "setup_build_trainer", "setup_param_init", "setup_env_reset",
        "train_dispatch", "train_dispatch",
    ]
    build, init, reset, first, second = spans
    assert init["parent"] == reset["parent"] == build["id"]
    assert build["parent"] is None and first["parent"] is None
    assert build["t0"] <= init["t0"] <= reset["t0"] <= build["t1"] <= first["t0"]
    assert first["attrs"] == {"step_num": 0} and second["attrs"] == {"step_num": 1}
    inside = [
        (s, e) for s, e in builds
        if first["t0"] - 1e-3 <= s and e <= first["t1"] + 1e-3
    ]
    assert first["counters"]["programs"] == len(inside) >= 1
    assert "programs" not in (second.get("counters") or {})
    assert set(HOST_SPANS) >= {r["name"] for r in spans}


def test_ring_to_trace_offset_holds_over_three_dispatches(tracer, tmp_path):
    trainer = train_entry.build_trainer(_tiny_cfg(tmp_path))
    jax.block_until_ready(trainer.run_chunk())  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _ in range(3):
            jax.block_until_ready(trainer.run_chunk())
    finally:
        jax.profiler.stop_trace()
    host = host_spans.read_host_spans(
        trace.find_xplane(tmp_path / "trace"), HOST_SPANS
    )
    found = program_spans.clock_offset(tracer.snapshot(), host)
    assert found["pairs"] == 3  # dispatches 1-3; dispatch 0 was not traced
    assert 0 <= found["spread_s"] < 100e-6


# ----------------------------------------------------------------------
# The seven readers, on a tracer made by hand
# ----------------------------------------------------------------------

SETUP_READERS = {
    "setup_build_trainer_s": 10.0,
    "setup_param_init_s": 3.0,
    "setup_env_reset_s": 2.0,
    "setup_first_dispatch_s": 4.0,
    "setup_trace_lower_s": 0.5 + 0.25 + 1.0 + 2.0,
    "setup_compile_s": 0.75 + 1.5,
    "setup_programs": 60 + 1,
}


SECONDS = {
    "setup_build_trainer": 10.0,
    "setup_param_init": 3.0,
    "setup_env_reset": 2.0,
    "train_dispatch": 4.0,
}


def _put(tracer, name, t0, span_id, parent=None, counters=None, attrs=None):
    """A closed span appended to the ring as the tracer would record it,
    ``t0`` seconds after epoch second 1000."""
    t = tracer.epoch_to_mono(1000.0)
    tracer._ring().append(
        Span(name, t + t0, t + t0 + SECONDS[name], None, attrs, span_id,
             parent, counters)
    )


def _made_by_hand(tracer):
    """An older build, then the newest: its two children, its first
    dispatch and a later one, with the counters a listener would leave."""
    _put(tracer, "setup_build_trainer", 0.0, 1, counters={"programs": 7})
    _put(tracer, "setup_param_init", 21.0, 3, parent=2,
         counters={"lower_s": 0.25, "compile_s": 0.75, "programs": 1})
    _put(tracer, "setup_env_reset", 25.0, 4, parent=2, counters={"programs": 1})
    _put(tracer, "setup_build_trainer", 20.0, 2,
         counters={"trace_s": 0.5, "programs": 58})
    _put(tracer, "train_dispatch", 31.0, 5, attrs={"step_num": 0},
         counters={"trace_s": 1.0, "lower_s": 2.0, "compile_s": 1.5,
                   "programs": 1, "cache_hits": 1})
    _put(tracer, "train_dispatch", 40.0, 6, attrs={"step_num": 1},
         counters={"programs": 5})


@pytest.mark.parametrize("metric,value", sorted(SETUP_READERS.items()))
def test_setup_reader_reads_its_span_or_nothing(tracer, metric, value):
    read = harness.load_reader(ROOT / BENCH["paths"][0], metric)
    cell = harness.load_cell("mlp5-train-m262k", ROOT)
    with tracer.span("train_dispatch", step_num=0):
        pass  # a dispatch, but no build: a program without set-up spans
    assert read({"cell": cell}) is None
    _made_by_hand(tracer)
    cell.bench_dir = ROOT / ".bench_out" / "none"  # no trace to read
    assert read({"cell": cell}) == pytest.approx(value)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "set-up", "setup_s", "lower"
    )
    assert "workloads" not in entry  # every cell sets up
    assert entry["unit"] == ("count" if metric == "setup_programs" else "s")
