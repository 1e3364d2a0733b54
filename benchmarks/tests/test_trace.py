"""The reduction from trace events to numbers, on events made by hand."""

import pytest

from benchmarks import trace
from benchmarks.trace import Event

HLO = (
    '%fusion.7 = f32[64,1048576]{1,0:T(8,128)} fusion(f32[8,1048576]{1,0} %p0), '
    'kind=kOutput, calls=%fused_computation.7, metadata={op_name='
    '"jit(train_iteration)/jit(main)/while/body/ppo_update/while/body/dot_general" '
    'source_file="ppo.py" source_line=120}'
)


def test_parse_op_reads_name_scope_and_opcode():
    name, scope, opcode = trace.parse_op(HLO)
    assert name == "fusion.7"
    assert opcode == "fusion"
    assert "ppo_update" in scope.split("/") and "rollout" not in scope.split("/")
    tuple_out = "%while.3 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), body=%b"
    assert trace.parse_op(tuple_out)[2] == "while"
    assert trace.parse_op("%copy.1 = u32[2]{0} copy(u32[2]{0} %key.1)")[1] == ""


def test_union_counts_overlap_once():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0


def test_self_time_takes_nested_ops_out_of_a_loop():
    loop = Event("while.1", "jit/rollout", "while", 0, 100)
    a = Event("fusion.1", "jit/rollout/policy", "fusion", 10, 40)
    b = Event("fusion.2", "jit/rollout/env_step", "fusion", 50, 90)
    selfs = {ev.name: (t, parent) for ev, t, parent in trace.nest([b, loop, a])}
    assert selfs == {"while.1": (30, -1), "fusion.1": (30, 0), "fusion.2": (40, 0)}


def _read(devices, host=()):
    return {"devices": devices, "host": list(host)}


def test_reduce_scopes_gaps_idle_and_breakdown():
    ops = [
        Event("while.1", "jit/rollout", "while", 0, 100),
        Event("fusion.1", "jit/rollout/policy", "fusion", 0, 40),
        Event("fusion.2", "jit/rollout/env_step", "fusion", 40, 100),
        Event("while.2", "jit/ppo_update/while", "while", 100, 400),
        Event("fusion.3", "", "fusion", 100, 400),  # no metadata: the loop's scope
        Event("while.2", "jit/ppo_update/while", "while", 600, 900),
        Event("fusion.3", "", "fusion", 600, 900),
        Event("custom-call.5", "jit/rollout/env_step/pallas_call", "custom-call", 900, 1000),
    ]
    modules = [Event("jit_train", "", "module", 0, 400), Event("jit_train", "", "module", 600, 1000)]
    host = [Event("device_get", "python", "host", 380, 620)]
    out = trace.reduce_events(_read({0: {"ops": ops, "modules": modules}}, host), 1)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(800e-9)
    assert out["dispatch_gap_s"] == pytest.approx(200e-9)
    assert out["module_runs"] == 2
    assert out["scope_s"]["rollout"] == pytest.approx(200e-9)
    assert out["scope_s"]["ppo_update"] == pytest.approx(600e-9)
    assert out["scope_s"]["env_step"] == pytest.approx(160e-9)
    assert out["collective_exposed_s"] == 0
    ((key, (calls, seconds)),) = out["kernel_s"].items()
    assert "pallas_call" in key and calls == 1 and seconds == pytest.approx(100e-9)
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(600e-9)
    assert out["breakdown"]["idle_gaps"] == [["device_get", pytest.approx(200e-9)]]
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_reduce_averages_chips_and_reads_collectives():
    def chip(shift):
        return {
            "ops": [
                Event("all-gather.1", "jit/ppo_update", "all-gather", shift, shift + 50),
                Event("fusion.1", "jit/ppo_update", "fusion", shift + 50, shift + 100),
            ],
            "modules": [Event("jit_train", "", "module", shift, shift + 100)],
        }

    out = trace.reduce_events(_read({0: chip(0), 1: chip(100), 2: chip(0), 3: chip(0)}), 4)
    assert out["collective_exposed_s"] == pytest.approx(50e-9)
    assert out["busy_s"] == pytest.approx(100e-9)
    assert out["window_s"] == pytest.approx(200e-9)


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce_events(_read({}), 1)


def test_a_cpu_trace_has_no_device_plane(tmp_path):
    """The reader runs on a recorded trace; recorded here, on the CPU, it
    finds host threads only, so nothing can be reduced from it."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    read = trace.read_xplane(trace.find_xplane(tmp_path))
    assert read["devices"] == {}
    with pytest.raises(RuntimeError):
        trace.reduce_events(read, 1)
