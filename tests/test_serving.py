"""Serving subsystem contract (tier-1, CPU): compiled bucket ladder,
micro-batching scheduler, hot-reload registry, and the checkpoint edges
the hot-reload path leans on.

The acceptance pins from the serving ISSUE live here:

- a mixed stream of request sizes spanning >= 3 buckets compiles each
  bucket exactly once (asserted through the engine's RetraceGuards);
- a checkpoint hot-swap mid-stream changes subsequent actions without
  dropping or corrupting any in-flight request, and never recompiles;
- the smoke benchmark reports batch occupancy and p50/p95 latency.
"""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

from marl_distributedformation_tpu.compat.policy import (  # noqa: E402
    LoadedPolicy,
    load_checkpoint_raw,
)
from marl_distributedformation_tpu.models import MLPActorCritic  # noqa: E402
from marl_distributedformation_tpu.serving import (  # noqa: E402
    BackpressureError,
    BucketedPolicyEngine,
    MicroBatchScheduler,
    ModelRegistry,
    RequestTimeout,
    ServingClient,
    run_smoke_benchmark,
)
from marl_distributedformation_tpu.utils.checkpoint import (  # noqa: E402
    latest_checkpoint,
    restore_checkpoint_partial,
    save_checkpoint,
)

OBS_DIM = 6
HIDDEN = (8, 8)


def _make_policy(seed=0, hidden=HIDDEN, obs_dim=OBS_DIM):
    model = MLPActorCritic(act_dim=2, hidden=hidden)
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, obs_dim)))
    return LoadedPolicy(dict(variables), model_kwargs={"hidden": hidden})


def _write_ckpt(log_dir, step, policy):
    """A trainer-shaped checkpoint file (policy name + variables)."""
    return save_checkpoint(
        log_dir,
        step,
        {
            "policy": type(policy.model).__name__,
            "params": policy.params,
            "num_timesteps": step,
        },
    )


def _obs(n, seed=0):
    return (
        np.random.default_rng(seed)
        .standard_normal((n, OBS_DIM))
        .astype(np.float32)
    )


# ---------------------------------------------------------------------------
# Engine: bucket ladder + compile-once pin
# ---------------------------------------------------------------------------


def test_engine_matches_loaded_policy_predict():
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8, 64))
    for n in (1, 3, 8):
        obs = _obs(n, seed=n)
        ref, _ = policy.predict(obs, deterministic=True)
        np.testing.assert_allclose(
            engine.act(obs, deterministic=True), ref, rtol=1e-5, atol=1e-6
        )


def test_engine_mixed_stream_compiles_each_bucket_exactly_once():
    """The serving contract: any mix of request sizes spanning the whole
    ladder costs exactly one compile per rung, ever (RetraceGuard budget
    1 — a second trace would raise, not just fail the count check)."""
    engine = BucketedPolicyEngine(
        _make_policy(), buckets=(1, 8, 64), max_traces_per_bucket=1
    )
    # Sizes straddle all three rungs, incl. the split path (> top rung)
    # and both deterministic modes over the same rung.
    for i, (n, det) in enumerate(
        [(1, True), (2, True), (8, False), (9, True), (40, False),
         (64, True), (65, True), (130, False), (1, False), (5, True)]
    ):
        actions = engine.act(_obs(n, seed=i), deterministic=det)
        assert actions.shape == (n, 2)
        assert np.abs(actions).max() <= 1.0 + 1e-6
    assert engine.compile_counts() == {1: 1, 8: 1, 64: 1}


def test_engine_split_path_matches_direct_apply():
    """Requests above the top bucket split into chunks; padding and
    splitting must be invisible in the numbers."""
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8, 64))
    obs = _obs(130, seed=3)
    ref, _ = policy.predict(obs, deterministic=True)
    np.testing.assert_allclose(engine.act(obs), ref, rtol=1e-5, atol=1e-6)


def test_engine_stochastic_draws_fresh_keys():
    engine = BucketedPolicyEngine(_make_policy(), buckets=(8,))
    obs = _obs(4, seed=1)
    a1 = engine.act(obs, deterministic=False)
    a2 = engine.act(obs, deterministic=False)
    assert not np.allclose(a1, a2), "same key consumed twice"
    assert np.abs(a1).max() <= 1.0 + 1e-6  # clipped to the action space


def test_engine_rejects_rowless_and_unbatched_obs():
    engine = BucketedPolicyEngine(_make_policy(), buckets=(8,))
    with pytest.raises(ValueError, match="leading batch axis"):
        engine.act(np.zeros(OBS_DIM, np.float32))
    with pytest.raises(ValueError, match="at least one row"):
        engine.act(np.zeros((0, OBS_DIM), np.float32))


# ---------------------------------------------------------------------------
# Scheduler: coalescing, backpressure, timeouts
# ---------------------------------------------------------------------------


def test_scheduler_coalesces_and_answers_each_request():
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8, 64))
    sched = MicroBatchScheduler(engine, window_ms=10.0)
    sizes = [1, 3, 5, 8, 2, 7, 4, 6]
    with sched:
        futures = [
            sched.submit(_obs(n, seed=10 + i), deterministic=True)
            for i, n in enumerate(sizes)
        ]
        results = [f.result(timeout=30) for f in futures]
    for i, (n, res) in enumerate(zip(sizes, results)):
        ref, _ = policy.predict(_obs(n, seed=10 + i), deterministic=True)
        np.testing.assert_allclose(res.actions, ref, rtol=1e-5, atol=1e-6)
        assert res.latency_s >= 0.0
    m = sched.metrics
    assert m.requests_total == len(sizes)
    assert m.rows_total == sum(sizes)
    # The 10ms window actually coalesced (requests were enqueued
    # back-to-back, far faster than the window).
    assert m.batches_total < len(sizes)
    assert m.padded_rows_total >= m.rows_total


def test_scheduler_mixed_deterministic_flags_split_correctly():
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8, 64))
    with MicroBatchScheduler(engine, window_ms=10.0) as sched:
        f_det = sched.submit(_obs(3, seed=1), deterministic=True)
        f_sto = sched.submit(_obs(3, seed=1), deterministic=False)
        det = f_det.result(timeout=30).actions
        sto = f_sto.result(timeout=30).actions
    ref, _ = policy.predict(_obs(3, seed=1), deterministic=True)
    np.testing.assert_allclose(det, ref, rtol=1e-5, atol=1e-6)
    assert not np.allclose(sto, ref), "stochastic group got the mode action"


def _slow_engine(engine, delay_s):
    """Wrap engine.act with a delay so the worker stays busy and the
    queue actually fills (backpressure/timeout tests)."""
    orig = engine.act

    def slow_act(*args, **kwargs):
        time.sleep(delay_s)
        return orig(*args, **kwargs)

    engine.act = slow_act
    return engine


def test_scheduler_backpressure_rejects_with_retry_after():
    engine = _slow_engine(
        BucketedPolicyEngine(_make_policy(), buckets=(8,)), 0.2
    )
    with MicroBatchScheduler(engine, max_queue=2, window_ms=0.0) as sched:
        futures, rejected = [], None
        # The worker is stuck ~200ms per batch; more submits than the
        # queue holds must hit the bound.
        for i in range(10):
            try:
                futures.append(sched.submit(_obs(2, seed=i)))
            except BackpressureError as e:
                rejected = e
                break
        assert rejected is not None, "queue bound never engaged"
        assert rejected.retry_after_s > 0.0
        assert sched.metrics.rejected_total >= 1
        for f in futures:  # accepted requests still complete
            assert f.result(timeout=30).actions.shape == (2, 2)


def test_scheduler_expires_timed_out_requests():
    engine = _slow_engine(
        BucketedPolicyEngine(_make_policy(), buckets=(8,)), 0.25
    )
    with MicroBatchScheduler(engine, window_ms=0.0) as sched:
        blocker = sched.submit(_obs(1, seed=0))  # occupies the worker
        doomed = sched.submit(_obs(1, seed=1), timeout_s=0.01)
        with pytest.raises(RequestTimeout):
            doomed.result(timeout=30)
        assert blocker.result(timeout=30).actions.shape == (1, 2)
        assert sched.metrics.timeouts_total == 1


def test_scheduler_survives_mismatched_row_shapes():
    """One client's malformed rows must fail only that client's future —
    never the coalesced neighbors, never the worker thread."""
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8, 64))
    with MicroBatchScheduler(engine, window_ms=20.0) as sched:
        good = sched.submit(_obs(2, seed=1))
        bad = sched.submit(
            np.zeros((2, OBS_DIM + 1), np.float32)  # wrong trailing shape
        )
        ref, _ = policy.predict(_obs(2, seed=1), deterministic=True)
        np.testing.assert_allclose(
            good.result(timeout=30).actions, ref, rtol=1e-5, atol=1e-6
        )
        with pytest.raises(Exception):
            bad.result(timeout=30)
        # The worker is still alive and serving.
        again = sched.submit(_obs(3, seed=2))
        assert again.result(timeout=30).actions.shape == (3, 2)


def test_malformed_first_request_does_not_poison_the_bucket():
    """The nastier ordering: the very FIRST request to a bucket is
    malformed. Its failed trace must not consume the budget-1
    RetraceGuard — valid requests on the same rung must still compile
    and serve afterwards."""
    policy = _make_policy()
    engine = BucketedPolicyEngine(
        policy, buckets=(8,), max_traces_per_bucket=1
    )
    with pytest.raises(Exception):
        engine.act(np.zeros((2, OBS_DIM + 1), np.float32))
    assert engine.compile_counts() == {8: 0}, (
        "a failed trace is not a compilation"
    )
    obs = _obs(2, seed=1)
    ref, _ = policy.predict(obs, deterministic=True)
    np.testing.assert_allclose(
        engine.act(obs), ref, rtol=1e-5, atol=1e-6
    )
    assert engine.compile_counts() == {8: 1}
    # With a row shape established, later mismatches fail fast (a
    # ValueError before any jit machinery) instead of burning a trace.
    with pytest.raises(ValueError, match="one compiled row shape"):
        engine.act(np.zeros((2, OBS_DIM + 1), np.float32))


# ---------------------------------------------------------------------------
# Registry: hot swap, version pinning, bad-checkpoint containment
# ---------------------------------------------------------------------------


def test_hot_swap_mid_stream_no_drops_no_recompiles(tmp_path):
    """The acceptance pin: a swap mid-stream changes subsequent actions,
    drops nothing, and reuses the compiled programs (params are an
    argument, not a closure)."""
    pol_a, pol_b = _make_policy(seed=0), _make_policy(seed=7)
    _write_ckpt(tmp_path, 100, pol_a)
    registry = ModelRegistry(tmp_path)
    engine = BucketedPolicyEngine(
        registry.policy, buckets=(1, 8, 64), max_traces_per_bucket=1
    )
    obs = _obs(5, seed=5)
    ref_a, _ = pol_a.predict(obs, deterministic=True)
    ref_b, _ = pol_b.predict(obs, deterministic=True)
    assert not np.allclose(ref_a, ref_b)

    with MicroBatchScheduler(engine, registry=registry, window_ms=1.0) as s:
        first = [s.submit(obs) for _ in range(8)]
        first_results = [f.result(timeout=30) for f in first]
        # Swap lands while the server keeps accepting work.
        inflight = [s.submit(obs) for _ in range(8)]
        _write_ckpt(tmp_path, 200, pol_b)
        assert registry.refresh(), "newer checkpoint must swap"
        second = [s.submit(obs) for _ in range(8)]
        inflight_results = [f.result(timeout=30) for f in inflight]
        second_results = [f.result(timeout=30) for f in second]

    for res in first_results:
        assert res.model_step == 100
        np.testing.assert_allclose(res.actions, ref_a, rtol=1e-5, atol=1e-6)
    # In-flight requests must all resolve, each answered consistently by
    # exactly ONE version (never a torn mix), whichever side of the swap
    # their batch dispatched on.
    for res in inflight_results:
        assert res.model_step in (100, 200)
        ref = ref_a if res.model_step == 100 else ref_b
        np.testing.assert_allclose(res.actions, ref, rtol=1e-5, atol=1e-6)
    for res in second_results:
        assert res.model_step == 200
        np.testing.assert_allclose(res.actions, ref_b, rtol=1e-5, atol=1e-6)
    assert registry.swap_count == 1
    # Budget-1 guards would have raised on any recompile; the counts
    # document it.
    assert all(c <= 1 for c in engine.compile_counts().values())


def test_registry_ignores_older_and_equal_steps(tmp_path):
    pol = _make_policy()
    _write_ckpt(tmp_path, 50, pol)
    registry = ModelRegistry(tmp_path)
    assert registry.active_step == 50
    assert not registry.refresh()  # same file
    _write_ckpt(tmp_path, 40, _make_policy(seed=9))
    assert not registry.refresh()  # older step: latest is still 50
    assert registry.active_step == 50


def test_registry_keeps_serving_on_mismatched_architecture(tmp_path):
    _write_ckpt(tmp_path, 10, _make_policy(hidden=(8, 8)))
    registry = ModelRegistry(tmp_path)
    params_before, step_before = registry.active()
    # A wider tower lands in the watch directory (operator error).
    _write_ckpt(tmp_path, 20, _make_policy(hidden=(16, 16)))
    assert not registry.refresh()
    assert registry.active_step == step_before == 10
    assert registry.active()[0] is params_before
    assert len(registry.load_errors) == 1
    path, err = registry.load_errors[0]
    assert "rl_model_20_steps" in path
    assert "architecture mismatch" in err


def test_registry_with_prebuilt_policy_upgrades_to_disk(tmp_path):
    """A pre-built policy has unknown provenance (step 0): the first
    refresh must adopt the newest on-disk checkpoint instead of treating
    its step as already served."""
    disk_policy = _make_policy(seed=3)
    _write_ckpt(tmp_path, 200, disk_policy)
    registry = ModelRegistry(tmp_path, policy=_make_policy(seed=0))
    assert registry.active_step == 0
    assert registry.refresh()
    assert registry.active_step == 200


def test_registry_params_live_on_device(tmp_path):
    """Swapped params must be device-resident (one upload at swap time),
    not the host numpy trees msgpack restores — a per-batch weight
    upload is the hot-loop poison the transfer guards exist for."""
    _write_ckpt(tmp_path, 1, _make_policy(seed=0))
    registry = ModelRegistry(tmp_path)
    _write_ckpt(tmp_path, 2, _make_policy(seed=1))
    assert registry.refresh()
    leaves = jax.tree_util.tree_leaves(registry.active()[0])
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)


def test_registry_rejects_same_shape_dtype_drift(tmp_path):
    """A same-architecture checkpoint at a drifted dtype must be refused
    at validation time: jit caches key on dtype, so serving it would
    retrace every bucket and trip the budget-1 RetraceGuards forever."""
    _write_ckpt(tmp_path, 10, _make_policy())
    registry = ModelRegistry(tmp_path)
    drifted = _make_policy(seed=2)
    drifted.params = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float64), drifted.params
    )
    _write_ckpt(tmp_path, 20, drifted)
    assert not registry.refresh()
    assert registry.active_step == 10
    assert "dtype" in registry.load_errors[0][1]


def test_registry_background_watcher_swaps(tmp_path):
    _write_ckpt(tmp_path, 1, _make_policy(seed=0))
    registry = ModelRegistry(tmp_path, poll_interval_s=0.05)
    with registry:
        _write_ckpt(tmp_path, 2, _make_policy(seed=1))
        deadline = time.time() + 10.0
        while registry.active_step != 2 and time.time() < deadline:
            time.sleep(0.02)
    assert registry.active_step == 2
    assert registry.swap_count == 1


# ---------------------------------------------------------------------------
# Checkpoint hot-reload edges (utils.checkpoint)
# ---------------------------------------------------------------------------


def test_latest_checkpoint_never_observes_partial_writes(tmp_path):
    """Discovery racing the atomic writer: every path latest_checkpoint
    returns must parse completely (the dot-prefixed .tmp + rename
    protocol is the hot-reload foundation)."""
    # Big enough that a non-atomic write would have a wide torn window.
    target = {"params": {"w": np.arange(50_000, dtype=np.float32)}}
    done = threading.Event()

    def writer():
        for step in range(1, 120):
            save_checkpoint(tmp_path, step, target)
        done.set()

    t = threading.Thread(target=writer)
    t.start()
    reads = 0
    try:
        while not done.is_set():
            path = latest_checkpoint(tmp_path)
            if path is None:
                continue
            raw = load_checkpoint_raw(path)  # raises on a torn file
            assert "params" in raw
            reads += 1
    finally:
        t.join(timeout=60)
    assert reads > 0, "reader never overlapped the writer"


def test_latest_checkpoint_skips_temp_files(tmp_path):
    save_checkpoint(tmp_path, 7, {"x": np.zeros(3)})
    # A crashed writer's leftovers with bigger step numbers.
    (tmp_path / ".rl_model_999_steps.msgpack.tmp").write_bytes(b"torn")
    (tmp_path / "rl_model_888_steps.msgpack.tmp").write_bytes(b"torn")
    found = latest_checkpoint(tmp_path)
    assert found is not None and found.name == "rl_model_7_steps.msgpack"


def test_restore_partial_mismatched_shapes_is_a_clean_error(tmp_path):
    path = _write_ckpt(tmp_path, 5, _make_policy(hidden=(8, 8)))
    template = {"params": _make_policy(hidden=(16, 16)).params}
    with pytest.raises(ValueError, match="architecture mismatch") as e:
        restore_checkpoint_partial(path, template)
    assert "pi_0" in str(e.value)  # names the offending leaf
    assert "rl_model_5_steps" in str(e.value)  # and the file


def test_restore_partial_dict_where_array_is_a_clean_error():
    """from_state_dict restores a dict-where-array drift VERBATIM (the
    template leaf is simply replaced by the deeper dict), so the
    validation must compare tree structures, not just zip leaves."""
    from marl_distributedformation_tpu.utils.checkpoint import (
        restore_state_dict_partial,
    )

    template = {"params": {"w": np.zeros(3, np.float32)}}
    deeper = {
        "params": {
            "w": {"sub": np.zeros(3, np.float32),
                  "sub2": np.zeros(3, np.float32)}
        }
    }
    with pytest.raises(ValueError, match="tree structure"):
        restore_state_dict_partial(deeper, template, origin="drifted.msgpack")
    # And the inverse (array where a dict subtree belongs) is a clean
    # ValueError naming the origin, not a bare AttributeError.
    flat = {"params": np.zeros(3, np.float32)}
    nested_template = {"params": {"w": np.zeros(3, np.float32)}}
    with pytest.raises(ValueError, match="flat.msgpack"):
        restore_state_dict_partial(flat, nested_template, origin="flat.msgpack")


def test_restore_partial_mismatched_structure_is_a_clean_error(tmp_path):
    path = _write_ckpt(tmp_path, 5, _make_policy())
    other = MLPActorCritic(act_dim=2, hidden=(8, 8, 8))  # extra layer
    template = {
        "params": dict(
            other.init(jax.random.PRNGKey(0), jnp.zeros((1, OBS_DIM)))
        )
    }
    with pytest.raises(ValueError, match="rl_model_5_steps"):
        restore_checkpoint_partial(path, template)


# ---------------------------------------------------------------------------
# Client retry behavior
# ---------------------------------------------------------------------------


def test_backoff_is_capped_exponential_with_retry_after_floor():
    from marl_distributedformation_tpu.serving import backoff_s

    # The server hint is a FLOOR: sleeping less guarantees a re-reject.
    assert backoff_s(0, retry_after_s=0.5, base_s=0.05) == 0.5
    assert backoff_s(5, retry_after_s=3.0, base_s=0.05, cap_s=2.0) == 3.0
    # The exponential leg grows 2^attempt from base while the hint is
    # small (the server underestimating its own congestion)...
    assert backoff_s(0, retry_after_s=0.01, base_s=0.05) == 0.05
    assert backoff_s(1, retry_after_s=0.01, base_s=0.05) == 0.1
    assert backoff_s(2, retry_after_s=0.01, base_s=0.05) == 0.2
    # ...and is capped so a long retry ladder never sleeps for minutes.
    assert backoff_s(10, retry_after_s=0.01, base_s=0.05, cap_s=2.0) == 2.0


def test_backoff_full_jitter_spreads_the_stampede():
    """A fleet of clients hitting the same 429 must NOT wake in
    lockstep: with jitter, the sleep is a uniform random fraction of
    the capped-exponential delay — spread over the window, still
    floored at the server's retry_after, still bounded by the cap.
    Distribution pinned with a seeded RNG."""
    import random

    from marl_distributedformation_tpu.serving import backoff_s

    rng = random.Random(1234)
    cap = 2.0
    samples = [
        backoff_s(
            10, retry_after_s=0.01, base_s=0.05, cap_s=cap,
            jitter=rng.random,
        )
        for _ in range(500)
    ]
    # Floor and cap both hold for every draw.
    assert all(0.01 <= s <= cap for s in samples)
    # Full jitter means SPREAD, not a point mass at the cap (the
    # un-jittered value): many distinct values across the window, with
    # mass in the low, middle, and high thirds.
    assert len(set(samples)) > 400
    assert min(samples) < 0.2 and max(samples) > 1.8
    mean = sum(samples) / len(samples)
    assert 0.8 < mean < 1.2  # E[U(0,1)] * cap == cap/2, within noise
    # The floor still wins when the server prices a LONGER wait than
    # any jittered exponential draw.
    assert backoff_s(
        0, retry_after_s=3.0, base_s=0.05, cap_s=2.0, jitter=rng.random
    ) == 3.0
    # The client wires its own RNG through: jitter=False keeps the
    # deterministic ladder for single-caller tools.
    from marl_distributedformation_tpu.serving import ServingClient

    client = ServingClient(
        object(), jitter=True, rng=random.Random(7)
    )
    assert client.jitter and client._rng.random() == random.Random(
        7
    ).random()


def test_client_retries_through_backpressure_and_succeeds():
    """Opt-in retries absorb transient rejects: a client facing a full
    queue sleeps the (floored, capped-exponential) backoff and lands the
    request instead of surfacing BackpressureError to the caller."""
    engine = _slow_engine(
        BucketedPolicyEngine(_make_policy(), buckets=(8,)), 0.15
    )
    with MicroBatchScheduler(engine, max_queue=1, window_ms=0.0) as sched:
        client = ServingClient(
            sched, max_retries=8, backoff_base_s=0.02, backoff_cap_s=0.5
        )
        blockers = [sched.submit(_obs(1, seed=0))]  # worker + queue busy
        try:
            blockers.append(sched.submit(_obs(1, seed=1)))
        except BackpressureError:
            pass
        actions, _ = client.predict(_obs(2, seed=2))
        assert actions.shape == (2, 2)
        assert sched.metrics.rejected_total >= 1, (
            "the retry path was never exercised"
        )
        for f in blockers:
            assert f.result(timeout=30).actions.shape == (1, 2)


def test_client_retries_backpressure_delivered_through_the_future():
    """A fleet router can deliver BackpressureError through the FUTURE
    (failover landed on replicas that were all full) — it must consume
    retry budget exactly like a submit-time reject, not bypass the
    retry loop."""
    from concurrent.futures import Future

    from marl_distributedformation_tpu.serving import ServedResult

    class StubTarget:
        default_timeout_s = 1.0

        def __init__(self):
            self.calls = 0
            self.trace_ids = []

        def submit(self, obs, deterministic=True, timeout_s=None,
                   trace_id=None, slo_class="interactive"):
            self.calls += 1
            self.trace_ids.append(trace_id)
            future = Future()
            if self.calls == 1:
                future.set_exception(BackpressureError(0.01))
            else:
                future.set_result(
                    ServedResult(
                        actions=np.zeros((1, 2), np.float32),
                        model_step=5,
                        latency_s=0.0,
                    )
                )
            return future

    stub = StubTarget()
    client = ServingClient(stub, max_retries=2, backoff_base_s=0.001)
    result = client.predict_full(np.zeros((1, OBS_DIM), np.float32))
    assert result.model_step == 5
    assert stub.calls == 2, "the future-delivered reject must be retried"
    # ONE trace ID for the whole logical request: the client mints it
    # once and re-sends it on every retry attempt (obs/), so the
    # server-side batch spans of all attempts correlate.
    assert stub.trace_ids[0] is not None
    assert stub.trace_ids == [stub.trace_ids[0]] * 2
    # And with the budget exhausted, the reject surfaces.
    stub2 = StubTarget()
    with pytest.raises(BackpressureError):
        ServingClient(stub2, max_retries=0).predict_full(
            np.zeros((1, OBS_DIM), np.float32)
        )


def test_client_with_no_retries_surfaces_the_reject():
    engine = _slow_engine(
        BucketedPolicyEngine(_make_policy(), buckets=(8,)), 0.3
    )
    with MicroBatchScheduler(engine, max_queue=1, window_ms=0.0) as sched:
        client = ServingClient(sched, max_retries=0)
        futures = [sched.submit(_obs(1, seed=0))]
        # Wait for the worker to pick request 0 up (it then sleeps 0.3s
        # inside the slow engine) before refilling the queue — the queue
        # is then deterministically full when the client predicts, with
        # no race against the worker's wakeup.
        deadline = time.time() + 5.0
        while sched.queue_depth > 0 and time.time() < deadline:
            time.sleep(0.001)
        assert sched.queue_depth == 0, "worker never picked up request 0"
        futures.append(sched.submit(_obs(1, seed=1)))
        with pytest.raises(BackpressureError):
            client.predict(_obs(1, seed=2))
        for f in futures:
            assert f.result(timeout=30).actions.shape == (1, 2)


# ---------------------------------------------------------------------------
# Smoke benchmark + CLI
# ---------------------------------------------------------------------------


def test_smoke_benchmark_reports_occupancy_and_latency():
    engine = BucketedPolicyEngine(_make_policy(), buckets=(1, 8, 64))
    with MicroBatchScheduler(engine, window_ms=2.0) as sched:
        report = run_smoke_benchmark(
            sched,
            row_shape=(OBS_DIM,),
            sizes=(1, 5, 40),  # spans all three rungs
            duration_s=0.5,
            num_clients=3,
        )
    assert report["client_requests_ok"] > 0
    assert 0.0 < report["batch_occupancy_pct"] <= 100.0
    assert report["latency_p50_ms"] > 0.0
    assert report["latency_p95_ms"] >= report["latency_p50_ms"]
    for bucket in (1, 8, 64):
        assert report[f"compiles_bucket_{bucket}"] <= 1.0


def test_serve_policy_cli_smoke(tmp_path):
    _write_ckpt(tmp_path, 30, _make_policy())
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "serve_policy.py"),
            str(tmp_path),
            "--smoke",
            "--duration",
            "0.5",
            "--clients",
            "2",
            "--buckets",
            "1,8,64",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/local/bin:/usr/bin:/bin"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["client_requests_ok"] > 0
    assert report["batch_occupancy_pct"] > 0.0
    assert report["model_step"] == 30.0
    assert report["buckets"] == "1,8,64"
    # Every rung answered once before the storm (which coalesces and may
    # never touch the small ones) and agreed with LoadedPolicy.predict —
    # one row past the top rung included.
    assert report["rung_sweep_sizes"] == "1,8,64,65"
    assert report["rung_sweep_max_abs_err"] < 1e-6
    assert [report[f"compiles_bucket_{b}"] for b in (1, 8, 64)] == [1.0] * 3
    # The device is named first and in the result.
    assert out.stderr.splitlines()[0].startswith(
        "[serve] device: platform=cpu"
    )
    assert report["platform"] == "cpu" and report["device_count"] == 1
    assert report["residency_bytes"]["0"] > 0
