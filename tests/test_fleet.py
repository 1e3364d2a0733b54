"""Serving fleet contract (tier-1, multi-device CPU): load-aware
routing, replica-kill failover, coordinated hot-swap step monotonicity,
and the HTTP frontend round trip.

The acceptance pins from the fleet ISSUE live here, exercised on the
8-virtual-device CPU mesh tests/conftest.py provisions (the same
`--xla_force_host_platform_device_count` mechanism the ISSUE names):

- a mixed-size request storm over >= 2 replicas completes with zero
  recompiles beyond one-per-rung-per-replica (RetraceGuard receipts);
- a replica killed mid-storm loses no accepted in-flight requests —
  its queued futures transparently fail over to surviving replicas;
- a mid-storm coordinated hot swap yields globally step-monotonic
  ``model_step``s in responses (the batch-barrier commit, fleet/reload);
- the stdlib HTTP frontend round-trips act/health/metrics on an
  ephemeral port with JSON backpressure (429 + Retry-After).
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.compat.policy import (  # noqa: E402
    LoadedPolicy,
)
from marl_distributedformation_tpu.models import MLPActorCritic  # noqa: E402
from marl_distributedformation_tpu.serving import (  # noqa: E402
    BackpressureError,
    BucketedPolicyEngine,
    ServingClient,
)
from marl_distributedformation_tpu.serving.fleet import (  # noqa: E402
    FleetFrontend,
    FleetReloadCoordinator,
    FleetRouter,
    NoHealthyReplicas,
    fleet_from_checkpoint_dir,
    run_fleet_smoke,
    warmup_fleet,
)
from marl_distributedformation_tpu.utils.checkpoint import (  # noqa: E402
    save_checkpoint,
)

OBS_DIM = 6
HIDDEN = (8, 8)


def _make_policy(seed=0, hidden=HIDDEN, obs_dim=OBS_DIM):
    model = MLPActorCritic(act_dim=2, hidden=hidden)
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, obs_dim)))
    return LoadedPolicy(dict(variables), model_kwargs={"hidden": hidden})


def _write_ckpt(log_dir, step, policy):
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


def _slow_engine(engine, delay_s):
    """Wrap engine.act with a delay AFTER warmup, so queues actually
    build and routing/failover behavior becomes observable."""
    orig = engine.act

    def slow_act(*args, **kwargs):
        time.sleep(delay_s)
        return orig(*args, **kwargs)

    engine.act = slow_act
    return engine


def test_fleet_requires_multiple_devices():
    """The whole point of the conftest mesh: these tests must exercise a
    REAL multi-device fleet, not N replicas piled on one device."""
    assert len(jax.local_devices()) >= 4


def test_replicas_land_on_distinct_devices():
    router = FleetRouter(_make_policy(), num_replicas=3, buckets=(1, 8))
    devices = [r.device for r in router.replicas]
    assert len(set(devices)) == 3
    for r in router.replicas:
        params, step = r.registry.active()
        leaf = jax.tree_util.tree_leaves(params)[0]
        assert leaf.devices() == {r.device}


def test_a_replicas_dispatch_stays_on_its_own_device():
    """Replica *i*'s rung runs on device *i* and nothing it needs per
    dispatch is made on jax's default device first: the per-dispatch key
    is folded on the replica's device (the base key is committed there),
    and the actions come back from it."""
    router = FleetRouter(_make_policy(), num_replicas=4, buckets=(1, 8))
    default = jax.devices()[0]
    for r in router.replicas[1:]:
        assert r.device != default
        key = r.engine._next_key()
        assert key.devices() == {r.device}
        params, _ = r.registry.active()
        actions = r.engine._run(
            8, params, np.zeros((8, OBS_DIM), np.float32), key, np.bool_(True)
        )
        assert actions.devices() == {r.device}
    # A single engine built without a device leaves placement to jax.
    engine = BucketedPolicyEngine(_make_policy(), buckets=(1,))
    assert engine._next_key().devices() == {default}


def test_router_routes_around_a_slow_replica():
    """Routing skew under uneven load: the drain-time estimator must
    shift traffic off a replica whose device got slow (its in-flight
    batch counts as backlog, not just its queue)."""
    policy = _make_policy()
    router = FleetRouter(
        policy, num_replicas=2, buckets=(1, 8), window_ms=0.0
    )
    warmup_fleet(router, (OBS_DIM,))
    _slow_engine(router.replicas[0].engine, 0.15)
    with router:
        futures = []
        deadline = time.perf_counter() + 1.0
        while time.perf_counter() < deadline:
            futures.append(router.submit(_obs(2, seed=len(futures))))
            time.sleep(0.01)
        results = [f.result(timeout=30) for f in futures]
    assert all(r.actions.shape == (2, 2) for r in results)
    served = {
        i: router.replicas[i].scheduler.metrics.requests_total
        for i in (0, 1)
    }
    # The slow replica serves SOME traffic (it is healthy, just slow)
    # but the fast one must carry the clear majority.
    assert served[1] > 2 * max(1, served[0]), served
    assert router.metrics.routed_per_replica()[1] > served[0]


def test_replica_kill_loses_no_accepted_requests():
    """The failover pin: kill a replica with requests in its queue —
    every accepted future still resolves (re-routed to the survivor),
    the dead replica is circuit-broken, and the fleet keeps serving."""
    policy = _make_policy()
    router = FleetRouter(
        policy,
        num_replicas=2,
        buckets=(1, 8),
        window_ms=0.0,
        probe_interval_s=0.05,
        max_failovers=2,
    )
    warmup_fleet(router, (OBS_DIM,))
    _slow_engine(router.replicas[0].engine, 0.1)
    ref, _ = policy.predict(_obs(2, seed=1), deterministic=True)
    with router:
        # Quarantine replica 1 so every submit lands on replica 0 and
        # its queue demonstrably holds accepted requests at kill time.
        router._break(router.replicas[1], "test quarantine")
        first = router.submit(_obs(2, seed=1))
        time.sleep(0.03)  # worker picks it up and blocks in the engine
        queued = [router.submit(_obs(2, seed=1)) for _ in range(5)]
        assert router.replicas[0].scheduler.queue_depth > 0
        router.kill_replica(0)
        # All six resolve: the in-flight one on replica 0, the queued
        # ones by failover onto replica 1 (readmitted by the half-open
        # probe once its interval elapsed).
        for fut in [first] + queued:
            res = fut.result(timeout=30)
            np.testing.assert_allclose(
                res.actions, ref, rtol=1e-5, atol=1e-6
            )
        assert not router.replicas[0].healthy
        assert router.metrics.failed_over_total >= len(queued)
        assert router.healthy_replicas == 1
        # The fleet still serves new traffic through the survivor.
        res = router.submit(_obs(3, seed=2)).result(timeout=30)
        assert res.actions.shape == (3, 2)
        assert res.replica == 1


def test_all_replicas_broken_raises_no_healthy():
    router = FleetRouter(
        _make_policy(), num_replicas=2, buckets=(1,),
        probe_interval_s=60.0,
    )
    with router:
        router.kill_replica(0)
        router.kill_replica(1)
        with pytest.raises(NoHealthyReplicas):
            router.submit(_obs(1))


def test_fleet_backpressure_aggregates_min_retry_after():
    """Fleet-level backpressure only when EVERY healthy replica is full,
    quoting the smallest retry_after any replica priced."""
    router = FleetRouter(
        _make_policy(), num_replicas=2, buckets=(1, 8),
        window_ms=0.0, max_queue=1,
    )
    warmup_fleet(router, (OBS_DIM,))
    for r in router.replicas:
        _slow_engine(r.engine, 0.3)
    with router:
        accepted = []
        rejected = None
        for i in range(12):
            try:
                accepted.append(router.submit(_obs(1, seed=i)))
            except BackpressureError as e:
                rejected = e
                break
        assert rejected is not None, "fleet queue bound never engaged"
        assert rejected.retry_after_s > 0.0
        assert router.metrics.rejected_total >= 1
        for f in accepted:
            assert f.result(timeout=30).actions.shape == (1, 2)


def test_coordinated_swap_mid_storm_is_globally_step_monotonic(tmp_path):
    """THE acceptance pin: mixed-size storm over 3 replicas; mid-storm
    one replica is killed AND a new checkpoint lands via the
    coordinator. Zero recompiles beyond one-per-rung-per-replica, no
    accepted request lost, and model_steps globally monotonic in
    completion order."""
    watch = tmp_path / "watch"
    stage = tmp_path / "stage"
    _write_ckpt(watch, 100, _make_policy(seed=0))
    # Pre-serialize the step-200 checkpoint off to the side; the chaos
    # hook lands it with one atomic rename (building a policy mid-storm
    # would stall the storm behind a jit init compile).
    staged = _write_ckpt(stage, 200, _make_policy(seed=7))
    router, coordinator = fleet_from_checkpoint_dir(
        watch, num_replicas=3, buckets=(1, 8, 64), window_ms=1.0
    )

    def chaos():
        router.kill_replica(0)
        os.replace(staged, watch / staged.name)
        assert coordinator.refresh(), "newer checkpoint must swap"

    with router:
        report = run_fleet_smoke(
            router,
            row_shape=(OBS_DIM,),
            duration_s=2.0,
            num_clients=4,
            coordinator=coordinator,
            mid_storm=chaos,
            mid_storm_at_s=0.5,
        )
    assert report["client_requests_ok"] > 0
    assert report["client_failed"] == 0.0, report
    assert report["step_monotonic_violations"] == 0.0
    assert report["model_step_min"] == 100.0
    assert report["model_step_max"] == 200.0, (
        "no post-swap response observed — swap never became visible"
    )
    assert report["max_compiles_per_rung"] <= 1.0
    assert report["fleet_swap_count"] == 1.0
    assert report["fleet_step"] == 200.0
    # Every replica swapped exactly once — including the dead one, so a
    # revival would serve the current step, never a stale one.
    assert all(r.registry.swap_count == 1 for r in router.replicas)
    assert all(
        r.registry.active_step == 200 for r in router.replicas
    )


def test_coordinator_polls_once_and_contains_bad_checkpoints(tmp_path):
    """One poller for the whole fleet: a mismatched-architecture
    checkpoint is a recorded error that leaves EVERY replica serving the
    old params; the next good checkpoint swaps them all."""
    _write_ckpt(tmp_path, 10, _make_policy(hidden=(8, 8)))
    router, coordinator = fleet_from_checkpoint_dir(
        tmp_path, num_replicas=2, buckets=(1,)
    )
    _write_ckpt(tmp_path, 20, _make_policy(hidden=(16, 16)))
    assert not coordinator.refresh()
    assert len(coordinator.load_errors) == 1
    assert "rl_model_20_steps" in coordinator.load_errors[0][0]
    assert all(r.registry.active_step == 10 for r in router.replicas)
    _write_ckpt(tmp_path, 30, _make_policy(seed=3, hidden=(8, 8)))
    assert coordinator.refresh()
    assert coordinator.fleet_step == 30
    assert all(r.registry.active_step == 30 for r in router.replicas)
    # Older steps never swap backward, fleet-wide.
    _write_ckpt(tmp_path, 25, _make_policy(seed=4, hidden=(8, 8)))
    assert not coordinator.refresh()
    assert coordinator.fleet_step == 30


def test_coordinator_commit_aborts_cleanly_on_wedged_replica(tmp_path):
    """A replica wedged mid-dispatch (its barrier held indefinitely)
    must not park the fleet behind closed gates or produce a partial
    swap: the commit times out, reopens every gate, records the error,
    and the old step keeps serving everywhere until a later retry."""
    _write_ckpt(tmp_path, 10, _make_policy(seed=0))
    router, coordinator = fleet_from_checkpoint_dir(
        tmp_path, num_replicas=2, buckets=(1, 8), probe_interval_s=60.0
    )
    coordinator.commit_timeout_s = 0.2
    warmup_fleet(router, (OBS_DIM,))
    _write_ckpt(tmp_path, 20, _make_policy(seed=1))
    wedged = router.replicas[1].registry.batch_lock
    wedged.acquire()  # simulate a worker stuck inside a device dispatch
    try:
        with router:
            assert not coordinator.refresh()
            assert coordinator.fleet_step == 10
            # No partial swap: BOTH replicas still serve the old step.
            assert all(
                r.registry.active_step == 10 for r in router.replicas
            )
            assert "commit aborted" in coordinator.load_errors[-1][1]
            # Gates reopened: the rest of the fleet keeps serving (pin
            # routing to the healthy replica — the wedged one would
            # block behind its held barrier).
            router._break(router.replicas[1], "wedged in test")
            res = router.submit(_obs(2, seed=1)).result(timeout=30)
            assert res.model_step == 10
            assert res.replica == 0
    finally:
        wedged.release()
    # The wedge cleared: the next poll lands the swap fleet-wide.
    assert coordinator.refresh()
    assert all(r.registry.active_step == 20 for r in router.replicas)


def test_wedged_abort_incident_fires_after_gates_reopen(tmp_path):
    """Regression: the ``wedged_barrier_abort`` postmortem (a flight-
    recorder file write) must fire AFTER the partially-acquired
    barriers are released and every gate reopened — it used to fire
    from inside the acquisition loop, extending the fleet-wide serving
    pause the wedged barrier already caused by the dump's IO."""
    from marl_distributedformation_tpu.obs import get_tracer

    _write_ckpt(tmp_path, 10, _make_policy(seed=0))
    router, coordinator = fleet_from_checkpoint_dir(
        tmp_path, num_replicas=2, buckets=(1, 8), probe_interval_s=60.0
    )
    coordinator.commit_timeout_s = 0.2
    warmup_fleet(router, (OBS_DIM,))
    candidate = _write_ckpt(tmp_path, 20, _make_policy(seed=1))
    healthy = router.replicas[0].registry.batch_lock
    wedged = router.replicas[1].registry.batch_lock
    wedged.acquire()  # simulate a worker stuck inside a device dispatch
    tracer = get_tracer()
    states = []
    original = tracer.incident

    def spy(name, **fields):
        if name == "wedged_barrier_abort":
            states.append(
                (
                    healthy._lock.locked(),
                    healthy._open.is_set(),
                    wedged._open.is_set(),
                )
            )
        return original(name, **fields)

    tracer.incident = spy
    try:
        with router:
            staged, reason = coordinator.prepare_global(candidate)
    finally:
        tracer.incident = original
        wedged.release()
    assert not staged and "barrier not acquired" in reason
    # Exactly one dump, and at dump time: the healthy replica's barrier
    # is released and BOTH gates are open again (workers unparked).
    assert states == [(False, True, True)], states


def test_coordinator_background_watcher_swaps(tmp_path):
    _write_ckpt(tmp_path, 1, _make_policy(seed=0))
    router, coordinator = fleet_from_checkpoint_dir(
        tmp_path, num_replicas=2, buckets=(1,), poll_interval_s=0.05
    )
    with router, coordinator:
        _write_ckpt(tmp_path, 2, _make_policy(seed=1))
        deadline = time.time() + 10.0
        while coordinator.fleet_step != 2 and time.time() < deadline:
            time.sleep(0.02)
    assert coordinator.fleet_step == 2
    assert coordinator.swap_count == 1


def test_serving_client_works_over_the_router():
    """ServingClient is duck-typed over scheduler-or-router: the same
    client code that talks to one engine talks to the fleet."""
    policy = _make_policy()
    router = FleetRouter(policy, num_replicas=2, buckets=(1, 8))
    warmup_fleet(router, (OBS_DIM,))
    with router:
        client = ServingClient(router, max_retries=1)
        obs = _obs(3, seed=5)
        actions, step = client.predict(obs, deterministic=True)
    ref, _ = policy.predict(obs, deterministic=True)
    np.testing.assert_allclose(actions, ref, rtol=1e-5, atol=1e-6)
    assert step == 0


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url + "/v1/act",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def test_frontend_round_trip_on_ephemeral_port():
    policy = _make_policy()
    router = FleetRouter(
        policy, num_replicas=2, buckets=(1, 8), initial_step=42
    )
    warmup_fleet(router, (OBS_DIM,))
    obs = _obs(3, seed=9)
    ref, _ = policy.predict(obs, deterministic=True)
    with router, FleetFrontend(router, port=0) as frontend:
        assert frontend.port > 0  # ephemeral bind resolved
        body = _post(frontend.url, {"obs": obs.tolist()})
        np.testing.assert_allclose(
            np.asarray(body["actions"], np.float32), ref,
            rtol=1e-5, atol=1e-6,
        )
        assert body["model_step"] == 42
        assert body["replica"] in (0, 1)
        assert body["latency_s"] >= 0.0
        health = json.loads(
            urllib.request.urlopen(
                frontend.url + "/v1/health", timeout=10
            ).read()
        )
        assert health == {
            "healthy_replicas": 2, "replicas": 2, "model_step": 42,
        }
        metrics = json.loads(
            urllib.request.urlopen(
                frontend.url + "/v1/metrics", timeout=10
            ).read()
        )
        assert metrics["fleet_routed_total"] >= 1.0


def test_frontend_maps_failure_taxonomy_to_status_codes():
    router = FleetRouter(
        _make_policy(), num_replicas=1, buckets=(1,),
        window_ms=0.0, max_queue=1, probe_interval_s=60.0,
    )
    warmup_fleet(router, (OBS_DIM,))
    _slow_engine(router.replicas[0].engine, 0.5)
    with router, FleetFrontend(router, port=0) as frontend:
        # Malformed JSON -> 400.
        try:
            urllib.request.urlopen(
                urllib.request.Request(
                    frontend.url + "/v1/act", data=b"not json"
                ),
                timeout=10,
            )
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        # Unknown path -> 404.
        try:
            urllib.request.urlopen(frontend.url + "/nope", timeout=10)
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # Fill the single replica (one in flight + one queued), then a
        # frontend request must see 429 with the retry hint in BOTH the
        # JSON body and the standard Retry-After header.
        in_flight = router.submit(_obs(1, seed=0))
        time.sleep(0.05)  # the worker picks it up and blocks
        queued = router.submit(_obs(1, seed=1))
        try:
            _post(frontend.url, {"obs": _obs(1, seed=2).tolist()})
            raise AssertionError("expected 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            payload = json.loads(e.read())
            assert payload["error"] == "backpressure"
            assert payload["retry_after_s"] > 0.0
            assert int(e.headers["Retry-After"]) >= 1
            # Error bodies are correlatable: the 429 carries the trace
            # ID (minted server-side here — no header was sent) in both
            # the body and the echoed header.
            assert payload["trace_id"]
            assert e.headers["X-Trace-Id"] == payload["trace_id"]
        for fut in (in_flight, queued):
            assert fut.result(timeout=30).actions.shape == (1, 2)
        # Whole fleet broken -> health 503 and act 503.
        router._break(router.replicas[0], "test")
        try:
            urllib.request.urlopen(
                frontend.url + "/v1/health", timeout=10
            )
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
        try:
            _post(frontend.url, {"obs": _obs(1, seed=3).tolist()})
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503


def test_frontend_concurrent_clients_consistent_answers():
    """ThreadingHTTPServer + router + 2 replicas under concurrent HTTP
    clients: every response carries the same deterministic actions for
    the same observation, whichever replica answered."""
    policy = _make_policy()
    router = FleetRouter(policy, num_replicas=2, buckets=(1, 8))
    warmup_fleet(router, (OBS_DIM,))
    obs = _obs(2, seed=3)
    ref, _ = policy.predict(obs, deterministic=True)
    errors = []
    replicas_seen = set()

    def worker():
        try:
            for _ in range(5):
                body = _post(frontend.url, {"obs": obs.tolist()})
                np.testing.assert_allclose(
                    np.asarray(body["actions"], np.float32), ref,
                    rtol=1e-5, atol=1e-6,
                )
                replicas_seen.add(body["replica"])
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    with router, FleetFrontend(router, port=0) as frontend:
        threads = [
            threading.Thread(target=worker, daemon=True) for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not errors, errors
    assert replicas_seen <= {0, 1}


# ---------------------------------------------------------------------------
# Trace-ID propagation (obs/): frontend -> router -> scheduler batch span
# ---------------------------------------------------------------------------


def _post_traced(url, payload, trace_id=None, timeout=30):
    """POST /v1/act returning (body, echoed X-Trace-Id header)."""
    headers = {"Content-Type": "application/json"}
    if trace_id is not None:
        headers["X-Trace-Id"] = trace_id
    req = urllib.request.Request(
        url + "/v1/act",
        data=json.dumps(payload).encode(),
        headers=headers,
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read()), resp.headers.get("X-Trace-Id")


def test_trace_id_propagates_frontend_to_batch_span():
    """ONE ID correlates a request across every layer: the header a
    client sends comes back on its own response (concurrent requests
    keep DISTINCT ids — no cross-talk through the coalescing batcher),
    a header-less request gets a minted ID, and the scheduler's
    ``serve.batch`` spans link the coalesced requests' trace IDs so the
    dispatch that served a request is findable by its ID."""
    from marl_distributedformation_tpu.obs import Tracer, set_tracer

    tracer = Tracer(ring_size=1024)
    previous = set_tracer(tracer)
    try:
        policy = _make_policy()
        router = FleetRouter(policy, num_replicas=2, buckets=(1, 8))
        warmup_fleet(router, (OBS_DIM,))
        sent_ids = [f"client-req-{i}" for i in range(8)]
        echoes = {}
        errors = []

        def worker(tid):
            try:
                body, header = _post_traced(
                    frontend.url, {"obs": _obs(2, seed=1).tolist()},
                    trace_id=tid,
                )
                echoes[tid] = (body["trace_id"], header)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        with router, FleetFrontend(router, port=0) as frontend:
            threads = [
                threading.Thread(target=worker, args=(tid,), daemon=True)
                for tid in sent_ids
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
            # Every concurrent request got ITS OWN id back, body+header.
            assert echoes == {tid: (tid, tid) for tid in sent_ids}
            # No header -> the frontend mints one and still echoes it.
            body, header = _post_traced(
                frontend.url, {"obs": _obs(1, seed=2).tolist()}
            )
            assert body["trace_id"] and header == body["trace_id"]
            assert body["trace_id"] not in sent_ids
            # An unusable header is re-minted, not parroted back.
            weird, _ = _post_traced(
                frontend.url, {"obs": _obs(1, seed=3).tolist()},
                trace_id='evil"id',
            )
            assert weird["trace_id"] != 'evil"id'
        # The batch spans LINK the request ids: every sent id appears in
        # some dispatch's linked set, and ids never bleed into spans
        # that did not serve them more than once each.
        batch_spans = [
            r
            for r in tracer.snapshot()
            if r["kind"] == "span" and r["name"] == "serve.batch"
        ]
        assert batch_spans, "no serve.batch spans recorded"
        linked = [
            tid
            for span in batch_spans
            for tid in span["attrs"].get("trace_ids", ())
        ]
        assert set(sent_ids) <= set(linked)
        for tid in sent_ids:
            assert linked.count(tid) == 1, f"{tid} served twice?"
        # And batch spans carry the dispatch facts a timeline needs.
        for span in batch_spans:
            assert span["attrs"]["rows"] >= 1
            assert span["attrs"]["model_step"] == 0
    finally:
        set_tracer(previous)
