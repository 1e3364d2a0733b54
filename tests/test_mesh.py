"""Mesh tier contract (tier-1): the cross-host serving invariants.

The fleet-of-fleets acceptance pins (serving/mesh/, docs/mesh.md),
exercised two ways:

- **in-process loopback hosts** (threads, real HTTP/RPC between them)
  for the control-plane logic: RPC taxonomy, gossip suspect->dead
  timing, stale-host quarantine + catch-up, drain-aware meta routing,
  the global barrier's monotonicity witness, wedged-host abort with
  every host restored, and trace-ID propagation through the extra hop;
- **one real 2-host SUBPROCESS e2e** (each host its own interpreter and
  XLA backend) for what threads cannot fake: ``model_step`` globally
  monotonic in response completion order across hosts through a
  coordinator-driven swap, and a real ``kill -9`` losing zero accepted
  requests.
"""

import json
import threading
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.chaos import (  # noqa: E402
    FaultSchedule,
    FaultSpec,
    check_step_monotonic,
    get_fault_plane,
)
from marl_distributedformation_tpu.compat.policy import (  # noqa: E402
    LoadedPolicy,
)
from marl_distributedformation_tpu.models import MLPActorCritic  # noqa: E402
from marl_distributedformation_tpu.serving import ServingClient  # noqa: E402
from marl_distributedformation_tpu.serving.mesh import (  # noqa: E402
    HOST_ALIVE,
    HOST_DEAD,
    HOST_SUSPECT,
    HostAgent,
    JsonRpcServer,
    MeshCoordinator,
    MeshFrontend,
    MeshRpcError,
    MeshUnreachable,
    MetaRouter,
    NoHealthyHosts,
    build_inprocess_host,
    rpc_call,
    spawn_local_mesh,
)
from marl_distributedformation_tpu.utils.checkpoint import (  # noqa: E402
    save_checkpoint,
)

OBS_DIM = 6
HIDDEN = (8, 8)


def _make_policy(seed=0):
    model = MLPActorCritic(act_dim=2, hidden=HIDDEN)
    variables = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, OBS_DIM))
    )
    return LoadedPolicy(dict(variables), model_kwargs={"hidden": HIDDEN})


def _write_ckpt(log_dir, step, policy):
    return save_checkpoint(
        Path(log_dir),
        step,
        {
            "policy": type(policy.model).__name__,
            "params": policy.params,
            "num_timesteps": step,
        },
    )


def _obs(n=1):
    return np.zeros((n, OBS_DIM), np.float32)


# ---------------------------------------------------------------------------
# RPC substrate
# ---------------------------------------------------------------------------


def test_rpc_roundtrip_and_error_taxonomy():
    """The one transport primitive: 200 -> payload, handler exception ->
    typed MeshRpcError (with the exception type, no traceback), unknown
    method -> 404, nobody listening -> MeshUnreachable (the host-death
    signal everything keys on)."""
    server = JsonRpcServer(
        {
            "echo": lambda p: {"got": p},
            "boom": lambda p: (_ for _ in ()).throw(KeyError("nope")),
        }
    ).start()
    try:
        reply = rpc_call(server.url, "echo", {"x": 1})
        assert reply == {"got": {"x": 1}}
        with pytest.raises(MeshRpcError) as err:
            rpc_call(server.url, "boom", {})
        assert err.value.status == 500
        assert err.value.error_type == "KeyError"
        with pytest.raises(MeshRpcError) as err:
            rpc_call(server.url, "nosuch", {})
        assert err.value.status == 404
        dead_port = server.port  # reuse after close: nobody listens
    finally:
        server.stop()
    with pytest.raises(MeshUnreachable):
        rpc_call(f"http://127.0.0.1:{dead_port}", "echo", {}, timeout_s=1.0)


# ---------------------------------------------------------------------------
# Gossip: lease taxonomy, quarantine, catch-up
# ---------------------------------------------------------------------------


def test_gossip_suspect_to_dead_timing_and_revival():
    """The health taxonomy over real heartbeat RPCs: a silent host
    walks alive -> suspect -> dead on the lease clock, and a fresh
    heartbeat revives it."""
    coord = MeshCoordinator(lease_s=0.25, dead_after_s=0.25).serve()
    try:
        reply = rpc_call(
            coord.url,
            "mesh.register",
            {
                "host_id": "h0",
                "control_url": "http://127.0.0.1:1",
                "data_url": "http://127.0.0.1:2",
                "step": 100,
            },
        )
        assert reply["registered"] and reply["lease_s"] == 0.25

        def state():
            return coord.hosts()[0]["state"]

        assert state() == HOST_ALIVE
        deadline = time.monotonic() + 5.0
        while state() == HOST_ALIVE and time.monotonic() < deadline:
            time.sleep(0.02)
        assert state() == HOST_SUSPECT  # lease missed, not yet dead
        while state() == HOST_SUSPECT and time.monotonic() < deadline:
            time.sleep(0.02)
        assert state() == HOST_DEAD
        assert coord.routable_hosts() == []
        # Revival: one heartbeat brings it back.
        reply = rpc_call(
            coord.url, "mesh.heartbeat", {"host_id": "h0", "step": 100}
        )
        assert reply["registered"]
        assert state() == HOST_ALIVE
        # An unknown host is told to re-register, not silently gossip.
        assert rpc_call(
            coord.url, "mesh.heartbeat", {"host_id": "ghost"}
        ) == {"registered": False}
    finally:
        coord.stop()


def test_sweep_emits_death_incident_outside_hosts_lock():
    """Regression: the ``mesh_host_dead`` incident dump (tracer ring
    lock + a flight-recorder file write) must run AFTER ``_hosts_lock``
    is released — it used to fire from inside the sweep's host walk,
    nesting the tracer's lock (and its IO) under the lock every
    heartbeat RPC dispatches through. The dead_reason verdict write
    itself stays under the lock."""
    from marl_distributedformation_tpu.obs import get_tracer

    coord = MeshCoordinator(lease_s=0.01, dead_after_s=0.01)
    coord._rpc_register(
        {
            "host_id": "h0",
            "control_url": "http://127.0.0.1:1",
            "data_url": "http://127.0.0.1:2",
            "step": 100,
        }
    )
    time.sleep(0.05)  # walk h0 past suspect into dead
    tracer = get_tracer()
    lock_states = []
    original = tracer.incident

    def spy(name, **fields):
        if name == "mesh_host_dead":
            lock_states.append(coord._hosts_lock.locked())
        return original(name, **fields)

    tracer.incident = spy
    try:
        coord.sweep()
    finally:
        tracer.incident = original
    assert lock_states == [False], (
        "the death incident must be emitted after the host-table lock "
        f"is released: {lock_states}"
    )
    # The verdict itself landed (written under the lock, once).
    assert "lease expired" in coord.hosts()[0]["dead_reason"]


def test_stale_host_quarantined_until_caught_up():
    """A host serving BEHIND the mesh step must be unroutable (routing
    to it would serve an old model_step after newer responses) until
    its heartbeat reports the mesh step again."""
    coord = MeshCoordinator(lease_s=5.0, dead_after_s=5.0).serve()
    try:
        rpc_call(
            coord.url,
            "mesh.register",
            {
                "host_id": "h0",
                "control_url": "http://127.0.0.1:1",
                "data_url": "http://127.0.0.1:2",
                "step": 100,
            },
        )
        assert [h.host_id for h in coord.routable_hosts()] == ["h0"]
        coord._mesh_step = 200  # a commit this host missed
        assert coord.routable_hosts() == []
        reply = rpc_call(
            coord.url, "mesh.heartbeat", {"host_id": "h0", "step": 200}
        )
        assert reply["mesh_step"] == 200
        assert [h.host_id for h in coord.routable_hosts()] == ["h0"]
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# In-process loopback hosts (threads, real HTTP/RPC)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh2(tmp_path_factory):
    """Coordinator + 2 in-process hosts + MetaRouter over a promoted
    directory seeded at step 100. Swap tests publish ascending steps
    relative to the CURRENT mesh step, so test order never matters."""
    promoted = tmp_path_factory.mktemp("mesh_promoted")
    policy = _make_policy()
    _write_ckpt(promoted, 100, policy)
    coord = MeshCoordinator(
        log_dir=promoted, lease_s=2.0, dead_after_s=2.0,
        prepare_timeout_s=10.0,
    ).serve()
    stacks = [
        build_inprocess_host(
            promoted,
            coord.url,
            f"host{i}",
            obs_dim=OBS_DIM,
            buckets=(1,),
            heartbeat_s=0.1,
        )
        for i in range(2)
    ]
    for _, _, _, agent in stacks:
        assert agent.wait_registered(15.0)
    router = MetaRouter(coord, probe_interval_s=0.3)
    yield {
        "coord": coord,
        "router": router,
        "stacks": stacks,
        "promoted": promoted,
        "policy": policy,
    }
    for r, _, fe, agent in stacks:
        agent.stop()
        fe.stop()
        r.stop()
    coord.stop()


def test_meta_router_serves_and_routes_by_gossiped_drain(mesh2):
    router, coord = mesh2["router"], mesh2["coord"]
    result = router.predict(_obs())
    assert result.host in ("host0", "host1")
    assert result.replica >= 0
    # Routing follows the gossip: a host advertising a deep backlog
    # must lose the next request to its idle peer.
    busy = result.host
    idle = "host1" if busy == "host0" else "host0"
    with coord._hosts_lock:
        coord._hosts[busy].metrics = {"fleet_estimated_drain_s": 9.0}
        coord._hosts[idle].metrics = {"fleet_estimated_drain_s": 0.0}
    assert router.predict(_obs()).host == idle
    # The next real heartbeat restores honest gossip (both idle).
    time.sleep(0.3)
    snap = router.snapshot()
    assert snap["mesh_hosts"] == 2.0
    assert snap["mesh_routed_total"] >= 2.0


def test_an_answer_cut_off_mid_body_fails_over_like_a_reset(mesh2):
    """A host killed between its status line and the end of its answer
    leaves a 200 with an unparseable body (post_json degrades it to
    ``{"error": prefix}``). That must take the host-death path — break
    the host, fail the request over — not surface as a KeyError the
    caller has no taxonomy for (the rare lost request of the real
    kill -9 e2e below)."""
    router = mesh2["router"]
    real_forward = router._forward
    calls = []

    def cut_off_once(data_url, body, trace_id, timeout_s):
        calls.append(data_url)
        if len(calls) == 1:
            return 200, {"error": '{"actions": [[0.1, 0.'}, None
        return real_forward(data_url, body, trace_id, timeout_s)

    before = router.failed_over_total
    router._forward = cut_off_once
    try:
        result = router.predict(_obs())
    finally:
        del router._forward
    assert result.actions.shape == (1, 2)
    assert len(calls) == 2 and calls[0] != calls[1]
    assert router.failed_over_total == before + 1
    time.sleep(0.4)  # the broken host half-opens again (probe_interval_s)
    assert {router.predict(_obs()).host for _ in range(8)} <= {
        "host0", "host1",
    }


def test_global_swap_is_monotonic_in_completion_order(mesh2):
    """The tentpole invariant, in-process edition: responses completed
    across a coordinator-driven two-phase swap never carry a step going
    backward, and the commit lands on EVERY host (host_count == 2)."""
    router, coord = mesh2["router"], mesh2["coord"]
    promoted, policy = mesh2["promoted"], mesh2["policy"]
    witness = []
    lock = threading.Lock()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                r = router.predict(_obs(), timeout_s=5.0)
            except Exception:  # noqa: BLE001 — typed errors are fine here
                continue
            with lock:
                witness.append((time.perf_counter(), r.model_step))

    threads = [
        threading.Thread(target=hammer, daemon=True) for _ in range(3)
    ]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)
        new_step = coord.fleet_step + 100
        _write_ckpt(promoted, new_step, policy)
        assert coord.refresh() is True
        assert coord.fleet_step == new_step
        assert coord.last_commit["host_count"] == 2
        assert coord.last_commit["commit_round"] >= 1
        # Post-commit responses must all carry the new step.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if router.predict(_obs()).model_step == new_step:
                break
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    with lock:
        assert check_step_monotonic(witness) == []
        assert witness and max(s for _, s in witness) == new_step
    # Both hosts serve the new step (no torn mesh).
    for _, fleet, _, _ in mesh2["stacks"]:
        assert fleet.fleet_step == new_step


def test_trace_id_through_the_extra_hop(mesh2):
    """One X-Trace-Id survives client -> MeshFrontend -> MetaRouter ->
    host frontend and comes back on every layer's response."""
    router = mesh2["router"]
    # Programmatic: the MeshResult carries the host frontend's echo.
    result = router.predict(_obs(), trace_id="mesh-trace-42")
    assert result.trace_id == "mesh-trace-42"
    # HTTP: the meta frontend echoes header AND body.
    frontend = MeshFrontend(router).start()
    try:
        req = urllib.request.Request(
            frontend.url + "/v1/act",
            data=json.dumps({"obs": _obs().tolist()}).encode(),
            headers={
                "Content-Type": "application/json",
                "X-Trace-Id": "mesh-trace-43",
            },
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.headers.get("X-Trace-Id") == "mesh-trace-43"
            body = json.loads(resp.read())
        assert body["trace_id"] == "mesh-trace-43"
        assert body["host"] in ("host0", "host1")
        assert body["model_step"] == mesh2["coord"].fleet_step
    finally:
        frontend.stop()


def test_serving_client_endpoint_failover(mesh2):
    """The client-side satellite: a dead frontend in the endpoint list
    costs ONE attempt of the shared retry budget, not the whole budget
    burned on one address."""
    live = [fe.url for _, _, fe, _ in mesh2["stacks"]]
    dead = "http://127.0.0.1:1"  # port 1: connection refused
    client = ServingClient(
        [dead] + live, max_retries=2, backoff_base_s=0.001
    )
    actions, step = client.predict(_obs())
    assert actions.shape == (1, 2)
    assert step == mesh2["coord"].fleet_step
    # All endpoints dead: the budget caps the damage with a typed error.
    client = ServingClient(
        [dead, dead], max_retries=1, backoff_base_s=0.001
    )
    with pytest.raises(ConnectionError):
        client.predict(_obs())


def test_catch_up_after_missed_commit(mesh2):
    """A host that misses a commit round (agent down during the swap)
    is quarantined from routing on revival and catches up from the
    heartbeat's advertised checkpoint — never serving a stale step
    into the routable pool."""
    coord = mesh2["coord"]
    promoted, policy = mesh2["promoted"], mesh2["policy"]
    router_b, fleet_b, frontend_b, agent_b = mesh2["stacks"][1]
    # Take host1's agent down (its data plane keeps serving).
    agent_b.stop(deregister=True)
    new_step = coord.fleet_step + 100
    _write_ckpt(promoted, new_step, policy)
    assert coord.refresh() is True  # commits on host0 alone
    assert coord.last_commit["host_count"] == 1
    assert fleet_b.fleet_step < new_step  # host1 missed it
    # Revive host1's control plane: it registers with its stale step,
    # is quarantined, then catches up from the heartbeat reply.
    agent_new = HostAgent(
        host_id="host1",
        router=router_b,
        fleet=fleet_b,
        coordinator_url=coord.url,
        data_url=frontend_b.url,
        heartbeat_interval_s=0.1,
    ).start()
    mesh2["stacks"][1] = (router_b, fleet_b, frontend_b, agent_new)
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            routable = {h.host_id for h in coord.routable_hosts()}
            if (
                "host1" in routable
                and fleet_b.fleet_step == new_step
                and agent_new.catch_ups >= 1
            ):
                break
            time.sleep(0.05)
        assert fleet_b.fleet_step == new_step
        assert "host1" in {h.host_id for h in coord.routable_hosts()}
        assert agent_new.catch_ups >= 1
    finally:
        pass  # module teardown stops the replacement agent


def test_wedged_host_barrier_abort_restores_every_host(mesh2):
    """A host wedged mid-prepare (chaos plane, mesh.prepare wedge past
    the coordinator's timeout) aborts the WHOLE round: no host commits,
    every host keeps serving the old step with gates open, and a later
    retry lands the swap — the cross-host restatement of the fleet's
    wedged-barrier abort."""
    coord = mesh2["coord"]
    router = mesh2["router"]
    promoted, policy = mesh2["promoted"], mesh2["policy"]
    old_step = coord.fleet_step
    plane = get_fault_plane()
    plane.reset()
    plane.arm(
        FaultSchedule(
            [FaultSpec("mesh.prepare", "wedge", at_hit=1, seconds=2.5)]
        )
    )
    plane.enabled = True
    coord.prepare_timeout_s, saved_timeout = 1.0, coord.prepare_timeout_s
    try:
        new_step = old_step + 100
        path = _write_ckpt(promoted, new_step, policy)
        assert coord.global_reload(path) is False  # round aborted
        assert coord.fleet_step == old_step
        assert any(
            "abort" in reason for _, reason in coord.load_errors
        )
        # Every host restored: still serving, still on the old step.
        for _, fleet, _, _ in mesh2["stacks"]:
            assert fleet.fleet_step == old_step
        assert router.predict(_obs()).model_step == old_step
        # The wedge drains; the retry (possibly twice: the first retry
        # clears a stale staged round left by the late-finishing
        # wedged prepare) must land on every host.
        plane.enabled = False
        time.sleep(2.0)
        deadline = time.monotonic() + 15.0
        landed = False
        while time.monotonic() < deadline and not landed:
            landed = coord.global_reload(path)
            if not landed:
                time.sleep(0.2)
        assert landed, f"retry never landed: {list(coord.load_errors)}"
        for _, fleet, _, _ in mesh2["stacks"]:
            assert fleet.fleet_step == new_step
    finally:
        plane.enabled = False
        plane.reset()
        coord.prepare_timeout_s = saved_timeout


def test_commit_retry_is_idempotent_and_already_at_step_short_circuits(
    tmp_path,
):
    """Two lost-ack recovery paths on the barrier's host side: a commit
    RPC retried after its response was lost must report what the first
    delivery did (not refuse a round the host already landed), and a
    prepare targeting the step the host ALREADY serves answers
    ``already_at_step`` so the coordinator counts it committed instead
    of aborting the round."""
    policy = _make_policy()
    _write_ckpt(tmp_path, 100, policy)
    coord = MeshCoordinator(lease_s=5.0, dead_after_s=5.0).serve()
    router, fleet, frontend, agent = build_inprocess_host(
        tmp_path, coord.url, "h0", obs_dim=OBS_DIM, buckets=(1,)
    )
    try:
        path = _write_ckpt(tmp_path, 150, policy)
        resp = rpc_call(
            agent.control_url,
            "mesh.prepare",
            {"round": 7, "path": str(path), "step": 150, "ttl_s": 30.0},
        )
        assert resp["staged"] is True
        first = rpc_call(agent.control_url, "mesh.commit", {"round": 7})
        assert first == {"ok": True, "step": 150}
        # The retry (lost ack) must echo the landed result, not refuse.
        retry = rpc_call(agent.control_url, "mesh.commit", {"round": 7})
        assert retry == {"ok": True, "step": 150}
        assert fleet.fleet_step == 150
        # A later round targeting the already-served step short-circuits.
        resp = rpc_call(
            agent.control_url,
            "mesh.prepare",
            {"round": 8, "path": str(path), "step": 150, "ttl_s": 30.0},
        )
        assert resp["already_at_step"] is True and not resp["staged"]
        # And the host never paused: it still serves.
        assert router.submit(_obs()).result(timeout=10.0).model_step == 150
    finally:
        agent.stop()
        frontend.stop()
        router.stop()
        coord.stop()


def test_no_routable_hosts_is_typed():
    """An empty mesh is DOWN, not busy — the taxonomy the frontend
    maps to 503."""
    coord = MeshCoordinator().serve()
    try:
        router = MetaRouter(coord)
        with pytest.raises(NoHealthyHosts):
            router.predict(_obs())
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# The real thing: 2 host subprocesses, kill -9, global monotonicity
# ---------------------------------------------------------------------------


def test_two_host_subprocess_e2e_swap_and_kill(tmp_path):
    """THE acceptance e2e: a loopback 2-host mesh of real subprocesses
    — model_step globally monotonic in response completion order
    through a coordinator-driven swap, then a real ``kill -9`` of one
    host loses zero accepted requests, the survivor absorbs the
    traffic, and the lease taxonomy declares the corpse dead."""
    policy = _make_policy()
    _write_ckpt(tmp_path, 100, policy)
    mesh = spawn_local_mesh(
        tmp_path,
        hosts=2,
        buckets=(1,),
        obs_dim=OBS_DIM,
        heartbeat_s=0.15,
        lease_s=0.6,
        dead_after_s=0.6,
        probe_interval_s=0.3,
    )
    witness = []
    outcomes = {"ok": 0, "typed": 0, "lost": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                r = mesh.router.predict(_obs(), timeout_s=5.0)
            except (
                NoHealthyHosts,
                RuntimeError,
                OSError,
                TimeoutError,
            ):
                with lock:
                    outcomes["typed"] += 1
                time.sleep(0.01)
                continue
            except BaseException:
                with lock:
                    outcomes["lost"] += 1
                continue
            with lock:
                outcomes["ok"] += 1
                witness.append((time.perf_counter(), r.model_step))

    threads = [
        threading.Thread(target=hammer, daemon=True) for _ in range(3)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.4)
        # Coordinator-driven global swap under load.
        path = _write_ckpt(tmp_path, 200, policy)
        assert mesh.coordinator.global_reload(path) is True
        assert mesh.coordinator.last_commit == {
            "commit_round": 1,
            "host_count": 2,
            "step": 200,
        }
        time.sleep(0.4)
        # The hammer: a REAL SIGKILL mid-load.
        killed = mesh.kill_host(0)
        time.sleep(1.5)
        # The survivor serves; the corpse is declared dead.
        post_kill = mesh.router.predict(_obs(), timeout_s=5.0)
        assert post_kill.model_step == 200
        states = {
            h["host_id"]: h["state"] for h in mesh.coordinator.hosts()
        }
        assert states[killed] == HOST_DEAD
        # A swap with one host dead still commits (host_count == 1).
        path = _write_ckpt(tmp_path, 300, policy)
        assert mesh.coordinator.global_reload(path) is True
        assert mesh.coordinator.last_commit["host_count"] == 1
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if mesh.router.predict(_obs(), timeout_s=5.0).model_step == 300:
                break
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15.0)
        receipts = mesh.router.host_compile_counts()
        mesh.stop()
    for t in threads:
        assert not t.is_alive(), "a client thread wedged inside a request"
    with lock:
        assert outcomes["lost"] == 0, outcomes
        assert outcomes["ok"] > 0
        assert check_step_monotonic(witness) == []
        assert max(s for _, s in witness) == 300
    # Budget-1 receipts per surviving host.
    assert receipts, "no host answered the receipts scrape"
    for host_id, per_rung in receipts.items():
        for rung, count in per_rung.items():
            assert count <= 1.0, (host_id, rung, count)
