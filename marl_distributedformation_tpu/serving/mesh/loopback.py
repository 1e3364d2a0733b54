"""Loopback mesh: a real multi-process mesh on one machine.

The container's jaxlib refuses multi-process collectives, but the mesh
tier never needed them — the control plane coordinates over RPC and
the data plane over HTTP, both of which loopback exercises for real.
:func:`spawn_local_mesh` boots the whole topology the tests, the chaos
storm's ``--mesh`` campaign, and the mesh smoke share:

- a :class:`~.coordinator.MeshCoordinator` RPC service in THIS process,
- N host SUBPROCESSES (``serving/mesh/host.py`` — each its own
  interpreter, its own XLA backend, its own compiled engines; ``kill
  -9`` of one is a real host death),
- a :class:`~.router.MetaRouter` (+ optional :class:`~.router.
  MeshFrontend`) routing over them.

:func:`build_inprocess_host` is the thread-level twin for unit tests:
the same fleet + frontend + agent stack, wired over real loopback
HTTP/RPC, but inside the current process where the chaos plane and
assertions can reach it.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from marl_distributedformation_tpu.serving.mesh.coordinator import (
    MeshCoordinator,
)
from marl_distributedformation_tpu.serving.mesh.router import (
    MeshFrontend,
    MetaRouter,
)

REPO_ROOT = Path(__file__).resolve().parents[3]


class MeshHostProcess:
    """One spawned host subprocess plus its parsed ready line."""

    def __init__(self, proc: subprocess.Popen, info: Dict[str, Any]):
        self.proc = proc
        self.host_id = str(info["host_id"])
        self.data_url = str(info["data_url"])
        self.control_url = str(info["control_url"])
        self.pid = int(info["pid"])
        self.step = int(info.get("step", -1))

    def kill(self, sig: int = signal.SIGKILL) -> None:
        """A REAL host death — the failure mode SimulatedCrash only
        imitates."""
        try:
            os.kill(self.pid, sig)
        except ProcessLookupError:
            pass

    def alive(self) -> bool:
        return self.proc.poll() is None


class LocalMesh:
    """Handle over the whole loopback topology; ``stop()`` tears down
    hosts, router state, and the coordinator."""

    def __init__(
        self,
        coordinator: MeshCoordinator,
        router: MetaRouter,
        hosts: List[MeshHostProcess],
        frontend: Optional[MeshFrontend] = None,
    ) -> None:
        self.coordinator = coordinator
        self.router = router
        self.hosts = hosts
        self.frontend = frontend

    def kill_host(self, index: int, sig: int = signal.SIGKILL) -> str:
        self.hosts[index].kill(sig)
        return self.hosts[index].host_id

    def stop(self) -> None:
        if self.frontend is not None:
            self.frontend.stop()
        for h in self.hosts:
            if h.alive():
                h.proc.terminate()
        for h in self.hosts:
            try:
                h.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                h.proc.kill()
        self.coordinator.stop()

    def __enter__(self) -> "LocalMesh":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def spawn_host_process(
    promoted_dir: str | Path,
    coordinator_url: str,
    host_id: str,
    replicas: int = 1,
    buckets: Sequence[int] = (1, 8),
    obs_dim: Optional[int] = None,
    num_agents: Optional[int] = None,
    heartbeat_s: float = 0.25,
    fault_spec: Optional[List[dict]] = None,
    ready_timeout_s: float = 120.0,
    extra_args: Sequence[str] = (),
) -> MeshHostProcess:
    """Spawn one host subprocess and block until its ready line (the
    first import of jax + engine warmup dominate; the shared
    compilation cache makes repeats fast)."""
    cmd = [
        sys.executable,
        "-m",
        "marl_distributedformation_tpu.serving.mesh.host",
        "--promoted-dir", str(promoted_dir),
        "--coordinator-url", coordinator_url,
        "--host-id", host_id,
        "--replicas", str(replicas),
        "--buckets", ",".join(str(b) for b in buckets),
        "--heartbeat-s", str(heartbeat_s),
    ]
    if num_agents is not None:
        cmd += ["--num-agents", str(num_agents)]
    if obs_dim is not None:
        cmd += ["--obs-dim", str(obs_dim)]
    if fault_spec:
        cmd += ["--fault-spec", json.dumps(fault_spec)]
    cmd += list(extra_args)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = (
        str(REPO_ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    proc = subprocess.Popen(
        cmd,
        cwd=str(REPO_ROOT),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL
        if os.environ.get("MESH_HOST_STDERR") != "1"
        else None,
        text=True,
    )
    import select

    deadline = time.monotonic() + ready_timeout_s
    line = ""
    while time.monotonic() < deadline:
        remaining = max(0.0, deadline - time.monotonic())
        readable, _, _ = select.select(
            [proc.stdout], [], [], min(remaining, 0.5)
        )
        if readable:
            line = proc.stdout.readline()
            if line:
                break
        if proc.poll() is not None:
            raise RuntimeError(
                f"mesh host {host_id} exited rc={proc.returncode} "
                "before its ready line (run with MESH_HOST_STDERR=1 "
                "for its stderr)"
            )
    if not line:
        proc.kill()
        raise TimeoutError(
            f"mesh host {host_id} produced no ready line in "
            f"{ready_timeout_s}s"
        )
    info = json.loads(line)
    if not info.get("ready"):
        proc.kill()
        raise RuntimeError(f"mesh host {host_id} not ready: {info}")
    return MeshHostProcess(proc, info)


def spawn_local_mesh(
    promoted_dir: str | Path,
    hosts: int = 2,
    replicas_per_host: int = 1,
    buckets: Sequence[int] = (1, 8),
    obs_dim: Optional[int] = None,
    num_agents: Optional[int] = None,
    heartbeat_s: float = 0.25,
    lease_s: float = 1.0,
    dead_after_s: float = 1.0,
    prepare_timeout_s: float = 30.0,
    frontend_port: Optional[int] = None,
    watch: bool = False,
    fault_specs: Optional[Dict[int, List[dict]]] = None,
    default_timeout_s: float = 10.0,
    max_failovers: int = 1,
    probe_interval_s: float = 1.0,
    ready_timeout_s: float = 120.0,
) -> LocalMesh:
    """Boot coordinator + N host subprocesses + MetaRouter, blocking
    until every host registered. ``watch=True`` also starts the
    coordinator's background poll of ``promoted_dir`` (the
    always-learning shape); tests usually drive ``refresh()``
    themselves. ``fault_specs`` maps a host index to the JSON fault
    list armed on that subprocess's chaos plane."""
    coordinator = MeshCoordinator(
        log_dir=promoted_dir,
        lease_s=lease_s,
        dead_after_s=dead_after_s,
        prepare_timeout_s=prepare_timeout_s,
    )
    if watch:
        coordinator.start()
    else:
        coordinator.serve()
    procs: List[MeshHostProcess] = []
    try:
        for i in range(hosts):
            procs.append(
                spawn_host_process(
                    promoted_dir,
                    coordinator.url,
                    host_id=f"host{i}",
                    replicas=replicas_per_host,
                    buckets=buckets,
                    obs_dim=obs_dim,
                    num_agents=num_agents,
                    heartbeat_s=heartbeat_s,
                    fault_spec=(fault_specs or {}).get(i),
                    ready_timeout_s=ready_timeout_s,
                )
            )
        deadline = time.monotonic() + ready_timeout_s
        while time.monotonic() < deadline:
            states = {h["host_id"] for h in coordinator.hosts()}
            if {p.host_id for p in procs} <= states:
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"hosts never registered: have "
                f"{[h['host_id'] for h in coordinator.hosts()]}"
            )
    except BaseException:
        for p in procs:
            p.proc.kill()
        coordinator.stop()
        raise
    router = MetaRouter(
        coordinator,
        default_timeout_s=default_timeout_s,
        max_failovers=max_failovers,
        probe_interval_s=probe_interval_s,
    )
    frontend = None
    if frontend_port is not None:
        frontend = MeshFrontend(router, port=frontend_port).start()
    return LocalMesh(coordinator, router, procs, frontend)


def build_inprocess_host(
    promoted_dir: str | Path,
    coordinator_url: str,
    host_id: str,
    obs_dim: int,
    env_params: Any = None,
    act_dim: int = 2,
    replicas: int = 1,
    buckets: Sequence[int] = (1,),
    heartbeat_s: float = 0.2,
    devices: Optional[Sequence[Any]] = None,
    window_ms: float = 2.0,
):
    """The host stack inside the CURRENT process (thread-level tests):
    returns ``(router, fleet, frontend, agent)``, all started. The
    caller owns teardown (agent/frontend/router stop order)."""
    from marl_distributedformation_tpu.serving.fleet import (
        FleetFrontend,
        fleet_from_checkpoint_dir,
        warmup_fleet,
    )
    from marl_distributedformation_tpu.serving.mesh.agent import HostAgent

    router, fleet = fleet_from_checkpoint_dir(
        promoted_dir,
        env_params=env_params,
        act_dim=act_dim,
        num_replicas=replicas,
        buckets=tuple(buckets),
        devices=devices,
        window_ms=window_ms,
    )
    router.start()
    warmup_fleet(router, (obs_dim,))
    frontend = FleetFrontend(router).start()
    agent = HostAgent(
        host_id=host_id,
        router=router,
        fleet=fleet,
        coordinator_url=coordinator_url,
        data_url=frontend.url,
        heartbeat_interval_s=heartbeat_s,
    ).start()
    return router, fleet, frontend, agent
