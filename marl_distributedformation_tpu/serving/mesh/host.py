"""Mesh host process: one fleet + frontend + agent, loopback-spawnable.

``python -m marl_distributedformation_tpu.serving.mesh.host`` boots the
full per-host serving stack — ``FleetRouter`` over the local devices,
``FleetFrontend`` on the data port, ``HostAgent`` on the control port —
from a promoted-checkpoint directory, registers with the coordinator,
and serves until killed. This is the unit the loopback mesh
(``serving/mesh/loopback.py``), the chaos storm's ``--mesh`` campaign,
and the mesh smoke spawn as real OS processes: ``kill -9`` of one of
these is a REAL host death, not a ``SimulatedCrash``.

The process prints exactly ONE JSON line on stdout when ready::

    {"ready": true, "host_id": ..., "data_url": ..., "control_url": ...,
     "pid": ..., "step": ...}

and nothing else (logs go to stderr), so a parent can parse the ports
it bound ephemerally. ``--fault-spec`` arms the process-local chaos
plane with an explicit JSON fault list — how the wedged-host barrier
tests make THIS host (and only this host) misbehave deterministically.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--promoted-dir", required=True,
        help="coordinator-watched checkpoint directory to serve from",
    )
    ap.add_argument("--coordinator-url", required=True)
    ap.add_argument("--host-id", required=True)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--buckets", default="1,8")
    ap.add_argument("--obs-dim", type=int, default=None)
    ap.add_argument("--act-dim", type=int, default=2)
    ap.add_argument(
        "--num-agents", type=int, default=None,
        help="build EnvParams(num_agents=...) for per-formation "
        "policies (obs-dim then derives from it)",
    )
    ap.add_argument("--port", type=int, default=0, help="data port")
    ap.add_argument("--control-port", type=int, default=0)
    ap.add_argument("--heartbeat-s", type=float, default=0.25)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument(
        "--fault-spec", default=None,
        help="JSON list of {point, kind, at_hit, seconds} to arm on "
        "THIS host's chaos plane (deterministic misbehavior for the "
        "barrier tests)",
    )
    args = ap.parse_args(argv)

    from marl_distributedformation_tpu.utils import (
        announce_device,
        widen_cpu_pool,
    )

    # One virtual device per replica where the CPU was asked for by name
    # (the loopback mesh spawns hosts under JAX_PLATFORMS=cpu); on an
    # accelerator the replicas share the devices the hardware has.
    widen_cpu_pool(max(1, args.replicas))
    announce_device(f"mesh-host {args.host_id}", file=sys.stderr)

    from marl_distributedformation_tpu.serving.fleet import (
        FleetFrontend,
        fleet_from_checkpoint_dir,
        warmup_fleet,
    )
    from marl_distributedformation_tpu.serving.mesh.agent import HostAgent

    env_params = None
    obs_dim = args.obs_dim
    if args.num_agents is not None:
        from marl_distributedformation_tpu.env import EnvParams

        env_params = EnvParams(num_agents=args.num_agents)
        obs_dim = env_params.obs_dim
    if obs_dim is None:
        ap.error("--obs-dim or --num-agents is required (warmup shape)")

    if args.fault_spec:
        from marl_distributedformation_tpu.chaos import (
            FaultSchedule,
            FaultSpec,
            get_fault_plane,
        )

        specs = [
            FaultSpec(
                point=str(s["point"]),
                kind=str(s["kind"]),
                at_hit=int(s.get("at_hit", 1)),
                seconds=float(s.get("seconds", 0.0)),
            )
            for s in json.loads(args.fault_spec)
        ]
        plane = get_fault_plane()
        plane.arm(FaultSchedule(specs))
        plane.enabled = True
        print(
            f"[mesh-host {args.host_id}] chaos armed: {len(specs)} "
            "fault(s)",
            file=sys.stderr,
        )

    router, fleet = fleet_from_checkpoint_dir(
        args.promoted_dir,
        env_params=env_params,
        act_dim=args.act_dim,
        num_replicas=args.replicas,
        buckets=tuple(int(b) for b in args.buckets.split(",") if b),
        window_ms=args.window_ms,
    )
    # The MESH coordinator drives every reload through the agent's
    # staged two-phase RPCs — the local directory watcher must stay
    # off, or host-local polls would race the global barrier.
    router.start()
    warmup_fleet(router, (obs_dim,))
    frontend = FleetFrontend(router, port=args.port).start()
    agent = HostAgent(
        host_id=args.host_id,
        router=router,
        fleet=fleet,
        coordinator_url=args.coordinator_url,
        data_url=frontend.url,
        control_port=args.control_port,
        heartbeat_interval_s=args.heartbeat_s,
    ).start()

    print(
        json.dumps(
            {
                "ready": True,
                "host_id": args.host_id,
                "data_url": frontend.url,
                "control_url": agent.control_url,
                "pid": os.getpid(),
                "step": int(fleet.fleet_step),
            }
        ),
        flush=True,
    )

    done = threading.Event()

    def _term(signum, frame) -> None:
        done.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    try:
        done.wait()
    finally:
        agent.stop()
        frontend.stop()
        router.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
