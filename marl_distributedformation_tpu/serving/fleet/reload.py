"""Fleet-wide coordinated hot reload: poll once, swap everywhere,
globally step-monotonic.

``ModelRegistry`` (serving/registry.py) solves hot reload for ONE
engine: snapshot-per-batch plus a step-monotonic swap under a lock. A
fleet of replicas re-raises the consistency question — if each replica
polled and swapped independently, two things go wrong: N replicas pay N
redundant restores per checkpoint, and (worse) a client hopping between
replicas can observe ``model_step`` going BACKWARD: replica A swaps to
step 200 and answers, then replica B — poll racing a slow restore —
answers with step 100. The ROADMAP names the fix: "coordinator polls,
broadcasts the step, hosts swap at a batch barrier".

:class:`FleetReloadCoordinator` implements exactly that:

1. **Poll once.** One watcher polls ``logs/{name}/`` via
   ``latest_checkpoint``; one restore + one validation per new
   checkpoint, regardless of fleet width.
2. **Prepare.** The validated host tree is ``device_put`` onto every
   replica's device BEFORE any replica is touched — no replica ever
   stalls mid-swap waiting for a weight upload.
3. **Commit at the fleet batch barrier.** Every replica's scheduler
   holds its registry's ``batch_lock`` for the duration of each
   dispatch (scheduler.py). The coordinator acquires ALL replica locks,
   which can only succeed at a moment when zero batches are in flight
   anywhere, flips every replica's ``(params, step)`` cell, and
   releases. Consequence: every response resolved before the commit
   carries the old step, every response dispatched after carries the
   new one — ``model_step`` is globally monotonic in response order,
   fleet-wide, with no pause longer than one in-flight batch.

Failure containment mirrors the single-engine registry: a
mismatched-architecture / drifted-dtype / foreign checkpoint is a
recorded ``load_errors`` entry and the fleet keeps serving the old
params; older/equal steps are ignored; broken replicas still receive
the new params so a later revival serves the current step, never a
stale one.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Deque, Optional, Tuple

from marl_distributedformation_tpu.chaos.plane import fault_point
from marl_distributedformation_tpu.obs import get_tracer
from marl_distributedformation_tpu.utils.checkpoint import (
    CheckpointDiscovery,
    checkpoint_step,
    latest_checkpoint,
    restore_state_dict_partial,
)


class BatchBarrier:
    """A dispatch lock with a coordinator-side gate.

    The worker side is a plain context manager held across each dispatch
    (``with registry.batch_lock:``). The subtlety is FAIRNESS: under
    load a worker releases its lock and re-acquires it microseconds
    later for the next batch, and CPython locks are not FIFO — a
    coordinator blocked in ``acquire()`` can starve for seconds behind
    that re-acquisition loop. So the coordinator first ``close()``s the
    gate; workers park at the gate BEFORE contending the lock, and the
    coordinator gets every lock within at most one in-flight batch.
    ``open()`` releases the parked workers after the commit.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open = threading.Event()
        self._open.set()

    # -- worker side (one dispatch) --------------------------------------

    def __enter__(self) -> "BatchBarrier":
        self._open.wait()
        self._lock.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._lock.release()

    # -- coordinator side (fleet commit) ---------------------------------

    def close(self) -> None:
        self._open.clear()

    def acquire(self, timeout: Optional[float] = None) -> bool:
        return self._lock.acquire(
            timeout=-1 if timeout is None else timeout
        )

    def release(self) -> None:
        self._lock.release()

    def open(self) -> None:
        self._open.set()


class ReplicaRegistry:
    """One replica's ``(params, step)`` cell plus its batch barrier.

    The scheduler holds ``batch_lock`` across each dispatch and reads
    :meth:`active` once per micro-batch; the coordinator writes via
    :meth:`install` only while holding every replica's barrier.
    ``active`` itself is lock-free — a single tuple attribute read is
    atomic in CPython, and the worker already holds the barrier when it
    snapshots (a locking ``active`` would self-deadlock)."""

    def __init__(self, params: Any, step: int, device: Any = None) -> None:
        self.device = device
        self.batch_lock = BatchBarrier()  # graftlock: gate
        self.swap_count = 0  # graftlock: guarded-by=batch_lock
        self._snapshot: Tuple[Any, int] = (params, step)  # graftlock: guarded-by=batch_lock

    def active(self) -> Tuple[Any, int]:
        return self._snapshot

    @property
    def active_step(self) -> int:
        return self._snapshot[1]

    # graftlock: holds=batch_lock
    def install(self, params: Any, step: int) -> None:
        """Replace the serving snapshot. Caller holds ``batch_lock``."""
        self._snapshot = (params, step)
        self.swap_count += 1


class FleetReloadCoordinator:
    """Single poller + fleet-wide batch-barrier swap over a router.

    Args:
      log_dir: the ``logs/{name}/`` directory the trainer checkpoints to.
      router: a started-or-not ``fleet.FleetRouter``; the coordinator
        swaps through its replicas' :class:`ReplicaRegistry` cells.
      poll_interval_s: cadence of the background watcher (``start()``);
        ``refresh()`` may also be called directly.
      commit_timeout_s: bound on waiting for any single replica's
        barrier at commit time. A worker wedged inside a device dispatch
        (a hung device op) holds its lock indefinitely; without the
        bound, one wedged replica would park the WHOLE fleet behind
        closed gates. On timeout the commit aborts cleanly — locks
        released, gates reopened, a recorded ``load_errors`` entry —
        and every replica keeps serving the old step (never a partial
        swap); the next poll retries.
      model_id: optional tenant lane (serving/tenancy): the coordinator
        then watches ONE lane's ``promoted/`` directory and commits
        into each replica's ``registries[model_id]`` cell, acquiring
        only that lane's batch barriers — other lanes' dispatch groups
        keep running through the whole commit, and ``fleet_step`` is
        that lane's own monotonic step (per-model monotonicity).
    """

    def __init__(
        self,
        log_dir: str | Path,
        router: Any,
        poll_interval_s: float = 2.0,
        max_recorded_errors: int = 32,
        commit_timeout_s: float = 30.0,
        model_id: Optional[str] = None,
    ) -> None:
        self.log_dir = Path(log_dir)
        self.router = router
        self.model_id = model_id
        self.poll_interval_s = poll_interval_s
        self.commit_timeout_s = commit_timeout_s
        self.swap_count = 0  # graftlock: guarded-by=_refresh_lock
        # Host-count/commit-round attribution of the newest landed swap
        # (promotions.jsonl schema 4). A single-host fleet always
        # commits 1 host; the mesh coordinator's global commit mirrors
        # this attribute with the real host count and round number.
        self.last_commit: Optional[dict] = None  # graftlock: guarded-by=_refresh_lock
        # Unannotated on purpose: deque.append is atomic under the GIL
        # and failure paths record without re-entering any lock.
        self.load_errors: Deque[Tuple[str, str]] = deque(
            maxlen=max_recorded_errors
        )
        # Cross-host staged state (prepare_global/commit_prepared): the
        # mesh coordinator's two-phase barrier holds this host paused —
        # gates closed, every replica barrier held, new params staged —
        # between the prepare ack and the commit/abort decision.
        self._staged: Optional[dict] = None  # graftlock: guarded-by=_staged_lock
        self._staged_lock = threading.Lock()
        # Incremental discovery: a long-running watcher polls this
        # directory forever, and re-listing + re-parsing every historic
        # checkpoint each poll degrades O(total checkpoints). Same
        # discovery contract as latest_checkpoint (utils.checkpoint).
        self._discovery = CheckpointDiscovery(self.log_dir)
        # The fleet step starts at the newest step any replica already
        # serves (the router seeds every replica identically).
        self._fleet_step = max(  # graftlock: guarded-by=_refresh_lock
            reg.active_step for reg in self._commit_registries()
        )
        self._refresh_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _commit_registries(self) -> list:
        """The registry cells this coordinator swaps — one per replica.
        Single-model: each replica's primary ``registry``. Lane-keyed
        (``model_id`` set): each replica's ``registries[model_id]``
        cell, whose barrier gates only that lane's dispatch groups."""
        if self.model_id is None:
            return [r.registry for r in self.router.replicas]
        return [
            r.registries[self.model_id] for r in self.router.replicas
        ]

    @property
    def fleet_step(self) -> int:
        """The step every post-commit dispatch serves (this lane's, when
        the coordinator is lane-keyed)."""
        return self._fleet_step

    # -- reload ---------------------------------------------------------

    def refresh(self, trace_id: Optional[str] = None) -> bool:
        """Check the directory once; coordinated-swap if a newer
        checkpoint landed. Returns True on swap. Load failures keep the
        old params serving fleet-wide and are recorded. ``trace_id``
        labels the commit's spans (the pipeline passes its candidate's
        ID so one trace reconstructs the whole promotion)."""
        with self._refresh_lock:
            path = self._discovery.latest()
            if path is None:
                return False
            step = checkpoint_step(path)
            if step <= self._fleet_step:
                return False
            return self._load_and_commit(path, step, trace_id)

    def reload_pinned(
        self,
        path: str | Path,
        monotonic: bool = True,
        trace_id: Optional[str] = None,
    ) -> bool:
        """Coordinated swap of an EXPLICIT checkpoint path, bypassing
        directory discovery. ``monotonic=False`` is the DEMOTION hook
        (pipeline/rollback): the swap is exempt from the never-go-
        backward rule, so a rollback to the last-good checkpoint is just
        a pinned reload at the same fleet batch barrier — responses
        after the commit legitimately carry the older step, and the
        caller owns retracting the demoted checkpoint from the watched
        directory (otherwise the next poll would re-promote it). With
        ``monotonic=True`` this is a targeted forward swap with the
        usual old-steps-ignored semantics. Same containment contract as
        :meth:`refresh`: a bad file is a recorded ``load_errors`` entry
        and the fleet keeps serving what it serves."""
        path = Path(path)
        with self._refresh_lock:
            try:
                step = checkpoint_step(path)
            except ValueError as e:
                self.load_errors.append((str(path), repr(e)))
                return False
            if monotonic and step <= self._fleet_step:
                return False
            if step == self._fleet_step:
                return False  # already serving exactly this step
            return self._load_and_commit(path, step, trace_id)

    # graftlock: holds=_refresh_lock
    def _load_and_commit(
        self, path: Path, step: int, trace_id: Optional[str] = None
    ) -> bool:
        """Restore + validate once, then commit fleet-wide at the batch
        barrier. Caller holds ``_refresh_lock``."""
        tracer = get_tracer()
        try:
            with tracer.span(
                "reload.load", trace_id=trace_id, step=step, path=str(path)
            ):
                restored = self._load_validated(path)
        except Exception as e:  # noqa: BLE001 — serving must not die
            self.load_errors.append((str(path), repr(e)))
            return False
        import jax

        # Prepare: one host->device upload per replica, all before
        # the barrier — the commit window stays lock-acquisition
        # plus pointer flips, never a weight transfer.
        with tracer.span("reload.stage", trace_id=trace_id, step=step):
            staged = [
                (reg, jax.device_put(restored, reg.device))
                for reg in self._commit_registries()
            ]
        barriers = [reg.batch_lock for reg, _ in staged]
        held = []
        installed = []
        wedged_replica = None
        try:
            # Close every gate FIRST: workers finish their current
            # batch and park instead of re-contending their lock, so
            # the acquisitions below complete within one in-flight
            # batch (BatchBarrier's fairness note). Workers only
            # ever hold their own lock — no cycle to deadlock on.
            # With all locks held, zero batches are in flight
            # fleet-wide: the commit point. The per-barrier timeout
            # bounds a wedged replica (hung device op holding its
            # lock): abort the WHOLE commit rather than park the
            # fleet or swap partially — the finally reopens every
            # gate and the old step keeps serving everywhere.
            for b in barriers:
                b.close()
            for i, b in enumerate(barriers):
                fault_point("fleet.barrier")
                t_acq = time.perf_counter()
                acquired = b.acquire(timeout=self.commit_timeout_s)
                tracer.add_span(
                    "reload.barrier_acquire",
                    t_acq,
                    time.perf_counter(),
                    trace_id=trace_id,
                    replica=i,
                    acquired=acquired,
                )
                if not acquired:
                    self.load_errors.append(
                        (
                            str(path),
                            f"commit aborted: replica {i} barrier "
                            f"not acquired in {self.commit_timeout_s}"
                            "s (wedged dispatch?); old step keeps "
                            "serving fleet-wide",
                        )
                    )
                    wedged_replica = i
                    return False
                held.append(b)
            with tracer.span(
                "reload.commit", trace_id=trace_id, step=step,
                replicas=len(staged),
            ):
                for reg, params in staged:
                    prev = reg.active()
                    fault_point("registry.swap")
                    reg.install(params, step)
                    installed.append((reg, prev))
                self._fleet_step = step
                self.swap_count += 1
                self.last_commit = {
                    "commit_round": self.swap_count,
                    "host_count": 1,
                    "step": step,
                }
                if self.model_id is not None:
                    self.last_commit["model_id"] = self.model_id
        except Exception as e:  # noqa: BLE001 — contain + untear
            # A failure mid-commit (an injected fault, a broken
            # registry) must not leave a TORN swap: some replicas on
            # the new step, others on the old, is exactly the
            # inconsistency the batch barrier exists to prevent. Roll
            # every installed replica back to its previous cell (all
            # locks are still held — the fleet never serves the torn
            # state), record, and keep serving the old step everywhere.
            for reg, (prev_params, prev_step) in reversed(installed):
                reg.install(prev_params, prev_step)
            self.load_errors.append(
                (
                    str(path),
                    f"commit aborted mid-swap and rolled back: {e!r}; "
                    "old step keeps serving fleet-wide",
                )
            )
            return False
        finally:
            for b in reversed(held):
                b.release()
            for b in barriers:
                b.open()
            if wedged_replica is not None:
                # A wedged barrier is a postmortem-grade incident: the
                # ring still holds the dispatches that led here. Dumped
                # AFTER the gates reopen — the flight-recorder file
                # write must not extend the fleet-wide serving pause.
                tracer.incident(
                    "wedged_barrier_abort",
                    trace_id=trace_id,
                    replica=wedged_replica,
                    step=step,
                    path=str(path),
                    commit_timeout_s=self.commit_timeout_s,
                )
        # Swap boundary: both param generations are still referenced
        # here (staged + the replicas' previous cells), which is the
        # transient double-residency peak the autoscaler must plan for —
        # sample it into the ledger's watermark gauge AFTER the gates
        # reopened, so the reading never extends the serving pause.
        from marl_distributedformation_tpu.analysis.guards import (
            sample_device_watermark,
        )

        sample_device_watermark(force=True)  # swaps are rare: always sample
        return True

    def _load_validated(self, path: Path) -> Any:
        """One restore + validation for the whole fleet, against replica
        0's live tree (all replicas serve the same architecture) — the
        same template validation ``ModelRegistry.refresh`` performs."""
        from marl_distributedformation_tpu.compat.policy import (
            load_checkpoint_raw,
        )

        raw = load_checkpoint_raw(path)
        want = type(self.router.policy.model).__name__
        got = raw.get("policy", want)
        if got != want:
            raise ValueError(
                f"checkpoint {path} was trained with policy {got!r}; "
                f"this fleet serves {want!r}"
            )
        template = {"params": self._commit_registries()[0].active()[0]}
        return restore_state_dict_partial(
            raw, template, origin=str(path)
        )["params"]

    # -- elastic re-split (serving/elastic) ------------------------------

    def commit_resplit(
        self,
        add: Any = (),
        retire: Any = (),
        sharded_min_rows: Optional[int] = None,
        trace_id: Optional[str] = None,
    ) -> dict:
        """Land a capacity re-split — replicas added, replicas retired,
        the big-rung routing threshold re-pinned — at the SAME fleet
        batch barrier a reload commits at, so no in-flight request ever
        observes a torn replica set and ``model_step`` monotonicity is
        untouched (added replicas must already serve the current fleet
        step; a prewarm the fleet stepped past is refused, the
        controller retries).

        ``add`` replicas come PREWARMED from the controller: engines
        built, every rung compiled off the serving path, schedulers
        started but unrouted. ``retire`` names replica indices to swap
        out of routing; the CALLER drains and stops them after the
        gates reopen (``router.drain_replica`` — drain-before-retire
        must not extend the serving pause).

        Returns a report dict; never raises. ``committed`` False means
        the old split keeps serving and ``load_errors`` records why.
        ``pause_ms`` is the barrier-commit pause only — gates closed to
        gates reopened — which is the whole serving interruption a
        re-split costs (prewarm compiles happen before, drains after).
        """
        if self.model_id is not None:
            raise ValueError(
                "elastic re-split over a lane-keyed coordinator is not "
                "supported yet (docs/serving.md 'Limits / next')"
            )
        add = list(add)
        retire_set = {int(i) for i in retire}
        tracer = get_tracer()
        report: dict = {
            "committed": False,
            "pause_ms": 0.0,
            "added": [r.index for r in add],
            "retired": sorted(retire_set),
        }
        with self._refresh_lock:
            current = list(self.router.replicas)
            known = {r.index for r in current}
            missing = retire_set - known
            if missing:
                self.load_errors.append(
                    (
                        "resplit",
                        f"resplit refused: retire names unknown "
                        f"replicas {sorted(missing)}",
                    )
                )
                return report
            stale = [
                r.index
                for r in add
                if r.registry.active_step != self._fleet_step
            ]
            if stale:
                # The fleet stepped forward while the controller was
                # prewarming: committing these replicas would serve an
                # older step after a newer one — exactly the
                # monotonicity violation the barrier exists to prevent.
                self.load_errors.append(
                    (
                        "resplit",
                        f"resplit refused: prewarmed replicas {stale} "
                        f"serve a step != fleet step {self._fleet_step} "
                        "(reload landed during prewarm); re-prewarm and "
                        "retry",
                    )
                )
                report["stale_prewarm"] = True
                return report
            barriers = [r.registry.batch_lock for r in current]
            held = []
            wedged_replica = None
            t_closed = 0.0
            t_open = 0.0
            try:
                for b in barriers:
                    b.close()
                t_closed = time.perf_counter()
                for i, b in enumerate(barriers):
                    fault_point("fleet.barrier")
                    acquired = b.acquire(timeout=self.commit_timeout_s)
                    if not acquired:
                        self.load_errors.append(
                            (
                                "resplit",
                                f"resplit aborted: replica {i} barrier "
                                f"not acquired in {self.commit_timeout_s}"
                                "s (wedged dispatch?); old split keeps "
                                "serving",
                            )
                        )
                        wedged_replica = i
                        return report
                    held.append(b)
                with tracer.span(
                    "elastic.commit",
                    trace_id=trace_id,
                    added=len(add),
                    retired=len(retire_set),
                ):
                    fault_point("elastic.commit")
                    self.router._commit_resplit(
                        add, retire_set, sharded_min_rows=sharded_min_rows
                    )
                    report["committed"] = True
                    report["step"] = self._fleet_step
            except Exception as e:  # noqa: BLE001 — contain, keep serving
                # The membership swap is one list assignment — a fault
                # before it (the armed elastic.commit seam) leaves the
                # old split fully intact; nothing to untear.
                self.load_errors.append(
                    (
                        "resplit",
                        f"resplit commit aborted: {e!r}; old split "
                        "keeps serving",
                    )
                )
                report["error"] = repr(e)
                return report
            finally:
                for b in reversed(held):
                    b.release()
                for b in barriers:
                    b.open()
                t_open = time.perf_counter()
                report["pause_ms"] = round(
                    max(0.0, (t_open - t_closed)) * 1e3, 3
                )
                if wedged_replica is not None:
                    tracer.incident(
                        "wedged_barrier_abort",
                        trace_id=trace_id,
                        replica=wedged_replica,
                        step=self._fleet_step,
                        path="resplit",
                        commit_timeout_s=self.commit_timeout_s,
                    )
        # Both the retiring and the incoming engines' params are live
        # here — the same double-residency shape a reload peaks at.
        # Sample AFTER the gates reopened: the watermark read must not
        # extend the pause it is measuring.
        from marl_distributedformation_tpu.analysis.guards import (
            sample_device_watermark,
        )

        sample_device_watermark(force=True)
        return report

    # -- cross-host staged two-phase (serving/mesh) ----------------------
    #
    # The mesh coordinator generalizes the batch-barrier commit across
    # hosts: it cannot hold every host's locks itself, so each host
    # splits _load_and_commit at the commit point. ``prepare_global``
    # does everything UP TO the pointer flip — restore + validate once,
    # stage per-replica uploads, close the gates, acquire every replica
    # barrier — then HOLDS that state (the host serves nothing) until
    # the coordinator decides: ``commit_prepared`` flips every cell and
    # resumes, ``abort_prepared`` resumes on the old step. Because every
    # host pauses before any host commits, no old-step response can
    # complete after a new-step response anywhere — model_step stays
    # globally monotonic in response completion order across the mesh.
    # ``ttl_s`` bounds an orphaned prepare (coordinator died mid-round):
    # the host auto-aborts and keeps serving the old step rather than
    # staying paused forever.

    def prepare_global(
        self,
        path: str | Path,
        step: Optional[int] = None,
        monotonic: bool = True,
        trace_id: Optional[str] = None,
        ttl_s: Optional[float] = 60.0,
    ) -> Tuple[bool, str]:
        """Phase 1 of the cross-host swap: stage + pause. Returns
        ``(staged, reason)``; on False the host is untouched and keeps
        serving. The refresh lock stays held across a successful
        prepare so no local reload can interleave with the mesh round —
        commit/abort release it."""
        path = Path(path)
        # Refuse FAST when the lock is busy instead of parking: the
        # refresh lock is only held long while a round is staged, and
        # a prepare that blocks past the coordinator's RPC timeout
        # becomes a zombie — its late "staged" ack lands after the
        # round aborted, wedging the NEXT round in turn. A quick typed
        # refusal lets the coordinator abort-and-clear and retry.
        if not self._refresh_lock.acquire(timeout=0.25):
            with self._staged_lock:
                staleness = (
                    f" (round {self._staged['round_tag']} is staged "
                    "here awaiting commit/abort)"
                    if self._staged is not None
                    else ""
                )
            return False, f"another reload holds the refresh lock{staleness}"
        staged_ok = False
        try:
            with self._staged_lock:
                if self._staged is not None:
                    return False, (
                        f"round {self._staged['round_tag']} is already "
                        "staged on this host (commit or abort it first)"
                    )
            try:
                step = checkpoint_step(path) if step is None else int(step)
            except ValueError as e:
                self.load_errors.append((str(path), repr(e)))
                return False, f"unparseable checkpoint name: {e}"
            if monotonic and step <= self._fleet_step:
                return False, (
                    f"stale step {step} <= served {self._fleet_step}"
                )
            if step == self._fleet_step:
                return False, f"already serving step {step}"
            tracer = get_tracer()
            try:
                with tracer.span(
                    "reload.load", trace_id=trace_id, step=step,
                    path=str(path),
                ):
                    restored = self._load_validated(path)
            except Exception as e:  # noqa: BLE001 — serving must not die
                self.load_errors.append((str(path), repr(e)))
                return False, f"load failed: {e!r}"
            import jax

            with tracer.span(
                "reload.stage", trace_id=trace_id, step=step
            ):
                staged = [
                    (reg, jax.device_put(restored, reg.device))
                    for reg in self._commit_registries()
                ]
            barriers = [reg.batch_lock for reg, _ in staged]
            held = []
            wedged_replica = None
            try:
                for b in barriers:
                    b.close()
                for i, b in enumerate(barriers):
                    fault_point("fleet.barrier")
                    t_acq = time.perf_counter()
                    acquired = b.acquire(timeout=self.commit_timeout_s)
                    tracer.add_span(
                        "reload.barrier_acquire",
                        t_acq,
                        time.perf_counter(),
                        trace_id=trace_id,
                        replica=i,
                        acquired=acquired,
                    )
                    if not acquired:
                        reason = (
                            f"prepare aborted: replica {i} barrier not "
                            f"acquired in {self.commit_timeout_s}s "
                            "(wedged dispatch?); old step keeps serving"
                        )
                        self.load_errors.append((str(path), reason))
                        wedged_replica = i
                        return False, reason
                    held.append(b)
            except BaseException as e:
                # Untear like _load_and_commit: an exception with gates
                # closed (an armed fleet.barrier fault, a broken
                # registry) must not leave the host paused forever —
                # the only finally below releases the refresh lock,
                # not these.
                reason = f"prepare aborted mid-acquisition: {e!r}"
                self.load_errors.append((str(path), reason))
                if isinstance(e, Exception):
                    return False, reason
                raise  # SimulatedCrash-grade: die, but gates reopened
            finally:
                landed = len(held) == len(barriers)
                if not landed:
                    for h in reversed(held):
                        h.release()
                    for b in barriers:
                        b.open()
                if wedged_replica is not None:
                    # Postmortem dump AFTER the partial acquisitions
                    # released and the gates reopened — mirroring
                    # _load_and_commit, the flight-recorder file write
                    # must not extend the serving pause the wedged
                    # barrier already caused.
                    tracer.incident(
                        "wedged_barrier_abort",
                        trace_id=trace_id,
                        replica=wedged_replica,
                        step=step,
                        path=str(path),
                        commit_timeout_s=self.commit_timeout_s,
                    )
            timer: Optional[threading.Timer] = None
            entry = {
                "round_tag": f"step{step}",
                "path": path,
                "step": step,
                "staged": staged,
                "barriers": barriers,
                "held": held,
                "trace_id": trace_id,
                "timer": None,
            }
            if ttl_s is not None:
                timer = threading.Timer(
                    ttl_s, self._ttl_abort, args=(entry,)
                )
                timer.daemon = True
                entry["timer"] = timer
            with self._staged_lock:
                self._staged = entry
            if timer is not None:
                timer.start()
            staged_ok = True
            return True, f"staged step {step}"
        finally:
            if not staged_ok:
                self._refresh_lock.release()

    def _take_staged(self) -> Optional[dict]:
        with self._staged_lock:
            entry, self._staged = self._staged, None
        if entry is not None and entry["timer"] is not None:
            entry["timer"].cancel()
        return entry

    # graftlock: holds=_refresh_lock
    def commit_prepared(self, trace_id: Optional[str] = None) -> bool:
        """Phase 2: flip every staged replica and resume. Returns False
        when nothing is staged (an aborted/TTL-expired round — the
        coordinator treats that as this host having dropped out).
        The refresh lock was acquired by :meth:`prepare_global` and is
        released here (or by abort) — the staged window holds it."""
        entry = self._take_staged()
        if entry is None:
            return False
        tracer = get_tracer()
        installed = []
        try:
            with tracer.span(
                "reload.commit",
                trace_id=trace_id or entry["trace_id"],
                step=entry["step"],
                replicas=len(entry["staged"]),
            ):
                for reg, params in entry["staged"]:
                    prev = reg.active()
                    fault_point("registry.swap")
                    reg.install(params, entry["step"])
                    installed.append((reg, prev))
                self._fleet_step = entry["step"]
                self.swap_count += 1
        except Exception as e:  # noqa: BLE001 — contain + untear
            for reg, (prev_params, prev_step) in reversed(installed):
                reg.install(prev_params, prev_step)
            self.load_errors.append(
                (
                    str(entry["path"]),
                    f"staged commit aborted mid-swap and rolled back: "
                    f"{e!r}; old step keeps serving",
                )
            )
            return False
        finally:
            for b in reversed(entry["held"]):
                b.release()
            for b in entry["barriers"]:
                b.open()
            self._refresh_lock.release()
        from marl_distributedformation_tpu.analysis.guards import (
            sample_device_watermark,
        )

        sample_device_watermark(force=True)
        return True

    def abort_prepared(self, reason: str = "") -> bool:
        """Resume on the old step without installing anything (the
        coordinator's round failed on some other host, or the local
        TTL expired). Always safe to call; returns False when nothing
        was staged."""
        entry = self._take_staged()
        if entry is None:
            return False
        for b in reversed(entry["held"]):
            b.release()
        for b in entry["barriers"]:
            b.open()
        self._refresh_lock.release()
        if reason:
            self.load_errors.append(
                (str(entry["path"]), f"prepare aborted: {reason}")
            )
        return True

    def _ttl_abort(self, entry: dict) -> None:
        """An orphaned prepare (no commit/abort before the TTL): the
        coordinator is gone — resume serving the OLD step rather than
        stay paused forever. Guarded against racing a landing commit:
        only fires if this exact entry is still the staged one."""
        with self._staged_lock:
            if self._staged is not entry:
                return  # commit/abort won the race
        self.abort_prepared(
            "prepare TTL expired with no commit/abort — coordinator "
            "presumed dead; serving resumed on the old step"
        )
        get_tracer().incident(
            "orphaned_prepare_abort",
            trace_id=entry["trace_id"],
            step=entry["step"],
            path=str(entry["path"]),
        )

    # -- background watcher ---------------------------------------------

    def start(self) -> "FleetReloadCoordinator":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._watch, name="fleet-reload-coordinator", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10.0)
        self._thread = None

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            self.refresh()

    def __enter__(self) -> "FleetReloadCoordinator":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def fleet_from_checkpoint_dir(
    log_dir: str | Path,
    env_params: Any = None,
    act_dim: int = 2,
    poll_interval_s: float = 2.0,
    **router_kwargs: Any,
):
    """Build a ``(FleetRouter, FleetReloadCoordinator)`` pair serving the
    newest checkpoint under ``log_dir`` — the fleet twin of constructing
    a ``ModelRegistry`` from a directory. Router kwargs (``buckets``,
    ``num_replicas``, ``window_ms``, …) pass through."""
    from marl_distributedformation_tpu.compat.policy import LoadedPolicy
    from marl_distributedformation_tpu.serving.fleet.router import (
        FleetRouter,
    )

    log_dir = Path(log_dir)
    path = latest_checkpoint(log_dir)
    if path is None:
        raise FileNotFoundError(
            f"no rl_model_*_steps.msgpack checkpoint under {log_dir} "
            "to serve"
        )
    policy = LoadedPolicy.from_checkpoint(
        path, act_dim=act_dim, env_params=env_params
    )
    router = FleetRouter(
        policy, initial_step=checkpoint_step(path), **router_kwargs
    )
    coordinator = FleetReloadCoordinator(
        log_dir, router, poll_interval_s=poll_interval_s
    )
    return router, coordinator
