"""Checkpoint save/restore with the reference's discovery contract.

Write path mirrors SB3's ``CheckpointCallback`` naming
(``rl_model_{num_timesteps}_steps`` under ``logs/{name}/``,
vectorized_env.py:124); read path mirrors ``visualize_policy.py:31`` — pick
the file whose step number (``name.split("_")[-2]``) is largest. Unlike the
reference (which never resumes optimizer state — SURVEY.md §5), checkpoints
here carry params, optimizer state, and PRNG key, so training resume is
exact.

Format: flax msgpack serialization of the train-state pytree in a single
file — host-side, TPU-independent, and restorable on any backend.
"""

from __future__ import annotations

import json
import os
import random
import re
import struct
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional

from flax import serialization

from marl_distributedformation_tpu.chaos.plane import (
    SimulatedCrash,
    fault_point,
)

_STEP_RE = re.compile(r"rl_model_(\d+)_steps")
# Population-sweep state files live beside member dirs under the sweep's
# log_dir; the distinct prefix keeps them invisible to the rl_model_*
# discovery scan (visualize_policy/member resume must never pick one up).
_SWEEP_STEP_RE = re.compile(r"sweep_state_(\d+)_steps")


def checkpoint_path(log_dir: str | Path, num_timesteps: int) -> Path:
    return Path(log_dir) / f"rl_model_{num_timesteps}_steps.msgpack"


def sweep_state_path(log_dir: str | Path, num_timesteps: int) -> Path:
    return Path(log_dir) / f"sweep_state_{num_timesteps}_steps.msgpack"


def save_checkpoint(
    log_dir: str | Path, num_timesteps: int, target: Any, sync: bool = True
) -> Optional[Path]:
    """Serialize ``target`` (any pytree) to ``rl_model_{steps}_steps.msgpack``.

    Multi-host: only the coordinator process writes; it returns the path and
    every other process returns **None** (the file does not exist on their
    disks). A ``sync_global_devices`` barrier after the write guarantees
    that when any process returns, the coordinator's file is durable — a
    host may immediately hand the path to a reader. Leaves must be
    process-addressable on the coordinator — replicated trees (params/opt
    state) always are; cross-host-sharded state must be excluded by the
    caller (as ``Trainer._checkpoint_target`` does for the dp-sharded env
    state).
    """
    import jax

    from marl_distributedformation_tpu.parallel.distributed import (
        is_coordinator,
    )

    path = checkpoint_path(log_dir, num_timesteps)
    on_coordinator = is_coordinator()
    if on_coordinator:
        try:
            _write_atomic(path, target)
        except NonFiniteCheckpointError as e:
            # Degrade, never die — and never skip the durability barrier
            # below (peers must not hang on a coordinator that refused a
            # poisoned write).
            _audit_nonfinite_skip(path, str(e))
            path = None
    if sync and jax.process_count() > 1:
        # ``sync=False`` lets a caller writing MANY files per logical
        # checkpoint (the sweep's per-member loop) batch the durability
        # barrier into one trailing synced write instead of paying a
        # cross-host round trip per file.
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"ckpt_{num_timesteps}")
    return path if on_coordinator else None


# ----------------------------------------------------------------------
# Crash-consistent format: payload + checksum footer
# ----------------------------------------------------------------------
#
# The rename-is-publication protocol makes a torn WRITE invisible, but
# it cannot see silent media damage or a truncation that happens after
# the rename (a crashed fsync-less host, a bad sector, an injected
# bit-flip in a chaos campaign). Every checkpoint therefore carries a
# 20-byte footer: crc32(payload) + payload length + magic, validated on
# every read. Footer-less files (pre-chaos-plane checkpoints, foreign
# msgpack files) read as legacy payloads unchanged, so THIS reader
# handles both formats. The converse does not hold: a plain
# ``msgpack_restore(read_bytes())`` from a pre-footer release chokes on
# the trailing 20 bytes — rolling the READER back past this change
# while a new trainer keeps writing is the one unsupported direction
# (roll the writer back too, or strip footers with
# read_checkpoint_payload first).

_CKPT_MAGIC = b"MARLCKPT"
_FOOTER = struct.Struct("<Iq8s")  # crc32, payload length, magic


class CorruptCheckpointError(ValueError):
    """A checkpoint whose bytes fail validation (checksum mismatch,
    truncation past the footer, undecodable msgpack) — damage, not an
    architecture mismatch."""


class NonFiniteCheckpointError(ValueError):
    """A checkpoint target carrying NaN/Inf float leaves. The write gate
    (:func:`_write_atomic`) refuses to publish these: a diverged trainer
    must never make a poisoned state visible to ``latest_checkpoint`` /
    ``CheckpointDiscovery`` — the gate would reject it one candidate at
    a time, resume would restore the divergence, and the recovery
    ladder's rollback walk would find poison where it needs a last-good
    state (train/recovery.py, docs/recovery.md). Callers degrade:
    the async writer skips-with-audit, ``save_checkpoint`` returns
    None."""


def _with_footer(payload: bytes) -> bytes:
    return payload + _FOOTER.pack(
        zlib.crc32(payload) & 0xFFFFFFFF, len(payload), _CKPT_MAGIC
    )


def _strip_footer(data: bytes, origin: str) -> bytes:
    """Validate + strip the checksum footer; legacy (footer-less) bytes
    pass through whole. Raises :class:`CorruptCheckpointError` on a
    failed check."""
    if len(data) < _FOOTER.size or data[-8:] != _CKPT_MAGIC:
        return data  # legacy file: no footer to validate
    crc, length, _ = _FOOTER.unpack(data[-_FOOTER.size:])
    payload = data[: -_FOOTER.size]
    if length != len(payload):
        raise CorruptCheckpointError(
            f"checkpoint {origin}: footer says {length} payload bytes "
            f"but {len(payload)} are present (truncated write?)"
        )
    if zlib.crc32(payload) & 0xFFFFFFFF != crc:
        raise CorruptCheckpointError(
            f"checkpoint {origin}: payload checksum mismatch "
            "(bit rot or torn write)"
        )
    return payload


def quarantine_checkpoint(path: str | Path, reason: str) -> Optional[Path]:
    """Move a corrupt checkpoint ASIDE instead of leaving it to wedge
    every future resume/reload: renamed to ``{name}.quarantined`` (the
    suffix is no longer ``.msgpack``, so ``latest_checkpoint`` and
    ``CheckpointDiscovery`` can never serve it), audit-logged to
    ``quarantine.jsonl`` beside it, counted and flight-recorded.
    Best-effort — returns the quarantine path or None; never raises
    (quarantine runs on already-failing paths)."""
    from marl_distributedformation_tpu.obs import get_registry, get_tracer

    path = Path(path)
    target = path.with_name(path.name + ".quarantined")
    try:
        path.replace(target)
    except OSError:
        target = None
    try:
        with open(path.parent / "quarantine.jsonl", "a") as f:
            f.write(json.dumps({
                "time": round(time.time(), 3),
                "file": path.name,
                "quarantined_as": target.name if target else None,
                "reason": str(reason)[:300],
            }) + "\n")
    except OSError:
        pass
    get_registry().counter("checkpoint_quarantined_total").inc()
    get_tracer().incident(
        "checkpoint_quarantined", path=str(path), reason=str(reason)[:300]
    )
    return target


def read_checkpoint_payload(
    path: str | Path, quarantine: bool = True
) -> bytes:
    """Checkpoint bytes with the checksum footer validated and
    stripped. A failed check quarantines the file (unless told not to)
    and raises :class:`CorruptCheckpointError` — corruption is detected
    HERE, at read time, never as a wedged restore downstream."""
    path = Path(path)
    data = path.read_bytes()
    try:
        return _strip_footer(data, origin=str(path))
    except CorruptCheckpointError as e:
        if quarantine:
            quarantine_checkpoint(path, str(e))
        raise


def msgpack_restore_file(path: str | Path, quarantine: bool = True) -> Any:
    """``msgpack_restore`` over a footer-validated checkpoint file —
    THE way to read raw checkpoint state (every reader shares the
    validation + quarantine policy). Undecodable msgpack is corruption
    too (a legacy-format truncation has no footer to fail)."""
    payload = read_checkpoint_payload(path, quarantine=quarantine)
    try:
        return serialization.msgpack_restore(payload)
    except Exception as e:  # noqa: BLE001 — any decode failure is damage
        err = CorruptCheckpointError(
            f"checkpoint {path}: undecodable msgpack payload: {e!r}"
        )
        if quarantine:
            quarantine_checkpoint(path, str(err))
        raise err from e


def nonfinite_leaf(target: Any) -> Optional[str]:
    """Path of the first float leaf carrying NaN/Inf, or None when the
    whole (host-side) tree is finite. The walk costs one pass over the
    bytes — the same order as the crc32 the footer already pays. THE
    one definition of the check — the write gate below, the chaos
    invariant checker, and the trainer's run-end finiteness guarantee
    all share it, so leaf-skipping and dtype rules can never drift."""
    import jax
    import numpy as np

    for path, leaf in jax.tree_util.tree_flatten_with_path(target)[0]:
        if isinstance(leaf, str) or leaf is None:
            continue
        try:
            arr = np.asarray(leaf)
        except (TypeError, ValueError):
            continue  # non-numeric leaf (provenance metadata)
        if np.issubdtype(arr.dtype, np.floating) and (
            not np.isfinite(arr).all()
        ):
            return jax.tree_util.keystr(path)
    return None


def _audit_nonfinite_skip(path: Path, leaf: str) -> None:
    """Counter + flight record for a write the non-finite gate refused —
    a skipped checkpoint is a degradation, never silent."""
    from marl_distributedformation_tpu.obs import get_registry, get_tracer

    get_registry().counter("checkpoint_nonfinite_skipped_total").inc()
    get_tracer().incident(
        "checkpoint_nonfinite_skipped", path=str(path), leaf=leaf
    )


def _write_atomic(
    path: Path, target: Any, check_finite: bool = True
) -> None:
    import jax

    path.parent.mkdir(parents=True, exist_ok=True)
    # Dot-prefixed temp name so a torn write can never be picked up by
    # latest_checkpoint (which also filters on the .msgpack suffix).
    tmp = path.parent / f".{path.name}.tmp"
    # Pull the whole tree in ONE batched transfer before serializing:
    # to_bytes converts leaf-by-leaf, and ~40 separate device->host
    # round-trips can dominate the training loop (the
    # reference-parity save_freq checkpoints every iteration).
    target = jax.device_get(target)
    # The non-finite write gate: a poisoned state must never become
    # discoverable (the train-lane invariant chaos_storm --train pins).
    # ``check_finite=False`` is for harnesses that deliberately forge a
    # diverged file (the pipeline e2e's gate-sabotage fixture) — every
    # production writer keeps the gate on.
    bad = nonfinite_leaf(target) if check_finite else None
    if bad is not None:
        raise NonFiniteCheckpointError(
            f"checkpoint {path.name}: leaf {bad} carries non-finite "
            "values — refusing to publish a diverged state (the async "
            "writer skips-with-audit; the recovery ladder owns the "
            "rollback)"
        )
    fault_point("checkpoint.write", path=tmp)
    tmp.write_bytes(_with_footer(serialization.to_bytes(target)))
    fault_point("checkpoint.pre_rename", path=tmp)
    tmp.replace(path)  # atomic: no torn checkpoints (SURVEY.md §5)
    fault_point("checkpoint.post_rename", path=path)


def own_restored(tree: Any) -> Any:
    """Copy every array leaf of a freshly-restored checkpoint tree into
    a JAX-owned buffer before handing it to a training loop.

    ``msgpack_restore`` returns numpy arrays that can VIEW the decoded
    checkpoint byte buffer, and the training jits DONATE their state
    inputs. On the zero-copy CPU backend a donated input buffer can
    alias that foreign memory — once the restore scope drops the bytes,
    the donated buffer is a use-after-free that later host allocations
    (the async writer serializing the next checkpoint was the observed
    scribbler) corrupt silently: a resumed fused-sweep run produced
    garbage params leaves while every intermediate comparison looked
    clean (tests/test_fused_sweep.py pins the fixed behavior). One
    explicit owning copy per leaf at restore time closes the hazard on
    every backend; non-array leaves (step counters, name strings) pass
    through untouched.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def leaf(x: Any) -> Any:
        if isinstance(x, (np.ndarray, jax.Array)):
            return jnp.array(np.asarray(x))
        return x

    return jax.tree_util.tree_map(leaf, tree)


def device_snapshot(target: Any) -> Any:
    """Device-side copy of every array leaf of a checkpoint target.

    The fused-scan trainer donates its state buffers to the next chunk's
    dispatch; handing the LIVE tree to a background writer would race the
    donation (the writer's ``device_get`` would read deleted buffers).
    ``jnp.copy`` enqueues one async device copy per leaf *behind* the
    program that produces the state — the copies are data-dependent on it
    and independent of everything after, so the next chunk can donate and
    overwrite the originals while the writer drains the snapshot. Host
    leaves (step counters, name strings) pass through untouched.
    """
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, target
    )


class AsyncCheckpointWriter:
    """Background checkpoint pipeline: ``device_get`` + atomic write on a
    writer thread, so a training loop's ``save`` costs one async device
    copy (:func:`device_snapshot`) instead of a synchronous serialize.

    At most ONE write is in flight — ``submit`` joins the previous write
    first, which bounds snapshot memory to one checkpoint and keeps the
    on-disk step order monotonic. The torn-write invariant is
    :func:`_write_atomic`'s — a crash at any point leaves only a
    dot-prefixed ``.tmp`` file that :func:`latest_checkpoint` can never
    pick up.

    **IO failures degrade, they never kill training.** A full disk
    (ENOSPC), a flaky mount, or an injected crash used to surface as
    ``RuntimeError`` on the next ``submit`` — which turned one missed
    checkpoint into a dead always-learning run. Now an ``OSError`` gets
    ``io_retries`` bounded jittered retries (the write callable is
    idempotent: tmp + rename), and an exhausted budget — or a
    :class:`~..chaos.plane.SimulatedCrash` kill of the write — is
    SKIPPED with a full audit trail (``checkpoint_writes_skipped_total``,
    a ``checkpoint_write_skipped`` flight record) while training
    continues; the next save_freq boundary writes the next checkpoint.
    Non-IO failures (a serialization bug, a bad snapshot) still surface
    as ``RuntimeError`` on the next ``submit``/``close`` — those are
    program errors, not weather.
    """

    def __init__(
        self,
        io_retries: int = 3,
        io_backoff_s: float = 0.05,
        rng: Optional[random.Random] = None,
        keep_last_n: int = 0,
        protect: Any = None,
    ) -> None:
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.io_retries = max(0, int(io_retries))
        self.io_backoff_s = float(io_backoff_s)
        self.writes_skipped = 0
        self._rng = rng if rng is not None else random.Random()
        # Retention ring (docs/recovery.md): after every successful
        # ``submit`` write, keep only the newest ``keep_last_n``
        # rl_model_* checkpoints in that file's directory (0 = keep
        # everything, the legacy behavior). ``protect`` is a zero-arg
        # callable returning paths that must survive pruning no matter
        # their age — the trainer passes its last-good rollback target.
        self.keep_last_n = max(0, int(keep_last_n))
        self._protect = protect

    def submit(
        self, path: str | Path, target: Any, on_done: Any = None
    ) -> Path:
        """Queue one atomic write of ``target`` to ``path``. ``target``
        must already be safe to read from another thread (host arrays, or
        a :func:`device_snapshot` the caller's donation cannot touch).
        ``on_done(path)``, if given, runs on the writer thread AFTER the
        rename lands — i.e. when the file is durably discoverable. The
        always-learning pipeline uses it to nudge its checkpoint stream
        the moment a candidate exists instead of waiting out a poll
        interval; a hook failure surfaces like a write failure (next
        submit/close), never silently."""
        path = Path(path)

        def write() -> None:
            _write_atomic(path, target)
            if on_done is not None:
                on_done(path)
            if self.keep_last_n > 0:
                prune_checkpoints(
                    path.parent,
                    self.keep_last_n,
                    protect=(
                        self._protect() if self._protect is not None else ()
                    ),
                )

        self.submit_write(write)
        return path

    def submit_write(self, write_fn: Any) -> None:
        """Queue an arbitrary checkpoint-writing callable on the writer
        thread — the population sweeps use this to land a whole logical
        checkpoint (per-member files + the ``sweep_state`` anchor) as one
        single-flight unit. ``write_fn`` must only touch state that is
        safe to read off-thread (host arrays / a :func:`device_snapshot`)
        and must keep :func:`_write_atomic`'s torn-write invariant for
        every file it produces. Same pipeline contract as :meth:`submit`:
        one write in flight, errors surface on the next submit/close."""
        from marl_distributedformation_tpu.obs.metrics import get_registry

        fault_point("ckpt_writer.submit")
        self.wait()
        # Live-metrics plane: single-flight writer, so depth is 0 or 1 —
        # a depth stuck at 1 means training outruns checkpoint IO.
        get_registry().gauge("checkpoint_queue_depth").set(1.0)
        thread = threading.Thread(
            target=self._run, args=(write_fn,),
            daemon=True, name="ckpt-writer",
        )
        self._thread = thread
        thread.start()

    def _run(self, write_fn: Any) -> None:
        from marl_distributedformation_tpu.obs.metrics import get_registry

        t0 = time.perf_counter()
        try:
            attempt = 0
            while True:
                try:
                    write_fn()
                    break
                except OSError as e:
                    # Disk weather (ENOSPC, a flaky mount): bounded
                    # jittered retries — write_fn is idempotent (tmp +
                    # rename) — then skip-with-audit. Never a dead run.
                    attempt += 1
                    if attempt > self.io_retries:
                        self._skip(e)
                        return
                    time.sleep(
                        self.io_backoff_s
                        * (2.0 ** (attempt - 1))
                        * self._rng.uniform(0.5, 1.5)
                    )
                except SimulatedCrash as e:
                    # An injected kill of this write: the checkpoint is
                    # simply lost (exactly what a real crash costs) —
                    # audit it and keep the training run alive.
                    self._skip(e)
                    return
                except NonFiniteCheckpointError as e:
                    # The write gate refused a diverged state: skip with
                    # the non-finite audit (its own counter + incident —
                    # a poisoned snapshot is a TRAIN-lane event, not IO
                    # weather) and keep training; the recovery ladder
                    # owns the rollback.
                    self.writes_skipped += 1
                    _audit_nonfinite_skip(Path("<async>"), str(e))
                    return
            registry = get_registry()
            registry.histogram("checkpoint_write_seconds").observe(
                time.perf_counter() - t0
            )
            registry.counter("checkpoint_writes_total").inc()
        except BaseException as e:  # noqa: BLE001 — surfaced on wait()
            self._error = e
        finally:
            get_registry().gauge("checkpoint_queue_depth").set(0.0)

    def _skip(self, error: BaseException) -> None:
        """Audit a degraded (skipped) write: counter + flight record.
        The run stays alive; the next save boundary tries again."""
        from marl_distributedformation_tpu.obs import get_registry, get_tracer

        self.writes_skipped += 1
        get_registry().counter("checkpoint_writes_skipped_total").inc()
        get_tracer().incident(
            "checkpoint_write_skipped",
            error=repr(error)[:300],
            retries=self.io_retries,
            writes_skipped=self.writes_skipped,
        )

    def wait(self) -> None:
        """Join the in-flight write (if any); re-raise its failure."""
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join()
        err, self._error = self._error, None
        if err is not None:
            raise RuntimeError(
                f"async checkpoint write failed: {err!r}"
            ) from err

    def close(self) -> None:
        """Drain the pipeline; raises if the last write failed."""
        self.wait()

    def close_quietly(self) -> None:
        """Teardown on an already-failing path: join without raising (a
        write error must not mask the exception that is unwinding)."""
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join()
        self._error = None


def save_sweep_state(
    log_dir: str | Path, num_timesteps: int, target: Any
) -> Optional[Path]:
    """Write the full population state of a sweep (train/sweep.py).
    Multi-host: coordinator-only write + durability barrier, same contract
    as :func:`save_checkpoint` (``target`` must be host-addressable on the
    coordinator — SweepTrainer passes the allgathered host population)."""
    import jax

    from marl_distributedformation_tpu.parallel.distributed import (
        is_coordinator,
    )

    path = sweep_state_path(log_dir, num_timesteps)
    on_coordinator = is_coordinator()
    if on_coordinator:
        try:
            _write_atomic(path, target)
        except NonFiniteCheckpointError as e:
            _audit_nonfinite_skip(path, str(e))
            path = None
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"sweep_state_{num_timesteps}")
    return path if on_coordinator else None


def latest_sweep_state(log_dir: str | Path) -> Optional[Path]:
    return _latest(log_dir, _SWEEP_STEP_RE)


def _latest(log_dir: str | Path, step_re: re.Pattern) -> Optional[Path]:
    log_dir = Path(log_dir)
    if not log_dir.is_dir():
        return None
    candidates = [
        p
        for p in log_dir.iterdir()
        if p.suffix == ".msgpack" and step_re.search(p.name)
    ]
    if not candidates:
        return None
    return max(candidates, key=lambda p: int(step_re.search(p.name).group(1)))


def latest_checkpoint(log_dir: str | Path) -> Optional[Path]:
    """Find the checkpoint with the largest step number, exactly like the
    reference's discovery scan (visualize_policy.py:29-32)."""
    return _latest(log_dir, _STEP_RE)


def prune_checkpoints(
    log_dir: str | Path,
    keep_last_n: int,
    protect: Any = (),
) -> List[Path]:
    """Checkpoint retention ring: delete all but the newest
    ``keep_last_n`` DISCOVERABLE ``rl_model_*`` checkpoints in
    ``log_dir`` — a months-long always-learning run's unbounded
    ``logs/{name}/`` growth is itself a robustness bug (the disk it
    fills is the disk the next checkpoint needs).

    Quarantine-aware by construction: only discoverable ``.msgpack``
    files are candidates — ``*.quarantined`` evidence, torn ``.tmp``
    files, ``sweep_state_*`` anchors, and the jsonl audit logs are
    untouched. ``protect`` paths (the recovery ladder's CURRENT
    last-good rollback target) survive no matter their age: pruning the
    only state a rollback could restore would turn a divergence into a
    halt. Best-effort (a prune failure is never worth a dead run);
    returns the paths actually removed and counts them into
    ``checkpoint_pruned_total``."""
    keep_last_n = int(keep_last_n)
    if keep_last_n <= 0:
        return []
    log_dir = Path(log_dir)
    if not log_dir.is_dir():
        return []
    protected = {
        Path(p).resolve() for p in (protect or ()) if p is not None
    }
    candidates = sorted(
        (
            p
            for p in log_dir.iterdir()
            if p.suffix == ".msgpack"
            and not p.name.startswith(".")
            and _STEP_RE.search(p.name)
        ),
        key=lambda p: int(_STEP_RE.search(p.name).group(1)),
        reverse=True,
    )
    pruned: List[Path] = []
    for path in candidates[keep_last_n:]:
        if path.resolve() in protected:
            continue
        try:
            path.unlink()
        except OSError:
            continue
        pruned.append(path)
    if pruned:
        from marl_distributedformation_tpu.obs.metrics import get_registry

        get_registry().counter("checkpoint_pruned_total").inc(len(pruned))
    return pruned


class CheckpointDiscovery:
    """Incremental ``rl_model_*`` discovery for long-running watchers.

    ``latest_checkpoint`` re-lists and re-regexes the WHOLE directory on
    every call — fine for a one-shot CLI, but an always-learning run
    polls its trainer directory for hours while the checkpoint count
    grows without bound, so each poll would degrade O(total
    checkpoints). This class keeps the same discovery contract (same
    filename filter, same step parse, torn ``.tmp`` files invisible —
    pinned by tests/test_pipeline.py) while bounding steady-state polls:

    - Filenames are parsed ONCE: a name→step cache means a re-listing
      only regexes names it has never seen.
    - Idle polls are one ``stat``: the directory's mtime changes
      whenever an entry is added/renamed into it, so an unchanged mtime
      means an unchanged listing. Because mtime granularity is finite,
      the skip is only trusted when the previous listing happened
      comfortably AFTER the recorded mtime (``_MTIME_SLACK_S``) — a
      file landing in the same mtime tick as a listing can therefore
      never be missed, only discovered one listing later.

    ``latest()`` is the non-consuming view (what the fleet coordinator
    polls); ``poll_new()`` is the consuming stream (ascending step
    order, each checkpoint yielded exactly once) the promotion pipeline
    tails. New steps at or below the consumed high-water mark are
    ignored by ``poll_new`` — the same never-go-backward semantics the
    serving registry applies to ``latest_checkpoint``.
    """

    _MTIME_SLACK_S = 2.0

    def __init__(
        self, log_dir: str | Path, start_after_step: int = -1
    ) -> None:
        self.log_dir = Path(log_dir)
        self._known: Dict[str, int] = {}  # filename -> parsed step
        self._high_water = int(start_after_step)
        self._dir_mtime_ns: Optional[int] = None
        self._listing_stable = False  # last listing postdated the mtime

    def _refresh(self) -> None:
        try:
            st = os.stat(self.log_dir)
        except OSError:  # directory not created yet
            self._dir_mtime_ns = None
            self._listing_stable = False
            return
        if (
            self._listing_stable
            and st.st_mtime_ns == self._dir_mtime_ns
        ):
            return  # idle poll: one stat, no listing, no parsing
        now = time.time()
        with os.scandir(self.log_dir) as entries:
            for entry in entries:
                name = entry.name
                if name in self._known or not name.endswith(".msgpack"):
                    continue
                m = _STEP_RE.search(name)
                if m is None:
                    continue
                self._known[name] = int(m.group(1))
        self._dir_mtime_ns = st.st_mtime_ns
        # Trust future mtime-equality skips only if this listing ran
        # strictly after the mtime tick it recorded — otherwise a file
        # created within the same tick could hide behind an "unchanged"
        # mtime forever.
        self._listing_stable = (now - st.st_mtime) > self._MTIME_SLACK_S

    def latest(self) -> Optional[Path]:
        """Newest checkpoint path — ``latest_checkpoint`` semantics,
        incremental cost. Deleted entries (the pipeline's rollback
        RETRACTS demoted checkpoints) are dropped from the cache on
        discovery, so ``latest`` can step back down to an older file."""
        self._refresh()
        while self._known:
            name = max(self._known, key=self._known.__getitem__)
            path = self.log_dir / name
            if path.exists():
                return path
            del self._known[name]
        return None

    def poll_new(self) -> List[Path]:
        """Checkpoints discovered above the consumed high-water mark, in
        ascending step order; advances the mark past everything
        returned."""
        self._refresh()
        fresh = sorted(
            (
                (step, name)
                for name, step in self._known.items()
                if step > self._high_water
            ),
        )
        if fresh:
            self._high_water = fresh[-1][0]
        return [self.log_dir / name for _, name in fresh]


def restore_checkpoint(path: str | Path, template: Any) -> Any:
    """Restore a pytree serialized by ``save_checkpoint`` into the structure
    of ``template`` (same-treedef pytree with correctly-shaped leaves).
    The checksum footer is validated first: damaged bytes are
    quarantined and raise :class:`CorruptCheckpointError` here instead
    of wedging the caller downstream."""
    return serialization.from_state_dict(
        template, msgpack_restore_file(path)
    )


def restore_checkpoint_partial(
    path: str | Path, template: dict
) -> dict:
    """Restore the intersection of a dict checkpoint and a dict template.

    Checkpoints written in different launch modes carry different keys
    (multi-host learner-only checkpoints omit the cross-host-sharded env
    state); this restores every template key present in the file and simply
    omits the rest, so a single-host checkpoint resumes multi-host and vice
    versa. Extra keys in the file are ignored.

    Every restored leaf is validated against the template leaf's shape: a
    checkpoint from a different architecture (other tower widths, another
    policy class) raises a ``ValueError`` naming the offending leaf here,
    at restore time — not a shape crash later inside a compiled train step
    or serving act function.
    """
    raw = msgpack_restore_file(path)
    assert isinstance(raw, dict), f"checkpoint at {path} is not a dict"
    return restore_state_dict_partial(raw, template, origin=str(path))


def restore_latest_partial(
    log_dir: str | Path, template: dict
) -> Optional[tuple]:
    """Resume from the newest VALID checkpoint: walk the discovery
    order newest-first, quarantining corrupt/truncated files as they
    are found, until one restores — a crashed writer or a bad sector
    costs one checkpoint of progress, never a wedged resume. Returns
    ``(path, restored)`` or None when no restorable checkpoint exists.
    Architecture mismatches still raise (that is a config error, not
    damage)."""
    while True:
        path = latest_checkpoint(log_dir)
        if path is None:
            return None
        try:
            return path, restore_checkpoint_partial(path, template)
        except CorruptCheckpointError:
            # Reader already quarantined the file (renamed aside), so
            # the next latest_checkpoint scan steps down one. If the
            # rename FAILED (read-only remount, permissions), the same
            # corrupt path stays discoverable forever — surface the
            # corruption instead of spinning (and flooding the flight
            # recorder with one incident per iteration).
            if path.exists():
                raise


def restore_state_dict_partial(
    raw: dict, template: dict, origin: str = "<state dict>"
) -> dict:
    """`restore_checkpoint_partial` over an already-parsed state dict
    (the serving registry reads the file once for its header check and
    restores from the same parse). Same intersection + leaf-shape
    validation contract; ``origin`` names the source in errors."""
    restored = {}
    for key, tmpl in template.items():
        if key not in raw:
            continue
        try:
            value = serialization.from_state_dict(tmpl, raw[key])
        except Exception as e:  # noqa: BLE001 — any flax restore failure
            # flax raises on structural mismatch (missing/renamed nested
            # keys as ValueError/KeyError, array-where-dict as
            # AttributeError/TypeError — all of them a different
            # architecture); add which file and key.
            raise ValueError(
                f"checkpoint {origin}: key {key!r} does not match the "
                f"restore template (architecture mismatch?): {e!r}"
            ) from e
        _check_leaf_shapes(tmpl, value, origin, key)
        restored[key] = value
    return restored


def _check_leaf_shapes(tmpl: Any, restored: Any, origin: str, key: str) -> None:
    """Leaf-by-leaf shape (and, for array leaves, dtype) comparison of a
    restored subtree against its template. ``from_state_dict`` copies
    leaf values verbatim, so a same-structure checkpoint with different
    layer widths — or same shapes at a drifted dtype — restores silently
    and only explodes later inside jit (a dtype drift is worse than a
    crash: it is a retrace, which a serving RetraceGuard turns into a
    permanent failure). Catch both here with the leaf path in hand.
    Dtype is compared only when BOTH leaves are arrays: scalar template
    leaves like ``num_timesteps: 0`` legitimately restore as whatever
    integer width the writer used."""
    import jax
    import numpy as np

    t_leaves, t_def = jax.tree_util.tree_flatten_with_path(tmpl)
    r_leaves, r_def = jax.tree_util.tree_flatten_with_path(restored)
    if t_def != r_def:
        # from_state_dict can hand back a DEEPER tree than the template
        # (a dict where an array leaf belongs restores verbatim) — a
        # plain leaf zip would silently pair across the drift.
        raise ValueError(
            f"checkpoint {origin}: key {key!r} tree structure does not "
            f"match the restore template — architecture mismatch "
            f"(template {t_def}, checkpoint {r_def})"
        )
    for (t_path, t_leaf), (_, r_leaf) in zip(t_leaves, r_leaves):
        t_shape, r_shape = np.shape(t_leaf), np.shape(r_leaf)
        problem = None
        if t_shape != r_shape:
            problem = f"shape {r_shape}, but the template expects {t_shape}"
        else:
            t_dtype = getattr(t_leaf, "dtype", None)
            r_dtype = getattr(r_leaf, "dtype", None)
            if (
                t_dtype is not None
                and r_dtype is not None
                and t_dtype != r_dtype
            ):
                problem = (
                    f"dtype {r_dtype}, but the template expects {t_dtype}"
                )
        if problem:
            leaf_name = jax.tree_util.keystr(t_path)
            raise ValueError(
                f"checkpoint {origin}: key {key!r} leaf {leaf_name} has "
                f"{problem} — architecture mismatch (refusing to restore "
                "an incompatible tree)"
            )


def broadcast_restore(log_dir: str | Path, template: dict) -> Optional[dict]:
    """Multi-host resume: the coordinator reads its latest checkpoint and
    every host receives the identical restored state.

    Checkpoints exist on the coordinator's disk only, so both the
    found/not-found decision and the state are broadcast — otherwise hosts
    would disagree on params/counters and the SPMD loop would deadlock on
    mismatched collective counts. ``template`` must be array/scalar leaves
    only (no strings — they can't ride the broadcast). Returns None when no
    checkpoint exists; all template keys must be present in the file.
    """
    import numpy as np
    from jax.experimental import multihost_utils

    from marl_distributedformation_tpu.parallel.distributed import (
        is_coordinator,
    )

    # ALL fallible coordinator work happens before the first broadcast:
    # if the coordinator raised mid-protocol, the other hosts would block
    # forever inside broadcast_one_to_all (a silent cluster hang). On
    # failure the coordinator broadcasts found=0 first — peers proceed with
    # a fresh start — and then re-raises so the launcher tears the job down
    # with a real error.
    restored, found, err = template, 0, None
    if is_coordinator():
        try:
            path = latest_checkpoint(log_dir)
            if path is not None:
                restored = restore_checkpoint_partial(path, template)
                missing = set(template) - set(restored)
                if missing:
                    raise ValueError(
                        f"checkpoint {path} is missing learner state "
                        f"{missing}"
                    )
                found = 1
        except Exception as e:  # noqa: BLE001 — converted to fail-fast
            restored, found, err = template, 0, e
    found = int(multihost_utils.broadcast_one_to_all(np.int32(found)))
    if err is not None:
        raise err
    if not found:
        return None
    return multihost_utils.broadcast_one_to_all(restored)


def checkpoint_step(path: str | Path) -> int:
    m = _STEP_RE.search(Path(path).name)
    if not m:
        raise ValueError(f"not a checkpoint path: {path}")
    return int(m.group(1))
