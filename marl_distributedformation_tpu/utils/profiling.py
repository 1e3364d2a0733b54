"""Profiling hooks (the reference has none — SURVEY.md §5).

Thin wrappers over ``jax.profiler`` plus a steps/sec meter, so any training
run can produce a TensorBoard-loadable TPU trace and throughput numbers.
Wired into training via ``TrainConfig.profile`` / the ``profile=true`` CLI
flag (train/trainer.py): the trainer captures a trace of a few post-warmup
iterations into ``{log_dir}/profile/`` and the jitted iteration is
``jax.named_scope``-annotated (``DEVICE_SCOPES``) so the trace viewer
attributes device time to pipeline stages, while the trainer's dispatch
and drain seams carry ``jax.profiler`` annotations (``HOST_SPANS``) on
the same clock.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Iterator, Optional

import jax

# Runtime tracing guards (the dynamic half of graftlint — see
# analysis/guards.py and docs/static_analysis.md): re-exported here so
# training code and notebooks reach them through the same module that
# owns the other observability hooks. Opt-in from TrainConfig via
# guard_retraces / guard_transfers / guard_nans.
from marl_distributedformation_tpu.analysis.guards import (  # noqa: F401
    LedgerDispatch,
    RetraceError,
    RetraceGuard,
    device_memory_bytes,
    ledgered_jit,
    nan_guard,
    no_host_transfers,
    register_aot_program,
    sample_device_watermark,
)

# The names the program gives its stages, in one place: the tests assert
# each is an exact part of some compiled instruction's ``op_name``, and
# the benchmark's per-layer readers (benchmarks/metrics/) key on them, so
# a rename is one edit here and a test failure. docs/profiling.md has the
# table of where each is opened and which metric reads it.
#
# ``jax.named_scope`` names. None is a jax primitive's or a jitted
# helper's name (``gather``, ``sort``, ``shuffle`` are taken), so a path
# part equal to one of these is that stage and nothing else.
DEVICE_SCOPES = (
    "rollout",  # train/trainer.py make_ppo_iteration
    "policy",  # algo/rollout.py, under rollout
    "env_step",  # algo/rollout.py, under rollout
    "gae",  # train/trainer.py
    "ppo_update",  # train/trainer.py
    "epoch_shuffle",  # algo/ppo.py, under ppo_update
    "minibatch_gather",  # algo/ppo.py, under ppo_update
    "row_pack",  # algo/ppo.py, under minibatch_gather, where rows pack
    "subrow_pick",  # algo/ppo.py, under minibatch_gather: >1 row a table row
    "loss_and_grad",  # algo/ppo.py, under ppo_update
    "optimizer_step",  # algo/ppo.py, under ppo_update
    "neighbor_gather",  # models/gnn.py: under policy and loss_and_grad
    # models/trunk.py, under policy and loss_and_grad
    "trunk_attention",  # q/k/v/o products, norms, RoPE, blocked softmax
    "trunk_indexer",  # its three products, the index scores, the selection
    "trunk_moe",  # router, the held experts' products, mask and combine
    "router",  # under trunk_moe: its norm, logits at highest, softmax, top-k
    "routed_experts",  # under trunk_moe: the held experts under the mask
    "shared_expert",  # under trunk_moe, where the model has one
    "trunk_gated_attention",  # gated NoPE GQA: products, blocked softmax, gate
    "trunk_kda",  # a Kimi-Delta layer's mixer whole
    "kda_recurrence",  # under trunk_kda: the chunked delta rule (models/kda.py)
    "trunk_mla",  # a latent-attention layer's mixer whole
    "mla_softmax",  # under trunk_mla: the tiles' scores, softmax and o
    "trunk_residual",  # hyper-connections: coefficients, pre-mix, write-back
    "hc_sinkhorn",  # under trunk_residual: the 20 normalisations of H_res
    "dense_ffn",  # a leading dense layer's SwiGLU
)
# ``pl.pallas_call(name=...)`` of the two k-NN kernels (ops/knn_pallas.py):
# N <= 512 fused, larger N streaming. Both keep the substring ``knn``.
KERNEL_NAMES = ("knn_fused", "knn_streaming")
# ``jax.profiler`` annotations on the trainer's host seams
# (train/trainer.py ``_dispatch`` and ``_drain_chunk``).
HOST_SPANS = ("train_dispatch", "train_drain")


class TraceWindow:
    """Dispatch-grained ``jax.profiler`` capture window for training
    loops (the ``profile=true`` implementation shared by the host-loop,
    fused-scan, and population-sweep drivers).

    The unit is one *dispatch* — a single iteration in the host loop, a
    whole fused chunk in Anakin mode — so ``profile=true`` composes with
    ``fused_chunk``: tracing ``count`` dispatches captures ``count``
    chunks (K iterations each) instead of fail-fasting. The first
    ``skip`` dispatches are excluded (they are compile-bound and would
    dominate the trace), and the window closes after syncing the last
    traced dispatch's outputs so the trace contains the full device
    execution, not just the async enqueue.

    Start/stop never touch the jit cache — a traced run compiles exactly
    as often as an untraced one (pinned by the profiler-under-fused
    smoke tests).

    Every completed (or aborted) window appends one JSON line to
    ``{trace_dir}/capture_ledger.jsonl`` naming what actually ran:
    the programs dispatched during the window (from the ProgramLedger's
    per-program dispatch counters), the chunk count, and the trace
    directory — so a profile artifact found weeks later is attributable
    without replaying the run.
    """

    AUDIT_NAME = "capture_ledger.jsonl"

    def __init__(
        self,
        log_dir: Optional[str],
        enabled: bool,
        count: int = 3,
        skip: int = 1,
    ) -> None:
        import os

        self.trace_dir = (
            os.path.join(log_dir, "profile") if log_dir else None
        )
        self.enabled = bool(enabled) and self.trace_dir is not None
        self.count = max(1, int(count))
        self.skip = max(0, int(skip))
        self._dispatches = 0
        self._traced = 0
        self.active = False
        self.captured = False
        self._window_baseline: Optional[dict] = None

    @staticmethod
    def _program_dispatches() -> dict:
        """``{dispatch_key: dispatches_total}`` from the ProgramLedger
        (empty when the ledger is disabled)."""
        from marl_distributedformation_tpu.obs.ledger import get_ledger

        suffix = "_dispatches_total"
        return {
            key[len("program_"):-len(suffix)]: value
            for key, value in get_ledger().snapshot().items()
            if key.startswith("program_") and key.endswith(suffix)
        }

    def _audit_line(self, completed: bool) -> None:
        """One durable line per capture window — never raises, never
        blocks the training loop on anything but one small append."""
        import json
        import os

        baseline, self._window_baseline = self._window_baseline, None
        try:
            now = self._program_dispatches()
            programs = {
                key: int(count - (baseline or {}).get(key, 0))
                for key, count in now.items()
                if count - (baseline or {}).get(key, 0) > 0
            }
            line = {
                "event": "profile_capture",
                "time": time.time(),
                "trace_dir": self.trace_dir,
                "completed": completed,
                "dispatches_traced": self._traced,
                "dispatches_skipped": self.skip,
                "programs": programs,
            }
            os.makedirs(self.trace_dir, exist_ok=True)
            with open(
                os.path.join(self.trace_dir, self.AUDIT_NAME), "a"
            ) as f:
                f.write(json.dumps(line) + "\n")
        except Exception:  # noqa: BLE001 — attribution is best-effort
            pass

    def before_dispatch(self) -> None:
        """Open the window once the warmup dispatches have passed."""
        if (
            self.enabled
            and not self.captured
            and not self.active
            and self._dispatches >= self.skip
        ):
            self._window_baseline = self._program_dispatches()
            jax.profiler.start_trace(self.trace_dir)
            self.active = True
            print(f"[profile] tracing -> {self.trace_dir}")

    def after_dispatch(self, sync_tree: Optional[object] = None) -> None:
        """Count the dispatch; once ``count`` traced dispatches are in,
        block on ``sync_tree`` (the dispatch's outputs) and stop."""
        self._dispatches += 1
        if not self.active:
            return
        self._traced += 1
        if self._traced >= self.count:
            if sync_tree is not None:
                jax.block_until_ready(sync_tree)
            jax.profiler.stop_trace()
            self.active = False
            self.captured = True
            self._audit_line(completed=True)

    def close(self) -> None:
        """Teardown guard for error paths: stop an open trace so the
        profiler session never leaks across runs."""
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self._audit_line(completed=False)


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace into ``log_dir`` (no-op if None)."""
    if log_dir is None:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Throughput:
    """Steps/sec meter over a rolling window of recent ticks.

    The first tick only starts the clock (that iteration's time includes
    compilation); after that the rate reflects the last ``window`` ticks, so
    quoted numbers converge to steady-state instead of blending early
    dispatch-bound iterations forever (round-1 VERDICT weak #6).
    """

    def __init__(self, window: int = 20) -> None:
        # (timestamp, cumulative_steps) ring; rate = slope over the ring.
        self._ticks: collections.deque = collections.deque(maxlen=window + 1)
        self._cum = 0

    def tick(self, steps: int = 1) -> None:
        if not self._ticks:  # first call: clock start only (compile)
            self._ticks.append((time.perf_counter(), 0))
            return
        self._cum += steps
        self._ticks.append((time.perf_counter(), self._cum))

    def rate(self) -> float:
        if len(self._ticks) < 2:
            return 0.0
        (t0, s0), (t1, s1) = self._ticks[0], self._ticks[-1]
        if t1 <= t0:
            return 0.0
        return (s1 - s0) / (t1 - t0)
