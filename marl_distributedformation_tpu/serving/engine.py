"""Bucketed, jit-compiled policy act functions — the compiled core of
the serving stack.

Why buckets: a jitted function compiles one XLA program per input
*shape*. Serving traffic arrives at arbitrary batch sizes, and compiling
a multi-hundred-millisecond program per distinct size is the classic
silent serving killer (the same failure mode graftlint's RetraceGuard
exists to catch in training). The engine therefore compiles a small
ladder of fixed batch shapes — 1/8/64/512 by default — and pads every
request batch up to the next rung, so the total number of compilations
is bounded by ``len(buckets)`` for the lifetime of the process, no
matter what sizes clients send. Each bucket's act function is wrapped in
a :class:`RetraceGuard` with a budget of one trace; a retrace (weak-type
drift, dtype drift, a params structure change) raises instead of
silently recompiling per call.

Params are an *argument* of the compiled function, not a closure
constant: a hot-swapped checkpoint with the same architecture reuses the
existing executable — swapping weights never recompiles. Nothing is
donated: the only output is the ``(rows, act_dim)`` action block, which
can alias neither the ``(rows, obs_dim)`` obs buffer nor the key — on
the TPU a donation here is refused by XLA with a warning per rung
("Some donated buffers were not usable", measured PR 21).

``dtype="bfloat16"`` opts a rung ladder into bf16 inference: each rung's
compiled program casts the float params and the obs to bf16 ON DEVICE
(part of the fused program — params stay f32 at rest, so hot swaps and
template validation are untouched and the jit cache keys never change),
computes the forward pass in bf16, and casts the actions back to f32
before the clip. The action divergence vs the f32 ladder is bounded the
same way the sharding parity gates are — an explicit amplification
budget (``tests/bf16_budget.py``), not a flat tolerance.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from marl_distributedformation_tpu.analysis.guards import (
    RetraceGuard,
    ledgered_jit,
)
from marl_distributedformation_tpu.models import distributions

# Powers-of-8-ish ladder: adjacent rungs are 8x apart, so padding waste
# is bounded (worst-case occupancy 1/8 just above a rung) while the
# compile count stays at 4 programs. See docs/serving.md for sizing.
DEFAULT_BUCKETS = (1, 8, 64, 512)


class BucketedPolicyEngine:
    """jit-compiled ``act`` over a ladder of fixed batch shapes.

    Args:
      policy: a ``compat.policy.LoadedPolicy`` (or anything with
        ``.model`` / ``.params`` of the same contract: ``model.apply``
        returns ``(mean, log_std, value)`` and is shape-polymorphic over
        leading batch axes).
      buckets: ascending batch-size ladder. Requests larger than the top
        rung are split into top-rung chunks plus a bucketed remainder.
      max_traces_per_bucket: RetraceGuard budget per rung. The default of
        1 is the serving contract — one bucket, one compile, ever; a
        second trace raises ``RetraceError`` naming the drifting
        signature.
      seed: base PRNG key for stochastic (non-deterministic) actions; a
        per-dispatch key is derived via ``fold_in`` on a dispatch
        counter, so no key is ever consumed twice.
      dtype: inference compute dtype. ``None``/"float32" serves f32;
        "bfloat16" compiles each rung with an in-program cast of float
        params + obs to bf16 (actions come back f32). Opt-in: the
        divergence budget is tests/bf16_budget.py's, not zero.
      device: the device this engine's dispatches run on (a fleet passes
        replica *i*'s device, where it also placed the params). The
        base PRNG key is committed there, so the per-dispatch
        ``fold_in`` runs on — and its key stays on — that device
        instead of being made on jax's default device and copied over
        on every dispatch. ``None`` leaves placement to jax.
    """

    def __init__(
        self,
        policy: Any,
        buckets: Tuple[int, ...] = DEFAULT_BUCKETS,
        max_traces_per_bucket: Optional[int] = 1,
        seed: int = 0,
        dtype: Optional[str] = None,
        device: Any = None,
    ) -> None:
        self.policy = policy
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.dtype = None if dtype in (None, "float32", "f32") else jnp.dtype(
            dtype
        )
        if self.dtype is not None and self.dtype != jnp.bfloat16:
            raise ValueError(
                f"inference dtype must be float32 or bfloat16, got {dtype!r}"
            )
        self.guards: Dict[int, RetraceGuard] = {
            b: RetraceGuard(
                f"serving-act-bucket{b}", max_traces=max_traces_per_bucket
            )
            for b in self.buckets
        }
        self._acts = {b: self._build_act(b) for b in self.buckets}
        self._base_key = jax.random.PRNGKey(seed)
        if device is not None:
            self._base_key = jax.device_put(self._base_key, device)
        self._dispatches = 0  # graftlock: guarded-by=_lock
        self._lock = threading.Lock()
        # Trailing row shape, recorded on the first successful dispatch:
        # later mismatches fail fast as a ValueError instead of burning
        # a trace attempt inside jit.
        self._row_shape: Optional[Tuple[int, ...]] = None

    # -- compiled path --------------------------------------------------

    def _act_core(self, nn_params, obs, key, deterministic):
        """The traced act body, shared by every rung builder (the mesh
        subclass wraps it with an in-program key fold)."""
        model = self.policy.model
        cast = self.dtype
        if cast is not None:
            # In-program bf16 cast: params stay f32 at rest (swap /
            # validation contract untouched), the forward pass runs
            # in bf16, actions return f32. Float leaves only — step
            # counters and integer tables keep their dtypes.
            nn_params = jax.tree_util.tree_map(
                lambda x: (
                    x.astype(cast)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else x
                ),
                nn_params,
            )
            obs = obs.astype(cast)
        mean, log_std, _ = model.apply(nn_params, obs)
        sampled = distributions.sample(key, mean, log_std)
        actions = jnp.where(
            deterministic, distributions.mode(mean), sampled
        )
        actions = actions.astype(jnp.float32)
        # Action-space clip, same contract as LoadedPolicy.predict.
        return jnp.clip(actions, -1.0, 1.0)

    def _build_act(self, bucket: int):
        def _act(nn_params, obs, key, deterministic):
            return self._act_core(nn_params, obs, key, deterministic)

        # ``deterministic`` rides as a traced bool scalar so ONE program
        # per bucket covers both modes (a static arg would double the
        # compile count for no win: the sampled branch is a cheap fused
        # normal draw).
        dtype_tag = "bf16" if self.dtype is not None else "f32"
        return ledgered_jit(
            _act,
            self.guards[bucket],
            subsystem="serving",
            program=f"act_rung{bucket}_{dtype_tag}",
        )

    # -- bucketing ------------------------------------------------------

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest rung holding ``n`` rows (``n`` <= max_bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"{n} rows exceed the top bucket {self.max_bucket}")

    def plan(self, n: int) -> List[int]:
        """Rung sizes a dispatch of ``n`` rows pads into (top-rung chunks
        plus one bucketed remainder). ``sum(plan)`` is the padded
        capacity the batch occupies — the occupancy denominator."""
        if n <= 0:
            raise ValueError(f"need at least one row, got {n}")
        chunks = [self.max_bucket] * (n // self.max_bucket)
        rest = n % self.max_bucket
        if rest:
            chunks.append(self.bucket_for(rest))
        return chunks

    def compile_counts(self) -> Dict[int, int]:
        """Traces per rung so far (the serving contract: at most 1 each)."""
        return {b: g.count for b, g in self.guards.items()}

    @property
    def dtype_label(self) -> str:
        """Short dtype tag for metrics labels ("f32" / "bf16")."""
        return "bf16" if self.dtype == jnp.bfloat16 else "f32"

    # Dispatch hooks the mesh-sharded subclass overrides: the base
    # engine calls its jitted rung directly and lets jit place the
    # padded buffer on the params' device.
    is_sharded = False

    def _run(
        self,
        bucket: int,
        nn_params: Any,
        padded: np.ndarray,
        key: jax.Array,
        det: np.bool_,
    ):
        """One compiled-rung dispatch (the mesh subclass swaps in its
        AOT-executable path here)."""
        return self._acts[bucket](nn_params, padded, key, det)

    def _default_params(self) -> Any:
        return self.policy.params

    # -- host-side dispatch ---------------------------------------------

    def _next_key(self) -> jax.Array:
        with self._lock:
            count = self._dispatches
            self._dispatches += 1
        return jax.random.fold_in(self._base_key, count)

    def act(
        self,
        obs: np.ndarray,
        deterministic: bool = True,
        nn_params: Any = None,
    ) -> np.ndarray:
        """Actions for ``obs`` rows ``(n, *row_shape)``; pads to the next
        bucket, runs the compiled rung, slices the padding back off.
        ``nn_params=None`` uses the wrapped policy's own params (the
        registry passes its active snapshot instead)."""
        if nn_params is None:
            nn_params = self._default_params()
        obs = np.asarray(obs, np.float32)
        if obs.ndim < 2:
            raise ValueError(
                f"obs must be (n, *row_shape) with a leading batch axis, "
                f"got shape {obs.shape}"
            )
        n = obs.shape[0]
        if self._row_shape is not None and obs.shape[1:] != self._row_shape:
            raise ValueError(
                f"obs rows have shape {obs.shape[1:]}; this engine serves "
                f"{self._row_shape} rows (one compiled row shape per "
                "engine — the bucket ladder is the only shape axis)"
            )
        det = np.bool_(deterministic)  # strong dtype: no weak-type retrace
        outs: List[np.ndarray] = []
        start = 0
        for bucket in self.plan(n):
            k = min(bucket, n - start)
            padded = np.zeros((bucket,) + obs.shape[1:], np.float32)
            padded[:k] = obs[start : start + k]
            actions = self._run(
                bucket, nn_params, padded, self._next_key(), det
            )
            outs.append(np.asarray(actions)[:k])
            start += k
        self._row_shape = obs.shape[1:]
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)
