"""Sebulba driver: the split acting/learning architecture (docs/sebulba.md).

The Podracer paper's SECOND architecture (PAPERS.md, arXiv:2104.06272)
next to Anakin: the local device pool is partitioned into an **actor
slice** that runs the compiled rollout program against a params snapshot
and a **learner slice** that drains K trajectory batches per fused
update chunk. The two meet only at host seams — a bounded
:class:`~.queues.TransferQueue` forward (backpressure + seq /
params-version stamps) and a single-slot :class:`~.queues.ParamBus`
back (latest-wins atomic swap at the actor dispatch boundary).

The functional split mirrors :func:`train.make_ppo_iteration` EXACTLY —
same key threading (``key, k_roll, k_update = split(key, 3)``), same op
sequence, just cut at the rollout/update boundary — so depth-1 lockstep
Sebulba (:meth:`SebulbaDriver.run_lockstep_iteration`) is bitwise
identical to the Anakin host loop at identical seeds
(tests/test_sebulba.py pins it). Neither slice program donates its
arguments: the ParamBus slot holds the same device buffers the learner's
``train_state.params`` point at (and the actor snapshots), so a donating
learner jit would invalidate the published weights mid-rollout — the
use-after-donation class utils/checkpoint.own_restored exists for, here
avoided by construction. That costs one extra params-sized buffer per
slice versus Anakin's donated carry; the un-contended gate/adversary
latency is what it buys (ROADMAP item 1).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from marl_distributedformation_tpu.algo import (
    MinibatchData,
    PPOConfig,
    collect_rollout,
    compute_gae,
    ppo_update,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.obs.metrics import get_registry
from marl_distributedformation_tpu.train.recovery import (
    HEALTH_DRIFT_BOUNDED,
    HEALTH_GRAD_BOUNDED,
    HEALTH_GRAD_FINITE,
    HEALTH_LOSS_FINITE,
    HealthConfig,
    record_health_flags,
)
from marl_distributedformation_tpu.train.sebulba.queues import (
    ParamBus,
    TransferItem,
    TransferQueue,
)
from marl_distributedformation_tpu.train.trainer import TrainConfig, Trainer
from marl_distributedformation_tpu.utils import (
    AsyncCheckpointWriter,
    MetricsLogger,
    Throughput,
)
from marl_distributedformation_tpu.utils import profiling


def partition_devices(
    actor_devices: int = 1,
) -> Tuple[Tuple[Any, ...], Tuple[Any, ...]]:
    """Split ``jax.local_devices()`` into (actor_slice, learner_slice).

    The first ``actor_devices`` devices act, the rest learn; at least one
    device is always left for the learner. A single-device host (the CPU
    default without ``xla_force_host_platform_device_count``) returns the
    SAME device in both slices — the lanes still pipeline through the
    queue, they just time-share silicon (and every cross-slice
    ``device_put`` is skipped: same-device placement is a no-op that
    would only add dispatch noise)."""
    devices = tuple(jax.local_devices())
    if len(devices) == 1:
        return devices, devices
    n = max(1, min(int(actor_devices), len(devices) - 1))
    return devices[:n], devices[n:]


def assign_gate_device(actor_devices: int = 1):
    """The promotion gate's OWN slice under the sebulba partition.

    Prefers a device neither the actor slice nor the learner's primary
    (``learner_slice[0]`` — the single device the fused update chunk
    dispatches on) occupies, so gate evals never contend with either
    lane; on a pool too small to spare one it falls back to the tail of
    the learner slice (an honest time-share, recorded as such by the
    supervisor's ``gate_device``)."""
    actor_slice, learner_slice = partition_devices(actor_devices)
    busy = {id(d) for d in actor_slice} | {id(learner_slice[0])}
    free = [d for d in jax.local_devices() if id(d) not in busy]
    return free[-1] if free else learner_slice[-1]


def make_actor_rollout(
    apply_fn: Any,
    env_params: EnvParams,
    ppo: PPOConfig,
    env_step_fn: Any = None,
    scenario_step_fn: Any = None,
):
    """The acting half of :func:`train.make_ppo_iteration` — byte-for-
    byte its rollout section, with the SAME key threading: the iteration
    key splits into ``(key, k_roll, k_update)`` here, ``k_roll`` drives
    the rollout, and ``k_update`` rides the trajectory payload to the
    learner so the update consumes exactly the key Anakin would have —
    the hinge of the bitwise lockstep-parity pin.

    ``(params, env_state, obs, key, *scenario_args) ->
    (env_state, last_obs, key, k_update, batch, last_value)``"""

    def actor_rollout(params, env_state, obs, key, *scenario_args):
        if scenario_step_fn is not None:
            (scenario_params,) = scenario_args
            step_fn = lambda s, v: scenario_step_fn(s, v, scenario_params)  # noqa: E731
        else:
            step_fn = env_step_fn
        key, k_roll, k_update = jax.random.split(key, 3)
        with jax.named_scope("rollout"):
            env_state, last_obs, batch, last_value = collect_rollout(
                apply_fn,
                params,
                env_state,
                obs,
                k_roll,
                env_params,
                ppo.n_steps,
                env_step_fn=step_fn,
            )
        return env_state, last_obs, key, k_update, batch, last_value

    return actor_rollout


def make_learner_update(
    env_params: EnvParams, ppo: PPOConfig, per_formation: bool = False
):
    """The learning half of :func:`train.make_ppo_iteration` — GAE,
    minibatch reshape, and all PPO epochs, producing the SAME metrics
    dict (rollout metric means, update metrics, reward, episode_dones)
    so a lockstep run's records match Anakin's field-for-field.

    ``(train_state, batch, last_value, k_update) ->
    (train_state, metrics)``"""
    if per_formation:
        n = env_params.num_agents
        update_ppo = dataclasses.replace(
            ppo, batch_size=max(1, ppo.batch_size // n)
        )
        row_shape = (n,)
    else:
        update_ppo = ppo
        row_shape = ()

    def learner_update(train_state, batch, last_value, k_update):
        with jax.named_scope("gae"):
            advantages, returns = compute_gae(
                batch.rewards,
                batch.values,
                batch.dones,
                last_value,
                ppo.gamma,
                ppo.gae_lambda,
            )
        flat = MinibatchData(
            obs=batch.obs.reshape(-1, *row_shape, env_params.obs_dim),
            actions=batch.actions.reshape(
                -1, *row_shape, env_params.act_dim
            ),
            old_log_probs=batch.log_probs.reshape(-1, *row_shape),
            advantages=advantages.reshape(-1, *row_shape),
            returns=returns.reshape(-1, *row_shape),
        )
        with jax.named_scope("ppo_update"):
            train_state, update_metrics = ppo_update(
                train_state, flat, k_update, update_ppo
            )
        metrics = {k: v.mean() for k, v in batch.metrics.items()}
        metrics.update(update_metrics)
        metrics["reward"] = batch.rewards.mean()
        metrics["episode_dones"] = batch.dones[..., 0].sum()
        return train_state, metrics

    return learner_update


def make_learner_health(update, health: HealthConfig):
    """The PR-15 health word, riding the learner unchanged: same four
    flags, same bit layout, same ``jnp.where`` skip-update guard as
    :func:`train.recovery.make_health_iteration` — restricted to the
    state the learner OWNS (``train_state``; env state and obs live on
    the actor slice and were produced by an already-published params
    version, so a flagged update leaves them untouched by design). On a
    healthy run ``jnp.where(True, new, old)`` selects ``new`` exactly,
    preserving the bitwise lockstep-parity pin with health on."""
    import optax

    gn_max = float(health.grad_norm_max)
    drift_max = float(health.param_drift_max)

    def health_update(train_state, batch, last_value, k_update):
        new_ts, metrics = update(train_state, batch, last_value, k_update)
        loss_ok = jnp.isfinite(metrics["loss"])
        grad_norm = metrics.get("grad_norm")
        if grad_norm is None:
            grad_finite = jnp.asarray(True)
            grad_bounded = jnp.asarray(True)
        else:
            grad_finite = jnp.isfinite(grad_norm)
            # NaN <= x is False, so a non-finite norm fails BOTH flags.
            grad_bounded = grad_norm <= jnp.asarray(gn_max, grad_norm.dtype)
        p_old = optax.global_norm(train_state.params)
        p_new = optax.global_norm(new_ts.params)
        drift_ok = jnp.isfinite(p_new) & (
            p_new <= jnp.asarray(drift_max, p_new.dtype) * (p_old + 1.0)
        )
        healthy = loss_ok & grad_finite & grad_bounded & drift_ok
        out_ts = jax.tree_util.tree_map(
            lambda n, o: jnp.where(healthy, n, o), new_ts, train_state
        )
        f32 = jnp.float32
        word = (
            loss_ok.astype(f32) * HEALTH_LOSS_FINITE
            + grad_finite.astype(f32) * HEALTH_GRAD_FINITE
            + grad_bounded.astype(f32) * HEALTH_GRAD_BOUNDED
            + drift_ok.astype(f32) * HEALTH_DRIFT_BOUNDED
        )
        metrics = dict(metrics)
        metrics["health_ok"] = healthy.astype(f32)
        metrics["health_word"] = word
        return out_ts, metrics

    return health_update


def make_learner_chunk(update):
    """Fuse the learner over a whole drained chunk: one ``lax.scan``
    device program consumes K stacked trajectory payloads
    ``(batch, last_value, k_update)`` (leading ``(k,)`` axis) and
    returns per-batch metrics stacked the same way — the learner-slice
    twin of :func:`train.make_fused_chunk`, with the trajectories as xs
    instead of re-rolling them (the actor already did). K is a trace
    constant via the xs shape, so a run's single chunk size compiles
    once (budget-1 receipts per slice)."""

    def learner_chunk(train_state, payload):
        def body(ts, xs):
            batch, last_value, k_update = xs
            ts, metrics = update(ts, batch, last_value, k_update)
            return ts, metrics

        train_state, stacked = jax.lax.scan(body, train_state, payload)
        return train_state, stacked

    return learner_chunk


def _stack_payloads(items: Sequence[TransferItem]):
    """Stack K dequeued payloads along a new leading axis — the
    ``lax.scan`` xs for one learner chunk. Host-side tree_map of
    ``jnp.stack``: on a split pool the leaves are already resident on
    the learner slice (the queue placed them at enqueue), so the stack
    is a device-local concat, not a transfer."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[item.payload for item in items]
    )


class SebulbaDriver(Trainer):
    """Trainer shell for ``TrainConfig.architecture = "sebulba"``.

    Subclasses :class:`Trainer` for everything that is NOT dispatch
    shape — model/optimizer construction, env reset, scenario machinery
    (schedules, samplers, the thread-safe curriculum handoff), the
    checkpoint read/write contract, resume. The Anakin jit the base
    class builds is never dispatched here, so it never compiles, never
    registers in the ledger, and its RetraceGuard stays at 0 — the
    sebulba slices carry their OWN budget-1 guards
    (``actor_guard`` / ``learner_guard``).

    ``fused_chunk`` is reinterpreted as **K**, the batches the learner
    drains per fused update chunk (0 -> 1). Two dispatch surfaces:

    - :meth:`run_lockstep_iteration` — depth-1 synchronous parity mode:
      one thread walks actor -> queue -> learner -> bus, driving the
      REAL transfer plumbing, bitwise identical to Anakin's
      ``run_iteration`` at identical seeds.
    - :meth:`train` — the pipelined mode: a daemon actor thread produces
      rollouts against the freshest published snapshot while the main
      thread drains/updates/publishes; queue backpressure bounds the
      actor's lead, the staleness gate bounds what the learner accepts.
    """

    def __init__(
        self,
        env_params: EnvParams,
        ppo: PPOConfig = PPOConfig(),
        config: TrainConfig = TrainConfig(),
        model: Any = None,
        shard_fn: Any = None,
        scenario_schedule: Any = None,
    ) -> None:
        if shard_fn is not None:
            raise SystemExit(
                "sebulba partitions WHOLE devices into actor/learner "
                "slices; mesh sharding (shard_fn) is Anakin-only — drop "
                "the mesh or use architecture=anakin"
            )
        if config.recovery:
            raise SystemExit(
                "the recovery ladder is Anakin-only for now (its rollback "
                "restores the full carry on one thread; the sebulba "
                "learner does not own env state) — drop recovery or use "
                "architecture=anakin. The in-program health word itself "
                "rides the sebulba learner fine: health=true"
            )
        super().__init__(
            env_params,
            ppo=ppo,
            config=config,
            model=model,
            shard_fn=None,
            scenario_schedule=scenario_schedule,
        )
        if self._multihost:
            raise SystemExit(
                "sebulba is single-host for now (the transfer queue and "
                "param bus are process-local); run single-process or use "
                "the mesh tier for cross-host scale"
            )
        self.actor_slice, self.learner_slice = partition_devices(
            config.actor_devices
        )
        self._split_slices = (
            self.actor_slice[0] is not self.learner_slice[0]
        )
        self._learner_chunk_k = max(1, self._fused_chunk)

        actor_core = make_actor_rollout(
            self.model.apply,
            env_params,
            self.ppo,
            self._env_step_fn,
            self._scenario_step_fn,
        )
        update_core = make_learner_update(
            env_params, self.ppo, self.per_formation
        )
        if config.health:
            update_core = make_learner_health(
                update_core,
                HealthConfig(
                    grad_norm_max=config.health_grad_norm_max,
                    param_drift_max=config.health_param_drift_max,
                ),
            )
        # Per-slice budget-1 guards + ledger attribution: each slice's
        # program is its own census entry under subsystem="sebulba".
        # NO donate_argnums on either program — the ParamBus slot and the
        # actor's in-flight snapshot alias the learner's params buffers,
        # and the async checkpoint writer snapshots the actor-owned env
        # carry; donating any of them is a use-after-free (the memory
        # cost vs Anakin's donated carry is one params/carry copy).
        self.actor_guard = profiling.RetraceGuard(
            "sebulba_actor", max_traces=config.guard_retraces or None
        )
        self.learner_guard = profiling.RetraceGuard(
            "sebulba_learner", max_traces=config.guard_retraces or None
        )
        self._actor_program = profiling.ledgered_jit(
            actor_core,
            self.actor_guard,
            subsystem="sebulba",
            program="sebulba_actor_rollout",
        )
        self._learner_program = profiling.ledgered_jit(
            make_learner_chunk(update_core),
            self.learner_guard,
            subsystem="sebulba",
            program="sebulba_learner_chunk",
        )
        self._queue = TransferQueue(
            config.transfer_queue_depth,
            learner_device=(
                self.learner_slice[0] if self._split_slices else None
            ),
        )
        self._bus = ParamBus(
            actor_device=self.actor_slice[0] if self._split_slices else None
        )
        if self._split_slices:
            # Commit each lane's carry onto its owning slice ONCE, here —
            # jit follows committed inputs, so neither program needs a
            # device= pin and every later dispatch is placement-free.
            self.train_state = jax.device_put(
                self.train_state, self.learner_slice[0]
            )
            self.env_state = jax.device_put(
                self.env_state, self.actor_slice[0]
            )
            self.obs = jax.device_put(self.obs, self.actor_slice[0])
            self.key = jax.device_put(self.key, self.actor_slice[0])
        # Version 0 = the initial (or resumed — super ran _try_resume
        # already) params; the learner bumps and republishes per chunk.
        self._learner_version = 0
        self._bus.publish(self.train_state.params, 0)
        # Host artifacts for the staleness contract:
        # ``staleness_samples`` records every DEQUEUED batch's
        # (learner_version - stamped_version) — including ones the gate
        # then drops (the p95 gauge's population); ``consumed_staleness``
        # only the batches that reached an update (the chaos
        # bounded-staleness invariant's population, which must never
        # exceed the bound); ``consumed_versions`` the consumed version
        # sequence the monotonicity invariant checks.
        self.staleness_samples: collections.deque = collections.deque(
            maxlen=65536
        )
        self.consumed_staleness: collections.deque = collections.deque(
            maxlen=65536
        )
        self.consumed_versions: List[int] = []
        self.stale_dropped = 0
        self._actor_thread: Optional[threading.Thread] = None
        self._actor_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._actor_heartbeat = None
        self._learner_heartbeat = None
        self._actor_meter = Throughput()

    # ------------------------------------------------------------------
    # Anakin dispatch surfaces are fenced off (dispatching them would
    # compile the fused Anakin program BESIDE the slice programs and
    # break the per-slice budget-1 receipts).
    # ------------------------------------------------------------------

    def run_iteration(self) -> Dict[str, float]:
        raise SystemExit(
            "sebulba dispatches via run_lockstep_iteration() (depth-1 "
            "parity mode) or train() (pipelined lanes) — Anakin's "
            "run_iteration() would compile the fused train program "
            "beside the slice programs"
        )

    def run_chunk(self) -> Dict[str, Any]:
        raise SystemExit(
            "sebulba has no Anakin chunk dispatch; fused_chunk is K, the "
            "learner's drain width — use train() or "
            "run_lockstep_iteration()"
        )

    # ------------------------------------------------------------------
    # Lockstep parity mode
    # ------------------------------------------------------------------

    def run_lockstep_iteration(self) -> Dict[str, Any]:
        """One synchronous actor->queue->learner->bus round trip on the
        calling thread, driving the REAL transfer plumbing (seq stamps,
        version stamps, occupancy gauges — everything but concurrency).
        Bitwise identical to Anakin's ``run_iteration()`` at identical
        seeds: same key threading, same op sequence, cut across two
        compiled programs (scan-of-1 at the learner; tests/test_sebulba
        pins params AND per-iteration metrics). Returns the iteration's
        metrics as device scalars (the chunk stack's single row).

        Under an armed chaos plane an enqueue-drop surfaces as an empty
        dict (the rollout happened, nothing was learned) — the host
        counters then advance by the ROLLOUT, not the update, exactly
        like the pipelined mode."""
        self._apply_pending_schedule()
        version, params = self._bus.latest()
        extra = (
            () if self.scenario_params is None else (self.scenario_params,)
        )
        env_state, last_obs, key, k_update, batch, last_value = (
            self._actor_program(
                params, self.env_state, self.obs, self.key, *extra
            )
        )
        self.env_state, self.obs, self.key = env_state, last_obs, key
        self.num_timesteps += self.ppo.n_steps * self.num_envs
        self._vec_steps_since_save += self.ppo.n_steps
        if self._scenario_schedule is not None:
            self._scenario_rollouts += 1
            self._scenario_draws += 1
            self._resample_scenario_params()
        seq = self._queue.put((batch, last_value, k_update), version)
        if seq is None:
            return {}
        item = self._queue.get(timeout_s=5.0)
        if item is None:
            return {}
        staleness = self._learner_version - item.params_version
        self.staleness_samples.append(staleness)
        self.consumed_staleness.append(staleness)
        self.consumed_versions.append(item.params_version)
        self.train_state, stacked = self._learner_program(
            self.train_state, _stack_payloads([item])
        )
        self._learner_version += 1
        self._bus.publish(self.train_state.params, self._learner_version)
        self._dispatches += 1
        get_registry().counter("train_iterations_total").inc()
        return jax.tree_util.tree_map(lambda v: v[0], stacked)

    # ------------------------------------------------------------------
    # Pipelined mode
    # ------------------------------------------------------------------

    def _spawn_actor(self) -> None:
        self._actor_thread = threading.Thread(
            target=self._actor_loop, name="sebulba-actor", daemon=True
        )
        self._actor_thread.start()

    def _restart_actor(self) -> None:
        """LaneWatchdog restart hook: respawn a dead actor thread (the
        carry attributes still hold the last completed rollout's state,
        so the respawn resumes the stream instead of resetting it)."""
        if self._stop.is_set():
            return
        if self._actor_thread is not None and self._actor_thread.is_alive():
            return
        self._actor_error = None
        self._spawn_actor()

    def attach_watchdog(self, watchdog: Any) -> None:
        """Register both lanes with a ``chaos.LaneWatchdog``: heartbeats
        age per rollout / per chunk, a dead actor thread restarts via
        :meth:`_restart_actor`, and a wedged learner (no beat past the
        watchdog's wedge timeout) is surfaced by the watchdog's existing
        escalation — the same supervision contract every other lane
        rides."""
        from marl_distributedformation_tpu.chaos.watchdog import Heartbeat

        self._actor_heartbeat = Heartbeat("sebulba_actor")
        self._learner_heartbeat = Heartbeat("sebulba_learner")
        watchdog.register(
            "sebulba_actor",
            self._actor_heartbeat,
            is_alive=lambda: (
                self._actor_thread is None
                or self._actor_thread.is_alive()
                or self._stop.is_set()
            ),
            restart=self._restart_actor,
        )
        watchdog.register(
            "sebulba_learner",
            self._learner_heartbeat,
            is_alive=lambda: True,  # the learner IS the main thread
            restart=lambda: None,
        )

    def _actor_loop(self) -> None:
        """Producer lane: snapshot the freshest published params, run one
        compiled rollout, enqueue the trajectory. The queue's
        backpressure (a full queue blocks ``put``) is the ONLY pacing —
        the actor never sleeps, never polls the learner. Carry
        attributes (env_state/obs/key) are written only by this thread
        while it runs; the learner thread reads them only after join
        (checkpointing happens at chunk boundaries off the same
        attributes Anakin uses, which is safe because `save` snapshots
        under the learner after the actor parked in `put` or exited)."""
        try:
            while not self._stop.is_set():
                self._apply_pending_schedule()
                version, params = self._bus.latest()
                extra = (
                    ()
                    if self.scenario_params is None
                    else (self.scenario_params,)
                )
                env_state, last_obs, key, k_update, batch, last_value = (
                    self._actor_program(
                        params, self.env_state, self.obs, self.key, *extra
                    )
                )
                self.env_state, self.obs, self.key = (
                    env_state,
                    last_obs,
                    key,
                )
                self.num_timesteps += self.ppo.n_steps * self.num_envs
                self._vec_steps_since_save += self.ppo.n_steps
                if self._scenario_schedule is not None:
                    self._scenario_rollouts += 1
                    self._scenario_draws += 1
                    self._resample_scenario_params()
                self._queue.put((batch, last_value, k_update), version)
                if self._queue.closed:
                    return
                if self._actor_heartbeat is not None:
                    self._actor_heartbeat.beat()
                self._actor_meter.tick(
                    self.ppo.n_steps * self.config.num_formations
                )
                get_registry().gauge("actor_env_steps_per_sec").set(
                    self._actor_meter.rate()
                )
        except BaseException as exc:  # surfaced by the learner loop
            self._actor_error = exc
            self._queue.close()

    def _collect_chunk(
        self, k: int, timeout_s: float = 60.0
    ) -> Optional[List[TransferItem]]:
        """Drain K fresh-enough batches for one learner chunk. Batches
        staler than ``max_param_staleness`` learner updates are dropped
        here (counted, never trained on) — which makes the bounded-
        staleness contract structural: every CONSUMED batch satisfies
        it. Returns None when the stream ended (queue closed / actor
        dead / timeout) before K arrived."""
        items: List[TransferItem] = []
        deadline = time.monotonic() + timeout_s
        registry = get_registry()
        while len(items) < k:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            item = self._queue.get(timeout_s=min(1.0, remaining))
            if item is None:
                if self._queue.closed or not (
                    self._actor_thread and self._actor_thread.is_alive()
                ):
                    return None
                continue
            staleness = self._learner_version - item.params_version
            self.staleness_samples.append(staleness)
            registry.gauge("param_staleness_updates").set(float(staleness))
            if staleness > self.config.max_param_staleness:
                self.stale_dropped += 1
                registry.counter("sebulba_stale_dropped_total").inc()
                continue
            self.consumed_staleness.append(staleness)
            self.consumed_versions.append(item.params_version)
            items.append(item)
        return items

    def train(self) -> Dict[str, float]:
        """Pipelined training: actor thread produces, this thread drains
        K batches per fused learner chunk, updates, publishes. Metrics
        records are per-iteration like Anakin's fused drain; checkpoints
        land at chunk boundaries on the background writer. Stops at the
        timestep budget (counted at the ACTOR — env interaction is the
        budget's unit; trailing in-queue batches past the budget are
        left unconsumed, matching on-policy semantics)."""
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        learner_meter = Throughput()
        writer = (
            AsyncCheckpointWriter(
                keep_last_n=self.config.keep_last_n,
                protect=self._protected_paths,
            )
            if self.config.checkpoint
            else None
        )
        registry = get_registry()
        k = self._learner_chunk_k
        per_iter = self.ppo.n_steps * self.num_envs
        last_record: Dict[str, float] = {}
        iteration = 0
        self._stop.clear()
        self._spawn_actor()
        try:
            while self.num_timesteps < self.total_timesteps:
                items = self._collect_chunk(k)
                if items is None:
                    break
                steps_before = self.num_timesteps
                self.train_state, stacked = self._learner_program(
                    self.train_state, _stack_payloads(items)
                )
                self._learner_version += 1
                self._bus.publish(
                    self.train_state.params, self._learner_version
                )
                if self._learner_heartbeat is not None:
                    self._learner_heartbeat.beat()
                self._dispatches += 1
                registry.counter("train_iterations_total").inc(k)
                host = jax.device_get(stacked)
                record_health_flags(host)
                learner_meter.tick(k)
                registry.gauge("learner_steps_per_sec").set(
                    learner_meter.rate()
                )
                registry.gauge("train_compiles").set(
                    self.actor_guard.count + self.learner_guard.count
                )
                for i in range(k):
                    if (iteration + i + 1) % self.config.log_interval:
                        continue
                    record = {name: float(v[i]) for name, v in host.items()}
                    record["learner_steps_per_sec"] = learner_meter.rate()
                    record["actor_env_steps_per_sec"] = (
                        self._actor_meter.rate()
                    )
                    record["param_staleness_updates"] = float(
                        self._learner_version - 1 - items[i].params_version
                    )
                    logger.log(record, steps_before + (i + 1) * per_iter)
                    last_record = record
                iteration += k
                if (
                    writer is not None
                    and self._vec_steps_since_save >= self.config.save_freq
                ):
                    self.save_async(writer)
        finally:
            self._stop.set()
            self._queue.close()
            if self._actor_thread is not None:
                self._actor_thread.join(timeout=30.0)
            if writer is not None:
                self.save_async(writer)
                writer.close_quietly()
            logger.close()
        if self._actor_error is not None:
            raise RuntimeError(
                "sebulba actor lane died"
            ) from self._actor_error
        return last_record

    # ------------------------------------------------------------------
    # Bench / campaign accessors
    # ------------------------------------------------------------------

    def occupancy_p95(self) -> float:
        """p95 transfer-queue occupancy over the run's enqueue samples
        (0.0 before any traffic)."""
        if not self._queue.occupancy_samples:
            return 0.0
        return float(
            np.percentile(np.asarray(self._queue.occupancy_samples), 95)
        )

    def staleness_p95(self) -> float:
        """p95 params-staleness (in learner updates) over every batch
        the learner SAW (consumed or staleness-dropped)."""
        if not self.staleness_samples:
            return 0.0
        return float(
            np.percentile(np.asarray(self.staleness_samples), 95)
        )

    @property
    def transfer_queue(self) -> TransferQueue:
        return self._queue

    @property
    def param_bus(self) -> ParamBus:
        return self._bus
