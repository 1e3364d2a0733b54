"""End-to-end PPO trainer: one jitted iteration = rollout + GAE + update.

Replaces the reference's training driver (``run()``, vectorized_env.py:112-137)
and the SB3 ``learn`` loop it delegates to (SURVEY.md §3.1). The entire hot
path — policy forward, action sampling, vectorized env stepping, GAE, and all
minibatch epochs — is a single XLA program per iteration; the host loop only
dispatches iterations, emits per-rollout metrics, and writes checkpoints.

Timestep accounting matches SB3: ``num_timesteps`` counts agent-transitions
(``+= num_envs = M*N`` per vec-step, SURVEY.md §2.2), and the default budget
is ``5000 * num_formations`` (vectorized_env.py:116,134).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.algo import (
    MinibatchData,
    PPOConfig,
    collect_rollout,
    compute_gae,
    minibatch_shape,
    ppo_update,
)
from marl_distributedformation_tpu.chaos.plane import (
    InjectedFault,
    fault_point,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.formation import compute_obs
from marl_distributedformation_tpu.envs import spec_for_params
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.obs.metrics import get_registry
from marl_distributedformation_tpu.utils import profiling
from marl_distributedformation_tpu.utils import (
    AsyncCheckpointWriter,
    MetricsLogger,
    Throughput,
    checkpoint_path,
    device_snapshot,
    own_restored,
    repo_root,
    restore_latest_partial,
    save_checkpoint,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Run-level configuration (what the reference spreads across cfg,
    ``run()``, and SB3 constructor arguments)."""

    num_formations: int = 1000  # cfg/config.yaml:3
    total_timesteps: Optional[int] = None  # default 5000 * M agent-transitions
    seed: int = 0
    save_freq: int = 10  # vec-steps between checkpoints (vectorized_env.py:124)
    checkpoint: bool = True
    name: str = "default"
    log_dir: Optional[str] = None  # default <repo>/logs/{name}
    use_wandb: bool = False
    use_tensorboard: bool = False  # SB3 writes tensorboard_log scalars
    #   (reference vectorized_env.py:129); opt-in equivalent via torch's
    #   SummaryWriter into {log_dir}/tensorboard/
    resume: bool = False
    log_interval: int = 1  # emit metrics every k rollouts
    fused_chunk: int = 0  # Anakin mode (docs/training.md): >0 compiles K
    #   rollout+update iterations into ONE lax.scan program with the full
    #   training state as the donated carry. Per-iteration metrics come
    #   back STACKED (one batched device_get per chunk, double-buffered
    #   against the next chunk's execution) and checkpoints are written by
    #   a background thread off a device-side snapshot. Chunk boundary =
    #   checkpoint boundary; logging stays per-iteration.
    profile: bool = False  # capture a jax.profiler trace of a few
    #   post-warmup dispatches into {log_dir}/profile/ (profile=true CLI).
    #   Composes with fused_chunk: the capture window is DISPATCH-grained
    #   (utils.profiling.TraceWindow), so fused mode traces
    #   profile_iterations whole chunks instead of fail-fasting.
    profile_iterations: int = 3  # dispatches to trace (chunks when fused)
    # Runtime tracing guards (analysis/guards.py; docs/static_analysis.md).
    guard_retraces: int = 0  # >0: fail the run if the jitted train
    #   iteration compiles more than this many times (1 = the steady-state
    #   contract: identical shapes must never retrace). 0 = count only.
    guard_transfers: bool = False  # disallow device->host transfers during
    #   post-warmup dispatches (the compile dispatch is exempt — constant
    #   uploads during tracing are legitimate)
    guard_nans: bool = False  # jax_debug_nans around every dispatch: ops
    #   producing NaN re-run op-by-op and raise at the source op
    # Self-healing train lane (train/recovery.py, docs/recovery.md).
    health: bool = False  # in-program health word + skip-update guard:
    #   every iteration computes finite-loss / bounded-grad-norm /
    #   param-drift flags and carries the PREVIOUS state through when
    #   flagged (identity update). Flags ride the stacked chunk metrics
    #   (zero extra dispatches); healthy-run outputs are bitwise
    #   identical health on vs off, and budget-1 receipts hold.
    health_grad_norm_max: float = 1.0e6  # raw global-grad-norm bound
    #   (healthy pre-clip norms reach the hundreds; divergence is
    #   1e18+/NaN — see train/recovery.py)
    health_param_drift_max: float = 10.0  # |p_new| <= this * (|p_old|+1)
    recovery: bool = False  # host-side escalation ladder at the drain
    #   seam (requires health=true): sustained breach -> rollback to the
    #   last-good checkpoint with a folded-in recovery counter advancing
    #   the PRNG stream -> bounded retries -> halt with flight record.
    #   Transitions land in logs/{name}/recovery.jsonl + train_* gauges.
    recovery_breach_iters: int = 3  # consecutive skipped iterations
    #   that count as a sustained breach
    recovery_max_rollbacks: int = 3  # retry budget before halting
    recovery_lr_backoff: float = 1.0  # per-rollback learning-rate
    #   multiplier (!= 1.0 builds the optimizer with inject_hyperparams
    #   so the rate lives in opt state — note that changes the opt-state
    #   layout vs default checkpoints)
    recovery_severity_backoff: float = 1.0  # per-rollback scenario
    #   severity multiplier (pure schedule data — no recompile)
    keep_last_n: int = 0  # checkpoint retention ring: keep only the
    #   newest N rl_model_* checkpoints (0 = unbounded, the legacy
    #   behavior). Quarantine-aware and never prunes the recovery
    #   ladder's current last-good rollback target.
    # Sebulba lane (train/sebulba/, docs/sebulba.md): the split
    # acting/learning architecture next to Anakin.
    architecture: str = "anakin"  # "anakin" (fused same-device dispatch,
    #   every mode above) | "sebulba" (actor slice + learner slice joined
    #   by a bounded host-side TransferQueue and a latest-wins ParamBus;
    #   fused_chunk is reinterpreted as K, the batches the learner drains
    #   per fused update chunk)
    actor_devices: int = 1  # sebulba: local devices assigned to the
    #   actor slice (the remainder learn; at least one device is always
    #   kept for the learner — a single-device host time-shares)
    transfer_queue_depth: int = 2  # sebulba: bound on in-flight
    #   trajectory batches; a full queue blocks the actor (backpressure),
    #   so the actor can never run more than this many rollouts ahead
    max_param_staleness: int = 2  # sebulba: drop (never train on) a
    #   batch acted with params more than this many learner updates old


def default_total_timesteps(config: "TrainConfig") -> int:
    """SB3 budget semantics shared by every trainer shell: explicit
    ``total_timesteps``, else ``5000 * M`` agent-transitions
    (reference vectorized_env.py:116,134)."""
    if config.total_timesteps is not None:
        return config.total_timesteps
    return 5000 * config.num_formations


def fill_ent_schedule(
    ppo: PPOConfig,
    env_params: EnvParams,
    config: "TrainConfig",
    iterations: Optional[int] = None,
) -> PPOConfig:
    """Fill ``ppo.total_iterations`` (the shared decay horizon for the
    ``ent_coef_final`` entropy schedule and the ``log_std_final``
    noise-decay schedule) from the run's planned iteration count.
    No-op when no schedule is requested or the horizon is already set —
    in particular, the default config path is left bit-identical."""
    if (
        ppo.ent_coef_final is None and ppo.log_std_final is None
    ) or ppo.total_iterations > 0:
        return ppo
    if iterations is None:
        per_iter = (
            config.num_formations * env_params.num_agents * ppo.n_steps
        )
        iterations = -(-default_total_timesteps(config) // per_iter)
    return dataclasses.replace(
        ppo, total_iterations=max(1, int(iterations))
    )


def _update_rows(
    ppo: PPOConfig, env_params: EnvParams, per_formation: bool
) -> Tuple[PPOConfig, Tuple[int, ...]]:
    """The update's config and the leading shape of one of its rows."""
    if not per_formation:
        return ppo, ()
    # Minibatch whole formations: rows are (N, ...) blocks so the
    # centralized critic sees every agent. batch_size stays denominated
    # in agent-transitions for comparable SGD noise across policies.
    n = env_params.num_agents
    return (
        dataclasses.replace(ppo, batch_size=max(1, ppo.batch_size // n)),
        (n,),
    )


def make_ppo_iteration(
    env_params: EnvParams,
    ppo: PPOConfig,
    per_formation: bool = False,
    env_step_fn: Any = None,
    scenario_step_fn: Any = None,
    rows_sharding: Any = None,
):
    """Build the functional training iteration: rollout + GAE + all
    minibatch epochs as one pure function
    ``(train_state, env_state, obs, key) -> (train_state, env_state,
    last_obs, key, metrics)``.

    Module-level (not a Trainer method) so other shells can transform it:
    ``Trainer`` jits it directly; ``SweepTrainer`` (train/sweep.py) vmaps
    it over a population of seeds before jitting.

    ``scenario_step_fn`` (``scenarios.make_scenario_step``) routes env
    stepping through the disturbance stack; the iteration then takes the
    batched ``ScenarioParams`` as a fifth, *traced* argument — severity
    schedules and per-formation scenario mixes are pure data, so the
    compiled program never changes (tests/test_scenarios.py pins the
    compile-once contract).

    ``rows_sharding`` goes to ``ppo_update`` on the flat rollout data: how
    a minibatch's rows are laid out over the trainer's mesh
    (``Trainer._minibatch_sharding``).
    """
    update_ppo, row_shape = _update_rows(ppo, env_params, per_formation)

    def iteration(
        train_state: TrainState,
        env_state,
        obs: Array,
        key: Array,
        *scenario_args,
    ) -> Tuple[TrainState, Any, Array, Array, Dict[str, Array]]:
        if scenario_step_fn is not None:
            (scenario_params,) = scenario_args
            step_fn = lambda s, v: scenario_step_fn(s, v, scenario_params)  # noqa: E731
        else:
            step_fn = env_step_fn
        key, k_roll, k_update = jax.random.split(key, 3)
        with jax.named_scope("rollout"):
            env_state, last_obs, batch, last_value = collect_rollout(
                train_state.apply_fn,
                train_state.params,
                env_state,
                obs,
                k_roll,
                env_params,
                ppo.n_steps,
                env_step_fn=step_fn,
            )
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
            rows_sharding=rows_sharding,
        )
        with jax.named_scope("ppo_update"):
            train_state, update_metrics = ppo_update(
                train_state, flat, k_update, update_ppo
            )
        metrics = {
            k: v.mean() for k, v in batch.metrics.items()
        }
        metrics.update(update_metrics)
        metrics["reward"] = batch.rewards.mean()
        # Formation-level episode count (batch.dones broadcasts the
        # per-formation done to all N agent rows; same reduction as
        # HeteroTrainer so the metric's unit matches across trainers).
        metrics["episode_dones"] = batch.dones[..., 0].sum()
        return train_state, env_state, last_obs, key, metrics

    return iteration


def make_fused_chunk(iteration, k: int):
    """Fuse ``k`` rollout+update iterations into ONE ``lax.scan`` device
    program — the Podracer "Anakin" dispatch shape (PAPERS.md): the carry
    is the full training state ``(train_state, env_state, obs, key)``
    (donated by the caller's jit), the host touches the device once per
    chunk, and per-iteration metrics come back stacked along a leading
    ``(k,)`` axis so a whole chunk's telemetry drains in one batched
    ``device_get``.

    Scenario params, when present, ride as the scan's xs with a leading
    ``(k,)`` axis — every fused iteration trains at its own schedule
    point, exactly like ``k`` host-loop dispatches (bitwise; pinned by
    tests/test_fused_scan.py).
    """

    def fused_chunk_iteration(train_state, env_state, obs, key, *scenario_seq):
        def body(carry, xs):
            train_state, env_state, obs, key = carry
            extra = () if xs is None else (xs,)
            train_state, env_state, obs, key, metrics = iteration(
                train_state, env_state, obs, key, *extra
            )
            return (train_state, env_state, obs, key), metrics

        xs = scenario_seq[0] if scenario_seq else None
        (train_state, env_state, obs, key), stacked = jax.lax.scan(
            body, (train_state, env_state, obs, key), xs, length=k
        )
        return train_state, env_state, obs, key, stacked

    return fused_chunk_iteration


class Trainer:
    """Imperative shell around the functional training core.

    ``mesh_axes``/``mesh`` wiring for multi-chip sharding lives in
    ``parallel/``; pass ``shard_fn`` to place env state and train state on a
    device mesh — the jitted iteration is sharding-agnostic.
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
        ppo = fill_ent_schedule(ppo, env_params, config)
        self.env_params = env_params
        # Env-generic dispatch (envs/): resolved from the params TYPE, so
        # formation params route to the legacy env/formation.py functions
        # verbatim (bitwise-identical path) and any registered env trains
        # through the same compiled program structure.
        self.env_spec = spec_for_params(env_params)
        self.ppo = ppo
        self.config = config
        self.num_envs = config.num_formations * env_params.num_agents

        self.model = model or MLPActorCritic(
            act_dim=env_params.act_dim, log_std_init=ppo.log_std_init
        )
        # Formation-level models (CTDE critic, GNN) must see whole
        # formations; agent-factored models (plain MLP) can be minibatched
        # over individual agent-transitions, as SB3 does.
        self.per_formation = getattr(self.model, "per_formation", False)

        key = jax.random.PRNGKey(config.seed)
        self.key, k_init, k_env = jax.random.split(key, 3)
        if self.per_formation:
            dummy_obs = jnp.zeros(
                (1, env_params.num_agents, env_params.obs_dim), jnp.float32
            )
        else:
            dummy_obs = jnp.zeros((1, env_params.obs_dim), jnp.float32)
        # jitted: only the parameters leave it, so the forward pass that
        # flax's init traces is dead code and never compiled or run
        params = jax.jit(self.model.init)(k_init, dummy_obs)
        # lr backoff needs the rate IN the optimizer state (pure data,
        # no recompile on a rollback) — inject only when the knob is
        # live so the default opt-state layout (and its checkpoints)
        # stays bit-identical.
        self.train_state = TrainState.create(
            apply_fn=self.model.apply,
            params=params,
            tx=ppo.make_optimizer(
                inject_lr=config.recovery_lr_backoff != 1.0
            ),
        )

        self._shard_fn = shard_fn
        # Agent-axis ('sp') sharding: swap the vmapped env step for the
        # sharded step (parallel/ring.py) so large swarms roll with N split
        # across devices — ring obs exchange one-agent halos (constant
        # per-device ICI traffic); knn obs all-gather positions and search
        # locally per slab.
        self._env_step_fn = None
        mesh = getattr(shard_fn, "mesh", None)
        if (
            mesh is not None or jax.process_count() > 1
        ) and self.env_spec.name != "formation":
            # The mesh-specialized steps (sp ring halo exchange, dp-mesh
            # shard_map knn) and the multi-host sharded reset are built
            # from formation functions — fail fast instead of silently
            # training the wrong env through them.
            raise SystemExit(
                f"env {self.env_spec.name!r} does not compose with mesh "
                "sharding / multi-host yet (the sharded env steps in "
                "parallel/ are formation-specialized); drop the mesh or "
                "use env=formation"
            )
        if mesh is not None and "sp" in mesh.shape:
            from marl_distributedformation_tpu.parallel import make_ring_step

            self._env_step_fn = make_ring_step(env_params, mesh)
        elif mesh is not None and env_params.obs_mode == "knn":
            # knn on a dp mesh: shard_map the env step so the Pallas
            # neighbor kernel sees its local block (the SPMD partitioner
            # cannot split a pallas_call; see parallel.make_dp_step).
            from marl_distributedformation_tpu.parallel import make_dp_step

            self._env_step_fn = make_dp_step(env_params, mesh)
        self._multihost = jax.process_count() > 1
        if self._multihost:
            # Multi-host: every process builds only its own formation shard
            # (parallel/distributed.py) — device_put onto a global mesh from
            # full host arrays is not possible across processes.
            assert shard_fn is not None and getattr(
                shard_fn, "mesh", None
            ), "multi-host training needs a mesh (cfg.mesh / make_shard_fn)"
            from marl_distributedformation_tpu.parallel import (
                replicate,
                reset_batch_sharded,
            )

            mesh = shard_fn.mesh
            self.env_state = reset_batch_sharded(
                k_env, env_params, config.num_formations, mesh
            )
            self.obs = jax.jit(
                functools.partial(compute_obs, params=env_params)
            )(self.env_state.agents, self.env_state.goal)
            self.train_state = replicate(self.train_state, mesh)
        else:
            self.env_state = self.env_spec.reset_batch(
                k_env, env_params, config.num_formations
            )
            # The spec's obs is shape-generic over the leading formation
            # axis and routes knn obs through the batched (Pallas-capable)
            # search — for formation these ARE reset_batch/compute_obs.
            self.obs = self.env_spec.obs(self.env_state, env_params)
            if shard_fn is not None:
                self.train_state, self.env_state, self.obs = shard_fn(
                    self.train_state, self.env_state, self.obs
                )

        # Scenario training (scenarios/, docs/scenarios.md): env stepping
        # routes through the disturbance stack and the iteration takes the
        # batched ScenarioParams as a traced argument — domain
        # randomization over the schedule's scenario set, severity ramps
        # per stage, zero recompiles across all of it.
        self._scenario_schedule = scenario_schedule
        self._scenario_step_fn = None
        self.scenario_params = None
        self.scenario_severity = 0.0
        # Recovery severity backoff (train/recovery.py): multiplies
        # every sampled severity; 1.0 (always, until a rollback with
        # recovery_severity_backoff != 1.0) keeps the sampling path
        # bitwise untouched. Set BEFORE the first resample below.
        self._severity_scale = 1.0
        # Per-iteration severities of the most recent chunked dispatch
        # (what the fused driver logs) — written by _next_scenario_chunk.
        self._last_chunk_severities = None
        # Auto-curriculum seam (scenarios/adversary.py, docs/adversarial.md):
        # a schedule handed to request_scenario_schedule() from another
        # thread (the pipeline supervisor feeding gate falsifiers back)
        # is applied at the next dispatch boundary — the only place the
        # training thread touches schedule state.
        self._pending_schedule: Any = None  # graftlock: guarded-by=_schedule_lock
        self._schedule_lock = threading.Lock()
        if scenario_schedule is not None:
            if self._env_step_fn is not None:
                # Which specialized step blocked it matters for the fix:
                # 'sp' meshes replace the env step wholesale; knn on a dp
                # mesh wraps it in shard_map — neither is scenario-wrapped.
                blocker = (
                    "the agent-axis ('sp') sharded ring step — drop 'sp' "
                    "from the mesh"
                    if "sp" in mesh.shape
                    else "the shard_map knn env step a dp mesh uses for "
                    "obs_mode=knn — use obs_mode=ring on this mesh, or "
                    "drop the mesh"
                )
                raise SystemExit(
                    f"scenario training does not compose with {blocker}; "
                    "scenarios currently wrap only the plain vmapped step"
                )
            if self._multihost:
                raise SystemExit(
                    "scenario training is single-host for now (per-host "
                    "scenario-param construction is not wired); drop "
                    "scenarios or run single-process"
                )
            from marl_distributedformation_tpu.scenarios import (
                get_scenario,
                make_scenario_step,
            )

            self._scenario_specs = tuple(
                get_scenario(n) for n in scenario_schedule.names
            )
            self._scenario_step_fn = make_scenario_step(env_params)
            self._build_scenario_samplers()
            # Base key for the sampling stream; per-dispatch keys fold in
            # the global rollout index, so the stream is a pure function
            # of (seed, rollout) and resume continues it exactly instead
            # of replaying the first dispatches' draws.
            self._scenario_base_key = jax.random.fold_in(
                jax.random.PRNGKey(config.seed), 0x5CE7
            )
            self._scenario_rollouts = 0
            # The key stream folds this GLOBAL draw counter, not the
            # schedule-relative rollout index: a curriculum swap resets
            # the schedule position but must never replay early-run
            # sampling keys. Identical to _scenario_rollouts until the
            # first update_scenario_schedule (bitwise parity with the
            # pre-feedback behavior, incl. fused==host pins).
            self._scenario_draws = 0
            self._resample_scenario_params()

        self.num_timesteps = 0
        self._vec_steps_since_save = 0
        self._iteration_core = self._make_iteration()
        # Self-healing train lane (train/recovery.py, docs/recovery.md):
        # the in-program health word + skip-update guard wrap the
        # functional core BEFORE fusion, so host-loop and fused
        # dispatch carry the same flags in their metrics.
        if config.health:
            from marl_distributedformation_tpu.train.recovery import (
                wrap_health,
            )

            self._iteration_core = wrap_health(
                self._iteration_core, config
            )
        self.halted = False
        self.recovery_ladder = None
        self._recovery_verdict: Optional[str] = None
        self._last_good_ckpt: Optional[Path] = None
        self._rollback_anchor: Optional[Dict[str, Any]] = None
        if config.recovery:
            if not config.health:
                raise SystemExit(
                    "recovery=true needs health=true — the escalation "
                    "ladder consumes the in-program health flags at the "
                    "drain seam; without them it is blind"
                )
            if self._multihost:
                raise SystemExit(
                    "the recovery ladder is single-host for now "
                    "(rollback restore has no cross-host broadcast "
                    "seam); drop recovery or run single-process"
                )
            from marl_distributedformation_tpu.train.recovery import (
                RecoveryConfig,
                RecoveryLadder,
            )

            self.recovery_ladder = RecoveryLadder(
                RecoveryConfig(
                    breach_iters=config.recovery_breach_iters,
                    max_rollbacks=config.recovery_max_rollbacks,
                    lr_backoff=config.recovery_lr_backoff,
                    severity_backoff=config.recovery_severity_backoff,
                ),
                config.log_dir or str(repo_root() / "logs" / config.name),
            )
        self._fused_chunk = max(0, int(config.fused_chunk))
        if self._fused_chunk and self._multihost:
            raise SystemExit(
                "fused-scan training is single-host for now (the async "
                "checkpoint writer has no cross-host durability barrier); "
                "drop fused_chunk or run single-process"
            )
        if self._fused_chunk:
            dispatch_fn = make_fused_chunk(
                self._iteration_core, self._fused_chunk
            )
        else:
            dispatch_fn = self._iteration_core
        # Retrace guard (analysis/guards.py): counts every compilation of
        # the outermost jitted dispatch; with guard_retraces=N the trace
        # that exceeds N raises RetraceError naming the drifting argument
        # signature. Always counting (budget or not) costs one Python
        # closure call per COMPILE, i.e. nothing per step.
        self.retrace_guard = profiling.RetraceGuard(
            "train_iteration",
            max_traces=config.guard_retraces or None,
        )
        # ledgered_jit == jax.jit(guard.wrap(fn)) + automatic
        # ProgramLedger registration of the compiled executable (cost/
        # memory facts, build timings, per-dispatch latency) — the
        # obs/ledger.py seam every budget-1 compile site shares.
        self._iteration = profiling.ledgered_jit(
            dispatch_fn,
            self.retrace_guard,
            subsystem="trainer",
            program="train_iteration",
            donate_argnums=(0, 1),
        )
        self._dispatches = 0

        self.log_dir = config.log_dir or str(
            repo_root() / "logs" / config.name
        )
        # Optional checkpoint-durability hook (the always-learning
        # pipeline sets it to nudge its CheckpointStream): called with
        # the path AFTER the atomic rename lands — for async writes that
        # is on the writer thread, when the file is discoverable, not at
        # submit time (the bytes are still in flight then).
        self.on_checkpoint: Optional[Any] = None

        if config.resume:
            self._try_resume()
        if self.recovery_ladder is not None:
            # Last-resort rollback target: a host copy of the run's
            # starting state (post-resume), so divergence BEFORE the
            # first checkpoint still recovers instead of halting with
            # nothing to restore.
            self._rollback_anchor = jax.device_get(
                self._checkpoint_target()
            )

    # ------------------------------------------------------------------
    # Functional core
    # ------------------------------------------------------------------

    def _make_iteration(self):
        return make_ppo_iteration(
            self.env_params,
            self.ppo,
            self.per_formation,
            self._env_step_fn,
            self._scenario_step_fn,
            self._minibatch_sharding(),
        )

    def _minibatch_sharding(self):
        """How ``ppo_update`` lays a minibatch's rows out: divided over the
        mesh's 'dp' axis where there is one and it divides their count,
        else ``None`` (every device takes the minibatch whole)."""
        mesh = getattr(self._shard_fn, "mesh", None)
        if mesh is None or mesh.shape["dp"] == 1:
            return None
        from marl_distributedformation_tpu.parallel import (
            minibatch_sharding,
        )

        update_ppo, row_shape = _update_rows(
            self.ppo, self.env_params, self.per_formation
        )
        total = self.ppo.n_steps * self.num_envs // math.prod(row_shape)
        _, batch_size = minibatch_shape(update_ppo, total)
        sharding = minibatch_sharding(mesh, batch_size)
        dp = mesh.shape["dp"]
        print(
            f"[trainer] minibatches of {batch_size} rows: "
            + (
                f"{batch_size // dp} a device over dp={dp}, gradients "
                "all-reduced"
                if sharding is not None
                else f"whole on every device (dp={dp} does not divide them)"
            )
        )
        return sharding

    def _build_scenario_samplers(self) -> None:
        """(Re)build the jitted domain-randomization samplers over the
        schedule's CURRENT spec union: stage changes move probability
        mass, severity ramps scale magnitudes — both traced, so each
        sampler compiles once per spec union. The chunked twin draws a
        whole fused chunk's per-iteration batches in one pass (leading
        (k,) axis over keys/severities/probs)."""
        from marl_distributedformation_tpu.scenarios import (
            sample_scenario_batch,
        )

        # The samplers are tiny jitted programs but programs all the
        # same: they register in the ProgramLedger under a persistent
        # count-only guard that survives schedule-swap rebuilds, so
        # every sampler compile stays an attributed census entry (and
        # the entry-count == receipt-count invariant holds).
        if not hasattr(self, "_sampler_guard"):
            self._sampler_guard = profiling.RetraceGuard("scenario_sampler")
        self._sample_scenarios = profiling.ledgered_jit(
            functools.partial(
                sample_scenario_batch,
                specs=self._scenario_specs,
                num_formations=self.config.num_formations,
            ),
            self._sampler_guard,
            subsystem="scenarios",
            program="scenario_sampler",
        )
        self._sample_scenario_chunk = profiling.ledgered_jit(
            jax.vmap(
                functools.partial(
                    sample_scenario_batch,
                    specs=self._scenario_specs,
                    num_formations=self.config.num_formations,
                )
            ),
            self._sampler_guard,
            subsystem="scenarios",
            program="scenario_sampler_chunk",
        )

    def update_scenario_schedule(self, schedule: Any) -> None:
        """Swap the training curriculum mid-run (the auto-curriculum
        seam: ``scenarios.from_falsifiers`` schedules land here).

        The expensive compiled artifact — the train-step / fused-chunk
        program — is untouched by ANY schedule change: ``ScenarioParams``
        ride as traced inputs with fixed shapes, so stage tables,
        severities, and spec magnitudes are pure data (pinned by
        tests/test_adversary.py with a budget-1 RetraceGuard across the
        swap). Only the tiny jitted SAMPLER is rebuilt, and only when
        the spec set changed by VALUE — expect that on every feedback
        round (a re-fed ``adv:`` spec carries new falsifier magnitudes),
        a milliseconds-scale host re-jit off the compiled train path;
        what the stable ``adv:`` names buy is a fixed spec-union SIZE
        (the sampler's stacked axis and the registry never grow across
        rounds). The new schedule starts at its own rollout 0; the
        sampling key stream folds a separate global draw counter that is
        never reset, so feedback rounds cannot replay early-run draws.
        Call from the training thread (or between dispatches) — other
        threads use :meth:`request_scenario_schedule`.
        """
        if self._scenario_schedule is None:
            raise ValueError(
                "this trainer was built without scenario training — the "
                "compiled step takes no scenario input, so a schedule "
                "cannot be installed mid-run (construct the trainer with "
                "scenarios=['clean'] to reserve the traced seam, then "
                "update freely)"
            )
        from marl_distributedformation_tpu.scenarios import get_scenario

        new_specs = tuple(get_scenario(n) for n in schedule.names)
        if new_specs != self._scenario_specs:
            self._scenario_specs = new_specs
            self._build_scenario_samplers()
        self._scenario_schedule = schedule
        self._scenario_rollouts = 0
        self._resample_scenario_params()

    def request_scenario_schedule(self, schedule: Any) -> None:
        """Thread-safe curriculum handoff: stash ``schedule`` for the
        training thread to apply at its next dispatch boundary (the
        pipeline supervisor's feedback path — it must never mutate
        sampler state while a dispatch is being prepared). Validates
        eagerly so the CALLER gets the error, not the training loop."""
        if self._scenario_schedule is None:
            raise ValueError(
                "this trainer was built without scenario training — "
                "construct it with scenarios=['clean'] to reserve the "
                "traced scenario seam for curriculum feedback"
            )
        from marl_distributedformation_tpu.scenarios import get_scenario

        for name in schedule.names:
            get_scenario(name)  # unknown names fail in the caller
        with self._schedule_lock:
            self._pending_schedule = schedule

    def _apply_pending_schedule(self) -> None:
        if self._pending_schedule is None:
            return
        with self._schedule_lock:
            pending, self._pending_schedule = self._pending_schedule, None
        if pending is not None:
            self.update_scenario_schedule(pending)

    def _resample_scenario_params(self) -> None:
        """Redraw the per-formation scenario mix at the schedule's current
        severity (called per dispatch — fresh domain randomization every
        rollout, values-only so the train step never retraces)."""
        schedule = self._scenario_schedule
        self.scenario_severity = schedule.severity_at(self._scenario_rollouts)
        if self._severity_scale != 1.0:
            # Recovery severity backoff (train/recovery.py): pure data,
            # applied at the sampling seam — the schedule object itself
            # stays untouched so a later scale reset is exact.
            self.scenario_severity = (
                self.scenario_severity * self._severity_scale
            )
        k_sample = jax.random.fold_in(
            self._scenario_base_key, self._scenario_draws
        )
        self.scenario_params = self._sample_scenarios(
            k_sample,
            jnp.float32(self.scenario_severity),
            jnp.asarray(schedule.probs_at(self._scenario_rollouts)),
        )

    def _next_scenario_chunk(self, k: int):
        """Stacked ``ScenarioParams`` (leading ``(k,)`` axis) for the next
        ``k`` rollouts ``[r0, r0+k)`` — the scan's xs for a fused chunk.
        Keys fold in each GLOBAL draw index (== the rollout index until a
        curriculum swap; never reset, so feedback rounds cannot replay
        early-run draws) and severities/probs come off the schedule per
        iteration, so every scanned iteration trains at exactly the
        params the host loop would have drawn at its rollout index
        (bitwise; tests/test_fused_scan.py) and resume re-enters
        mid-schedule unchanged. One jitted pass, values-only: stage
        changes and severity ramps never retrace. The severity row is
        kept on ``_last_chunk_severities`` so the fused driver logs the
        EXACT values this chunk trains at (no second schedule read that
        a concurrent curriculum swap could race)."""
        schedule = self._scenario_schedule
        r0 = self._scenario_rollouts
        d0 = self._scenario_draws
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            self._scenario_base_key, jnp.arange(d0, d0 + k)
        )
        severities = schedule.severity_chunk(r0, k)
        if self._severity_scale != 1.0:
            # Recovery severity backoff: scale the whole chunk's row;
            # the stash below then logs the severities ACTUALLY trained.
            severities = [s * self._severity_scale for s in severities]
        self._last_chunk_severities = severities
        return self._sample_scenario_chunk(
            keys,
            jnp.asarray(severities),
            jnp.asarray(schedule.probs_chunk(r0, k)),
        )

    # ------------------------------------------------------------------
    # Imperative shell
    # ------------------------------------------------------------------

    @property
    def total_timesteps(self) -> int:
        return default_total_timesteps(self.config)

    def _dispatch(self, rollouts: int) -> Dict[str, Array]:
        """Dispatch the jitted program once (``rollouts`` iterations of
        training), under the opt-in runtime guards, and advance the host
        counters. Shared by the host-loop and fused-scan shells."""
        self._apply_pending_schedule()
        # Train-lane chaos seams (chaos/plane.py, docs/chaos.md): a
        # 'raise' armed at the poison points is interpreted HERE, at the
        # dispatch boundary, as state corruption — a NaN bomb into the
        # carry, or a finite 1e18 scale whose gradients explode — the
        # deterministic stand-ins for organic divergence the health word
        # + recovery ladder exist to absorb. Host-side only (rule 19).
        try:
            fault_point("train.carry_poison")
        except InjectedFault:
            self._poison_carry(float("nan"))
        try:
            fault_point("train.grad_bomb")
        except InjectedFault:
            self._poison_carry(1.0e18)
        with contextlib.ExitStack() as stack:
            if self.config.guard_transfers and self._dispatches > 0:
                # Post-warmup only: the compile dispatch legitimately
                # uploads trace-time constants; from the second dispatch
                # on, any device->host sync in here is a hot-loop bug.
                stack.enter_context(profiling.no_host_transfers())
            if self.config.guard_nans:
                stack.enter_context(profiling.nan_guard())
            if self.scenario_params is None:
                extra = ()
            elif self._fused_chunk:
                # Chunked dispatch (any fused_chunk — a K=1 scan still
                # takes xs with a leading (1,) axis): each scanned
                # iteration gets the params the host loop would draw at
                # its rollout index, resampled per iteration — not one
                # batch frozen across the chunk.
                extra = (self._next_scenario_chunk(rollouts),)
            else:
                extra = (self.scenario_params,)
            # On the profiler's own clock (a relaxed atomic load while no
            # trace is open): the dispatch index is the identifier every
            # later span of this chunk shares.
            with jax.profiler.StepTraceAnnotation(
                "train_dispatch", step_num=self._dispatches
            ):
                (
                    self.train_state,
                    self.env_state,
                    self.obs,
                    self.key,
                    metrics,
                ) = self._iteration(
                    self.train_state,
                    self.env_state,
                    self.obs,
                    self.key,
                    *extra,
                )
        self._dispatches += 1
        # Live-metrics plane (obs/metrics.py, docs/observability.md):
        # recorded at the dispatch seam, never under trace (graftlint
        # rule 18). Two dict ops per dispatch — noise next to a rollout.
        get_registry().counter("train_iterations_total").inc(rollouts)
        self.num_timesteps += rollouts * self.ppo.n_steps * self.num_envs
        self._vec_steps_since_save += rollouts * self.ppo.n_steps
        if self._scenario_schedule is not None:
            self._scenario_rollouts += rollouts
            self._scenario_draws += rollouts
            if not self._fused_chunk:
                # Fused mode draws its params from
                # _next_scenario_chunk at dispatch time — resampling the
                # single-dispatch batch here would be one wasted device
                # program per chunk on the hot path.
                self._resample_scenario_params()
        return metrics

    def run_iteration(self) -> Dict[str, float]:
        """One host-loop dispatch — one rollout+update iteration;
        returns device metrics."""
        assert not self._fused_chunk, (
            "fused_chunk trainers dispatch via run_chunk() (stacked "
            "per-iteration metrics), not run_iteration()"
        )
        return self._dispatch(1)

    def run_chunk(self) -> Dict[str, Array]:
        """Anakin mode: dispatch ONE fused-scan chunk (``fused_chunk``
        iterations) and return the per-iteration metrics stack as DEVICE
        arrays (leading ``(k,)`` axis). The call returns as soon as the
        program is enqueued — the caller overlaps the host drain of the
        previous chunk with this one's execution (see ``_train_fused``)."""
        assert self._fused_chunk > 0, (
            "run_chunk() needs fused_chunk > 0 (Anakin mode)"
        )
        return self._dispatch(self._fused_chunk)

    def train(self) -> Dict[str, float]:
        """Full training run with metrics + checkpoints; returns the last
        emitted metrics record."""
        if self._fused_chunk:
            return self._train_fused()
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        meter = Throughput()
        last_record: Dict[str, float] = {}
        iteration = 0
        # profile=true: trace a few post-warmup dispatches (the first is
        # compile-bound and would dominate the trace).
        tracer = profiling.TraceWindow(
            self.log_dir, self.config.profile, self.config.profile_iterations
        )
        try:
            while self.num_timesteps < self.total_timesteps and (
                not self.halted
            ):
                tracer.before_dispatch()
                metrics = self.run_iteration()
                iteration += 1
                tracer.after_dispatch(metrics)
                meter.tick(self.ppo.n_steps * self.config.num_formations)
                # Live gauges every dispatch (three dict writes), not
                # just at log cadence — GET /metrics must answer "how
                # fast right now" even when log_interval is long.
                self._record_lane_metrics(meter.rate())
                if iteration % self.config.log_interval == 0:
                    # One host sync per log interval, after dispatch — a
                    # single batched device_get, NOT per-metric float():
                    # every transfer is a host sync that stalls the
                    # dispatch pipeline, and ~16 of them per iteration
                    # can cost more than the iteration itself. The health flags ride the SAME
                    # sync — never a per-iteration finiteness probe
                    # (graftlint rule 22), so with log_interval > 1 the
                    # host-loop ladder observes at log cadence.
                    host_metrics = jax.device_get(metrics)
                    if self._observe_health(host_metrics, iteration):
                        # Rolled back (or halted): the state was
                        # restored; this dispatch's record is poisoned
                        # telemetry — drop it and continue/stop.
                        continue
                    last_record = {
                        k: float(v) for k, v in host_metrics.items()
                    }
                    last_record["env_steps_per_sec"] = meter.rate()
                    if self._scenario_schedule is not None:
                        # Severity of the NEXT dispatch was already
                        # resampled; record the one this metrics batch
                        # actually trained at.
                        last_record["scenario_severity"] = float(
                            self._scenario_schedule.severity_at(
                                max(self._scenario_rollouts - 1, 0)
                            )
                        )
                    logger.log(last_record, self.num_timesteps)
                if (
                    self.config.checkpoint
                    and self._vec_steps_since_save >= self.config.save_freq
                ):
                    if (
                        self.recovery_ladder is not None
                        and iteration % self.config.log_interval != 0
                    ):
                        # With log_interval > 1 this dispatch's flags
                        # were never drained — and publishing an
                        # unobserved state can mint a finite-but-
                        # poisoned checkpoint at a newer step per save,
                        # outrunning the quarantine walk. The save
                        # boundary is already an IO seam, so one small
                        # flag pull here is not the per-iteration probe
                        # rule 22 bans.
                        flags = jax.device_get({
                            k: metrics[k]
                            for k in ("health_ok", "health_word")
                            if k in metrics
                        })
                        if self._observe_health(flags, iteration):
                            continue  # rolled back: nothing to save
                    if not self._saves_suspended():
                        self.save()
            if self.recovery_ladder is not None and not self.halted:
                # Run-end guarantee, host-loop flavor (the fused driver
                # has its own call): finite final params even when a
                # tail poison never tripped the ladder.
                self._ensure_finite_final_state(None, iteration)
            if self.config.checkpoint and not self._saves_suspended():
                # The final save honors the suspect window too: a
                # finite-but-diverged tail state (shorter than
                # breach_iters) must not become the newest discoverable
                # checkpoint — the last-good file already on disk is
                # the state worth resuming.
                self.save()
        finally:
            tracer.close()
            logger.close()
        return last_record

    # ------------------------------------------------------------------
    # Anakin mode (fused_chunk > 0): whole-loop scan dispatch with an
    # async metrics drain and a background checkpoint pipeline
    # (docs/training.md "Anakin mode").
    # ------------------------------------------------------------------

    def _train_fused(self) -> Dict[str, float]:
        """Fused-scan driver: dispatch chunk N+1 BEFORE draining chunk
        N's metrics (double-buffered — the device computes while the host
        logs), and checkpoint at chunk boundaries on a background writer
        thread off a device-side snapshot. The emitted records are
        per-iteration, identical to the host loop's (log_interval honored
        on the global iteration index)."""
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        meter = Throughput()
        writer = (
            AsyncCheckpointWriter(
                keep_last_n=self.config.keep_last_n,
                protect=self._protected_paths,
            )
            if self.config.checkpoint
            else None
        )
        # Chunk-granular profile=true: trace profile_iterations whole
        # chunks post-warmup — one dispatch is one chunk here.
        tracer = profiling.TraceWindow(
            self.log_dir, self.config.profile, self.config.profile_iterations
        )
        last_record: Dict[str, float] = {}
        k = self._fused_chunk
        iteration = 0
        pending = None  # the chunk in flight, drained one dispatch later
        try:
            while self.num_timesteps < self.total_timesteps and (
                not self.halted
            ):
                steps_before = self.num_timesteps
                tracer.before_dispatch()
                stacked = self.run_chunk()
                tracer.after_dispatch(stacked)
                # The severities this chunk ACTUALLY trained at — stashed
                # by _next_scenario_chunk inside the dispatch, after any
                # pending curriculum swap was applied, so a feedback
                # schedule landing concurrently can never desync the
                # logged severities from the trained ones.
                severities = self._last_chunk_severities
                if pending is not None:
                    last_record = (
                        self._drain_chunk(logger, meter, *pending)
                        or last_record
                    )
                    if self._act_on_recovery_verdict(writer, iteration):
                        # Rolled back (or halted): the chunk just
                        # dispatched trained FROM the diverged state —
                        # abandon it undrained and restart the pipeline
                        # from the restored state.
                        pending = None
                        continue
                pending = (stacked, iteration, steps_before, severities)
                iteration += k
                if (
                    writer is not None
                    and self._vec_steps_since_save >= self.config.save_freq
                    and not self._saves_suspended()
                ):
                    self.save_async(writer)
            if pending is not None:
                last_record = (
                    self._drain_chunk(logger, meter, *pending) or last_record
                )
                self._act_on_recovery_verdict(writer, iteration)
            if self.recovery_ladder is not None and not self.halted:
                # Terminal guarantee: the run must END on finite params
                # even when the budget expired mid-breach (a tail poison
                # shorter than breach_iters never trips the ladder). ONE
                # host check at run end — never inside the dispatch loop.
                self._ensure_finite_final_state(writer, iteration)
            if writer is not None:
                if not self._saves_suspended():
                    # Suspect tail states stay unpublished (see the
                    # host loop's final save) — the ring's last-good
                    # file is the resume point.
                    self.save_async(writer)
                writer.close()  # the final write is durable before return
                writer = None
        finally:
            tracer.close()
            if writer is not None:
                # Unwinding on an error: drain the writer without letting
                # a secondary write failure mask the original exception.
                writer.close_quietly()
            logger.close()
        return last_record

    def _record_lane_metrics(self, env_steps_rate: float) -> None:
        """Publish this lane's throughput gauges into the process
        registry (the ``GET /metrics`` namespace): env-steps/s,
        train-steps/s, and the live RetraceGuard compile counter —
        what ROADMAP item 3's autoscaler and the RegressionSentinel
        watch. Host-seam only (the drain, after device_get)."""
        registry = get_registry()
        registry.gauge("train_env_steps_per_sec").set(env_steps_rate)
        per_iter = self.ppo.n_steps * self.config.num_formations
        registry.gauge("train_steps_per_sec").set(
            env_steps_rate / per_iter if per_iter else 0.0
        )
        registry.gauge("train_compiles").set(self.retrace_guard.count)

    def _drain_chunk(
        self, logger, meter, stacked, first_iteration, steps_before,
        severities,
    ) -> Dict[str, float]:
        """ONE batched ``device_get`` for a whole chunk's telemetry, then
        emit per-iteration records exactly like the host loop would.
        Called after the NEXT chunk has been dispatched, so this blocks on
        the finished chunk while the device already runs the new one."""
        t_drain = time.perf_counter()
        with jax.profiler.TraceAnnotation(
            "train_drain", chunk=first_iteration
        ):
            host = jax.device_get(stacked)
        meter.tick(
            self._fused_chunk * self.ppo.n_steps * self.config.num_formations
        )
        registry = get_registry()
        registry.histogram("train_chunk_drain_seconds").observe(
            time.perf_counter() - t_drain
        )
        registry.counter("train_chunks_total").inc()
        # Device-memory watermark at the drain boundary: the one host
        # seam per chunk where a sync just happened anyway, so the
        # sample costs no extra pipeline stall (obs/ledger.py).
        profiling.sample_device_watermark()
        self._record_lane_metrics(meter.rate())
        if "health_ok" in host:
            # The drain seam IS the detection seam: the health flags
            # arrived in the same batched device_get as the rest of the
            # chunk telemetry (zero extra syncs), so a divergence is
            # seen within ONE chunk drain of the poisoned dispatch. The
            # ladder's verdict is acted on by the driver loop (it owns
            # the in-flight chunk and the writer).
            if self.recovery_ladder is not None:
                self._recovery_verdict = self.recovery_ladder.observe(
                    host["health_ok"],
                    host.get("health_word"),
                    first_iteration,
                )
            else:
                from marl_distributedformation_tpu.train.recovery import (
                    record_health_flags,
                )

                record_health_flags(host)
        per_iter = self.ppo.n_steps * self.num_envs
        last_record: Dict[str, float] = {}
        for i in range(self._fused_chunk):
            if (first_iteration + i + 1) % self.config.log_interval:
                continue
            record = {name: float(v[i]) for name, v in host.items()}
            record["env_steps_per_sec"] = meter.rate()
            if severities is not None:
                record["scenario_severity"] = float(severities[i])
            logger.log(record, steps_before + (i + 1) * per_iter)
            last_record = record
        return last_record

    # ------------------------------------------------------------------
    # Recovery ladder actions (train/recovery.py, docs/recovery.md)
    # ------------------------------------------------------------------

    def _saves_suspended(self) -> bool:
        """Checkpoint cadence gate: while the ladder's most recent
        observation ended unhealthy, submit NOTHING. A finite-but-
        diverged state (grad bomb) passes the non-finite write gate;
        writing one per chunk would hand every rollback a fresh copy of
        the poison at an ever-newer step, defeating the quarantine-on-
        retarget walk. The first poisoned pre-detection write is
        unavoidable (detection lags one chunk) — that one file is
        exactly what the walk quarantines."""
        return (
            self.recovery_ladder is not None
            and self.recovery_ladder.suspect
        )

    def _poison_carry(self, value: float) -> None:
        """Chaos effect for the ``train.carry_poison`` / ``train.
        grad_bomb`` seams: corrupt the LIVE device params at the
        dispatch boundary (NaN kills the loss; a finite 1e18 scale
        explodes the gradients) — the deterministic stand-in for
        organic divergence."""
        poison = jnp.float32(value)
        self.train_state = self.train_state.replace(
            params=jax.tree_util.tree_map(
                lambda p: p * poison, self.train_state.params
            )
        )

    def _observe_health(self, host_metrics, iteration: int) -> bool:
        """Host-loop seam: feed the just-synced health flags to the
        ladder and act on its verdict. Returns True when the state was
        restored (rollback or halt) — the caller drops the poisoned
        record and continues (or stops)."""
        if "health_ok" not in host_metrics:
            return False
        if self.recovery_ladder is None:
            from marl_distributedformation_tpu.train.recovery import (
                record_health_flags,
            )

            record_health_flags(host_metrics)
            return False
        self._recovery_verdict = self.recovery_ladder.observe(
            host_metrics["health_ok"],
            host_metrics.get("health_word"),
            iteration,
        )
        return self._act_on_recovery_verdict(None, iteration)

    def _act_on_recovery_verdict(
        self, writer: Optional[AsyncCheckpointWriter], iteration: int
    ) -> bool:
        """Consume the verdict the last drain stored; perform the
        rollback / halt. Returns True when state was restored."""
        verdict, self._recovery_verdict = self._recovery_verdict, None
        if verdict in (None, "ok"):
            return False
        if verdict == "rollback":
            self._perform_rollback(writer, iteration)
            return True
        self._perform_rollback(
            writer,
            iteration,
            halt_reason=(
                "sustained divergence with the rollback budget "
                f"exhausted ({self.recovery_ladder.recoveries} "
                "recoveries spent)"
            ),
        )
        return True

    def _perform_rollback(
        self,
        writer: Optional[AsyncCheckpointWriter],
        iteration: int,
        halt_reason: Optional[str] = None,
    ) -> None:
        """Restore the newest VALID last-good state (checkpoint walk, or
        the run-start anchor when none exists), advance the PRNG stream
        past the divergence via the folded recovery counter, and apply
        the configured lr/severity backoff. With ``halt_reason`` the
        restore is terminal: the run ends here, on finite params, with
        a flight record."""
        from marl_distributedformation_tpu.train.recovery import (
            fold_recovery_key,
            scale_injected_lr,
        )
        from marl_distributedformation_tpu.utils.checkpoint import (
            quarantine_checkpoint,
        )

        t0 = time.perf_counter()
        ladder = self.recovery_ladder
        if writer is not None:
            try:
                # Join the in-flight write: it may be publishing the very
                # last-good file the walk below should find (or skipping
                # a poisoned one — the non-finite gate's audit trail owns
                # that).
                writer.wait()
            except RuntimeError:
                pass  # a failed WRITE must never block recovery; the
                #   skip/quarantine audit trail already recorded it
        found = None
        if self.config.checkpoint:
            for _ in range(8):
                found = restore_latest_partial(
                    self.log_dir, self._checkpoint_target()
                )
                if (
                    found is not None
                    and ladder is not None
                    and ladder.last_rollback_path == str(found[0])
                ):
                    # The previous rollback restored THIS file and the
                    # run re-diverged without any healthy progress: the
                    # checkpoint itself carries the poison (finite-but-
                    # diverged params slip past the non-finite write
                    # gate). Quarantine it and walk further back.
                    quarantine_checkpoint(
                        found[0],
                        "rollback target re-diverged (finite but "
                        "unhealthy state); walking back",
                    )
                    found = None
                    continue
                break
        if found is not None:
            path, restored = found
        else:
            path, restored = None, dict(self._rollback_anchor)
        restored = own_restored(restored)
        self.train_state = self.train_state.replace(
            params=restored["params"],
            opt_state=restored.get("opt_state", self.train_state.opt_state),
        )
        if "key" in restored:
            self.key = jnp.asarray(restored["key"])
        self.num_timesteps = int(restored["num_timesteps"])
        if "env_state" in restored:
            self.env_state = restored["env_state"]
            self.obs = restored["obs"]
        if self._shard_fn is not None:
            self.train_state, self.env_state, self.obs = self._shard_fn(
                self.train_state, self.env_state, self.obs
            )
        recoveries_next = (ladder.recoveries if ladder is not None else 0) + 1
        # The retry must not bitwise-replay the divergence: fold the
        # recovery counter into the restored key (deterministic — retry
        # N from checkpoint C is a pure function of (C, N)).
        self.key = fold_recovery_key(self.key, recoveries_next)
        lr_scale = None
        if self.config.recovery_lr_backoff != 1.0:
            scaled = scale_injected_lr(
                self.train_state.opt_state, self.config.recovery_lr_backoff
            )
            if scaled is not None:
                self.train_state = self.train_state.replace(opt_state=scaled)
                lr_scale = self.config.recovery_lr_backoff
            else:
                from marl_distributedformation_tpu.obs import get_tracer

                get_tracer().incident(
                    "train_lr_backoff_unavailable",
                    detail="opt state carries no injected learning_rate "
                    "leaf; backoff skipped",
                )
        severity_scale = None
        if (
            self.config.recovery_severity_backoff != 1.0
            and self._scenario_schedule is not None
        ):
            self._severity_scale *= self.config.recovery_severity_backoff
            severity_scale = self._severity_scale
        if self._scenario_schedule is not None:
            self._scenario_rollouts = self.num_timesteps // (
                self.ppo.n_steps * self.num_envs
            )
            # The draw counter NEVER rewinds (the no-replay law the
            # curriculum feedback loop already obeys) — the retry draws
            # fresh domain randomization instead of replaying the
            # possibly-divergence-inducing draws.
            self._scenario_draws = max(
                self._scenario_draws, self._scenario_rollouts
            )
            self._resample_scenario_params()
        self._vec_steps_since_save = 0
        if path is not None:
            self._last_good_ckpt = Path(path)
        mttr_s = time.perf_counter() - t0
        if ladder is None:
            return
        if halt_reason is None:
            ladder.note_rollback(
                to_step=self.num_timesteps,
                path=str(path) if path is not None else None,
                mttr_s=mttr_s,
                iteration=iteration,
                lr_scale=lr_scale,
                severity_scale=severity_scale,
            )
        else:
            ladder.note_halt(iteration, halt_reason)
            self.halted = True

    def _ensure_finite_final_state(
        self, writer: Optional[AsyncCheckpointWriter], iteration: int
    ) -> None:
        """Run-end guarantee: finite final params, even when the budget
        expired mid-breach (a tail poison shorter than breach_iters
        never trips the ladder; this terminal restore may exceed the
        retry budget by one — it is a guarantee, not a retry). One host
        pull, outside the dispatch loop."""
        from marl_distributedformation_tpu.utils.checkpoint import (
            nonfinite_leaf,
        )

        if nonfinite_leaf(
            jax.device_get(self.train_state.params)
        ) is not None:
            self._perform_rollback(writer, iteration)

    def _protected_paths(self):
        """Retention-ring protection set: the ladder's current last-good
        rollback target must survive pruning no matter how old it is."""
        return (
            {self._last_good_ckpt}
            if self._last_good_ckpt is not None
            else set()
        )

    def _snapshot_for_write(self) -> Dict[str, Any]:
        """The checkpoint target, through the ``train.snapshot`` chaos
        seam: an armed fault poisons the SNAPSHOT copy (never the live
        carry) — checkpoint-time state corruption, which the non-finite
        write gate (utils/checkpoint.py) must keep invisible to
        discovery."""
        target = self._checkpoint_target()
        try:
            fault_point("train.snapshot")
        except InjectedFault:
            poison = jnp.float32(float("nan"))
            target = dict(target)
            target["params"] = jax.tree_util.tree_map(
                lambda p: p * poison, target["params"]
            )
        return target

    def save_async(self, writer: AsyncCheckpointWriter) -> str:
        """Chunk-boundary checkpoint that never stalls the dispatch
        pipeline: snapshot the state on DEVICE (async copies enqueued
        behind the chunk that produced it — the next chunk's donation
        cannot invalidate them; utils.device_snapshot), then hand the
        snapshot to the writer thread, which ``device_get``s and writes
        atomically while the device keeps training."""
        path = checkpoint_path(self.log_dir, self.num_timesteps)
        on_checkpoint = self.on_checkpoint

        def on_done(p) -> None:
            # Runs on the writer thread AFTER the rename lands — i.e.
            # the file passed the non-finite gate and is durably
            # discoverable: the newest valid rollback target.
            self._last_good_ckpt = Path(p)
            if on_checkpoint is not None:
                on_checkpoint(p)

        writer.submit(
            path,
            device_snapshot(self._snapshot_for_write()),
            on_done=on_done,
        )
        self._vec_steps_since_save = 0
        return str(path)

    # ------------------------------------------------------------------
    # Checkpointing (write/read contract: SURVEY.md §5)
    # ------------------------------------------------------------------

    def _checkpoint_target(self) -> Dict[str, Any]:
        target = {
            "policy": self.model.__class__.__name__,
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "key": self.key,
            "num_timesteps": self.num_timesteps,
            # Provenance: the rate this state was trained at (sweep member
            # checkpoints record their per-member rate here; resume warns
            # on mismatch).
            "learning_rate": float(self.ppo.learning_rate),
        }
        if hasattr(self.model, "arch"):
            # the architecture file playback rebuilds a trunk from
            target["trunk"] = self.model.arch.name
        if not self._multihost:
            # dp-sharded env state is not coordinator-addressable across
            # hosts; multi-host checkpoints carry the learner state only and
            # resume re-resets the environment (on-policy PPO loses nothing
            # but the tail of one rollout).
            target["env_state"] = self.env_state
            target["obs"] = self.obs
        return target

    def save(self) -> Optional[str]:
        """Write a checkpoint; returns its path on the coordinator process
        and None on every other host (the file exists only on the
        coordinator's disk — see utils.save_checkpoint) or when the
        non-finite write gate skipped a poisoned state (audited —
        docs/recovery.md)."""
        path = save_checkpoint(
            self.log_dir, self.num_timesteps, self._snapshot_for_write()
        )
        self._vec_steps_since_save = 0
        if path is not None:
            self._last_good_ckpt = Path(path)
            if self.config.keep_last_n > 0:
                from marl_distributedformation_tpu.utils.checkpoint import (
                    prune_checkpoints,
                )

                prune_checkpoints(
                    self.log_dir,
                    self.config.keep_last_n,
                    protect=self._protected_paths(),
                )
            if self.on_checkpoint is not None:
                self.on_checkpoint(path)
        return str(path) if path is not None else None

    def _learner_template(self) -> Dict[str, Any]:
        return {
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "key": self.key,
            "num_timesteps": self.num_timesteps,
        }

    def _try_resume(self) -> None:
        if self._multihost:
            self._try_resume_multihost()
            return
        # Partial restore: a multi-host-written (learner-only) checkpoint
        # resumes fine single-host — env state just starts fresh. A
        # converted SB3 checkpoint (compat/sb3_import.py) carries params
        # only; missing learner pieces (opt_state, key) keep their fresh
        # values — a warm-started fine-tune re-estimates Adam moments
        # within a few iterations. Corrupt/truncated files are
        # quarantined and the walk-back resumes from the newest VALID
        # checkpoint (utils.restore_latest_partial) — a crashed writer
        # costs one checkpoint, never a wedged resume.
        found = restore_latest_partial(
            self.log_dir, self._checkpoint_target()
        )
        if found is None:
            return
        path, restored = found
        # Owning copies BEFORE the donating dispatch sees this state
        # (utils.own_restored: msgpack leaves can alias the checkpoint
        # bytes, and donating an aliased buffer is a use-after-free on
        # the zero-copy CPU backend — observed as garbage params in a
        # resumed fused sweep; the single-run path shares the hazard).
        restored = own_restored(restored)
        self.train_state = self.train_state.replace(
            params=restored["params"],
            opt_state=restored.get("opt_state", self.train_state.opt_state),
        )
        if "key" in restored:
            self.key = restored["key"]
        # num_timesteps stays REQUIRED: every writer (trainer save,
        # sb3_import) records it, so its absence means a truncated or
        # foreign file — silently restarting the counter at 0 would write
        # low-step checkpoints beside high-step ones and reset schedules.
        self.num_timesteps = int(restored["num_timesteps"])
        ckpt_lr = restored.get("learning_rate")
        if ckpt_lr is not None and not jnp.isclose(
            float(ckpt_lr), self.ppo.learning_rate, rtol=1e-6
        ):
            print(
                f"[trainer] WARNING: checkpoint was trained at "
                f"learning_rate={float(ckpt_lr):g} but this run uses "
                f"{self.ppo.learning_rate:g} — pass "
                f"learning_rate={float(ckpt_lr):g} to continue at the "
                "original rate"
            )
        if "env_state" in restored:
            self.env_state = restored["env_state"]
            self.obs = restored["obs"]
        if self._shard_fn is not None:
            # Checkpoints restore as host arrays; re-place them on the
            # mesh or the resumed run silently trains single-device.
            self.train_state, self.env_state, self.obs = self._shard_fn(
                self.train_state, self.env_state, self.obs
            )
        if self._scenario_schedule is not None:
            # Re-enter the schedule where the run left off — every rollout
            # advances num_timesteps by exactly n_steps * num_envs, so the
            # global rollout index is recoverable without extra checkpoint
            # state (restarting at 0 would silently replay the severity
            # ramp from the first stage).
            self._scenario_rollouts = self.num_timesteps // (
                self.ppo.n_steps * self.num_envs
            )
            # The draw counter equals the global rollout index for any
            # run that has not swapped schedules (mid-run swaps are
            # live-process state, not checkpointed — docs/adversarial.md).
            self._scenario_draws = self._scenario_rollouts
            self._resample_scenario_params()
        print(f"[trainer] resumed from {path} at {self.num_timesteps} steps")

    def _try_resume_multihost(self) -> None:
        """Coordinator restores, every host receives the same learner state
        (utils.broadcast_restore); env state stays freshly reset."""
        from marl_distributedformation_tpu.parallel import replicate
        from marl_distributedformation_tpu.utils import broadcast_restore

        restored = broadcast_restore(self.log_dir, self._learner_template())
        if restored is None:
            return
        self.train_state = self.train_state.replace(
            params=restored["params"], opt_state=restored["opt_state"]
        )
        self.key = jnp.asarray(restored["key"])
        self.num_timesteps = int(restored["num_timesteps"])
        self.train_state = replicate(self.train_state, self._shard_fn.mesh)
        print(
            f"[trainer] process {jax.process_index()} resumed (broadcast) "
            f"at {self.num_timesteps} steps"
        )
