"""Candidate-seed populations of the heterogeneous curriculum in ONE jit.

Why this exists (round 5): deterministic-mode quality of the config-5
curriculum is SEED-VARIANT — the CPU study behind
docs/acceptance/hetero5/README.md measured only ~1/3-1/2 of seeds
producing a mode action that beats the scripted baseline in every eval
row, and a same-seed retrain is deterministic, so the chip acceptance
workflow was train-one-candidate -> det-gate -> reseed, one chip
session per candidate. This trainer collapses that loop: K candidate
seeds of the FULL curriculum train simultaneously as one vmapped XLA
program (the population axis is embarrassingly parallel — zero
collectives), so ONE window trains every candidate and held-out
deterministic evaluation (evaluate.py's sweep mode ranks all members)
selects the winner.

Composition of two existing shells, not new machinery:

- the functional iteration is ``curriculum.make_hetero_iteration`` —
  the exact program ``HeteroTrainer`` jits — ``jax.vmap``-ed over a
  leading (K,) member axis (the ``SweepTrainer`` pattern,
  train/sweep.py);
- member ``i`` follows ``HeteroTrainer(seed=config.seed + i)``'s key
  discipline exactly — init split, per-stage count/env splits — so a
  population member IS the corresponding single run (equivalence pinned
  at float tolerance by tests/test_hetero_sweep.py; over hundreds of
  iterations the vmapped and single programs can drift apart through
  fusion-level rounding on this chaotic objective, as any two
  compilations of the same run can);
- artifacts follow the sweep contract: per-member checkpoints under
  ``{log_dir}/seed{i}/`` (standard single-run tooling plays them back)
  plus ``sweep_summary.json``, so ``evaluate.py name=run`` ranks all
  members and ``visualize_policy.py`` descends to the best member with
  no new code.

Deliberate scope (documented restrictions, enforced loudly):
single-controller only (the config-5 acceptance runs on one chip; use
``SweepTrainer`` for multi-host populations) and no per-member learning
rates.
``fused_chunk=K`` (round 6) DOES compose: within a stage, K vmapped
iterations fuse into one ``lax.scan`` dispatch — chunks clip at the
host-driven stage boundaries (a stage tail shorter than K compiles its
own scan length, once, cached), telemetry drains double-buffered, and
population checkpoints write async off a device-side snapshot at chunk
boundaries (``tests/test_fused_sweep.py`` pins bitwise parity with the
host loop across stage changes). ``resume=true`` restores the latest
``sweep_state_*`` population checkpoint — params, batched optimizer
state, member PRNG streams, env state, per-member counters, and the
curriculum cursor — and continues bit-identically to an uninterrupted
run, including MID-stage (the partially-walked stage is not resampled).
Operationally critical where chip time comes in bounded calls: the
K-candidate curriculum is the longest stage in the validation queue.
An optional ``mesh={dp: D}`` shards the member axis over devices
(``jax.shard_map``, K % D == 0), which is the 7th ``dryrun_multichip``
path (__graft_entry__.py).
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.hetero import (
    hetero_compute_obs,
    hetero_reset_batch,
)
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.train.curriculum import (
    Curriculum,
    CurriculumStage,
    make_hetero_iteration,
    sample_stage_counts,
)
from marl_distributedformation_tpu.train.recovery import record_health_flags
from marl_distributedformation_tpu.train.sweep import (
    population_aggregate,
    write_sweep_summary,
)
from marl_distributedformation_tpu.train.trainer import (
    TrainConfig,
    fill_ent_schedule,
    make_fused_chunk,
)
from marl_distributedformation_tpu.utils import (
    AsyncCheckpointWriter,
    MetricsLogger,
    Throughput,
    device_snapshot,
    latest_sweep_state,
    own_restored,
    repo_root,
    save_sweep_state,
)
from marl_distributedformation_tpu.utils import profiling
from marl_distributedformation_tpu.utils.checkpoint import (
    _write_atomic,
    checkpoint_path,
)

Array = jax.Array


class HeteroSweepTrainer:
    """K candidate seeds of the hetero curriculum under one jit.

    Args:
      curriculum / env_params / ppo / config: as :class:`HeteroTrainer`.
      num_seeds: population size K; member ``i`` trains at seed
        ``config.seed + i``.
      model: policy module shared across members (fresh params per
        member); agent-factored MLP or per-formation CTDE.
      mesh: optional ``jax.sharding.Mesh`` whose ``'dp'`` axis shards the
        member axis (K must divide by it).
    """

    def __init__(
        self,
        curriculum: Curriculum = Curriculum(),
        env_params: Optional[EnvParams] = None,
        ppo: PPOConfig = PPOConfig(),
        config: TrainConfig = TrainConfig(),
        num_seeds: int = 4,
        model: Any = None,
        mesh: Any = None,
    ) -> None:
        assert num_seeds >= 1
        if jax.process_count() > 1:
            raise SystemExit(
                "HeteroSweepTrainer is single-controller: the config-5 "
                "candidate workflow runs on one chip. Multi-host "
                "populations are SweepTrainer's domain (drop the "
                "curriculum), or run one process."
            )
        self._fused_chunk = max(0, int(config.fused_chunk))
        self.curriculum = curriculum
        if env_params is None:
            env_params = EnvParams()
        self.env_params = env_params.replace(
            num_agents=max(curriculum.max_agents, env_params.num_agents),
            num_obstacles=max(
                curriculum.max_obstacles, env_params.num_obstacles
            ),
        )
        ppo = fill_ent_schedule(
            ppo, self.env_params, config,
            iterations=curriculum.total_rollouts,
        )
        self.ppo = ppo
        self.config = config
        self.num_seeds = num_seeds
        self.model = model or MLPActorCritic(
            act_dim=self.env_params.act_dim, log_std_init=ppo.log_std_init
        )
        self.per_formation = getattr(self.model, "per_formation", False)

        if self.per_formation:
            dummy_obs = jnp.zeros(
                (1, self.env_params.num_agents, self.env_params.obs_dim),
                jnp.float32,
            )
        else:
            dummy_obs = jnp.zeros(
                (1, self.env_params.obs_dim), jnp.float32
            )
        model_ref = self.model
        tx = ppo.make_optimizer()

        def init_member(seed: Array):
            # EXACTLY HeteroTrainer.__init__'s key discipline so member i
            # == HeteroTrainer(seed=config.seed + i) (same PRNG streams;
            # equivalence pinned by tests/test_hetero_sweep.py).
            key = jax.random.PRNGKey(seed)
            key, k_init = jax.random.split(key)
            params = model_ref.init(k_init, dummy_obs)
            ts = TrainState.create(
                apply_fn=model_ref.apply, params=params, tx=tx
            )
            return ts, key

        self._mesh = mesh
        if mesh is not None:
            assert set(mesh.axis_names) == {"dp"}, (
                f"hetero-sweep meshes shard the MEMBER axis over 'dp' "
                f"only; got axes {tuple(mesh.axis_names)} (the padded "
                "dynamic ring cannot shard the agent axis — see "
                "HeteroTrainer)"
            )
            dp = int(mesh.shape["dp"])
            assert num_seeds % dp == 0, (
                f"num_seeds={num_seeds} must be divisible by the mesh dp "
                f"axis ({dp})"
            )

        seeds = config.seed + jnp.arange(num_seeds)
        self.train_state, self.key = jax.jit(jax.vmap(init_member))(seeds)

        iteration = make_hetero_iteration(
            self.env_params, ppo, self.per_formation
        )
        # In-program health word + skip-update guard (train/recovery.py),
        # wrapped before the vmap so each curriculum candidate carries
        # and acts on its own flags.
        from marl_distributedformation_tpu.train.recovery import wrap_health

        iteration = wrap_health(iteration, config)
        iteration_pop = jax.vmap(iteration)
        if mesh is not None:
            # shard_map over the member axis (not bare jit-under-mesh):
            # members are independent, each device runs K/D of them
            # entirely locally — provably zero collectives (the
            # SweepTrainer rationale, train/sweep.py).
            from jax.sharding import PartitionSpec

            spec = PartitionSpec("dp")
            iteration_pop = jax.shard_map(
                iteration_pop,
                mesh=mesh,
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )
        self._iteration_pop = iteration_pop
        # ONE guard across the host-loop program and every fused chunk
        # length: `count` is the total number of compiles this trainer
        # triggered. A curriculum whose stage lengths divide fused_chunk
        # compiles exactly once; a clipped stage tail costs one extra
        # compile per DISTINCT tail length (cached below, never per
        # dispatch) — size the guard_retraces budget accordingly.
        self.retrace_guard = profiling.RetraceGuard(
            "hetero_sweep_iteration",
            max_traces=config.guard_retraces or None,
        )
        self._iteration = profiling.ledgered_jit(
            iteration_pop,
            self.retrace_guard,
            subsystem="hetero_sweep",
            program="hetero_sweep_iteration",
            donate_argnums=(0, 1),
        )
        self._fused_programs: Dict[int, Any] = {}

        self.env_state = None
        self.obs = None
        # Per-member active agent-transition counters (the SB3
        # num_timesteps analog; members sample their own mixes, so the
        # counts differ per member).
        self.num_timesteps_members = np.zeros(num_seeds, np.int64)
        self.completed_rollouts = 0
        self._vec_steps_since_save = 0
        self._active_agents = np.zeros(num_seeds, np.int64)
        self.log_dir = config.log_dir or str(
            repo_root() / "logs" / config.name
        )
        if config.resume:
            # Restore BEFORE mesh placement (start_stage re-places) —
            # exactly the SweepTrainer ordering. An interrupted candidate
            # block continues bit-identically instead of retraining from
            # scratch: operationally critical where chip time comes in
            # bounded calls, the K-candidate curriculum being the
            # longest single stage in the validation queue.
            self._try_resume()

    # ------------------------------------------------------------------

    @property
    def num_timesteps(self) -> int:
        """Max over members — the checkpoint-naming / budget scalar (all
        members advance the same rollout count; only their live agent
        mixes differ)."""
        return int(self.num_timesteps_members.max(initial=0))

    @property
    def total_timesteps(self) -> int:
        """Per-member budget. NB when an explicit
        ``config.total_timesteps`` BINDS before the curriculum finishes,
        the whole population stops in LOCKSTEP once the FASTEST-counting
        member (members sample their own mixes, so active-transition
        counts differ) reaches it — slower members then see fewer
        rollouts than their standalone single run would under the same
        cap. The member == HeteroTrainer(seed+i) equivalence therefore
        holds only for non-binding caps (the candidate workflow's case:
        the cap is an upper bound, never attained with mixed stages)."""
        if self.config.total_timesteps is not None:
            return self.config.total_timesteps
        return (
            self.curriculum.total_rollouts
            * self.ppo.n_steps
            * self.config.num_formations
            * self.env_params.num_agents
        )

    def _member_stage_fn(self, stage: CurriculumStage):
        """Per-member stage reset ``key -> (key, env_state, obs)`` — the
        ONE definition of the stage key-split/reset/obs discipline, used
        live by ``start_stage`` and shape-only (``jax.eval_shape``) by
        ``_state_template`` so the resume template cannot drift from the
        real state structure."""
        m = self.config.num_formations
        env_params = self.env_params

        def member_stage(key: Array):
            key, k_counts, k_env = jax.random.split(key, 3)
            n_agents, n_obstacles = sample_stage_counts(k_counts, stage, m)
            env_state = hetero_reset_batch(
                k_env, env_params, n_agents, n_obstacles
            )
            obs = jax.vmap(hetero_compute_obs, in_axes=(0, None))(
                env_state, env_params
            )
            return key, env_state, obs

        return member_stage

    def start_stage(self, stage: CurriculumStage) -> None:
        """Resample every member's formation mix and reset its envs —
        the vmapped analog of ``HeteroTrainer.start_stage`` (each member
        draws its OWN mix from its own key stream, preserving the
        member == single-run equivalence)."""
        self.key, self.env_state, self.obs = jax.jit(
            jax.vmap(self._member_stage_fn(stage))
        )(self.key)
        self._place_on_mesh()
        self._refresh_active_agents()

    def _place_on_mesh(self) -> None:
        """(Re-)place the whole population on the dp mesh — after a stage
        reset or a resume restore; no-op unmeshed."""
        if self._mesh is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec

        shard = NamedSharding(self._mesh, PartitionSpec("dp"))
        place = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jax.device_put(x, shard), t
        )
        self.train_state = place(self.train_state)
        self.env_state = place(self.env_state)
        self.obs = place(self.obs)
        self.key = place(self.key)

    def _refresh_active_agents(self) -> None:
        # ONE host pull for the per-member active-agent counts.
        self._active_agents = np.asarray(
            jax.device_get(self.env_state.n_agents.sum(axis=-1)), np.int64
        )

    def run_iteration(self) -> Dict[str, Array]:
        """One vectorized iteration; metric values carry a leading (K,)
        member axis."""
        assert self.env_state is not None, "call start_stage() first"
        (
            self.train_state,
            self.env_state,
            self.obs,
            self.key,
            metrics,
        ) = self._iteration(
            self.train_state, self.env_state, self.obs, self.key
        )
        self.num_timesteps_members += self.ppo.n_steps * self._active_agents
        self.completed_rollouts += 1
        self._vec_steps_since_save += self.ppo.n_steps
        return metrics

    def _fused_dispatch(self, r: int):
        """The jitted fused program for an ``r``-iteration chunk, cached
        per length. Stage boundaries are host-driven env rebuilds, so a
        chunk never crosses one — stage tails shorter than ``fused_chunk``
        dispatch through a shorter scan, compiled once per distinct
        length and shared by every stage with that remainder."""
        fn = self._fused_programs.get(r)
        if fn is None:
            # One ledger entry per DISTINCT chunk length — exactly the
            # compile cadence the shared guard already accounts for.
            fn = profiling.ledgered_jit(
                make_fused_chunk(self._iteration_pop, r),
                self.retrace_guard,
                subsystem="hetero_sweep",
                program=f"hetero_sweep_chunk_k{r}",
                donate_argnums=(0, 1),
            )
            self._fused_programs[r] = fn
        return fn

    def run_chunk(self, r: Optional[int] = None) -> Dict[str, Array]:
        """Anakin mode: dispatch ONE fused-scan chunk of ``r`` vmapped
        iterations (default ``fused_chunk``; callers clip ``r`` at stage
        boundaries) and return the stacked ``(r, num_seeds, ...)`` device
        metrics. Returns as soon as the program is enqueued."""
        assert self._fused_chunk > 0, (
            "run_chunk() needs fused_chunk > 0 (Anakin mode)"
        )
        assert self.env_state is not None, "call start_stage() first"
        r = self._fused_chunk if r is None else int(r)
        (
            self.train_state,
            self.env_state,
            self.obs,
            self.key,
            stacked,
        ) = self._fused_dispatch(r)(
            self.train_state, self.env_state, self.obs, self.key
        )
        # Active-agent mixes are frozen within a stage and chunks never
        # cross one, so the per-member accounting of r host iterations
        # collapses to one increment.
        self.num_timesteps_members += r * self.ppo.n_steps * self._active_agents
        self.completed_rollouts += r
        self._vec_steps_since_save += r * self.ppo.n_steps
        return stacked

    def train(self) -> Dict[str, float]:
        """Run the full curriculum for every member; logs population
        aggregates per rollout (sweep metric contract: ``reward`` is the
        population mean plus ``reward_best``/``reward_worst``/
        ``best_seed``) and writes per-member checkpoints + the ranking
        summary at the end."""
        if self._fused_chunk:
            return self._train_fused()
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        meter = Throughput()
        tracer = profiling.TraceWindow(
            self.log_dir, self.config.profile, self.config.profile_iterations
        )
        record: Dict[str, float] = {}
        # Resume continuity: the log_interval cadence is phased on the
        # GLOBAL rollout index, so a resumed run logs the same rollouts
        # an uninterrupted one would.
        iteration = self.completed_rollouts
        metrics = None
        done_budget = False
        try:
            stage_end = 0
            for stage_idx, stage in enumerate(self.curriculum.stages):
                if done_budget:
                    break
                stage_start = stage_end
                stage_end = stage_start + stage.rollouts
                if self.completed_rollouts >= stage_end:
                    continue  # resumed past this stage — don't replay it
                if (
                    self.config.total_timesteps is not None
                    and self.num_timesteps >= self.config.total_timesteps
                ):
                    # Budget bound BEFORE the stage reset: starting the
                    # stage just to stop would burn a key split and an env
                    # resample, so the final checkpoint would hold
                    # post-reset state — and a resume (completed_rollouts
                    # == stage_start) would re-run start_stage from that
                    # key and silently diverge from an uninterrupted run.
                    break
                if (
                    self.completed_rollouts == stage_start
                    or self.env_state is None
                ):
                    self.start_stage(stage)
                # else: resumed MID-stage — env/counters restored by
                # _try_resume; re-running start_stage would resample the
                # stage and break bit-exact continuation.
                for _ in range(stage_end - self.completed_rollouts):
                    if (
                        self.config.total_timesteps is not None
                        and self.num_timesteps
                        >= self.config.total_timesteps
                    ):
                        done_budget = True
                        break
                    tracer.before_dispatch()
                    metrics = self.run_iteration()
                    tracer.after_dispatch(metrics)
                    iteration += 1
                    meter.tick(
                        self.ppo.n_steps
                        * self.config.num_formations
                        * self.num_seeds
                    )
                    if iteration % self.config.log_interval == 0:
                        host = jax.device_get(metrics)  # one batched pull
                        record_health_flags(host)  # drain-seam counter
                        record = self._aggregate(host)
                        record["env_steps_per_sec"] = meter.rate()
                        record["curriculum_stage"] = float(stage_idx)
                        logger.log(record, self.num_timesteps)
                    if (
                        self.config.checkpoint
                        and self._vec_steps_since_save
                        >= self.config.save_freq
                    ):
                        self.save()
            if metrics is not None and self.config.checkpoint:
                # Rank on the final iteration's rewards, matching the
                # final checkpoints (the SweepTrainer rule).
                final = jax.device_get(metrics)
                self.save()
                self._write_summary(np.asarray(final["reward"]))
        finally:
            tracer.close()
            logger.close()
        return record

    # ------------------------------------------------------------------
    # Anakin mode (fused_chunk > 0): fused-scan chunks clipped at stage
    # boundaries, double-buffered drain, async population checkpoints.
    # ------------------------------------------------------------------

    def _train_fused(self) -> Dict[str, float]:
        """Fused-scan curriculum driver. The stage walk is the host
        loop's — stage resets stay host-driven — but within a stage the
        iterations dispatch as fused chunks of ``min(fused_chunk,
        rollouts left in the stage)``: chunk N+1 (or the next stage's
        first chunk) is dispatched BEFORE chunk N's stacked telemetry
        drains, and population checkpoints write on the background
        writer off a device-side snapshot at chunk boundaries. An
        explicit ``total_timesteps`` cap quantizes to the chunk (checked
        between dispatches — the member == single-run equivalence
        already only holds for non-binding caps, see
        ``total_timesteps``)."""
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        meter = Throughput()
        writer = AsyncCheckpointWriter() if self.config.checkpoint else None
        tracer = profiling.TraceWindow(
            self.log_dir, self.config.profile, self.config.profile_iterations
        )
        record: Dict[str, float] = {}
        final_rewards = None
        pending = None  # the chunk in flight, drained one dispatch later
        done_budget = False
        try:
            stage_end = 0
            for stage_idx, stage in enumerate(self.curriculum.stages):
                if done_budget:
                    break
                stage_start = stage_end
                stage_end = stage_start + stage.rollouts
                if self.completed_rollouts >= stage_end:
                    continue  # resumed past this stage — don't replay it
                if (
                    self.config.total_timesteps is not None
                    and self.num_timesteps >= self.config.total_timesteps
                ):
                    # Budget bound before the stage reset (the host-loop
                    # rule): never burn a key split on a stage that will
                    # not train — the boundary checkpoint must hold the
                    # PRE-reset key so resume replays start_stage exactly
                    # once, identically to an uninterrupted run.
                    break
                if (
                    self.completed_rollouts == stage_start
                    or self.env_state is None
                ):
                    self.start_stage(stage)
                # else: resumed MID-stage — continue without resampling
                # (the host-loop rule); the next chunks re-clip to the
                # stage remainder, so resume re-enters bit-exactly.
                while self.completed_rollouts < stage_end:
                    if (
                        self.config.total_timesteps is not None
                        and self.num_timesteps
                        >= self.config.total_timesteps
                    ):
                        done_budget = True
                        break
                    r = min(
                        self._fused_chunk,
                        stage_end - self.completed_rollouts,
                    )
                    first_iteration = self.completed_rollouts
                    steps_before = self.num_timesteps_members.copy()
                    active = self._active_agents.copy()
                    tracer.before_dispatch()
                    stacked = self.run_chunk(r)
                    tracer.after_dispatch(stacked)
                    if pending is not None:
                        rec, final_rewards = self._drain_chunk(
                            logger, meter, *pending
                        )
                        record = rec or record
                    pending = (
                        stacked, r, first_iteration, steps_before,
                        active, stage_idx,
                    )
                    if (
                        writer is not None
                        and self._vec_steps_since_save
                        >= self.config.save_freq
                    ):
                        self.save_async(writer)
            if pending is not None:
                rec, final_rewards = self._drain_chunk(
                    logger, meter, *pending
                )
                record = rec or record
            if self.config.checkpoint:
                if writer is not None:
                    self.save_async(writer)
                    writer.close()  # final write durable before the summary
                    writer = None
                if final_rewards is not None:
                    self._write_summary(final_rewards)
        finally:
            tracer.close()
            if writer is not None:
                writer.close_quietly()
            logger.close()
        return record

    def _drain_chunk(self, logger, meter, stacked, r, first_iteration,
                     steps_before, active, stage_idx):
        """ONE batched ``device_get`` for a chunk's population telemetry;
        emit per-iteration aggregate records at the host loop's step
        stamps (reconstructed from the per-member counters BEFORE the
        chunk plus the stage's frozen active-agent counts). Returns
        ``(last_emitted_record, final_iteration_rewards)``."""
        host = jax.device_get(stacked)
        profiling.sample_device_watermark()  # drain boundary (ledger)
        # Drain-seam health pin (train/recovery.py): per-member skips
        # land in train_skipped_updates_total with the same batched
        # device_get the telemetry already paid for.
        record_health_flags(host)
        meter.tick(
            r * self.ppo.n_steps * self.config.num_formations
            * self.num_seeds
        )
        record: Dict[str, float] = {}
        for i in range(r):
            if (first_iteration + i + 1) % self.config.log_interval:
                continue
            rec = self._aggregate(
                {name: v[i] for name, v in host.items()}
            )
            rec["env_steps_per_sec"] = meter.rate()
            rec["curriculum_stage"] = float(stage_idx)
            step = int(
                (steps_before + (i + 1) * self.ppo.n_steps * active).max()
            )
            logger.log(rec, step)
            record = rec
        return record, np.asarray(host["reward"][-1])

    def _aggregate(self, host: Dict[str, np.ndarray]) -> Dict[str, float]:
        return population_aggregate(host, self.config.seed)

    def _device_target(self) -> Dict[str, Any]:
        return {
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "key": self.key,
            "env_state": self.env_state,
            "obs": self.obs,
        }

    def _write_population_files(
        self, tree: Dict[str, Any], members: np.ndarray, rollouts: int
    ) -> None:
        """Write one LOGICAL population checkpoint: per-member
        ``seed{i}/rl_model_*`` files (standard single-run tooling plays
        them back / fine-tunes them) plus the ``sweep_state`` resume
        anchor. ``tree`` is a host pull or a ``device_snapshot`` (the
        async writer thread drains either in one batched ``device_get``);
        ``members``/``rollouts`` are the progress counters captured when
        the checkpoint was requested. The anchor writes LAST so discovery
        never finds an anchor whose member files are missing."""
        host = jax.device_get(tree)
        for i in range(self.num_seeds):
            # np.array: owning copies, not views keeping the full
            # population tree alive (the SweepTrainer.member_state rule).
            take = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: np.array(x[i]), t
            )
            state = {
                "policy": self.model.__class__.__name__,
                "params": take(host["params"]),
                "opt_state": take(host["opt_state"]),
                "key": np.array(host["key"][i]),
                "num_timesteps": int(members[i]),
                "completed_rollouts": int(rollouts),
            }
            _write_atomic(
                checkpoint_path(
                    Path(self.log_dir) / f"seed{i}", int(members[i])
                ),
                state,
            )
        # ONE population-state file so an interrupted block RESUMES
        # (resume=true) mid-curriculum instead of retraining from
        # scratch — the identity fields are validated on restore.
        save_sweep_state(
            self.log_dir,
            int(members.max(initial=0)),
            {
                "policy": self.model.__class__.__name__,
                "num_seeds": self.num_seeds,
                "seed": int(self.config.seed),
                "num_formations": int(self.config.num_formations),
                "curriculum_spec": self._curriculum_spec(),
                "num_timesteps_members": np.asarray(members),
                "completed_rollouts": int(rollouts),
                **{
                    k: host[k]
                    for k in ("params", "opt_state", "key",
                              "env_state", "obs")
                },
            },
        )

    def save(self) -> None:
        """Synchronous population checkpoint: one batched device pull
        serves every member (the trainer-wide rule: sync once, slice on
        host), then per-member files + the sweep_state anchor."""
        self._write_population_files(
            jax.device_get(self._device_target()),
            self.num_timesteps_members.copy(),
            self.completed_rollouts,
        )
        self._vec_steps_since_save = 0

    def save_async(self, writer: AsyncCheckpointWriter) -> None:
        """Chunk-boundary population checkpoint off a device-side
        snapshot (``utils.device_snapshot``): the writer thread drains
        and writes while the device runs the next chunk; the progress
        counters are captured NOW, so the files record the state the
        snapshot actually holds."""
        writer.submit_write(
            functools.partial(
                self._write_population_files,
                device_snapshot(self._device_target()),
                self.num_timesteps_members.copy(),
                self.completed_rollouts,
            )
        )
        self._vec_steps_since_save = 0

    def _state_template(self):
        """Host-side zero template with the population shapes — env/obs
        shapes come from ``jax.eval_shape`` over the SAME stage-reset
        function ``start_stage`` runs (no PRNG is consumed, no device
        compute runs)."""
        _, env_shape, obs_shape = jax.eval_shape(
            jax.vmap(self._member_stage_fn(self.curriculum.stages[0])),
            self.key,
        )
        zeros = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: np.zeros(x.shape, x.dtype), t
        )
        return {
            "params": zeros(self.train_state.params),
            "opt_state": zeros(self.train_state.opt_state),
            "key": zeros(self.key),
            "env_state": zeros(env_shape),
            "obs": zeros(obs_shape),
        }

    def _curriculum_spec(self) -> str:
        """Canonical string of the full stage structure for the resume
        identity check (msgpack-friendly; compared verbatim)."""
        return repr(
            [
                (s.rollouts, tuple(s.agent_counts),
                 None if s.probs is None else tuple(s.probs),
                 s.num_obstacles)
                for s in self.curriculum.stages
            ]
        )

    def _try_resume(self) -> None:
        """Restore the latest ``sweep_state_*`` population checkpoint:
        params, batched optimizer state, member PRNG streams, env state,
        per-member transition counters, and the curriculum cursor — the
        resumed run continues bit-identically to an uninterrupted one
        (pinned by tests/test_hetero_sweep.py)."""
        from flax import serialization

        path = latest_sweep_state(self.log_dir)
        if path is None:
            print(
                "[hetero-sweep] resume=true but no sweep_state_* "
                f"population checkpoint under {self.log_dir}; starting "
                "fresh"
            )
            return
        from marl_distributedformation_tpu.utils.checkpoint import (
            msgpack_restore_file,
        )

        raw = msgpack_restore_file(path)
        ident = {
            "policy": self.model.__class__.__name__,
            "num_seeds": self.num_seeds,
            "seed": int(self.config.seed),
            "num_formations": int(self.config.num_formations),
            # The FULL stage structure, not just the rollout total — a
            # reshuffled curriculum with the same total would otherwise
            # resume onto wrong stage boundaries.
            "curriculum_spec": self._curriculum_spec(),
        }
        for field, want in ident.items():
            got = raw.get(field)
            if got != want and str(got) != str(want):
                raise SystemExit(
                    f"hetero-sweep resume mismatch: {path} was written "
                    f"with {field}={got!r} but this run uses {want!r} — "
                    "candidate identities would silently change"
                )
        template = self._state_template()
        for name in (*template, "num_timesteps_members",
                     "completed_rollouts"):
            if name not in raw:
                raise SystemExit(
                    f"hetero-sweep resume: {path} is missing {name!r} — "
                    "truncated or foreign file"
                )
        restored = {
            name: serialization.from_state_dict(tmpl, raw[name])
            for name, tmpl in template.items()
        }
        # Owning copies BEFORE the donating dispatch sees this state
        # (utils.own_restored: msgpack leaves can alias the checkpoint
        # bytes; donation of an aliased buffer is a use-after-free on
        # the zero-copy CPU backend).
        restored = own_restored(restored)
        self.train_state = self.train_state.replace(
            params=restored["params"], opt_state=restored["opt_state"]
        )
        self.key = jnp.asarray(restored["key"])
        self.env_state = restored["env_state"]
        self.obs = jnp.asarray(restored["obs"])
        # np.array (owning copy): msgpack_restore hands back read-only
        # buffers, and this counter is incremented in place per rollout.
        self.num_timesteps_members = np.array(
            raw["num_timesteps_members"], np.int64
        )
        self.completed_rollouts = int(raw["completed_rollouts"])
        self._place_on_mesh()
        self._refresh_active_agents()
        # Drop metrics rows the resumed run will re-log (the logger
        # appends; rollouts past the restored checkpoint were recorded
        # by the interrupted attempt and are about to replay) — the
        # banked curve must carry each rollout once.
        mpath = Path(self.log_dir) / "metrics.jsonl"
        if mpath.exists():
            import json

            kept = [
                ln
                for ln in mpath.read_text().splitlines()
                if ln.strip()
                and json.loads(ln).get("step", 0) <= self.num_timesteps
            ]
            mpath.write_text("".join(ln + "\n" for ln in kept))
        print(
            f"[hetero-sweep] resumed {self.num_seeds}-candidate block "
            f"from {path} at rollout {self.completed_rollouts}/"
            f"{self.curriculum.total_rollouts}"
        )

    def _write_summary(self, rewards: np.ndarray) -> None:
        write_sweep_summary(
            self.log_dir,
            self.config.seed,
            self.num_seeds,
            rewards,
            {"curriculum_rollouts": self.curriculum.total_rollouts},
        )
