"""Curriculum over formation size + obstacle count, and the hetero trainer.

BASELINE.json config 5: "Heterogeneous multi-formation (mixed 5/20-agent
groups) with obstacle field, curriculum over num_agents_per_formation". The
reference has no curriculum machinery — every run fixes one
``num_agents_per_formation`` for all formations forever
(reference ``vectorized_env.py:39-43``, ``cfg/config.yaml:4``).

TPU-first design: the padded heterogeneous env (env/hetero.py) keeps all
shapes static at ``(M, N_max, ...)`` while the *active* counts are data, so a
stage transition is just resampling two ``(M,)`` int32 arrays and resetting —
the jitted training iteration is compiled exactly once for the whole
curriculum. Contrast the reference, where changing ``num_agents_per_formation``
means rebuilding every simulator object and the SB3 model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.algo import (
    MinibatchData,
    PPOConfig,
    collect_rollout,
    compute_gae,
    ppo_update,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.hetero import (
    HeteroState,
    agent_mask,
    hetero_compute_obs,
    hetero_reset_batch,
    hetero_step_batch,
)
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.train.trainer import (
    TrainConfig,
    fill_ent_schedule,
)
from marl_distributedformation_tpu.utils import (
    MetricsLogger,
    Throughput,
    latest_checkpoint,
    repo_root,
    restore_checkpoint,
    save_checkpoint,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CurriculumStage:
    """One curriculum phase.

    ``agent_counts``/``probs`` define the per-formation size distribution —
    each formation slot independently draws its agent count for the whole
    stage. ``num_obstacles`` is the active obstacle count per formation
    (the obstacle *capacity* ``EnvParams.num_obstacles`` stays static).
    """

    rollouts: int
    agent_counts: Tuple[int, ...]
    probs: Optional[Tuple[float, ...]] = None
    num_obstacles: int = 0

    def __post_init__(self) -> None:
        assert self.rollouts > 0
        assert len(self.agent_counts) >= 1
        assert all(n >= 2 for n in self.agent_counts)
        if self.probs is not None:
            assert len(self.probs) == len(self.agent_counts)


@dataclasses.dataclass(frozen=True)
class Curriculum:
    """An ordered sequence of stages.

    The default mirrors the BASELINE.json config-5 storyline: learn plain
    5-agent formations, mix in 20-agent groups, then add an obstacle field.
    """

    stages: Tuple[CurriculumStage, ...] = (
        CurriculumStage(rollouts=40, agent_counts=(5,)),
        CurriculumStage(rollouts=40, agent_counts=(5, 20)),
        CurriculumStage(rollouts=20, agent_counts=(5, 20), num_obstacles=4),
    )

    @property
    def max_agents(self) -> int:
        return max(max(s.agent_counts) for s in self.stages)

    @property
    def max_obstacles(self) -> int:
        return max(s.num_obstacles for s in self.stages)

    @property
    def total_rollouts(self) -> int:
        return sum(s.rollouts for s in self.stages)


def sample_stage_counts(
    key: Array, stage: CurriculumStage, num_formations: int
) -> Tuple[Array, Array]:
    """Draw per-formation ``(n_agents, n_obstacles)`` for a stage."""
    counts = jnp.asarray(stage.agent_counts, jnp.int32)
    if stage.probs is None:
        idx = jax.random.randint(key, (num_formations,), 0, counts.shape[0])
    else:
        idx = jax.random.choice(
            key,
            counts.shape[0],
            (num_formations,),
            p=jnp.asarray(stage.probs, jnp.float32),
        )
    n_agents = counts[idx]
    n_obstacles = jnp.full((num_formations,), stage.num_obstacles, jnp.int32)
    return n_agents, n_obstacles


class HeteroTrainer:
    """PPO over padded heterogeneous formations with a stage curriculum.

    Same imperative-shell shape as ``train.Trainer`` (rollout + GAE + all
    minibatch epochs in ONE jitted program per iteration); differences:

    - env state is ``HeteroState`` with per-formation dynamic counts;
    - padded agents carry zero loss weight (``MinibatchData.weights``);
    - ``train()`` walks the curriculum, resampling counts and resetting the
      env at each stage boundary — no recompilation across stages;
    - timestep accounting counts *active* agent-transitions (the SB3
      ``num_timesteps`` analogue, SURVEY.md §2.2, scaled to the live mix).

    ``model`` may be agent-factored (the shared per-agent MLP — the
    reference's parameter-sharing trick, ``vectorized_env.py:32``) or
    per-formation (``CTDEActorCritic``): per-formation models receive the
    ``(M, N_max)`` agent-validity mask in every forward pass — rollout and
    update — so padded agents are excluded from the pooled critic, their
    values are 0, and their transitions carry zero loss weight.
    """

    def __init__(
        self,
        curriculum: Curriculum = Curriculum(),
        env_params: Optional[EnvParams] = None,
        ppo: PPOConfig = PPOConfig(),
        config: TrainConfig = TrainConfig(),
        model: Any = None,
        shard_fn: Any = None,
    ) -> None:
        self.curriculum = curriculum
        if env_params is None:
            env_params = EnvParams()
        self.env_params = env_params.replace(
            num_agents=max(curriculum.max_agents, env_params.num_agents),
            num_obstacles=max(
                curriculum.max_obstacles, env_params.num_obstacles
            ),
        )
        # The curriculum's budget is its stage plan: the entropy-decay
        # horizon is the total rollout count across stages.
        ppo = fill_ent_schedule(
            ppo, self.env_params, config,
            iterations=curriculum.total_rollouts,
        )
        self.ppo = ppo
        self.config = config
        if int(config.fused_chunk) > 0:
            # Stage boundaries are host-driven (count resampling + env
            # reset between stages); fusing iterations across them would
            # silently blur the curriculum, and fusing within a stage
            # would need stage-length-aware chunk sizing. Reject loudly
            # instead of silently running at cadence 1 — unlike scenario
            # schedules, curriculum stage data is not a traced input to
            # one compiled program.
            raise SystemExit(
                "fused_chunk does not compose with curriculum training "
                "(stage boundaries are host-driven); unset it or drop "
                "the curriculum"
            )

        self.model = model or MLPActorCritic(
            act_dim=self.env_params.act_dim, log_std_init=ppo.log_std_init
        )
        self.per_formation = getattr(self.model, "per_formation", False)
        key = jax.random.PRNGKey(config.seed)
        self.key, k_init = jax.random.split(key)
        if self.per_formation:
            dummy_obs = jnp.zeros(
                (1, self.env_params.num_agents, self.env_params.obs_dim),
                jnp.float32,
            )
        else:
            dummy_obs = jnp.zeros((1, self.env_params.obs_dim), jnp.float32)
        params = self.model.init(k_init, dummy_obs)
        self.train_state = TrainState.create(
            apply_fn=self.model.apply,
            params=params,
            tx=ppo.make_optimizer(),
        )

        self._shard_fn = shard_fn
        mesh = getattr(shard_fn, "mesh", None)
        if mesh is not None and "sp" in mesh.shape:
            raise ValueError(
                "curriculum/hetero training does not support agent-axis "
                "('sp') sharding: padded dynamic rings gather (i±1) mod n "
                "neighbors across the whole formation, which the ring "
                "halo-exchange layout cannot serve — use a dp-only mesh "
                "(mesh={dp: N})"
            )
        self.env_state: Optional[HeteroState] = None
        self.obs: Optional[Array] = None
        self.num_timesteps = 0
        self.completed_rollouts = 0  # global rollout index (for resume)
        self._vec_steps_since_save = 0
        self._active_agents = 0  # sum of n_agents across formations (host int)
        self._iteration = jax.jit(
            self._make_iteration(), donate_argnums=(0, 1)
        )
        self.log_dir = config.log_dir or str(
            repo_root() / "logs" / config.name
        )
        if config.resume:
            self._try_resume()

    # ------------------------------------------------------------------
    # Functional core
    # ------------------------------------------------------------------

    def _make_iteration(self):
        return make_hetero_iteration(
            self.env_params, self.ppo, self.per_formation
        )


    # ------------------------------------------------------------------
    # Imperative shell
    # ------------------------------------------------------------------

    @property
    def total_timesteps(self) -> int:
        """Training budget in active agent-transitions: the explicit
        ``TrainConfig.total_timesteps`` when set (an early-stop cap on top of
        the curriculum), else an upper bound over the whole curriculum (the
        exact count depends on the sampled mix; see ``num_timesteps``)."""
        if self.config.total_timesteps is not None:
            return self.config.total_timesteps
        return (
            self.curriculum.total_rollouts
            * self.ppo.n_steps
            * self.config.num_formations
            * self.env_params.num_agents
        )

    def start_stage(self, stage: CurriculumStage) -> None:
        """Resample the formation mix and reset every formation.

        Multi-host: the stage counts derive from the replicated ``self.key``
        so every host samples the identical mix, but each host materializes
        only its own formation slice of the padded state
        (``parallel.hetero_reset_batch_sharded``) — mirroring ``Trainer``'s
        multi-host construction (no full batch on any host, no cross-process
        ``device_put``).
        """
        self.key, k_counts, k_env = jax.random.split(self.key, 3)
        n_agents, n_obstacles = sample_stage_counts(
            k_counts, stage, self.config.num_formations
        )
        if jax.process_count() > 1:
            from marl_distributedformation_tpu.parallel import (
                hetero_reset_batch_sharded,
                replicate,
            )

            assert self._shard_fn is not None and getattr(
                self._shard_fn, "mesh", None
            ), "multi-host hetero training needs a mesh (cfg.mesh)"
            mesh = self._shard_fn.mesh
            self.env_state = hetero_reset_batch_sharded(
                k_env, self.env_params, n_agents, n_obstacles, mesh
            )
            self.obs = jax.jit(
                jax.vmap(hetero_compute_obs, in_axes=(0, None)),
                static_argnums=1,
            )(self.env_state, self.env_params)
            self.train_state = replicate(self.train_state, mesh)
        else:
            self.env_state = hetero_reset_batch(
                k_env, self.env_params, n_agents, n_obstacles
            )
            self.obs = jax.vmap(hetero_compute_obs, in_axes=(0, None))(
                self.env_state, self.env_params
            )
            if self._shard_fn is not None:
                # Every stage builds a fresh env state on the host; re-place
                # it (and keep params replicated) on the mesh. This also
                # covers resume, since start_stage precedes run_iteration.
                self.train_state, self.env_state, self.obs = self._shard_fn(
                    self.train_state, self.env_state, self.obs
                )
        self._active_agents = int(n_agents.sum())

    def run_iteration(self) -> Dict[str, Array]:
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
        self.num_timesteps += self.ppo.n_steps * self._active_agents
        self.completed_rollouts += 1
        self._vec_steps_since_save += self.ppo.n_steps
        return metrics

    def train(self) -> Dict[str, float]:
        """Run the full curriculum; returns the last emitted metrics."""
        logger = MetricsLogger(
            self.log_dir,
            run_name=self.config.name,
            use_wandb=self.config.use_wandb,
            use_tensorboard=self.config.use_tensorboard,
        )
        meter = Throughput()
        last_record: Dict[str, float] = {}
        iteration = 0
        done_budget = False
        try:
            for stage_idx, stage in enumerate(self.curriculum.stages):
                stage_end = (
                    sum(
                        s.rollouts
                        for s in self.curriculum.stages[: stage_idx + 1]
                    )
                )
                if self.completed_rollouts >= stage_end:
                    continue  # resumed past this stage — don't replay it
                self.start_stage(stage)
                remaining = stage_end - self.completed_rollouts
                for _ in range(remaining):
                    if (
                        self.config.total_timesteps is not None
                        and self.num_timesteps >= self.config.total_timesteps
                    ):
                        done_budget = True
                        break
                    metrics = self.run_iteration()
                    iteration += 1
                    meter.tick(
                        self.ppo.n_steps * self.config.num_formations
                    )
                    if iteration % self.config.log_interval == 0:
                        # Single batched device_get — per-metric float()
                        # pays one host sync per key (see Trainer.train).
                        host_metrics = jax.device_get(metrics)
                        last_record = {
                            k: float(v) for k, v in host_metrics.items()
                        }
                        last_record["env_steps_per_sec"] = meter.rate()
                        last_record["curriculum_stage"] = float(stage_idx)
                        logger.log(last_record, self.num_timesteps)
                    if (
                        self.config.checkpoint
                        and self._vec_steps_since_save
                        >= self.config.save_freq
                    ):
                        self.save()
                if done_budget:
                    break
            if self.config.checkpoint:
                self.save()
        finally:
            logger.close()
        return last_record

    # ------------------------------------------------------------------
    # Checkpointing (same write/read contract as train.Trainer)
    # ------------------------------------------------------------------

    def _checkpoint_target(self) -> Dict[str, Any]:
        return {
            "policy": self.model.__class__.__name__,
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "key": self.key,
            "num_timesteps": self.num_timesteps,
            "completed_rollouts": self.completed_rollouts,
        }

    def save(self) -> Optional[str]:
        """Coordinator returns the written path, other hosts None (see
        utils.save_checkpoint's multi-host contract)."""
        path = save_checkpoint(
            self.log_dir, self.num_timesteps, self._checkpoint_target()
        )
        self._vec_steps_since_save = 0
        return str(path) if path is not None else None

    def _try_resume(self) -> None:
        if jax.process_count() > 1:
            # Coordinator-only disk: broadcast the learner state so every
            # host agrees on params/counters (utils.broadcast_restore). The
            # "policy" name string can't ride the broadcast and is excluded.
            from marl_distributedformation_tpu.utils import broadcast_restore

            template = {
                k: v
                for k, v in self._checkpoint_target().items()
                if k != "policy"
            }
            restored = broadcast_restore(self.log_dir, template)
            if restored is None:
                return
            restored["key"] = jnp.asarray(restored["key"])
        else:
            path = latest_checkpoint(self.log_dir)
            if path is None:
                return
            restored = restore_checkpoint(path, self._checkpoint_target())
        self.train_state = self.train_state.replace(
            params=restored["params"], opt_state=restored["opt_state"]
        )
        self.key = restored["key"]
        self.num_timesteps = int(restored["num_timesteps"])
        self.completed_rollouts = int(restored["completed_rollouts"])
        # Mesh re-placement (multi-host replication included) happens in
        # start_stage via shard_fn before any iteration runs.
        print(
            f"[hetero] resumed at {self.num_timesteps} steps "
            f"({self.completed_rollouts} rollouts)"
        )


def make_hetero_iteration(env_params, ppo, per_formation: bool):
    """Build the functional hetero training iteration (rollout + GAE +
    update over padded dynamic-count formations) as one pure function —
    the heterogeneous analog of ``trainer.make_ppo_iteration``.
    Module-level so other shells can transform it: ``HeteroTrainer`` jits
    it directly; ``HeteroSweepTrainer`` (train/hetero_sweep.py) vmaps it
    over a candidate-seed population before jitting."""
    n_max = env_params.num_agents
    if per_formation:
        # Minibatch whole formations so the centralized critic sees every
        # agent; batch_size stays denominated in agent-transitions for
        # comparable SGD noise across policies (same as train.Trainer).
        update_ppo = dataclasses.replace(
            ppo, batch_size=max(1, ppo.batch_size // n_max)
        )
        row_shape = (n_max,)
    else:
        update_ppo = ppo
        row_shape = ()

    def env_step(state: HeteroState, velocity: Array):
        return hetero_step_batch(state, velocity, env_params)

    def iteration(
        train_state: TrainState,
        env_state: HeteroState,
        obs: Array,
        key: Array,
    ):
        key, k_roll, k_update = jax.random.split(key, 3)
        # n_agents is preserved across auto-resets, so one (M, N_max)
        # mask covers every step of the rollout (and the whole stage).
        mask = jax.vmap(agent_mask, in_axes=(0, None))(
            env_state.n_agents, n_max
        ).astype(jnp.float32)
        env_state, last_obs, batch, last_value = collect_rollout(
            train_state.apply_fn,
            train_state.params,
            env_state,
            obs,
            k_roll,
            env_params,
            ppo.n_steps,
            env_step_fn=env_step,
            mask=mask if per_formation else None,
        )
        advantages, returns = compute_gae(
            batch.rewards,
            batch.values,
            batch.dones,
            last_value,
            ppo.gamma,
            ppo.gae_lambda,
        )
        weights = jnp.broadcast_to(
            mask[None], (ppo.n_steps, *mask.shape)
        ).reshape(-1, *row_shape)
        flat = MinibatchData(
            obs=batch.obs.reshape(-1, *row_shape, env_params.obs_dim),
            actions=batch.actions.reshape(
                -1, *row_shape, env_params.act_dim
            ),
            old_log_probs=batch.log_probs.reshape(-1, *row_shape),
            advantages=advantages.reshape(-1, *row_shape),
            returns=returns.reshape(-1, *row_shape),
            weights=weights,
            mask=weights if per_formation else None,
        )
        train_state, update_metrics = ppo_update(
            train_state, flat, k_update, update_ppo
        )
        metrics = {k: v.mean() for k, v in batch.metrics.items()}
        metrics.update(update_metrics)
        w_flat = weights.reshape(-1)
        w = jnp.maximum(w_flat.sum(), 1.0)
        metrics["reward"] = (batch.rewards.reshape(-1) * w_flat).sum() / w
        # Formation-level episode count: batch.dones is the per-formation
        # done broadcast to all N_max agent rows (rollout.py), so a plain
        # sum counts every padded row, inflating the count x N_max.
        # Agent row 0 is always active (n >= 2).
        metrics["episode_dones"] = batch.dones[..., 0].sum()
        return train_state, env_state, last_obs, key, metrics

    return iteration


def curriculum_from_cfg(cfg: Any) -> Curriculum:
    """Build a ``Curriculum`` from the Hydra config's ``curriculum`` list
    (cfg/config.yaml) — each entry: ``{rollouts, agent_counts, probs?,
    num_obstacles?}``. A YAML string (the form a quoted CLI override or the
    documented example produces) is parsed first."""
    if isinstance(cfg, str):
        import yaml

        cfg = yaml.safe_load(cfg)
    stages = []
    for entry in cfg:
        stages.append(
            CurriculumStage(
                rollouts=int(entry["rollouts"]),
                agent_counts=tuple(int(n) for n in entry["agent_counts"]),
                probs=(
                    tuple(float(p) for p in entry["probs"])
                    if entry.get("probs") is not None
                    else None
                ),
                num_obstacles=int(entry.get("num_obstacles", 0)),
            )
        )
    return Curriculum(stages=tuple(stages))
