"""Population training: K independent PPO runs in ONE jitted program.

The reference's stack trains one policy per process — a seed sweep is K
sequential SB3 invocations (reference vectorized_env.py:112-137 has no
sweep story at all). Here the whole training iteration
(``make_ppo_iteration``) is ``vmap``-ed over a leading seed axis: policy
params, optimizer state, env state, and PRNG streams all carry a ``(K,
...)`` population dimension, and XLA compiles one program that advances
every member per dispatch.

TPU mapping: population members are fully independent, so sharding the
seed axis over the mesh (``mesh={dp: D}``) is embarrassingly parallel —
XLA inserts ZERO collectives and each chip trains ``K/D`` members. This
turns one chip's tuned 4096-formation throughput into a multi-chip
hyperparameter/seed search with perfect scaling, which is the idiomatic
TPU answer to "train many policies": no multiprocessing, no per-process
checkpoints to reconcile, one metrics stream. Multi-host (round 4):
every process initializes only its own member block (per-host
construction, the ``parallel.global_from_local`` pattern), the training
step runs SPMD over the global mesh, and checkpoint IO allgathers the
population to the coordinator — pinned by a real two-process test
(tests/test_multiprocess.py).

Seed semantics: member ``i`` uses root key ``PRNGKey(config.seed + i)``
— bit-identical to a single :class:`Trainer` constructed with
``seed=config.seed + i`` (pinned by ``tests/test_sweep.py``), so a sweep
is exactly K reference-parity runs, just fused.

Hyperparameter search: pass ``learning_rates`` (length K) to give every
member its own learning rate in the same single program. The optimizer is
wrapped in ``optax.inject_hyperparams`` so the rate lives in the
OPTIMIZER STATE (an array leaf the vmap batches) rather than the
transform closure — one shared ``tx`` serves the whole population. These
members' checkpoints carry params only (their opt_state tree differs from
the single-run optimizer's; the resume path re-estimates Adam moments,
same as SB3-imported checkpoints).

Resume: ``resume=true`` restores the latest ``sweep_state_{steps}_steps``
population checkpoint — the full batched learner state (params, optimizer
moments AND injected per-member rates), member PRNG streams, env state,
and progress — and continues bit-identically to an uninterrupted run
(pinned by ``tests/test_sweep.py``). Operationally critical where chip
time comes in bounded calls and a run can be cut off mid-way.

Anakin population mode (round 6): ``fused_chunk=K`` compiles K whole
vmapped population iterations into ONE ``lax.scan`` program (the
single-run trainer's fused-scan shape, docs/training.md), so the host
dispatch overhead that used to be paid per population iteration is paid
once per chunk. Per-member metrics come back stacked
``(fused_chunk, num_seeds, ...)`` and drain in one batched ``device_get``
per chunk, double-buffered against the next chunk's execution;
population checkpoints (every member file + the sweep_state anchor)
write on a background thread off a device-side snapshot, at chunk
boundaries — chunk boundary == checkpoint boundary == bit-exact resume
boundary (pinned by ``tests/test_fused_sweep.py``).
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.envs import spec_for_params
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.train.recovery import record_health_flags
from marl_distributedformation_tpu.train.trainer import (
    TrainConfig,
    default_total_timesteps,
    fill_ent_schedule,
    make_fused_chunk,
    make_ppo_iteration,
)
from marl_distributedformation_tpu.utils import (
    AsyncCheckpointWriter,
    MetricsLogger,
    Throughput,
    device_snapshot,
    latest_checkpoint,
    latest_sweep_state,
    own_restored,
    repo_root,
    save_checkpoint,
    save_sweep_state,
)
from marl_distributedformation_tpu.utils import profiling
from marl_distributedformation_tpu.utils.checkpoint import (
    _write_atomic,
    checkpoint_path,
    sweep_state_path,
)

Array = jax.Array


class SweepTrainer:
    """K-seed population PPO under one jit.

    Args:
      env_params / ppo / config: as :class:`Trainer`; every member trains
        the full ``total_timesteps`` budget at identical hyperparameters.
      num_seeds: population size K.
      model: policy module shared across members (fresh params per member).
      mesh: optional ``jax.sharding.Mesh`` whose ``'dp'`` axis shards the
        seed axis (K must divide by it). Members never communicate, so
        this composes with any mesh the single-run trainer accepts.
      learning_rates: optional length-K array — per-member learning rates
        (population hyperparameter search). None keeps every member at
        ``ppo.learning_rate`` with the exact single-run optimizer.
    """

    def __init__(
        self,
        env_params: EnvParams,
        ppo: PPOConfig = PPOConfig(),
        config: TrainConfig = TrainConfig(),
        num_seeds: int = 4,
        model: Any = None,
        mesh: Any = None,
        learning_rates: Any = None,
    ) -> None:
        assert num_seeds >= 1
        self._fused_chunk = max(0, int(config.fused_chunk))
        self._multihost = jax.process_count() > 1
        if self._fused_chunk and self._multihost:
            raise SystemExit(
                "fused-scan sweeps are single-host for now (the async "
                "population checkpoint writer allgathers off-thread, "
                "which has no cross-host durability barrier); drop "
                "fused_chunk or run single-process"
            )
        if self._multihost:
            # Multi-host sweeps: every process initializes ONLY its own
            # members (per-host construction, parallel/distributed.py
            # style), the seed axis is globally 'dp'-sharded, and
            # checkpoint IO allgathers to the coordinator. Requires a
            # mesh spanning every global device.
            assert mesh is not None, (
                "multi-host sweeps need a global mesh (cfg mesh={dp: -1})"
            )
            assert num_seeds % jax.process_count() == 0, (
                f"num_seeds={num_seeds} must be divisible by "
                f"process_count={jax.process_count()} (even per-host "
                "member construction)"
            )
        # Every member runs the same per-member budget, so the single-run
        # horizon formula applies unchanged (bit-compat with Trainer).
        ppo = fill_ent_schedule(ppo, env_params, config)
        self.env_params = env_params
        # Env-generic dispatch (envs/): formation params resolve to the
        # legacy env/formation.py functions verbatim, so member i stays
        # bit-identical to Trainer(seed=config.seed + i) on the default env.
        self.env_spec = spec_for_params(env_params)
        self.ppo = ppo
        self.config = config
        self.num_seeds = num_seeds
        self.model = model or MLPActorCritic(
            act_dim=env_params.act_dim, log_std_init=ppo.log_std_init
        )
        self.per_formation = getattr(self.model, "per_formation", False)
        m = config.num_formations

        if self.per_formation:
            dummy_obs = jnp.zeros(
                (1, env_params.num_agents, env_params.obs_dim), jnp.float32
            )
        else:
            dummy_obs = jnp.zeros((1, env_params.obs_dim), jnp.float32)

        model_ref = self.model  # close over the module, not self
        env_spec = self.env_spec  # likewise — init_member is jit/vmapped

        self._lr_sweep = learning_rates is not None
        if self._lr_sweep:
            # float() each element: YAML 1.1 keeps dotless sci-notation
            # ("3e-4") as STRINGS, so the documented CLI syntax
            # learning_rates=[3e-4,1e-3] arrives as a list of str.
            lrs = jnp.asarray(
                [float(x) for x in np.ravel(learning_rates)], jnp.float32
            )
            assert lrs.shape == (num_seeds,), (
                f"learning_rates must have one entry per member: got "
                f"{lrs.shape[0]} for num_seeds={num_seeds}"
            )
            # One SHARED transform whose rate is optimizer-STATE, so the
            # vmap can batch it per member (a per-member closure would
            # need per-member tx callables, which TrainState can't carry).
            tx = ppo.make_optimizer(inject_lr=True)
        else:
            lrs = None
            tx = ppo.make_optimizer()

        def init_member(seed: Array, lr: Optional[Array] = None):
            # EXACTLY Trainer.__init__'s key discipline so member i ==
            # Trainer(seed=config.seed + i) bit-for-bit.
            key = jax.random.PRNGKey(seed)
            key, k_init, k_env = jax.random.split(key, 3)
            params = model_ref.init(k_init, dummy_obs)
            train_state = TrainState.create(
                apply_fn=model_ref.apply, params=params, tx=tx
            )
            if lr is not None:
                # inject_hyperparams keeps the rate in its state's
                # hyperparams dict; overwrite it with this member's value.
                clip_s, inject_s = train_state.opt_state
                assert hasattr(inject_s, "hyperparams"), (
                    "expected InjectHyperparamsState second in the chain"
                )
                inject_s = inject_s._replace(
                    hyperparams={
                        **inject_s.hyperparams, "learning_rate": lr
                    }
                )
                train_state = train_state.replace(
                    opt_state=(clip_s, inject_s)
                )
            env_state = env_spec.reset_batch(k_env, env_params, m)
            obs = env_spec.obs(env_state, env_params)
            return train_state, env_state, obs, key

        self._mesh = mesh
        if mesh is not None:
            # Validate the mesh BEFORE the population init: compiling the
            # vmapped init just to then fail an assert wastes ~10s.
            assert set(mesh.axis_names) == {"dp"}, (
                f"sweep meshes shard the SEED axis over 'dp' only; got "
                f"axes {tuple(mesh.axis_names)} — an 'sp' axis would "
                "replicate every member redundantly across it"
            )
            dp = int(mesh.shape["dp"])
            assert num_seeds % dp == 0, (
                f"num_seeds={num_seeds} must be divisible by the mesh dp "
                f"axis ({dp}) so every device holds the same member count"
            )

        seeds = config.seed + jnp.arange(num_seeds)
        init_args = (seeds,) if lrs is None else (seeds, lrs)
        if self._multihost:
            # Per-host construction: this process initializes ONLY its own
            # contiguous member block and the population is assembled as
            # globally 'dp'-sharded arrays (mirrors
            # parallel.reset_batch_sharded — required for correctness:
            # cross-process device_put of host-global arrays is
            # impossible). Checkpoint IO does transiently allgather the
            # population to every host (see _to_host).
            from marl_distributedformation_tpu.parallel import (
                global_from_local,
            )

            start, count = self._member_slice()
            local = jax.jit(jax.vmap(init_member))(
                *(a[start : start + count] for a in init_args)
            )
            (
                self.train_state,
                self.env_state,
                self.obs,
                self.key,
            ) = global_from_local(jax.device_get(local), mesh)
        else:
            (
                self.train_state,
                self.env_state,
                self.obs,
                self.key,
            ) = jax.jit(jax.vmap(init_member))(*init_args)
        self.learning_rates = lrs
        # Host copy for checkpoint/summary provenance — reading the device
        # array per member would pay a host sync each.
        self._lrs_host = None if lrs is None else np.asarray(lrs)
        self.num_timesteps = 0  # per-member agent-transitions (SB3 unit)
        self.log_dir = config.log_dir or str(
            repo_root() / "logs" / config.name
        )
        if config.resume:
            # Restore BEFORE mesh placement so the resumed population is
            # re-placed on the dp sharding exactly like a fresh one.
            self._try_resume()

        if mesh is not None and not self._multihost:
            # Multi-host state is already globally placed by
            # global_from_local (cross-host device_put is impossible).
            from jax.sharding import NamedSharding, PartitionSpec

            shard = NamedSharding(mesh, PartitionSpec("dp"))
            place = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jax.device_put(x, shard), t
            )
            self.train_state = place(self.train_state)
            self.env_state = place(self.env_state)
            self.obs = place(self.obs)
            self.key = place(self.key)

        iteration = make_ppo_iteration(
            env_params, ppo, self.per_formation, None
        )
        # In-program health word + skip-update guard (train/recovery.py):
        # wrapped BEFORE the vmap, so every member carries its OWN flags
        # and a diverged member skips its own updates while the rest of
        # the population trains on. Flags stack into the chunk metrics
        # like any other entry; the drain seam counts the skips.
        from marl_distributedformation_tpu.train.recovery import wrap_health

        iteration = wrap_health(iteration, config)
        iteration_pop = jax.vmap(iteration)
        if mesh is not None:
            # shard_map over the seed axis, not bare jit-under-mesh: each
            # device runs its K/D members entirely locally, so per-device
            # code (the Pallas knn kernels, which the SPMD partitioner
            # cannot split — see parallel.make_dp_step) keeps working, and
            # XLA provably inserts zero collectives. One partition spec
            # broadcasts over every pytree leaf (all carry the leading
            # seed axis).
            from jax.sharding import PartitionSpec

            spec = PartitionSpec("dp")
            iteration_pop = jax.shard_map(
                iteration_pop,
                mesh=mesh,
                in_specs=spec,
                out_specs=spec,
                # Collective-free program: the varying-across-mesh checker
                # buys nothing and trips on pallas outputs (see
                # parallel/mesh.py).
                check_vma=False,
            )
        if self._fused_chunk:
            # Anakin population mode: fused_chunk whole vmapped
            # iterations in ONE lax.scan — the (members,) axis rides
            # through the scan untouched, so per-member per-iteration
            # metrics come back stacked (fused_chunk, members, ...).
            iteration_pop = make_fused_chunk(iteration_pop, self._fused_chunk)
        # Compile-once receipt for the population program
        # (guard_retraces=1 enforces it).
        self.retrace_guard = profiling.RetraceGuard(
            "sweep_iteration", max_traces=config.guard_retraces or None
        )
        self._iteration = profiling.ledgered_jit(
            iteration_pop,
            self.retrace_guard,
            subsystem="sweep",
            program="sweep_iteration",
            donate_argnums=(0, 1),
        )
        self._vec_steps_since_save = 0
        self.num_envs = m * env_params.num_agents

    # ------------------------------------------------------------------

    def _member_slice(self):
        """``(start, count)`` of this process's contiguous member block —
        the seed-axis analog of ``parallel.local_formation_slice``."""
        n_proc = jax.process_count()
        count = self.num_seeds // n_proc
        return jax.process_index() * count, count

    def _to_host(self, tree):
        """Full host copy of a (possibly cross-host-sharded) tree: plain
        ``device_get`` single-controller, allgather multi-host (the
        coordinator needs every member for checkpoints/summaries;
        multihost_utils has no coordinator-only gather, so every host
        transiently holds the full population — fine at this env's state
        sizes: K members x M formations of 2-D agent positions is MBs,
        not the multi-GB regime where a p2p path would be warranted)."""
        if not self._multihost:
            return jax.device_get(tree)
        from jax.experimental import multihost_utils

        return jax.tree_util.tree_map(
            np.asarray, multihost_utils.process_allgather(tree, tiled=True)
        )

    @property
    def total_timesteps(self) -> int:
        return default_total_timesteps(self.config)

    def _dispatch(self, rollouts: int):
        """Dispatch the jitted population program once (``rollouts``
        iterations for every member) and advance the host counters."""
        (
            self.train_state,
            self.env_state,
            self.obs,
            self.key,
            metrics,
        ) = self._iteration(
            self.train_state, self.env_state, self.obs, self.key
        )
        self.num_timesteps += rollouts * self.ppo.n_steps * self.num_envs
        self._vec_steps_since_save += rollouts * self.ppo.n_steps
        return metrics

    def run_iteration(self) -> Dict[str, Array]:
        """One vectorized iteration; metrics values carry a leading (K,)
        seed axis."""
        assert not self._fused_chunk, (
            "fused_chunk sweeps dispatch via run_chunk() (stacked "
            "per-iteration metrics), not run_iteration()"
        )
        return self._dispatch(1)

    def run_chunk(self) -> Dict[str, Array]:
        """Anakin population mode: dispatch ONE fused-scan chunk
        (``fused_chunk`` vmapped iterations) and return the metrics stack
        as DEVICE arrays with leading ``(fused_chunk, num_seeds)`` axes.
        Returns as soon as the program is enqueued — ``_train_fused``
        overlaps the previous chunk's drain with this one's execution."""
        assert self._fused_chunk > 0, (
            "run_chunk() needs fused_chunk > 0 (Anakin mode)"
        )
        return self._dispatch(self._fused_chunk)

    def _host_population(self) -> Dict[str, Any]:
        """ONE batched device pull of everything checkpoints need —
        per-leaf-per-member transfers would pay K x leaves host syncs (the trainer-wide rule: sync once, slice on host).
        Both the per-member checkpoints and the population sweep_state
        file are built from this single pull."""
        return self._to_host(
            {
                "params": self.train_state.params,
                "opt_state": self.train_state.opt_state,
                "key": self.key,
                "env_state": self.env_state,
                "obs": self.obs,
            }
        )

    def member_state(
        self,
        i: int,
        host: Optional[Dict[str, Any]] = None,
        steps: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Slice member ``i``'s full learner state out of the population —
        a standard (Trainer-compatible) checkpoint target. Pass ``host``
        (from ``_host_population``) when saving many members so the
        device pull happens once; ``steps`` pins the recorded progress
        (the async writer captures it at submit time — the live counter
        has moved on by the time the writer thread runs)."""
        if host is None:
            host = self._host_population()
        # np.array (not asarray): slices of the shared host pull must be
        # OWNING copies, or every member's checkpoint dict aliases (and
        # keeps alive) the full K-member tree.
        take = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: np.array(x[i]), t
        )
        state = {
            "policy": self.model.__class__.__name__,
            "params": take(host["params"]),
            "key": np.array(host["key"][i]),
            "num_timesteps": (
                self.num_timesteps if steps is None else int(steps)
            ),
            # Provenance the single-run resume path checks: fine-tuning a
            # member at a different rate than it trained with warns loudly.
            "learning_rate": float(
                self._lrs_host[i]
                if self._lrs_host is not None
                else self.ppo.learning_rate
            ),
        }
        if not self._lr_sweep:
            # lr-sweep members use the inject_hyperparams state tree, which
            # the single-run optimizer can't restore into — omit it from
            # MEMBER checkpoints (the tolerant resume path re-estimates
            # Adam moments, same as SB3-imported checkpoints). The
            # population sweep_state file keeps the full tree either way.
            state["opt_state"] = take(host["opt_state"])
        return state

    def save(self) -> None:
        """Per-member checkpoints under ``{log_dir}/seed{i}/`` — each one
        plays back / resumes through the standard single-run tooling
        (``visualize_policy.py name={name}/seed{i}``) — plus ONE
        population-state file (``sweep_state_{steps}_steps.msgpack``)
        carrying the full batched learner + env state, so an interrupted
        sweep resumes exactly (``resume=true``) instead of restarting."""
        from marl_distributedformation_tpu.parallel import is_coordinator

        host = self._host_population()
        on_coord = is_coordinator()
        for i in range(self.num_seeds):
            # Non-coordinators skip both the member-state slicing (K
            # owning copies nobody would write) and the per-file barrier;
            # the single synced sweep_state write below is the durability
            # point for the whole logical checkpoint.
            save_checkpoint(
                Path(self.log_dir) / f"seed{i}",
                self.num_timesteps,
                self.member_state(i, host) if on_coord else None,
                sync=False,
            )
        save_sweep_state(
            self.log_dir, self.num_timesteps, self._population_target(host)
        )
        self._vec_steps_since_save = 0

    def _population_target(
        self, host: Dict[str, Any], steps: Optional[int] = None
    ) -> Dict[str, Any]:
        """The full resume anchor: everything ``run_iteration`` threads,
        batched over the (K,) seed axis — including the lr-sweep's
        ``inject_hyperparams`` state, which member checkpoints must omit
        (their tree differs from the single-run optimizer's) — plus the
        identity fields resume validates against. Built from the
        ``_host_population`` pull so a save costs ONE device round trip."""
        target: Dict[str, Any] = {
            "policy": self.model.__class__.__name__,
            "num_seeds": self.num_seeds,
            "seed": int(self.config.seed),
            "num_formations": int(self.config.num_formations),
            "num_timesteps": (
                self.num_timesteps if steps is None else int(steps)
            ),
            **host,
        }
        if self._lrs_host is not None:
            target["learning_rates"] = self._lrs_host
        return target

    def _write_population_files(self, tree: Dict[str, Any], steps: int):
        """Write one LOGICAL population checkpoint — every member's
        ``rl_model_{steps}_steps`` file plus the ``sweep_state`` resume
        anchor — from ``tree`` (a host pull, or a ``device_snapshot`` when
        called on the async writer thread; ``device_get`` drains either in
        one batched transfer). Single-controller only: the async path
        fail-fasts multi-host in ``__init__``, so no durability barrier
        is needed here. The sweep_state anchor is written LAST — if the
        process dies mid-logical-checkpoint, resume discovery never sees
        an anchor whose member files are missing."""
        host = jax.device_get(tree)
        for i in range(self.num_seeds):
            _write_atomic(
                checkpoint_path(Path(self.log_dir) / f"seed{i}", steps),
                self.member_state(i, host, steps),
            )
        _write_atomic(
            sweep_state_path(self.log_dir, steps),
            self._population_target(host, steps),
        )

    def save_async(self, writer: AsyncCheckpointWriter) -> None:
        """Chunk-boundary population checkpoint that never stalls the
        dispatch lane: snapshot the full sweep state ON DEVICE
        (``utils.device_snapshot`` — the copies are enqueued behind the
        chunk that produced the state, so the next chunk's donation
        cannot invalidate them), then hand the snapshot to the writer
        thread, which drains and writes every member file + the
        sweep_state anchor while the device keeps training. Chunk
        boundary == checkpoint boundary == bit-exact resume boundary."""
        assert not self._multihost
        snapshot = device_snapshot(
            {
                "params": self.train_state.params,
                "opt_state": self.train_state.opt_state,
                "key": self.key,
                "env_state": self.env_state,
                "obs": self.obs,
            }
        )
        writer.submit_write(
            functools.partial(
                self._write_population_files, snapshot, self.num_timesteps
            )
        )
        self._vec_steps_since_save = 0

    def _try_resume(self) -> None:
        """Restore the latest ``sweep_state_*`` population checkpoint into
        the freshly-initialized state. The restored run continues
        bit-identically to an uninterrupted one (pinned by
        tests/test_sweep.py): params, the batched optimizer state
        (moments + per-member injected rates), member PRNG streams, env
        state, and the step counter all come from the file."""
        if self._multihost:
            self._try_resume_multihost()
            return
        path = latest_sweep_state(self.log_dir)
        if path is None:
            self._note_no_population_file()
            return
        restored, steps, stored_lrs = self._read_population_file(path)
        # Owning copies BEFORE the donating dispatch sees this state:
        # msgpack_restore leaves can view the checkpoint's byte buffer,
        # and donating an aliased buffer is a use-after-free on the
        # zero-copy CPU backend (utils.own_restored).
        restored = own_restored(restored)
        self._adopt_checkpoint_lrs(stored_lrs)
        self.train_state = self.train_state.replace(
            params=restored["params"], opt_state=restored["opt_state"]
        )
        self.key = jnp.asarray(restored["key"])
        self.env_state = restored["env_state"]
        self.obs = jnp.asarray(restored["obs"])
        self.num_timesteps = steps
        print(
            f"[sweep] resumed {self.num_seeds}-member population from "
            f"{path} at {self.num_timesteps} steps"
        )

    def _note_no_population_file(self) -> None:
        if latest_checkpoint(Path(self.log_dir) / "seed0") is not None:
            print(
                "[sweep] resume=true but no sweep_state_* population "
                f"checkpoint under {self.log_dir} (member checkpoints "
                "predate sweep resume or were written by an old "
                "version); starting fresh — resume individual members "
                "via their seed{i}/ dirs instead"
            )

    def _host_template(self) -> Dict[str, Any]:
        """Host-zero template with the GLOBAL population shapes — usable
        on every process even when the live state is cross-host-sharded
        (shape/dtype are known without addressability)."""
        template = {
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "key": self.key,
            "env_state": self.env_state,
            "obs": self.obs,
        }
        return jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, x.dtype), template
        )

    def _read_population_file(self, path):
        """Parse + validate a sweep_state file; returns
        ``(restored_host_tree, num_timesteps, stored_lrs)``. Raises
        SystemExit on any identity/compatibility mismatch."""
        from flax import serialization

        from marl_distributedformation_tpu.utils.checkpoint import (
            msgpack_restore_file,
        )

        raw = msgpack_restore_file(path)
        ident = {
            "policy": self.model.__class__.__name__,
            "num_seeds": self.num_seeds,
            "seed": int(self.config.seed),
            # num_formations drifting silently would corrupt the timestep
            # accounting (num_envs uses the NEW config while the restored
            # env batch keeps the OLD M — batch dims are data-driven, so
            # nothing else would catch it).
            "num_formations": int(self.config.num_formations),
        }
        for field, want in ident.items():
            got = raw.get(field)
            if got != want and str(got) != str(want):
                raise SystemExit(
                    f"sweep resume mismatch: checkpoint {path} was written "
                    f"with {field}={got!r} but this run uses {want!r} — "
                    "member identities would silently change"
                )
        stored_lrs = raw.get("learning_rates")
        if (stored_lrs is None) != (self._lrs_host is None):
            raise SystemExit(
                f"sweep resume mismatch: checkpoint {path} was written "
                f"{'with' if stored_lrs is not None else 'without'} "
                "learning_rates but this run is the opposite — the "
                "optimizer state trees are incompatible; pass the same "
                "learning_rates the sweep was started with"
            )
        if stored_lrs is not None:
            stored_lrs = np.asarray(stored_lrs, np.float32)
        template = self._host_template()
        for name in (*template, "num_timesteps"):
            if name not in raw:
                raise SystemExit(
                    f"sweep resume: checkpoint {path} is missing {name!r} "
                    "— truncated or foreign file"
                )
        restored = {
            name: serialization.from_state_dict(tmpl, raw[name])
            for name, tmpl in template.items()
        }
        return restored, int(raw["num_timesteps"]), stored_lrs

    def _adopt_checkpoint_lrs(self, stored_lrs) -> None:
        if stored_lrs is None:
            return
        if not np.allclose(stored_lrs, self._lrs_host, rtol=1e-6):
            print(
                "[sweep] WARNING: checkpoint member learning rates "
                f"{stored_lrs.tolist()} differ from this run's "
                f"{self._lrs_host.tolist()} — continuing at the "
                "CHECKPOINT's rates (they live in the restored "
                "optimizer state)"
            )
        # Keep provenance truthful: member checkpoints record the rate
        # actually used, which is the restored one.
        self._lrs_host = stored_lrs
        self.learning_rates = jnp.asarray(stored_lrs)

    def _try_resume_multihost(self) -> None:
        """Multi-host population resume: the coordinator reads + validates
        the file, every host receives the identical host state, slices its
        own member block, and re-places it globally — mirroring
        ``utils.broadcast_restore``'s fail-fast protocol (on a coordinator
        error peers are released with found=0 BEFORE the error re-raises,
        so nobody blocks inside the broadcast)."""
        from jax.experimental import multihost_utils

        from marl_distributedformation_tpu.parallel import (
            global_from_local,
            is_coordinator,
        )

        template = self._host_template()
        restored, steps, found, err = template, 0, 0, None
        stored_lrs = (
            np.zeros_like(self._lrs_host)
            if self._lrs_host is not None else None
        )
        if is_coordinator():
            try:
                path = latest_sweep_state(self.log_dir)
                if path is None:
                    self._note_no_population_file()
                else:
                    restored, steps, stored_lrs = (
                        self._read_population_file(path)
                    )
                    found = 1
            except BaseException as e:  # noqa: BLE001 — incl. SystemExit;
                # converted to fail-fast after releasing the peers
                restored, err = template, e
        found = int(multihost_utils.broadcast_one_to_all(np.int32(found)))
        if err is not None:
            raise err
        if not found:
            return
        payload = [restored, np.int64(steps)]
        if stored_lrs is not None:
            payload.append(np.asarray(stored_lrs, np.float32))
        payload = multihost_utils.broadcast_one_to_all(payload)
        restored, steps = payload[0], int(payload[1])
        if stored_lrs is not None:
            self._adopt_checkpoint_lrs(np.asarray(payload[2]))
        start, count = self._member_slice()
        local = jax.tree_util.tree_map(
            lambda x: x[start : start + count], restored
        )
        placed = global_from_local(local, self._mesh)
        self.train_state = self.train_state.replace(
            params=placed["params"], opt_state=placed["opt_state"]
        )
        self.key = placed["key"]
        self.env_state = placed["env_state"]
        self.obs = placed["obs"]
        self.num_timesteps = steps
        print(
            f"[sweep] process {jax.process_index()} resumed "
            f"{self.num_seeds}-member population (broadcast) at "
            f"{self.num_timesteps} steps"
        )

    def train(self) -> Dict[str, float]:
        """Full sweep; logs population-aggregate metrics per rollout and
        writes per-member checkpoints + a ranking summary at the end.
        Returns the final aggregate record."""
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
        iteration = 0
        metrics = None
        try:
            while self.num_timesteps < self.total_timesteps:
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
                    host = self._to_host(metrics)  # one batched pull
                    record_health_flags(host)  # drain-seam skip counter
                    record = self._aggregate(host)
                    record["env_steps_per_sec"] = meter.rate()
                    logger.log(record, self.num_timesteps)
                if (
                    self.config.checkpoint
                    and self._vec_steps_since_save >= self.config.save_freq
                ):
                    self.save()
            if metrics is not None:
                # Rank on the FINAL iteration's rewards even when
                # log_interval didn't land on it — a stale ranking would
                # disagree with the final checkpoints it points at.
                final = self._to_host(metrics)
                record = self._aggregate(final)
                record["env_steps_per_sec"] = meter.rate()
                if self.config.checkpoint:
                    self.save()
                    self._write_summary(np.asarray(final["reward"]))
        finally:
            tracer.close()
            logger.close()
        return record

    # ------------------------------------------------------------------
    # Anakin population mode (fused_chunk > 0): whole-loop scan dispatch
    # for every member at once, double-buffered telemetry drain, async
    # population checkpoints (docs/training.md "Population fusion").
    # ------------------------------------------------------------------

    def _train_fused(self) -> Dict[str, float]:
        """Fused-scan population driver: dispatch chunk N+1 BEFORE
        draining chunk N's stacked ``(fused_chunk, num_seeds, ...)``
        telemetry (the device trains while the host aggregates and logs),
        and checkpoint the whole population at chunk boundaries on the
        background writer off a device-side snapshot. Emitted records are
        per-iteration population aggregates — identical cadence and step
        stamps to the host loop's."""
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
        k = self._fused_chunk
        iteration = 0
        pending = None  # the chunk in flight, drained one dispatch later
        try:
            while self.num_timesteps < self.total_timesteps:
                steps_before = self.num_timesteps
                tracer.before_dispatch()
                stacked = self.run_chunk()
                tracer.after_dispatch(stacked)
                if pending is not None:
                    rec, final_rewards = self._drain_chunk(
                        logger, meter, *pending
                    )
                    record = rec or record
                pending = (stacked, iteration, steps_before)
                iteration += k
                if (
                    writer is not None
                    and self._vec_steps_since_save >= self.config.save_freq
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
                    # Rank on the final iteration's rewards, matching the
                    # final checkpoints (the host-loop rule).
                    self._write_summary(final_rewards)
        finally:
            tracer.close()
            if writer is not None:
                # Unwinding on an error: drain the writer without letting
                # a secondary write failure mask the original exception.
                writer.close_quietly()
            logger.close()
        return record

    def _drain_chunk(self, logger, meter, stacked, first_iteration,
                     steps_before):
        """ONE batched ``device_get`` for a whole chunk's population
        telemetry, then emit per-iteration aggregate records exactly like
        the host loop would (``log_interval`` phased on the global
        iteration index). Called after the NEXT chunk has been
        dispatched, so this blocks on the finished chunk while the device
        already runs the new one. Returns ``(last_emitted_record,
        final_iteration_rewards)`` — the rewards feed the ranking
        summary."""
        host = jax.device_get(stacked)
        profiling.sample_device_watermark()  # drain boundary (ledger)
        # Drain-seam health pin (train/recovery.py): per-member skips
        # land in train_skipped_updates_total — the flags arrived in
        # the same batched device_get as the rest of the telemetry.
        record_health_flags(host)
        meter.tick(
            self._fused_chunk
            * self.ppo.n_steps
            * self.config.num_formations
            * self.num_seeds
        )
        per_iter = self.ppo.n_steps * self.num_envs
        record: Dict[str, float] = {}
        for i in range(self._fused_chunk):
            if (first_iteration + i + 1) % self.config.log_interval:
                continue
            rec = self._aggregate(
                {name: v[i] for name, v in host.items()}
            )
            rec["env_steps_per_sec"] = meter.rate()
            logger.log(rec, steps_before + (i + 1) * per_iter)
            record = rec
        return record, np.asarray(host["reward"][-1])

    def _aggregate(self, host: Dict[str, np.ndarray]) -> Dict[str, float]:
        return population_aggregate(host, self.config.seed)

    def _write_summary(self, rewards: Optional[np.ndarray]) -> None:
        from marl_distributedformation_tpu.parallel import is_coordinator

        if rewards is None or not is_coordinator():
            return
        extra = None
        if self._lrs_host is not None:
            extra = {
                "learning_rates": [float(lr) for lr in self._lrs_host]
            }
        write_sweep_summary(
            self.log_dir, self.config.seed, self.num_seeds, rewards, extra
        )


def population_aggregate(
    host: Dict[str, np.ndarray], seed0: int
) -> Dict[str, float]:
    """Population means under the CANONICAL metric names (the reference
    metric-name contract, utils/logging.py — so JSONL consumers and the
    stdout brief keep working), plus population spread fields. The
    single sweep metric contract — shared by ``SweepTrainer`` and
    ``HeteroSweepTrainer`` so the two cannot drift."""
    rewards = np.asarray(host["reward"])
    record = {k: float(np.mean(v)) for k, v in host.items()}
    record["reward_best"] = float(rewards.max())
    record["reward_worst"] = float(rewards.min())
    record["best_seed"] = int(seed0 + rewards.argmax())
    return record


def write_sweep_summary(
    log_dir,
    seed0: int,
    num_seeds: int,
    rewards: np.ndarray,
    extra: Optional[Dict[str, Any]] = None,
) -> None:
    """The ``sweep_summary.json`` artifact contract (consumed by
    evaluate.py's member ranking and visualize_policy.py's best-member
    descent) — shared by both population trainers."""
    summary = {
        "seeds": [int(seed0 + i) for i in range(num_seeds)],
        "final_reward": [float(r) for r in rewards],
        "best_seed": int(seed0 + rewards.argmax()),
        "best_dir": f"seed{int(rewards.argmax())}",
    }
    if extra:
        summary.update(extra)
    path = Path(log_dir) / "sweep_summary.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(summary, indent=2))
