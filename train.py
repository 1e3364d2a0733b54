#!/usr/bin/env python
"""Training entry point — the reference's ``python vectorized_env.py name=x``
workflow (reference vectorized_env.py:112-137, README.md:18) on the
TPU-native backend.

Usage:
    python train.py name=myrun num_formation=4096 num_agents_per_formation=5

Any key in cfg/config.yaml can be overridden with ``key=value`` (hydra CLI
contract; hydra itself is optional — see utils/config.py).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.train import TrainConfig, Trainer
from marl_distributedformation_tpu.utils import (
    announce_device,
    device_residency,
    env_params_from_config,
    load_config,
    run_dir,
    scenario_schedule_from_config,
    setup_platform,
)
from marl_distributedformation_tpu.utils.profiling import host_span


def ppo_from_config(cfg) -> PPOConfig:
    return PPOConfig(
        n_steps=cfg.n_steps,
        learning_rate=cfg.learning_rate,
        ent_coef=cfg.ent_coef,
        gamma=cfg.gamma,
        gae_lambda=cfg.gae_lambda,
        clip_range=cfg.clip_range,
        clip_range_vf=cfg.get("clip_range_vf"),
        n_epochs=cfg.n_epochs,
        batch_size=cfg.batch_size,
        vf_coef=cfg.vf_coef,
        max_grad_norm=cfg.max_grad_norm,
        normalize_advantage=cfg.normalize_advantage,
        log_std_init=cfg.log_std_init,
        ent_coef_final=cfg.get("ent_coef_final"),
        log_std_final=cfg.get("log_std_final"),
        log_std_decay_start=float(cfg.get("log_std_decay_start") or 0.0),
    )


def train_config_from_config(cfg) -> TrainConfig:
    run_name = str(cfg.name)  # hydra parses numeric-looking names as ints
    return TrainConfig(
        num_formations=cfg.num_formation,
        total_timesteps=cfg.total_timesteps,
        seed=cfg.seed,
        save_freq=cfg.save_freq,
        name=run_name,
        log_dir=str(run_dir(cfg)),
        use_wandb=cfg.use_wandb,
        use_tensorboard=bool(cfg.get("use_tensorboard", False)),
        resume=cfg.get("resume", False),
        log_interval=cfg.log_interval,
        profile=bool(cfg.get("profile", False)),
        # Dispatches to trace under profile=true — whole fused chunks in
        # Anakin mode (chunk-granular capture, docs/profiling.md).
        profile_iterations=int(cfg.get("profile_iterations", 3)),
        # Anakin mode (docs/training.md): K iterations per lax.scan
        # dispatch, stacked metrics drained double-buffered, checkpoints
        # on a background writer. fused_chunk=32 is a good TPU default.
        # Composes with num_seeds>1 population sweeps AND curriculum
        # populations (chunks clip at stage boundaries).
        fused_chunk=int(cfg.get("fused_chunk", 0)),
        # Runtime tracing guards (analysis/guards.py): guard_retraces=1
        # enforces the compiles-exactly-once contract on the train step.
        guard_retraces=int(cfg.get("guard_retraces", 0)),
        guard_transfers=bool(cfg.get("guard_transfers", False)),
        guard_nans=bool(cfg.get("guard_nans", False)),
        # Self-healing train lane (train/recovery.py, docs/recovery.md):
        # in-program health word + skip guard, the host-side escalation
        # ladder, and the checkpoint retention ring.
        health=bool(cfg.get("health", False)),
        health_grad_norm_max=float(cfg.get("health_grad_norm_max", 1.0e6)),
        health_param_drift_max=float(
            cfg.get("health_param_drift_max", 10.0)
        ),
        recovery=bool(cfg.get("recovery", False)),
        recovery_breach_iters=int(cfg.get("recovery_breach_iters", 3)),
        recovery_max_rollbacks=int(cfg.get("recovery_max_rollbacks", 3)),
        recovery_lr_backoff=float(cfg.get("recovery_lr_backoff", 1.0)),
        recovery_severity_backoff=float(
            cfg.get("recovery_severity_backoff", 1.0)
        ),
        keep_last_n=int(cfg.get("keep_last_n", 0)),
        # Sebulba lane (train/sebulba/, docs/sebulba.md): split
        # acting/learning with hardened host-side transfer queues.
        architecture=str(cfg.get("architecture", "anakin")),
        actor_devices=int(cfg.get("actor_devices", 1)),
        transfer_queue_depth=int(cfg.get("transfer_queue_depth", 2)),
        max_param_staleness=int(cfg.get("max_param_staleness", 2)),
    )


def _hidden_sizes(cfg):
    """Optional ``hidden_sizes=[w1, w2, ...]`` — the SB3
    ``policy_kwargs={'net_arch': ...}`` analog (the reference uses the
    'MlpPolicy' default [64, 64]; this knob replaces that part of SB3's
    constructor surface). None/null keeps each model's default."""
    sizes = cfg.get("hidden_sizes")
    if not sizes:
        return None
    return tuple(int(w) for w in sizes)


def build_model(cfg, env_params, policy: str):
    """The ONE policy-module construction site (both the plain and the
    curriculum trainer paths build through here): maps the ``policy``
    name + config knobs (``hidden_sizes``, ``log_std_init``, knn
    geometry) to a model instance, or None for the default-shape MLP
    (trainer shells construct that themselves)."""
    hidden = _hidden_sizes(cfg)
    extra = {"hidden": hidden} if hidden else {}
    if policy == "ctde":
        from marl_distributedformation_tpu.models import CTDEActorCritic

        return CTDEActorCritic(
            act_dim=env_params.act_dim, log_std_init=cfg.log_std_init,
            **extra,
        )
    if policy == "gnn":
        if env_params.obs_mode != "knn":
            raise SystemExit(
                "policy=gnn needs the k-NN observation graph: set "
                "obs_mode=knn (and knn_k) in the config"
            )
        from marl_distributedformation_tpu.models import GNNActorCritic

        return GNNActorCritic(
            k=env_params.knn_k,
            act_dim=env_params.act_dim,
            goal_in_obs=env_params.goal_in_obs,
            log_std_init=cfg.log_std_init,
            **extra,
        )
    if policy == "trunk":
        if env_params.obs_mode != "knn":
            raise SystemExit(
                "policy=trunk reads the k-NN observation's layout: set "
                "obs_mode=knn (and knn_k) in the config"
            )
        if not cfg.get("trunk"):
            raise SystemExit(
                "policy=trunk needs trunk=<name>, an architecture file "
                "cfg/trunk/<name>.yaml (e.g. trunk=keye-vl2-a3b-ep8)"
            )
        from marl_distributedformation_tpu.models.trunk import (
            TrunkActorCritic,
            load_trunk_arch,
        )

        try:
            arch = load_trunk_arch(str(cfg.trunk))
        except ValueError as e:
            raise SystemExit(str(e)) from e
        return TrunkActorCritic(
            arch=arch,
            k=env_params.knn_k,
            act_dim=env_params.act_dim,
            goal_in_obs=env_params.goal_in_obs,
            log_std_init=cfg.log_std_init,
        )
    if policy == "mlp":
        if not hidden:
            return None
        from marl_distributedformation_tpu.models import MLPActorCritic

        return MLPActorCritic(
            act_dim=env_params.act_dim,
            hidden=hidden,
            log_std_init=cfg.log_std_init,
        )
    raise SystemExit(
        f"policy={policy!r} is not implemented; available: mlp, ctde, gnn, "
        "trunk"
    )


def shard_fn_from_config(cfg):
    if not cfg.get("mesh"):
        return None
    from marl_distributedformation_tpu.parallel import (
        make_hybrid_mesh,
        make_shard_fn,
    )

    # Hybrid construction keeps the gradient psum on ICI within a slice
    # with only slice-partials over DCN; single-slice it is a plain mesh.
    return make_shard_fn(mesh=make_hybrid_mesh(dict(cfg.mesh)))


def build_trainer(cfg) -> Trainer:
    """The trainer ``cfg`` asks for, whatever its kind, built under the
    ``setup_build_trainer`` span (``utils.profiling.host_span``)."""
    with host_span("setup_build_trainer"):
        return _build_trainer(cfg)


def _build_trainer(cfg) -> Trainer:
    if cfg.backend != "jax":
        raise SystemExit(
            f"backend={cfg.backend!r} is not available in this repo; the "
            "TPU-native backend is 'jax' (the reference torch/SB3 stack "
            "lives in the original repository)."
        )
    env_params = env_params_from_config(cfg)
    ppo = ppo_from_config(cfg)
    train_cfg = train_config_from_config(cfg)
    shard_fn = shard_fn_from_config(cfg)
    num_seeds = int(cfg.get("num_seeds", 1))
    learning_rates = cfg.get("learning_rates")
    if learning_rates and num_seeds <= 1:
        # Validated before any dispatch so no path can silently drop it.
        raise SystemExit(
            "learning_rates is a population knob: set num_seeds to the "
            "number of rates (one member per rate)"
        )
    # Fail-fast at config time: unknown scenario names raise here naming
    # the registry entries (never a silent clean-env run).
    scenario_schedule = scenario_schedule_from_config(cfg)
    if train_cfg.architecture == "sebulba" and cfg.get("curriculum"):
        raise SystemExit(
            "architecture=sebulba does not compose with curriculum "
            "training yet (the hetero stage machinery is Anakin-shaped); "
            "drop one of the two"
        )
    if cfg.get("curriculum"):
        if num_seeds > 1 and learning_rates:
            raise SystemExit(
                "learning_rates does not compose with curriculum "
                "populations (candidate-seed selection trains at one "
                "rate); drop one of the two"
            )
        if scenario_schedule is not None:
            raise SystemExit(
                "scenarios do not compose with curriculum training yet "
                "(the hetero step is not scenario-wrapped); drop one of "
                "the two"
            )
        return build_hetero_trainer(
            cfg, env_params, ppo, train_cfg, shard_fn, num_seeds
        )
    policy = cfg.get("policy", "mlp")
    model = build_model(cfg, env_params, policy)
    if train_cfg.architecture == "sebulba":
        if num_seeds > 1:
            raise SystemExit(
                "architecture=sebulba does not compose with num_seeds>1 "
                "population sweeps yet (the sweep's vmapped iteration is "
                "Anakin-shaped); drop one of the two"
            )
        from marl_distributedformation_tpu.train import SebulbaDriver

        # Mesh / curriculum / recovery incompatibilities fail fast inside
        # the driver with actionable messages.
        return SebulbaDriver(
            env_params,
            ppo=ppo,
            config=train_cfg,
            model=model,
            shard_fn=shard_fn,
            scenario_schedule=scenario_schedule,
        )
    if train_cfg.architecture != "anakin":
        raise SystemExit(
            f"architecture={train_cfg.architecture!r} is unknown; "
            "available: anakin (fused same-device), sebulba (split "
            "acting/learning — docs/sebulba.md)"
        )
    if num_seeds > 1:
        if scenario_schedule is not None:
            raise SystemExit(
                "scenarios do not compose with num_seeds>1 population "
                "sweeps yet (the vmapped sweep iteration is not "
                "scenario-wrapped); drop one of the two"
            )
        from marl_distributedformation_tpu.train import SweepTrainer

        return SweepTrainer(
            env_params,
            ppo=ppo,
            config=train_cfg,
            num_seeds=num_seeds,
            model=model,
            mesh=getattr(shard_fn, "mesh", None),
            learning_rates=learning_rates,
        )
    return Trainer(
        env_params,
        ppo=ppo,
        config=train_cfg,
        model=model,
        shard_fn=shard_fn,
        scenario_schedule=scenario_schedule,
    )


def build_hetero_trainer(cfg, env_params, ppo, train_cfg, shard_fn,
                         num_seeds: int = 1):
    """Curriculum path (BASELINE.json config 5): mixed-size padded formations
    with an obstacle field, staged over ``cfg.curriculum``. With
    ``num_seeds > 1``, K candidate seeds of the full curriculum train in
    one vmapped program (train/hetero_sweep.py) — the det-gate candidate
    selection workflow (docs/acceptance/hetero5/)."""
    from marl_distributedformation_tpu.envs import spec_for_params
    from marl_distributedformation_tpu.train import (
        HeteroTrainer,
        curriculum_from_cfg,
    )

    env_name = spec_for_params(env_params).name
    if env_name != "formation":
        raise SystemExit(
            f"curriculum training is formation-only (the hetero padded-"
            f"formation machinery wraps env/hetero.py, not the registered-"
            f"env dispatch); env={env_name!r} does not compose — drop "
            "curriculum or set env=formation"
        )
    policy = cfg.get("policy", "mlp")
    if policy not in ("mlp", "ctde"):
        raise SystemExit(
            f"curriculum training supports policy=mlp (shared per-agent "
            f"MLP) and policy=ctde (masked centralized critic); "
            f"policy={policy!r} is not supported — the GNN needs knn obs, "
            "and heterogeneous formations are ring-observed"
        )
    if env_params.obs_mode != "ring":
        raise SystemExit(
            "curriculum training uses the ring observation model (padded "
            f"formations mask the ring per transition); obs_mode="
            f"{env_params.obs_mode!r} is not supported — set obs_mode=ring"
        )
    model = build_model(cfg, env_params, policy)
    curriculum = curriculum_from_cfg(cfg.curriculum)
    if num_seeds > 1:
        from marl_distributedformation_tpu.train import HeteroSweepTrainer

        return HeteroSweepTrainer(
            curriculum=curriculum,
            env_params=env_params,
            ppo=ppo,
            config=train_cfg,
            num_seeds=num_seeds,
            model=model,
            mesh=getattr(shard_fn, "mesh", None),
        )
    return HeteroTrainer(
        curriculum=curriculum,
        env_params=env_params,
        ppo=ppo,
        config=train_cfg,
        model=model,
        shard_fn=shard_fn,
    )


def _snapshot_config(cfg, log_dir, stamp) -> None:
    """Save the resolved run config to ``{log_dir}/config.json`` — the
    analog of hydra's per-run ``.hydra/config.yaml`` snapshot (the
    reference gets one implicitly via ``@hydra.main``; see
    docs/migration.md 'Run directory'). Only process 0 writes. A
    ``resume=true`` invocation never writes the canonical file —
    ``config.json`` always describes the config the run was originally
    trained with; resumes snapshot to ``config_resume.json`` (latest
    resume wins)."""
    from marl_distributedformation_tpu.parallel import is_coordinator

    if not is_coordinator():
        return
    path = Path(log_dir)
    path.mkdir(parents=True, exist_ok=True)
    name = "config_resume.json" if cfg.get("resume") else "config.json"
    snap = dict(cfg)
    # The requested config says what the user asked for; these say what
    # actually ran — a record claiming "TPU" must be able to prove it
    # from the run directory.
    snap["resolved_platform"] = stamp["platform"]
    snap["resolved_device"] = stamp["device_kind"]
    snap["resolved_device_count"] = stamp["device_count"]
    with open(path / name, "w") as f:
        json.dump(snap, f, indent=2, default=str)


def main(argv=None) -> dict:
    cfg = load_config(sys.argv[1:] if argv is None else argv)
    setup_platform(cfg.get("platform"))
    from marl_distributedformation_tpu.parallel import init_distributed

    # Before the first device query: jax.distributed.initialize() must
    # precede backend start-up (no-op single-process).
    multi_host = init_distributed()
    stamp = announce_device("train")
    if multi_host:
        import jax

        print(
            f"[train] multi-host: process {jax.process_index()}/"
            f"{jax.process_count()}, {len(jax.local_devices())} local "
            f"of {len(jax.devices())} global devices"
        )
    trainer = build_trainer(cfg)
    _snapshot_config(cfg, trainer.log_dir, stamp)
    # Live-metrics plane (obs/metrics.py, docs/observability.md): the
    # trainer records env-steps/s, chunk drain latency, checkpoint-writer
    # health, and compile counters into the process registry;
    # telemetry_port serves them as Prometheus text on GET /metrics so a
    # bare training run is scrapeable without a serving fleet.
    from marl_distributedformation_tpu.obs import (
        TelemetryServer,
        configure_ledger,
        configure_metrics,
        get_ledger,
        get_registry,
    )

    configure_metrics(
        enabled=bool(cfg.get("telemetry", True)),
        reservoir=int(cfg.get("telemetry_reservoir", 512)),
    )
    # Program ledger (obs/ledger.py): every compile this run performs
    # registers its executable's cost/memory facts; the census lands
    # beside the checkpoints at exit for program_report.py.
    configure_ledger(
        enabled=bool(cfg.get("ledger", True)),
        reservoir=int(cfg.get("ledger_reservoir", 256)),
    )
    telemetry = None
    if cfg.get("telemetry_port") is not None:
        telemetry = TelemetryServer(port=int(cfg.telemetry_port)).start()
        print(f"[train] telemetry: {telemetry.url}")
    print(
        f"[train] {cfg.name}: M={cfg.num_formation} formations x "
        f"N={cfg.num_agents_per_formation} agents, "
        f"{trainer.total_timesteps} agent-transitions, "
        f"logs -> {trainer.log_dir}"
    )
    try:
        final = trainer.train()
    finally:
        if telemetry is not None:
            telemetry.stop()
        ledger = get_ledger()
        if ledger.enabled and ledger.entries():
            try:
                path = ledger.write_census(
                    Path(trainer.log_dir) / "program_ledger.json"
                )
                print(f"[train] program ledger census -> {path}")
            except OSError as e:
                print(f"[train] census write failed: {e!r}")
    print(f"[train] done at {trainer.num_timesteps} steps: {final}")
    result = {
        "entry": "train",
        "name": str(cfg.name),
        "log_dir": str(trainer.log_dir),
        "num_timesteps": int(trainer.num_timesteps),
        # RetraceGuard receipts as the lane published them (anakin: the
        # one train program; sebulba: actor + learner).
        "train_compiles": get_registry().snapshot().get("train_compiles"),
        # Where the run's arrays sit while the trainer still holds them.
        "residency_bytes": device_residency(),
        "final": final,
        **stamp,
    }
    print(json.dumps(result, default=str))
    return result


if __name__ == "__main__":
    main()
