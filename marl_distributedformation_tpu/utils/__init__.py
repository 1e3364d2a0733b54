"""Config, logging, checkpointing, and profiling utilities."""

from marl_distributedformation_tpu.utils.config import (  # noqa: F401
    Config,
    announce_device,
    apply_overrides,
    cpu_requested,
    device_residency,
    device_stamp,
    ensure_devices,
    env_params_from_config,
    load_config,
    repo_root,
    run_dir,
    scenario_schedule_from_config,
    setup_compile_cache,
    setup_platform,
    validate_override_keys,
    widen_cpu_pool,
)
from marl_distributedformation_tpu.utils.checkpoint import (  # noqa: F401
    AsyncCheckpointWriter,
    CheckpointDiscovery,
    CorruptCheckpointError,
    broadcast_restore,
    checkpoint_path,
    checkpoint_step,
    device_snapshot,
    NonFiniteCheckpointError,
    latest_checkpoint,
    latest_sweep_state,
    msgpack_restore_file,
    own_restored,
    prune_checkpoints,
    quarantine_checkpoint,
    read_checkpoint_payload,
    restore_checkpoint,
    restore_checkpoint_partial,
    restore_latest_partial,
    save_checkpoint,
    save_sweep_state,
    sweep_state_path,
)
from marl_distributedformation_tpu.utils.logging import MetricsLogger  # noqa: F401
from marl_distributedformation_tpu.utils.profiling import (  # noqa: F401
    Throughput,
)
