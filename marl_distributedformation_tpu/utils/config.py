"""Hydra-compatible configuration loading.

The reference wires its CLI through ``@hydra.main(config_path="cfg",
config_name="config")`` with ``key=value`` overrides (vectorized_env.py:112,
README.md:18). This module preserves that exact CLI contract — ``python
train.py name=x num_formation=16`` — with a small, dependency-free YAML +
override parser (hydra itself is not installable in the TPU image;
SURVEY.md §2.2). Hydra features beyond flat ``key=value``/dotted overrides
(config groups, ``${...}`` interpolation, multirun) are intentionally out of
scope: the reference uses none of them.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional

import yaml

# Dot-less scientific notation that YAML 1.1 fails to parse as a float.
_SCI_NOTATION_RE = re.compile(r"^[+-]?\d+(\.\d*)?[eE][+-]?\d+$")


class Config(dict):
    """Dict with attribute access, mirroring omegaconf's DictConfig usage
    in the reference (``cfg.num_formation`` etc.)."""

    def __getattr__(self, name: str) -> Any:
        try:
            return self[name]
        except KeyError as e:  # pragma: no cover
            raise AttributeError(name) from e

    def __setattr__(self, name: str, value: Any) -> None:
        self[name] = value


def _parse_value(raw: str) -> Any:
    """Parse an override value with YAML semantics (hydra behavior):
    ``true``/``false`` -> bool, numbers -> int/float, ``null`` -> None.

    YAML 1.1 leaves dot-less scientific notation (``3e-4``) as a string;
    hydra parses it as a float, so coerce exactly that shape — and nothing
    else, so string-typed values like ``name=2024a`` survive untouched."""
    value = yaml.safe_load(raw)
    if isinstance(value, str) and _SCI_NOTATION_RE.match(value):
        return float(value)
    return value


def apply_overrides(cfg: Dict[str, Any], overrides: Iterable[str]) -> None:
    """Apply ``key=value`` (dotted keys allowed) overrides in place.

    Unknown top-level keys are accepted, as in hydra's default struct-less
    mode for this config (the reference's cfg is flat and unvalidated).
    """
    for item in overrides:
        if "=" not in item:
            raise ValueError(
                f"override {item!r} is not of the form key=value"
            )
        key, raw = item.split("=", 1)
        target = cfg
        parts = key.split(".")
        for part in parts[:-1]:
            # Replace null/scalar intermediates so `mesh.dp=4` works when the
            # config ships `mesh: null`.
            if not isinstance(target.get(part), dict):
                target[part] = Config()
            target = target[part]
        target[parts[-1]] = _parse_value(raw)


# Named hyperparameter presets (``preset=tpu`` on any entry point).
# Precedence: YAML defaults < preset < explicit CLI overrides — so
# ``python train.py preset=tpu batch_size=4096`` keeps the user's batch size.
#
# "tpu": the TPU-shaped training configuration. The parity defaults inherit
# SB3's batch_size=64, which turns each update into n_epochs x (rollout/64)
# *sequential* tiny SGD steps — at M=4096 that is 32,000 serial launches of
# MXU-starving (64, obs_dim) matmuls, 98% of iteration wall-clock
# (docs/profiling.md). A large batch_size keeps the same epochs/passes over
# the data with far fewer, far larger steps — the shape the MXU wants.
# 16384 is the measured sweet spot from the on-chip sweep
# (docs/acceptance/tpu_tuning_r4.txt): +7% throughput over 8192 AND a
# better held-out eval return (5271 vs 5078 in the same harness); 32768 is
# marginally faster but gives back eval quality, and the full-buffer point
# (one minibatch per epoch) fails the quality guard outright.
PRESETS: Dict[str, Dict[str, Any]] = {
    "tpu": {"batch_size": 16384},
}


def load_config(
    overrides: Optional[List[str]] = None,
    config_path: str = "cfg/config.yaml",
) -> Config:
    """Load the YAML config and apply presets + CLI overrides.

    ``config_path`` is resolved relative to the repo root (this file's
    grandparent), so entry points work from any cwd — the equivalent of the
    reference's ``hydra.utils.get_original_cwd()`` dance
    (vectorized_env.py:121)."""
    path = Path(config_path)
    if not path.is_absolute() and not path.exists():
        path = repo_root() / config_path
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    cfg = _to_config(data)
    overrides = list(overrides or [])
    preset = next(
        (
            _parse_value(o.split("=", 1)[1])
            for o in reversed(overrides)
            if "=" in o and o.split("=", 1)[0] == "preset"
        ),
        data.get("preset"),
    )  # a bare "preset" token falls through to apply_overrides' error
    if preset:
        if preset not in PRESETS:
            raise ValueError(
                f"unknown preset {preset!r}; available: {sorted(PRESETS)}"
            )
        cfg.update(_to_config(PRESETS[preset]))
    apply_overrides(cfg, overrides)
    return cfg


def _to_config(data: Any) -> Any:
    if isinstance(data, dict):
        return Config({k: _to_config(v) for k, v in data.items()})
    return data


def setup_platform(platform: Optional[str]) -> None:
    """Force a JAX backend before first device use (the ``platform=cpu``
    CLI knob shared by every entry point; the ``JAX_PLATFORMS``
    environment variable does the same from outside). No-op on falsy:
    the run takes whatever jax resolves, and :func:`device_stamp` says
    what that was."""
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


# The one compile-cache location the program ever sets in code (every
# entry point, chip_smoke.py and tests/conftest.py go through
# setup_compile_cache). Fixed and inside the checkout — the directory is
# part of the cache key's environment, so a path that moves (tmp dirs,
# pids, timestamps) never hits.
COMPILE_CACHE_DIRNAME = ".jax_cache"


def setup_compile_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    no directory is set in code; otherwise the cache goes to
    ``<repo>/.jax_cache`` (git-ignored). Call before the first
    compilation.

    Either way the cache's key takes in the program's metadata: jax
    leaves it out by default, and an executable loaded from the cache
    then carries the ``jax.named_scope`` names of whatever source first
    compiled it, so a trace (and the benchmark's per-stage metrics, which
    read those names from the executable) would show the stages of an
    older checkout that shares the directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(repo_root() / COMPILE_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp() -> Dict[str, Any]:
    """The device this process resolved, as jax reports it — the stamp
    every entry point prints first and carries in its result, so no
    record can be read without knowing what it ran on. Raises when jax
    finds no usable backend: a run that cannot name its device is not
    evidence."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def announce_device(tag: str, file: Any = None) -> Dict[str, Any]:
    """An entry point's opening move: place the compile cache, resolve
    the device, print ``[tag] device: platform=... device_kind=...
    device_count=... compile_cache=...`` as the first output line, and
    return the stamp for the result. Anything that must precede backend
    start-up (``init_distributed``, :func:`widen_cpu_pool`) goes before
    the call."""
    cache_dir = setup_compile_cache()
    stamp = device_stamp()
    print(
        f"[{tag}] device: platform={stamp['platform']} "
        f"device_kind={stamp['device_kind']!r} "
        f"device_count={stamp['device_count']} compile_cache={cache_dir}",
        file=file,
    )
    return stamp


def device_residency() -> Dict[str, int]:
    """Bytes of this process's live jax arrays per local device id — where
    params, state and buffers actually sit, whatever was asked for. On
    one chip everything is under ``"0"``; a dp mesh, a one-replica-per-
    device fleet or an actor/learner split must show up on several."""
    import jax

    held = {str(d.id): 0 for d in jax.local_devices()}
    for array in jax.live_arrays():
        # From the sharding alone — no shard data is touched, so an array
        # another thread deletes meanwhile cannot raise here.
        sharding = array.sharding
        shard_bytes = (
            math.prod(sharding.shard_shape(array.shape))
            * array.dtype.itemsize
        )
        for device in sharding.addressable_devices:
            held[str(device.id)] += shard_bytes
    return held


def cpu_requested() -> bool:
    """True when the CPU platform was asked for BY NAME — ``platform=cpu``
    / ``JAX_PLATFORMS=cpu`` (both land in ``jax_platforms``). The only
    condition under which code may provision virtual CPU devices."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def widen_cpu_pool(n: int) -> None:
    """When — and only when — the CPU was asked for by name
    (:func:`cpu_requested`), provision at least ``n`` virtual CPU
    devices; never shrinks what ``XLA_FLAGS`` or an earlier call already
    provisioned. Must run before the backend initializes (jax raises
    otherwise). On an accelerator this does nothing: the devices are
    what the hardware has."""
    import jax

    if not cpu_requested():
        return
    flag = re.search(
        r"--xla_force_host_platform_device_count=(\d+)",
        os.environ.get("XLA_FLAGS", ""),
    )
    provisioned = max(
        int(jax.config.jax_num_cpu_devices),
        int(flag.group(1)) if flag else 1,
    )
    if provisioned < n:
        jax.config.update("jax_num_cpu_devices", n)


def ensure_devices(n: int) -> None:
    """At least ``n`` local devices, or raise: too few chips is an
    error, never a quiet move to virtual CPU devices (those exist only
    through :func:`widen_cpu_pool`, i.e. when the CPU was named)."""
    import jax

    widen_cpu_pool(n)
    devices = jax.local_devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} local devices, have {len(devices)} "
            f"({devices[0].platform}); virtual devices are provisioned "
            "only when the CPU is asked for by name (platform=cpu / "
            "JAX_PLATFORMS=cpu)"
        )


def run_dir(cfg: Config) -> Path:
    """Where a run's checkpoints, metrics and snapshots go: the
    ``log_dir`` key when given, else ``<repo>/logs/{name}``."""
    given = cfg.get("log_dir")
    # hydra parses numeric-looking names as ints
    return Path(str(given)) if given else repo_root() / "logs" / str(cfg.name)


def repo_root() -> Path:
    """Root of this repository (where ``cfg/`` and ``logs/`` live)."""
    return Path(__file__).resolve().parent.parent.parent


def scenario_schedule_from_config(cfg: Config):
    """Build the scenario-training schedule from the flat config
    (``scenarios`` + ``scenario_severity`` keys, cfg/config.yaml) — None
    when scenario training is off. Unknown scenario names fail fast here,
    at config time, naming the registry entries."""
    raw = cfg.get("scenarios")
    if not raw:
        return None
    from marl_distributedformation_tpu.scenarios import schedule_from_cfg

    return schedule_from_cfg(
        raw, default_severity=float(cfg.get("scenario_severity") or 0.0)
    )


def _env_spec_or_exit(name: str):
    """Resolve a registered env by name, converting the registry's
    ValueError (did-you-mean + listing) into the entry-point SystemExit."""
    from marl_distributedformation_tpu.envs import get_env

    try:
        return get_env(str(name))
    except ValueError as e:
        raise SystemExit(str(e)) from e


def validate_override_keys(
    overrides: Iterable[str],
    extra_keys: Iterable[str] = (),
    config_path: str = "cfg/config.yaml",
) -> None:
    """Fail fast on mistyped CLI override keys (read-only entry points).

    ``train.py`` keeps hydra's struct-less tolerance (experimental knobs
    ride along in the config snapshot), but evaluation entry points have
    no snapshot to expose the typo — an unknown key silently evaluates
    the default (e.g. the clean env), which is exactly the failure mode
    this guards. Valid keys = the YAML defaults + ``extra_keys``; dotted
    overrides validate their top-level segment."""
    overrides = list(overrides)
    path = Path(config_path)
    if not path.is_absolute() and not path.exists():
        path = repo_root() / config_path
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    known = set(data)
    # Every field of the SELECTED env's params class is honored by
    # env_params_from_config even when the YAML defaults omit it (e.g.
    # max_steps, pursuer_speed) — all are valid overrides. Peek the env=
    # override the same way load_config peeks preset=, so a mistyped env
    # name fails here with the registry's did-you-mean, and env-specific
    # knobs (PursuitParams.capture_radius, ...) validate precisely.
    env_name = next(
        (
            _parse_value(o.split("=", 1)[1])
            for o in reversed(overrides)
            if "=" in o and o.split("=", 1)[0] == "env"
        ),
        data.get("env", "formation"),
    )
    spec = _env_spec_or_exit(env_name)
    known |= {f.name for f in dataclasses.fields(spec.params_cls)}
    known |= {"env"}
    known |= set(extra_keys)
    for item in overrides:
        if "=" not in item:
            continue  # apply_overrides raises its own error for these
        key = item.split("=", 1)[0].split(".")[0]
        if key not in known:
            import difflib

            close = difflib.get_close_matches(key, known, n=1)
            hint = f" (did you mean {close[0]!r}?)" if close else ""
            raise SystemExit(
                f"unknown config key {key!r}{hint}; valid keys: "
                f"{', '.join(sorted(known))}"
            )


def env_params_from_config(cfg: Config):
    """Build env params from the flat config, forwarding every knob —
    including ``share_reward_ratio``, which the reference silently drops
    (SURVEY.md Q6).

    The ``env`` key (cfg/config.yaml) selects which REGISTERED environment's
    params class to build (``envs.get_env`` — unknown names exit with the
    registry's did-you-mean), so ``env=pursuit_evasion`` routes every env
    consumer (train.py, evaluate.py, the robustness matrix) through
    ``envs.spec_for_params`` dispatch with no further plumbing. Default is
    the formation env, whose params class is the legacy ``EnvParams``."""
    spec = _env_spec_or_exit(cfg.get("env", "formation"))
    fields = {f.name for f in dataclasses.fields(spec.params_cls)}
    kwargs = {
        "num_agents": cfg.num_agents_per_formation,
        "share_reward_ratio": cfg.share_reward_ratio,
        "goal_in_obs": cfg.goal_in_obs,
    }
    for key in fields:
        if key in cfg and key not in ("num_agents",):
            kwargs[key] = cfg[key]
    return spec.params_cls(**kwargs)
