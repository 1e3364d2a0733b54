"""Test configuration: JAX on the CPU with 8 virtual devices.

``XLA_FLAGS`` must be set before the first backend initialization for the
8 virtual CPU devices (SURVEY.md §4) that mesh/sharding tests need, so
this runs before anything imports jax. ``MDF_TPU_TESTS=1`` leaves the real
backend in place so the @skipif-cpu tests (compiled-mode Pallas parity)
can run on hardware.
"""

import os

os.environ.setdefault("MPLBACKEND", "Agg")  # headless matplotlib for frontends

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from marl_distributedformation_tpu.utils import (  # noqa: E402
    setup_compile_cache,
)

# Persistent XLA compilation cache, placed like every entry point's
# (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache): the quick split's
# wall-clock is dominated by re-compiling near-identical jitted trainer
# programs across test files. The cache is keyed on HLO + compile
# options, so correctness is unaffected.
setup_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

if os.environ.get("MDF_TPU_TESTS") != "1":
    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) == 8, (
        f"the suite needs the 8-device CPU test mesh, have "
        f"{len(jax.devices())}: XLA_FLAGS pins another "
        "--xla_force_host_platform_device_count"
    )
