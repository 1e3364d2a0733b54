"""Each committed cell's program, compiled for a described TPU v5e at the
cell's real sizes, with nothing run: arguments plus temporaries on the
fullest device lie between the driver's floor (4 GiB, a quarter of the
chip) and the chip's 16 GiB. A cell that is too small, or that does not
fit, is caught here before any chip time is spent on it.

All of it is in this one file and inside fixtures: only one process may
load the TPU's library, and only the worker that is given this file does.
The compiles take a minute or two each.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from benchmarks import harness
from benchmarks.tests.conftest import ROOT

GIB = 2**30
FLOOR_GIB, CHIP_GIB = 4.0, 16.0
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _abstract_program(cell):
    """The program's functional core at the cell's sizes - the same
    ``make_ppo_iteration`` + ``make_fused_chunk`` the trainer jits - and
    the shapes of its state. ``knn_impl=pallas`` is steered here because
    the program's own choice asks ``jax.default_backend()``, which is the
    CPU in this process."""
    import train as entry
    from flax.training.train_state import TrainState
    from marl_distributedformation_tpu.envs import spec_for_params
    from marl_distributedformation_tpu.models import MLPActorCritic
    from marl_distributedformation_tpu.train.trainer import (
        make_fused_chunk,
        make_ppo_iteration,
    )
    from marl_distributedformation_tpu.utils import env_params_from_config, load_config

    overrides = harness.program_overrides(cell, 0)
    if cell.config["env"]["obs_mode"] == "knn":
        overrides = [o for o in overrides if not o.startswith("knn_impl=")]
        overrides.append("knn_impl=pallas")
    cfg = load_config(overrides)
    env_params = env_params_from_config(cfg)
    ppo = entry.ppo_from_config(cfg)
    model = entry.build_model(cfg, env_params, cfg.get("policy", "mlp")) or (
        MLPActorCritic(act_dim=env_params.act_dim, log_std_init=ppo.log_std_init)
    )
    per_formation = getattr(model, "per_formation", False)
    spec = spec_for_params(env_params)
    m = cfg.num_formation

    def make():
        key = jax.random.PRNGKey(0)
        row = (1, env_params.num_agents) if per_formation else (1,)
        train_state = TrainState.create(
            apply_fn=model.apply,
            params=model.init(key, jnp.zeros((*row, env_params.obs_dim))),
            tx=ppo.make_optimizer(),
        )
        env_state = spec.reset_batch(key, env_params, m)
        return train_state, env_state, spec.obs(env_state, env_params), key

    program = make_fused_chunk(
        make_ppo_iteration(env_params, ppo, per_formation), cell.job["fused_chunk"]
    )
    return program, jax.eval_shape(make)


def _placed(shapes, replicated, by_formation):
    train_state, env_state, obs, key = shapes

    def put(tree, sharding):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
        )

    return (
        put(train_state, replicated),
        put(env_state, by_formation),
        put(obs, by_formation),
        put(key, replicated),
    )


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_fills_a_quarter_of_the_chip_and_fits(topo, no_compile_cache, cell_name):
    cell = harness.load_cell(cell_name, ROOT)
    program, shapes = _abstract_program(cell)
    mesh_axes = cell.job.get("mesh")
    if mesh_axes:
        mesh = Mesh(
            np.asarray(topo.devices[: cell.chips], dtype=object).reshape(
                tuple(mesh_axes.values())
            ),
            tuple(mesh_axes),
        )
        args = _placed(shapes, NamedSharding(mesh, P()), NamedSharding(mesh, P("dp")))
    else:
        one_chip = SingleDeviceSharding(topo.devices[0])
        args = _placed(shapes, one_chip, one_chip)
    compiled = jax.jit(program, donate_argnums=(0, 1)).lower(*args).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / GIB
    assert FLOOR_GIB <= held <= CHIP_GIB, (
        f"{cell_name}: arguments + temporaries = {held:.2f} GiB on the fullest "
        f"device; a cell has to hold between {FLOOR_GIB} and {CHIP_GIB} GiB"
    )
    if cell.config["env"]["obs_mode"] == "knn":
        assert "tpu_custom_call" in compiled.as_text()  # the kernel is in it
