"""Multi-host distributed primitives, exercised single-process.

True multi-host behavior (DCN collectives, per-host shards) can't run in a
single-process CI; these tests pin the single-process degradations — which
the multi-host paths are written to share — plus the pure factoring logic
and the process-local -> global array construction on the 8-virtual-device
CPU mesh (conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.formation import reset_batch
from marl_distributedformation_tpu.parallel import (
    global_from_local,
    init_distributed,
    is_coordinator,
    local_formation_slice,
    make_hybrid_mesh,
    shard_batch,
)
from marl_distributedformation_tpu.utils import MetricsLogger, save_checkpoint


def test_init_distributed_single_process_noop():
    assert init_distributed() is False  # no coordinator configured
    assert is_coordinator()


def test_hybrid_mesh_falls_back_single_slice():
    mesh = make_hybrid_mesh({"dp": 4, "sp": 2})
    assert mesh.shape == {"dp": 4, "sp": 2}
    mesh2 = make_hybrid_mesh({"dp": -1})
    assert mesh2.shape == {"dp": 8}


def test_local_formation_slice_single_process():
    start, count = local_formation_slice(4096)
    assert (start, count) == (0, 4096)
    # Explicit process_index computes any host's shard (here: as if 4 hosts
    # existed, host 3 of a 4096 split would start at 3072 — but with one
    # process the divisor is process_count, so the shard is the whole batch).
    start, count = local_formation_slice(64, process_index=0)
    assert (start, count) == (0, 64)


def test_global_from_local_matches_shard_batch():
    """Single-process, the process-local assembly must produce the same
    values and the same 'dp' placement as plain device_put sharding."""
    mesh = make_hybrid_mesh({"dp": 8})
    params = EnvParams(num_agents=5)
    state = reset_batch(jax.random.PRNGKey(0), params, 16)

    via_local = global_from_local(state, mesh)
    via_put = shard_batch(state, mesh)

    for a, b in zip(
        jax.tree_util.tree_leaves(via_local),
        jax.tree_util.tree_leaves(via_put),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding.is_equivalent_to(
            NamedSharding(mesh, P("dp")), a.ndim
        )


def test_global_from_local_usable_in_jit():
    mesh = make_hybrid_mesh({"dp": 8})
    local = jnp.arange(32, dtype=jnp.float32).reshape(16, 2)
    g = global_from_local(local, mesh)
    out = jax.jit(lambda x: (x * 2).sum())(g)
    assert float(out) == float(local.sum() * 2)


def test_partial_restore_across_checkpoint_layouts(tmp_path):
    """A learner-only (multi-host-style) checkpoint restores into a
    full single-host template — env keys simply stay fresh — and extra
    keys in the file are ignored."""
    from marl_distributedformation_tpu.utils import (
        restore_checkpoint_partial,
        save_checkpoint,
    )

    learner_only = {"params": {"w": jnp.ones((2, 2))}, "num_timesteps": 40}
    path = save_checkpoint(tmp_path, 40, learner_only)
    full_template = {
        "params": {"w": jnp.zeros((2, 2))},
        "num_timesteps": 0,
        "env_state": jnp.zeros((3,)),
    }
    restored = restore_checkpoint_partial(path, full_template)
    assert set(restored) == {"params", "num_timesteps"}
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), 1.0)

    # Reverse: full checkpoint into a learner-only template.
    full = dict(full_template, extra=jnp.ones((1,)))
    path2 = save_checkpoint(tmp_path, 41, full)
    restored2 = restore_checkpoint_partial(
        path2, {"params": {"w": jnp.ones((2, 2))}, "num_timesteps": 7}
    )
    assert set(restored2) == {"params", "num_timesteps"}
    assert int(restored2["num_timesteps"]) == 0


def test_coordinator_guards_are_noops_single_process(tmp_path):
    """save_checkpoint writes and MetricsLogger emits on the coordinator
    (which a single process always is)."""
    path = save_checkpoint(tmp_path, 7, {"x": jnp.ones((2,))})
    assert path.exists()
    logger = MetricsLogger(tmp_path, use_wandb=False)
    logger.log({"reward": 1.0}, step=7)
    logger.close()
    assert (tmp_path / "metrics.jsonl").read_text().strip() != ""


def test_hetero_reset_batch_sharded_matches_unsharded():
    """Single-process degradation: the per-host-shard hetero reset equals
    the plain hetero_reset_batch (same keys, same counts), globally
    'dp'-sharded (round-1 ADVICE: HeteroTrainer multi-host start_stage)."""
    from marl_distributedformation_tpu.env.hetero import hetero_reset_batch
    from marl_distributedformation_tpu.parallel import (
        hetero_reset_batch_sharded,
        make_mesh,
    )

    params = EnvParams(num_agents=6, num_obstacles=2)
    n_agents = jnp.asarray([3, 6, 4, 2, 6, 5, 3, 4], jnp.int32)
    n_obstacles = jnp.asarray([0, 2, 1, 0, 2, 1, 0, 2], jnp.int32)
    key = jax.random.PRNGKey(7)
    mesh = make_mesh({"dp": 8})

    ref = hetero_reset_batch(key, params, n_agents, n_obstacles)
    sharded = hetero_reset_batch_sharded(
        key, params, n_agents, n_obstacles, mesh
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref),
        jax.tree_util.tree_leaves(sharded),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert not sharded.agents.sharding.is_fully_replicated


def test_init_distributed_multi_host_launch_failure_raises(monkeypatch):
    """A launch environment describing several hosts that cannot be wired
    up must raise — N hosts quietly training as N independent
    single-process jobs is a fallback, not a run."""
    import marl_distributedformation_tpu.parallel.distributed as dist

    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setenv("SLURM_JOB_NUM_NODES", "2")
    # No real Slurm env (and the backend is already up): initialize raises.
    with pytest.raises(Exception):
        dist.init_distributed()
    assert not dist._initialized


def test_init_distributed_single_host_tpu_vm_stays_single_process(
    monkeypatch,
):
    """A single-host TPU VM sets the pod-slice variables too
    (``TPU_WORKER_ID=0``, ``TPU_WORKER_HOSTNAMES=localhost``): one host is
    nothing to wire up, and cluster detection on a sealed machine can
    hang — ``jax.distributed.initialize`` must not be called."""
    import marl_distributedformation_tpu.parallel.distributed as dist

    def _must_not_run(*args, **kwargs):
        raise AssertionError("jax.distributed.initialize was called")

    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setattr(jax.distributed, "initialize", _must_not_run)
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert dist.init_distributed() is False
    assert dist._initialized
    # ... and a pod slice (several hostnames) does call it.
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "10.0.0.1,10.0.0.2")
    with pytest.raises(AssertionError, match="initialize was called"):
        dist.init_distributed()


def test_save_checkpoint_returns_path_single_process(tmp_path):
    path = save_checkpoint(tmp_path, 42, {"x": jnp.zeros((2,))})
    assert path is not None and path.exists()
