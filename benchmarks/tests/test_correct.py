"""``correct`` has to be able to come out false. The control (the
reference computed in bfloat16, put in the program's place) fails the
committed limits, and a run driven with the timed path broken underneath
reports ``correct: false``: a step that hands back its state unchanged,
and a minibatch that leaves half of its rows out and takes its means over
the rest. The limits are those of the committed cells, read on the chip;
the sizes here are what a test run can hold."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness

SINGLE_CHIP = ["mlp5-tiny-mlp5", "gnn100-tiny-gnn100"]


def _failed(rows):
    return [r["name"] for r in rows if not r["ok"]]


@pytest.mark.parametrize("cell_name", SINGLE_CHIP)
@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_control_in_bfloat16_is_not_correct(tiny_root, cell_name, seed):
    cell = harness.load_cell(cell_name, tiny_root)
    followed = cell.limits["follow_chunks"] * cell.job["fused_chunk"]
    ref = harness.follow_reference(cell, seed, followed)
    control = harness.follow_reference(cell, seed, followed, dtype="bfloat16")
    assert _failed(harness.judge(harness.compare(control, ref), cell.limits))
    # and the reference, put in the program's place unchanged, passes
    same = harness.follow_reference(cell, seed, followed)
    assert not _failed(harness.judge(harness.compare(same, ref), cell.limits))


def _run(cell, build):
    return harness.run_cell(
        cell, seed=11, seconds=0.2, trace=False, started=time.perf_counter(),
        require_chip=False, build=build, log=lambda line: None,
    )


@pytest.mark.parametrize("cell_name", SINGLE_CHIP)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(tiny_root, cell_name):
    def build(cell, seed):
        trainer = harness.build_program(cell, seed)
        run_chunk = trainer.run_chunk

        def stuck():
            kept = jax.tree_util.tree_map(jnp.copy, trainer.train_state)
            stacked = run_chunk()
            trainer.train_state = kept
            return stacked

        trainer.run_chunk = stuck
        return trainer

    result = _run(harness.load_cell(cell_name, tiny_root), build)
    assert result["correct"] is False
    value, limit = result["compared"]["param_change_gap"]
    assert value == pytest.approx(1.0) and value > limit


@pytest.mark.parametrize("cell_name", SINGLE_CHIP)
def test_half_of_the_batch_left_out_is_not_correct(tiny_root, cell_name, monkeypatch):
    from marl_distributedformation_tpu.train import trainer as program

    whole = program.ppo_update

    def half(train_state, data, key, config):
        kept = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], data)
        return whole(
            train_state, kept, key,
            dataclasses.replace(config, batch_size=config.batch_size // 2),
        )

    monkeypatch.setattr(program, "ppo_update", half)
    result = _run(harness.load_cell(cell_name, tiny_root), harness.build_program)
    assert result["correct"] is False


def test_the_exchange_between_chips_left_out_is_not_correct(tiny_root, monkeypatch):
    """Each chip updating from its own formations alone, with nothing
    gathered or reduced over the mesh: what chip 0 would then hold."""
    from marl_distributedformation_tpu.train import trainer as program

    cell = harness.load_cell("mlp5-tiny-mlp5-dp4", tiny_root)
    if cell.chips > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    whole = program.ppo_update
    steps, m = cell.config["ppo"]["n_steps"], cell.job["num_formation"]

    def own_shard(train_state, data, key, config):
        def first_chip(x):
            by_formation = x.reshape(steps, m, -1, *x.shape[1:])
            return by_formation[:, : m // cell.chips].reshape(-1, *x.shape[1:])

        return whole(
            train_state, jax.tree_util.tree_map(first_chip, data), key,
            dataclasses.replace(config, batch_size=config.batch_size // cell.chips),
        )

    monkeypatch.setattr(program, "ppo_update", own_shard)
    result = _run(cell, harness.build_program)
    assert result["correct"] is False
