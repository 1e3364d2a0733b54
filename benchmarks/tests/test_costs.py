"""Operations, bytes and peaks: numbers worked out by hand."""

import json

import pytest

from benchmarks import costs, peaks
from benchmarks.tests.conftest import ROOT


def _config(name):
    return json.loads((ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())


def _job(name):
    return json.loads((ROOT / "benchmarks" / "workloads" / f"{name}.json").read_text())


def test_mlp5_shape_and_flops():
    config, job = _config("mlp5"), _job("train-m262k")
    shape = costs.job_shape(config, job)
    assert shape == {"agent_steps": 13107200, "minibatches": 12, "used": 12582912}
    forward = 2 * (8 * 64 + 64 * 64 + 64 * 2) + 2 * (8 * 64 + 64 * 64 + 64 * 1)
    assert forward == 18816
    expected = forward * (13107200 + 262144 * 5 + 3 * 10 * 12582912)
    assert costs.train_flops_per_iteration(config, job) == expected


def test_gnn100_shape_and_flops():
    config, job = _config("gnn100"), _job("train-m8k")
    shape = costs.job_shape(config, job)
    assert shape == {"agent_steps": 8192000, "minibatches": 12, "used": 12 * 6553 * 100}
    forward = (
        2 * 4 * 64
        + 2 * (4 * 2 * 131 * 64 + 2 * 132 * 64)
        + 2 * 64 * 64 + 2 * 128 * 64 + 2 * 64 * 2 + 2 * 64
    )
    assert costs.train_flops_per_iteration(config, job) == forward * (
        8192000 + 819200 + 30 * shape["used"]
    )


def test_knn_cost():
    cost = costs.knn_call_cost(8192, 100, 4)
    assert cost["ops"] == 8192 * 100 * 100 * 13
    assert cost["bytes"] == 8192 * 100 * 18 * 4


def test_peaks_table():
    assert peaks.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.load_peaks("cpu")


def test_overrides_say_what_the_reference_computes():
    """A configuration's overrides (what the program is given) and its
    env/ppo/policy groups (what the reference computes) state one thing."""
    for name in ("mlp5", "gnn100"):
        config = _config(name)
        given = dict(o.split("=", 1) for o in config["overrides"])
        stated = {**config["env"], **config["ppo"], **config["policy"]}
        for key, value in given.items():
            if key == "policy":
                assert value == config["policy"]["kind"]
            elif key in stated:
                try:
                    value = json.loads(value.lower())
                except json.JSONDecodeError:
                    pass  # a bare word, as obs_mode=ring
                assert value == stated[key], (name, key)
