"""``test_costs.py``'s checks for the configuration this PR adds (a file of
its own because this PR may edit no file the benchmark has): the job's shape
and model FLOP worked out by hand, and the overrides stating what the
reference computes."""

import json

import pytest

from benchmarks import costs
from benchmarks.tests.conftest import ROOT

NAME = "xing4-29b-a4b-ep8-s8k"


def _config():
    return json.loads((ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())


def _job():
    return json.loads((ROOT / "benchmarks" / "workloads" / "train-m1.json").read_text())


def test_shape_and_flops():
    config, job = _config(), _job()
    shape = costs.job_shape(config, job)
    # one swarm x 8,192 agents x 2 steps; a minibatch is one swarm-step
    assert shape == {"agent_steps": 16384, "minibatches": 2, "used": 16384}
    h, heads, s = 3584, 16, 8192
    mla = (
        2 * (h * 768 + 768 * heads * 192 + h * 576 + 512 * heads * 256 + heads * 128 * h)
        + (s + 1) / 2 * 2 * heads * (192 + 128)
    )
    hyper = 2 * 4 * h * 24 + 4 * 16 * 20 + 2 * h * (4 + 16 + 4)
    dense = 6 * h * 9216
    experts = 2 * h * 64 + 4 * 8 / 64 * 6 * h * 1024 + 6 * h * 1024
    forward = 5 * (mla + 2 * hyper) + dense + 4 * experts + 2 * 16 * h + 2 * h * 2 + 4 * h
    assert forward == pytest.approx(7.168e8, rel=1e-3)
    # rollout and bootstrap forward, then forward and backward over the used rows
    expected = forward * (16384 + 8192 + 3 * 1 * 16384)
    assert costs.train_flops_per_iteration(config, job) == pytest.approx(expected)


def test_overrides_say_what_the_reference_computes():
    config = _config()
    given = dict(o.split("=", 1) for o in config["overrides"])
    assert given["policy"] == "trunk" and given["trunk"] == config["policy"]["trunk"]
    stated = {**config["env"], **config["ppo"]}
    for key, value in given.items():
        if key in stated:
            try:
                value = json.loads(value.lower())
            except json.JSONDecodeError:
                pass  # a bare word, as obs_mode=knn
            assert value == stated[key], key
    # the top level is the published config as it is run: what is cut is
    # listed, with the published value beside it
    assert set(config["reduced"]) == set(config["published"])
    for key, published in config["published"].items():
        assert config[key] != published, key
