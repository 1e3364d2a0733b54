"""``test_costs.py``'s checks for the trunk of gated short convolutions
(LFM2-24B-A2B's block; a file of its own beside the configuration's other
new files): the job's shape and model FLOP worked out by hand, and the
overrides stating what the reference computes."""

import json

import pytest

from benchmarks import costs
from benchmarks.tests.conftest import ROOT

NAME = "lfm2-24b-a2b-ep8-s8k"


def _config():
    return json.loads((ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())


def _job():
    return json.loads((ROOT / "benchmarks" / "workloads" / "train-m2.json").read_text())


def test_shape_and_flops():
    config, job = _config(), _job()
    shape = costs.job_shape(config, job)
    # one swarm x 8,192 agents x 4 steps; a minibatch is two swarm-steps
    assert shape == {"agent_steps": 32768, "minibatches": 2, "used": 32768}
    h, s = 2048, 8192
    # [B | C | x] and W_out; the B x gate, three taps a multiply-add each, the C gate
    conv = 2 * (3 * h * h + h * h) + (1 + 2 * 3 + 1) * h
    gqa = (
        2 * (h * (32 + 2 * 8) * 64 + 32 * 64 * h)
        + (s + 1) / 2 * 2 * 32 * (64 + 64)  # scores and their product with v
    )
    dense = 6 * h * 11776
    experts = 2 * h * 64 + 4 * 8 / 64 * 6 * h * 1536  # router; top-4's share held
    heads = 2 * 16 * h + 2 * h * 2 + 4 * h
    # layer 1 conv + dense; layer 2 attention + experts; layers 3-5 conv + experts
    forward = 4 * conv + gqa + dense + 4 * experts + heads
    assert forward == pytest.approx(3.724e8, rel=1e-3)
    # rollout and bootstrap forward, then forward and backward over the used
    # rows in each of two epochs
    expected = forward * (32768 + 8192 + 3 * 2 * 32768)
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
