"""Smoke tests for the driver entry point (__graft_entry__.py)."""

import pytest
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft


def test_graft_entry_compiles():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    mean, log_std, value = out
    assert mean.shape == (4096 * 5, 2)
    assert value.shape == (4096 * 5,)
    assert np.isfinite(np.asarray(mean)).all()


@pytest.mark.slow
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_odd():
    graft.dryrun_multichip(1)
