"""The trunk's cell, compiled for a described TPU v5e at its real size with
nothing run, as ``test_tpu_compile.py`` does for every cell: arguments plus
temporaries between the driver's floor (4 GiB) and the chip's 16 GiB.

That file steers every k-NN cell to ``knn_impl=pallas``, the fused kernel,
because the program's own choice asks ``jax.default_backend()``, which is
the CPU here. A swarm of 8,192 is past that kernel's VMEM, and on the chip
``knn_impl=auto`` gives it the streaming kernel (``pallas_big``): this file
steers there, and that file's case for this cell cannot pass (PERF.md
section 7). Run it in the process that runs that file: only one may load
the TPU's library.
"""

import pytest

from benchmarks import harness
from benchmarks.tests import test_tpu_compile as described
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_tpu_compile import no_compile_cache, topo  # noqa: F401

CELL = "keye-vl2-a3b-ep8-s8k-train-m2"


def test_the_trunks_cell_fills_a_quarter_of_the_chip_and_fits(
    topo, no_compile_cache, monkeypatch  # noqa: F811
):
    from jax.sharding import SingleDeviceSharding

    import jax
    from marl_distributedformation_tpu import utils

    load_config = utils.load_config
    monkeypatch.setattr(
        utils,
        "load_config",
        lambda overrides: load_config(
            ["knn_impl=pallas_big" if o == "knn_impl=pallas" else o for o in overrides]
        ),
    )
    cell = harness.load_cell(CELL, ROOT)
    program, shapes = described._abstract_program(cell)
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = (
        jax.jit(program, donate_argnums=(0, 1))
        .lower(*described._placed(shapes, one_chip, one_chip))
        .compile()
    )
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / described.GIB
    assert described.FLOOR_GIB <= held <= described.CHIP_GIB, (
        f"{CELL}: arguments + temporaries = {held:.2f} GiB on the chip"
    )
    assert "knn_streaming" in compiled.as_text()  # the streaming kernel is in it
