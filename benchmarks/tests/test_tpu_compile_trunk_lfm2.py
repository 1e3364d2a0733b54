"""The cell of the trunk of gated short convolutions (LFM2-24B-A2B's block),
compiled for a described TPU v5e at its real size with nothing run, the
streaming k-NN kernel steered in as ``test_tpu_compile_trunk.py`` does. The
compiler refuses a program that does not fit the chip's 15.75 GiB, so a
compile that ends is the check of the program's memory; its own
``memory_analysis()`` counts a scan over stacked layers well over what the
buffer assignment holds (PERF.md section 7 item 8) and is only held to the
floor of a quarter of the chip. Beside it the harness's set-up holds two states (the
trainer's own draw and the seeded one: parameters and both of Adam's
moments each), which have to fit the chip too. Run it in the process that
runs ``test_tpu_compile.py`` (only one may load the TPU's library).
"""

import jax
from jax.sharding import SingleDeviceSharding

from benchmarks import harness
from benchmarks.tests import test_tpu_compile as described
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_tpu_compile import no_compile_cache, topo  # noqa: F401

CELL = "lfm2-24b-a2b-ep8-s8k-train-m2"
CHIP_GIB = 15.75  # what the compiler gives a program of a v5e's 16 GB


def test_the_cell_fits_the_chip_and_so_do_its_two_set_up_states(
    topo, no_compile_cache, monkeypatch  # noqa: F811
):
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
    parameters = sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(shapes[0].params)
    )
    assert parameters == 452_551_045
    # parameters and both moments, float32, twice: the trainer's and the seeded
    assert 2 * 12 * parameters / described.GIB < CHIP_GIB
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = (
        jax.jit(program, donate_argnums=(0, 1))
        .lower(*described._placed(shapes, one_chip, one_chip))
        .compile()  # raises RESOURCE_EXHAUSTED past the chip's memory
    )
    memory = compiled.memory_analysis()
    arguments = memory.argument_size_in_bytes / described.GIB
    assert described.FLOOR_GIB <= arguments <= CHIP_GIB, (
        f"{CELL}: the program's state is {arguments:.2f} GiB on the chip"
    )
    assert "knn_streaming" in compiled.as_text()  # the streaming kernel is in it
