"""The hybrid trunk's cell (Solar-Open2-250B's period), compiled for a
described TPU v5e at its real size with nothing run, by
``test_tpu_compile_trunk.py``'s own test with this cell's name in the place
of its ``CELL``: the streaming k-NN kernel steered in, arguments plus
temporaries between 4 GiB and the chip's 16 GiB. A file of its own because
this PR may edit no file the benchmark has; run it in the process that runs
that file (only one may load the TPU's library).
"""

from benchmarks.tests import test_tpu_compile_trunk as keye
from benchmarks.tests.test_tpu_compile import no_compile_cache, topo  # noqa: F401

CELL = "solar-open2-250b-ep40-tp8-s8k-train-m1"


def test_the_hybrid_trunks_cell_fills_a_quarter_of_the_chip_and_fits(
    topo, no_compile_cache, monkeypatch  # noqa: F811
):
    monkeypatch.setattr(keye, "CELL", CELL)
    keye.test_the_trunks_cell_fills_a_quarter_of_the_chip_and_fits(
        topo, no_compile_cache, monkeypatch
    )
