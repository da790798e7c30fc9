"""The controls at a size a test run can hold: the plain reference put in
the program's place in three bfloat16 passes (``high``, the next tier
below the configuration's ``HIGHEST``) and in one, checked by the cell's
own comparison and limit, come out not correct where the program comes
out correct. (On the chip the same is run at the cell's own size by
``bench/readings.py``.)"""
import pytest

from bench import harness
from bench.tests.tiny import tiny_catalog

SEED = 1


@pytest.fixture(scope="module")
def load(tmp_path_factory):
    catalog = harness.Catalog(tiny_catalog(tmp_path_factory.mktemp("ctrl")))
    spec = catalog.cell("ingest-distinct")
    traffic = catalog.traffic(spec["traffic"])
    drv = catalog.load_kind(traffic["kind"])(catalog.config(spec["config"]),
                                             traffic, SEED)
    drv.setup(0.3)
    drv.window(0.3)
    drv.release()
    return drv


def test_program_is_correct(load):
    limit = load.config["check_limits"]["gap_max"]
    attempted, failed, program = load.check()
    assert attempted > 0 and failed == 0 and program["gap_max"] <= limit


@pytest.mark.parametrize("control", ["high", "bf16"])
def test_lower_precision_control_is_caught(load, control):
    limit = load.config["check_limits"]["gap_max"]
    attempted, failed, found = load.check(control)
    assert failed > 0 and found["gap_max"] > limit
