"""The control of the correctness check comes out as not correct, and the
program as correct, on tiny cells (``hbench/control.py`` reads the same
numbers at each cell's own size on the chip)."""
import pytest

from hbench import compare, control
from hbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("root"))


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_control_fails_and_program_passes(root, cell, seed):
    r = control.readings(root, cell, seed, 0.5)
    for k in ("outs_mismatch", "state_mismatch", "float_counter_gap",
              "requests_uncounted"):
        assert r["program"][k] <= compare.LIMITS[k], (k, r)
    assert r["control"]["outs_mismatch"] > compare.LIMITS["outs_mismatch"]
