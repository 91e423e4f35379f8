"""The control — the reference in the next precision below the one the
configuration states, put in the program's place — comes out not correct
under the cell's limits, at a tiny size on the CPU, and the reference
repeats itself exactly."""
import importlib

import pytest

from perfbench import check

import tiny_cells


@pytest.mark.parametrize("cell", tiny_cells.ONE_CHIP)
def test_control_fails_and_reference_repeats(cell):
    res = tiny_cells.tiny(cell)
    system = importlib.import_module(
        f"perfbench.systems.{res['config']['system']}")
    cell = system.build(res["config"], res["traffic"], seed=2 ** 33 + 9,
                        seconds=1, tracer=None, engine=False)
    ref = cell.reference()
    assert check.gaps(cell.reference(), ref) == {
        "loss": 0.0, "grad": 0.0, "change": 0.0}
    ok, table = check.judge(check.gaps(cell.reference(system.VARIANTS[0]),
                                       ref), res["limits"])
    assert not ok, table
