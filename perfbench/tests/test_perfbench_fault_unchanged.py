"""A run with the timed path broken underneath (fault: unchanged) comes out
not correct, at a tiny size on the CPU with the chip check skipped."""
import pytest

import tiny_cells


@pytest.mark.parametrize("cell", tiny_cells.ONE_CHIP)
def test_unchanged_is_caught(cell, monkeypatch):
    res = tiny_cells.tiny(cell)
    tiny_cells.plant("unchanged", monkeypatch.setattr)
    out = tiny_cells.run(res)
    assert out["correct"] is False, out["checks"]
