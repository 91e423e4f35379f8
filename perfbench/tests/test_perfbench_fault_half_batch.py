"""A run with the timed path broken underneath (fault: half_batch) comes out
not correct, at a tiny size on the CPU with the chip check skipped."""
import pytest

import tiny_cells


@pytest.mark.parametrize("cell", tiny_cells.ONE_CHIP)
def test_half_batch_is_caught(cell, monkeypatch):
    res = tiny_cells.tiny(cell)
    tiny_cells.plant("half_batch", monkeypatch.setattr)
    out = tiny_cells.run(res)
    assert out["correct"] is False, out["checks"]
