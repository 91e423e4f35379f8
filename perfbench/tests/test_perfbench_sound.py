"""A whole run of each one-chip cell at a tiny size on the CPU, the chip
check skipped: the program agrees with the reference within the cell's
limits, and the result line has the contract's shape."""
import math

import pytest

import tiny_cells

CELLS = tiny_cells.ONE_CHIP


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = tiny_cells.tiny(cell)
    out = tiny_cells.run(res)
    assert out["correct"], out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in res["end_to_end"]}
    for m in out["metrics"].values():
        assert m["value"] > 0 and math.isfinite(m["value"])
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, v in out["checks"].items():
        assert v["value"] <= v["limit"], name
