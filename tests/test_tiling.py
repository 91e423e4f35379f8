"""The (8, 128) slab layout and the interpret-mode policy of the kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tiling
from repro.kernels.adaseg_update.ops import adaseg_tree_update


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert tiling.interpret_mode() is interpret


def test_fused_path_refuses_other_backends(monkeypatch):
    """No quiet interpreter on an accelerator that is not a TPU; the
    reference path stays available there."""
    z = {"w": jnp.ones((37,))}
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        adaseg_tree_update(z, z, z, 0.1)
    adaseg_tree_update(z, z, z, 0.1, use_kernel=False)


@pytest.mark.parametrize("streams", [2, 3, 5, 12])
@pytest.mark.parametrize("rows", [8, 1024, 133_000])
@pytest.mark.parametrize("block", [None, 4096])
def test_block_rows_fit_vmem_and_tiles(streams, rows, block):
    br = tiling.block_rows(rows, streams, block)
    assert br % tiling.SUBLANES == 0 and 0 < br <= rows
    # double-buffered f32 blocks of every stream: a quarter of scoped VMEM
    assert 2 * streams * br * tiling.LANES * 4 <= tiling.SCOPED_VMEM_BYTES // 4
    if block is not None:
        assert br * tiling.LANES <= max(block, tiling.TILE)


@pytest.mark.parametrize("n", [1, 896, 1024, 5000])
def test_slab_round_trip_and_mask(n):
    x = jnp.arange(2 * n, dtype=jnp.float32).reshape(2, n)
    rows = tiling.num_rows(n)
    slab = tiling.to_slab(x, rows)
    assert slab.shape == (2, rows, tiling.LANES)
    assert rows % tiling.SUBLANES == 0
    np.testing.assert_array_equal(tiling.from_slab(slab, n), x)
    br = tiling.SUBLANES
    inside = sum(int(jnp.sum(tiling.valid(n, br, i)))
                 for i in range(rows // br))
    assert inside == n
    assert tiling.needs_mask(n, rows, br) == (n != rows * tiling.LANES)
