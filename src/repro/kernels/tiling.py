"""TPU tiling shared by the elementwise Pallas kernels.

``adaseg_update`` and ``sync_compress`` stream flat parameter leaves. A TPU
vector register holds an (8, 128) tile, and Mosaic only accepts blocks
whose last two dimensions are multiples of (8, 128) or span the whole
array. So every kernel here sees a flat leaf of ``n`` elements as a
lane-dense slab of ``rows`` × 128 (padded to whole (8, 128) tiles, which
real leaves already are), and one grid step takes ``br`` rows:

* ``br`` is a multiple of 8 sized so that the double-buffered blocks of
  all the kernel's streams fit a quarter of the default scoped VMEM; the
  rest is left to the f32 temporaries of the kernel body.
* The last block may be ragged. Element-wise outputs need no care (the
  out-of-bounds part of a block is never written back); every reduction
  masks by the global element index (:func:`valid`).
* A reduction leaves one (8, 128) f32 tile per grid step (:func:`fold`),
  a VMEM output of ``nb·8 × 128`` that the caller sums. Nothing grows in
  SMEM, which holds only scalars read one element at a time.

The blocks depend on the shape alone, so interpret mode on the CPU runs
the same tiling as the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES

#: Default scoped-VMEM limit of a TPU v5e TensorCore, as the compiler
#: reports it when a kernel asks for more ("scoped vmem limit of 16.00M").
SCOPED_VMEM_BYTES = 16 * 2 ** 20


def interpret_mode() -> bool:
    """Whether a Pallas kernel runs in interpret mode: on the CPU only.

    On a TPU the kernels compile; any other backend has neither, and
    raises rather than quietly interpreting on an accelerator.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; the "
        f"{platform!r} backend has neither (use the reference backend)"
    )


def num_rows(n: int) -> int:
    """Rows of the (rows, 128) slab of an ``n``-element leaf: whole tiles."""
    return max(-(-n // TILE), 1) * SUBLANES


def block_rows(rows: int, streams: int, block: int | None = None) -> int:
    """Rows per grid step for a kernel moving ``streams`` blocks of
    (br, 128) f32 each step. ``block`` caps the elements per step (tests
    use it to force several blocks); it is rounded down to whole tiles."""
    cap = SCOPED_VMEM_BYTES // 4 // (2 * streams * LANES * 4)
    if block is not None:
        cap = min(cap, block // LANES)
    br = max(SUBLANES, cap - cap % SUBLANES)
    return min(br, rows)


def to_slab(x, rows: int):
    """(…, n) → (…, rows, 128), zero-padding the tail of the last axis."""
    n = x.shape[-1]
    pad = rows * LANES - n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], rows, LANES)


def from_slab(y, n: int):
    """Inverse of :func:`to_slab`: (…, rows, 128) → (…, n)."""
    return y.reshape(*y.shape[:-2], -1)[..., :n]


def needs_mask(n: int, rows: int, br: int) -> bool:
    """Whether some grid step holds elements past ``n`` (pad or ragged)."""
    return n != rows * LANES or rows % br != 0


def valid(n: int, br: int, block_index):
    """(br, 128) mask of the elements of grid block ``block_index`` that
    lie inside the leaf."""
    r = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
    return (block_index * br + r) * LANES + c < n


def fold(x, op: str = "sum"):
    """Reduce a (br, 128) block to one (8, 128) tile of partials."""
    x = x.reshape(-1, SUBLANES, LANES)
    return jnp.max(x, axis=0) if op == "max" else jnp.sum(x, axis=0)


def smem():
    """Whole-array SMEM operand (scalars, indexed one element at a time)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def scalar(x, dtype=jnp.float32):
    """A (1, 1) SMEM scalar. Two dimensions, so that a ``vmap`` over the
    kernel batches it to (M, 1, 1) blocks that Mosaic accepts."""
    return jnp.asarray(x, dtype).reshape(1, 1)
