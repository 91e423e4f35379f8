"""Mamba2 SSD chunked-scan Pallas kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the CUDA kernel's
warp-level scan is replaced by a two-level scheme that matches the TPU
memory/compute hierarchy —

* **intra-chunk** (dense, MXU): C·Bᵀ Gram matrix against a lower-triangular
  decay matrix, all (Q×Q)/(Q×N)/(Q×P) tiles resident in VMEM;
* **inter-chunk** (sequential): a per-(batch·head) running summary state
  S ∈ R^{P×N} carried in VMEM scratch across the innermost grid dimension —
  one decay-scale + rank-Q update per chunk.

The per-head decay ``a`` is read one element at a time from SMEM; ``dt``
arrives in two layouts, a (Q, 1) column and a (1, Q) row, so that both
orientations of its cumulative sum come from masked reductions.

Grid: (batch·heads, num_chunks), chunk axis "arbitrary" (sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref,
                state_scr, *, chunk, heads):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[pl.program_id(0) % heads]                # per-head SMEM scalar
    x = x_ref[0].astype(jnp.float32)                   # (Q, P)
    dt_c = dtc_ref[0].astype(jnp.float32)              # (Q, 1)
    dt_r = dtr_ref[0].astype(jnp.float32)              # (1, Q)
    b = b_ref[0].astype(jnp.float32)                   # (Q, N)
    c = c_ref[0].astype(jnp.float32)                   # (Q, N)

    # Inclusive cumsum of dt·a ≤ 0 as masked lane / sublane sums, once as a
    # column and once as a row (no transposes, no scan primitive).
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da_c, da_r = dt_c * a, dt_r * a
    cum_c = jnp.sum(jnp.where(jj <= ii, da_r, 0.0), axis=1, keepdims=True)
    cum_r = jnp.sum(jnp.where(ii <= jj, da_c, 0.0), axis=0, keepdims=True)
    total = jnp.sum(da_r, axis=1, keepdims=True)       # (1, 1) = cum_{Q−1}
    xdt = x * dt_c                                     # (Q, P)

    # intra-chunk: y_d[i] = Σ_{j≤i} (C_i·B_j) e^{cum_i − cum_j} xdt_j
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (Q, Q)
    l_mat = jnp.where(jj <= ii, scores * jnp.exp(cum_c - cum_r), 0.0)
    y = jax.lax.dot_general(
        l_mat, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (Q, P)

    # inter-chunk: y_off[i] = C_i e^{cum_i} S_in ;  S ← e^{cum_Q} S_in + ΔS
    s_in = state_scr[...]                              # (P, N)
    y += jnp.exp(cum_c) * jax.lax.dot_general(
        c, s_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # ΔS = Σ_j e^{cum_Q − cum_j} xdt_j ⊗ B_j
    w = jnp.exp(total - cum_c) * xdt                   # (Q, P)
    delta = jax.lax.dot_general(
        w, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (P, N)
    state_scr[...] = jnp.exp(total) * s_in + delta

    y_ref[0, ...] = y.astype(y_ref.dtype)


def ssd_scan(
    x, dt, a, b, c, *, chunk: int = 128, interpret: bool = False
):
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, N) → (B, L, H, P)."""
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, l, p)     # (BH, L, P)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, l)         # (BH, L)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, heads=h)
    y = pl.pallas_call(
        kernel,
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, p), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ic: (bh, 0, ic)),
            pl.BlockSpec((1, chunk, n), lambda bh, ic: (bh // h, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ic: (bh // h, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a.astype(jnp.float32), xf, dtf[:, :, None], dtf[:, None, :], b, c)
    return y.reshape(bsz, h, l, p).transpose(0, 2, 1, 3)
