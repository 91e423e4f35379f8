"""Fused LocalAdaSEG extragradient-update Pallas kernels.

The optimizer hot loop is memory-bound: the naive implementation reads
z*, M_t, g_t and writes z_t, z̃ plus re-reads both outputs to form the
adaptive-learning-rate statistic (Z_t)² — ≈9 HBM passes over the parameter
vector. The kernels here fuse the learning-rate computation, the projection,
the updates and the (Z_t)² partial reduction so each pass over HBM does all
the element-wise work at once.

Three per-leaf primitives (composed over pytrees by :mod:`.ops`, and into
the optimizer step by ``core.adaseg.local_step(backend="fused")``):

* :func:`adaseg_explore` — exploration half-step z_t = Π(z* − η·M_t) with a
  fused ‖M_t‖² reduction (1 output pass instead of update + norm passes).
* :func:`adaseg_anchor`  — anchor half-step z̃ = Π(z* − η·g_t) that also
  accumulates the (Z_t)² statistic ‖z_t − z*‖² + ‖z_t − z̃‖² and ‖g_t‖²
  in the same pass.
* :func:`adaseg_update`  — the one-shot double update (both M_t and g_t
  known), used by benchmarks and parity tests.

η fusion: instead of materializing η on the host, each kernel can take the
running AdaGrad accumulator Σ(Z_τ)² as its SMEM scalar and compute
η = D·α/√(G₀² + Σ(Z_τ)²) in-register (``sum_sq=...`` instead of ``eta=...``).

Projections: the box clip Π_[lo,hi] fuses into every kernel directly. The
l2-ball projection needs the *global* norm of the candidate point, so it is
a two-pass scheme: pass 1 writes the raw (unprojected) update and reduces
per-block partial squared norms (``want_norm=True`` / :func:`adaseg_raw`),
the caller folds the partials into the scale min(1, r/‖·‖), and pass 2
(:func:`adaseg_finish`) applies the scale while accumulating (Z_t)².

Layout (:mod:`..tiling`): a flat leaf is a lane-dense (rows, 128) slab,
the grid is 1-D over VMEM-sized (br, 128) blocks, the η schedule arrives
as an SMEM scalar, and each reduction leaves one (8, 128) f32 tile of
partials per block, summed by the caller. Partial sums mask everything
past the leaf, so neither the zero padding nor a ragged last block can
leak into a statistic (e.g. clip(0) under a box with lo > 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..tiling import (
    LANES,
    SUBLANES,
    block_rows,
    fold,
    from_slab,
    needs_mask,
    num_rows,
    scalar,
    smem,
    to_slab,
    valid,
)


def _resolve_eta(sched_ref, *, fuse_eta, g0_sq, d_alpha):
    """η from the SMEM schedule scalar: either η itself, or the AdaGrad
    accumulator Σ(Z_τ)² with η = D·α/√(G₀² + Σ) computed in-register."""
    s = sched_ref[0, 0]
    if fuse_eta:
        return d_alpha / jnp.sqrt(g0_sq + s)
    return s


def _sched_arg(eta, sum_sq):
    """(SMEM scalar, fuse_eta flag) from the mutually-exclusive η inputs."""
    if (eta is None) == (sum_sq is None):
        raise ValueError("pass exactly one of eta= or sum_sq=")
    if sum_sq is not None:
        return scalar(sum_sq), True
    return scalar(eta), False


def _partial(x, *, n, br, mask):
    """This block's (8, 128) partial sums of ``x``, masked to the leaf."""
    if mask:
        x = jnp.where(valid(n, br, pl.program_id(0)), x, 0.0)
    return fold(x)


# ---------------------------------------------------------------------------
# Kernel bodies. ``tile`` is functools.partial(_partial, n=, br=, mask=).
# ---------------------------------------------------------------------------

def _explore_kernel(sched_ref, z_ref, m_ref, out_ref, *acc_refs, tile,
                    lo, hi, fuse_eta, g0_sq, d_alpha, want_norm):
    eta = _resolve_eta(sched_ref, fuse_eta=fuse_eta, g0_sq=g0_sq,
                       d_alpha=d_alpha)
    z = z_ref[...].astype(jnp.float32)                 # update math in f32
    m = m_ref[...].astype(jnp.float32)
    out = z - eta * m
    if lo is not None:
        out = jnp.clip(out, lo, hi)
    out_ref[...] = out.astype(out_ref.dtype)
    acc_refs[0][...] = tile(m * m)                     # fused ‖M_t‖² partial
    if want_norm:
        acc_refs[1][...] = tile(out * out)             # raw/l2 pass 1: ‖out‖²


def _anchor_kernel(sched_ref, z_ref, zt_ref, g_ref, ztl_ref, stat_ref,
                   gsq_ref, *, tile, lo, hi, fuse_eta, g0_sq, d_alpha):
    eta = _resolve_eta(sched_ref, fuse_eta=fuse_eta, g0_sq=g0_sq,
                       d_alpha=d_alpha)
    z = z_ref[...].astype(jnp.float32)
    zt = zt_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    ztl = z - eta * g
    if lo is not None:
        ztl = jnp.clip(ztl, lo, hi)
    ztl_ref[...] = ztl.astype(ztl_ref.dtype)
    d1 = zt - z
    d2 = zt - ztl
    stat_ref[...] = tile(d1 * d1 + d2 * d2)
    gsq_ref[...] = tile(g * g)                         # fused ‖g_t‖² partial


def _finish_kernel(scales_ref, z_ref, zt_raw_ref, ztl_raw_ref,
                   zt_ref, ztl_ref, stat_ref, *, tile):
    s_t = scales_ref[0, 0]
    s_l = scales_ref[0, 1]
    z = z_ref[...].astype(jnp.float32)
    zt = s_t * zt_raw_ref[...].astype(jnp.float32)
    ztl = s_l * ztl_raw_ref[...].astype(jnp.float32)
    zt_ref[...] = zt.astype(zt_ref.dtype)
    ztl_ref[...] = ztl.astype(ztl_ref.dtype)
    d1 = zt - z
    d2 = zt - ztl
    stat_ref[...] = tile(d1 * d1 + d2 * d2)


def _update_kernel(sched_ref, z_ref, m_ref, g_ref, zt_ref, ztl_ref,
                   *acc_refs, tile, lo, hi, fuse_eta, g0_sq, d_alpha,
                   raw_norms):
    eta = _resolve_eta(sched_ref, fuse_eta=fuse_eta, g0_sq=g0_sq,
                       d_alpha=d_alpha)
    z = z_ref[...].astype(jnp.float32)
    z_t = z - eta * m_ref[...].astype(jnp.float32)
    z_tl = z - eta * g_ref[...].astype(jnp.float32)
    if raw_norms:
        # l2 pass 1: write raw candidates, reduce their squared norms.
        zt_ref[...] = z_t.astype(zt_ref.dtype)
        ztl_ref[...] = z_tl.astype(ztl_ref.dtype)
        acc_refs[0][...] = tile(z_t * z_t)
        acc_refs[1][...] = tile(z_tl * z_tl)
        return
    if lo is not None:
        z_t = jnp.clip(z_t, lo, hi)
        z_tl = jnp.clip(z_tl, lo, hi)
    zt_ref[...] = z_t.astype(zt_ref.dtype)
    ztl_ref[...] = z_tl.astype(ztl_ref.dtype)
    d1 = z_t - z
    d2 = z_t - z_tl
    acc_refs[0][...] = tile(d1 * d1 + d2 * d2)


# ---------------------------------------------------------------------------
# Per-leaf entry points (flat 1-D vectors; pytree composition in ops.py).
# ---------------------------------------------------------------------------

def _leaf_call(kernel, scalars, vectors, n_out, n_acc, *, block, interpret,
               **static):
    """Shared pallas_call plumbing: ``scalars`` go to SMEM, the flat
    ``vectors`` stream as (br, 128) blocks into ``n_out`` leaf-shaped
    outputs and ``n_acc`` partial-sum tiles. Returns the outputs as flat
    leaves followed by the summed reductions."""
    (n,) = vectors[0].shape
    rows = num_rows(n)
    br = block_rows(rows, len(vectors) + n_out, block)
    nb = pl.cdiv(rows, br)
    vec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    acc = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    tile = functools.partial(_partial, n=n, br=br,
                             mask=needs_mask(n, rows, br))
    outs = pl.pallas_call(
        functools.partial(kernel, tile=tile, **static),
        grid=(nb,),
        in_specs=[smem()] * len(scalars) + [vec] * len(vectors),
        out_specs=[vec] * n_out + [acc] * n_acc,
        out_shape=(
            [jax.ShapeDtypeStruct((rows, LANES), vectors[0].dtype)] * n_out
            + [jax.ShapeDtypeStruct((nb * SUBLANES, LANES), jnp.float32)]
            * n_acc
        ),
        interpret=interpret,
    )(*scalars, *(to_slab(v, rows) for v in vectors))
    return ([from_slab(o, n) for o in outs[:n_out]]
            + [jnp.sum(a) for a in outs[n_out:]])


def adaseg_explore(z_star, m_t, eta=None, *, sum_sq=None, g0=0.0,
                   d_alpha=1.0, lo=None, hi=None, want_norm=False,
                   block: int | None = None, interpret: bool = False):
    """Exploration half-step z_t = Π_box(z* − η·m_t) on a flat leaf.

    Returns ``(z_t, norm_partial, msq_partial)`` — ``norm_partial`` is
    ‖z_t‖² when ``want_norm`` (the l2 two-pass raw mode; pass ``lo=None``),
    else 0; ``msq_partial`` is the fused ‖m_t‖² reduction. ``block`` caps
    the elements per grid step (default: sized to VMEM).
    """
    sched, fuse = _sched_arg(eta, sum_sq)
    outs = _leaf_call(
        _explore_kernel, [sched], [z_star, m_t], 1, 1 + want_norm,
        block=block, interpret=interpret, lo=lo, hi=hi, fuse_eta=fuse,
        g0_sq=g0 ** 2, d_alpha=d_alpha, want_norm=want_norm,
    )
    norm = outs[2] if want_norm else jnp.float32(0.0)
    return outs[0], norm, outs[1]


def adaseg_anchor(z_star, z_t, g_t, eta=None, *, sum_sq=None, g0=0.0,
                  d_alpha=1.0, lo=None, hi=None, block: int | None = None,
                  interpret: bool = False):
    """Anchor half-step z̃ = Π_box(z* − η·g_t) given the materialized z_t.

    Returns ``(z_tilde, stat_partial, gsq_partial)`` with
    ``stat_partial = ‖z_t − z*‖² + ‖z_t − z̃‖²`` (caller divides by 5η²).
    """
    sched, fuse = _sched_arg(eta, sum_sq)
    ztl, stat, gsq = _leaf_call(
        _anchor_kernel, [sched], [z_star, z_t, g_t], 1, 2, block=block,
        interpret=interpret, lo=lo, hi=hi, fuse_eta=fuse, g0_sq=g0 ** 2,
        d_alpha=d_alpha,
    )
    return ztl, stat, gsq


def adaseg_finish(z_star, zt_raw, ztl_raw, scale_t, scale_tl, *,
                  block: int | None = None, interpret: bool = False):
    """l2 pass 2: scale raw candidates onto the ball, fuse the (Z_t)² stat.

    Returns ``(z_t, z_tilde, stat_partial)``.
    """
    scales = jnp.stack([
        jnp.asarray(scale_t, jnp.float32), jnp.asarray(scale_tl, jnp.float32)
    ]).reshape(1, 2)
    z_t, z_tl, stat = _leaf_call(
        _finish_kernel, [scales], [z_star, zt_raw, ztl_raw], 2, 1,
        block=block, interpret=interpret,
    )
    return z_t, z_tl, stat


def adaseg_update(
    z_star, m_t, g_t, eta=None, *, sum_sq=None, g0=0.0, d_alpha=1.0,
    lo=None, hi=None, raw_norms: bool = False, block: int | None = None,
    interpret: bool = False,
):
    """One-shot fused EG double update on a flat leaf (both oracles known).

    Default mode returns ``(z_t, z_tilde, zsq_partial_sum)`` with the box
    clip applied when ``lo``/``hi`` are given. ``raw_norms=True`` is the l2
    two-pass raw mode: no projection, and the partials are
    ``(‖z_t‖², ‖z̃‖²)`` for the caller's ball-scale computation.
    """
    sched, fuse = _sched_arg(eta, sum_sq)
    outs = _leaf_call(
        _update_kernel, [sched], [z_star, m_t, g_t], 2, 1 + raw_norms,
        block=block, interpret=interpret, lo=lo, hi=hi, fuse_eta=fuse,
        g0_sq=g0 ** 2, d_alpha=d_alpha, raw_norms=raw_norms,
    )
    if raw_norms:
        return outs[0], outs[1], (outs[2], outs[3])
    return outs[0], outs[1], outs[2]
