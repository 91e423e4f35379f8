"""Fused sync-codec Pallas kernels: the Line-5/7 uplink and server merge.

The Parameter-Server sync round is memory-bound: the reference path forms
``messages = w·payload``, adds the error-feedback residual, reduces the
quantizer scale, quantizes (or top-k masks), writes the new residual and
finally weighted-sums the fleet — each as its own pass over the parameter
vector (~5 tree sweeps before XLA fusion, ~12 HBM passes by the traffic
model). The kernels here fuse every element-wise stage of that pipeline so
each HBM pass does all the work available at that point:

* :func:`uplink_stats`    — quantize pass 1: the scale reduction
  ``max|w·z + ef|`` computed straight from the raw payload and residual
  (``eff`` is never materialized).
* :func:`quantize_uplink` — quantize pass 2: stochastic uniform quantization
  of ``eff`` with the rounding bits generated **in-register** (explicit
  threefry2x32 on the element counter — the shared derivation of
  :mod:`.ref`), the per-worker Line-7 weight applied on load, and the
  residual ``eff − sent`` written back in the same pass.
* :func:`eff_uplink`      — top-k pass 1: materialize ``eff = w·z + ef``
  (the host selects the top-k indices on it).
* :func:`mask_uplink`     — top-k pass 2: apply the survivor mask and write
  the complementary residual in one pass.
* :func:`merge_stacked`   — the server side: weight normalization
  (optionally over survivors), weighted sum over the worker axis and the
  broadcast back, one read + one write of the stacked fleet payload.

Layout (:mod:`..tiling`): leaves arrive worker-stacked and flattened as
``(M, n)`` and stream as ``(M, rows, 128)`` slabs. The uplink kernels run
on an ``(M, nb)`` grid of ``(1, br, 128)`` blocks and read their
per-worker scalars (weight, scale, aliveness, seed words) one element at a
time from whole ``(M,)`` SMEM vectors; the merges run on an ``(nb,)`` grid
over full-fleet ``(M, br, 128)`` blocks. Reductions leave one (8, 128)
tile of partials per block. Dead workers (``alive = 0``) send exact zeros
and keep their residual frozen — the engines' fault semantics, fused.
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
    smem,
    to_slab,
    valid,
)
from .ref import bits_to_uniform, threefry2x32


def _eff_tile(z_ref, ef_ref, w_ref, *, has_w, has_ef):
    """The codec's effective message for the current (worker, block) tile:
    ``w·z (+ ef)`` — computed in-register, never written to HBM unless the
    kernel's job IS to write it."""
    eff = z_ref[0].astype(jnp.float32)
    if has_w:
        eff = w_ref[pl.program_id(0)] * eff
    if has_ef:
        eff = eff + ef_ref[0].astype(jnp.float32)
    return eff


def _kernel_uniform(seed_ref, br):
    """The shared uniform stream for this tile's global element indices:
    threefry2x32 bits generated in-kernel, same derivation as
    :func:`.ref.threefry_uniform`."""
    i = pl.program_id(0)
    idx = (pl.program_id(1) * br
           + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0)) * LANES
    idx = (idx + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1))
    idx = idx.astype(jnp.uint32)
    bits, _ = threefry2x32(seed_ref[2 * i], seed_ref[2 * i + 1],
                           idx, jnp.zeros_like(idx))
    return bits_to_uniform(bits)


def _flag(ref, k, like):
    """Scalar ``ref[k] > 0`` broadcast to a vector mask shaped ``like``."""
    return jnp.full(like.shape, ref[k], jnp.float32) > 0.0


# ---------------------------------------------------------------------------
# Kernel bodies. Argument lists are assembled dynamically from the static
# has_* flags, so optional inputs (weight, residual, aliveness) cost nothing
# when absent. Order: scalars (w, scale, alive, seed) then vectors (z/eff,
# ef, mask), then outputs (sent/eff, ef_out / acc).
# ---------------------------------------------------------------------------

def _stats_kernel(*refs, has_w, has_ef, n, br, mask):
    it = iter(refs)
    w_ref = next(it) if has_w else None
    z_ref = next(it)
    ef_ref = next(it) if has_ef else None
    acc_ref = next(it)
    mag = jnp.abs(_eff_tile(z_ref, ef_ref, w_ref, has_w=has_w,
                            has_ef=has_ef))
    if mask:
        # 0 cannot win the max (the caller clamps the scale to ≥ 1e-30)
        mag = jnp.where(valid(n, br, pl.program_id(1)), mag, 0.0)
    acc_ref[0] = fold(mag, "max")


def _quantize_kernel(*refs, levels, has_w, has_ef, has_alive, br, **_):
    it = iter(refs)
    w_ref = next(it) if has_w else None
    scale_ref = next(it)
    alive_ref = next(it) if has_alive else None
    seed_ref = next(it)
    z_ref = next(it)
    ef_ref = next(it) if has_ef else None
    sent_ref = next(it)
    ef_out_ref = next(it) if has_ef else None

    eff = _eff_tile(z_ref, ef_ref, w_ref, has_w=has_w, has_ef=has_ef)
    scale = scale_ref[pl.program_id(0)]
    y = jnp.abs(eff) / scale * levels
    lo = jnp.floor(y)
    up = _kernel_uniform(seed_ref, br) < (y - lo)
    mag = (lo + up.astype(eff.dtype)) * (scale / levels)
    sent = jnp.sign(eff) * mag
    ef_new = eff - sent
    if has_alive:
        ok = _flag(alive_ref, pl.program_id(0), eff)
        sent = jnp.where(ok, sent, jnp.zeros_like(sent))
        if has_ef:
            ef_new = jnp.where(ok, eff - sent,
                               ef_ref[0].astype(jnp.float32))
    sent_ref[0] = sent.astype(sent_ref.dtype)
    if has_ef:
        ef_out_ref[0] = ef_new.astype(ef_out_ref.dtype)


def _eff_kernel(*refs, has_w, has_ef, **_):
    it = iter(refs)
    w_ref = next(it) if has_w else None
    z_ref = next(it)
    ef_ref = next(it) if has_ef else None
    out_ref = next(it)
    eff = _eff_tile(z_ref, ef_ref, w_ref, has_w=has_w, has_ef=has_ef)
    out_ref[0] = eff.astype(out_ref.dtype)


def _mask_kernel(*refs, has_ef, has_alive, **_):
    it = iter(refs)
    alive_ref = next(it) if has_alive else None
    eff_ref = next(it)
    mask_ref = next(it)
    ef_ref = next(it) if (has_ef and has_alive) else None
    sent_ref = next(it)
    ef_out_ref = next(it) if has_ef else None

    eff = eff_ref[0].astype(jnp.float32)
    sent = jnp.where(mask_ref[0] != 0, eff, jnp.zeros_like(eff))
    ef_new = eff - sent
    if has_alive:
        ok = _flag(alive_ref, pl.program_id(0), eff)
        sent = jnp.where(ok, sent, jnp.zeros_like(sent))
        if has_ef:
            ef_new = jnp.where(ok, eff - sent,
                               ef_ref[0].astype(jnp.float32))
    sent_ref[0] = sent.astype(sent_ref.dtype)
    if has_ef:
        ef_out_ref[0] = ef_new.astype(ef_out_ref.dtype)


def _deliver(out_ref, merged, recv_ref, old_ref, m):
    """Write the merge to every worker row; with ``recv``, non-receiving
    rows keep their ``old`` value."""
    for k in range(m):
        row = merged
        if recv_ref is not None:
            row = jnp.where(_flag(recv_ref, k, merged), merged,
                            old_ref[k].astype(jnp.float32))
        out_ref[k] = row.astype(out_ref.dtype)


def _trimmed_kernel(*refs, m, has_recv, **_):
    """Robust server merge: per-coordinate β-trimmed weighted mean via
    sort-free streaming rank selection — same expressions as
    :func:`.ref.trimmed_merge_ref`, on the full-fleet (M, br, 128) block.

    The rank accumulation is unrolled over the static worker count; the
    tie-break ``k < i`` is static, so each pair costs one compare and the
    selection stays in-register (no sort network, no gather). The trim
    window ``[b, n_incl − 1 − b]`` arrives as two SMEM scalars."""
    it = iter(refs)
    w_ref = next(it)
    incl_ref = next(it)
    win_ref = next(it)
    recv_ref = next(it) if has_recv else None
    z_ref = next(it)
    old_ref = next(it) if has_recv else None
    out_ref = next(it)

    z = [z_ref[k].astype(jnp.float32) for k in range(m)]
    b, top = win_ref[0], win_ref[1]
    num = den = None
    for i in range(m):
        rank = jnp.zeros_like(z[i])
        for k in range(m):
            if k == i:
                continue
            less = (z[k] <= z[i]) if k < i else (z[k] < z[i])
            rank = rank + incl_ref[k] * less.astype(jnp.float32)
        keep = (rank >= b) & (rank <= top) & _flag(incl_ref, i, rank)
        wk = w_ref[i] * keep.astype(jnp.float32)
        num = wk * z[i] if num is None else num + wk * z[i]
        den = wk if den is None else den + wk
    mean = num / jnp.maximum(den, 1e-30)
    _deliver(out_ref, mean, recv_ref, old_ref, m)


def _merge_kernel(*refs, m, has_w, has_recv, **_):
    it = iter(refs)
    recv_ref = next(it) if has_recv else None
    w_ref = next(it) if has_w else None
    z_ref = next(it)
    old_ref = next(it) if has_recv else None
    out_ref = next(it)

    z = z_ref[...].astype(jnp.float32)
    if has_w:
        z = w_ref[...] * z
    mean = jnp.sum(z, axis=0)
    _deliver(out_ref, mean, recv_ref, old_ref, m)


def _outer_kernel(*refs, spec, slots, n, br, mask, **_):
    """Server outer-optimizer pass: form Δ = merged − z and apply one
    moment update + step of the static ``spec`` policy in-register — one
    read of (merged, z, moments), one write of (z′, moments′) per block,
    the single extra HBM pass the two-level scheme costs. Each block's
    ``Σ Δ²`` partials land in a (8, 128) tile for the ‖Δ‖ telemetry.
    Exact expression sequence of :func:`.ref.outer_apply_ref`; adam's bias
    corrections ``1 − β^t`` arrive precomputed as SMEM scalars."""
    it = iter(refs)
    bias_ref = next(it)
    g_ref = next(it)
    z_ref = next(it)
    mom_refs = [next(it) for _ in range(slots)]
    z_out_ref = next(it)
    mom_out_refs = [next(it) for _ in range(slots)]
    acc_ref = next(it)

    kind = spec[0]
    g = g_ref[0].astype(jnp.float32)
    zz = z_ref[0].astype(jnp.float32)
    d = g - zz
    if kind == "momentum":
        _, lr, beta = spec
        m_new = jnp.float32(beta) * mom_refs[0][0].astype(jnp.float32) + d
        z_new = zz + jnp.float32(lr) * m_new
        mom_new = (m_new,)
    elif kind == "nesterov":
        _, lr, beta = spec
        m_new = jnp.float32(beta) * mom_refs[0][0].astype(jnp.float32) + d
        z_new = zz + jnp.float32(lr) * (d + jnp.float32(beta) * m_new)
        mom_new = (m_new,)
    else:                                               # adam
        _, lr, b1, b2, eps = spec
        m_new = (jnp.float32(b1) * mom_refs[0][0].astype(jnp.float32)
                 + jnp.float32(1.0 - b1) * d)
        v_new = (jnp.float32(b2) * mom_refs[1][0].astype(jnp.float32)
                 + jnp.float32(1.0 - b2) * d * d)
        m_hat = m_new / bias_ref[0]
        v_hat = v_new / bias_ref[1]
        z_new = zz + jnp.float32(lr) * m_hat / (jnp.sqrt(v_hat)
                                                + jnp.float32(eps))
        mom_new = (m_new, v_new)
    z_out_ref[0] = z_new.astype(z_out_ref.dtype)
    for out_ref, mn in zip(mom_out_refs, mom_new):
        out_ref[0] = mn.astype(out_ref.dtype)
    dsq = d * d
    if mask:
        dsq = jnp.where(valid(n, br, pl.program_id(0)), dsq, 0.0)
    acc_ref[0] = fold(dsq)


# ---------------------------------------------------------------------------
# Per-leaf entry points: worker-stacked flat (M, n) leaves; pytree
# composition and the reference/fused switch live in ops.py.
# ---------------------------------------------------------------------------

def _uplink_call(kernel, scalars, vectors, out_vectors, acc, m, n, block,
                 interpret, dtype, **static):
    """Shared pallas_call plumbing for the (M, nb)-grid uplink kernels:
    ``scalars`` are whole (M,)-vectors in SMEM, ``vectors`` stream as
    (1, br, 128) blocks, and ``acc`` adds one (8, 128) partial tile per
    block. Every kernel gets the block geometry ``n``, ``br``, ``mask``."""
    rows = num_rows(n)
    br = block_rows(rows, len(vectors) + out_vectors, block)
    nb = pl.cdiv(rows, br)
    row = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    out_specs = [row] * out_vectors
    out_shape = [jax.ShapeDtypeStruct((m, rows, LANES), dtype)] * out_vectors
    if acc:
        out_specs.append(pl.BlockSpec((1, SUBLANES, LANES),
                                      lambda i, j: (i, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((m, nb * SUBLANES, LANES),
                                              jnp.float32))
    outs = pl.pallas_call(
        functools.partial(kernel, n=n, br=br, mask=needs_mask(n, rows, br),
                          **static),
        grid=(m, nb),
        in_specs=[smem()] * len(scalars) + [row] * len(vectors),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, *(to_slab(v, rows) for v in vectors))
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    return [from_slab(o, n) for o in outs[:out_vectors]] + outs[out_vectors:]


def _fleet_call(kernel, scalars, vectors, out_dtypes, *, m, n, block,
                interpret, tiles=(), acc=False, **static):
    """Shared pallas_call plumbing for the (nb,)-grid server kernels:
    ``vectors`` stream as full-fleet (M, br, 128) blocks; ``out_dtypes``
    name the leaf-shaped outputs, and ``acc`` adds one (8, 128) partial
    tile per block. Every kernel gets ``m`` and the block geometry."""
    rows = num_rows(n)
    br = block_rows(rows, m * (len(vectors) + len(out_dtypes)), block)
    nb = pl.cdiv(rows, br)
    full = pl.BlockSpec((m, br, LANES), lambda j: (0, j, 0))
    out_specs = [full] * len(out_dtypes)
    out_shape = [jax.ShapeDtypeStruct((m, rows, LANES), d)
                 for d in out_dtypes]
    if acc:
        out_specs.append(pl.BlockSpec((1, SUBLANES, LANES),
                                      lambda j: (0, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, nb * SUBLANES, LANES),
                                              jnp.float32))
    outs = pl.pallas_call(
        functools.partial(kernel, m=m, n=n, br=br,
                          mask=needs_mask(n, rows, br), **static),
        grid=(nb,),
        in_specs=([smem()] * len(scalars)
                  + [pl.BlockSpec(t.shape, lambda j: (0,) * t.ndim)
                     for t in tiles]
                  + [full] * len(vectors)),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, *tiles, *(to_slab(v, rows) for v in vectors))
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    k = len(out_dtypes)
    return [from_slab(o, n) for o in outs[:k]] + outs[k:]


def _vec(x, dtype=jnp.float32):
    return jnp.asarray(x, dtype).reshape(-1)


def uplink_stats(z, w=None, ef=None, *, block: int | None = None,
                 interpret: bool = False):
    """Quantize pass 1 on a stacked (M, n) leaf: per-worker ``max|w·z+ef|``
    without materializing the effective message. Returns ``(M,)`` maxima
    (caller applies the 1e-30 clamp)."""
    m, n = z.shape
    scalars = [] if w is None else [_vec(w)]
    vectors = [z] + ([] if ef is None else [ef])
    (acc,) = _uplink_call(_stats_kernel, scalars, vectors, 0, True, m, n,
                          block, interpret, z.dtype, has_w=w is not None,
                          has_ef=ef is not None)
    return jnp.max(acc, axis=(1, 2))


def quantize_uplink(z, seeds, scale, w=None, ef=None, alive=None, *,
                    levels: float, block: int | None = None,
                    interpret: bool = False):
    """Quantize pass 2: one fused sweep doing EF add + w scaling +
    stochastic quantization (threefry bits in-register) + residual
    write-back on a stacked (M, n) leaf.

    ``seeds`` is (M, 2) uint32 — the per-(worker, leaf) keys of the shared
    derivation; ``scale`` is (M,) clamped maxima from :func:`uplink_stats`.
    Returns ``(sent, ef_new)`` (``ef_new`` is None when ``ef`` is None).
    """
    m, n = z.shape
    scalars = [] if w is None else [_vec(w)]
    scalars.append(_vec(scale))
    if alive is not None:
        scalars.append(_vec(alive))
    scalars.append(_vec(seeds, jnp.uint32))
    vectors = [z] + ([] if ef is None else [ef])
    outs = _uplink_call(
        _quantize_kernel, scalars, vectors, 1 + (ef is not None), False, m,
        n, block, interpret, z.dtype, levels=levels,
        has_w=w is not None, has_ef=ef is not None,
        has_alive=alive is not None,
    )
    return (outs[0], outs[1]) if ef is not None else (outs[0], None)


def eff_uplink(z, w=None, ef=None, *, block: int | None = None,
               interpret: bool = False):
    """Top-k pass 1: materialize ``eff = w·z + ef`` in one fused sweep."""
    m, n = z.shape
    scalars = [] if w is None else [_vec(w)]
    vectors = [z] + ([] if ef is None else [ef])
    (out,) = _uplink_call(_eff_kernel, scalars, vectors, 1, False, m, n,
                          block, interpret, z.dtype, has_w=w is not None,
                          has_ef=ef is not None)
    return out


def mask_uplink(eff, mask, ef=None, alive=None, *, want_ef: bool = True,
                block: int | None = None, interpret: bool = False):
    """Top-k pass 2: apply the survivor mask and write the complementary
    residual in the same sweep. ``ef`` (the pre-round residual) is only
    read when ``alive`` is given, to freeze dead workers' memory.
    Returns ``(sent, ef_new)`` (``ef_new`` None when ``want_ef`` is False).
    """
    m, n = eff.shape
    scalars = [] if alive is None else [_vec(alive)]
    vectors = [eff, mask]
    if want_ef and alive is not None:
        vectors.append(jnp.zeros_like(eff) if ef is None else ef)
    outs = _uplink_call(_mask_kernel, scalars, vectors, 1 + want_ef, False,
                        m, n, block, interpret, eff.dtype, has_ef=want_ef,
                        has_alive=alive is not None)
    return (outs[0], outs[1]) if want_ef else (outs[0], None)


def merge_stacked(z, w=None, recv=None, old=None, *, normalize: bool = False,
                  block: int | None = None, interpret: bool = False):
    """Fused server merge on a stacked (M, n) leaf: weighted sum over the
    worker axis (weights optionally normalized first — the Line-7
    renormalization over survivors) broadcast back to every worker, with
    non-receiving workers (``recv`` falsy) keeping ``old``.
    """
    m, n = z.shape
    scalars = [] if recv is None else [_vec(recv)]
    tiles = []
    if w is not None:
        w = _vec(w)
        if normalize:
            w = w / jnp.sum(w)
        tiles.append(jnp.broadcast_to(w.reshape(m, 1, 1), (m, 1, LANES)))
    vectors = [z] + ([] if recv is None else [z if old is None else old])
    (out,) = _fleet_call(
        _merge_kernel, scalars, vectors, [z.dtype], m=m, n=n, block=block,
        interpret=interpret, tiles=tiles, has_w=w is not None,
        has_recv=recv is not None,
    )
    return out


def trimmed_merge_stacked(z, w, incl, recv=None, old=None, *, trim: int,
                          block: int | None = None, interpret: bool = False):
    """Fused *robust* server merge on a stacked (M, n) leaf: per-coordinate
    ``trim``-per-side trimmed weighted mean over the included rows
    (``incl`` — 0/1, dead/unselected lanes never enter the order
    statistics), renormalized over the survivors' weight mass and broadcast
    back. ``trim = ⌊(M−1)/2⌋`` is the coordinate median. Same (nb,)-grid
    full-fleet block layout as :func:`merge_stacked`; ``recv``/``old`` gate
    delivery identically.
    """
    m, n = z.shape
    incl = _vec(incl)
    n_incl = jnp.sum(incl)
    b = jnp.minimum(jnp.float32(trim), jnp.floor((n_incl - 1.0) * 0.5))
    scalars = [_vec(w), incl, jnp.stack([b, n_incl - 1.0 - b])]
    if recv is not None:
        scalars.append(_vec(recv))
    vectors = [z] + ([] if recv is None else [z if old is None else old])
    (out,) = _fleet_call(
        _trimmed_kernel, scalars, vectors, [z.dtype], m=m, n=n, block=block,
        interpret=interpret, has_recv=recv is not None,
    )
    return out


def outer_apply(merged, z, mom, t, *, spec, block: int | None = None,
                interpret: bool = False):
    """Fused server outer-optimizer step on one server leaf ``(1, n)``:
    Δ = merged − z, one moment update + apply of the static ``spec``
    policy (``repro.ps.server_opt`` tuples), all in-register on the same
    ``(nb,)``-grid blocks as :func:`merge_stacked`.

    ``mom`` is the tuple of moment leaves (matched to the policy's slot
    count), ``t`` the f32 round count before this step (only adam's bias
    correction reads it). Returns ``(z_new, mom_new, delta_sq)`` with
    ``delta_sq = Σ Δ²`` summed from the per-block partial tiles.
    """
    if z.shape[0] != 1:
        raise ValueError(f"outer_apply takes a (1, n) leaf, got {z.shape}")
    n = z.shape[1]
    bias = jnp.ones((2,), jnp.float32)
    if spec[0] == "adam":
        t_new = jnp.asarray(t, jnp.float32) + 1.0
        bias = jnp.stack([1.0 - jnp.float32(spec[2]) ** t_new,
                          1.0 - jnp.float32(spec[3]) ** t_new])
    outs = _fleet_call(
        _outer_kernel, [bias], [merged, z, *mom],
        [z.dtype] + [mm.dtype for mm in mom], m=1, n=n, block=block,
        interpret=interpret, spec=spec, slots=len(mom), acc=True,
    )
    slots = len(mom)
    return outs[0], tuple(outs[1:1 + slots]), jnp.sum(outs[-1])
