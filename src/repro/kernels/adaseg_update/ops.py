"""Jit'd pytree-level wrappers for the fused AdaSEG update kernels.

These are the functions the optimizer actually calls
(``core.adaseg.local_step(backend="fused")`` routes through
:func:`adaseg_tree_explore` + :func:`adaseg_tree_anchor`; benchmarks and
parity tests use the one-shot :func:`adaseg_tree_update`). They fall back to
interpret mode automatically off-TPU so the same call site works in CPU
tests and on real hardware (:func:`..tiling.interpret_mode`: CPU only),
and to pure-jnp references with ``use_kernel=False``.

Projections are passed as a static *spec* rather than a callable so the
kernel can fuse them without a semantics fork:

* ``("identity",)``      — unconstrained;
* ``("box", lo, hi)``    — per-element clip, fused into every kernel pass;
* ``("l2", radius)``     — joint ball projection over the WHOLE pytree
  (the paper's ‖·‖_Z on the product space): a two-pass scheme — pass 1
  writes raw updates and reduces per-block/per-leaf partial squared norms,
  the scale min(1, r/‖·‖) is folded on the host, pass 2 applies it while
  accumulating the (Z_t)² statistic.

η handling mirrors the kernels: pass ``eta=`` directly, or ``sum_sq=`` (the
AdaGrad accumulator Σ(Z_τ)²) plus static ``g0``/``d_alpha`` to fuse
η = D·α/√(G₀² + Σ) into the kernels.

Examples
--------
The one-shot fused double update with η computed in-kernel from the
AdaGrad accumulator, box projection fused:

>>> import jax, jax.numpy as jnp, numpy as np
>>> from repro.kernels.adaseg_update.ops import adaseg_tree_update
>>> z = {"w": jnp.array([0.5, -0.8, 0.2])}
>>> m = jax.tree.map(lambda v: 0.3 * v, z)
>>> g = jax.tree.map(lambda v: 0.1 * v, z)
>>> z_t, z_tl, zsq = adaseg_tree_update(z, m, g, sum_sq=4.0, g0=1.0,
...                                     d_alpha=2.0, lo=-1.0, hi=1.0)
>>> ref = adaseg_tree_update(z, m, g, sum_sq=4.0, g0=1.0, d_alpha=2.0,
...                          lo=-1.0, hi=1.0, use_kernel=False)
>>> bool(np.allclose(z_t["w"], ref[0]["w"], rtol=1e-6))
True
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tiling import interpret_mode
from .kernel import (
    adaseg_anchor,
    adaseg_explore,
    adaseg_finish,
    adaseg_update,
)
from .ref import (
    adaseg_anchor_ref,
    adaseg_explore_ref,
    adaseg_finish_ref,
    adaseg_update_ref,
)


def _kernel_kwargs(use_kernel, block, interp):
    """Per-leaf kernel kwargs shared by the tree wrappers."""
    return dict(block=block, interpret=interp) if use_kernel else {}


def _norm_proj(proj, lo, hi):
    """Fold legacy lo/hi kwargs into a projection spec (one-sided boxes
    keep the old jnp.clip semantics via ±inf)."""
    if proj is not None:
        if lo is not None or hi is not None:
            raise ValueError("pass either proj= or lo=/hi=, not both")
        if proj[0] not in ("identity", "box", "l2"):
            raise ValueError(f"unknown projection spec {proj!r}")
        return proj
    if lo is not None or hi is not None:
        return ("box",
                float(lo) if lo is not None else float("-inf"),
                float(hi) if hi is not None else float("inf"))
    return ("identity",)


def _box_bounds(spec):
    return (spec[1], spec[2]) if spec[0] == "box" else (None, None)


def _eta_value(eta, sum_sq, g0, d_alpha):
    """Host-side η (for the 1/(5η²) normalization; kernels recompute it)."""
    if sum_sq is not None:
        return d_alpha / jnp.sqrt(g0 ** 2 + jnp.asarray(sum_sq, jnp.float32))
    return jnp.asarray(eta, jnp.float32)


def _ball_scale(radius, norm_sq):
    """Same formula as core.projections.l2_ball for exact parity."""
    norm = jnp.sqrt(norm_sq)
    return jnp.minimum(1.0, radius / jnp.maximum(norm, 1e-30))


def _flatten_with(treedef, leaves_z, *trees):
    out = [leaves_z]
    for t in trees:
        out.append(treedef.flatten_up_to(t))
    return out


_STATIC = ("g0", "d_alpha", "proj", "lo", "hi", "use_kernel", "block")


@functools.partial(jax.jit, static_argnames=_STATIC)
def adaseg_tree_update(z_star, m_t, g_t, eta=None, *, sum_sq=None,
                       g0=0.0, d_alpha=1.0, proj=None, lo=None, hi=None,
                       use_kernel=True, block=None):
    """Apply the fused EG double update leaf-wise over a parameter pytree.

    Returns (z_t_tree, z_tilde_tree, z_sq) with
    z_sq = Σ_leaves (‖z_t − z*‖² + ‖z_t − z̃‖²) / (5η²).
    """
    spec = _norm_proj(proj, lo, hi)
    leaves_z, treedef = jax.tree.flatten(z_star)
    leaves_z, leaves_m, leaves_g = _flatten_with(treedef, leaves_z, m_t, g_t)
    interp = interpret_mode() if use_kernel else False
    eta_val = _eta_value(eta, sum_sq, g0, d_alpha)
    kw = dict(eta=eta, sum_sq=sum_sq, g0=g0, d_alpha=d_alpha)

    if spec[0] != "l2":
        blo, bhi = _box_bounds(spec)
        zs, zts, parts = [], [], []
        for z, m, g in zip(leaves_z, leaves_m, leaves_g):
            shape = z.shape
            if use_kernel:
                z_t, z_tl, part = adaseg_update(
                    z.reshape(-1), m.reshape(-1), g.reshape(-1),
                    lo=blo, hi=bhi, block=block,
                    interpret=interp, **kw,
                )
                z_t, z_tl = z_t.reshape(shape), z_tl.reshape(shape)
            else:
                z_t, z_tl, part = adaseg_update_ref(z, m, g, lo=blo, hi=bhi,
                                                    **kw)
            zs.append(z_t)
            zts.append(z_tl)
            parts.append(part)
        stat = sum(parts)
    else:
        radius = spec[1]
        # Pass 1: raw candidates + per-leaf partial squared norms.
        raws, norms_t, norms_l = [], [], []
        for z, m, g in zip(leaves_z, leaves_m, leaves_g):
            if use_kernel:
                zt_raw, ztl_raw, (nt, nl) = adaseg_update(
                    z.reshape(-1), m.reshape(-1), g.reshape(-1),
                    raw_norms=True, block=block,
                    interpret=interp, **kw,
                )
            else:
                zt_raw, ztl_raw, _ = adaseg_update_ref(z, m, g, **kw)
                zt_raw, ztl_raw = zt_raw.reshape(-1), ztl_raw.reshape(-1)
                nt = jnp.sum(zt_raw.astype(jnp.float32) ** 2)
                nl = jnp.sum(ztl_raw.astype(jnp.float32) ** 2)
            raws.append((zt_raw, ztl_raw))
            norms_t.append(nt)
            norms_l.append(nl)
        s_t = _ball_scale(radius, sum(norms_t))
        s_l = _ball_scale(radius, sum(norms_l))
        # Pass 2: scale onto the ball, fuse the (Z_t)² statistic.
        zs, zts, parts = [], [], []
        for z, (zt_raw, ztl_raw) in zip(leaves_z, raws):
            shape = z.shape
            if use_kernel:
                z_t, z_tl, part = adaseg_finish(
                    z.reshape(-1), zt_raw, ztl_raw, s_t, s_l,
                    block=block,
                    interpret=interp,
                )
            else:
                z_t, z_tl, part = adaseg_finish_ref(
                    z.reshape(-1), zt_raw, ztl_raw, s_t, s_l,
                )
            zs.append(z_t.reshape(shape))
            zts.append(z_tl.reshape(shape))
            parts.append(part)
        stat = sum(parts)

    z_sq = stat / (5.0 * eta_val ** 2)
    return treedef.unflatten(zs), treedef.unflatten(zts), z_sq


@functools.partial(jax.jit, static_argnames=_STATIC)
def adaseg_tree_explore(z_star, m_t, eta=None, *, sum_sq=None, g0=0.0,
                        d_alpha=1.0, proj=None, lo=None, hi=None,
                        use_kernel=True, block=None):
    """Exploration half-step z_t = Π(z* − η·M_t) over a pytree.

    Returns ``(z_t_tree, m_sq)`` where m_sq = Σ‖M_t‖² (fused into the same
    pass — the V_t(T) diagnostic comes for free).
    """
    spec = _norm_proj(proj, lo, hi)
    leaves_z, treedef = jax.tree.flatten(z_star)
    leaves_z, leaves_m = _flatten_with(treedef, leaves_z, m_t)
    interp = interpret_mode() if use_kernel else False
    kw = dict(eta=eta, sum_sq=sum_sq, g0=g0, d_alpha=d_alpha)
    fn = adaseg_explore if use_kernel else adaseg_explore_ref
    kkw = _kernel_kwargs(use_kernel, block, interp)

    if spec[0] != "l2":
        blo, bhi = _box_bounds(spec)
        outs, msqs = [], []
        for z, m in zip(leaves_z, leaves_m):
            shape = z.shape
            out, _, msq = fn(z.reshape(-1), m.reshape(-1), lo=blo, hi=bhi,
                             **kw, **kkw)
            outs.append(out.reshape(shape))
            msqs.append(msq)
        return treedef.unflatten(outs), sum(msqs)

    radius = spec[1]
    raws, norms, msqs = [], [], []
    for z, m in zip(leaves_z, leaves_m):
        out, nrm, msq = fn(z.reshape(-1), m.reshape(-1), want_norm=True,
                           **kw, **kkw)
        raws.append(out)
        norms.append(nrm)
        msqs.append(msq)
    scale = _ball_scale(radius, sum(norms))
    outs = [
        (scale * r.astype(jnp.float32)).astype(z.dtype).reshape(z.shape)
        for z, r in zip(leaves_z, raws)
    ]
    return treedef.unflatten(outs), sum(msqs)


@functools.partial(jax.jit, static_argnames=_STATIC)
def adaseg_tree_anchor(z_star, z_t, g_t, eta=None, *, sum_sq=None, g0=0.0,
                       d_alpha=1.0, proj=None, lo=None, hi=None,
                       use_kernel=True, block=None):
    """Anchor half-step z̃ = Π(z* − η·g_t) over a pytree, given z_t.

    Returns ``(z_tilde_tree, stat, g_sq)`` with
    stat = Σ_leaves ‖z_t − z*‖² + ‖z_t − z̃‖² (caller divides by 5η²) and
    g_sq = Σ‖g_t‖² fused into the same pass.
    """
    spec = _norm_proj(proj, lo, hi)
    leaves_z, treedef = jax.tree.flatten(z_star)
    leaves_z, leaves_t, leaves_g = _flatten_with(treedef, leaves_z, z_t, g_t)
    interp = interpret_mode() if use_kernel else False
    kw = dict(eta=eta, sum_sq=sum_sq, g0=g0, d_alpha=d_alpha)

    if spec[0] != "l2":
        blo, bhi = _box_bounds(spec)
        outs, stats, gsqs = [], [], []
        for z, zt, g in zip(leaves_z, leaves_t, leaves_g):
            shape = z.shape
            if use_kernel:
                ztl, stat, gsq = adaseg_anchor(
                    z.reshape(-1), zt.reshape(-1), g.reshape(-1),
                    lo=blo, hi=bhi, block=block,
                    interpret=interp, **kw,
                )
                ztl = ztl.reshape(shape)
            else:
                ztl, stat, gsq = adaseg_anchor_ref(z, zt, g, lo=blo, hi=bhi,
                                                   **kw)
            outs.append(ztl)
            stats.append(stat)
            gsqs.append(gsq)
        return treedef.unflatten(outs), sum(stats), sum(gsqs)

    radius = spec[1]
    fn = adaseg_explore if use_kernel else adaseg_explore_ref
    kkw = _kernel_kwargs(use_kernel, block, interp)

    # Pass 1: raw z̃ candidate (an explore with g_t) + partial norms.
    raws, norms, gsqs = [], [], []
    for z, g in zip(leaves_z, leaves_g):
        raw, nrm, gsq = fn(z.reshape(-1), g.reshape(-1), want_norm=True,
                           **kw, **kkw)
        raws.append(raw)
        norms.append(nrm)
        gsqs.append(gsq)
    s_l = _ball_scale(radius, sum(norms))
    # Pass 2: scale z̃ onto the ball; z_t is already final (scale 1).
    outs, stats = [], []
    for z, zt, raw in zip(leaves_z, leaves_t, raws):
        shape = z.shape
        if use_kernel:
            _, ztl, stat = adaseg_finish(
                z.reshape(-1), zt.reshape(-1), raw, 1.0, s_l,
                block=block, interpret=interp,
            )
        else:
            _, ztl, stat = adaseg_finish_ref(
                z.reshape(-1), zt.reshape(-1), raw, 1.0, s_l,
            )
        outs.append(ztl.reshape(shape))
        stats.append(stat)
    return treedef.unflatten(outs), sum(stats), sum(gsqs)
