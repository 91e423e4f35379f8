"""Jit'd pytree-level wrappers for the fused sync-codec kernels.

These are the functions the Parameter-Server runtime calls when a config
says ``codec_backend="fused"``: :func:`codec_uplink_stacked` replaces the
serial engines' message-scale-compress-residual tree pipeline (and
:func:`codec_uplink` the per-shard / single-worker form), while
:func:`sync_merge_stacked` replaces the weighted-sum-broadcast server side
(``core.adaseg.sync_weighted_stacked(backend="fused")`` routes here too).
They run in interpret mode on the CPU (:func:`..tiling.interpret_mode`)
and fall back to the pure-jnp references in :mod:`.ref` with ``use_kernel=False``.

Codecs are passed as a static *spec* (mirroring the projection specs of
``kernels.adaseg_update``) so the kernels can fuse them without a semantics
fork — ``repro.ps.compress`` compressors export theirs as
``SyncCompressor.codec_spec``:

* ``("identity",)``        — no codec: the uplink is just the w-scaling;
* ``("quantize", bits)``   — stochastic uniform quantization, two fused
  passes (scale reduction; EF add + quantize + residual write-back with the
  threefry rounding bits generated in-kernel);
* ``("topk", fraction)``   — top-k sparsification, two fused passes around
  a host-side ``lax.top_k`` index selection (EF add / mask + residual).

RNG: ``rngs`` are the engines' per-worker compression keys; per-leaf keys
are derived with the same ``jax.random.split`` chain the reference
compressors use, and the per-*element* bits inside the kernel follow the
shared threefry derivation of :mod:`.ref` — which is exactly why the fused
and reference stochastic-quantize paths agree to float tolerance.

Examples
--------
A q8 uplink for two stacked workers, fused vs reference:

>>> import jax, jax.numpy as jnp, numpy as np
>>> from repro.kernels.sync_compress.ops import codec_uplink_stacked
>>> z = {"p": jnp.array([[0.5, -1.0, 2.0], [1.5, 0.25, -0.75]])}
>>> ef = {"p": jnp.zeros((2, 3))}
>>> w = jnp.array([0.25, 0.75])
>>> rngs = jax.random.split(jax.random.PRNGKey(0), 2)
>>> sent, ef_new = codec_uplink_stacked(z, rngs, w=w, ef=ef,
...                                     codec=("quantize", 8))
>>> ref, ef_ref = codec_uplink_stacked(z, rngs, w=w, ef=ef,
...                                    codec=("quantize", 8),
...                                    use_kernel=False)
>>> bool(np.allclose(sent["p"], ref["p"], rtol=1e-5))
True
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..tiling import interpret_mode
from . import ref as _ref
from .kernel import (
    eff_uplink,
    mask_uplink,
    merge_stacked,
    outer_apply,
    quantize_uplink,
    trimmed_merge_stacked,
    uplink_stats,
)

PyTree = Any

_CODECS = ("identity", "quantize", "topk")


def _check_codec(codec):
    if not (isinstance(codec, tuple) and codec and codec[0] in _CODECS):
        raise ValueError(f"unknown codec spec {codec!r}")
    return codec


def _flat2(leaf):
    """Worker-stacked leaf (M, ...) → (M, n)."""
    return leaf.reshape(leaf.shape[0], -1)


def _topk_mask(eff2, fraction):
    """Per-worker top-k survivor mask on a flat (M, n) leaf — the same
    index selection (``lax.top_k`` on magnitudes, ties to lowest index) the
    reference ``TopKCompressor`` scatters through, so fused ≡ reference
    entry-for-entry."""
    n = eff2.shape[1]
    k = max(1, int(math.ceil(fraction * n)))

    def one(e):
        _, idx = jax.lax.top_k(jnp.abs(e), k)
        return jnp.zeros_like(e).at[idx].set(1.0)

    return jax.vmap(one)(eff2)


_STATIC = ("codec", "use_kernel", "block")


@functools.partial(jax.jit, static_argnames=_STATIC)
def codec_uplink_stacked(payload, rngs, w=None, ef=None, alive=None, *,
                         codec, use_kernel=True, block=None):
    """The whole Line-5 uplink for M stacked workers in fused sweeps:
    per-leaf, apply the Line-7 weight ``w``, add the error-feedback
    residual ``ef``, run the codec, and write the new residual back.

    ``payload``/``ef`` are worker-stacked pytrees (leading axis M);
    ``rngs`` is (M, 2) per-worker keys (consumed only by stochastic
    codecs); ``w`` (M,) weights (None = no scaling — the async wire
    format); ``alive`` (M,) masks dead workers (they send exact zeros and
    keep their residual frozen). Returns ``(sent, ef_new)`` with
    ``ef_new = ef`` (identity) or None when ``ef`` is None.
    """
    kind = _check_codec(codec)[0]
    if rngs is not None:
        rngs = _ref.key_data(rngs)      # typed keys → raw uint32 (M, 2)
    leaves, treedef = jax.tree.flatten(payload)
    ef_leaves = (treedef.flatten_up_to(ef) if ef is not None
                 else [None] * len(leaves))
    interp = interpret_mode() if use_kernel else False
    w = None if w is None else jnp.asarray(w, jnp.float32)
    alive = None if alive is None else jnp.asarray(alive, jnp.float32)

    if kind == "quantize":
        levels = float(2 ** codec[1] - 1)
        leaf_keys = jax.vmap(
            lambda k: jax.random.split(k, len(leaves))
        )(jnp.asarray(rngs))                              # (M, L, 2)

    sents, ef_news = [], []
    for li, (z, e) in enumerate(zip(leaves, ef_leaves)):
        shape = z.shape
        z2 = _flat2(z)
        e2 = None if e is None else _flat2(e)
        kw = dict(block=block, interpret=interp)

        if kind == "identity":
            if use_kernel:
                sent2 = eff_uplink(z2, w, e2, **kw) if (
                    w is not None or e2 is not None) else z2
            else:
                sent2 = _ref.eff_uplink_ref(z2, ef=e2, w=None if w is None
                                            else w[:, None])
            ef2 = e2
        elif kind == "quantize":
            keys = leaf_keys[:, li]                       # (M, 2)
            if use_kernel:
                stats = uplink_stats(z2, w, e2, **kw)
                scale = jnp.maximum(stats, 1e-30)
                sent2, ef2 = quantize_uplink(z2, keys, scale, w, e2, alive,
                                             levels=levels, **kw)
            else:
                # per-worker reference oracle, identical expressions
                outs = [
                    _ref.quantize_uplink_ref(
                        z2[m], keys[m],
                        jnp.maximum(_ref.uplink_stats_ref(
                            z2[m], ef=None if e2 is None else e2[m],
                            w=None if w is None else w[m]), 1e-30),
                        levels=levels,
                        ef=None if e2 is None else e2[m],
                        w=None if w is None else w[m],
                        alive=None if alive is None else alive[m] > 0,
                    )
                    for m in range(z2.shape[0])
                ]
                sent2 = jnp.stack([o[0] for o in outs])
                ef2 = (jnp.stack([o[1] for o in outs])
                       if e2 is not None else None)
        else:                                             # topk
            fraction = codec[1]
            if use_kernel:
                eff2 = eff_uplink(z2, w, e2, **kw) if (
                    w is not None or e2 is not None) else z2
                mask2 = _topk_mask(eff2, fraction)
                sent2, ef2 = mask_uplink(eff2, mask2, e2, alive,
                                         want_ef=e2 is not None, **kw)
            else:
                wb = None if w is None else w[:, None]
                eff2 = _ref.eff_uplink_ref(z2, ef=e2, w=wb)
                mask2 = _topk_mask(eff2, fraction)
                ab = None if alive is None else alive[:, None] > 0
                sent2, ef2 = _ref.mask_uplink_ref(eff2, mask2, alive=ab,
                                                  ef=e2)
                if e2 is None:
                    ef2 = None
        sents.append(sent2.reshape(shape))
        ef_news.append(None if ef2 is None else ef2.reshape(shape))

    sent_tree = treedef.unflatten(sents)
    ef_tree = (treedef.unflatten(ef_news) if ef is not None else None)
    return sent_tree, ef_tree


@functools.partial(jax.jit, static_argnames=_STATIC)
def codec_uplink(payload, rng, w=None, ef=None, alive=None, *, codec,
                 use_kernel=True, block=None):
    """Single-worker form of :func:`codec_uplink_stacked` (no leading worker
    axis) — the per-shard uplink of the ``shard_map`` engines and the
    stateless ``make_compressed_psum_sync`` hook. ``w``/``alive`` are
    scalars, ``rng`` one (2,) key."""
    p1 = jax.tree.map(lambda v: v[None], payload)
    e1 = None if ef is None else jax.tree.map(lambda v: v[None], ef)
    w1 = None if w is None else jnp.asarray(w, jnp.float32).reshape(1)
    a1 = (None if alive is None
          else jnp.asarray(alive, jnp.float32).reshape(1))
    sent, ef_new = codec_uplink_stacked(
        p1, _ref.key_data(rng).reshape(1, 2), w1, e1, a1, codec=codec,
        use_kernel=use_kernel, block=block,
    )
    sent = jax.tree.map(lambda v: v[0], sent)
    if ef_new is not None:
        ef_new = jax.tree.map(lambda v: v[0], ef_new)
    return sent, ef_new


def _krum_select(z2s, w, *, f, m_sel):
    """(Multi-)Krum selection on flat (M, n) leaves: score each included
    worker by the sum of its ``nb = max(1, M − f − 2)`` smallest squared
    distances to *other* included workers, keep the ``m_sel`` lowest-scoring
    (ties to lowest worker index — ``lax.top_k`` order), and return a (M,)
    0/1 selection mask. Zero-weight lanes (dead / unselected) never enter
    the distance pool and are never selected."""
    m = z2s[0].shape[0]
    zc = jnp.concatenate([zz.astype(jnp.float32) for zz in z2s], axis=1)
    sq = jnp.sum(zc * zc, axis=1)
    d = sq[:, None] + sq[None, :] - 2.0 * (zc @ zc.T)
    incl = (jnp.ones((m,), jnp.float32) if w is None
            else jnp.asarray(w, jnp.float32)) > 0
    pair = incl[None, :] & incl[:, None] & ~jnp.eye(m, dtype=bool)
    inf = jnp.float32(jnp.inf)
    d = jnp.where(pair, d, inf)
    nb = max(1, m - f - 2)
    score = jnp.sum(jnp.sort(d, axis=1)[:, :nb], axis=1)
    score = jnp.where(incl, score, inf)
    _, idx = jax.lax.top_k(-score, min(m_sel, m))
    sel = jnp.zeros((m,), jnp.float32).at[idx].set(1.0)
    return sel * incl.astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("normalize", "agg", "use_kernel",
                                    "block"))
def sync_merge_stacked(z, w=None, recv=None, old=None, *, normalize=False,
                       agg=None, use_kernel=True, block=None):
    """The fused Line-7 server side on a worker-stacked pytree: weighted sum
    over the worker axis (``w`` raw weights, normalized in-register when
    ``normalize``) broadcast back to every worker — one read + one write of
    the fleet payload per leaf instead of the scale/sum/broadcast tree
    passes. ``recv`` (M,) gates delivery: non-receiving workers keep their
    ``old`` (default: ``z``) row, the engines' fault semantics.

    ``agg`` selects a *robust* merge instead of the plain weighted mean (the
    static specs produced by ``repro.ps.robust`` aggregators; ``None`` is
    the historical mean — robust aggregators at zero budget resolve to
    ``None``, so clean-fleet degradation is the same compiled function):

    * ``("trimmed", b)``     — per-coordinate b-per-side trimmed weighted
      mean over the positive-weight lanes (``b = ⌊(M−1)/2⌋`` is the
      coordinate median), survivor-renormalized; fused via the sort-free
      streaming-rank kernel, reference via :func:`.ref.trimmed_merge_ref`.
    * ``("krum", f, m_sel)`` — multi-Krum: keep the ``m_sel`` workers with
      the smallest sum of ``max(1, M−f−2)`` nearest squared distances, then
      the survivor-renormalized weighted mean of the keepers.
    """
    leaves, treedef = jax.tree.flatten(z)
    old_leaves = (treedef.flatten_up_to(old) if old is not None
                  else [None] * len(leaves))
    interp = interpret_mode() if use_kernel else False
    w = None if w is None else jnp.asarray(w, jnp.float32)
    recv = None if recv is None else jnp.asarray(recv, jnp.float32)
    m = leaves[0].shape[0]

    if agg is not None and agg[0] == "krum":
        sel = _krum_select([_flat2(l) for l in leaves], w,
                           f=int(agg[1]), m_sel=int(agg[2]))
        w = sel if w is None else w * sel
        agg, normalize = None, True     # mean over the Krum survivors

    if agg is not None:                 # ("trimmed", b)
        trim = int(agg[1])
        wt = jnp.ones((m,), jnp.float32) if w is None else w
        incl = (wt > 0).astype(jnp.float32)
        outs = []
        for zl, ol in zip(leaves, old_leaves):
            shape = zl.shape
            z2 = _flat2(zl)
            o2 = None if ol is None else _flat2(ol)
            if use_kernel:
                out2 = trimmed_merge_stacked(
                    z2, wt, incl, recv, o2, trim=trim,
                    block=block, interpret=interp,
                )
            else:
                out2 = _ref.trimmed_merge_ref(
                    z2, wt, incl, trim=trim,
                    recv=None if recv is None else recv > 0, old=o2,
                )
            outs.append(out2.reshape(shape))
        return treedef.unflatten(outs)

    outs = []
    for zl, ol in zip(leaves, old_leaves):
        shape = zl.shape
        z2 = _flat2(zl)
        o2 = None if ol is None else _flat2(ol)
        if use_kernel:
            out2 = merge_stacked(
                z2, w, recv, o2, normalize=normalize,
                block=block, interpret=interp,
            )
        else:
            out2 = _ref.merge_ref(z2, w, normalize=normalize,
                                  recv=None if recv is None else recv > 0,
                                  old=o2)
        outs.append(out2.reshape(shape))
    return treedef.unflatten(outs)


@functools.partial(jax.jit,
                   static_argnames=("spec", "use_kernel", "block"))
def server_outer_apply(merged, z, mom, t, *, spec, use_kernel=True,
                       block=None):
    """The server's outer-optimizer step on pytrees: per leaf, form the
    round delta Δ = merged − z and apply one fused moment update + step of
    the static ``spec`` policy (``repro.ps.server_opt`` tuples) — one
    extra HBM pass over the merged server anchor, downstream of whatever
    (robust) merge produced it.

    ``merged``/``z`` are server-space pytrees (leading axis 1), ``mom`` a
    tuple of z-shaped moment trees (1 for momentum/nesterov, 2 for adam),
    ``t`` the int32 count of outer steps taken so far. Returns
    ``(z_new, mom_new, t_new, eff_lr, delta_norm)`` where ``eff_lr`` is
    the policy's effective step size this round (adam: bias-correction
    folded in) and ``delta_norm = ‖Δ‖₂`` over all leaves — the trace
    telemetry pair.

    Examples
    --------
    Nesterov's first step moves by lr·(1+β)·Δ off a zero moment:

    >>> import jax.numpy as jnp, numpy as np
    >>> from repro.kernels.sync_compress.ops import server_outer_apply
    >>> z = {"p": jnp.zeros((1, 3))}
    >>> merged = {"p": jnp.array([[1.0, -2.0, 0.5]])}
    >>> mom = ({"p": jnp.zeros((1, 3))},)
    >>> zn, mn, tn, lr, dn = server_outer_apply(
    ...     merged, z, mom, jnp.int32(0), spec=("nesterov", 0.5, 0.8))
    >>> bool(np.allclose(zn["p"], 0.5 * 1.8 * merged["p"], rtol=1e-6))
    True
    >>> float(lr), int(tn)
    (0.5, 1)
    >>> bool(np.allclose(dn, jnp.sqrt(jnp.sum(merged["p"] ** 2))))
    True
    """
    interp = interpret_mode() if use_kernel else False
    z_leaves, treedef = jax.tree.flatten(z)
    g_leaves = treedef.flatten_up_to(merged)
    mom_leaves = [treedef.flatten_up_to(mm) for mm in mom]
    t_f = jnp.asarray(t, jnp.float32)
    z_new_l = []
    mom_new_l = [[] for _ in mom]
    dsq = jnp.float32(0.0)
    for i, (g, zl) in enumerate(zip(g_leaves, z_leaves)):
        shape = zl.shape
        g2, z2 = _flat2(g), _flat2(zl)
        m2 = tuple(_flat2(ml[i]) for ml in mom_leaves)
        if use_kernel:
            zn2, mn2, ds = outer_apply(
                g2, z2, m2, t_f, spec=spec,
                block=block, interpret=interp,
            )
        else:
            zn2, mn2, ds = _ref.outer_apply_ref(g2, z2, m2, t_f, spec=spec)
        z_new_l.append(zn2.reshape(shape))
        for s, mn in enumerate(mn2):
            mom_new_l[s].append(mn.reshape(shape))
        dsq = dsq + ds
    t_new = jnp.asarray(t, jnp.int32) + 1
    if spec[0] == "adam":
        _, lr, b1, b2, _ = spec
        tf = t_new.astype(jnp.float32)
        eff_lr = (jnp.float32(lr)
                  * jnp.sqrt(1.0 - jnp.float32(b2) ** tf)
                  / (1.0 - jnp.float32(b1) ** tf))
    else:
        eff_lr = jnp.float32(spec[1])
    return (treedef.unflatten(z_new_l),
            tuple(treedef.unflatten(l) for l in mom_new_l),
            t_new, eff_lr, jnp.sqrt(dsq))


# ---------------------------------------------------------------------------
# Analytic HBM-traffic model (benchmarks/bench_ps.py, bench_kernels-style):
# passes over the parameter vector per uplink, reference tree pipeline vs
# fused kernels. Reads and writes both count as one pass.
# ---------------------------------------------------------------------------

#: passes per sync uplink (error-feedback codecs): {codec: (ref, fused)}.
#: reference = message scale + EF add + scale/select reduction + quantize/
#: scatter + residual, each a separate tree sweep; fused = the 2-pass
#: kernels above (stats/eff + codec-with-residual). identity is the
#: degenerate 1-pass scaling vs scale+sum+broadcast.
CODEC_PASS_MODEL = {
    "identity": (4, 2),
    "quantize": (11, 6),
    "topk": (10, 8),
}


def codec_passes(codec) -> tuple[int, int]:
    """(reference, fused) HBM passes per uplink for a codec spec."""
    return CODEC_PASS_MODEL[_check_codec(codec)[0]]
