"""PartitionSpec utilities: worker-axis stacking, FSDP augmentation, and
mesh-divisibility sanitation.

Placement model (see DESIGN.md §5):

* ``model`` axis — tensor parallel (attention heads / d_ff / experts / vocab),
  encoded in each module's ``(params, specs)`` pair.
* worker axes — LocalAdaSEG's per-worker parameter copies: every param leaf
  gains a leading axis of size M sharded over the worker axes
  (paper-faithful: ``("pod", "data")``; hierarchical: ``("pod",)``).
* ``data`` axis — batch sharding; in hierarchical mode additionally FSDP:
  each param's first model-free divisible dim is sharded over ``data``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free ``AbstractMesh`` for spec logic, which only reads
    ``mesh.shape`` and ``mesh.axis_names``."""
    return AbstractMesh(shape, axes)


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name]


def stack_spec(spec: P, worker_axes: tuple[str, ...]) -> P:
    """Prepend the worker axis: leaf (…,) → (M, …)."""
    lead = worker_axes if len(worker_axes) > 1 else (worker_axes[0] if worker_axes else None)
    return P(lead, *spec)


def fsdp_spec(spec: P, shape: tuple[int, ...], mesh: Mesh,
              axis: str = "data") -> P:
    """Add ``axis`` to the first dim that is unsharded and divisible.

    Only touches leaves with ≥ 2 dims (norm scales etc. stay replicated —
    gathering them is cheaper than the bookkeeping).
    """
    if len(shape) < 2:
        return spec
    size = _axis_size(mesh, axis)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, d) in enumerate(zip(entries, shape)):
        if e is None and d % size == 0 and d >= size:
            entries[i] = axis
            return P(*entries)
    return spec


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis names whose mesh size does not divide the dim size.

    GSPMD tolerates uneven sharding via padding, but padded KV-head shards
    waste memory and produce misleading memory analyses — we replicate
    instead and let the hillclimb phase re-place them deliberately.
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for e, d in zip(entries, shape):
        if e is None:
            out.append(None)
            continue
        names = e if isinstance(e, (tuple, list)) else (e,)
        kept = [n for n in names if d % _axis_size(mesh, n) == 0]
        # partial keeps must still divide jointly
        while kept and d % int(np.prod([_axis_size(mesh, n) for n in kept])):
            kept.pop()
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def repair_axis(spec: P, shape: tuple[int, ...], mesh: Mesh,
                axis: str = "model", *, skip_dims: tuple[int, ...] = ()) -> P:
    """If ``axis`` was dropped everywhere by sanitation, re-place it on the
    largest divisible free dim (e.g. MoE expert dim 8 < 16-way model axis →
    shard d_ff instead: tensor-parallel within expert)."""
    if any(
        (e == axis or (isinstance(e, tuple) and axis in e)) for e in spec
    ):
        return spec
    size = _axis_size(mesh, axis)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best = None
    for i in range(len(shape)):
        if i in skip_dims or entries[i] is not None:
            continue
        if shape[i] % size == 0 and shape[i] >= size:
            if best is None or shape[i] > shape[best]:
                best = i
    if best is not None:
        entries[best] = axis
    return P(*entries)


def build_param_shardings(
    params, specs, mesh: Mesh, *, worker_axes: tuple[str, ...] = (),
    fsdp: bool = False, repair_model: bool = False,
):
    """Materialize NamedShardings for a (stacked) parameter tree.

    ``params`` may be abstract (ShapeDtypeStruct) — only shapes are read.
    When ``worker_axes`` is non-empty the params are expected to carry the
    leading worker axis already. ``repair_model=True`` re-places a dropped
    'model' axis on the largest divisible dim (§Perf lever).
    """
    n_skip = (1 + len(worker_axes[1:])) if worker_axes else 0

    def one(leaf, spec):
        shape = leaf.shape
        base_shape = shape[1:] if worker_axes else shape
        s = spec
        if fsdp:
            s = fsdp_spec(s, base_shape, mesh)
        if worker_axes:
            s = stack_spec(s, worker_axes)
        s = sanitize_spec(s, shape, mesh)
        if repair_model and len(base_shape) >= 2:
            skip = (0,) if worker_axes else ()
            s = repair_axis(s, shape, mesh, "model", skip_dims=skip)
            s = sanitize_spec(s, shape, mesh)
        return NamedSharding(mesh, s)

    return jax.tree.map(one, params, specs)


def abstract_like(params, *, stacked: int | None = None, dtype=None):
    """ShapeDtypeStruct pytree mirroring ``params`` (optionally worker-stacked)."""

    def one(leaf):
        shape = (stacked, *leaf.shape) if stacked else leaf.shape
        return jax.ShapeDtypeStruct(shape, dtype or leaf.dtype)

    return jax.tree.map(one, params)
