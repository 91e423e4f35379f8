"""Production mesh construction (TPU v5e target).

Kept as functions — importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pods: int | None = None):
    """Small mesh over however many (possibly fake) devices exist — used by
    CPU integration tests with xla_force_host_platform_device_count."""
    if pods:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def worker_axes_for(mesh, mode: str) -> tuple[str, ...]:
    """LocalAdaSEG worker placement.

    * ``paper``        — every data shard is a worker (M = pod·data): the
                         Parameter-Server topology of the paper.
    * ``hierarchical`` — workers = pods (M = #pods); intra-pod axes do
                         FSDP/TP with per-step sync; only the slow inter-pod
                         link pays the K-amortized LocalAdaSEG sync.
    """
    names = mesh.axis_names
    if mode == "paper":
        return tuple(n for n in ("pod", "data") if n in names)
    if mode == "hierarchical":
        return ("pod",) if "pod" in names else ()
    raise ValueError(f"unknown worker mode {mode!r}")


def num_workers(mesh, worker_axes: tuple[str, ...]) -> int:
    m = 1
    for a in worker_axes:
        m *= mesh.shape[a]
    return max(m, 1)
