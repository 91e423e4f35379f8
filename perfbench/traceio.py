"""Reading the profiler's trace of a traced window, and the reductions the
per-layer readers share.

The JAX profiler writes an ``.xplane.pb`` (a ``tensorflow.profiler.XSpace``
protobuf). A device is a plane named ``/device:TPU:<i>``; its operations
are the events of its ``XLA Ops`` line. Each event's metadata carries the
HLO instruction (its name), ``hlo_category`` and ``tf_op``, the scope path
the program gave it with ``jax.named_scope`` (``…/local-compute/…``,
``…/sync/…``). That line nests a loop's body inside the loop op, so sums of
op time use the innermost ops only. Host spans are the
``jax.profiler.TraceAnnotation`` events on the host plane: the window the
harness marks, and the engine's own spans (``run [r0,r1)``,
``chunk [r0,r1)``, ``eval r``) when the engine is given a
``SpanTracer(profile=True)``. All times share one clock: the line's
timestamp plus the event's offset.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import os
import re

WINDOW = "perfbench-window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN = re.compile(r"^(perfbench-window|run \[|chunk \[|eval r)")


@dataclasses.dataclass
class Op:
    name: str
    t0: float          # seconds, on the trace's clock
    t1: float
    scope: str = ""    # tf_op: the scope path
    category: str = ""  # hlo_category
    leaf: bool = True  # no other op of its line starts inside it

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Op]]        # device id → its operations
    host: list[Op]                      # host spans
    window: tuple[float, float]         # the harness's traced window

    def ops_in_window(self, dev: int) -> list[Op]:
        w0, w1 = self.window
        return [o for o in self.devices[dev] if o.t1 > w0 and o.t0 < w1]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _xspace_class():
    """The generated ``XSpace`` message of the installed profiler protos,
    loaded on its own (it needs only ``google.protobuf``)."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise RuntimeError("the XPlane protobuf schema is not installed")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mspec = importlib.util.spec_from_file_location("perfbench_xplane_pb2",
                                                   path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod.XSpace


def _events(plane, line, want_stats: bool):
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    base_ps = line.timestamp_ns * 1000
    for e in line.events:
        md = plane.event_metadata[e.metadata_id]
        t0 = (base_ps + e.offset_ps) * 1e-12
        op = Op(md.name, t0, t0 + e.duration_ps * 1e-12)
        if want_stats:
            for st in md.stats:
                key = names.get(st.metadata_id)
                if key == "tf_op":
                    op.scope = st.str_value
                elif key == "hlo_category":
                    op.category = st.str_value
        yield op


def _mark_leaves(ops: list[Op]) -> None:
    ops.sort(key=lambda o: (o.t0, -o.t1))
    for a, b in zip(ops, ops[1:]):
        if b.t0 < a.t1:
            a.leaf = False


def load(path: str) -> Trace:
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices: dict[int, list[Op]] = {}
    host: list[Op] = []
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    _events(plane, line, True))
            elif not m and plane.name.startswith("/host"):
                host.extend(o for o in _events(plane, line, False)
                            if HOST_SPAN.match(o.name))
    win = [h for h in host if h.name == WINDOW]
    if not win:
        raise RuntimeError("the traced window's annotation is missing")
    for ops in devices.values():
        _mark_leaves(ops)
    return Trace(devices, host, (win[0].t0, win[0].t1))


def busy_intervals(ops: list[Op], w0: float, w1: float):
    """The union of the ops' intervals, clipped to [w0, w1]."""
    out: list[list[float]] = []
    for o in sorted(ops, key=lambda o: o.t0):
        a, b = max(o.t0, w0), min(o.t1, w1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(trace: Trace, dev: int) -> float:
    w0, w1 = trace.window
    return sum(b - a for a, b in busy_intervals(trace.devices[dev], w0, w1))


def idle_gaps(trace: Trace, dev: int):
    """(start, end) of every stretch of the window with no op running."""
    w0, w1 = trace.window
    gaps, t = [], w0
    for a, b in busy_intervals(trace.devices[dev], w0, w1):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def host_span_at(trace: Trace, t: float) -> str:
    """The innermost host span covering time ``t`` (the shortest)."""
    cover = [h for h in trace.host if h.t0 <= t <= h.t1]
    return min(cover, key=lambda h: h.dur).name if cover else "outside"


def op_seconds(trace: Trace, dev: int, pred) -> float:
    """Summed device time, inside the window, of the innermost ops matching
    ``pred`` (clipped to the window)."""
    w0, w1 = trace.window
    return sum(min(o.t1, w1) - max(o.t0, w0)
               for o in trace.ops_in_window(dev) if o.leaf and pred(o))


def mean_over_devices(trace: Trace, devs, fn) -> float:
    return sum(fn(d) for d in devs) / len(devs)


def in_scope(scope: str):
    """Ops whose scope path holds the component ``scope``."""
    pat = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    return lambda o: bool(pat.search(o.scope))


def is_all_reduce(o: Op) -> bool:
    return "all-reduce" in o.category or " all-reduce(" in o.name


KERNEL = re.compile(r"^%\S*adaseg_tree_(explore|anchor)\S* = .* custom-call\(")


def is_adaseg_update(o: Op) -> bool:
    """The fused AdaSEG update's Pallas kernels (explore and anchor): the
    custom calls named after their ``kernels.adaseg_update.ops`` entries."""
    return bool(KERNEL.match(o.name))


def ms_per_round(ctx, pred) -> float | None:
    """Device milliseconds per round of the ops matching ``pred``, mean
    over the cell's devices; None where no op matches."""
    secs = mean_over_devices(ctx.trace, ctx.device_ids,
                             lambda d: op_seconds(ctx.trace, d, pred))
    if secs <= 0.0:
        return None
    return 1e3 * secs / ctx.window.rounds


def scope_ms_per_round(ctx, scope: str) -> float | None:
    return ms_per_round(ctx, in_scope(scope))
