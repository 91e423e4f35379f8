"""One run of one cell: set-up, the measured (or traced) window, the check
against the reference, and the result line.

Order of a run:

1. The program is built from the seed and driven through its first three
   rounds by the window's own call; their readings are kept for the check.
   This compiles and warms every program the window uses, so the set-up
   time ``setup_s`` runs from process start to here.
2. The window: ``run(until_round=r+1)`` per round, each ending in
   ``block_until_ready``, until ``--seconds`` have passed. With
   ``--trace 1`` a short window runs under the profiler instead.
3. The device's peak memory is read, the program's state is freed, and
   the plain reference recomputes the first three rounds. Each compared
   number is printed beside its limit, last on standard error and under
   ``checks`` in the result line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time

from . import check, spec, traceio
from .peaks import peaks

CHECK_ROUNDS = 3
TRACE_SECONDS = 2.0     # the traced window: this long, and 3 rounds or more
TOP = 10


@dataclasses.dataclass
class Window:
    first_round: int
    round_s: list            # host-clock seconds of every call
    window_s: float

    @property
    def rounds(self) -> int:
        return len(self.round_s)


@dataclasses.dataclass
class Run:
    """What the end-to-end readers see."""
    setup_s: float
    window: Window
    work: dict               # per round: tokens, worker_steps


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def first_rounds(cell, n: int = CHECK_ROUNDS) -> dict:
    """Drive rounds 1..n through the window's call and keep the readings
    the check compares (see ``perfbench.check``). The anchors after round
    1 are kept on the host, so the check holds no device memory while the
    rounds run."""
    import jax
    import jax.numpy as jnp

    eng = cell.engine
    snap = None
    out = {}
    for r in range(n):
        t = time.perf_counter()
        cell.run_round(r)
        _log(f"set-up: round {r} took {time.perf_counter() - t:.3f} s")
        if r == 0:
            out["grad_sq"] = [float(g) for g in
                              jax.device_get(eng.state.grad_sq_sum)]
            snap = jax.device_get(jax.tree.leaves(eng.state.z_tilde))
    sq = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))
    # b goes back as a is laid out: on a mesh, each chip gets its workers'
    # share, not the whole stacked leaf on the first chip.
    out["change"] = [math.sqrt(float(sq(a, jax.device_put(b, a.sharding))))
                     for a, b in zip(jax.tree.leaves(eng.state.z_tilde),
                                     snap)]
    del snap
    out["loss"] = [_finite(rec.residual) for rec in eng.trace.rounds[:n]]
    return out


def _finite(x) -> float:
    return float("nan") if x is None else float(x)


def timed_window(cell, r0: int, seconds: float) -> Window:
    durs = []
    t_begin = time.perf_counter()
    r = r0
    while True:
        t = time.perf_counter()
        cell.run_round(r)
        durs.append(time.perf_counter() - t)
        r += 1
        if time.perf_counter() - t_begin >= seconds:
            break
    return Window(r0, durs, time.perf_counter() - t_begin)


def traced_window(cell, r0: int, seconds: float):
    """A short window under the profiler; returns (Window, Trace)."""
    import jax

    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        durs = []
        t_begin = time.perf_counter()
        r = r0
        with jax.profiler.TraceAnnotation(traceio.WINDOW):
            while True:
                t = time.perf_counter()
                cell.run_round(r)
                durs.append(time.perf_counter() - t)
                r += 1
                if (len(durs) >= CHECK_ROUNDS and time.perf_counter()
                        - t_begin >= min(TRACE_SECONDS, seconds)):
                    break
        window = Window(r0, durs, time.perf_counter() - t_begin)
        jax.profiler.stop_trace()
        trace = traceio.load(traceio.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return window, trace


def peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class LayerContext:
    """What the per-layer readers see."""
    trace: traceio.Trace
    window: Window
    cell: object
    peaks: dict
    device_ids: list


def breakdown(trace: traceio.Trace, devs) -> dict:
    by_op: dict[str, float] = {}
    for d in devs:
        w0, w1 = trace.window
        for o in trace.ops_in_window(d):
            if o.leaf:
                name = o.name.split(" = ", 1)[0]     # the HLO instruction
                by_op[name] = by_op.get(name, 0.0) + (
                    min(o.t1, w1) - max(o.t0, w0)) / len(devs)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((traceio.host_span_at(trace, 0.5 * (a + b)), b - a)
                   for a, b in traceio.idle_gaps(trace, devs[0])),
                  key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def run_cell(res: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, cache: bool = True) -> dict:
    """Everything after the device check; returns the result object.
    ``cache=False`` leaves JAX's persistent compilation cache off (the
    tests' CPU runs)."""
    import jax
    from repro.launch.cache import enable_compile_cache
    from repro.obs import SpanTracer

    if cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config, traffic = res["config"], res["traffic"]
    system = importlib.import_module(f"perfbench.systems.{config['system']}")
    dev0 = jax.devices()[0]
    with system.context(config):
        cell = system.build(config, traffic, seed=seed, seconds=seconds,
                            tracer=SpanTracer(profile=trace))
        _log(f"set-up: built at {time.perf_counter() - t_start:.3f} s")
        prog = first_rounds(cell)
        setup_s = time.perf_counter() - t_start
        r0 = cell.engine.round
        if trace:
            window, tr = traced_window(cell, r0, seconds)
        else:
            window, tr = timed_window(cell, r0, seconds), None
        losses = [rec.residual for rec in cell.engine.trace.rounds[r0:]]
        mem = peak_bytes(cell.devices)
        dev_ids = [d.id for d in cell.devices]
        cell.free()
        gc.collect()
        t_ref = time.perf_counter()
        ref = cell.reference()
        _log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    numbers = check.gaps(prog, ref)
    ok, table = check.judge(numbers, res["limits"])

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    metrics = {}
    extra = {}
    if trace:
        ctx = LayerContext(tr, window, cell, peaks(dev0.device_kind),
                           dev_ids)
        for m in res["per_layer"]:
            value = spec.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = traceio.mean_over_devices(
            tr, dev_ids, lambda d: traceio.busy_s(tr, d))
        device["window_s"] = tr.window[1] - tr.window[0]
        extra["breakdown"] = breakdown(tr, dev_ids)
    else:
        run = Run(setup_s, window, cell.work)
        for m in res["end_to_end"]:
            value = spec.load_module("e2e", m["name"]).read(run)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for x in losses if x is None or not math.isfinite(x))
    _log(f"window: {window.rounds} rounds in {window.window_s:.6f} s "
         f"from round {r0}; set-up {setup_s:.3f} s")
    slow = sorted(enumerate(window.round_s), key=lambda kv: -kv[1])[:3]
    _log("slowest rounds (index in window, s): "
         + ", ".join(f"({i}, {t:.6f})" for i, t in slow)
         + f"; median {sorted(window.round_s)[window.rounds // 2]:.6f}")
    for name in sorted(set(numbers) - set(table)):
        _log(f"reading {name}: {numbers[name]!r} (not compared)")
    for name, v in table.items():
        _log(f"check {name}: {v['value']!r} limit {v['limit']!r}")
    return {"correct": ok, "attempted": window.rounds, "failed": failed,
            "metrics": metrics, "device": device, **extra, "checks": table}


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    res = spec.resolve(spec.load_benchmark(), args.workload)
    chips = res["cell"]["chips"]
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        _log(f"no program to measure here: {e}")
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"no TPU: JAX runs on {devices[0].platform!r}")
        return 2
    if len(devices) < chips:
        _log(f"the cell needs {chips} chips; JAX sees {len(devices)}")
        return 2
    result = run_cell(res, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=t_start)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
