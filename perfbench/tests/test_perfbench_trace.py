"""The trace reduction on traces recorded on the chip: the window, the
busy time against a direct count, the update kernels by hand, and every
per-layer metric of the cell within its range."""
import importlib
import json
import lzma
import os

import pytest

from perfbench import harness, spec, traceio
from perfbench.peaks import peaks

FIX = os.path.join(spec.HERE, "fixtures")
CELLS = sorted(f[:-len(".json")] for f in os.listdir(FIX) if f.endswith(".json"))


@pytest.fixture(scope="module", params=CELLS)
def recorded(request, tmp_path_factory):
    name = request.param
    meta = json.load(open(os.path.join(FIX, f"{name}.json")))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with lzma.open(os.path.join(FIX, f"{name}.xplane.pb.xz")) as src:
        path.write_bytes(src.read())
    return meta, traceio.load(str(path))


def test_window_devices_and_host_spans(recorded):
    meta, tr = recorded
    assert list(tr.devices) == [0]
    w0, w1 = tr.window
    assert w1 > w0
    chunks = [h for h in tr.host if h.name.startswith("chunk [")]
    assert len(chunks) == meta["rounds"]
    assert all(w0 <= h.t0 and h.t1 <= w1 for h in chunks)


def test_busy_time_against_a_direct_count(recorded):
    _, tr = recorded
    w0, w1 = tr.window
    live = [o for o in tr.devices[0] if o.t1 > w0 and o.t0 < w1]
    # a direct count: sweep every op's start and end in time order
    edges = sorted([(max(o.t0, w0), 1) for o in live]
                   + [(min(o.t1, w1), -1) for o in live])
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert traceio.busy_s(tr, 0) == pytest.approx(busy, rel=1e-9)
    gaps = sum(b - a for a, b in traceio.idle_gaps(tr, 0))
    assert gaps + busy == pytest.approx(w1 - w0, rel=1e-9)


def test_update_kernels_by_hand(recorded):
    meta, tr = recorded
    res = spec.resolve(spec.load_benchmark(), meta["workload"])
    kernels = [o for o in tr.ops_in_window(0) if traceio.is_adaseg_update(o)]
    # an explore and an anchor call per leaf (workers vmapped into one
    # call) per local step
    assert len(kernels) == (2 * meta["leaves"] * res["traffic"]["local_steps"]
                            * meta["rounds"])
    assert all(o.leaf for o in kernels)


def test_per_layer_metrics_of_the_cell(recorded):
    """Every reader of the cell reads, but one of a layer that the recorded
    program did not scope yet (the fixture's ``scopes`` say which it
    carries), which reads nothing. The top-level layers' times are
    disjoint, so they sum to less than a round; the layers nested in the
    local step sum to no more than it."""
    meta, tr = recorded
    res = spec.resolve(spec.load_benchmark(), meta["workload"])
    system = importlib.import_module(
        f"perfbench.systems.{res['config']['system']}")
    cell = system.build(res["config"], res["traffic"], seed=1, seconds=1,
                        tracer=None, engine=False)
    w = tr.window[1] - tr.window[0]
    window = harness.Window(3, [w / meta["rounds"]] * meta["rounds"], w)
    ctx = harness.LayerContext(tr, window, cell, peaks(meta["device_kind"]),
                               [0])
    readers = {m["name"]: spec.load_module("metrics", m["name"])
               for m in res["per_layer"]}
    got = {k: r.read(ctx) for k, r in readers.items()}
    nested = {k for k, r in readers.items() if hasattr(r, "LAYER")}
    for k in nested:
        if readers[k].LAYER not in meta["scopes"]:
            assert got[k] is None, (k, got[k])
            del got[k]
    assert all(v is not None for v in got.values()), got
    per_round_ms = 1e3 * w / meta["rounds"]
    timed = {k: v for k, v in got.items()
             if k.split(".")[0].endswith("_ms")}
    assert timed and all(v > 0 for v in timed.values())
    assert sum(v for k, v in timed.items() if k not in nested) < per_round_ms
    local = [v for k, v in timed.items()
             if k.split(".")[0] == "local_compute_ms"]
    assert len(local) == 1
    assert sum(v for k, v in timed.items() if k in nested) <= local[0]
    for k, v in got.items():
        if k.startswith("idle_share"):
            assert 0.0 <= v < 100.0
        if "roofline" in k or k.startswith("mfu"):
            assert 0.0 < v <= 100.0, (k, v)
    bd = harness.breakdown(tr, [0])
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert all(s > 0 for _, s in bd["device_ops"] + bd["idle_gaps"])
