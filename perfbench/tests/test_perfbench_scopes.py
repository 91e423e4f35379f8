"""The layers the program names inside its local step, read from traces
recorded on the chip.

On the trace recorded with the scopes (``*.scoped``) the attention,
vocabulary and update readers are positive, their sum stays within the
local step, and each covers the kernels it names; the engine's host spans
leave no idle gap of over a millisecond to the harness's window. On the
trace recorded before the scopes, each reader reads nothing."""
import json
import lzma
import os
import re

import pytest

from perfbench import harness, spec, traceio

FIX = os.path.join(spec.HERE, "fixtures")
CELL = "qwen2-0.5b.m1-k4-q8-s4096"
SCOPED = f"{CELL}.scoped"
READERS = ("attention_ms.lm", "vocab_ms.lm", "update_ms.lm")
# the Pallas attention forward: custom calls named after its entry point
ATTENTION_CALL = re.compile(r"^%attention(\.\d+)? = .* custom-call\(")


def _context(name, tmp_path_factory):
    meta = json.load(open(os.path.join(FIX, f"{name}.json")))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with lzma.open(os.path.join(FIX, f"{name}.xplane.pb.xz")) as src:
        path.write_bytes(src.read())
    tr = traceio.load(str(path))
    w = tr.window[1] - tr.window[0]
    window = harness.Window(3, [w / meta["rounds"]] * meta["rounds"], w)
    # the layer readers read the trace and the window alone
    return harness.LayerContext(tr, window, None, None, [0])


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return _context(SCOPED, tmp_path_factory)


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    return _context(CELL, tmp_path_factory)


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_layers_positive_and_within_the_local_step(scoped):
    got = {n: _read(n, scoped) for n in READERS}
    assert all(v is not None and v > 0.0 for v in got.values()), got
    local = _read("local_compute_ms.lm", scoped)
    assert sum(got.values()) <= local, (got, local)


def test_attention_covers_its_pallas_calls(scoped):
    local = traceio.in_scope("local-compute")
    calls = traceio.ms_per_round(
        scoped, lambda o: bool(ATTENTION_CALL.match(o.name)) and local(o))
    assert calls is not None and calls > 0.0
    assert _read("attention_ms.lm", scoped) >= calls


def test_update_covers_its_kernels(scoped):
    kernels = traceio.ms_per_round(scoped, traceio.is_adaseg_update)
    assert kernels is not None and kernels > 0.0
    assert _read("update_ms.lm", scoped) >= kernels


@pytest.mark.parametrize("name", READERS)
def test_layers_silent_before_the_scopes(plain, name):
    assert _read(name, plain) is None


def test_no_idle_gap_charged_to_the_window(scoped):
    tr = scoped.trace
    gaps = [(traceio.host_span_at(tr, 0.5 * (a + b)), b - a)
            for a, b in traceio.idle_gaps(tr, 0)]
    assert gaps
    window = [s for n, s in gaps if n == traceio.WINDOW]
    assert all(s <= 1e-3 for s in window), sorted(window)[-3:]
