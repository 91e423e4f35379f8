"""Observability layer: span/metric JSONL round-trips, Perfetto export
validity on both clocks, the span→trace bridge, and — the load-bearing
pins — proof that tracing is *inert*: every parity-sensitive path produces
bit-identical numerics with tracing enabled and disabled."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.obs import (
    MetricsRegistry,
    Span,
    SpanTracer,
    save_trace_events,
    to_trace_events,
    validate_trace_events,
)
from repro.problems import make_bilinear_game
from repro.ps import (
    AsyncPSConfig,
    AsyncPSEngine,
    ConstantLatency,
    PSConfig,
    PSEngine,
    StochasticQuantizeCompressor,
    TraceRecorder,
)
from repro.core import AdaSEGConfig
from repro.ps.trace import TRACE_VERSION

M, R, K = 4, 5, 4
N = 10


@pytest.fixture(scope="module")
def game():
    return make_bilinear_game(jax.random.PRNGKey(0), n=N, sigma=0.1)


def _cfg(k=K):
    return AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=k)


def _sync_engine(game, **kw):
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                   **{k: v for k, v in kw.items()
                      if k in ("compressor", "codec_backend")})
    eng_kw = {k: v for k, v in kw.items() if k in ("tracer", "metrics")}
    return PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(4),
                    eval_fn=game.residual, **eng_kw)


def _async_engine(game, *, tau=0.0, latency=None, **eng_kw):
    cfg = AsyncPSConfig(
        adaseg=_cfg(), num_workers=M, rounds=R,
        latency=latency or ConstantLatency(step_s=1.0),
        staleness_bound=tau,
    )
    return AsyncPSEngine(game.problem, cfg, rng=jax.random.PRNGKey(4),
                         eval_fn=game.residual, **eng_kw)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Span / metric primitives
# ---------------------------------------------------------------------------

def test_span_jsonl_roundtrip(tmp_path):
    tr = SpanTracer()
    with tr.span("run", cat="run", engine="sync"):
        with tr.span("round 0", cat="round", steps=7):
            pass
    tr.add_span("uplink r0", cat="uplink", track="worker/2",
                sim_t0=0.5, sim_t1=0.7, bytes=128.0)
    path = tmp_path / "spans.jsonl"
    tr.save_jsonl(str(path))
    back = SpanTracer.load_jsonl(str(path))
    assert [s.to_dict() for s in back.spans] == [
        s.to_dict() for s in tr.spans]
    # hierarchy survives: "round 0" closed inside "run"
    by_name = {s.name: s for s in back.spans}
    assert by_name["round 0"].parent == by_name["run"].id
    assert by_name["uplink r0"].sim_dur == pytest.approx(0.2)


def test_span_unknown_keys_dropped():
    sp = Span.from_dict({"name": "x", "cat": "round", "track": "server",
                         "id": 3, "frobnicate": 1})
    assert sp.name == "x" and not hasattr(sp, "frobnicate")


def test_disabled_tracer_times_but_records_nothing():
    tr = SpanTracer(enabled=False)
    with tr.span("chunk", cat="chunk") as sp:
        pass
    assert sp.wall_dur is not None and sp.wall_dur >= 0.0
    assert tr.spans == [] and tr.add_span("x", cat="round").id == -1
    assert tr.spans == []


def test_metrics_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("bytes_up", 80.0, engine="sync")
    reg.inc("bytes_up", 40.0, engine="sync")
    reg.set_gauge("eta_spread", 1.25)
    reg.observe("round_wall_s", 0.01, t_sim=3.0, codec="q8")
    path = tmp_path / "metrics.jsonl"
    reg.save_jsonl(str(path))
    back = MetricsRegistry.load_jsonl(str(path))
    assert back.records == reg.records
    assert back.total("bytes_up") == 120.0
    assert back.last("eta_spread") == 1.25
    assert back.histogram("round_wall_s")["count"] == 1
    assert back.names() == ["bytes_up", "eta_spread", "round_wall_s"]


def test_disabled_metrics_record_nothing():
    reg = MetricsRegistry(enabled=False)
    reg.inc("bytes_up", 80.0)
    assert reg.records == [] and reg.total("bytes_up") == 0.0


# ---------------------------------------------------------------------------
# Perfetto export — both clocks
# ---------------------------------------------------------------------------

def test_perfetto_export_sync_wall(game, tmp_path):
    engine = _sync_engine(game)
    engine.run(checkpoint_every=2)
    path = tmp_path / "sync.json"
    payload = save_trace_events(str(path), engine.tracer, clock="wall")
    validate_trace_events(payload)              # nesting + non-negative durs
    assert json.loads(path.read_text()) == payload
    names = {e["name"] for e in payload["traceEvents"] if e["ph"] == "X"}
    assert {f"round {r}" for r in range(R)} <= names
    assert any(n.startswith("chunk") for n in names)
    assert any(n.startswith("run") for n in names)
    # round spans nest inside their chunk span by construction
    rounds = engine.tracer.by_cat("round")
    chunks = {s.id: s for s in engine.tracer.by_cat("chunk")}
    for sp in rounds:
        ch = chunks[sp.parent]
        assert ch.wall_t0 <= sp.wall_t0 and sp.wall_t1 <= ch.wall_t1 + 1e-9


def test_perfetto_export_async_sim(game, tmp_path):
    engine = _async_engine(
        game, tau=2.0,
        latency=ConstantLatency(step_s=(1.0, 1.0, 1.0, 6.0),
                                up_s=0.2, down_s=0.1),
    )
    engine.run()
    payload = to_trace_events(engine.tracer.spans, clock="sim")
    validate_trace_events(payload)
    tracks = {e["args"]["name"] for e in payload["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {f"worker/{m}" for m in range(M)} <= tracks  # per-worker lanes
    cats = {s.cat for s in engine.tracer.spans}
    assert {"uplink", "broadcast", "local-compute", "admission"} <= cats
    assert engine.tracer.by_cat("held")         # τ=2 + a 6× straggler holds
    # the sim story is consistent: every span's sim interval is ordered
    for sp in engine.tracer.spans:
        if sp.sim_dur is not None:
            assert sp.sim_dur >= 0.0
    # wall clock of the same tracer also exports cleanly
    validate_trace_events(to_trace_events(engine.tracer.spans, clock="wall"))


def test_export_rejects_bad_payloads():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace_events({})
    bad = {"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "name": "a",
                            "ts": 0.0, "dur": -5.0}]}
    with pytest.raises(ValueError, match="negative"):
        validate_trace_events(bad)
    overlap = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0, "dur": 10.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 5.0, "dur": 10.0},
    ]}
    with pytest.raises(ValueError, match="partially overlaps"):
        validate_trace_events(overlap)


# ---------------------------------------------------------------------------
# Inertness: tracing-enabled ≡ tracing-disabled, bit for bit
# ---------------------------------------------------------------------------

def _off():
    return dict(tracer=SpanTracer(enabled=False),
                metrics=MetricsRegistry(enabled=False))


def test_sync_engine_tracing_inert(game):
    z_on = _sync_engine(game).run()
    z_off = _sync_engine(game, **_off()).run()
    _assert_trees_equal(z_on, z_off)


def test_sync_fused_codec_tracing_inert(game):
    kw = dict(compressor=StochasticQuantizeCompressor(bits=8),
              codec_backend="fused")
    e_on = _sync_engine(game, **kw)
    e_off = _sync_engine(game, **kw, **_off())
    _assert_trees_equal(e_on.run(), e_off.run())
    _assert_trees_equal(e_on.state, e_off.state)


def test_async_tau0_tracing_inert(game):
    e_on = _async_engine(game, tau=0.0)
    e_off = _async_engine(game, tau=0.0, **_off())
    _assert_trees_equal(e_on.run(), e_off.run())
    _assert_trees_equal(e_on.state, e_off.state)
    # the recorded telemetry itself is deterministic (wall timings live in
    # the span layer, not the trace), so it matches dict-for-dict too
    assert [dataclasses.asdict(r) for r in e_on.trace.rounds] == [
        dataclasses.asdict(r) for r in e_off.trace.rounds]
    assert e_on.tracer.spans and not e_off.tracer.spans


# ---------------------------------------------------------------------------
# Span→trace bridge and trace versioning
# ---------------------------------------------------------------------------

def test_from_spans_rebuilds_sync_trace(game):
    engine = _sync_engine(game)
    engine.run()
    bridged = TraceRecorder.from_spans(engine.tracer)
    assert [dataclasses.asdict(r) for r in bridged.rounds] == [
        dataclasses.asdict(r) for r in engine.trace.rounds]


def test_from_spans_derives_async_wall_from_spans(game):
    engine = _async_engine(game, tau=0.0)
    engine.run()
    bridged = TraceRecorder.from_spans(engine.tracer)
    assert len(bridged.rounds) == len(engine.trace.rounds)
    for b, r in zip(bridged.rounds, engine.trace.rounds):
        assert r.wall_time_s is None            # engine trace: deterministic
        db, dr = dataclasses.asdict(b), dataclasses.asdict(r)
        if b.round < R:                          # admission spans are timed
            assert db.pop("wall_time_s") > 0.0   # bridge: from the span
            db.pop("steps_per_sec"), dr.pop("wall_time_s"), \
                dr.pop("steps_per_sec")
        assert db == dr


def test_trace_version_roundtrip_and_legacy_load(game, tmp_path):
    engine = _sync_engine(game)
    engine.run(until_round=2)
    path = tmp_path / "trace.json"
    engine.trace.save(str(path))
    payload = json.loads(path.read_text())
    assert payload["version"] == TRACE_VERSION
    back = TraceRecorder.load(str(path))
    assert back.version == TRACE_VERSION and len(back.rounds) == 2
    # a versionless (pre-observability) trace still loads, as version 1
    del payload["version"]
    path.write_text(json.dumps(payload))
    legacy = TraceRecorder.load(str(path))
    assert legacy.version == 1
    assert [dataclasses.asdict(r) for r in legacy.rounds] == [
        dataclasses.asdict(r) for r in back.rounds]


def test_sync_metrics_carry_measured_round_wall(game):
    engine = _sync_engine(game,
                          compressor=StochasticQuantizeCompressor(bits=8))
    engine.run()
    assert engine.metrics.total("bytes_up") == engine.trace.total_bytes_up
    hist = engine.metrics.histogram("round_wall_s")
    assert hist["count"] == R and hist["min"] > 0.0
    rec = [r for r in engine.metrics.records
           if r["name"] == "round_wall_s"][0]
    assert rec["labels"] == {"engine": "sync", "codec": "q8",
                             "backend": "reference"}
