"""Observability: dual-clock spans, metrics, and Perfetto export.

The Parameter-Server story is a *time* story — local compute traded against
uplink cost (sync engines), staleness traded against idle time (the
event-driven engine) — and this package is the layer that makes the time
visible without perturbing a single bit of the numerics:

* :mod:`~repro.obs.spans` — hierarchical :class:`SpanTracer` recording
  host wall-clock **and** (in the async engine) simulated-clock intervals,
  on per-worker tracks;
* :mod:`~repro.obs.metrics` — :class:`MetricsRegistry` counters/gauges/
  histograms with JSONL sinks;
* :mod:`~repro.obs.export` — Chrome/Perfetto trace-event JSON of either
  clock (:func:`save_trace_events`), schema-checked by
  :func:`validate_trace_events`.

Every engine takes ``tracer=``/``metrics=`` (defaults are enabled,
in-memory, near-zero overhead); the instrumentation never runs inside jit,
so all bit-exactness and parity pins hold with tracing on — enforced by
``tests/test_obs.py``.

Examples
--------
>>> from repro.obs import SpanTracer, to_trace_events, validate_trace_events
>>> tr = SpanTracer()
>>> _ = tr.add_span("local-compute r0", cat="local-compute",
...                 track="worker/0", sim_t0=0.0, sim_t1=2.0)
>>> validate_trace_events(to_trace_events(tr.spans, clock="sim"))
"""
from .export import save_trace_events, to_trace_events, validate_trace_events
from .metrics import MetricsRegistry
from .spans import CATEGORIES, Span, SpanTracer

__all__ = [
    "CATEGORIES",
    "MetricsRegistry",
    "Span",
    "SpanTracer",
    "save_trace_events",
    "to_trace_events",
    "validate_trace_events",
]
