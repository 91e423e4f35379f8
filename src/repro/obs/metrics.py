"""Counter/gauge/histogram registry with JSONL + in-memory sinks.

The engines emit their quantitative telemetry here — bytes up/down,
effective local steps, η spread, admitted staleness, round wall times and
the chunk tracings each call set off (``chunk_traces``).

Records are plain dicts (``kind``/``name``/``value``/``labels`` + optional
``t_wall``/``t_sim``) accumulated in memory; :meth:`MetricsRegistry.save_jsonl`
streams them one-per-line and :meth:`MetricsRegistry.load_jsonl` is the
inverse. Like the span tracer, emission is host-side only — nothing touches
a jitted computation, so metrics are inert by construction (and pinned so
by ``tests/test_obs.py``).

Examples
--------
>>> reg = MetricsRegistry()
>>> reg.inc("bytes_up", 80.0, engine="sync")
>>> reg.inc("bytes_up", 40.0, engine="sync")
>>> reg.set_gauge("eta_spread", 1.5)
>>> reg.observe("staleness", 2.0)
>>> reg.total("bytes_up"), reg.last("eta_spread")
(120.0, 1.5)
>>> reg.histogram("staleness")["count"]
1
"""
from __future__ import annotations

import json
from typing import Any


class MetricsRegistry:
    """In-memory metric sink with counter/gauge/histogram semantics.

    Examples
    --------
    >>> reg = MetricsRegistry()
    >>> reg.inc("steps", 12, worker="0")
    >>> reg.total("steps")
    12.0
    """

    def __init__(self, *, enabled: bool = True):
        self.enabled = bool(enabled)
        self.records: list[dict] = []

    # -- emission -----------------------------------------------------------

    def emit(self, kind: str, name: str, value: float,
             t_wall: float | None = None, t_sim: float | None = None,
             **labels: Any) -> None:
        if not self.enabled:
            return
        rec: dict = {"kind": kind, "name": name, "value": float(value)}
        if labels:
            rec["labels"] = labels
        if t_wall is not None:
            rec["t_wall"] = float(t_wall)
        if t_sim is not None:
            rec["t_sim"] = float(t_sim)
        self.records.append(rec)

    def inc(self, name: str, value: float = 1.0, **labels: Any) -> None:
        self.emit("counter", name, value, **labels)

    def set_gauge(self, name: str, value: float, **labels: Any) -> None:
        self.emit("gauge", name, value, **labels)

    def observe(self, name: str, value: float, **labels: Any) -> None:
        self.emit("histogram", name, value, **labels)

    # -- in-memory aggregation ----------------------------------------------

    def _values(self, name: str, kind: str | None = None) -> list[float]:
        return [r["value"] for r in self.records
                if r["name"] == name and (kind is None or r["kind"] == kind)]

    def total(self, name: str) -> float:
        """Sum of every ``counter`` emission under ``name``."""
        return float(sum(self._values(name, "counter")))

    def last(self, name: str) -> float | None:
        """Latest ``gauge`` value under ``name`` (None if never set)."""
        vals = self._values(name, "gauge")
        return vals[-1] if vals else None

    def histogram(self, name: str) -> dict:
        """Summary stats over every ``histogram`` observation of ``name``."""
        vals = self._values(name, "histogram")
        if not vals:
            return {"count": 0}
        return {
            "count": len(vals),
            "sum": float(sum(vals)),
            "min": float(min(vals)),
            "max": float(max(vals)),
            "mean": float(sum(vals) / len(vals)),
        }

    def names(self) -> list[str]:
        seen: dict[str, None] = {}
        for r in self.records:
            seen.setdefault(r["name"])
        return list(seen)

    # -- serialization ------------------------------------------------------

    def save_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for r in self.records:
                f.write(json.dumps(r) + "\n")

    @classmethod
    def load_jsonl(cls, path: str) -> "MetricsRegistry":
        reg = cls()
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    reg.records.append(json.loads(line))
        return reg

