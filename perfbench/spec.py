"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, one traffic mix, one metric or
one cell's correctness limits sits in a file of its own, found by name:

* ``configs/<config>.json``      — the configuration as it is run;
* ``traffic/<traffic>.json``     — the traffic mix (workers, local steps,
  batch, uplink codec, placement);
* ``limits/<workload>.json``     — the limit of each number that decides
  ``correct`` in that cell, with the readings it was set from;
* ``e2e/<metric>.py``            — how an end-to-end metric is taken from
  the window's host clock;
* ``metrics/<metric>.py``        — how a per-layer metric is read from the
  traced window.

A later change adds a cell by adding files and entries, never by editing a
file that is already here. A configuration names its ``system``, a module
of ``systems/`` found by that name (see ``systems/__init__.py``), so a new
kind of model joins by a module of its own as well.

A cell reports a metric when its name is in that metric's ``workloads``
list (an end-to-end metric without the list is reported in every cell, a
per-layer metric without it in every cell that reports the metric it
moves). A change that adds a cell appends the cell's name to the lists of
the metrics it reports, and changes no bound and no reader.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str, e2e_names: set[str]) -> bool:
    """A metric with ``workloads`` is reported in those cells; a per-layer
    metric without it in every cell that reports the metric it moves; an
    end-to-end metric without it in every cell."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def resolve(bench: dict, workload: str) -> dict:
    """The cell ``workload`` with its config, traffic, limits and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, e2e_names)]
    return {
        "cell": cell,
        "config_entry": entry,
        "config": config,
        "traffic": _json("traffic", cell["traffic"]),
        "limits": _json("limits", workload),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }
