"""BENCHMARK.json keeps to its contract, and every cell resolves by name to
its configuration, traffic mix, limits and metric readers."""
import importlib
import json
import os
import re

import pytest

from perfbench import spec, systems

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(spec.BENCHMARK) <= 64 * 1024


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert len(set(CELLS)) == len(CELLS)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_setup_bound_and_layer_names_agree():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]
    layers = {}
    for m in BENCH["per_layer"]:
        stem = m["name"].split(".")[0]
        layers.setdefault(stem, set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    res = spec.resolve(BENCH, cell)
    entry = res["config_entry"]
    assert entry["file"].startswith("perfbench/configs/")
    assert os.path.isfile(os.path.join(spec.HERE, "systems",
                                       res["config"]["system"] + ".py"))
    system = importlib.import_module(
        f"perfbench.systems.{res['config']['system']}")
    missing = [n for n in systems.CONTRACT if not hasattr(system, n)]
    assert not missing, missing
    assert callable(system.context) and callable(system.build)
    assert system.VARIANTS and set(system.TINY) <= set(res["config"])
    assert set(system.TINY_TRAFFIC) <= set(res["traffic"])
    for key in entry["reduced"]:
        assert key in res["config"]["published"], key
    assert res["limits"] and set(res["limits"]) <= {"loss", "grad", "change"}
    assert all(0.0 < v["limit"] < 1.0 for v in res["limits"].values())
    e2e = {m["name"] for m in res["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert res["per_layer"]
    for m in res["end_to_end"]:
        assert callable(spec.load_module("e2e", m["name"]).read)
    for m in res["per_layer"]:
        assert m["moves"] in e2e, (m["name"], cell)
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_every_config_is_used_and_files_are_unique():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)


def test_per_layer_workloads_report_what_they_move():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            e2e = {e["name"] for e in spec.resolve(BENCH, cell)["end_to_end"]}
            assert m["moves"] in e2e, (m["name"], cell)
