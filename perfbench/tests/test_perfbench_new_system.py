"""A new kind of model joins the benchmark by new files and entries alone.

In a copy of ``perfbench/`` and ``BENCHMARK.json`` a throwaway system — a
Qwen2-shaped decoder without the QKV bias — is added as a module that
reuses ``LMCell`` with its own ``arch``, reference, counts and tiny sizes,
with its config, traffic and limits files, a cell entry, and its name
appended to the ``workloads`` of the metrics it reports. No file that was
in the copy is edited but ``BENCHMARK.json``; the copy's spec tests pass,
the cell resolves, and a tiny sound run on the CPU is correct."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from perfbench import spec

SYSTEM = '''
import dataclasses

from . import lm
from .lm import LMCell, VARIANTS, context  # noqa: F401
from ..reference import nobias as reference

TINY = {"hidden_size": 16, "intermediate_size": 48,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 64}
TINY_TRAFFIC = {"seq": 8}


def arch(config):
    return dataclasses.replace(lm.arch(config), qkv_bias=False)


def counts(config, seq):
    out = lm.counts(config, seq)
    h, kh = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["hidden_size"] // h
    params = out["params"] - config["num_hidden_layers"] * (h + 2 * kh) * dh
    bytes_per_param = out["update_bytes_per_worker_step"] // out["params"]
    return dict(out, params=params,
                update_bytes_per_worker_step=bytes_per_param * params)


def build(config, traffic, **run):
    return LMCell(config, traffic, arch=arch, reference=reference,
                  counts=counts, **run)
'''

REFERENCE = '''
import jax.numpy as jnp

from . import qwen2

BIASES = ("bq", "bk", "bv")


def init_params(key, c, dtype=jnp.float32):
    p = qwen2.init_params(key, c, dtype)
    for b in BIASES:
        del p["stages"][0]["mixer"][b]
    return p


def loss(params, c, batch, dtype=jnp.float32):
    stage = params["stages"][0]
    mixer = dict(stage["mixer"])
    for b, w in zip(BIASES, ("wq", "wk", "wv")):
        mixer[b] = jnp.zeros(mixer[w].shape[:1] + mixer[w].shape[2:],
                             mixer[w].dtype)
    stages = [dict(stage, mixer=mixer)]
    return qwen2.loss(dict(params, stages=stages), c, batch, dtype)
'''

CELL = "nobias.m2-k2-q8"

RUN = """
import json, sys
import tiny_cells
from perfbench import spec
res = spec.resolve(spec.load_benchmark(), sys.argv[1])
out = tiny_cells.run(tiny_cells.tiny(sys.argv[1]))
print(json.dumps({"system": res["config"]["system"],
                  "end_to_end": [m["name"] for m in res["end_to_end"]],
                  "per_layer": [m["name"] for m in res["per_layer"]],
                  "correct": out["correct"], "checks": out["checks"],
                  "metrics": out["metrics"]}))
"""


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _add(root, rel, text):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel
    with open(path, "w") as f:
        f.write(text)


def test_second_system_by_new_files_alone(tmp_path):
    root = str(tmp_path)
    shutil.copy(spec.BENCHMARK, os.path.join(root, "BENCHMARK.json"))
    shutil.copytree(spec.HERE, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    before = _digests(root)

    _add(root, "perfbench/systems/nobias.py", SYSTEM)
    _add(root, "perfbench/reference/nobias.py", REFERENCE)
    with open(os.path.join(spec.HERE, "configs", "qwen2-0.5b.json")) as f:
        config = json.load(f)
    config.update(system="nobias", name="nobias-0.5b")
    config["run"]["qkv_bias"] = False
    _add(root, "perfbench/configs/nobias-0.5b.json", json.dumps(config))
    with open(os.path.join(spec.HERE, "traffic", "m1-k4-q8-s4096.json")) as f:
        traffic = json.load(f)
    traffic.update(workers=2, local_steps=2)
    _add(root, "perfbench/traffic/m2-k2-q8.json", json.dumps(traffic))
    with open(os.path.join(spec.HERE, "limits",
                           "qwen2-0.5b.m1-k4-q8-s4096.json")) as f:
        _add(root, f"perfbench/limits/{CELL}.json", f.read())

    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "nobias-0.5b", "source": "https://example.org/nobias",
        "file": "perfbench/configs/nobias-0.5b.json", "reduced": [],
        "why": "a throwaway second system"})
    bench["workloads"].append({
        "name": CELL, "config": "nobias-0.5b", "traffic": "m2-k2-q8",
        "chips": 1, "why": "a throwaway cell of the second system"})
    joined = ("tokens_per_s", "mfu.lm", "local_compute_ms.lm")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in joined:
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    after = _digests(root)
    assert {k for k in before if after[k] != before[k]} == {"BENCHMARK.json"}

    tests = os.path.join(root, "perfbench", "tests")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [tests, root, os.path.join(spec.ROOT, "src")]))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         os.path.join(tests, "test_perfbench_spec.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    p = subprocess.run([sys.executable, "-c", RUN, CELL], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["system"] == "nobias"
    assert out["end_to_end"] == ["tokens_per_s", "setup_s"]
    assert sorted(out["per_layer"]) == sorted(joined[1:])
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["correct"] is True, out["checks"]
