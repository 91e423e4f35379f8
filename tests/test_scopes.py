"""Where the round's work is named.

On the device: the compiled round scopes ``sync`` and ``local-compute``,
on the serial and the ``shard_map`` path alike, and inside the local step
the model scopes ``attention`` and ``vocab`` and the update scopes
``adaseg-update`` (HLO ``op_name`` metadata, read from CPU compiles). On
the host: ``PSEngine.run`` spans the work between chunk calls, and each
chunk span counts the tracings its call set off."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from repro.core import AdaSEGConfig
from repro.models import ModelWorker, make_lm_problem, tiny_lm_config
from repro.models.problem import make_eval_loss
from repro.obs import MetricsRegistry, SpanTracer
from repro.problems import make_bilinear_game
from repro.ps import PSConfig, PSEngine, StochasticQuantizeCompressor

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
LAYERS = ("attention", "vocab", "adaseg-update")
BATCH, SEQ = 1, 8


def tiny_lm_engine(mesh=None, workers=1, tracer=None):
    """A tiny Qwen2-style decoder (QKV bias, Pallas attention) trained as
    the benchmark trains it: fused AdaSEG step, fused q8-EF uplink."""
    cfg = dataclasses.replace(tiny_lm_config(attn_backend="pallas"),
                              qkv_bias=True)
    acfg = AdaSEGConfig(g0=20.0, diameter=0.2, alpha=1.0, k=2,
                        average_output=False)
    ps = PSConfig(num_workers=workers, rounds=4, local_k=2,
                  worker=ModelWorker(acfg, backend="fused", arch=cfg.name),
                  compressor=StochasticQuantizeCompressor(bits=8),
                  codec_backend="fused")
    return PSEngine(make_lm_problem(cfg, batch=BATCH, seq=SEQ), ps,
                    jax.random.PRNGKey(0), mesh=mesh,
                    eval_fn=make_eval_loss(cfg, batch=BATCH, seq=SEQ),
                    tracer=tracer)


def op_names(hlo_text: str) -> list[str]:
    """The scope paths of the compiled program's top-level ops."""
    return [n for n in re.findall(r'op_name="([^"]*)"', hlo_text)
            if n.startswith("jit(")]


def component(name: str, path: str):
    """Where ``name`` sits in ``path`` as a component, bare or inside
    transformation wrappers (``vmap(adaseg-update)``), not as ``jit(name)``,
    a function of that name."""
    return re.search(rf"(^|/)(?:(?!p?jit\()[\w.-]+\()*{re.escape(name)}"
                     r"\)*(/|$)", path)


@pytest.fixture(scope="module")
def serial_names():
    eng = tiny_lm_engine()
    return op_names(eng.lower_rounds(1).compile().as_text())


@pytest.mark.parametrize("layer", LAYERS)
def test_serial_chunk_scopes_layer_inside_local_compute(serial_names,
                                                        layer):
    inside = [n for n in serial_names
              if component(layer, n) and "/local-compute/" in n]
    assert inside, layer
    # the layer nests inside local-compute and never wraps it
    assert all(n.index("/local-compute/") < component(layer, n).start()
               for n in inside)


def pallas_kernels(jaxpr, path=""):
    """(kernel name, scope path) of every Pallas call in ``jaxpr``, its
    sub-jaxprs included: the device's custom calls, named by the kernel."""
    if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        here = "/".join(filter(None, [path, str(eqn.source_info.name_stack)]))
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"].debug_info.func_name, here
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else [p]:
                if isinstance(sub, (jax.extend.core.Jaxpr,
                                    jax.extend.core.ClosedJaxpr)):
                    yield from pallas_kernels(sub, here)


def test_attention_backward_kernel_scoped_inside_local_compute():
    """The Pallas attention backward runs under ``attention`` inside
    ``local-compute``, where ``attention_ms`` and ``local_compute_ms`` read
    it; its compiled instruction names, which the update roofline must not
    read, are checked in ``test_tpu_compile.py``."""
    eng = tiny_lm_engine()
    jaxpr = jax.make_jaxpr(eng._chunk_fn)(*eng._chunk_args(0, 1))
    kernels = list(pallas_kernels(jaxpr))
    bwd = [p for n, p in kernels if n == "_flash_bwd_kernel"]
    # one backward a step's oracle call (explore and anchor), and no other
    # attention kernel than the forward (and its recompute)
    assert len(bwd) == 2, kernels
    assert {n for n, p in kernels if component("attention", p)} == {
        "_flash_kernel", "_flash_bwd_kernel"}
    for p in bwd:
        assert p.startswith("local-compute/") and "transpose(" in p, p
        assert component("attention", p), p


def test_serial_chunk_local_compute_stays_plain(serial_names):
    # traceio.in_scope matches a bare component: no wrapper may reach it
    assert any("/local-compute/" in n for n in serial_names)
    assert any("/sync/" in n for n in serial_names)
    assert not any(re.search(r"\((local-compute|sync)\)", n)
                   for n in serial_names)
    # the update runs only in the local step, and its scope leaves the
    # oracle calls out: no op is both update and model
    update = [n for n in serial_names if component("adaseg-update", n)]
    assert update and all("/local-compute/" in n for n in update)
    assert not any(component("attention", n) or component("vocab", n)
                   for n in update)


SHARDED = """
import json, re, sys
sys.path.insert(0, {here!r})
import jax
from repro.launch.mesh import make_test_mesh
from repro.obs import SpanTracer
from test_scopes import op_names, tiny_lm_engine

tracer = SpanTracer()
eng = tiny_lm_engine(mesh=make_test_mesh(4, 1), workers=4, tracer=tracer)
eng.run(until_round=1)
eng.run(until_round=2)
traces = [s.attrs["traces"] for s in tracer.by_cat("chunk")]
lowered = jax.jit(eng._chunk_fn).lower(*eng._chunk_args(2, 3))
text = lowered.compile().as_text()
reduces = [re.search(r'op_name="([^"]*)"', line).group(1)
           for line in text.splitlines()
           if re.search(r" all-reduce(-start)?\\(", line)
           and "op_name=" in line]
print(json.dumps({{"traces": traces, "all_reduce": reduces,
                   "names": op_names(text),
                   "spans": [s.name for s in tracer.spans]}}))
"""


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           HERE]))
    p = subprocess.run([sys.executable, "-c", SHARDED.format(here=HERE)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_chunk_scopes_sync_and_local_compute(sharded):
    # the round's psums; the η statistics over workers, reduced outside
    # the shard_map round, are the chunk's own
    reduces = [n for n in sharded["all_reduce"] if "/shard_map/" in n]
    assert reduces and all("/sync/" in n for n in reduces), reduces
    names = sharded["names"]
    assert any("/local-compute/" in n for n in names)
    for layer in LAYERS:
        assert any(component(layer, n) and "/local-compute/" in n
                   for n in names), layer
    assert not any(re.search(r"\((local-compute|sync)\)", n) for n in names)


def test_sharded_chunk_counts_its_tracings(sharded):
    assert sharded["traces"] == [1, 0]
    assert "run [1,2) args" in sharded["spans"]


# ---------------------------------------------------------------------------
# Host spans and the tracing counter
# ---------------------------------------------------------------------------

def _game_engine(game, rounds, *, k=3, workers=5, tracer=None,
                 metrics=None):
    cfg = PSConfig(adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=k),
                   num_workers=workers, rounds=rounds)
    return PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(1),
                    eval_fn=game.residual,
                    tracer=tracer or SpanTracer(), metrics=metrics)


def test_host_spans_between_chunk_calls():
    game = make_bilinear_game(jax.random.PRNGKey(0), n=6, sigma=0.1)
    tr = SpanTracer()
    eng = _game_engine(game, 5, tracer=tr)
    eng.run(until_round=4, checkpoint_every=2)
    eng.step_round()
    by_name = {s.name: s for s in tr.spans}
    run = by_name["run [0,4)"]
    for child in ("run [0,4) args", "chunk [0,2)", "run [0,4) telemetry",
                  "chunk [2,4)", "run [0,4) z_bar"):
        sp = by_name[child]
        assert sp.parent == run.id, child
        assert run.wall_t0 <= sp.wall_t0 <= sp.wall_t1 <= run.wall_t1
    names = [s.name for s in tr.spans]
    # per chunk: arguments, the call, its telemetry, in that order
    assert names.count("run [0,4) args") == 2
    assert names.count("run [0,4) telemetry") == 2
    args = [s for s in tr.spans if s.name == "run [0,4) args"]
    chunks = [by_name["chunk [0,2)"], by_name["chunk [2,4)"]]
    for a, c in zip(args, chunks):
        assert a.wall_t1 <= c.wall_t0
    assert by_name["run [0,4) z_bar"].wall_t0 >= chunks[-1].wall_t1
    # a lone step has its own args and telemetry spans, no z_bar
    assert {"run [4,5) args", "chunk [4,5)",
            "run [4,5) telemetry"} <= set(names)
    assert "run [4,5) z_bar" not in names
    assert {s.cat for s in tr.spans} >= {"run", "args", "chunk",
                                         "telemetry", "output", "round"}


def test_chunk_spans_count_tracings():
    # a game of its own: no other test shares its compiled chunks
    game = make_bilinear_game(jax.random.PRNGKey(5), n=7, sigma=0.1)
    reg = MetricsRegistry()
    tr = SpanTracer()
    _game_engine(game, 5, tracer=tr, metrics=reg).run(checkpoint_every=2)
    # chunk lengths 2, 2, 1: a new length compiles, a repeat does not
    assert [s.attrs["traces"] for s in tr.by_cat("chunk")] == [1, 0, 1]
    assert reg.total("chunk_traces") == 2.0
    tr2 = SpanTracer()
    _game_engine(game, 5, tracer=tr2).run(checkpoint_every=2)
    assert [s.attrs["traces"] for s in tr2.by_cat("chunk")] == [0, 0, 0]
