#!/usr/bin/env python3
"""Smoke run of LocalAdaSEG's Parameter-Server training path on a TPU.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the shard_map engine only

Phase A trains qwen2-0.5b at its published widths through
``launch.train.make_ps_engine`` → ``PSEngine``: the fused Pallas AdaSEG
step and sync codec, q8 uplinks with error feedback and Pallas flash
attention, M=2 workers on the chip, K=4 local steps, 3 rounds. The depth
is the largest the compiled round's ``memory_analysis()`` fits on the chip
(at least 4 layers). It fails unless the round program contains the
kernels (``tpu_custom_call``), every eval loss is finite and the last is
below the first.

Phase B runs the §4.1 bilinear game through ``PSEngine`` twice, all fused
and all reference, from one seed and with A@y at f32: z̄ must agree within
rtol=1e-5. The game's leaves fit one block, so the tree-level kernel
wrappers then meet their references on leaves cut into several ragged
blocks.

``--chips 4`` runs ``PSEngine(mesh=...)`` with one worker per chip: the
bilinear game with q8 uplinks against the serial engine (rtol=1e-5), then
the Phase A model at M=4.

Everything runs in this one process. The last line of stdout is one JSON
object naming the device, printed only when every phase passed; without a
TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RTOL, ATOL = 1e-5, 1e-6
GIB = 2 ** 30
K, ROUNDS = 4, 3                  # local steps per round, rounds
BATCH, SEQ = 2, 256               # per worker and oracle call
MIN_LAYERS = 4
MEM_SHARE = 0.95                  # of bytes_limit a round may plan to use
GAME_N = 1_024                    # bilinear game: A is n×n (4 MiB)
MESH_GAME_N = 64                  # small: under q8 an ulp can flip a draw
# The bilinear comparisons run A@y at full f32. At the TPU's default
# (one bf16 pass) a last-ulp difference between two programs' z moves a
# bf16 rounding of an input of A@y, and the trajectories part by far more
# than rtol=1e-5 for reasons that have nothing to do with the kernels.
GAME_PRECISION = "float32"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {msg}")


def agree(name, got, want, atol=ATOL):
    """Every leaf of ``got`` within rtol=1e-5 (and ``atol``) of ``want``."""
    import jax

    worst = 0.0          # max |got − want| / (atol + rtol·|want|), ≤ 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        check(a.shape == b.shape, f"{name}: shapes {a.shape} vs {b.shape}")
        ratio = np.abs(a - b) / (atol + RTOL * np.abs(b))
        worst = max(worst, float(np.max(ratio, initial=0.0)))
    check(worst <= 1.0, f"{name}: |diff| reaches {worst:.3g}× the "
          f"tolerance (rtol={RTOL}, atol={atol:.3g})")
    log(f"  {name}: agree, |diff| ≤ {worst:.3g}× the tolerance "
        f"(rtol={RTOL}, atol={atol:.3g})")


# ---------------------------------------------------------------------------
# The qwen2-0.5b training path
# ---------------------------------------------------------------------------

def lm_engine(layers: int, workers: int, mesh=None):
    import jax

    from repro.configs.qwen2_0_5b import CONFIG
    from repro.core import AdaSEGConfig
    from repro.launch.train import TrainPlan, make_ps_engine
    from repro.ps import StochasticQuantizeCompressor

    cfg = dataclasses.replace(CONFIG, num_layers=layers,
                              attn_backend="pallas")
    plan = TrainPlan(
        cfg=cfg,
        adaseg=AdaSEGConfig(g0=20.0, diameter=2.0,
                            alpha=1.0 / math.sqrt(workers), k=K,
                            average_output=False),
        worker_mode="paper", k_local=K, global_batch=BATCH * workers,
        seq=SEQ, workers_override=workers,
    )
    return make_ps_engine(
        plan, jax.random.PRNGKey(0), rounds=ROUNDS, mesh=mesh,
        compressor=StochasticQuantizeCompressor(bits=8),
        backend="fused", codec_backend="fused",
    )


def round_bytes(layers: int) -> tuple[int, float]:
    """Device bytes the compiled one-round program needs at ``layers``
    (arguments + outputs not aliased to them + temporaries)."""
    gc.collect()                  # free the last probe's state on device
    eng = lm_engine(layers, 2)
    t0 = time.perf_counter()
    ma = eng.lower_rounds(1).compile().memory_analysis()
    secs = time.perf_counter() - t0
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    log(f"  {layers:2d} layers: round needs {need / GIB:.2f} GiB "
        f"(arguments {ma.argument_size_in_bytes / GIB:.2f}, temporaries "
        f"{ma.temp_size_in_bytes / GIB:.2f}), compiled in {secs:.1f} s")
    return need, secs


def pick_depth(budget: float, full: int) -> tuple[int, float, str]:
    """The most layers whose compiled round fits ``budget`` bytes: two
    compiles give the per-layer slope (memory is linear in depth under the
    layer scan), then the prediction is confirmed, stepping down if it
    does not fit."""
    need = {}
    for layers in (MIN_LAYERS, 2 * MIN_LAYERS):
        need[layers] = round_bytes(layers)
    check(need[MIN_LAYERS][0] <= budget,
          f"{MIN_LAYERS} layers need {need[MIN_LAYERS][0] / GIB:.2f} GiB "
          f"> budget {budget / GIB:.2f} GiB")
    slope = (need[2 * MIN_LAYERS][0] - need[MIN_LAYERS][0]) / MIN_LAYERS
    layers = MIN_LAYERS + int((budget - need[MIN_LAYERS][0]) // slope)
    layers = max(MIN_LAYERS, min(full, layers))
    log(f"  slope {slope / GIB:.3f} GiB/layer → predict {layers} layers")
    while True:
        if layers not in need:
            need[layers] = round_bytes(layers)
        if need[layers][0] <= budget or layers == MIN_LAYERS:
            break
        layers -= 1
    over = [f"{n} ({b / GIB:.2f} GiB)" for n, (b, _) in sorted(need.items())
            if b > budget]
    why = (f"{full} published; kept {layers}: its round needs "
           f"{need[layers][0] / GIB:.2f} GiB of the {budget / GIB:.2f} GiB "
           f"budget" + (f"; over budget: {', '.join(over)}" if over else ""))
    return layers, need[layers][1], why


def phase_a(dev) -> None:
    import jax

    from repro.configs.qwen2_0_5b import CONFIG

    log("== Phase A: qwen2-0.5b through make_ps_engine, M=2, K=4, q8-EF, "
        "fused step + codec, Pallas attention")
    limit = dev.memory_stats()["bytes_limit"]
    budget = MEM_SHARE * limit
    log(f"  device bytes_limit {limit / GIB:.2f} GiB; round budget "
        f"{budget / GIB:.2f} GiB ({MEM_SHARE:.0%})")
    layers, compile_s, why = pick_depth(budget, CONFIG.num_layers)
    log(f"  layers: {why}")

    gc.collect()
    eng = lm_engine(layers, 2)
    text = eng.lower_rounds(1).compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"  round program: {n_kernels} tpu_custom_call "
        f"(compile {compile_s:.1f} s)")
    check(n_kernels > 0, "no Pallas kernel in the compiled round program")
    params = sum(v.size for v in jax.tree.leaves(eng.state.z_tilde)) // 2
    log(f"  widths d_model={CONFIG.d_model} heads={CONFIG.num_heads}/"
        f"{CONFIG.num_kv_heads} d_ff={CONFIG.d_ff} vocab={CONFIG.vocab_size}"
        f" layers={layers}: {params / 1e6:.1f}M parameters per worker")

    losses = []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        eng.run(until_round=r + 1)        # ends in block_until_ready
        wall = time.perf_counter() - t0
        rec = eng.trace.rounds[-1]
        losses.append(rec.residual)
        log(f"  round {r}: wall {wall:.3f} s, eval loss {rec.residual:.5f}, "
            f"η mean {rec.eta_mean:.5f}, uplinked {rec.bytes_up} B")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"eval loss did not fall: {losses[0]:.5f} → {losses[-1]:.5f}")
    stats = dev.memory_stats()
    log(f"  peak device memory {stats.get('peak_bytes_in_use', 0) / GIB:.2f}"
        f" GiB")


# ---------------------------------------------------------------------------
# Fused ≡ reference on the chip
# ---------------------------------------------------------------------------

def bilinear_engine(game, backend: str, workers: int, *, q8: bool,
                    mesh=None):
    import jax

    from repro.core import AdaSEGConfig
    from repro.ps import PSConfig, PSEngine, StochasticQuantizeCompressor

    cfg = PSConfig(
        adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=K),
        num_workers=workers, rounds=ROUNDS,
        compressor=StochasticQuantizeCompressor(bits=8) if q8 else None,
        backend=backend, codec_backend=backend,
    )
    return PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(1), mesh=mesh,
                    eval_fn=game.residual)


def phase_b() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.adaseg_update.ops import (
        adaseg_tree_anchor,
        adaseg_tree_explore,
        adaseg_tree_update,
    )
    from repro.kernels.sync_compress.ops import (
        codec_uplink_stacked,
        server_outer_apply,
        sync_merge_stacked,
    )
    from repro.problems import make_bilinear_game

    # Identity uplinks: under q8 the step backends' last-ulp differences
    # flip stochastic-rounding decisions, a whole quantization step each.
    log(f"== Phase B: bilinear game n={GAME_N} through PSEngine, M=4, "
        f"identity uplinks, fused vs reference")
    game = make_bilinear_game(jax.random.PRNGKey(0), n=GAME_N, sigma=0.1)
    out = {}
    for backend in ("fused", "reference"):
        t0 = time.perf_counter()
        with jax.default_matmul_precision(GAME_PRECISION):
            eng = bilinear_engine(game, backend, 4, q8=False)
            out[backend] = eng.run()
        log(f"  {backend}: residual {eng.trace.rounds[-1].residual:.6f} "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")
    agree("z̄ fused vs reference", out["fused"], out["reference"])

    log("== Phase B: kernel wrappers on several ragged blocks "
        "(block cap 65536 elements)")
    ks = iter(jax.random.split(jax.random.PRNGKey(2), 16))

    def tree():
        return {"a": jax.random.normal(next(ks), (1_000_003,)),
                "b": jax.random.normal(next(ks), (517, 896))}

    z, m, g = tree(), tree(), tree()
    blk = dict(block=65_536)
    eta = dict(sum_sq=jnp.float32(3.0), g0=1.0, d_alpha=2.0)
    for proj in (("box", -1.0, 1.0), ("l2", 900.0)):
        agree(f"adaseg_tree_update {proj[0]}",
              adaseg_tree_update(z, m, g, proj=proj, **eta, **blk),
              adaseg_tree_update(z, m, g, proj=proj, use_kernel=False,
                                 **eta))
        zt = adaseg_tree_explore(z, m, proj=proj, **eta, **blk)
        agree(f"adaseg_tree_explore {proj[0]}", zt,
              adaseg_tree_explore(z, m, proj=proj, use_kernel=False, **eta))
        agree(f"adaseg_tree_anchor {proj[0]}",
              adaseg_tree_anchor(z, zt[0], g, proj=proj, **eta, **blk),
              adaseg_tree_anchor(z, zt[0], g, proj=proj, use_kernel=False,
                                 **eta))

    stacked = jax.tree.map(lambda *v: jnp.stack(v), tree(), tree(), tree(),
                           tree())
    w = jnp.array([0.1, 0.4, 0.2, 0.3])
    alive = jnp.array([1.0, 0.0, 1.0, 1.0])
    recv = alive > 0
    ef = jax.tree.map(lambda v: 0.01 * v, stacked)
    rngs = jax.random.split(jax.random.PRNGKey(3), 4)
    for codec in (("identity",), ("topk", 0.25)):
        agree(f"codec_uplink_stacked {codec[0]}",
              codec_uplink_stacked(stacked, rngs, w, ef, alive, codec=codec,
                                   **blk),
              codec_uplink_stacked(stacked, rngs, w, ef, alive, codec=codec,
                                   use_kernel=False))
    # A stochastic-rounding decision right at a boundary may flip on one
    # ulp of difference in w·z + ef: allow one quantization step there.
    sent_f, ef_f = codec_uplink_stacked(stacked, rngs, w, ef, alive,
                                        codec=("quantize", 8), **blk)
    sent_r, ef_r = codec_uplink_stacked(stacked, rngs, w, ef, alive,
                                        codec=("quantize", 8),
                                        use_kernel=False)
    step = max(float(jnp.max(jnp.abs(v))) for v in jax.tree.leaves(sent_r))
    step = 2.0 * step / 255.0
    flips = sum(int(jnp.sum(jnp.abs(a - b) > ATOL + RTOL * jnp.abs(b)))
                for a, b in zip(jax.tree.leaves(sent_f),
                                jax.tree.leaves(sent_r)))
    log(f"  codec_uplink_stacked quantize: {flips} rounding flips")
    check(flips <= 4, f"{flips} quantize rounding flips")
    agree("codec_uplink_stacked quantize sent", sent_f, sent_r, atol=step)
    agree("codec_uplink_stacked quantize ef", ef_f, ef_r, atol=step)
    for agg in (None, ("trimmed", 1)):
        kw = dict(recv=recv, old=stacked, normalize=True, agg=agg)
        agree(f"sync_merge_stacked {agg}",
              sync_merge_stacked(stacked, w, **kw, **blk),
              sync_merge_stacked(stacked, w, **kw, use_kernel=False))
    srv = jax.tree.map(lambda v: v[:1], stacked)
    merged = jax.tree.map(lambda v: v[1:2], stacked)
    mom = (jax.tree.map(jnp.zeros_like, srv),) * 2
    spec = ("adam", 0.01, 0.9, 0.99, 1e-8)
    agree("server_outer_apply adam",
          server_outer_apply(merged, srv, mom, jnp.int32(3), spec=spec,
                             **blk),
          server_outer_apply(merged, srv, mom, jnp.int32(3), spec=spec,
                             use_kernel=False))


# ---------------------------------------------------------------------------
# Four chips: the shard_map engine, one worker per chip
# ---------------------------------------------------------------------------

def phase_mesh(devices) -> None:
    import jax

    from repro.launch.mesh import make_test_mesh
    from repro.problems import make_bilinear_game

    mesh = make_test_mesh(data=len(devices), model=1)
    m = len(devices)
    log(f"== Mesh: bilinear game n={MESH_GAME_N}, M={m} sharded over "
        f"{dict(mesh.shape)} vs serial, q8-EF uplinks, fused")
    game = make_bilinear_game(jax.random.PRNGKey(0), n=MESH_GAME_N,
                              sigma=0.1)
    z = {}
    for name, mm in (("sharded", mesh), ("serial", None)):
        t0 = time.perf_counter()
        with jax.default_matmul_precision(GAME_PRECISION):
            eng = bilinear_engine(game, "fused", m, q8=True, mesh=mm)
            z[name] = eng.run()
        log(f"  {name}: residual {eng.trace.rounds[-1].residual:.6f} "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")
    agree("z̄ sharded vs serial", z["sharded"], z["serial"])

    layers = 2 * MIN_LAYERS
    log(f"== Mesh: qwen2-0.5b at {layers} layers, M={m}, one worker per "
        f"chip, q8-EF, fused step + codec, Pallas attention")
    eng = lm_engine(layers, m, mesh=mesh)
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        eng.run(until_round=r + 1)
        rec = eng.trace.rounds[-1]
        log(f"  round {r}: wall {time.perf_counter() - t0:.3f} s, eval loss "
            f"{rec.residual:.5f}")
        check(math.isfinite(rec.residual), "non-finite eval loss")
    leaf = max(jax.tree.leaves(eng.state.z_tilde), key=lambda v: v.size)
    placed = sorted((s.index[0].start, s.device.id)
                    for s in leaf.addressable_shards)
    log(f"  {leaf.shape} state leaf: device_set "
        f"{sorted(d.id for d in leaf.sharding.device_set)}; "
        f"(worker, device) of its shards: {placed}")
    check(sorted(w for w, _ in placed) == list(range(m))
          and len({d for _, d in placed}) == m,
          f"workers are not one per device: {placed}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device {dev.device_kind} ({dev.platform}) x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir} "
        f"({entries} entries)")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(devices[:4])
    else:
        phase_a(dev)
        phase_b()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"persistent compile-cache hits: {len(hits)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
