"""Parameter-Server fleet simulation: stragglers, 8-bit sync, faults, resume.

    PYTHONPATH=src python examples/ps_simulate.py

Runs LocalAdaSEG on the paper's §4.1 bilinear game through the PS runtime
(``repro.ps``) in a deliberately hostile fleet: Dirichlet-heterogeneous
worker data, a straggler schedule, per-round worker failures, and 8-bit
stochastically-quantized uplinks with error feedback. Mid-run the engine is
"killed" (checkpointed + discarded) and resumed from disk — the resumed
trajectory is the one an uninterrupted run would have produced.

The runtime is optimizer-generic: the same hostile fleet then runs a zoo
baseline (LocalSEGDA via ``MinimaxWorker``) for comparison — the paper's
Fig. 4 match-up, but under production conditions.

The third act drops the barrier entirely: the *event-driven* engine
(``AsyncPSEngine``) runs the same algorithm over simulated time with one
Markov-slow worker and a τ=2 staleness bound, crashes mid-event-queue, and
resumes bit-exactly — admissions, simulated clock and all.

The final act makes the fleet *hostile* (``repro.ps.robust``): 20% of the
workers sign-flip their uplinks every round, the server swaps its weighted
mean for a trimmed-mean merge, and the run is killed and resumed
mid-attack — the resumed trajectory is bit-exact because the attack table,
like every other policy, is a deterministic function of its seed. The same
attacked fleet under the plain mean shows why the robust merge earns its
keep.

Both engines record ``repro.obs`` spans as they go; the script exports two
Perfetto/Chrome timelines next to itself (open them at
https://ui.perfetto.dev):

* ``perfetto_sync_wall.json``  — the synchronous run on the host wall clock;
* ``perfetto_async_sim.json``  — the τ=2 straggler run on the *simulated*
  clock, one swimlane per worker: uplink flights, staleness holds,
  broadcasts, local compute and the slow worker's long phases.
"""
import dataclasses
import math
import os
import tempfile

import jax
import numpy as np

from repro.core import AdaSEGConfig
from repro.launch.cache import enable_compile_cache
from repro.obs import save_trace_events, validate_trace_events
from repro.optim import MinimaxWorker, segda
from repro.problems import make_bilinear_game
from repro.ps import (
    AsyncPSConfig,
    AsyncPSEngine,
    BernoulliFaults,
    MarkovLatency,
    PSConfig,
    PSEngine,
    SignFlipAttack,
    StochasticQuantizeCompressor,
    StragglerSchedule,
    TrimmedMean,
    heterogeneous_bilinear,
)

M, K, R = 4, 20, 30
N = 10


def main():
    enable_compile_cache()
    game = make_bilinear_game(jax.random.PRNGKey(0), n=N, sigma=0.1)
    problem = heterogeneous_bilinear(game, M, jax.random.PRNGKey(1), alpha=0.4)
    pscfg = PSConfig(
        adaseg=AdaSEGConfig(g0=1.0, diameter=float(np.sqrt(2 * N)),
                            alpha=1.0, k=K),
        num_workers=M,
        rounds=R,
        schedule=StragglerSchedule(k=K, min_frac=0.5, seed=2,
                                   slow_workers=(3,)),
        compressor=StochasticQuantizeCompressor(bits=8),
        faults=BernoulliFaults(p=0.1, seed=3),
    )

    def fresh():
        return PSEngine(problem, pscfg, rng=jax.random.PRNGKey(4),
                        eval_fn=game.residual)

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "engine.msgpack")

        engine = fresh()
        engine.run(until_round=R // 2, checkpoint_path=ckpt,
                   checkpoint_every=5)
        print(f"ran {engine.round}/{R} rounds, 'crashed'; "
              f"checkpoint at {os.path.basename(ckpt)}")

        engine = fresh().restore(ckpt)        # new process, same config+seed
        zbar = engine.run()

    res = float(game.residual(zbar))
    tr = engine.trace                      # covers the resumed half
    print(f"resumed and finished at round {engine.round}")
    print(f"KKT residual:  {res:.4f}")
    print(f"since resume:  {tr.total_steps} local steps "
          f"(ideal {M * K * (R - R // 2)} — stragglers/faults ate the rest)")
    print(f"throughput:    {tr.steps_per_sec:,.0f} local steps/sec")
    print(f"bytes up:      {tr.total_bytes_up:,.0f} "
          f"(dense would be {tr.total_bytes_down:,.0f}, like the downlink)")
    for r in tr.rounds[:3]:
        print(f"  round {r.round:2d}: K={r.local_steps} alive={r.alive} "
              f"η∈[{r.eta_min:.3f},{r.eta_max:.3f}] res={r.residual:.4f}")

    # Same fleet, same policies — a Fig. 4 baseline through the same engine.
    zoo_cfg = dataclasses.replace(
        pscfg, adaseg=None, worker=MinimaxWorker(segda(0.05)), local_k=K)
    baseline = PSEngine(problem, zoo_cfg, rng=jax.random.PRNGKey(4),
                        eval_fn=game.residual)
    res_zoo = float(game.residual(baseline.run()))
    print(f"\nsame hostile fleet, LocalSEGDA (uniform averaging): "
          f"residual {res_zoo:.4f} vs LocalAdaSEG {res:.4f} "
          f"at {baseline.trace.steps_per_sec:,.0f} steps/sec")

    # Wall-clock timeline of the resumed synchronous run.
    out = os.path.join(os.path.dirname(__file__), "perfetto_sync_wall.json")
    validate_trace_events(save_trace_events(out, engine.tracer, clock="wall"))
    print(f"wall-clock Perfetto trace -> {out} "
          f"({len(engine.tracer.spans)} spans; open at ui.perfetto.dev)")

    async_demo(game, problem)
    hostile_demo(game)


def hostile_demo(game):
    """The fleet turns adversarial: 20% sign-flip uplinks vs a trimmed-mean
    server, with a crash and a bit-exact resume *mid-attack* — the attack
    table re-derives from its seed like every other policy."""
    m, rounds, k = 10, 12, 4
    byz = SignFlipAttack(fraction=0.2, scale=8.0, seed=11)
    robust_cfg = PSConfig(
        adaseg=AdaSEGConfig(g0=1.0, diameter=float(np.sqrt(2 * N)),
                            alpha=1.0, k=k),
        num_workers=m, rounds=rounds,
        byzantine=byz, aggregator=TrimmedMean(beta=0.2),
    )

    def fresh(cfg):
        return PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(4),
                        eval_fn=game.residual)

    reference = fresh(robust_cfg)
    z_ref = reference.run()               # the uninterrupted hostile run

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "hostile_engine.msgpack")
        engine = fresh(robust_cfg)
        engine.run(until_round=rounds // 2)
        engine.save(ckpt)
        attacked_so_far = sum(
            len(r.byzantine_workers) for r in engine.trace.rounds)
        print(f"\n-- hostile: 'crashed' at round {engine.round} with "
              f"{attacked_so_far} corrupted uplinks already admitted "
              f"({byz.name})")
        engine = fresh(robust_cfg).restore(ckpt)
        zbar = engine.run()

    exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(z_ref), jax.tree.leaves(zbar))
    )
    res_robust = float(game.residual(zbar))
    print(f"-- hostile: resumed mid-attack, bit-exact with the "
          f"uninterrupted run: {exact}")

    clean = fresh(dataclasses.replace(robust_cfg, byzantine=None,
                                      aggregator=None))
    res_clean = float(game.residual(clean.run()))
    mean = fresh(dataclasses.replace(robust_cfg, aggregator=None))
    res_mean = float(game.residual(mean.run()))
    print(f"   residuals: clean fleet {res_clean:.4f} | attacked, "
          f"trimmed-mean {res_robust:.4f} ({res_robust / res_clean:.2f}x) | "
          f"attacked, plain mean {res_mean:.4f} "
          f"({res_mean / res_clean:.2f}x — the mean never recovers)")
    last = engine.trace.rounds[-1]
    print(f"   final round corrupted workers: {last.byzantine_workers}, "
          f"server rejecting "
          f"{engine.aggregator.reject_frac(m):.0%} of lanes per coordinate")


def async_demo(game, problem):
    """No barrier: the event-driven engine over simulated time — one
    Markov-slow worker, τ=2 bounded staleness, and a mid-event-queue crash
    with bit-exact resume."""
    acfg = AsyncPSConfig(
        adaseg=AdaSEGConfig(g0=1.0, diameter=float(np.sqrt(2 * N)),
                            alpha=1.0, k=K),
        num_workers=M,
        rounds=R,
        latency=MarkovLatency(step_s=1.0, slow_factor=8.0, p_slow=0.05,
                              p_recover=0.25, up_s=0.2, down_s=0.1,
                              seed=6, start_slow=(3,)),
        staleness_bound=2.0,
    )

    def fresh():
        return AsyncPSEngine(problem, acfg, rng=jax.random.PRNGKey(4),
                             eval_fn=game.residual)

    reference = fresh()
    z_ref = reference.run()               # the uninterrupted timeline

    # Simulated-clock timeline of the τ=2 straggler run: per-worker
    # swimlanes of uplink / held / broadcast / compute, server admissions
    # on their own lane.
    out = os.path.join(os.path.dirname(__file__), "perfetto_async_sim.json")
    validate_trace_events(
        save_trace_events(out, reference.tracer, clock="sim")
    )
    print(f"\nsim-clock Perfetto trace -> {out} "
          f"({len(reference.tracer.spans)} spans on "
          f"{len(reference.tracer.tracks())} tracks)")

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "async_engine.msgpack")
        engine = fresh()
        engine.run(until_time=reference.sim_time / 2)
        engine.save(ckpt)
        print(f"\n-- async: 'crashed' at simulated t={engine.sim_time:.1f}s "
              f"({engine.n_admissions} admissions in the books)")
        engine = fresh().restore(ckpt)    # event queue rebuilt from disk
        zbar = engine.run()

    exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(z_ref), jax.tree.leaves(zbar))
    )
    tr = engine.trace
    print(f"-- async: resumed to completion at t={engine.sim_time:.1f}s, "
          f"bit-exact with the uninterrupted run: {exact}")
    print(f"   residual {float(game.residual(zbar)):.4f}, "
          f"fleet idle {engine.idle_fraction():.1%}, "
          f"max admitted staleness {tr.max_staleness} rounds")
    for r in tr.rounds[:3]:
        stale = [s if s is not None else "-" for s in r.staleness]
        print(f"   t={r.sim_time_s:7.2f}s  admitted="
              f"{[i for i, a in enumerate(r.alive) if a]} "
              f"staleness={stale} res={r.residual:.4f}")
    barrier = dataclasses.replace(acfg, staleness_bound=0.0)
    sync_ref = AsyncPSEngine(problem, barrier, rng=jax.random.PRNGKey(4),
                             eval_fn=game.residual)
    sync_ref.run()
    target = sync_ref.trace.summary()["final_residual"]
    # the resumed engine's trace covers only the second half; the reference
    # run holds the full residual-vs-time curve
    ttt = reference.trace.time_to_residual(target)
    if ttt is not None and not math.isinf(ttt):
        print(f"   τ=2 reached the barrier run's final residual at "
              f"t={ttt:.1f}s vs the barrier's t={sync_ref.sim_time:.1f}s")
    else:
        print(f"   barrier baseline finished at t={sync_ref.sim_time:.1f}s "
              f"with residual {target:.4f}")


if __name__ == "__main__":
    main()
