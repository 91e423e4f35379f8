"""Benchmark orchestrator — one harness per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows (see each bench module's
docstring for the figure it reproduces):

    fig3   bench_bilinear_ksweep      K/σ sweep on the bilinear game
    fig4   bench_bilinear_optimizers  optimizer-zoo comparison
    fig4x  bench_fig4_scenarios       the zoo + LocalAdaSEG on the PS engine
                                      under hetero/compression/dropout/faults
    figE1  bench_async                time-to-target: sync barrier vs
                                      bounded-staleness async (sim clock)
    extra  bench_ps                   PS runtime: compression/dropout/hetero
    extra  bench_ps_models            real-model ModelWorkers (tiny LM +
                                      WGAN) on the engine → BENCH_ps_models.json
    figE1d bench_vt_growth            V_t cumulative gradient growth
    figE2  bench_wgan                 WGAN-GP (homog + Dirichlet hetero)
    extra  bench_robust               robust logistic (beyond paper)
    extra  bench_kernels              kernel micro-benches + traffic models
    extra  bench_fleet                fleet scale: scan-vs-loop speedup,
                                      sampled-client sweep to 10k workers

``--only``/``--skip`` filter the sweep by substring of the bench label
(e.g. ``--skip fleet`` keeps the heavy fleet bench out of a quick local
run); the registry-completeness check always sees the full list.

The roofline/dry-run table is produced by ``repro.launch.dryrun`` +
``benchmarks/bench_roofline.py`` (it needs the 512-device env var and is
therefore a separate entry point).
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time
import traceback

#: bench modules with their own entry point (env-gated), exempt from the
#: registry-completeness check below
EXEMPT = {"bench_roofline"}


def _check_registry(benches) -> None:
    """Every ``bench_*.py`` in this directory must be wired into the
    orchestrator (or listed in EXEMPT) — a new bench that silently never
    runs is how perf trajectories go stale."""
    here = pathlib.Path(__file__).resolve().parent
    on_disk = {p.stem for p in here.glob("bench_*.py")}
    wired = {fn.__module__.rsplit(".", 1)[-1] for _, fn in benches}
    missing = on_disk - wired - EXEMPT
    if missing:
        raise RuntimeError(
            f"bench modules not in the run.py registry: {sorted(missing)} "
            "(add them to `benches` or to EXEMPT)"
        )


def registry() -> list:
    """The orchestrator's bench list: (label, entry point) per module."""
    from . import (
        bench_alpha_theory,
        bench_async,
        bench_bilinear_ksweep,
        bench_bilinear_optimizers,
        bench_fig4_scenarios,
        bench_fleet,
        bench_kernels,
        bench_ps,
        bench_ps_models,
        bench_robust,
        bench_vt_growth,
        bench_wgan,
    )

    return [
        ("fig3:bilinear_ksweep", bench_bilinear_ksweep.main),
        ("fig4:bilinear_optimizers", bench_bilinear_optimizers.main),
        ("fig4x:fig4_scenarios", bench_fig4_scenarios.main),
        ("figE1:async", bench_async.main),
        ("extra:ps_runtime", bench_ps.main),
        ("extra:ps_models", bench_ps_models.main),
        ("figE1d:vt_growth", bench_vt_growth.main),
        ("figE2-E5:wgan", bench_wgan.main),
        ("thm1-2-5:alpha_regimes", bench_alpha_theory.main),
        ("extra:robust_logistic", bench_robust.main),
        ("extra:kernels", bench_kernels.main),
        ("extra:fleet", bench_fleet.main),
    ]


def select(benches, only=None, skip=None) -> list:
    """Filter (label, fn) rows by substring: keep labels matching any
    ``--only`` term (all, when none given), then drop any matching a
    ``--skip`` term. Raises on a filter that matches nothing — a typo'd
    filter silently running everything (or nothing) is worse than an
    error."""
    out = benches
    if only:
        out = [row for row in out if any(t in row[0] for t in only)]
        if not out:
            raise SystemExit(f"--only {only} matches no bench label")
    if skip:
        dropped = [row for row in out if any(t in row[0] for t in skip)]
        if not dropped:
            raise SystemExit(f"--skip {skip} matches no bench label")
        out = [row for row in out if row not in dropped]
    return out


def main(argv=None) -> int:
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description="run every benchmark harness")
    ap.add_argument("--json-dir", default=None,
                    help="redirect BENCH_*.json trajectory persistence "
                         "(default: repo root)")
    ap.add_argument("--only", action="append", default=None,
                    help="run only benches whose label contains this "
                         "substring (repeatable)")
    ap.add_argument("--skip", action="append", default=None,
                    help="skip benches whose label contains this substring "
                         "(repeatable), e.g. --skip fleet")
    args = ap.parse_args(argv)
    if args.json_dir is not None:
        from .common import set_json_dir

        set_json_dir(args.json_dir)

    benches = registry()
    # completeness check runs on the UNFILTERED registry: filtering is for
    # this invocation, wiring is forever
    _check_registry(benches)
    benches = select(benches, only=args.only, skip=args.skip)
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        t0 = time.perf_counter()
        try:
            fn()
            print(f"{name},{(time.perf_counter()-t0)*1e6:.0f},status=ok")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,status=FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
