#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own
size on the chip: the controls and faults put in the program's place,
each compared with the plain reference by ``perfbench.check.gaps``.

    python3 perfbench/calibrate.py --workload <name> --seeds 1 2 3

One JSON line per (seed, variant) on standard output. The variants are the
system's ``VARIANTS``: the reference in the next precision below the one
the configuration states (the control), and the faults a broken program
would show (a batch cut in half, the exchange left out). A state left
unchanged reads 1 by construction and needs no run. The benchmark's own
runs never run this.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import check, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="*")
    args = ap.parse_args()

    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.load_benchmark()
    res = spec.resolve(bench, args.workload)
    system = importlib.import_module(
        f"perfbench.systems.{res['config']['system']}")
    variants = args.variants or system.VARIANTS
    for seed in args.seeds:
        cell = system.build(res["config"], res["traffic"], seed=seed,
                            seconds=bench["run_seconds"], tracer=None,
                            engine=False)
        with system.context(res["config"]):
            t = time.perf_counter()
            ref = cell.reference()
            ref_s = time.perf_counter() - t
            for v in variants:
                t = time.perf_counter()
                got = cell.reference(v)
                print(json.dumps({
                    "workload": args.workload, "seed": seed, "variant": v,
                    "gaps": check.gaps(got, ref),
                    "reference_s": ref_s,
                    "variant_s": time.perf_counter() - t,
                    "loss_ref": ref["loss"], "loss_variant": got["loss"]}),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
