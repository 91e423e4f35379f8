"""Train a WGAN-GP on the 8-mode Gaussian mixture with LocalAdaSEG — through
the Parameter-Server runtime (paper §4.2, offline proxy — see DESIGN.md §7
for metric substitutions).

    PYTHONPATH=src python examples/wgan_train.py
    PYTHONPATH=src python examples/wgan_train.py --hetero --alpha 0.3
    PYTHONPATH=src python examples/wgan_train.py --q8

The generator/discriminator minimax game runs as a ``repro.ps.ModelWorker``
on ``PSEngine`` — the same engine code path as the transformer LM and the
synthetic zoo, so ``--q8`` error-feedback compression, schedules, faults and
mid-stream checkpointing all apply. The engine is driven *incrementally*
(``run(until_round=r)``), evaluating the Wasserstein estimate on the global
output iterate z̄ (Line 14) as training progresses.

--hetero partitions the mixture modes across workers with a Dirichlet(α)
prior (the paper's federated-GAN setting, Fig. E3–E5).
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import AdaSEGConfig
from repro.launch.cache import enable_compile_cache
from repro.problems import make_wgan_problem
from repro.ps import ModelWorker, PSConfig, PSEngine, StochasticQuantizeCompressor


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--k-local", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--rounds-total", type=int, default=50)
    ap.add_argument("--hetero", action="store_true")
    ap.add_argument("--alpha", type=float, default=0.6)
    ap.add_argument("--q8", action="store_true",
                    help="q8 stochastic-quantize uplinks + error feedback")
    args = ap.parse_args()

    wg = make_wgan_problem(jax.random.PRNGKey(0))
    problem = wg.problem
    if args.hetero:
        from benchmarks.bench_wgan import _dirichlet_mode_logits, _heterogeneous

        logits = _dirichlet_mode_logits(
            jax.random.PRNGKey(7), args.alpha, args.workers
        )
        problem = _heterogeneous(problem, wg, logits)
        print(f"heterogeneous: Dirichlet(α={args.alpha}) mode weights/worker")

    cfg = AdaSEGConfig(g0=50.0, diameter=1.0, alpha=1.0, k=args.k_local,
                       average_output=False)
    worker = ModelWorker(cfg, arch=problem.name)
    eval_rng = jax.random.PRNGKey(99)
    engine = PSEngine(
        problem,
        PSConfig(
            worker=worker, local_k=args.k_local,
            num_workers=args.workers, rounds=args.rounds_total,
            compressor=(StochasticQuantizeCompressor(bits=8) if args.q8
                        else None),
        ),
        rng=jax.random.PRNGKey(1),
        eval_fn=lambda z: wg.wasserstein_estimate(z, eval_rng),
    )
    for r in range(args.rounds, args.rounds_total + 1, args.rounds):
        z = engine.run(until_round=r)
        w_est = float(wg.wasserstein_estimate(z, eval_rng))
        md = float(wg.moment_distance(z, eval_rng))
        print(f"rounds {r:3d}: W-estimate = {w_est:+.4f}   "
              f"moment-distance = {md:.4f}")
    samples = wg.generate(engine.z_bar()[0], jax.random.PRNGKey(3), 8)
    print("generated samples (first 8):")
    print(jnp.round(samples, 2))


if __name__ == "__main__":
    main()
