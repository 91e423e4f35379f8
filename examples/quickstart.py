"""Quickstart: solve a stochastic bilinear saddle game with LocalAdaSEG.

    PYTHONPATH=src python examples/quickstart.py

Builds the paper's §4.1 problem (min_x max_y xᵀAy + bᵀx + cᵀy over the box,
noisy oracle), runs LocalAdaSEG with M=4 workers × K=50 local steps, and
prints the KKT residual as rounds of communication proceed.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AdaSEGConfig, run_local_adaseg
from repro.launch.cache import enable_compile_cache
from repro.problems import make_bilinear_game


def main():
    enable_compile_cache()
    game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
    cfg = AdaSEGConfig(
        g0=1.0,                        # guess of the gradient bound G
        diameter=float(np.sqrt(20.0)),  # D for the box [-1,1]^10 × [-1,1]^10
        alpha=1.0,                     # nonsmooth base lr (Theorem 1)
        k=50,                          # local steps between communications
    )
    z0 = game.problem.init(jax.random.PRNGKey(1))
    print(f"round  0: residual = {float(game.residual(z0)):.4f}  (init)")

    for rounds in (1, 2, 5, 10, 20):
        zbar, (state, _) = run_local_adaseg(
            game.problem, cfg, num_workers=4, rounds=rounds,
            rng=jax.random.PRNGKey(2),
        )
        res = float(game.residual(zbar))
        gap = float(game.duality_gap(zbar))
        eta = jnp.mean(
            cfg.diameter * cfg.alpha
            / jnp.sqrt(cfg.g0**2 + state.sum_sq)
        )
        print(f"round {rounds:2d}: residual = {res:.4f}  duality-gap = "
              f"{gap:.4f}  mean η = {float(eta):.4f}")


if __name__ == "__main__":
    main()
