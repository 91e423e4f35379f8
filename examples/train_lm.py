"""End-to-end driver: train a language model with LocalAdaSEG — through the
Parameter-Server runtime (the unified stack).

    PYTHONPATH=src python examples/train_lm.py                     # ~20M model
    PYTHONPATH=src python examples/train_lm.py --preset 100m --rounds 40
    PYTHONPATH=src python examples/train_lm.py --arch mamba2-370m --smoke
    PYTHONPATH=src python examples/train_lm.py --q8 --pallas       # q8-EF uplinks
    PYTHONPATH=src python examples/train_lm.py --tau 1             # async SSP τ=1

The model (ArchConfig zoo + synthetic Markov-Zipf pipeline) runs as a
``repro.ps.ModelWorker`` on ``PSEngine`` via ``launch.train.make_ps_engine``:
M workers × K local extragradient steps (a ``lax.scan``), inverse-η weighted
sync, per-round telemetry, and msgpack checkpointing all come from the same
engine that drives the paper's bilinear/WGAN experiments. ``--tau`` switches
to the discrete-event ``AsyncPSEngine`` under bounded staleness; ``--q8``
compresses the uplinks with error feedback; ``--pallas`` puts the flash
attention kernel on the forward hot path.
"""
import argparse
import dataclasses
import time

import jax

from repro.configs import get_config, smoke_config
from repro.core.adaseg import AdaSEGConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.train import TrainPlan, make_ps_engine
from repro.ps import LognormalLatency, StochasticQuantizeCompressor

PRESETS = {
    # name: (layers, d_model, heads, kv, d_ff, vocab) — ~20M / ~100M params
    "20m": (8, 384, 6, 2, 1536, 8192),
    "100m": (12, 768, 12, 4, 3072, 16384),
}


def build_config(args):
    if args.arch:
        cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    else:
        layers, dm, h, kv, ff, vocab = PRESETS[args.preset]
        base = get_config("qwen2-0.5b")  # dense GQA family
        cfg = dataclasses.replace(
            base, name=f"lm-{args.preset}", num_layers=layers, d_model=dm,
            num_heads=h, num_kv_heads=kv, d_ff=ff, vocab_size=vocab,
            head_dim=dm // h, max_seq_len=args.seq,
        )
    if args.pallas:
        cfg = dataclasses.replace(cfg, attn_backend="pallas")
    return cfg


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=sorted(PRESETS))
    ap.add_argument("--arch", default=None, help="use a zoo architecture")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced variant of --arch")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--k-local", type=int, default=5)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--hetero", action="store_true",
                    help="per-worker Markov-Zipf token distributions")
    ap.add_argument("--q8", action="store_true",
                    help="q8 stochastic-quantize uplinks + error feedback")
    ap.add_argument("--pallas", action="store_true",
                    help="flash-attention Pallas kernel on the hot path")
    ap.add_argument("--tau", type=float, default=None,
                    help="async engine with SSP staleness bound τ")
    ap.add_argument("--ckpt", default=None, help="checkpoint path")
    args = ap.parse_args()

    cfg = build_config(args)
    plan = TrainPlan(
        cfg=cfg,
        adaseg=AdaSEGConfig(g0=20.0, diameter=2.0,
                            alpha=1.0 / args.workers**0.5,
                            k=args.k_local, average_output=False),
        worker_mode="paper",
        k_local=args.k_local,
        global_batch=args.batch * args.workers,
        seq=args.seq,
        workers_override=args.workers,
    )
    engine = make_ps_engine(
        plan, jax.random.PRNGKey(0), rounds=args.rounds,
        hetero=args.hetero,
        compressor=StochasticQuantizeCompressor(bits=8) if args.q8 else None,
        latency=LognormalLatency(sigma=0.4) if args.tau is not None else None,
        staleness_bound=args.tau,
    )
    n_params = sum(
        v.size for v in jax.tree.leaves(engine.problem.init(
            jax.random.PRNGKey(0)))
    )
    mode = (f"async τ={args.tau}" if args.tau is not None else "sync")
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params/worker, "
          f"M={args.workers} workers, K={plan.k_local}, "
          f"batch={plan.global_batch}×{plan.seq}, {mode}, "
          f"codec={'q8+EF' if args.q8 else 'identity'}")

    t_start = time.time()
    if args.tau is not None:
        engine.run()                       # drive the event queue to the end
        for rec in engine.trace.rounds:
            loss = ("-" if rec.residual is None else f"{rec.residual:.4f}")
            idle = ("-" if rec.idle_frac is None else f"{rec.idle_frac:.0%}")
            print(f"admission {rec.round:3d}  eval-loss={loss}  "
                  f"mean η={rec.eta_mean:.5f}  "
                  f"sim_t={rec.sim_time_s:.1f}s  idle={idle}")
    else:
        for r in range(1, args.rounds + 1):
            engine.run(until_round=r)
            rec = engine.trace.rounds[-1]
            print(f"round {r:3d}/{args.rounds}  "
                  f"eval-loss={rec.residual:.4f}  "
                  f"mean η={rec.eta_mean:.5f}  "
                  f"up={rec.bytes_up/1e6:.2f}MB  "
                  f"({time.time()-t_start:.1f}s)")
    if args.ckpt:
        engine.save(args.ckpt)
        print(f"checkpoint written to {args.ckpt}")


if __name__ == "__main__":
    main()
