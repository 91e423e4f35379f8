"""Serve a model: batched greedy decoding against the ring-buffer KV cache.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-27b --tokens 32

Uses the reduced (smoke) variant of the chosen architecture so it runs on
CPU; the same ``decode_step`` is what ``repro.launch.serve`` lowers against
the production mesh for the decode_32k / long_500k shapes.

The production loop — **checkpoints → live traffic** — is closed against the
PS runtime: ``--ps-train PATH`` trains the tiny-lm demo config through
``PSEngine`` + ``ModelWorker`` and writes a mid-training checkpoint, and
``--ps-ckpt PATH`` restores that checkpoint into a fresh engine and serves
greedy decodes from its trained z̄ instead of stub init weights:

    PYTHONPATH=src python examples/serve_lm.py --ps-train /tmp/lm.ckpt
    PYTHONPATH=src python examples/serve_lm.py --ps-ckpt /tmp/lm.ckpt
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import list_archs, smoke_config
from repro.core import AdaSEGConfig
from repro.launch.cache import enable_compile_cache
from repro.models import (
    ModelWorker,
    decode_step,
    init_cache,
    init_model,
    make_lm_problem,
    tiny_lm_config,
)
from repro.models.transformer import encode
from repro.ps import PSConfig, PSEngine


def _demo_engine(*, rounds: int, workers: int, local_k: int):
    """The canonical tiny-lm training engine: ``--ps-ckpt`` must rebuild the
    exact engine that wrote the checkpoint (worker fingerprint + seed are
    validated on restore), so train and serve share this constructor."""
    cfg = tiny_lm_config()
    prob = make_lm_problem(cfg, batch=2, seq=8)
    worker = ModelWorker(AdaSEGConfig(g0=5.0, diameter=1.0, k=local_k),
                         arch=cfg.name)
    eng = PSEngine(
        prob,
        PSConfig(worker=worker, local_k=local_k, num_workers=workers,
                 rounds=rounds),
        rng=jax.random.PRNGKey(0),
    )
    return cfg, eng


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--ps-train", metavar="PATH", default=None,
                    help="train the tiny-lm demo config on the PS runtime "
                         "and write a checkpoint to PATH, then exit")
    ap.add_argument("--ps-ckpt", metavar="PATH", default=None,
                    help="serve from a PSEngine checkpoint written by "
                         "--ps-train instead of stub init weights")
    ap.add_argument("--ps-rounds", type=int, default=2,
                    help="rounds for the --ps-train/--ps-ckpt demo engine")
    args = ap.parse_args()

    if args.ps_train:
        _, eng = _demo_engine(rounds=args.ps_rounds, workers=2, local_k=2)
        eng.run(checkpoint_path=args.ps_train, checkpoint_every=1)
        print(f"trained tiny-lm for {eng.round} PS rounds -> "
              f"{args.ps_train}")
        return

    if args.ps_ckpt:
        cfg, eng = _demo_engine(rounds=args.ps_rounds, workers=2, local_k=2)
        eng.restore(args.ps_ckpt)
        params = eng.z_bar()
        print(f"serving tiny-lm from PS checkpoint {args.ps_ckpt} "
              f"(round {eng.round})")
    else:
        cfg = smoke_config(args.arch)
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.tokens
    cache = init_cache(cfg, args.batch, max_len=max_len)

    enc = None
    if cfg.is_encoder_decoder:
        frames = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, cfg.encoder_seq, cfg.d_model)
        )
        enc = encode(params, cfg, frames)
        print(f"encoded {cfg.encoder_seq} frontend frames")
    elif cfg.cross_attn_every:
        enc = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, cfg.encoder_seq, cfg.d_model)
        )
        print(f"conditioning on {cfg.encoder_seq} image patch embeddings")

    step = jax.jit(
        lambda p, t, pos, c: decode_step(p, cfg, t, pos, c, enc_states=enc)
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(2), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    # prefill token-by-token (production prefill lowers the batched forward)
    tok = prompt[:, :1]
    for t in range(args.prompt_len):
        logits, cache = step(params, prompt[:, t:t + 1],
                             jnp.full((args.batch,), t, jnp.int32), cache)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)

    out = [tok]
    t0 = time.time()
    for i in range(args.tokens - 1):
        pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
        logits, cache = step(params, tok, pos, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"{cfg.name} (reduced): generated {gen.shape} tokens "
          f"in {dt:.2f}s ({args.batch * (args.tokens-1) / dt:.1f} tok/s)")
    print(gen)


if __name__ == "__main__":
    main()
