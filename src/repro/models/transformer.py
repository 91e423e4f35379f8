"""Model assembly: decoder-only, encoder-decoder, and cross-attn VLM stacks.

Pre-norm residual blocks; the per-layer mixer is attention (global or
sliding-window), a Mamba2 SSD block, or an RG-LRU block, per
``cfg.layer_kinds()``; the channel mixer is a gated MLP or an MoE layer.

**Layer stacking**: layers are organized as ``num_groups`` repetitions of a
``pattern_period``-long stage (e.g. gemma2: (local, global); recurrentgemma:
(rglru, rglru, local)). Parameters for each period position are stacked with
a leading group axis and the full-sequence forward is a rematerialized
``lax.scan`` over groups — one compiled block body regardless of depth,
which keeps both compile time and activation memory O(1) in ``num_layers``.
Decode unrolls the (cheap) per-token graph with static indexing instead.

Everything returns ``(params, specs)`` pairs for GSPMD placement.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from .attention import (
    apply_attention,
    decode_attention,
    init_attention,
    init_kv_cache,
    kv_cache_specs,
)
from .layers import (
    _normal,
    apply_embedding,
    apply_layernorm,
    apply_rmsnorm,
    init_embedding,
    init_layernorm,
    init_rmsnorm,
    softcap,
)
from .mlp import apply_mlp, init_mlp
from .moe import apply_moe, init_moe
from .rglru import (
    apply_rglru,
    decode_rglru,
    init_rglru,
    init_rglru_cache,
    rglru_cache_specs,
)
from .ssm import apply_ssm, decode_ssm, init_ssm, init_ssm_cache, ssm_cache_specs

PyTree = Any

# ``jax.named_scope`` of the vocabulary-wide work on the device profile: the
# embedding lookup, the head, its log-softmax and the NLL gather.
VOCAB_SCOPE = "vocab"


def init_norm(cfg: ArchConfig):
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.norm == "layernorm":
        return init_layernorm(cfg.d_model, dt)
    return init_rmsnorm(cfg.d_model, dt)


def apply_norm(cfg: ArchConfig, p, x):
    if cfg.norm == "layernorm":
        return apply_layernorm(p, x, cfg.norm_eps)
    return apply_rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, kind: dict):
    keys = jax.random.split(key, 8)
    p: dict = {}
    s: dict = {}
    p["pre_norm"], s["pre_norm"] = init_norm(cfg)
    if kind["kind"] == "attn":
        p["mixer"], s["mixer"] = init_attention(keys[0], cfg)
    elif kind["kind"] == "ssm":
        p["mixer"], s["mixer"] = init_ssm(keys[0], cfg)
    elif kind["kind"] == "rglru":
        p["mixer"], s["mixer"] = init_rglru(keys[0], cfg)
    else:
        raise ValueError(kind)
    if kind.get("cross_attn"):
        p["xattn_norm"], s["xattn_norm"] = init_norm(cfg)
        p["xattn"], s["xattn"] = init_attention(keys[1], cfg, cross=True)
        if cfg.cross_attn_every:
            # gating scalar for VLM cross-attn (llama-3.2-vision style, init 0)
            p["xattn_gate"] = jnp.zeros((), jnp.float32)
            s["xattn_gate"] = P()
    if kind.get("moe"):
        p["mlp_norm"], s["mlp_norm"] = init_norm(cfg)
        p["mlp"], s["mlp"] = init_moe(keys[2], cfg)
    elif cfg.d_ff > 0:
        p["mlp_norm"], s["mlp_norm"] = init_norm(cfg)
        p["mlp"], s["mlp"] = init_mlp(keys[2], cfg)
    if cfg.post_norm:
        p["mixer_post"], s["mixer_post"] = init_norm(cfg)
        p["mlp_post"], s["mlp_post"] = init_norm(cfg)
    return p, s


def _init_stage(key, cfg: ArchConfig, kind: dict, n_groups: int):
    """Stack one period-position's block params over the group axis."""
    keys = jax.random.split(key, n_groups)
    box = {}

    def params_only(k):
        p, s = _init_block(k, cfg, kind)
        box["specs"] = s
        return p

    p = jax.vmap(params_only)(keys)
    s = jax.tree.map(
        lambda sp: P(None, *sp), box["specs"],
        is_leaf=lambda x: isinstance(x, P),
    )
    return p, s


def init_model(key, cfg: ArchConfig):
    """Returns (params, specs) for the full stack (+ encoder if enc-dec)."""
    cfg.validate()
    period = cfg.pattern_period()
    n_groups = cfg.num_groups()
    kinds = cfg.layer_kinds()
    keys = jax.random.split(key, period + cfg.tail_layers() + 4)
    p: dict = {}
    s: dict = {}
    p["embed"], s["embed"] = init_embedding(
        keys[0], cfg.vocab_size, cfg.d_model, jnp.dtype(cfg.param_dtype)
    )
    if cfg.pos_embed == "learned":
        p["pos_embed"] = _normal(
            keys[-3], (cfg.max_seq_len, cfg.d_model), 0.02,
            jnp.dtype(cfg.param_dtype),
        )
        s["pos_embed"] = P(None, None)

    stages_p, stages_s = [], []
    for j in range(period):
        sp, ss = _init_stage(keys[1 + j], cfg, kinds[j], n_groups)
        stages_p.append(sp)
        stages_s.append(ss)
    p["stages"], s["stages"] = stages_p, stages_s

    tails_p, tails_s = [], []
    for i in range(cfg.tail_layers()):
        tp, ts = _init_block(
            keys[1 + period + i], cfg, kinds[n_groups * period + i]
        )
        tails_p.append(tp)
        tails_s.append(ts)
    if tails_p:
        p["tail"], s["tail"] = tails_p, tails_s

    p["final_norm"], s["final_norm"] = init_norm(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = _normal(
            keys[-2], (cfg.d_model, cfg.vocab_size), cfg.d_model**-0.5,
            jnp.dtype(cfg.param_dtype),
        )
        s["lm_head"] = P(None, "model")
    if cfg.is_encoder_decoder:
        p["encoder"], s["encoder"] = _init_encoder(keys[-1], cfg)
    return p, s


def _init_encoder(key, cfg: ArchConfig):
    """Non-causal encoder stack (whisper-style); frontend conv is a STUB —
    inputs arrive as precomputed frame embeddings of shape (B, S_enc, D)."""
    k_stage, k_norm = jax.random.split(key)
    box = {}

    def params_only(k):
        keys = jax.random.split(k, 2)
        lp, ls = {}, {}
        lp["pre_norm"], ls["pre_norm"] = init_norm(cfg)
        lp["mixer"], ls["mixer"] = init_attention(keys[0], cfg)
        lp["mlp_norm"], ls["mlp_norm"] = init_norm(cfg)
        lp["mlp"], ls["mlp"] = init_mlp(keys[1], cfg)
        box["specs"] = ls
        return lp

    stage = jax.vmap(params_only)(jax.random.split(k_stage, cfg.encoder_layers))
    specs = jax.tree.map(
        lambda sp: P(None, *sp), box["specs"],
        is_leaf=lambda x: isinstance(x, P),
    )
    p = {"stage": stage}
    s = {"stage": specs}
    p["final_norm"], s["final_norm"] = init_norm(cfg)
    return p, s


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def encode(params, cfg: ArchConfig, frames):
    """Encoder forward. frames: (B, S_enc, D) stub embeddings → states."""
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    @jax.checkpoint
    def body(x, lp):
        h = apply_norm(cfg, lp["pre_norm"], x)
        x = x + apply_attention(lp["mixer"], cfg, h, positions, causal=False)
        h = apply_norm(cfg, lp["mlp_norm"], x)
        x = x + apply_mlp(lp["mlp"], cfg, h)
        return x, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["encoder"]["stage"])
    else:
        for g in range(cfg.encoder_layers):
            x, _ = body(x, jax.tree.map(lambda v: v[g],
                                        params["encoder"]["stage"]))
    return apply_norm(cfg, params["encoder"]["final_norm"], x)


def _block_forward(lp, cfg: ArchConfig, kind, x, positions, enc_states):
    h = apply_norm(cfg, lp["pre_norm"], x)
    if kind["kind"] == "attn":
        out = apply_attention(lp["mixer"], cfg, h, positions, window=kind["window"])
    elif kind["kind"] == "ssm":
        out = apply_ssm(lp["mixer"], cfg, h)
    else:
        out = apply_rglru(lp["mixer"], cfg, h)
    if cfg.post_norm:
        out = apply_norm(cfg, lp["mixer_post"], out)
    x = x + out
    aux = jnp.float32(0.0)
    if kind.get("cross_attn") and enc_states is not None:
        h = apply_norm(cfg, lp["xattn_norm"], x)
        xo = apply_attention(lp["xattn"], cfg, h, positions, cross_states=enc_states)
        if "xattn_gate" in lp:
            xo = jnp.tanh(lp["xattn_gate"]).astype(x.dtype) * xo
        x = x + xo
    if "mlp" not in lp:  # attn/ssm-only blocks (mamba2 stacks)
        return x, aux
    h = apply_norm(cfg, lp["mlp_norm"], x)
    if kind.get("moe"):
        out, aux = apply_moe(lp["mlp"], cfg, h,
                             shard_dispatch=cfg.moe_shard_dispatch)
    else:
        out = apply_mlp(lp["mlp"], cfg, h)
    if cfg.post_norm:
        out = apply_norm(cfg, lp["mlp_post"], out)
    return x + out, aux


def forward(params, cfg: ArchConfig, tokens, *, enc_states=None,
            head_last_only: bool = False):
    """tokens: (B, S) → (logits, moe_aux). enc_states: (B, S_enc, D) for
    enc-dec / VLM cross-attention (stub frontend output).

    ``head_last_only``: apply the LM head to the final position only —
    logits (B, 1, V) instead of (B, S, V). Serving prefill uses this: the
    full-vocab logits tensor is O(S·V) and dominates prefill HBM otherwise.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope(VOCAB_SCOPE):
        x = apply_embedding(params["embed"], tokens).astype(cdt)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model**0.5, cdt)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    if "pos_embed" in params:
        x = x + params["pos_embed"][positions].astype(cdt)

    kinds = cfg.layer_kinds()
    period = cfg.pattern_period()

    @jax.checkpoint
    def stage_body(x, stage_slice):
        aux_sum = jnp.float32(0.0)
        for j in range(period):
            x, aux = _block_forward(
                stage_slice[j], cfg, kinds[j], x, positions, enc_states
            )
            aux_sum = aux_sum + aux
        return x, aux_sum

    if cfg.scan_layers:
        x, auxs = jax.lax.scan(stage_body, x, tuple(params["stages"]))
        aux_total = jnp.sum(auxs)
    else:
        aux_total = jnp.float32(0.0)
        for g in range(cfg.num_groups()):
            stage_slice = jax.tree.map(lambda v: v[g], tuple(params["stages"]))
            x, aux = stage_body(x, stage_slice)
            aux_total = aux_total + aux
    for i, lp in enumerate(params.get("tail", [])):
        x, aux = _block_forward(
            lp, cfg, kinds[cfg.num_groups() * period + i], x, positions,
            enc_states,
        )
        aux_total = aux_total + aux

    x = apply_norm(cfg, params["final_norm"], x)
    if head_last_only:
        x = x[:, -1:, :]
    with jax.named_scope(VOCAB_SCOPE):
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = (x @ head.astype(cdt)).astype(jnp.float32)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
    return logits, aux_total


def loss_fn(params, cfg: ArchConfig, batch):
    """Next-token cross-entropy (+ MoE router aux). batch: {tokens, labels,
    frontend?}. labels = tokens shifted, −1 = masked."""
    enc_states = None
    if cfg.is_encoder_decoder:
        enc_states = encode(params, cfg, batch["frontend"])
    elif cfg.cross_attn_every:
        enc_states = batch["frontend"].astype(jnp.dtype(cfg.compute_dtype))
    logits, aux = forward(params, cfg, batch["tokens"], enc_states=enc_states)
    labels = batch["labels"]
    with jax.named_scope(VOCAB_SCOPE):
        mask = labels >= 0
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss + cfg.router_aux_weight * aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _layer_params(params, cfg: ArchConfig, i: int):
    """Static lookup of layer i's params from the stacked representation."""
    period = cfg.pattern_period()
    n_stacked = cfg.num_groups() * period
    if i < n_stacked:
        g, j = divmod(i, period)
        return jax.tree.map(lambda v: v[g], params["stages"][j])
    return params["tail"][i - n_stacked]


def init_cache(cfg: ArchConfig, batch, max_len, dtype=jnp.float32):
    """Per-layer cache pytree sized for decode at context ``max_len``.
    Sliding-window layers allocate only O(window) slots."""
    caches = []
    for kind in cfg.layer_kinds():
        if kind["kind"] == "attn":
            slots = min(kind["window"] or max_len, max_len)
            caches.append(init_kv_cache(cfg, batch, slots, dtype))
        elif kind["kind"] == "ssm":
            caches.append(init_ssm_cache(cfg, batch, dtype))
        else:
            caches.append(init_rglru_cache(cfg, batch, dtype))
    return caches


def cache_specs(cfg: ArchConfig, worker_axes=()):
    specs = []
    for kind in cfg.layer_kinds():
        if kind["kind"] == "attn":
            specs.append(kv_cache_specs(worker_axes))
        elif kind["kind"] == "ssm":
            specs.append(ssm_cache_specs(worker_axes))
        else:
            specs.append(rglru_cache_specs(worker_axes))
    return specs


def decode_step(params, cfg: ArchConfig, token, pos, cache, *, enc_states=None):
    """token: (B, 1) int32; pos: (B,) int32 → (logits (B, 1, V), new_cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = apply_embedding(params["embed"], token).astype(cdt)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model**0.5, cdt)
    if "pos_embed" in params:
        x = x + params["pos_embed"][pos[:, None]].astype(cdt)
    new_caches = []
    for i, (kind, lc) in enumerate(zip(cfg.layer_kinds(), cache)):
        lp = _layer_params(params, cfg, i)
        h = apply_norm(cfg, lp["pre_norm"], x)
        if kind["kind"] == "attn":
            out, lc = decode_attention(
                lp["mixer"], cfg, h, pos, lc, window=kind["window"]
            )
        elif kind["kind"] == "ssm":
            out, lc = decode_ssm(lp["mixer"], cfg, h, lc)
        else:
            out, lc = decode_rglru(lp["mixer"], cfg, h, lc)
        if cfg.post_norm:
            out = apply_norm(cfg, lp["mixer_post"], out)
        x = x + out
        if kind.get("cross_attn") and enc_states is not None:
            h = apply_norm(cfg, lp["xattn_norm"], x)
            xo, _ = decode_attention(
                lp["xattn"], cfg, h, pos, None, cross_states=enc_states
            )
            if "xattn_gate" in lp:
                xo = jnp.tanh(lp["xattn_gate"]).astype(x.dtype) * xo
            x = x + xo
        if "mlp" in lp:
            h = apply_norm(cfg, lp["mlp_norm"], x)
            if kind.get("moe"):
                out, _ = apply_moe(lp["mlp"], cfg, h,
                                   shard_dispatch=cfg.moe_shard_dispatch)
            else:
                out = apply_mlp(lp["mlp"], cfg, h)
            if cfg.post_norm:
                out = apply_norm(cfg, lp["mlp_post"], out)
            x = x + out
        new_caches.append(lc)
    x = apply_norm(cfg, params["final_norm"], x)
    head = params["embed"]["table"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cdt)).astype(jnp.float32)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches
