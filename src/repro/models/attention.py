"""Grouped-query attention with RoPE, qk-norm, logit softcap, sliding window,
cross-attention, and a unified ring-buffer KV cache for decode.

Reference (pure-jnp) path — the Pallas flash kernel in ``repro.kernels``
computes the same math and is validated against this implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..kernels.flash_attention import ops as flash_ops
from .layers import (
    MODEL,
    _normal,
    apply_rmsnorm,
    apply_rope,
    init_rmsnorm,
    softcap,
)

NEG_INF = -1e30
ATTENTION_SCOPE = "attention"   # ``jax.named_scope`` of the attention core


def init_attention(key, cfg: ArchConfig, *, cross: bool = False):
    dm, h, kh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 6)
    scale = dm ** -0.5
    p = {
        "wq": _normal(keys[0], (dm, h, dh), scale, dtype),
        "wk": _normal(keys[1], (dm, kh, dh), scale, dtype),
        "wv": _normal(keys[2], (dm, kh, dh), scale, dtype),
        "wo": _normal(keys[3], (h, dh, dm), (h * dh) ** -0.5, dtype),
    }
    s = {
        "wq": P(None, MODEL, None),
        "wk": P(None, MODEL, None),
        "wv": P(None, MODEL, None),
        "wo": P(MODEL, None, None),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kh, dh), dtype)
        p["bv"] = jnp.zeros((kh, dh), dtype)
        s["bq"] = P(MODEL, None)
        s["bk"] = P(MODEL, None)
        s["bv"] = P(MODEL, None)
    if cfg.qk_norm:
        for n in ("q_norm", "k_norm"):
            p[n], s[n] = init_rmsnorm(dh, dtype)
    return p, s


def _project_qkv(p, cfg: ArchConfig, x, kv_x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def gqa_scores(q, k, v, mask, *, scale, cap=None):
    """q: (B,S,H,Dh), k/v: (B,T,Kh,Dh), mask: broadcastable to (B,Kh,G,S,T)."""
    b, s, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if cap is not None:
        logits = softcap(logits, cap)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, dh)


def causal_mask(s, t, *, offset=0, window=None):
    """(s, t) boolean mask. offset = (t - s) for prefill continuation."""
    qi = jnp.arange(s)[:, None] + offset
    ki = jnp.arange(t)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m


# Sequences at or above this length use the chunked (flash-style) path:
# the O(S²) logit tensor is never materialized in HBM — the XLA analogue of
# the Pallas flash kernel (which replaces this on real TPUs). 4k training
# stays on the dense path (fits VMEM-tiled fusion fine); 32k+ does not.
CHUNKED_ATTN_THRESHOLD = 8192


def _chunked_attention(q, k, v, *, scale, cap, causal, window, block=1024):
    """Online-softmax attention over query blocks. q: (B,S,H,D); k/v:
    (B,T,Kh,D). O(S·D) HBM footprint; logits live only per (block × T)."""
    b, s, h, dh = q.shape
    kh, t = k.shape[2], k.shape[1]
    g = h // kh
    assert s % block == 0, (s, block)
    nq = s // block
    qb = q.reshape(b, nq, block, kh, g, dh).transpose(1, 0, 3, 4, 2, 5)
    ki = jnp.arange(t)

    def one_block(carry, inp):
        qi_block, idx = inp                       # (B,Kh,G,bq,D), scalar
        logits = jnp.einsum(
            "bkgsd,btkd->bkgst", qi_block.astype(jnp.float32),
            k.astype(jnp.float32)
        ) * scale
        if cap is not None:
            logits = cap * jnp.tanh(logits / cap)
        qi = idx * block + jnp.arange(block)
        mask = jnp.ones((block, t), bool)
        if causal:
            mask &= ki[None, :] <= qi[:, None]
        if window is not None:
            mask &= ki[None, :] > qi[:, None] - window
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgst,btkd->bkgsd", probs, v)
        return carry, out

    _, outs = jax.lax.scan(one_block, None, (qb, jnp.arange(nq)))
    # outs: (nq, B, Kh, G, block, D) → (B, S, H, D)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, dh)


def _flash_self_attention(q, k, v, *, scale, cap, window):
    """Self-causal attention on the model's (B, S, H, D) layout via the
    Pallas flash kernels (``cfg.attn_backend="pallas"``): the forward
    kernel, and under autodiff its FlashAttention-2 backward kernel, which
    recomputes each score tile from the forward's row log-sum-exp
    (validated against the VJP of `attention_ref`)."""
    out = flash_ops.attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=True, window=window, softcap=cap, scale=scale,
    )
    return out.transpose(0, 2, 1, 3)


def apply_attention(
    p,
    cfg: ArchConfig,
    x,
    positions,
    *,
    window=None,
    causal=True,
    cross_states=None,
):
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    kv_x = cross_states if cross_states is not None else x
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if cross_states is None and cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    s_len, t_len = q.shape[1], k.shape[1]
    scale = cfg.attn_scale or cfg.head_dim_ ** -0.5
    is_self_causal = causal and cross_states is None
    # Scores, softmax and values (with their backward and recompute) carry
    # the scope ATTENTION_SCOPE on the device profile; the projections not.
    with jax.named_scope(ATTENTION_SCOPE):
        if is_self_causal and cfg.attn_backend == "pallas":
            out = _flash_self_attention(
                q, k, v, scale=scale, cap=cfg.attn_softcap, window=window,
            )
        elif is_self_causal and s_len >= CHUNKED_ATTN_THRESHOLD:
            out = _chunked_attention(
                q, k, v, scale=scale, cap=cfg.attn_softcap,
                causal=True, window=window,
            )
        else:
            if is_self_causal:
                mask = causal_mask(s_len, t_len, window=window)
            else:
                mask = jnp.ones((s_len, t_len), dtype=bool)
            out = gqa_scores(q, k, v, mask, scale=scale,
                             cap=cfg.attn_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# KV cache (decode). Unified ring buffer: full attention uses W = max_seq,
# sliding-window layers use W = window — O(window) memory for long contexts.
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ArchConfig, batch, slots, dtype):
    kh, dh = cfg.num_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, slots, kh, dh), dtype),
        "v": jnp.zeros((batch, slots, kh, dh), dtype),
        "pos": jnp.full((batch, slots), -1, jnp.int32),  # -1 = empty slot
    }


def kv_cache_specs(worker_axes=()):
    data_axes = ("data",) if "data" not in worker_axes else ()
    batch_spec = tuple(worker_axes) + data_axes
    spec = P(batch_spec if batch_spec else None, None, MODEL, None)
    return {"k": spec, "v": spec, "pos": P(batch_spec if batch_spec else None, None)}


def decode_attention(p, cfg: ArchConfig, x_t, pos_t, cache, *, window=None,
                     cross_states=None):
    """One-token decode. x_t: (B, 1, D); pos_t: (B,) current position.

    Returns (out (B,1,D), new_cache). Cross-attention decodes against the
    full encoder states instead of the cache.
    """
    if cross_states is not None:
        q, k, v = _project_qkv(p, cfg, x_t, cross_states)
        mask = jnp.ones((1, k.shape[1]), dtype=bool)
        scale = cfg.attn_scale or cfg.head_dim_ ** -0.5
        out = gqa_scores(q, k, v, mask, scale=scale, cap=cfg.attn_softcap)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache

    q, k, v = _project_qkv(p, cfg, x_t, x_t)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, pos_t[:, None], cfg.rope_theta)
        k = apply_rope(k, pos_t[:, None], cfg.rope_theta)

    slots = cache["k"].shape[1]
    slot = (pos_t % slots).astype(jnp.int32)                     # (B,)
    b_idx = jnp.arange(k.shape[0])
    new_k = cache["k"].at[b_idx, slot].set(k[:, 0])
    new_v = cache["v"].at[b_idx, slot].set(v[:, 0])
    new_pos = cache["pos"].at[b_idx, slot].set(pos_t)
    new_cache = {"k": new_k, "v": new_v, "pos": new_pos}

    # validity: slot holds a real position, ≤ current, and within window
    valid = (new_pos >= 0) & (new_pos <= pos_t[:, None])
    if window is not None:
        valid &= new_pos > pos_t[:, None] - window
    mask = valid[:, None, None, None, :]                         # (B,1,1,1,T)
    scale = cfg.attn_scale or cfg.head_dim_ ** -0.5
    out = gqa_scores(q, new_k, new_v, mask, scale=scale, cap=cfg.attn_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache
