"""Pallas TPU flash-attention kernel (blockwise online softmax).

Grid layout: ``(batch·heads, num_q_blocks, num_kv_blocks)`` with the KV axis
innermost and sequential ("arbitrary" dimension semantics): the running max
``m``, normalizer ``l`` and output accumulator live in VMEM scratch and are
carried across KV iterations; the normalized output tile is written once on
the final KV step. Q/K/V tiles are (block_q × head_dim) / (block_k ×
head_dim) VMEM blocks — the working set is
``(block_q + 2·block_k)·head_dim·4B + block_q·block_k·4B``, well under VMEM
for the default 512/512 blocking at head_dim ≤ 256.

Supports causal masking, sliding windows (gemma2/mixtral/recurrentgemma
local layers), gemma2 logit soft-capping, and GQA via an index map that
folds query-head groups onto shared KV heads. Fully-masked KV blocks are
skipped with ``pl.when`` — for causal attention that halves the work, and
for sliding windows it reduces it to O(S·W).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale, causal, window, softcap, block_q, block_k, num_kv_blocks,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # Block-level reachability: skip KV tiles that are fully masked.
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window is not None:
        # newest query in the block can reach back at most `window`
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window
        )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, ...].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, ...].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, ...].astype(jnp.float32)          # (bk, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= ki > qi - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                             # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)                     # guard exp(NEG_INF-…)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, ...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """q: (B, H, S, D); k, v: (B, Kh, T, D). Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    g = h // kh
    scale = scale if scale is not None else d**-0.5
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    nq, nk = s // block_q, t // block_k

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * kh, t, d)
    vf = v.reshape(b * kh, t, d)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
