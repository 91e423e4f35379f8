"""Pallas TPU flash-attention kernels: forward (blockwise online softmax)
and its FlashAttention-2 backward.

Forward grid: ``(batch·heads, num_q_blocks, num_kv_blocks)`` with the KV
axis innermost and sequential ("arbitrary" dimension semantics): the running
max ``m``, normalizer ``l`` and output accumulator live in VMEM scratch and
are carried across KV iterations; the normalized output tile and the row
log-sum-exp ``lse = m + log l`` are written once on the final KV step. Q/K/V
tiles are (block_q × head_dim) / (block_k × head_dim) VMEM blocks — the
working set is ``(block_q + 2·block_k)·head_dim·4B + block_q·block_k·4B``,
well under VMEM for the default 512/512 blocking at head_dim ≤ 256.

Backward grid: ``(batch·heads, num_kv_blocks, num_q_blocks)`` with both
sequence axes sequential. Each tile recomputes ``P = exp(s − lse)`` in VMEM
from Q, K and the forward's ``lse``; ``dV``/``dK`` of the KV tile accumulate
in scratch over the Q axis, and ``dQ`` accumulates in its (S × head_dim) f32
output block, resident in VMEM for the whole head. ``dK``/``dV`` are written
per query head; the caller sums the ``heads // kv_heads`` heads of a group.
No S×T array ever reaches HBM.

Both support causal masking, sliding windows (gemma2/mixtral/recurrentgemma
local layers), gemma2 logit soft-capping, and GQA via an index map that
folds query-head groups onto shared KV heads. Fully-masked tiles are
skipped with ``pl.when`` — for causal attention that halves the work, and
for sliding windows it reduces it to O(S·W). Operands are cast to f32 and
every dot accumulates in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _reachable(q_start, k_start, *, causal, window, block_q, block_k):
    """Whether any (query, key) pair of the tile is unmasked."""
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window is not None:
        # newest query in the block can reach back at most `window`
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window
        )
    return reachable


def _logits(q, k, q_start, k_start, *, scale, causal, window, softcap):
    """Masked (block_q × block_k) logits, and tanh(s/softcap) for the
    softcap backward (None without a softcap)."""
    block_q, block_k = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    t = None
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    return jnp.where(mask, s, NEG_INF), mask, t


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
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
    @pl.when(_reachable(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[0, ...].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, ...].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, ...].astype(jnp.float32)          # (bk, d)
        s, mask, _ = _logits(q, k, q_start, k_start, scale=scale,
                             causal=causal, window=window, softcap=softcap)

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
        lse_ref[0, 0, :] = m_scr[...] + jnp.log(l)


def _blocks(q, k, block_q, block_k):
    s, t = q.shape[2], k.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    return block_q, block_k


def flash_attention_fwd(
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
    """q: (B, H, S, D); k, v: (B, Kh, T, D). Returns the output (B, H, S, D)
    and the f32 row log-sum-exp of the scaled logits, (B, H, S)."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    g = h // kh
    scale = scale if scale is not None else d**-0.5
    block_q, block_k = _blocks(q, k, block_q, block_k)
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

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            # (1, S) rows: a lane-dense block whose second-minor dim spans
            # the array, as Mosaic requires of a (1, block_q) block.
            pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
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
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


def flash_attention(q, k, v, **kwargs):
    """q: (B, H, S, D); k, v: (B, Kh, T, D). Returns (B, H, S, D).

    Keyword arguments as :func:`flash_attention_fwd`."""
    return flash_attention_fwd(q, k, v, **kwargs)[0]


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, window, softcap, block_q, block_k, num_q_blocks,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(jnp.logical_and(ik == 0, iq == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(iq == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    @pl.when(_reachable(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[0, ...].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, ...].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, ...].astype(jnp.float32)          # (bk, d)
        do = do_ref[0, ...].astype(jnp.float32)        # (bq, d)
        lse = lse_ref[0, 0, :]                         # (bq,)
        delta = delta_ref[0, 0, :]                     # (bq,)
        s, mask, t = _logits(q, k, q_start, k_start, scale=scale,
                             causal=causal, window=window, softcap=softcap)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)

        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        ds = ds * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
        dq_ref[0, rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = dk_scr[...]
        dv_ref[0, ...] = dv_scr[...]


def flash_attention_bwd(
    q, k, v, o, lse, do,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Gradients of :func:`flash_attention` given its output ``o``, its row
    log-sum-exp ``lse`` (B, H, S) and the output cotangent ``do``.

    Returns (dq, dk, dv) in the dtypes of (q, k, v).
    """
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    g = h // kh
    scale = scale if scale is not None else d**-0.5
    block_q, block_k = _blocks(q, k, block_q, block_k)
    nq, nk = s // block_q, t // block_k

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * kh, t, d)
    vf = v.reshape(b * kh, t, d)
    dof = do.reshape(b * h, s, d)
    lsef = lse.reshape(b * h, 1, s)
    deltaf = delta.reshape(b * h, 1, s)

    def q_block(ik, iq):
        """Q-side block index of step (ik, iq). Unreachable steps keep the
        block of their nearest reachable one, so the pipeline fetches
        nothing for them."""
        k_start = ik * block_k
        if causal:
            iq = jnp.minimum(jnp.maximum(iq, k_start // block_q), nq - 1)
        if window is not None:
            iq = jnp.minimum(iq, (k_start + block_k - 2 + window) // block_q)
        return iq

    q_rows = pl.BlockSpec((1, block_q, d),
                          lambda bh, ik, iq: (bh, q_block(ik, iq), 0))
    q_stats = pl.BlockSpec((1, 1, block_q),
                           lambda bh, ik, iq: (bh, 0, q_block(ik, iq)))
    kv_rows = pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh // g, ik, 0))

    kernel = functools.partial(
        _flash_bwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_q_blocks=nq,
    )
    f32 = jnp.float32
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, nk, nq),
        in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stats, q_stats],
        out_specs=[
            pl.BlockSpec((1, s, d), lambda bh, ik, iq: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), f32),
            jax.ShapeDtypeStruct((b * h, t, d), f32),
            jax.ShapeDtypeStruct((b * h, t, d), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), f32),
            pltpu.VMEM((block_k, d), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)
    # dK/dV per query head: sum each KV head's group of g query heads.
    dk = dk.reshape(b, kh, g, t, d).sum(axis=2)
    dv = dv.reshape(b, kh, g, t, d).sum(axis=2)
    return (dq.reshape(b, h, s, d).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))
