"""Jit'd wrapper for the flash-attention kernel (interpreted on the CPU)
and automatic sequence padding to the block size.

Examples
--------
Causal attention agrees with the pure-jnp reference:

>>> import jax, jax.numpy as jnp, numpy as np
>>> from repro.kernels.flash_attention.ops import attention
>>> from repro.kernels.flash_attention.ref import attention_ref
>>> q = k = v = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
>>> out = attention(q, k, v, causal=True, block_q=16, block_k=16)
>>> bool(np.allclose(out, attention_ref(q, k, v, causal=True), atol=1e-5))
True
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tiling import interpret_mode
from .kernel import flash_attention
from .ref import attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_k", "use_kernel"),
)
def attention(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
              block_q=512, block_k=512, use_kernel=True):
    """(B, H, S, D) x (B, Kh, T, D) attention; pads S/T up to block multiples."""
    if not use_kernel:
        return attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    b, h, s, d = q.shape
    t = k.shape[2]
    bq, bk = min(block_q, s), min(block_k, t)
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # padded K slots sit at positions > every real query → masked by causal;
    # for non-causal the window/mask below would need explicit lengths, so we
    # only allow padding in the causal path.
    assert causal or (pad_q == 0 and pad_k == 0)
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=bq, block_k=bk, interpret=interpret_mode(),
    )
    return out[:, :, :s, :]
