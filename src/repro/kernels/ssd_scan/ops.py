"""Jit'd wrapper for the SSD scan kernel (interpreted on the CPU).
Examples
--------
The chunked SSD form agrees with the sequential scan reference:

>>> import jax, jax.numpy as jnp, numpy as np
>>> from repro.kernels.ssd_scan.ops import ssd
>>> from repro.kernels.ssd_scan.ref import ssd_ref
>>> ks = jax.random.split(jax.random.PRNGKey(0), 5)
>>> x = jax.random.normal(ks[0], (1, 32, 2, 4))
>>> dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 32, 2)))
>>> a = -jnp.exp(jax.random.normal(ks[2], (2,)))
>>> b = jax.random.normal(ks[3], (1, 32, 8))
>>> c = jax.random.normal(ks[4], (1, 32, 8))
>>> out = ssd(x, dt, a, b, c, chunk=16)
>>> bool(np.allclose(out, ssd_ref(x, dt, a, b, c), atol=1e-4))
True
"""
from __future__ import annotations

import functools

import jax

from ..tiling import interpret_mode
from .kernel import ssd_scan
from .ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel"))
def ssd(x, dt, a, b, c, *, chunk=128, use_kernel=True):
    if not use_kernel:
        return ssd_ref(x, dt, a, b, c)
    return ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret_mode())
