"""The benchmark's own token stream and seed keys.

The stream is a Zipf unigram over the vocabulary mixed with a first-order
Markov shift: with probability ``markov_p`` a token is its predecessor + 1
(mod vocab), otherwise a fresh Zipf(``zipf_alpha``) draw — the distribution
of ``repro.data.synthetic.sample_tokens``. Draws cost O(tokens · log vocab):
inverse CDF over a cumulative table built once, instead of a categorical
over every logit per token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key holding all 64 bits of ``seed`` (JAX's own
    ``PRNGKey`` keeps only the low 32 without x64)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def subkey(seed: int, purpose: int) -> jax.Array:
    """The key of one use of the seed (weights, engine, data, …)."""
    return jax.random.fold_in(seed_key(seed), purpose)


def zipf_cdf(vocab: int, alpha: float) -> np.ndarray:
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(alpha)
    cdf = np.cumsum(w) / np.sum(w)
    cdf[-1] = 1.0
    return cdf.astype(np.float32)


def make_sampler(*, vocab: int, batch: int, seq: int, zipf_alpha: float,
                 markov_p: float):
    """``sample(rng) -> {"tokens", "labels"}``, each (batch, seq) int32."""
    cdf = zipf_cdf(vocab, zipf_alpha)

    def sample(rng):
        r1, r2 = jax.random.split(rng)
        u = jax.random.uniform(r1, (batch, seq + 1))
        base = jnp.searchsorted(jnp.asarray(cdf), u, side="right")
        base = jnp.minimum(base, vocab - 1).astype(jnp.int32)
        rep = jax.random.bernoulli(r2, markov_p, (batch, seq + 1))
        shifted = (jnp.roll(base, 1, axis=1) + 1) % vocab
        toks = jnp.where(rep, shifted, base).astype(jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return sample
