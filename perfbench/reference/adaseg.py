"""Plain reference of LocalAdaSEG's Parameter-Server rounds (arXiv:2106.10022,
Algorithm 1) with q-bit stochastic quantization and error feedback on the
uplink — written from the paper and the engine's documented key
derivation, one worker and one local step at a time.

Per round r, with ``rk`` the round's key:

1. Sync (Line 5–8). Worker m sends ``eff = w_m·z̃_m + e_m`` with
   ``w_m = (1/η_m) / Σ 1/η``, quantized per leaf to ``levels`` magnitude
   steps of ``scale = max|eff|``; element i rounds up when
   ``u_i < y_i − ⌊y_i⌋``, ``u_i`` the threefry-2x32 uniform of counter
   ``(i, 0)`` under the leaf's key (``split(split(fold_in(rk, 7), M)[m],
   L)[l]``). ``e_m ← eff − sent``; every worker's anchor becomes Σ sent.
2. K local extragradient steps (Line 3–4), step key
   ``split(rk, K·M)[k·M + m]`` split once more for the two oracle calls:
   ``z_t = Π(z̃ − η M_t)``, ``z̃ ← Π(z̃ − η g_t)``,
   ``η = Dα / √(G0² + Σ Z²)``, ``Z² = (‖z_t − z̃‖² + ‖z_t − z̃'‖²)/(5η²)``.
3. Evaluation of the workers' mean output iterate.

Initial keys follow the engine: ``split(key, M + 1)`` gives the round
stream's base and one init key per worker; round keys are
``split(base, total_rounds)``.

``fault`` plants what a broken program would do, for the calibration of
the limits: ``"no_exchange"`` leaves the sync out (a batch cut in half is
the sampler's, given by the caller).

Worker m's local steps run on ``devices[m % len(devices)]``, so workers
that the program spreads over chips fit there in the reference too; the
sync, the merge and the evaluation run on ``devices[0]``. Where a value
moves between devices it moves as it is: the arithmetic does not depend on
the placement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry_2x32

BLOCK = 1 << 22          # elements per block of the quantizer's uniforms


def _uniforms(key, offset, n):
    """Threefry uniforms for counters ``offset .. offset + n − 1``."""
    idx = offset + jnp.arange(n, dtype=jnp.uint32)
    bits = threefry_2x32(
        key, jnp.concatenate([idx, jnp.zeros_like(idx)]))[:n]
    f = jax.lax.bitcast_convert_type(
        (bits >> np.uint32(9)) | np.uint32(0x3F800000), jnp.float32)
    return f - 1.0


@functools.partial(jax.jit, static_argnames=("levels",))
def _uplink_leaf(z, e, w, key, *, levels):
    """One worker's message for one leaf: (sent, new residual); with
    ``levels=None`` the message is sent as it is."""
    eff = w * z.astype(jnp.float32).reshape(-1) + e.reshape(-1)
    if levels is None:
        return eff.reshape(z.shape), e
    scale = jnp.maximum(jnp.max(jnp.abs(eff)), 1e-30)
    n = eff.size
    nb = -(-n // BLOCK)
    pad = jnp.pad(eff, (0, nb * BLOCK - n)).reshape(nb, BLOCK)

    def block(args):
        x, b = args
        u = _uniforms(key, (b * BLOCK).astype(jnp.uint32), BLOCK)
        y = jnp.abs(x) / scale * levels
        lo = jnp.floor(y)
        return jnp.sign(x) * (lo + (u < y - lo)) * (scale / levels)

    sent = jax.lax.map(block, (pad, jnp.arange(nb, dtype=jnp.int32)))
    sent = sent.reshape(-1)[:n]
    return sent.reshape(z.shape), (eff - sent).reshape(z.shape)


@jax.jit
def _axpy_leaves(z, g, eta):
    return jax.tree.map(lambda a, b: (a - eta * b).astype(a.dtype), z, g)


@jax.jit
def _sq(t):
    return sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
               for v in jax.tree.leaves(t))


@jax.jit
def _sq_diff(a, b):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)
                                  - y.astype(jnp.float32)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@jax.jit
def _leaf_norms(t):
    return [jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for v in jax.tree.leaves(t)]


def readings(*, oracle, sample, project, evaluate, init_worker, key,
             workers, local_steps, total_rounds, g0, d_alpha, levels,
             average_output, rounds=3, fault=None, devices=None):
    """The numbers that decide ``correct``, computed by the reference over
    the first ``rounds`` rounds: the evaluation after each round, √(Σ‖G‖²)
    per worker after round 1 (every oracle gradient of that round as the
    optimizer got it), the per-leaf norm of the anchors' change from round
    1 to round ``rounds`` (all workers stacked), and the per-leaf norm of
    the very first oracle gradient (worker 0), which says which leaves the
    gradient reaches at all."""
    m, k = workers, local_steps
    devs = devices or jax.devices()[:1]
    home = devs[0]
    keys = jax.random.split(key, m + 1)
    round_keys = jax.random.split(keys[0], total_rounds)
    z = []
    for i in range(m):
        with jax.default_device(devs[i % len(devs)]):
            z.append(project(init_worker(keys[1 + i])))
    # The uplink residuals wait on the host between syncs.
    e = [jax.tree.map(lambda v: np.zeros(v.shape, np.float32), z[0])
         for _ in range(m)]
    # The output iterate: a running average per worker, or (without
    # averaging) each worker's last z_t, summed into the mean as it comes.
    zbar = ([jax.tree.map(jnp.zeros_like, zi) for zi in z]
            if average_output else None)
    sum_sq = [jnp.float32(0.0)] * m
    grad_sq = [jnp.float32(0.0)] * m
    t = [0] * m
    out = {"loss": [], "first_grad": None}
    snap = None
    for r in range(rounds):
        rk = round_keys[r]
        if fault != "no_exchange":
            inv = [1.0 / (d_alpha
                          / jnp.sqrt(g0 ** 2 + jax.device_put(s, home)))
                   for s in sum_sq]
            tot = sum(inv)
            ckeys = jax.random.split(jax.random.fold_in(rk, 7), m)
            leaves0, tdef = jax.tree.flatten(z[0])
            merged = [jnp.zeros(v.shape, jnp.float32) for v in leaves0]
            for i in range(m):
                lkeys = jax.random.split(ckeys[i], len(leaves0))
                zl = jax.tree.leaves(z[i])
                el = jax.tree.leaves(e[i])
                new_e = []
                for li in range(len(zl)):
                    sent, res = _uplink_leaf(
                        jax.device_put(zl[li], home), el[li], inv[i] / tot,
                        lkeys[li], levels=levels)
                    merged[li] = merged[li] + sent
                    new_e.append(np.asarray(res))
                e[i] = jax.tree.unflatten(tdef, new_e)
            z = [jax.tree.unflatten(
                tdef, [v.astype(l.dtype) for v, l in zip(merged, leaves0)])
            ] * m
            del merged, leaves0, zl, el, new_e, sent, res
        step_keys = jax.random.split(rk, k * m).reshape(k, m, 2)
        mean = None
        for i in range(m):
            dev = devs[i % len(devs)]
            z[i] = jax.device_put(z[i], dev)
            with jax.default_device(dev):
                for s in range(k):
                    zt = None       # the last step's z_t: no longer needed
                    r1, r2 = jax.random.split(step_keys[s, i])
                    eta = d_alpha / jnp.sqrt(g0 ** 2 + sum_sq[i])
                    mt = oracle(z[i], sample(r1))
                    if out["first_grad"] is None:
                        out["first_grad"] = [float(v)
                                             for v in _leaf_norms(mt)]
                    mt_sq = _sq(mt)
                    zt = project(_axpy_leaves(z[i], mt, eta))
                    del mt
                    gt = oracle(zt, sample(r2))
                    grad_sq[i] = grad_sq[i] + _sq(gt) + mt_sq
                    zn = project(_axpy_leaves(z[i], gt, eta))
                    del gt
                    sum_sq[i] = sum_sq[i] + (
                        _sq_diff(zt, z[i]) + _sq_diff(zt, zn)) / (5 * eta ** 2)
                    t[i] += 1
                    if average_output:
                        zbar[i] = jax.tree.map(
                            lambda b, x: b + (x - b) / jnp.float32(
                                t[i]).astype(x.dtype), zbar[i], zt)
                    z[i] = zn
                last = zbar[i] if average_output else zt
                part = jax.tree.map(lambda x: x / m, last)
            part = jax.device_put(part, home)
            mean = part if mean is None else jax.tree.map(jnp.add, mean, part)
            del zt, last, part
        out["loss"].append(float(evaluate(mean)))
        del mean
        if r == 0:
            out["grad_sq"] = [float(g) for g in grad_sq]
            snap = [jax.tree.map(np.asarray, zi) for zi in z]
    change = [0.0] * len(jax.tree.leaves(z[0]))
    for zi, si in zip(z, snap):
        for li, (a, b) in enumerate(zip(jax.tree.leaves(zi),
                                        jax.tree.leaves(si))):
            change[li] += float(_sq_diff(a, jnp.asarray(b)))
    out["change"] = [c ** 0.5 for c in change]
    return out
