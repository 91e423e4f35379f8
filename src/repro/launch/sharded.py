"""shard_map production driver for LocalAdaSEG.

The serial driver (``core.adaseg.run_local_adaseg``) stacks M workers on a
leading axis and vmaps the step — fine for CPU experiments, but every
worker's parameters live on one device. This driver places one worker (or
one worker group) per mesh shard with ``shard_map``: each shard runs its K
local steps independently, and the paper's Parameter-Server round
(Line 5–8: gather → inverse-stepsize weighted average → broadcast)
collapses to a single ``lax.psum`` all-reduce of w·z̃ per round via
``core.adaseg.make_psum_sync`` — the K-amortized communication pattern the
paper's bounds are about.

RNG derivation is bit-identical to the serial driver, so for a given seed
``run_local_adaseg_sharded`` reproduces ``run_local_adaseg`` trajectories
exactly (up to all-reduce summation order) — the parity tests in
``tests/test_distributed.py`` pin this. The step backend is pluggable here
exactly as in the serial driver (``backend="reference" | "fused"``).

Heterogeneous local work (the asynchronous variant of Appendix E.1) is
supported via ``local_steps``: per-worker K_m with the same ``enabled``
masking semantics as the serial driver, parity-pinned against it. The
Line-7 sync is a hook: pass ``sync_fn(z_tilde, inv_eta)`` — or, for codecs
that need randomness, ``sync_fn(z_tilde, inv_eta, rng)`` — to replace the
dense psum with e.g. a compressed-psum from ``repro.ps.compress``
(``make_compressed_psum_sync``). Sync rngs are derived eagerly on the host
(fold_in(round_rng, 7), split per worker — the PS engine's derivation):
with the default non-partitionable threefry, key derivation inside the jit
that feeds a shard_map would be re-sharded and silently change the stream.

This module remains the *one-shot* sharded driver for Algorithm 1. The
configurable runtime — schedules × compression × faults × resume, for
LocalAdaSEG and the whole optimizer zoo — is ``repro.ps.PSEngine`` with
``mesh=``, whose sharded chunk reproduces this driver's psum-sync and rng
semantics (parity-pinned in ``tests/test_distributed.py``).
"""
from __future__ import annotations

import inspect
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.adaseg import (
    AdaSEGConfig,
    eta_of,
    init,
    local_step,
    make_psum_sync,
)
from ..core.types import MinimaxProblem


def _worker_count(mesh, worker_axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in worker_axes)


def run_local_adaseg_sharded(
    problem: MinimaxProblem,
    cfg: AdaSEGConfig,
    *,
    mesh,
    worker_axes: tuple[str, ...] = ("data",),
    rounds: int,
    rng,
    backend: str = "reference",
    collect_aux: bool = False,
    local_steps=None,
    sync_fn=None,
):
    """Run LocalAdaSEG with one worker per shard of ``worker_axes``.

    Returns ``(z_bar, (state, history))`` exactly like the serial driver:
    ``z_bar`` is the global output iterate (replicated), ``state`` carries
    the leading worker axis (sharded over ``worker_axes``), and ``history``
    holds per-step diagnostics stacked as (R, K, M) when ``collect_aux``.

    ``local_steps`` (int array of shape (M,), optional) gives heterogeneous
    per-worker step counts K_m — the asynchronous Parameter-Server variant —
    with the same masking semantics as the serial driver (workers beyond
    their K_m hold their state; the Line-14 output weights workers by their
    realized step counts). ``sync_fn`` overrides the Line-7 all-reduce
    (default: ``make_psum_sync(worker_axes)``); a 3-argument hook also
    receives a per-worker, per-round rng for stochastic codecs.
    """
    if not worker_axes:
        raise ValueError("worker_axes must name at least one mesh axis")
    m = _worker_count(mesh, worker_axes)

    has_ls = local_steps is not None
    if has_ls:
        ls = jnp.asarray(local_steps, dtype=jnp.int32)
        if ls.shape != (m,):
            raise ValueError(f"local_steps must have shape ({m},), got {ls.shape}")
        k = int(jnp.max(ls))
    else:
        ls = None
        k = int(cfg.k)

    sync = sync_fn if sync_fn is not None else make_psum_sync(worker_axes)
    wants_rng = (
        sync_fn is not None
        and len(inspect.signature(sync_fn).parameters) >= 3
    )

    # Identical rng derivation to run_local_adaseg: worker inits from
    # split(rng, M+1)[1:], then per-round step rngs split(round_rng, K·M)
    # laid out as (K, M, 2) — transposed here to a leading worker axis.
    init_rngs = jax.random.split(rng, m + 1)
    rng0, worker_rngs = init_rngs[0], init_rngs[1:]
    round_rngs = jax.random.split(rng0, rounds)
    step_rngs = jax.vmap(
        lambda r: jax.random.split(r, k * m).reshape(k, m, 2)
    )(round_rngs)                                     # (R, K, M, 2)
    step_rngs = jnp.transpose(step_rngs, (2, 0, 1, 3))  # (M, R, K, 2)
    worker_ids = jnp.arange(m, dtype=jnp.int32)

    lead = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    spec_w = P(lead)

    operands = [worker_rngs, step_rngs, worker_ids]
    in_specs = [spec_w, P(lead, None, None, None), spec_w]
    if has_ls:
        operands.append(ls)
        in_specs.append(spec_w)
    if wants_rng:
        sync_rngs = jax.vmap(
            lambda r: jax.random.split(jax.random.fold_in(r, 7), m)
        )(round_rngs)                                 # (R, M, 2)
        operands.append(jnp.transpose(sync_rngs, (1, 0, 2)))  # (M, R, 2)
        in_specs.append(P(lead, None, None))

    def shard_fn(w_rng, s_rngs, wid, *rest):
        # Per-shard shapes: w_rng (1, 2), s_rngs (1, R, K, 2), wid (1,),
        # then optionally ls (1,) and sync_rngs (1, R, 2).
        rest = list(rest)
        k_m = rest.pop(0)[0] if has_ls else None
        sy_rngs = rest.pop(0)[0] if wants_rng else jnp.zeros(
            (rounds, 2), jnp.uint32
        )
        state = init(problem, cfg, w_rng[0], wid[0])

        def round_fn(st, inputs):
            rngs_round, sync_rng = inputs
            # Line 5–8: weighted sync at the top of each round, as one
            # all-reduce of (possibly compressed) w·z̃ across worker axes.
            with jax.named_scope("sync"):
                inv_eta = 1.0 / eta_of(cfg, st.sum_sq)
                if wants_rng:
                    st = st._replace(
                        z_tilde=sync(st.z_tilde, inv_eta, sync_rng)
                    )
                else:
                    st = st._replace(z_tilde=sync(st.z_tilde, inv_eta))

            with jax.named_scope("local-compute"):
                if has_ls:
                    def body(s, inp):
                        r, i = inp
                        return local_step(problem, cfg, s, r,
                                          enabled=i < k_m, backend=backend)

                    return lax.scan(body, st, (rngs_round, jnp.arange(k)))

                def body(s, r):
                    return local_step(problem, cfg, s, r, backend=backend)

                return lax.scan(body, st, rngs_round)

        state, hist = lax.scan(round_fn, state, (s_rngs[0], sy_rngs))

        # Line 14 global output: worker means weighted by realized step
        # counts (uniform K degenerates to the plain mean).
        if has_ls:
            count_m = (k_m * rounds).astype(jnp.float32)
            w_m = count_m / lax.psum(count_m, worker_axes)
            z_bar = jax.tree.map(
                lambda v: lax.psum(w_m.astype(v.dtype) * v, worker_axes),
                state.z_bar,
            )
        else:
            z_bar = jax.tree.map(
                lambda v: lax.psum(v, worker_axes) / m, state.z_bar
            )
        state_out = jax.tree.map(lambda v: v[None], state)
        hist_out = jax.tree.map(lambda v: v[:, :, None], hist)  # (R, K, 1)
        return z_bar, state_out, hist_out

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        # Prefix specs: z_bar replicated (post-psum), state leaves carry the
        # leading worker axis, history is (R, K, M) with M sharded.
        out_specs=(P(), spec_w, P(None, None, lead)),
        check_vma=False,
    )
    z_bar, state, hist = jax.jit(fn)(*operands)
    return z_bar, (state, hist if collect_aux else None)
