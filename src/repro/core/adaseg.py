"""LocalAdaSEG — Algorithm 1 of the paper, as composable JAX functions.

Per-worker state and the three ingredients of the method:

1.  Extragradient double update from the (possibly synced) anchor z̃*:
        z_t  = Π_Z[z̃* − η_t · G(z̃*, ξ₁)]          (exploration step)
        z̃_t = Π_Z[z̃* − η_t · G(z_t, ξ₂)]          (anchor update)

2.  AdaGrad-type local learning rate (Line 4):
        η_t = D·α / sqrt(G₀² + Σ_{τ<t} (Z_τ)²),
        (Z_t)² = (‖z_t − z̃*_{t−1}‖² + ‖z_t − z̃_t‖²) / (5 η_t²)

3.  Inverse-stepsize weighted periodic averaging (Line 7):
        w_t^m ∝ 1/η_t^m,  z̃° = Σ_m w_t^m z̃_{t−1}^m        every K steps.

The sync is abstracted as ``sync_fn(z_tilde, inv_eta) -> z̃°`` so that the same
step code runs in three harnesses:
  * serial/vmap over a leading worker axis (CPU experiments, tests),
  * ``shard_map`` with ``lax.psum`` over mesh worker axes (production —
    see ``launch.sharded.run_local_adaseg_sharded``),
  * single worker (degenerates to the serial AdaSEG of Bach & Levy '19).

The configurable production runtime (schedules, compression, faults,
checkpoint/resume) is ``repro.ps.PSEngine``, which consumes this module
through ``core.worker.AdaSEGWorker`` — the LocalWorker-protocol face of
Algorithm 1 — and stays bit-exact with :func:`run_local_adaseg` in the
identity configuration.

Step backends
-------------
The inner extragradient update is pluggable (``backend=`` on
:func:`local_step` / :func:`run_local_adaseg`):

* ``"reference"`` — naive pytree ops (this module): ~9 HBM passes over the
  parameter vector per step; always available, always correct.
* ``"fused"``     — the Pallas kernels in ``kernels.adaseg_update``: the
  η computation, projection, both updates and the (Z_t)²/‖G‖² reductions
  fuse into an exploration pass + an anchor pass (interpret mode off-TPU).
  Selected whenever the problem's projection carries a static spec
  (``projections.spec_of`` — identity/box/l2-ball, which covers the
  paper's BilinearGame and WGAN problems); opaque projections silently
  fall back to the reference math so semantics never fork.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import projections
from .tree import (
    tree_axpy,
    tree_norm_sq,
    tree_scale,
    tree_sub,
    tree_where,
    tree_zeros_like,
)
from .types import MinimaxProblem, draw

PyTree = Any
SyncFn = Callable[[PyTree, jax.Array], PyTree]

# ``jax.named_scope`` of the update around the oracle calls (both halves of
# the extragradient step, its η/Z² bookkeeping and the ``enabled`` masks).
UPDATE_SCOPE = "adaseg-update"


@dataclasses.dataclass(frozen=True)
class AdaSEGConfig:
    """Hyper-parameters of LocalAdaSEG(G0, D; K, M, R; alpha)."""

    g0: float          # initial guess of the gradient bound G
    diameter: float    # D, diameter bound of Z (Assumption 1)
    alpha: float = 1.0  # base lr: 1.0 nonsmooth (Thm 1), 1/sqrt(M) smooth (Thm 2)
    k: int = 1         # communication interval K
    average_output: bool = True  # return uniform iterate average (convex-concave)


class AdaSEGState(NamedTuple):
    """Per-worker state. In multi-worker harnesses every leaf gains a leading
    worker axis (vmap) or is the per-shard value (shard_map)."""

    z_tilde: PyTree       # z̃_t  — the anchor iterate
    sum_sq: jax.Array     # Σ_τ (Z_τ)²  (f32 scalar)
    t: jax.Array          # local step counter (int32)
    z_bar: PyTree         # running uniform average of {z_τ}  (output iterate)
    grad_sq_sum: jax.Array  # Σ_τ ‖g_τ‖² + ‖M_τ‖²  — the V_t(T) diagnostic (Fig E1d)
    worker_id: jax.Array  # int32 — used by heterogeneous samplers


class StepAux(NamedTuple):
    eta: jax.Array
    z_sq: jax.Array       # (Z_t)² increment
    grad_norm_sq: jax.Array


def eta_of(cfg: AdaSEGConfig, sum_sq: jax.Array) -> jax.Array:
    return cfg.diameter * cfg.alpha / jnp.sqrt(cfg.g0 ** 2 + sum_sq)


def init(problem: MinimaxProblem, cfg: AdaSEGConfig, rng,
         worker_id=0) -> AdaSEGState:
    z0 = problem.project(problem.init(rng))
    return AdaSEGState(
        z_tilde=z0,
        sum_sq=jnp.float32(0.0),
        t=jnp.int32(0),
        z_bar=tree_zeros_like(z0),
        grad_sq_sum=jnp.float32(0.0),
        worker_id=jnp.int32(worker_id),
    )


def local_step(
    problem: MinimaxProblem,
    cfg: AdaSEGConfig,
    state: AdaSEGState,
    rng,
    *,
    enabled=None,
    backend: str = "reference",
) -> tuple[AdaSEGState, StepAux]:
    """One extragradient step from the current anchor ``state.z_tilde``.

    ``enabled`` (bool scalar, optional) masks the update — used by the
    asynchronous variant where workers run heterogeneous K_m local steps per
    round (Appendix E.1): disabled workers keep their state unchanged.

    ``backend`` selects the update implementation (see module docstring):
    ``"reference"`` runs naive tree ops; ``"fused"`` routes through the
    Pallas extragradient kernels when ``problem.project`` carries a static
    projection spec, and falls back to the reference math otherwise.
    """
    if backend not in ("reference", "fused"):
        raise ValueError(f"unknown step backend {backend!r}")
    spec = projections.spec_of(problem.project) if backend == "fused" else None

    r1, r2 = jax.random.split(rng)
    z_star = state.z_tilde
    m_t = problem.oracle(z_star, draw(problem, r1, state.worker_id))  # M_t

    if spec is not None:
        # Fused path: η recomputed in-kernel from Σ(Z_τ)², projection and
        # the (Z_t)²/‖G‖² reductions fused into the two update passes.
        from ..kernels.adaseg_update.ops import (
            adaseg_tree_anchor,
            adaseg_tree_explore,
        )

        d_alpha = cfg.diameter * cfg.alpha

    with jax.named_scope(UPDATE_SCOPE):
        eta = eta_of(cfg, state.sum_sq)
        if spec is not None:
            z_t, m_sq = adaseg_tree_explore(
                z_star, m_t, sum_sq=state.sum_sq, g0=cfg.g0,
                d_alpha=d_alpha, proj=spec,
            )
        else:
            z_t = problem.project(tree_axpy(-eta, m_t, z_star))

    g_t = problem.oracle(z_t, draw(problem, r2, state.worker_id))  # g_t

    with jax.named_scope(UPDATE_SCOPE):
        if spec is not None:
            z_tilde_new, stat, g_sq = adaseg_tree_anchor(
                z_star, z_t, g_t, sum_sq=state.sum_sq, g0=cfg.g0,
                d_alpha=d_alpha, proj=spec,
            )
            z_sq = stat / (5.0 * eta ** 2)
            grad_norm_sq = g_sq + m_sq
        else:
            z_tilde_new = problem.project(tree_axpy(-eta, g_t, z_star))
            z_sq = (
                tree_norm_sq(tree_sub(z_t, z_star))
                + tree_norm_sq(tree_sub(z_t, z_tilde_new))
            ) / (5.0 * eta ** 2)
            grad_norm_sq = tree_norm_sq(g_t) + tree_norm_sq(m_t)

        t_new = state.t + 1
        # Incremental uniform mean of the exploration iterates z_t (Line 14).
        if cfg.average_output:
            z_bar_new = jax.tree.map(
                lambda zb, zt: zb + (zt - zb) / t_new.astype(zt.dtype),
                state.z_bar,
                z_t,
            )
        else:
            z_bar_new = z_t

        new = AdaSEGState(
            z_tilde=z_tilde_new,
            sum_sq=state.sum_sq + z_sq,
            t=t_new,
            z_bar=z_bar_new,
            grad_sq_sum=state.grad_sq_sum + grad_norm_sq,
            worker_id=state.worker_id,
        )
        if enabled is not None:
            new = AdaSEGState(
                z_tilde=tree_where(enabled, new.z_tilde, state.z_tilde),
                sum_sq=jnp.where(enabled, new.sum_sq, state.sum_sq),
                t=jnp.where(enabled, new.t, state.t),
                z_bar=tree_where(enabled, new.z_bar, state.z_bar),
                grad_sq_sum=jnp.where(enabled, new.grad_sq_sum,
                                      state.grad_sq_sum),
                worker_id=state.worker_id,
            )
    aux = StepAux(eta=eta, z_sq=z_sq, grad_norm_sq=grad_norm_sq)
    return new, aux


# ---------------------------------------------------------------------------
# Sync functions (Line 7): serial (stacked worker axis) and psum (shard_map).
# ---------------------------------------------------------------------------

def sync_weighted_stacked(z_tilde: PyTree, inv_eta: jax.Array, *,
                          backend: str = "reference",
                          server=None, srv=None):
    """Weighted average over a leading worker axis; returns the average
    broadcast back to every worker (axis preserved).

    ``backend="fused"`` routes through the Pallas server-merge kernel
    (``kernels.sync_compress.ops.sync_merge_stacked``): the 1/η weight
    normalization, the weighted sum over workers and the broadcast back run
    as one read + one write of the stacked fleet payload per leaf, instead
    of the scale/sum/broadcast tree passes here.

    ``server``/``srv`` compose the server-side outer optimizer
    (:mod:`repro.ps.server_opt`) downstream of the merge: the Line-7
    weighted mean becomes the pseudo-gradient Δ against the server anchor
    ``srv = (z, moments, t)``, the outer update runs (fused under
    ``backend="fused"``), and the *post-step* anchor is broadcast instead
    of the raw mean. The return value then grows to
    ``(synced, srv_new, telem)`` with ``telem = [eff_lr, ‖Δ‖]``; both
    ``None`` (the default) keeps the historical single-pytree return.
    """
    if server is not None:
        from ..kernels.sync_compress.ops import (
            server_outer_apply,
            sync_merge_stacked,
        )

        merged = sync_merge_stacked(
            z_tilde, inv_eta, normalize=True,
            use_kernel=backend == "fused",
        )
        z, mom, t = srv
        merged_row = jax.tree.map(lambda v: v[:1], merged)
        z_new, mom_new, t_new, eff_lr, dn = server_outer_apply(
            merged_row, z, mom, t, spec=server.spec,
            use_kernel=backend == "fused",
        )
        synced = jax.tree.map(
            lambda v, old: jnp.broadcast_to(v, old.shape), z_new, z_tilde
        )
        return synced, (z_new, mom_new, t_new), jnp.stack([eff_lr, dn])
    if backend == "fused":
        from ..kernels.sync_compress.ops import sync_merge_stacked

        return sync_merge_stacked(z_tilde, inv_eta, normalize=True)
    if backend != "reference":
        raise ValueError(f"unknown sync backend {backend!r}")
    w = inv_eta / jnp.sum(inv_eta)                      # (M,) simplex weights

    def avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        mean = jnp.sum(wb * leaf, axis=0, keepdims=True)
        return jnp.broadcast_to(mean, leaf.shape)

    return jax.tree.map(avg, z_tilde)


def make_psum_sync(axis_names: tuple[str, ...]) -> SyncFn:
    """Weighted average across mesh worker axes, for use inside shard_map.

    The parameter-server's gather + weighted-average + broadcast collapses to
    a single all-reduce of w·z̃ (plus a scalar all-reduce for the normalizer).
    """

    def sync(z_tilde: PyTree, inv_eta: jax.Array) -> PyTree:
        denom = lax.psum(inv_eta, axis_names)
        w = inv_eta / denom
        return jax.tree.map(
            lambda v: lax.psum((w.astype(v.dtype)) * v, axis_names), z_tilde
        )

    return sync


def sync_state(state: AdaSEGState, cfg: AdaSEGConfig, sync_fn: SyncFn) -> AdaSEGState:
    """Apply Line 5–8: replace every worker's anchor with the weighted average."""
    inv_eta = 1.0 / eta_of(cfg, state.sum_sq)
    return state._replace(z_tilde=sync_fn(state.z_tilde, inv_eta))


def weighted_worker_average(z_stacked: PyTree, counts: jax.Array) -> PyTree:
    """Line 14 global output: average a leading worker axis with weights
    ∝ per-worker step counts (uniform over all z_t^m). Shared by the serial
    driver and the PS engine so both compute the identical expression."""
    w = counts.astype(jnp.float32) / jnp.sum(counts.astype(jnp.float32))

    def avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(wb * leaf, axis=0)

    return jax.tree.map(avg, z_stacked)


# ---------------------------------------------------------------------------
# Serial multi-worker driver (vmap over workers) — used by the paper-
# experiment benchmarks and tests. Communication = weighted mean over axis 0.
# ---------------------------------------------------------------------------

def run_local_adaseg(
    problem: MinimaxProblem,
    cfg: AdaSEGConfig,
    *,
    num_workers: int,
    rounds: int,
    rng,
    local_steps: jax.Array | None = None,
    collect_aux: bool = True,
    backend: str = "reference",
):
    """Run LocalAdaSEG with M stacked workers for R rounds of K local steps.

    ``local_steps`` (int array of shape (M,), optional) gives heterogeneous
    per-worker step counts K_m for the asynchronous variant; by default every
    worker runs cfg.k steps per round. ``backend`` selects the step
    implementation (``"reference"`` tree ops or the ``"fused"`` Pallas
    kernels — see module docstring). Returns ``(z_bar, history)`` where
    z_bar is the global output iterate (Line 14) and history holds per-step
    diagnostics stacked as (R, K, M).
    """
    m = num_workers
    k = int(cfg.k)
    if local_steps is None:
        local_steps = jnp.full((m,), k, dtype=jnp.int32)
    else:
        local_steps = jnp.asarray(local_steps, dtype=jnp.int32)
        k = int(jnp.max(local_steps))

    init_rngs = jax.random.split(rng, m + 1)
    rng, worker_rngs = init_rngs[0], init_rngs[1:]
    state = jax.vmap(lambda r, w: init(problem, cfg, r, w))(
        worker_rngs, jnp.arange(m, dtype=jnp.int32)
    )

    vstep = jax.vmap(
        lambda st, r, en: local_step(problem, cfg, st, r, enabled=en,
                                     backend=backend)
    )

    def round_fn(state: AdaSEGState, rng_round):
        # Line 5–8: weighted sync at the top of each round (t-1 ∈ S).
        inv_eta = 1.0 / eta_of(cfg, state.sum_sq)  # (M,)
        state = state._replace(
            z_tilde=sync_weighted_stacked(state.z_tilde, inv_eta)
        )
        step_rngs = jax.random.split(rng_round, k * m).reshape(k, m, 2)

        def body(st, inputs):
            rngs, i = inputs
            enabled = i < local_steps  # (M,) mask for async variant
            st, aux = vstep(st, rngs, enabled)
            return st, aux

        state, aux = lax.scan(body, state, (step_rngs, jnp.arange(k)))
        return state, aux

    round_rngs = jax.random.split(rng, rounds)
    state, history = lax.scan(round_fn, state, round_rngs)

    # Global output: average worker means weighted by their step counts
    # (uniform over all z_t^m as in Line 14).
    counts = local_steps.astype(jnp.float32) * rounds
    z_bar = weighted_worker_average(state.z_bar, counts)
    return z_bar, (state, history if collect_aux else None)
