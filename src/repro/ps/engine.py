"""Parameter-Server round engine — optimizer-generic (Algorithm 1 at fleet
scale, for the whole zoo).

The engine owns the round loop of the paper's Parameter-Server model and
threads the pluggable policies through it:

* :class:`~repro.core.worker.LocalWorker` → everything optimizer-specific:
  init, the (enabled-masked) local step, the Line-7 sync weight/payload,
  the output iterate. ``AdaSEGWorker`` is the paper's Algorithm 1;
  ``optim.base.MinimaxWorker`` lifts every zoo baseline (SGDA, SEGDA,
  Adam, UMP, ASMP) onto the same runtime;
* :class:`~repro.ps.schedule.WorkerSchedule` → per-round, per-worker local
  step counts K_m^r (Line 3–4), fed through the worker's ``enabled`` mask;
* :class:`~repro.ps.compress.SyncCompressor` → lossy codec for the uphill
  w·payload messages (Line 5/7), with error feedback when biased;
* :class:`~repro.ps.faults.FaultPolicy` → per-round worker failures, with
  the sync weights renormalized over survivors (Line 6–7) and dead workers
  keeping their stale payload;
* :class:`~repro.ps.trace.TraceRecorder` → per-round telemetry (bytes
  up/down, effective K, η spread, wall-clock, local-steps/sec, residual).

Two execution paths, same semantics:

* ``mesh=None`` — the serial vmap path (a stacked worker axis). With the
  AdaSEG worker, the identity compressor, no faults and a uniform schedule
  this path is **bit-exact** with ``core.adaseg.run_local_adaseg``; with a
  ``MinimaxWorker`` it reproduces the historical ``optim.base.run_local``
  trajectories (each worker carries its family's rng derivation).
* ``mesh=...`` — one worker per shard of ``worker_axes`` via ``shard_map``,
  with Line 7 as a single psum all-reduce of the (compressed) weighted
  messages, like ``launch.sharded.run_local_adaseg_sharded``.

Checkpointed execution: the engine state (per-worker optimizer state —
including optimizer-specific ``inner`` extras like Adam moments or UMP
accumulators — error-feedback memory, round counter, seed and optimizer
fingerprints) serializes through ``checkpoint.serialize``; schedules and
fault traces are *re-derived* from the config seeds rather than stored, so
a killed run resumes bit-exactly (serial) mid-stream. Restores from a
different seed *or a different optimizer* are rejected.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..checkpoint.serialize import load_pytree, save_pytree
from ..core.adaseg import AdaSEGConfig, weighted_worker_average
from ..core.tree import tree_add, tree_sub, tree_where, tree_zeros_like
from ..core.types import MinimaxProblem
from ..core.worker import AdaSEGWorker, LocalWorker
from ..obs import MetricsRegistry, SpanTracer, modeled_sync_cost
from .compress import (
    IdentityCompressor,
    SyncCompressor,
    check_codec_backend,
    dense_bytes,
)
from .faults import FaultPolicy, NoFaults
from .robust import ByzantinePolicy, DPUplink, RobustAggregator, WeightedMean
from .sampler import ClientSampler
from .schedule import UniformSchedule, WorkerSchedule
from .server_opt import NoServerOpt, ServerOptimizer, resolve_server_opt
from .trace import RoundRecord, TraceRecorder

PyTree = Any

# The chunk jits donate the stacked state/EF buffers (the engine never
# reads them after the call), so a 10k-worker fleet updates in place
# instead of round-tripping host<->device copies every chunk. CPU ignores
# donation (it has no aliasing support in this jax build) and would warn
# once per compile; the semantics are identical either way.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


@dataclasses.dataclass(frozen=True)
class PSConfig:
    """Everything the Parameter-Server simulator needs beyond the problem.

    The optimizer is given either as ``adaseg=`` (an :class:`AdaSEGConfig`,
    wrapped into an :class:`AdaSEGWorker` with ``backend`` — the historical
    spelling, kept as the primary one for the paper's method) or as
    ``worker=`` (any :class:`LocalWorker`, e.g. ``MinimaxWorker(sgda(...))``
    for the zoo). Generic workers carry no communication interval of their
    own, so give them ``local_k=`` (or an explicit ``schedule=``).

    Examples
    --------
    >>> from repro.core import AdaSEGConfig
    >>> cfg = PSConfig(adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=5),
    ...                num_workers=4, rounds=10, codec_backend="fused")
    >>> cfg.num_workers, cfg.codec_backend
    (4, 'fused')
    """

    num_workers: int
    rounds: int
    adaseg: AdaSEGConfig | None = None       # AdaSEG spelling (+ backend)
    worker: LocalWorker | None = None        # generic spelling
    local_k: int | None = None               # uniform K for generic workers
    schedule: WorkerSchedule | None = None   # default: uniform K
    compressor: SyncCompressor | None = None  # default: identity
    faults: FaultPolicy | None = None        # default: no faults
    backend: str = "reference"               # AdaSEG step backend
    codec_backend: str = "reference"         # sync codec: reference | fused
    # Sampled-client rounds: draw sampler.sample of num_workers fleet
    # members per round (None = full participation, the historical path).
    sampler: ClientSampler | None = None
    # Hostile-fleet subsystem (repro.ps.robust). Any of these switches the
    # uplink to the unweighted wire format with Line-7 weights applied
    # server-side; all None (or a zero-budget aggregator) compiles the
    # identical historical path.
    byzantine: ByzantinePolicy | None = None  # adversarial uplinks
    aggregator: RobustAggregator | None = None  # robust server merge
    dp: DPUplink | None = None               # l2 clip + Gaussian noise
    # Server-side outer optimizer over round deltas (DiLoCo/FedOpt):
    # None (or NoServerOpt) is the historical Line-7 broadcast, bit-exact.
    server_opt: ServerOptimizer | None = None


@dataclasses.dataclass(frozen=True)
class RobustPipeline:
    """The *resolved* hostile-fleet configuration threaded into the chunk
    builders (and their process-wide cache keys): the attack policy, the
    static merge spec at the compiled lane width, and the DP transform.
    ``None`` anywhere means that layer is off; the engines only build a
    pipeline at all when at least one layer is active."""

    byzantine: ByzantinePolicy | None
    agg: tuple | None
    dp: DPUplink | None


def resolve_robust(config: PSConfig, lanes: int) -> RobustPipeline | None:
    """Resolve a config's hostile-fleet fields at compiled lane width
    ``lanes`` (the sampled width under a ``ClientSampler``, else the
    fleet). Returns ``None`` — the exact historical path — when no attack,
    no DP, and the aggregator degrades (``spec(lanes) is None``)."""
    agg = config.aggregator or WeightedMean()
    spec = agg.spec(lanes)
    if config.byzantine is None and spec is None and config.dp is None:
        return None
    return RobustPipeline(config.byzantine, spec, config.dp)


def _resolve_worker(config: PSConfig) -> LocalWorker:
    if config.worker is not None and config.adaseg is not None:
        raise ValueError("give either adaseg= or worker=, not both")
    if config.worker is not None:
        if config.backend != "reference":
            # backend only parameterizes the AdaSEGWorker this config would
            # build; a custom worker brings its own — don't ignore it silently
            raise ValueError(
                "backend= has no effect on an explicit worker=; set the "
                "backend on the worker itself (e.g. AdaSEGWorker(cfg, "
                "backend=...))"
            )
        return config.worker
    if config.adaseg is not None:
        return AdaSEGWorker(config.adaseg, backend=config.backend)
    raise ValueError("PSConfig needs adaseg= or worker=")


def _resolve_schedule(config: PSConfig) -> WorkerSchedule:
    if config.schedule is not None:
        return config.schedule
    if config.local_k is not None:
        return UniformSchedule(config.local_k)
    if config.adaseg is not None:
        return UniformSchedule(config.adaseg.k)
    raise ValueError(
        "a generic worker has no communication interval of its own — "
        "give PSConfig a schedule= or local_k="
    )


def _per_worker(mask, leaf):
    """Broadcast a (M,) mask over a worker-stacked leaf."""
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


# ---------------------------------------------------------------------------
# Compiled-chunk cache. One jitted chunk per (problem, worker, compressor,
# fleet, k_pad, eval, faults, codec, sampler) configuration, shared across
# every engine instance in the process — so building a second engine with
# the same config (benchmark loops, checkpoint-restore drills, the async
# engine's lockstep path) reuses the compiled program instead of retracing.
# jax.jit's own cache then keys on argument shapes, so a remainder chunk
# (checkpoint_every leaving rounds % every != 0) costs exactly one extra
# trace per distinct scan length, ever.
# ---------------------------------------------------------------------------

_CHUNK_CACHE: dict = {}
_TRACE_COUNT = 0


def _count_trace() -> None:
    # Called from inside the traced chunk body: jax executes the Python
    # body exactly once per trace (i.e. per compilation), so this global
    # counts compilations — the same signal jax.monitoring's
    # '/jax/core/compile' events carry, without requiring a listener hook.
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def serial_chunk_traces() -> int:
    """Process-wide count of serial round-chunk tracings (≈ compilations).
    Regression tests read deltas of this to pin that remainder chunks and
    same-config engines do not retrigger compilation."""
    return _TRACE_COUNT


def _hashable(x):
    try:
        hash(x)
        return x
    except TypeError:
        return id(x)


def cached_chunk(key: tuple, builder, *, donate: bool = True):
    """Memoize ``jax.jit(builder(), donate_argnums=(0, 1))`` on ``key``.

    Unhashable key components fall back to ``id()``; the cache entry keeps
    a strong reference to the raw key objects so an id is never recycled
    while its entry is alive."""
    k = tuple(_hashable(x) for x in key) + (donate,)
    hit = _CHUNK_CACHE.get(k)
    if hit is not None:
        return hit[0]
    fn = jax.jit(builder(), donate_argnums=(0, 1) if donate else ())
    _CHUNK_CACHE[k] = (fn, key)
    return fn


def make_sync_stacked(worker: LocalWorker, compressor: SyncCompressor,
                      num_workers: int, codec_backend: str = "reference",
                      robust: RobustPipeline | None = None,
                      server: ServerOptimizer | None = None):
    """Line 5–8 on the stacked worker axis: compress(w·payload) per worker,
    server sum, broadcast to survivors. The returned function takes
    ``(state, ef, alive_r, c_rng)``; ``alive_r is None`` means the fault
    policy statically guarantees everyone is up — that path emits the *same
    expressions* as the one-shot drivers' syncs, so identity/no-fault
    rounds stay bit-exact with them (dynamic all-True masks would still
    perturb XLA fusion).

    ``codec_backend="fused"`` swaps the message-scale / EF add / codec /
    residual tree pipeline and the weighted-sum-broadcast server side for
    the fused Pallas sweeps of ``kernels.sync_compress`` (identity and
    top-k stay bit-exact with this reference path; stochastic quantize
    agrees to float tolerance under the shared threefry derivation).

    Module-level so the event-driven engine can build the *identical*
    program: bit-parity between the engines is shared code, not a
    maintained coincidence.

    ``robust`` (a resolved :class:`RobustPipeline`) swaps in the hostile-
    fleet round and changes the signature to ``(state, ef, alive_r, c_rng,
    byz_r)`` — ``byz_r`` the (M,) attacked-lane mask. The wire format
    becomes *unweighted* (the async engine's native one): attacks and the
    DP transform corrupt/privatize the raw z̃ payload after local compute,
    the codec compresses that, and Line-7 weights + the robust aggregation
    happen server-side in ``sync_merge_stacked(agg=...)`` — order
    statistics must rank workers' iterates, not their weighted messages.
    Attack/DP keys fold constants 13/11 off the per-worker codec keys, so
    both engines (and resumes) corrupt identically.

    ``server`` (a *resolved* :class:`~repro.ps.server_opt.ServerOptimizer`,
    i.e. never ``NoServerOpt``) inserts the outer-optimizer step between
    the (robust) merge and delivery: the merge runs ungated, its row-0
    mean becomes the pseudo-gradient Δ against the server anchor, and the
    *post-step* anchor is what survivors receive — recv gating moves from
    the merge to the broadcast, which is semantics-preserving because recv
    only ever gated delivery, never the mean. The closures then take a
    trailing ``srv = (z, moments, t)`` carry and return
    ``(state, ef_new, srv_new, telem)`` with ``telem = [eff_lr, ‖Δ‖]``.
    ``server=None`` compiles the byte-identical historical closures.
    """
    comp = compressor
    m = num_workers

    if server is not None:
        from ..kernels.sync_compress.ops import server_outer_apply

        srv_spec = server.spec
        srv_kernel = codec_backend == "fused"

        def outer_broadcast(state, merged, recv, payload, srv):
            """Row-0 of the ungated merge → outer step → gated delivery."""
            z, mom, t = srv
            merged_row = jax.tree.map(lambda v: v[:1], merged)
            z_new, mom_new, t_new, eff_lr, dn = server_outer_apply(
                merged_row, z, mom, t, spec=srv_spec,
                use_kernel=srv_kernel,
            )
            if recv is None:
                synced = jax.tree.map(
                    lambda v, old: jnp.broadcast_to(v, old.shape),
                    z_new, payload,
                )
            else:
                synced = jax.tree.map(
                    lambda v, old: jnp.where(
                        _per_worker(recv, old),
                        jnp.broadcast_to(v, old.shape), old,
                    ),
                    z_new, payload,
                )
            telem = jnp.stack([eff_lr, dn])
            return (worker.merge_synced(state, synced),
                    (z_new, mom_new, t_new), telem)
    if robust is not None:
        from ..kernels.sync_compress.ops import (
            codec_uplink_stacked,
            sync_merge_stacked,
        )

        use_kernel = codec_backend == "fused"

        @jax.named_scope("sync-robust")
        def sync_stacked_robust(state, ef, alive_r, c_rng, byz_r,
                                srv=None):
            sw = jax.vmap(worker.sync_weight)(state)          # (M,)
            if alive_r is None:
                w_raw = sw
                recv = None
            else:
                w_raw = jnp.where(alive_r, sw, jnp.zeros_like(sw))
                any_alive = jnp.sum(w_raw) > 0.0
                recv = jnp.logical_and(alive_r, any_alive)
            payload = worker.sync_payload(state)
            c_rngs = jax.random.split(c_rng, m)
            uplink = payload
            if robust.byzantine is not None:
                a_rngs = jax.vmap(
                    lambda k: jax.random.fold_in(k, 13)
                )(c_rngs)
                uplink = robust.byzantine.apply(uplink, byz_r, a_rngs)
            if robust.dp is not None:
                d_rngs = jax.vmap(
                    lambda k: jax.random.fold_in(k, 11)
                )(c_rngs)
                uplink = robust.dp.apply(uplink, d_rngs)
            if comp.is_identity:
                sent, ef_new = uplink, ef
            else:
                sent, ef_new = codec_uplink_stacked(
                    uplink, c_rngs, w=None,
                    ef=ef if comp.error_feedback else None,
                    alive=alive_r, codec=comp.codec_spec,
                    use_kernel=use_kernel,
                )
                if not comp.error_feedback:
                    ef_new = ef
            if server is not None:
                # ungated robust merge → outer step → gated delivery
                merged = sync_merge_stacked(
                    sent, w=w_raw, normalize=True, agg=robust.agg,
                    use_kernel=use_kernel,
                )
                state, srv_new, telem = outer_broadcast(
                    state, merged, recv, payload, srv
                )
                return state, ef_new, srv_new, telem
            synced = sync_merge_stacked(
                sent, w=w_raw, recv=recv,
                old=None if recv is None else payload,
                normalize=True, agg=robust.agg, use_kernel=use_kernel,
            )
            return worker.merge_synced(state, synced), ef_new

        return sync_stacked_robust

    if codec_backend == "fused":
        from ..kernels.sync_compress.ops import (
            codec_uplink_stacked,
            sync_merge_stacked,
        )

        @jax.named_scope("sync")
        def sync_stacked_fused(state, ef, alive_r, c_rng, srv=None):
            sw = jax.vmap(worker.sync_weight)(state)          # (M,)
            if alive_r is None:
                recv = None
                w = sw / jnp.sum(sw)
            else:
                w_raw = jnp.where(alive_r, sw, jnp.zeros_like(sw))
                denom = jnp.sum(w_raw)
                any_alive = denom > 0.0
                w = w_raw / jnp.where(any_alive, denom, 1.0)
                recv = jnp.logical_and(alive_r, any_alive)
            payload = worker.sync_payload(state)
            if comp.is_identity:
                if server is not None:
                    merged = sync_merge_stacked(payload, w)
                    state, srv_new, telem = outer_broadcast(
                        state, merged, recv, payload, srv
                    )
                    return state, ef, srv_new, telem
                # one fused sweep: w-scale + server sum + broadcast
                synced = sync_merge_stacked(payload, w, recv=recv,
                                            old=None if recv is None
                                            else payload)
                return worker.merge_synced(state, synced), ef
            c_rngs = jax.random.split(c_rng, m)
            sent, ef_new = codec_uplink_stacked(
                payload, c_rngs, w=w,
                ef=ef if comp.error_feedback else None,
                alive=alive_r, codec=comp.codec_spec,
            )
            if not comp.error_feedback:
                ef_new = ef
            if server is not None:
                merged = sync_merge_stacked(sent)
                state, srv_new, telem = outer_broadcast(
                    state, merged, recv, payload, srv
                )
                return state, ef_new, srv_new, telem
            synced = sync_merge_stacked(sent, recv=recv,
                                        old=None if recv is None
                                        else payload)
            return worker.merge_synced(state, synced), ef_new

        return sync_stacked_fused

    @jax.named_scope("sync")
    def sync_stacked(state, ef, alive_r, c_rng, srv=None):
        sw = jax.vmap(worker.sync_weight)(state)              # (M,)
        if alive_r is None:
            any_alive = None
            w = sw / jnp.sum(sw)
        else:
            w_raw = jnp.where(alive_r, sw, jnp.zeros_like(sw))
            denom = jnp.sum(w_raw)
            any_alive = denom > 0.0
            w = w_raw / jnp.where(any_alive, denom, 1.0)

        payload = worker.sync_payload(state)
        messages = jax.tree.map(
            lambda leaf: _per_worker(w, leaf).astype(leaf.dtype) * leaf,
            payload,
        )
        if comp.is_identity:
            sent, ef_new = messages, ef
        elif alive_r is None:
            c_rngs = jax.random.split(c_rng, m)
            eff = tree_add(messages, ef) if comp.error_feedback else messages
            sent = jax.vmap(comp.compress)(eff, c_rngs)
            ef_new = tree_sub(eff, sent) if comp.error_feedback else ef
        else:
            c_rngs = jax.random.split(c_rng, m)
            eff = tree_add(messages, ef) if comp.error_feedback else messages
            sent = jax.vmap(comp.compress)(eff, c_rngs)
            # dead workers send nothing and keep their error memory frozen
            sent = jax.tree.map(
                lambda s: jnp.where(_per_worker(alive_r, s), s, 0.0), sent
            )
            if comp.error_feedback:
                ef_new = jax.tree.map(
                    lambda e_new, e_old: jnp.where(
                        _per_worker(alive_r, e_new), e_new, e_old
                    ),
                    tree_sub(eff, sent), ef,
                )
            else:
                ef_new = ef

        if server is not None:
            merged = jax.tree.map(
                lambda s: jnp.sum(s, axis=0, keepdims=True), sent
            )
            recv = (None if alive_r is None
                    else jnp.logical_and(alive_r, any_alive))
            state, srv_new, telem = outer_broadcast(
                state, merged, recv, payload, srv
            )
            return state, ef_new, srv_new, telem
        if alive_r is None:
            synced = jax.tree.map(
                lambda s: jnp.broadcast_to(
                    jnp.sum(s, axis=0, keepdims=True), s.shape
                ),
                sent,
            )
        else:
            recv = jnp.logical_and(alive_r, any_alive)        # (M,)
            synced = jax.tree.map(
                lambda s, old: jnp.where(
                    _per_worker(recv, old),
                    jnp.broadcast_to(
                        jnp.sum(s, axis=0, keepdims=True), old.shape
                    ),
                    old,
                ),
                sent, payload,
            )
        return worker.merge_synced(state, synced), ef_new

    return sync_stacked


def make_serial_chunk(
    problem: MinimaxProblem,
    worker: LocalWorker,
    compressor: SyncCompressor,
    num_workers: int,
    k_pad: int,
    eval_fn,
    no_faults: bool,
    codec_backend: str = "reference",
    robust: RobustPipeline | None = None,
    server: ServerOptimizer | None = None,
):
    """Build the serial-path round chunk: scan of (sync → K_m^r masked local
    steps) over a leading rounds axis. ``PSEngine`` jits this as its whole
    execution path; ``AsyncPSEngine`` jits the identical program and feeds
    it one-round slices whenever an admission batch is full-fleet lockstep,
    which is what makes the synchronous engine a *bit-exact special case*
    of the event-driven one (the chunking-invariance test pins that a
    1-round slice equals the full scan).

    With a :class:`RobustPipeline` the chunk signature gains a ``byz``
    ``(C, M)`` attacked-lane table between ``alive`` and ``counts_cum``.

    Returns ``(state, ef, eta_stats, ress)`` where ``eta_stats`` is
    ``(C, 3)`` per-round ``[min, max, mean]`` over the fleet — the
    telemetry reduction happens on device so the per-chunk device→host
    transfer is O(rounds), not O(rounds × fleet).

    A resolved ``server`` outer optimizer threads its ``(z, moments, t)``
    state through the scan carry: the chunk takes it as a trailing ``srv``
    argument (after ``counts_cum``, so the donated state/EF positions are
    untouched) and the return grows to ``(state, ef, eta_stats, ress,
    srv, outer)`` with ``outer`` the per-round ``(C, 2)``
    ``[eff_lr, ‖Δ‖]`` telemetry. ``server=None`` builds the historical
    chunk, signature and jaxpr unchanged."""
    m = num_workers
    sync_stacked = make_sync_stacked(worker, compressor, m, codec_backend,
                                     robust, server)

    vstep = jax.vmap(
        lambda st, rr, en: worker.step(problem, st, rr, enabled=en)
    )
    veta = jax.vmap(worker.eta)

    def round_body(carry, inputs):
        if server is not None:
            state, ef, srv = carry
        else:
            state, ef = carry
            srv = None
        telem = None
        if robust is not None:
            rng_round, ks_r, alive_r, byz_r, counts_r = inputs
            sync_args = (state, ef, None if no_faults else alive_r,
                         jax.random.fold_in(rng_round, 7), byz_r)
        else:
            rng_round, ks_r, alive_r, counts_r = inputs
            sync_args = (state, ef, None if no_faults else alive_r,
                         jax.random.fold_in(rng_round, 7))
        if server is not None:
            state, ef, srv, telem = sync_stacked(*sync_args, srv)
        else:
            state, ef = sync_stacked(*sync_args)

        # Line 3–4: K_m^r masked local steps.
        step_rngs = jax.random.split(rng_round, k_pad * m).reshape(
            k_pad, m, 2
        )

        def body(st, inp):
            rngs, i = inp
            enabled = i < ks_r
            if not no_faults:
                enabled = jnp.logical_and(enabled, alive_r)
            st = vstep(st, rngs, enabled)
            return st, None

        with jax.named_scope("local-compute"):
            state, _ = lax.scan(
                body, state, (step_rngs, jnp.arange(k_pad))
            )

        eta_end = veta(state)                             # (M,)
        eta_stats = jnp.stack([
            jnp.min(eta_end), jnp.max(eta_end), jnp.mean(eta_end)
        ])
        with jax.named_scope("eval"):
            if eval_fn is None:
                res = jnp.float32(jnp.nan)
            else:
                counts = jnp.where(
                    jnp.sum(counts_r) > 0.0, counts_r,
                    jnp.ones_like(counts_r),
                )
                res = jnp.asarray(
                    eval_fn(weighted_worker_average(
                        worker.output(state), counts
                    )),
                    dtype=jnp.float32,
                )
        if server is not None:
            return (state, ef, srv), (eta_stats, res, telem)
        return (state, ef), (eta_stats, res)

    if server is not None:
        if robust is not None:
            def chunk(state, ef, round_rngs, ks, alive, byz, counts_cum,
                      srv):
                _count_trace()
                (state, ef, srv), (eta_stats, ress, outer) = lax.scan(
                    round_body, (state, ef, srv),
                    (round_rngs, ks, alive, byz, counts_cum),
                )
                return state, ef, eta_stats, ress, srv, outer
        else:
            def chunk(state, ef, round_rngs, ks, alive, counts_cum, srv):
                _count_trace()
                (state, ef, srv), (eta_stats, ress, outer) = lax.scan(
                    round_body, (state, ef, srv),
                    (round_rngs, ks, alive, counts_cum),
                )
                return state, ef, eta_stats, ress, srv, outer
    elif robust is not None:
        def chunk(state, ef, round_rngs, ks, alive, byz, counts_cum):
            _count_trace()
            (state, ef), (eta_stats, ress) = lax.scan(
                round_body, (state, ef),
                (round_rngs, ks, alive, byz, counts_cum),
            )
            return state, ef, eta_stats, ress
    else:
        def chunk(state, ef, round_rngs, ks, alive, counts_cum):
            _count_trace()
            (state, ef), (eta_stats, ress) = lax.scan(
                round_body, (state, ef), (round_rngs, ks, alive, counts_cum)
            )
            return state, ef, eta_stats, ress

    return chunk


def make_sampled_chunk(
    problem: MinimaxProblem,
    worker: LocalWorker,
    compressor: SyncCompressor,
    fleet: int,
    sample: int,
    k_pad: int,
    eval_fn,
    no_faults: bool,
    codec_backend: str = "reference",
    robust: RobustPipeline | None = None,
    server: ServerOptimizer | None = None,
):
    """Sampled-client round chunk (partial participation). The fleet store
    stays ``(N, ...)`` in the scan carry; each round gathers the
    M = ``sample`` drawn workers' rows — optimizer state *and* persistent
    error-feedback residuals — runs the usual sync + K masked local steps
    on the compact ``(M, ...)`` stack, then scatters the rows back. Workers
    not drawn this round keep their η accumulators and EF memory frozen in
    the store, exactly as if the round never reached them. The sampled
    lanes compose with schedules/faults/compression unchanged: ``ks_r`` /
    ``alive_r`` inputs are the fleet tables gathered onto the drawn lanes.

    Same return convention as :func:`make_serial_chunk`; ``eta_stats`` is
    reduced over the *sampled* lanes, and ``counts_cum`` rows are fleet-
    shaped ``(N,)`` so the in-chunk residual evaluates the true Line-14
    z̄ over everyone who has ever participated. A :class:`RobustPipeline`
    adds a ``byz`` ``(C, S)`` lane table (gathered onto the drawn lanes)
    between ``alive`` and ``counts_cum``, like the serial chunk.

    A resolved ``server`` outer optimizer carries ONE global ``srv``
    through the scan (trailing chunk argument, like the serial chunk): the
    outer step sees the merge of the drawn lanes, and only those lanes
    receive the post-step anchor — undrawn workers keep their stale one,
    exactly as the round never reached them."""
    del fleet  # shapes are carried by the arrays; kept for cache keying
    m = sample
    sync_stacked = make_sync_stacked(worker, compressor, m, codec_backend,
                                     robust, server)
    vstep = jax.vmap(
        lambda st, rr, en: worker.step(problem, st, rr, enabled=en)
    )
    veta = jax.vmap(worker.eta)
    has_ef = compressor.error_feedback

    def round_body(carry, inputs):
        if server is not None:
            state, ef, srv = carry
        else:
            state, ef = carry
            srv = None
        telem = None
        if robust is not None:
            idx_r, rng_round, ks_r, alive_r, byz_r, counts_r = inputs
        else:
            idx_r, rng_round, ks_r, alive_r, counts_r = inputs

        with jax.named_scope("gather-sampled"):
            sub = jax.tree.map(lambda v: v[idx_r], state)
            sub_ef = jax.tree.map(lambda v: v[idx_r], ef) if has_ef else ef

        if robust is not None:
            sync_args = (sub, sub_ef, None if no_faults else alive_r,
                         jax.random.fold_in(rng_round, 7), byz_r)
        else:
            sync_args = (sub, sub_ef, None if no_faults else alive_r,
                         jax.random.fold_in(rng_round, 7))
        if server is not None:
            sub, sub_ef, srv, telem = sync_stacked(*sync_args, srv)
        else:
            sub, sub_ef = sync_stacked(*sync_args)

        step_rngs = jax.random.split(rng_round, k_pad * m).reshape(
            k_pad, m, 2
        )

        def body(st, inp):
            rngs, i = inp
            enabled = i < ks_r
            if not no_faults:
                enabled = jnp.logical_and(enabled, alive_r)
            return vstep(st, rngs, enabled), None

        with jax.named_scope("local-compute"):
            sub, _ = lax.scan(body, sub, (step_rngs, jnp.arange(k_pad)))

        with jax.named_scope("scatter-sampled"):
            # draws are without replacement, so idx_r rows are unique and
            # the scatter is well-defined
            state = jax.tree.map(
                lambda v, s: v.at[idx_r].set(s), state, sub
            )
            if has_ef:
                ef = jax.tree.map(
                    lambda v, s: v.at[idx_r].set(s), ef, sub_ef
                )

        eta_end = veta(sub)                               # (M,) lanes
        eta_stats = jnp.stack([
            jnp.min(eta_end), jnp.max(eta_end), jnp.mean(eta_end)
        ])
        with jax.named_scope("eval"):
            if eval_fn is None:
                res = jnp.float32(jnp.nan)
            else:
                counts = jnp.where(
                    jnp.sum(counts_r) > 0.0, counts_r,
                    jnp.ones_like(counts_r),
                )
                res = jnp.asarray(
                    eval_fn(weighted_worker_average(
                        worker.output(state), counts
                    )),
                    dtype=jnp.float32,
                )
        if server is not None:
            return (state, ef, srv), (eta_stats, res, telem)
        return (state, ef), (eta_stats, res)

    if server is not None:
        if robust is not None:
            def chunk(state, ef, idx, round_rngs, ks, alive, byz,
                      counts_cum, srv):
                _count_trace()
                (state, ef, srv), (eta_stats, ress, outer) = lax.scan(
                    round_body, (state, ef, srv),
                    (idx, round_rngs, ks, alive, byz, counts_cum),
                )
                return state, ef, eta_stats, ress, srv, outer
        else:
            def chunk(state, ef, idx, round_rngs, ks, alive, counts_cum,
                      srv):
                _count_trace()
                (state, ef, srv), (eta_stats, ress, outer) = lax.scan(
                    round_body, (state, ef, srv),
                    (idx, round_rngs, ks, alive, counts_cum),
                )
                return state, ef, eta_stats, ress, srv, outer
    elif robust is not None:
        def chunk(state, ef, idx, round_rngs, ks, alive, byz, counts_cum):
            _count_trace()
            (state, ef), (eta_stats, ress) = lax.scan(
                round_body, (state, ef),
                (idx, round_rngs, ks, alive, byz, counts_cum),
            )
            return state, ef, eta_stats, ress
    else:
        def chunk(state, ef, idx, round_rngs, ks, alive, counts_cum):
            _count_trace()
            (state, ef), (eta_stats, ress) = lax.scan(
                round_body, (state, ef),
                (idx, round_rngs, ks, alive, counts_cum),
            )
            return state, ef, eta_stats, ress

    return chunk


class PSEngine:
    """Configurable Parameter-Server runtime, generic over LocalWorker.

    Examples
    --------
    Two workers, two rounds of K=2 local steps on the bilinear game, with
    per-round telemetry:

    >>> import jax
    >>> from repro.core import AdaSEGConfig
    >>> from repro.problems import make_bilinear_game
    >>> game = make_bilinear_game(jax.random.PRNGKey(0), n=4, sigma=0.1)
    >>> cfg = PSConfig(adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=2),
    ...                num_workers=2, rounds=2)
    >>> eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(1),
    ...                eval_fn=game.residual)
    >>> zbar = eng.run()                  # z̄ = (x̄, ȳ), Line 14
    >>> [v.shape for v in jax.tree.leaves(zbar)], eng.round
    ([(4,), (4,)], 2)
    >>> len(eng.trace.rounds), eng.trace.rounds[-1].residual is not None
    (2, True)
    """

    def __init__(
        self,
        problem: MinimaxProblem,
        config: PSConfig,
        rng,
        *,
        mesh=None,
        worker_axes: tuple[str, ...] = ("data",),
        eval_fn: Callable[[PyTree], jax.Array] | None = None,
        trace_meta: dict | None = None,
        tracer: SpanTracer | None = None,
        metrics: MetricsRegistry | None = None,
    ):
        self.problem = problem
        self.config = config
        # Observability is host-side only (spans/metrics never enter a jit),
        # so the default-enabled tracer cannot perturb the numerics — the
        # inertness pins in tests/test_obs.py run with it on.
        self.tracer = tracer if tracer is not None else SpanTracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.worker = _resolve_worker(config)
        self.schedule = _resolve_schedule(config)
        self.compressor = config.compressor or IdentityCompressor()
        self.faults = config.faults or NoFaults()
        check_codec_backend(config.codec_backend, self.compressor)
        self.codec_backend = config.codec_backend
        self.eval_fn = eval_fn
        self._mesh = mesh
        self._worker_axes = tuple(worker_axes)
        if mesh is not None:
            import math

            m_mesh = math.prod(mesh.shape[a] for a in self._worker_axes)
            if m_mesh != config.num_workers:
                raise ValueError(
                    f"mesh worker axes give {m_mesh} workers, "
                    f"config.num_workers={config.num_workers}"
                )

        m, r = config.num_workers, config.rounds
        # Deterministic policy tables — re-derived (never stored) on resume.
        self._ks = np.asarray(
            self.schedule.steps(m, r), dtype=np.int32
        )                                                     # (R, M)
        self._alive = np.asarray(self.faults.alive(m, r), dtype=bool)
        if self._ks.shape != (r, m) or self._alive.shape != (r, m):
            raise ValueError("schedule/fault table shape mismatch")
        self._k_pad = int(self.schedule.max_steps(m))
        if not (self._ks <= self._k_pad).all():
            # the per-round scan runs max_steps iterations — larger entries
            # would silently truncate local work while still being counted
            raise ValueError(
                f"schedule emits step counts above its max_steps={self._k_pad}"
            )
        self._eff_steps = np.where(self._alive, self._ks, 0)  # (R, M)
        self._counts_cum = np.cumsum(
            self._eff_steps, axis=0
        ).astype(np.float32)

        # Sampled-client rounds: gather the fleet policy tables onto the
        # M drawn lanes per round; effective step counts scatter back to
        # fleet shape so z̄ / counts_cum stay Line-14 over the whole fleet.
        self.sampler = config.sampler
        if self.sampler is not None:
            if mesh is not None:
                raise NotImplementedError(
                    "sampled-client rounds run on the serial path only "
                    "(mesh=None)"
                )
            self._draws = self.sampler.draws(m, r)            # (R, S)
            self._ks_lane = np.take_along_axis(
                self._ks, self._draws, axis=1
            )
            self._alive_lane = np.take_along_axis(
                self._alive, self._draws, axis=1
            )
            self._eff_lane = np.where(
                self._alive_lane, self._ks_lane, 0
            )                                                 # (R, S)
            eff_fleet = np.zeros((r, m), dtype=self._eff_lane.dtype)
            np.put_along_axis(
                eff_fleet, self._draws, self._eff_lane, axis=1
            )
            self._eff_steps = eff_fleet                       # (R, N)
            self._counts_cum = np.cumsum(
                eff_fleet, axis=0
            ).astype(np.float32)
        else:
            self._draws = None

        # Hostile-fleet subsystem: resolve the attack/aggregator/DP config
        # at the compiled lane width (the sampled width under a sampler).
        lanes = self.sampler.sample if self.sampler is not None else m
        self.aggregator = config.aggregator or WeightedMean()
        self.byzantine = config.byzantine
        self.dp = config.dp
        self._robust = resolve_robust(config, lanes)
        if self._robust is not None and mesh is not None:
            raise NotImplementedError(
                "the hostile-fleet subsystem (byzantine/aggregator/dp) "
                "runs on the serial path only — robust aggregation needs "
                "full cross-worker order statistics, not a psum (mesh=None)"
            )
        if self.byzantine is not None:
            self._byz = np.asarray(
                self.byzantine.attacked(m, r), dtype=bool
            )
            if self._byz.shape != (r, m):
                raise ValueError("byzantine table shape mismatch")
        else:
            self._byz = np.zeros((r, m), dtype=bool)
        self._byz_lane = (
            np.take_along_axis(self._byz, self._draws, axis=1)
            if self._draws is not None else None
        )

        # Server-side outer optimizer: resolve to None (the historical
        # Line-7 broadcast, identical compiled chunk) for None/NoServerOpt.
        self.server_opt = config.server_opt or NoServerOpt()
        self._server = resolve_server_opt(config)
        if self._server is not None and mesh is not None:
            raise NotImplementedError(
                "the server-side outer optimizer runs on the serial path "
                "only — the outer step needs the gathered server merge, "
                "not a per-shard psum (mesh=None)"
            )

        # RNG derivation — each worker family keeps its historical stream
        # (AdaSEG: run_local_adaseg's; the zoo: run_local's), so the engine
        # reproduces the pre-engine drivers bit-exactly.
        rng0, worker_rngs = self.worker.derive_rngs(jnp.asarray(rng), m)
        self._rng0 = np.asarray(rng0)
        self._round_rngs = jax.random.split(rng0, r)          # (R, 2)

        def init_state(rngs):
            state = jax.vmap(lambda rr, w: self.worker.init(problem, rr, w))(
                rngs, jnp.arange(m, dtype=jnp.int32))
            ef = (tree_zeros_like(self.worker.sync_payload(state))
                  if self.compressor.error_feedback else ())
            return state, ef

        if mesh is None:
            self._state, self._ef = init_state(worker_rngs)
        else:
            # Each worker's state is made on its own device: made on one
            # device first, all M workers' state would have to fit there.
            from jax.sharding import NamedSharding, PartitionSpec as P

            axes = self._worker_axes
            on_workers = NamedSharding(
                mesh, P(axes if len(axes) > 1 else axes[0]))
            self._state, self._ef = jax.jit(
                init_state, out_shardings=on_workers)(worker_rngs)
        self.round = 0

        # Outer-optimizer state (z_server, moment trees, round count). The
        # anchor starts at the fleet mean of the initial payloads, so the
        # first round's pseudo-gradient measures the fleet's movement, not
        # an arbitrary worker's init.
        if self._server is not None:
            z0 = jax.tree.map(
                lambda v: jnp.mean(v, axis=0, keepdims=True),
                self.worker.sync_payload(self._state),
            )
            self._srv = (z0, self._server.init_moments(z0), jnp.int32(0))
        else:
            self._srv = None

        z_like = jax.tree.map(
            lambda v: v[0], self.worker.sync_payload(self._state)
        )
        self._msg_bytes = self.compressor.message_bytes(z_like)
        self._dense_bytes = dense_bytes(z_like)
        self.trace = TraceRecorder(meta={
            "problem": problem.name,
            "optimizer": self.worker.name,
            "workers": m,
            "rounds": r,
            "schedule": type(self.schedule).__name__,
            "compressor": self.compressor.name,
            "faults": type(self.faults).__name__,
            # the worker's actual step backend (None for workers without one)
            "backend": getattr(self.worker, "backend", None),
            "codec_backend": self.codec_backend,
            "execution": "sharded" if mesh is not None else "serial",
            **({"sampler": self.sampler.name,
                "sample": self.sampler.sample}
               if self.sampler is not None else {}),
            **({"byzantine": self.byzantine.name}
               if self.byzantine is not None else {}),
            **({"aggregator": self.aggregator.name,
                "dp": None if self.dp is None else self.dp.name}
               if self._robust is not None else {}),
            **({"server_opt": self.server_opt.name}
               if self._server is not None else {}),
            **(trace_meta or {}),
        })

        # Static: a NoFaults policy lets the chunk builders skip the
        # aliveness masking entirely, keeping identity/no-fault rounds
        # bit-exact with the one-shot drivers.
        self._no_faults = isinstance(self.faults, NoFaults)

        if mesh is None:
            # Process-wide compiled-chunk cache + buffer donation: the
            # stacked state/EF inputs are dead after each call (the engine
            # rebinds them to the outputs), so XLA may update in place.
            if self.sampler is not None:
                key = ("sampled", self.problem, self.worker,
                       self.compressor, m, self.sampler.sample,
                       self._k_pad, self.eval_fn, self._no_faults,
                       self.codec_backend, self._robust, self._server)
                self._chunk_fn = cached_chunk(
                    key, self._make_sampled_chunk
                )
            else:
                key = ("serial", self.problem, self.worker,
                       self.compressor, m, self._k_pad, self.eval_fn,
                       self._no_faults, self.codec_backend, self._robust,
                       self._server)
                self._chunk_fn = cached_chunk(
                    key, self._make_serial_chunk
                )
        else:
            # NOT jit-wrapped here: the sharded chunk derives its rng tables
            # eagerly and jits only the shard_map body — with the default
            # non-partitionable threefry, deriving keys inside the jit that
            # feeds a shard_map re-shards the key computation itself and
            # silently changes the stream (same reason the one-shot sharded
            # driver precomputes its step rngs on the host).
            self._chunk_fn = self._make_sharded_chunk()

    # ------------------------------------------------------------------
    # Round-loop bodies
    # ------------------------------------------------------------------

    def _make_serial_chunk(self):
        return make_serial_chunk(
            self.problem, self.worker, self.compressor,
            self.config.num_workers, self._k_pad, self.eval_fn,
            self._no_faults, self.codec_backend, self._robust,
            self._server,
        )

    def _make_sampled_chunk(self):
        return make_sampled_chunk(
            self.problem, self.worker, self.compressor,
            self.config.num_workers, self.sampler.sample, self._k_pad,
            self.eval_fn, self._no_faults, self.codec_backend,
            self._robust, self._server,
        )

    def _make_sharded_chunk(self):
        from jax.sharding import PartitionSpec as P

        problem, worker = self.problem, self.worker
        comp = self.compressor
        codec_backend = self.codec_backend
        m, k_pad = self.config.num_workers, self._k_pad
        axes = self._worker_axes
        lead = axes if len(axes) > 1 else axes[0]

        def shard_fn(state_s, ef_s, s_rngs, c_rngs, ks_m, alive_m):
            # Per-shard shapes: state leaves (1, ...), s_rngs (1, C, K, 2),
            # c_rngs (1, C, 2), ks_m/alive_m (1, C).
            st0 = jax.tree.map(lambda v: v[0], state_s)
            ef0 = jax.tree.map(lambda v: v[0], ef_s)

            no_faults = self._no_faults

            def round_body(carry, inputs):
                st, ef = carry
                rngs_round, c_rng, k_m, al = inputs

                # Line 5–8 as one all-reduce of the compressed message.
                sw = worker.sync_weight(st)
                if no_faults:
                    # same expressions as core.adaseg.make_psum_sync
                    any_alive = None
                    w = sw / lax.psum(sw, axes)
                else:
                    w_raw = jnp.where(al, sw, 0.0)
                    denom = lax.psum(w_raw, axes)
                    any_alive = denom > 0.0
                    w = w_raw / jnp.where(any_alive, denom, 1.0)
                payload = worker.sync_payload(st)
                if comp.is_identity:
                    msg = jax.tree.map(
                        lambda v: w.astype(v.dtype) * v, payload
                    )
                    sent, ef_new = msg, ef
                elif codec_backend == "fused":
                    # fused uplink sweep: w scaling + EF add + codec +
                    # residual write-back, aliveness handled in-kernel
                    from ..kernels.sync_compress.ops import codec_uplink

                    sent, ef_new = codec_uplink(
                        payload, c_rng, w=w,
                        ef=ef if comp.error_feedback else None,
                        alive=None if no_faults else al,
                        codec=comp.codec_spec,
                    )
                    if not comp.error_feedback:
                        ef_new = ef
                else:
                    msg = jax.tree.map(
                        lambda v: w.astype(v.dtype) * v, payload
                    )
                    eff = tree_add(msg, ef) if comp.error_feedback else msg
                    sent = comp.compress(eff, c_rng)
                    if not no_faults:
                        sent = tree_where(al, sent, tree_zeros_like(sent))
                    ef_new = ef
                    if comp.error_feedback:
                        ef_new = tree_sub(eff, sent)
                        if not no_faults:
                            ef_new = tree_where(al, ef_new, ef)
                z_sum = jax.tree.map(lambda v: lax.psum(v, axes), sent)
                if no_faults:
                    st = worker.merge_synced(st, z_sum)
                else:
                    recv = jnp.logical_and(al, any_alive)
                    st = worker.merge_synced(
                        st, tree_where(recv, z_sum, payload)
                    )

                def body(s, inp):
                    rngs, i = inp
                    enabled = i < k_m
                    if not no_faults:
                        enabled = jnp.logical_and(enabled, al)
                    s = worker.step(problem, s, rngs, enabled=enabled)
                    return s, None

                st, _ = lax.scan(
                    body, st, (rngs_round, jnp.arange(k_pad))
                )
                return (st, ef_new), worker.eta(st)

            (st, ef), etas = lax.scan(
                round_body, (st0, ef0),
                (s_rngs[0], c_rngs[0], ks_m[0], alive_m[0]),
            )
            state_out = jax.tree.map(lambda v: v[None], st)
            ef_out = jax.tree.map(lambda v: v[None], ef)
            return state_out, ef_out, etas[:, None]           # (C, 1)

        spec_w = P(lead)
        fn = jax.shard_map(
            shard_fn,
            mesh=self._mesh,
            in_specs=(spec_w, spec_w, P(lead, None, None, None),
                      P(lead, None, None), P(lead, None), P(lead, None)),
            out_specs=(spec_w, spec_w, P(None, lead)),
            check_vma=False,
        )

        jfn = jax.jit(fn)

        def chunk(state, ef, round_rngs, ks, alive, counts_cum):
            del counts_cum  # sharded residuals are chunk-boundary only
            # Eager rng derivation (see __init__): keys must be materialized
            # before they cross the shard_map boundary.
            step_rngs = jax.vmap(
                lambda rr: jax.random.split(rr, k_pad * m).reshape(
                    k_pad, m, 2
                )
            )(round_rngs)                                     # (C, K, M, 2)
            step_rngs = jnp.transpose(step_rngs, (2, 0, 1, 3))  # (M, C, K, 2)
            c_rngs = jax.vmap(
                lambda rr: jax.random.split(jax.random.fold_in(rr, 7), m)
            )(round_rngs)                                     # (C, M, 2)
            c_rngs = jnp.transpose(c_rngs, (1, 0, 2))         # (M, C, 2)
            state, ef, etas = jfn(
                state, ef, step_rngs, c_rngs,
                jnp.asarray(ks).T, jnp.asarray(alive).T,
            )
            eta_stats = jnp.stack(
                [etas.min(axis=1), etas.max(axis=1), etas.mean(axis=1)],
                axis=1,
            )                                                 # (C, 3)
            ress = jnp.full((round_rngs.shape[0],), jnp.nan, jnp.float32)
            return state, ef, eta_stats, ress

        return chunk

    # ------------------------------------------------------------------
    # Driving, output, telemetry
    # ------------------------------------------------------------------

    def _chunk_args(self, r0: int, r1: int) -> list:
        sl = slice(r0, r1)
        if self._draws is not None:
            args = [
                self._state, self._ef,
                jnp.asarray(self._draws[sl]),
                self._round_rngs[sl],
                jnp.asarray(self._ks_lane[sl]),
                jnp.asarray(self._alive_lane[sl]),
            ]
            if self._robust is not None:
                args.append(jnp.asarray(self._byz_lane[sl]))
        else:
            args = [
                self._state, self._ef,
                self._round_rngs[sl],
                jnp.asarray(self._ks[sl]),
                jnp.asarray(self._alive[sl]),
            ]
            if self._robust is not None:
                args.append(jnp.asarray(self._byz[sl]))
        args.append(jnp.asarray(self._counts_cum[sl]))
        if self._server is not None:
            args.append(self._srv)
        return args

    def lower_rounds(self, rounds: int = 1):
        """The serial round program for the next ``rounds`` rounds, lowered
        and not run (``jax.stages.Lowered``): ``.compile()`` gives its
        ``memory_analysis()`` and HLO text before any device memory is
        spent on it. The sharded path jits only its ``shard_map`` body and
        has no such single program."""
        if self._mesh is not None:
            raise NotImplementedError("lower_rounds covers the serial path")
        r1 = min(self.round + rounds, self.config.rounds)
        return self._chunk_fn.lower(*self._chunk_args(self.round, r1))

    def _run_chunk(self, r0: int, r1: int) -> None:
        with self.tracer.span(f"chunk [{r0},{r1})", cat="chunk",
                              rounds=r1 - r0) as chunk_sp:
            args = self._chunk_args(r0, r1)
            if self._server is not None:
                (state, ef, etas, ress,
                 self._srv, outer) = self._chunk_fn(*args)
            else:
                state, ef, etas, ress = self._chunk_fn(*args)
                outer = None
            jax.block_until_ready(state)
        self._state, self._ef = state, ef
        self.round = r1

        # Attribute the chunk's wall-clock uniformly across its rounds
        # (dispatch is per-chunk; finer attribution would need per-round
        # host sync, which is exactly what the chunked scan avoids). The
        # timing source is the span layer, not an ad-hoc timer.
        wall = chunk_sp.wall_dur
        per_round_wall = wall / max(r1 - r0, 1)
        cost = modeled_sync_cost(
            getattr(self.compressor, "codec_spec", None),
            self._dense_bytes, workers=self.config.num_workers,
            backend=self.codec_backend,
        )
        # Bulk telemetry: the chunk already reduced η to per-round
        # [min, max, mean] on device, so this is one O(rounds) transfer —
        # never O(rounds × fleet) — regardless of fleet size.
        stats = np.asarray(etas)                              # (C, 3)
        ress = np.asarray(ress)
        outer = None if outer is None else np.asarray(outer)  # (C, 2)
        sampled = self._draws is not None
        for i, r in enumerate(range(r0, r1)):
            if sampled:
                alive = self._alive_lane[r]
                steps_row = self._eff_lane[r]
                sampled_workers = self._draws[r].tolist()
                byz_ids = (self._draws[r][self._byz_lane[r]].tolist()
                           if self.byzantine is not None else None)
            else:
                alive = self._alive[r]
                steps_row = self._eff_steps[r]
                sampled_workers = None
                byz_ids = (np.nonzero(self._byz[r])[0].tolist()
                           if self.byzantine is not None else None)
            n_alive = int(alive.sum())
            eff = int(steps_row.sum())
            res = float(ress[i])
            if np.isnan(res):
                res = None
            if (res is None and self.eval_fn is not None and r == r1 - 1):
                # sharded path: residual at the chunk boundary, host-side
                with self.tracer.span(f"eval r{r}", cat="eval", round=r):
                    res = float(self.eval_fn(self.z_bar()))
            rec = RoundRecord(
                round=r,
                local_steps=steps_row.tolist(),
                alive=alive.tolist(),
                bytes_up=n_alive * self._msg_bytes,
                bytes_down=n_alive * self._dense_bytes,
                eta_min=float(stats[i, 0]),
                eta_max=float(stats[i, 1]),
                eta_mean=float(stats[i, 2]),
                residual=res,
                wall_time_s=per_round_wall,
                steps_per_sec=eff / per_round_wall if per_round_wall > 0
                else None,
                sampled_workers=sampled_workers,
                byzantine_workers=byz_ids,
                outer_lr=None if outer is None else float(outer[i, 0]),
                delta_norm=None if outer is None else float(outer[i, 1]),
            )
            self.trace.record(rec)
            # Round span: the chunk's wall uniformly attributed, carrying
            # the full RoundRecord so TraceRecorder.from_spans can rebuild
            # the telemetry from the span layer alone. (vars(), not
            # dataclasses.asdict: the record is flat and asdict's deep copy
            # costs ~25µs — real money in the per-round hot path.)
            if self.tracer.enabled:
                self.tracer.add_span(
                    f"round {r}", cat="round", parent=chunk_sp.id,
                    wall_t0=chunk_sp.wall_t0 + i * per_round_wall,
                    wall_t1=chunk_sp.wall_t0 + (i + 1) * per_round_wall,
                    **vars(rec),
                )
            self.metrics.inc("bytes_up", rec.bytes_up, engine="sync")
            self.metrics.inc("bytes_down", rec.bytes_down, engine="sync")
            self.metrics.inc("local_steps", eff, engine="sync")
            self.metrics.set_gauge("eta_spread", rec.eta_spread,
                                   engine="sync")
            if self._server is not None:
                self.metrics.set_gauge(
                    "outer_delta_norm", rec.delta_norm, engine="sync",
                    server_opt=self.server_opt.name,
                )
            if self._robust is not None:
                self.metrics.inc("byzantine_workers",
                                 len(byz_ids or []), engine="sync")
                self.metrics.set_gauge(
                    "agg_reject_frac",
                    self.aggregator.reject_frac(
                        len(alive)), engine="sync",
                    aggregator=self.aggregator.name,
                )
            # measured round wall next to the traffic model's prediction
            self.metrics.observe(
                "round_wall_s", per_round_wall, engine="sync",
                codec=self.compressor.name, backend=self.codec_backend,
                modeled_hbm_passes=cost["hbm_passes"],
                modeled_hbm_s=cost["hbm_s"],
            )

    def run(
        self,
        *,
        until_round: int | None = None,
        checkpoint_path: str | None = None,
        checkpoint_every: int | None = None,
    ) -> PyTree:
        """Advance to ``until_round`` (default: all rounds) and return the
        global output iterate z̄ (Line 14). ``checkpoint_every`` chunks the
        round scan and writes ``checkpoint_path`` at each boundary."""
        target = self.config.rounds if until_round is None else int(until_round)
        target = min(target, self.config.rounds)
        with self.tracer.span(f"run [{self.round},{target})", cat="run",
                              engine="sync"):
            while self.round < target:
                r1 = (min(target, self.round + checkpoint_every)
                      if checkpoint_every else target)
                self._run_chunk(self.round, r1)
                if checkpoint_path is not None:
                    self.save(checkpoint_path)
        return self.z_bar()

    def step_round(self) -> None:
        """Advance exactly one round (smoke tests, interactive driving)."""
        if self.round >= self.config.rounds:
            raise ValueError("engine already ran all configured rounds")
        self._run_chunk(self.round, self.round + 1)

    @property
    def state(self) -> PyTree:
        return self._state

    def z_bar(self) -> PyTree:
        """Global output iterate: worker outputs weighted by realized step
        counts — the same expression as the serial drivers' Line 14."""
        counts = self._eff_steps[:max(self.round, 1)].sum(axis=0)
        counts = counts.astype(np.float32)
        if counts.sum() == 0.0:
            counts = np.ones_like(counts)
        z = weighted_worker_average(
            self.worker.output(self._state), jnp.asarray(counts)
        )
        if self._mesh is not None:
            # On one device: code that evaluates z̄ is jitted outside any
            # shard_map, where a Pallas kernel cannot span the mesh.
            z = jax.device_put(z, self._mesh.devices.flat[0])
        return z

    # ------------------------------------------------------------------
    # Checkpointing
    # ------------------------------------------------------------------

    def _ckpt_tree(self) -> dict:
        tree = {
            "worker_state": self._state,
            "ef": self._ef,
            "round": jnp.int32(self.round),
            "rng0": jnp.asarray(self._rng0),
            "worker_fp": jnp.uint32(self.worker.fingerprint),
        }
        if self.sampler is not None:
            # present only for sampled runs: a sampled checkpoint can never
            # be restored into a full-participation engine (or vice versa)
            # because the leaf structure itself differs
            tree["sampler_fp"] = jnp.uint32(self.sampler.fingerprint)
        if self._robust is not None:
            # present only for robust runs — the merge semantics (and the
            # threat model the EF memory accumulated under) must match
            tree["aggregator_fp"] = jnp.uint32(self.aggregator.fingerprint)
        if self._server is not None:
            # present only under an active outer optimizer, so the
            # historical (`none`) layout stays byte-identical
            z, mom, t = self._srv
            tree["server_opt"] = {"z": z, "mom": mom, "t": t}
            tree["server_opt_fp"] = jnp.uint32(self.server_opt.fingerprint)
        return tree

    def save(self, path: str) -> None:
        """Serialize engine state via checkpoint.serialize (msgpack)."""
        with self.tracer.span(f"checkpoint r{self.round}", cat="checkpoint",
                              round=self.round) as sp:
            sp.attrs["bytes"] = save_pytree(path, self._ckpt_tree())
            self.metrics.inc("checkpoint_bytes", sp.attrs["bytes"],
                             engine="sync")

    def restore(self, path: str) -> "PSEngine":
        """Resume mid-stream: policies and rng streams are re-derived from
        the config, so only the worker states, error-feedback memory and the
        round counter come from disk. Refuses checkpoints from a different
        seed (the round-rng stream would silently diverge) or a different
        optimizer (the state leaves would be reinterpreted)."""
        try:
            loaded = load_pytree(path, self._ckpt_tree())
        except ValueError as e:
            raise ValueError(
                "checkpoint does not match this engine's optimizer state "
                f"layout ({self.worker.name}): {e}"
            ) from e
        if int(np.asarray(loaded["worker_fp"])) != self.worker.fingerprint:
            raise ValueError(
                "checkpoint was written by a run with a different optimizer "
                f"(engine runs {self.worker.name})"
            )
        if not np.array_equal(
            np.asarray(loaded["rng0"]), np.asarray(self._rng0)
        ):
            raise ValueError(
                "checkpoint was written by a run with a different seed"
            )
        if self.sampler is not None and int(
            np.asarray(loaded["sampler_fp"])
        ) != self.sampler.fingerprint:
            raise ValueError(
                "checkpoint was written by a run with a different client "
                "sampler (the participation tables would diverge)"
            )
        if self._robust is not None and int(
            np.asarray(loaded["aggregator_fp"])
        ) != self.aggregator.fingerprint:
            raise ValueError(
                "checkpoint was written by a run with a different robust "
                "aggregator (the merge semantics would diverge)"
            )
        if self._server is not None:
            if int(
                np.asarray(loaded["server_opt_fp"])
            ) != self.server_opt.fingerprint:
                raise ValueError(
                    "checkpoint was written by a run with a different "
                    "server-side outer optimizer (engine runs "
                    f"{self.server_opt.name})"
                )
            so = loaded["server_opt"]
            self._srv = (so["z"], tuple(so["mom"]), so["t"])
        self._state = loaded["worker_state"]
        self._ef = loaded["ef"]
        self.round = int(loaded["round"])
        # drop telemetry from rounds past the restore point so a rewound
        # engine doesn't accumulate duplicate round records
        self.trace.rounds = [
            rec for rec in self.trace.rounds if rec.round < self.round
        ]
        return self
