"""Event-driven asynchronous Parameter-Server over simulated time.

The synchronous :class:`~repro.ps.engine.PSEngine` counts *rounds*: every
worker blocks on one barrier per round no matter how fast it ran. This
module adds the missing time axis. :class:`AsyncPSEngine` is a discrete-
event simulator of the same Parameter-Server fleet: a
:class:`~repro.ps.latency.LatencyModel` assigns every worker-round its
compute and network delays, an event queue advances a simulated clock, and
the server **admits each worker's uplink as it arrives** — no barrier —
under a configurable bounded-staleness rule:

* every worker cycles through ``send payload → receive broadcast → run its
  K_m^r local steps`` at its own speed (Line 3–8 of Algorithm 1, unrolled
  per worker instead of per barrier);
* the server keeps the **last heard** payload and 1/η sync weight of every
  worker; on each admission it recomputes the Line-7 weighted average over
  the whole table with staleness-aware re-weighting
  ``w_m ∝ sw_m / (1 + s_m)^γ`` (``s_m`` = how many rounds behind the
  freshest entry worker ``m``'s stored payload is) and broadcasts back *to
  the admitted workers only*;
* a round-``r`` uplink is admitted only once every live worker's round-
  ``(r − τ)`` uplink has landed (τ = ``staleness_bound``) — the stale-
  synchronous-parallel rule, gated on what the server has *heard*, not on
  what workers have started. ``τ=∞`` never blocks; ``τ=0`` is a true
  barrier, which makes the synchronous engine a special case *along the
  staleness axis* and gives the sync baseline its simulated-time cost
  under any latency model.

Parity anchor (pinned by ``tests/test_ps_async.py``): with worker-equal
:class:`~repro.ps.latency.ConstantLatency`, ``τ=∞`` (or ``τ=0``), identity
compression and no faults, the fleet moves in lockstep, every arrival lands
in one batch, and this engine reproduces ``PSEngine``'s serial path
**bit-exactly**. Two mechanisms make that structural rather than
approximate: local phases execute on the *full stacked worker state* with a
one-hot ``enabled`` mask (per-worker unbatched math has different matmul
accumulation order and is NOT bit-equal to the engine's vmapped steps), and
full-fleet lockstep admissions execute the synchronous engine's own
compiled round chunk (``engine.make_serial_chunk``) — shared code rather
than a parallel implementation, because even re-emitting the identical
expression sequence in a differently-shaped jit graph perturbs XLA fusion
at the last ulp.

Everything PR 2–3 built composes: schedules feed ``K_m^r``, compressors run
on the payload uplinks (error feedback per worker; the Line-7 weights are
applied server-side where the normalizer lives — the one place the async
wire format must differ from the sync engine's pre-weighted messages),
fault policies knock workers out of their own round ``r`` (no send, no
receive, no steps — a reboot that only costs time), and both
``AdaSEGWorker`` and ``MinimaxWorker`` run unmodified. Checkpoint/resume
serializes the *dynamic* state only — stacked worker state, server table,
per-worker event-machine arrays, the simulated clock — while schedules,
faults, latency tables and rng streams are re-derived from the config
seeds, so a killed simulation resumes bit-exactly mid-event-queue.

Execution is host-driven and serial by design: the simulator's product is
*simulated* time-to-accuracy, not wall-clock throughput — the sharded
``shard_map`` path remains the synchronous engine's domain.

One timeline nuance: local phases normally execute when they *complete* on
the simulated clock (so mid-run residuals only count finished work), but a
full-fleet lockstep admission runs the synchronous chunk eagerly — those
workers' states may then be up to one phase ahead of the clock until their
START events fire. Admission records are written before the chunk, and
resume replays the same decision, so telemetry and checkpoints stay
consistent either way.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..checkpoint.serialize import load_pytree, save_pytree
from ..core.adaseg import weighted_worker_average
from ..core.tree import tree_add, tree_sub, tree_zeros_like
from ..core.types import MinimaxProblem
from .compress import IdentityCompressor, check_codec_backend, dense_bytes
from .engine import (
    PSConfig,
    _per_worker,
    _resolve_schedule,
    _resolve_worker,
    cached_chunk,
    make_serial_chunk,
    resolve_robust,
)
from ..obs import MetricsRegistry, SpanTracer
from .faults import NoFaults
from .latency import ConstantLatency, LatencyModel
from .robust import WeightedMean
from .server_opt import NoServerOpt, resolve_server_opt
from .trace import RoundRecord, TraceRecorder

PyTree = Any

# Worker event-machine status codes (serialized in checkpoints).
#
# The per-worker arrays (_status, _ev_time, _ev_round, ...) ARE the event
# queue: each worker has at most one pending event, so "pop the next
# event" is an argmin over _ev_time of the workers in an event-bearing
# status — a vectorized numpy scan rather than a heap, which lets the
# driver process *every* event at one timestamp in a single sweep.
#
# Deterministic tie-break (pinned by tests/test_ps_async.py): at one
# simulated instant, START events (compute/reboot completions) are
# processed before ARRIVE events (uplink landings) — a START may spawn a
# same-instant ARRIVE under zero network delay, never the reverse — and
# the admission batch formed afterwards is ordered by ascending worker
# id. The order is a pure function of the deterministic latency/schedule
# tables, so it is identical across reruns and across checkpoint/resume.
_UPLINK = 0    # uplink in flight — an ARRIVE event is pending
_COMPUTE = 1   # computing/rebooting — a START event is pending
_HELD = 2      # arrived, held at the server by the staleness bound
_DONE = 3      # all rounds finished


@dataclasses.dataclass(frozen=True)
class AsyncPSConfig(PSConfig):
    """:class:`PSConfig` plus the async policy layer.

    ``latency`` assigns per-(round, worker) compute/network delays (default:
    zero-delay lockstep). ``staleness_bound`` is the SSP τ — a round-``r``
    uplink is held until every live worker's round-``(r − τ)`` uplink has
    arrived at the server; ``math.inf`` never waits, ``0`` is a full
    barrier. ``staleness_discount`` is the γ in the server's
    staleness-aware re-weighting ``w ∝ sw/(1+s)^γ`` (``0`` disables the
    discount).
    """

    latency: LatencyModel | None = None
    staleness_bound: float = math.inf
    staleness_discount: float = 1.0


class AsyncPSEngine:
    """Discrete-event asynchronous Parameter-Server runtime (serial path).

    Examples
    --------
    A 2-worker fleet with a 3× straggler under bounded staleness τ=1: the
    run finishes on the simulated clock with per-admission telemetry.

    >>> import jax
    >>> from repro.core import AdaSEGConfig
    >>> from repro.problems import make_bilinear_game
    >>> from repro.ps import ConstantLatency
    >>> game = make_bilinear_game(jax.random.PRNGKey(0), n=4, sigma=0.1)
    >>> acfg = AsyncPSConfig(adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=2),
    ...                      num_workers=2, rounds=2,
    ...                      latency=ConstantLatency(step_s=(1.0, 3.0),
    ...                                              up_s=0.1, down_s=0.1),
    ...                      staleness_bound=1.0)
    >>> eng = AsyncPSEngine(game.problem, acfg, rng=jax.random.PRNGKey(1))
    >>> zbar = eng.run()
    >>> eng.done, eng.sim_time > 0.0
    (True, True)
    >>> eng.trace.rounds[-1].sim_time_s is not None
    True
    """

    def __init__(
        self,
        problem: MinimaxProblem,
        config: AsyncPSConfig,
        rng,
        *,
        eval_fn: Callable[[PyTree], jax.Array] | None = None,
        trace_meta: dict | None = None,
        tracer: SpanTracer | None = None,
        metrics: MetricsRegistry | None = None,
    ):
        if config.staleness_bound < 0:
            raise ValueError("staleness_bound must be >= 0")
        # Dual-clock observability: spans carry the simulated clock (exact —
        # the event machine knows each phase's interval) next to host wall
        # time; recording is host-side only, so it cannot perturb numerics.
        self.tracer = tracer if tracer is not None else SpanTracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.problem = problem
        self.config = config
        self.worker = _resolve_worker(config)
        self.schedule = _resolve_schedule(config)
        self.compressor = config.compressor or IdentityCompressor()
        self.faults = config.faults or NoFaults()
        check_codec_backend(config.codec_backend, self.compressor)
        self.codec_backend = config.codec_backend
        self.latency = config.latency or ConstantLatency()
        self.eval_fn = eval_fn
        self.tau = float(config.staleness_bound)
        self.gamma = float(config.staleness_discount)

        m, r = config.num_workers, config.rounds
        # Deterministic policy tables — re-derived (never stored) on resume,
        # exactly like the synchronous engine's.
        self._ks = np.asarray(self.schedule.steps(m, r), dtype=np.int32)
        self._alive = np.asarray(self.faults.alive(m, r), dtype=bool)
        if self._ks.shape != (r, m) or self._alive.shape != (r, m):
            raise ValueError("schedule/fault table shape mismatch")
        self._k_pad = int(self.schedule.max_steps(m))
        if not (self._ks <= self._k_pad).all():
            raise ValueError(
                f"schedule emits step counts above its max_steps={self._k_pad}"
            )
        lat = self.latency.tables(m, r)
        if lat.step_s.shape != (r, m):
            raise ValueError(
                f"latency tables have shape {lat.step_s.shape}, "
                f"engine needs ({r}, {m})"
            )
        self._lat = lat
        # Sampled-client rounds: a (R, M) participation mask — rounds a
        # worker isn't drawn for are skipped at zero simulated cost (no
        # send, no receive, no steps, no reboot), with progress advanced
        # through the skip so the staleness gate never waits on a round
        # that will never uplink.
        self.sampler = config.sampler
        self._sampled = (
            None if self.sampler is None
            else self.sampler.participation(m, r)
        )
        # Hostile-fleet subsystem: attacks corrupt uplinks at *store* time
        # (per the sender's own round), the robust merge runs at admission
        # over the last-heard table. Resolved at full fleet width — the
        # async table always spans every worker.
        self.aggregator = config.aggregator or WeightedMean()
        self.byzantine = config.byzantine
        self.dp = config.dp
        self._robust = resolve_robust(config, m)
        # Server-side outer optimizer (DiLoCo/FedOpt): in the event-driven
        # engine the outer step runs once per *admission* — Δ is the change
        # of the staleness-weighted table average between consecutive
        # admissions, so partial batches take smaller, more frequent outer
        # steps while a τ=0 lockstep fleet reproduces the synchronous
        # engine's per-round cadence through the shared chunk.
        self.server_opt = config.server_opt or NoServerOpt()
        self._server = resolve_server_opt(config)
        if self.byzantine is not None:
            self._byz = np.asarray(
                self.byzantine.attacked(m, r), dtype=bool
            )
            if self._byz.shape != (r, m):
                raise ValueError("byzantine table shape mismatch")
        else:
            self._byz = np.zeros((r, m), dtype=bool)

        # RNG derivation: identical to PSEngine so the lockstep trajectory
        # (and each worker family's historical stream) is reproduced.
        rng0, worker_rngs = self.worker.derive_rngs(jnp.asarray(rng), m)
        self._rng0 = np.asarray(rng0)
        self._round_rngs = jax.random.split(rng0, r)
        self._state: PyTree = jax.vmap(
            lambda rr, w: self.worker.init(problem, rr, w)
        )(worker_rngs, jnp.arange(m, dtype=jnp.int32))
        self._ef: PyTree = (
            tree_zeros_like(self.worker.sync_payload(self._state))
            if self.compressor.error_feedback else ()
        )

        # Server memory: last-heard payload/weight per worker.
        self._srv_payload: PyTree = tree_zeros_like(
            self.worker.sync_payload(self._state)
        )
        self._srv_sw = np.zeros((m,), np.float32)
        self._srv_version = np.full((m,), -1, np.int32)
        self._heard = np.zeros((m,), bool)
        # Outer-optimizer state (z_server, moment trees, admission count) —
        # the same fleet-mean anchor derivation as PSEngine, so a τ=0
        # lockstep run feeds the shared chunk an identical srv carry.
        if self._server is not None:
            z0 = jax.tree.map(
                lambda v: jnp.mean(v, axis=0, keepdims=True),
                self.worker.sync_payload(self._state),
            )
            self._srv = (z0, self._server.init_moments(z0), jnp.int32(0))
        else:
            self._srv = None

        # Per-worker event machine (one outstanding event per worker).
        self._status = np.full((m,), _COMPUTE, np.int32)
        self._ev_time = np.zeros((m,), np.float64)
        self._ev_round = np.zeros((m,), np.int32)
        self._ev_busy = np.zeros((m,), np.float64)
        self._ev_is_phase = np.zeros((m,), bool)
        # Server-side progress knowledge: the highest round whose uplink has
        # arrived, per worker (−1 before the init send lands). The staleness
        # gate reads this — a round-r uplink is admitted only once every
        # live worker's round-(r−τ) uplink has landed — so τ=0 is a true
        # barrier: the server waits for the whole fleet's payloads, not
        # merely for the fleet to have started the round.
        self._progress = np.full((m,), -1, np.int32)
        self._arrive_t = np.zeros((m,), np.float64)   # span layer only
        self._busy_s = np.zeros((m,), np.float64)
        self._steps_cum = np.zeros((m,), np.int32)
        # Steps already attributed to a trace record: each admission records
        # the *previous* phase's steps, so the terminal record carries the
        # remainder (steps_cum − steps_recorded) and the trace's total
        # matches the work actually done.
        self._steps_recorded = np.zeros((m,), np.int32)
        self._done_at = np.zeros((m,), np.float64)
        self.now = 0.0
        self.n_admissions = 0
        self._final_recorded = False

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
            "latency": type(self.latency).__name__,
            "staleness_bound": (None if math.isinf(self.tau) else self.tau),
            "staleness_discount": self.gamma,
            "backend": getattr(self.worker, "backend", None),
            "codec_backend": self.codec_backend,
            "execution": "event-driven",
            **({"sampler": self.sampler.name,
                "sample": self.sampler.sample}
               if self.sampler is not None else {}),
            **({"byzantine": self.byzantine.name}
               if self.byzantine is not None else {}),
            **({"server_opt": self.server_opt.name}
               if self._server is not None else {}),
            **({"aggregator": self.aggregator.name,
                "dp": None if self.dp is None else self.dp.name}
               if self._robust is not None else {}),
            **(trace_meta or {}),
        })

        self._rng_cache: dict[int, jax.Array] = {}
        self._np_rng_cache: dict[int, np.ndarray] = {}
        self._c_rng_cache: dict[int, jax.Array] = {}
        # Whenever an admission batch is the whole fleet in the same round
        # (lockstep), the engine runs the synchronous engine's own round
        # chunk instead of the per-arrival path — so "sync is a special
        # case" is shared compiled code, bit-exact by construction, not a
        # reimplementation that happens to agree. Only the identity/
        # no-fault configuration can take it (a faultful PSEngine compiles
        # the masked sync branch, and async compression has per-payload
        # semantics — see _admit_batch).
        self._lockstep_ok = (
            isinstance(self.faults, NoFaults)
            and self.compressor.is_identity
            and self.sampler is None
        )
        self._build_jit()
        for w in range(m):
            self._enter_round(w, 0, 0.0)

    # ------------------------------------------------------------------
    # Jitted numerics — the exact expression sequences of PSEngine's
    # serial path, reindexed for per-arrival execution.
    # ------------------------------------------------------------------

    def _build_jit(self) -> None:
        worker, problem = self.worker, self.problem
        comp = self.compressor
        k_pad = self._k_pad

        vstep = jax.vmap(
            lambda st, rr, en: worker.step(problem, st, rr, enabled=en)
        )

        def phase(state, step_rngs, ks_vec):
            # One worker's K_m^r local steps on the stacked state: ks_vec is
            # one-hot in the worker, so every other lane's update is masked
            # off bit-exactly — the engine's own heterogeneous-K mechanism.
            def body(st, inp):
                rngs, i = inp
                enabled = i < ks_vec
                st = vstep(st, rngs, enabled)
                return st, None

            state, _ = lax.scan(
                body, state, (step_rngs, jnp.arange(k_pad))
            )
            return state

        def store(state, table, sw, mask):
            # Admit uplinks: overwrite the masked lanes of the server table
            # with the senders' current payload/weight. (A blocked sender's
            # lane hasn't changed since send time, so reading it at
            # admission is exact.)
            payload = worker.sync_payload(state)
            new_table = jax.tree.map(
                lambda cur, old: jnp.where(_per_worker(mask, cur), cur, old),
                payload, table,
            )
            sw_now = jax.vmap(worker.sync_weight)(state)
            return new_table, jnp.where(mask, sw_now, sw)

        def store_compressed(state, table, sw, ef, mask, c_rngs):
            payload = worker.sync_payload(state)
            if self.codec_backend == "fused":
                # fused per-payload uplink: EF add + codec + residual
                # write-back in kernel sweeps; the admission mask plays the
                # aliveness role (non-admitted workers keep their residual)
                from ..kernels.sync_compress.ops import codec_uplink_stacked

                sent, ef_new = codec_uplink_stacked(
                    payload, c_rngs,
                    ef=ef if comp.error_feedback else None,
                    alive=mask, codec=comp.codec_spec,
                )
                if not comp.error_feedback:
                    ef_new = ef
            else:
                eff = (tree_add(payload, ef) if comp.error_feedback
                       else payload)
                sent = jax.vmap(comp.compress)(eff, c_rngs)
                if comp.error_feedback:
                    ef_new = jax.tree.map(
                        lambda e_new, e_old: jnp.where(
                            _per_worker(mask, e_new), e_new, e_old
                        ),
                        tree_sub(eff, sent), ef,
                    )
                else:
                    ef_new = ef
            new_table = jax.tree.map(
                lambda s, old: jnp.where(_per_worker(mask, s), s, old),
                sent, table,
            )
            sw_now = jax.vmap(worker.sync_weight)(state)
            return new_table, jnp.where(mask, sw_now, sw), ef_new

        robust = self._robust

        def store_robust(state, table, sw, ef, mask, byz_mask, c_rngs):
            # Robust store: corrupt (attack) then privatize (DP) the raw
            # payload, codec the result *unweighted* — the same pipeline the
            # synchronous robust sync runs, so τ=0 stays a shared-semantics
            # special case. ``byz_mask`` selects the admitted lanes whose
            # sender is adversarial in its own round.
            payload = worker.sync_payload(state)
            uplink = payload
            if robust.byzantine is not None:
                a_rngs = jax.vmap(
                    lambda k: jax.random.fold_in(k, 13)
                )(c_rngs)
                uplink = robust.byzantine.apply(uplink, byz_mask, a_rngs)
            if robust.dp is not None:
                d_rngs = jax.vmap(
                    lambda k: jax.random.fold_in(k, 11)
                )(c_rngs)
                uplink = robust.dp.apply(uplink, d_rngs)
            if comp.is_identity:
                sent, ef_new = uplink, ef
            else:
                from ..kernels.sync_compress.ops import codec_uplink_stacked

                sent, ef_new = codec_uplink_stacked(
                    uplink, c_rngs, w=None,
                    ef=ef if comp.error_feedback else None,
                    alive=mask, codec=comp.codec_spec,
                    use_kernel=self.codec_backend == "fused",
                )
                if not comp.error_feedback:
                    ef_new = ef
            new_table = jax.tree.map(
                lambda s, old: jnp.where(_per_worker(mask, s), s, old),
                sent, table,
            )
            sw_now = jax.vmap(worker.sync_weight)(state)
            return new_table, jnp.where(mask, sw_now, sw), ef_new

        server = self._server

        def outer_broadcast(state, merged, recv, payload, srv):
            # Row-0 of the ungated merge → outer step → recv-gated delivery:
            # the event-driven twin of engine.make_sync_stacked's helper.
            from ..kernels.sync_compress.ops import server_outer_apply

            z, mom, t = srv
            merged_row = jax.tree.map(lambda v: v[:1], merged)
            z_new, mom_new, t_new, eff_lr, dn = server_outer_apply(
                merged_row, z, mom, t, spec=server.spec,
                use_kernel=self.codec_backend == "fused",
            )
            synced = jax.tree.map(
                lambda v, old: jnp.where(
                    _per_worker(recv, old),
                    jnp.broadcast_to(v, old.shape), old,
                ),
                z_new, payload,
            )
            return (worker.merge_synced(state, synced),
                    (z_new, mom_new, t_new), jnp.stack([eff_lr, dn]))

        def admit_robust(state, table, sw, discount, heard, recv, srv=None):
            # Robust Line 5–8 per arrival: the table rows are unweighted
            # z̃ uplinks, so the robust merge (and its weight
            # renormalization over heard lanes) runs server-side — the
            # same sync_merge_stacked(agg=...) call the synchronous robust
            # path compiles. An active outer optimizer takes the merge
            # ungated (recv only ever gated delivery, never the mean) and
            # runs the outer step downstream of the robust aggregation.
            from ..kernels.sync_compress.ops import sync_merge_stacked

            sw_eff = sw * discount
            w_raw = jnp.where(heard, sw_eff, jnp.zeros_like(sw_eff))
            payload = worker.sync_payload(state)
            if server is not None:
                merged = sync_merge_stacked(
                    table, w=w_raw, normalize=True, agg=robust.agg,
                    use_kernel=self.codec_backend == "fused",
                )
                return outer_broadcast(state, merged, recv, payload, srv)
            synced = sync_merge_stacked(
                table, w=w_raw, recv=recv, old=payload,
                normalize=True, agg=robust.agg,
                use_kernel=self.codec_backend == "fused",
            )
            return worker.merge_synced(state, synced)

        def admit(state, table, sw, discount, heard, recv, srv=None):
            # Line 5–8 per arrival: weighted average of the whole last-heard
            # table, broadcast to the admitted workers only. Mirrors
            # engine.make_sync_stacked's no-fault branch with the staleness
            # discount folded into the weights (full-lockstep batches don't
            # come here — they run the shared synchronous chunk).
            sw_eff = sw * discount
            w_raw = jnp.where(heard, sw_eff, jnp.zeros_like(sw_eff))
            w = w_raw / jnp.sum(w_raw)
            msg = jax.tree.map(
                lambda leaf: _per_worker(w, leaf).astype(leaf.dtype) * leaf,
                table,
            )
            payload = worker.sync_payload(state)
            if server is not None:
                merged = jax.tree.map(
                    lambda s: jnp.sum(s, axis=0, keepdims=True), msg
                )
                return outer_broadcast(state, merged, recv, payload, srv)
            synced = jax.tree.map(
                lambda s, old: jnp.where(
                    _per_worker(recv, old),
                    jnp.broadcast_to(
                        jnp.sum(s, axis=0, keepdims=True), old.shape
                    ),
                    old,
                ),
                msg, payload,
            )
            return worker.merge_synced(state, synced)

        self._phase_fn = jax.jit(phase)
        self._store_fn = jax.jit(store)
        self._store_c_fn = jax.jit(store_compressed)
        self._store_r_fn = jax.jit(store_robust) if robust else None
        self._admit_fn = jax.jit(admit_robust if robust else admit)
        self._veta = jax.jit(jax.vmap(worker.eta))
        # Shared with PSEngine through the process-wide chunk cache: a
        # lockstep-eligible async engine literally reuses the synchronous
        # engine's *compiled* round chunk (same cache key ⇒ same jitted
        # callable), donation included. A robust pipeline keys (and
        # builds) the robust variant of the same chunk.
        self._lockstep_chunk = (
            cached_chunk(
                ("serial", self.problem, worker, comp,
                 self.config.num_workers, k_pad, self.eval_fn, True,
                 self.codec_backend, robust, server),
                lambda: make_serial_chunk(
                    self.problem, worker, comp, self.config.num_workers,
                    k_pad, self.eval_fn, no_faults=True,
                    codec_backend=self.codec_backend, robust=robust,
                    server=server,
                ),
            )
            if self._lockstep_ok else None
        )

    def _step_rngs(self, r: int) -> jax.Array:
        """(k_pad, M, 2) step-key table of round ``r`` — the engine's
        derivation, so a worker in round ``r`` consumes the same keys the
        synchronous serial chunk would feed its lane."""
        if r not in self._rng_cache:
            m = self.config.num_workers
            self._rng_cache[r] = jax.random.split(
                self._round_rngs[r], self._k_pad * m
            ).reshape(self._k_pad, m, 2)
        return self._rng_cache[r]

    def _np_step_rngs(self, r: int) -> np.ndarray:
        """Host copy of :meth:`_step_rngs` — mixed-round phase batches
        splice per-worker key columns out of these."""
        if r not in self._np_rng_cache:
            self._np_rng_cache[r] = np.asarray(self._step_rngs(r))
        return self._np_rng_cache[r]

    def _c_rngs(self, r: int) -> jax.Array:
        if r not in self._c_rng_cache:
            self._c_rng_cache[r] = jax.random.split(
                jax.random.fold_in(self._round_rngs[r], 7),
                self.config.num_workers,
            )
        return self._c_rng_cache[r]

    # ------------------------------------------------------------------
    # Event machine
    # ------------------------------------------------------------------

    def _enter_round(self, m: int, r: int, t: float) -> None:
        """Worker ``m`` enters round ``r`` at simulated time ``t``: send the
        uplink (alive), burn a reboot (dead), skip (not sampled), or finish
        (r == rounds)."""
        if self._sampled is not None:
            # rounds the worker isn't drawn for cost nothing; progress
            # advances through the skip as if the round had trivially
            # arrived, so the staleness gate never deadlocks on it
            while r < self.config.rounds and not self._sampled[r, m]:
                self._progress[m] = max(int(self._progress[m]), r)
                r += 1
        if r >= self.config.rounds:
            self._status[m] = _DONE
            self._done_at[m] = t
            self._progress[m] = r
            return
        if self._alive[r, m]:
            self._status[m] = _UPLINK
            self._ev_round[m] = r
            self._ev_time[m] = t + self._lat.up_s[r, m]
        else:
            # Dead round: no send, no receive, no steps — the worker keeps
            # its stale anchor and the server keeps its stale entry (the
            # synchronous fault semantics, minus the barrier); rebooting
            # costs the compute time the round's steps would have taken.
            reboot = float(self._ks[r, m]) * self._lat.step_s[r, m]
            self._status[m] = _COMPUTE
            self._ev_round[m] = r + 1
            self._ev_time[m] = t + reboot
            self._ev_busy[m] = reboot
            self._ev_is_phase[m] = False
            self.tracer.add_span(
                f"reboot r{r}", cat="reboot", track=f"worker/{m}",
                sim_t0=t, sim_t1=t + reboot, round=int(r), worker=int(m),
            )

    def _run_phases(self, ms: list[int]) -> None:
        """Execute the pending local phases of workers ``ms`` (their rounds
        may differ) in ONE compiled masked scan. vmap lanes are independent
        — lane ``m``'s result depends only on its own (state column, key
        column, K) — so a multi-hot ``ks_vec`` is bit-identical to running
        the same phases one-hot sequentially, in any order; batching just
        collapses the per-event dispatch overhead."""
        live = []
        ks_vec = np.zeros((self.config.num_workers,), np.int32)
        for m in ms:
            r = int(self._ev_round[m]) - 1
            k = int(self._ks[r, m])
            if k:
                ks_vec[m] = k
                live.append((m, r, k))
        if not live:
            return
        rounds = {r for _, r, _ in live}
        if len(rounds) == 1:
            # single-round batch: feed the round's key table untouched so
            # the call hits the same jit-cache entry (and the same key
            # buffers) a one-hot phase would
            rngs = self._step_rngs(live[0][1])
        else:
            # mixed rounds at one instant: splice each worker's key column
            # out of its own round's table — lane m consumes exactly the
            # keys the synchronous chunk would feed its lane in round r
            cols = self._np_step_rngs(live[0][1]).copy()
            for m, r, _ in live[1:]:
                cols[:, m] = self._np_step_rngs(r)[:, m]
            rngs = jnp.asarray(cols)
        # wall-clock view: the host executes phases back-to-back; each
        # phase's sim interval was spanned at admission time
        label = (f"phase r{live[0][1]} w{live[0][0]}" if len(live) == 1
                 else f"phase-batch ×{len(live)}")
        with self.tracer.span(label, cat="local-compute",
                              workers=[m for m, _, _ in live],
                              steps=int(sum(k for _, _, k in live))):
            self._state = self._phase_fn(
                self._state, rngs, jnp.asarray(ks_vec)
            )
        for m, _, k in live:
            self._steps_cum[m] += k

    def _handle_starts(self, idx: np.ndarray, t: float) -> None:
        """Complete every compute/reboot ending at instant ``t``: run the
        pending phases as one batch, then enter each worker's next round."""
        phase_ms = [int(m) for m in idx if self._ev_is_phase[m]]
        if phase_ms:
            self._run_phases(phase_ms)
            self._ev_is_phase[phase_ms] = False
        self._busy_s[idx] += self._ev_busy[idx]
        self._ev_busy[idx] = 0.0
        for m in idx:
            self._enter_round(int(m), int(self._ev_round[m]), t)

    def _handle_arrivals(self, idx: np.ndarray, t: float) -> None:
        """Land every uplink arriving at instant ``t`` at the server."""
        self._status[idx] = _HELD
        self._progress[idx] = self._ev_round[idx]
        self._arrive_t[idx] = t
        if self.tracer.enabled:
            for m in idx:
                r = int(self._ev_round[m])
                self.tracer.add_span(
                    f"uplink r{r}", cat="uplink", track=f"worker/{int(m)}",
                    sim_t0=t - float(self._lat.up_s[r, m]), sim_t1=t,
                    round=r, worker=int(m),
                    bytes=float(self._msg_bytes),
                )

    def _min_progress(self) -> int:
        active = self._status != _DONE
        if not active.any():
            return self.config.rounds
        return int(self._progress[active].min())

    def _admissible(self) -> list[int]:
        # ascending worker id — the documented admission order within a
        # batch (np.nonzero enumerates in index order)
        floor = self._min_progress() + self.tau
        return [int(m) for m in np.nonzero(
            (self._status == _HELD) & (self._ev_round <= floor)
        )[0]]

    def _admit_batch(self, adm: list[int], t: float) -> None:
        """One server update: fold the admitted uplinks into the last-heard
        table, recompute the staleness-weighted Line-7 average, broadcast to
        the admitted workers, and schedule their local phases."""
        m_tot = self.config.num_workers
        mask = np.zeros((m_tot,), bool)
        mask[adm] = True
        rounds_of = {m: int(self._ev_round[m]) for m in adm}
        byz_mask = np.zeros((m_tot,), bool)
        if self.byzantine is not None:
            for m in adm:
                byz_mask[m] = self._byz[rounds_of[m], m]

        with self.tracer.span(
            f"admission {self.n_admissions}", cat="admission",
            sim_t0=t, sim_t1=t, admitted=len(adm),
        ) as adm_sp:
            with self.tracer.span("uplink-decode", cat="uplink-encode",
                                  sim_t0=t, sim_t1=t):
                if self._robust is not None:
                    # attack/DP keys derive from the sender's own round, so
                    # even the identity codec needs the spliced key table
                    c_rngs = np.asarray(self._c_rngs(0)).copy()
                    for m in adm:
                        c_rngs[m] = np.asarray(self._c_rngs(rounds_of[m]))[m]
                    self._srv_payload, srv_sw, self._ef = self._store_r_fn(
                        self._state, self._srv_payload,
                        jnp.asarray(self._srv_sw), self._ef,
                        jnp.asarray(mask), jnp.asarray(byz_mask),
                        jnp.asarray(c_rngs),
                    )
                elif self.compressor.is_identity:
                    self._srv_payload, srv_sw = self._store_fn(
                        self._state, self._srv_payload,
                        jnp.asarray(self._srv_sw), jnp.asarray(mask),
                    )
                else:
                    c_rngs = np.asarray(self._c_rngs(0)).copy()
                    for m in adm:
                        c_rngs[m] = np.asarray(self._c_rngs(rounds_of[m]))[m]
                    self._srv_payload, srv_sw, self._ef = self._store_c_fn(
                        self._state, self._srv_payload,
                        jnp.asarray(self._srv_sw),
                        self._ef, jnp.asarray(mask), jnp.asarray(c_rngs),
                    )
            self._srv_sw = np.asarray(srv_sw)
            for m in adm:
                self._srv_version[m] = rounds_of[m]
            self._heard[adm] = True

            # Staleness of every stored entry, rounds behind the freshest.
            vmax = int(self._srv_version[self._heard].max())
            stale = np.where(self._heard, vmax - self._srv_version, 0)

            r0 = rounds_of[adm[0]]
            lockstep = (
                self._lockstep_chunk is not None
                and len(adm) == m_tot
                and all(r == r0 for r in rounds_of.values())
            )
            # Record before mutating state: η and residual at admission time
            # (post-previous-phase, pre-merge — merge_synced never touches
            # the output iterate, so the residual is the same either side).
            self._record_admission(
                adm, t, np.asarray(self._veta(self._state)), stale, byz_mask
            )
            rec = self.trace.rounds[-1]

            with self.tracer.span("server-merge", cat="server-merge",
                                  sim_t0=t, sim_t1=t,
                                  lockstep=lockstep) as merge_sp:
                if lockstep:
                    # The whole fleet is here, in the same round, with zero
                    # staleness: run the synchronous engine's compiled round
                    # body (sync + all local steps fused), making PSEngine a
                    # bit-exact special case by shared code. Phases are
                    # thereby pre-executed; the START events below only
                    # carry the timing.
                    counts = (
                        self._steps_cum + self._ks[r0] * self._alive[r0]
                    ).astype(np.float32)
                    chunk_args = [
                        self._state, self._ef,
                        self._round_rngs[r0:r0 + 1],
                        jnp.asarray(self._ks[r0:r0 + 1]),
                        jnp.asarray(self._alive[r0:r0 + 1]),
                    ]
                    if self._robust is not None:
                        chunk_args.append(jnp.asarray(self._byz[r0:r0 + 1]))
                    chunk_args.append(jnp.asarray(counts[None]))
                    if self._server is not None:
                        chunk_args.append(self._srv)
                        (self._state, self._ef, _, _, self._srv,
                         outer) = self._lockstep_chunk(*chunk_args)
                        outer = np.asarray(outer)[0]
                    else:
                        self._state, self._ef, _, _ = self._lockstep_chunk(
                            *chunk_args
                        )
                        outer = None
                else:
                    discount = np.asarray(
                        (1.0 + stale) ** (-self.gamma), np.float32
                    )
                    admit_args = [
                        self._state, self._srv_payload,
                        jnp.asarray(self._srv_sw),
                        jnp.asarray(discount), jnp.asarray(self._heard),
                        jnp.asarray(mask),
                    ]
                    if self._server is not None:
                        admit_args.append(self._srv)
                        self._state, self._srv, outer = self._admit_fn(
                            *admit_args
                        )
                        outer = np.asarray(outer)
                    else:
                        self._state = self._admit_fn(*admit_args)
                        outer = None
                if outer is not None:
                    rec.outer_lr = float(outer[0])
                    rec.delta_norm = float(outer[1])
                jax.block_until_ready(jax.tree.leaves(self._state)[0])

            # Schedule every admitted worker's next compute in one sweep:
            # status/time/round updates are plain array writes (the arrays
            # are the event queue), so a 10k-worker admission costs numpy
            # vector ops, not 10k heap pushes.
            adm_idx = np.asarray(adm, dtype=np.intp)
            rs = self._ev_round[adm_idx]
            compute = (self._ks[rs, adm_idx].astype(np.float64)
                       * self._lat.step_s[rs, adm_idx])
            down = self._lat.down_s[rs, adm_idx]
            self._status[adm_idx] = _COMPUTE
            self._ev_round[adm_idx] = rs + 1
            self._ev_time[adm_idx] = t + down + compute
            self._ev_busy[adm_idx] = compute
            self._ev_is_phase[adm_idx] = not lockstep
            if lockstep:
                self._steps_cum[adm_idx] += self._ks[rs, adm_idx]
            if self.tracer.enabled:
                # Per-worker simulated-clock story of this admission: the
                # staleness hold, the broadcast flight, and the local phase
                # the worker now starts (its sim interval is known exactly).
                for i, m in enumerate(adm):
                    r = int(rs[i])
                    track = f"worker/{m}"
                    if t > self._arrive_t[m]:
                        self.tracer.add_span(
                            f"held r{r}", cat="held", track=track,
                            sim_t0=float(self._arrive_t[m]), sim_t1=t,
                            round=r, worker=int(m),
                        )
                    if down[i] > 0.0:
                        self.tracer.add_span(
                            f"broadcast r{r}", cat="broadcast", track=track,
                            sim_t0=t, sim_t1=t + float(down[i]),
                            round=r, worker=int(m),
                            bytes=float(self._dense_bytes),
                        )
                    if compute[i] > 0.0:
                        self.tracer.add_span(
                            f"local-compute r{r}", cat="local-compute",
                            track=track, sim_t0=t + float(down[i]),
                            sim_t1=t + float(down[i]) + float(compute[i]),
                            round=r, worker=int(m),
                            steps=int(self._ks[r, m]),
                            staleness=int(stale[m]),
                        )
            self.n_admissions += 1

        # Wall timing stays in the span layer (the recorded trace must be
        # deterministic for crash-resume bit-exactness); the full record
        # rides on the admission span so TraceRecorder.from_spans can
        # rebuild it with wall_time_s derived from the span.
        adm_sp.attrs.update(vars(rec))
        self.metrics.inc("bytes_up", rec.bytes_up, engine="async")
        self.metrics.inc("bytes_down", rec.bytes_down, engine="async")
        self.metrics.inc("admissions", 1, engine="async")
        self.metrics.set_gauge("eta_spread", rec.eta_spread, engine="async")
        if self._robust is not None:
            self.metrics.inc("byzantine_workers",
                             len(rec.byzantine_workers or []),
                             engine="async")
            self.metrics.set_gauge(
                "agg_reject_frac", self.aggregator.reject_frac(len(adm)),
                engine="async", aggregator=self.aggregator.name,
            )
        if self._server is not None and rec.delta_norm is not None:
            self.metrics.set_gauge(
                "outer_delta_norm", rec.delta_norm, engine="async",
                server_opt=self.server_opt.name,
            )
        if rec.idle_frac is not None:
            self.metrics.set_gauge("idle_frac", rec.idle_frac,
                                   engine="async", t_sim=t)
        for m in adm:
            self.metrics.observe("staleness", float(stale[m]),
                                 engine="async", t_sim=t)
        self.metrics.observe(
            "admission_wall_s", adm_sp.wall_dur, engine="async",
            codec=self.compressor.name, backend=self.codec_backend, t_sim=t,
        )

    def _idle_frac(self, t: float) -> float | None:
        if t <= 0.0:
            return None
        busy = float(self._busy_s.sum())
        return max(0.0, 1.0 - busy / (self.config.num_workers * t))

    def _record_admission(self, adm, t, etas, stale, byz_mask) -> None:
        m_tot = self.config.num_workers
        # Steps newly completed since the worker's previous record: exactly
        # one phase lies between its consecutive admissions (or none, when
        # the intervening round was a dead reboot or an unsampled skip), so
        # the delta is that phase's K — and the ledger stays conserved
        # (Σ local_steps over all records ≡ steps_cum) under faults and
        # client sampling alike.
        steps = [0] * m_tot
        for m in adm:
            d = int(self._steps_cum[m] - self._steps_recorded[m])
            steps[m] = d
            self._steps_recorded[m] += d
        adm_etas = etas[list(adm)]
        res = None
        if self.eval_fn is not None:
            res = float(self.eval_fn(self.z_bar()))
        self.trace.record(RoundRecord(
            round=self.n_admissions,
            local_steps=steps,
            alive=[bool(m in adm) for m in range(m_tot)],
            bytes_up=len(adm) * self._msg_bytes,
            bytes_down=len(adm) * self._dense_bytes,
            eta_min=float(adm_etas.min()),
            eta_max=float(adm_etas.max()),
            eta_mean=float(adm_etas.mean()),
            residual=res,
            sim_time_s=float(t),
            staleness=[int(s) if h else None
                       for s, h in zip(stale, self._heard)],
            idle_frac=self._idle_frac(t),
            byzantine_workers=(
                [int(m) for m in adm if byz_mask[m]]
                if self.byzantine is not None else None
            ),
        ))

    def _record_final(self) -> None:
        """Terminal record once the whole fleet has finished: the final
        residual/η state at the fleet's completion time, carrying the last
        phases' step counts (there is no sync after the last local phase,
        so no admission covers them)."""
        if self._final_recorded:
            return
        t = float(self._done_at.max())
        etas = np.asarray(self._veta(self._state))
        res = None
        if self.eval_fn is not None:
            res = float(self.eval_fn(self.z_bar()))
        if self._heard.any():
            vmax = int(self._srv_version[self._heard].max())
            stale = np.where(self._heard, vmax - self._srv_version, 0)
        else:
            # an all-dead fleet never uplinked anything
            stale = np.zeros_like(self._srv_version)
        final_steps = self._steps_cum - self._steps_recorded
        self._steps_recorded += final_steps
        rec = RoundRecord(
            round=self.n_admissions,
            local_steps=final_steps.tolist(),
            alive=[False] * self.config.num_workers,
            bytes_up=0.0,
            bytes_down=0.0,
            eta_min=float(etas.min()),
            eta_max=float(etas.max()),
            eta_mean=float(etas.mean()),
            residual=res,
            sim_time_s=t,
            staleness=[int(s) if h else None
                       for s, h in zip(stale, self._heard)],
            idle_frac=self._idle_frac(t),
        )
        self.trace.record(rec)
        self.tracer.add_span(
            "final", cat="admission", sim_t0=t, sim_t1=t, **vars(rec)
        )
        self._final_recorded = True

    # ------------------------------------------------------------------
    # Driving
    # ------------------------------------------------------------------

    @property
    def done(self) -> bool:
        return bool((self._status == _DONE).all())

    @property
    def sim_time(self) -> float:
        """Current simulated-clock reading (seconds)."""
        return float(self._done_at.max()) if self.done else self.now

    def idle_fraction(self) -> float | None:
        """Fleet fraction of elapsed simulated time not spent computing
        (communication + staleness blocking; in-progress phases count as
        idle until they complete)."""
        return self._idle_frac(self.sim_time)

    def run(
        self,
        *,
        until_time: float | None = None,
        until_admissions: int | None = None,
        checkpoint_path: str | None = None,
        checkpoint_every: int | None = None,
    ) -> PyTree:
        """Drive the event queue (to completion by default) and return the
        global output iterate z̄. ``until_time`` stops before the first
        event past that simulated instant; ``until_admissions`` stops after
        that many server admissions (lifetime total); ``checkpoint_every``
        saves ``checkpoint_path`` every that-many admissions."""
        last_ckpt = self.n_admissions
        t_start = self.now
        with self.tracer.span("run", cat="run", engine="async",
                              tau=self.tau) as run_sp:
            self._drive(until_time, until_admissions,
                        checkpoint_path, checkpoint_every, last_ckpt)
            run_sp.sim_t0 = t_start
            run_sp.sim_t1 = self.sim_time
        return self.z_bar()

    def _next_time(self) -> float | None:
        """Earliest pending event instant — min over the per-worker event
        machine's COMPUTE (phase end) and UPLINK (arrival) times. ``None``
        when no worker has a pending event (fleet done, or deadlocked)."""
        pending = (self._status == _COMPUTE) | (self._status == _UPLINK)
        if not pending.any():
            return None
        return float(self._ev_time[pending].min())

    def _drive(self, until_time, until_admissions, checkpoint_path,
               checkpoint_every, last_ckpt) -> None:
        while True:
            t = self._next_time()
            if t is None:
                if not self.done:
                    raise RuntimeError(
                        "event queue drained with workers still blocked — "
                        "staleness deadlock (this is a bug)"
                    )
                break
            if until_time is not None and t > until_time:
                break
            if (until_admissions is not None
                    and self.n_admissions >= until_admissions):
                break
            # Drain every event at instant t: phase ends (STARTs) first —
            # they may spawn same-instant arrivals under zero uplink delay —
            # then arrivals, looping until the instant is quiet. This is
            # the documented tie-break (see the event-machine note up top).
            while True:
                at_t = self._ev_time == t
                s_idx = np.nonzero((self._status == _COMPUTE) & at_t)[0]
                if s_idx.size:
                    self._handle_starts(s_idx, t)
                    continue
                a_idx = np.nonzero((self._status == _UPLINK) & at_t)[0]
                if a_idx.size:
                    self._handle_arrivals(a_idx, t)
                    continue
                break
            self.now = t
            adm = self._admissible()
            if adm:
                self._admit_batch(adm, t)
            if (checkpoint_path is not None and checkpoint_every
                    and self.n_admissions - last_ckpt >= checkpoint_every):
                self.save(checkpoint_path)
                last_ckpt = self.n_admissions
        if self.done:
            self._record_final()
        if checkpoint_path is not None:
            self.save(checkpoint_path)

    @property
    def state(self) -> PyTree:
        return self._state

    def z_bar(self) -> PyTree:
        """Global output iterate: worker outputs weighted by the local step
        counts *completed on the simulated clock* — the synchronous
        engine's Line-14 expression over realized work."""
        counts = self._steps_cum.astype(np.float32)
        if counts.sum() == 0.0:
            counts = np.ones_like(counts)
        return weighted_worker_average(
            self.worker.output(self._state), jnp.asarray(counts)
        )

    # ------------------------------------------------------------------
    # Checkpointing — dynamic state only; policies re-derived from seeds.
    # ------------------------------------------------------------------

    def _ckpt_tree(self) -> dict:
        tree = {
            "worker_state": self._state,
            "ef": self._ef,
            "srv_payload": self._srv_payload,
            "srv_sw": jnp.asarray(self._srv_sw),
            "srv_version": jnp.asarray(self._srv_version),
            "heard": jnp.asarray(self._heard),
            "status": jnp.asarray(self._status),
            "ev_round": jnp.asarray(self._ev_round),
            "ev_is_phase": jnp.asarray(self._ev_is_phase),
            "progress": jnp.asarray(self._progress),
            "steps_cum": jnp.asarray(self._steps_cum),
            "steps_recorded": jnp.asarray(self._steps_recorded),
            # float64 event times round-trip as raw bytes: jnp would
            # silently truncate them to float32 without jax_enable_x64.
            "ev_time": _f64_bytes(self._ev_time),
            "ev_busy": _f64_bytes(self._ev_busy),
            "busy_s": _f64_bytes(self._busy_s),
            "done_at": _f64_bytes(self._done_at),
            "now": _f64_bytes(np.float64([self.now])),
            "n_admissions": jnp.int32(self.n_admissions),
            "final_recorded": jnp.asarray(bool(self._final_recorded)),
            "rng0": jnp.asarray(self._rng0),
            "worker_fp": jnp.uint32(self.worker.fingerprint),
        }
        if self._robust is not None:
            # only when the robust subsystem changes the merge semantics —
            # plain runs keep the historical checkpoint layout byte-for-byte
            tree["aggregator_fp"] = jnp.uint32(self.aggregator.fingerprint)
        if self._server is not None:
            # present only under an active outer optimizer — `none` keeps
            # the historical checkpoint layout byte-identical
            z, mom, t = self._srv
            tree["server_opt"] = {"z": z, "mom": mom, "t": t}
            tree["server_opt_fp"] = jnp.uint32(self.server_opt.fingerprint)
        return tree

    def save(self, path: str) -> None:
        with self.tracer.span("checkpoint-save", cat="checkpoint",
                              sim_t0=self.now, sim_t1=self.now,
                              path=path) as sp:
            sp.attrs["bytes"] = save_pytree(path, self._ckpt_tree())
            self.metrics.inc("checkpoint_bytes", sp.attrs["bytes"],
                             engine="async")

    def restore(self, path: str) -> "AsyncPSEngine":
        """Resume mid-event-queue: the per-worker event machine (status,
        times, rounds) IS the queue, so loading the arrays restores it
        wholesale; schedules, faults, latency tables and rng streams are
        re-derived from the config. Refuses checkpoints from a different
        seed or optimizer, like the synchronous engine."""
        try:
            loaded = load_pytree(path, self._ckpt_tree())
        except ValueError as e:
            raise ValueError(
                "checkpoint does not match this engine's state layout "
                f"({self.worker.name}): {e}"
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
        if self._robust is not None and (
            int(np.asarray(loaded["aggregator_fp"]))
            != self.aggregator.fingerprint
        ):
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
        m = self.config.num_workers
        self._state = loaded["worker_state"]
        self._ef = loaded["ef"]
        self._srv_payload = loaded["srv_payload"]
        self._srv_sw = np.asarray(loaded["srv_sw"]).copy()
        self._srv_version = np.asarray(loaded["srv_version"]).copy()
        self._heard = np.asarray(loaded["heard"]).copy()
        self._status = np.asarray(loaded["status"]).copy()
        self._ev_round = np.asarray(loaded["ev_round"]).copy()
        self._ev_is_phase = np.asarray(loaded["ev_is_phase"]).copy()
        self._progress = np.asarray(loaded["progress"]).copy()
        self._steps_cum = np.asarray(loaded["steps_cum"]).copy()
        self._steps_recorded = np.asarray(loaded["steps_recorded"]).copy()
        self._ev_time = _f64_unbytes(loaded["ev_time"], m)
        self._ev_busy = _f64_unbytes(loaded["ev_busy"], m)
        self._busy_s = _f64_unbytes(loaded["busy_s"], m)
        self._done_at = _f64_unbytes(loaded["done_at"], m)
        self.now = float(_f64_unbytes(loaded["now"], 1)[0])
        self.n_admissions = int(np.asarray(loaded["n_admissions"]))
        self._final_recorded = bool(np.asarray(loaded["final_recorded"]))
        # drop telemetry from admissions past the restore point so a
        # rewound engine doesn't accumulate duplicate records
        self.trace.rounds = [
            rec for rec in self.trace.rounds if rec.round < self.n_admissions
        ]
        # held workers' uplink-arrival instants aren't checkpointed (span
        # layer only); clamp to "arrived by now" so held spans stay sane
        self._arrive_t[:] = np.minimum(self._arrive_t, self.now)
        return self


def _f64_bytes(arr: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(
        np.frombuffer(np.ascontiguousarray(arr, np.float64).tobytes(),
                      np.uint8)
    )


def _f64_unbytes(leaf, n: int) -> np.ndarray:
    return np.frombuffer(
        np.asarray(leaf, np.uint8).tobytes(), np.float64
    ).reshape(n).copy()
