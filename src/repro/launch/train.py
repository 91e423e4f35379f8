"""Distributed LocalAdaSEG training: the paper's Algorithm 1 composed with
the LM substrate under GSPMD.

The lowered unit is one **communication round**: a ``lax.scan`` of K local
extragradient steps (each = two vmapped grad calls over the worker axis, no
cross-worker collectives) followed by the inverse-η weighted parameter
average — one all-reduce over the worker mesh axes. The compiled HLO thus
exhibits the paper's collective schedule directly: worker-sync bytes are
amortized 1/K, which is what §Roofline measures.

Worker placement (see ``launch.mesh.worker_axes_for``):
* paper mode        — M = pod·data workers, params replicated per worker
                      (tensor-parallel over ``model`` only).
* hierarchical mode — M = #pods; within a worker params are FSDP-sharded
                      over ``data`` (per-step reduce-scatter/all-gather),
                      and only the inter-pod sync is K-amortized.

Since the unified-stack refactor this module owns **no optimizer math of its
own**: η and the Line-7 sync come from ``core.adaseg`` (``eta_of``,
``sync_weighted_stacked``) — the same functions the PS engines compile —
and :func:`make_ps_engine` turns a :class:`TrainPlan` directly into a
:class:`repro.ps.PSEngine` / :class:`repro.ps.AsyncPSEngine` over a
:class:`repro.ps.ModelWorker`, which is how the examples and benchmarks
drive real-model training. ``make_round_fn`` remains as the GSPMD-lowering
adapter (one jit-able round over pre-materialized batches) for the
dry-run/roofline tooling.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.adaseg import AdaSEGConfig, eta_of, sync_weighted_stacked
from ..core.tree import tree_norm_sq
from ..data.synthetic import batch_struct, make_batch
from ..models import init_model, loss_fn
from ..sharding.specs import build_param_shardings, sanitize_spec, stack_spec
from .mesh import num_workers, worker_axes_for

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree          # z̃ — worker-stacked (M, …)
    sum_sq: jax.Array       # (M,) Σ (Z_τ)²  (f32)
    t: jax.Array            # scalar int32
    grad_sq_sum: jax.Array  # (M,) V_t diagnostic


# Per-worker ‖·‖² over a (M, …) stacked pytree → (M,): the canonical
# tree_norm_sq vmapped over the worker axis (bit-exact vs the old private
# reduction — pinned by tests/test_model_worker.py).
_stacked_norm_sq = jax.vmap(tree_norm_sq)


def _bcast(eta: jax.Array, leaf: jax.Array) -> jax.Array:
    return eta.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """Everything needed to lower/run one arch's training round."""

    cfg: ArchConfig
    adaseg: AdaSEGConfig
    worker_mode: str           # "paper" | "hierarchical"
    k_local: int
    global_batch: int
    seq: int
    # scan the K local steps (fast compile) vs python-unroll them (XLA cost
    # analysis counts every step — used together with cfg.scan_layers=False
    # for the accurate §Roofline pass)
    scan_rounds: bool = True
    # explicit worker count for single-device (CPU example) runs where the
    # mesh carries no worker axis but M stacked workers are still wanted
    workers_override: int | None = None
    # --- §Perf levers (hillclimb) -----------------------------------------
    # re-place a sanitation-dropped 'model' axis on the largest divisible
    # dim (MoE experts < model-axis size → TP within expert)
    repair_model: bool = False
    # pad the frontend patch/frame axis to a shardable multiple (VLM:
    # 6404 patches → e.g. 6656 = 16·416, avoids involuntary resharding)
    frontend_pad_to: int | None = None

    def worker_axes(self, mesh):
        return worker_axes_for(mesh, self.worker_mode)

    def num_workers(self, mesh) -> int:
        if self.workers_override:
            return self.workers_override
        return num_workers(mesh, self.worker_axes(mesh))

    def per_worker_batch(self, mesh) -> int:
        m = self.num_workers(mesh)
        assert self.global_batch % m == 0, (self.global_batch, m)
        return self.global_batch // m


def make_round_fn(plan: TrainPlan):
    """Returns round_fn(state, batches) -> (state, metrics).

    ``batches``: pytree with leading (K, 2, M, per_worker, …) — K local
    steps × two oracle calls (extragradient) × M workers.
    """
    cfg, acfg = plan.cfg, plan.adaseg

    def worker_loss(params, batch):
        return loss_fn(params, cfg, batch)

    vgrad = jax.vmap(jax.value_and_grad(worker_loss))

    def local_step(carry: TrainState, batch_k):
        b1 = jax.tree.map(lambda v: v[0], batch_k)
        b2 = jax.tree.map(lambda v: v[1], batch_k)
        eta = eta_of(acfg, carry.sum_sq)                 # (M,)

        _, m_t = vgrad(carry.params, b1)                 # M_t = G(z̃)
        z_t = jax.tree.map(
            lambda z, g: z - _bcast(eta, z) * g, carry.params, m_t
        )
        loss, g_t = vgrad(z_t, b2)                       # g_t = G(z_t)
        z_new = jax.tree.map(
            lambda z, g: z - _bcast(eta, z) * g, carry.params, g_t
        )

        diff1 = jax.tree.map(jnp.subtract, z_t, carry.params)
        diff2 = jax.tree.map(jnp.subtract, z_t, z_new)
        z_sq = (_stacked_norm_sq(diff1) + _stacked_norm_sq(diff2)) / (
            5.0 * eta**2
        )
        gss = carry.grad_sq_sum + _stacked_norm_sq(g_t) + _stacked_norm_sq(m_t)
        new = TrainState(
            params=z_new,
            sum_sq=carry.sum_sq + z_sq,
            t=carry.t + 1,
            grad_sq_sum=gss,
        )
        return new, jnp.mean(loss)

    def sync(state: TrainState) -> TrainState:
        """Line 7: the engine's inverse-η weighted average
        (``core.adaseg.sync_weighted_stacked``), accumulated in f32 like
        the historical driver (a no-op cast for f32 params)."""
        inv_eta = 1.0 / eta_of(acfg, state.sum_sq)       # (M,)
        f32 = jax.tree.map(lambda l: l.astype(jnp.float32), state.params)
        avg = sync_weighted_stacked(f32, inv_eta)
        params = jax.tree.map(
            lambda a, l: a.astype(l.dtype), avg, state.params
        )
        return state._replace(params=params)

    def round_fn(state: TrainState, batches):
        state = sync(state)
        if plan.scan_rounds:
            state, losses = jax.lax.scan(local_step, state, batches)
        else:
            losses = []
            for k in range(plan.k_local):
                state, loss_k = local_step(
                    state, jax.tree.map(lambda v: v[k], batches)
                )
                losses.append(loss_k)
            losses = jnp.stack(losses)
        return state, {"loss": losses, "eta": eta_of(acfg, state.sum_sq)}

    return round_fn


# ---------------------------------------------------------------------------
# Concrete state/batch construction & shardings
# ---------------------------------------------------------------------------

def init_train_state(rng, plan: TrainPlan, mesh) -> TrainState:
    """Materialized state for real (small-mesh / CPU) runs."""
    m = plan.num_workers(mesh)
    rngs = jax.random.split(rng, m)
    params = jax.vmap(lambda r: init_model(r, plan.cfg)[0])(rngs)
    return TrainState(
        params=params,
        sum_sq=jnp.zeros((m,), jnp.float32),
        t=jnp.int32(0),
        grad_sq_sum=jnp.zeros((m,), jnp.float32),
    )


def abstract_train_state(plan: TrainPlan, mesh) -> TrainState:
    """ShapeDtypeStruct state — used by the dry-run (no allocation)."""
    m = plan.num_workers(mesh)
    params, _ = _spec_tree(plan.cfg)
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((m, *l.shape), l.dtype), params
    )
    return TrainState(
        params=params,
        sum_sq=jax.ShapeDtypeStruct((m,), jnp.float32),
        t=jax.ShapeDtypeStruct((), jnp.int32),
        grad_sq_sum=jax.ShapeDtypeStruct((m,), jnp.float32),
    )


def make_shardings(plan: TrainPlan, mesh):
    """(state_shardings, batch_shardings) for jit in/out."""
    waxes = plan.worker_axes(mesh)
    _, specs = _spec_tree(plan.cfg)
    abstract = abstract_train_state(plan, mesh)
    param_sh = build_param_shardings(
        abstract.params, specs, mesh,
        worker_axes=waxes, fsdp=(plan.worker_mode == "hierarchical"),
        repair_model=plan.repair_model,
    )
    scal = NamedSharding(mesh, P())
    vec_m = NamedSharding(
        mesh, sanitize_spec(P(waxes if waxes else None),
                            (plan.num_workers(mesh),), mesh)
    )
    state_sh = TrainState(params=param_sh, sum_sq=vec_m, t=scal,
                          grad_sq_sum=vec_m)

    lead = None if not waxes else (waxes if len(waxes) != 1 else waxes[0])
    data_free = "data" not in waxes
    bspec = P(None, None, lead, "data" if data_free else None, None)
    # frontend: shard the patch/frame axis over 'model' when divisible —
    # cross-attn KV is then produced already-sharded (perf lever)
    ecfg = effective_cfg(plan)
    patch_axis = None
    if ecfg.encoder_seq and ecfg.encoder_seq % mesh.shape["model"] == 0:
        patch_axis = "model"
    fspec = P(None, None, lead, "data" if data_free else None, patch_axis,
              None)
    bsh = {
        "tokens": NamedSharding(mesh, bspec),
        "labels": NamedSharding(mesh, bspec),
    }
    if plan.cfg.encoder_seq:
        bsh["frontend"] = NamedSharding(mesh, fspec)
    return state_sh, bsh


_SPEC_CACHE: dict = {}


def _spec_tree(cfg: ArchConfig):
    """(abstract_params, specs) without allocating real parameters.

    ``init_model`` is traced abstractly; the PartitionSpec tree is captured
    as a trace-time side effect (specs are plain Python objects)."""
    if cfg.name in _SPEC_CACHE and _SPEC_CACHE[cfg.name][0] is cfg:
        return _SPEC_CACHE[cfg.name][1]
    box = {}

    def build(seed):
        key = jax.random.wrap_key_data(seed)
        p, s = init_model(key, cfg)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(build, jax.ShapeDtypeStruct((2,), jnp.uint32))
    out = (shapes, box["specs"])
    _SPEC_CACHE[cfg.name] = (cfg, out)
    return out


def effective_cfg(plan: TrainPlan):
    cfg = plan.cfg
    if plan.frontend_pad_to and cfg.encoder_seq:
        cfg = dataclasses.replace(
            cfg, encoder_seq=max(cfg.encoder_seq, plan.frontend_pad_to)
        )
    return cfg


def abstract_batches(plan: TrainPlan, mesh):
    m = plan.num_workers(mesh)
    return batch_struct(
        effective_cfg(plan),
        (plan.k_local, 2, m),
        plan.per_worker_batch(mesh),
        plan.seq,
        dtype=jnp.dtype(plan.cfg.compute_dtype),
    )


def make_batches(rng, plan: TrainPlan, mesh):
    """Materialized (K, 2, M, b, S) batches for real runs."""
    m = plan.num_workers(mesh)
    b = plan.per_worker_batch(mesh)
    flat = make_batch(rng, plan.cfg, plan.k_local * 2 * m * b, plan.seq)
    return jax.tree.map(
        lambda v: v.reshape(plan.k_local, 2, m, b, *v.shape[1:]), flat
    )


# ---------------------------------------------------------------------------
# The unified stack: a TrainPlan is a PSEngine configuration
# ---------------------------------------------------------------------------

def make_ps_engine(
    plan: TrainPlan,
    rng,
    *,
    rounds: int,
    mesh=None,
    hetero: bool = False,
    schedule=None,
    compressor=None,
    faults=None,
    backend: str = "reference",
    codec_backend: str = "reference",
    latency=None,
    staleness_bound: float | None = None,
    staleness_discount: float = 1.0,
    eval_fn="loss",
    trace_meta: dict | None = None,
    tracer=None,
    metrics=None,
):
    """A TrainPlan as a Parameter-Server engine — the one training stack.

    Builds the plan's architecture as a :func:`repro.models.make_lm_problem`
    and its AdaSEG spelling as a :class:`repro.ps.ModelWorker`, then hands
    both to the PS runtime, so real-model training gets schedules,
    compression + error feedback, faults, checkpoint/resume and telemetry
    from the exact same code path as the optimizer zoo.

    * ``mesh=None`` — serial vmap engine (``plan.workers_override`` sets M).
    * ``mesh=...``  — ``shard_map`` engine over ``plan.worker_axes(mesh)``.
    * ``backend``/``codec_backend`` — the AdaSEG step and sync-codec
      implementations (``"reference"`` or ``"fused"`` Pallas kernels).
    * ``latency``/``staleness_bound`` — :class:`repro.ps.AsyncPSEngine`
      discrete-event simulation instead (serial path; τ=0 is bit-exact with
      the synchronous engine by shared code).

    ``eval_fn="loss"`` installs :func:`repro.models.make_eval_loss` on a
    held-out batch; pass ``None`` (or a callable) to override.
    """
    from ..models.problem import make_eval_loss, make_lm_problem
    from ..models.worker import ModelWorker
    from ..ps import AsyncPSConfig, AsyncPSEngine, PSConfig, PSEngine

    m = plan.num_workers(mesh) if mesh is not None else plan.workers_override
    if not m:
        raise ValueError(
            "make_ps_engine needs a mesh or plan.workers_override"
        )
    b = plan.per_worker_batch(mesh) if mesh is not None else (
        plan.global_batch // m
    )
    problem = make_lm_problem(
        plan.cfg, batch=b, seq=plan.seq,
        hetero_workers=(m if hetero else None),
    )
    worker = ModelWorker(plan.adaseg, backend=backend, arch=plan.cfg.name)
    if eval_fn == "loss":
        eval_fn = make_eval_loss(plan.cfg, batch=b, seq=plan.seq)

    is_async = latency is not None or staleness_bound is not None
    common = dict(
        num_workers=m, rounds=rounds, worker=worker, local_k=plan.k_local,
        schedule=schedule, compressor=compressor, faults=faults,
        codec_backend=codec_backend,
    )
    if is_async:
        if mesh is not None:
            raise ValueError("the async engine runs the serial path only")
        config = AsyncPSConfig(
            **common, latency=latency,
            staleness_bound=(math.inf if staleness_bound is None
                             else staleness_bound),
            staleness_discount=staleness_discount,
        )
        return AsyncPSEngine(problem, config, rng, eval_fn=eval_fn,
                             trace_meta=trace_meta, tracer=tracer,
                             metrics=metrics)
    config = PSConfig(**common)
    waxes = plan.worker_axes(mesh) if mesh is not None else ("data",)
    return PSEngine(problem, config, rng, mesh=mesh,
                    worker_axes=waxes, eval_fn=eval_fn,
                    trace_meta=trace_meta, tracer=tracer,
                    metrics=metrics)
