"""Hostile-fleet subsystem (ps/robust): clean-fleet degradation to the
weighted mean, robust-merge correctness against numpy oracles, fused↔
reference parity, attack efficacy on both engines, DP uplinks, checkpoint
fingerprints, and the serial-path-only pins.

Degradation bar (the PR's satellite #1): every robust aggregator at zero
robustness budget — β=0 trimmed mean, f=0 multi-Krum, coordinate median of
≤2 workers, and the explicit ``WeightedMean`` — reproduces
``sync_weighted_stacked``'s Line-7 weighted average *bit-exactly* on the
reference backend and within rtol=1e-5 on the fused one, because the
resolved spec is ``None`` and the historical merge path compiles unchanged.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaSEGConfig
from repro.core.adaseg import sync_weighted_stacked
from repro.kernels.sync_compress.ops import sync_merge_stacked
from repro.problems import make_bilinear_game
from repro.ps import (
    AsyncPSConfig,
    AsyncPSEngine,
    ClientSampler,
    ConstantLatency,
    CoordinateMedian,
    DPUplink,
    LognormalLatency,
    MultiKrum,
    PSConfig,
    PSEngine,
    SignFlipAttack,
    StochasticQuantizeCompressor,
    TraceRecorder,
    TrimmedMean,
    WeightedMean,
    ZeroAttack,
)

M, R, K, N = 5, 6, 4, 10


@pytest.fixture(scope="module")
def game():
    return make_bilinear_game(jax.random.PRNGKey(0), n=N, sigma=0.1)


@pytest.fixture(scope="module")
def stacked():
    key = jax.random.PRNGKey(0)
    return {
        "a": jax.random.normal(key, (M, 257)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (M, 7, 3)),
    }


def _cfg(k=K):
    return AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=k)


def _as_async(pscfg: PSConfig, **extra) -> AsyncPSConfig:
    base = {f.name: getattr(pscfg, f.name)
            for f in dataclasses.fields(PSConfig)}
    return AsyncPSConfig(**base, **extra)


def _assert_trees(a, b, exact=True, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Satellite #1 — clean-fleet degradation: zero budget ⇒ the weighted mean
# ---------------------------------------------------------------------------

ZERO_BUDGET = [
    (WeightedMean(), M),
    (TrimmedMean(beta=0.0), M),
    (MultiKrum(f=0), M),
    (CoordinateMedian(), 2),     # median of ≤2 inliers trims nobody
]


@pytest.mark.parametrize("agg,m", ZERO_BUDGET,
                         ids=lambda p: getattr(p, "name", p))
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference", "fused"])
def test_zero_budget_reproduces_weighted_mean(stacked, agg, m, use_kernel):
    z = jax.tree.map(lambda v: v[:m], stacked)
    w = jnp.linspace(0.5, 2.0, m)
    assert agg.spec(m) is None          # the degradation is *static*
    got = sync_merge_stacked(z, w, normalize=True, agg=agg.spec(m),
                             use_kernel=use_kernel)
    want = sync_weighted_stacked(z, w)
    # different compiled programs: the cross-program bar, not bit-exact
    _assert_trees(got, want, exact=False)


@pytest.mark.parametrize("agg", [TrimmedMean(beta=0.0), MultiKrum(f=0),
                                 WeightedMean()],
                         ids=lambda a: a.name)
def test_zero_budget_engine_bit_exact(game, agg):
    """A zero-budget robust config compiles the historical engine path:
    the whole trajectory is bit-identical to a plain run, and the trace
    carries no robust metadata."""
    plain = PSEngine(game.problem,
                     PSConfig(adaseg=_cfg(), num_workers=M, rounds=R),
                     rng=jax.random.PRNGKey(2))
    z0 = plain.run()
    robust = PSEngine(game.problem,
                      PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                               aggregator=agg),
                      rng=jax.random.PRNGKey(2))
    z1 = robust.run()
    _assert_trees(z0, z1)
    _assert_trees(plain.state, robust.state)
    assert "aggregator" not in robust.trace.meta
    assert robust.trace.rounds[-1].byzantine_workers is None


# ---------------------------------------------------------------------------
# Robust merges against numpy oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference", "fused"])
def test_coordinate_median_matches_numpy(use_kernel):
    z = {"w": jnp.asarray(np.random.RandomState(0).randn(M, 33), jnp.float32)}
    agg = CoordinateMedian()
    got = sync_merge_stacked(z, jnp.ones(M), normalize=True,
                             agg=agg.spec(M), use_kernel=use_kernel)
    want = np.median(np.asarray(z["w"]), axis=0)
    np.testing.assert_allclose(np.asarray(got["w"][0]), want,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference", "fused"])
def test_trimmed_mean_excludes_outlier_lane(use_kernel):
    z = {"w": jnp.asarray(np.random.RandomState(1).randn(M, 17), jnp.float32)}
    hostile = z["w"].at[2].set(1e6)
    agg = TrimmedMean(beta=0.2)       # trims 1 lane per side at M=5
    got = sync_merge_stacked({"w": hostile}, jnp.ones(M), normalize=True,
                             agg=agg.spec(M), use_kernel=use_kernel)
    assert float(jnp.abs(got["w"]).max()) < 1e3   # outlier never averaged in
    # oracle: drop min and max per coordinate, average the rest
    s = np.sort(np.asarray(hostile), axis=0)[1:-1]
    np.testing.assert_allclose(np.asarray(got["w"][0]), s.mean(axis=0),
                               rtol=1e-5, atol=1e-6)


def test_trimmed_fused_matches_reference_weighted_recv(stacked):
    w = jnp.linspace(0.5, 2.0, M).at[1].set(0.0)   # a dead lane too
    recv = jnp.array([1.0, 0.0, 1.0, 1.0, 1.0])
    old = jax.tree.map(lambda v: v + 1.0, stacked)
    kw = dict(w=w, recv=recv, old=old, normalize=True,
              agg=("trimmed", 1))
    got_f = sync_merge_stacked(stacked, **kw, use_kernel=True)
    got_r = sync_merge_stacked(stacked, **kw, use_kernel=False)
    _assert_trees(got_f, got_r, exact=False)
    # non-receiving lane keeps its old payload on both backends
    for leaf_g, leaf_o in zip(jax.tree.leaves(got_r), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(leaf_g[1]),
                                      np.asarray(leaf_o[1]))


def test_krum_rejects_planted_outlier(stacked):
    hostile = jax.tree.map(lambda v: v.at[3].set(50.0), stacked)
    agg = MultiKrum(f=1)
    got = sync_merge_stacked(hostile, jnp.ones(M), normalize=True,
                             agg=agg.spec(M))
    # selection averages only the m_select closest lanes: the planted
    # outlier cannot appear in the merge
    honest = jax.tree.map(lambda v: jnp.delete(v, 3, axis=0), hostile)
    for g, h in zip(jax.tree.leaves(got), jax.tree.leaves(honest)):
        assert float(jnp.abs(g[0]).max()) <= float(jnp.abs(h).max()) + 1e-5


# ---------------------------------------------------------------------------
# Attack efficacy — the acceptance criterion in miniature (bench_fig4 runs
# the full matrix): robust merges survive 20% sign-flip, the mean does not
# ---------------------------------------------------------------------------

def _residual(game, agg, byz, m=10, rounds=12):
    cfg = PSConfig(adaseg=_cfg(), num_workers=m, rounds=rounds,
                   byzantine=byz, aggregator=agg)
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    return float(game.residual(eng.run()))


def test_robust_aggregators_survive_sign_flip(game):
    byz = SignFlipAttack(fraction=0.2, scale=3.0, seed=5)
    clean = _residual(game, None, None)
    mean = _residual(game, None, byz)
    median = _residual(game, CoordinateMedian(), byz)
    trimmed = _residual(game, TrimmedMean(beta=0.2), byz)
    assert median <= 2.0 * clean
    assert trimmed <= 2.0 * clean
    assert mean > 2.0 * clean           # the plain mean stalls/diverges


def test_byzantine_ids_recorded_and_composable_with_codec(game):
    byz = SignFlipAttack(fraction=0.4, scale=3.0, seed=5)
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                   byzantine=byz, aggregator=CoordinateMedian(),
                   compressor=StochasticQuantizeCompressor(
                       bits=8, error_feedback=True),
                   codec_backend="fused")
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    eng.run()
    ids = [r.byzantine_workers for r in eng.trace.rounds]
    table = byz.attacked(M, R)
    assert ids == [sorted(np.nonzero(table[r])[0].tolist())
                   for r in range(R)]
    assert eng.trace.meta["byzantine"] == byz.name
    assert eng.trace.meta["aggregator"] == "coordinate_median"
    assert eng.metrics.total("byzantine_workers") == int(table.sum())


# ---------------------------------------------------------------------------
# Both engines: τ=0 lockstep parity and a genuinely-async robust run
# ---------------------------------------------------------------------------

def test_async_lockstep_robust_parity_bit_exact(game):
    pscfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                     byzantine=SignFlipAttack(fraction=0.4, seed=5),
                     aggregator=TrimmedMean(beta=0.2),
                     dp=DPUplink(clip=5.0, sigma=0.01))
    eng = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(2))
    z_sync = eng.run()
    a = AsyncPSEngine(
        game.problem,
        _as_async(pscfg, latency=ConstantLatency(step_s=1.0, up_s=0.5),
                  staleness_bound=0.0),
        rng=jax.random.PRNGKey(2))
    z_async = a.run()
    assert a._lockstep_chunk is not None
    # the async engine runs the lockstep chunk round by round, the sync
    # engine all rounds in one scan: two programs, the cross-program bar
    _assert_trees(z_sync, z_async, exact=False)
    _assert_trees(eng.state, a.state, exact=False)
    assert ([r.byzantine_workers for r in eng.trace.rounds]
            == [r.byzantine_workers for r in a.trace.rounds][:R])


def test_async_staleness_robust_run_records_attacks(game):
    pscfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                     byzantine=SignFlipAttack(fraction=0.4, seed=5),
                     aggregator=CoordinateMedian())
    a = AsyncPSEngine(
        game.problem,
        _as_async(pscfg, latency=LognormalLatency(step_s=0.01, sigma=0.8,
                                                  seed=3),
                  staleness_bound=3.0),
        rng=jax.random.PRNGKey(2), eval_fn=game.residual)
    z = a.run()
    assert np.isfinite(float(game.residual(z)))
    assert any(r.byzantine_workers for r in a.trace.rounds)
    assert a.metrics.total("byzantine_workers") > 0
    assert "agg_reject_frac" in a.metrics.names()


# ---------------------------------------------------------------------------
# DP uplinks
# ---------------------------------------------------------------------------

def test_dp_clips_joint_l2_and_noise_is_seeded(stacked):
    dp = DPUplink(clip=1.0, sigma=0.5)
    rngs = jax.random.split(jax.random.PRNGKey(7), M)
    out1 = dp.apply(stacked, rngs)
    out2 = dp.apply(stacked, rngs)
    _assert_trees(out1, out2)                     # same keys ⇒ same noise
    clip_only = DPUplink(clip=1.0).apply(stacked, rngs)
    flat = np.concatenate([np.asarray(v).reshape(M, -1)
                           for v in jax.tree.leaves(clip_only)], axis=1)
    np.testing.assert_array_less(np.linalg.norm(flat, axis=1), 1.0 + 1e-5)


def test_dp_engine_run_attack_not_clipped_before_corruption(game):
    """DP composes with attacks and codecs end-to-end, and the run's
    uplinks stay bounded — a sanity bar, not a privacy accountant."""
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                   byzantine=ZeroAttack(fraction=0.2, seed=1),
                   aggregator=TrimmedMean(beta=0.2),
                   dp=DPUplink(clip=0.5, sigma=0.1),
                   compressor=StochasticQuantizeCompressor(bits=8))
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    z = eng.run()
    assert np.isfinite(float(game.residual(z)))
    assert eng.trace.meta["dp"] == "dp(clip=0.5,sigma=0.1)"


# ---------------------------------------------------------------------------
# Checkpoint fingerprints + crash/resume mid-attack
# ---------------------------------------------------------------------------

def test_resume_mid_attack_bit_exact_and_fp_rejected(game, tmp_path):
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                   byzantine=SignFlipAttack(fraction=0.4, seed=5),
                   aggregator=TrimmedMean(beta=0.2))
    p = os.path.join(tmp_path, "ck.npz")
    e1 = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    e1.run(until_round=3)
    e1.save(p)
    z1 = e1.run()
    e2 = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    e2.restore(p)
    _assert_trees(z1, e2.run())

    other = PSEngine(game.problem,
                     dataclasses.replace(cfg, aggregator=CoordinateMedian()),
                     rng=jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="robust aggregator"):
        other.restore(p)


def test_zero_budget_checkpoint_layout_unchanged(game, tmp_path):
    """Plain checkpoints carry no aggregator_fp — a robust-capable build
    still round-trips historical checkpoints."""
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R)
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    assert "aggregator_fp" not in eng._ckpt_tree()


# ---------------------------------------------------------------------------
# Sampled rounds + the serial-path-only pins (satellite #2)
# ---------------------------------------------------------------------------

def test_sampled_rounds_with_robust_attack(game):
    fleet, sample = 8, 4
    cfg = PSConfig(adaseg=_cfg(), num_workers=fleet, rounds=R,
                   sampler=ClientSampler(sample=sample, seed=3),
                   byzantine=SignFlipAttack(fraction=0.5, seed=5),
                   aggregator=TrimmedMean(beta=0.25))
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    z = eng.run()
    assert np.isfinite(float(game.residual(z)))
    for r, rec in enumerate(eng.trace.rounds):
        assert rec.byzantine_workers is not None
        drawn = set(rec.sampled_workers)
        # recorded attackers are fleet ids inside this round's draw
        assert set(rec.byzantine_workers) <= drawn


def test_sampler_with_mesh_raises_not_implemented(game):
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(1, 1)
    cfg = PSConfig(adaseg=_cfg(), num_workers=1, rounds=2,
                   sampler=ClientSampler(sample=1, seed=0))
    with pytest.raises(NotImplementedError, match="serial path only"):
        PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2),
                 mesh=mesh, worker_axes=("data",))


def test_robust_with_mesh_raises_not_implemented(game):
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(1, 1)
    cfg = PSConfig(adaseg=_cfg(), num_workers=1, rounds=2,
                   byzantine=SignFlipAttack(fraction=1.0, seed=0))
    with pytest.raises(NotImplementedError, match="serial path only"):
        PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2),
                 mesh=mesh, worker_axes=("data",))


# ---------------------------------------------------------------------------
# Satellite #4 backstop — trace v7 round-trips, v6 still loads
# ---------------------------------------------------------------------------

def test_trace_v7_roundtrip_and_v6_loads(game, tmp_path):
    cfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=3,
                   byzantine=SignFlipAttack(fraction=0.4, seed=5),
                   aggregator=CoordinateMedian())
    eng = PSEngine(game.problem, cfg, rng=jax.random.PRNGKey(2))
    eng.run()
    p = os.path.join(tmp_path, "t.json")
    eng.trace.save(p)
    back = TraceRecorder.load(p)
    assert back.version == 8
    assert ([r.byzantine_workers for r in back.rounds]
            == [r.byzantine_workers for r in eng.trace.rounds])
    assert back.meta["aggregator"] == "coordinate_median"

    # a v6-era trace (no hostile-fleet fields) loads with the new defaults
    with open(p) as f:
        payload = json.load(f)
    payload["version"] = 6
    payload["meta"].pop("byzantine"), payload["meta"].pop("aggregator")
    for r in payload["rounds"]:
        r.pop("byzantine_workers")
    p6 = os.path.join(tmp_path, "t6.json")
    with open(p6, "w") as f:
        json.dump(payload, f)
    old = TraceRecorder.load(p6)
    assert old.version == 6
    assert all(r.byzantine_workers is None for r in old.rounds)
