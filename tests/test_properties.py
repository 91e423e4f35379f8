"""Hypothesis property tests on system invariants beyond projections."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra import numpy as hnp

from repro.core import AdaSEGConfig, sync_weighted_stacked
from repro.core.adaseg import eta_of
from repro.ps import (
    ClientSampler,
    ElasticSchedule,
    FixedSchedule,
    StragglerSchedule,
    UniformSchedule,
)
from repro.roofline.hlo_parse import _decode_groups, classify_axes

_pos_floats = st.floats(0.01, 100.0, width=32, allow_nan=False,
                        allow_subnormal=False)


@given(st.lists(st.floats(0.01, 100.0), min_size=2, max_size=8))
@settings(max_examples=50, deadline=None)
def test_sync_is_convex_combination(inv_eta):
    """The synced anchor lies in the convex hull of worker anchors —
    componentwise between min and max — for ANY positive weights."""
    inv_eta = np.asarray(inv_eta, np.float32)
    m = len(inv_eta)
    z = {"w": jnp.asarray(np.random.RandomState(0).randn(m, 5), jnp.float32)}
    out = sync_weighted_stacked(z, jnp.asarray(inv_eta))
    lo = jnp.min(z["w"], axis=0)
    hi = jnp.max(z["w"], axis=0)
    assert bool(jnp.all(out["w"][0] >= lo - 1e-5))
    assert bool(jnp.all(out["w"][0] <= hi + 1e-5))


@given(hnp.arrays(np.float32, st.integers(1, 20),
                  elements=st.floats(0, 1000, width=32, allow_nan=False)))
@settings(max_examples=50, deadline=None)
def test_eta_antitone_in_accumulator(z_sqs):
    """η is antitone in Σ(Z_τ)² for any nonnegative increment sequence."""
    cfg = AdaSEGConfig(g0=1.0, diameter=3.0, alpha=1.0, k=1)
    acc = np.concatenate([[0.0], np.cumsum(z_sqs)])
    etas = [float(eta_of(cfg, jnp.float32(a))) for a in acc]
    assert all(a >= b - 1e-9 for a, b in zip(etas, etas[1:]))
    assert etas[0] == cfg.diameter * cfg.alpha / cfg.g0


@given(st.floats(0.01, 100.0), st.floats(0.01, 100.0))
@settings(max_examples=50, deadline=None)
def test_eta_scale_covariance(d, alpha):
    """η scales linearly in D·α (the theory's D-dependence)."""
    cfg1 = AdaSEGConfig(g0=1.0, diameter=d, alpha=alpha, k=1)
    cfg2 = AdaSEGConfig(g0=1.0, diameter=2 * d, alpha=alpha, k=1)
    s = jnp.float32(3.7)
    np.testing.assert_allclose(
        2 * float(eta_of(cfg1, s)), float(eta_of(cfg2, s)), rtol=1e-6
    )


# --- Worker-schedule properties ---------------------------------------------
#
# Every WorkerSchedule must be (a) reproducible from its config alone — the
# engines never store the (R, M) table, they re-derive it, which is what
# makes checkpoint/resume (sync round counter, async event queue) bit-exact
# — and (b) bounded by max_steps, the static scan length both engines pad
# to (a larger entry would silently truncate local work while still being
# counted).

@st.composite
def _schedules(draw):
    m = draw(st.integers(1, 8))
    k = draw(st.integers(1, 64))
    seed = draw(st.integers(0, 2**31 - 1))
    kind = draw(st.sampled_from(["uniform", "fixed", "straggler", "elastic"]))
    if kind == "uniform":
        sched = UniformSchedule(k)
    elif kind == "fixed":
        sched = FixedSchedule(tuple(
            draw(st.lists(st.integers(1, 64), min_size=m, max_size=m))))
    elif kind == "straggler":
        slow = draw(st.lists(st.integers(0, m - 1), max_size=m, unique=True))
        sched = StragglerSchedule(
            k=k, min_frac=draw(st.floats(0.05, 1.0, allow_nan=False)),
            seed=seed, slow_workers=tuple(slow))
    else:
        sched = ElasticSchedule(
            UniformSchedule(k), dropout=draw(st.floats(0.0, 1.0,
                                                       allow_nan=False)),
            seed=seed)
    return sched, m, draw(st.integers(1, 30))


@given(_schedules())
@settings(max_examples=80, deadline=None)
def test_schedule_reproducible_and_bounded(case):
    sched, m, rounds = case
    a = sched.steps(m, rounds)
    b = sched.steps(m, rounds)          # re-derived, as resume does
    np.testing.assert_array_equal(a, b)
    assert a.shape == (rounds, m)
    assert np.issubdtype(a.dtype, np.integer)
    assert (a >= 0).all()
    assert (a <= sched.max_steps(m)).all()


# --- ClientSampler properties ------------------------------------------------
#
# Like schedules, the sampling tables must be reproducible from the config
# alone (the engines re-derive them on resume; the checkpoint only carries a
# fingerprint) and exact: every round draws exactly M distinct workers of N,
# rows sorted ascending — the documented participation order.

@st.composite
def _samplers(draw):
    n = draw(st.integers(1, 12))
    sample = draw(st.integers(1, n))
    seed = draw(st.integers(0, 2**31 - 1))
    weights = None
    if draw(st.booleans()):
        weights = tuple(draw(st.lists(
            st.floats(0.1, 10.0, allow_nan=False),
            min_size=n, max_size=n)))
    return ClientSampler(sample=sample, seed=seed, weights=weights), n, \
        draw(st.integers(1, 20))


@given(_samplers())
@settings(max_examples=80, deadline=None)
def test_sampler_reproducible_and_exactly_m_of_n(case):
    sampler, n, rounds = case
    a = sampler.draws(n, rounds)
    b = sampler.draws(n, rounds)        # re-derived, as resume does
    np.testing.assert_array_equal(a, b)
    assert a.shape == (rounds, sampler.sample)
    assert a.dtype == np.int32
    for row in a:
        ids = row.tolist()
        assert len(set(ids)) == sampler.sample      # without replacement
        assert ids == sorted(ids)                   # ascending
        assert 0 <= min(ids) and max(ids) < n
    mask = sampler.participation(n, rounds)
    assert mask.shape == (rounds, n)
    assert (mask.sum(axis=1) == sampler.sample).all()


@given(_samplers())
@settings(max_examples=30, deadline=None)
def test_sampler_fingerprint_separates_laws(case):
    sampler, _, _ = case
    bumped = ClientSampler(sample=sampler.sample, seed=sampler.seed + 1,
                           weights=sampler.weights)
    assert sampler.fingerprint == ClientSampler(
        sample=sampler.sample, seed=sampler.seed,
        weights=sampler.weights).fingerprint
    assert sampler.fingerprint != bumped.fingerprint


def test_sampler_weighted_marginals():
    """Weighted draws match the requested marginals: with sample=1 the
    inclusion probability is exactly w/Σw, so empirical frequencies over
    many rounds converge to it. (Deterministic — one fixed seed, enough
    rounds that a law change trips the tolerance.)"""
    w = (1.0, 2.0, 4.0, 8.0)
    sampler = ClientSampler(sample=1, seed=0, weights=w)
    rounds = 6000
    hits = np.bincount(sampler.draws(4, rounds).ravel(), minlength=4)
    freq = hits / rounds
    expect = np.asarray(w) / sum(w)
    np.testing.assert_allclose(freq, expect, atol=0.02)


# --- Byzantine attack-table properties ---------------------------------------

@st.composite
def _attacks(draw):
    from repro.ps import (
        CollusionAttack,
        ScaledNoiseAttack,
        SignFlipAttack,
        ZeroAttack,
    )

    cls = draw(st.sampled_from([SignFlipAttack, ScaledNoiseAttack,
                                ZeroAttack, CollusionAttack]))
    policy = cls(
        fraction=draw(st.floats(0.0, 1.0, allow_nan=False)),
        seed=draw(st.integers(0, 2**31 - 1)),
        per_round=draw(st.booleans()),
    )
    return policy, draw(st.integers(1, 16)), draw(st.integers(1, 20))


@given(_attacks())
@settings(max_examples=80, deadline=None)
def test_byzantine_table_reproducible_shaped_and_bounded(case):
    """The attack-membership law the engines (and checkpoint resume)
    rely on: ``attacked`` is a pure function of (seed, fraction,
    per_round) with shape (rounds, workers), and every round corrupts
    exactly ``count(m) = min(m, round(fraction·m))`` workers — the
    configured attack fraction is a hard bound, not an expectation."""
    policy, workers, rounds = case
    a = np.asarray(policy.attacked(workers, rounds))
    b = np.asarray(policy.attacked(workers, rounds))   # re-derived on resume
    np.testing.assert_array_equal(a, b)
    assert a.shape == (rounds, workers)
    assert a.dtype == bool
    want = policy.count(workers)
    assert want <= workers
    assert (a.sum(axis=1) == want).all()
    if not policy.per_round:
        # fixed conspiracy: the same subset every round
        assert (a == a[0]).all()


@given(_attacks())
@settings(max_examples=30, deadline=None)
def test_byzantine_fingerprint_separates_laws(case):
    policy, _, _ = case
    import dataclasses as dc

    same = dc.replace(policy)
    bumped = dc.replace(policy, seed=policy.seed + 1)
    assert policy.fingerprint == same.fingerprint
    assert policy.fingerprint != bumped.fingerprint
    assert policy.name == same.name


# --- HLO parser properties ---------------------------------------------------

def test_iota_replica_groups_decode():
    assert _decode_groups("replica_groups=[2,2]<=[4]") == [[0, 1], [2, 3]]
    assert _decode_groups("replica_groups=[2,2]<=[2,2]T(1,0)") == [
        [0, 2], [1, 3]
    ]
    assert _decode_groups("replica_groups={{0,1},{2,3}}") == [[0, 1], [2, 3]]


@given(st.sampled_from([(2, 2), (4, 2), (2, 4), (4, 4)]))
@settings(max_examples=12, deadline=None)
def test_iota_decode_partitions_devices(shape):
    g, s = shape
    groups = _decode_groups(f"replica_groups=[{g},{s}]<=[{g*s}]")
    flat = sorted(d for grp in groups for d in grp)
    assert flat == list(range(g * s))        # exact partition
    assert all(len(grp) == s for grp in groups)


def test_classify_axes_abstract():
    """Axis classification against a mesh with known device layout."""
    import dataclasses

    class FakeDev:
        def __init__(self, i):
            self.id = i

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}
        devices = np.array([[FakeDev(0), FakeDev(1)],
                            [FakeDev(2), FakeDev(3)]])

    mesh = FakeMesh()
    assert classify_axes([[0, 1], [2, 3]], mesh) == "model"
    assert classify_axes([[0, 2], [1, 3]], mesh) == "data"
    assert classify_axes([[0, 1, 2, 3]], mesh) == "data,model"


# --- ServerOptimizer properties ----------------------------------------------
#
# The outer-optimizer math (ps/server_opt + the kernel twins) has laws the
# engines lean on: momentum states are geometric sums of past deltas (so
# they stay bounded when the deltas do), a zero-momentum unit-lr policy IS
# the historical Line-7 merge, fingerprints separate any two hyperparameter
# settings, and a fixed seed fully determines the trajectory across rerun
# AND checkpoint/resume. The math properties drive the eager reference twin
# (``outer_apply_ref``) directly — no jit cache pollution across examples.

def _ref_chain(spec, deltas, z0):
    """Run the reference outer update over a sequence of deltas; returns
    final (z, mom) plus every intermediate moment tuple."""
    from repro.kernels.sync_compress.ref import outer_apply_ref

    slots = 2 if spec[0] == "adam" else 1
    z = jnp.asarray(z0)
    mom = tuple(jnp.zeros_like(z) for _ in range(slots))
    t = jnp.float32(0.0)
    moms = []
    for d in deltas:
        g = z + jnp.asarray(d)           # merged such that merged − z = d
        z, mom, _ = outer_apply_ref(g, z, mom, t, spec=spec)
        t = t + 1.0
        moms.append(mom)
    return z, moms


@given(
    st.floats(0.0, 0.95, allow_nan=False),
    st.lists(hnp.arrays(np.float32, 6,
                        elements=st.floats(-5.0, 5.0, width=32,
                                           allow_nan=False)),
             min_size=1, max_size=8),
)
@settings(max_examples=40, deadline=None)
def test_momentum_moment_geometric_bound(beta, deltas):
    """Heavy-ball moment is a geometric sum of past deltas: for any
    bounded delta sequence, ‖m‖∞ ≤ max‖Δ‖∞ / (1 − β)."""
    deltas = [d.reshape(1, -1) for d in deltas]
    dmax = max(float(np.abs(d).max()) for d in deltas)
    _, moms = _ref_chain(("momentum", 1.0, beta), deltas,
                         np.zeros((1, 6), np.float32))
    bound = dmax / (1.0 - beta) + 1e-4
    for mom in moms:
        assert float(jnp.abs(mom[0]).max()) <= bound


@given(
    st.floats(0.0, 0.99, allow_nan=False),
    st.floats(0.0, 0.99, allow_nan=False),
    st.lists(hnp.arrays(np.float32, 4,
                        elements=st.floats(-3.0, 3.0, width=32,
                                           allow_nan=False)),
             min_size=1, max_size=6),
)
@settings(max_examples=40, deadline=None)
def test_adam_moments_are_convex_averages(b1, b2, deltas):
    """Adam's m/v are exponential *averages* (decay + (1−β)·new), so they
    never escape the range of the deltas: ‖m‖∞ ≤ max‖Δ‖∞ and
    v ≤ max(Δ²) componentwise — with no 1/(1−β) inflation."""
    deltas = [d.reshape(1, -1) for d in deltas]
    dmax = max(float(np.abs(d).max()) for d in deltas)
    # lr=0 keeps z at 0, so each merged − z is exactly its delta. With a
    # moving z, a zero delta at β₂=0 gives v̂=0 and a step of lr/ε ≈ 1e7,
    # after which z + Δ rounds in f32 and the chain applies another Δ.
    _, moms = _ref_chain(("adam", 0.0, b1, b2, 1e-8), deltas,
                         np.zeros((1, 4), np.float32))
    for m, v in moms:
        assert float(jnp.abs(m).max()) <= dmax + 1e-4
        assert float(v.max()) <= dmax * dmax + 1e-4
        assert float(v.min()) >= -1e-7                 # v is a square average


@given(hnp.arrays(np.float32, (3, 7),
                  elements=st.floats(-10.0, 10.0, width=32,
                                     allow_nan=False)))
@settings(max_examples=40, deadline=None)
def test_unit_lr_zero_beta_momentum_is_line7_identity(rows):
    """β=0, lr=1 heavy-ball IS the historical merge: z′ = z + Δ = merged,
    for any merged/anchor pair — the algebraic root of the `none`
    bit-exactness guarantee."""
    from repro.kernels.sync_compress.ref import outer_apply_ref

    merged = jnp.asarray(rows[:1])
    z = jnp.asarray(rows[1:2])
    z_new, _, _ = outer_apply_ref(merged, z, (jnp.zeros_like(z),),
                                  jnp.float32(0.0),
                                  spec=("momentum", 1.0, 0.0))
    np.testing.assert_allclose(np.asarray(z_new), np.asarray(merged),
                               rtol=1e-6, atol=1e-6)


@given(st.floats(0.01, 10.0), st.floats(0.0, 0.99),
       st.floats(0.01, 10.0), st.floats(0.0, 0.99))
@settings(max_examples=50, deadline=None)
def test_server_opt_fingerprints_separate_hypers(lr1, b1, lr2, b2):
    from repro.ps import ServerMomentum, ServerNesterov

    a = ServerMomentum(lr=lr1, beta=b1)
    b = ServerMomentum(lr=lr2, beta=b2)
    if a.name == b.name:
        assert a.fingerprint == b.fingerprint
    else:
        assert a.fingerprint != b.fingerprint
    # policy kind always separates, even at identical hypers
    assert (ServerMomentum(lr=lr1, beta=b1).fingerprint
            != ServerNesterov(lr=lr1, beta=b1).fingerprint)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_server_opt_seed_determinism_rerun_and_resume(seed):
    """One seed, one trajectory: rerunning an outer-Nesterov engine from
    the same rng reproduces z̄ and the outer telemetry bit-exactly, and a
    mid-stream save/restore lands on the identical trajectory."""
    import tempfile, os
    from repro.problems import make_bilinear_game
    from repro.ps import PSConfig, PSEngine, ServerNesterov

    game = make_bilinear_game(jax.random.PRNGKey(7), n=4, sigma=0.1)
    cfg = PSConfig(adaseg=AdaSEGConfig(g0=1.0, diameter=2.0, k=2),
                   num_workers=2, rounds=3,
                   server_opt=ServerNesterov(lr=0.7, beta=0.9))
    mk = lambda: PSEngine(game.problem, cfg,
                          rng=jax.random.PRNGKey(seed),
                          eval_fn=game.residual)
    e1, e2 = mk(), mk()
    z1, z2 = e1.run(), e2.run()
    for a, b in zip(jax.tree.leaves(z1), jax.tree.leaves(z2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ([(r.outer_lr, r.delta_norm) for r in e1.trace.rounds]
            == [(r.outer_lr, r.delta_norm) for r in e2.trace.rounds])
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "srv.msgpack")
        e3 = mk()
        e3.run(until_round=2)
        e3.save(path)
        e4 = mk()
        e4.restore(path)
        z4 = e4.run()
        for a, b in zip(jax.tree.leaves(z1), jax.tree.leaves(z4)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- Cross-version trace property --------------------------------------------
#
# Every trace vintage (v5 explicit-version onward, through v8's outer-
# optimizer fields) loads through TraceRecorder.load with missing optional
# fields defaulted — the loader contract the bench/plot stack relies on.

_V_FIELDS = {
    5: [],
    6: ["sampled_workers"],
    7: ["sampled_workers", "byzantine_workers"],
    8: ["sampled_workers", "byzantine_workers", "outer_lr", "delta_norm"],
}


@given(version=st.sampled_from([5, 6, 7, 8]),
       rnd=st.randoms(use_true_random=False))
@settings(max_examples=40, deadline=None)
def test_any_trace_vintage_loads_with_defaults(version, rnd, tmp_path_factory):
    import json
    from repro.ps import TraceRecorder

    base = {"round": 0, "local_steps": [1, 1], "alive": [True, True],
            "bytes_up": 4.0, "bytes_down": 4.0,
            "eta_min": 1.0, "eta_max": 2.0, "eta_mean": 1.5}
    # a random subset of the vintage's optional fields is present
    extras = {}
    if _V_FIELDS[version] and rnd.random() < 0.7:
        for f in rnd.sample(_V_FIELDS[version],
                            rnd.randint(1, len(_V_FIELDS[version]))):
            extras[f] = [0] if f.endswith("workers") else 0.5
    payload = {"version": version, "meta": {"v": version},
               "rounds": [dict(base, **extras)]}
    path = tmp_path_factory.mktemp("traces") / f"v{version}.json"
    path.write_text(json.dumps(payload))
    rec = TraceRecorder.load(str(path))
    assert rec.version == version
    r = rec.rounds[0]
    for f in ("sampled_workers", "byzantine_workers", "outer_lr",
              "delta_norm"):
        assert getattr(r, f) == extras.get(f)   # present ⇒ kept, absent ⇒ None
    assert r.eta_spread == pytest.approx(2.0)
