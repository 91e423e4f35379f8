"""PS engine semantics: parity with the one-shot serial driver, policy
behavior (schedules, compression, faults) and telemetry."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaSEGConfig, run_local_adaseg
from repro.problems import make_bilinear_game
from repro.ps import (
    BernoulliFaults,
    ElasticSchedule,
    FixedSchedule,
    IdentityCompressor,
    OutageFaults,
    PSConfig,
    PSEngine,
    StochasticQuantizeCompressor,
    StragglerSchedule,
    TopKCompressor,
    UniformSchedule,
)

M, R = 4, 4


@pytest.fixture(scope="module")
def game():
    return make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)


def _cfg(k=5):
    return AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=k)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_trees_close(a, b):
    """Two different compiled programs (engine chunk vs one-shot driver):
    XLA may order their float ops differently, so they agree to the
    cross-program bar of docs/architecture.md, not bit for bit."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_engine_reproduces_serial_driver_bit_exact(game, backend):
    """Identity compressor + no faults + uniform K must give the exact same
    trajectory as run_local_adaseg — the acceptance bar for the subsystem."""
    z_ser, (s_ser, _) = run_local_adaseg(
        game.problem, _cfg(), num_workers=M, rounds=R,
        rng=jax.random.PRNGKey(2), backend=backend)
    engine = PSEngine(
        game.problem,
        PSConfig(adaseg=_cfg(), num_workers=M, rounds=R, backend=backend),
        rng=jax.random.PRNGKey(2))
    z_eng = engine.run()
    _assert_trees_close(z_ser, z_eng)
    _assert_trees_close(s_ser.z_tilde, engine.state.z_tilde)
    np.testing.assert_allclose(np.asarray(s_ser.sum_sq),
                               np.asarray(engine.state.sum_sq), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_ser.t),
                                  np.asarray(engine.state.t))


def test_engine_fixed_schedule_matches_serial_local_steps(game):
    """FixedSchedule == the serial driver's heterogeneous local_steps."""
    ks = jnp.array([5, 4, 3, 2])
    z_ser, (s_ser, _) = run_local_adaseg(
        game.problem, _cfg(), num_workers=M, rounds=R,
        rng=jax.random.PRNGKey(3), local_steps=ks)
    engine = PSEngine(
        game.problem,
        PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                 schedule=FixedSchedule(ks)),
        rng=jax.random.PRNGKey(3))
    z_eng = engine.run()
    _assert_trees_close(z_ser, z_eng)
    np.testing.assert_array_equal(np.asarray(s_ser.t),
                                  np.asarray(engine.state.t))


def test_engine_run_is_chunking_invariant(game):
    """run() in one chunk == round-by-round step_round() — the property the
    checkpoint/resume machinery rests on."""
    pscfg = PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                     compressor=StochasticQuantizeCompressor(bits=8))
    e1 = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(4))
    z1 = e1.run()
    e2 = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(4))
    for _ in range(R):
        e2.step_round()
    _assert_trees_equal(z1, e2.z_bar())
    _assert_trees_equal(e1.state, e2.state)
    _assert_trees_equal(e1._ef, e2._ef)


def test_quantized_sync_stays_close(game):
    """≥8-bit stochastic quantization must not blow up the trajectory: the
    residual stays within 2× of the uncompressed one (PR acceptance bar)."""
    res = {}
    for comp in (IdentityCompressor(), StochasticQuantizeCompressor(bits=8)):
        engine = PSEngine(
            game.problem,
            PSConfig(adaseg=_cfg(k=10), num_workers=M, rounds=10,
                     compressor=comp),
            rng=jax.random.PRNGKey(5))
        res[comp.name] = float(game.residual(engine.run()))
    assert np.isfinite(res["q8"])
    assert res["q8"] < 2.0 * res["identity"]


def test_compression_reduces_bytes(game):
    z_like = jax.tree.map(lambda v: v, game.problem.init(jax.random.PRNGKey(0)))
    dense = IdentityCompressor().message_bytes(z_like)
    q8 = StochasticQuantizeCompressor(bits=8).message_bytes(z_like)
    topk = TopKCompressor(fraction=0.1).message_bytes(z_like)
    assert q8 < dense
    assert topk < dense


def test_faults_exclude_dead_workers(game):
    """A worker down for rounds [1, 3) runs no steps there, keeps its stale
    anchor through the sync, and the survivors' weighted average still
    propagates (renormalized over survivors)."""
    engine = PSEngine(
        game.problem,
        PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                 faults=OutageFaults(events=((2, 1, 3),))),
        rng=jax.random.PRNGKey(6))
    engine.run(until_round=1)
    z_before = jax.tree.map(
        lambda v: np.asarray(v[2]).copy(), engine.state.z_tilde)
    t_before = int(engine.state.t[2])
    engine.run(until_round=2)
    # dead worker: no local steps, anchor unchanged by the round-2 sync
    assert int(engine.state.t[2]) == t_before
    _assert_trees_equal(
        z_before, jax.tree.map(lambda v: np.asarray(v[2]),
                               engine.state.z_tilde))
    # survivors stepped
    assert int(engine.state.t[0]) == t_before + 5
    z = engine.run()
    assert np.isfinite(float(game.residual(z)))
    # trace reflects the outage
    assert engine.trace.rounds[1].alive == [True, True, False, True]
    assert engine.trace.rounds[1].local_steps[2] == 0
    assert engine.trace.rounds[1].bytes_up < engine.trace.rounds[0].bytes_up


def test_elastic_schedule_masks_steps(game):
    """Workers sitting out a round (K_m^r = 0) skip local work but still
    count as members: step counters must match the schedule table exactly."""
    sched = ElasticSchedule(UniformSchedule(5), dropout=0.4, seed=11)
    engine = PSEngine(
        game.problem,
        PSConfig(adaseg=_cfg(), num_workers=M, rounds=R, schedule=sched),
        rng=jax.random.PRNGKey(7))
    engine.run()
    expect = sched.steps(M, R).sum(axis=0)
    assert expect.min() >= 0 and (sched.steps(M, R) == 0).any()
    np.testing.assert_array_equal(np.asarray(engine.state.t), expect)


def test_straggler_schedule_deterministic():
    s = StragglerSchedule(k=10, min_frac=0.5, seed=3, slow_workers=(1,))
    a, b = s.steps(4, 6), s.steps(4, 6)
    np.testing.assert_array_equal(a, b)
    assert (a[:, 1] == 5).all()          # pinned straggler
    assert a.min() >= 5 and a.max() <= 10


def test_faults_deterministic_and_protected():
    f = BernoulliFaults(p=0.5, seed=9)
    a, b = f.alive(4, 8), f.alive(4, 8)
    np.testing.assert_array_equal(a, b)
    assert a[:, 0].all()                 # protected worker 0
    assert not a.all()                   # some failures at p=0.5


def test_trace_json_roundtrip(game, tmp_path):
    engine = PSEngine(
        game.problem,
        PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                 compressor=StochasticQuantizeCompressor(bits=8)),
        rng=jax.random.PRNGKey(8), eval_fn=game.residual)
    engine.run()
    payload = json.loads(engine.trace.to_json())
    assert payload["summary"]["rounds"] == R
    assert payload["meta"]["compressor"] == "q8"
    assert all(r["bytes_up"] > 0 for r in payload["rounds"])
    assert all(r["residual"] is not None for r in payload["rounds"])
    assert all(r["eta_max"] >= r["eta_min"] > 0 for r in payload["rounds"])
    path = str(tmp_path / "trace.json")
    engine.trace.save(path)
    from repro.ps import TraceRecorder
    loaded = TraceRecorder.load(path)
    assert loaded.summary() == engine.trace.summary()


def test_trace_load_accepts_other_vintages(tmp_path):
    """TraceRecorder.load is the inverse of save across format versions:
    records written before the async engine existed (no sim_time_s /
    staleness / idle_frac keys) fall back to defaults, and keys from a
    newer version than this one are dropped instead of crashing."""
    old = {
        "meta": {"optimizer": "adaseg", "compressor": "identity"},
        "summary": {"rounds": 1},
        "rounds": [{
            "round": 0,
            "local_steps": [5, 5],
            "alive": [True, True],
            "bytes_up": 80.0,
            "bytes_down": 80.0,
            "eta_min": 0.5,
            "eta_max": 0.7,
            "eta_mean": 0.6,
            # pre-PR-4 file: no residual/wall/sim-time keys at all
        }],
    }
    path = tmp_path / "old_trace.json"
    path.write_text(json.dumps(old))
    from repro.ps import TraceRecorder
    tr = TraceRecorder.load(str(path))
    rec = tr.rounds[0]
    assert rec.local_steps == [5, 5]
    assert rec.residual is None and rec.sim_time_s is None
    assert rec.staleness is None and rec.idle_frac is None
    assert tr.sim_time_s is None
    assert "sim_time_s" not in tr.summary()

    future = dict(old)
    future["rounds"] = [dict(old["rounds"][0],
                             from_the_future=123, sim_time_s=4.2)]
    path2 = tmp_path / "future_trace.json"
    path2.write_text(json.dumps(future))
    tr2 = TraceRecorder.load(str(path2))
    assert tr2.rounds[0].sim_time_s == 4.2
    assert not hasattr(tr2.rounds[0], "from_the_future")


def test_engine_rejects_mismatched_schedule(game):
    with pytest.raises(ValueError):
        PSEngine(
            game.problem,
            PSConfig(adaseg=_cfg(), num_workers=M, rounds=R,
                     schedule=FixedSchedule([5, 4])),
            rng=jax.random.PRNGKey(1))
