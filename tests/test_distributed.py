"""Distributed-path tests. These need >1 XLA device, which requires setting
``xla_force_host_platform_device_count`` BEFORE jax initializes — so they run
in a subprocess (the main pytest process keeps the default 1-device view, as
required for the smoke tests)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_psum_sync_equals_stacked_sync():
    """The shard_map/psum weighted sync must equal the serial stacked sync."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import make_psum_sync, sync_weighted_stacked
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(4, 2)
        m = 4
        z = {"w": jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)}
        inv_eta = jnp.array([0.5, 1.0, 1.5, 2.0])

        expected = sync_weighted_stacked(z, inv_eta)

        sync = make_psum_sync(("data",))
        def shard_fn(z, ie):
            # per-shard: z {"w": (1, 6)}, ie (1,)
            out = sync({"w": z["w"][0]}, ie[0])
            return {"w": out["w"][None]}, None
        got, _ = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("data", None), P("data")),
            out_specs=(P("data", None), None),
        )({"w": z["w"]}, inv_eta)
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(expected["w"]), rtol=1e-6)
        print("PSUM_SYNC_OK")
    """)
    assert "PSUM_SYNC_OK" in out


def test_sharded_driver_matches_serial_both_backends():
    """run_local_adaseg_sharded (shard_map + psum sync, 4 workers on a 4×2
    mesh) must reproduce the serial vmap driver's trajectory for BOTH step
    backends — reference tree ops and the fused Pallas kernels — within the
    PR's rtol=1e-5 acceptance bar on the bilinear game."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AdaSEGConfig, run_local_adaseg
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharded import run_local_adaseg_sharded
        from repro.problems import make_bilinear_game

        game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
        cfg = AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=5)
        mesh = make_test_mesh(4, 2)
        for backend in ("reference", "fused"):
            z_ser, (s_ser, _) = run_local_adaseg(
                game.problem, cfg, num_workers=4, rounds=4,
                rng=jax.random.PRNGKey(2), backend=backend)
            z_sh, (s_sh, hist) = run_local_adaseg_sharded(
                game.problem, cfg, mesh=mesh, worker_axes=("data",),
                rounds=4, rng=jax.random.PRNGKey(2), backend=backend,
                collect_aux=True)
            for a, b in zip(jax.tree.leaves(z_ser), jax.tree.leaves(z_sh)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(np.asarray(s_ser.sum_sq),
                                       np.asarray(s_sh.sum_sq), rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(s_ser.t),
                                          np.asarray(s_sh.t))
            assert hist.eta.shape == (4, 5, 4)   # (R, K, M)
        print("SHARDED_PARITY_OK")
    """)
    assert "SHARDED_PARITY_OK" in out


def test_sharded_hetero_local_steps_and_sync_hook():
    """The lifted uniform-K restriction: per-worker local_steps through the
    sharded driver must match the serial driver's masking semantics (both
    backends, rtol=1e-5), and the compressed-psum sync hook must stay close
    to the dense all-reduce."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AdaSEGConfig, run_local_adaseg
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharded import run_local_adaseg_sharded
        from repro.problems import make_bilinear_game
        from repro.ps import StochasticQuantizeCompressor, make_compressed_psum_sync

        game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
        cfg = AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=5)
        mesh = make_test_mesh(4, 2)
        ks = jnp.array([5, 4, 3, 2])
        for backend in ("reference", "fused"):
            z_ser, (s_ser, _) = run_local_adaseg(
                game.problem, cfg, num_workers=4, rounds=4,
                rng=jax.random.PRNGKey(3), local_steps=ks, backend=backend)
            z_sh, (s_sh, hist) = run_local_adaseg_sharded(
                game.problem, cfg, mesh=mesh, worker_axes=("data",),
                rounds=4, rng=jax.random.PRNGKey(3), local_steps=ks,
                backend=backend, collect_aux=True)
            for a, b in zip(jax.tree.leaves(z_ser), jax.tree.leaves(z_sh)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(np.asarray(s_ser.sum_sq),
                                       np.asarray(s_sh.sum_sq), rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(s_ser.t),
                                          np.asarray(s_sh.t))
            assert hist.eta.shape == (4, 5, 4)

        z_dense, _ = run_local_adaseg_sharded(
            game.problem, cfg, mesh=mesh, rounds=4,
            rng=jax.random.PRNGKey(2))
        sync = make_compressed_psum_sync(
            ("data",), StochasticQuantizeCompressor(bits=8))
        z_q, _ = run_local_adaseg_sharded(
            game.problem, cfg, mesh=mesh, rounds=4,
            rng=jax.random.PRNGKey(2), sync_fn=sync)
        rd, rq = float(game.residual(z_dense)), float(game.residual(z_q))
        assert np.isfinite(rq) and rq < 2.0 * rd + 0.1, (rd, rq)
        print("HETERO_SHARDED_OK")
    """)
    assert "HETERO_SHARDED_OK" in out


def test_ps_engine_sharded_matches_serial_and_resumes():
    """PS engine acceptance on the sharded path: identity/no-fault engine
    reproduces the serial engine (rtol=1e-5, both backends); the full
    policy stack (hetero K + q8 + faults) agrees across execution paths;
    and a killed sharded run resumes within rtol=1e-5."""
    out = run_in_subprocess("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AdaSEGConfig
        from repro.launch.mesh import make_test_mesh
        from repro.problems import make_bilinear_game
        from repro.ps import (BernoulliFaults, FixedSchedule, PSConfig,
                              PSEngine, StochasticQuantizeCompressor)

        game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
        cfg = AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=5)
        mesh = make_test_mesh(4, 2)

        def close(a, b, **kw):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)

        for backend in ("reference", "fused"):
            pscfg = PSConfig(adaseg=cfg, num_workers=4, rounds=4,
                             backend=backend)
            es = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(2))
            eh = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(2),
                          mesh=mesh, worker_axes=("data",))
            close(es.run(), eh.run(), rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(np.asarray(es.state.sum_sq),
                                       np.asarray(eh.state.sum_sq),
                                       rtol=1e-5)

        pscfg = PSConfig(adaseg=cfg, num_workers=4, rounds=6,
                         schedule=FixedSchedule([5, 4, 3, 2]),
                         compressor=StochasticQuantizeCompressor(bits=8),
                         faults=BernoulliFaults(p=0.25, seed=5))
        es = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3))
        eh = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3),
                      mesh=mesh)
        close(es.run(), eh.run(), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(es.state.t),
                                      np.asarray(eh.state.t))

        z_full = eh.z_bar()
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "ps.msgpack")
            e2 = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3),
                          mesh=mesh)
            e2.run(until_round=2)
            e2.save(p)
            e3 = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3),
                          mesh=mesh)
            e3.restore(p)
            assert e3.round == 2
            close(z_full, e3.run(), rtol=1e-5, atol=1e-7)
        print("PS_SHARDED_OK")
    """)
    assert "PS_SHARDED_OK" in out


def test_zoo_engine_sharded_matches_serial():
    """Zoo acceptance on the sharded path: a MinimaxWorker (Adam with its
    moments, UMP with its 1/η sync weighting) through the shard_map engine
    must match the serial engine within rtol=1e-5 — identity config and the
    full heterogeneity + q8 + faults policy stack."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.optim import MinimaxWorker, adam_minimax, ump
        from repro.problems import make_bilinear_game
        from repro.ps import (BernoulliFaults, FixedSchedule, PSConfig,
                              PSEngine, StochasticQuantizeCompressor)

        game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
        mesh = make_test_mesh(4, 2)

        def close(a, b, **kw):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)

        for opt in (adam_minimax(0.02), ump(1.0, 2.0)):
            pscfg = PSConfig(num_workers=4, rounds=4,
                             worker=MinimaxWorker(opt), local_k=5)
            es = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(2))
            eh = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(2),
                          mesh=mesh, worker_axes=("data",))
            close(es.run(), eh.run(), rtol=1e-5, atol=1e-7)
            close(es.state, eh.state, rtol=1e-5, atol=1e-7)

            pscfg = PSConfig(num_workers=4, rounds=4,
                             worker=MinimaxWorker(opt),
                             schedule=FixedSchedule([5, 4, 3, 2]),
                             compressor=StochasticQuantizeCompressor(bits=8),
                             faults=BernoulliFaults(p=0.25, seed=5))
            es = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3))
            eh = PSEngine(game.problem, pscfg, rng=jax.random.PRNGKey(3),
                          mesh=mesh)
            close(es.run(), eh.run(), rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(es.state.t),
                                          np.asarray(eh.state.t))
        print("ZOO_SHARDED_OK")
    """)
    assert "ZOO_SHARDED_OK" in out


def test_train_round_multidevice_matches_singledevice():
    """One LocalAdaSEG round on a 4×2 mesh must equal the same round on one
    device (GSPMD partitioning is semantics-preserving for our round_fn)."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.core.adaseg import AdaSEGConfig
        from repro.launch.mesh import make_test_mesh
        from repro.launch.train import (TrainPlan, init_train_state,
                                        make_batches, make_round_fn,
                                        make_shardings)
        cfg = smoke_config("qwen2-0.5b")
        mesh = make_test_mesh(4, 2)
        plan = TrainPlan(cfg=cfg,
                         adaseg=AdaSEGConfig(g0=5.0, diameter=1.0, alpha=0.5,
                                             k=2, average_output=False),
                         worker_mode="paper", k_local=2,
                         global_batch=8, seq=16)
        state = init_train_state(jax.random.PRNGKey(0), plan, mesh)
        batches = make_batches(jax.random.PRNGKey(1), plan, mesh)
        round_fn = make_round_fn(plan)

        # single-device reference
        ref_state, ref_metrics = jax.jit(round_fn)(state, batches)

        state_sh, batch_sh = make_shardings(plan, mesh)
        with mesh:
            got_state, got_metrics = jax.jit(
                round_fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
            )(jax.device_put(state, state_sh),
              jax.device_put(batches, batch_sh))
        np.testing.assert_allclose(np.asarray(ref_metrics["loss"]),
                                   np.asarray(got_metrics["loss"]),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(ref_state.sum_sq),
                                   np.asarray(got_state.sum_sq),
                                   rtol=2e-3)
        print("ROUND_MATCH_OK")
    """)
    assert "ROUND_MATCH_OK" in out


def test_dryrun_smoke_mesh():
    """Lower + compile one train round and one serve step on a small mesh
    end-to-end through the dryrun entry points."""
    out = run_in_subprocess("""
        import jax, json
        from repro.launch.mesh import make_test_mesh
        from repro.launch.dryrun import run_one
        mesh = make_test_mesh(4, 2)
        recs = []
        for arch, shape in [("qwen2-0.5b", "train_4k"),
                            ("granite-moe-1b-a400m", "decode_32k"),
                            ("mamba2-370m", "long_500k")]:
            rec = run_one(arch, shape, mesh, "test4x2", k_local=2)
            assert rec["flops"] > 0
            assert rec["bytes_per_device"] > 0
            recs.append(rec["arch"])
        print("DRYRUN_OK", json.dumps(recs))
    """)
    assert "DRYRUN_OK" in out


def test_multipod_axis_shards():
    """The 'pod' axis must actually shard: hierarchical worker mode on a
    (2, 2, 2) mesh gives M = 2 pod-workers and the sync crosses 'pod'."""
    out = run_in_subprocess("""
        import jax
        from repro.launch.mesh import make_test_mesh
        from repro.launch.dryrun import lower_train
        from repro.roofline.analysis import analyze_compiled
        mesh = make_test_mesh(2, 2, pods=2)
        lowered, compiled, plan = lower_train(
            "qwen2-0.5b", "train_4k", mesh, k_local=1,
            worker_mode="hierarchical")
        assert plan.num_workers(mesh) == 2
        rec = analyze_compiled(lowered, compiled, mesh)
        axes = rec["collective_bytes_by_axis"]
        assert any("pod" in a for a in axes), axes
        print("MULTIPOD_OK", axes)
    """)
    assert "MULTIPOD_OK" in out


def test_fused_codec_sharded_matches_serial():
    """Fused-codec acceptance on the sharded path (the PR's codec_backend
    switch through shard_map): for every built-in codec the sharded fused
    engine must match the serial fused engine within the rtol=1e-5 bar
    (and the sharded reference engine at the same tolerance), under the
    full hetero-K + faults policy stack; the fused compressed-psum hook
    must agree with the reference hook through the one-shot driver."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AdaSEGConfig
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharded import run_local_adaseg_sharded
        from repro.problems import make_bilinear_game
        from repro.ps import (BernoulliFaults, FixedSchedule, PSConfig,
                              PSEngine, StochasticQuantizeCompressor,
                              TopKCompressor, make_compressed_psum_sync)

        game = make_bilinear_game(jax.random.PRNGKey(0), n=10, sigma=0.1)
        cfg = AdaSEGConfig(g0=1.0, diameter=2.0, alpha=1.0, k=5)
        mesh = make_test_mesh(4, 2)

        def close(a, b, **kw):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)

        for comp in (TopKCompressor(fraction=0.25),
                     StochasticQuantizeCompressor(bits=8)):
            kw = dict(adaseg=cfg, num_workers=4, rounds=4,
                      schedule=FixedSchedule([5, 4, 3, 2]), compressor=comp,
                      faults=BernoulliFaults(p=0.25, seed=5))
            serial = PSEngine(game.problem,
                              PSConfig(codec_backend="fused", **kw),
                              rng=jax.random.PRNGKey(3))
            sharded = PSEngine(game.problem,
                               PSConfig(codec_backend="fused", **kw),
                               rng=jax.random.PRNGKey(3), mesh=mesh)
            sharded_ref = PSEngine(game.problem,
                                   PSConfig(codec_backend="reference", **kw),
                                   rng=jax.random.PRNGKey(3), mesh=mesh)
            z_ser, z_sh, z_ref = serial.run(), sharded.run(), sharded_ref.run()
            close(z_ser, z_sh, rtol=1e-5, atol=1e-6)
            close(z_ref, z_sh, rtol=1e-5, atol=1e-6)
            close(serial.state, sharded.state, rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(serial.state.t),
                                          np.asarray(sharded.state.t))

        # stateless fused hook through the one-shot driver: identical keys →
        # identical quantization decisions, so reference ≡ fused to rtol.
        for frac_comp in (StochasticQuantizeCompressor(bits=8),):
            z_r, _ = run_local_adaseg_sharded(
                game.problem, cfg, mesh=mesh, rounds=4,
                rng=jax.random.PRNGKey(2),
                sync_fn=make_compressed_psum_sync(("data",), frac_comp))
            z_f, _ = run_local_adaseg_sharded(
                game.problem, cfg, mesh=mesh, rounds=4,
                rng=jax.random.PRNGKey(2),
                sync_fn=make_compressed_psum_sync(("data",), frac_comp,
                                                  codec_backend="fused"))
            close(z_r, z_f, rtol=1e-5, atol=1e-6)
        print("FUSED_CODEC_SHARDED_OK")
    """)
    assert "FUSED_CODEC_SHARDED_OK" in out


def test_model_worker_sharded_matches_serial():
    """The unified stack's acceptance bar: a real transformer ModelWorker
    runs the shard_map engine path (q8-EF uplinks through the fused Pallas
    sync codec) and matches the serial vmap engine at rtol=1e-5 — real
    model pytrees get the PR-2…5 runtime for free."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AdaSEGConfig
        from repro.launch.mesh import make_test_mesh
        from repro.models import ModelWorker, make_lm_problem, tiny_lm_config
        from repro.ps import PSConfig, PSEngine, StochasticQuantizeCompressor

        problem = make_lm_problem(tiny_lm_config(), batch=2, seq=8)
        worker = ModelWorker(
            AdaSEGConfig(g0=20.0, diameter=2.0, alpha=1.0, k=2,
                         average_output=False),
            arch="tiny-lm")
        mesh = make_test_mesh(2, 2)
        kw = dict(worker=worker, local_k=2, num_workers=2, rounds=2,
                  compressor=StochasticQuantizeCompressor(bits=8),
                  codec_backend="fused")
        serial = PSEngine(problem, PSConfig(**kw),
                          rng=jax.random.PRNGKey(1))
        sharded = PSEngine(problem, PSConfig(**kw),
                           rng=jax.random.PRNGKey(1), mesh=mesh,
                           worker_axes=("data",))
        z_ser, z_sh = serial.run(), sharded.run()
        for a, b in zip(jax.tree.leaves(z_ser), jax.tree.leaves(z_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(serial.state),
                        jax.tree.leaves(sharded.state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        print("MODEL_WORKER_SHARDED_OK")
    """, devices=4)
    assert "MODEL_WORKER_SHARDED_OK" in out
