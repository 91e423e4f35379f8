"""Per-kernel correctness sweeps: Pallas (interpret mode) vs pure-jnp oracle
across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adaseg_update.kernel import adaseg_update
from repro.kernels.adaseg_update.ref import adaseg_update_ref
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.kernel import (flash_attention,
                                                  flash_attention_fwd)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 2, 2, 64, 32),      # MHA
    (2, 4, 2, 128, 64),     # GQA 2:1
    (1, 8, 1, 64, 64),      # MQA
    (1, 4, 4, 96, 32),      # non-power-of-two seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, h, kh, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kh, s, d), dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("feature", ["window", "softcap", "noncausal", "scale"])
def test_flash_attention_features(feature):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64))
    k = jax.random.normal(ks[1], (2, 2, 128, 64))
    v = jax.random.normal(ks[2], (2, 2, 128, 64))
    kwargs = {
        "window": dict(causal=True, window=32),
        "softcap": dict(causal=True, softcap=20.0),
        "noncausal": dict(causal=False),
        "scale": dict(causal=True, scale=0.05),
    }[feature]
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True,
                          **kwargs)
    ref = attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_invariance():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [
        flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        for bq, bk in [(32, 32), (64, 128), (256, 64), (128, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-5)


# (heads, kv heads, seq, attention kwargs, dtype): the backward kernel's
# features, each a case of one sweep against the VJP of the reference.
BWD_CASES = {
    "mha": (4, 4, 128, dict(), jnp.float32),
    "gqa2": (4, 2, 128, dict(), jnp.float32),
    "gqa7": (7, 1, 128, dict(), jnp.float32),
    "window": (4, 2, 128, dict(window=40), jnp.float32),
    "softcap": (4, 2, 128, dict(softcap=5.0), jnp.float32),
    "noncausal": (4, 2, 128, dict(causal=False), jnp.float32),
    "scale": (4, 2, 128, dict(scale=0.3), jnp.float32),
    "padded": (4, 2, 100, dict(window=40, softcap=5.0), jnp.float32),
    "bf16": (4, 2, 128, dict(), jnp.bfloat16),
    "gqa7_bf16": (7, 1, 128, dict(window=40), jnp.bfloat16),
}


def _qkv(key, h, kh, s, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (2, h, s, d), dtype)
    k = jax.random.normal(ks[1], (2, kh, s, d), dtype)
    v = jax.random.normal(ks[2], (2, kh, s, d), dtype)
    do = jax.random.normal(ks[3], (2, h, s, d), dtype)
    return q, k, v, do


def _flash_vjp(q, k, v, do, block_q=32, block_k=32, **kw):
    out, pull = jax.vjp(
        lambda q, k, v: flash_ops.attention(q, k, v, block_q=block_q,
                                            block_k=block_k, **kw), q, k, v)
    return out, pull(do)


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_attention_backward_matches_reference_vjp(case):
    h, kh, s, kw, dtype = BWD_CASES[case]
    q, k, v, do = _qkv(jax.random.PRNGKey(9), h, kh, s, dtype=dtype)
    out, grads = _flash_vjp(q, k, v, do, **kw)
    ref, pull = jax.vjp(lambda q, k, v: attention_ref(q, k, v, **kw), q, k, v)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(f32(out), f32(ref), **_tol(dtype))
    for name, g, r in zip("qkv", grads, pull(do)):
        assert g.dtype == r.dtype == dtype, name
        np.testing.assert_allclose(f32(g), f32(r), err_msg=name,
                                   **_tol(dtype))


@pytest.mark.parametrize("bq,bk", [(64, 128), (128, 32), (16, 64)])
def test_flash_attention_backward_block_shape_invariance(bq, bk):
    """Gradients must not depend on the backward's tiling."""
    q, k, v, do = _qkv(jax.random.PRNGKey(10), 4, 2, 128)
    kw = dict(window=48)
    _, base = _flash_vjp(q, k, v, do, 32, 32, **kw)
    _, grads = _flash_vjp(q, k, v, do, bq, bk, **kw)
    for g, b in zip(grads, base):
        np.testing.assert_allclose(g, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kw", [dict(), dict(window=24, softcap=5.0)],
                         ids=["causal", "window_softcap"])
def test_flash_attention_lse_matches_reference_logsumexp(kw):
    q, k, v, _ = _qkv(jax.random.PRNGKey(11), 4, 2, 128)
    _, lse = flash_attention_fwd(q, k, v, block_q=32, block_k=32,
                                 interpret=True, **kw)
    s = jnp.einsum("bkgsd,bktd->bkgst", q.reshape(2, 2, 2, 128, 32), k)
    s = s * 32 ** -0.5
    if "softcap" in kw:
        s = kw["softcap"] * jnp.tanh(s / kw["softcap"])
    qi, ki = jnp.arange(128)[:, None], jnp.arange(128)[None, :]
    mask = (ki <= qi) & (ki > qi - kw.get("window", 128))
    ref = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, ref.reshape(2, 4, 128), rtol=1e-5,
                               atol=1e-5)


def test_flash_attention_grad_materializes_no_score_matrix():
    """Under ``jax.grad`` the Pallas backend holds no (S, T) array: only
    (block_q, block_k) tiles inside the kernels."""
    q, k, v, _ = _qkv(jax.random.PRNGKey(12), 4, 2, 256, d=64)

    def loss(q, k, v):
        return flash_ops.attention(q, k, v, block_q=128,
                                   block_k=128).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    shapes = []

    def walk(jx):
        if isinstance(jx, jax.extend.core.ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            shapes.extend(x.aval.shape for x in eqn.invars + eqn.outvars
                          if hasattr(x.aval, "shape"))
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else [p]:
                    if isinstance(sub, (jax.extend.core.Jaxpr,
                                        jax.extend.core.ClosedJaxpr)):
                        walk(sub)

    walk(jaxpr)
    assert (128, 128) in {sh[-2:] for sh in shapes}   # the kernels' tiles
    assert not [sh for sh in shapes if sh[-2:] == (256, 256)]


@pytest.mark.parametrize("n", [64, 1000, 4096, 5000])
@pytest.mark.parametrize("box", [None, (-1.0, 1.0)])
def test_adaseg_update_kernel(n, box):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    z = jax.random.normal(ks[0], (n,))
    m = jax.random.normal(ks[1], (n,))
    g = jax.random.normal(ks[2], (n,))
    lo, hi = box if box else (None, None)
    z_t, z_tl, part = adaseg_update(z, m, g, 0.3, lo=lo, hi=hi,
                                    block=1024, interpret=True)
    rz, rtl, rpart = adaseg_update_ref(z, m, g, 0.3, lo=lo, hi=hi)
    np.testing.assert_allclose(z_t, rz, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(z_tl, rtl, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(part), float(rpart), rtol=1e-4)


def test_adaseg_update_fused_eta_matches_host_eta():
    """η = D·α/√(G₀²+Σ) computed in-kernel must equal passing η directly."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    n = 1000
    z, m, g = (jax.random.normal(k, (n,)) for k in ks)
    g0, d_alpha, sum_sq = 1.5, 2.0, 7.0
    eta = d_alpha / np.sqrt(g0**2 + sum_sq)
    z_t, z_tl, part = adaseg_update(
        z, m, g, sum_sq=jnp.float32(sum_sq), g0=g0, d_alpha=d_alpha,
        lo=-1.0, hi=1.0, block=256, interpret=True,
    )
    rz, rtl, rpart = adaseg_update_ref(z, m, g, eta, lo=-1.0, hi=1.0)
    np.testing.assert_allclose(z_t, rz, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(z_tl, rtl, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(part), float(rpart), rtol=1e-5)


def test_adaseg_update_pad_mask_box_above_zero():
    """A box with lo > 0 must not leak clip(0) from the zero-padded tail
    into the (Z_t)² statistic (n chosen to force padding)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    n = 1000  # pad = 24 at block=128
    z, m, g = (jax.random.normal(k, (n,)) for k in ks)
    z_t, z_tl, part = adaseg_update(z, m, g, 0.3, lo=0.5, hi=1.0,
                                    block=128, interpret=True)
    rz, rtl, rpart = adaseg_update_ref(z, m, g, 0.3, lo=0.5, hi=1.0)
    np.testing.assert_allclose(z_t, rz, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(part), float(rpart), rtol=1e-5)


def test_adaseg_explore_anchor_match_refs():
    """The step-path primitives (explore + anchor) against their oracles."""
    from repro.kernels.adaseg_update.kernel import (adaseg_anchor,
                                                    adaseg_explore)
    from repro.kernels.adaseg_update.ref import (adaseg_anchor_ref,
                                                 adaseg_explore_ref)

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    n = 777
    z, m, g = (jax.random.normal(k, (n,)) for k in ks)
    kw = dict(sum_sq=jnp.float32(3.0), g0=1.0, d_alpha=2.0)
    z_t, nrm, msq = adaseg_explore(z, m, lo=-1.0, hi=1.0, block=256,
                                   interpret=True, **kw)
    rz, rnrm, rmsq = adaseg_explore_ref(z, m, lo=-1.0, hi=1.0, **kw)
    np.testing.assert_allclose(z_t, rz, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(msq), float(rmsq), rtol=1e-5)

    ztl, stat, gsq = adaseg_anchor(z, z_t, g, lo=-1.0, hi=1.0, block=256,
                                   interpret=True, **kw)
    rtl, rstat, rgsq = adaseg_anchor_ref(z, rz, g, lo=-1.0, hi=1.0, **kw)
    np.testing.assert_allclose(ztl, rtl, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(stat), float(rstat), rtol=1e-5)
    np.testing.assert_allclose(float(gsq), float(rgsq), rtol=1e-5)


def test_adaseg_tree_update_l2_matches_tree_reference():
    """The kernel two-pass l2 scheme == reference tree-level projection."""
    from repro.core import projections
    from repro.core.tree import tree_norm
    from repro.kernels.adaseg_update.ops import adaseg_tree_update

    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    tree = {"a": jax.random.normal(ks[0], (300,)),
            "b": jax.random.normal(ks[1], (4, 50))}
    m = {"a": jax.random.normal(ks[2], (300,)),
         "b": jax.random.normal(ks[3], (4, 50))}
    g = {"a": jax.random.normal(ks[4], (300,)),
         "b": jax.random.normal(ks[5], (4, 50))}
    radius, eta = 1.2, 0.4
    z_t, z_tl, z_sq = adaseg_tree_update(tree, m, g, eta,
                                         proj=("l2", radius), block=128)

    proj = projections.l2_ball(radius)
    from repro.core.tree import tree_axpy, tree_norm_sq, tree_sub

    rz_t = proj(tree_axpy(-eta, m, tree))
    rz_tl = proj(tree_axpy(-eta, g, tree))
    rz_sq = (tree_norm_sq(tree_sub(rz_t, tree))
             + tree_norm_sq(tree_sub(rz_t, rz_tl))) / (5.0 * eta**2)
    for k in tree:
        np.testing.assert_allclose(z_t[k], rz_t[k], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(z_tl[k], rz_tl[k], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(z_sq), float(rz_sq), rtol=1e-4)
    # the candidates genuinely left the ball, so the scaling pass fired
    assert float(tree_norm(z_t)) <= radius + 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adaseg_update_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    z = jax.random.normal(ks[0], (512,), dtype)
    m = jax.random.normal(ks[1], (512,), dtype)
    g = jax.random.normal(ks[2], (512,), dtype)
    z_t, z_tl, part = adaseg_update(z, m, g, 0.1, block=128, interpret=True)
    rz, rtl, rpart = adaseg_update_ref(z, m, g, jnp.asarray(0.1, dtype))
    np.testing.assert_allclose(
        z_t.astype(np.float32), rz.astype(np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("l,chunk", [(64, 8), (64, 16), (128, 64), (96, 32)])
@pytest.mark.parametrize("h,p,n", [(2, 16, 32), (4, 32, 16)])
def test_ssd_scan_kernel(l, chunk, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (2, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (2, l, n))
    c = jax.random.normal(ks[4], (2, l, n))
    out = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ssd_chunk_invariance():
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(ks[0], (1, 128, 2, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 2)))
    a = -jnp.exp(jax.random.normal(ks[2], (2,)))
    b = jax.random.normal(ks[3], (1, 128, 8))
    c = jax.random.normal(ks[4], (1, 128, 8))
    outs = [ssd_scan(x, dt, a, b, c, chunk=ch, interpret=True)
            for ch in (8, 16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-4, atol=1e-4)
