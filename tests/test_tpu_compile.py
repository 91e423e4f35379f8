"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode on the CPU never checks Mosaic's tiling rules or VMEM
limits, so these tests hand each kernel of the training path to the TPU
compiler at the widths of qwen2-0.5b (d_model 896, d_ff 4864, vocab
151,936, 14/2 heads of 64) and check that it compiles into a
``tpu_custom_call``. Nothing runs: no chip is needed, only libtpu.

The topology is described inside a module-scoped fixture (never at
import), so that only the test process that runs this file loads libtpu.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adaseg_update import kernel as ak
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.sync_compress import kernel as sk

D_MODEL, D_FF, VOCAB = 896, 4864, 151_936
EMBED = VOCAB * D_MODEL          # the tied embedding: 136M elements
MLP = D_MODEL * D_FF             # one layer's gate/up/down leaf
BIAS = D_MODEL                   # a leaf smaller than one (8, 128) tile
M = 2                            # workers stacked on one chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, U32 = jnp.float32, jnp.uint32


# ---------------------------------------------------------------------------
# adaseg_update: the fused step, per leaf and vmapped over the workers (the
# engine's form).
# ---------------------------------------------------------------------------

def _explore(z, m, s):
    return ak.adaseg_explore(z, m, sum_sq=s[0, 0], g0=1.0, interpret=False)


def _explore_l2(z, m, s):
    return ak.adaseg_explore(z, m, sum_sq=s[0, 0], want_norm=True,
                             interpret=False)


def _anchor(z, zt, g, s):
    return ak.adaseg_anchor(z, zt, g, sum_sq=s[0, 0], g0=1.0, lo=-1.0,
                            hi=1.0, interpret=False)


def _update(z, m, g, s):
    return ak.adaseg_update(z, m, g, sum_sq=s[0, 0], g0=1.0,
                            interpret=False)


def _update_raw(z, m, g, s):
    return ak.adaseg_update(z, m, g, eta=s[0, 0], raw_norms=True,
                            interpret=False)


def _finish(z, a, b, s):
    return ak.adaseg_finish(z, a, b, s[0, 0], s[0, 0], interpret=False)


STEP_KERNELS = {
    "explore": (_explore, 2),
    "explore_l2": (_explore_l2, 2),
    "anchor": (_anchor, 3),
    "update": (_update, 3),
    "update_raw": (_update_raw, 3),
    "finish": (_finish, 3),
}


@pytest.mark.parametrize("n", [EMBED, MLP, BIAS],
                         ids=["embed", "mlp", "bias"])
@pytest.mark.parametrize("name", sorted(STEP_KERNELS))
def test_step_kernel_compiles(one_chip, name, n):
    fn, vecs = STEP_KERNELS[name]
    _compile(fn, [((n,), F32)] * vecs + [((1, 1), F32)], one_chip)


@pytest.mark.parametrize("name", ["explore", "anchor"])
def test_step_kernel_vmapped_over_workers_compiles(one_chip, name):
    fn, vecs = STEP_KERNELS[name]
    _compile(jax.vmap(fn), [((M, MLP), F32)] * vecs + [((M, 1, 1), F32)],
             one_chip)


def test_step_kernel_bf16_leaf_compiles(one_chip):
    _compile(_update, [((MLP,), jnp.bfloat16)] * 3 + [((1, 1), F32)],
             one_chip)


# ---------------------------------------------------------------------------
# sync_compress: uplink, merges and the outer step on M-stacked leaves.
# ---------------------------------------------------------------------------

def _stats(z, w, ef):
    return sk.uplink_stats(z, w, ef, interpret=False)


def _quantize(z, seeds, scale, w, ef, alive):
    return sk.quantize_uplink(z, seeds, scale, w, ef, alive, levels=255.0,
                              interpret=False)


def _eff(z, w, ef):
    return sk.eff_uplink(z, w, ef, interpret=False)


def _mask(eff, mask, ef, alive):
    return sk.mask_uplink(eff, mask, ef, alive, interpret=False)


def _merge(z, w, recv):
    return sk.merge_stacked(z, w, recv, z, normalize=True, interpret=False)


def _trimmed(z, w, recv):
    return sk.trimmed_merge_stacked(z, w, (w > 0).astype(F32), recv, z,
                                    trim=1, interpret=False)


CODEC_KERNELS = ["stats", "quantize", "eff", "mask", "merge", "trimmed"]


def _codec_args(name, m, n):
    leaf, vec = ((m, n), F32), ((m,), F32)
    return {
        "stats": (_stats, [leaf, vec, leaf]),
        "quantize": (_quantize, [leaf, ((m, 2), U32), vec, vec, leaf, vec]),
        "eff": (_eff, [leaf, vec, leaf]),
        "mask": (_mask, [leaf, leaf, leaf, vec]),
        "merge": (_merge, [leaf, vec, vec]),
        "trimmed": (_trimmed, [leaf, vec, vec]),
    }[name]


@pytest.mark.parametrize("n", [EMBED, MLP, BIAS],
                         ids=["embed", "mlp", "bias"])
@pytest.mark.parametrize("name", CODEC_KERNELS)
def test_codec_kernel_compiles(one_chip, name, n):
    _compile(*_codec_args(name, M, n), one_chip)


@pytest.mark.parametrize("name", CODEC_KERNELS)
def test_codec_kernel_per_shard_compiles(one_chip, name):
    """M=1: the per-shard uplink of the shard_map engine."""
    _compile(*_codec_args(name, 1, MLP), one_chip)


@pytest.mark.parametrize("spec", [("nesterov", 0.7, 0.9),
                                  ("adam", 1e-3, 0.9, 0.99, 1e-8)],
                         ids=lambda s: s[0])
def test_outer_apply_compiles(one_chip, spec):
    slots = 2 if spec[0] == "adam" else 1

    def fn(merged, z, t, *mom):
        return sk.outer_apply(merged, z, mom, t, spec=spec, interpret=False)

    leaf = ((1, MLP), F32)
    _compile(fn, [leaf, leaf, ((), F32)] + [leaf] * slots, one_chip)


# ---------------------------------------------------------------------------
# The model kernels.
# ---------------------------------------------------------------------------

def test_flash_attention_compiles(one_chip):
    fn = functools.partial(flash_attention, causal=True, block_q=512,
                           block_k=512)
    bf16 = jnp.bfloat16
    _compile(fn, [((1, 14, 2048, 64), bf16), ((1, 2, 2048, 64), bf16),
                  ((1, 2, 2048, 64), bf16)], one_chip)


def test_ssd_scan_compiles(one_chip):
    b, l, h, p, n = 2, 2048, 32, 64, 128
    _compile(functools.partial(ssd_scan, chunk=128),
             [((b, l, h, p), F32), ((b, l, h), F32), ((h,), F32),
              ((b, l, n), F32), ((b, l, n), F32)], one_chip)
