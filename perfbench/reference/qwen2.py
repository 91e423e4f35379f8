"""Plain reference of the Qwen2 decoder (arXiv:2407.10671) for the LM cells,
and the benchmark's own weights.

The forward pass follows the published equations: token embedding, per
layer pre-RMSNorm → GQA self-attention with QKV bias and rotate-half RoPE
(θ from the config) → residual, pre-RMSNorm → SwiGLU MLP → residual; a
final RMSNorm and the LM head tied to the embedding; mean next-token
cross-entropy. Straight ``jax.numpy``, one layer after another, no
kernels, no cache, no scan. Precision is the caller's: the check runs it
at float32 under ``jax.default_matmul_precision("highest")``, its control
at bfloat16.

Weights are laid out as the program under test stores them (a dict with
``embed``, ``final_norm`` and one ``stages`` entry whose leaves carry a
leading layer axis), so the same seeded tree feeds both. They are made here
from the seed, never by the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEAD_ROWS = 512         # tokens whose logits the head holds at a time


def shapes(c: dict) -> dict:
    """Leaf shapes of the weights for config ``c`` (Hugging Face keys)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    dh, n, v = d // h, c["num_hidden_layers"], c["vocab_size"]
    return {
        "embed": {"table": (v, d)},
        "final_norm": {"scale": (d,)},
        "stages": [{
            "pre_norm": {"scale": (n, d)},
            "mixer": {"wq": (n, d, h, dh), "wk": (n, d, kh, dh),
                      "wv": (n, d, kh, dh), "wo": (n, h, dh, d),
                      "bq": (n, h, dh), "bk": (n, kh, dh), "bv": (n, kh, dh)},
            "mlp_norm": {"scale": (n, d)},
            "mlp": {"w_in": (n, d, f), "w_gate": (n, d, f),
                    "w_out": (n, f, d)},
        }],
    }


def _std(path: str, shape: tuple) -> float | None:
    """Init scale of a leaf: None = ones (norm scales), 0 = zeros
    (biases), else the normal's standard deviation (fan-in scaled)."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return None
    if leaf.startswith("b"):
        return 0.0
    if leaf == "table":
        return 0.02
    if leaf == "wo":
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5       # (n, fan_in, ...) projections


def init_params(key, c: dict, dtype=jnp.float32):
    """The seeded weights: leaf i drawn from ``fold_in(key, i)``."""
    paths = []

    def visit(node, prefix):
        if isinstance(node, dict):
            return {k: visit(v, f"{prefix}/{k}") for k, v in node.items()}
        if isinstance(node, list):
            return [visit(v, f"{prefix}/{i}") for i, v in enumerate(node)]
        i = len(paths)
        paths.append(prefix)
        std = _std(prefix, node)
        if std is None:
            return jnp.ones(node, dtype)
        if std == 0.0:
            return jnp.zeros(node, dtype)
        return (std * jax.random.normal(jax.random.fold_in(key, i), node,
                                        jnp.float32)).astype(dtype)

    return visit(shapes(c), "")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** -(jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def loss(params, c: dict, batch, dtype=jnp.float32):
    """Mean next-token cross-entropy of ``batch`` under ``params``. The
    layers run as one scan; each layer, and the head over each block of
    ``HEAD_ROWS`` tokens, is recomputed in the backward pass, so a long
    sequence keeps one layer's attention scores and one block's logits at
    a time."""
    p = jax.tree.map(lambda v: v.astype(dtype), params)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    tokens = batch["tokens"]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(x, a, m, pre, post):
        y = _rmsnorm(x, pre, eps)
        q = jnp.einsum("bsd,dhk->bshk", y, a["wq"]) + a["bq"]
        k = jnp.einsum("bsd,dhk->bshk", y, a["wk"]) + a["bk"]
        v = jnp.einsum("bsd,dhk->bshk", y, a["wv"]) + a["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kh, axis=2)         # head j reads kv j // g
        v = jnp.repeat(v, h // kh, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        sc = jnp.where(causal, sc * q.shape[-1] ** -0.5, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
        y = _rmsnorm(x, post, eps)
        return x + (jax.nn.silu(y @ m["w_gate"]) * (y @ m["w_in"])) @ m["w_out"]

    st = p["stages"][0]
    x, _ = jax.lax.scan(
        lambda x, w: (layer(x, *w), None), p["embed"]["table"][tokens],
        (st["mixer"], st["mlp"], st["pre_norm"]["scale"],
         st["mlp_norm"]["scale"]))
    x = _rmsnorm(x, p["final_norm"]["scale"], eps)
    table = p["embed"]["table"]

    @jax.checkpoint
    def head(xl):
        """Summed cross-entropy of one block of rows."""
        x_rows, labels = xl
        logits = (x_rows @ table.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt)

    rows = x.reshape(-1, x.shape[-1])
    labels = batch["labels"].reshape(-1)
    block = math.gcd(HEAD_ROWS, rows.shape[0])
    nb = rows.shape[0] // block
    sums = jax.lax.map(head, (rows.reshape(nb, block, -1),
                              labels.reshape(nb, block)))
    return jnp.sum(sums) / labels.shape[0]
