"""Language-model cells: a decoder trained by LocalAdaSEG through
``repro.ps.PSEngine``, built as ``launch.train.make_ps_engine`` builds it
(``ModelWorker`` over ``models.transformer.loss_fn``), with the benchmark's
token stream as the problem's sampler and the benchmark's seeded weights as
its init. ``placement: "mesh"`` puts one worker on each chip through the
engine's ``shard_map`` round; ``"serial"`` stacks the workers on one chip.

``LMCell`` is shared by every language-model system; what is particular to
a model it takes from the system: ``arch(config)``, the program's
``ArchConfig``; ``reference``, the plain model (``init_params``, ``loss``);
``counts(config, seq)``, the operations and bytes of its shapes. This
module is the Qwen2 system and passes Qwen2's three.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from ..counts import adaseg_update_bytes, lm_flops_per_token, lm_params
from ..reference import adaseg as ref_alg
from ..reference import qwen2 as reference
from ..tokens import make_sampler, subkey

ROUNDS_PER_SECOND = 10       # more than any LM cell can run
EVAL_KEY = 987               # the held-out batch, the same for every seed

VARIANTS = ("bfloat16", "half_batch", "no_exchange")  # control first

# The tests' CPU size: widths and depth over the config's, and the traffic's
# sequence length.
TINY = {"hidden_size": 32, "intermediate_size": 64,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "num_hidden_layers": 1, "vocab_size": 128}
TINY_TRAFFIC = {"seq": 8}


def context(config):
    prec = config["run"]["matmul_precision"]
    return (contextlib.nullcontext() if prec == "default"
            else jax.default_matmul_precision(prec))


def arch(config):
    from repro.configs.base import ArchConfig

    run = config["run"]
    return ArchConfig(
        name=config["name"], arch_type="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qkv_bias=run["qkv_bias"], tie_embeddings=config["tie_word_embeddings"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        attn_backend=run["attn_backend"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
    )


def counts(config, seq):
    params = lm_params(config)
    return {"flops_per_token": lm_flops_per_token(config, seq),
            "update_bytes_per_worker_step": adaseg_update_bytes(params),
            "params": params}


def _alpha(rule, workers):
    return 1.0 / math.sqrt(workers) if rule == "1/sqrt(workers)" else rule


class LMCell:
    def __init__(self, config, traffic, *, arch, reference, counts, seed,
                 seconds, tracer, engine=True):
        from repro.core import AdaSEGConfig, projections
        from repro.core.types import MinimaxProblem
        from repro.models import transformer
        from repro.models.worker import ModelWorker
        from repro.ps import PSConfig, PSEngine, StochasticQuantizeCompressor

        self.config, self.traffic, self.seed = config, traffic, seed
        self.model = reference
        cfg = arch(config)
        cfg.validate()
        m, k = traffic["workers"], traffic["local_steps"]
        b, s = traffic["batch"], traffic["seq"]
        opt = config["adaseg"]
        self.adaseg = AdaSEGConfig(
            g0=opt["g0"], diameter=opt["diameter"],
            alpha=_alpha(opt["alpha"], m), k=k,
            average_output=opt["average_output"])
        tok = traffic["tokens"]
        self.sample = make_sampler(vocab=cfg.vocab_size, batch=b, seq=s,
                                   zipf_alpha=tok["zipf_alpha"],
                                   markov_p=tok["markov_p"])
        self.eval_batch = jax.jit(self.sample)(jax.random.PRNGKey(EVAL_KEY))
        init = jax.jit(lambda key: reference.init_params(key, config))
        want = jax.eval_shape(
            lambda: transformer.init_model(jax.random.PRNGKey(0), cfg)[0])
        got = jax.eval_shape(init, jax.random.PRNGKey(0))
        if (jax.tree.structure(want) != jax.tree.structure(got)
                or jax.tree.leaves(want) != jax.tree.leaves(got)):
            raise SystemExit(f"the model's parameter tree changed: {want} "
                             f"vs the benchmark's weights {got}")
        problem = MinimaxProblem(
            init=init, sample=self.sample,
            oracle=lambda z, xi: jax.grad(transformer.loss_fn)(z, cfg, xi),
            project=projections.identity(),
            name=f"lm[{cfg.name}]x{b}x{s}")
        batch = self.eval_batch
        eval_fn = jax.jit(lambda p: transformer.loss_fn(p, cfg, batch))
        up = traffic["uplink"]
        comp = (StochasticQuantizeCompressor(bits=up["bits"])
                if up["codec"] == "quantize" else None)
        self.levels = float(2 ** up["bits"] - 1) if comp else None
        self.total_rounds = 3 + ROUNDS_PER_SECOND * int(seconds) + 50
        mesh = None
        if traffic["placement"] == "mesh":
            from repro.launch.mesh import make_test_mesh

            mesh = make_test_mesh(data=m, model=1)
        ps = PSConfig(
            num_workers=m, rounds=self.total_rounds,
            worker=ModelWorker(self.adaseg, backend=traffic["backend"],
                               arch=cfg.name),
            local_k=k, compressor=comp,
            codec_backend=traffic["codec_backend"])
        self.engine_key = subkey(seed, 1)
        if engine:
            self.engine = PSEngine(problem, ps, self.engine_key, mesh=mesh,
                                   worker_axes=("data",), eval_fn=eval_fn,
                                   tracer=tracer)
        self.devices = (list(mesh.devices.flat) if mesh is not None
                        else [jax.devices()[0]])
        self.work = {"tokens": m * k * 2 * b * s, "worker_steps": m * k}
        self.counts = counts(config, s)

    def run_round(self, r):
        self.engine.run(until_round=r + 1)

    def free(self):
        del self.engine

    def reference(self, variant=None, rounds=3):
        """Readings of the plain reference at float32 (``highest``), or of
        a control (``"bfloat16"``) or a fault (``"half_batch"``: half of
        each batch's tokens left out, the mean taken over the rest;
        ``"no_exchange"``) put in its place."""
        c, tr, ref_model = self.config, self.traffic, self.model
        bf16 = variant == "bfloat16"
        dtype = jnp.bfloat16 if bf16 else jnp.float32
        sample = self.sample
        if variant == "half_batch":
            half = tr["seq"] // 2

            def sample(key):
                return jax.tree.map(lambda v: v[:, :half], self.sample(key))
        batch = self.eval_batch
        with jax.default_matmul_precision("default" if bf16 else "highest"):
            return ref_alg.readings(
                oracle=jax.jit(jax.grad(
                    lambda p, bt: ref_model.loss(p, c, bt, dtype))),
                sample=jax.jit(sample),
                project=lambda z: z,
                evaluate=jax.jit(
                    lambda p: ref_model.loss(p, c, batch, dtype)),
                init_worker=jax.jit(
                    lambda key: ref_model.init_params(key, c, dtype)),
                key=self.engine_key, workers=tr["workers"],
                local_steps=tr["local_steps"],
                total_rounds=self.total_rounds, g0=self.adaseg.g0,
                d_alpha=self.adaseg.diameter * self.adaseg.alpha,
                levels=self.levels,
                average_output=self.adaseg.average_output, rounds=rounds,
                fault=variant if variant == "no_exchange" else None,
                devices=self.devices)


def build(config, traffic, **run):
    return LMCell(config, traffic, arch=arch, reference=reference,
                  counts=counts, **run)
