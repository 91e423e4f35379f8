"""Operations and bytes the work requires, from the configuration's shapes.

Every share of a peak or a roofline divides one of these by a measured
time. They count the work the algorithm needs, not what an implementation
streams, so a share reads the same work whichever kernels run it and
cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

F32 = 4


def lm_matmul_params(c: dict) -> int:
    """Parameters that multiply activations: per layer the Q/K/V/O
    projections and the gated MLP, plus the LM head (tied to the
    embedding, so counted once as a matmul)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    dh = d // h
    per_layer = d * h * dh * 2 + d * kh * dh * 2 + 3 * d * f
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def lm_params(c: dict) -> int:
    """Every parameter the optimizer updates."""
    d = c["hidden_size"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    dh = d // h
    per_layer_extra = (h + 2 * kh) * dh + 2 * d     # QKV bias, two norms
    head = 0 if c["tie_word_embeddings"] else c["vocab_size"] * d
    return (lm_matmul_params(c) + head
            + c["num_hidden_layers"] * per_layer_extra + d)


def lm_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs of one training token: 6 per matmul
    parameter, plus causal attention's scores and weighted sum (on average
    seq/2 keys per query: 2·seq·H·Dh forward per layer, three times that
    with the backward)."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    return 6.0 * lm_matmul_params(c) + 6.0 * layers * seq * d


def adaseg_update_bytes(params: int, itemsize: int = F32) -> int:
    """Least HBM bytes of one worker's AdaSEG update in one local step.
    The oracle call at z_t sits between the step's two updates, so they
    are two passes at least: the exploration pass reads z̃ and M_t and
    writes z_t; the anchor pass reads z̃, z_t and g_t (its Z² needs
    ‖z_t − z̃'‖) and writes the new z̃. Seven streams of the parameters."""
    return 7 * params * itemsize
