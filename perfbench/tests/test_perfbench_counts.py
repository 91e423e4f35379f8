"""The benchmark's FLOP and byte counts against hand counts at a tiny
configuration, and its seeded weights against the program's layout."""
import jax
import numpy as np

from perfbench import counts, tokens
from perfbench.reference import qwen2 as ref_model

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 32,
        "tie_word_embeddings": True, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6}


def test_lm_counts_by_hand():
    # per layer: wq 8·2·4 + wo 2·4·8 = 128, wk + wv 2·8·1·4 = 64,
    # MLP 3·8·16 = 384 → 576; head (tied) 32·8 = 256
    assert counts.lm_matmul_params(TINY) == 3 * 576 + 256
    # + biases (2 + 2·1)·4 = 16 and two norms 16 per layer, final norm 8
    assert counts.lm_params(TINY) == 3 * 576 + 256 + 3 * 32 + 8
    # 6 per matmul parameter + causal attention 6·L·S·d at S = 4
    assert counts.lm_flops_per_token(TINY, 4) == 6 * (3 * 576 + 256) \
        + 6 * 3 * 4 * 8
    # explore: read z̃, M_t, write z_t; anchor: read z̃, z_t, g_t, write z̃'
    assert counts.adaseg_update_bytes(10) == 7 * 10 * 4


def test_lm_params_match_the_seeded_weights_and_the_program():
    from repro.configs.base import ArchConfig
    from repro.models.transformer import init_model

    w = jax.eval_shape(lambda: ref_model.init_params(
        jax.random.PRNGKey(0), TINY))
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(w)) \
        == counts.lm_params(TINY)
    cfg = ArchConfig(name="t", arch_type="dense", num_layers=3, d_model=8,
                     num_heads=2, num_kv_heads=1, d_ff=16, vocab_size=32,
                     qkv_bias=True, tie_embeddings=True)
    p = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg)[0])
    assert jax.tree.structure(p) == jax.tree.structure(w)
    assert jax.tree.leaves(p) == jax.tree.leaves(w)


def test_token_stream_is_seeded_and_matches_the_programs_distribution():
    from repro.data.synthetic import sample_tokens

    sample = jax.jit(tokens.make_sampler(vocab=50, batch=256, seq=128,
                                         zipf_alpha=1.2, markov_p=0.3))
    a = sample(tokens.subkey(2 ** 33 + 5, 4))
    b = sample(tokens.subkey(2 ** 33 + 5, 4))
    c = sample(tokens.subkey(5, 4))
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    t = np.asarray(a["tokens"])
    assert t.shape == (256, 128) and t.min() >= 0 and t.max() < 50
    assert np.array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    ref = np.asarray(sample_tokens(jax.random.PRNGKey(3), 256, 128, 50))

    def stats(x):
        follow = np.mean((x[:, 1:] - x[:, :-1]) % 50 == 1)
        return [follow] + [np.mean(x == v) for v in range(4)]

    assert np.allclose(stats(t), stats(ref), atol=0.01)


def test_seed_key_keeps_all_64_bits():
    k1 = np.asarray(tokens.seed_key(2 ** 33 + 5))
    k2 = np.asarray(tokens.seed_key(5))
    assert k1.tolist() == [2, 5] and k2.tolist() == [0, 5]
