"""The benchmark's copies of the program's generators and model-FLOP
arithmetic give what the program's own give today."""

import jax
import numpy as np
import pytest

from chipbench import inputs
from chipbench.counts import lm


@pytest.mark.parametrize("seed", [0, 7])
def test_libsvm_like_matches_program(seed):
    from repro.data.synthetic import LIBSVM_SHAPES, make_libsvm_like

    s = LIBSVM_SHAPES["a1a"]
    want = make_libsvm_like(jax.random.PRNGKey(seed), "a1a")
    a, b = inputs.libsvm_like(jax.random.PRNGKey(seed), s["n"], s["m"], s["d"])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want.a))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(want.b))


@pytest.mark.parametrize("seed,step", [(0, 0), (3, 5)])
def test_token_batch_matches_program(seed, step):
    from repro.data.tokens import TokenPipeline

    want = TokenPipeline(vocab_size=1000, seq_len=64, global_batch=4,
                         seed=seed).batch(step)
    got = inputs.token_batch(jax.random.PRNGKey(seed), step, 1000, 4, 64)
    for k in ("tokens", "targets"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_seed_key_keeps_high_bits():
    a = inputs.seed_key(5)
    b = inputs.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        inputs.seed_key(-1)


def test_model_flops_match_program():
    from repro.configs import get_config
    from repro.launch.roofline import count_params, model_flops
    from repro.launch.shapes import InputShape

    cfg = get_config("qwen2-0.5b")
    ours = dict(hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.kv_heads, vocab_size=cfg.vocab,
                intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers,
                tie_word_embeddings=cfg.tie_embeddings)
    assert lm.params(ours) == count_params(cfg)
    shape = InputShape("train", 1024, 4, "train")
    assert lm.train_flops(ours, 4 * 1024) == model_flops(cfg, shape, "train")
