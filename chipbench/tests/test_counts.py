"""The operation and byte counts against hand counts at two shapes."""

import pytest

from chipbench.counts import fednl, kernels, lm


@pytest.mark.parametrize("n,k,rows,cols,flops,nbytes", [
    # 2 silos x 3 pairs of 8 bytes, a 4 x 5 f32 sum
    (2, 3, 4, 5, 6, 2 * 3 * 8 + 4 * 5 * 4),
    # w8a TopK 3000: 142 x 3000 pairs, a 300 x 300 sum
    (142, 3000, 300, 300, 426_000, 3_408_000 + 360_000),
])
def test_scatter_accum(n, k, rows, cols, flops, nbytes):
    assert kernels.scatter_accum(n, k, rows, cols) == {"flops": flops,
                                                       "bytes": nbytes}


@pytest.mark.parametrize("n,k,rows,cols,tiles", [
    (1, 2048, 128, 128, 1),      # one whole tile
    (142, 1024, 300, 300, 9),    # w8a: 3 x 3 tiles, the last ones padded
])
def test_block_scatter(n, k, rows, cols, tiles):
    c = kernels.block_scatter(n, k, rows, cols)
    assert c["flops"] == n * tiles * k
    assert c["bytes"] == n * tiles * k * 8 + rows * cols * 4


@pytest.mark.parametrize("n,k,rows,cols,tiles", [
    (1, 2048, 896, 4864, 7 * 38),
    (142, 1024, 300, 300, 9),
])
def test_diff_topk_payload(n, k, rows, cols, tiles):
    c = kernels.diff_topk_payload(n, k, rows, cols)
    assert c["flops"] == n * 3 * rows * cols
    assert c["bytes"] == n * (2 * rows * cols * 4 + tiles * k * 8 + tiles * 4)


def test_least_seconds_names_its_bound():
    t, bound = kernels.least_seconds({"flops": 1e3, "bytes": 819e9}, 197e12,
                                     819e9)
    assert (t, bound) == (1.0, "bytes")
    t, bound = kernels.least_seconds({"flops": 197e12, "bytes": 1.0}, 197e12,
                                     819e9)
    assert (t, bound) == (1.0, "flops")


@pytest.mark.parametrize("n,m,d", [(2, 3, 4), (142, 350, 300)])
def test_fednl_round_flops(n, m, d):
    assert fednl.round_flops(n, m, d) == pytest.approx(
        2 * n * m * d * d + 4 * n * m * d + 2 * d ** 3 / 3)


QWEN2 = dict(hidden_size=896, num_attention_heads=14, num_key_value_heads=2,
             vocab_size=151936, intermediate_size=4864, num_hidden_layers=24,
             tie_word_embeddings=True)
TINY = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
            vocab_size=10, intermediate_size=16, num_hidden_layers=2,
            tie_word_embeddings=False)


@pytest.mark.parametrize("cfg,params", [
    # 151936*896 + 24*(896*896*2 + 2*896*128 + 3*896*4864)
    (QWEN2, 151936 * 896 + 24 * (1_605_632 + 229_376 + 13_074_432)),
    # 2*10*8 + 2*(8*8 + 2*8*4 + 8*8 + 3*8*16)
    (TINY, 160 + 2 * (64 + 64 + 64 + 384)),
])
def test_lm_params(cfg, params):
    assert lm.params(cfg) == params


def test_lm_flops_per_token():
    seq = 1024
    assert lm.train_flops_per_token(QWEN2, seq) == pytest.approx(
        6 * lm.params(QWEN2) + 12 * 24 * 896 * seq)
    assert lm.train_flops(TINY, 5) == 6 * lm.params(TINY) * 5
