"""Wire-format payload API tests (no optional deps): payload round-trips
are bit-identical to the seed-era dense operators, analytic bits are
clamped to what the payload can contain, measured bits (payload
structure via jax.eval_shape) match the analytic claims under x64, the
compressor registry constructs every family, and payload shapes stay
static under vmap over a silo axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _dense_refs import (
    blocktopk_dense_ref,
    randk_dense_ref,
    rankr_dense_ref,
    topk_dense_ref,
)
from repro.core.compressors import (
    FLOAT_BITS,
    INDEX_BITS,
    BlockTopK,
    RandK,
    RankR,
    TopK,
    Zero,
    available_compressors,
    make_compressor,
    payload_bits,
)

# -- bits clamps (regression: no overcount on small problems) ----------------


def test_topk_bits_clamped_to_numel():
    # a Top-K larger than the matrix ships the matrix, not more
    assert TopK(k=100).bits((3, 3)) == 9 * (FLOAT_BITS + INDEX_BITS)
    assert TopK(k=9).bits((3, 3)) == 9 * (FLOAT_BITS + INDEX_BITS)


def test_topk_symmetric_bits_count_lower_triangle_only():
    # symmetric Top-K keeps (and ships) only lower-triangular entries
    tri = 4 * 5 // 2
    assert TopK(k=100, symmetric=True).bits((4, 4)) == \
        tri * (FLOAT_BITS + INDEX_BITS)
    assert TopK(k=3, symmetric=True).bits((4, 4)) == \
        3 * (FLOAT_BITS + INDEX_BITS)


def test_randk_bits_clamped_to_numel():
    assert RandK(k=100).bits((3, 3)) == 9 * (FLOAT_BITS + INDEX_BITS)


def test_blocktopk_bits_clamped_to_block_size():
    # k_per_block larger than a tile ships the tile
    assert BlockTopK(k_per_block=100, block=4).bits((4, 4)) == \
        16 * (FLOAT_BITS + INDEX_BITS)


def test_bits_match_payload_shapes_after_clamp():
    # the analytic claim equals the measured payload structure under x64
    with jax.enable_x64(True):
        for comp, shape in [(TopK(k=100), (3, 3)),
                            (TopK(k=100, symmetric=True), (4, 4)),
                            (RandK(k=100), (3, 3)),
                            (BlockTopK(k_per_block=100, block=4), (4, 4)),
                            (RankR(r=100), (5, 5)),
                            (Zero(), (5, 5))]:
            assert comp.bits(shape) == payload_bits(comp, shape), comp


# -- payload round-trips: bit-identical to the seed-era dense operators ------


def _rand(seed, d0, d1):
    return jax.random.normal(jax.random.PRNGKey(seed), (d0, d1))


@pytest.mark.parametrize("k", [1, 17, 144, 600])
def test_topk_roundtrip_bit_identical(k):
    m = _rand(0, 12, 12)
    comp = TopK(k=k)
    out = comp.decompress(comp.compress(m), m.shape)
    assert np.array_equal(np.asarray(out), np.asarray(topk_dense_ref(m, k)))


@pytest.mark.parametrize("k", [1, 17, 78, 600])
def test_topk_symmetric_roundtrip_bit_identical(k):
    m = _rand(1, 12, 12)
    comp = TopK(k=k, symmetric=True)
    out = comp.decompress(comp.compress(m), m.shape)
    ref = topk_dense_ref(m, k, symmetric=True)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("k", [1, 9, 63, 200])
def test_randk_roundtrip_bit_identical(k):
    m = _rand(2, 7, 9)
    key = jax.random.PRNGKey(42)
    comp = RandK(k=k)
    out = comp.decompress(comp.compress(m, key), m.shape)
    assert np.array_equal(np.asarray(out),
                          np.asarray(randk_dense_ref(m, k, key)))


@pytest.mark.parametrize("kb", [1, 5, 16, 30])
def test_blocktopk_roundtrip_bit_identical(kb):
    m = _rand(3, 10, 14)
    comp = BlockTopK(k_per_block=kb, block=4)
    out = comp.decompress(comp.compress(m), m.shape)
    ref = blocktopk_dense_ref(m, kb, 4)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("r", [1, 3, 12])
def test_rankr_roundtrip_bit_identical(r):
    m = _rand(4, 12, 12)
    m = 0.5 * (m + m.T)
    comp = RankR(r=r)
    out = comp.decompress(comp.compress(m), m.shape)
    assert np.array_equal(np.asarray(out), np.asarray(rankr_dense_ref(m, r)))


# -- threshold-variant tie handling (regressions) ----------------------------


def test_blocktopk_threshold_negative_padding_is_dropped():
    """jax normalizes negative indices before the mode='drop' bounds
    check, so -1 payload padding must be remapped before the scatter —
    regression: the padding pair (0, -1) used to zero the tile's last
    surviving entry."""
    from repro.core.compressors import BlockSparsePayload, BlockTopKThreshold

    comp = BlockTopKThreshold(k_per_block=3, block=2)
    pay = BlockSparsePayload(values=jnp.asarray([[5.0, 9.0, 0.0]]),
                             indices=jnp.asarray([[2, 3, -1]], jnp.int32))
    out = comp.decompress(pay, (2, 2))
    np.testing.assert_array_equal(np.asarray(out), [[0.0, 0.0], [5.0, 9.0]])


def test_blocktopk_threshold_tie_cluster_keeps_exactly_k():
    """A tie cluster spanning the k-th position must not undershoot: the
    two-phase selection (strict survivors, then boundary ties) keeps
    exactly k entries including the strictly-largest one, preserving
    the Def 3.3 contraction spec() reports."""
    from repro.core.compressors import BlockTopKThreshold

    t = jnp.full((4, 4), 1.0).at[0, 0].set(1.0001)
    comp = BlockTopKThreshold(k_per_block=3, block=4)
    out = comp(t)
    kept = np.asarray(out) != 0
    assert kept.sum() == 3
    assert float(out[0, 0]) == float(np.float32(1.0001))
    nm2 = float(jnp.sum(t * t))
    err = float(jnp.sum((out - t) ** 2))
    delta = comp.spec((4, 4)).delta
    assert err <= (1 - delta) * nm2 * (1 + 1e-6)


# -- registry-wide properties ------------------------------------------------

# every registered family with a usable level for the round-trip test
_FAMILY_LEVELS = {
    "rankr": 2, "rank": 2, "topk": 17, "topksym": 17, "powersgd": 2,
    "randk": 17, "blocktopk": 5, "blocktopkthreshold": 5,
    "natural": 0.4, "identity": None, "none": None, "zero": None,
    "dithering": 4, "randomdithering": 4,
}


def test_every_registered_family_has_level_params():
    missing = [f for f in available_compressors() if f not in _FAMILY_LEVELS]
    assert not missing, f"no round-trip level for families {missing}"


@pytest.mark.parametrize("family", sorted(_FAMILY_LEVELS))
def test_registry_roundtrip_call_equals_decompress_compress(family):
    """For every registered family: the registry constructs it, the dense
    __call__ equals decompress(compress(...)) exactly, and the payload
    keeps a static structure under vmap over a silo axis."""
    comp = make_compressor(family, _FAMILY_LEVELS[family])
    shape = (12,) if family in ("dithering", "randomdithering") else (12, 12)
    m = jax.random.normal(jax.random.PRNGKey(3), shape)
    key = jax.random.PRNGKey(4)
    out_call = comp(m, key)
    out_rt = comp.decompress(comp.compress(m, key), shape)
    assert np.array_equal(np.asarray(out_call), np.asarray(out_rt)), family

    # payload shapes static under vmap: leading silo axis only
    stack = jax.random.normal(jax.random.PRNGKey(5), (3,) + shape)
    keys = jax.random.split(key, 3)
    single = jax.eval_shape(comp.compress, m, key)
    batched = jax.eval_shape(
        lambda s, ks: jax.vmap(comp.compress)(s, ks), stack, keys)
    for one, bat in zip(jax.tree.leaves(single), jax.tree.leaves(batched)):
        assert bat.shape == (3,) + one.shape, family
        assert bat.dtype == one.dtype, family
    # per-silo measured bits are batching-invariant
    assert single.bits() == batched.bits(), family


def test_registry_unknown_family():
    with pytest.raises(ValueError, match="unknown compressor family"):
        make_compressor("not-a-compressor", 1)


@pytest.mark.parametrize("family", sorted(
    f for f in _FAMILY_LEVELS if f != "zero"))
def test_registry_def33_def32_inequalities(family):
    """Def 3.3 contraction for every deterministic family (PowerSGD at
    its guaranteed delta=0), Def 3.2 first inequality (unbiasedness to
    MC tolerance) for randomized ones."""
    comp = make_compressor(family, _FAMILY_LEVELS[family])
    shape = (12,) if family in ("dithering", "randomdithering") else (12, 12)
    sp = comp.spec(shape)
    m = jax.random.normal(jax.random.PRNGKey(7), shape)
    if family == "topksym":  # symmetric variant: domain is Hessian diffs
        m = 0.5 * (m + m.T)
    if sp.deterministic:
        delta = 0.0 if family == "powersgd" else sp.delta
        c = comp(m)
        nm = float(jnp.linalg.norm(m))
        err = float(jnp.linalg.norm(c - m)) ** 2
        assert float(jnp.linalg.norm(c)) <= nm * (1 + 1e-5), family
        assert err <= (1 - delta) * nm**2 + 1e-5 * nm**2, family
    else:
        keys = jax.random.split(jax.random.PRNGKey(8), 3000)
        mean = jnp.mean(jax.vmap(lambda k: comp(m, k))(keys), axis=0)
        np.testing.assert_allclose(mean, m, atol=0.3)
        assert sp.omega is not None and sp.omega >= 0
