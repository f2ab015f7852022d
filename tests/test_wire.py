"""Wire-layer pins: bitstream codec round trips (bit-exact fp32/f64,
documented quantization bounds), -1 padding survival, per-silo encoding
of vmapped payload stacks, the traffic model, the unified ``WireReport``
cost API vs its deprecated aliases, and the ``seconds_per_round`` sweep
column."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BlockTopK,
    DensePayload,
    DitheredPayload,
    Identity,
    LowRankPayload,
    NaturalSparsification,
    PowerSGD,
    RandK,
    RandomDithering,
    RankR,
    SparsePayload,
    TopK,
    payload_bits,
)
from repro.wire import (
    PRESETS,
    LinkModel,
    WireFormatError,
    WireReport,
    canonical,
    decode,
    encode,
    encode_silos,
    encoded_bytes,
    link_model,
    round_seconds,
    seconds_curve,
    silo_encoded_bytes,
    transfer_seconds,
    wire_cost,
)

D = 16


def _m(dtype=jnp.float32, d=D, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (d, d), dtype)
    return 0.5 * (x + x.T)


def _families():
    return {
        "topk": TopK(k=3 * D),
        "randk": RandK(k=3 * D),
        "blocktopk": BlockTopK(k_per_block=4, block=8),
        "rankr": RankR(2),
        "powersgd": PowerSGD(r=2),
        "natural": NaturalSparsification(p=0.3),
        "identity": Identity(),
        "dithering": RandomDithering(s=4),
    }


def _bit_equal(a, b):
    """Array-for-array bitwise equality of two payload pytrees (-0.0 and
    +0.0 are DIFFERENT here — that is the point of the raw pin)."""
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape:
            return False
        if x.tobytes() != y.tobytes():
            return False
    return True


# -- round trips: raw is bit-exact for every family -------------------------


@pytest.mark.parametrize("name", sorted(_families()))
def test_roundtrip_fp32_bit_exact(name):
    comp = _families()[name]
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    dec = decode(encode(p))
    assert _bit_equal(dec, canonical(p))
    # and the dense reconstruction is unchanged by canonicalization
    np.testing.assert_array_equal(
        np.asarray(comp.decompress(jax.tree_util.tree_map(jnp.asarray, dec),
                                   (D, D))),
        np.asarray(comp.decompress(p, (D, D))))


@pytest.mark.parametrize("name", sorted(_families()))
def test_roundtrip_f64_bit_exact(name):
    with jax.enable_x64(True):
        comp = _families()[name]
        p = comp.compress(_m(jnp.float64), jax.random.PRNGKey(1))
        dec = decode(encode(p))
        assert _bit_equal(dec, canonical(p))


@pytest.mark.parametrize("name", sorted(_families()))
def test_roundtrip_unsorted_preserves_order(name):
    comp = _families()[name]
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    dec = decode(encode(p, sort_indices=False))
    host = jax.tree_util.tree_map(np.asarray, p)
    assert _bit_equal(dec, host)


def test_payload_encode_method_matches_module():
    comp = TopK(k=3 * D)
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    assert p.encode() == encode(p)
    assert comp.encode(p) == encode(p)
    assert _bit_equal(comp.decode(encode(p)), canonical(p))
    assert encoded_bytes(p) == len(encode(p))


# -- quantized value formats: documented bounds -----------------------------


def test_fp16_value_format_is_exact_cast():
    comp = TopK(k=3 * D)
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    dec = decode(encode(p, value_format="fp16"))
    want = np.asarray(canonical(p).values)
    got = np.asarray(dec.values)
    # decoded == orig.astype(f16).astype(f32), EXACTLY — and the index
    # stream is untouched by value quantization
    np.testing.assert_array_equal(got,
                                  want.astype(np.float16).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(dec.indices),
                                  np.asarray(canonical(p).indices))


def test_int8_value_format_error_bound():
    comp = TopK(k=3 * D)
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    dec = decode(encode(p, value_format="int8"))
    want = np.asarray(canonical(p).values, np.float64)
    got = np.asarray(dec.values, np.float64)
    bound = np.max(np.abs(want)) / 250.0  # documented: <= max|v| / 250
    assert np.max(np.abs(got - want)) <= bound


def test_quantized_formats_shrink_the_buffer():
    comp = TopK(k=3 * D)
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    raw, f16, i8 = (len(encode(p, value_format=f))
                    for f in ("raw", "fp16", "int8"))
    assert i8 < f16 < raw


def test_dithered_bit_exact_under_every_value_format():
    """Dithered payloads are categorical — quantizing the (already
    integer) level stream would be a bug; all three formats round-trip
    bit-exactly."""
    comp = RandomDithering(s=4)
    p = comp.compress(_m(jnp.float32), jax.random.PRNGKey(1))
    for fmt in ("raw", "fp16", "int8"):
        assert _bit_equal(decode(encode(p, value_format=fmt)), canonical(p))


# -- padding, signed zero, malformed buffers --------------------------------


def test_minus_one_padding_survives():
    p = SparsePayload(values=jnp.array([1.5, -2.0, 0.0, 0.0], jnp.float32),
                      indices=jnp.array([7, 3, -1, -1], jnp.int32),
                      universe=D * D)
    dec = decode(encode(p))
    can = canonical(p)
    assert _bit_equal(dec, can)
    assert np.sum(np.asarray(dec.indices) == -1) == 2
    # padding slots are dropped by decompress on both sides
    comp = TopK(k=4)
    np.testing.assert_array_equal(
        np.asarray(comp.decompress(jax.tree_util.tree_map(jnp.asarray, dec),
                                   (D, D))),
        np.asarray(comp.decompress(p, (D, D))))


def test_negative_zero_survives_indexed_dense():
    p = DensePayload(values=jnp.array([[0.0, -0.0], [3.0, 0.0]], jnp.float32),
                     count=1, indexed=True, universe=4)
    dec = decode(encode(p))
    got = np.asarray(dec.values)
    assert got[0, 1] == 0.0 and np.signbit(got[0, 1])  # -0.0 kept
    assert not np.signbit(got[0, 0])
    assert _bit_equal(dec, canonical(p))


def test_decode_rejects_garbage_and_wrong_shape():
    with pytest.raises(WireFormatError):
        decode(b"\x00\x01\x02\x03")
    comp = Identity()
    buf = encode(comp.compress(_m(jnp.float32)))
    with pytest.raises(WireFormatError):
        decode(buf, shape=(D + 1, D + 1))
    with pytest.raises(WireFormatError):
        encode(comp.compress(_m(jnp.float32)), value_format="fp8")


def test_stacked_payload_must_use_encode_silos():
    comp = TopK(k=3 * D)
    diffs = jax.random.normal(jax.random.PRNGKey(0), (4, D, D))
    stack = jax.vmap(comp.compress)(diffs)
    with pytest.raises(WireFormatError, match="encode_silos"):
        encode(stack)


def test_encode_silos_per_silo_buffers():
    """A vmapped-over-silos stack (the engine's uplink unit) encodes to
    one buffer per silo, each decoding to that silo's canonical slice.
    ``encode_silos`` is a LAZY generator (cross-device cohorts encode
    10k+ buffers — they must stream, not materialize)."""
    import types

    n = 4
    comp = TopK(k=3 * D)
    diffs = jax.random.normal(jax.random.PRNGKey(0), (n, D, D))
    stack = jax.vmap(comp.compress)(diffs)
    gen = encode_silos(stack)
    assert isinstance(gen, types.GeneratorType)
    bufs = list(gen)
    assert len(bufs) == n
    for i, buf in enumerate(bufs):
        single = comp.compress(diffs[i])
        assert _bit_equal(decode(buf), canonical(single))
    sizes = silo_encoded_bytes(stack)
    assert sizes.shape == (n,) and all(sizes == [len(b) for b in bufs])


# -- the honest bits() signature --------------------------------------------


def test_bits_rejects_unknown_index_coding():
    p = TopK(k=4).compress(_m(jnp.float32))
    with pytest.raises(ValueError, match="index_coding"):
        p.bits(index_coding="huffman")


def test_index_coding_noop_families_documented():
    """LowRank and Dithered payloads carry no index stream: the entropy
    coding is a no-op (raw == entropy), by the one documented rule on
    the Payload base class rather than silently-ignored kwargs."""
    lr = RankR(2).compress(_m(jnp.float32))
    di = RandomDithering(s=4).compress(_m(jnp.float32), jax.random.PRNGKey(1))
    assert isinstance(lr, LowRankPayload)
    assert isinstance(di, DitheredPayload)
    for p in (lr, di):
        assert p.bits() == p.bits(index_coding="entropy")
    # indexed families genuinely differ
    sp = TopK(k=3 * D).compress(_m(jnp.float32))
    assert sp.bits(index_coding="entropy") < sp.bits()


# -- WireReport: the unified cost surface vs the deprecated quartet ---------


def test_wire_cost_matches_deprecated_aliases():
    comp = TopK(k=3 * D)
    rep = wire_cost(comp, (D, D), dtype=jnp.float32)
    assert isinstance(rep, WireReport)
    assert rep.analytic_bits == comp.bits((D, D)) == comp.spec((D, D)).bits
    assert rep.raw_bits == payload_bits(comp, (D, D), dtype=jnp.float32)
    assert rep.entropy_bits == payload_bits(comp, (D, D), dtype=jnp.float32,
                                            index_coding="entropy")
    p = comp.compress(jax.random.normal(jax.random.PRNGKey(0), (D, D),
                                        jnp.float32), jax.random.PRNGKey(1))
    assert rep.encoded_bytes == len(encode(p))
    assert rep.encoded_bits == 8 * rep.encoded_bytes
    assert rep.entropy_bits <= rep.raw_bits
    assert rep.seconds("wan", n=4) > 0.0


def test_wire_cost_lazy_core_reexport():
    import repro.core as core

    assert core.wire_cost is wire_cost
    assert core.WireReport is WireReport
    with pytest.raises(AttributeError):
        core.not_a_wire_name


# -- traffic model ----------------------------------------------------------


def test_traffic_deterministic_and_monotone():
    bits = 8.0 * 1e6
    a = round_seconds(bits, "wan", n=8, seed=3)
    assert a == round_seconds(bits, "wan", n=8, seed=3)  # deterministic
    assert round_seconds(2 * bits, "wan", n=8, seed=3) > a  # more bits
    # straggler max dominates the mean
    assert a >= round_seconds(bits, "wan", n=8, seed=3, reduce="mean")
    with pytest.raises(ValueError):
        round_seconds(bits, "wan", reduce="median")


def test_traffic_presets_ordered():
    bits = 8.0 * 1e6
    t = {name: round_seconds(bits, name, n=8) for name in PRESETS}
    assert t["datacenter"] < t["wan"] < t["fl-cross-device"]
    with pytest.raises(ValueError, match="unknown link preset"):
        link_model("dialup")
    assert link_model(None) is None
    custom = LinkModel("lab", bandwidth_bps=1e9, latency_s=0.001)
    assert link_model(custom) is custom
    # sigma=0 link: exact closed form
    assert round_seconds(1e9, custom, n=4) == pytest.approx(1.001)


def test_traffic_curves_and_bytes():
    curve = seconds_curve(1e6, "wan", n=4, num_rounds=5, init_bits=2e6)
    assert curve.shape == (6,)
    assert np.all(np.diff(curve) > 0)
    assert curve[0] > 0  # the init ship is charged up front
    assert transfer_seconds(125000, "datacenter") == \
        round_seconds(1e6, "datacenter")


def test_mean_corrected_bandwidth_spread():
    link = PRESETS["fl-cross-device"]
    bw = link.silo_bandwidths(20000, seed=0)
    assert np.all(bw > 0)
    assert abs(np.mean(bw) / link.bandwidth_bps - 1.0) < 0.05


# -- sweep integration: the seconds_per_round column ------------------------


@pytest.mark.slow
def test_sweep_records_seconds_per_round():
    from repro.core.objectives import batch_grad, batch_hess, global_value
    from repro.data.synthetic import make_synthetic
    from repro.engine import ExperimentSpec, Sweep

    with jax.enable_x64(True):
        data = make_synthetic(jax.random.PRNGKey(0), alpha=0.5, beta=0.5,
                              n=4, m=24, d=8, lam=1e-3)
        problem = dict(grad=lambda x: batch_grad(x, data),
                       hess=lambda x: batch_hess(x, data),
                       val=lambda x: global_value(x, data), n=4, d=8,
                       fstar=0.0)
        spec = ExperimentSpec("fednl", "topk", 16, num_rounds=3)
        res = Sweep([spec]).run(problem, x0=jnp.zeros(8))  # link="wan"
        cell = res.cells[0]
        assert cell.seconds_per_round is not None
        assert np.isfinite(cell.seconds_per_round)
        assert cell.seconds_per_round > 0
        rows = res.records()
        assert all(r["seconds_per_round"] == cell.seconds_per_round
                   for r in rows)
        assert res.summary()[0]["seconds_per_round"] == cell.seconds_per_round
        # pricing is the traffic model on the measured wire bits
        from repro.engine import measured_bits_per_round, seconds_per_round
        method = spec.build(__import__("repro.engine.method",
                                       fromlist=["Oracles"]).Oracles(
            value=problem["val"], grad=problem["grad"], hess=problem["hess"]))
        want = round_seconds(measured_bits_per_round(method, 8), "wan", n=4)
        assert cell.seconds_per_round == want
        assert seconds_per_round(method, 8, 4) == want
        # link=None switches the model off
        res2 = Sweep([spec], link=None).run(problem, x0=jnp.zeros(8))
        assert res2.cells[0].seconds_per_round is None
        assert np.isnan(res2.records()[0]["seconds_per_round"])
