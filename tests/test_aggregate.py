"""Payload-space server aggregation tests.

Pins the tentpole equivalence — for every registered compressor family,
``comp.aggregate(stacked payloads) == mean_i decompress(payload_i)`` to
f64 tolerance — under the plain path, under vmap over seeds, and under
shard_map over silos, including the -1 padding and k-ties edge cases of
the wire format; plus the ``scale_payload`` masked mean (partial
participation), end-to-end FedNL/FedNL-PP run equivalence fast-path vs
fallback, the fednl_precond silo-axis observation path, and the
entropy-coded index-stream accounting."""

import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedNL, FedNLPP, TopK
from repro.core.compressors import (
    BlockSparsePayload,
    BlockTopKThreshold,
    Compressor,
    SparsePayload,
    available_compressors,
    make_compressor,
    payload_bits,
)
from repro.core.objectives import batch_grad, batch_hess
from repro.data.synthetic import make_synthetic

# every registered family with a usable level (mirrors test_payloads)
_FAMILY_LEVELS = {
    "rankr": 2, "rank": 2, "topk": 17, "topksym": 17, "powersgd": 2,
    "randk": 17, "blocktopk": 5, "blocktopkthreshold": 5,
    "natural": 0.4, "identity": None, "none": None, "zero": None,
    "dithering": 4, "randomdithering": 4,
}

N_SILOS = 5


def _family_shape(family):
    return (12,) if family in ("dithering", "randomdithering") else (12, 12)


def _stacked_payloads(comp, shape, n=N_SILOS, seed=0):
    stack = jax.random.normal(jax.random.PRNGKey(seed), (n,) + shape)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n)
    return stack, jax.vmap(comp.compress)(stack, keys)


def test_every_registered_family_covered():
    missing = [f for f in available_compressors() if f not in _FAMILY_LEVELS]
    assert not missing, f"no aggregate coverage for families {missing}"


@pytest.mark.parametrize("family", sorted(_FAMILY_LEVELS))
def test_aggregate_matches_decompress_mean(family):
    """Acceptance: aggregate == mean of per-silo decompression, per
    registered family, at f64 tolerance (reduction order differs)."""
    with jax.enable_x64(True):
        comp = make_compressor(family, _FAMILY_LEVELS[family])
        shape = _family_shape(family)
        _, payloads = _stacked_payloads(comp, shape)
        fast = comp.aggregate(payloads, shape)
        slow = Compressor.aggregate(comp, payloads, shape)  # fallback
        scale = float(jnp.max(jnp.abs(slow))) + 1e-30
        np.testing.assert_allclose(np.asarray(fast), np.asarray(slow),
                                   rtol=0, atol=1e-13 * max(1.0, scale))


@pytest.mark.parametrize("family", [
    "topk", "topksym", "randk", "blocktopk", "blocktopkthreshold",
    "rankr", "powersgd", "identity", "natural", "dithering", "zero"])
def test_aggregate_fast_path_is_registered(family):
    """Guard: the structure-aware families must actually override the
    generic decompress-then-mean fallback — a silent fallback would
    reintroduce the (n, d, d) server stack."""
    comp = make_compressor(family, _FAMILY_LEVELS[family])
    assert type(comp).aggregate is not Compressor.aggregate, family


def test_aggregate_under_vmap_over_seeds():
    """The engine vmaps whole steps over the seed axis; aggregate must
    batch transparently and match the per-seed serial results."""
    with jax.enable_x64(True):
        comp = make_compressor("randk", 13)
        shape = (12, 12)
        stack = jax.random.normal(jax.random.PRNGKey(0),
                                  (N_SILOS,) + shape)

        def one(seed_key):
            keys = jax.random.split(seed_key, N_SILOS)
            payloads = jax.vmap(comp.compress)(stack, keys)
            return comp.aggregate(payloads, shape)

        seed_keys = jax.random.split(jax.random.PRNGKey(7), 3)
        batched = jax.jit(jax.vmap(one))(seed_keys)
        for i in range(3):
            np.testing.assert_allclose(np.asarray(batched[i]),
                                       np.asarray(one(seed_keys[i])),
                                       rtol=0, atol=1e-14)


def test_aggregate_under_shard_map_over_silos():
    """Real 4-way shard_map over the silo axis: per-shard payload-space
    aggregation + one pmean of the dense (d, d) accumulator equals the
    serial aggregate over the full stack. Subprocess so the forced host
    device count doesn't leak into this session."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_enable_x64", True)
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.compressors import SparsePayload, TopK

        comp = TopK(k=50)
        shape = (12, 12)
        n = 8
        stack = jax.random.normal(jax.random.PRNGKey(0), (n,) + shape)
        keys = jax.random.split(jax.random.PRNGKey(1), n)
        payloads = jax.vmap(comp.compress)(stack, keys)
        serial = comp.aggregate(payloads, shape)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=P())
        def sharded_agg(values, indices):
            local = SparsePayload(values=values, indices=indices,
                                  universe=comp._slots(shape))
            return jax.lax.pmean(comp.aggregate(local, shape), "data")

        out = sharded_agg(payloads.values, payloads.indices)
        np.testing.assert_allclose(np.asarray(out), np.asarray(serial),
                                   rtol=0, atol=1e-14)
        print("SHARDED_AGG_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_AGG_OK" in out.stdout, out.stdout + out.stderr


# -- wire-format edge cases ---------------------------------------------------


def test_aggregate_sparse_negative_padding_dropped():
    """-1 payload padding must vanish from the aggregate even when its
    value slot is nonzero (same regression class as decompress: jax
    normalizes negative indices ahead of mode='drop')."""
    with jax.enable_x64(True):
        pay = SparsePayload(
            values=jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 0.0]]),
            indices=jnp.asarray([[0, 5, -1], [5, -1, -1]], jnp.int32),
            universe=6)
        comp = TopK(k=3)
        out = comp.aggregate(pay, (2, 3))
        np.testing.assert_allclose(
            np.asarray(out), [[0.5, 0.0, 0.0], [0.0, 0.0, 3.0]],
            rtol=0, atol=0)
        slow = Compressor.aggregate(comp, pay, (2, 3))
        np.testing.assert_allclose(np.asarray(out), np.asarray(slow),
                                   rtol=0, atol=0)


def test_aggregate_blocksparse_ties_and_padding():
    """BlockTopKThreshold payloads under a tie cluster spanning the k-th
    position carry -1 padding and exactly-k survivors (PR-2 semantics);
    the per-tile scatter-add aggregate must agree with the fallback."""
    with jax.enable_x64(True):
        comp = BlockTopKThreshold(k_per_block=3, block=4)
        base = jnp.full((4, 4), 1.0).at[0, 0].set(1.0001)
        stack = jnp.stack([base, 2.0 * base, -base])
        payloads = jax.vmap(lambda m: comp.compress(m))(stack)
        assert bool(jnp.any(payloads.indices >= 0))
        fast = comp.aggregate(payloads, (4, 4))
        slow = Compressor.aggregate(comp, payloads, (4, 4))
        np.testing.assert_allclose(np.asarray(fast), np.asarray(slow),
                                   rtol=0, atol=1e-15)


def test_aggregate_blocksparse_nonmultiple_shape_cropped():
    """Shapes that don't divide the block: padded tiles accumulate zeros
    and the aggregate crops back to the true shape."""
    with jax.enable_x64(True):
        comp = make_compressor("blocktopk", 5)  # block=128 > shape
        shape = (10, 14)
        _, payloads = _stacked_payloads(comp, shape, seed=3)
        fast = comp.aggregate(payloads, shape)
        slow = Compressor.aggregate(comp, payloads, shape)
        assert fast.shape == shape
        np.testing.assert_allclose(np.asarray(fast), np.asarray(slow),
                                   rtol=0, atol=1e-15)


@pytest.mark.parametrize("family", ["topk", "rankr", "dithering", "natural"])
def test_scale_payload_masked_mean(family):
    """aggregate(p, shape, weights=w) == mean_i w_i * decompress_i — the
    partial-participation masking used by FedNL-PP/PPBC, across wire
    formats (values / low-rank middle / dithering signs); the weighting
    is ``scale_payload`` applied inside the aggregate entry point."""
    with jax.enable_x64(True):
        comp = make_compressor(family, _FAMILY_LEVELS[family])
        shape = _family_shape(family)
        _, payloads = _stacked_payloads(comp, shape, seed=4)
        w = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
        out = comp.aggregate(payloads, shape, weights=w)
        dec = jax.vmap(lambda p: comp.decompress(p, shape))(payloads)
        ref = jnp.mean(w.reshape((-1,) + (1,) * len(shape)) * dec, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-13)


# -- end-to-end: serial .run numerics unchanged -------------------------------


class _FallbackTopK(TopK):
    """TopK forced onto the generic decompress-then-mean server."""

    def aggregate(self, payloads, shape, weights=None):
        return Compressor.aggregate(self, payloads, shape, weights=weights)


@pytest.fixture(scope="module")
def problem():
    with jax.enable_x64(True):
        data = make_synthetic(jax.random.PRNGKey(0), alpha=0.5, beta=0.5,
                              n=6, m=40, d=10, lam=1e-3)
        data = data._replace(a=data.a.astype(jnp.float64),
                             b=data.b.astype(jnp.float64))
        yield dict(grad=lambda x: batch_grad(x, data),
                   hess=lambda x: batch_hess(x, data), n=6, d=10)


def test_fednl_run_fast_path_matches_fallback(problem):
    """Swapping the structure-aware aggregate for decompress-then-mean
    must not move serial .run trajectories beyond f64 noise."""
    with jax.enable_x64(True):
        x0 = jnp.full((10,), 0.4, jnp.float64)
        runs = {}
        for tag, comp in [("fast", TopK(k=30)), ("slow", _FallbackTopK(k=30))]:
            alg = FedNL(problem["grad"], problem["hess"], comp, option=2)
            _, runs[tag] = alg.run(x0, problem["n"], 8)
        np.testing.assert_allclose(np.asarray(runs["fast"]),
                                   np.asarray(runs["slow"]),
                                   rtol=0, atol=1e-12)


def test_fednl_pp_masked_fast_path_matches_fallback(problem):
    """FedNL-PP's masked server aggregate (zero-weighted inactive silos
    in payload space) equals the dense masked mean, end to end."""
    with jax.enable_x64(True):
        x0 = jnp.full((10,), 0.4, jnp.float64)
        runs = {}
        for tag, comp in [("fast", TopK(k=30)), ("slow", _FallbackTopK(k=30))]:
            alg = FedNLPP(problem["grad"], problem["hess"], comp, tau=3)
            _, runs[tag] = alg.run(x0, problem["n"], 8)
        np.testing.assert_allclose(np.asarray(runs["fast"]),
                                   np.asarray(runs["slow"]),
                                   rtol=0, atol=1e-12)


# -- large-d: the tiled accumulator ------------------------------------------


@pytest.mark.slow
def test_aggregate_topk_randk_exact_at_d4096_via_tiled_kernel():
    """Acceptance: TopK/RandK aggregate is exact (f64, vs decompress-
    then-mean) at d=4096 — and the Pallas TILED scatter kernel (the
    budget dispatch auto-tiles: 4096^2 f64 >> 8 MiB) reproduces the
    same sum bit-for-bit against the XLA oracle."""
    from repro.core.compressors import RandK
    from repro.kernels.scatter_accum import scatter_accumulate

    with jax.enable_x64(True):
        d, n = 4096, 2
        stack = jax.random.normal(jax.random.PRNGKey(0), (n, d, d))
        keys = jax.random.split(jax.random.PRNGKey(1), n)
        for comp in (TopK(k=64), RandK(k=64)):
            pay = jax.vmap(comp.compress)(stack, keys)
            fast = comp.aggregate(pay, (d, d))
            fallback = Compressor.aggregate(comp, pay, (d, d))
            scale = float(jnp.max(jnp.abs(fallback))) + 1e-30
            err = float(jnp.max(jnp.abs(fast - fallback)))
            assert err <= 1e-12 * max(1.0, scale), (type(comp).__name__, err)
            # force the Pallas path: the budget dispatch must pick the
            # tiled kernel and agree with the aggregate exactly
            tiled = scatter_accumulate(pay.values, pay.indices, (d, d),
                                       use_pallas=True, interpret=True) / n
            err_t = float(jnp.max(jnp.abs(tiled - fast)))
            assert err_t <= 1e-12 * max(1.0, scale), (type(comp).__name__,
                                                      err_t)


# -- fednl_precond silo-axis observations -------------------------------------


def test_fednl_precond_silo_axis_aggregates_payloads():
    """Observations with a leading silo axis: H learns from the payload-
    space mean of per-silo compressed diffs (here k = block^2, so the
    compression is exact and H must equal the mean observation)."""
    from repro.second_order.fednl_precond import FedNLPrecondOptimizer

    opt = FedNLPrecondOptimizer(lr=0.1, alpha=1.0, k_per_block=64, block=8)
    params = {"w": jnp.zeros((8, 8))}
    state = opt.init(params)
    grads = {"w": jnp.ones((8, 8))}
    obs = {"w": jnp.stack([jnp.full((8, 8), v) for v in (1.0, 2.0, 6.0)])}
    _, state = opt.update(grads, state, params, observations=obs)
    np.testing.assert_allclose(np.asarray(state.h["w"]), 3.0, atol=1e-6)


def test_fednl_precond_adapter_threads_observations():
    """Regression: the Optimizer-protocol adapter used to wrap update in
    a 3-arg lambda, silently dropping ``observations`` — the PR 3
    cross-silo branch was dead code through the protocol. The adapter
    must drive it, and the plain 3-arg call must keep working."""
    from repro.second_order import fednl_precond

    opt = fednl_precond(0.1, alpha=1.0, k_per_block=64, block=8)
    params = {"w": jnp.zeros((8, 8))}
    state = opt.init(params)
    grads = {"w": jnp.ones((8, 8))}
    obs = {"w": jnp.stack([jnp.full((8, 8), v) for v in (1.0, 2.0, 6.0)])}
    _, state = opt.update(grads, state, params, observations=obs)
    # k = block^2 -> exact compression: H must equal the silo mean,
    # which is only reachable if observations survived the adapter
    np.testing.assert_allclose(np.asarray(state.h["w"]), 3.0, atol=1e-6)
    upd, state = opt.update(grads, state, params)  # 3-arg still fine
    assert jax.tree.leaves(upd)[0].shape == (8, 8)


def test_fednl_precond_silo_axis_matches_per_silo_reference():
    """Lossy case (k < block^2): the update equals the mean of each
    silo's individually compressed diff — the paper's placement."""
    from repro.second_order.fednl_precond import FedNLPrecondOptimizer

    opt = FedNLPrecondOptimizer(lr=0.1, alpha=0.5, k_per_block=9, block=8)
    comp = opt.compressor
    params = {"w": jnp.zeros((8, 8))}
    state = opt.init(params)
    grads = {"w": jnp.ones((8, 8))}
    sil = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 8)) ** 2
    _, new_state = opt.update(grads, state, params, observations={"w": sil})
    ref = 0.5 * jnp.mean(jax.vmap(lambda t: comp(t))(sil), axis=0)
    np.testing.assert_allclose(np.asarray(new_state.h["w"]),
                               np.asarray(ref), atol=1e-6)


# -- entropy-coded index-stream accounting ------------------------------------


def test_entropy_index_bits_below_raw_for_sparse():
    with jax.enable_x64(True):
        comp = TopK(k=16)
        raw = payload_bits(comp, (32, 32))
        ent = payload_bits(comp, (32, 32), index_coding="entropy")
        assert ent < raw
        # value stream unchanged: the saving is entirely index-side
        assert raw - ent <= 16 * 32


def test_entropy_index_bits_formula():
    """ceil(log2 C(universe, k)), capped at raw k*32 — checked against
    exact math.comb (lgamma evaluation may differ by <= 1 bit)."""
    pay = SparsePayload(values=jnp.zeros((16,)),
                        indices=jnp.zeros((16,), jnp.int32), universe=1024)
    got = pay.bits(index_coding="entropy") - pay.bits() + 16 * 32
    want = math.ceil(math.log2(math.comb(1024, 16)))
    assert abs(got - want) <= 1


def test_entropy_index_bits_edge_cases():
    # k == universe: the index set is fully determined -> 0 index bits,
    # leaving only the value stream (9 f32 values here)
    full = SparsePayload(values=jnp.zeros((9,), jnp.float32),
                         indices=jnp.zeros((9,), jnp.int32), universe=9)
    assert full.bits(index_coding="entropy") == 9 * 32
    # empty payload (Zero): no bits at all
    empty = SparsePayload(values=jnp.zeros((0,)),
                          indices=jnp.zeros((0,), jnp.int32), universe=100)
    assert empty.bits(index_coding="entropy") == 0
    # unknown universe: falls back to raw
    unk = SparsePayload(values=jnp.zeros((4,)),
                        indices=jnp.zeros((4,), jnp.int32))
    assert unk.bits(index_coding="entropy") == unk.bits()


def test_entropy_bits_blocksparse_scales_with_tiles():
    pay = BlockSparsePayload(values=jnp.zeros((6, 4), jnp.float32),
                             indices=jnp.zeros((6, 4), jnp.int32),
                             universe=64)
    per_tile = math.ceil(math.log2(math.comb(64, 4)))
    got = pay.bits(index_coding="entropy")
    assert abs(got - 6 * (4 * 32 + per_tile)) <= 6


def test_sweep_records_carry_entropy_column(problem):
    """Sweep rows surface bits_entropy as a third accounting column:
    <= the raw measured column always, strictly below it for index-
    carrying sparsifiers."""
    from repro.engine import ExperimentSpec, Sweep

    with jax.enable_x64(True):
        spec = ExperimentSpec("fednl", "topk", 20,
                              params=dict(option=2), num_rounds=2)
        res = Sweep([spec]).run(
            dict(grad=problem["grad"], hess=problem["hess"],
                 n=problem["n"], d=problem["d"]),
            x0=jnp.zeros(problem["d"], jnp.float64))
        cell = res.cells[0]
        assert cell.bits_entropy is not None
        assert np.all(cell.bits_entropy <= cell.bits_measured)
        assert cell.bits_entropy[-1] < cell.bits_measured[-1]
        rows = res.records()
        assert all(r["bits_entropy"] <= r["bits_measured"] for r in rows)
        summ = res.summary()
        assert 0 < summ[0]["bits_per_round_entropy"] < \
            summ[0]["bits_per_round_measured"]


# -- fused diff -> top-k -> payload uplink ------------------------------------


class _UnfusedView:
    """Proxy hiding ``fused_diff_payloads`` so MethodBase falls back to
    the unfused compress(h_new - h_old) + frob_norm uplink."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name == "fused_diff_payloads":
            raise AttributeError(name)
        return getattr(self._inner, name)


def test_fused_diff_payloads_matches_unfused_compress():
    """Compressor-level pin at f64: the fused device uplink (one-pass
    diff -> select -> payload + ||D||_F) equals compressing the
    materialized diff, silo by silo."""
    from repro.core.compressors import BlockTopK
    from repro.core.linalg import frob_norm

    with jax.enable_x64(True):
        comp = BlockTopK(k_per_block=9, block=8)
        kh, ko = jax.random.split(jax.random.PRNGKey(21))
        h_new = jax.random.normal(kh, (3, 16, 16), jnp.float64)
        h_old = jax.random.normal(ko, (3, 16, 16), jnp.float64)
        payloads, l = comp.fused_diff_payloads(h_new, h_old)
        diff = h_new - h_old
        ref_pay = jax.vmap(lambda m: comp.compress(m))(diff)
        dec = lambda p: comp.decompress(p, (16, 16))
        np.testing.assert_allclose(
            np.asarray(jax.vmap(dec)(payloads)),
            np.asarray(jax.vmap(dec)(ref_pay)), rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(l),
                                   np.asarray(jax.vmap(frob_norm)(diff)),
                                   rtol=1e-12)


def test_fednl_fused_uplink_run_matches_unfused(problem):
    """Method-level pin: a FedNL run through the fused uplink
    (``fused_diff_payloads``) tracks the unfused fallback trajectory to
    f64 noise — the fusion changes scheduling, not numerics."""
    from repro.core.compressors import BlockTopK

    with jax.enable_x64(True):
        x0 = jnp.full((10,), 0.4, jnp.float64)
        comp = BlockTopK(k_per_block=9, block=8)
        runs = {}
        for tag, c in [("fused", comp), ("unfused", _UnfusedView(comp))]:
            alg = FedNL(problem["grad"], problem["hess"], c, option=2)
            _, runs[tag] = alg.run(x0, problem["n"], 8)
        np.testing.assert_allclose(np.asarray(runs["fused"]),
                                   np.asarray(runs["unfused"]),
                                   rtol=0, atol=1e-11)


# -- cross-device scale: streamed dispatch + sharded accumulator --------------


def test_aggregate_streams_above_vmem_budget():
    """A concrete payload stack whose (value, index) pair stream
    outgrows the kernel VMEM budget must take the streamed silo-slab
    path — and land BITWISE on the stacked kernel over the same scaled
    pairs. Traced stacks (inside jit) must keep the stacked path."""
    from repro.core.compressors import _should_stream, scale_payload
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.scatter_accum import scatter_accumulate

    with jax.enable_x64(True):
        n, k, d = 700, 1024, 64
        pair = jnp.dtype(jnp.float64).itemsize + jnp.dtype(jnp.int32).itemsize
        assert n * k * pair > VMEM_BUDGET_BYTES  # the premise
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        pay = SparsePayload(
            values=jax.random.normal(ks[0], (n, k), dtype=jnp.float64),
            indices=jax.random.randint(ks[1], (n, k), 0, d * d,
                                       dtype=jnp.int32),
            universe=d * d)
        w = jax.random.uniform(ks[2], (n,), dtype=jnp.float64)
        assert _should_stream(pay.values, pay.indices)
        assert not _should_stream(
            jax.ShapeDtypeStruct((n, k), jnp.float64),
            jax.ShapeDtypeStruct((n, k), jnp.int32))

        comp = TopK(k=k)
        streamed = comp.aggregate(pay, (d, d), weights=w)  # eager: streams
        scaled = scale_payload(pay, w)
        stacked = (scatter_accumulate(scaled.values, scaled.indices,
                                      (d, d)) / n).reshape(d, d)
        np.testing.assert_array_equal(np.asarray(streamed),
                                      np.asarray(stacked))
        # inside jit the stack is a tracer: stacked kernel, same answer
        # to f64 tolerance (XLA may fuse the x*w and /n multiplies)
        jitted = jax.jit(lambda p: comp.aggregate(p, (d, d), weights=w))(pay)
        np.testing.assert_allclose(np.asarray(jitted), np.asarray(streamed),
                                   rtol=0, atol=1e-15)


def test_aggregate_weight_zero_silo_bit_exact():
    """A weight-0 silo contributes nothing, bit-exactly: zeroing silo
    j's weight gives the same aggregate as padding silo j's indices
    out of the payload entirely."""
    with jax.enable_x64(True):
        comp = TopK(k=17)
        shape = (12, 12)
        _, pay = _stacked_payloads(comp, shape)
        w = jnp.asarray([1.0, 0.7, 0.0, 1.0, 0.3])
        dropped = SparsePayload(
            values=pay.values, universe=pay.universe,
            indices=pay.indices.at[2].set(-1))
        w_one = w.at[2].set(1.0)  # padding drops silo 2 regardless
        out = comp.aggregate(pay, shape, weights=w)
        ref = comp.aggregate(dropped, shape, weights=w_one)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_scatter_accumulate_four_devices():
    """The mesh-sharded accumulator on 4 forced host devices: each
    device scatters only its owned row window, and the gathered result
    equals the unsharded scatter EXACTLY — plain, and symmetric via the
    pre-shard mirror expansion. Subprocess so the forced device count
    doesn't leak into this session."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import PartitionSpec as P
        from repro.kernels.scatter_accum import (
            mirror_expand_pairs, scatter_accumulate,
            sharded_scatter_accumulate)
        from repro.launch.sharding import accumulator_spec

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        shape = (16, 16)
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        vals = jax.random.normal(ks[0], (6, 20), dtype=jnp.float64)
        idx = jax.random.randint(ks[1], (6, 20), 0, 256, dtype=jnp.int32)
        idx = idx.at[:, -3:].set(-1)   # wire padding stays inert
        idx = idx.at[4].set(-1)        # one dropped silo

        out = sharded_scatter_accumulate(vals, idx, shape, mesh)
        ref = scatter_accumulate(vals, idx, shape)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

        sym = sharded_scatter_accumulate(vals, idx, shape, mesh,
                                         symmetric=True)
        mv, mi = mirror_expand_pairs(vals, idx, 16)
        np.testing.assert_array_equal(
            np.asarray(sym), np.asarray(scatter_accumulate(mv, mi, shape)))
        base = np.asarray(ref)
        two_pass = base + base.T - np.diag(np.diag(base))
        np.testing.assert_allclose(np.asarray(sym), two_pass,
                                   rtol=0, atol=1e-14)

        spec = accumulator_spec(mesh, shape)
        assert spec.spec == P("data", None), spec.spec
        rep = accumulator_spec(mesh, (15, 16))   # 15 % 4 != 0: replicate
        assert rep.spec == P(None, None), rep.spec
        try:
            sharded_scatter_accumulate(vals, idx, (15, 16), mesh)
        except ValueError:
            pass
        else:
            raise AssertionError("15-row accumulator must refuse 4-way")
        print("SHARDED_SCATTER_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_SCATTER_OK" in out.stdout, out.stdout + out.stderr
