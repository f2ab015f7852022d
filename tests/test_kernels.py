"""Per-kernel allclose sweeps: shapes x dtypes vs the pure-jnp oracles,
executed with interpret=True (the kernel body itself runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_topk import (
    block_topk,
    block_topk_payload,
    block_topk_payload_ref,
    block_topk_ref,
    payload_to_dense,
)
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.hess_update import hess_update, hess_update_ref
from repro.kernels.scatter_accum import (
    block_scatter_accumulate,
    block_scatter_accumulate_ref,
    scatter_accumulate,
    scatter_accumulate_ref,
)
from repro.kernels.tiled_matmul import (
    powersgd_rank_r,
    powersgd_rank_r_ref,
    tiled_matmul,
    tiled_matmul_ref,
)

SHAPES_2D = [(128, 128), (256, 128), (300, 123), (64, 200), (17, 31)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("k", [1, 16, 1000])
def test_block_topk_matches_ref_f32(shape, k):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    out = block_topk(x, k=k, block=128)
    m, n = shape
    pm, pn = (-m) % 128, (-n) % 128
    xp = jnp.pad(x, ((0, pm), (0, pn)))
    ref = block_topk_ref(xp, k=k, block=128)[:m, :n]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("k", [16, 1000])
def test_block_topk_bf16_semantics(shape, k):
    """bf16 quantization produces magnitude TIES, so threshold selection
    may keep a few more entries than the sort-based oracle; check the
    operator semantics instead of entrywise equality: kept entries are a
    superset-by-magnitude selection, count >= min(k, numel), and the
    contraction property holds."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(jnp.bfloat16)
    out = block_topk(x, k=k, block=128)
    xo = np.asarray(out, np.float32)
    xi = np.asarray(x, np.float32)
    kept = xo != 0
    # kept entries equal the input there
    np.testing.assert_allclose(xo[kept], xi[kept])
    # magnitude selection: every kept entry >= every dropped entry within
    # the single 128-block (shapes here are <= 128x... per block) up to ties
    assert kept.sum() >= min(k, (np.abs(xi) > 0).sum()) * 0.99
    # contraction with delta = k/block^2 per tile
    nm2 = float((xi ** 2).sum())
    assert float(((xo - xi) ** 2).sum()) <= nm2 + 1e-3


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("k", [1, 16, 200])
def test_block_topk_payload_matches_ref(shape, k):
    """The payload-emitting kernel agrees with the jnp payload oracle
    entrywise (values AND indices, flat in-tile order) and reconstructs
    the dense kernel's output exactly."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    vals, idx = block_topk_payload(x, k=k, block=128, use_pallas=True,
                                   interpret=True)
    m, n = shape
    pm, pn = (-m) % 128, (-n) % 128
    xp = jnp.pad(x, ((0, pm), (0, pn)))
    rv, ri = block_topk_payload_ref(xp, k=k, block=128)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv))
    dense = payload_to_dense(vals, idx, shape, block=128)
    ref_dense = block_topk(x, k=k, block=128)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(ref_dense))


def test_block_topk_payload_vmap_over_silos():
    """Acceptance: the Pallas payload op agrees with the jnp reference
    under vmap over the silo axis (stacked Hessian diffs), with static
    payload shapes."""
    stack = jax.random.normal(jax.random.PRNGKey(2), (3, 256, 130))
    pad = jnp.pad(stack, ((0, 0), (0, 0), (0, (-130) % 128)))
    vv, ii = jax.vmap(lambda m: block_topk_payload(
        m, k=32, block=128, use_pallas=True, interpret=True))(stack)
    rv, ri = jax.vmap(
        lambda m: block_topk_payload_ref(m, k=32, block=128))(pad)
    assert vv.shape == (3, 2 * 2, 32) and ii.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ii), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(vv), np.asarray(rv))


def test_block_topk_payload_tie_cluster_keeps_exactly_k():
    """Regression: a tie cluster spanning the k-th position must not
    undershoot (threshold-only cut) nor corrupt the reconstruction
    through -1 padding; the kernel's two-phase fill keeps exactly k."""
    t = jnp.zeros((128, 128)).at[:4, :4].set(
        jnp.full((4, 4), 1.0).at[0, 0].set(1.0001))
    vals, idx = block_topk_payload(t, k=3, block=128, use_pallas=True,
                                   interpret=True)
    dense = payload_to_dense(vals, idx, (128, 128), block=128)
    kept = np.asarray(dense) != 0
    assert kept.sum() == 3
    assert float(dense[0, 0]) == float(np.float32(1.0001))
    err = float(jnp.sum((dense - t) ** 2))
    nm2 = float(jnp.sum(t * t))
    assert err <= (1 - 3 / (128 * 128)) * nm2 * (1 + 1e-6)


def test_block_topk_payload_matches_compressor_payload():
    """The kernel's native output format IS BlockSparsePayload: same
    decompressed matrix as the core BlockTopK codec (selection sets
    agree on tie-free data; entry order differs, scatter doesn't care)."""
    from repro.core.compressors import BlockTopK

    x = jax.random.normal(jax.random.PRNGKey(3), (256, 256))
    comp = BlockTopK(k_per_block=64, block=128)
    vals, idx = block_topk_payload(x, k=64, block=128, use_pallas=True,
                                   interpret=True)
    via_kernel = payload_to_dense(vals, idx, x.shape, block=128)
    via_codec = comp.decompress(comp.compress(x), x.shape)
    np.testing.assert_array_equal(np.asarray(via_kernel),
                                  np.asarray(via_codec))


def test_block_topk_payload_dispatch_oracle_matches_kernel():
    """The off-TPU dispatch path (use_pallas=False -> sort-based jnp
    oracle) emits the same payload as the forced Pallas kernel body on
    tie-free data — the two backends of the one payload op agree."""
    x = jax.random.normal(jax.random.PRNGKey(7), (300, 123))
    kv, ki = block_topk_payload(x, k=48, block=128, use_pallas=True,
                                interpret=True)
    ov, oi = block_topk_payload(x, k=48, block=128, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(ov))


def _wide_range_tiles(seed, shape, k, cluster, block=128):
    """f32 entries of magnitude 1e-30..1e30 with random signs, laid out
    so that the kernel and the sort-based reference keep the same
    entries: in each tile the k kept entries (ranked at random positions)
    lie in [1e24, 1e30] and the rest in [1e-30, 1e20] with 10% exact
    zeros, far apart next to the bisection's resolution (max * 2**-32).
    With ``cluster``, 48 entries around rank k share the magnitude 1e23
    (random signs): a tie across the cut, kept in flat order by the
    kernel as by the stable sort. At k >= block**2 every entry is kept
    and the magnitudes span the whole range. Entries outside ``shape``
    are the zero padding the ops add."""
    rng = np.random.default_rng(seed)
    m, n = shape
    gm, gn = -(-m // block), -(-n // block)
    x = np.zeros((gm * block, gn * block), np.float32)
    valid = np.zeros(x.shape, bool)
    valid[:m, :n] = True
    for i in range(gm):
        for j in range(gn):
            win = np.s_[i * block:(i + 1) * block, j * block:(j + 1) * block]
            spots = rng.permutation(np.flatnonzero(valid[win]))  # by rank
            mag = 10.0 ** rng.uniform(-30, 20, spots.size)
            zero = rng.random(spots.size) < 0.1
            if k >= block * block:
                mag = 10.0 ** rng.uniform(-30, 30, spots.size)
            else:
                top = k - 24 if cluster else k
                mag[:top] = 10.0 ** rng.uniform(24, 30, top)
                zero[:top] = False
                if cluster:
                    mag[top:k + 24] = 1e23
                    zero[top:k + 24] = False
            sign = rng.choice([-1.0, 1.0], spots.size)
            tile = x[win]
            tile.flat[spots] = np.where(zero, 0.0, sign * mag)
            x[win] = tile
    return x[:m, :n]


@pytest.mark.parametrize("op", ["block", "diff"])
@pytest.mark.parametrize("shape, k, cluster", [
    ((256, 256), 1024, False),   # n_hi 8: the w8a cell's k
    ((256, 384), 2048, False),   # n_hi 16: the qwen2 cell's k
    ((300, 200), 1024, True),    # padding tiles, a tie across the cut
    ((200, 300), 2048, True),
    ((130, 140), 16384, False),  # k >= block**2: every entry, in order
])
def test_payload_kernels_bitwise_wide_range(op, shape, k, cluster):
    """The payload kernel body keeps exactly the reference's entries and
    emits their values bit for bit, on f32 values spanning 1e-30..1e30
    (the compaction splits each value into bf16 pieces and puts it back
    together); every tile fills exactly min(k, block**2) slots; the
    fused op's sumsq is the reference's."""
    x = jnp.asarray(_wide_range_tiles(k + len(shape) * shape[0], shape, k,
                                      cluster))
    m, n = shape
    xp = jnp.pad(x, ((0, (-m) % 128), (0, (-n) % 128)))
    if op == "block":
        vals, idx = block_topk_payload(x, k=k, block=128, use_pallas=True,
                                       interpret=True)
        rv, ri = block_topk_payload_ref(xp, k=min(k, 128 * 128), block=128)
    else:
        from repro.kernels.block_topk import diff_topk_payload
        from repro.kernels.block_topk.ref import diff_topk_payload_ref

        # 2x - x == x exactly: the fused diff sees the wide-range values
        vals, idx, sq = diff_topk_payload(2 * x, x, k=k, block=128,
                                          use_pallas=True, interpret=True)
        rv, ri, rsq = diff_topk_payload_ref(2 * xp, xp,
                                            k=min(k, 128 * 128), block=128)
        np.testing.assert_allclose(float(sq), float(jnp.sum(rsq)),
                                   rtol=1e-6)
    idx, vals = np.asarray(idx), np.asarray(vals)
    assert ((idx >= 0).sum(axis=1) == min(k, 128 * 128)).all()
    # slots hold the strict entries, then the boundary ties, each in flat
    # order; the reference sorts a tile's kept indices
    if not cluster:
        np.testing.assert_array_equal(idx, np.asarray(ri))
    order = np.argsort(idx, axis=1)
    np.testing.assert_array_equal(np.take_along_axis(idx, order, 1),
                                  np.asarray(ri))
    np.testing.assert_array_equal(
        np.take_along_axis(vals, order, 1).view(np.uint32),
        np.asarray(rv).view(np.uint32))


def test_payload_kernel_empty_slots_are_minus_one():
    """Slots past the selection's count hold index -1 and value 0. They
    exist only in the kernel's uncropped (kp/128, 128) blocks: here k =
    1000 rounds up to kp = 1024, and the selection is the 1000 largest
    plus the one entry in the bisection's last bracket, so slots 1001 on
    are empty."""
    import functools

    from jax.experimental import pallas as pl

    from repro.kernels.block_topk import kernel as K

    k = 1000
    rng = np.random.default_rng(3)
    x = np.full(128 * 128, 1e-3, np.float32) * rng.uniform(1, 2, 128 * 128)
    spots = rng.permutation(128 * 128)
    x[spots[:k]] = rng.uniform(1, 2, k)
    x[spots[k]] = 0.5
    x = jnp.asarray(x.reshape(128, 128))
    row, scratch, kp = K._payload_specs(128, k, 1)
    slots = (1, kp // 128, 128)
    vals, idx = pl.pallas_call(
        functools.partial(K._topk_payload_tile_kernel, k=k),
        grid=(1, 1),
        in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
        out_specs=(row, row),
        out_shape=(jax.ShapeDtypeStruct(slots, jnp.float32),
                   jax.ShapeDtypeStruct(slots, jnp.int32)),
        scratch_shapes=scratch,
        interpret=True,
    )(x)
    vals, idx = np.asarray(vals).reshape(kp), np.asarray(idx).reshape(kp)
    kept = np.append(np.sort(spots[:k]), spots[k])  # strict, then the tie
    np.testing.assert_array_equal(idx[:k + 1], kept)
    np.testing.assert_array_equal(vals[:k + 1],
                                  np.asarray(x).reshape(-1)[kept])
    assert (idx[k + 1:] == -1).all() and (vals[k + 1:] == 0).all()


# None -> single-block kernel; (8, 128) -> forced tiled kernel (multi-
# tile grids even on the small test shapes)
SCATTER_PATHS = [None, (8, 128)]


@pytest.mark.parametrize("tile", SCATTER_PATHS)
@pytest.mark.parametrize("shape", [(37, 41), (128, 128), (1, 300)])
@pytest.mark.parametrize("k", [7, 700])
def test_scatter_accum_matches_ref(shape, k, tile):
    """The Pallas scatter-accumulate kernels (one-hot-matmul scatter,
    chunked over silos x entries; single-block and output-tiled) agree
    with the XLA scatter-add oracle, including duplicate indices across
    silos and -1 payload padding."""
    n = 4
    d0, d1 = shape
    vals = jax.random.normal(jax.random.PRNGKey(0), (n, k))
    idx = jax.random.randint(jax.random.PRNGKey(1), (n, k), 0,
                             d0 * d1).astype(jnp.int32)
    idx = idx.at[:, -2:].set(-1)  # padding slots with nonzero values
    out = scatter_accumulate(vals, idx, shape, use_pallas=True,
                             interpret=True, tile=tile)
    ref = scatter_accumulate_ref(vals, idx, shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("tile", SCATTER_PATHS)
def test_scatter_accum_accumulates_duplicates(tile):
    """Every silo addressing the same cell: the accumulator must sum all
    of them (the server S = sum_i S_i semantics), not keep the last."""
    vals = jnp.ones((5, 3))
    idx = jnp.zeros((5, 3), jnp.int32).at[:, 1].set(7).at[:, 2].set(-1)
    out = scatter_accumulate(vals, idx, (2, 4), use_pallas=True,
                             interpret=True, tile=tile)
    expect = np.zeros((2, 4))
    expect[0, 0] = 5.0
    expect[1, 3] = 5.0
    np.testing.assert_allclose(np.asarray(out), expect, rtol=0, atol=1e-6)


@pytest.mark.parametrize("tile", SCATTER_PATHS)
@pytest.mark.parametrize("shape", [(37, 41), (17, 200)])
def test_scatter_accum_k_not_chunk_multiple(shape, tile):
    """k that is neither a _CHUNK multiple nor below it (513, 700 with
    _CHUNK=512) forces the zero/-1 chunk padding on both kernels; the
    padded tail must contribute nothing."""
    n = 3
    d0, d1 = shape
    for k in (513, 700):
        vals = jax.random.normal(jax.random.PRNGKey(k), (n, k))
        idx = jax.random.randint(jax.random.PRNGKey(k + 1), (n, k), 0,
                                 d0 * d1).astype(jnp.int32)
        out = scatter_accumulate(vals, idx, shape, use_pallas=True,
                                 interpret=True, tile=tile)
        ref = scatter_accumulate_ref(vals, idx, shape)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-5)


@pytest.mark.parametrize("tile", SCATTER_PATHS)
def test_scatter_accum_duplicates_across_silos_and_chunks(tile):
    """The same flat cell addressed by every silo AND from both sides of
    a chunk boundary (k=600 > _CHUNK=512 splits each silo's stream into
    two kernel programs) must accumulate every contribution."""
    n, k, shape = 3, 600, (37, 41)
    target = 5 * 41 + 7  # one fixed cell
    vals = jax.random.normal(jax.random.PRNGKey(0), (n, k))
    idx = jax.random.randint(jax.random.PRNGKey(1), (n, k), 0,
                             shape[0] * shape[1]).astype(jnp.int32)
    # first and last slot of every silo -> same cell (slot 599 lands in
    # the second chunk after padding to 1024)
    idx = idx.at[:, 0].set(target).at[:, -1].set(target)
    out = scatter_accumulate(vals, idx, shape, use_pallas=True,
                             interpret=True, tile=tile)
    ref = scatter_accumulate_ref(vals, idx, shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    expect_cell = float(jnp.sum(jnp.where(idx == target, vals, 0.0)))
    assert abs(float(out[5, 7]) - expect_cell) < 1e-4


@pytest.mark.parametrize("tile", SCATTER_PATHS)
def test_scatter_accum_all_padding_silo(tile):
    """A silo whose payload is entirely -1 padding (an absent
    participant) contributes exactly zero even with nonzero values."""
    n, k, shape = 4, 20, (17, 31)
    vals = jax.random.normal(jax.random.PRNGKey(2), (n, k))
    idx = jax.random.randint(jax.random.PRNGKey(3), (n, k), 0,
                             shape[0] * shape[1]).astype(jnp.int32)
    idx = idx.at[1, :].set(-1)
    out = scatter_accumulate(vals, idx, shape, use_pallas=True,
                             interpret=True, tile=tile)
    ref = scatter_accumulate_ref(vals, idx, shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    without = scatter_accumulate_ref(
        jnp.delete(vals, 1, axis=0), jnp.delete(idx, 1, axis=0), shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(without),
                               rtol=0, atol=1e-5)


def test_scatter_accum_auto_tiles_above_vmem_budget():
    """Dispatch: a padded accumulator above the VMEM budget (f32
    1600x1664 > 8 MiB) silently routes to the tiled kernel and still
    matches the oracle — the d ~ 1500 single-block ceiling is gone."""
    from repro.kernels.scatter_accum.ops import _VMEM_ACC_BUDGET_BYTES

    n, k, shape = 2, 64, (1600, 1664)
    assert shape[0] * shape[1] * 4 > _VMEM_ACC_BUDGET_BYTES
    vals = jax.random.normal(jax.random.PRNGKey(4), (n, k))
    idx = jax.random.randint(jax.random.PRNGKey(5), (n, k), 0,
                             shape[0] * shape[1]).astype(jnp.int32)
    out = scatter_accumulate(vals, idx, shape, use_pallas=True,
                             interpret=True)
    ref = scatter_accumulate_ref(vals, idx, shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("grid", [(1, 1), (2, 3)])
@pytest.mark.parametrize("kb", [1, 11])
def test_block_scatter_accum_matches_ref(grid, kb):
    gm, gn = grid
    n, b = 4, 8
    nblk = gm * gn
    vals = jax.random.normal(jax.random.PRNGKey(2), (n, nblk, kb))
    idx = jax.random.randint(jax.random.PRNGKey(3), (n, nblk, kb), 0,
                             b * b).astype(jnp.int32)
    idx = idx.at[:, :, -1:].set(-1)
    out = block_scatter_accumulate(vals, idx, grid, b, use_pallas=True,
                                   interpret=True)
    ref = block_scatter_accumulate_ref(vals, idx, grid, b)
    assert out.shape == (gm * b, gn * b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_scatter_accum_backs_compressor_aggregate():
    """Cross-validation: the kernel path reproduces the TopK/BlockTopK
    aggregate (which routes through the same ops with backend dispatch)
    on real compressed payloads."""
    from repro.core.compressors import BlockTopK, TopK

    m = jax.random.normal(jax.random.PRNGKey(4), (5, 256, 256))
    tk = TopK(k=300)
    pay = jax.vmap(tk.compress)(m)
    via_kernel = scatter_accumulate(pay.values, pay.indices, (1, 256 * 256),
                                    use_pallas=True,
                                    interpret=True).reshape(256, 256) / 5
    np.testing.assert_allclose(np.asarray(via_kernel),
                               np.asarray(tk.aggregate(pay, (256, 256))),
                               rtol=0, atol=1e-5)

    bt = BlockTopK(k_per_block=16, block=128)
    payb = jax.vmap(lambda x: bt.compress(x))(m)
    via_kernel = block_scatter_accumulate(payb.values, payb.indices, (2, 2),
                                          128, use_pallas=True,
                                          interpret=True) / 5
    np.testing.assert_allclose(np.asarray(via_kernel),
                               np.asarray(bt.aggregate(payb, (256, 256))),
                               rtol=0, atol=1e-5)


def test_block_topk_is_contractive():
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    out = block_topk(x, k=64, block=128)
    delta = 64 / (128 * 128)
    nm2 = float(jnp.sum(x * x))
    assert float(jnp.sum(out * out)) <= nm2 + 1e-4
    assert float(jnp.sum((out - x) ** 2)) <= (1 - delta) * nm2 * (1 + 1e-6)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_hess_update_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], shape).astype(dtype)
    d = jax.random.normal(ks[1], shape).astype(dtype)
    s = jax.random.normal(ks[2], shape).astype(dtype)
    out, l = hess_update(h, d, s, alpha=0.7)
    ref_out, ref_l = hess_update_ref(h, d, s, alpha=0.7)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32), atol=tol,
                               rtol=tol)
    assert abs(float(l) - float(ref_l)) <= tol * max(1.0, float(ref_l))


@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 64, 128),
                                 (100, 90, 70), (33, 257, 129)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_tiled_matmul_matches_ref(mnk, dtype):
    m, n, k = mnk
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
    out = tiled_matmul(a, b)
    ref = tiled_matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol * k,
                               rtol=tol)


@pytest.mark.parametrize("shape", [(150, 170), (256, 128)])
@pytest.mark.parametrize("r", [1, 4])
def test_powersgd_matches_ref(shape, r):
    m = jax.random.normal(jax.random.PRNGKey(0), shape)
    out = powersgd_rank_r(m, r)
    ref = powersgd_rank_r_ref(m, r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_powersgd_captures_low_rank():
    """On an exactly rank-r matrix the compressor is (near) exact."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    u = jax.random.normal(k1, (96, 3))
    v = jax.random.normal(k2, (3, 80))
    m = u @ v
    out = powersgd_rank_r(m, 3, iters=4)
    rel = float(jnp.linalg.norm(out - m) / jnp.linalg.norm(m))
    assert rel < 1e-3


@pytest.mark.parametrize("t", [128, 200, 384])
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_matches_ref(t, dtype):
    b, h, hd = 2, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, h, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, h, hd)).astype(dtype)
    out = flash_attention(q, k, v)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    ref = flash_attention_ref(fold(q), fold(k), fold(v)) \
        .reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_is_causal():
    b, t, h, hd = 1, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    out1 = flash_attention(q, k, v)
    # perturbing the FUTURE must not change past outputs
    k2 = k.at[:, -1].add(10.0)
    v2 = v.at[:, -1].add(10.0)
    out2 = flash_attention(q, k2, v2)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)


def test_flash_kernel_matches_model_attention_path():
    """Cross-validation: the Pallas flash kernel agrees with the model's
    XLA chunked-attention path on identical GQA inputs (n_rep folded)."""
    from repro.models.attention import _sdpa_chunked

    b, t, h, hd = 1, 320, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    out_model = _sdpa_chunked(q, k, v, n_rep=1, window=None, chunk=128)
    out_flash = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_flash),
                               atol=3e-5, rtol=3e-5)


# -- hess_update edge tiles (regression: grid used to floor-divide) -----------


def test_hess_update_kernel_edge_tiles_not_dropped():
    """Direct kernel call on a shape smaller than one block in the
    column dim: the old ``grid = (m // block, n // block)`` produced an
    EMPTY grid for (300, 123) and silently dropped every edge tile; the
    kernel now pads to the block grid and crops."""
    from repro.kernels.hess_update.kernel import hess_update_kernel

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(ks[0], (300, 123))
    d = jax.random.normal(ks[1], (300, 123))
    s = jax.random.normal(ks[2], (300, 123))
    out, err = hess_update_kernel(h, d, s, 0.7, block=128, interpret=True)
    ref_out, ref_l = hess_update_ref(h, d, s, alpha=0.7)
    assert out.shape == (300, 123)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-6, rtol=1e-6)
    # zero padding contributes exactly 0 to the error partials
    np.testing.assert_allclose(float(jnp.sqrt(jnp.sum(err))), float(ref_l),
                               rtol=1e-6)
    # the edge rows/cols are real data, not zeros
    assert float(jnp.abs(out[256:, :]).sum()) > 0
    assert float(jnp.abs(out[:, 120:]).sum()) > 0


# -- fused diff -> top-k -> payload -------------------------------------------


@pytest.mark.parametrize("use_pallas", [True, False])
def test_diff_topk_payload_fused_matches_unfused_f64(use_pallas):
    """Equivalence pin at f64: the fused kernel's payload equals the
    unfused ``block_topk_payload(a - b)`` on the same backend, and its
    sumsq equals ``sum((a - b)**2)``. Zero accuracy change is the
    acceptance bar for the fusion."""

    from repro.kernels.block_topk import diff_topk_payload

    with jax.enable_x64(True):
        ka, kb = jax.random.split(jax.random.PRNGKey(11))
        a = jax.random.normal(ka, (256, 256), jnp.float64)
        b = jax.random.normal(kb, (256, 256), jnp.float64)
        vals, idx, sq = diff_topk_payload(a, b, k=32, block=128,
                                          use_pallas=use_pallas,
                                          interpret=True)
        uv, ui = block_topk_payload(a - b, k=32, block=128,
                                    use_pallas=use_pallas, interpret=True)
        assert vals.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ui))
        np.testing.assert_allclose(np.asarray(vals), np.asarray(uv),
                                   rtol=1e-15, atol=0)
        # the free norm: per-tile partials vs the dense reduction
        np.testing.assert_allclose(float(sq), float(jnp.sum((a - b) ** 2)),
                                   rtol=1e-12)


def test_diff_topk_payload_dispatch_oracle_matches_kernel():
    """The two backends of the fused op (Pallas body vs sort-based jnp
    oracle) agree on tie-free data: same dense reconstruction, same
    sumsq."""
    ka, kb = jax.random.split(jax.random.PRNGKey(12))
    a = jax.random.normal(ka, (300, 123))
    b = jax.random.normal(kb, (300, 123))
    from repro.kernels.block_topk import diff_topk_payload

    kv, ki, ksq = diff_topk_payload(a, b, k=48, block=128, use_pallas=True,
                                    interpret=True)
    ov, oi, osq = diff_topk_payload(a, b, k=48, block=128, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(ov))
    np.testing.assert_allclose(float(ksq), float(osq), rtol=1e-6)
    # padding tiles contribute zero: sumsq is the UNPADDED diff norm
    np.testing.assert_allclose(float(ksq),
                               float(jnp.sum((a - b) ** 2)), rtol=1e-6)


def test_diff_topk_payload_mixed_dtype_promotes():
    """result_type promotion matches the semantics of ``a - b``."""
    from repro.kernels.block_topk import diff_topk_payload

    a = jax.random.normal(jax.random.PRNGKey(1), (128, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (128, 128)).astype(
        jnp.bfloat16)
    vals, idx, sq = diff_topk_payload(a, b, k=8, block=128,
                                      use_pallas=False)
    assert vals.dtype == (a - b).dtype


# -- symmetric mirror fused into the scatter ----------------------------------


@pytest.mark.parametrize("tile", SCATTER_PATHS)
def test_scatter_accum_symmetric_fused_matches_two_pass_f64(tile):
    """The in-kernel mirror (every off-diagonal (r, c) also lands at
    (c, r)) equals the two-pass oracle ``c + c.T - diag(diag(c))`` at
    f64 — on both the single-block and tiled kernels, with -1 payload
    padding present."""

    with jax.enable_x64(True):
        d = 64
        ks = jax.random.split(jax.random.PRNGKey(13), 3)
        r = jax.random.randint(ks[0], (3, 40), 0, d)
        c = jax.random.randint(ks[1], (3, 40), 0, d)
        rows, cols = jnp.maximum(r, c), jnp.minimum(r, c)  # lower triangle
        idx = (rows * d + cols).astype(jnp.int32)
        idx = idx.at[:, -5:].set(-1)  # payload padding must stay inert
        vals = jax.random.normal(ks[2], (3, 40), jnp.float64)
        out = scatter_accumulate(vals, idx, (d, d), use_pallas=True,
                                 interpret=True, tile=tile, symmetric=True)
        base = scatter_accumulate_ref(vals, idx, (d, d))
        expect = base + base.T - jnp.diag(jnp.diag(base))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-12, atol=1e-12)
        # the jnp dispatch path agrees exactly
        ref = scatter_accumulate(vals, idx, (d, d), use_pallas=False,
                                 symmetric=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(expect))


def test_scatter_accum_symmetric_diagonal_not_doubled():
    """A payload of diagonal entries only: the fused mirror must leave
    the diagonal single-counted (mirror contribution masked at r==c)."""
    d = 16
    diag_idx = (jnp.arange(8) * d + jnp.arange(8)).astype(jnp.int32)
    vals = jnp.arange(1.0, 9.0)[None, :]
    out = scatter_accumulate(vals, diag_idx[None, :], (d, d),
                             use_pallas=True, interpret=True,
                             symmetric=True)
    plain = scatter_accumulate(vals, diag_idx[None, :], (d, d),
                               use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


# -- streamed silo-slab scatter-accumulate ------------------------------------


def _pair_stream(n, k, shape, seed=0, pad_rows=(), dtype=jnp.float32):
    d0, d1 = shape
    kv, ki = jax.random.split(jax.random.PRNGKey(seed))
    vals = jax.random.normal(kv, (n, k), dtype=dtype)
    idx = jax.random.randint(ki, (n, k), 0, d0 * d1, dtype=jnp.int32)
    for r in pad_rows:
        idx = idx.at[r].set(-1)  # all-padding silo (e.g. dropped client)
    return vals, idx


@pytest.mark.parametrize("silo_chunk", [1, 2, 3, 7, None])
@pytest.mark.parametrize("symmetric", [False, True])
def test_streamed_matches_stacked_bitwise(silo_chunk, symmetric):
    """The streamed silo-slab path must be BITWISE equal to the stacked
    scatter on the portable path — including slabs that are entirely
    padding (silos 10 and 11 form one all-padding chunk at
    silo_chunk=2) and across every chunk-boundary alignment."""
    from repro.kernels.scatter_accum import streamed_scatter_accumulate

    shape = (24, 24)
    vals, idx = _pair_stream(13, 40, shape, pad_rows=(3, 10, 11, 12))
    stacked = scatter_accumulate(vals, idx, shape, use_pallas=False,
                                 symmetric=symmetric)
    streamed = streamed_scatter_accumulate(
        vals, idx, shape, silo_chunk=silo_chunk, use_pallas=False,
        symmetric=symmetric)
    np.testing.assert_array_equal(np.asarray(streamed),
                                  np.asarray(stacked))


@pytest.mark.parametrize("tile", [None, (8, 8)])
@pytest.mark.parametrize("silo_chunk", [2, 5])
def test_streamed_matches_stacked_forced_pallas(tile, silo_chunk):
    """Forced Pallas dispatch (interpret mode — the kernel bodies run):
    chaining silo slabs through the init-accumulator kernels replays
    the stacked kernel's add sequence exactly."""
    from repro.kernels.scatter_accum import streamed_scatter_accumulate

    shape = (16, 16)
    vals, idx = _pair_stream(7, 12, shape, pad_rows=(4,))
    stacked = scatter_accumulate(vals, idx, shape, use_pallas=True,
                                 interpret=True, tile=tile, chunk=8)
    streamed = streamed_scatter_accumulate(
        vals, idx, shape, silo_chunk=silo_chunk, use_pallas=True,
        interpret=True, tile=tile, chunk=8)
    np.testing.assert_array_equal(np.asarray(streamed),
                                  np.asarray(stacked))


def test_silo_chunk_for_respects_budget():
    """The streaming rule: the largest silo slab whose (value, index)
    pair stream still fits the shared kernel VMEM budget — never zero,
    even when one silo alone overflows the budget."""
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.scatter_accum import silo_chunk_for

    k = 1024
    pair = jnp.dtype(jnp.float64).itemsize + jnp.dtype(jnp.int32).itemsize
    chunk = silo_chunk_for(k, jnp.float64)
    assert chunk >= 1
    assert chunk * k * pair <= VMEM_BUDGET_BYTES
    assert (chunk + 1) * k * pair > VMEM_BUDGET_BYTES
    # a single monster silo still streams, one silo at a time
    assert silo_chunk_for(10 * VMEM_BUDGET_BYTES, jnp.float64) == 1
