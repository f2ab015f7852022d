"""Block-local Top-K compressor kernels: dense-masked and payload-emitting.

Grid: one program per (bm, bn) tile held in VMEM. Per tile, keep the k
largest-magnitude entries and zero the rest. Instead of a sort (hostile
to the VPU), the k-th magnitude is found by ~32 rounds of bisection on
[0, max|x|] — each round is a full-tile compare+popcount, all
vector-friendly. Entries with |x| >= threshold survive.

``block_topk_kernel`` writes the dense masked tile back (the seed-era
output format). ``block_topk_payload_kernel`` emits the WIRE FORMAT
directly — per tile, k (value, in-tile flat index) pairs in flat order —
so the compressed uplink never materializes a dense (d, d) buffer. The
survivor compaction is scatter/sort-free: flat-order positions come from
triangular-matmul cumsums, and the payload slots are filled 8 tile rows
at a time with a two-level one-hot contraction (slot = 128 * hi + lo, so
a row costs a (kp/128, b) and a (128, b) one-hot instead of a (b*b, k)
one — bounded VMEM at any k); empty slots carry index -1. Every
contraction is a single bf16 MXU pass accumulated in f32 whose operands
are exact in bf16 (0/1 masks, counts <= b, one-hots times bf16 pieces of
the value and the entry's row and column), so the payload is exact:
f32 tiles split each value into three bf16 pieces, bf16 tiles take it
whole; f64 tiles (interpret mode only) keep an f64 contraction. Tiles
are at most 256 wide, where counts and coordinates stay exact in bf16.
Payload rows leave the kernel as (kp/128, 128) blocks per tile, kp = k
rounded up to a lane multiple, and the wrappers crop them to k.

The resulting operator is contractive with delta = k / (bm*bn) per
Definition 3.3 (contraction holds per tile; Frobenius norm is separable
across tiles) — see DESIGN.md §3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROW_GROUP = 8  # tile rows compacted per loop step
_HIGHEST = jax.lax.Precision.HIGHEST


def _topk_tile_kernel(x_ref, o_ref, *, k: int, iters: int = 32):
    x = x_ref[...]
    ax = jnp.abs(x).astype(jnp.float32)
    numel = ax.size

    if k >= numel:
        o_ref[...] = x
        return

    hi = jnp.max(ax)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((ax >= mid).astype(jnp.int32))
        # too many survivors -> raise threshold
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    thr = hi  # count(ax >= hi) <= k <= count(ax >= lo)
    o_ref[...] = jnp.where(ax >= thr, x, jnp.zeros_like(x))


def block_topk_kernel(x: jax.Array, k: int, block: int = 128,
                      interpret: bool = False) -> jax.Array:
    """x: (M, N) with M, N multiples of ``block`` (ops.py pads)."""
    m, n = x.shape
    grid = (m // block, n // block)
    return pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((block, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def _bisect_bracket(ax: jax.Array, k: int, iters: int):
    """Bisection bracket (lo, hi) on |x| with
    count(ax >= hi) <= k <= count(ax >= lo) (full-tile scalars)."""
    hi = jnp.max(ax)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((ax >= mid).astype(jnp.int32))
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    return jax.lax.fori_loop(0, iters, body, (lo, hi))


def _iota(shape, dim):
    """f32 iota (the TPU lowering builds only integer iotas)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(
        jnp.float32)


def _mxu_bf16(a, b, dims):
    """One bf16 MXU pass accumulated in f32: exact wherever every operand
    is exact in bf16 and every output sums one nonzero product (or
    integers below 2**24). The precision is explicit: under a caller's
    ``default_matmul_precision("highest")`` Mosaic would be asked for fp32
    passes, which it refuses on bf16 operands."""
    return jax.lax.dot_general(a.astype(jnp.bfloat16),
                               b.astype(jnp.bfloat16), dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _flat_positions(mask: jax.Array) -> jax.Array:
    """Flat-order exclusive position of each True entry, scatter/sort-
    free: the within-row inclusive cumsum and the row offsets are
    triangular matmuls (MXU work, no 1D scans), every operand a full
    (b, b) tile of 0/1 or row counts <= b (exact in bf16 for b <= 256).
    mask is (b0, b1) f32."""
    b0, b1 = mask.shape
    mm = (((1,), (0,)), ((), ()))
    col = _iota((b1, b1), 0)
    upper = (col <= _iota((b1, b1), 1)).astype(jnp.float32)
    incl = _mxu_bf16(mask, upper, mm)                     # (b0, b1)
    row_count = _mxu_bf16(mask, jnp.ones((b1, b1), jnp.float32), mm)
    row = _iota((b0, b0), 0)
    strict_lower = (_iota((b0, b0), 1) < row).astype(jnp.float32)
    row_offset = _mxu_bf16(strict_lower, row_count, mm)  # (b0, b1), per row
    return row_offset + incl - mask                       # (b0, b1)


def _bf16_pieces(x: jax.Array, n: int) -> list:
    """x (f32) as n terms, each x's residual with its low 16 bits cut, so
    exact in bf16; for n = 3 the last residual has at most 8 significant
    bits, and x_0 + (x_1 + x_2) == x exactly (normal f32 x)."""
    pieces = []
    for _ in range(n - 1):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        top = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                           jnp.float32)
        pieces.append(top)
        x = x - top
    return pieces + [x]


def _compact_bf16(x_rows, pos_ref, mask_ref, vals_ref, idx_ref, n_sel,
                  dtype, b0: int, b1: int):
    """Compaction of f32 and bf16 tiles: one single-pass bf16 contraction
    per 8-row group. Slot s = 128 * hi + lo; entry e fills (hi_e, lo_e).
    The stationary operand is the lo one-hot lo[l, e] (the MXU latches it
    transposed), the streamed one the hi one-hot times a stack of rows
    exact in bf16: the value's pieces (3 for f32, 1 for bf16), the tile
    row and the tile column of each entry. The group's 8 rows go side by
    side along the contraction (K = 8 * b1). Each slot sums one nonzero
    product, so the f32 results are exact; value and index are put back
    together after the loop. Tie overflow (pos >= kp) matches no kept
    slot."""
    n_hi = vals_ref.shape[0]
    nh = -(-n_hi // 8) * 8              # hi rows padded to the f32 tiling
    n_pieces = 3 if dtype == jnp.float32 else 1
    rows = _ROW_GROUP if b0 % _ROW_GROUP == 0 else 1
    hio = _iota((nh, b1), 0)
    loio = _iota((_LANES, b1), 0)
    col = _iota((1, b1), 1)
    nt = (((1,), (1,)), ((), ()))       # contract the entries (lanes)

    def group_body(g, acc):
        r0 = g * rows
        pos = pos_ref[pl.ds(r0, rows), :]               # (rows, b1)
        sel = mask_ref[pl.ds(r0, rows), :]
        p_hi = jnp.floor(pos * (1.0 / _LANES))
        p_lo = pos - _LANES * p_hi
        pieces = _bf16_pieces(x_rows(r0, rows).astype(jnp.float32), n_pieces)
        streamed, lo_onehot = [], []
        for r in range(rows):
            hi_onehot = jnp.where(p_hi[r:r + 1] == hio, sel[r:r + 1], 0.0)
            tile_row = jnp.full((1, b1), (r0 + r).astype(jnp.float32))
            terms = [p[r:r + 1] for p in pieces] + [tile_row, col]
            streamed.append(jnp.concatenate([hi_onehot * t for t in terms]))
            lo_onehot.append(jnp.where(p_lo[r:r + 1] == loio, 1.0, 0.0))
        return acc + _mxu_bf16(jnp.concatenate(streamed, axis=1),
                               jnp.concatenate(lo_onehot, axis=1), nt)

    acc = jax.lax.fori_loop(
        0, b0 // rows, group_body,
        jnp.zeros(((n_pieces + 2) * nh, _LANES), jnp.float32))
    part = [acc[i * nh:i * nh + n_hi] for i in range(n_pieces + 2)]
    vals = part[0]
    if n_pieces == 3:
        vals = vals + (part[1] + part[2])   # smallest first: exact
    # the selected entries hold positions 0 .. n_sel - 1: slot s is
    # filled iff s < n_sel
    slot = _iota((n_hi, _LANES), 0) * _LANES + _iota((n_hi, _LANES), 1)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = jnp.where(slot < n_sel, part[-2] * b1 + part[-1],
                             -1.0).astype(jnp.int32)


def _contract_rows(a, b, acc):
    """sum_r a[r] @ b[r].T for (rows, m, b1) and (rows, n, b1) stacks: a
    batched matmul over the row axis, contracting lanes, then a sum."""
    out = jax.lax.dot_general(a, b, (((2,), (2,)), ((0,), (0,))),
                              precision=_HIGHEST,
                              preferred_element_type=acc)
    return jnp.sum(out, axis=0)


def _compact_f64(x_rows, pos_ref, mask_ref, vals_ref, idx_ref, b0: int,
                 b1: int):
    """Compaction of f64 tiles (interpret mode only): per 8-row group,
    hi_onehot[h, e] (with the value, id or fill flag folded in) times
    lo_onehot[l, e], contracted over the row, adds that row's entries to
    the (kp/128, 128) slot grid in f64. Each slot sums one entry + zeros,
    so the contraction is exact."""
    n_hi = vals_ref.shape[0]
    rows = _ROW_GROUP if b0 % _ROW_GROUP == 0 else 1
    hio = _iota((rows, n_hi, b1), 1)
    loio = _iota((rows, _LANES, b1), 1)
    local_ids = _iota((rows, 1, b1), 0) * b1 + _iota((rows, 1, b1), 2)

    def group_body(g, carry):
        vals, ids, filled = carry
        r0 = g * rows
        pos = pos_ref[pl.ds(r0, rows), :][:, None, :]   # (rows, 1, b1)
        sel = mask_ref[pl.ds(r0, rows), :][:, None, :]
        p_hi = jnp.floor(pos * (1.0 / _LANES))
        p_lo = pos - _LANES * p_hi
        hi_onehot = (p_hi == hio).astype(jnp.float32) * sel
        lo_onehot = (p_lo == loio).astype(jnp.float32)
        flat_ids = r0.astype(jnp.float32) * b1 + local_ids
        xr = x_rows(r0, rows).astype(jnp.float64)[:, None, :]
        vals = vals + _contract_rows(hi_onehot.astype(jnp.float64) * xr,
                                     lo_onehot.astype(jnp.float64),
                                     jnp.float64)
        ids = ids + _contract_rows(hi_onehot * flat_ids, lo_onehot,
                                   jnp.float32)
        filled = filled + _contract_rows(hi_onehot, lo_onehot, jnp.float32)
        return vals, ids, filled

    zeros = jnp.zeros((n_hi, _LANES), jnp.float32)
    vals, ids, filled = jax.lax.fori_loop(
        0, b0 // rows, group_body, (zeros.astype(jnp.float64), zeros, zeros))
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = jnp.where(filled > 0.0, ids, -1.0).astype(jnp.int32)


def _emit_topk_payload(x, x_rows, vals_ref, idx_ref, pos_ref, mask_ref, *,
                       k: int, iters: int = 32):
    """Shared payload-emission body: select the k largest-magnitude
    entries of the in-VMEM tile ``x`` and write the (kp/128, 128)
    value/index payload blocks — used by both the plain top-k kernel and
    the fused diff->top-k kernel. ``x_rows(r0, n)`` re-reads tile rows
    [r0, r0 + n); ``pos_ref``/``mask_ref`` are (b0, b1) f32 scratch."""
    b0, b1 = x.shape
    ax = jnp.abs(x).astype(jnp.float32)

    # two-phase selection (exactly k entries, Def 3.3-preserving even
    # under ties): everything strictly above the bisection bracket
    # first, then boundary ties in flat order until k slots fill
    if k >= b0 * b1:
        strict = jnp.ones(x.shape, jnp.float32)
        tie = jnp.zeros(x.shape, jnp.float32)
    else:
        lo, hi = _bisect_bracket(ax, k, iters)
        strict = (ax >= hi).astype(jnp.float32)
        tie = (ax >= lo).astype(jnp.float32) * (1.0 - strict)

    n_strict = jnp.sum(strict)
    pos_ref[...] = jnp.where(strict > 0, _flat_positions(strict),
                             n_strict + _flat_positions(tie))
    mask_ref[...] = strict + tie

    if x.dtype == jnp.float64:
        _compact_f64(x_rows, pos_ref, mask_ref, vals_ref, idx_ref, b0, b1)
    else:
        _compact_bf16(x_rows, pos_ref, mask_ref, vals_ref, idx_ref,
                      n_strict + jnp.sum(tie), x.dtype, b0, b1)


def _topk_payload_tile_kernel(x_ref, vals_ref, idx_ref, pos_ref, mask_ref,
                              *, k: int, iters: int = 32):
    _emit_topk_payload(x_ref[...], lambda r, n: x_ref[pl.ds(r, n), :],
                       vals_ref, idx_ref, pos_ref, mask_ref, k=k,
                       iters=iters)


def _diff_topk_payload_tile_kernel(a_ref, b_ref, vals_ref, idx_ref, sq_ref,
                                   pos_ref, mask_ref, *, k: int,
                                   iters: int = 32):
    """Fused uplink tile: D = a - b is formed IN VMEM, its squared
    Frobenius partial written to the tile's (1, 128) cell, and its
    top-k payload emitted — the dense (d, d) difference never exists in
    HBM."""
    x = a_ref[...] - b_ref[...]                     # (b0, b1), VMEM only
    acc = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    xa = x.astype(acc)
    sq_ref[...] = jnp.broadcast_to(jnp.sum(xa * xa, keepdims=True),
                                   sq_ref.shape).astype(sq_ref.dtype)
    _emit_topk_payload(
        x, lambda r, n: a_ref[pl.ds(r, n), :] - b_ref[pl.ds(r, n), :],
        vals_ref, idx_ref, pos_ref, mask_ref, k=k, iters=iters)


def _payload_specs(block: int, k: int, gn: int):
    """Per-tile (kp/128, 128) payload blocks of a (nblocks, kp/128, 128)
    output (full trailing dims: a legal TPU block at any k), the two
    (block, block) f32 scratch tiles, and kp."""
    if block > 2 * _LANES:
        raise ValueError(f"block {block} > 256: the compaction's counts "
                         "and coordinates would not be exact in bf16")
    kp = -(-k // _LANES) * _LANES
    row = pl.BlockSpec((None, kp // _LANES, _LANES),
                       lambda i, j: (i * gn + j, 0, 0))
    scratch = [pltpu.VMEM((block, block), jnp.float32)] * 2
    return row, scratch, kp


def block_topk_payload_kernel(x: jax.Array, k: int, block: int = 128,
                              interpret: bool = False):
    """Payload-emitting variant: x (M, N) with M, N multiples of
    ``block``; returns (values, indices) of shape (nblocks, k), tiles in
    row-major grid order, entries in flat in-tile order, empty slots at
    index -1. ``k`` must be <= block**2 (ops.py clamps)."""
    m, n = x.shape
    gm, gn = m // block, n // block
    row, scratch, kp = _payload_specs(block, k, gn)
    slots = (gm * gn, kp // _LANES, _LANES)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_payload_tile_kernel, k=k),
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((block, block), lambda i, j: (i, j))],
        out_specs=(row, row),
        out_shape=(
            jax.ShapeDtypeStruct(slots, x.dtype),
            jax.ShapeDtypeStruct(slots, jnp.int32),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x)
    return (vals.reshape(gm * gn, kp)[:, :k],
            idx.reshape(gm * gn, kp)[:, :k])


def diff_topk_payload_kernel(a: jax.Array, b: jax.Array, k: int,
                             block: int = 128, interpret: bool = False):
    """Fused diff->top-k->payload: a, b (M, N) with M, N multiples of
    ``block`` (ops.py pads); per tile computes D = a - b in VMEM,
    selects its top-k, and emits (values, indices) of shape
    (nblocks, k) plus the per-tile squared Frobenius partials
    (nblocks, 1) — summing them gives ||D||_F^2 for free (the l_i
    FedNL ships with each payload). The dense difference never
    round-trips through HBM."""
    m, n = a.shape
    gm, gn = m // block, n // block
    tile = pl.BlockSpec((block, block), lambda i, j: (i, j))
    row, scratch, kp = _payload_specs(block, k, gn)
    slots = (gm * gn, kp // _LANES, _LANES)
    acc = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    vals, idx, sq = pl.pallas_call(
        functools.partial(_diff_topk_payload_tile_kernel, k=k),
        grid=(gm, gn),
        in_specs=[tile, tile],
        out_specs=(
            row, row,
            pl.BlockSpec((None, 1, _LANES), lambda i, j: (i * gn + j, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(slots, a.dtype),
            jax.ShapeDtypeStruct(slots, jnp.int32),
            jax.ShapeDtypeStruct((gm * gn, 1, _LANES), acc),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
    )(a, b)
    return (vals.reshape(gm * gn, kp)[:, :k],
            idx.reshape(gm * gn, kp)[:, :k], sq[:, :, 0])
