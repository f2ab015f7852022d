"""Pallas scatter-accumulate kernels — the server side of the FedNL
uplink in payload space.

The server's job per round is S = sum_i S_i where each S_i arrives as a
sparse payload (values + indices). Instead of decompressing every silo
to a dense (d, d) and meaning the (n, d, d) stack, these kernels keep
ONE dense accumulator and scatter every silo's pairs into it.

TPU VPUs have no native scatter, so the scatter is recast as MXU work:
for a chunk of entries, build two one-hot matrices from the decomposed
(row, col) indices — R[r, e] = [row_e == r] with the value folded in,
C[c, e] = [col_e == c] — and the chunk's dense contribution is the
matmul R @ C^T (each output cell sums exactly the entries addressing
it, so accumulation of duplicate indices is automatic and exact in the
accumulate dtype; the matmul runs at HIGHEST precision so f32 values
pass the MXU unrounded). Payload padding (index -1) yields row_e = -1,
which matches no row one-hot and contributes zero.

The pair stream reaches every kernel as (rows, ck) arrays cut into
8-row blocks (one f32 sublane tile — the TPU lowering needs the last
two block dims divisible by (8, 128) or equal to the array's); each
program walks its 8 chunk rows in order, so the per-cell add sequence
is the same as one chunk per program.

``scatter_accum_kernel``: global flat indices, grid over pair-stream
row blocks, all programs revisiting the same full-matrix output block
(init at program 0, accumulate after) — the standard Pallas
revisiting-output reduction. ops.py dispatches to it only while the
whole accumulator fits the VMEM budget.

``scatter_accum_tiled_kernel``: the same chunked pair stream, but the
output is a 2-D grid of (tm, tn) tiles with the chunk axis innermost —
each (row-tile, col-tile) program streams every (silo, chunk) pair and
contributes only its in-window entries (the index range test is free:
tile-local coordinates outside [0, tile) match no one-hot column). Only
ONE output tile is ever resident in VMEM, so arbitrary d scales; each
pair is re-examined once per tile, which is the classic compute-for-
memory trade of a tiled scatter (the one-hot matmuls are MXU work
either way).

``block_scatter_accum_kernel``: in-tile indices, one program per output
tile, the tile's n silo payload rows contracted silo by silo.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROWS = 8  # pair-stream chunk rows per grid program (one sublane tile)


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _pad_rows(values, indices):
    """Pad the (nchunks, ck) pair stream to a whole number of 8-row
    blocks with inert (0, -1) rows."""
    pad = (-values.shape[0]) % _ROWS
    if not pad:
        return values, indices
    return (jnp.pad(values, ((0, pad), (0, 0))),
            jnp.pad(indices, ((0, pad), (0, 0)), constant_values=-1))


def _onehot_contribution(vals, rows, cols, d0: int, d1: int, acc):
    """Dense (d0, d1) sum of entries vals[e] at (rows[e], cols[e]) via
    two one-hot matmuls; negative rows match nothing (padding). All
    three are (1, ck) rows: the one-hots broadcast them down sublanes,
    so no lane-to-sublane relayout is needed."""
    ck = vals.shape[-1]
    rio = jax.lax.broadcasted_iota(jnp.int32, (d0, ck), 0)
    cio = jax.lax.broadcasted_iota(jnp.int32, (d1, ck), 0)
    r_onehot = (rows == rio).astype(acc) * vals.astype(acc)   # (d0, ck)
    c_onehot = (cols == cio).astype(acc)                      # (d1, ck)
    return jax.lax.dot_general(
        r_onehot, c_onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=acc)                     # (d0, d1)


def _mirror_vals(vals, rows, cols):
    """Values for the mirrored (col, row) contribution of a symmetric
    scatter: diagonal entries (row == col) are zeroed so they land
    exactly once — together with the direct contribution this fuses the
    ``c + c.T - diag(diag(c))`` second pass into the kernel. Padding
    (row = -1, col >= 0) never equals its col and keeps its value, but
    its mirrored *column* index is negative and matches no one-hot."""
    return jnp.where(rows == cols, jnp.zeros_like(vals), vals)


def _chunk_contribution(vals, idx, *, d1: int, row0, col0, tm: int,
                        tn: int, symmetric: bool):
    """Dense (tm, tn) window contribution of one (1, ck) pair chunk row.

    ``row0``/``col0`` shift into window-local coordinates (0 for the
    single-block kernel, the tile origin for the tiled one): entries
    outside the window — including -1 padding, whose row is negative —
    match no one-hot column and contribute zero. ``symmetric`` adds each
    off-diagonal entry's mirror through the identical window test."""
    rows = idx // d1                                    # -1 -> -1 (no match)
    cols = idx - rows * d1
    acc = _acc_dtype(vals.dtype)
    contrib = _onehot_contribution(vals, rows - row0, cols - col0,
                                   tm, tn, acc)
    if symmetric:
        contrib += _onehot_contribution(_mirror_vals(vals, rows, cols),
                                        cols - row0, rows - col0,
                                        tm, tn, acc)
    return contrib


def _accumulate_rows(vals_ref, idx_ref, out_ref, *, d1: int, row0, col0,
                     symmetric: bool):
    """Add each chunk row of this program's (8, ck) pair block to the
    resident output block, in row order."""
    tm, tn = out_ref.shape

    def body(j, carry):
        contrib = _chunk_contribution(vals_ref[pl.ds(j, 1), :],
                                      idx_ref[pl.ds(j, 1), :], d1=d1,
                                      row0=row0, col0=col0, tm=tm, tn=tn,
                                      symmetric=symmetric)
        out_ref[...] += contrib.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, vals_ref.shape[0], body, 0)


def _scatter_accum_tile_kernel(vals_ref, idx_ref, out_ref, *, d1: int,
                               symmetric: bool = False):
    """One 8-row block of (value, index) chunks; all programs revisit
    the same full-matrix out block. ``d1`` is the UNPADDED column count
    the flat indices were built against."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate_rows(vals_ref, idx_ref, out_ref, d1=d1, row0=0, col0=0,
                     symmetric=symmetric)


def _scatter_accum_tile_init_kernel(vals_ref, idx_ref, init_ref, out_ref,
                                    *, d1: int, symmetric: bool = False):
    """Streaming variant of ``_scatter_accum_tile_kernel``: program 0
    seeds the output block from a caller-provided accumulator instead of
    zeros, so a slab of silos continues the running server sum in the
    exact same add order as one stacked pass over all silos."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = init_ref[...]

    _accumulate_rows(vals_ref, idx_ref, out_ref, d1=d1, row0=0, col0=0,
                     symmetric=symmetric)


def scatter_accum_kernel(values: jax.Array, indices: jax.Array,
                         out_shape, d1: int,
                         interpret: bool = False,
                         symmetric: bool = False,
                         init: jax.Array | None = None) -> jax.Array:
    """values/indices: (nchunks, ck) — silo payloads flattened into
    fixed-size chunks (ops.py pads with value 0 / index -1; the chunk
    rows are padded here to whole 8-row blocks). Returns the
    (d0p, d1p) = ``out_shape`` dense SUM; ``d1`` is the unpadded column
    count of the matrix the flat indices address. ``symmetric`` adds
    each off-diagonal entry's mirror in the same pass (lower-triangular
    payloads: the fused symmetric-TopK server sum). ``init`` seeds the
    accumulator with a prior (d0p, d1p) partial sum (the streamed path's
    running total) instead of zeros."""
    values, indices = _pad_rows(values, indices)
    nrows, ck = values.shape
    pairs = pl.BlockSpec((_ROWS, ck), lambda i: (i, 0))
    if init is None:
        return pl.pallas_call(
            functools.partial(_scatter_accum_tile_kernel, d1=d1,
                              symmetric=symmetric),
            grid=(nrows // _ROWS,),
            in_specs=[pairs, pairs],
            out_specs=pl.BlockSpec(out_shape, lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(out_shape, values.dtype),
            interpret=interpret,
        )(values, indices)
    return pl.pallas_call(
        functools.partial(_scatter_accum_tile_init_kernel, d1=d1,
                          symmetric=symmetric),
        grid=(nrows // _ROWS,),
        in_specs=[pairs, pairs, pl.BlockSpec(out_shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(out_shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, values.dtype),
        interpret=interpret,
    )(values, indices, init)


def _scatter_accum_tiled_tile_kernel(vals_ref, idx_ref, out_ref, *, d1: int,
                                     symmetric: bool = False):
    """One (row-tile, col-tile, chunk-block) program: contribute this
    block's in-window entries to the (tm, tn) output tile. The chunk
    axis is the innermost grid dim, so each output tile is revisited
    consecutively over the whole (silo, chunk) pair stream while staying
    resident in VMEM — the accumulator never exists as one full
    (d0, d1) block."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    tm, tn = out_ref.shape
    _accumulate_rows(vals_ref, idx_ref, out_ref, d1=d1,
                     row0=pl.program_id(0) * tm,
                     col0=pl.program_id(1) * tn, symmetric=symmetric)


def _scatter_accum_tiled_tile_init_kernel(vals_ref, idx_ref, init_ref,
                                          out_ref, *, d1: int,
                                          symmetric: bool = False):
    """Streaming variant of ``_scatter_accum_tiled_tile_kernel``: each
    output tile's first chunk program copies the matching tile of a
    caller-provided accumulator instead of zeroing, so slabs of silos
    chain with the identical per-tile add order as one stacked pass."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        out_ref[...] = init_ref[...]

    tm, tn = out_ref.shape
    _accumulate_rows(vals_ref, idx_ref, out_ref, d1=d1,
                     row0=pl.program_id(0) * tm,
                     col0=pl.program_id(1) * tn, symmetric=symmetric)


def scatter_accum_tiled_kernel(values: jax.Array, indices: jax.Array,
                               out_shape, d1: int, tile,
                               interpret: bool = False,
                               symmetric: bool = False,
                               init: jax.Array | None = None) -> jax.Array:
    """Tiled variant of ``scatter_accum_kernel``: same (nchunks, ck)
    chunked pair stream, but the output is produced as a 2-D grid of
    (tm, tn) = ``tile`` blocks so VMEM holds one tile, not the matrix.
    ``out_shape`` must be a multiple of ``tile`` in both dims (ops.py
    pads); ``d1`` is the unpadded column count the flat indices address.
    ``symmetric`` mirrors off-diagonal entries in the same pass — the
    mirrored coordinates go through the identical tile-window test, so
    each mirror lands in exactly the tile that owns it. ``init`` seeds
    each output tile from the matching tile of a prior (d0p, d1p)
    partial sum (the streamed path's running total) instead of zeros.
    """
    values, indices = _pad_rows(values, indices)
    nrows, ck = values.shape
    d0p, d1p = (int(s) for s in out_shape)
    tm, tn = (int(t) for t in tile)
    assert d0p % tm == 0 and d1p % tn == 0, (out_shape, tile)
    grid = (d0p // tm, d1p // tn, nrows // _ROWS)
    pairs = pl.BlockSpec((_ROWS, ck), lambda i, j, c: (c, 0))
    if init is None:
        return pl.pallas_call(
            functools.partial(_scatter_accum_tiled_tile_kernel, d1=d1,
                              symmetric=symmetric),
            grid=grid,
            in_specs=[pairs, pairs],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, c: (i, j)),
            out_shape=jax.ShapeDtypeStruct((d0p, d1p), values.dtype),
            interpret=interpret,
        )(values, indices)
    return pl.pallas_call(
        functools.partial(_scatter_accum_tiled_tile_init_kernel, d1=d1,
                          symmetric=symmetric),
        grid=grid,
        in_specs=[pairs, pairs,
                  pl.BlockSpec((tm, tn), lambda i, j, c: (i, j))],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d0p, d1p), values.dtype),
        interpret=interpret,
    )(values, indices, init)


def _block_scatter_tile_kernel(vals_ref, idx_ref, out_ref, *, block: int):
    """One output tile: scatter the n silos' k pairs for this tile, one
    one-hot matmul pair per silo payload row, in silo order."""
    acc = _acc_dtype(vals_ref.dtype)
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, carry):
        idx = idx_ref[pl.ds(i, 1), :]                   # (1, k) int32
        rows = idx // block                             # -1 -> -1 (no match)
        cols = idx - rows * block
        contrib = _onehot_contribution(vals_ref[pl.ds(i, 1), :], rows,
                                       cols, block, block, acc)
        out_ref[...] += contrib.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, vals_ref.shape[0], body, 0)


def block_scatter_accum_kernel(values: jax.Array, indices: jax.Array,
                               grid, block: int,
                               interpret: bool = False) -> jax.Array:
    """values/indices: (n, nblocks, k) in the BlockSparsePayload layout
    (row-major tiles, in-tile flat indices, -1 padding); nblocks must
    equal gm*gn. Returns the (gm*block, gn*block) dense SUM."""
    gm, gn = (int(g) for g in grid)
    n, nblk, k = values.shape
    assert nblk == gm * gn, (nblk, grid)
    # tile-major, so one program's block is the whole (n, k) payload
    # slab of its tile (full trailing dims: a legal TPU block)
    values = jnp.swapaxes(values, 0, 1)
    indices = jnp.swapaxes(indices, 0, 1)
    tile_pairs = pl.BlockSpec((None, n, k), lambda i, j: (i * gn + j, 0, 0))
    return pl.pallas_call(
        functools.partial(_block_scatter_tile_kernel, block=block),
        grid=(gm, gn),
        in_specs=[tile_pairs, tile_pairs],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * block, gn * block),
                                       values.dtype),
        interpret=interpret,
    )(values, indices)
