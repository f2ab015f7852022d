"""Dispatching wrappers for payload-space scatter-accumulation.

One op per payload family, both returning the dense SUM over silos from
ONE accumulator (the caller divides by n for the server mean):

  scatter_accumulate        — SparsePayload: global flat indices
  block_scatter_accumulate  — BlockSparsePayload: per-tile indices

On TPU the Pallas kernels run; elsewhere the pure-jnp oracle (a single
XLA scatter-add) IS the fast path — interpret-mode Pallas would emulate
the kernel body at Python speed on the hot loop of every step. Tests
force the kernel body with ``use_pallas=True, interpret=True``.

Config resolution (``tile``, ``chunk``) is explicit argument > tuned
winner (``repro.kernels.tuning`` cache, keyed on (d-bucket, k, n,
dtype, device kind)) > untuned default (``_TILE``/``_CHUNK`` with the
VMEM-budget single-block-vs-tiled dispatch). Resolution happens in the
plain-Python wrapper BEFORE the jitted impl, so a cache warmed between
calls takes effect on the next trace instead of being baked forever at
the first one."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import VMEM_BUDGET_BYTES
from ..tuning import lookup
from .kernel import (
    block_scatter_accum_kernel,
    scatter_accum_kernel,
    scatter_accum_tiled_kernel,
)
from .ref import block_scatter_accumulate_ref, scatter_accumulate_ref

_CHUNK = 512  # default (value, index) pairs per kernel program

# Single-block vs tiled dispatch: the single-block kernel holds the
# whole padded accumulator in ONE VMEM block; beyond the limit below the
# tiled kernel streams the pair stream per (tm, tn) output tile, so
# arbitrary d scales. The program holds the accumulator about four
# times over — two pipeline buffers, the chunk contribution, and its
# mirror when symmetric — next to the chunk one-hots, so the block may
# take a quarter of the shared budget. The v5e compiler agrees: f32
# single-block programs compile at a 2.1 MiB accumulator (d=720,
# symmetric and init-seeded included) and a symmetric one runs out of
# VMEM at 3.9 MiB (d=1000).
_VMEM_ACC_BUDGET_BYTES = VMEM_BUDGET_BYTES // 4
_TILE = (512, 512)  # default tiled-path output block (1 MiB f32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def scatter_accumulate(values: jax.Array, indices: jax.Array, shape,
                       use_pallas: bool | None = None,
                       interpret: bool | None = None,
                       tile=None, chunk: int | None = None,
                       symmetric: bool = False) -> jax.Array:
    """Dense (d0, d1) SUM of n sparse silo payloads.

    values/indices: (n, k) per-silo (value, row-major flat index) pairs
    into ``shape``; -1 indices (payload padding) are dropped; duplicate
    indices accumulate. On the Pallas path the accumulator lives in ONE
    VMEM block while the padded matrix fits ``_VMEM_ACC_BUDGET_BYTES``
    and is otherwise tiled into (tm, tn) output blocks (the chunk pair
    stream replayed per tile) — any d stays in VMEM. ``tile`` forces
    the tiled kernel with that (tm, tn) block (tm a multiple of 8, tn
    of 128) and ``chunk`` the pair-stream chunk length; leaving BOTH
    None consults the autotuner cache first, then budget-dispatches
    with the defaults. ``symmetric`` treats each payload as the lower
    triangle of a symmetric matrix and lands every off-diagonal entry
    at (r, c) AND (c, r) in the same kernel pass — the fused
    ``c + c.T - diag(diag(c))`` used by symmetric TopK aggregation."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return scatter_accumulate_ref(values, indices, shape,
                                      symmetric=symmetric)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, k = values.shape
    if tile is None and chunk is None:  # untuned call: cache decides
        cfg = lookup("scatter_accumulate", shape=shape, k=k, n=n,
                     dtype=values.dtype)
        if cfg is not None:
            tile, chunk = cfg.tile, cfg.chunk
    if chunk is None:
        chunk = _CHUNK
    shape = tuple(int(s) for s in shape)
    tile = (int(tile[0]), int(tile[1])) if tile is not None else None
    return _scatter_accumulate_pallas(values, indices, shape,
                                      interpret=bool(interpret), tile=tile,
                                      chunk=int(chunk),
                                      symmetric=bool(symmetric))


@partial(jax.jit, static_argnames=("shape", "interpret", "tile", "chunk",
                                   "symmetric"))
def _scatter_accumulate_pallas(values, indices, shape, interpret: bool,
                               tile, chunk: int,
                               symmetric: bool) -> jax.Array:
    d0, d1 = shape
    n, k = values.shape
    kp = _round_up(max(k, 1), chunk) if k > chunk else max(k, 1)
    ck = min(kp, chunk)
    vals = jnp.pad(values, ((0, 0), (0, kp - k)))
    idx = jnp.pad(indices, ((0, 0), (0, kp - k)), constant_values=-1)
    # fixed-size chunks -> one grid program each, revisiting the output
    nchunks = n * (kp // ck)
    vals = vals.reshape(nchunks, ck)
    idx = idx.reshape(nchunks, ck)
    acc_bytes = (_round_up(d0, 8) * _round_up(d1, 128)
                 * jnp.dtype(values.dtype).itemsize)
    if tile is None and acc_bytes > _VMEM_ACC_BUDGET_BYTES:
        # over budget the single-block kernel is illegal no matter what
        # a cache entry says — the budget guard outranks the tuner
        tile = _TILE
    if tile is None:
        d0p, d1p = _round_up(d0, 8), _round_up(d1, 128)
        out = scatter_accum_kernel(vals, idx, (d0p, d1p), d1,
                                   interpret=interpret,
                                   symmetric=symmetric)
    else:
        tm = _round_up(int(tile[0]), 8)
        tn = _round_up(int(tile[1]), 128)
        d0p, d1p = _round_up(d0, tm), _round_up(d1, tn)
        out = scatter_accum_tiled_kernel(vals, idx, (d0p, d1p), d1,
                                         (tm, tn), interpret=interpret,
                                         symmetric=symmetric)
    return out[:d0, :d1]


@partial(jax.jit, static_argnames=("shape", "interpret", "tile", "chunk",
                                   "symmetric"))
def streamed_slab_update(acc, values, indices, shape,
                         interpret: bool = False, tile=None,
                         chunk: int = _CHUNK,
                         symmetric: bool = False) -> jax.Array:
    """One streamed silo-slab update of the running server sum.

    ``acc`` is the PADDED (d0p, d1p) accumulator (zeros before the first
    slab); ``values``/``indices`` are one (m, k) slab of the stacked
    silo payloads. Chunks the slab exactly as the stacked Pallas path
    chunks the full stack and seeds the kernel's output block from
    ``acc`` — so chaining slabs replays the identical per-cell add
    sequence as ONE stacked pass, and the result is bitwise equal.
    Traceable: the analysis sweep checks vmem-budget on this jaxpr (the
    slab, not n, bounds what the kernel stages into VMEM)."""
    d0, d1 = (int(s) for s in shape)
    m, k = values.shape
    chunk = int(chunk)
    kp = _round_up(max(k, 1), chunk) if k > chunk else max(k, 1)
    ck = min(kp, chunk)
    vals = jnp.pad(values, ((0, 0), (0, kp - k)))
    idx = jnp.pad(indices, ((0, 0), (0, kp - k)), constant_values=-1)
    nchunks = m * (kp // ck)
    vals = vals.reshape(nchunks, ck)
    idx = idx.reshape(nchunks, ck)
    if tile is None:
        return scatter_accum_kernel(vals, idx, acc.shape, d1,
                                    interpret=interpret,
                                    symmetric=symmetric, init=acc)
    return scatter_accum_tiled_kernel(vals, idx, acc.shape, d1, tile,
                                      interpret=interpret,
                                      symmetric=symmetric, init=acc)


@partial(jax.jit, static_argnames=("shape",))
def _streamed_ref_slab(acc, values, indices, shape) -> jax.Array:
    """One silo-slab scatter into the running (d0, d1) accumulator on
    the portable path. The symmetric mirror is NOT applied here — the
    caller mirrors ONCE after the last slab (mirroring per slab would
    change the add association and break bitwise equality)."""
    return scatter_accumulate_ref(values, indices, shape,
                                  symmetric=False, init=acc)


def silo_chunk_for(k: int, value_dtype, index_dtype=jnp.int32) -> int:
    """Largest silo-slab size whose (value, index) pair stream fits the
    shared kernel VMEM budget — the streaming rule: stream once
    n * k * pair_bytes outgrows ``VMEM_BUDGET_BYTES``."""
    pair = (jnp.dtype(value_dtype).itemsize
            + jnp.dtype(index_dtype).itemsize)
    return max(1, int(VMEM_BUDGET_BYTES // max(1, int(k) * pair)))


def streamed_scatter_accumulate(values, indices, shape,
                                silo_chunk: int | None = None,
                                use_pallas: bool | None = None,
                                interpret: bool | None = None,
                                tile=None, chunk: int | None = None,
                                symmetric: bool = False) -> jax.Array:
    """Dense (d0, d1) SUM of n sparse silo payloads, streamed over silo
    slabs from host memory — bitwise equal to ``scatter_accumulate`` on
    the same stack, at bounded device footprint.

    The stacked path stages the whole (n, k) pair stream; once
    n * k * pair_bytes outgrows the VMEM budget the server must not.
    This wrapper cuts the stack into ``silo_chunk``-silo slabs (default:
    the largest slab whose pair stream fits ``VMEM_BUDGET_BYTES``),
    stages each slab with ``jax.device_put`` — the NEXT slab's transfer
    is issued before blocking on the current slab's kernel, so the copy
    double-buffers behind the compute — and chains the slab kernels
    through their ``init`` accumulator. Kernel config (tile, chunk) is
    resolved ONCE against the FULL stacked problem so every slab runs
    the identical kernel the stacked path would pick; device memory
    holds one padded accumulator plus at most two slabs, independent of
    n. ``values``/``indices`` may be numpy (host) or jax arrays."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, k = values.shape
    shape = tuple(int(s) for s in shape)
    d0, d1 = shape
    if silo_chunk is None:
        silo_chunk = silo_chunk_for(k, values.dtype, indices.dtype)
    silo_chunk = max(1, int(silo_chunk))
    if tile is None and chunk is None:  # untuned: full-n cache key
        cfg = lookup("scatter_accumulate", shape=shape, k=k, n=n,
                     dtype=values.dtype)
        if cfg is not None:
            tile, chunk = cfg.tile, cfg.chunk
    if chunk is None:
        chunk = _CHUNK
    chunk = int(chunk)

    starts = list(range(0, n, silo_chunk))

    def fetch(s: int):
        e = min(s + silo_chunk, n)
        return (jax.device_put(values[s:e]), jax.device_put(indices[s:e]))

    if not use_pallas:
        acc = jnp.zeros(shape, values.dtype)
        nxt = fetch(starts[0])
        for pos, _ in enumerate(starts):
            cur_v, cur_i = nxt
            if pos + 1 < len(starts):
                nxt = fetch(starts[pos + 1])
            acc = _streamed_ref_slab(acc, cur_v, cur_i, shape)
        if symmetric:
            acc = acc + acc.T - jnp.diag(jnp.diag(acc))
        return acc

    acc_bytes = (_round_up(d0, 8) * _round_up(d1, 128)
                 * jnp.dtype(values.dtype).itemsize)
    if tile is None and acc_bytes > _VMEM_ACC_BUDGET_BYTES:
        tile = _TILE  # budget guard outranks the tuner, as in the stacked path
    if tile is None:
        d0p, d1p = _round_up(d0, 8), _round_up(d1, 128)
    else:
        tile = (_round_up(int(tile[0]), 8), _round_up(int(tile[1]), 128))
        d0p, d1p = _round_up(d0, tile[0]), _round_up(d1, tile[1])
    acc = jnp.zeros((d0p, d1p), values.dtype)
    nxt = fetch(starts[0])
    for pos, _ in enumerate(starts):
        cur_v, cur_i = nxt
        if pos + 1 < len(starts):
            nxt = fetch(starts[pos + 1])
        acc = streamed_slab_update(acc, cur_v, cur_i, shape,
                                   interpret=bool(interpret), tile=tile,
                                   chunk=chunk,
                                   symmetric=bool(symmetric))
    return acc[:d0, :d1]


@partial(jax.jit, static_argnames=("grid", "block", "use_pallas",
                                   "interpret"))
def block_scatter_accumulate(values: jax.Array, indices: jax.Array, grid,
                             block: int,
                             use_pallas: bool | None = None,
                             interpret: bool | None = None) -> jax.Array:
    """Dense (gm*block, gn*block) SUM of n block-sparse silo payloads
    ((n, nblocks, k) values/indices, BlockSparsePayload layout)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return block_scatter_accumulate_ref(values, indices, grid, block)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return block_scatter_accum_kernel(values, indices, grid, block,
                                      interpret=interpret)
