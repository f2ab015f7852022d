"""Mesh-sharded server accumulator: per-device row-tile ownership.

The streamed path (ops.py) bounds what ONE device stages per slab, but
the dense (d0, d1) accumulator itself still lives whole on every
device. Here the accumulator is sharded over a mesh axis instead: each
device owns a contiguous [row0, row0 + rows_per) row window and
scatters ONLY the pairs that land in its window (the payload stream is
replicated — payloads are tiny, the accumulator is what scales with d).
Aggregate capacity then grows with the device slice, not one chip's
HBM, and the output is born sharded ``P(axis, None)`` — ready to feed a
row-sharded Newton solve without a gather.

Out-of-window pairs are remapped to the -1 padding sentinel, so each
window scatter is the ordinary ``scatter_accumulate`` dispatch (ref or
Pallas kernel) at (rows_per, d1). Per accumulator cell, exactly one
device sees exactly the stacked stream's contributions in stream order,
so the gathered result equals the unsharded sum bitwise on the ref
path.

The symmetric (lower-triangular payload) sum cannot use the kernels'
fused per-window mirror — an entry's mirror may belong to a DIFFERENT
device's window — so the pair stream is mirror-expanded to (n, 2k)
before sharding: each off-diagonal entry appears once as (r, c) and
once as (c, r); diagonal and padding mirrors are sent to -1. This file
must not import ``repro.launch`` (launch imports models; kernels stay
leaf-level) — the placement helper ``accumulator_spec`` lives in
``launch/sharding.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ops import scatter_accumulate


def row_window_scatter(values: jax.Array, indices: jax.Array, shape,
                       row0, rows_per: int,
                       use_pallas: bool | None = None,
                       interpret: bool | None = None,
                       tile=None, chunk: int | None = None) -> jax.Array:
    """Dense (rows_per, d1) SUM of the pairs whose row lands in
    [row0, row0 + rows_per); everything else — including -1 padding,
    whose row decomposes negative — becomes the -1 sentinel and is
    dropped by the scatter. ``row0`` may be traced (it is
    ``axis_index * rows_per`` inside ``shard_map``)."""
    d0, d1 = (int(s) for s in shape)
    rows = indices // d1                                # -1 -> -1
    cols = indices - rows * d1
    local = rows - row0
    in_window = (indices >= 0) & (local >= 0) & (local < rows_per)
    local_idx = jnp.where(in_window, local * d1 + cols, -1)
    return scatter_accumulate(values, local_idx, (int(rows_per), d1),
                              use_pallas=use_pallas, interpret=interpret,
                              tile=tile, chunk=chunk)


def mirror_expand_pairs(values: jax.Array, indices: jax.Array, d1: int):
    """(n, k) lower-triangular pairs -> (n, 2k) symmetric pairs: each
    off-diagonal entry once at (r, c) and once at (c, r). Diagonal
    mirrors AND padding mirrors are forced to the -1 sentinel — a
    mirrored padding index can decompose to a non-negative flat index,
    and even a zero-valued diagonal mirror would add 0.0 to a cell the
    unsharded sum never touches twice."""
    rows = indices // d1
    cols = indices - rows * d1
    off_diag = (indices >= 0) & (rows != cols)
    mirror_idx = jnp.where(off_diag, cols * d1 + rows, -1)
    return (jnp.concatenate([values, values], axis=-1),
            jnp.concatenate([indices, mirror_idx], axis=-1))


def sharded_scatter_accumulate(values: jax.Array, indices: jax.Array,
                               shape, mesh: Mesh, axis: str = "data",
                               use_pallas: bool | None = None,
                               interpret: bool | None = None,
                               tile=None, chunk: int | None = None,
                               symmetric: bool = False) -> jax.Array:
    """Dense (d0, d1) SUM of n sparse silo payloads with the
    accumulator sharded ``P(axis, None)`` over ``mesh``: each device
    owns d0 / mesh.shape[axis] contiguous rows and scatters only its
    in-window pairs. Requires d0 divisible by the axis extent (pad d0
    at the caller otherwise). ``symmetric`` mirror-expands the pair
    stream BEFORE sharding (see ``mirror_expand_pairs``) — the fused
    in-kernel mirror cannot cross window boundaries."""
    d0, d1 = (int(s) for s in shape)
    ndev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if d0 % ndev != 0:
        raise ValueError(
            f"sharded accumulator needs d0 % mesh[{axis!r}] == 0, "
            f"got d0={d0}, extent={ndev}")
    rows_per = d0 // ndev
    if symmetric:
        values, indices = mirror_expand_pairs(values, indices, d1)

    def window(v, i):
        row0 = jax.lax.axis_index(axis) * rows_per
        return row_window_scatter(v, i, (d0, d1), row0, rows_per,
                                  use_pallas=use_pallas,
                                  interpret=interpret, tile=tile,
                                  chunk=chunk)

    # check_vma=False: the per-device body may lower to a pallas_call,
    # which the varying-axes checker has no rule for; the out_specs
    # already state the (axis, None) layout exactly.
    return _shard_map(window, mesh=mesh, in_specs=(P(), P()),
                      out_specs=P(axis, None),
                      check_vma=False)(values, indices)
