"""Dispatching wrappers: pad to block multiples, dispatch to the Pallas
kernels (interpret=True on CPU so the kernel body itself is what runs).

``block_topk`` returns the dense masked matrix (seed-era format);
``block_topk_payload`` returns the wire format — per-tile (values,
indices) arrays matching ``repro.core.compressors.BlockSparsePayload``
— without ever materializing the dense compressed matrix.
``diff_topk_payload`` is the fused uplink: D = a - b is computed
tile-wise INSIDE the kernel, its top-k payload emitted directly along
with ||D||_F^2, so the dense difference never round-trips through HBM.

On TPU the payload ops run the Pallas kernels; elsewhere the sort-based
jnp oracle IS the fast path (interpret-mode Pallas would run the kernel
body at interpreter speed inside every optimizer step). The two paths
agree exactly on tie-free data; under bisection-resolution ties the
kernel keeps boundary ties in flat order while the oracle keeps the
sort order — both exactly k entries per tile. A tuned
``repro.kernels.tuning`` cache entry overrides the backend rule when
the caller passes ``use_pallas=None`` (explicit argument > cache >
backend default); resolution happens in the plain-Python wrapper so a
freshly warmed cache applies at the next trace."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..tuning import lookup
from .kernel import (
    block_topk_kernel,
    block_topk_payload_kernel,
    diff_topk_payload_kernel,
)
from .ref import block_topk_payload_ref, diff_topk_payload_ref


@partial(jax.jit, static_argnames=("k", "block", "interpret"))
def block_topk(x: jax.Array, k: int, block: int = 128,
               interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, n = x.shape
    pm, pn = (-m) % block, (-n) % block
    xp = jnp.pad(x, ((0, pm), (0, pn))) if (pm or pn) else x
    out = block_topk_kernel(xp, k=k, block=block, interpret=interpret)
    return out[:m, :n] if (pm or pn) else out


def _resolve_use_pallas(op: str, use_pallas, shape, k: int, block: int,
                        dtype) -> bool:
    if use_pallas is not None:
        return bool(use_pallas)
    cfg = lookup(op, shape=shape, k=k, n=block, dtype=dtype)
    if cfg is not None and cfg.use_pallas is not None:
        return bool(cfg.use_pallas)
    return jax.default_backend() == "tpu"


def block_topk_payload(x: jax.Array, k: int, block: int = 128,
                       use_pallas: bool | None = None,
                       interpret: bool | None = None):
    """Compressed payload of ``x``: (values, indices), both
    (ceil(m/block) * ceil(n/block), min(k, block**2)); tiles in row-major
    grid order, in-tile flat indices, empty slots at index -1. Pallas
    kernel on TPU, jnp oracle elsewhere (see module docstring; a tuned
    cache entry overrides); tests force the kernel body with
    ``use_pallas=True, interpret=True``."""
    k = min(int(k), block * block)
    use_pallas = _resolve_use_pallas("block_topk_payload", use_pallas,
                                     x.shape, k, block, x.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _block_topk_payload_impl(x, k=k, block=block,
                                    use_pallas=use_pallas,
                                    interpret=bool(interpret))


@partial(jax.jit, static_argnames=("k", "block", "use_pallas",
                                   "interpret"))
def _block_topk_payload_impl(x, k: int, block: int, use_pallas: bool,
                             interpret: bool):
    m, n = x.shape
    pm, pn = (-m) % block, (-n) % block
    xp = jnp.pad(x, ((0, pm), (0, pn))) if (pm or pn) else x
    if not use_pallas:
        return block_topk_payload_ref(xp, k=k, block=block)
    return block_topk_payload_kernel(xp, k=k, block=block,
                                     interpret=interpret)


def diff_topk_payload(a: jax.Array, b: jax.Array, k: int, block: int = 128,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None):
    """Fused uplink payload of D = a - b: returns (values, indices,
    sumsq) where values/indices are the Block-TopK payload of the
    difference (same layout as ``block_topk_payload``) and sumsq is the
    scalar ||D||_F^2 (per-tile partials summed — padding tiles are
    zero), so the l_i = ||D||_F every FedNL variant ships comes out of
    the same pass. On the Pallas path the dense (d, d) difference is
    never materialized — each tile's diff lives only in VMEM."""
    k = min(int(k), block * block)
    use_pallas = _resolve_use_pallas("diff_topk_payload", use_pallas,
                                     a.shape, k, block, a.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _diff_topk_payload_impl(a, b, k=k, block=block,
                                   use_pallas=use_pallas,
                                   interpret=bool(interpret))


@partial(jax.jit, static_argnames=("k", "block", "use_pallas",
                                   "interpret"))
def _diff_topk_payload_impl(a, b, k: int, block: int, use_pallas: bool,
                            interpret: bool):
    dt = jnp.result_type(a.dtype, b.dtype)
    a = a.astype(dt)
    b = b.astype(dt)
    m, n = a.shape
    pm, pn = (-m) % block, (-n) % block
    ap, bp = a, b
    if pm or pn:
        ap = jnp.pad(a, ((0, pm), (0, pn)))
        bp = jnp.pad(b, ((0, pm), (0, pn)))
    if use_pallas:
        vals, idx, sq = diff_topk_payload_kernel(ap, bp, k=k, block=block,
                                                 interpret=interpret)
        return vals, idx, jnp.sum(sq)
    # off the kernel the norm is the plain sum over the unpadded diff:
    # bitwise what the unfused uplink computes, so the two paths agree
    # exactly (FedNL's tie-broken selections amplify a one-ulp l^k
    # difference into a different trajectory)
    vals, idx, _ = diff_topk_payload_ref(ap, bp, k=k, block=block)
    d = a - b
    return vals, idx, jnp.sum(d * d)
