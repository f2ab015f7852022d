"""Causal flash attention (forward) — online-softmax tiling.

Grid (batch*heads, T/bq); each program streams the key/value blocks
j <= i for its query block, keeping running (max, sum, acc) statistics in
VMEM scratch. This is the TPU-native replacement for materializing the
(T, T) score matrix; the serving path uses it for long-context prefill.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale            # (bq, hd)
    hd = q.shape[-1]

    m0 = jnp.full((bq,), -1e30, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)

    q_pos = qi * bq + jnp.arange(bq)
    n_kblocks = (qi * bq) // bk + 1                        # causal: j <= i

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                        # (bq, bk)
        k_pos = j * bk + jnp.arange(bk)
        mask = k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask, s, -1e30)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kblocks, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, T, hd); causal. T must be a multiple of bq and bk."""
    bh, t, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    grid = (bh, t // bq)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, t, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, t, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
