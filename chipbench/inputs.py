"""Input generators of the benchmark: the data of a federated cell and the
token batches of a training cell, both made on the device from a seed.

These are copies of the program's generators (``repro.data.synthetic.
make_libsvm_like`` and ``repro.data.tokens.TokenPipeline.batch``), kept
here so that a change to the program cannot change the yardstick.
``chipbench/tests/test_copies.py`` shows that each copy gives the same
arrays as the program's today.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed of up to 64 bits (a plain
    ``PRNGKey`` drops the high word without x64)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def libsvm_like(key, n: int, m: int, d: int, density: float = 0.15,
                scale: float = 1.0):
    """Federated logistic-regression data with a LIBSVM dataset's shape:
    (n, m, d) binary features of the given density and (n, m) labels in
    {-1, +1} drawn from a planted linear teacher. Returns (a, b)."""
    ks = jax.random.split(key, 4)
    mask = jax.random.bernoulli(ks[0], density, (n, m, d))
    a = mask.astype(jnp.float32) * scale
    w = jax.random.normal(ks[1], (d,)) / jnp.sqrt(d * density)
    logits = jnp.einsum("nmd,d->nm", a, w)
    neg = jax.random.bernoulli(ks[2], jax.nn.sigmoid(logits))
    return a, jnp.where(neg, -1.0, 1.0)


def token_batch(seed_key_, step, vocab: int, batch: int, seq: int,
                motif_len: int = 16, num_motifs: int = 256) -> dict:
    """One (batch, seq) batch of Zipfian unigrams with pasted motifs, and
    its next-token targets; distinct for every ``step``."""
    key = jax.random.fold_in(seed_key_, step)
    k1, k2, k3 = jax.random.split(key, 3)
    u = jax.random.uniform(k1, (batch, seq), minval=1e-6, maxval=1.0)
    ranks = jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1.0
    tokens = ranks.astype(jnp.int32) % vocab
    motifs = jax.random.randint(seed_key_, (num_motifs, motif_len), 0, vocab)
    which = jax.random.randint(k2, (batch,), 0, num_motifs)
    offs = jax.random.randint(k3, (batch,), 0, max(1, seq - motif_len))

    def paste(row, motif, off):
        return row.at[off + jnp.arange(motif_len)].set(motif)

    tokens = jax.vmap(paste)(tokens, motifs[which], offs)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=-1)}
