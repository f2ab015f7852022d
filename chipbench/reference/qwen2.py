"""Plain reference of the training cells: a Qwen2 decoder (arXiv:2407.10671)
trained by FedNL-learned diagonal curvature, in float32 ``jax.numpy`` at
the ``highest`` matmul precision. It imports nothing of the program.

Model, per the configuration file (``chipbench/configs/qwen2-0.5b.json``):
token embedding; per layer, RMSNorm (eps ``rms_norm_eps``), grouped-query
attention with biases on q, k and v, rotary position embedding
(half-split rotation, base ``rope_theta``) and a causal softmax; RMSNorm
and a SwiGLU MLP; a final RMSNorm and the tied embedding as the head;
mean next-token cross-entropy. Parameters are stored in bfloat16, as the
configuration states, and every computation reads them in float32.

Optimizer step (FedNL's Hessian learning on a diagonal, option 2, a
refresh every step), per parameter tensor viewed as a 2-D (rows, last
axis) matrix cut into 128 x 128 tiles, with n silos of equal size, silo
i's gradient g_i and the step's gradient g their mean:

    D_i = g_i*g_i - h;  h += mean_i TopK_k(D_i) per tile;
    l = mean_i sqrt(||D_i||^2 / size)
    mu = 0.9 mu + g / (sqrt(max(h, 0)) + sqrt(l) + 1e-8)
    p = bf16(p + bf16(-lr mu))

The layers are scanned with rematerialisation and the loss is taken over
blocks of tokens, so the whole reference fits on the chip next to
nothing else. ``quant`` rounds every matrix product's operands, which is
how the control (``control.py``) computes the same in float8."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

LOSS_BLOCK = 512  # tokens per block of the head and the loss


def _dot(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (b, t, heads, hd); rotate the two halves of each head."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, cfg, quant):
    b, t, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f = lambda a: a.astype(jnp.float32)
    att, mlp = p["mixer"], p["ffn"]
    h = _rmsnorm(x, f(p["norm1"]["w"]), eps)
    q = (_dot(h, f(att["wq"]), quant) + f(att["bq"])).reshape(b, t, heads, hd)
    k = (_dot(h, f(att["wk"]), quant) + f(att["bk"])).reshape(b, t, kv, hd)
    v = (_dot(h, f(att["wv"]), quant) + f(att["bv"])).reshape(b, t, kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv
    k = jnp.repeat(k, rep, axis=2)          # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)
    qq = q if quant is None else quant(q)
    kk = k if quant is None else quant(k)
    s = jnp.einsum("bthd,bshd->bhts", qq, kk,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(float(hd))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    if quant is not None:
        w, v = quant(w), quant(v)
    o = jnp.einsum("bhts,bshd->bthd", w, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(b, t, d)
    x = x + _dot(o, f(att["wo"]), quant)
    h2 = _rmsnorm(x, f(p["norm2"]["w"]), eps)
    gate = _dot(h2, f(mlp["wg"]), quant)
    up = _dot(h2, f(mlp["wi"]), quant)
    return x + _dot(jax.nn.silu(gate) * up, f(mlp["wo"]), quant)


def loss(params, batch, cfg, quant=None):
    """Mean next-token cross-entropy of a batch, in float32."""
    f = lambda a: a.astype(jnp.float32)
    emb = f(params["embed"])
    x = emb[batch["tokens"]]

    @jax.checkpoint
    def body(x, p):
        return _layer(x, p, cfg, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"][0])
    x = _rmsnorm(x, f(params["norm_f"]["w"]), cfg["rms_norm_eps"])
    d = x.shape[-1]
    rows = min(LOSS_BLOCK, x.shape[0] * x.shape[1])
    xs = x.reshape(-1, rows, d)
    ts = batch["targets"].reshape(-1, rows)

    @jax.checkpoint
    def block(carry, xt):
        xb, tb = xt
        logits = _dot(xb, emb.T, quant)
        lz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lz - gold), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xs, ts))
    return total / ts.size


def _as2d(x):
    if x.ndim == 1:
        return x.reshape(1, -1)
    return x.reshape(-1, x.shape[-1])


def block_topk(dm, k: int, block: int = 128):
    """Keep the k largest-magnitude entries of every (block x block) tile
    of the 2-D matrix dm (zero-padded to whole tiles); zero the rest."""
    r, c = dm.shape
    pr, pc = (-r) % block, (-c) % block
    x = jnp.pad(dm, ((0, pr), (0, pc)))
    gr, gc = x.shape[0] // block, x.shape[1] // block
    tiles = x.reshape(gr, block, gc, block).transpose(0, 2, 1, 3) \
        .reshape(gr * gc, block * block)
    kk = min(k, block * block)
    _, idx = jax.lax.top_k(jnp.abs(tiles), kk)
    kept = jnp.zeros_like(tiles).at[jnp.arange(tiles.shape[0])[:, None],
                                    idx].set(jnp.take_along_axis(tiles, idx, 1))
    out = kept.reshape(gr, gc, block, block).transpose(0, 2, 1, 3) \
        .reshape(gr * block, gc * block)
    return out[:r, :c]


def _learn_and_step(g_silos, h, mu, p, mix, exchange=True):
    """One tensor: ``g_silos`` holds each silo's gradient on a leading
    axis; the step's gradient is their mean (silos of equal size). With
    ``exchange`` off, the server hears the first silo alone (a fault)."""
    k, lr = int(mix["curvature_k"]), float(mix["lr"])
    g = jnp.mean(g_silos, axis=0)
    heard = g_silos if exchange else g_silos[:1]
    d = heard * heard - h
    s = jax.vmap(lambda di: block_topk(_as2d(di), k).reshape(h.shape))(d)
    h = h + jnp.mean(s, axis=0)
    sq = jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
    l = jnp.mean(jnp.sqrt(sq / h.size + 1e-30))
    mu = 0.9 * mu + g / (jnp.sqrt(jnp.maximum(h, 0.0)) + jnp.sqrt(l) + 1e-8)
    u = (-lr * mu).astype(p.dtype)
    return h, mu, (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype)


def init_state(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return jax.tree.map(z, params), jax.tree.map(z, params)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mix_items",
                                             "quant_name", "exchange"),
                   donate_argnames=("h", "mu"))
def _step(params, h, mu, silo_batches, cfg_items, mix_items, quant_name,
          exchange):
    """``silo_batches``: the batch cut into silos on a leading axis, each
    silo's rows in order; on several chips that axis lies across them."""
    cfg, mix = dict(cfg_items), dict(mix_items)
    quant = QUANT[quant_name]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    values, g_silos = jax.vmap(
        lambda b: jax.value_and_grad(loss)(p32, b, cfg, quant))(silo_batches)
    value = jnp.mean(values)
    out = jax.tree.map(lambda gg, hh, mm, pp: _learn_and_step(
        gg, hh, mm, pp, mix, exchange), g_silos, h, mu, params)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    gnorm = jax.tree.map(lambda gs: jnp.linalg.norm(jnp.mean(gs, 0).ravel()),
                         g_silos)
    return value, gnorm, pick(0), pick(1), pick(2)


def _fp8(x):
    """x rounded to float8 e4m3 in the forward pass; the backward pass
    takes the rounding as the identity (straight through), as float8
    training does."""
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


QUANT = {"none": None, "fp8": _fp8}


def _hashable(d: dict):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


def train(params, batches, cfg: dict, mix: dict, quant: str = "none",
          silos: int = 1, exchange: bool = True) -> dict:
    """Three (or len(batches)) steps from ``params``, the batch of each
    cut into ``silos`` silos of consecutive rows, silo i on chip i
    (``exchange=False`` plants the fault of a server that hears only the
    first silo's curvature).
    Returns each step's loss, the per-leaf norms of the first step's
    gradient, and the per-leaf norms of the parameters' change over all
    the steps (leaves in ``jax.tree.leaves`` order)."""
    mesh = Mesh(np.array(jax.devices()[:silos]), ("silo",))
    on_silos = NamedSharding(mesh, PartitionSpec("silo"))
    replicated = NamedSharding(mesh, PartitionSpec())
    h, mu = init_state(jax.device_put(params, replicated))
    p = jax.device_put(params, replicated)
    losses, gnorm0 = [], None
    for batch in batches:
        cut = jax.tree.map(lambda x: jax.device_put(
            x.reshape((silos, x.shape[0] // silos) + x.shape[1:]), on_silos),
            batch)
        value, gnorm, h, mu, p = _step(p, h, mu, cut, _hashable(cfg),
                                       _hashable(mix), quant, exchange)
        losses.append(float(value))
        if gnorm0 is None:
            gnorm0 = [float(x) for x in jax.tree.leaves(gnorm)]
    # the program's mesh may order the chips otherwise than this one does
    start = jax.device_put(params, replicated)
    change = [float(jnp.linalg.norm((a.astype(jnp.float32)
                                     - b.astype(jnp.float32)).ravel()))
              for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(start))]
    return {"losses": losses, "grad_norms": gnorm0, "change_norms": change}
