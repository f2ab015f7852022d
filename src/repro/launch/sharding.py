"""Sharding rules: parameter-path -> PartitionSpec, activation hints,
cache specs. All rules degrade gracefully: an axis is only used when the
dimension is divisible by its mesh extent (GQA head counts like 14 or 24
don't divide 16; those dims fall back to replication on that axis).

Layout (see DESIGN.md §5):
  * batch over ("pod", "data")
  * attention heads / ffn hidden / vocab over "model"
  * FSDP-style second factor: the non-"model" weight dim over ("pod","data")
  * MoE experts over "model" when divisible (expert parallel), otherwise
    the expert ffn dim goes to "model" (tensor parallel within expert)
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

from .mesh import batch_axes


def _ax(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= _ax(mesh, n)
        return out
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _fits(dim: int, mesh: Mesh, name) -> bool:
    return dim % _ax(mesh, name) == 0


def _spec(mesh: Mesh, shape, wants) -> P:
    """wants: per-dim axis name (or tuple or None); drop non-divisible."""
    out = []
    for dim, w in zip(shape, wants):
        if w is None:
            out.append(None)
        elif _fits(dim, mesh, w):
            out.append(w)
        else:
            # try a prefix of a tuple request, e.g. ("pod","data") -> "data"
            if isinstance(w, tuple):
                picked = None
                for sub in w:
                    if _fits(dim, mesh, sub):
                        picked = sub
                        break
                out.append(picked)
            else:
                out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------


def param_spec(path: str, shape, mesh: Mesh, cfg: ModelConfig) -> P:
    """``path`` is a '/'-joined key path; ``shape`` excludes nothing (the
    stacked segment axis, if present, is dim 0 and is detected by name)."""
    ba = batch_axes(mesh)
    name = path.split("/")[-1]
    stacked = "layers" in path or "enc_layers" in path
    body = shape[1:] if stacked else shape
    lead = (None,) if stacked else ()

    def done(wants):
        return _spec(mesh, shape, lead + tuple(wants))

    # --- embeddings & head ---------------------------------------------------
    if name in ("embed", "lm_head"):
        return _spec(mesh, shape, ("model", ba))

    # --- norms / scalars / biases ---------------------------------------------
    if len(body) <= 1:
        if name in ("bq", "bk", "bv") and len(body) == 1:
            return done(["model"])
        return done([None] * len(body))

    # --- MoE (E, din, dout) ----------------------------------------------------
    if len(body) == 3 and name in ("wi", "wg", "wo"):
        e = body[0]
        if _fits(e, mesh, "model"):
            return done(["model", ba, None])
        # E doesn't divide the model axis: tensor parallelism inside each
        # expert, FSDP on the other dim. NB (§Perf iteration 3, REFUTED):
        # moving the FSDP factor onto the contraction dims of both expert
        # einsums ("wo": (None, ba, "model")) to avoid the output-axis
        # conflict DOUBLED collective traffic (63.6 s -> 133.8 s on
        # grok-1 train_4k) — GSPMD's resharding of the conflicted output
        # is cheaper than explicit gathers of TP'd expert weights here.
        if name == "wo":
            return done([None, "model", ba])
        return done([None, ba, "model"])
    if name == "router":
        return done([None, None])

    # --- projections (din, dout) -------------------------------------------------
    if len(body) == 2:
        reduce_in = name in ("wo", "wout", "wuk", "wuv")
        # MLA down-projections keep latent replicated
        if name in ("wdq", "wdkv", "wkrope"):
            return done([ba, None])
        if name in ("wuq",):
            return done([None, "model"])
        if reduce_in:
            return done(["model", ba])
        return done([ba, "model"])

    # conv kernels etc.
    return done([None] * len(body))


def tree_param_specs(params_shape: Any, mesh: Mesh, cfg: ModelConfig):
    """Map a pytree of ShapeDtypeStructs/arrays to NamedShardings."""

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + "/" + k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, prefix + f"/{i}") for i, v in enumerate(tree)]
            return type(tree)(t) if not isinstance(tree, tuple) else tuple(t)
        spec = param_spec(prefix, tree.shape, mesh, cfg)
        return NamedSharding(mesh, spec)

    return walk(params_shape, "params")


def make_layer_param_constrainer(mesh: Mesh, cfg: ModelConfig):
    """Constraint for the per-layer param slice INSIDE a scan body (same
    name-based rules, no stacked leading axis). Keeps the FSDP all-gather
    per-layer instead of letting XLA hoist a whole-stack gather."""

    def constrain(tree):
        def walk(t, prefix):
            if isinstance(t, dict):
                return {k: walk(v, prefix + "/" + k) for k, v in t.items()}
            if isinstance(t, (list, tuple)):
                out = [walk(v, prefix + f"/{i}") for i, v in enumerate(t)]
                return tuple(out) if isinstance(t, tuple) else out
            spec = param_spec(prefix, t.shape, mesh, cfg)
            return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

        return walk(tree, "inloop")

    return constrain


def opt_state_shardings(state_shape: Any, params: Any, mesh: Mesh,
                        cfg: ModelConfig):
    """NamedShardings for an optimizer-state pytree (``jax.eval_shape``
    of ``opt.init``): every state field that mirrors the params tree —
    Adam moments, fednl's diagonal curvature H and its momentum — gets
    the params' own ``param_spec`` shardings, so second-order state
    scales with the param shards and never concentrates on one chip's
    HBM. Fields with any other structure (step counters, the per-tensor
    scalar ridge ``l``, empty ``()`` slots) are replicated."""
    pspecs = tree_param_specs(params, mesh, cfg)
    pdef = jax.tree.structure(params)
    pshapes = [p.shape for p in jax.tree.leaves(params)]
    rep = NamedSharding(mesh, P())

    def field(sub):
        mirrors = (jax.tree.structure(sub) == pdef and
                   [getattr(x, "shape", None)
                    for x in jax.tree.leaves(sub)] == pshapes)
        if mirrors:
            return pspecs
        return jax.tree.map(lambda _: rep, sub)

    if hasattr(state_shape, "_fields"):  # NamedTuple states
        return type(state_shape)(*[field(f) for f in state_shape])
    return field(state_shape)


# ---------------------------------------------------------------------------
# Activation hints (installed via models.common.set_activation_sharder)
# ---------------------------------------------------------------------------


def make_activation_sharder(mesh: Mesh):
    ba = batch_axes(mesh)

    def shard(x, kind: str):
        if kind == "btd":
            spec = _spec(mesh, x.shape, (ba,) + (None,) * (x.ndim - 1))
        elif kind == "btf":
            spec = _spec(mesh, x.shape, (ba,) + (None,) * (x.ndim - 2) + ("model",))
        elif kind == "bthd":
            spec = _spec(mesh, x.shape, (ba, None, "model", None))
        elif kind == "logits":
            spec = _spec(mesh, x.shape, (ba,) + (None,) * (x.ndim - 2) + ("model",))
        elif kind == "ecf":
            # MoE expert intermediates (NG, E, C, d_or_ff): groups follow the
            # batch axes; experts over "model" when divisible (expert
            # parallel), else the hidden dim over "model" (TP inside expert).
            if _fits(x.shape[1], mesh, "model"):
                wants = (ba, "model") + (None,) * (x.ndim - 2)
            else:
                wants = (ba,) + (None,) * (x.ndim - 2) + ("model",)
            spec = _spec(mesh, x.shape, wants)
        elif kind == "moe_route":
            # routing tensors (NG, ...): groups over the batch axes only
            spec = _spec(mesh, x.shape, (ba,) + (None,) * (x.ndim - 1))
        elif kind == "carry":
            # sequence parallelism at segment boundaries: the scan-carried
            # residual (B, T, d) shards T over "model", so the remat stash
            # (n_segments x carry) is 16x smaller per chip; attention/scan
            # mixers re-gather T inside the layer, MLPs stay seq-sharded.
            spec = _spec(mesh, x.shape, (ba, "model", None))
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard


# ---------------------------------------------------------------------------
# Server accumulator placement
# ---------------------------------------------------------------------------


def accumulator_spec(mesh: Mesh, shape, axis: str = "data") -> NamedSharding:
    """Placement of the server's dense (d0, d1) aggregation accumulator:
    row-sharded over ``mesh[axis]`` — the layout
    ``sharded_scatter_accumulate`` (kernels/scatter_accum/sharded.py)
    produces, each device owning a contiguous row window. Degrades to
    replication when d0 doesn't divide the axis extent, like every other
    rule here (the sharded scatter itself then refuses; callers fall
    back to the streamed single-device path)."""
    return NamedSharding(mesh, _spec(mesh, shape, (axis, None)))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def batch_specs(batch_shape: Any, mesh: Mesh):
    ba = batch_axes(mesh)

    def one(x):
        spec = _spec(mesh, x.shape, (ba,) + (None,) * (x.ndim - 1))
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape: Any, mesh: Mesh, cfg: ModelConfig):
    """KV caches: batch over ("pod","data"); kv-head dim over "model" when
    divisible, else sequence dim over "model" (sequence-parallel cache),
    else replicated. SSM states: feature dim over "model"."""
    ba = batch_axes(mesh)

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + "/" + k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, prefix + f"/{i}") for i, v in enumerate(tree))
        shape = tree.shape
        name = prefix.split("/")[-1]
        # layouts by leaf name
        if name in ("k", "v"):           # (seg, B, S, KV, hd)
            wants = (None, ba, None, "model", None)
            if not _fits(shape[3], mesh, "model") and _fits(shape[2], mesh, "model"):
                wants = (None, ba, "model", None, None)
            return NamedSharding(mesh, _spec(mesh, shape, wants))
        if name in ("ckv", "krope"):     # (seg, B, S, r)
            wants = (None, ba, "model" if _fits(shape[2], mesh, "model") else None, None)
            return NamedSharding(mesh, _spec(mesh, shape, wants))
        if name == "conv":               # (seg, B, k, Di)
            return NamedSharding(mesh, _spec(mesh, shape, (None, ba, None, "model")))
        if name == "ssm":                # (seg, B, Di, S)
            return NamedSharding(mesh, _spec(mesh, shape, (None, ba, "model", None)))
        if name == "c" and len(shape) == 5:  # mlstm (seg, B, H, hd, hd)
            return NamedSharding(mesh, _spec(mesh, shape, (None, ba, "model", None, None)))
        if name in ("c", "n", "m", "h"):
            wants = (None, ba) + (None,) * (len(shape) - 2)
            return NamedSharding(mesh, _spec(mesh, shape, wants))
        if name == "enc":                # (B, S_enc, d)
            return NamedSharding(mesh, _spec(mesh, shape, (ba, None, None)))
        return NamedSharding(mesh, _spec(mesh, shape, (None,) * len(shape)))

    return walk(cache_shape, "cache")
