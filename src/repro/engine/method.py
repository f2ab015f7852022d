"""The ``Method`` protocol and registry — the contract every optimizer in
the FedNL family (and the Newton reference methods) implements so one
engine can drive all of them.

A *method* is a stateless-config object with three hooks:

  init(x0, n, *, seed=0, **kw) -> State   # pytree (NamedTuple) of arrays
  step(State) -> State                    # one communication round, jittable
  bits_per_round(d) -> int | (int, int)   # analytic uplink (and downlink)

plus two class attributes consumed by the shared driver:

  traj_field: str   # which State field is the monitored iterate
                    # ("x" for most methods, "z" for FedNL-BC)
  silo_fields: tuple[str, ...]  # State fields with a leading silo axis
                    # (used by the shard_map execution path)

``MethodBase`` supplies the single ``run`` loop (lax.scan over rounds)
that used to be copy-pasted into every algorithm module, and
``scan_rounds`` is the same driver in function form for the sweep
runner, where it sits under an extra ``vmap`` over seeds.

The registry maps string keys ("fednl", "fednl-pp", ...) to factories
``factory(oracles, compressor=None, **params) -> Method`` so sweeps and
CLIs can construct any method declaratively. Factories self-register in
the module that defines the method class.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp


class Oracles(NamedTuple):
    """Problem oracles in the paper's federated form.

    value: x -> ()        global objective f(x) (may be None for methods
                          that never evaluate f, e.g. plain FedNL)
    grad:  x -> (n, d)    stacked per-silo gradients
    hess:  x -> (n, d, d) stacked per-silo Hessians
    """

    value: Optional[Callable[[jax.Array], jax.Array]]
    grad: Callable[[jax.Array], jax.Array]
    hess: Callable[[jax.Array], jax.Array]


@runtime_checkable
class Method(Protocol):
    traj_field: str

    def init(self, x0: jax.Array, n: int, *, seed=0, **kw): ...

    def step(self, state): ...

    def bits_per_round(self, d: int): ...


def scan_rounds(method, state, num_rounds: int):
    """Shared round loop: ``lax.scan`` of ``method.step``, recording the
    method's monitored iterate each round. Returns (final_state, xs)
    with xs of shape (num_rounds, d) — the caller prepends x0."""

    def body(s, _):
        ns = method.step(s)
        return ns, getattr(ns, method.traj_field)

    return jax.lax.scan(body, state, None, length=num_rounds)


class MethodBase:
    """Mixin providing the one true ``run`` driver plus the shared
    payload wire helpers.

    Subclasses implement init/step/bits_per_round; ``run`` is the scan
    loop every algorithm module used to duplicate. The uplink is split
    the way the deployment is: ``_uplink_payloads`` (device side:
    compress), ``_local_hessians`` (device side: each silo's OWN dense
    S_i for its H_i update), ``_server_aggregate`` (server side: ONE
    dense (d, d) mean straight from payload space — no silo's dense
    matrix ever reaches the server, and no (n, d, d) stack is formed
    there). ``measured_bits_per_round`` is the measured wire accounting
    every compressed method shares.
    """

    traj_field: str = "x"
    silo_fields: tuple = ("h_local",)

    def _uplink_payloads(self, diff, silo_keys):
        """Device side: each silo compresses its own (d, d) Hessian
        diff into the wire payload it uplinks (vmapped over the silo
        axis; payload shapes are static)."""
        with jax.named_scope("fednl.uplink"):
            return jax.vmap(self.comp.compress)(diff, silo_keys)

    def _uplink_diff_payloads(self, h_new, h_old, silo_keys):
        """Device side, fused: payloads of D_i = h_new_i - h_old_i plus
        l_i = ||D_i||_F, both from one pass. Compressors exposing
        ``fused_diff_payloads`` (the block-sparse family) diff, select,
        and emit tile-wise inside a single kernel — the dense (n, d, d)
        difference never round-trips through HBM on the Pallas path;
        everyone else falls back to compress(h_new - h_old). Callers
        that don't need the norms leave them dead (XLA DCE removes the
        reduction)."""
        fused = getattr(self.comp, "fused_diff_payloads", None)
        with jax.named_scope("fednl.uplink"):
            if fused is not None:
                return fused(h_new, h_old)
            from ..core.linalg import frob_norm

            diff = h_new - h_old
            return (jax.vmap(self.comp.compress)(diff, silo_keys),
                    jax.vmap(frob_norm)(diff))

    def _local_hessians(self, payloads, shape):
        """Device side: each silo reconstructs its OWN dense S_i from
        the payload it just built — the H_i^{k+1} = H_i^k + alpha S_i^k
        update happens on-device, per silo, never aggregated."""
        return jax.vmap(lambda p: self.comp.decompress(p, shape))(payloads)

    def _server_aggregate(self, payloads, shape, weights=None):
        """Server side: S^k = mean_i S_i^k computed in payload space
        (``Compressor.aggregate`` — scatter-add / stacked factors /
        direct mean, one dense accumulator total). ``weights`` rescales
        per-silo contributions (partial-participation masks with 0/1,
        the cohort layer's staleness weights) and is applied INSIDE
        ``aggregate`` — the one weighting point for every wire format.
        Under shard_map (``axis_name`` set) the cross-silo reduction
        happens HERE, on the dense accumulator: one pmean of (d, d)."""
        with jax.named_scope("fednl.server"):
            s = self.comp.aggregate(payloads, shape, weights=weights)
            axis = getattr(self, "axis_name", None)
            if axis is not None:
                s = jax.lax.pmean(s, axis)
            return s

    def measured_bits_per_round(self, d: int, index_coding: str = "raw"):
        """MEASURED per-round wire bits: the compressor's actual payload
        structure (via jax.eval_shape) plus the (d + 1) uncompressed
        floats every single-uplink FedNL variant ships (gradient-sized
        vector + one scalar), at the ambient float width — matches the
        analytic ``bits_per_round`` layout of FedNL/PP/CR/LS/Stochastic
        under x64. ``index_coding="entropy"`` charges the sparsifier
        index streams their entropy-coded estimate (log2 C(d^2, k))
        instead of k raw 32-bit ints. Methods with a different wire
        layout (FedNL-BC, FedNL-PPBC) override. Payload-free methods
        (Newton references) return the analytic number: their wire IS
        dense FLOAT_BITS floats, so the claim equals the wire count by
        construction."""
        comp = getattr(self, "comp", None)
        if comp is None:
            return self.bits_per_round(d)
        from ..core.compressors import canonical_float_bits
        from ..wire.report import wire_cost

        rep = wire_cost(comp, (d, d), encoded=False)
        s_bits = rep.entropy_bits if index_coding == "entropy" else rep.raw_bits
        return s_bits + (d + 1) * canonical_float_bits()

    def run(self, x0, n, num_rounds, *args, seed: int = 0, **init_kw):
        """Run ``num_rounds`` communication rounds from ``x0``.

        Returns (final_state, (num_rounds+1, d) iterate history with x0
        prepended). Extra positional/keyword args (e.g. ``h0``) are
        forwarded to ``init``.
        """
        state = self.init(x0, n, *args, seed=seed, **init_kw)
        final, xs = scan_rounds(self, state, num_rounds)
        return final, jnp.concatenate([jnp.asarray(x0)[None], xs], axis=0)


# -- registry -----------------------------------------------------------------

_REGISTRY: dict[str, Callable[..., Any]] = {}


def register(name: str):
    """Decorator: register ``factory(oracles, compressor=None, **params)``
    under ``name``. Re-registration overwrites (last wins) so notebooks
    can hot-patch methods."""

    def deco(factory):
        _REGISTRY[name] = factory
        return factory

    return deco


def _ensure_registered() -> None:
    # Factories live next to their method classes in repro.core; import
    # lazily to avoid a package-init cycle (core modules import this
    # module for MethodBase). Unconditional: a user registering their own
    # method first must not hide the built-ins (sys.modules makes this
    # free after the first call).
    from .. import core  # noqa: F401


def available_methods() -> list[str]:
    _ensure_registered()
    return sorted(_REGISTRY)


def registered_methods() -> dict[str, Callable[..., Any]]:
    """Snapshot of the method registry (name -> factory) — the
    introspection hook the static-analysis sweep (``repro.analysis``)
    enumerates so every registered method gets traced and checked."""
    _ensure_registered()
    return dict(_REGISTRY)


def make_method(name: str, oracles: Oracles, compressor=None, **params):
    """Construct a registered method by string key.

    ``params`` are forwarded to the factory (e.g. alpha, option, mu,
    tau, p, eta, l_star, model_compressor)."""
    _ensure_registered()
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown method {name!r}; available: {available_methods()}"
        ) from None
    for k, v in params.items():
        # declarative compressor params: ("topk", 16) -> TopK(k=16),
        # resolved through the compressor registry in core.compressors
        if k.endswith("compressor") and isinstance(v, tuple):
            from ..core.compressors import make_compressor

            params[k] = make_compressor(*v)
    return factory(oracles, compressor, **params)
