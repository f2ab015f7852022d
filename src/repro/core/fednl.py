"""FedNL — Algorithm 1 (Federated Newton Learn), faithful implementation.

One communication round (paper Sec. 3):

  devices i = 1..n in parallel:
      receive x^k
      S_i^k = C_i^k(H2_i(x^k) - H_i^k)             # compressed Hessian diff
      l_i^k = ||H_i^k - H2_i(x^k)||_F              # one float
      send  grad_i(x^k), S_i^k, l_i^k
      H_i^{k+1} = H_i^k + alpha S_i^k
  server:
      grad = mean_i grad_i ; S = mean_i S_i ; l = mean_i l_i
      H^{k+1} = H^k + alpha S
      Option 1: x^{k+1} = x^k - [H^k]_mu^{-1} grad
      Option 2: x^{k+1} = x^k - (H^k + l^k I)^{-1} grad

The implementation is a pure jittable step over *stacked* per-silo state,
so the same code runs (a) single-process via vmap, and (b) sharded over a
mesh axis via shard_map (see core/federated.py). The device uplink is an
explicit wire object: each silo builds a compressed ``Payload``, keeps
its OWN dense S_i for the local H_i update, and the server computes
S = mean_i S_i *in payload space* (``Compressor.aggregate`` — one dense
(d, d) accumulator, no per-silo decompression server-side). Communicated
bits are *measured* from the payload structure
(``measured_bits_per_round``) next to the paper's analytic accounting
(``bits_per_round``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..engine.method import MethodBase, Oracles, register
from .compressors import Compressor
from .linalg import project_psd, solve_newton_system


class FedNLState(NamedTuple):
    x: jax.Array        # (d,) global model
    h_local: jax.Array  # (n, d, d) local Hessian estimates H_i
    h_global: jax.Array  # (d, d) server estimate H = mean_i H_i
    key: jax.Array      # PRNG for randomized compressors
    step: jax.Array     # iteration counter


class FedNL(MethodBase):
    """Vanilla FedNL. ``option`` in {1, 2}; ``mu`` needed for Option 1.

    grad_fn:  x -> (n, d) stacked per-silo gradients
    hess_fn:  x -> (n, d, d) stacked per-silo Hessians
    """

    def __init__(
        self,
        grad_fn: Callable[[jax.Array], jax.Array],
        hess_fn: Callable[[jax.Array], jax.Array],
        compressor: Compressor,
        alpha: float = 1.0,
        option: int = 1,
        mu: float = 0.0,
        axis_name: Optional[str] = None,
    ):
        """``axis_name``: when set, the step is written for execution under
        ``shard_map`` with the silo dimension sharded over that mesh axis —
        per-silo math runs on the local slab and "send to server" becomes a
        ``lax.pmean`` over the axis (the TPU-idiomatic server)."""
        assert option in (1, 2)
        self.grad_fn = grad_fn
        self.hess_fn = hess_fn
        self.comp = compressor
        self.alpha = alpha
        self.option = option
        self.mu = mu
        self.axis_name = axis_name

    def _mean(self, v: jax.Array) -> jax.Array:
        m = jnp.mean(v, axis=0)
        if self.axis_name is not None:
            m = jax.lax.pmean(m, self.axis_name)
        return m

    # -- state ---------------------------------------------------------------

    def init(self, x0: jax.Array, n: int, h0: Optional[jax.Array] = None,
             seed: int = 0) -> FedNLState:
        """h0: (n,d,d) initial local estimates; default = exact local
        Hessians at x0 (the paper's initialization for FedNL)."""
        if h0 is None:
            h0 = self.hess_fn(x0)
        h0 = jnp.asarray(h0)
        return FedNLState(
            x=x0,
            h_local=h0,
            h_global=jnp.mean(h0, axis=0),
            key=jax.random.PRNGKey(seed),
            step=jnp.zeros((), jnp.int32),
        )

    # -- one communication round ----------------------------------------------

    def step(self, state: FedNLState) -> FedNLState:
        n = state.h_local.shape[0]
        key, sub = jax.random.split(state.key)
        if self.axis_name is not None:
            sub = jax.random.fold_in(sub, jax.lax.axis_index(self.axis_name))
        silo_keys = jax.random.split(sub, n)

        with jax.named_scope("fednl.oracle"):
            grads = self.grad_fn(state.x)                 # (n, d)
            hesses = self.hess_fn(state.x)                # (n, d, d)

        # devices uplink payloads of D_i = hess_i - H_i (fused
        # diff->select->payload where the compressor supports it, so the
        # dense diff stays in VMEM); each silo keeps its OWN dense S_i
        # for the local H_i update, the server means in payload space —
        # the (n, d, d) decompressed stack never reaches the server
        payloads, l_i = self._uplink_diff_payloads(hesses, state.h_local,
                                                   silo_keys)
        with jax.named_scope("fednl.local_update"):
            s_i = self._local_hessians(payloads, hesses.shape[1:])
            h_local = state.h_local + self.alpha * s_i

        with jax.named_scope("fednl.server"):
            grad = self._mean(grads)
            s_mean = self._server_aggregate(payloads, hesses.shape[1:])
            l_mean = self._mean(l_i)
            h_global = state.h_global + self.alpha * s_mean

        # Model update uses the *current* H^k (paper lines 11-12 use H^k).
        with jax.named_scope("fednl.solve"):
            if self.option == 1:
                h_eff = project_psd(state.h_global, self.mu)
            else:
                d = state.x.shape[0]
                h_eff = state.h_global + l_mean * jnp.eye(d, dtype=state.x.dtype)
        x_new = state.x - solve_newton_system(h_eff, grad)

        return FedNLState(x_new, h_local, h_global, key, state.step + 1)

    # -- communication accounting ----------------------------------------------

    def bits_per_round(self, d: int) -> int:
        """ANALYTIC uplink bits per device per round: gradient + S_i + l_i
        (the paper's x-axis, FLOAT_BITS-denominated)."""
        from ..wire.report import wire_cost
        from .compressors import FLOAT_BITS

        s_bits = wire_cost(self.comp, (d, d), encoded=False).analytic_bits
        return d * FLOAT_BITS + s_bits + FLOAT_BITS

    # measured_bits_per_round comes from MethodBase: payload structure
    # (jax.eval_shape) + (d + 1) ambient floats — the same layout.

    def init_bits(self, d: int) -> int:
        """The paper counts the cost of shipping H_i^0 = hess(x0) once."""
        from .compressors import FLOAT_BITS

        return d * (d + 1) // 2 * FLOAT_BITS  # symmetric matrix

    # The round loop (``run``) comes from MethodBase: lax.scan of ``step``
    # recording ``x``, with x0 prepended.


@register("fednl")
def _make_fednl(oracles: Oracles, compressor, **params):
    return FedNL(oracles.grad, oracles.hess, compressor, **params)
