"""Distributed execution of FedNL over a JAX mesh.

The paper's communication pattern (devices -> server -> devices) maps to:

* the silo dimension of the DATA sharded over a mesh axis (default
  "data") — each device holds its silos' (a, b) slabs and Hessian
  estimates H_i, and computes purely locally;
* "send compressed update to server" = ``lax.pmean`` over that axis;
* "broadcast x^{k+1}" = the replicated output of the collective.

``run_fednl_sharded`` builds the per-shard oracles from the local data
slab inside ``shard_map``, so no device ever touches another silo's
training data — the paper's [pe] privacy posture holds structurally, not
just in accounting. Works on any mesh whose axis divides the silo count,
including a single-device mesh (trivial collectives), so the same code
path runs in CI and on a pod.

Byte accounting: the paper's bits-per-round metric is analytic
(``FedNL.bits_per_round``); inside one pod the all-reduce moves dense
tiles and is what §Roofline measures for the LM-scale adaptation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compressors import Compressor
from .fednl import FedNL, FedNLState
from .objectives import LogRegData, silo_grad, silo_hess


def run_fednl_sharded(data: LogRegData, compressor: Compressor, mesh: Mesh,
                      x0: jax.Array, num_rounds: int, alpha: float = 1.0,
                      option: int = 2, mu: float = 0.0, axis: str = "data",
                      seed: int = 0):
    """FedNL with silos sharded over ``mesh[axis]``. Returns
    (final state with sharded h_local, (rounds+1, d) iterate history)."""
    n = data.a.shape[0]
    lam = data.lam

    def local_oracles(a, b):
        grad_fn = lambda x: jax.vmap(lambda aa, bb: silo_grad(x, aa, bb, lam))(a, b)
        hess_fn = lambda x: jax.vmap(lambda aa, bb: silo_hess(x, aa, bb, lam))(a, b)
        return grad_fn, hess_fn

    state_specs = FedNLState(x=P(), h_local=P(axis), h_global=P(), key=P(),
                             step=P())

    # check_vma=False: on the TPU the step's compressor and aggregate
    # lower to pallas_calls, which the varying-axes checker cannot type;
    # the specs state every layout.
    @partial(_shard_map, mesh=mesh,
             in_specs=(state_specs, P(axis), P(axis)),
             out_specs=state_specs, check_vma=False)
    def sharded_step(state: FedNLState, a, b) -> FedNLState:
        grad_fn, hess_fn = local_oracles(a, b)
        alg = FedNL(grad_fn, hess_fn, compressor, alpha=alpha, option=option,
                    mu=mu, axis_name=axis)
        return alg.step(state)

    # global init (exact local Hessians at x0), then shard
    grad_all = lambda x: jax.vmap(lambda aa, bb: silo_grad(x, aa, bb, lam))(
        data.a, data.b)
    hess_all = lambda x: jax.vmap(lambda aa, bb: silo_hess(x, aa, bb, lam))(
        data.a, data.b)
    alg0 = FedNL(grad_all, hess_all, compressor, alpha=alpha, option=option,
                 mu=mu)
    state = alg0.init(x0, n, seed=seed)

    shard = lambda arr, spec: jax.device_put(arr, NamedSharding(mesh, spec))
    state = state._replace(h_local=shard(state.h_local, P(axis)))
    a_sh = shard(data.a, P(axis))
    b_sh = shard(data.b, P(axis))

    step = jax.jit(sharded_step)
    xs = [x0]
    for _ in range(num_rounds):
        state = step(state, a_sh, b_sh)
        xs.append(state.x)
    return state, jnp.stack(xs)
