"""FedNL curvature learning at LLM scale (beyond-paper adaptation).

The paper's full d x d Hessian is infeasible for d >= 1e6, but its core
mechanism — learn a curvature estimate H via compressed differences

    H^{k+1} = H^k + alpha * C(D^k - H^k),        C contractive,

with the l^k = ||D^k - H^k||_F correction making H + l I a safe
preconditioner (Option 2) — applies verbatim to *structured* curvature.
Here H is per-parameter-tensor **diagonal** curvature, D^k is a local
curvature observation:

  * 'fisher'     — minibatch empirical Fisher diagonal, D = E[g^2]
  * 'hutchinson' — Hutchinson diagonal estimate z * (Hess z) via one
                   extra HVP per step (true GGN curvature)

and C is Block-TopK over the (2D-reshaped) tensor — the same operator
class (delta = k_b/b^2) the core library proves rates for, and the same
Pallas kernel the TPU path uses.

Placement of compression: in cross-silo deployment each silo compresses
its D_i^k before uplink (the paper's accounting); inside a single pod the
data-parallel all-reduce is dense, so the compressed learning rule is
applied to the aggregated D^k. The contraction argument (Lemma B.1 with
y = aggregated observation) is unchanged; DESIGN.md §3 records this
deviation. Both placements speak the payload wire format end to end:
compression goes through the FUSED diff payload op
(``kernels/block_topk.diff_topk_payload`` — the Pallas kernel on TPU,
the sort-based jnp oracle elsewhere): D = obs - H is formed tile-wise
in VMEM, selected, and emitted as payload arrays in one pass, with
||D||_F^2 accumulated from the same tiles, so the dense difference
never round-trips HBM and the l^k norm costs no extra reduction. The
dense H increment is reconstructed through the payload-space scatter
(``kernels/scatter_accum.block_scatter_accumulate``), so the training
step materializes neither a dense (nblocks, block^2) selection mask nor
a per-silo dense decompression round-trip. When ``observations`` carry
a leading silo axis (one observation per silo — the paper's placement)
each silo compresses its own diff and H is updated from the server-side
payload-space mean — the same aggregation subsystem the core methods
use. On a multi-device ``mesh`` the kernels run inside ``shard_map``
(the TPU compiler cannot partition a Pallas kernel itself): each device
compresses the silos of its ``data`` shard when the silo count matches
that axis, against the replicated H, and the server mean is computed
on every device from the gathered payloads.

Update rule per tensor (Option-2 Newton-type step, diagonal solve):

    l^k   = ||D^k - H^k||_F / sqrt(numel)        (scale-matched ridge)
    u     = -lr * g / (sqrt(max(H^k, 0)) + sqrt(l^k) + eps)
    H^{k+1} = H^k + alpha * C(D^k - H^k)

The sqrt denominator is deliberate (pinned by tests/test_infra.py):
H tracks *squared*-gradient curvature (Fisher / Hutchinson-GGN), so
sqrt(H) is the gradient's natural scale — the Adam/AdaGrad-consistent
diagonal Newton step — and the ridge enters as sqrt(l) so both terms
live in the same units.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compressors import BlockSparsePayload, BlockTopK, BlockTopKThreshold
from repro.kernels.block_topk import block_topk_payload, diff_topk_payload

from .optim import Optimizer


class FedNLPrecondState(NamedTuple):
    step: jax.Array
    h: Any            # per-tensor diagonal curvature estimates (fp32)
    mu: Any           # momentum on the preconditioned step
    l: Any = ()       # per-tensor Option-2 ridge from the last refresh


def _shape2d(shape) -> tuple:
    """Block-partition layout of a tensor: collapse every leading axis
    onto the rows so a stacked per-layer param (n_seg, din, dout) tiles
    as (n_seg * din, dout) — each layer's rows land in their own block
    rows instead of one long smeared row per segment."""
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1, shape[0])
    rows = 1
    for s in shape[:-1]:
        rows *= s
    return (rows, shape[-1])


def _as2d(x: jax.Array) -> jax.Array:
    return x.reshape(_shape2d(x.shape))


@dataclasses.dataclass(frozen=True)
class FedNLPrecondOptimizer:
    lr: float = 1e-3
    alpha: float = 1.0                 # Hessian learning rate (Assumption 3.4(ii))
    k_per_block: int = 2048            # Block-TopK sparsity (delta = k/b^2)
    block: int = 128
    momentum: float = 0.9
    eps: float = 1e-8
    weight_decay: float = 0.0
    curvature: str = "fisher"          # fisher | hutchinson
    selector: str = "threshold"        # threshold (bisection) | sort
    use_pallas: Optional[bool] = None  # None = auto (Pallas ops on TPU)
    mesh: Any = None                   # device mesh the train step runs on

    def _k(self) -> int:
        return min(self.k_per_block, self.block * self.block)

    @property
    def compressor(self):
        """The Block-TopK codec — the analytic Def 3.3 operator
        (``spec``/delta accounting and the aggregate reference).
        ``update`` itself routes compression through the payload op,
        whose selection matches ``threshold`` (bisection, the Pallas
        kernel) on TPU and ``sort`` (jax.lax.top_k) elsewhere — the two
        differ only inside bisection-resolution tie clusters."""
        if self.selector == "threshold":
            # §Perf pair 3: bisection selection (the Pallas kernel's
            # algorithm) instead of a per-tile sort inside every step.
            return BlockTopKThreshold(k_per_block=self._k(), block=self.block)
        return BlockTopK(k_per_block=self._k(), block=self.block)

    def init(self, params) -> FedNLPrecondState:
        z32 = lambda p: jnp.zeros(p.shape, jnp.float32)
        return FedNLPrecondState(
            jnp.zeros((), jnp.int32),
            jax.tree.map(z32, params),
            jax.tree.map(z32, params),
            jax.tree.map(lambda p: jnp.zeros((), jnp.float32), params),
        )

    def observe(self, grads, params=None, hvp=None):
        """Local curvature observation D^k per tensor."""
        if self.curvature == "hutchinson":
            if hvp is None:
                raise ValueError(
                    "curvature='hutchinson' requires the hvp=(z, Hz) "
                    "probe (one Hessian-vector product per step); got "
                    "hvp=None — refusing to silently fall back to the "
                    "Fisher diagonal")
            # hutchinson: caller supplies hvp = Hessian @ z and the probe z
            z, hz = hvp
            return jax.tree.map(
                lambda zz, hh: (zz.astype(jnp.float32)
                                * hh.astype(jnp.float32)), z, hz)
        return jax.tree.map(lambda g: g.astype(jnp.float32) ** 2, grads)

    def _compress_payload(self, x2d: jax.Array):
        """Device-side compress of one 2D diff into the
        BlockSparsePayload arrays via the payload-emitting op: the step
        never materializes a dense (nblocks, block^2) selection mask on
        the Pallas path."""
        return block_topk_payload(x2d, k=self._k(), block=self.block,
                                  use_pallas=self.use_pallas)

    def _diff_payload(self, a2d: jax.Array, b2d: jax.Array):
        """Fused diff -> select -> payload of D = a2d - b2d plus the
        Frobenius sum-of-squares of D, one pass: on the Pallas path the
        dense (d, d) difference lives only in VMEM tiles — it never
        round-trips HBM — and ||D||_F comes free from the same tiles."""
        with jax.named_scope("fednl.uplink"):
            return diff_topk_payload(a2d, b2d, k=self._k(), block=self.block,
                                     use_pallas=self.use_pallas)

    def _payload_mean(self, vals: jax.Array, idx: jax.Array, shape2):
        """Dense mean of n stacked per-silo payloads through the one
        payload-space aggregation (``_BlockSparse.aggregate`` — the
        tiled-by-construction block scatter kernel on TPU): no per-silo
        dense decompression, ONE accumulator."""
        payloads = BlockSparsePayload(values=vals, indices=idx,
                                      universe=self.block * self.block)
        with jax.named_scope("fednl.server"):
            return self.compressor.aggregate(payloads, tuple(shape2),
                                             use_pallas=self.use_pallas)

    def _sharded(self, fn, silo_specs):
        """``fn`` under ``shard_map`` on a multi-device mesh (the silo
        axis on ``data`` where ``silo_specs`` marks it, everything else
        replicated); ``fn`` itself on one device."""
        if self.mesh is None or self.mesh.size == 1:
            return fn
        spec = lambda silo: P("data") if silo else P()
        ins, outs = silo_specs
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=tuple(spec(s) for s in ins),
                             out_specs=tuple(spec(s) for s in outs),
                             check_vma=False)

    def _learn_tensor(self, h, d_obs):
        """One tensor's compressed Hessian learning: the payload-space
        increment s = C(D^k - H^k) (or the server mean of per-silo
        payloads when ``d_obs`` carries a leading silo axis) plus the
        scale-matched Option-2 ridge l^k. Returns (s, l)."""
        h2 = _as2d(h)
        if d_obs.ndim == h.ndim + 1:
            # cross-silo: per-silo payloads, ONE dense accumulator.
            # Each silo runs the fused diff kernel against the same
            # shared H — the per-silo dense diff never materializes.
            n = d_obs.shape[0]
            obs2 = d_obs.astype(jnp.float32).reshape((n,) + h2.shape)
            silo = (self.mesh is not None
                    and dict(self.mesh.shape).get("data") == n)
            vals, idx, sq = self._sharded(
                lambda o, hh: jax.vmap(
                    lambda a: self._diff_payload(a, hh))(o),
                ((silo, False), (silo, silo, silo)))(obs2, h2)
            s = self._sharded(
                lambda v, i: (self._payload_mean(v, i, h2.shape),),
                ((False, False), (False,)))(vals, idx)[0].reshape(h.shape)
            # l^k = mean_i ||D_i - H||_F, scale-matched (Option 2)
            l = jnp.mean(jnp.sqrt(sq / h.size + 1e-30))
        else:
            # the uplink object is the payload; H learns from it.
            # Fused: D = obs - H is formed tile-wise inside the
            # payload kernel, and sq = ||D||_F^2 rides along.
            def learn(o, hh):
                vals, idx, sq = self._diff_payload(o, hh)
                return self._payload_mean(vals[None], idx[None],
                                          hh.shape), sq

            s, sq = self._sharded(learn, ((False, False), (False, False)))(
                _as2d(d_obs), h2)
            s = s.reshape(h.shape)
            # l^k correction (Option 2), scale-matched to the diagonal
            l = jnp.sqrt(sq / h.size + 1e-30)
        return s, l

    def _precond_tensor(self, g, h, m, p, l):
        """The cheap per-step preconditioned update from stored (h, l)."""
        g32 = g.astype(jnp.float32)
        denom = jnp.sqrt(jnp.maximum(h, 0.0)) + jnp.sqrt(l) + self.eps
        step = g32 / denom
        if self.weight_decay:
            step = step + self.weight_decay * p.astype(jnp.float32)
        m_new = self.momentum * m + step
        u = (-self.lr * m_new).astype(p.dtype)
        return u, m_new

    @staticmethod
    def _pick(out, i):
        return jax.tree.map(lambda t: t[i], out,
                            is_leaf=lambda t: isinstance(t, tuple))

    def refresh(self, state: FedNLPrecondState, observations
                ) -> FedNLPrecondState:
        """Learn curvature from (possibly silo-stacked) observations —
        the expensive, uplink-bearing phase. Updates ``h`` and the
        stored ridge ``l``; ``step``/``mu`` are untouched, so the train
        step can run this under ``lax.cond`` every ``refresh_every``
        steps and ``precondition`` every step."""
        out = jax.tree.map(self._learn_tensor, state.h, observations)
        s, l = self._pick(out, 0), self._pick(out, 1)
        with jax.named_scope("fednl.server"):
            h_new = jax.tree.map(lambda h, si: h + self.alpha * si, state.h,
                                 s)
        return state._replace(h=h_new, l=l)

    def precondition(self, grads, state: FedNLPrecondState, params):
        """Preconditioned step from the curvature stored by the last
        ``refresh`` (h AND its matching l — unlike legacy ``update``,
        which blends the pre-learning h with the current obs l)."""
        unset = isinstance(state.l, tuple) and len(state.l) == 0
        l = jax.tree.map(lambda h: jnp.zeros((), jnp.float32),
                         state.h) if unset else state.l
        out = jax.tree.map(self._precond_tensor, grads, state.h, state.mu,
                           params, l)
        return self._pick(out, 0), state._replace(
            step=state.step + 1, mu=self._pick(out, 1))

    def uplink_bits(self, params, n_silos: int = 1) -> int:
        """Host-side wire cost of ONE curvature refresh: every silo
        ships one Block-TopK diff payload per parameter tensor
        (``wire_cost`` analytic accounting — k values + k indices per
        block on the 2D block partition). Call at setup time, not
        inside the jitted step."""
        from repro.wire import wire_cost

        total = 0
        for p in jax.tree.leaves(params):
            rep = wire_cost(self.compressor, _shape2d(p.shape),
                            encoded=False)
            total += int(rep.analytic_bits)
        return total * int(n_silos)

    def update(self, grads, state: FedNLPrecondState, params,
               observations=None):
        """``observations`` leaves may carry a leading silo axis (ndim ==
        param.ndim + 1): then each silo's diff is compressed on-device
        and H learns from the payload-space server mean.

        This is the fused learn-and-step path (curvature every step);
        the amortized train-step path is ``refresh`` + ``precondition``.
        Pinned semantics: the denominator uses the PRE-learning h with
        the CURRENT observation's l."""

        obs = observations if observations is not None else self.observe(grads)

        def per_tensor(g, h, m, p, d_obs):
            s, l = self._learn_tensor(h, d_obs)
            u, m_new = self._precond_tensor(g, h, m, p, l)
            h_new = h + self.alpha * s
            return u, h_new, m_new, l

        out = jax.tree.map(per_tensor, grads, state.h, state.mu, params, obs)
        return self._pick(out, 0), FedNLPrecondState(
            state.step + 1, self._pick(out, 1), self._pick(out, 2),
            self._pick(out, 3))


def fednl_precond(lr: float = 1e-3, **kw) -> Optimizer:
    """Adapter matching the Optimizer(init, update) protocol. ``update``
    is bound directly (NOT wrapped in a 3-arg lambda) so the optional
    ``observations`` 4th argument — the cross-silo payload path —
    reaches the optimizer through the protocol; the amortized
    second-order hooks (observe / refresh / precondition) and the
    host-side uplink accounting are bound alongside so
    ``make_train_step`` can drive the refresh-interval path."""
    opt = FedNLPrecondOptimizer(lr=lr, **kw)
    return Optimizer(opt.init, opt.update, observe=opt.observe,
                     refresh=opt.refresh, precondition=opt.precondition,
                     uplink_bits=opt.uplink_bits)
