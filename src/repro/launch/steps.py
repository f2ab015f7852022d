"""Jittable train/prefill/serve steps shared by the trainer, the server,
and the dry-run.

``make_train_step``  : (params, opt_state, batch) -> (params, opt_state, metrics)
``make_prefill``     : (params, batch) -> logits
``make_serve_step``  : (params, cache, token, pos) -> (logits, cache)

Optimizer choice: 'adamw' | 'sgd' | 'fednl' (the paper's technique as a
structured-curvature preconditioner — see second_order/fednl_precond.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import Model
from repro.second_order import adamw, fednl_precond, sgd
from repro.second_order.optim import apply_updates


def make_optimizer(name: str, lr: float, moment_dtype=None, **kw):
    if name == "adamw":
        return adamw(lr, moment_dtype=moment_dtype)
    if name == "sgd":
        return sgd(lr, momentum=0.9)
    if name == "fednl":
        # the adapter binds update directly (the observations 4th arg —
        # the cross-silo payload path — must survive) AND the amortized
        # observe/refresh/precondition protocol that make_train_step's
        # curvature phase drives.
        return fednl_precond(lr, **kw)
    raise ValueError(name)


def make_train_step(model: Model, optimizer, microbatches: int = 1,
                    unroll_microbatches: bool = False,
                    refresh_every: int = 1, n_silos: int = 1,
                    hvp: bool = False, probe_seed: int = 0):
    """``microbatches > 1`` splits the global batch and accumulates grads
    with an inner scan — the remat residual stash then holds one
    microbatch's activations instead of the whole batch's (the difference
    between 51 GB and 6 GB per chip for grok-1 at train_4k).
    ``unroll_microbatches`` unrolls that scan so cost_analysis counts
    every microbatch (dry-run probes only).

    Second-order optimizers (``optimizer.refresh`` is set — the fednl
    path) get a curvature-observation phase: every ``refresh_every``
    steps (a jittable ``lax.cond`` on the step counter, so the interval
    costs nothing to the compiled graph on the other steps) the global
    batch is split along its leading axis into ``n_silos`` shards — the
    mesh data axis in the launch driver, so each data shard plays one
    FedNL silo — and an inner scan computes one curvature observation
    per silo (empirical-Fisher g^2, or a Hutchinson z*(Hz) probe via
    one jvp-of-grad when ``hvp``). The silo-stacked observations flow
    through ``optimizer.refresh`` (per-silo fused diff payloads +
    payload-space server mean — the paper's uplink placement) and the
    actual parameter update is ``optimizer.precondition`` from the
    stored curvature: refresh cost is amortized, the per-step cost is
    an elementwise diagonal solve. First-order optimizers ignore all
    of this and take the plain ``update`` path."""

    second_order = getattr(optimizer, "refresh", None) is not None \
        and refresh_every >= 1

    def grads_of(params, batch):
        return jax.value_and_grad(model.loss_fn)(params, batch)

    def observe_and_refresh(state, params, batch):
        """One curvature refresh: scan over the silo shards of the
        batch, one observation each, then learn H from the stack."""
        sb = jax.tree.map(
            lambda x: x.reshape((n_silos, x.shape[0] // n_silos)
                                + x.shape[1:]), batch)

        def silo_obs(carry, xs):
            b_i, i = xs
            if hvp:
                # forward-over-reverse: primal out is the silo grad,
                # tangent out is Hz — one pass buys both.
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(probe_seed),
                                       state.step), i)
                leaves, treedef = jax.tree_util.tree_flatten(params)
                keys = jax.random.split(key, len(leaves))
                z = treedef.unflatten([
                    jax.random.rademacher(k, p.shape, jnp.int8
                                          ).astype(p.dtype)
                    for k, p in zip(keys, leaves)])
                gfn = lambda p: jax.grad(model.loss_fn)(p, b_i)
                g_i, hz = jax.jvp(gfn, (params,), (z,))
                obs = optimizer.observe(g_i, params, hvp=(z, hz))
            else:
                g_i = jax.grad(model.loss_fn)(params, b_i)
                obs = optimizer.observe(g_i)
            return carry, obs

        with jax.named_scope("train.observe"):
            _, obs = jax.lax.scan(silo_obs, 0,
                                  (sb, jnp.arange(n_silos, dtype=jnp.int32)))
        return optimizer.refresh(state, obs)

    def train_step(params, opt_state, batch):
        with jax.named_scope("train.forward_backward"):
            if microbatches == 1:
                loss, grads = grads_of(params, batch)
            else:
                mb = jax.tree.map(
                    lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                        + x.shape[1:]), batch)

                def acc_body(carry, mb_batch):
                    loss_acc, g_acc = carry
                    loss_i, g_i = grads_of(params, mb_batch)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, g_i)
                    return (loss_acc + loss_i, g_acc), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (loss, grads), _ = jax.lax.scan(
                    acc_body, (jnp.zeros((), jnp.float32), g0), mb,
                    unroll=microbatches if unroll_microbatches else 1)
                loss = loss / microbatches
                grads = jax.tree.map(
                    lambda g, p: (g / microbatches).astype(p.dtype), grads, params)

        refreshed = jnp.zeros((), jnp.float32)
        if second_order:
            b0 = jax.tree.leaves(batch)[0].shape[0]
            if b0 % n_silos:
                raise ValueError(
                    f"global batch {b0} must divide into n_silos={n_silos}")
            do_refresh = (opt_state.step % refresh_every) == 0
            opt_state = jax.lax.cond(
                do_refresh,
                lambda s: observe_and_refresh(s, params, batch),
                lambda s: s, opt_state)
            refreshed = do_refresh.astype(jnp.float32)
        with jax.named_scope("train.update"):
            if second_order:
                updates, opt_state = optimizer.precondition(
                    grads, opt_state, params)
            else:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
            params = apply_updates(params, updates)
            # NB: reduce per-leaf WITHOUT reshaping — flattening a
            # 2D-sharded tensor forces GSPMD to all-gather it (412 GB for
            # grok-1's stacked expert grads); jnp.sum over all axes
            # partitions cleanly.
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "curv_refreshed": refreshed}

    return train_step


def make_prefill(model: Model):
    def prefill(params, batch):
        logits, _ = model.forward(params, batch)
        return logits

    return prefill


def make_serve_step(model: Model):
    def serve_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)

    return serve_step
