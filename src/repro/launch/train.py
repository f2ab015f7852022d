"""Training driver: runs real steps on whatever devices exist.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --optimizer fednl

On the CPU container this trains the reduced (smoke) configs; pointed at
a TPU slice the same code paths run the full configs on the production
mesh (the dry-run proves those lower+compile).
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Callable, NamedTuple

import jax

from repro.checkpoint import save as save_ckpt
from repro.compile_cache import use_compile_cache
from repro.configs import ARCHS, get_config
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import (
    make_activation_sharder,
    make_layer_param_constrainer,
    opt_state_shardings,
    tree_param_specs,
)
from repro.launch.steps import make_optimizer, make_train_step
from repro.models import build_model
from repro.models.common import set_activation_sharder


def add_modality_inputs(batch, cfg, step: int):
    key = jax.random.fold_in(jax.random.PRNGKey(1234), step)
    b = batch["tokens"].shape[0]
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.d_model), cfg.jdtype) * 0.02
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (b, cfg.enc_seq, cfg.d_model), cfg.jdtype) * 0.02
    return batch


class TrainRun(NamedTuple):
    """What a ``train`` call leaves behind: the per-step losses, how many
    steps refreshed the curvature, the wire bits one refresh ships (0
    for first-order optimizers), the jitted step and the final state
    it was last called with."""
    losses: list
    refreshes: int
    curv_bits: int
    step: Callable
    params: Any
    opt_state: Any


def train(arch: str, smoke: bool = True, steps: int = 20, batch: int = 8,
          seq: int = 128, lr: float = 3e-4, optimizer: str = "adamw",
          microbatches: int = 1, log_every: int = 10, ckpt: str | None = None,
          seed: int = 0, refresh_every: int = 4, curvature_k: int = 2048,
          hvp: bool = False):
    cfg = get_config(arch, smoke=smoke)
    mesh = make_host_mesh()
    set_activation_sharder(make_activation_sharder(mesh),
                           make_layer_param_constrainer(mesh, cfg))
    model = build_model(cfg, use_remat=True)
    params = model.init_params(jax.random.PRNGKey(seed))
    params = jax.device_put(params, tree_param_specs(params, mesh, cfg))

    opt_kw = {}
    if optimizer == "fednl":
        opt_kw = dict(k_per_block=curvature_k, mesh=mesh,
                      curvature="hutchinson" if hvp else "fisher")
    opt = make_optimizer(optimizer, lr, **opt_kw)
    # second-order curvature state (and first-order moments) carry the
    # params' own shardings — state scales with the shards, not one
    # chip's HBM.
    state_shape = jax.eval_shape(opt.init, params)
    opt_state = jax.jit(opt.init, out_shardings=opt_state_shardings(
        state_shape, params, mesh, cfg))(params)

    # every shard on the mesh data axis plays one FedNL silo for the
    # curvature observations
    n_silos = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
    if batch % n_silos:
        raise ValueError(f"batch {batch} does not divide the mesh data "
                         f"axis of {n_silos} devices")
    step_fn = jax.jit(make_train_step(
        model, opt, microbatches=microbatches, refresh_every=refresh_every,
        n_silos=n_silos, hvp=hvp, probe_seed=seed))

    # host-side wire accounting: what one curvature refresh ships
    # (per-silo Block-TopK diff payloads, every param tensor)
    curv_bits = (opt.uplink_bits(params, n_silos=n_silos)
                 if opt.uplink_bits is not None else 0)
    if curv_bits:
        print(f"curvature uplink: {curv_bits} bits/refresh "
              f"({n_silos} silo(s), refresh_every={refresh_every})",
              flush=True)

    t_text = seq - (cfg.vision_tokens if cfg.family == "vlm" else 0)
    pipe = TokenPipeline(vocab_size=cfg.vocab, seq_len=t_text,
                         global_batch=batch, seed=seed)
    history = []
    refreshes = 0
    unread = []  # (loss, curv_refreshed) still on the device
    t0 = time.time()
    for i in range(steps):
        with jax.profiler.TraceAnnotation("train.batch"):
            b = add_modality_inputs(pipe.batch(i), cfg, i)
        with jax.profiler.TraceAnnotation("train.dispatch"):
            params, opt_state, metrics = step_fn(params, opt_state, b)
        unread.append((metrics["loss"], metrics["curv_refreshed"]))
        if i % log_every == 0 or i == steps - 1:
            # the host waits for the device only here, so steps between
            # log lines queue back to back
            for loss, refreshed in jax.device_get(unread):
                history.append(float(loss))
                refreshes += int(refreshed)
            unread.clear()
            extra = (f" curv_bits {curv_bits * refreshes}"
                     if curv_bits else "")
            print(f"step {i:5d} loss {history[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}"
                  f"{extra} ({(time.time()-t0):.1f}s)", flush=True)
    if ckpt:
        save_ckpt(ckpt, {"params": params}, step=steps)
        print(f"checkpoint -> {ckpt}")
    return TrainRun(history, refreshes, curv_bits, step_fn, params,
                    opt_state)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "fednl"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--refresh-every", type=int, default=4,
                    help="curvature refresh interval (fednl): observe + "
                         "learn H every N steps, precondition every step")
    ap.add_argument("--curvature-k", type=int, default=2048,
                    help="Block-TopK k per 128x128 block for the "
                         "curvature-diff uplink (fednl)")
    ap.add_argument("--hvp", action="store_true",
                    help="Hutchinson z*(Hz) curvature probes (one "
                         "jvp-of-grad per silo per refresh) instead of "
                         "the empirical-Fisher g^2 diagonal")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    train(args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
          seq=args.seq, lr=args.lr, optimizer=args.optimizer,
          microbatches=args.microbatches, ckpt=args.ckpt,
          refresh_every=args.refresh_every, curvature_k=args.curvature_k,
          hvp=args.hvp)


if __name__ == "__main__":
    main()
