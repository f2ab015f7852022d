import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
# The two lines above MUST run before any jax import (device count locks
# at first init). Everything else follows.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) pair on
the production mesh, print memory/cost analysis, and emit roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]

Exit code is non-zero if any requested pair fails to compile.
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, collective_bytes, model_flops
from repro.launch.shapes import (
    SHAPES,
    decode_input_specs,
    skip_reason,
    token_batch_specs,
)
from repro.launch.sharding import (
    batch_specs,
    cache_specs,
    make_activation_sharder,
    make_layer_param_constrainer,
    tree_param_specs,
)
from repro.launch.steps import (
    make_optimizer,
    make_prefill,
    make_serve_step,
    make_train_step,
)
from repro.models import build_model
from repro.models.common import set_activation_sharder


def _opt_state_shardings(opt_shape, param_shards, mesh):
    """Moment trees mirror the param tree, so the param sharding tree is a
    valid pytree (prefix) for them; scalars replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    out = {}
    for k, v in opt_shape._asdict().items():
        if k == "step":
            out[k] = rep
        elif isinstance(v, tuple) and v == ():
            out[k] = ()
        else:
            out[k] = param_shards
    return type(opt_shape)(**out)


def _lower_one(cfg, shape, mesh, optimizer: str, unroll: bool,
               donate: bool, microbatches: int = 16):
    """Build model + step for (cfg, shape) and return the lowered artifact."""
    model = build_model(cfg, use_remat=True)
    model.unroll = unroll
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    param_shards = tree_param_specs(params_shape, mesh, cfg)

    if shape.kind == "train":
        opt = make_optimizer(optimizer, 1e-4, moment_dtype=jnp.bfloat16)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        opt_shards = _opt_state_shardings(opt_shape, param_shards, mesh)
        batch = token_batch_specs(cfg, shape)
        b_shards = batch_specs(batch, mesh)
        step = make_train_step(model, opt, microbatches=microbatches)
        jitted = jax.jit(
            step,
            in_shardings=(param_shards, opt_shards, b_shards),
            out_shardings=(param_shards, opt_shards, None),
            donate_argnums=(0, 1) if donate else (),
        )
        return jitted.lower(params_shape, opt_shape, batch)
    if shape.kind == "prefill":
        batch = token_batch_specs(cfg, shape)
        b_shards = batch_specs(batch, mesh)
        fn = make_prefill(model)
        jitted = jax.jit(fn, in_shardings=(param_shards, b_shards))
        return jitted.lower(params_shape, batch)
    # decode
    specs = decode_input_specs(cfg, shape, model)
    c_shards = cache_specs(specs["cache"], mesh, cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P

    tok_shard = batch_specs({"t": specs["token"]}, mesh)["t"]
    pos_shard = NamedSharding(mesh, P())
    fn = make_serve_step(model)
    jitted = jax.jit(
        fn,
        in_shardings=(param_shards, c_shards, tok_shard, pos_shard),
        out_shardings=(None, c_shards),
        donate_argnums=(1,) if donate else (),
    )
    return jitted.lower(params_shape, specs["cache"], specs["token"],
                        specs["pos"])


def _compiled_costs(compiled, chips):
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def _probe_costs(cfg, shape, mesh, optimizer: str, model):
    """Exact per-device costs. Scans hide trip counts from cost_analysis
    (loop bodies are counted once), so we either unroll everything (small
    stacks) or extrapolate from 1- and 2-segment unrolled probes:
        total = probe1 + (n_segments - 1) * (probe2 - probe1).
    """
    import dataclasses as dc

    chips = mesh.devices.size
    segs = model.n_segments
    # probes run microbatches=1: a k-microbatch scan hides (k-1)/k of the
    # step's work from cost_analysis, while one full-batch pass does the
    # same total arithmetic as the k accumulated passes.
    if cfg.n_layers <= 8:
        lowered = _lower_one(cfg, shape, mesh, optimizer, unroll=True,
                             donate=False, microbatches=1)
        return _compiled_costs(lowered.compile(), chips), "unrolled"

    enc_per = (cfg.enc_layers // segs) if cfg.enc_layers else 0
    cfg1 = dc.replace(cfg, n_layers=model.period, enc_layers=enc_per)
    cfg2 = dc.replace(cfg, n_layers=2 * model.period, enc_layers=2 * enc_per)
    c1 = _compiled_costs(
        _lower_one(cfg1, shape, mesh, optimizer, unroll=True, donate=False,
                   microbatches=1).compile(), chips)
    c2 = _compiled_costs(
        _lower_one(cfg2, shape, mesh, optimizer, unroll=True, donate=False,
                   microbatches=1).compile(), chips)

    def extrap(a, b):
        return a + (segs - 1) * (b - a)

    out = {
        "flops": extrap(c1["flops"], c2["flops"]),
        "bytes": extrap(c1["bytes"], c2["bytes"]),
        "coll": {k: max(0, int(extrap(c1["coll"][k], c2["coll"][k])))
                 for k in c1["coll"]},
    }
    return out, "probe-extrapolated"


def dryrun_pair(arch: str, shape_name: str, multi_pod: bool = False,
                optimizer: str = "adamw", verbose: bool = True,
                donate: bool = True, with_probes: bool = True,
                mesh=None, smoke: bool = False,
                microbatches: int = 16) -> dict:
    """Lower+compile one pair; returns a result row (raises on failure).
    ``mesh``/``smoke`` let tests run the same path on a tiny host mesh
    with the reduced configs."""
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": reason}

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    set_activation_sharder(make_activation_sharder(mesh),
                           make_layer_param_constrainer(mesh, cfg))
    model = build_model(cfg, use_remat=True)

    t0 = time.time()
    lowered = _lower_one(cfg, shape, mesh, optimizer, unroll=False,
                         donate=donate, microbatches=microbatches)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    if with_probes:
        costs, cost_mode = _probe_costs(cfg, shape, mesh, optimizer, model)
    else:
        costs, cost_mode = _compiled_costs(compiled, chips), "scan-body-once"

    flops = costs["flops"]
    bytes_hbm = costs["bytes"]
    coll = costs["coll"]
    rl = Roofline(flops=flops, bytes_hbm=bytes_hbm, coll=coll, chips=chips,
                  model_flops=model_flops(cfg, shape, shape.kind))

    def _mem_field(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    row = {
        "arch": arch, "shape": shape_name, "mesh": "2x16x16" if multi_pod
        else "16x16", "status": "ok", "kind": shape.kind,
        "optimizer": optimizer if shape.kind == "train" else None,
        "cost_mode": cost_mode,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "argument_bytes": _mem_field("argument_size_in_bytes"),
        "output_bytes": _mem_field("output_size_in_bytes"),
        "temp_bytes": _mem_field("temp_size_in_bytes"),
        "peak_bytes_per_device": (
            (_mem_field("argument_size_in_bytes") or 0)
            + (_mem_field("temp_size_in_bytes") or 0)),
        **rl.row(),
    }
    if verbose:
        print(f"== {arch} x {shape_name} on {row['mesh']} "
              f"({shape.kind}) ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: args={row['argument_bytes']} "
              f"temp={row['temp_bytes']} out={row['output_bytes']}")
        print(f"  cost_analysis: flops={flops:.3e} bytes={bytes_hbm:.3e}")
        print(f"  collectives: { {k: v for k, v in coll.items() if v} }")
        print(f"  roofline: compute={rl.t_compute:.4f}s memory={rl.t_memory:.4f}s "
              f"collective={rl.t_collective:.4f}s -> {rl.bottleneck}-bound; "
              f"useful_ratio={rl.useful_ratio:.3f}")
        sys.stdout.flush()
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "fednl"])
    ap.add_argument("--out", default=None, help="append JSONL rows here")
    ap.add_argument("--microbatches", type=int, default=16)
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the cost probes (compile-proof only; the "
                         "roofline table is single-pod, so the multi-pod "
                         "pass can run without them)")
    args = ap.parse_args(argv)

    pairs = []
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    failures = 0
    for arch, shape_name, mp in pairs:
        try:
            row = dryrun_pair(arch, shape_name, multi_pod=mp,
                              optimizer=args.optimizer,
                              with_probes=not args.no_probes,
                              microbatches=args.microbatches)
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            row = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "fail", "error": repr(e)[:500]}
            failures += 1
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
