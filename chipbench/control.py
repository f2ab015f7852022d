"""Readings from which the limits of ``correct`` are set, taken on the
chip at a cell's own size, many seeds in one process:

    python chipbench/control.py --workload fednl-w8a.topk3000 \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 3

For each seed of ``--seeds`` it prints the numbers that a sound run of
the program gives (the lower reading of each limit); for each seed of
``--control-seeds`` the numbers of the control, the reference in the
program's place one precision step below the configured one (the upper
reading), and for a training cell also those of planted faults, each in
the reference put in the program's place: half of the batch left out,
and on several chips the server hearing only the first silo's curvature
(the exchange between chips left out). One JSON line per reading. The
benchmark's own runs never run this.

Federated cells: the control is the cell with its silos' oracles and its
server's payload values taken in three-pass bfloat16
(``reference/logreg.control_oracles``, ``lower_values``), over a short
window at the cell's own load; the faults, for each seed of
``--control-seeds``, break the server aggregate alone (its payload values
in one bfloat16 pass; one pair in 16 lost). Training cells: the program's first three
steps against the float32 reference, and the reference computed with
float8 (e4m3) matrix products against the same.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


FEDERATED_KINDS = (("program", {}), ("control", {"control": True}),
                   ("fault:aggregate_bf16", {"fault": "aggregate_bf16"}),
                   ("fault:aggregate_drop", {"fault": "aggregate_drop"}))


def federated(cell, seeds, control_seeds, seconds, require_tpu=True):
    for kind, kw in FEDERATED_KINDS:
        for seed in seeds if kind == "program" else control_seeds:
            r = run.run_cell(cell, seed, seconds, False, require_tpu,
                             driver_kw=kw)
            yield {"kind": kind, "seed": seed, "numbers": r["numbers"],
                   "metrics": {k: v["value"] for k, v in r["metrics"].items()}}


def training(cell, seeds, control_seeds, require_tpu=True):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train_steps import FIRST_STEPS, compare
    from chipbench.reference import qwen2 as ref

    devs, _ = run.devices_for(int(cell["chips"]), require_tpu)
    for seed in sorted(set(seeds) | set(control_seeds)):
        driver = run.make_driver(cell, seed, devices=devs)
        driver.setup()
        got = driver.first
        driver.release()
        params = driver.make_weights(driver.wkey)
        batches = [driver.make_batch(driver.tkey, jnp.int32(i))
                   for i in range(FIRST_STEPS)]
        cfg, mix = driver.cfg, driver.mix
        silos = driver.silos
        want = ref.train(params, batches, cfg, mix, silos=silos)
        if seed in seeds:
            yield {"kind": "program", "seed": seed,
                   "numbers": compare(got, want)}
        if seed in control_seeds:
            fp8 = ref.train(params, batches, cfg, mix, quant="fp8",
                            silos=silos)
            yield {"kind": "control", "seed": seed,
                   "numbers": compare(fp8, want)}
            half = [jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
                    for b in batches]
            yield {"kind": "fault:half_batch", "seed": seed,
                   "numbers": compare(ref.train(params, half, cfg, mix,
                                                silos=silos), want)}
            if silos > 1:
                alone = ref.train(params, batches, cfg, mix, silos=silos,
                                  exchange=False)
                yield {"kind": "fault:exchange_left_out", "seed": seed,
                       "numbers": compare(alone, want)}
        del params, batches, driver
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    cell = run.find_cell(args.workload)
    run.use_compile_cache()
    if cell["traffic_data"]["driver"] == "train_steps":
        readings = training(cell, seeds, control_seeds)
    else:
        readings = federated(cell, seeds, control_seeds, args.seconds)
    for r in readings:
        print(json.dumps(dict(workload=args.workload, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
