"""CLI front-end for the experiment engine: run a method x level x seed
grid on a named problem from the command line, optionally sharded over
the host mesh, and print tidy records (or a per-cell summary) as CSV —
records carry the analytic ``bits``, the payload-measured
``bits_measured``, the entropy-index-coded ``bits_entropy``, and the
traffic-model ``seconds_per_round`` (``--link`` preset) columns side
by side.

    PYTHONPATH=src python -m repro.launch.sweep \
        --problem a1a --method fednl --compressor rankr --levels 1,2,4 \
        --seeds 0,1,2 --rounds 40 --option 1 --mu 1e-3 --target 1e-12

    # whole-grid sharded execution over the data axis
    PYTHONPATH=src python -m repro.launch.sweep --problem a1a \
        --method fednl --compressor rankr --levels 1 --sharded
"""

from __future__ import annotations

import argparse
import sys


def _parse_list(s: str, cast=float):
    return [cast(x) for x in s.split(",") if x != ""]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--problem", default="a1a",
                    help="a1a | phishing | ... | synthetic:ALPHA:BETA")
    ap.add_argument("--method", default="fednl")
    ap.add_argument("--compressor", default="rankr")
    ap.add_argument("--levels", default="1")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--alpha", type=float, default=None,
                    help="Hessian learning rate (omit for the method default;"
                         " not every method takes one)")
    ap.add_argument("--option", type=int, default=None)
    ap.add_argument("--mu", type=float, default=0.0)
    ap.add_argument("--tau", type=int, default=None)
    ap.add_argument("--lam", type=float, default=1e-3)
    ap.add_argument("--x64", action=argparse.BooleanOptionalAction,
                    default=True, help="run in float64 (--no-x64 for f32)")
    ap.add_argument("--target", type=float, default=None,
                    help="emit per-cell summary with bits/rounds to target")
    ap.add_argument("--records", action="store_true",
                    help="emit full (cell, seed, round) tidy records")
    ap.add_argument("--sharded", action="store_true",
                    help="run through the shard_map path over the host mesh")
    ap.add_argument("--link", default="wan",
                    help="traffic-model link preset for the "
                         "seconds_per_round column (datacenter | wan | "
                         "fl-cross-device | none)")
    args = ap.parse_args(argv)

    import jax

    from ..compile_cache import use_compile_cache

    use_compile_cache()
    if args.x64:
        jax.config.update("jax_enable_x64", True)

    from ..data.problems import make_problem
    from ..engine import ExperimentSpec, Sweep

    params = {}
    if args.alpha is not None:
        params["alpha"] = args.alpha
    if args.option is not None:
        params["option"] = args.option
    if args.mu:
        params["mu"] = args.mu
    if args.tau is not None:
        params["tau"] = args.tau

    prob = make_problem(args.problem, args.lam, seed=0)
    seeds = tuple(int(s) for s in _parse_list(args.seeds, int))
    specs = [
        ExperimentSpec(args.method, args.compressor, lvl, params=params,
                       seeds=seeds, num_rounds=args.rounds)
        for lvl in _parse_list(args.levels)
    ]
    mesh = None
    if args.sharded:
        from .mesh import make_mesh

        mesh = make_mesh((jax.device_count(),), ("data",))

    x0 = prob["xstar"] + 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), (prob["d"],))
    link = None if args.link in ("none", "") else args.link
    res = Sweep(specs, mesh=mesh, link=link).run(prob, x0=x0)

    rows = (res.records() if args.records
            else res.summary(target=args.target))
    if not rows:
        return 0
    cols = list(rows[0])
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))
    return 0


if __name__ == "__main__":
    sys.exit(main())
