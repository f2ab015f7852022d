"""Benchmark harness — one function per paper figure/table.

FedNL-family cells are declarative ``ExperimentSpec`` grids executed by
``repro.engine.Sweep`` (one vmapped+scanned jitted program per cell);
first-order and inexact-Newton baselines keep their bespoke drivers.
Prints ``name,us_per_call,derived`` CSV to stdout (derived = the claim
check for that artifact) and writes full curves to benchmarks/out/*.csv
with a ``us_per_round`` column per cell.

  fig2_local        FedNL & N0 vs GD/DIANA/ADIANA/DINGO, bits to 1e-6
  fig2_global       FedNL-LS/N0-LS/FedNL-CR vs first-order, from far
  fig2_nl1          FedNL (Rank-1/Top-K/PowerSGD) vs NL1
  fig3_compression  Rank-R / Top-K / PowerSGD level sweep
  fig4_options      Option 1 vs Option 2
  fig6_update_rules alpha rules (Top-K a=1, a=1-sqrt(1-d), Rand-K 1/(w+1))
  fig7_bc           FedNL-BC compression-level sweep + vs DORE
  fig9_pp           FedNL-PP tau sweep + vs Artemis
  fig14_heterogeneity  synthetic(alpha, beta) sweep
  table2_rates      Thm 3.6 / NS / N0 rate checks
  codec_roundtrip   bitstream codec encode/decode per payload family:
                    bytes vs entropy estimate, fp32 bit-exact pin
  autotune          kernel autotuner: measured winners vs untuned defaults
                    (exact numerics + not-slower pins, cache JSON
                    round-trip; honors $REPRO_TUNING_CACHE)
  server_aggregate  payload-space aggregate vs decompress-then-mean (n x d,
                    incl. the tiled-accumulator large-d sweep)
  precond_step      fednl_precond payload-op path vs dense-mask path
  engine_vmap       multi-seed vmap speedup vs serial per-seed loops
  roofline          (arch x shape) table from the dry-run JSONL

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

# The paper's separation between Newton-type and first-order methods shows
# at deep accuracy (superlinear regime); run the convex benchmarks in f64.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from benchmarks.common import bits_to_accuracy, gaps, problem, write_csv
from repro.compile_cache import use_compile_cache
from repro.core import FedNL, RandK, RandomDithering, RankR, TopK
from repro.core.baselines import (
    NL1,
    Adiana,
    Artemis,
    Diana,
    Dingo,
    Dore,
    gd_ls_run,
    gd_run,
)
from repro.core.compressors import FLOAT_BITS
from repro.engine import (
    ExperimentSpec,
    Sweep,
    bits_to_accuracy as bits_at,
    rounds_to_accuracy as rounds_at,
)

RESULTS = []
TARGET = 1e-12


def report(name, us_per_call, derived):
    RESULTS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")
    sys.stdout.flush()


def _near_x0(prob, scale=0.05, seed=1):
    return prob["xstar"] + scale * jax.random.normal(
        jax.random.PRNGKey(seed), (prob["d"],))


def _run(alg_run, *args, **kw):
    t0 = time.time()
    out = alg_run(*args, **kw)
    jax.block_until_ready(out[1])
    return out, (time.time() - t0) * 1e6


def _sweep(prob, specs, x0):
    """Run an ExperimentSpec grid; returns (SweepResult, total wall us)."""
    res = Sweep(specs).run(prob, x0=x0)
    us = sum(c.us_per_round * c.spec.num_rounds for c in res.cells)
    return res, us


# ---------------------------------------------------------------------------


def fig2_local(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    rounds = 60 if fast else 150

    res, us = _sweep(prob, [
        ExperimentSpec("fednl", "rankr", 1, params=dict(option=1, mu=1e-3),
                       num_rounds=25, name="FedNL-Rank1"),
        ExperimentSpec("n0", num_rounds=40, name="N0"),
    ], x0)
    cf, c0 = res.cells
    b_fednl = bits_at(cf.gaps[0], cf.bits, TARGET)
    b_n0 = bits_at(c0.gaps[0], c0.bits, TARGET)
    rows = [("FedNL-Rank1", float(b), float(g))
            for b, g in zip(cf.bits, cf.gaps[0])]

    (_, xs_gd), _ = _run(gd_run, x0, prob["grad"], 1.0 / prob["consts"]["L"],
                         rounds * 40)
    b_gd = bits_to_accuracy(gaps(prob, xs_gd), d * FLOAT_BITS, TARGET)

    rd = RandomDithering(s=int(d ** 0.5))
    om = rd.spec((d,)).omega
    diana = Diana(prob["grad"], rd, prob["consts"]["L"], n, om)
    (_, xs_di), _ = _run(diana.run, x0, n, rounds * 10)
    b_diana = bits_to_accuracy(gaps(prob, xs_di), diana.bits_per_round(d),
                               TARGET)

    adiana = Adiana(prob["grad"], rd, prob["consts"]["L"], 1e-3, n, om)
    (_, xs_ad), _ = _run(adiana.run, x0, n, rounds * 10)
    b_adiana = bits_to_accuracy(gaps(prob, xs_ad), adiana.bits_per_round(d),
                                TARGET)

    dingo = Dingo(prob["val"], prob["grad"], prob["hess"])
    (_, xs_dg), _ = _run(dingo.run, x0, 40)
    b_dingo = bits_to_accuracy(gaps(prob, xs_dg), dingo.bits_per_round(d),
                               TARGET)

    write_csv("fig2_local", ["method", "bits", "gap"], rows)
    best_fo = min(b_gd, b_diana, b_adiana)
    claim = (b_fednl < best_fo) and (b_n0 < best_fo) and (b_fednl < b_dingo)
    report("fig2_local", us,
           f"bits(FedNL)={b_fednl:.2e}|N0={b_n0:.2e}|GD={b_gd:.2e}|"
           f"DIANA={b_diana:.2e}|ADIANA={b_adiana:.2e}|DINGO={b_dingo:.2e}|"
           f"claim_fednl_beats_all={claim}")


def fig2_global(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    x0 = jnp.ones(d) * 2.0
    rounds = 40 if fast else 80

    res, us = _sweep(prob, [
        ExperimentSpec("fednl-ls", "rankr", 1, params=dict(mu=1e-3),
                       num_rounds=rounds, name="FedNL-LS"),
        ExperimentSpec("n0-ls", params=dict(mu=1e-3), num_rounds=rounds,
                       name="N0-LS"),
        ExperimentSpec("fednl-cr", "rankr", 1,
                       params=dict(l_star=prob["consts"]["L_star"]),
                       num_rounds=rounds * 4, name="FedNL-CR"),
    ], x0)
    b_ls = bits_at(res.cell("FedNL-LS").gaps[0], res.cell("FedNL-LS").bits,
                   TARGET)
    b_n0ls = bits_at(res.cell("N0-LS").gaps[0], res.cell("N0-LS").bits,
                     TARGET)
    b_cr = bits_at(res.cell("FedNL-CR").gaps[0], res.cell("FedNL-CR").bits,
                   TARGET)

    (_, xs_gd), _ = _run(gd_run, x0, prob["grad"], 1.0 / prob["consts"]["L"],
                         rounds * 20)
    b_gd = bits_to_accuracy(gaps(prob, xs_gd), d * FLOAT_BITS, TARGET)
    (_, xs_gls), _ = _run(gd_ls_run, x0, prob["val"], prob["grad"], rounds * 20)
    b_gdls = bits_to_accuracy(gaps(prob, xs_gls), d * FLOAT_BITS, TARGET)

    rd = RandomDithering(s=int(d ** 0.5))
    om = rd.spec((d,)).omega
    diana = Diana(prob["grad"], rd, prob["consts"]["L"], n, om)
    (_, xs_di), _ = _run(diana.run, x0, n, rounds * 20)
    b_diana = bits_to_accuracy(gaps(prob, xs_di), diana.bits_per_round(d),
                               TARGET)

    claim = (b_ls < min(b_gd, b_gdls, b_diana)) and \
        (b_n0ls < min(b_gd, b_gdls)) and (b_cr < min(b_gd, b_gdls))
    report("fig2_global", us,
           f"bits(FedNL-LS)={b_ls:.2e}|N0-LS={b_n0ls:.2e}|FedNL-CR={b_cr:.2e}|"
           f"GD={b_gd:.2e}|GD-LS={b_gdls:.2e}|DIANA={b_diana:.2e}|"
           f"claim_ls_beats_first_order={claim}")


def fig2_nl1(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    # start far enough that the Hessian-learning transient matters (NL1
    # must re-learn m coefficients per silo at K=1/round)
    x0 = _near_x0(prob, scale=0.3)
    res, us = _sweep(prob, [
        ExperimentSpec("fednl", "rankr", 1, params=dict(option=1, mu=1e-3),
                       num_rounds=40, name="Rank1"),
        ExperimentSpec("fednl", "topk", d, params=dict(option=1, mu=1e-3),
                       num_rounds=40, name=f"Top{d}"),
        ExperimentSpec("fednl", "powersgd", 1, params=dict(option=1, mu=1e-3),
                       num_rounds=40, name="PowerSGD1"),
    ], x0)
    bits = {c.spec.label: bits_at(c.gaps[0], c.bits, TARGET)
            for c in res.cells}
    nl1 = NL1(prob["data"], k=1)
    (_, xs), _ = _run(nl1.run, x0, 400 if not fast else 150)
    bits["NL1-Rand1"] = bits_to_accuracy(gaps(prob, xs),
                                         nl1.bits_per_round(d), TARGET,
                                         d * (d + 1) // 2 * FLOAT_BITS)
    fednl_best = min(v for k, v in bits.items() if k != "NL1-Rand1")
    claim = (fednl_best < bits["NL1-Rand1"]
             and bits["Rank1"] < bits["NL1-Rand1"])
    report("fig2_nl1", us,
           "|".join(f"{k}={v:.2e}" for k, v in bits.items())
           + f"|claim_fednl_beats_nl1={claim}")


def fig3_compression(fast=False):
    prob = problem("phishing")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    grid = [("rankr", [1, 2, 4]), ("topk", [d, 4 * d, 16 * d]),
            ("powersgd", [1, 2, 4])]
    specs = [ExperimentSpec("fednl", fam, lvl,
                            params=dict(option=1, mu=1e-3), num_rounds=40)
             for fam, levels in grid for lvl in levels]
    res, us = _sweep(prob, specs, x0)
    rows, verdicts = [], []
    by = {(c.spec.compressor, c.spec.level): c for c in res.cells}
    for fam, levels in grid:
        bl = {lvl: bits_at(by[(fam, lvl)].gaps[0], by[(fam, lvl)].bits,
                           TARGET) for lvl in levels}
        rows += [(fam, lvl, bl[lvl], by[(fam, lvl)].us_per_round)
                 for lvl in levels]
        verdicts.append(bl[levels[0]] <= bl[levels[-1]])
    write_csv("fig3_compression", ["family", "level", "bits", "us_per_round"],
              rows)
    report("fig3_compression", us,
           f"rows={len(rows)}|claim_smaller_level_better={all(verdicts)}")


def fig4_options(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    res, us = _sweep(prob, [
        ExperimentSpec("fednl", "rankr", 1, params=dict(option=opt, mu=1e-3),
                       num_rounds=120, name=f"opt{opt}")
        for opt in (1, 2)
    ], x0)
    out = {opt: bits_at(res.cell(f"opt{opt}").gaps[0],
                        res.cell(f"opt{opt}").bits, TARGET)
           for opt in (1, 2)}
    report("fig4_options", us,
           f"opt1={out[1]:.2e}|opt2={out[2]:.2e}|"
           f"claim_opt1_not_worse={out[1] <= out[2] * 1.01}")


def fig6_update_rules(fast=False):
    prob = problem("phishing")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob, scale=0.3)
    k = d // 2
    delta = TopK(k=k).spec((d, d)).delta
    omega = RandK(k=k).spec((d, d)).omega
    res, us = _sweep(prob, [
        ExperimentSpec("fednl", "topk", k,
                       params=dict(alpha=1.0, option=1, mu=1e-3),
                       num_rounds=150, name="topk_a1"),
        ExperimentSpec("fednl", "topk", k,
                       params=dict(alpha=1.0 - (1.0 - delta) ** 0.5,
                                   option=1, mu=1e-3),
                       num_rounds=150, name="topk_contract"),
        ExperimentSpec("fednl", "randk", k,
                       params=dict(alpha=1.0 / (1.0 + omega),
                                   option=1, mu=1e-3),
                       num_rounds=150, name="randk_unbiased"),
    ], x0)
    rounds_out = {c.spec.label: rounds_at(c.gaps[0], TARGET)
                  for c in res.cells}
    ok = {k_: (v if v >= 0 else 10**9) for k_, v in rounds_out.items()}
    claim = ok["topk_a1"] <= min(ok.values())
    report("fig6_update_rules", us,
           "|".join(f"{k_}={v}" for k_, v in rounds_out.items())
           + f"|claim_topk_a1_best={claim}")


def fig7_bc(fast=False):
    prob = problem("phishing")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    ps = [0.9, 0.6] if fast else [1.0, 0.9, 0.6, 0.5]
    res, us = _sweep(prob, [
        ExperimentSpec("fednl-bc", "topk", max(1, int(p * d)),
                       params=dict(model_compressor=("topk",
                                                     max(1, int(p * d))),
                                   p=p, option=1, mu=1e-3),
                       num_rounds=600, name=f"p={p}")
        for p in ps
    ], x0)
    bits = {c.spec.label: bits_at(c.gaps[0], c.bits, TARGET)
            for c in res.cells}
    rd = RandomDithering(s=int(d ** 0.5))
    om = rd.spec((d,)).omega
    dore = Dore(prob["grad"], rd, rd, prob["consts"]["L"], n, om, om)
    (_, xs), _ = _run(dore.run, x0, n, 3000 if not fast else 800)
    up, down = dore.bits_per_round(d)
    bits["DORE"] = bits_to_accuracy(gaps(prob, xs), up + down, TARGET)
    best_bc = min(v for k, v in bits.items() if k != "DORE")
    report("fig7_bc", us,
           "|".join(f"{k}={v:.2e}" for k, v in bits.items())
           + f"|claim_bc_beats_dore={best_bc < bits['DORE']}")


def fig9_pp(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    taus = [max(1, int(0.2 * n)), max(1, int(0.5 * n)), n]
    res, us = _sweep(prob, [
        ExperimentSpec("fednl-pp", "rankr", 1, params=dict(tau=tau),
                       num_rounds=200, name=f"tau={tau}")
        for tau in taus
    ], x0)
    rounds_out = {tau: rounds_at(res.cell(f"tau={tau}").gaps[0], TARGET)
                  for tau in taus}
    mono = rounds_out[taus[0]] >= rounds_out[taus[-1]] >= 0

    rd = RandomDithering(s=int(d ** 0.5))
    om = rd.spec((d,)).omega
    art = Artemis(prob["grad"], rd, prob["consts"]["L"], n, om,
                  tau=max(1, int(0.5 * n)))
    (_, xs), _ = _run(art.run, x0, n, 3000 if not fast else 800)
    pp_cell = res.cell(f"tau={max(1, int(0.5 * n))}")
    b_art = bits_to_accuracy(gaps(prob, xs), art.bits_per_round(d), TARGET)
    b_pp = bits_at(pp_cell.gaps[0], pp_cell.bits, TARGET)
    report("fig9_pp", us,
           "|".join(f"tau={k}:rounds={v}" for k, v in rounds_out.items())
           + f"|mono_in_tau={mono}|bits_pp={b_pp:.2e}|bits_artemis={b_art:.2e}"
           f"|claim_pp_beats_artemis={b_pp < b_art}")


def fig14_heterogeneity(fast=False):
    us = 0.0
    out = {}
    for tag, ab in [("iid", (0.0, 0.0)), ("mid", (0.5, 0.5)),
                    ("high", (1.0, 1.0))]:
        prob = problem(f"synthetic:{ab[0]}:{ab[1]}")
        d, n = prob["d"], prob["n"]
        x0 = _near_x0(prob)
        res, u = _sweep(prob, [
            ExperimentSpec("fednl", "rankr", 1, params=dict(option=2),
                           num_rounds=30, name="FedNL"),
        ], x0)
        us += u
        cell = res.cells[0]
        b_f = bits_at(cell.gaps[0], cell.bits, TARGET)
        (_, xs_gd), _ = _run(gd_run, x0, prob["grad"],
                             1.0 / prob["consts"]["L"], 1500 if fast else 4000)
        b_g = bits_to_accuracy(gaps(prob, xs_gd), d * FLOAT_BITS, TARGET)
        out[tag] = (b_f, b_g)
    # FedNL stays put; GD degrades (or at least never closes the gap)
    claim = all(v[0] < v[1] for v in out.values())
    report("fig14_heterogeneity", us,
           "|".join(f"{k}:fednl={v[0]:.2e},gd={v[1]:.2e}"
                    for k, v in out.items())
           + f"|claim_fednl_wins_all_levels={claim}")


def table2_rates(fast=False):
    prob = problem("a1a")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob, scale=0.02)
    checks = {}
    alg = FedNL(prob["grad"], prob["hess"], RankR(1), option=1, mu=1e-3)
    (_, xs), us = _run(alg.run, x0, n, 16)
    r = np.asarray(jnp.sum((xs - prob["xstar"]) ** 2, axis=-1))
    ks = [k for k in range(1, 12) if r[k] > 1e-14]
    checks["fednl_linear_eq6"] = all(r[k] <= r[0] / 2**k * 8 for k in ks)
    # superlinear: the rate factor is (1-A)^k with A = delta/4; use a
    # high-delta compressor (Top-50% => A = 1/8) so the decay of the
    # per-round ratio is measurable before machine precision.
    alg_s = FedNL(prob["grad"], prob["hess"], TopK(k=d * d // 2), option=1,
                  mu=1e-3)
    x0_s = _near_x0(prob, scale=0.12, seed=5)  # inside the local basin
    (_, xs_s), _ = _run(alg_s.run, x0_s, n, 16)
    rs = np.asarray(jnp.sum((xs_s - prob["xstar"]) ** 2, axis=-1))
    ratios = [rs[k + 1] / rs[k] for k in range(10) if rs[k] > 1e-24]
    checks["fednl_superlinear"] = (len(ratios) >= 3
                                   and ratios[-1] < ratios[0] * 0.5)

    from repro.core.newton import fixed_hessian_run

    hstar = jnp.mean(prob["hess"](prob["xstar"]), axis=0)
    (_, xs_ns), _ = _run(fixed_hessian_run, x0, hstar, prob["grad"], 6)
    rr = np.linalg.norm(np.asarray(xs_ns) - np.asarray(prob["xstar"]), axis=-1)
    c = prob["consts"]["L_star"] / (2 * 1e-3)
    checks["ns_quadratic"] = all(
        rr[k + 1] <= 20 * c * rr[k] ** 2 + 1e-14
        for k in range(3) if rr[k] > 1e-9)

    h0 = jnp.mean(prob["hess"](x0), axis=0)
    (_, xs_n0), _ = _run(fixed_hessian_run, x0, h0, prob["grad"], 12)
    r0 = np.sum((np.asarray(xs_n0) - np.asarray(prob["xstar"])) ** 2, -1)
    checks["n0_linear"] = r0[10] <= r0[0] / 2**10 * 32
    report("table2_rates", us,
           "|".join(f"{k}={v}" for k, v in checks.items())
           + f"|all={all(checks.values())}")


def payload_roundtrip(fast=False):
    """Compressor wire-format micro-benchmark: payload compress /
    decompress round-trip vs INDEPENDENT seed-era dense oracles on a
    (d, d) Hessian diff (so a lossy codec actually fails the claim),
    measured-vs-analytic bits, and the Pallas block_topk payload op vs
    the jnp codec."""
    from repro.core import BlockTopK, payload_bits
    from repro.kernels.block_topk import block_topk, block_topk_payload, \
        payload_to_dense

    d = 128 if fast else 256
    m = jax.random.normal(jax.random.PRNGKey(0), (d, d))
    m = 0.5 * (m + m.T)
    key = jax.random.PRNGKey(1)

    # independent dense oracles (seed-era formulas / the Pallas kernel
    # path), deliberately NOT comp.__call__ — that is the round-trip
    def topk_oracle(x, _):
        flat = x.reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(flat), 4 * d)
        return jnp.zeros_like(flat).at[idx].set(flat[idx]).reshape(x.shape)

    def rankr_oracle(x, _):
        lam, q = jnp.linalg.eigh(0.5 * (x + x.T))
        _, idx = jax.lax.top_k(jnp.abs(lam), 4)
        return (q[:, idx] * lam[idx]) @ q[:, idx].T

    def randk_oracle(x, k):
        flat = x.reshape(-1)
        n = flat.shape[0]
        idx = jax.random.choice(k, n, (4 * d,), replace=False)
        mask = jnp.zeros((n,), x.dtype).at[idx].set(1.0)
        return (flat * mask * (n / (4 * d))).reshape(x.shape)

    cases = {
        "topk": (TopK(k=4 * d), topk_oracle),
        "blocktopk": (BlockTopK(k_per_block=64, block=128),
                      lambda x, _: block_topk(x, k=64, block=128)),
        "rankr": (RankR(4), rankr_oracle),
        "randk": (RandK(k=4 * d), randk_oracle),
    }

    def bench(fn, *args, reps=20):
        out = jax.block_until_ready(fn(*args))  # compile
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return out, (time.time() - t0) * 1e6 / reps

    us_total, fields, ok_bits, ok_ident = 0.0, [], True, True
    for name, (comp, oracle) in cases.items():
        dense_fn = jax.jit(oracle)
        rt_fn = jax.jit(lambda x, k, c=comp: c.decompress(
            c.compress(x, k), x.shape))
        out_dense, us_dense = bench(dense_fn, m, key)
        out_rt, us_rt = bench(rt_fn, m, key)
        ok_ident &= bool(jnp.all(out_dense == out_rt))
        measured = payload_bits(comp, (d, d))
        analytic = comp.bits((d, d))
        ok_bits &= (measured == analytic)
        us_total += us_rt
        # ';' not ',' inside the derived field — bench stdout is 3-col CSV
        fields.append(f"{name}:us_dense={us_dense:.0f};us_rt={us_rt:.0f};"
                      f"bits={measured}")

    # Pallas payload op agrees with the jnp codec's decompressed matrix
    # (kernel body forced — the off-TPU dispatch is the jnp oracle)
    bt = cases["blocktopk"][0]
    vals, idx = block_topk_payload(m, k=64, block=128, use_pallas=True,
                                   interpret=True)
    kernel_dense = payload_to_dense(vals, idx, m.shape, block=128)
    codec_dense = bt.decompress(bt.compress(m), m.shape)
    ok_kernel = bool(jnp.all(kernel_dense == codec_dense))

    report("payload_roundtrip", us_total,
           "|".join(fields)
           + f"|claim_roundtrip_bit_identical={ok_ident}"
           f"|claim_measured_eq_analytic={ok_bits}"
           f"|claim_pallas_payload_matches_codec={ok_kernel}")


def codec_roundtrip(fast=False):
    """Bitstream codec micro-benchmark: for one payload per family,
    host-side encode/decode throughput, actual wire bytes vs the
    ``bits_entropy`` accounting estimate, and the round-trip pins. The
    fp32 ``value_format="raw"`` path must be BIT-exact against
    ``canonical(payload)`` for every family, and the Golomb–Rice index
    coder must land within 1.1x of the entropy estimate for TopK (the
    estimate is a lower-bound-style count; the codec pays real container
    and rice-parameter overhead)."""
    from repro.core import BlockTopK, NaturalSparsification
    from repro.wire import canonical, decode, encode, wire_cost

    d = 32 if fast else 128
    key = jax.random.PRNGKey(1)
    m32 = jax.random.normal(jax.random.PRNGKey(0), (d, d), jnp.float32)

    cases = {
        "topk": TopK(k=4 * d),
        "blocktopk": BlockTopK(k_per_block=8, block=16),
        "rankr": RankR(4),
        "natural": NaturalSparsification(p=0.25),
        "dithering": RandomDithering(s=8),
    }

    def bit_equal(a, b):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        if len(la) != len(lb):
            return False
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            if x.dtype != y.dtype or x.shape != y.shape:
                return False
            if x.tobytes() != y.tobytes():  # bitwise: -0.0 != +0.0 here
                return False
        return True

    def bench_host(fn, *args, reps=10):
        out = fn(*args)  # warm (device->host pull, rice param search)
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args)
        return out, (time.time() - t0) * 1e6 / reps

    rows, fields = [], []
    ok_exact, ok_topk_entropy, us_total = True, True, 0.0
    for name, comp in cases.items():
        payload = jax.block_until_ready(comp.compress(m32, key))
        buf, us_enc = bench_host(encode, payload)
        dec, us_dec = bench_host(decode, buf, (d, d))
        exact = bit_equal(dec, canonical(payload))
        ok_exact &= exact
        rep = wire_cost(comp, (d, d), dtype=jnp.float32)
        if name == "topk":
            ok_topk_entropy = rep.encoded_bits <= 1.1 * rep.entropy_bits
        us_total += us_enc + us_dec
        rows.append((name, len(buf), rep.raw_bits, rep.entropy_bits,
                     us_enc, us_dec))
        fields.append(f"{name}:bytes={len(buf)};entropy={rep.entropy_bits};"
                      f"us_enc={us_enc:.0f};us_dec={us_dec:.0f}")

    write_csv("codec_roundtrip",
              ["family", "encoded_bytes", "raw_bits", "entropy_bits",
               "us_encode", "us_decode"], rows)
    report("codec_roundtrip", us_total,
           "|".join(fields)
           + f"|claim_fp32_roundtrip_exact={ok_exact}"
           f"|claim_topk_encoded_le_1p1x_entropy={ok_topk_entropy}")


def autotune(fast=False):
    """Kernel autotuner micro-benchmark (the CI smoke case): run the
    measured tuner for every tunable op on small operands, then time
    the cache-driven dispatch against the untuned default config.
    Claims: (a) tuned output == default output on tie-free operands
    (exact for the order-free ops, f32-tolerance for the hess_update
    error norm whose tile-sum order depends on block), (b) tuned is not
    slower than default up to timer noise — the default IS a candidate,
    so the measured winner can only match or beat it, (c) the winner
    cache round-trips through its JSON persistence unchanged. With
    $REPRO_TUNING_CACHE set (CI pins benchmarks/tuning_cache_ci.json)
    pinned entries are used as-is and only missing keys are tuned; the
    active cache is saved to benchmarks/out/tuning_cache.json either
    way — copy it over the committed pin to refresh it. The warmed
    cache stays active so the tuned columns in ``server_aggregate`` and
    ``precond_step`` (which run after this bench) dispatch through it."""
    from repro.kernels import tuning
    from repro.kernels.hess_update import hess_update
    from repro.kernels.scatter_accum import scatter_accumulate

    interp = jax.default_backend() != "tpu"
    pin = os.environ.get(tuning.CACHE_ENV)
    pinned = bool(pin and os.path.exists(pin))
    reps = 2 if fast else 3
    rows = []

    # -- scatter_accumulate: the headline op ------------------------------
    # unique flat indices -> every output cell receives at most one
    # contribution, so any (tile, chunk) config must be BITWISE equal
    d, k, n = 256, 128, 2
    vals = jax.random.normal(jax.random.PRNGKey(0), (n, k))
    idx = jax.random.permutation(
        jax.random.PRNGKey(1), d * d)[:n * k].reshape(n, k).astype(jnp.int32)

    def run_scatter():
        return scatter_accumulate(vals, idx, (d, d), use_pallas=True,
                                  interpret=interp)

    # default-config dispatch: pin an EMPTY cache so lookup misses
    tuning.set_cache(tuning.TuningCache())
    out_default = jax.block_until_ready(run_scatter())
    us_default = tuning.time_us(run_scatter, reps=reps)

    # tuned dispatch: restore the ambient cache (the CI pin when set),
    # tune any missing key, and re-dispatch through the plain wrapper
    tuning.set_cache(None)
    cfg_s = tuning.lookup("scatter_accumulate", shape=(d, d), k=k, n=n,
                          dtype=vals.dtype)
    if cfg_s is None:
        cfg_s = tuning.autotune_scatter_accumulate(
            vals, idx, (d, d), use_pallas=True, interpret=interp, reps=reps)
    out_tuned = jax.block_until_ready(run_scatter())
    us_tuned = tuning.time_us(run_scatter, reps=reps)
    err_s = float(jnp.max(jnp.abs(out_tuned - out_default)))
    ok_exact = bool(jnp.array_equal(out_tuned, out_default))
    # 1.25x + 100us absolute slack: these are ~ms interpret kernels and
    # CI runner timers are noisy; the winner was MEASURED no slower
    ok_speed = us_tuned <= 1.25 * us_default + 100.0
    rows.append(("scatter_accumulate", f"d{d};k{k};n{n}",
                 f"tile={cfg_s.tile};chunk={cfg_s.chunk}",
                 us_default, us_tuned, err_s))

    # -- hess_update: non-multiple-of-block shape (edge-tile path) --------
    hm = jax.random.normal(jax.random.PRNGKey(2), (300, 123), jnp.float32)
    dm = jax.random.normal(jax.random.PRNGKey(3), (300, 123), jnp.float32)
    sm = jax.random.normal(jax.random.PRNGKey(4), (300, 123), jnp.float32)
    h_def, e_def = jax.block_until_ready(
        hess_update(hm, dm, sm, 0.5, block=128, interpret=interp))
    us_h_def = tuning.time_us(
        lambda: hess_update(hm, dm, sm, 0.5, block=128, interpret=interp),
        reps=reps)
    cfg_h = tuning.lookup("hess_update", shape=hm.shape, dtype=hm.dtype)
    if cfg_h is None:
        cfg_h = tuning.autotune_hess_update(hm, dm, sm, 0.5,
                                            interpret=interp, reps=reps)
    h_tun, e_tun = jax.block_until_ready(
        hess_update(hm, dm, sm, 0.5, interpret=interp))
    us_h_tun = tuning.time_us(
        lambda: hess_update(hm, dm, sm, 0.5, interpret=interp), reps=reps)
    # H' is elementwise (block-independent -> exact); the fused error
    # norm sums per-tile partials, so its order depends on block
    ok_exact &= bool(jnp.array_equal(h_tun, h_def))
    err_e = abs(float(e_tun) - float(e_def)) / max(float(e_def), 1e-30)
    ok_exact &= err_e <= 1e-6
    ok_speed &= us_h_tun <= 1.25 * us_h_def + 100.0
    rows.append(("hess_update", "d300x123", f"block={cfg_h.block}",
                 us_h_def, us_h_tun, err_e))

    # -- diff_topk_payload: kernel-vs-oracle dispatch ---------------------
    from repro.kernels.block_topk import diff_topk_payload

    a = jax.random.normal(jax.random.PRNGKey(5), (d, d))
    b = jax.random.normal(jax.random.PRNGKey(6), (d, d))
    v_def, i_def, q_def = jax.block_until_ready(
        diff_topk_payload(a, b, k=64, block=128, use_pallas=not interp,
                          interpret=interp))
    cfg_t = tuning.lookup("diff_topk_payload", shape=a.shape, k=64, n=128,
                          dtype=a.dtype)
    if cfg_t is None:
        cfg_t = tuning.autotune_diff_topk_payload(a, b, k=64, block=128,
                                                  interpret=interp,
                                                  reps=reps)
    v_tun, i_tun, q_tun = jax.block_until_ready(
        diff_topk_payload(a, b, k=64, block=128, interpret=interp))
    ok_exact &= bool(jnp.array_equal(v_tun, v_def)
                     and jnp.array_equal(i_tun, i_def))
    ok_exact &= abs(float(q_tun) - float(q_def)) <= 1e-9 * float(q_def)
    rows.append(("diff_topk_payload", f"d{d};k64;b128",
                 f"use_pallas={cfg_t.use_pallas}", 0.0, 0.0, 0.0))

    if not fast:
        # pin-generation keys: the bench-smoke shapes the tuned columns
        # in server_aggregate (f64 TopK payloads at d=2048) and
        # precond_step (f32 block-diff at d=1024) dispatch through
        comp_vals = jax.random.normal(jax.random.PRNGKey(7), (2, 256))
        comp_idx = jax.random.permutation(
            jax.random.PRNGKey(8),
            2048 * 2048)[:512].reshape(2, 256).astype(jnp.int32)
        if tuning.lookup("scatter_accumulate", shape=(2048, 2048), k=256,
                         n=2, dtype=comp_vals.dtype) is None:
            tuning.autotune_scatter_accumulate(
                comp_vals, comp_idx, (2048, 2048), use_pallas=True,
                interpret=interp, max_measured=3, reps=1)
        a32 = jax.random.normal(jax.random.PRNGKey(9), (1024, 1024),
                                jnp.float32)
        b32 = jnp.zeros((1024, 1024), jnp.float32)
        if tuning.lookup("diff_topk_payload", shape=a32.shape, k=2048,
                         n=128, dtype=a32.dtype) is None:
            tuning.autotune_diff_topk_payload(a32, b32, k=2048, block=128,
                                              interpret=interp, reps=1)

    # -- JSON persistence round-trip --------------------------------------
    cache = tuning.get_cache()
    out_dir = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(out_dir, exist_ok=True)
    cache_path = os.path.join(out_dir, "tuning_cache.json")
    cache.save(cache_path)
    ok_roundtrip = tuning.TuningCache.load(cache_path).entries() \
        == cache.entries()

    write_csv("autotune", ["op", "case", "winner", "us_default", "us_tuned",
                           "err"], rows)
    report("autotune", us_tuned,
           f"cache_pinned={pinned}|entries={len(cache.entries())}"
           f"|scatter=tile={cfg_s.tile};chunk={cfg_s.chunk}"
           f"|hess_block={cfg_h.block}|topk_pallas={cfg_t.use_pallas}"
           f"|us_default={us_default:.0f}|us_tuned={us_tuned:.0f}"
           f"|claim_tuned_exact={ok_exact}"
           f"|claim_tuned_not_slower={ok_speed}"
           f"|claim_cache_roundtrip={ok_roundtrip}")


def server_aggregate(fast=False):
    """Payload-space server aggregation micro-benchmark: for an n-silo
    stack of compressed (d, d) Hessian-diff payloads, time the
    structure-aware ``Compressor.aggregate`` fast path (one dense
    accumulator) against the decompress-then-mean fallback (the
    (n, d, d) stack the PR-2 era server built), over an n x d sweep —
    now including LLM-diagonal-scale d in {1024, 2048, 4096}, where the
    Pallas path runs the TILED accumulator kernel (the single-block
    ceiling was d ~ 1500). Claims: fast == fallback to f64 tolerance
    everywhere, the sparse fast paths are >= 2x at n >= 32, d >= 256,
    and the forced tiled kernel reproduces the fallback exactly at
    every large d (d = 2048 in --fast — the CI smoke case). The large-d
    rows also time the AUTOTUNED dispatch (no explicit tile/chunk — the
    active tuning cache decides, the CI pin or the winners the
    ``autotune`` bench just recorded) against the untuned
    (512, 512)/512 default, pinning that the tuned config changes
    nothing numerically. The cross-device rows (n in {1k, 10k} silos,
    payload-space only) pin the streamed silo-slab path bitwise equal
    to the stacked kernel under cohort weights, with the staged slab
    bounded by the VMEM budget regardless of n."""
    from repro.core import BlockTopK, Compressor, RankR, TopK
    from repro.kernels.scatter_accum import scatter_accumulate
    from repro.kernels.tuning import lookup as tuned_lookup

    shapes = [(8, 128), (32, 256)] if fast else [
        (8, 256), (32, 256), (32, 512), (64, 512)]
    # large-d sweep: modest n and k keep the interpret-mode tiled kernel
    # (CPU) affordable; on TPU the same dispatch compiles the real thing
    big = [(2, 2048)] if fast else [(2, 1024), (2, 2048), (2, 4096)]

    def bench(fn, arg, reps=10):
        out = jax.block_until_ready(fn(arg))  # compile
        t0 = time.time()
        for _ in range(reps):
            out = fn(arg)
        jax.block_until_ready(out)
        return out, (time.time() - t0) * 1e6 / reps

    rows, fields = [], []
    ok_match, ok_speed, ok_tiled, ok_tuned = True, True, True, True
    us_total = 0.0
    interp = jax.default_backend() != "tpu"
    for n, d in big:
        comp = TopK(k=256)
        diffs = jax.random.normal(jax.random.PRNGKey(0), (n, d, d))
        payloads = jax.block_until_ready(
            jax.jit(jax.vmap(comp.compress))(diffs))
        fallback = jax.jit(lambda P, c=comp, dd=d: Compressor.aggregate(
            c, P, (dd, dd)))
        fast_fn = jax.jit(lambda P, c=comp, dd=d: c.aggregate(P, (dd, dd)))
        out_slow, us_slow = bench(fallback, payloads)
        out_fast, us_fast = bench(fast_fn, payloads)
        # pin exactness of the TILED Pallas kernel (forced via tile= —
        # at d=1024 the f64 accumulator is exactly the 8 MiB budget, so
        # auto-dispatch would still pick the single-block kernel), and
        # time the forced default config against the autotuned dispatch
        # (tile/chunk omitted: the active tuning cache decides)
        t_def = lambda P, dd=d: scatter_accumulate(
            P.values, P.indices, (dd, dd), use_pallas=True,
            interpret=interp, tile=(512, 512), chunk=512) / n
        t_tuned = lambda P, dd=d: scatter_accumulate(
            P.values, P.indices, (dd, dd), use_pallas=True,
            interpret=interp) / n
        tiled, us_tdef = bench(t_def, payloads, reps=1)
        tuned, us_ttun = bench(t_tuned, payloads, reps=1)
        cfg = tuned_lookup("scatter_accumulate", shape=(d, d),
                           k=payloads.values.shape[1], n=n,
                           dtype=payloads.values.dtype)
        cfg_desc = ("default" if cfg is None
                    else f"tile={cfg.tile};chunk={cfg.chunk}")
        scale = float(jnp.max(jnp.abs(out_slow))) + 1e-30
        err = float(jnp.max(jnp.abs(out_fast - out_slow)))
        err_t = float(jnp.max(jnp.abs(tiled - out_slow)))
        err_tu = float(jnp.max(jnp.abs(tuned - out_slow)))
        speedup = us_slow / max(us_fast, 1e-9)
        ok_match &= err <= 1e-12 * max(1.0, scale)
        ok_tiled &= err_t <= 1e-12 * max(1.0, scale)
        ok_tuned &= err_tu <= 1e-12 * max(1.0, scale)
        us_total += us_fast
        rows.append((n, d, "topk-tiled", us_slow, us_fast, speedup, err,
                     us_tdef, us_ttun, cfg_desc))
        fields.append(f"n{n}d{d}:topk={speedup:.1f}x;tiled_err={err_t:.1e};"
                      f"tuned={cfg_desc}")
    for n, d in shapes:
        diffs = jax.random.normal(jax.random.PRNGKey(0), (n, d, d))
        diffs = 0.5 * (diffs + jnp.swapaxes(diffs, -1, -2))
        keys = jax.random.split(jax.random.PRNGKey(1), n)
        cases = {
            "topk": TopK(k=4 * d),
            "blocktopk": BlockTopK(k_per_block=64, block=128),
            "rankr": RankR(4),
        }
        cell = []
        for name, comp in cases.items():
            payloads = jax.block_until_ready(
                jax.jit(jax.vmap(comp.compress))(diffs, keys))
            # the PR-2 era server: decompress every silo, mean the stack
            fallback = jax.jit(lambda P, c=comp: Compressor.aggregate(
                c, P, (d, d)))
            fast_fn = jax.jit(lambda P, c=comp: c.aggregate(P, (d, d)))
            out_slow, us_slow = bench(fallback, payloads)
            out_fast, us_fast = bench(fast_fn, payloads)
            err = float(jnp.max(jnp.abs(out_fast - out_slow)))
            scale = float(jnp.max(jnp.abs(out_slow))) + 1e-30
            speedup = us_slow / max(us_fast, 1e-9)
            ok_match &= err <= 1e-12 * max(1.0, scale)
            if name in ("topk", "blocktopk") and n >= 32 and d >= 256:
                ok_speed &= speedup >= 2.0
            us_total += us_fast
            rows.append((n, d, name, us_slow, us_fast, speedup, err,
                         "", "", ""))
            cell.append(f"{name}={speedup:.1f}x")
        fields.append(f"n{n}d{d}:" + ";".join(cell))

    # -- cross-device scale: streamed vs stacked over thousands of silos --
    # Synthetic TopK pair streams built DIRECTLY in payload space (an
    # (n, d, d) dense stack at n = 10k would be 20 GiB — the exact
    # thing this path exists to never materialize). Weights come from
    # the cohort layer: K-of-N sampling + deadline/staleness discount
    # on the fl-cross-device link, applied through
    # ``Compressor.aggregate(..., weights=)``. At both sizes the
    # concrete pair stream outgrows the 8 MiB VMEM budget, so the
    # aggregate auto-dispatches the streamed silo-slab path; the
    # comparator runs the same scaled payloads through the stacked
    # kernel (jit keeps ``_should_stream`` off the traced path).
    # Claims: streamed == stacked BITWISE at every n, and the streamed
    # slab never stages more than the VMEM budget of pairs.
    from repro.core.cohort import (
        CohortSpec,
        arrival_times,
        on_time_mask,
        sample_cohort,
        staleness_weights,
    )
    from repro.core.compressors import SparsePayload, scale_payload
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.scatter_accum import silo_chunk_for

    ok_stream, ok_chunk = True, True
    k_pairs, d_acc = 1024, 512
    for n_cd in ([1000] if fast else [1000, 10000]):
        spec = CohortSpec(cohort=max(1, n_cd // 10), population=n_cd,
                          link="fl-cross-device", seed=0)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        payloads = SparsePayload(
            values=jax.random.normal(ks[0], (n_cd, k_pairs)),
            indices=jax.random.randint(ks[1], (n_cd, k_pairs), 0,
                                       d_acc * d_acc, dtype=jnp.int32),
            universe=d_acc * d_acc)
        comp = TopK(k=k_pairs)
        active = sample_cohort(ks[2], n_cd, spec.cohort)
        times = arrival_times(spec, n_cd, bits_per_silo=96 * k_pairs)
        on_time = jnp.asarray(on_time_mask(times, spec.deadline_quantile))
        late = staleness_weights(jnp.ones((n_cd,), jnp.int32),
                                 spec.staleness_beta)
        wts = jnp.where(active, jnp.where(on_time, 1.0, late), 0.0)
        pair = (payloads.values.dtype.itemsize
                + payloads.indices.dtype.itemsize)
        chunk = silo_chunk_for(k_pairs, payloads.values.dtype)
        ok_chunk &= chunk * k_pairs * pair <= VMEM_BUDGET_BYTES
        streamed_fn = lambda P, c=comp, dd=d_acc, w=wts: c.aggregate(
            P, (dd, dd), weights=w)           # eager: streams
        # stacked comparator: the SAME eagerly-scaled pairs through the
        # stacked kernel (jitting the whole aggregate would let XLA
        # reassociate the x*w and /n multiplies and shift last bits)
        scaled = scale_payload(payloads, wts)
        stacked_fn = lambda _, s=scaled, dd=d_acc, m=n_cd: (
            scatter_accumulate(s.values, s.indices, (dd, dd)) / m
        ).reshape(dd, dd)
        out_stacked, us_stacked = bench(stacked_fn, payloads, reps=3)
        out_streamed, us_streamed = bench(streamed_fn, payloads, reps=3)
        exact = bool(jnp.array_equal(out_streamed, out_stacked))
        ok_stream &= exact
        err_s = float(jnp.max(jnp.abs(out_streamed - out_stacked)))
        us_total += us_streamed
        rows.append((n_cd, d_acc, "topk-streamed", us_stacked,
                     us_streamed, us_stacked / max(us_streamed, 1e-9),
                     err_s, "", "", f"silo_chunk={chunk}"))
        fields.append(f"n{n_cd}d{d_acc}:streamed_exact={exact};"
                      f"chunk={chunk}")

    write_csv("server_aggregate",
              ["n", "d", "compressor", "us_decompress_mean", "us_aggregate",
               "speedup", "max_abs_err", "us_tiled_default",
               "us_tiled_tuned", "tuned_cfg"], rows)
    report("server_aggregate", us_total,
           "|".join(fields)
           + f"|claim_fast_matches_fallback={ok_match}"
           f"|claim_sparse_speedup_ge_2x={ok_speed}"
           f"|claim_tiled_matches_fallback={ok_tiled}"
           f"|claim_tuned_matches_fallback={ok_tuned}"
           f"|claim_streamed_matches_stacked={ok_stream}"
           f"|claim_stream_chunk_le_budget={ok_chunk}")


def precond_step(fast=False):
    """second_order/fednl_precond update micro-benchmark: the payload-op
    path (compress through the payload-emitting op, H reconstructed via
    the payload-space scatter — the shipped code) vs the PR-3-era
    dense-mask path (codec compress building (nblocks, block^2)
    selection masks + dense decompress round-trip inside every step),
    on a (d, d) parameter tensor. Claims: the payload path is no slower
    at d >= 1024 (off-TPU both are jnp; on TPU the payload path is the
    Pallas kernel), the two paths learn the same H on tie-free data,
    and the AUTOTUNED dispatch of the payload step (tracing under the
    active tuning cache — the CI pin or the ``autotune`` bench's
    winners) learns the same H as tracing under an empty cache (the
    untuned defaults)."""
    from repro.kernels import tuning
    from repro.second_order.fednl_precond import (FedNLPrecondOptimizer,
                                                  _as2d)

    ds = [1024] if fast else [1024, 2048]

    def bench(fn, *args, reps=5):
        out = jax.block_until_ready(fn(*args))  # compile
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return out, (time.time() - t0) * 1e6 / reps

    rows, fields = [], []
    ok_speed, ok_match, ok_tuned, us_total = True, True, True, 0.0
    for d in ds:
        opt = FedNLPrecondOptimizer(lr=1e-3, k_per_block=2048, block=128)
        comp = opt.compressor
        params = {"w": jnp.zeros((d, d), jnp.float32)}
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (d, d),
                                        jnp.float32)}
        state = opt.init(params)

        def dense_mask_update(g, s):
            # the PR-3-era per-tensor body: codec round-trip (compress
            # builds the dense per-tile selection masks)
            h = s.h["w"]
            diff = g["w"].astype(jnp.float32) ** 2 - h
            sd = comp.decompress(comp.compress(_as2d(diff)),
                                 _as2d(h).shape).reshape(h.shape)
            l = jnp.sqrt(jnp.mean(diff * diff) + 1e-30)
            denom = jnp.sqrt(jnp.maximum(h, 0.0)) + jnp.sqrt(l) + opt.eps
            m_new = opt.momentum * s.mu["w"] + g["w"] / denom
            return (-opt.lr * m_new,
                    type(s)(s.step + 1, {"w": h + opt.alpha * sd},
                            {"w": m_new}))

        # tuned column: the SAME update traced twice — once under an
        # empty tuning cache (untuned default dispatch) and once under
        # the ambient cache (the CI pin / autotune winners). Fresh jit
        # lambdas per cache state: dispatch resolves at trace time.
        ambient = tuning.get_cache()
        try:
            tuning.set_cache(tuning.TuningCache())
            default_fn = jax.jit(lambda g, s: opt.update(g, s, params))
            (_, st_def), us_payload_def = bench(default_fn, grads, state)
        finally:
            tuning.set_cache(ambient)
        payload_fn = jax.jit(lambda g, s: opt.update(g, s, params))
        dense_fn = jax.jit(dense_mask_update)
        (_, st_p), us_payload = bench(payload_fn, grads, state)
        (_, st_d), us_dense = bench(dense_fn, grads, state)
        err = float(jnp.max(jnp.abs(st_p.h["w"] - st_d.h["w"])))
        err_tuned = float(jnp.max(jnp.abs(st_p.h["w"] - st_def.h["w"])))
        speedup = us_dense / max(us_payload, 1e-9)
        if d >= 1024:
            ok_speed &= speedup >= 0.95  # "no slower" with timer noise
        ok_match &= err <= 1e-5
        ok_tuned &= err_tuned <= 1e-6  # f32 state; 0 when configs agree
        us_total += us_payload
        rows.append((d, us_dense, us_payload, speedup, err,
                     us_payload_def, err_tuned))
        fields.append(f"d{d}:payload={us_payload:.0f}us;"
                      f"densemask={us_dense:.0f}us;{speedup:.1f}x;"
                      f"default={us_payload_def:.0f}us")

    write_csv("precond_step",
              ["d", "us_dense_mask", "us_payload", "speedup", "max_h_err",
               "us_payload_default", "max_h_err_tuned"],
              rows)
    report("precond_step", us_total,
           "|".join(fields)
           + f"|claim_payload_not_slower={ok_speed}"
           f"|claim_same_h={ok_match}"
           f"|claim_tuned_same_h={ok_tuned}")


def train_step(fast=False):
    """What FedNL costs per token on a real architecture: end-to-end
    jitted train-step time and tokens/sec for fednl vs adamw on reduced
    (smoke) configs of >=2 model-zoo archs, across >=2 curvature refresh
    intervals. refresh_every=1 pays the full observation+learning cost
    every step (the paper's per-round placement); refresh_every=16
    amortizes it — non-refresh steps are just the elementwise diagonal
    solve, so the amortized cost approaches adamw. Claim: amortized
    fednl step-time at refresh_every=16 stays within 3x of adamw on
    every arch (timing claims stay local-only for the speedup benches;
    this one is a bound with 3x headroom, so it holds on shared CI
    runners too)."""
    from repro.configs import get_config
    from repro.data.tokens import TokenPipeline
    from repro.launch.steps import make_optimizer, make_train_step
    from repro.models import build_model

    archs = ["qwen2-0.5b", "xlstm-350m"]
    b, t = (2, 32) if fast else (4, 64)
    reps = 2 if fast else 4
    n_silos, r_long, bound = 2, 16, 3.0

    def run_steps(step_fn, params, state, batch, n):
        out = None
        t0 = time.time()
        for _ in range(n):
            params, state, out = step_fn(params, state, batch)
        jax.block_until_ready(out["loss"])
        return (time.time() - t0) * 1e6 / n, params, state

    rows, fields = [], []
    ok_bound, ok_finite, us_total = True, True, 0.0
    for arch in archs:
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg, use_remat=True)
        params0 = model.init_params(jax.random.PRNGKey(0))
        pipe = TokenPipeline(vocab_size=cfg.vocab, seq_len=t,
                             global_batch=b, seed=0)
        batch = pipe.batch(0)

        def cell(opt_name, refresh_every, **kw):
            opt = make_optimizer(opt_name, 1e-3, **kw)
            step_fn = jax.jit(make_train_step(
                model, opt, refresh_every=refresh_every, n_silos=n_silos))
            params, state = params0, opt.init(params0)
            # warm step: compiles BOTH lax.cond branches and runs the
            # step-0 refresh, so timed steps measure steady state
            _, params, state = run_steps(step_fn, params, state, batch, 1)
            us, params, state = run_steps(step_fn, params, state, batch,
                                          reps)
            return us, state

        fk = dict(k_per_block=256, block=128)
        us_adamw, _ = cell("adamw", 1)
        us_refresh, st1 = cell("fednl", 1, **fk)      # every step refreshes
        us_quiet, st16 = cell("fednl", r_long, **fk)  # none of the timed do
        us_amort = (us_refresh + (r_long - 1) * us_quiet) / r_long
        toks = lambda us: b * t / us * 1e6
        ok_bound &= us_amort <= bound * us_adamw
        ok_finite &= all(bool(jnp.all(jnp.isfinite(x)))
                         for st in (st1, st16) for x in jax.tree.leaves(st.h))
        us_total += us_adamw + us_refresh + us_quiet
        rows.append((arch, us_adamw, us_refresh, us_quiet, us_amort,
                     toks(us_adamw), toks(us_refresh), toks(us_amort)))
        fields.append(f"{arch}:adamw={us_adamw:.0f}us;"
                      f"fednl_r1={us_refresh:.0f}us;"
                      f"fednl_r16={us_amort:.0f}us;"
                      f"tok/s={toks(us_amort):.0f}")

    write_csv("train_step",
              ["arch", "us_adamw", "us_fednl_refresh", "us_fednl_quiet",
               "us_fednl_r16_amortized", "toks_adamw", "toks_fednl_r1",
               "toks_fednl_r16"],
              rows)
    report("train_step", us_total,
           "|".join(fields)
           + f"|claim_fednl16_amortized_le_3x_adamw={ok_bound}"
           f"|claim_curvature_finite={ok_finite}")


def engine_vmap(fast=False):
    """The engine's headline: an s-seed cell as ONE vmapped jitted program
    vs s serial per-seed runs (the seed-era execution model)."""
    prob = problem("phishing")
    d, n = prob["d"], prob["n"]
    x0 = _near_x0(prob)
    seeds = (0, 1, 2) if fast else (0, 1, 2, 3)
    rounds = 40

    t0 = time.time()
    alg = FedNL(prob["grad"], prob["hess"], RankR(1), option=1, mu=1e-3)
    serial = [alg.run(x0, n, rounds, seed=s)[1] for s in seeds]
    jax.block_until_ready(serial[-1])
    us_serial = (time.time() - t0) * 1e6

    spec = ExperimentSpec("fednl", "rankr", 1,
                          params=dict(option=1, mu=1e-3),
                          seeds=seeds, num_rounds=rounds)
    t0 = time.time()
    res = Sweep([spec]).run(prob, x0=x0)
    us_vmap = (time.time() - t0) * 1e6

    cell = res.cells[0]
    err = max(float(np.max(np.abs(cell.xs[i] - np.asarray(serial[i]))))
              for i in range(len(seeds)))
    speedup = us_serial / max(us_vmap, 1.0)
    report("engine_vmap", us_vmap,
           f"seeds={len(seeds)}|us_serial={us_serial:.0f}|us_vmap={us_vmap:.0f}"
           f"|speedup={speedup:.2f}x|max_abs_diff={err:.2e}"
           f"|claim_speedup_ge_3x={speedup >= 3.0}")


def roofline(fast=False):
    path = os.path.join(os.path.dirname(__file__), "..",
                        "results_dryrun_1pod.jsonl")
    if not os.path.exists(path):
        report("roofline", 0.0, "missing results_dryrun_1pod.jsonl (run "
               "python -m repro.launch.dryrun --all --out ...)")
        return
    rows = [json.loads(l) for l in open(path)]
    ok = [r for r in rows if r["status"] == "ok"]
    skip = [r for r in rows if r["status"] == "skip"]
    csv_rows = [(r["arch"], r["shape"], r["t_compute_s"], r["t_memory_s"],
                 r["t_collective_s"], r["bottleneck"], r["useful_ratio"],
                 r["peak_bytes_per_device"]) for r in ok]
    write_csv("roofline", ["arch", "shape", "t_compute", "t_memory",
                           "t_collective", "bottleneck", "useful_ratio",
                           "peak_bytes_per_device"], csv_rows)
    bcounts = {}
    for r in ok:
        bcounts[r["bottleneck"]] = bcounts.get(r["bottleneck"], 0) + 1
    report("roofline", 0.0,
           f"pairs_ok={len(ok)}|skips={len(skip)}|bottlenecks={bcounts}")


BENCHES = [fig2_local, fig2_global, fig2_nl1, fig3_compression, fig4_options,
           fig6_update_rules, fig7_bc, fig9_pp, fig14_heterogeneity,
           table2_rates, payload_roundtrip, codec_roundtrip, autotune,
           server_aggregate, precond_step, train_step, engine_vmap,
           roofline]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (one object per "
                         "bench: name, us_per_call, derived) — the "
                         "BENCH_*.json artifact the CI bench-smoke lane "
                         "uploads")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if args.only and bench.__name__ not in args.only.split(","):
            continue
        try:
            bench(fast=args.fast)
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            report(bench.__name__, 0.0, f"ERROR:{type(e).__name__}:{e}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump([dict(name=n, us_per_call=u, derived=d)
                       for n, u, d in RESULTS], f, indent=2)


if __name__ == "__main__":
    main()
