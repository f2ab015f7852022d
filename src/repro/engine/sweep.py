"""Declarative experiment sweeps for the FedNL family.

The paper's figures are grids — method x compressor x level x seed — and
the seed-era harness executed every cell as its own Python loop. Here a
grid is a list of ``ExperimentSpec`` cells and the ``Sweep`` runner
executes each cell as ONE jitted program: ``jax.vmap`` stacks the
homogeneous seed axis and ``lax.scan`` runs the rounds, so an s-seed
cell costs roughly one single-run wall-clock instead of s. Compressor
levels are static to XLA (top-k sizes, SVD ranks), so distinct levels
compile per cell-shape; hold on to ``batched_runner``'s callable to
amortize the trace across repeated executions of the same cell.

Execution paths:

* default — vmap-over-seeds + scan-over-rounds, single process;
* ``mesh=`` — the shard_map path of ``core/federated.py``: silo data and
  Hessian state sharded over the mesh's "data" axis, one pod runs the
  cell. Only plain-FedNL cells whose silo count divides the axis can
  shard; any other cell raises rather than running unsharded.

Results come back as ``CellResult`` (stacked iterate/gap histories, the
analytic AND measured cumulative-bits curves, per-cell ``us_per_round``,
and traffic-model ``seconds_per_round`` — measured wire bits priced on
the sweep's ``link`` preset) and tidy row dicts via
``SweepResult.records()`` — figure code becomes spec + plot, with
``bits``/``bits_measured``/``seconds_per_round`` side by side per row.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import records as rec
from .method import Oracles, make_method, scan_rounds


# -- compressor construction by (family, level) --------------------------------


def build_compressor(family: str, level=None):
    """String-keyed compressor factory — now a thin alias for the
    self-registering registry in ``core.compressors``
    (``make_compressor``); kept so engine callers and old specs keep
    working."""
    from ..core.compressors import make_compressor

    return make_compressor(family, level)


# -- specs ---------------------------------------------------------------------


@dataclass(frozen=True)
class ExperimentSpec:
    """One cell of a sweep grid.

    method:     registry key ("fednl", "fednl-pp", "fednl-bc", ...)
    compressor: compressor family for ``build_compressor`` (None for
                methods that take no compressor, e.g. "newton")
    level:      the family's level knob (rank / k / s)
    params:     extra method kwargs (alpha, option, mu, tau, p, eta,
                l_star, model_compressor=("topk", k), ...)
    seeds:      PRNG seeds — stacked into one vmapped program
    num_rounds: communication rounds (the scan length)
    name:       display label (auto-generated when omitted)
    cohort:     optional ``repro.core.cohort.CohortSpec`` — the
                cross-device participation model, passed through to
                methods that take one (``"fednl-cohort"``); the ONE
                place a cell declares population/cohort/arrival instead
                of ad-hoc per-callsite kwargs. Also retargets the
                ``seconds_per_round`` traffic column onto the cohort's
                link and size.
    """

    method: str
    compressor: Optional[str] = None
    level: Optional[float] = None
    params: Mapping[str, Any] = field(default_factory=dict)
    seeds: Sequence[int] = (0,)
    num_rounds: int = 50
    name: Optional[str] = None
    cohort: Optional[Any] = None

    def __post_init__(self):
        object.__setattr__(self, "params", dict(self.params))
        object.__setattr__(self, "seeds", tuple(int(s) for s in self.seeds))

    @property
    def label(self) -> str:
        if self.name:
            return self.name
        parts = [self.method]
        if self.compressor:
            lvl = "" if self.level is None else f"{self.level:g}"
            parts.append(f"{self.compressor}{lvl}")
        if self.cohort is not None:
            pop = self.cohort.population
            parts.append(f"K{self.cohort.cohort}" +
                         (f"ofN{pop}" if pop is not None else ""))
        return ":".join(parts)

    def build(self, oracles: Oracles):
        """Instantiate the method object for this cell."""
        comp = (build_compressor(self.compressor, self.level)
                if self.compressor else None)
        params = dict(self.params)
        if self.cohort is not None:
            params["cohort"] = self.cohort
        return make_method(self.method, oracles, comp, **params)


@dataclass
class CellResult:
    spec: ExperimentSpec
    xs: np.ndarray        # (num_seeds, num_rounds+1, d) iterate history
    gaps: np.ndarray      # (num_seeds, num_rounds+1) f(x_k) - f*
    bits: np.ndarray      # (num_rounds+1,) cumulative bits/node (analytic)
    us_per_round: float   # cell wall-clock / num_rounds — END-TO-END cost
                          # including the one-time jit trace+compile (the
                          # quantity the engine optimizes vs serial loops),
                          # not steady-state per-round latency
    bits_measured: Optional[np.ndarray] = None
                          # (num_rounds+1,) cumulative bits/node, measured
                          # from the method's payload structure
    bits_entropy: Optional[np.ndarray] = None
                          # (num_rounds+1,) cumulative bits/node with the
                          # sparsifier index streams entropy-coded
                          # (log2 C(d^2, k) accounting, no actual codec)
    seconds_per_round: Optional[float] = None
                          # simulated uplink seconds per synchronous round:
                          # measured wire bits priced through the traffic
                          # model (Sweep's ``link`` preset, straggler max
                          # over the problem's n silos); None if link=None


@dataclass
class SweepResult:
    cells: list

    def records(self) -> list[dict]:
        return [row for c in self.cells for row in rec.cell_records(c)]

    def summary(self, target: Optional[float] = None) -> list[dict]:
        return rec.summary_records(self.cells, target)

    def cell(self, label: str) -> CellResult:
        for c in self.cells:
            if c.spec.label == label:
                return c
        raise KeyError(label)


# -- cell execution ------------------------------------------------------------


def batched_runner(method, n: int, num_rounds: int):
    """One jitted program per cell-shape: vmap over the seed axis of a
    scan over rounds. Hold on to the returned callable to amortize the
    trace across repeated executions (new x0, new seeds of the same
    count); method objects are rebuilt per Sweep.run, so caching here
    by method identity would never hit."""

    def one(x0, seed):
        state = method.init(x0, n, seed=seed)
        _, xs = scan_rounds(method, state, num_rounds)
        return xs

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def run_cell(method, x0, n: int, num_rounds: int, seeds: Sequence[int]):
    """Execute one cell; returns (num_seeds, num_rounds+1, d) history."""
    runner = batched_runner(method, n, num_rounds)
    xs = runner(jnp.asarray(x0), jnp.asarray(seeds))
    x0b = jnp.broadcast_to(jnp.asarray(x0), (len(seeds), 1, x0.shape[-1]))
    return jnp.concatenate([x0b, xs], axis=1)


# -- the sweep runner ----------------------------------------------------------


class Sweep:
    """Run a grid of ``ExperimentSpec`` cells against one problem.

    ``problem`` (to ``run``) is a mapping with the benchmark-harness
    keys: "grad", "hess" (stacked per-silo oracles), optional "val" and
    "fstar" for gap curves, "n", "d", and optional "data"
    (``LogRegData``, required by the sharded path).

    ``link`` prices each cell's measured wire bits through the traffic
    model (``repro.wire.traffic`` preset name or ``LinkModel``) into the
    ``seconds_per_round`` record column; ``link=None`` skips the model
    (the column reads NaN).
    """

    def __init__(self, specs: Sequence[ExperimentSpec], mesh=None,
                 axis: str = "data", link="wan"):
        self.specs = list(specs)
        self.mesh = mesh
        self.axis = axis
        self.link = link

    def run(self, problem, x0=None) -> SweepResult:
        oracles = Oracles(value=problem.get("val"), grad=problem["grad"],
                          hess=problem["hess"])
        n, d = int(problem["n"]), int(problem["d"])
        fstar = problem.get("fstar")
        if x0 is None:
            x0 = jnp.zeros(d)
        cells = []
        for spec in self.specs:
            method = spec.build(oracles)
            t0 = time.perf_counter()
            if self.mesh is not None:
                self._check_shardable(spec, problem)
                xs = self._run_sharded(spec, problem, x0)
            else:
                xs = run_cell(method, x0, n, spec.num_rounds, spec.seeds)
            xs = jax.block_until_ready(xs)
            wall_us = (time.perf_counter() - t0) * 1e6
            val = problem.get("val")
            if val is not None:
                gaps = np.asarray(jax.vmap(jax.vmap(val))(xs))
                if fstar is not None:
                    gaps = gaps - fstar
            else:
                gaps = np.full(xs.shape[:2], np.nan)
            cells.append(CellResult(
                spec=spec,
                xs=np.asarray(xs),
                gaps=gaps,
                bits=rec.bits_curve(method, d, spec.num_rounds),
                bits_measured=rec.measured_bits_curve(
                    method, d, spec.num_rounds),
                bits_entropy=rec.entropy_bits_curve(
                    method, d, spec.num_rounds),
                us_per_round=wall_us / max(1, spec.num_rounds),
                seconds_per_round=self._cell_seconds(spec, method, d, n),
            ))
        return SweepResult(cells)

    def _cell_seconds(self, spec: ExperimentSpec, method, d: int,
                      n: int) -> Optional[float]:
        """Traffic-model pricing for one cell: a ``cohort=`` cell is
        priced on ITS link and cohort size (the round waits for the
        sampled K, not all N registered clients); everything else uses
        the sweep-wide ``link`` preset over the problem's n silos."""
        if spec.cohort is not None:
            return rec.seconds_per_round(method, d, spec.cohort.cohort,
                                         link=spec.cohort.link)
        if self.link is None:
            return None
        return rec.seconds_per_round(method, d, n, link=self.link)

    # -- shard_map path (reuses core/federated.py's mesh axis) -----------------

    def _check_shardable(self, spec: ExperimentSpec, problem) -> None:
        if spec.method != "fednl" or problem.get("data") is None:
            raise ValueError(
                f"cell {spec.label!r} cannot run on the mesh: only 'fednl' "
                "cells on a problem with 'data' shard")
        n, extent = int(problem["n"]), int(self.mesh.shape[self.axis])
        if n % extent:
            raise ValueError(
                f"cell {spec.label!r} cannot run on the mesh: {n} silos do "
                f"not divide the {self.axis!r} axis of {extent} devices")

    def _run_sharded(self, spec: ExperimentSpec, problem, x0):
        from ..core.federated import run_fednl_sharded

        comp = build_compressor(spec.compressor, spec.level)
        p = dict(spec.params)
        out = []
        for seed in spec.seeds:
            # defaults must match FedNL.__init__ so the same spec runs the
            # same algorithm with and without mesh=
            _, xs = run_fednl_sharded(
                problem["data"], comp, self.mesh, x0, spec.num_rounds,
                alpha=p.get("alpha", 1.0), option=p.get("option", 1),
                mu=p.get("mu", 0.0), axis=self.axis, seed=seed)
            out.append(xs)
        return jnp.stack(out)


def run_sweep(specs: Sequence[ExperimentSpec], problem, x0=None,
              mesh=None, axis: str = "data", link="wan") -> SweepResult:
    """Convenience wrapper: ``Sweep(specs, mesh, axis, link).run(...)``."""
    return Sweep(specs, mesh=mesh, axis=axis, link=link).run(problem, x0=x0)
