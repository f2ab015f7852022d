"""A run with the timed path broken underneath comes out not correct: for
each fault the cell can have, the rest of the run as it is (toy size, the
look for a chip skipped)."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests.toy import benchmark_cells

FEDNL = benchmark_cells("fednl_rounds")
TRAIN = benchmark_cells("train_steps")


def _incorrect(cell):
    r = run.run_cell(cell, 2**31 + 9, 0.5, False, require_tpu=False)
    assert not r["correct"], r["checks"]
    assert r["failed"] == r["attempted"]


@pytest.mark.parametrize("workload", FEDNL)
def test_fednl_state_unchanged(workload, toy_cell, monkeypatch):
    from repro.core.fednl import FedNL

    monkeypatch.setattr(FedNL, "step", lambda self, state: state)
    _incorrect(toy_cell(workload))


@pytest.mark.parametrize("workload", FEDNL)
def test_fednl_half_the_silos(workload, toy_cell, monkeypatch):
    """The server's means taken over the first half of the silos only."""
    from repro.core.fednl import FedNL

    mean, aggregate = FedNL._mean, FedNL._server_aggregate
    half = lambda t: jax.tree.map(lambda x: x[: x.shape[0] // 2], t)
    monkeypatch.setattr(FedNL, "_mean", lambda self, v: mean(self, half(v)))
    monkeypatch.setattr(FedNL, "_server_aggregate",
                        lambda self, p, shape, weights=None:
                        aggregate(self, half(p), shape, weights))
    _incorrect(toy_cell(workload))


@pytest.mark.parametrize("fault", ["aggregate_bf16", "aggregate_drop"])
@pytest.mark.parametrize("workload", FEDNL)
def test_fednl_aggregate_broken(workload, fault, toy_cell):
    """The server aggregate alone broken: its payload values in one
    bfloat16 pass, or one pair in 16 lost. The silos' H_i and x do not
    see it; the server's H does."""
    cell = toy_cell(workload)
    r = run.run_cell(cell, 2**31 + 11, 0.5, False, require_tpu=False,
                     driver_kw={"fault": fault})
    assert not r["correct"], r["checks"]
    drift = r["checks"]["h_global_drift"]
    assert drift["value"] > drift["limit"], drift


@pytest.mark.parametrize("workload", FEDNL)
def test_fednl_answer_altered(workload, toy_cell, monkeypatch):
    """The server's Newton step altered where it is produced."""
    import repro.core.fednl as fednl

    solve = fednl.solve_newton_system
    monkeypatch.setattr(fednl, "solve_newton_system",
                        lambda h, g: solve(h, g) + 1e-3)
    _incorrect(toy_cell(workload))


def _wrap_step(monkeypatch, wrap):
    import repro.launch.steps as steps

    make = steps.make_train_step
    monkeypatch.setattr(steps, "make_train_step",
                        lambda *a, **k: wrap(make(*a, **k)))


@pytest.mark.parametrize("workload", TRAIN)
def test_train_state_unchanged(workload, toy_cell, monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return unchanged

    _wrap_step(monkeypatch, wrap)
    _incorrect(toy_cell(workload))


@pytest.mark.parametrize("workload", TRAIN)
def test_train_half_the_batch(workload, toy_cell, monkeypatch):
    def wrap(step):
        def half(params, opt_state, batch):
            rows = jax.tree.leaves(batch)[0].shape[0] // 2
            return step(params, opt_state,
                        jax.tree.map(lambda x: jnp.concatenate([x[:rows]] * 2),
                                     batch))
        return half

    _wrap_step(monkeypatch, wrap)
    _incorrect(toy_cell(workload))
