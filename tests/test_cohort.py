"""Cross-device cohort layer tests: CohortSpec validation, K-of-N
sampling determinism, staleness-weight edge cases, the FedNL-PP
recovery guarantee (beta = 0, deadline_quantile = 1 reproduces FedNL-PP
with tau = cohort bitwise), and the ExperimentSpec/Sweep plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CohortSpec, FedNLPP, TopK
from repro.core.cohort import (
    CohortFedNLPP,
    arrival_times,
    on_time_mask,
    sample_cohort,
    staleness_weights,
)
from repro.core.objectives import batch_grad, batch_hess, global_value
from repro.data.synthetic import make_synthetic
from repro.engine import ExperimentSpec, Sweep

D, N = 10, 6


@pytest.fixture(scope="module")
def problem():
    with jax.enable_x64(True):
        data = make_synthetic(jax.random.PRNGKey(0), alpha=0.5, beta=0.5,
                              n=N, m=30, d=D, lam=1e-3)
        data = data._replace(a=data.a.astype(jnp.float64),
                             b=data.b.astype(jnp.float64))
        yield dict(data=data,
                   grad=lambda x: batch_grad(x, data),
                   hess=lambda x: batch_hess(x, data),
                   val=lambda x: global_value(x, data),
                   n=N, d=D, fstar=0.0)


# -- CohortSpec ----------------------------------------------------------------


@pytest.mark.parametrize("kwargs", [
    dict(cohort=0),
    dict(cohort=3, population=2),
    dict(cohort=1, deadline_quantile=0.0),
    dict(cohort=1, deadline_quantile=1.5),
    dict(cohort=1, staleness_beta=-0.1),
])
def test_cohort_spec_rejects_bad_config(kwargs):
    with pytest.raises(ValueError):
        CohortSpec(**kwargs)


def test_cohort_spec_defaults_are_cross_device():
    spec = CohortSpec(cohort=100, population=10_000)
    assert spec.link == "fl-cross-device"
    assert 0.0 < spec.deadline_quantile <= 1.0
    assert spec.staleness_beta >= 0.0


# -- sampling / arrival / staleness -------------------------------------------


def test_sample_cohort_exactly_k_and_deterministic():
    key = jax.random.PRNGKey(7)
    mask = sample_cohort(key, 50, 10)
    assert mask.shape == (50,) and mask.dtype == jnp.bool_
    assert int(mask.sum()) == 10
    np.testing.assert_array_equal(np.asarray(mask),
                                  np.asarray(sample_cohort(key, 50, 10)))
    other = sample_cohort(jax.random.PRNGKey(8), 50, 10)
    assert not bool(jnp.array_equal(mask, other))
    # K >= N degenerates to everyone
    assert int(sample_cohort(key, 4, 9).sum()) == 4


def test_staleness_weights_edge_cases():
    s = jnp.asarray([0, 1, 3, 7])
    # beta = 0: no discount at any staleness (the FedNL-PP recovery)
    np.testing.assert_array_equal(np.asarray(staleness_weights(s, 0.0)),
                                  np.ones(4))
    w = np.asarray(staleness_weights(s, 0.5))
    assert w[0] == 1.0                       # fresh client: full weight
    assert np.all(np.diff(w) < 0)            # strictly decaying
    np.testing.assert_allclose(w[2], 0.5)    # (1 + 3)^(-1/2)
    # negative staleness (never-sampled init) clamps to fresh
    assert float(staleness_weights(jnp.asarray(-2), 0.5)) == 1.0


def test_arrival_times_deterministic_and_deadline():
    spec = CohortSpec(cohort=8, population=32, seed=3)
    t1 = arrival_times(spec, 32, bits_per_silo=1e6)
    t2 = arrival_times(spec, 32, bits_per_silo=1e6)
    np.testing.assert_array_equal(t1, t2)
    assert t1.shape == (32,) and np.all(t1 > 0)
    assert bool(np.all(on_time_mask(t1, 1.0)))       # quantile 1: everyone
    frac = float(np.mean(on_time_mask(t1, 0.5)))     # median deadline
    assert 0.25 <= frac <= 0.75


# -- CohortFedNLPP -------------------------------------------------------------


def test_cohort_recovers_fednl_pp_bitwise(problem):
    """beta = 0 + deadline_quantile = 1 is FedNL-PP with tau = cohort:
    identical key usage, unit weights for the sampled cohort — the two
    trajectories must agree BITWISE round for round."""
    with jax.enable_x64(True):
        comp = TopK(k=20)
        x0 = jnp.zeros(D, jnp.float64)
        pp = FedNLPP(problem["grad"], problem["hess"], comp, tau=2)
        spec = CohortSpec(cohort=2, staleness_beta=0.0,
                          deadline_quantile=1.0)
        co = CohortFedNLPP(problem["grad"], problem["hess"], comp,
                           cohort=spec)
        _, xs_pp = pp.run(x0, N, 6)
        _, xs_co = co.run(x0, N, 6)
        np.testing.assert_array_equal(np.asarray(xs_co), np.asarray(xs_pp))


def test_cohort_straggler_discount_applied(problem):
    """With an aggressive deadline and beta > 0, sampled stragglers get
    exactly the (1 + staleness)^(-beta) weight and unsampled silos get
    0 — checked against the hand-computed arrival mask."""
    with jax.enable_x64(True):
        spec = CohortSpec(cohort=4, staleness_beta=0.5,
                          deadline_quantile=0.5, seed=1)
        co = CohortFedNLPP(problem["grad"], problem["hess"], TopK(k=20),
                           cohort=spec)
        state = co.init(jnp.zeros(D, jnp.float64), N)
        state = state._replace(step=state.step + 3)  # 3 rounds stale
        active = jnp.asarray([True, True, True, False, False, True])
        wts = np.asarray(co._round_weights(state, active))
        from repro.wire import wire_cost

        bits = wire_cost(co.comp, (D, D), encoded=False).analytic_bits
        on_time = on_time_mask(arrival_times(spec, N, bits),
                               spec.deadline_quantile)
        assert np.all(wts[~np.asarray(active)] == 0.0)
        late = np.asarray(active) & ~on_time
        np.testing.assert_allclose(wts[late], (1 + 3) ** -0.5)
        assert np.all(wts[np.asarray(active) & on_time] == 1.0)


def test_cohort_population_mismatch_raises(problem):
    spec = CohortSpec(cohort=2, population=4)   # problem has n = 6
    co = CohortFedNLPP(problem["grad"], problem["hess"], TopK(k=20),
                       cohort=spec)
    with pytest.raises(ValueError, match="population"):
        co.init(jnp.zeros(D), N)


def test_cohort_converges_and_is_deterministic(problem):
    with jax.enable_x64(True):
        spec = CohortSpec(cohort=3, population=N)
        co = CohortFedNLPP(problem["grad"], problem["hess"], TopK(k=30),
                           cohort=spec, alpha=1.0)
        x0 = jnp.zeros(D, jnp.float64)
        _, xs1 = co.run(x0, N, 60)
        _, xs2 = co.run(x0, N, 60)
        np.testing.assert_array_equal(np.asarray(xs1), np.asarray(xs2))
        # drives the GLOBAL gradient to (near) zero despite sampling +
        # straggler discounts; the objective itself plateaus at f* > 0
        gnorm = [float(jnp.linalg.norm(jnp.mean(problem["grad"](x), 0)))
                 for x in xs1]
        assert gnorm[-1] < 1e-8 * gnorm[0]
        assert gnorm[-1] < 1e-9


# -- engine plumbing -----------------------------------------------------------


def test_experiment_spec_cohort_through_sweep(problem):
    """ONE CohortSpec drives the whole cell: the method construction,
    the display label, and the traffic-model pricing (cohort link +
    cohort size, not the sweep-wide preset)."""
    with jax.enable_x64(True):
        spec = ExperimentSpec("fednl-cohort", "topk", 20,
                              cohort=CohortSpec(cohort=3, population=N),
                              num_rounds=8)
        assert spec.label == "fednl-cohort:topk20:K3ofN6"
        res = Sweep([spec]).run(problem, x0=jnp.zeros(D, jnp.float64))
        cell = res.cells[0]
        assert cell.xs.shape == (1, 9, D)
        assert np.all(np.isfinite(cell.xs))
        assert cell.gaps[0, -1] < cell.gaps[0, 1]
        assert cell.seconds_per_round is not None
        assert cell.seconds_per_round > 0.0
