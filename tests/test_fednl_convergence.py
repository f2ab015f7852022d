"""Theorem-level convergence tests for the FedNL family (float64).

Long-running (many rounds at f64): marked slow; the CI lane skips them,
the local tier-1 command runs them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedNL,
    FedNLBC,
    FedNLCR,
    FedNLLS,
    FedNLPP,
    RandK,
    RankR,
    TopK,
    Zero,
)
from repro.core.newton import fixed_hessian_run, newton_run
from repro.core.objectives import (
    batch_grad,
    batch_hess,
    global_value,
    lipschitz_constants,
)
from repro.data.synthetic import make_synthetic

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def problem():
    with jax.enable_x64(True):
        data = make_synthetic(jax.random.PRNGKey(0), alpha=0.5, beta=0.5,
                              n=8, m=60, d=16, lam=1e-3)
        data = data._replace(a=data.a.astype(jnp.float64),
                             b=data.b.astype(jnp.float64))
        grad_fn = lambda x: batch_grad(x, data)
        hess_fn = lambda x: batch_hess(x, data)
        val_fn = lambda x: global_value(x, data)
        xstar, _ = newton_run(jnp.zeros(16, jnp.float64), grad_fn, hess_fn, 50)
        yield dict(data=data, grad=grad_fn, hess=hess_fn, val=val_fn,
                   xstar=xstar, consts=lipschitz_constants(data))


def _x0_near(problem, scale=1e-2, seed=3):
    return problem["xstar"] + scale * jax.random.normal(
        jax.random.PRNGKey(seed), problem["xstar"].shape, jnp.float64)


def test_fednl_linear_rate_eq6(problem):
    """(6): ||x^k - x*||^2 <= (1/2^k) ||x^0 - x*||^2 locally."""
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        alg = FedNL(problem["grad"], problem["hess"], RankR(1), alpha=1.0,
                    option=1, mu=1e-3)
        _, xs = alg.run(x0, 8, 18)
        r = jnp.sum((xs - problem["xstar"]) ** 2, axis=-1)
        for k in range(1, 15):
            assert float(r[k]) <= float(r[0]) / 2**k * 4 + 1e-24, k


def test_fednl_superlinear_ratio_decreases(problem):
    """(8): r_{k+1}/r_k -> 0."""
    with jax.enable_x64(True):
        x0 = _x0_near(problem, scale=5e-2)
        alg = FedNL(problem["grad"], problem["hess"], RankR(2), alpha=1.0,
                    option=1, mu=1e-3)
        _, xs = alg.run(x0, 8, 14)
        r = jnp.sum((xs - problem["xstar"]) ** 2, axis=-1)
        ratios = [float(r[k + 1] / r[k]) for k in range(10) if r[k] > 1e-28]
        assert ratios[-1] < 0.2 * ratios[0] + 1e-12


def test_fednl_hessian_learning(problem):
    """Phi^k linear decay (7): H_i^k -> hess_i(x*)."""
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        alg = FedNL(problem["grad"], problem["hess"], TopK(k=64), alpha=1.0,
                    option=2)
        state = alg.init(x0, 8)
        hstar = problem["hess"](problem["xstar"])
        h_err = [float(jnp.mean(jnp.sum((state.h_local - hstar) ** 2, (-2, -1))))]
        step = jax.jit(alg.step)
        for _ in range(25):
            state = step(state)
            h_err.append(float(jnp.mean(jnp.sum((state.h_local - hstar) ** 2,
                                                (-2, -1)))))
        assert h_err[-1] < 1e-3 * h_err[0]


def test_fednl_option2_converges(problem):
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        alg = FedNL(problem["grad"], problem["hess"], RankR(1), alpha=1.0,
                    option=2)
        final, xs = alg.run(x0, 8, 25)
        gap = float(problem["val"](final.x) - problem["val"](problem["xstar"]))
        assert gap < 1e-16


def test_fednl_unbiased_randk(problem):
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        comp = RandK(k=64)
        omega = comp.spec((16, 16)).omega
        alg = FedNL(problem["grad"], problem["hess"], comp,
                    alpha=1.0 / (1.0 + omega), option=1, mu=1e-3)
        final, _ = alg.run(x0, 8, 60)
        gap = float(problem["val"](final.x) - problem["val"](problem["xstar"]))
        assert gap < 1e-14


def test_n0_linear_ns_quadratic(problem):
    with jax.enable_x64(True):
        x0 = _x0_near(problem, scale=5e-2)
        grad_fn = problem["grad"]
        h0 = jnp.mean(problem["hess"](x0), axis=0)
        _, xs = fixed_hessian_run(x0, h0, grad_fn, 15)
        r = jnp.linalg.norm(xs - problem["xstar"], axis=-1) ** 2
        assert float(r[10]) <= float(r[0]) / 2**10 * 16  # N0: 1/2^k up to slack

        hstar = jnp.mean(problem["hess"](problem["xstar"]), axis=0)
        _, xs = fixed_hessian_run(x0, hstar, grad_fn, 6)
        rr = jnp.linalg.norm(xs - problem["xstar"], axis=-1)
        # NS quadratic: r_{k+1} <= C r_k^2
        c = problem["consts"]["L_star"] / (2 * 1e-3)
        for k in range(3):
            if rr[k] > 1e-14:
                assert float(rr[k + 1]) <= c * float(rr[k]) ** 2 * 10


def test_fednl_pp_converges(problem):
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        alg = FedNLPP(problem["grad"], problem["hess"], RankR(1), tau=3)
        final, _ = alg.run(x0, 8, 60)
        gap = float(problem["val"](final.x) - problem["val"](problem["xstar"]))
        assert gap < 1e-14


def test_fednl_ls_global(problem):
    with jax.enable_x64(True):
        x_far = jnp.full((16,), 3.0, jnp.float64)
        alg = FedNLLS(problem["val"], problem["grad"], problem["hess"],
                      RankR(1), mu=1e-3)
        final, xs = alg.run(x_far, 8, 40)
        vals = [float(problem["val"](x)) for x in xs]
        assert all(vals[i + 1] <= vals[i] + 1e-12 for i in range(len(vals) - 1)), \
            "line search must be monotone"
        assert vals[-1] - float(problem["val"](problem["xstar"])) < 1e-12


def test_fednl_cr_global(problem):
    with jax.enable_x64(True):
        x_far = jnp.full((16,), 2.0, jnp.float64)
        alg = FedNLCR(problem["grad"], problem["hess"], RankR(1),
                      l_star=problem["consts"]["L_star"])
        final, xs = alg.run(x_far, 8, 150)
        vals = [float(problem["val"](x)) for x in xs]
        fstar = float(problem["val"](problem["xstar"]))
        assert all(vals[i + 1] <= vals[i] + 1e-10 for i in range(len(vals) - 1)), \
            "cubic model step must decrease f"
        assert vals[-1] - fstar < 0.5 * (vals[0] - fstar)


def test_fednl_bc_converges(problem):
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        d = 16
        alg = FedNLBC(problem["grad"], problem["hess"],
                      TopK(k=int(0.9 * d * d)), TopK(k=d), p=0.9,
                      option=1, mu=1e-3)
        final, zs = alg.run(x0, 8, 80)
        gap = float(problem["val"](final.z) - problem["val"](problem["xstar"]))
        assert gap < 1e-12


def test_newton_triangle_specializations(problem):
    """FedNL with C=0, alpha=0, H_i^0 = hess_i(x0) IS Newton-Zero."""
    with jax.enable_x64(True):
        x0 = _x0_near(problem)
        alg = FedNL(problem["grad"], problem["hess"], Zero(), alpha=0.0,
                    option=1, mu=1e-3)
        _, xs_fednl = alg.run(x0, 8, 8)
        h0 = jnp.mean(problem["hess"](x0), axis=0)
        _, xs_n0 = fixed_hessian_run(x0, h0, problem["grad"], 8, mu=1e-3)
        np.testing.assert_allclose(np.asarray(xs_fednl),
                                   np.asarray(xs_n0), atol=1e-10)
