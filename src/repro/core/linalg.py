"""Linear-algebra substrate for the FedNL family.

* ``project_psd`` — [X]_mu, projection onto {M = M^T, M >= mu I}
  (paper A.4, eqs. (19)-(20)).
* ``solve_newton_system`` — stable solve for the (projected/corrected)
  Newton step.
* ``solve_cubic_subproblem`` — argmin <g,h> + 1/2 <(H+lI)h, h> + (L/6)||h||^3
  by reduction to a 1-D secular equation on the eigenbasis (paper E.2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def symmetrize(m: jax.Array) -> jax.Array:
    return 0.5 * (m + m.T)


def project_psd(m: jax.Array, mu: float = 0.0) -> jax.Array:
    """[X]_mu := [X - mu I]_0 + mu I with [Y]_0 clipping eigenvalues at 0."""
    sym = symmetrize(m)
    d = sym.shape[0]
    eye = jnp.eye(d, dtype=sym.dtype)
    evals, evecs = jnp.linalg.eigh(sym - mu * eye)
    clipped = jnp.maximum(evals, 0.0)
    return (evecs * clipped) @ evecs.T + mu * eye


def solve_newton_system(h: jax.Array, g: jax.Array) -> jax.Array:
    """Solve H x = g for symmetric (assumed PD) H via Cholesky with an
    LU fallback baked in numerically (jnp.linalg.solve is LAPACK gesv on
    CPU and a triangular solve pipeline on TPU)."""
    with jax.named_scope("fednl.solve"):
        return jnp.linalg.solve(h, g)


def solve_cubic_subproblem(
    g: jax.Array,
    h_mat: jax.Array,
    m_cubic: float,
    iters: int = 100,
) -> jax.Array:
    """argmin_h T(h) = <g,h> + 1/2 h^T H h + (M/6) ||h||^3.

    Stationarity: (H + (M/2)||h|| I) h = -g. Let r = ||h||; in the
    eigenbasis of H = Q diag(lam) Q^T, with b = Q^T g:

        phi(r) = sum_i b_i^2 / (lam_i + (M/2) r)^2 - r^2 = 0

    phi is decreasing in r for r >= r_min where all denominators are
    positive; we bisect on r in [r_lo, r_hi]. H may be indefinite —
    cubic regularization handles that; we start the bracket at
    r_lo = max(0, -2 lam_min / M) + eps. The Moré–Sorensen "hard case"
    (g orthogonal to the bottom eigenvector with an interior boundary
    solution) is approximated by the bracket endpoint, which is accurate
    to the bisection tolerance — sufficient for FedNL-CR, whose theory
    only needs T(h) <= 0 = T(0) (descent on the cubic model).
    """
    lam, q = jnp.linalg.eigh(symmetrize(h_mat))
    b = q.T @ g
    m_half = m_cubic / 2.0

    lam_min = lam[0]
    r_lo = jnp.maximum(0.0, -2.0 * lam_min / m_cubic) + 1e-12
    # upper bound: ||h|| <= r with (M/2) r^2 >= ||g|| + |lam_min| r
    gnorm = jnp.linalg.norm(g)
    r_hi = (jnp.abs(lam_min) + jnp.sqrt(lam_min**2 + 2.0 * m_cubic * gnorm)) / m_cubic + 1.0

    def phi(r):
        denom = lam + m_half * r
        denom = jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom)
        return jnp.sum((b / denom) ** 2) - r**2

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        val = phi(mid)
        lo = jnp.where(val > 0, mid, lo)
        hi = jnp.where(val > 0, hi, mid)
        return lo, hi

    r_lo, r_hi = jax.lax.fori_loop(0, iters, body, (r_lo, r_hi))
    r = 0.5 * (r_lo + r_hi)

    denom = lam + m_half * r
    denom = jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom)
    h = -(q @ (b / denom))
    # Degenerate case g = 0: h = 0 is the minimizer when H is PSD.
    return jnp.where(gnorm > 1e-30, h, jnp.zeros_like(h))


def frob_norm(m: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.sum(m * m))
