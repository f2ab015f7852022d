"""Operations of one FedNL round on federated logistic regression (paper
eq. (10)), from the shapes: n silos of m rows and d features."""

from __future__ import annotations


def round_flops(n: int, m: int, d: int) -> float:
    """Each silo's Hessian (A^T diag(w) A: 2 m d^2), its margins and
    gradient (A x and A^T c: 4 m d), and the server's d x d solve
    (2/3 d^3)."""
    return float(2 * n * m * d * d + 4 * n * m * d + 2 * d ** 3 / 3)
