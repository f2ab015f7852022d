"""Plain reference of the federated cells: l2-regularised logistic
regression over silos (FedNL paper, eq. (10)),

    f(x) = (1/n) sum_i f_i(x),
    f_i(x) = (1/m) sum_j log(1 + exp(-b_ij a_ij^T x)) + (lam/2) ||x||^2,

in float64 NumPy on the host. FedNL's state has a fixed point: x at the
minimiser x*, every silo's learned Hessian H_i at its local Hessian
grad^2 f_i(x*), and the server's H at their mean. ``solution`` gives it.

``control_oracles`` is the control of the comparison: the same oracles in
float32 with every matrix product taken in three bfloat16 passes (the
``high`` precision, one step below the configured ``highest``), in the
program's place. It imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _sigmoid(t):
    return 0.5 * (1.0 + np.tanh(0.5 * t))


def _global_grad_hess(a2, b2, lam, x):
    """Gradient and Hessian of f over the stacked (n m, d) rows."""
    z = -b2 * (a2 @ x)
    s = _sigmoid(z)
    g = a2.T @ (s * -b2) / a2.shape[0] + lam * x
    w = s * (1.0 - s)
    h = (a2.T * w) @ a2 / a2.shape[0] + lam * np.eye(x.size)
    return g, h


def newton_minimiser(a, b, lam: float, tol: float = 1e-14,
                     max_iter: int = 60) -> np.ndarray:
    """x* by Newton's method from 0 in float64, to ||grad f|| <= tol."""
    n, m, d = a.shape
    a2 = np.asarray(a, np.float64).reshape(n * m, d)
    b2 = np.asarray(b, np.float64).reshape(n * m)
    x = np.zeros(d)
    for _ in range(max_iter):
        g, h = _global_grad_hess(a2, b2, lam, x)
        if np.linalg.norm(g) <= tol:
            break
        x = x - np.linalg.solve(h, g)
    return x


def local_hessians(a, b, lam: float, x) -> np.ndarray:
    """(n, d, d) grad^2 f_i(x) in float64."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    n, m, d = a.shape
    z = -b * np.einsum("nmd,d->nm", a, x)
    s = _sigmoid(z)
    w = s * (1.0 - s)
    return (np.matmul(a.transpose(0, 2, 1) * w[:, None, :], a) / m
            + lam * np.eye(d))


def solution(a, b, lam: float) -> dict:
    """FedNL's fixed point: x*, the local Hessians there and their mean."""
    x = newton_minimiser(a, b, lam)
    h_local = local_hessians(a, b, lam, x)
    return {"x": x, "h_local": h_local, "h_global": h_local.mean(axis=0)}


def rel_errors(state: dict, ref: dict) -> dict:
    """Relative distances of a FedNL state from the fixed point: of x, of
    the server's H, and of the worst silo's H_i (Frobenius norms); and
    ``h_global_drift``, the server's H from the float64 mean of the
    silos' own H_i. FedNL keeps H = mean_i H_i every round (each silo adds
    its S_i, the server their mean), so the drift reads the server
    aggregate alone, whatever the oracles gave."""
    x = np.asarray(state["x"], np.float64)
    hg = np.asarray(state["h_global"], np.float64)
    hl = np.asarray(state["h_local"], np.float64)
    per_silo = (np.linalg.norm(hl - ref["h_local"], axis=(1, 2))
                / np.linalg.norm(ref["h_local"], axis=(1, 2)))
    mean = hl.mean(axis=0)
    return {
        "x_rel_err": float(np.linalg.norm(x - ref["x"])
                           / np.linalg.norm(ref["x"])),
        "h_global_rel_err": float(np.linalg.norm(hg - ref["h_global"])
                                  / np.linalg.norm(ref["h_global"])),
        "h_local_rel_err": float(per_silo.max()),
        "h_global_drift": float(np.linalg.norm(hg - mean)
                                / np.linalg.norm(mean)),
    }


# -- the control: the oracles in three-pass bfloat16 -------------------------


def _bf16(x):
    """x rounded to bfloat16 and held in float32. ``reduce_precision``,
    not a convert pair: a TPU compiler may drop a float32 -> bfloat16 ->
    float32 round trip as excess precision, never this op."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    lo = _bf16(x - hi)
    return hi, lo


def dot3(p, q):
    """p @ q from three bfloat16 products (hi hi + hi lo + lo hi), each
    exact in float32: the ``high`` matmul precision."""
    ph, pl = _split(p)
    qh, ql = _split(q)
    dot = lambda u, v: jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST)
    return dot(ph, qh) + dot(ph, ql) + dot(pl, qh)


def control_oracles(a, b, lam: float):
    """Stacked per-silo (grad, hess) oracles with three-pass products."""

    def grad_i(x, ai, bi):
        coef = jax.nn.sigmoid(-bi * dot3(ai, x)) * (-bi)
        return dot3(ai.T, coef) / ai.shape[0] + lam * x

    def hess_i(x, ai, bi):
        s = jax.nn.sigmoid(-bi * dot3(ai, x))
        w = s * (1.0 - s)
        return (dot3(ai.T * w, ai) / ai.shape[0]
                + lam * jnp.eye(x.shape[0], dtype=x.dtype))

    grad = lambda x: jax.vmap(lambda ai, bi: grad_i(x, ai, bi))(a, b)
    hess = lambda x: jax.vmap(lambda ai, bi: hess_i(x, ai, bi))(a, b)
    return grad, hess


def lower_values(values, passes: int):
    """Payload values as a one-hot product at a lower precision carries
    them: ``passes`` 3 keeps a bfloat16 high part and low part (``high``),
    1 the high part alone (bfloat16, the default precision)."""
    hi, lo = _split(values.astype(jnp.float32))
    return (hi + lo if passes == 3 else hi).astype(values.dtype)
