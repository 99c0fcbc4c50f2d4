"""Convergence-error expressions of GenQSGD (Theorem 1 + Lemmas 1-3).

All functions are NumPy-float implementations (they feed the offline GP-based
parameter optimizer, not the device-side training step) and accept vectorized
``K_n``.  ``c_constant``, ``c_exponential`` and ``c_diminishing`` also take a
grid of points at once: ``Kn`` of shape (G, N) with ``B`` (and the step size)
of shape (G,), against an ndarray ``K0`` ladder of shape (L,), give a (G, L)
surface whose row g holds, bit for bit, what grid point g gives alone.

Notation:
  K0        : number of global iterations
  Kn        : array (N,) of per-worker local iteration counts
  B         : mini-batch size
  gammas    : step-size sequence (K0,)
  c = (c1, c2, c3, c4) with
      c1 = 2 N (f(x^(1)) - f*),  c2 = 4 G^2 L^2,  c3 = L sigma^2 / N,
      c4 = 2 L G^2                                    (Theorem 1)
  q_pairs   : array (N,) of q_{s0,sn} = q_{s0} + q_{sn} + q_{s0} q_{sn}
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["MLProblemConstants", "coefficients", "c_arbitrary", "c_constant",
           "c_exponential", "c_diminishing", "c_m"]


def per_point(Kn: np.ndarray, x):
    """``x`` (one value per grid point) as a column against the K0 ladder
    where ``Kn`` carries a leading grid axis, (G, N); unchanged otherwise."""
    return np.asarray(x)[..., None] if Kn.ndim == 2 else x


def _square(x):
    """``x ** 2`` as a scalar gets it (``pow``), element by element.  NumPy
    squares an array by a multiply, which can land an ulp away, so a grid
    row would not repeat its point's bits."""
    if np.ndim(x) == 0:
        return x ** 2
    return np.array([v ** 2 for v in np.ravel(x).tolist()]).reshape(
        np.shape(x))


def _weighted_blocks(Kn: np.ndarray, q_pairs: np.ndarray, eps):
    """``(sum_n eps_n K_n, sum_n q_n (eps_n K_n)^2)`` — the two aggregation
    blocks of the bound, summed over the workers (the last axis).
    ``eps=None`` (uniform weights) takes the exact historical arithmetic,
    so GenQSGD results stay bitwise unchanged; weighted families
    (``eps_n = N w_n``, :mod:`repro.families`) reweight the effective local
    work and the quantization variance per worker."""
    if eps is None:
        return Kn.sum(axis=-1), (q_pairs * Kn**2).sum(axis=-1)
    e = np.asarray(eps, dtype=np.float64)
    eK = e * Kn
    return eK.sum(axis=-1), (q_pairs * eK**2).sum(axis=-1)


def _grid_terms(Kn, B, q_pairs, eps):
    """``(sum_K, qK2, max_n K_n ** 2, B)`` per point, as columns against
    the K0 ladder where ``Kn`` is a (G, N) grid."""
    sum_K, qK2 = _weighted_blocks(Kn, q_pairs, eps)
    kmax2 = _square(Kn.max(axis=-1))
    return tuple(per_point(Kn, x) for x in (sum_K, qK2, kmax2, B))


@dataclasses.dataclass(frozen=True)
class MLProblemConstants:
    """Pre-training estimates describing the ML problem (Sec. IV-A)."""
    L: float            # gradient Lipschitz constant (Assumption 3)
    sigma: float        # stochastic-gradient std bound (Assumption 4)
    G: float            # second-moment bound (Assumption 5)
    f_gap: float        # f(x^(1)) - lower bound on f*
    N: int              # number of workers

    @property
    def c(self):
        return coefficients(self.L, self.sigma, self.G, self.f_gap, self.N)


def coefficients(L: float, sigma: float, G: float, f_gap: float, N: int):
    c1 = 2.0 * N * f_gap
    c2 = 4.0 * G**2 * L**2
    c3 = L * sigma**2 / N
    c4 = 2.0 * L * G**2
    return c1, c2, c3, c4


def c_arbitrary(K0, Kn, B, gammas, c, q_pairs, eps=None) -> float:
    """C_A(K, B, Gamma) — eq. (9), arbitrary step-size sequence."""
    c1, c2, c3, c4 = c
    Kn = np.asarray(Kn, dtype=np.float64)
    g = np.asarray(gammas, dtype=np.float64)
    assert g.shape[0] == int(round(K0)), (g.shape, K0)
    q_pairs = np.asarray(q_pairs, dtype=np.float64)
    sum_g = g.sum()
    sum_g2 = (g**2).sum()
    sum_g3 = (g**3).sum()
    sum_K, qK2 = _weighted_blocks(Kn, q_pairs, eps)
    kmax = Kn.max()
    t1 = c1 / (sum_K * sum_g)
    t2 = c2 * kmax**2 * sum_g3 / sum_g
    t3 = c3 * sum_g2 / (B * sum_g)
    t4 = c4 * qK2 * sum_g2 / (sum_K * sum_g)
    return float(t1 + t2 + t3 + t4)


def c_constant(K0, Kn, B, gamma_c, c, q_pairs, eps=None):
    """C_C — eq. (11).  Broadcasts over an ndarray ``K0`` (the feasibility
    grid search evaluates whole K0 ladders at once) and over a grid of
    points (``gamma_c`` may then be one per point); scalar in, float out."""
    c1, c2, c3, c4 = c
    Kn = np.asarray(Kn, dtype=np.float64)
    q_pairs = np.asarray(q_pairs, dtype=np.float64)
    sum_K, qK2, kmax2, B = _grid_terms(Kn, B, q_pairs, eps)
    if np.ndim(gamma_c):
        gamma_c = per_point(Kn, gamma_c)
    out = (
        c1 / (gamma_c * K0 * sum_K)
        + c2 * _square(gamma_c) * kmax2
        + c3 * gamma_c / B
        + c4 * gamma_c * qK2 / sum_K
    )
    return out if np.ndim(out) else float(out)


def c_exponential(K0, Kn, B, gamma_e, rho_e, c, q_pairs, eps=None):
    """C_E — eq. (13).  Broadcasts over an ndarray ``K0`` and a grid."""
    c1, c2, c3, c4 = c
    Kn = np.asarray(Kn, dtype=np.float64)
    q_pairs = np.asarray(q_pairs, dtype=np.float64)
    a1 = (1.0 - rho_e) / gamma_e
    a2 = gamma_e**2 / (1.0 + rho_e + rho_e**2)
    a3 = gamma_e / (1.0 + rho_e)
    r1 = rho_e**K0
    sum_K, qK2, kmax2, B = _grid_terms(Kn, B, q_pairs, eps)
    out = (
        a1 * c1 / ((1.0 - r1) * sum_K)
        + a2 * c2 * (1.0 - rho_e ** (3 * K0)) / (1.0 - r1) * kmax2
        + a3 * (1.0 - rho_e ** (2 * K0)) / (1.0 - r1)
        * (c3 / B + c4 * qK2 / sum_K)
    )
    return out if np.ndim(out) else float(out)


def c_diminishing(K0, Kn, B, gamma_d, rho_d, c, q_pairs, eps=None):
    """C_D — eq. (16) (upper bound used for optimization).  Broadcasts over
    an ndarray ``K0`` and a grid."""
    c1, c2, c3, c4 = c
    Kn = np.asarray(Kn, dtype=np.float64)
    q_pairs = np.asarray(q_pairs, dtype=np.float64)
    b1 = 1.0 / (rho_d * gamma_d)
    b2 = (rho_d**2 * gamma_d**2) / (rho_d + 1.0) ** 3 \
        + (rho_d**2 * gamma_d**2) / (2.0 * (rho_d + 1.0) ** 2)
    b3 = rho_d * gamma_d / (rho_d + 1.0) ** 2 + rho_d * gamma_d / (rho_d + 1.0)
    logt = np.log((K0 + rho_d + 1.0) / (rho_d + 1.0))
    sum_K, qK2, kmax2, B = _grid_terms(Kn, B, q_pairs, eps)
    out = (
        b1 * c1 / (logt * sum_K)
        + b2 * c2 * kmax2 / logt
        + b3 * c3 / (B * logt)
        + b3 * c4 * qK2 / (logt * sum_K)
    )
    return out if np.ndim(out) else float(out)


def c_m(m: str, K0, Kn, B, rule, c, q_pairs, eps=None) -> float:
    """Dispatch on the paper's m in {A, C, E, D}."""
    if m == "C":
        return c_constant(K0, Kn, B, rule.gamma, c, q_pairs, eps)
    if m == "E":
        return c_exponential(K0, Kn, B, rule.gamma, rule.rho, c, q_pairs, eps)
    if m == "D":
        return c_diminishing(K0, Kn, B, rule.gamma, rule.rho, c, q_pairs, eps)
    if m == "A":
        return c_arbitrary(K0, Kn, B, rule.sequence(int(round(K0))), c,
                           q_pairs, eps)
    raise ValueError(f"unknown convergence measure m={m!r}")
