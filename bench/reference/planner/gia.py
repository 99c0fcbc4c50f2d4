"""The GIA outer loop and integer recovery of the paper's Algorithms 2-3,
copied from the program's scalar NumPy path (``opt/gia.py``) for the
GenQSGD family with fixed step-size rules (m = C, E, D).

Each iteration condenses the problem at the previous point into a GP,
solves it with :func:`gp.solve_gp`, and stops once successive projected
expansion points move by less than ``tol``.  Integer recovery rounds the
continuous point down, to nearest and up, takes for each the smallest K0
that meets the convergence budget, and keeps the least-energy candidate.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

from .gp import solve_gp
from .problems import ParamOptProblem

__all__ = ["GIAResult", "solve_param_opt", "min_feasible_K0"]


@dataclasses.dataclass
class GIAResult:
    converged: bool
    feasible: bool
    iterations: int
    z: np.ndarray                  # final log-space point (continuous)
    K0: int
    Kn: np.ndarray                 # integer per-worker local iterations
    B: int
    E: float                       # true energy cost at the integer point
    T: float
    C: float


def _extract(problem: ParamOptProblem, z: np.ndarray):
    v = problem.vmap
    K0 = float(np.exp(v.K0.logvalue(z)))
    Kn = np.array([float(np.exp(k.logvalue(z))) for k in v.Kn])
    B = float(np.exp(v.B.logvalue(z)))
    extra = float(np.exp(v.extra.logvalue(z))) if v.extra is not None else None
    return K0, Kn, B, extra


def solve_param_opt(problem: ParamOptProblem,
                    z0: Optional[np.ndarray] = None,
                    tol: float = 1e-4, max_iter: int = 60,
                    dtype=np.float64) -> GIAResult:
    """GIA from ``z0`` (default ``problem.z_init()``); each GP solve runs
    its Newton iterations in ``dtype``."""
    if problem.m.value == "J":
        raise ValueError("the reference covers fixed step-size rules only")
    z = problem.z_init() if z0 is None else np.asarray(z0, dtype=np.float64)
    history: List[float] = []
    converged = False
    stall = 0
    for _ in range(max_iter):
        z = problem.project_expansion(z)
        res = solve_gp(problem.build(z), z, dtype=dtype)
        if not res.feasible:
            # the condensed problem can be infeasible away from a good
            # expansion point: rebuild at the phase-I minimizer and retry
            z = res.z
            stall += 1
            if stall > 8:
                break
            continue
        stall = 0
        step = float(np.max(np.abs(problem.project_expansion(res.z) - z)))
        z = res.z
        history.append(res.obj)
        if step < tol:
            converged = True
            break
    return _finalize(problem, z, len(history), converged)


def _finalize(problem: ParamOptProblem, z: np.ndarray, iterations: int,
              converged: bool) -> GIAResult:
    """Integer recovery + true-constraint evaluation at the continuous point."""
    _, _, _, extra = _extract(problem, z)
    K0i, Kni, Bi = _round_integer(problem, z, extra)
    ev = problem.evaluate(K0i, Kni, Bi, extra)
    return GIAResult(
        converged=converged,
        feasible=problem.feasible(K0i, Kni, Bi, extra),
        iterations=iterations, z=z, K0=K0i, Kn=Kni, B=Bi,
        E=ev["E"], T=ev["T"], C=ev["C"])


def min_feasible_K0(problem: ParamOptProblem, Kn, B,
                    extra: Optional[float] = None, K0_lo: int = 1,
                    ctol: float = 1e-9, ttol: float = 1e-9,
                    max_doublings: int = 200):
    """Smallest integer ``K0 >= K0_lo`` with ``C(K0) <= C_max*(1+ctol)``,
    by exponential bracketing and bisection (C is non-increasing and T
    non-decreasing in K0).  Returns ``(K0, ok)``, ``ok`` requiring
    ``T(K0) <= T_max*(1+ttol)`` as well."""
    C_cap = problem.C_max * (1 + ctol)
    T_cap = problem.T_max * (1 + ttol)
    ev = problem.evaluate(K0_lo, Kn, B, extra)
    if ev["C"] <= C_cap:
        return K0_lo, ev["T"] <= T_cap
    lo, hi = K0_lo, K0_lo
    for _ in range(max_doublings):
        if ev["T"] > problem.T_max:
            return hi, False            # time budget dies before C is met
        lo, hi = hi, hi * 2
        ev = problem.evaluate(hi, Kn, B, extra)
        if ev["C"] <= C_cap:
            break
    else:
        return hi, False
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if problem.evaluate(mid, Kn, B, extra)["C"] <= C_cap:
            hi = mid
        else:
            lo = mid
    return hi, problem.evaluate(hi, Kn, B, extra)["T"] <= T_cap


def _round_integer(problem: ParamOptProblem, z: np.ndarray,
                   extra: Optional[float]):
    """A feasible integer (K0, Kn, B) near the continuous optimum: the
    least-energy of the floor, round and ceil candidates, each with its
    smallest budget-meeting K0; the ceil point if none is feasible."""
    v = problem.vmap
    int_idx = [i for i, nm in enumerate(v.names)
               if nm == "K0" or nm.startswith("K") or nm in ("l", "B")]
    best = None
    for mode in (math.floor, round, math.ceil):
        zc = z.copy()
        for i in int_idx:
            zc[i] = np.log(max(1, mode(float(np.exp(z[i])))))
        K0f, Knf, Bf, _ = _extract(problem, zc)
        Kni = np.maximum(1, np.ceil(Knf - 1e-9)).astype(np.int64)
        Bi = max(1, int(round(Bf)))
        K0i, ok = min_feasible_K0(problem, Kni, Bi, extra,
                                  K0_lo=max(1, math.floor(K0f)))
        if not ok:
            continue
        E = problem.evaluate(K0i, Kni, Bi, extra)["E"]
        if best is None or E < best[3]:
            best = (K0i, Kni, Bi, E)
    if best is None:
        K0f, Knf, Bf, _ = _extract(problem, z)
        return (max(1, math.ceil(K0f)),
                np.maximum(1, np.ceil(Knf)).astype(np.int64),
                max(1, math.ceil(Bf)))
    return best[:3]
