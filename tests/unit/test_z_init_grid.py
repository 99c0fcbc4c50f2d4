"""z_init's feasible-start search as one broadcast over (grid point, K0 ladder).

The closed forms of :mod:`repro.core` take a leading grid axis — ``Kn`` of
shape (G, N), ``B`` (and a per-point step size) of shape (G,) — and return
(G, L) surfaces against an (L,) K0 ladder.  Row g must carry, bit for bit,
what grid point g gives alone: ``z_init`` picks its start from ``<=``
tests on those surfaces, and its start point must not move by an ulp.

  * every closed form on a grid equals the same form called once per row;
  * ``z_init`` equals, byte for byte, ``z_init`` run on the historical
    per-grid-point loop (kept here as a plain reference) across
    m x family x sampling x fault margins, and on the 256 budgets of the
    benchmark's Gen-C sweep of the paper's 10-worker system.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.api import (ConstantRule, DiminishingRule, EdgeSystem,
                       ExponentialRule, GQFedWAvgFamily, MLProblemConstants,
                       Scenario, edge_faults, uniform)
from repro.core import convergence as conv
from repro.core.cost import energy_cost, time_cost
from repro.opt.problems import Objective, ParamOptProblem

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N = 4
CONSTS = MLProblemConstants(L=0.084, sigma=33.18, G=33.63, f_gap=2.3, N=N)
SYS = EdgeSystem.paper_sec_vii(dim=64, N=N)

_STEP = {"C": dict(step=ConstantRule(0.01)),
         "J": dict(step=None),
         "E": dict(step=ExponentialRule(0.05, 0.9995)),
         "D": dict(step=DiminishingRule(0.02, 600.0))}
_FAMILY = {"genqsgd": "genqsgd",
           "gqfedwavg-w": GQFedWAvgFamily(key="gqfedwavg-w",
                                          weights=(4.0, 2.0, 1.0, 1.0))}
_SAMPLING = {"full": "full", "uniform-S2": uniform(S=2), "free-S": uniform()}
_FAULTS = {"none": "none",
           "margins": edge_faults(crash_prob=0.1, crash_rounds=2,
                                  freq_margin=0.2, rate_margin=0.1)}


def _grid_CTE_per_point(p, ks, Kn, B, gam_arr, S0=None):
    """The historical search: each scalar closed form once per grid point,
    over the K0 ladder."""
    c, qp = p._conv_coeffs(S0)
    pi = p._pi_at(S0)
    eps = p._agg_eps
    G, L = Kn.shape[0], ks.shape[0]
    C = np.empty((G, L))
    T = np.empty((G, L))
    E = np.empty((G, L))
    for g in range(G):
        if p.m is Objective.EXPONENTIAL:
            C[g] = conv.c_exponential(ks, Kn[g], B[g], p.gamma, p.rho, c, qp,
                                      eps)
        elif p.m is Objective.DIMINISHING:
            C[g] = conv.c_diminishing(ks, Kn[g], B[g], p.gamma, p.rho, c, qp,
                                      eps)
        else:
            gam = gam_arr[g] if p.m is Objective.JOINT else p.gamma
            C[g] = conv.c_constant(ks, Kn[g], B[g], gam, c, qp, eps)
        T[g] = time_cost(p.sys, ks, Kn[g], B[g], worst_case=True)
        E[g] = energy_cost(p.sys, ks, Kn[g], B[g], pi=pi)
    return C, T, E


def _z_init_with(monkeypatch, p, grid_cte):
    """``p.z_init()`` with its surfaces from ``grid_cte``; -> (z, the
    (C, T, E) of every cohort size it priced)."""
    surfaces = []

    def rec(ks, Kn, B, gam_arr, S0=None):
        out = grid_cte(p, ks, Kn, B, gam_arr, S0)
        surfaces.append(out)
        return out

    with monkeypatch.context() as mp:
        mp.setattr(p, "_grid_CTE", rec)
        z = p.z_init()
    return z, surfaces


def _assert_z_init_matches_oracle(monkeypatch, p):
    z, got = _z_init_with(monkeypatch, p, ParamOptProblem._grid_CTE)
    z_ref, want = _z_init_with(monkeypatch, p, _grid_CTE_per_point)
    assert z.tobytes() == z_ref.tobytes()
    assert len(got) == len(want) == len(p._init_S_levels())
    for cte, cte_ref in zip(got, want):
        for a, b in zip(cte, cte_ref):
            assert a.shape == b.shape and a.tobytes() == b.tobytes()
    assert p.init_grid_points == len(got) * got[0][0].shape[0]


@pytest.mark.parametrize("faults", list(_FAULTS))
@pytest.mark.parametrize("sampling", list(_SAMPLING))
@pytest.mark.parametrize("family", list(_FAMILY))
@pytest.mark.parametrize("m", list(_STEP))
def test_z_init_matches_per_point_oracle(monkeypatch, m, family, sampling,
                                         faults):
    p = Scenario(system=SYS, consts=CONSTS, T_max=1e5, C_max=0.25,
                 family=_FAMILY[family], sampling=_SAMPLING[sampling],
                 faults=_FAULTS[faults], **_STEP[m]).problem()
    if family != "genqsgd":
        assert p._agg_eps is not None
    if faults != "none":
        assert p.sys.freq_margin > 0 and p.sys.rate_margin > 0
    _assert_z_init_matches_oracle(monkeypatch, p)


def test_z_init_matches_oracle_on_sweep_budgets(monkeypatch):
    """The 256 budgets of the benchmark's Gen-C sweep of ``secvii-n10``."""
    with open(os.path.join(ROOT, "bench", "configs", "secvii-n10.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           "sweep.genC-256.json")) as f:
        traffic = json.load(f)
    s, rule = cfg["system"], cfg["rules"][traffic["rule"]]
    assert rule["kind"] == "constant"
    system = EdgeSystem.paper_sec_vii(dim=s["dim"], F_ratio=s["F_ratio"],
                                      s_ratio=s["s_ratio"], s0=s["s0"],
                                      N=s["N"])
    consts = MLProblemConstants(N=s["N"], **cfg["consts"])
    lo, hi = traffic["cmax"]
    n = traffic["points"]
    for c_max in lo + (hi - lo) * (np.arange(n) + 0.5) / n:
        p = Scenario(system=system, consts=consts, T_max=cfg["T_max"],
                     C_max=float(c_max),
                     step=ConstantRule(rule["gamma"])).problem()
        _assert_z_init_matches_oracle(monkeypatch, p)


# ---------------------------------------------------------------------------
# the closed forms on a (G, N) grid, row by row
# ---------------------------------------------------------------------------
def _squares_apart(rng, lo, hi, n):
    """``n`` values in [lo, hi) whose square a multiply rounds differently
    from ``pow``, the scalar's square (the arithmetic a grid row must
    repeat)."""
    cand = np.exp(rng.uniform(np.log(lo), np.log(hi), 400_000)).tolist()
    out = [v for v in cand if v * v != v ** 2][:n]
    assert len(out) == n
    return np.array(out)


def _grid_inputs(G=96):
    """Kn (G, N), B (G,), gamma (G,), the K0 ladder, q pairs and weights.
    Each row's largest K_n, and each gamma, is a value whose array square
    differs from its scalar one, so a grid that squared them as an array
    would show."""
    rng = np.random.default_rng(7)
    Kn = np.exp(rng.uniform(0.0, np.log(16.0), (G, N)))
    Kn[np.arange(G), np.arange(G) % N] = _squares_apart(rng, 16.0, 32.0, G)
    B = np.exp(rng.uniform(0.0, np.log(128.0), G))
    gam = _squares_apart(rng, 1e-3, 12.0, G)
    ladder = ParamOptProblem._k0_ladder()
    qp = rng.uniform(0.0, 2.0, N)
    eps = N * np.array([4.0, 2.0, 1.0, 1.0]) / 8.0
    return Kn, B, gam, ladder, qp, eps


_SYS_M = dataclasses.replace(SYS, freq_margin=0.2, rate_margin=0.1)
_C = CONSTS.c

#: name -> f(ks, Kn, B, gamma, qp, eps) for one form
_FORMS = {
    "c_constant": lambda ks, Kn, B, g, qp, eps: conv.c_constant(
        ks, Kn, B, 0.01, _C, qp, eps),
    "c_constant-gamma-per-point": lambda ks, Kn, B, g, qp, eps:
        conv.c_constant(ks, Kn, B, g, _C, qp, eps),
    "c_exponential": lambda ks, Kn, B, g, qp, eps: conv.c_exponential(
        ks, Kn, B, 0.05, 0.9995, _C, qp, eps),
    "c_diminishing": lambda ks, Kn, B, g, qp, eps: conv.c_diminishing(
        ks, Kn, B, 0.02, 600.0, _C, qp, eps),
    "time_cost": lambda ks, Kn, B, g, qp, eps: time_cost(_SYS_M, ks, Kn, B),
    "time_cost-worst_case": lambda ks, Kn, B, g, qp, eps: time_cost(
        _SYS_M, ks, Kn, B, worst_case=True),
    "energy_cost": lambda ks, Kn, B, g, qp, eps: energy_cost(
        _SYS_M, ks, Kn, B),
    "energy_cost-pi": lambda ks, Kn, B, g, qp, eps: energy_cost(
        _SYS_M, ks, Kn, B, pi=np.array([0.9, 0.5, 0.5, 0.25])),
}


@pytest.mark.parametrize("weighted", [False, True], ids=["uniform", "eps"])
@pytest.mark.parametrize("form", list(_FORMS))
def test_closed_form_grid_rows_bitwise(form, weighted):
    Kn, B, gam, ks, qp, eps = _grid_inputs()
    eps = eps if weighted else None
    f = _FORMS[form]
    grid = f(ks, Kn, B, gam, qp, eps)
    assert grid.shape == (Kn.shape[0], ks.shape[0])
    for g in range(Kn.shape[0]):
        row = f(ks, Kn[g], B[g], gam[g], qp, eps)
        assert row.shape == ks.shape
        assert row.tobytes() == grid[g].tobytes(), (form, g)
        # a scalar K0 still gives a float, as before
        assert type(f(ks[5], Kn[g], B[g], gam[g], qp, eps)) is float
