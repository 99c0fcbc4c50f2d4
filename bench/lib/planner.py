"""What the planner drivers (``sweep``, ``serve``) share: the scenarios
of a configuration, for the program and for the plain reference, and the
comparison of answers with the reference solver.

A configuration file of kind ``edge_system`` holds the paper's system
(``system``), the problem constants (``consts``), the time budget
``T_max``, the step-size ``rules`` by name and the ``solver`` settings.
An answer is checked by solving its problem with the reference
(``bench/reference/planner``) from the same starting point: cold from
``z_init``, or from the cached point a warm-started request was seeded
with.  Two numbers come out: how many answers' integer plans (feasible,
K0, Kn, B) differ from the reference's, and the largest gap of the
continuous GIA point z, where the answer carries one.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from bench.reference import planner as REF

__all__ = ["Answer", "scenario", "reference_problem", "compare",
           "draw_budgets", "REF"]


@dataclasses.dataclass
class Answer:
    rule: str
    cmax: float
    z0: Optional[np.ndarray]         # the warm seed it was solved from
    feasible: bool
    K0: int
    Kn: Tuple[int, ...]
    B: int
    z: Optional[np.ndarray]          # its continuous point, where known


def _system_kwargs(config: dict) -> dict:
    s = config["system"]
    if s["kind"] != "paper_sec_vii":
        raise ValueError(f"unknown system kind {s['kind']!r}")
    return dict(dim=int(s["dim"]), F_ratio=float(s["F_ratio"]),
                s_ratio=float(s["s_ratio"]), s0=int(s["s0"]), N=int(s["N"]))


def scenario(config: dict, rule: str, cmax: float):
    """The program's ``Scenario`` for one request."""
    from repro.api import (ConstantRule, EdgeSystem, ExponentialRule,
                           MLProblemConstants, Scenario)

    r = config["rules"][rule]
    step = (ConstantRule(float(r["gamma"])) if r["kind"] == "constant"
            else ExponentialRule(float(r["gamma"]), float(r["rho"])))
    system = EdgeSystem.paper_sec_vii(**_system_kwargs(config))
    consts = MLProblemConstants(N=int(config["system"]["N"]),
                                **config["consts"])
    return Scenario(system=system, consts=consts,
                    T_max=float(config["T_max"]), C_max=float(cmax),
                    step=step)


def reference_problem(config: dict, rule: str, cmax: float):
    r = config["rules"][rule]
    system = REF.EdgeSystem.paper_sec_vii(**_system_kwargs(config))
    consts = REF.MLProblemConstants(N=int(config["system"]["N"]),
                                    **config["consts"])
    m = (REF.Objective.CONSTANT if r["kind"] == "constant"
         else REF.Objective.EXPONENTIAL)
    return REF.ParamOptProblem(
        sys=system, consts=consts, T_max=float(config["T_max"]),
        C_max=float(cmax), m=m, gamma=float(r["gamma"]), rho=r.get("rho"))


def compare(config: dict, answers: Sequence[Answer]) -> dict:
    """-> {"plan_mismatches": count, "z_gap": largest max|z - z_ref|}."""
    sv = config["solver"]
    mismatches, zgap = 0, 0.0
    for a in answers:
        r = REF.solve_param_opt(reference_problem(config, a.rule, a.cmax),
                                z0=a.z0, tol=float(sv["tol"]),
                                max_iter=int(sv["max_iter"]))
        if (bool(a.feasible), int(a.K0), tuple(int(k) for k in a.Kn),
                int(a.B)) != (bool(r.feasible), int(r.K0),
                              tuple(int(k) for k in r.Kn), int(r.B)):
            mismatches += 1
        if a.z is not None:
            zgap = max(zgap, float(np.max(np.abs(np.asarray(a.z) - r.z))))
    return {"plan_mismatches": mismatches, "z_gap": zgap}


def grid(lo: float, hi: float, n: int) -> np.ndarray:
    """The midpoints of ``n`` equal strata of [lo, hi]."""
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


def draw_budgets(run, rng) -> List[Tuple[str, float]]:
    """Cold requests of the cell's mix, as many as its check samples: a
    sweep's grid points, or a stream's new budgets."""
    t = run.traffic
    lo, hi = t["cmax"]
    n = int(t["check_sample"])
    if "points" in t:
        pts = rng.choice(grid(lo, hi, int(t["points"])), size=n,
                         replace=False)
        return [(t["rule"], float(c)) for c in pts]
    rules = list(t["rules"])
    return [(rules[i % len(rules)], float(rng.uniform(lo, hi)))
            for i in range(n)]
