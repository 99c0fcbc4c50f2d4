"""Driver ``sweep``: back-to-back ``Scenario.sweep`` calls on one signature.

Each sweep asks for ``points`` plans over the C_max budgets at the
midpoints of ``points`` equal strata of ``cmax``, in an order drawn from
the seed.  Every seed so asks for the same work: which budgets a sweep
holds decides how many Newton steps its slowest row takes, and with it the
sweep's time (budgets drawn fresh per seed moved it by 25%).
``Scenario.sweep`` keeps no cache, so a repeated sweep reuses nothing.
Set-up runs one sweep, which compiles the fused GIA program (or loads it
from the persistent cache).  The window runs every sweep started within
``--seconds`` and ends when the last returns.

The sweep's plans carry no continuous point, so the driver records the
``GIAResult`` rows the sweep's batched solve returns as they pass (a
pass-through around ``repro.api.sweep.solve_param_opt_batched``).  After
the window a sample of ``check_sample`` answers, drawn from the seed, is
solved cold by the reference and compared (``bench/lib/planner.py``).

Traffic keys: ``rule``, ``points``, ``cmax`` ([lo, hi]), ``check_sample``,
``limits``, ``trace_seconds``.
"""
from __future__ import annotations

import time

from bench.lib import planner as PC
from bench.lib.harness import Check, Outcome


class _Recorder:
    """Keeps the rows a wrapped batched solve returns, in call order."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def __call__(self, *args, **kw):
        out = self.fn(*args, **kw)
        self.calls.append(out)
        return out


def run(run) -> Outcome:
    from repro.api import sweep as SW

    t = run.traffic
    cfg = run.config
    rule, n = t["rule"], int(t["points"])
    lo, hi = t["cmax"]
    base = PC.scenario(cfg, rule, lo)
    rng = run.rng(1)
    sv = cfg["solver"]

    def one_sweep(budgets):
        return base.sweep(over={"cmax": budgets}, tol=float(sv["tol"]),
                          max_iter=int(sv["max_iter"]))

    budgets_all = PC.grid(lo, hi, n)
    # set-up: the fused program for this shape, compiled or loaded
    one_sweep(rng.permutation(budgets_all))

    rec = _Recorder(SW.solve_param_opt_batched)
    SW.solve_param_opt_batched = rec
    sweeps = []
    try:
        with run.window():
            start = time.perf_counter()
            while time.perf_counter() - start < run.seconds:
                budgets = rng.permutation(budgets_all)
                t0 = time.perf_counter()
                with run.span("scenario.sweep"):
                    rep = one_sweep(budgets)
                sweeps.append((budgets, rep, time.perf_counter() - t0))
                run.between()
    finally:
        SW.solve_param_opt_batched = rec.fn
    points = sum(len(rep) for _, rep, _ in sweeps)
    run.note(f"{len(sweeps)} sweeps of {n} points in the window, "
             f"seconds each: {[round(dt, 4) for *_, dt in sweeps]}")

    # the check: a seeded sample of the window's answers against the
    # reference, each solved cold as the sweep solved it
    answers = []
    for (budgets, rep, _), rows in zip(sweeps, rec.calls):
        for i, (c, plan) in enumerate(zip(budgets, rep.plans)):
            answers.append(PC.Answer(rule, float(c), None, plan.feasible,
                                     plan.K0, tuple(plan.Kn), plan.B,
                                     rows[i].z))
    pick = run.rng(3).choice(len(answers),
                             size=min(int(t["check_sample"]), len(answers)),
                             replace=False)
    t0 = time.perf_counter()
    got = PC.compare(cfg, [answers[i] for i in sorted(pick)])
    run.note(f"reference solved {len(pick)} sampled answers in "
             f"{time.perf_counter() - t0:.1f} s")
    lim = t["limits"]
    checks = [Check("plan_mismatches", got["plan_mismatches"],
                    lim["plan_mismatches"]),
              Check("z_gap", got["z_gap"], lim["z_gap"])]
    infeasible = sum(not a.feasible for a in answers)
    return Outcome(attempted=points, failed=got["plan_mismatches"],
                   end_to_end={"plans_per_s": points / run.window_s},
                   checks=checks,
                   counters={"sweeps": len(sweeps), "points": points,
                             "infeasible": infeasible})
