"""The readings that set the upper end of each limit: the precision control
and the planted faults, compared with the plain reference exactly as a run
compares the program.

    python3 bench/tests/controls.py --workload <cell> --seeds 11 12 13

For a ``round`` cell the control is the reference with every matmul
operand rounded to float8 (e4m3), the precision below the configuration's
bfloat16, put in the program's place; the planted fault is half of each
batch left out, the mean taken over the rest.  A state left unchanged
reads 1 on the parameter-change numbers by their definition and needs no
run.  For a ``sweep`` or ``serve`` cell the control is the reference
solver computing its GP solves in float32 instead of float64, and the
planted faults are a solve that returns its starting point unchanged and
an answer altered where it is produced (K0 + 1).

The benchmark's own runs never run this.  It prints one JSON line per
seed and case, and needs the chips the cell asks for unless ``--cpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402

__all__ = ["round_readings", "planner_readings", "main"]


def round_readings(run, cases=("control", "half_batch")) -> dict:
    """-> {case: {number: reading}} for a ``round`` cell: each case's
    reference run compared with the float32 reference."""
    import numpy as np

    from bench.drivers import round as R

    job = R.Job(run)
    n = int(run.traffic["check_rounds"])
    ref = R.reference_rounds(job, n)
    keep = ref["grad_norms"] >= 1e-3 * np.median(ref["grad_norms"])
    out = {}
    for case in cases:
        import jax.numpy as jnp
        kw = ({"matmul_dtype": jnp.float8_e4m3fn} if case == "control"
              else {"rows": job.B // 2})
        got = R.reference_rounds(job, n, **kw)
        out[case] = {
            "loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(got["losses"], ref["losses"])),
            "update_gap": R.gap_by_leaf(got["first_update"],
                                        ref["first_update"], keep),
            "change_gap": R.gap_by_leaf(got["change"], ref["change"], keep),
        }
    return out


def planner_readings(run, cases=("control", "unchanged", "altered")) -> dict:
    """-> {case: {number: reading}} for a ``sweep`` or ``serve`` cell, over
    the answers the cell's check samples from a seed's cold requests."""
    import numpy as np

    from bench.lib import planner as PC

    rng = run.rng(9)
    reqs = PC.draw_budgets(run, rng)
    out = {}
    for case in cases:
        answers = []
        for rule, cmax in reqs:
            prob = PC.reference_problem(run.config, rule, cmax)
            if case == "control":
                r = PC.REF.solve_param_opt(prob, dtype=np.float32)
            elif case == "unchanged":
                r = PC.REF.solve_param_opt(prob, max_iter=0)
            else:
                r = PC.REF.solve_param_opt(prob)
                r.K0 += 1
            answers.append(PC.Answer(rule, cmax, None, r.feasible, r.K0,
                                     tuple(int(k) for k in r.Kn), r.B, r.z))
        out[case] = PC.compare(run.config, answers)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cases", nargs="*")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    spec = harness.Spec.load(args.workload)
    if not args.cpu:
        ok, _, msg = harness._device_ok(int(spec.cell["chips"]))
        if not ok:
            print(f"controls: {msg}", file=sys.stderr)
            return 2
        harness.enable_compile_cache()
    fn = (round_readings if spec.traffic["driver"] == "round"
          else planner_readings)
    for seed in args.seeds:
        run = harness.Run(spec, seed, 0.0, False, time.perf_counter(),
                          on_chip=not args.cpu)
        t0 = time.perf_counter()
        kw = {"cases": tuple(args.cases)} if args.cases else {}
        res = fn(run, **kw)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "readings": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
