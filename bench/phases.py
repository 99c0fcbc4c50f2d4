#!/usr/bin/env python3
"""Run one benchmark cell as ``bench/run.py`` does and, in a traced run,
split its time by what the program names in the trace.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        [--trace 0|1] [--obs 0|1] [--keep <dir>]

The result line is ``bench/run.py``'s, with one more key in a traced run,
``program`` (``bench/lib/program_trace.py``):

* ``spans``: count and seconds of each ``repro.*`` span in the trace;
* ``round``: device self seconds per run of the round program for each of
  its four named phases and the rest;
* ``sweep``: host seconds per sweep before and after the device solve, and
  the fused GIA's lockstep iterations per dispatch and active-row share.

``--obs 1`` turns ``repro.obs`` on for the run (its cost is this run's
end-to-end metrics against an ``--obs 0`` run of the same seed).
``--keep <dir>`` copies the trace's ``.xplane.pb`` there.  A traced run
keys the compilation cache with the programs' metadata, so that it runs
programs compiled from this tree's name stacks (the first such run of a
tree compiles).
"""
import os
import shutil
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.lib import harness as H  # noqa: E402
from bench.lib import program_trace as PT  # noqa: E402
from bench.lib import trace as T  # noqa: E402

#: the round program, as XLA names ``jax.jit(round_fn)`` in train/trainer.py
ROUND_PROGRAM = r"^jit_genqsgd_round(\(|$|\.)"


def program_summary(pt: PT.ProgramTrace, tr: T.Trace) -> dict:
    spans = {}
    for s in pt.spans:
        c = spans.setdefault(s.name, {"count": 0, "s": 0.0})
        c["count"] += 1
        c["s"] += s.seconds
    runs, _ = tr.program_time(ROUND_PROGRAM)
    return {"spans": spans, "round": PT.round_phases(pt, runs),
            "sweep": PT.sweep_stages(pt)}


def execute(cell: str, seed: int, seconds: float, trace: bool,
            keep=None, t0=None, **kw) -> dict:
    """``harness.execute``, keeping the program's view of the trace before
    the harness deletes it."""
    kept = []

    class _Run(H.Run):
        def read_trace(self):
            if self.trace_result is not None or self._trace_dir is None:
                return super().read_trace()
            path = T.find_xplane(self._trace_dir)
            pt = PT.load(path)
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(keep,
                                               f"{cell}.{seed}.xplane.pb"))
            tr = super().read_trace()
            kept.append(program_summary(pt, tr))
            return tr

    run_cls, H.Run = H.Run, _Run
    try:
        line = H.execute(cell, seed, seconds, trace, t0=t0, **kw)
    finally:
        H.Run = run_cls
    if kept:
        line["program"] = kept[0]
    return line


def main(argv=None, t0=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    if args.obs:
        from repro import obs
        obs.enable()
    if args.trace:
        import jax

        # name stacks are metadata, which the compilation cache leaves out
        # of its key by default: a cached program would carry the name
        # stacks of whichever tree compiled it first
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    try:
        line = execute(args.workload, args.seed, args.seconds,
                       bool(args.trace), keep=args.keep, t0=t0)
    except H.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t0=T0))
