"""Driver ``serve``: an open-loop stream of plan requests to one PlanServer.

Set-up starts the measured server and fills its plan cache with a hot set:
``hot.n`` budgets in ``hot.cmax`` under each of ``rules``, submitted at
once, so each signature's fused program is compiled (or loaded) and every
hot plan is solved cold.  The window then offers ``rate`` requests per
second for ``--seconds``, open loop: ``round(rate * seconds)`` arrival
times drawn uniform over the window (a Poisson stream conditioned on its
count), with exact shares of exact repeats of the hot set (cache hits),
near-duplicates with the budget jittered by up to ``jitter`` (warm
starts) and new budgets uniform in ``cmax`` (cold), rules in equal shares.
So every seed offers the same work in another order.  A request's latency
runs from when it was due, so a late generator counts; a request that
fails, or has not returned ``drain_s`` after the last was due, counts as
waiting until then.

The check draws ``check_sample`` of the window's requests from the seed,
one of each source at least, and solves each with the reference from the
point the server started it from: cold from ``z_init``, or the cached
point a warm start was seeded with.  The server's continuous point is read
from its plan cache, where every converged answer is kept.

Traffic keys: ``rules``, ``hot`` ({n, cmax}), ``mix`` ({hit, warm,
cold}), ``jitter``, ``cmax``, ``rate``, ``drain_s``, ``check_sample``,
``limits``, ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import planner as PC
from bench.lib.harness import Check, Outcome


def schedule(rng, t: dict, seconds: float, hot: list):
    """-> sorted [(due_s, rule, cmax, source)]: the window's requests."""
    n = int(round(float(t["rate"]) * seconds))
    mix = t["mix"]
    n_hit = int(round(n * mix["hit"]))
    n_warm = int(round(n * mix["warm"]))
    sources = (["hit"] * n_hit + ["warm"] * n_warm
               + ["cold"] * (n - n_hit - n_warm))
    rng.shuffle(sources)
    lo, hi = t["cmax"]
    rules = list(t["rules"])
    reqs = []
    for i, src in enumerate(sources):
        if src == "cold":
            rule, c = rules[i % len(rules)], float(rng.uniform(lo, hi))
        else:
            rule, c = hot[int(rng.integers(len(hot)))]
            if src == "warm":
                c *= 1.0 + float(rng.uniform(-t["jitter"], t["jitter"]))
        reqs.append((rule, c, src))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return [(float(d), *r) for d, r in zip(due, reqs)]


def run(run) -> Outcome:
    from repro.serve import PlanServer

    t = run.traffic
    cfg = run.config
    rules = list(t["rules"])
    hot = [(r, float(c)) for r in rules
           for c in np.linspace(*t["hot"]["cmax"], int(t["hot"]["n"]))]
    reqs = schedule(run.rng(1), t, run.seconds, hot)
    srv = PlanServer(max_batch=int(cfg["planserver"]["max_batch"]),
                     window_s=float(cfg["planserver"]["window_s"]),
                     tol=float(cfg["solver"]["tol"]),
                     max_iter=int(cfg["solver"]["max_iter"]))
    try:
        # set-up: compile each signature and fill the plan cache
        for h in [srv.submit(PC.scenario(cfg, r, c)) for r, c in hot]:
            h.result(timeout=1200)
        base = srv.stats()
        handles = []
        with run.window():
            start = time.perf_counter()
            for due, rule, c, src in reqs:
                wait = start + due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                with run.span("planserver.submit"):
                    h = srv.submit(PC.scenario(cfg, rule, c))
                handles.append((start + due, rule, c, src, h))
                run.between()
            deadline = start + run.seconds + float(t["drain_s"])
            with run.span("drain"):
                for *_, h in handles:
                    try:
                        h.result(max(0.0, deadline - time.perf_counter()))
                    except (TimeoutError, RuntimeError):
                        pass            # counted below as failed
        stats = srv.stats()
    finally:
        srv.close()

    lat, late, failed = [], [], 0
    for due, rule, c, src, h in handles:
        late.append(h.t_submit - due)
        if h.done() and h.error is None:
            lat.append(h.t_done - due)
        else:
            failed += 1
            lat.append(deadline - due)
    lat = np.asarray(lat)
    sources = [s for *_, s, _ in handles]
    served = {s: sum(h.source == s for *_, h in handles)
              for s in ("hit", "warm", "cold")}
    run.note(f"{len(handles)} requests due in the window (offered "
             f"{sources.count('hit')} repeats, {sources.count('warm')} "
             f"near-duplicates, {sources.count('cold')} new; served "
             f"{served}), {failed} failed or unfinished; latency p50 "
             f"{np.percentile(lat, 50):.3f} s, p95 "
             f"{np.percentile(lat, 95):.3f} s, max {lat.max():.3f} s")
    half = len(lat) // 2
    if half:
        run.note(f"latency p95 of the first half due "
                 f"{np.percentile(lat[:half], 95):.3f} s, of the second "
                 f"{np.percentile(lat[half:], 95):.3f} s (a growing "
                 f"backlog shows as the second exceeding the first)")
    run.note(f"generator lateness p50 {np.percentile(late, 50) * 1e3:.3f} "
             f"ms, p95 {np.percentile(late, 95) * 1e3:.3f} ms, max "
             f"{max(late) * 1e3:.3f} ms")
    traced = (sum(stats["compiles"].values())
              - sum(base["compiles"].values()))
    run.note(f"fused programs traced in the window: {traced}; "
             f"dispatches {stats['batches'] - base['batches']}")

    # the check: a seeded sample, one of each source at least
    rng = run.rng(3)
    by_src = {}
    for i, (*_, h) in enumerate(handles):
        by_src.setdefault(h.source, []).append(i)
    pick = {int(rng.choice(v)) for v in by_src.values()}
    rest = [i for i in range(len(handles)) if i not in pick]
    k = max(0, min(int(t["check_sample"]) - len(pick), len(rest)))
    pick |= {int(i) for i in rng.choice(rest, size=k, replace=False)}
    answers, unanswered = [], 0
    for i in sorted(pick):
        due, rule, c, src, h = handles[i]
        if h.plan is None:
            unanswered += 1
            continue
        entry = srv.cache.get(h.sig, h.fp)
        p = h.plan
        answers.append(PC.Answer(rule, c, h.z0, p.feasible, p.K0,
                                 tuple(p.Kn), p.B,
                                 None if entry is None else entry.result.z))
    t0 = time.perf_counter()
    got = PC.compare(cfg, answers)
    run.note(f"reference solved {len(answers)} sampled answers "
             f"({sorted(handles[i][4].source for i in pick)}) in "
             f"{time.perf_counter() - t0:.1f} s")
    lim = t["limits"]
    checks = [Check("failed_requests", failed, lim["failed_requests"]),
              Check("plan_mismatches", got["plan_mismatches"] + unanswered,
                    lim["plan_mismatches"]),
              Check("z_gap", got["z_gap"], lim["z_gap"])]
    # the window's share of the server's own counters: queue wait as the
    # server records it (taken - submitted), rows per dispatch from its
    # batch_rows histogram less the set-up's dispatches
    waits = [h.t_taken - h.t_submit for *_, h in handles
             if h.t_taken is not None]
    batches = stats["batches"] - base["batches"]
    rows = (stats["mean_batch"] * stats["batches"]
            - base["mean_batch"] * base["batches"])
    return Outcome(
        attempted=len(handles), failed=failed,
        end_to_end={"plan_p95_s": float(np.percentile(lat, 95))},
        checks=checks,
        counters={"queue_wait_p50_s": (float(np.median(waits)) if waits
                                       else None),
                  "p95_halves": ([float(np.percentile(lat[:half], 95)),
                                  float(np.percentile(lat[half:], 95))]
                                 if half else None),
                  "mean_batch": rows / batches if batches else None,
                  "served": served})
