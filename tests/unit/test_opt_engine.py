"""Batched solver engine: padded-structure packing, NumPy-vs-jnp backend
parity, lockstep-batched GIA vs the scalar loop, bisection integer
recovery, and the Scenario sweep / Pareto API.

The fast subset runs in tier-1; the full (m, family) grid parity sweep is
marked slow (it compiles one jnp program per structure signature).
"""
import dataclasses

import numpy as np
import pytest

from repro.api import (ConstantRule, DiminishingRule, EdgeSystem,
                       ExponentialRule, MLProblemConstants, Objective,
                       Scenario, SweepReport, family_names, sweep_scenarios)
from repro.opt import (GPStructure, ParamOptProblem, min_feasible_K0,
                       min_feasible_K0_joint, solve_gp, solve_gp_batch,
                       solve_param_opt, solve_param_opt_batched,
                       structure_signature)
from repro.opt.gp import _Batched

CONSTS = MLProblemConstants(L=0.084, sigma=33.18, G=33.63, f_gap=2.3, N=4)

STEPS = {
    Objective.CONSTANT: ConstantRule(0.01),
    Objective.EXPONENTIAL: ExponentialRule(0.02, 0.9995),
    Objective.DIMINISHING: DiminishingRule(0.02, 600.0),
    Objective.JOINT: None,
}


def _scenario(family, m, C_max=0.25, T_max=1e5):
    sys_ = EdgeSystem.paper_sec_vii(dim=1024, N=4)
    return Scenario(system=sys_, consts=CONSTS, T_max=T_max, C_max=C_max,
                    family=family, step=STEPS[m])


def _problems(family, m, budgets=(0.22, 0.25, 0.3)):
    return [_scenario(family, m, C_max=c).problem() for c in budgets]


# ---------------------------------------------------------------------------
# structure packing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", list(Objective))
def test_packed_system_matches_unpadded(m):
    """Padding terms contribute exactly zero: per-constraint log-values of
    the packed arrays equal the unpadded reference for every instance."""
    probs = _problems("genqsgd", m)
    zs = [p.z_init() for p in probs]
    st = GPStructure(probs[0])
    pack = st.pack_batch(probs, zs)
    assert pack.batch == len(probs)
    for i, gp in enumerate(pack.gps):
        ref = _Batched(gp)
        z = pack.z0[i]
        t = pack.con_logc[i] + pack.con_A[i] @ z
        mx = np.full(pack.m_cons, -np.inf)
        np.maximum.at(mx, pack.seg, t)
        s = np.zeros(pack.m_cons)
        np.add.at(s, pack.seg, np.exp(t - mx[pack.seg]))
        g_packed = mx + np.log(s)
        assert np.allclose(g_packed, ref.g(z), rtol=1e-12, atol=1e-12)


def test_structure_signature_grouping():
    pc = _scenario("genqsgd", Objective.CONSTANT).problem()
    pc2 = _scenario("genqsgd", Objective.CONSTANT, C_max=0.4).problem()
    pe = _scenario("genqsgd", Objective.EXPONENTIAL).problem()
    pm = _scenario("pm", Objective.CONSTANT).problem()
    assert structure_signature(pc) == structure_signature(pc2)
    assert structure_signature(pc) != structure_signature(pe)
    assert structure_signature(pc) != structure_signature(pm)
    with pytest.raises(ValueError, match="structure"):
        GPStructure(pc).pack_batch([pe], [pe.z_init()])
    with pytest.raises(ValueError, match="signature"):
        solve_param_opt_batched([pc, pe], backend="numpy")


# ---------------------------------------------------------------------------
# backend parity: one batched GP solve
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [Objective.CONSTANT, Objective.JOINT])
def test_gp_backends_agree_fast(m):
    probs = _problems("genqsgd", m)
    st = GPStructure(probs[0])
    pack = st.pack_batch(probs, [p.z_init() for p in probs])
    rn = solve_gp_batch(pack, backend="numpy")
    rj = solve_gp_batch(pack, backend="jnp")
    assert np.array_equal(rn.feasible, rj.feasible)
    assert np.allclose(rn.z, rj.z, atol=1e-6)
    assert np.allclose(rn.obj, rj.obj, rtol=1e-8)


# ---------------------------------------------------------------------------
# fused device-resident GIA (backend="jnp-fused")
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family,m", [
    ("genqsgd", Objective.CONSTANT),
    ("genqsgd", Objective.EXPONENTIAL),
    ("pm", Objective.DIMINISHING),
    ("genqsgd", Objective.JOINT),
    ("gqfedwavg", Objective.CONSTANT),
    ("gqfedwavg", Objective.JOINT),
])
def test_fused_gia_matches_numpy_fast(family, m):
    """The fused single-while-loop engine lands on the NumPy reference:
    same feasibility, same GIA iteration counts and history length, same
    integer recovery, continuous point to 1e-5."""
    rn = solve_param_opt_batched(_problems(family, m), backend="numpy")
    rf = solve_param_opt_batched(_problems(family, m), backend="jnp-fused")
    for a, b in zip(rn, rf):
        assert a.feasible == b.feasible
        assert a.iterations == b.iterations
        assert np.allclose(a.z, b.z, atol=1e-5)
        if a.feasible:
            assert (a.K0, a.B) == (b.K0, b.B)
            assert np.array_equal(a.Kn, b.Kn)
            assert b.E == pytest.approx(a.E, rel=1e-9)
        assert b.history == pytest.approx(a.history, rel=1e-9)


def test_fused_one_compile_per_signature():
    """Re-solving a same-signature batch reuses the compiled fused program —
    the whole GIA (refresh included) stays on device with zero host round
    trips per outer iteration, so the trace counter must not move."""
    from repro.opt import gia_jax
    from repro.opt.refresh import RefreshPlan

    probs = _problems("genqsgd", Objective.CONSTANT)
    key = RefreshPlan.build(probs).signature_key
    solve_param_opt_batched(probs, backend="jnp-fused")
    n1 = gia_jax.trace_count(key)
    assert n1 >= 1
    solve_param_opt_batched(
        _problems("genqsgd", Objective.CONSTANT, budgets=(0.21, 0.26, 0.31)),
        backend="jnp-fused")
    assert gia_jax.trace_count(key) == n1


def test_fused_stalled_instance_regression():
    """A hopeless instance inside a fused batch (budgets no point can meet;
    its GIA stalls out through phase-I retries) must neither crash the
    device-side refresh nor stretch the healthy instances' lockstep: the
    healthy row's iterations, history, and solution match its solo solve."""
    healthy = _scenario("genqsgd", Objective.CONSTANT, C_max=0.25).problem()
    hopeless = _scenario("genqsgd", Objective.CONSTANT, C_max=1e-9,
                         T_max=10.0).problem()
    solo = solve_param_opt_batched([healthy], backend="jnp-fused")[0]
    bad, good = solve_param_opt_batched([hopeless, healthy],
                                        backend="jnp-fused")
    assert not bad.feasible and not bad.converged
    assert good.feasible == solo.feasible
    assert good.iterations == solo.iterations
    # rows are independent up to XLA's batch-shape-dependent vectorization
    assert good.history == pytest.approx(solo.history, rel=1e-12)
    assert np.allclose(good.z, solo.z, atol=1e-9)
    assert (good.K0, good.B) == (solo.K0, solo.B)
    assert np.array_equal(good.Kn, solo.Kn)


def _assert_warm_matches_cold(family, m, tol, z_atol):
    """Warm-starting a solve at its own cold solution must reach the same
    KKT point: 1-3 GIA iterations (no cold phase-I), continuous point to
    ``z_atol``, identical integer recovery."""
    budgets = (0.22, 0.25, 0.3)
    cold = solve_param_opt_batched(_problems(family, m, budgets),
                                   backend="jnp-fused", tol=tol)
    warm = solve_param_opt_batched(_problems(family, m, budgets),
                                   z0s=[r.z for r in cold],
                                   backend="jnp-fused", tol=tol,
                                   joint_restart=False)
    for c, w in zip(cold, warm):
        if not c.converged:
            continue                  # nothing cached seeds from such a row
        assert w.converged
        assert 1 <= w.iterations <= 3
        assert np.allclose(w.z, c.z, atol=z_atol)
        assert c.feasible == w.feasible
        if c.feasible:
            assert (c.K0, c.B) == (w.K0, w.B)
            assert np.array_equal(c.Kn, w.Kn)
            assert w.E == pytest.approx(c.E, rel=1e-9)


@pytest.mark.parametrize("family,m", [
    ("genqsgd", Objective.CONSTANT),
    ("genqsgd", Objective.JOINT),
])
def test_warm_start_reaches_cold_kkt_fast(family, m):
    # measured fixed-point accuracy at tol=1e-8: C/D/J ~1e-9, E ~4e-9
    _assert_warm_matches_cold(family, m, tol=1e-8, z_atol=1e-8)


@pytest.mark.slow
@pytest.mark.serve
@pytest.mark.families
@pytest.mark.parametrize("family", family_names())
@pytest.mark.parametrize("m", list(Objective))
def test_warm_start_reaches_cold_kkt_full_grid(family, m):
    """Warm-start correctness over the whole (m, family) grid: the plan
    cache may only ever hand out seeds that re-converge to the cold
    answer."""
    _assert_warm_matches_cold(family, m, tol=1e-8, z_atol=1e-8)


def test_fused_mixed_warm_cold_with_stalled_row():
    """PR-4 stalled-row regression, extended with warm/cold mixing: a
    stalled/infeasible row inside a mixed warm/cold micro-batch must not
    perturb the healthy rows — the warm row still converges in 1-3
    iterations onto its cold KKT point, the cold row matches its solo
    solve, and the padding rows of a fixed-shape dispatch change nothing."""
    healthy = _scenario("genqsgd", Objective.CONSTANT, C_max=0.25).problem()
    other = _scenario("genqsgd", Objective.CONSTANT, C_max=0.3).problem()
    hopeless = _scenario("genqsgd", Objective.CONSTANT, C_max=1e-9,
                         T_max=10.0).problem()
    solo_h = solve_param_opt_batched([healthy], backend="jnp-fused")[0]
    solo_o = solve_param_opt_batched([other], backend="jnp-fused")[0]

    mixed = solve_param_opt_batched(
        [_scenario("genqsgd", Objective.CONSTANT, C_max=0.25).problem(),
         _scenario("genqsgd", Objective.CONSTANT, C_max=1e-9,
                   T_max=10.0).problem(),
         _scenario("genqsgd", Objective.CONSTANT, C_max=0.3).problem()],
        z0s=[solo_h.z, None, None], backend="jnp-fused", pad_to=8)
    warm, bad, cold = mixed
    assert not bad.feasible and not bad.converged
    assert warm.converged and 1 <= warm.iterations <= 3
    assert np.allclose(warm.z, solo_h.z, atol=1e-6)
    assert (warm.K0, warm.B) == (solo_h.K0, solo_h.B)
    assert np.array_equal(warm.Kn, solo_h.Kn)
    assert cold.iterations == solo_o.iterations
    assert cold.history == pytest.approx(solo_o.history, rel=1e-12)
    assert np.allclose(cold.z, solo_o.z, atol=1e-9)
    assert (cold.K0, cold.B) == (solo_o.K0, solo_o.B)


def test_fused_pad_to_rows_bitwise_unchanged():
    """Padding a fused batch to a fixed shape (the serving path) is a
    bitwise no-op for the real rows."""
    ref = solve_param_opt_batched(
        _problems("genqsgd", Objective.CONSTANT), backend="jnp-fused")
    pad = solve_param_opt_batched(
        _problems("genqsgd", Objective.CONSTANT), backend="jnp-fused",
        pad_to=8)
    assert len(pad) == 3
    for a, b in zip(ref, pad):
        assert np.array_equal(a.z, b.z)
        assert a.history == b.history
        assert a.iterations == b.iterations
        assert (a.K0, a.B, a.E) == (b.K0, b.B, b.E)


def test_fused_lockstep_counters():
    """The fused program counts its while loop's trips and, per row, the
    lockstep iterations in which the row was still active: the loop runs
    until its slowest row stops, so the trip count is the largest row
    count.  Padding rows are not reported, and the counters reach the
    ``gia.finalize`` span and, with obs on, the registry."""
    from repro import obs
    from repro.opt.gia_jax import solve_gia_fused

    probs = _problems("genqsgd", Objective.CONSTANT,
                      budgets=(0.22, 0.25, 0.3, 0.6))
    out = solve_gia_fused(probs, None, 1e-4, 60, pad_to=8)
    assert out.active_iters.shape == (4,) and out.z.shape[0] == 4
    assert out.lockstep_iters == int(out.active_iters.max())
    obs.enable(reset=True)
    try:
        res = solve_param_opt_batched(probs, backend="jnp-fused", pad_to=8)
        fin = [e for e in obs.TRACER.events() if e["name"] == "gia.finalize"]
        snap = obs.REGISTRY.snapshot()
    finally:
        obs.disable()
        obs.TRACER.clear()
        obs.REGISTRY.reset()
    assert [e["args"] for e in fin] == [{
        "lockstep_iters": out.lockstep_iters,
        "active_row_iters": int(out.active_iters.sum()), "rows": 4}]
    assert snap['gia.lockstep_iters{backend="jnp-fused"}'] == \
        out.lockstep_iters
    assert snap['gia.active_row_iters{backend="jnp-fused"}'] == \
        int(out.active_iters.sum())
    for i, r in enumerate(res):
        assert np.array_equal(r.z, out.z[i]) and r.history == out.history(i)
        # every GIA iteration takes at least one lockstep Newton step
        assert 1 <= r.iterations <= out.active_iters[i]


def test_optimize_fused_compile_cache_is_process_level():
    """Repeated ``Scenario.optimize(backend='jnp-fused')`` calls across
    *distinct* Scenario objects reuse the compiled fused executable: the
    cache is the process-level LRU in repro.opt.gia_jax, keyed by structure
    signature — not tied to any Scenario / sweep / GPStructure instance —
    so the trace counter must stay flat after the first call."""
    from repro.opt import gia_jax
    from repro.opt.refresh import RefreshPlan

    key = RefreshPlan.build(
        [_scenario("genqsgd", Objective.CONSTANT).problem()]).signature_key
    p1 = _scenario("genqsgd", Objective.CONSTANT,
                   C_max=0.24).optimize(backend="jnp-fused")
    n1 = gia_jax.trace_count(key)
    assert n1 >= 1
    p2 = _scenario("genqsgd", Objective.CONSTANT,
                   C_max=0.28).optimize(backend="jnp-fused")
    assert gia_jax.trace_count(key) == n1
    assert p1.feasible and p2.feasible
    # and the scalar reference agrees with the fused single-row solve
    ref = _scenario("genqsgd", Objective.CONSTANT, C_max=0.28).optimize()
    assert p2.K0 == ref.K0 and p2.B == ref.B and p2.Kn == ref.Kn


def test_gp_batch_numpy_rows_equal_scalar_solver():
    probs = _problems("genqsgd", Objective.CONSTANT)
    st = GPStructure(probs[0])
    pack = st.pack_batch(probs, [p.z_init() for p in probs])
    rb = solve_gp_batch(pack, backend="numpy")
    for i, gp in enumerate(pack.gps):
        r = solve_gp(gp, pack.z0[i])
        assert np.array_equal(r.z, rb.z[i])
        assert r.obj == rb.obj[i] and r.feasible == rb.feasible[i]


def test_unknown_backend_rejected():
    probs = _problems("genqsgd", Objective.CONSTANT)
    st = GPStructure(probs[0])
    pack = st.pack_batch(probs, [p.z_init() for p in probs])
    with pytest.raises(ValueError, match="unknown GP backend"):
        solve_gp_batch(pack, backend="cvxpy")


# ---------------------------------------------------------------------------
# batched GIA vs the scalar loop
# ---------------------------------------------------------------------------
def test_batched_numpy_gia_identical_to_sequential():
    """backend="numpy" lockstep is the scalar loop row-for-row (bitwise)."""
    for m in (Objective.CONSTANT, Objective.DIMINISHING):
        seq = [solve_param_opt(p) for p in _problems("genqsgd", m)]
        bat = solve_param_opt_batched(_problems("genqsgd", m),
                                      backend="numpy")
        for r, b in zip(seq, bat):
            assert np.array_equal(r.z, b.z)
            assert (r.K0, r.B, r.feasible, r.converged, r.iterations) == \
                (b.K0, b.B, b.feasible, b.converged, b.iterations)
            assert np.array_equal(r.Kn, b.Kn)
            assert r.E == b.E and r.history == b.history


@pytest.mark.parametrize("family,m", [
    ("genqsgd", Objective.CONSTANT),
    ("genqsgd", Objective.JOINT),
    ("pm", Objective.DIMINISHING),
])
def test_batched_jnp_gia_matches_scalar_fast(family, m):
    seq = [solve_param_opt(p) for p in _problems(family, m)]
    bat = solve_param_opt_batched(_problems(family, m), backend="jnp")
    for r, b in zip(seq, bat):
        assert r.feasible == b.feasible
        assert np.allclose(r.z, b.z, atol=1e-5)
        assert (r.K0, r.B) == (b.K0, b.B)
        assert np.array_equal(r.Kn, b.Kn)
        assert b.E == pytest.approx(r.E, rel=1e-9)


@pytest.mark.slow
@pytest.mark.families
@pytest.mark.parametrize("backend", ["jnp", "jnp-fused"])
@pytest.mark.parametrize("family", family_names())
@pytest.mark.parametrize("m", list(Objective))
def test_batched_jnp_gia_matches_scalar_full_grid(backend, family, m):
    """Property over the full (m, family) grid — gqfedwavg included: both
    device engines land on the scalar NumPy reference's solution — same
    feasibility verdict, same integer recovery, matching continuous point
    and costs — including the infeasible (fa, *) / (pr, E) combinations."""
    probs = _problems(family, m, budgets=(0.25, 0.3))
    seq = [solve_param_opt(p) for p in _problems(family, m,
                                                 budgets=(0.25, 0.3))]
    bat = solve_param_opt_batched(probs, backend=backend)
    for r, b in zip(seq, bat):
        assert r.feasible == b.feasible
        assert np.allclose(r.z, b.z, atol=1e-4)
        if r.feasible:
            assert (r.K0, r.B) == (b.K0, b.B)
            assert np.array_equal(r.Kn, b.Kn)
            assert b.E == pytest.approx(r.E, rel=1e-6)
            assert b.C == pytest.approx(r.C, rel=1e-6)
            if r.gamma is not None:
                assert b.gamma == pytest.approx(r.gamma, rel=1e-6)


# ---------------------------------------------------------------------------
# integer recovery bisection
# ---------------------------------------------------------------------------
def test_min_feasible_K0_matches_linear_scan():
    prob = _scenario("genqsgd", Objective.CONSTANT).problem()
    Kn = np.array([2, 2, 3, 3], dtype=np.int64)
    for B in (1, 4, 16):
        K0, ok = min_feasible_K0(prob, Kn, B)
        # brute force the same definition
        k, ok_ref = 1, False
        while k < 10**7:
            ev = prob.evaluate(k, Kn, B, None)
            if ev["C"] <= prob.C_max * (1 + 1e-9):
                ok_ref = ev["T"] <= prob.T_max * (1 + 1e-9)
                break
            if ev["T"] > prob.T_max:
                break
            k += 1
        assert ok == ok_ref
        if ok:
            assert K0 == k


def test_min_feasible_K0_infeasible_budget():
    prob = _scenario("genqsgd", Objective.CONSTANT, C_max=1e-9,
                     T_max=10.0).problem()
    _, ok = min_feasible_K0(prob, np.array([1, 1, 1, 1]), 1)
    assert not ok


def test_min_feasible_K0_joint_beats_any_fixed_gamma():
    """The closed-form gamma-optimized recovery: for fixed (Kn, B) it finds
    a (K0, gamma) meeting the error budget with K0 no larger than the best
    K0 any gamma on a fine grid achieves (E is increasing in K0 and
    gamma-independent, so smaller K0 == better joint integer point)."""
    prob = _scenario("genqsgd", Objective.JOINT).problem()
    cap = 1.0 / CONSTS.L
    for Kn_v, B in ((2, 2), (4, 1), (3, 4)):
        Kn = np.full(4, Kn_v, dtype=np.int64)
        K0, g, ok = min_feasible_K0_joint(prob, Kn, B)
        assert ok and 0 < g <= cap * (1 + 1e-12)
        assert prob.evaluate(K0, Kn, B, g)["C"] <= prob.C_max * (1 + 1e-9)
        best_grid = None
        for gg in np.exp(np.linspace(np.log(1e-4 * cap), np.log(cap), 160)):
            k, okk = min_feasible_K0(prob, Kn, B, extra=float(gg))
            if okk:
                best_grid = k if best_grid is None else min(best_grid, k)
        assert best_grid is not None and K0 <= best_grid


def test_joint_restart_keeps_gen_o_at_or_below_gen_c():
    """Lemma 4 / Table-claim guard: with the Gen-C-seeded restart and the
    gamma-optimizing integer recovery, the jointly-optimized objective never
    lands above the fixed-constant-rule solution at the same budgets."""
    rc = _scenario("genqsgd", Objective.CONSTANT).optimize()
    ro = _scenario("genqsgd", Objective.JOINT).optimize()
    assert ro.feasible and rc.feasible
    assert ro.predicted_E <= rc.predicted_E * (1 + 1e-3)


# ---------------------------------------------------------------------------
# Scenario.sweep / SweepReport
# ---------------------------------------------------------------------------
def test_scenario_sweep_matches_pointwise_optimize():
    scn = _scenario("genqsgd", Objective.CONSTANT)
    grid = [0.22, 0.3]
    rep = scn.sweep(over={"cmax": grid}, backend="jnp")
    assert len(rep) == 2 and rep.backend == "jnp" and rep.n_groups == 1
    for c, row, plan in zip(grid, rep.rows, rep.plans):
        ref = dataclasses.replace(scn, C_max=c).optimize()
        assert row["C_max"] == c and row["feasible"] and plan.feasible
        assert (plan.K0, plan.Kn, plan.B) == (ref.K0, ref.Kn, ref.B)
        assert plan.predicted_E == pytest.approx(ref.predicted_E, rel=1e-9)
    # tighter budget costs more energy (Fig. 5a monotonicity)
    assert rep.rows[0]["E"] > rep.rows[1]["E"]


def test_sweep_heterogeneous_groups_and_names():
    scns = [_scenario("genqsgd", Objective.CONSTANT),
            _scenario("genqsgd", Objective.JOINT),
            _scenario("genqsgd", Objective.CONSTANT, C_max=0.3)]
    rep = sweep_scenarios(scns, names=["a", "b", "c"], backend="numpy",
                          parallel=False)
    assert [r["name"] for r in rep] == ["a", "b", "c"]
    assert rep.n_groups == 2         # C-budget pair batches, J solos
    assert [r["m"] for r in rep] == ["C", "J", "C"]


def test_sweep_over_validation():
    scn = _scenario("genqsgd", Objective.CONSTANT)
    with pytest.raises(ValueError, match="cannot sweep over"):
        scn.sweep(over={"warp_factor": [9]})
    with pytest.raises(ValueError, match="duplicate"):
        scn.sweep(over={"cmax": [0.2], "C_max": [0.3]})


def _report_from(points):
    rows = tuple({"name": f"p{i}", "E": e, "T": t, "C": c, "feasible": f}
                 for i, (e, t, c, f) in enumerate(points))
    return SweepReport(rows=rows, plans=(None,) * len(rows),
                       backend="numpy", n_groups=1, wall_time_s=0.0)


def test_pareto_front_dominance():
    rep = _report_from([
        (1.0, 1.0, 1.0, True),     # kept
        (2.0, 2.0, 2.0, True),     # dominated by p0
        (0.5, 3.0, 1.0, True),     # kept: better E, worse T
        (1.0, 1.0, 1.0, True),     # tie with p0: both survive
        (0.1, 0.1, 0.1, False),    # infeasible: filtered by default
    ])
    front = rep.pareto_front()
    assert [r["name"] for r in front] == ["p0", "p2", "p3"]
    assert [r["name"] for r in rep.pareto_front(feasible_only=False)] == \
        ["p4"]
    row, _ = rep.best()
    assert row["name"] == "p2"
    with pytest.raises(ValueError, match="no feasible"):
        _report_from([(1, 1, 1, False)]).best()


def test_sweep_report_csv(tmp_path):
    rep = _report_from([(1.0, 2.0, 3.0, True)])
    rep = dataclasses.replace(
        rep, rows=({**rep.rows[0], "Kn": (1, 2, 3)},))
    path = rep.to_csv(str(tmp_path / "s.csv"))
    lines = open(path).read().splitlines()
    assert lines[0].split(",")[:2] == ["name", "E"]
    assert "1|2|3" in lines[1]
