"""Device-resident GIA: the whole outer loop as one jitted ``lax.while_loop``.

``backend="jnp-fused"`` of :func:`repro.opt.gia.solve_param_opt_batched`:
the per-expansion-point coefficient refresh (:mod:`repro.opt.refresh`), the
phase-I/Newton log-barrier interior point, and the per-instance convergence /
stall masking all live inside **one** ``lax.while_loop``, compiled once per
structure signature — a GIA outer iteration performs zero host syncs and
zero Python work, which is what turns 1e3+-point ``Scenario.sweep`` grids
into one compile + one device call per (m, family, N) group.

The loop is a per-row *state machine*, not a nest of per-phase loops: every
body iteration performs exactly one damped-Newton step for every row, and
each row independently advances its own schedule — phase-I stages, barrier
t-ramp, GIA expansion-point transitions (where the surrogate coefficients
refresh on device) — under lockstep masks.  A nested ``vmap``-of-while
formulation pays the *product* of per-level maxima across rows (a batch of
heterogeneously-converging instances runs every row to the slowest row's
iteration count at every nesting level); the flat machine pays only the
maximum of per-row *total* Newton-step counts, which is what makes batched
throughput scale with batch size instead of degrading with it.

Per-row semantics replicate the host loop in :mod:`repro.opt.gia` and the
scalar solver schedule in :mod:`repro.opt.gp` exactly: same Newton tolerance
and per-stage cap, same Armijo backtracking on precomputed term logs, same
damping ramp, same phase-I margins and stage budget, same barrier t-ramp,
same infeasible-retry / 8-strike stall-out bookkeeping.  Objective history
is journaled into a fixed ``(B, max_iter)`` buffer (NaN = no accepted step)
and unpacked host-side after the single device call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import REGISTRY as _METRICS
from ..obs import trace as _otrace
from ..obs.metrics import GLOBAL_SWITCH as _OBS_ON
from .gp_jax import (_LS_ALPHA, _LS_BETA, _LS_MAX, _MU, _NEWTON_MAX,
                     _NEWTON_TOL, _P1_MARGIN, _P1_STAGES, _T0, _TOL_GAP)
from .problems import Objective
from .refresh import RefreshPlan, make_project, make_refresh
from .structure import PAD_LOGC

__all__ = ["FusedSolve", "solve_gia_fused", "trace_count", "TRACE_COUNTS",
           "compile_cache_info", "compile_cache_clear"]
#: host-loop stall budget, verbatim (gia.solve_param_opt_batched)
_STALL_MAX = 8
#: emergency bound on total body iterations (a legitimate solve is ~1e3-1e4
#: Newton steps; this only guards CI against a logic bug hanging the loop)
_IT_CAP = 1_000_000

#: fused-program trace counter per static signature key — the test hook
#: asserting "one compile per structure signature" (the traced body below
#: executes only while jax traces; cache hits never touch it)
TRACE_COUNTS: Dict[tuple, int] = {}


def trace_count(plan_or_key) -> int:
    key = getattr(plan_or_key, "signature_key", plan_or_key)
    return sum(v for k, v in TRACE_COUNTS.items() if k[0] == key)


def compile_cache_info():
    """Hit/miss statistics of the process-level fused-program cache.

    The cache is owned by the module (``functools.lru_cache`` on
    :func:`_compiled`), not by any solver or batch object: every
    ``Scenario.optimize``, sweep, and :class:`~repro.serve.PlanServer`
    micro-batch in the process shares the same traced refresh plans and
    compiled executables, keyed by (structure signature, max_iter) and —
    inside jax.jit — the padded batch shape.
    """
    return _compiled.cache_info()


def compile_cache_clear():
    _compiled.cache_clear()


@functools.lru_cache(maxsize=64)
def _compiled(m_value: str, n: int, m_cons: int, seg_bytes: bytes,
              caps: Tuple[int, ...], i_x0: int, max_iter: int,
              sampled: bool = False):
    seg = jnp.asarray(np.frombuffer(seg_bytes, dtype=np.int32))
    m = Objective(m_value)
    refresh_one = make_refresh(m, n, caps, sampled)
    project_one = make_project(m, i_x0)
    key = (m_value, n, m_cons, caps, seg_bytes, i_x0, sampled)

    def _seg_max(t):
        return jax.ops.segment_max(t, seg, num_segments=m_cons,
                                   indices_are_sorted=True)

    def _seg_sum(x):
        return jax.ops.segment_sum(x, seg, num_segments=m_cons,
                                   indices_are_sorted=True)

    def _expand(s):
        return s[seg]

    def g_of(z, logc, A):
        t = logc + A @ z
        mx = _seg_max(t)
        return mx + jnp.log(_seg_sum(jnp.exp(t - _expand(mx))))

    def f0_of(z, obj_logc, obj_A):
        t0 = obj_logc + obj_A @ z
        mx0 = jnp.max(t0)
        return mx0 + jnp.log(jnp.sum(jnp.exp(t0 - mx0)))

    def g_from_terms(t):
        mx = _seg_max(t)
        return mx + jnp.log(_seg_sum(jnp.exp(t - _expand(mx))))

    def barrier_aug(z, s, p1f, tscale, obj_logc, obj_A, logc, A):
        """(phi, grad, hess, g_main) of the row's current barrier over the
        (n+1) variables (z, S) — the phase-I slack enters *analytically*.

        In phase-I every constraint term carries a ``-S`` (the auxiliary GP
        divides each f_i by S) and the objective is S itself; because the
        per-constraint softmax weights sum to 1, the S-column of every
        per-constraint gradient is exactly -1 and all S-blocks of the
        Hessian reduce to weight sums — no (T, n+1) system is ever
        materialized, which keeps the hot loop's memory traffic to reads of
        the packed (log c, A) tensors.  In main mode (p1f = 0) the spare
        coordinate is ridged so the Newton system stays definite; its step
        component is exactly 0.
        """
        t0 = obj_logc + obj_A @ z
        mx0 = jnp.max(t0)
        e0 = jnp.exp(t0 - mx0)
        s0 = jnp.sum(e0)
        w0 = e0 / s0
        q0 = obj_A.T @ w0
        H0 = (obj_A.T * w0) @ obj_A - jnp.outer(q0, q0)
        f0 = p1f * s + (1.0 - p1f) * (mx0 + jnp.log(s0))
        t_main = logc + A @ z
        g_main = g_from_terms(t_main)
        t = t_main - s * p1f
        mx = _seg_max(t)
        e = jnp.exp(t - _expand(mx))
        ssum = _seg_sum(e)
        g = mx + jnp.log(ssum)
        negg = jnp.where(g < 0.0, -g, 1.0)
        phi = tscale * f0 - jnp.sum(jnp.log(negg))
        w = e / _expand(ssum)
        cinv = 1.0 / negg
        Q = _seg_sum(w[:, None] * A)
        wc = w * _expand(cinv)
        mv = cinv**2 - cinv
        grad_n = (1.0 - p1f) * (tscale * q0) + Q.T @ cinv
        grad_s = p1f * (tscale - jnp.sum(cinv))
        Awc = A.T @ wc
        Qm = Q.T @ mv
        H_nn = (1.0 - p1f) * (tscale * H0) + (A.T * wc) @ A \
            + (Q.T * mv) @ Q
        H_ns = p1f * (-Awc - Qm)
        H_ss = p1f * (jnp.sum(wc) + jnp.sum(mv)) + (1.0 - p1f)
        H = jnp.concatenate(
            [jnp.concatenate([H_nn, H_ns[:, None]], axis=1),
             jnp.concatenate([H_ns[None, :], H_ss[None, None]], axis=1)],
            axis=0)
        grad = jnp.concatenate([grad_n, grad_s[None]])
        phi = jnp.where(jnp.all(g < 0.0), phi, jnp.inf)
        return phi, grad, H, g_main, t_main, t0

    def run(tol, z0, obj_logc, obj_A, skel_logc, skel_A, arrays):
        # this body executes only while jax traces (cache hits never reach
        # it), so both hooks count trace/compile events, not dispatches
        TRACE_COUNTS[(key, z0.shape[0])] = \
            TRACE_COUNTS.get((key, z0.shape[0]), 0) + 1
        if _OBS_ON.on:
            _METRICS.counter("gia.compile_events").inc()
        B = z0.shape[0]
        eye = jnp.eye(n + 1)

        def row_body(z_aug, z_exp, z_out, c_logc, c_A, p1, t, p1_stage,
                     newton_it, gia_it, stall, conv, active, hist, nh,
                     active_it, o_logc, o_A, sk_logc, sk_A, a):
            active_it = jnp.where(active, active_it + 1, active_it)
            logc = jnp.concatenate([sk_logc, c_logc])
            A = jnp.concatenate([sk_A, c_A], axis=0)
            p1f = jnp.where(p1, 1.0, 0.0)
            z = z_aug[:n]
            s = z_aug[n]
            phi, grad, H, g_main, t_main, t0 = barrier_aug(
                z, s, p1f, t, o_logc, o_A, logc, A)

            def damp_cond(cc):
                lam, L = cc
                return jnp.any(jnp.isnan(L)) & (lam < 1e8)

            def damp_body(cc):
                lam, _ = cc
                lam = jnp.maximum(lam * 10.0, 1e-10)
                return lam, jnp.linalg.cholesky(H + lam * eye)

            _, L = lax.while_loop(
                damp_cond, damp_body,
                (1e-12, jnp.linalg.cholesky(H + 1e-12 * eye)))
            step = -jax.scipy.linalg.cho_solve((L, True), grad)
            dec = -(grad @ step)
            small = dec / 2.0 <= _NEWTON_TOL
            gs = grad @ step
            dz, ds = step[:n], step[n]
            dt_main = A @ dz
            dt0 = o_A @ dz
            t_eff = t_main - s * p1f
            dt_eff = dt_main - ds * p1f

            def ls_cond(c):
                _, k, ok = c
                return (~ok) & (k < _LS_MAX)

            def ls_body(c):
                al, k, _ = c
                # barrier value along the ray from precomputed term logs
                # (the line-search hot path: no matvecs per backtrack)
                t0a = t0 + al * dt0
                mx0 = jnp.max(t0a)
                f0m = mx0 + jnp.log(jnp.sum(jnp.exp(t0a - mx0)))
                ga = g_from_terms(t_eff + al * dt_eff)
                phin = t * (p1f * (s + al * ds) + (1.0 - p1f) * f0m) \
                    - jnp.sum(jnp.log(jnp.where(ga < 0.0, -ga, 1.0)))
                phin = jnp.where(jnp.all(ga < 0.0), phin, jnp.inf)
                ok = jnp.isfinite(phin) & (phin <= phi + _LS_ALPHA * al * gs)
                return jnp.where(ok, al, al * _LS_BETA), k + 1, ok

            al, _, ls_ok = lax.while_loop(ls_cond, ls_body,
                                          (jnp.ones(()), 0, small))
            progressed = active & ~small & ls_ok
            au = jnp.where(progressed, al, 0.0)
            z_aug = jnp.where(progressed, z_aug + al * step, z_aug)
            newton_it = jnp.where(progressed, newton_it + 1, newton_it)
            stage_end = active & (small | ~ls_ok | (newton_it >= _NEWTON_MAX))

            # ---- stage transitions ------------------------------------
            # post-step term logs by linear shift — no fresh matvecs
            z_main = z_aug[:n]
            gmax = jnp.max(g_from_terms(t_main + au * dt_main))
            t0p = t0 + au * dt0
            mx0p = jnp.max(t0p)
            f0m = mx0p + jnp.log(jnp.sum(jnp.exp(t0p - mx0p)))
            s_val = z_aug[n]
            ok_margin = (s_val < -_P1_MARGIN) & (gmax < -_P1_MARGIN)
            p1_finished = ok_margin | (m_cons / t < 1e-9) \
                | (p1_stage + 1 >= _P1_STAGES)
            p1_ok = ok_margin | (gmax < 0.0)
            solve_done = (m_cons / t) < _TOL_GAP

            p1_orig = p1
            ramp = stage_end & jnp.where(p1_orig, ~p1_finished, ~solve_done)
            t = jnp.where(ramp, t * _MU, t)
            p1_stage = jnp.where(ramp & p1_orig, p1_stage + 1, p1_stage)
            newton_it = jnp.where(stage_end, 0, newton_it)

            p1_to_main = stage_end & p1_orig & p1_finished & p1_ok
            t = jnp.where(p1_to_main, _T0, t)

            # ---- GIA expansion-point transition -----------------------
            gia_tr = stage_end & jnp.where(p1_orig, p1_finished & ~p1_ok,
                                           solve_done)
            # feasible only via a completed main solve; a phase-I failure
            # is the infeasible-retry path (min-slack point, stall strike)
            feas = gia_tr & ~p1_orig & (gmax <= 1e-7)
            p1 = p1_orig & ~p1_to_main & ~gia_tr
            zp_next = project_one(z_main, a)
            # projected-vs-projected step, as in the host loop: m=E holds X0
            # a delta-margin off the manifold the projection re-imposes
            gstep = jnp.max(jnp.abs(zp_next - z_exp))
            hist = hist.at[gia_it].set(
                jnp.where(feas, jnp.exp(f0m), hist[gia_it]))
            nh = nh + feas
            stall = jnp.where(gia_tr, jnp.where(feas, 0, stall + 1), stall)
            newly_conv = feas & (gstep < tol)
            gia_next = jnp.where(gia_tr, gia_it + 1, gia_it)
            conv = conv | newly_conv
            active = active & jnp.where(
                gia_tr, ~newly_conv & (stall <= _STALL_MAX)
                & (gia_next < max_iter), True)
            z_out = jnp.where(gia_tr, z_main, z_out)
            z_exp = jnp.where(gia_tr & active, zp_next, z_exp)
            gia_it = gia_next

            # re-entry at the new expansion point: the device-side surrogate
            # refresh (AM-GM / Taylor condensation of repro.opt.refresh),
            # phase-I iff the retry point is not strictly feasible
            cl_new, cA_new = refresh_one(z_exp, a)
            reenter = gia_tr & active
            c_logc = jnp.where(reenter, cl_new, c_logc)
            c_A = jnp.where(reenter, cA_new, c_A)
            t_re = jnp.concatenate([sk_logc + sk_A @ z_exp,
                                    c_logc + c_A @ z_exp])
            g0 = jnp.max(g_from_terms(t_re))
            need_p1 = g0 >= 0.0
            p1 = jnp.where(reenter, need_p1, p1)
            t = jnp.where(reenter, _T0, t)
            p1_stage = jnp.where(reenter, 0, p1_stage)
            z_aug = jnp.where(
                reenter,
                jnp.concatenate([z_exp,
                                 jnp.where(need_p1, g0 + 1.0, 0.0)[None]]),
                z_aug)
            return (z_aug, z_exp, z_out, c_logc, c_A, p1, t, p1_stage,
                    newton_it, gia_it, stall, conv, active, hist, nh,
                    active_it)

        row_body_v = jax.vmap(row_body)

        def body(st):
            rows, it = st
            return row_body_v(*rows, obj_logc, obj_A, skel_logc, skel_A,
                              arrays), it + 1

        def cond(st):
            rows, it = st
            return jnp.any(rows[12]) & (it < _IT_CAP)

        # initial GIA entry, identical to every later re-entry
        project_v = jax.vmap(project_one, in_axes=(0, 0))
        zp0 = project_v(z0, arrays)
        cl0, cA0 = jax.vmap(refresh_one, in_axes=(0, 0))(zp0, arrays)

        def g0_row(zp, cl, cA, sk_logc, sk_A):
            t_full = jnp.concatenate([sk_logc + sk_A @ zp, cl + cA @ zp])
            return jnp.max(g_from_terms(t_full))

        g0 = jax.vmap(g0_row)(zp0, cl0, cA0, skel_logc, skel_A)
        need_p1 = g0 >= 0.0
        z_aug0 = jnp.concatenate(
            [zp0, jnp.where(need_p1, g0 + 1.0, 0.0)[:, None]], axis=1)
        rows = (z_aug0, zp0, zp0, cl0, cA0, need_p1,
                jnp.full((B,), _T0), jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, dtype=bool),
                jnp.ones(B, dtype=bool), jnp.full((B, max_iter), jnp.nan),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32))
        rows, trips = lax.while_loop(cond, body, (rows, jnp.int32(0)))
        # (z, converged, history, history length, per-row active lockstep
        # iterations, the loop's trip count)
        return rows[2], rows[11], rows[13], rows[14], rows[15], trips

    # donate the starting points' buffer (a no-op on CPU, which has no
    # donation support — avoid the warning there)
    donate = () if jax.default_backend() == "cpu" else (1,)
    return jax.jit(run, donate_argnums=donate)


@dataclasses.dataclass
class FusedSolve:
    """One fused dispatch's outputs for its real rows (padding dropped), as
    read at the dispatch's single host sync."""

    z: np.ndarray                  # (rows, n) final points
    converged: np.ndarray          # (rows,) bool
    hist: np.ndarray               # (rows, max_iter) objective, NaN = none
    n_hist: np.ndarray             # (rows,) accepted GIA iterations
    active_iters: np.ndarray       # (rows,) lockstep iterations row was live
    lockstep_iters: int            # the while loop's trip count

    def history(self, i: int) -> List[float]:
        col = self.hist[i]
        history = [float(v) for v in col[~np.isnan(col)]]
        assert len(history) == int(self.n_hist[i])
        return history


def solve_gia_fused(problems: Sequence, z0s: Optional[Sequence], tol: float,
                    max_iter: int, pad_to: int = 0) -> FusedSolve:
    """Run the fused lockstep GIA over ``problems`` in one dispatch.

    ``z0s`` holds a log-space start per row, or ``None`` (for all rows or
    one) for the row's cold ``z_init()``.  ``pad_to > len(problems)`` pads
    the device batch to a fixed row count by replicating row 0 (padding
    rows solve normally and are discarded), so every dispatch of a
    structure signature shares one jitted shape — a serving loop whose
    micro-batches vary in size still pays exactly one trace/compile per
    signature.  Padding rows cannot stretch the lockstep: the flat state
    machine's trip count is the max of per-row totals, and a duplicate of
    row 0 finishes exactly when row 0 does.

    Host spans ``gia.pack`` (start points, refresh plan, padding) and
    ``gia.fused_dispatch`` (the call and its single sync) bracket the work;
    ``gia.z_init``, inside ``gia.pack``, holds the start points, with the
    rows started cold and the grid points their searches priced.
    """
    B = len(problems)
    padded = max(B, int(pad_to))
    z0s = [None] * B if z0s is None else list(z0s)
    cold = [p for p, z in zip(problems, z0s) if z is None]
    with _otrace.span("gia.pack", rows=B, padded=padded):
        with _otrace.span("gia.z_init", rows=len(cold), grid_points=sum(
                p.init_grid_points for p in cold)):
            z0 = np.stack([p.z_init() if z is None
                           else np.asarray(z, dtype=np.float64)
                           for p, z in zip(problems, z0s)])
        plan = RefreshPlan.build(problems)
        fn = _compiled(plan.m.value, plan.n, plan.m_cons, plan.seg.tobytes(),
                       plan.caps, plan.i_x0, int(max_iter), plan.sampled)
        if padded > B:
            def _pad(a):
                return np.concatenate([a, np.repeat(a[:1], padded - B,
                                                    axis=0)])
            z0 = _pad(z0)
            plan = dataclasses.replace(
                plan, obj_logc=_pad(plan.obj_logc), obj_A=_pad(plan.obj_A),
                skel_logc=_pad(plan.skel_logc), skel_A=_pad(plan.skel_A),
                arrays={k: _pad(v) for k, v in plan.arrays.items()})
    with _otrace.span("gia.fused_dispatch", rows=B, padded=padded,
                      sig=str(plan.signature_key)[:160]):
        with jax.enable_x64(True):
            out = fn(float(tol), z0, plan.obj_logc, plan.obj_A,
                     plan.skel_logc, plan.skel_A, plan.arrays)
            # the single host sync of the whole solve
            z, conv, hist, nh, active, trips = (np.asarray(a) for a in out)
    return FusedSolve(z=z[:B], converged=conv[:B], hist=hist[:B],
                      n_hist=nh[:B], active_iters=active[:B],
                      lockstep_iters=int(trips))
