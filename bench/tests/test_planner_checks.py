"""The planner cells' check at smoke size: the float32 control and each
fault planted under the timed path come out as not correct."""
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench.lib import harness  # noqa: E402
from bench.tests import controls, smoke  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("planner"))


def _altered(solve):
    def wrapped(*a, **kw):
        return [dataclasses.replace(r, K0=r.K0 + 1) for r in solve(*a, **kw)]
    return wrapped


def _unchanged(solve):
    """The solve hands back the point it started from."""
    def wrapped(problems, *a, **kw):
        problems = list(problems)
        return [dataclasses.replace(r, z=p.z_init())
                for p, r in zip(problems, solve(problems, *a, **kw))]
    return wrapped


def _half(solve):
    def wrapped(problems, *a, **kw):
        problems = list(problems)
        kw.pop("z0s", None)
        half = solve(problems[: max(1, len(problems) // 2)], *a, **kw)
        return [half[i % len(half)] for i in range(len(problems))]
    return wrapped


@pytest.mark.parametrize("fault", [_altered, _unchanged, _half])
def test_sweep_fault_fails(root, monkeypatch, fault):
    from repro.api import sweep as SW

    monkeypatch.setattr(SW, "solve_param_opt_batched",
                        fault(SW.solve_param_opt_batched))
    out = smoke.run_driver(root, "sweep.smoke")
    assert not out.correct, [(c.name, c.value) for c in out.checks]


def test_serve_altered_answer_fails(root, monkeypatch):
    from repro.serve import planserver as PS

    monkeypatch.setattr(PS, "solve_param_opt_batched",
                        _altered(PS.solve_param_opt_batched))
    out = smoke.run_driver(root, "serve.smoke", seconds=2.0)
    assert not out.correct, [(c.name, c.value) for c in out.checks]


def test_float32_control_and_reference_faults_fail(root):
    spec = harness.Spec.load("sweep.smoke", root, os.path.join(root, "bench"))
    run = harness.Run(spec, 11, 0.0, False, time.perf_counter(),
                      on_chip=False)
    got = controls.planner_readings(run)
    lim = spec.traffic["limits"]
    for case, readings in got.items():
        assert any(readings[k] > lim[k] for k in readings), (case, readings)
