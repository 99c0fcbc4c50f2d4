"""The round cell's check at smoke size: the program passes, and the
precision control and each planted fault come out as not correct."""
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
    return smoke.make_root(tmp_path_factory.mktemp("round"))


def _round_driver():
    return harness.load_module(os.path.join(ROOT, "bench", "drivers",
                                            "round.py"))


def test_round_driver_end_to_end(root):
    line = smoke.execute(root, "round.smoke")
    assert line["correct"] is True, line["checks"]
    assert line["metrics"] == {}
    assert set(line["checks"]) == {"loss_gap", "update_gap", "change_gap"}


def _fails(out):
    assert not out.correct
    return {c.name: c.value for c in out.checks if not c.ok}


def test_state_left_unchanged_fails(root):
    R = _round_driver()

    class Frozen(R.Job):
        def trainer(self):
            tr = super().trainer()
            inner = tr._round

            def frozen(x, *args):
                _, metrics = inner(x, *args)
                return x, metrics
            tr._round = frozen
            return tr

    failed = _fails(smoke.run_driver(root, "round.smoke", job_cls=Frozen))
    assert failed["update_gap"] == pytest.approx(1.0)
    assert failed["change_gap"] == pytest.approx(1.0)


class _HalfBatch:
    """The model with half of every batch left out of its loss."""

    def __init__(self, api):
        self._api = api

    def __getattr__(self, name):
        return getattr(self._api, name)

    def loss_train(self, params, cfg, batch, aux_weight=0.01):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return self._api.loss_train(params, cfg, half, aux_weight)


def test_half_batch_fails(root):
    R = _round_driver()

    class Half(R.Job):
        def trainer(self):
            self.api = _HalfBatch(self.api)
            return super().trainer()

    _fails(smoke.run_driver(root, "round.smoke", job_cls=Half))


def test_precision_control_fails(root):
    spec = harness.Spec.load("round.smoke", root, os.path.join(root, "bench"))
    run = harness.Run(spec, 11, 0.0, False, time.perf_counter(),
                      on_chip=False)
    got = controls.round_readings(run)
    lim = spec.traffic["limits"]
    for case in ("control", "half_batch"):
        assert any(got[case][k] > lim[k] for k in lim), (case, got[case])
