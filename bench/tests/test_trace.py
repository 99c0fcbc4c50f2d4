"""The trace reduction, on a trace recorded here on the CPU backend and on
hand-made intervals."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import trace as T  # noqa: E402


def test_union_and_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T._subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


def _trace(ops, window, spans=()):
    return T.Trace(ops=[T._op(d, n, "p", s, e) for d, n, s, e in ops],
                   programs=[], spans=list(spans), window=window)


def test_op_names_from_hlo_text():
    op = T._op("d", "%all-gather-start.3 = (s8[4,96]{1,0}) all-gather-start("
               "s8[1,96] %p)", "m", 0, 1)
    assert op.name == "all-gather-start.3" and op.collective
    assert not T._op("d", "%fusion.2 = f32[8] fusion(%x)", "m", 0, 1) \
        .collective


def test_busy_idle_and_exposed_collectives():
    tr = _trace([("d0", "fusion.1", 0, 40), ("d0", "all-gather.3", 30, 70),
                 ("d0", "fusion.2", 60, 80),
                 ("d1", "fusion.1", 0, 50), ("d1", "all-reduce.1", 50, 60)],
                window=(0, 100))
    assert tr.busy_s() == pytest.approx((80 + 60) / 2 / 1e9)
    assert tr.idle_share() == pytest.approx(1 - 70 / 100)
    total, exposed = tr.collective_s()
    # d0: 40 ns of collective, 20 of it with no compute; d1: 10 of 10
    assert total == pytest.approx((40 + 10) / 2 / 1e9)
    assert exposed == pytest.approx((20 + 10) / 2 / 1e9)


def test_top_ops_count_self_time():
    tr = _trace([("d0", "while.1", 0, 100), ("d0", "fusion.2", 10, 40),
                 ("d0", "fusion.3", 50, 60), ("d0", "copy.4", 100, 105)],
                window=(0, 110))
    top = dict(tr.top_ops())
    assert top == {"while.1": pytest.approx(60e-9),
                   "fusion.2": pytest.approx(30e-9),
                   "fusion.3": pytest.approx(10e-9),
                   "copy.4": pytest.approx(5e-9)}


def test_idle_gaps_take_the_innermost_open_span():
    tr = _trace([("d0", "a", 0, 10), ("d0", "b", 40, 50)], window=(0, 60),
                spans=[("bench.outer", 0, 60), ("bench.inner", 12, 38)])
    gaps = tr.idle_gaps()
    assert gaps[0] == ["bench.inner", pytest.approx(30e-9)]
    assert gaps[1] == ["bench.outer", pytest.approx(10e-9)]


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    tr = T.load(T.find_xplane(str(tmp_path)))
    assert 0 < tr.window_s < 5
    assert [s[0] for s in tr.spans].count("bench.call") == 3
    busy = tr.busy_s()
    assert busy is not None and 0 < busy < tr.window_s
    assert 0 < tr.idle_share() < 1
    runs, secs = tr.program_time(r"jit__lambda|jit_<lambda>")
    assert runs >= 3 and secs > 0
    assert tr.top_ops(3) and all(s > 0 for _, s in tr.top_ops(3))
    assert tr.idle_gaps(2)
