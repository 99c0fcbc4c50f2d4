"""The program's spans and named scopes, on a trace recorded here on the
CPU backend, on hand-made operations, and through ``bench/phases.py`` on a
smoke sweep."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import program_trace as PT  # noqa: E402
from bench.lib import trace as T  # noqa: E402
from bench.tests import smoke  # noqa: E402


def test_recorded_cpu_trace_keeps_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("gia.finalize", lockstep_iters=40,
                      active_row_iters=90, rows=3):
            f(x).block_until_ready()
            time.sleep(0.002)
    with obs.span("outside"):
        pass
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    pt = PT.load(path)
    names = [s.name for s in pt.spans]
    assert names == ["gia.finalize", "outside"]
    fin, = pt.named("gia.finalize")
    assert fin.args == {"lockstep_iters": 40, "active_row_iters": 90,
                        "rows": 3}
    assert fin.seconds >= 0.002 and pt.span_s("gia.finalize") == fin.seconds
    # the same window and operations as the benchmark's own reduction; the
    # CPU backend records no name stack
    tr = T.load(path)
    assert pt.window == tr.window
    assert len(pt.ops) == len(tr.ops) > 0
    assert all(o.scope == "" for o in pt.ops)
    assert [s[0] for s in tr.spans] == ["bench.window"]


def _pb(num, value) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_op_scopes_from_event_metadata(tmp_path):
    """A TPU keeps an operation's name stack in its event metadata's
    ``tf_op`` stat, as a string or as a reference to an interned one."""
    def plane(name, events, strings):
        body = _pb(2, name)
        for i, (ename, stat) in enumerate(events):
            meta = _pb(1, i + 1) + _pb(2, ename) + _pb(5, stat)
            body += _pb(4, _pb(1, i + 1) + _pb(2, meta))
        for sid, text in strings.items():
            body += _pb(5, _pb(1, sid) + _pb(2, _pb(1, sid) + _pb(2, text)))
        return _pb(1, body)

    strings = {3: "tf_op", 4: "flops", 9: "jit(r)/aggregate/add"}
    space = plane("/device:TPU:0", [
        ("%fusion.1 = f32[8] fusion()",
         _pb(1, 3) + _pb(5, "jit(r)/local_steps/mul")),
        ("%add.2 = f32[8] add()", _pb(1, 3) + _pb(7, 9)),
        ("%copy.3 = f32[8] copy()", _pb(1, 4) + _pb(3, 12))], strings)
    space += plane("/host:CPU", [("python", _pb(1, 3) + _pb(5, "x"))],
                   strings)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert PT._op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(r)/local_steps/mul",
        "%add.2 = f32[8] add()": "jit(r)/aggregate/add"}}


def _ops(*rows):
    return [PT.ScopedOp("d0", n, s, e, scope) for n, s, e, scope in rows]


def test_scope_self_s_counts_nested_ops_once():
    pt = PT.ProgramTrace(spans=[], window=(0, 200), ops=_ops(
        ("while.1", 0, 100, "jit(r)/local_steps/while"),
        ("fusion.2", 10, 40, "jit(r)/local_steps/while/body/transpose("
                             "jvp(dot_general))"),
        ("fusion.3", 50, 60, "jit(r)/worker_encode/mul"),
        ("fusion.4", 100, 130, "jit(r)/server_update/aggregate_like/add"),
        ("copy.5", 130, 135, "jit(r)/transpose(jvp(aggregate))/copy"),
        ("copy.6", 140, 150, ""),
        ("fusion.7", 150, 160, "jit(r)/local_steps_extra/mul")))
    got = pt.scope_self_s(PT.ROUND_PHASES)
    ns = {k: round(v * 1e9) for k, v in got.items()}
    # the while's body ops are its own scope's; an op inside the while in
    # another scope is taken off the while's scope, not counted twice;
    # a component matches whole, never as a prefix of another name
    assert ns == {"local_steps": 90, "worker_encode": 10, "aggregate": 5,
                  "server_update": 30, "other": 20}
    assert sum(ns.values()) == 155


def test_scope_self_s_averages_devices():
    pt = PT.ProgramTrace(spans=[], window=(0, 100), ops=[
        PT.ScopedOp("d0", "f", 0, 40, "aggregate"),
        PT.ScopedOp("d1", "f", 0, 20, "aggregate")])
    assert pt.scope_self_s(["aggregate"])["aggregate"] == \
        pytest.approx(30e-9)


def test_round_phases_per_program_run():
    pt = PT.ProgramTrace(spans=[], window=(0, 100), ops=_ops(
        ("a", 0, 60, "local_steps"), ("b", 60, 70, "worker_encode"),
        ("c", 70, 75, "aggregate"), ("d", 75, 95, "server_update"),
        ("e", 95, 99, "jit(split)")))
    got = PT.round_phases(pt, runs=2)
    assert {k: round(v * 1e9, 3) for k, v in got.items()} == {
        "local_steps": 30, "worker_encode": 5, "aggregate": 2.5,
        "server_update": 10, "other": 2}
    assert PT.round_phases(pt, runs=0) is None
    unscoped = PT.ProgramTrace(spans=[], window=(0, 9),
                               ops=_ops(("a", 0, 9, "")))
    assert PT.round_phases(unscoped, runs=1) is None


def test_sweep_stages_from_spans():
    S = PT.Span
    pt = PT.ProgramTrace(ops=[], window=(0, 10**10), spans=[
        S("sweep", 0, 10_000, {}),
        S("sweep.build", 0, 1_000, {}),
        S("gia.pack", 1_000, 1_500, {"rows": 4}),
        S("gia.fused_dispatch", 1_500, 9_000, {}),
        S("gia.finalize", 9_000, 9_600,
          {"lockstep_iters": 50, "active_row_iters": 120, "rows": 4}),
        S("sweep.freeze", 9_600, 10_000, {})])
    got = PT.sweep_stages(pt)
    assert got["host_prep_s"] == pytest.approx(1.5e-6)
    assert got["host_freeze_s"] == pytest.approx(1.0e-6)
    assert got["dispatch_s"] == pytest.approx(7.5e-6)
    assert got["lockstep_iters_per_dispatch"] == 50
    assert got["active_row_share"] == pytest.approx(60.0)
    assert PT.sweep_stages(PT.ProgramTrace(spans=[], ops=[],
                                           window=(0, 1))) is None


def test_phases_script_on_a_smoke_sweep(tmp_path):
    from bench import phases

    root = smoke.make_root(tmp_path)
    keep = tmp_path / "kept"
    line = phases.execute("sweep.smoke", 2**33 + 5, 1.0, True,
                          keep=str(keep), on_chip=False, root=root,
                          bench_dir=os.path.join(root, "bench"))
    assert line["correct"] is True
    prog = line["program"]
    for name in ("sweep", "sweep.build", "gia.solve", "gia.pack",
                 "gia.fused_dispatch", "gia.finalize", "sweep.freeze"):
        assert prog["spans"][name]["count"] >= 1, name
    st = prog["sweep"]
    assert st["lockstep_iters_per_dispatch"] > 0
    assert 0 < st["active_row_share"] <= 100
    assert st["host_prep_s"] > 0 and st["host_freeze_s"] > 0
    assert prog["round"] is None
    assert [p.name for p in keep.iterdir()] == \
        [f"sweep.smoke.{2**33 + 5}.xplane.pb"]
