"""repro.obs: spans, metrics, the drift ledger — and the observer-effect
guarantee.

Covers the ISSUE-10 acceptance bar:

  * **inert when off** — with the global switch down (the default), every
    instrument drops its sample after one attribute check, ``span()``
    is only a profiler annotation, and nothing is buffered;
  * **observer effect = none** — enabling observability leaves the Plan,
    the RunReport (modulo its wall-clock field — real time differs
    between *any* two runs) and the FaultTrace bit-identical across an
    (m, family) grid, and flipping it on over a warm fused cache re-traces
    nothing (one-compile-per-signature still holds);
  * **ledger purity** — ``RunReport.drift()`` is a pure function of the
    frozen report: identical object whether obs is on or off, exact
    cumulative sums, JSONL round-trip;
  * **PlanServer stats as a registry view** — per-source latency
    summaries, queue depth / inflight gauges, balanced queue→solve async
    span pairs in the Chrome export.
"""
import dataclasses
import json
import math
import os

import numpy as np
import pytest

from repro import obs
from repro.api import (ConstantRule, EdgeSystem, MLProblemConstants,
                       QuadraticTask, Scenario, edge_faults)
from repro.obs.bench import ENVELOPE_KEYS, write_bench
from repro.obs.ledger import RunLedger
from repro.obs.metrics import MetricsRegistry, Switch
from repro.obs.trace import Tracer

pytestmark = pytest.mark.obs

N = 4
CONSTS = MLProblemConstants(L=0.084, sigma=33.18, G=33.63, f_gap=2.3, N=N)
SYS = EdgeSystem.paper_sec_vii(dim=64, N=N)
#: same signature as test_planserver, so the one fused compile is shared
SYS_1024 = EdgeSystem.paper_sec_vii(dim=1024, N=N)

FAULTY = edge_faults(straggler_prob=0.3, straggler_factor=4.0,
                     crash_prob=0.1, crash_rounds=2, corrupt_prob=0.05,
                     deadline_slack=1.5)


def _scenario(m="C", family="genqsgd", faults="none", system=SYS,
              C_max=1.0):
    step = None if m == "J" else ConstantRule(0.01)
    return Scenario(system=system, consts=CONSTS, T_max=1e6, C_max=C_max,
                    family=family, faults=faults, step=step)


def _strip_wall(report):
    """RunReport modulo its one genuinely non-deterministic field."""
    return dataclasses.replace(report, wall_time_s=0.0)


@pytest.fixture(autouse=True)
def _obs_off_after():
    """Every test starts and ends with observability off and clean."""
    obs.disable()
    yield
    obs.disable()
    obs.TRACER.clear()
    obs.REGISTRY.reset()


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
def test_instruments_inert_when_off():
    reg = MetricsRegistry(Switch(False))
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5)
    g.set(3.0)
    g.add(1.0)
    h.observe(1.0)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    assert h.summary() == {"count": 0}
    # the global registry is gated on the global switch (down by default)
    obs.REGISTRY.counter("test.never").inc()
    assert obs.REGISTRY.counter("test.never").value == 0.0


def test_counter_gauge_histogram_record():
    reg = MetricsRegistry()                      # own switch: always on
    c = reg.counter("solves", backend="numpy")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("depth")
    g.set(4)
    g.add(-1)
    assert g.value == 3.0
    h = reg.histogram("lat")
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and h.total == 4950.0
    assert h.vmin == 0.0 and h.vmax == 99.0 and h.mean == 49.5
    # exact linear-interpolation percentiles over the retained samples
    assert h.percentile(50) == pytest.approx(49.5)
    assert h.percentile(99) == pytest.approx(98.01)
    s = h.summary()
    assert s["count"] == 100 and s["p95"] == pytest.approx(94.05)


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("x", backend="jnp")
    b = reg.counter("x", backend="jnp")
    c = reg.counter("x", backend="numpy")
    assert a is b and a is not c
    assert a.full_name == 'x{backend="jnp"}'
    assert len(reg) == 2
    reg.reset()
    assert len(reg) == 0


def test_prometheus_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("gia.solves", backend="jnp-fused").inc(3)
    reg.gauge("planserver.queue_depth").set(2)
    reg.histogram("lat").observe(1.0)
    text = reg.to_prometheus()
    assert 'gia_solves{backend="jnp-fused"} 3' in text
    assert "# TYPE gia_solves counter" in text
    assert "planserver_queue_depth 2" in text
    assert "lat_count 1" in text and 'quantile="0.50"' in text
    snap = reg.snapshot()
    assert snap['gia.solves{backend="jnp-fused"}'] == 3.0
    assert snap["lat"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def _profiled_spans(tmp_path, fn):
    """Run ``fn`` under a profiler trace; -> the trace's ``repro.*`` host
    events as (name, start ns, end ns, stats) in start order."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith("repro.")]
    return sorted(evs, key=lambda e: e[1])


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler trace; -> the trace's ``repro.*`` host
    events as (name, stats) in start order."""
    return [(n, st) for n, _, _, st in _profiled_spans(tmp_path, fn)]


def test_tracer_noop_when_off(tmp_path):
    tr = Tracer(Switch(False))

    def work():
        with tr.span("a", rows=3, sig="g"):
            pass
        tr.add_span("b", 0.0, 1.0)
        tr.async_span("c", 1, 0.0, 1.0)
        tr.instant("d")

    # off, a span is still a profiler annotation (with its arguments); the
    # past intervals cannot reach the profiler; nothing is buffered
    assert _profiled(tmp_path, work) == [("repro.a", {"rows": 3, "sig": "g"})]
    assert len(tr) == 0


@pytest.mark.parametrize("on", [False, True])
def test_span_lands_in_profiler_trace(tmp_path, on):
    """``obs.span`` writes ``repro.<name>`` with its arguments into any
    profiler trace, obs on or off; only when on does it also buffer."""
    if on:
        obs.enable(reset=True)

    def work():
        with obs.span("outer", n=2):
            with obs.span("inner"):
                pass

    assert _profiled(tmp_path, work) == [("repro.outer", {"n": 2}),
                                         ("repro.inner", {})]
    assert [e["name"] for e in obs.TRACER.events()] == \
        (["inner", "outer"] if on else [])


@pytest.mark.parametrize("on", [False, True])
def test_z_init_span_nested_in_pack(tmp_path, on):
    """A fused solve writes ``repro.gia.z_init`` (rows started cold, grid
    points priced) inside ``repro.gia.pack``, obs on or off."""
    if on:
        obs.enable(reset=True)
    scn = _scenario(system=SYS_1024, C_max=0.25)
    spans = _profiled_spans(
        tmp_path, lambda: scn.optimize(backend="jnp-fused"))
    pack, = [s for s in spans if s[0] == "repro.gia.pack"]
    z_init, = [s for s in spans if s[0] == "repro.gia.z_init"]
    assert pack[1] <= z_init[1] <= z_init[2] <= pack[2]
    assert z_init[3] == {"rows": 1,
                         "grid_points": scn.problem().init_grid_points}
    assert z_init[3]["grid_points"] == 80          # m = C: 8 B x 10 K_n
    assert ("gia.z_init" in {e["name"] for e in obs.TRACER.events()}) is on


def test_tracer_spans_and_chrome_export(tmp_path):
    import time

    tr = Tracer()
    with tr.span("outer", note="warm"):
        with tr.span("inner"):
            pass
    t = time.perf_counter()
    tr.async_span("req", span_id=7, t_start=t, t_end=t + 0.5, cat="srv",
                  source="hit")
    tr.instant("mark")
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["X", "X", "b", "e", "i"]
    assert evs[0]["name"] == "inner"             # inner exits first
    assert evs[1]["args"] == {"note": "warm"}
    assert evs[2]["id"] == 7 and evs[2]["cat"] == "srv"
    assert all(e["ts"] >= 0 for e in evs)
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms" and len(doc["traceEvents"]) == 5
    path = tr.save(str(tmp_path / "trace.json"))
    assert json.load(open(path)) == doc
    tr.clear()
    assert len(tr) == 0


# ---------------------------------------------------------------------------
# bench envelope
# ---------------------------------------------------------------------------
def test_write_bench_uniform_envelope(tmp_path):
    p = str(tmp_path / "BENCH_x.json")
    doc = write_bench(p, "x", {"speedup": 2.0}, smoke=True)
    loaded = json.load(open(p))
    assert loaded == doc
    for k in ENVELOPE_KEYS:
        assert k in loaded
    assert loaded["bench"] == "x" and loaded["smoke"] is True
    assert loaded["bench_schema"] == 2 and loaded["speedup"] == 2.0
    assert loaded["machine"]["cpus"] >= 1
    with pytest.raises(ValueError, match="shadow"):
        write_bench(p, "x", {"machine": {}})


def test_repo_bench_artifacts_share_schema():
    """Every committed BENCH_*.json rides the uniform envelope."""
    import glob

    root = os.path.join(os.path.dirname(__file__), "..", "..")
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    assert paths, "no BENCH_*.json artifacts at the repo root"
    for p in paths:
        doc = json.load(open(p))
        missing = [k for k in ENVELOPE_KEYS if k not in doc]
        assert not missing, f"{os.path.basename(p)} missing {missing}"
        assert doc["bench_schema"] == 2, os.path.basename(p)


# ---------------------------------------------------------------------------
# drift ledger
# ---------------------------------------------------------------------------
def test_ledger_rows_and_cumulative_sums(tmp_path):
    scn = _scenario(faults=FAULTY)
    plan = scn.optimize("C")
    rep = scn.run(plan, task=QuadraticTask(dim=8), seed=3, max_rounds=12)
    led = rep.drift()
    assert isinstance(led, RunLedger) and len(led) == rep.rounds
    assert led.backend == "reference" and led.family == "genqsgd"
    # per-round predictions are the plan totals amortized over K0
    r0 = led.rows[0]
    assert r0.predicted_time_s == pytest.approx(plan.predicted_T / plan.K0)
    assert r0.predicted_energy_j == pytest.approx(plan.predicted_E / plan.K0)
    assert r0.predicted_bits == pytest.approx(plan.expected_round_bits())
    # measured round times come from the fault trace, cut at the deadline
    for row, rec in zip(led.rows, rep.fault_trace.records):
        assert row.measured_time_s == pytest.approx(rec.t_round)
        assert row.measured_time_s <= plan.faults.deadline + 1e-12
    # cumulative columns are exact running sums; drift matches by hand
    last = led.rows[-1]
    assert last.cum_measured_time_s == pytest.approx(
        sum(r.measured_time_s for r in led.rows))
    assert last.drift_time == pytest.approx(
        last.cum_measured_time_s / last.cum_predicted_time_s - 1.0)
    assert led.cumulative()["drift_time"] == last.drift_time
    assert "cumulative drift" in led.summary()
    # JSONL round-trip, summary line included
    path = led.to_jsonl(str(tmp_path / "ledger.jsonl"))
    assert RunLedger.load_jsonl(path) == led
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == len(led) + 1 and lines[-1]["summary"] is True


def test_ledger_is_pure_function_of_report():
    scn = _scenario(faults=FAULTY)
    plan = scn.optimize("C")
    task = QuadraticTask(dim=8)
    obs.disable()
    rep_off = scn.run(plan, task=task, seed=3, max_rounds=10)
    obs.enable(reset=True)
    rep_on = scn.run(plan, task=task, seed=3, max_rounds=10)
    obs.disable()
    assert rep_on.drift() == rep_off.drift()


def test_empty_ledger_cumulative_is_nan():
    c = RunLedger().cumulative()
    assert all(math.isnan(v) for v in c.values())


# ---------------------------------------------------------------------------
# observer effect: enabling obs changes no result, adds no compile
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", ["C", "J"])
@pytest.mark.parametrize("family", ["genqsgd", "gqfedwavg"])
def test_plans_bit_identical_on_off(m, family):
    scn = _scenario(m, family, faults=FAULTY, C_max=0.5)
    obs.disable()
    p_off = scn.optimize(backend="numpy")
    obs.enable(reset=True)
    p_on = scn.optimize(backend="numpy")
    assert p_on == p_off
    # the instrumentation did record while on (the scalar numpy engine is
    # wrapped by the scenario.optimize span, not the batched-dispatch hooks)
    assert any(e["name"] == "scenario.optimize"
               for e in obs.TRACER.events())


def test_run_report_and_fault_trace_bit_identical_on_off():
    scn = _scenario(faults=FAULTY)
    plan = scn.optimize("C")
    task = QuadraticTask(dim=8)
    obs.disable()
    rep_off = scn.run(plan, task=task, seed=7, max_rounds=10)
    obs.enable(reset=True)
    rep_on = scn.run(plan, task=task, seed=7, max_rounds=10)
    obs.disable()
    # == compares every field including FaultTrace and history; only the
    # wall-clock field may differ (it differs between ANY two runs)
    assert _strip_wall(rep_on) == _strip_wall(rep_off)
    assert rep_on.fault_trace == rep_off.fault_trace


def test_enabling_obs_adds_no_fused_compile():
    from repro.opt import gia_jax

    scn = _scenario(system=SYS_1024, C_max=0.25)
    obs.disable()
    p_off = scn.optimize(backend="jnp-fused")    # pays the compile (or warm)
    warm = sum(gia_jax.TRACE_COUNTS.values())
    obs.enable(reset=True)
    p_on = scn.optimize(backend="jnp-fused")
    obs.disable()
    assert sum(gia_jax.TRACE_COUNTS.values()) == warm, \
        "enabling obs re-traced the fused engine"
    assert p_on == p_off
    # the dispatch span (the call and its one host sync) was buffered
    names = {e["name"] for e in obs.TRACER.events()}
    assert "gia.fused_dispatch" in names


def test_scenario_run_writes_ledger_artifact(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    scn = _scenario(faults=FAULTY)
    plan = scn.optimize("C")
    obs.enable(reset=True)
    rep = scn.run(plan, task=QuadraticTask(dim=8), seed=3, max_rounds=8)
    obs.disable()
    path = tmp_path / "ledger_genqsgd_reference_seed3.jsonl"
    assert path.exists()
    assert RunLedger.load_jsonl(str(path)) == rep.drift()


# ---------------------------------------------------------------------------
# PlanServer: stats() as a registry view + span export
# ---------------------------------------------------------------------------
def test_planserver_stats_and_spans():
    from repro.serve import PlanServer

    obs.enable(reset=True)
    try:
        with PlanServer(max_batch=4, window_s=0.01) as srv:
            h1 = srv.submit(_scenario(system=SYS_1024, C_max=0.25))
            h1.result(timeout=300)
            h2 = srv.submit(_scenario(system=SYS_1024, C_max=0.25))  # hit
            h2.result(timeout=300)
            st = srv.stats()
    finally:
        obs.disable()

    # historical keys survive the registry-view rewrite
    for k in ("submitted", "hits", "warm", "cold", "hit_rate", "batches",
              "mean_batch", "cancelled", "bisections", "quarantined",
              "poisoned", "non_converged", "signatures", "cache_entries",
              "compiles"):
        assert k in st, k
    assert st["submitted"] == 2 and st["hits"] == 1
    # new: live gauges (drained server: all idle) + latency summaries
    assert st["queue_depth"] == 0 and st["inflight"] == 0
    assert isinstance(st["queue_depth"], int)
    assert st["latency_s"]["all"]["count"] == 2
    assert st["latency_s"]["hit"]["count"] == 1
    assert st["latency_s"]["hit"]["p50"] <= st["latency_s"]["all"]["max"]
    assert st["queue_wait_s"]["count"] >= 1

    # queue -> solve async pairs are balanced (Perfetto drops unbalanced
    # tracks) and the batch span is present
    evs = obs.TRACER.events()
    names = {e["name"] for e in evs}
    assert {"planserver.queue", "planserver.solve",
            "planserver.batch"} <= names
    for nm in ("planserver.queue", "planserver.solve"):
        b = sum(1 for e in evs if e["name"] == nm and e["ph"] == "b")
        e_ = sum(1 for e in evs if e["name"] == nm and e["ph"] == "e")
        assert b == e_ > 0, (nm, b, e_)
    assert any(e["name"] == "planserver.hit" and e["ph"] == "i"
               for e in evs)


def test_planserver_measures_even_with_global_obs_off():
    """stats() is public API: the server's own registry is always on."""
    from repro.serve import PlanServer

    assert not obs.enabled()
    with PlanServer(max_batch=2, window_s=0.01) as srv:
        srv.solve(_scenario(system=SYS_1024, C_max=0.25))
        st = srv.stats()
    assert st["submitted"] == 1 and st["latency_s"]["all"]["count"] == 1
    # ...but the global tracer stayed empty (no span leaks while off)
    assert len(obs.TRACER) == 0
