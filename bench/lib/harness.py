"""The benchmark's one general runner.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
runner finds everything else by those names:

* ``bench/configs/<config>.json``   sizes and settings of the configuration;
* ``bench/traffic/<traffic>.json``  the mix's parameters, and the ``driver``
  that runs it;
* ``bench/drivers/<driver>.py``     a module with ``run(run) -> Outcome``;
* ``bench/metrics/<metric>.py``     a module with ``read(view)`` per
  per-layer metric, returning a number or None.

So a later change adds a cell, a configuration, a mix or a per-layer metric
by adding files and entries only.  The runner checks the device, keeps
JAX's persistent compilation cache at a fixed path, times set-up and the
measured window, traces the window when asked, reads the per-layer
metrics, and prints the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

__all__ = ["Spec", "Run", "Check", "Outcome", "View", "execute", "main",
           "derived_seed"]

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a Python file by path (file names may hold dots)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_") \
        .replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def derived_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed drawn from the whole of ``seed`` (which may exceed 32
    bits) and ``salt``: JAX's integer keys keep only the low 32 bits."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# the specification, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Spec:
    bench: dict                      # BENCHMARK.json
    cell: dict                       # its workloads entry
    config: dict                     # bench/configs/<config>.json
    traffic: dict                    # bench/traffic/<traffic>.json
    bench_dir: str

    @classmethod
    def load(cls, cell_name: str, root: str = ROOT,
             bench_dir: str = BENCH_DIR) -> "Spec":
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if cell_name not in cells:
            raise KeyError(f"no workload {cell_name!r}; known: "
                           f"{sorted(cells)}")
        cell = cells[cell_name]
        config = _load_json(os.path.join(bench_dir, "configs",
                                         cell["config"] + ".json"))
        traffic = _load_json(os.path.join(bench_dir, "traffic",
                                          cell["traffic"] + ".json"))
        return cls(bench, cell, config, traffic, bench_dir)

    def driver(self):
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        self.traffic["driver"] + ".py"))

    def _applies(self, metric: dict) -> bool:
        return ("workloads" not in metric
                or self.cell["name"] in metric["workloads"])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bench["per_layer"] if self._applies(m)]

    def reader(self, metric: dict) -> Callable:
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric["name"] + ".py")).read


# ---------------------------------------------------------------------------
# what a driver returns
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]     # measured values, by metric name
    checks: List[Check]
    counters: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclasses.dataclass
class View:
    """What a per-layer metric's reader may read."""
    trace: object                    # lib.trace.Trace, or None
    end_to_end: Dict[str, float]
    counters: Dict[str, object]
    config: dict
    traffic: dict
    device_kind: str
    chips: int


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class Run:
    """The state of one run, handed to the driver.

    The driver builds what it needs during set-up, measures inside
    ``with run.window():`` (calling ``run.between()`` between units of
    work, so a traced run can close its trace), and checks its answers
    after the window.  ``run.span(name)`` marks a call into a layer; in a
    traced run it lands in the profiler's trace on the device's clock.
    """

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 t0: float, on_chip: bool = True):
        self.spec = spec
        self.cell = spec.cell
        self.config = spec.config
        self.traffic = spec.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.on_chip = on_chip
        self.t0 = t0
        self.chips = int(spec.cell["chips"])
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.compiles_in_window = 0
        self._tracing = False
        self._trace_dir: Optional[str] = None
        self._traced = None
        self._trace_stop_s = 0.0
        self._trace_until = None
        self.trace_result = None
        self.notes: List[str] = []

    # -- seeds --------------------------------------------------------------
    def rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence([self.seed & (2**64 - 1), *salt]))

    def key(self, *salt: int):
        import jax
        return jax.random.PRNGKey(derived_seed(self.seed, *salt))

    def devices(self):
        import jax
        return jax.devices()[:self.chips]

    def note(self, msg: str):
        """A line for standard error, printed before the checks."""
        self.notes.append(msg)
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    # -- spans ----------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        if self._tracing:
            import jax
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield

    # -- the measured window ------------------------------------------------
    @contextlib.contextmanager
    def window(self):
        import jax

        self.setup_s = time.perf_counter() - self.t0
        counter = _CompileCounter()
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._trace_dir)
            # the traced window, on the trace's clock: opened here, closed
            # just before the trace stops
            self._traced = jax.profiler.TraceAnnotation("bench.window")
            self._traced.__enter__()
            self._tracing = True
            cap = self.traffic.get("trace_seconds")
            self._trace_until = (None if cap is None else
                                 time.perf_counter() + float(cap))
        start = time.perf_counter()
        with counter:
            yield self
            # a trace stopped inside the window (``between``) held the host
            # while the device had no work queued: not the program's time
            self.window_s = (time.perf_counter() - start
                             - self._trace_stop_s)
        self.compiles_in_window = counter.count
        self._stop_trace()
        self.memory_peak_bytes = self._memory_peak()

    def between(self, drain: Optional[Callable] = None):
        """Call between units of work inside the window.  A traced run
        whose traffic sets ``trace_seconds`` closes its trace there once
        that much of the window has passed: ``drain`` (where a unit
        returns before its device work is done) waits for that work, and
        the time the profiler then takes to stop is left out of the
        window."""
        if (self._tracing and self._trace_until is not None
                and time.perf_counter() >= self._trace_until):
            if drain is not None:
                drain()
            t0 = time.perf_counter()
            self._stop_trace()
            self._trace_stop_s = time.perf_counter() - t0

    def _stop_trace(self):
        if not self._tracing:
            return
        import jax
        self._traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False

    def _memory_peak(self) -> Optional[int]:
        peaks = []
        for d in self.devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None

    def read_trace(self):
        """Reduce the window's trace (once) and delete its files."""
        if self.trace_result is None and self._trace_dir is not None:
            from . import trace as T
            try:
                self.trace_result = T.load(T.find_xplane(self._trace_dir))
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None
        return self.trace_result


class _CompileCounter:
    """Counts XLA compilations and persistent-cache loads, by JAX's
    monitoring events, while entered."""

    EVENTS = ("backend_compile", "cache_retrieval")

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **kw):
        if any(e in event for e in self.EVENTS):
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


# ---------------------------------------------------------------------------
def _device_ok(chips: int):
    """-> (ok, devices, message).  A run needs TPUs, as many as asked."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return False, devs, f"no TPU: JAX found {devs[0].platform}"
    if len(devs) < chips:
        return False, devs, f"{chips} chips wanted, JAX found {len(devs)}"
    return True, devs, ""


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` where the environment sets it), keeping
    every program however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            t0: Optional[float] = None, on_chip: bool = True,
            root: str = ROOT, bench_dir: str = BENCH_DIR) -> dict:
    """Run one cell; -> the result line as a dict.  ``on_chip=False``
    (tests only) runs on whatever JAX finds and reports no metric."""
    t0 = time.perf_counter() if t0 is None else t0
    spec = Spec.load(cell_name, root, bench_dir)
    import jax

    if on_chip:
        ok, devs, msg = _device_ok(int(spec.cell["chips"]))
        if not ok:
            raise NoChip(msg)
        enable_compile_cache(root)
    devs = jax.devices()
    run = Run(spec, seed, seconds, trace, t0, on_chip=on_chip)
    out: Outcome = spec.driver().run(run)
    if run.window_s is None:
        raise RuntimeError("the driver measured no window")
    run.note(f"set-up {run.setup_s:.3f} s, window {run.window_s:.3f} s, "
             f"compilations in the window {run.compiles_in_window}")

    metrics: Dict[str, dict] = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    line: Dict[str, object] = {}
    e2e = dict(out.end_to_end, setup_s=run.setup_s)
    tr = run.read_trace() if trace else None
    if trace and tr is not None:
        busy = tr.busy_s()
        device["busy_s"] = busy
        device["window_s"] = tr.window_s
    if on_chip:
        if trace:
            view = View(trace=tr, end_to_end=e2e, counters=out.counters,
                        config=spec.config, traffic=spec.traffic,
                        device_kind=devs[0].device_kind, chips=run.chips)
            for m in spec.per_layer():
                v = spec.reader(m)(view)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        else:
            for m in spec.end_to_end():
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    line.update(correct=out.correct, attempted=int(out.attempted),
                failed=int(out.failed), metrics=metrics, device=device)
    if trace and tr is not None:
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    line["checks"] = {c.name: {"value": float(c.value),
                               "limit": float(c.limit)} for c in out.checks}
    return line


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = execute(args.workload, args.seed, args.seconds,
                       bool(args.trace), t0=t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in line["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if ok else ' FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
