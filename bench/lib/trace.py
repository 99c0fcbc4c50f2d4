"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a traced window wrote, with nothing but
``jax.profiler.ProfileData``, into plain intervals:

* device operations: on a TPU the events of each ``/device:TPU:<n>``
  plane's "XLA Ops" line; where there is no device plane (the CPU backend)
  the host events that carry an ``hlo_op`` stat, which is where that
  backend records its thunks;
* programs: a TPU's "XLA Modules" line (one event per run of a jitted
  program); on the CPU backend the ``hlo_module`` stat of each operation;
* host spans: the ``TraceAnnotation`` events whose names start with
  ``bench.``, which the benchmark records around its calls into each layer.

``Trace`` then gives busy and idle time as the union of operation
intervals, device time per program, collective time exposed or hidden
behind compute, and the longest idle gaps with the host span that was open
across each.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Trace", "load", "find_xplane", "union", "COLLECTIVE"]

#: HLO collectives, by the op names XLA gives them (async start/done pairs
#: included): all-gather, all-reduce, reduce-scatter, all-to-all,
#: collective-permute
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute)")

SPAN_PREFIX = "bench."

Interval = Tuple[int, int]          # (start_ns, end_ns)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Op:
    device: str
    name: str
    program: str
    start: int
    end: int
    collective: bool = False


def _op(device: str, text: str, program: str, start: int, end: int) -> Op:
    """An operation from its trace text: a TPU names its events by the
    whole HLO instruction (``%all-gather-start.3 = bf16[...] ...``); the
    name kept is the part before `` = ``, the kind is read from the whole."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return Op(device, name, program, start, end,
              bool(COLLECTIVE.search(text)))


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    programs: List[Op]               # one interval per program run
    spans: List[Tuple[str, int, int]]
    window: Interval                 # the traced window, host clock

    # -- busy and idle ------------------------------------------------------
    @property
    def devices(self) -> List[str]:
        return sorted({o.device for o in self.ops})

    def busy(self, device: str) -> List[Interval]:
        return union([(o.start, o.end) for o in self.ops
                      if o.device == device])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the devices
        that ran any; None where no device operation was recorded."""
        devs = self.devices
        if not devs:
            return None
        return sum(_length(self.busy(d)) for d in devs) / len(devs) / 1e9

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s()
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    # -- programs -----------------------------------------------------------
    def program_time(self, pattern: str) -> Tuple[int, float]:
        """(runs, device seconds) of the programs whose name matches
        ``pattern`` (a regular expression), over all devices' runs."""
        rx = re.compile(pattern)
        runs = [p for p in self.programs if rx.search(p.name)]
        return len(runs), sum(p.end - p.start for p in runs) / 1e9

    # -- collectives ---------------------------------------------------------
    def collective_s(self) -> Tuple[float, float]:
        """(collective seconds, of which exposed), averaged over devices.
        Exposed is the part of the collectives' union during which no
        other operation runs on that device."""
        devs = self.devices
        if not devs:
            return 0.0, 0.0
        tot = exp = 0
        for d in devs:
            coll = union([(o.start, o.end) for o in self.ops
                          if o.device == d and o.collective])
            comp = union([(o.start, o.end) for o in self.ops
                          if o.device == d and not o.collective])
            tot += _length(coll)
            exp += _length(_subtract(coll, comp))
        return tot / len(devs) / 1e9, exp / len(devs) / 1e9

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operation names that took most device self time (an
        operation's time less that of the operations nested in it, as a
        ``while`` holds its body's), in seconds summed over all runs and
        averaged over devices."""
        per = collections.Counter()
        for d in self.devices:
            ops = sorted((o for o in self.ops if o.device == d),
                         key=lambda o: (o.start, -o.end))
            stack: List[Op] = []
            for o in ops:
                while stack and stack[-1].end <= o.start:
                    stack.pop()
                per[o.name] += o.end - o.start
                if stack and o.end <= stack[-1].end:
                    per[stack[-1].name] -= o.end - o.start
                stack.append(o)
        k = max(1, len(self.devices))
        return [[name, ns / k / 1e9] for name, ns in per.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first device inside the
        window, each named by the innermost benchmark span open across its
        middle ("none" where no span was open)."""
        devs = self.devices
        if not devs:
            return []
        busy = self.busy(devs[0])
        gaps = _subtract([self.window], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            open_ = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
            name = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                    if open_ else "none")
            out.append([name, (e - s) / 1e9])
        return out


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str, window: Optional[Interval] = None) -> Trace:
    """Read one ``.xplane.pb``.  ``window`` defaults to the span of the
    ``bench.window`` annotation, else to the extent of all events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    programs: List[Op] = []
    spans: List[Tuple[str, int, int]] = []
    device_planes = {p.name for p in pd.planes
                     if p.name.startswith("/device:")
                     and not p.name.startswith("/device:CUSTOM")}
    for plane in pd.planes:
        on_device = plane.name in device_planes
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                name = ev.name
                if on_device:
                    if line.name == "XLA Ops":
                        ops.append(_op(plane.name, name, "", s, e))
                    elif line.name == "XLA Modules":
                        programs.append(Op(plane.name, name, name, s, e))
                    continue
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, e))
                elif not device_planes and e > s:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        dev = f"/cpu:{st.get('device_ordinal', 0)}"
                        prog = str(st.get("hlo_module", ""))
                        ops.append(_op(dev, name, prog, s, e))
    if not device_planes:
        # the CPU backend has no program line: one interval per program run
        runs = collections.defaultdict(list)
        for o in ops:
            runs[(o.device, o.program)].append((o.start, o.end))
        for (dev, prog), iv in runs.items():
            for s, e in union(iv):
                programs.append(Op(dev, prog, prog, s, e))
    if window is None:
        marks = [sp for sp in spans if sp[0] == SPAN_PREFIX + "window"]
        if marks:
            window = (marks[0][1], marks[0][2])
        else:
            allt = [o.start for o in ops] + [o.end for o in ops]
            window = (min(allt), max(allt)) if allt else (0, 0)
    lo, hi = window
    ops = [dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))
           for o in ops if o.end > lo and o.start < hi]
    return Trace(ops=ops, programs=programs, spans=spans, window=window)
