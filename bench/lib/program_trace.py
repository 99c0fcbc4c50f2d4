"""The program's own spans and named scopes, from a ``jax.profiler`` trace.

``bench/lib/trace.py`` reads device operations, programs and the
benchmark's ``bench.*`` annotations.  This module reads, from the same
``.xplane.pb``, what the program itself names:

* program spans: the host ``TraceAnnotation`` events whose names start
  with ``repro.``, which every ``repro.obs.trace.span`` writes whether or
  not ``repro.obs`` is on, each with its arguments (event stats);
* each device operation's scope: the JAX name stack of the operation
  (``jit(genqsgd_round)/local_steps/...``), which holds the program's
  ``jax.named_scope``s.  A TPU trace keeps it in the stat ``tf_op`` of the
  operation's event metadata, which ``ProfileData`` does not expose, so it
  is read from the file's protobuf wire format (``_op_scopes``); the CPU
  backend records none, so there every scope is empty.

XLA's persistent compilation cache leaves the name stack out of its key:
a program loaded from the cache carries the name stacks of the tree that
first compiled it.  ``bench/phases.py`` keys its cache with them.

``ProgramTrace.scope_self_s`` splits device self time by scope, and the
functions below reduce a trace to a round's time by phase and a sweep's
host stages and GIA counters.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as T

__all__ = ["ProgramTrace", "Span", "ScopedOp", "load", "ROUND_PHASES",
           "round_phases", "sweep_stages", "SCOPE_STAT"]

SPAN_PREFIX = "repro."
#: the stat of an operation's event metadata that holds its name stack
SCOPE_STAT = "tf_op"
#: the four ``jax.named_scope``s of ``fed/runtime.py``'s round, in order
ROUND_PHASES = ("local_steps", "worker_encode", "aggregate", "server_update")


@dataclasses.dataclass
class Span:
    name: str                        # without the ``repro.`` prefix
    start: int
    end: int
    args: Dict[str, object]

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class ScopedOp:
    device: str
    name: str
    start: int
    end: int
    scope: str                       # the JAX name stack, "" where unknown


def _in_stack(scope: str, name: str) -> bool:
    """Whether ``name`` is one component of the name stack ``scope``,
    alone or inside a transform's parentheses (``transpose(jvp(name))``)."""
    return re.search(r"(^|[/(])" + re.escape(name) + r"([/):]|$)",
                     scope) is not None


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]
    ops: List[ScopedOp]
    window: T.Interval

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def span_s(self, name: str) -> float:
        """Seconds of all spans called ``name``."""
        return sum(s.seconds for s in self.named(name))

    def scope_self_s(self, names: Sequence[str]) -> Dict[str, float]:
        """Device self time per scope, in seconds summed over all runs and
        averaged over devices.  An operation's self time is its time less
        that of the operations nested in it (as ``Trace.top_ops`` counts
        it); it belongs to the first of ``names`` that appears in its name
        stack, else to ``"other"``."""
        per = collections.Counter({n: 0 for n in (*names, "other")})
        devices = sorted({o.device for o in self.ops})
        for d in devices:
            ops = sorted((o for o in self.ops if o.device == d),
                         key=lambda o: (o.start, -o.end))
            stack: List[Tuple[ScopedOp, str]] = []
            for o in ops:
                key = next((n for n in names if _in_stack(o.scope, n)),
                           "other")
                while stack and stack[-1][0].end <= o.start:
                    stack.pop()
                per[key] += o.end - o.start
                if stack and o.end <= stack[-1][0].end:
                    per[stack[-1][1]] -= o.end - o.start
                stack.append((o, key))
        k = max(1, len(devices))
        return {n: ns / k / 1e9 for n, ns in per.items()}


# -- the xplane's protobuf wire format, for what ProfileData leaves out ------
def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, v


def _op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {operation's event name: its name stack}}.

    Field numbers of ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes
    1; XPlane.name 2, event_metadata 4 and stat_metadata 5 (maps: key 1,
    value 2); XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7 (a stat metadata id whose
    name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, strings = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum in (4, 5):
                value = dict(_fields(v)).get(2)
                if value is None:
                    continue
                if pnum == 4:
                    events.append(value)
                else:
                    meta = dict(_fields(value))
                    strings[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:") or SCOPE_STAT not in \
                strings.values():
            continue
        stat_id = next(k for k, v in strings.items() if v == SCOPE_STAT)
        scopes = out.setdefault(name, {})
        for ev in events:
            ename, scope = "", ""
            for enum, v in _fields(ev):
                if enum == 2:
                    ename = bytes(v).decode()
                elif enum == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) != stat_id:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = strings.get(stat[7], "")
            if scope:
                scopes[ename] = scope
    return out


def load(path: str, window: Optional[T.Interval] = None) -> ProgramTrace:
    """Read one ``.xplane.pb``: the program's spans, and the device
    operations with their scopes, clipped to ``window`` (by default the
    ``bench.window`` annotation's span, else the extent of all events),
    as ``trace.load`` clips them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    scopes = _op_scopes(path)
    spans: List[Span] = []
    ops: List[ScopedOp] = []
    marks: List[T.Interval] = []
    device_planes = {p.name for p in pd.planes
                     if p.name.startswith("/device:")
                     and not p.name.startswith("/device:CUSTOM")}
    for plane in pd.planes:
        on_device = plane.name in device_planes
        for line in plane.lines:
            if on_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                name = ev.name
                if on_device:
                    ops.append(ScopedOp(plane.name,
                                        T._op(plane.name, name, "", s, e)
                                        .name, s, e,
                                        scopes.get(plane.name, {})
                                        .get(name, "")))
                elif name.startswith(SPAN_PREFIX):
                    spans.append(Span(name[len(SPAN_PREFIX):], s, e,
                                      T._stats(ev)))
                elif name == T.SPAN_PREFIX + "window":
                    marks.append((s, e))
                elif not device_planes and e > s:
                    st = T._stats(ev)
                    if "hlo_op" in st:
                        ops.append(ScopedOp(
                            f"/cpu:{st.get('device_ordinal', 0)}", name, s,
                            e, ""))
    if window is None:
        if marks:
            window = marks[0]
        else:
            allt = [o.start for o in ops] + [o.end for o in ops]
            window = (min(allt), max(allt)) if allt else (0, 0)
    lo, hi = window
    ops = [dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))
           for o in ops if o.end > lo and o.start < hi]
    return ProgramTrace(spans=spans, ops=ops, window=window)


# ---------------------------------------------------------------------------
# the reductions
# ---------------------------------------------------------------------------
def round_phases(pt: ProgramTrace, runs: int) -> Optional[Dict[str, float]]:
    """Device self seconds per run of the round program for each of its
    four phases and for the rest (``other``); None where no operation
    carries a phase's scope or the program did not run."""
    split = pt.scope_self_s(ROUND_PHASES)
    if runs <= 0 or not any(split[n] for n in ROUND_PHASES):
        return None
    return {n: v / runs for n, v in split.items()}


def sweep_stages(pt: ProgramTrace) -> Optional[Dict[str, float]]:
    """Per ``sweep`` span: host seconds before the device solve
    (``sweep.build`` + ``gia.pack``) and after it (``gia.finalize`` +
    ``sweep.freeze``), and the fused dispatches' lockstep counters from the
    ``gia.finalize`` spans; None where the trace holds no sweep.  The
    active-row share is the row iterations in which a row still worked
    over all rows times the loop's trip count, in %."""
    sweeps = len(pt.named("sweep"))
    fin = pt.named("gia.finalize")
    if not sweeps or not fin:
        return None
    iters = sum(int(s.args["lockstep_iters"]) for s in fin)
    slots = sum(int(s.args["lockstep_iters"]) * int(s.args["rows"])
                for s in fin)
    active = sum(int(s.args["active_row_iters"]) for s in fin)
    return {
        "host_prep_s": (pt.span_s("sweep.build")
                        + pt.span_s("gia.pack")) / sweeps,
        "host_freeze_s": (pt.span_s("gia.finalize")
                          + pt.span_s("sweep.freeze")) / sweeps,
        "dispatch_s": pt.span_s("gia.fused_dispatch") / sweeps,
        "lockstep_iters_per_dispatch": iters / len(fin),
        "active_row_share": 100.0 * active / slots if slots else None,
    }
