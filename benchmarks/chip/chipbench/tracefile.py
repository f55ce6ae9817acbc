"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records: the operations of every TPU device (one interval each), the
programs (XLA modules) they ran, and the benchmark's host spans. Everything
after that is plain Python over those records, so the tests check it on a
small recorded trace with no profiler at all.

Busy time is the union of a device's operation intervals inside the window;
the idle share is one minus busy over the window, averaged over devices.
Each idle gap is put against the innermost host span open on the window's
thread during it, so the gaps say what the host was doing.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]      # (name, start_ns, end_ns)

WINDOW = "window"
MARKS = ("window_start", "window_end")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "host:none"


@dataclass
class Trace:
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    def to_json(self) -> Dict:
        return {"ops": self.ops, "modules": self.modules, "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        conv = lambda m: {k: [tuple(e) for e in v] for k, v in m.items()}
        return cls(conv(d["ops"]), conv(d["modules"]), conv(d["spans"]))


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, span_names: Sequence[str]) -> Trace:
    """Device operations and modules of every ``/device:TPU:<n>`` plane, and
    the host spans named in ``span_names`` per host thread."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    wanted = set(span_names) | {WINDOW, *MARKS}
    tr = Trace()
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = tr.ops if line.name == OPS_LINE else tr.modules
                    dst[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                       if e.name in wanted]
                if evs:
                    tr.spans[f"{plane.name}/{line.name}"] = evs
    return tr


# --------------------------------------------------------------------------- #
def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covering ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def window(tr: Trace, wall_ns: Optional[float] = None
           ) -> Tuple[float, float, str, str]:
    """The benchmark's ``window`` span, or else its two short marker spans:
    (start, end, the thread they are on, ``"markers"``).

    The profiler does not always keep these host annotations. Then the
    window has the length the host clock gave it, ``wall_ns``, and ends
    where the last program on a device ended (the window closes when the
    host has waited for the device's last result): (start, end, the thread
    holding the most kinds of host span, ``"host_clock"``)."""
    marks = {}
    for thread, evs in tr.spans.items():
        for name, s, e in evs:
            if name == WINDOW:
                return s, e, thread, "markers"
            if name in MARKS:
                marks[name] = (s, thread)
    if len(marks) == 2:
        return (marks[MARKS[0]][0], marks[MARKS[1]][0], marks[MARKS[0]][1],
                "markers")
    runs = [x for evs in tr.modules.values() for x in evs]
    if not runs or not wall_ns:
        raise ValueError("the trace holds no 'window' span or markers, and "
                         "no program ran on a device or no host-clock "
                         "length was given")
    thread = max(tr.spans, default=None, key=lambda t: (
        len({n for n, _, _ in tr.spans[t]}), len(tr.spans[t])))
    hi = max(e for _, _, e in runs)
    return hi - wall_ns, hi, thread, "host_clock"


def innermost(spans: Sequence[Interval]) -> List[Interval]:
    """Properly nested spans of one thread -> disjoint segments, each named
    by the deepest span open over it (the window itself excluded)."""
    out: List[Interval] = []

    def emit(a: float, b: float, name: str) -> None:
        if b <= a:
            return
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))

    stack: List[Interval] = []
    t = 0.0
    for x in sorted((x for x in spans if x[0] != WINDOW),
                    key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= x[1]:
            top = stack.pop()
            emit(t, top[2], top[0])
            t = top[2]
        if stack:
            emit(t, x[1], stack[-1][0])
        stack.append(x)
        t = x[1]
    while stack:
        top = stack.pop()
        emit(t, top[2], top[0])
        t = top[2]
    return out


def attribute(idle: Sequence[Tuple[float, float]],
              segments: Sequence[Interval]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` under each segment's name (``host:none``
    where no span is open)."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(segments) and segments[j][2] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][1] < e:
            name, a, b = segments[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        out[NO_SPAN] += (e - s) - covered
    return dict(out)


def reduce(tr: Trace, wall_ns: Optional[float] = None, top: int = 10) -> Dict:
    """busy_s and window_s (averaged over devices), the idle share, the
    device operations that took most time, the longest idle causes, and
    the window (``window_ns``) and where it came from (``window_from``)."""
    lo, hi, thread, source = window(tr, wall_ns)
    if not tr.ops:
        raise ValueError("the trace holds no TPU device operations")
    busy_ns = 0.0
    idle_by: Dict[str, float] = defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    segs = innermost(tr.spans.get(thread, []))
    for dev, ops in tr.ops.items():
        u = union(ops, lo, hi)
        busy_ns += sum(b - a for a, b in u)
        for name, ns in attribute(gaps(u, lo, hi), segs).items():
            idle_by[name] += ns
        for name, s, e in ops:
            if e > lo and s < hi:
                op_time[name] += min(e, hi) - max(s, lo)
    n = len(tr.ops)
    busy_s = busy_ns / n * 1e-9
    window_s = (hi - lo) * 1e-9
    rank = lambda d: [[k, v / n * 1e-9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": rank(op_time), "idle_gaps": rank(idle_by),
            "window_ns": (lo, hi), "window_from": source}


def matching(events: Dict[str, List[Interval]], pattern: str,
             lo: float, hi: float) -> List[float]:
    """Durations (ns) of the events whose name matches ``pattern`` and that
    lie inside [lo, hi], over all devices."""
    rx = re.compile(pattern)
    return [e - s for evs in events.values() for name, s, e in evs
            if rx.search(name) and s >= lo and e <= hi]


def save(tr: Trace, path: str) -> None:
    """As JSON, gzipped where ``path`` ends in ``.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(tr.to_json(), f)


def describe(path: str) -> Dict[str, Dict[str, List]]:
    """Plane -> line -> [event count, a few distinct event names]."""
    import jax
    out: Dict[str, Dict[str, List]] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            names, n = [], 0
            for e in line.events:
                n += 1
                if len(names) < 12 and e.name not in names:
                    names.append(e.name)
            lines[line.name] = [n, names]
    return out
