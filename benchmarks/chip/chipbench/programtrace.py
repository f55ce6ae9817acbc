"""The program's own spans and events, beside the benchmark's trace.

Where the program has them (``repro.runtime.profiling``), a ``Trainer.run``
that starts under a JAX profile records its step and phase spans
(``PROGRAM_SPANS``) as profiler annotations and as txtrace spans on the
``trainer`` site, where the store's transaction events land too, and
writes an anchor: an ``ANCHOR`` annotation and a txtrace instant stamped
with the program's clock at its midpoint. ``read`` gathers, once per run:

* the program's annotations, from the run's ``.xplane.pb``;
* the ``trainer`` site's events, drained from their rings and mapped onto
  the profile's clock by the anchor
  (``trace_ns = anchor_ns + (t - t_anchor) * 1e9``);
* each device operation's op name
  (``jit(scoped_train_step)/.../optimizer/...``), from the compiled
  programs' HLO that the profile stores in its ``/host:metadata`` plane
  (a TPU trace's operation events carry none), so the step's ``loss``
  and ``optimizer`` scopes split its device time;
* the program's compile log (``repro.obs.compiles``).

It prints the lines that check these against the trace, and the per-layer
readers compute from it. Against a program without them every reading is
``None``.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import tracefile
from chipbench.tracefile import Interval, Trace

STEP = "train"
PHASES = ("train.batch", "train.dispatch", "train.loss_sync", "train.commit",
          "train.ckpt")
ANCHOR = "txtrace.anchor"
PROGRAM_SPANS = (STEP, *PHASES, ANCHOR)
COMMIT_SLACK_NS = 50e3     # the clock mapping's error the tests allow
STALL_NS = 0.5e9
ProgramEvent = Tuple[str, float, float, int, int, str]  # kind, start, end,
#                                                         ring, pv, txn


@dataclass
class Program:
    """The run's trace with the program's spans among its host spans, the
    op names of its device operations, and the program's events on the
    trace's clock (``mapped`` false: the anchor was lost, none mapped)."""
    trace: Trace
    window: Tuple[float, float]
    scopes: Dict[str, str] = field(default_factory=dict)
    events: List[ProgramEvent] = field(default_factory=list)
    mapped: bool = False
    compile_s: Optional[float] = None       # before the window


_read: Dict[str, Program] = {}


def read(run: Dict) -> Program:
    """The program's side of ``run`` (a per-layer reader's input), read
    once per trace."""
    s = run["session"]
    if s.trace_dir not in _read:
        _read[s.trace_dir] = _gather(run)
    return _read[s.trace_dir]


def _gather(run: Dict) -> Program:
    s, base = run["session"], run["trace"]
    xplane = tracefile.find_xplane(s.trace_dir)
    spans = {k: list(v) for k, v in base.spans.items()}
    for thread, evs in _program_spans(xplane).items():
        spans[thread] = sorted(spans.get(thread, []) + evs,
                               key=lambda x: (x[1], -x[2]))
    p = Program(Trace(base.ops, base.modules, spans), run["trace_window"],
                scopes=hlo_scopes(xplane))
    try:
        from repro.obs import compiles
        from repro.runtime import profiling
    except ImportError:
        return p
    events = profiling.drain()
    anchors = [e["ts"] for e in events if e["kind"] == ANCHOR]
    origin = anchor_ns(p.trace)
    if anchors and origin is not None:
        to_ns = lambda t: origin + (t - anchors[-1]) * 1e9
        p.events = [(e["kind"], to_ns(e["ts"]), to_ns(e["ts"] + e["dur"]),
                     e["ring"], e["pv"], e["txn"]) for e in events]
        p.mapped = True
    t0, t1 = s.window_s
    p.compile_s = compiles.LOG.between(float("-inf"), t0)[2]
    built = [r[3] for r in compiles.LOG.records
             if "train_step" in r[0] and r[1] in ("compile", "load")
             and r[3] is not None]
    inside = compiles.LOG.between(t0, t1)
    print(f"[bench] the program's events: {len(events)}, "
          f"{'mapped by the anchor' if p.mapped else 'not mapped'}; its "
          f"compile log in the window: {inside[0] + inside[1]} "
          f"({inside[0]} compiled, {inside[1]} loaded), before it "
          f"{p.compile_s:.3f}s; the train step built at traced steps "
          f"{built}",
          flush=True)
    _print_checks(p)
    return p


def _program_spans(path: str) -> Dict[str, List[Interval]]:
    """The program's annotations in the profile at ``path``, per host
    thread (named as ``tracefile.load`` names them)."""
    import jax
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                       if e.name in PROGRAM_SPANS]
                if evs:
                    out[f"{plane.name}/{line.name}"] = evs
    return out


def _print_checks(p: Program) -> None:
    lo, hi = p.window
    miss, n = commit_misses(p, COMMIT_SLACK_NS)
    print(f"[bench] trainer transaction events outside their train.commit: "
          f"{miss} of {n}", flush=True)
    ph = phases(p)
    busy = sum(ph.values())
    print("[bench] device time by phase: " + ", ".join(
        f"{k} {v * 1e-9:.3f}s" for k, v in ph.items())
        + f" of busy {busy * 1e-9:.3f}s", flush=True)
    thread = tracefile.window(p.trace, hi - lo)[2]
    idle: Dict[str, float] = {}
    segs = tracefile.innermost(p.trace.spans.get(thread, []))
    for ops in p.trace.ops.values():
        gaps = tracefile.gaps(tracefile.union(ops, lo, hi), lo, hi)
        for k, v in tracefile.attribute(gaps, segs).items():
            idle[k] = idle.get(k, 0.0) + v / len(p.trace.ops)
    print("[bench] idle by innermost span, the program's included: " +
          ", ".join(f"{k} {v * 1e-9:.4f}s" for k, v in
                    sorted(idle.items(), key=lambda kv: -kv[1])), flush=True)
    for st in stalls(p, thread):
        where = ("the device program was open over it" if st["program_open"]
                 else f"the last device program had ended "
                 f"{st['program_ended_before_ms']} ms before it")
        print(f"[bench] stall: {st['gap_s']:.3f}s idle at "
              f"+{st['at_s']:.3f}s, step {st['step']}, under {st['span']}; "
              f"{where}", flush=True)


# -- the clock mapping ---------------------------------------------------------- #
def anchor_ns(tr: Trace) -> Optional[float]:
    """The midpoint of the program's last anchor annotation, or ``None``."""
    anchors = [(s, e) for evs in tr.spans.values() for name, s, e in evs
               if name == ANCHOR]
    return sum(max(anchors)) / 2 if anchors else None


# -- readings ---------------------------------------------------------------------- #
def host_spans(tr: Trace, name: str, lo: float, hi: float) -> List[Interval]:
    """The host spans called ``name`` inside [lo, hi], over all threads."""
    return sorted((x for evs in tr.spans.values() for x in evs
                   if x[0] == name and x[1] >= lo and x[2] <= hi),
                  key=lambda x: x[1])


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]
            ) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(p: Program, name: str) -> Optional[float]:
    """Mean device idle (ns, averaged over devices) inside each host span
    called ``name`` in the window; ``None`` where there is no such span."""
    lo, hi = p.window
    spans = host_spans(p.trace, name, lo, hi)
    if not spans or not p.trace.ops:
        return None
    inside = tracefile.union(spans, lo, hi)
    idle = sum(overlap(tracefile.gaps(tracefile.union(ops, lo, hi), lo, hi),
                       inside) for ops in p.trace.ops.values())
    return idle / len(p.trace.ops) / len(spans)


def trainer_ring(p: Program) -> Optional[int]:
    """The ring (thread) of the program's events that holds its commit
    phases: the trainer's."""
    rings = [r for k, _, _, r, _, _ in p.events if k == "train.commit"]
    return max(set(rings), key=rings.count) if rings else None


def commit_wait(p: Program) -> Optional[float]:
    """Mean trainer time (ns) in the store's ``vwait`` spans inside each
    ``train.commit`` annotation in the window; ``None`` without the
    program's events or commit spans."""
    lo, hi = p.window
    ring = trainer_ring(p)
    commits = host_spans(p.trace, "train.commit", lo, hi)
    if ring is None or not commits:
        return None
    waits = tracefile.union([(k, s, e) for k, s, e, r, _, _ in p.events
                             if k == "vwait" and r == ring], lo, hi)
    return overlap(waits, tracefile.union(commits, lo, hi)) / len(commits)


def commit_misses(p: Program, slack_ns: float) -> Tuple[int, int]:
    """(trainer transaction events outside every ``train.commit``
    annotation, trainer transaction events) in the window: the trainer's
    transactions are those whose ``txn`` span is on the trainer's ring;
    their events are every event carrying their uid, and the ring's
    ``vwait`` spans."""
    lo, hi = p.window
    ring = trainer_ring(p)
    if ring is None:
        return 0, 0
    mine = {t for k, _, _, r, _, t in p.events
            if k == "txn" and r == ring and t}
    evs = [(s, e) for k, s, e, r, _, t in p.events
           if (t in mine or (k == "vwait" and r == ring))
           and s >= lo and e <= hi]
    commits = host_spans(p.trace, "train.commit", lo - slack_ns,
                         hi + slack_ns)
    starts = [c[1] for c in commits]
    miss = 0
    for s, e in evs:
        k = bisect.bisect_right(starts, s + slack_ns) - 1
        miss += not (k >= 0 and e <= commits[k][2] + slack_ns)
    return miss, len(evs)


def short_name(op: str) -> str:
    """``fusion.250`` of a device op's event name, which on a TPU is the
    instruction's text (``%fusion.250 = f32[...] fusion(...), ...``)."""
    return op.split(" ", 1)[0].lstrip("%")


def phase_of(scope: Optional[str]) -> str:
    """``optimizer``, ``loss`` or ``other``, from an op name's scopes."""
    parts = set(re.split(r"[/()]", scope or ""))
    if "optimizer" in parts:
        return "optimizer"
    return "loss" if "loss" in parts else "other"


def phases(p: Program) -> Dict[str, float]:
    """Device busy time (ns, averaged over devices) in the window by phase.
    Each instant of busy time goes to the earliest-starting op that covers
    it (the outer one where ops nest), so the phases sum to busy time."""
    lo, hi = p.window
    out = {"loss": 0.0, "optimizer": 0.0, "other": 0.0}
    for ops in p.trace.ops.values():
        t = lo
        for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
            s, e = max(s, t), min(e, hi)
            if e > s:
                out[phase_of(p.scopes.get(short_name(name)))] += e - s
                t = e
    return {k: v / max(1, len(p.trace.ops)) for k, v in out.items()}


def stalls(p: Program, thread: Optional[str], min_ns: float = STALL_NS
           ) -> List[Dict]:
    """Device idle gaps over ``min_ns`` in the window: when, how long, the
    step (from the program's step spans), the innermost program span open
    over most of the gap on ``thread``, and whether the device program
    that started last before the gap was still open over it (else how long
    before the gap it had ended)."""
    lo, hi = p.window
    segs = tracefile.innermost([x for x in p.trace.spans.get(thread, [])
                                if x[0] in PROGRAM_SPANS and x[0] != ANCHOR])
    steps = [(s, e, pv) for k, s, e, _, pv, _ in p.events if k == STEP]
    out = []
    for dev, ops in p.trace.ops.items():
        for a, b in tracefile.gaps(tracefile.union(ops, lo, hi), lo, hi):
            if b - a < min_ns:
                continue
            under = tracefile.attribute([(a, b)], segs)
            ended = [e for _, s, e in p.trace.modules.get(dev, []) if s <= a]
            out.append({"device": dev, "at_s": (a - lo) * 1e-9,
                        "gap_s": (b - a) * 1e-9,
                        "step": next((pv for s, e, pv in steps
                                      if s <= a < e), None),
                        "span": max(under, key=under.get),
                        "program_open": bool(ended) and max(ended) > a,
                        "program_ended_before_ms":
                            (a - max(ended)) * 1e-6 if ended else None})
    return out


# -- op names from the profile's HLO ----------------------------------------------- #
METADATA_PLANE = "/host:metadata"
HLO_STATS = ("Hlo Proto", "hlo_proto")    # the stat's name, by version


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        if i >= len(buf):
            raise ValueError("truncated varint")
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a protobuf message: ints for varints,
    memoryviews for length-delimited fields, raw bytes for fixed ones."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unknown wire type {wire}")
        yield key >> 3, v


def _text(v: object) -> str:
    """A string field's value; anything else (a varint where the message
    differs from the schema) reads as no string."""
    return bytes(v).decode() if isinstance(v, memoryview) else ""


def _module_scopes(hlo_proto: memoryview) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction name -> op name) of an HloProto:
    hlo_module (1) .name (1) and .computations (3) .instructions (2),
    each with name (1) and metadata (7) .op_name (2)."""
    module_name, out = "", {}
    for f, module in _fields(hlo_proto):
        if f != 1 or not isinstance(module, memoryview):
            continue
        for f2, comp in _fields(module):
            if f2 == 1:
                module_name = _text(comp)
            if f2 != 3 or not isinstance(comp, memoryview):
                continue
            for f3, inst in _fields(comp):
                if f3 != 2 or not isinstance(inst, memoryview):
                    continue
                name = op_name = None
                for f4, v in _fields(inst):
                    if f4 == 1:
                        name = _text(v)
                    elif f4 == 7 and isinstance(v, memoryview):
                        op_name = next((_text(x) for f5, x in _fields(v)
                                        if f5 == 2), None)
                if name and op_name:
                    out[name] = op_name
    return module_name, out


def hlo_scopes(path: str) -> Dict[str, str]:
    """Instruction name -> op name over every program whose HLO the profile
    at ``path`` stores (XSpace.planes(1) named ``/host:metadata``: its
    event metadata's (4) ``Hlo Proto`` stats (5, bytes 6)); the train
    step's names win where programs share one. Empty where there is none
    or it does not parse."""
    modules = []
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    try:
        for f, plane in _fields(buf):
            if f != 1:
                continue
            if not isinstance(plane, memoryview):
                continue
            name = next((_text(v) for k, v in _fields(plane) if k == 2), "")
            if name != METADATA_PLANE:
                continue
            fields = list(_fields(plane))
            stat_names = {}
            for k, entry in fields:
                if k == 5 and isinstance(entry, memoryview):
                    # map<int64, XStatMetadata>: value (2) .id (1), .name (2)
                    md = dict(_fields(entry)).get(2)
                    if isinstance(md, memoryview):
                        md = dict(_fields(md))
                        stat_names[md.get(1)] = _text(md.get(2))
            for k, entry in fields:
                if k != 4 or not isinstance(entry, memoryview):
                    continue        # map<int64, XEventMetadata>
                for k2, em in _fields(entry):
                    if k2 != 2 or not isinstance(em, memoryview):
                        continue
                    for k3, stat in _fields(em):
                        if k3 != 5 or not isinstance(stat, memoryview):
                            continue
                        st = dict(_fields(stat))
                        if (stat_names.get(st.get(1)) in HLO_STATS
                                and isinstance(st.get(6), memoryview)):
                            modules.append(_module_scopes(st[6]))
    except ValueError:
        return {}
    out: Dict[str, str] = {}
    for _, scopes in sorted(modules, key=lambda m: "train_step" in m[0]):
        out.update(scopes)
    return out
