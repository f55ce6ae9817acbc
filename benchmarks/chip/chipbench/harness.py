"""One run of one cell: find its files by name, check the device, hand the
cell to its driver, print the result line.

Everything a cell is made of is found by the names in ``BENCHMARK.json``:

* ``configs[].file``            the configuration, as it is run;
* ``traffic/<traffic>.json``    the mix; its ``kind`` names the driver;
* ``drivers/<kind>.py``         the driver of that kind of traffic;
* ``limits/<workload>.json``    the limits of the numbers ``correct`` compares;
* ``metrics/<name>.py``         one reader per per-layer metric;
* ``refs/<reference>.py``       the configuration's plain reference;
* ``archs/<model_type>.py``     what the yardstick knows of a model type:
  its widths and layer groups, its operation counts, its weight rules.

A metric may be split by cells: ``<metric>.<part>``
(``train_tokens_per_s.mesh`` beside ``train_tokens_per_s``), so that the
cells of each part carry a bound or a ``moves`` of their own. Where it has
no reader or driver value of its own, it is ``<metric>``'s, read in its own
cells.

An unknown name is an error, never a default. A driver builds the program,
warms it up, runs the timed window inside ``Session.window``, reads device
memory, frees the program and compares what the window produced with the
reference. The harness turns that into the result line.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout


class BenchError(Exception):
    """A run that cannot produce a result (exit code 2)."""


def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise BenchError(f"no such file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str) -> ModuleType:
    """``<kind>/<name>.py`` under the benchmark's directory."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"unknown {kind[:-1]} {name!r}: no {kind}/{name}.py")
    d = str(path.parent)
    if kind == "refs" and d not in sys.path:
        sys.path.insert(0, d)      # a reference may import its sibling
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ARCHS: Dict[Tuple[Path, str], ModuleType] = {}


def arch(model_type: str) -> ModuleType:
    """``archs/<model_type>.py``, loaded once."""
    key = (HERE, model_type)
    if key not in _ARCHS:
        _ARCHS[key] = load_module("archs", model_type)
    return _ARCHS[key]


def reader(name: str) -> ModuleType:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or,
    for a part of a split metric without one, its metric's."""
    base = name.rsplit(".", 1)[0]
    if (not (HERE / "metrics" / f"{name}.py").is_file()
            and (HERE / "metrics" / f"{base}.py").is_file()):
        name = base
    return load_module("metrics", name)


def driver_value(values: Dict[str, float], name: str) -> float:
    """End-to-end metric ``name`` among a driver's values; a part of a
    split metric is its metric's value."""
    return float(values[name] if name in values
                 else values[name.rsplit(".", 1)[0]])


def applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


@dataclass
class Cell:
    """Everything one workload is made of, found by name."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @classmethod
    def find(cls, workload: str, bench: Optional[Dict] = None) -> "Cell":
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        w = cells[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        if w["config"] not in configs:
            raise BenchError(f"unknown configuration {w['config']!r}")
        config = load_json(ROOT / configs[w["config"]]["file"])
        traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
        limits = load_json(HERE / "limits" / f"{workload}.json")
        return cls(workload, int(w["chips"]), config, traffic, limits,
                   [m for m in bench["end_to_end"] if applies(m, workload)],
                   [m for m in bench["per_layer"] if applies(m, workload)])


# --------------------------------------------------------------------------- #
class StampList(list):
    """``Request.out`` that stamps each token with the host clock as the
    program appends it."""

    def __init__(self):
        super().__init__()
        self.stamps: List[float] = []

    def append(self, tok) -> None:
        self.stamps.append(time.perf_counter())
        super().append(tok)


class GcClock:
    """Pauses of Python's cyclic collector: (generation, start, seconds)."""

    def __init__(self):
        self.pauses: List[Tuple[int, float, float]] = []
        self._start: Optional[Tuple[int, float]] = None

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._start = (info["generation"], time.perf_counter())
        elif self._start is not None:
            gen, t = self._start
            self.pauses.append((gen, t, time.perf_counter() - t))
            self._start = None

    def summary(self, t0: float) -> str:
        parts = []
        for gen in (0, 1, 2):
            p = [x for x in self.pauses if x[0] == gen]
            if p:
                g, t, d = max(p, key=lambda x: x[2])
                parts.append(f"gen{gen} {len(p)} (longest {d * 1e3:.1f} ms "
                             f"at +{t - t0:.2f}s)")
        return ", ".join(parts) or "none"


class HoldClock:
    """Times the whole interpreter was held up: a thread wakes every
    ``TICK`` seconds and keeps each wake-up over ``LIMIT`` late as (start,
    seconds). A held lock on the interpreter shows here, and so does a
    process the host did not run; a thread waiting on the device does not
    (it lets the others run)."""
    TICK, LIMIT = 0.05, 0.5

    def __init__(self):
        self.holds: List[Tuple[float, float]] = []
        self._last: Optional[float] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def observe(self, now: float) -> None:
        if self._last is not None and now - self._last > self.TICK + self.LIMIT:
            self.holds.append((self._last, now - self._last - self.TICK))
        self._last = now

    def _loop(self) -> None:
        self.observe(time.perf_counter())
        while not self._stop.wait(self.TICK):
            self.observe(time.perf_counter())

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def summary(self, t0: float) -> str:
        if not self.holds:
            return "none"
        t, d = max(self.holds, key=lambda x: x[1])
        return (f"{len(self.holds)} over {self.LIMIT}s (longest {d:.3f}s at "
                f"+{t - t0:.2f}s)")


def host_counters() -> Dict[str, float]:
    """What the host did beside the run, from ``/proc`` where it has it:
    seconds of CPU the machine's host took away (``steal``) or spent waiting
    on I/O (``iowait``), summed over CPUs; seconds in which some task waited
    for CPU, memory or I/O (``psi_*``); this process's major page faults.
    Read at the window's two ends, to put a hold-up beside its cause."""
    out: Dict[str, float] = {}

    def read(path: str) -> str:
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ""

    cpu = read("/proc/stat").split("\n", 1)[0].split()
    if len(cpu) > 8:
        hz = os.sysconf("SC_CLK_TCK")
        out["iowait"], out["steal"] = int(cpu[5]) / hz, int(cpu[8]) / hz
    for res in ("cpu", "memory", "io"):
        for line in read(f"/proc/pressure/{res}").splitlines():
            if line.startswith("some"):
                out[f"psi_{res}"] = int(line.rsplit("total=", 1)[1]) * 1e-6
    stat = read("/proc/self/stat").rsplit(")", 1)[-1].split()
    if len(stat) > 9:
        out["majflt"] = float(stat[9])
    return out


@dataclass
class Session:
    """What a driver gets: the cell, the arguments, and the run's clocks,
    spans and trace."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    devices: List[Any] = field(default_factory=list)
    spans: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    window_s: Tuple[float, float] = (0.0, 0.0)
    setup_s: float = 0.0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    trace_dir: Optional[str] = None
    _lock: threading.Lock = field(default_factory=threading.Lock)

    # -- spans (traced runs only) ---------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: a profiler annotation, and its host-clock interval
        kept under ``name``. A no-op unless the run is traced."""
        if not self.trace:
            yield
            return
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        with self._lock:
            self.spans.setdefault(name, []).append((t, time.perf_counter()))

    def wrap(self, obj: Any, attr: str, name: str,
             post: Optional[Callable[[Any], Any]] = None) -> None:
        """Put ``span(name)`` around ``obj.attr`` (traced runs only)."""
        if not self.trace:
            return
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self.span(name):
                out = fn(*a, **k)
            return post(out) if post else out

        setattr(obj, attr, wrapped)

    # -- the window -----------------------------------------------------------
    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens; compilations
        inside it are counted; a traced run records it."""
        import jax
        from jax import monitoring
        count = [0]

        def on_event(event: str, *_a, **_k) -> None:
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/compilation_cache/cache_hits"):
                count[0] += 1

        monitoring.register_event_duration_secs_listener(on_event)
        monitoring.register_event_listener(on_event)
        gc_clock, hold_clock = GcClock(), HoldClock()
        gc.callbacks.append(gc_clock)
        host0 = host_counters()
        hold_clock.start()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        self.setup_s = time.monotonic() - self.t_process
        try:
            if self.trace:
                with jax.profiler.TraceAnnotation("window_start"):
                    pass
                with jax.profiler.TraceAnnotation("window"):
                    yield
                with jax.profiler.TraceAnnotation("window_end"):
                    pass
            else:
                yield
        finally:
            t1 = time.perf_counter()
            self.window_s = (t0, t1)
            hold_clock.stop()
            gc.callbacks.remove(gc_clock)
            if self.trace:
                jax.profiler.stop_trace()
            monitoring.unregister_event_duration_listener(on_event)
            monitoring.unregister_event_listener(on_event)
            self.compiles_in_window = count[0]
            print(f"[bench] collector pauses in the window: "
                  f"{gc_clock.summary(t0)}", flush=True)
            print(f"[bench] interpreter held up in the window: "
                  f"{hold_clock.summary(t0)}", flush=True)
            host1 = host_counters()
            print("[bench] host in the window: " + ", ".join(
                f"{k} {host1[k] - v:.3f}" for k, v in host0.items()
                if k in host1), flush=True)

    def note(self, what: str) -> None:
        """A line on standard output: ``what`` and the seconds since the
        process started (where a run's time goes)."""
        print(f"[bench] {what} at {time.monotonic() - self.t_process:.1f}s",
              flush=True)

    @property
    def wall_s(self) -> float:
        return self.window_s[1] - self.window_s[0]

    def read_memory(self) -> None:
        """The peak on the fullest chip; read before the reference runs."""
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices[:self.cell.chips])

    def spans_in_window(self, name: str) -> List[float]:
        lo, hi = self.window_s
        return [e - s for s, e in self.spans.get(name, []) if s >= lo and e <= hi]


@dataclass
class Outcome:
    """What a driver returns."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, Tuple[float, float]]      # name -> (value, limit)
    layer: Dict[str, Any] = field(default_factory=dict)   # readers' inputs


# --------------------------------------------------------------------------- #
def per_layer(session: Session, outcome: Outcome, keep: Optional[str]
              ) -> Tuple[Dict[str, Dict], Dict, Dict]:
    """Reduce the trace and run each per-layer reader of the cell."""
    from chipbench import peaks as peaks_mod
    from chipbench import tracefile
    span_names = sorted(set(session.spans) | {"window"})
    xplane = tracefile.find_xplane(session.trace_dir)
    t = time.perf_counter()
    tr = tracefile.load(xplane, span_names)
    if keep:
        base = Path(keep) / f"{session.cell.name}.seed{session.seed}"
        base.parent.mkdir(parents=True, exist_ok=True)
        Path(f"{base}.planes.json").write_text(
            json.dumps(tracefile.describe(xplane), indent=1))
        tracefile.save(tr, f"{base}.trace.json.gz")
    red = tracefile.reduce(tr, wall_ns=session.wall_s * 1e9)
    print(f"[bench] trace of {os.path.getsize(xplane) / 2**20:.1f} MiB "
          f"read in {time.perf_counter() - t:.1f}s; window from "
          f"{red['window_from']}", flush=True)
    run = dict(outcome.layer, trace=tr, reduced=red, session=session,
               trace_window=red["window_ns"],
               config=session.cell.config, traffic=session.cell.traffic,
               peaks=peaks_mod.peaks(session.devices[0].device_kind),
               chips=session.cell.chips)
    out: Dict[str, Dict] = {}
    for m in session.cell.per_layer:
        value = reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    device = {"busy_s": red["busy_s"], "window_s": red["window_s"],
              "window_from": red["window_from"]}
    return out, breakdown, device


def run_cell(args: argparse.Namespace, t_process: float,
             require_tpu: bool = True) -> Tuple[int, Optional[Dict]]:
    cell = Cell.find(args.workload)
    driver = load_module("drivers", cell.traffic["kind"])
    for m in cell.per_layer:                   # fail early on a missing one
        reader(m["name"])
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"[bench] needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3, None
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache}", flush=True)

    s = Session(cell, args.seed, float(args.seconds), bool(args.trace),
                t_process, devices)
    try:
        out = driver.run(s)
        print(f"[bench] compilations inside the window: "
              f"{s.compiles_in_window}", flush=True)
        if s.trace:
            metrics, breakdown, busy = per_layer(s, out, args.keep_trace)
        else:
            metrics = {m["name"]: {"value": driver_value(out.end_to_end,
                                                         m["name"]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] != "setup_s"}
            metrics["setup_s"] = {"value": s.setup_s, "unit": "s"}
            breakdown, busy = None, {}
    finally:
        if s.trace_dir:
            shutil.rmtree(s.trace_dir, ignore_errors=True)

    correct = out.failed == 0 and all(
        math.isfinite(v) and v <= lim for v, lim in out.checks.values())
    for name, (v, lim) in out.checks.items():
        print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"check correct = {correct}", file=sys.stderr, flush=True)
    dev = devices[0]
    result = {
        "correct": correct, "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics,
        "device": dict({"platform": dev.platform, "kind": dev.device_kind,
                        "count": cell.chips,
                        "memory_peak_bytes": s.memory_peak_bytes}, **busy)}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return 0, result


def main(argv: List[str], t_process: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the reduced trace in (JSON)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        code, result = run_cell(args, t_process)
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    if result is not None:
        print(json.dumps(result), flush=True)
    return code
