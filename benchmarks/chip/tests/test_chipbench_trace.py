"""The trace reduction on a small trace: busy time, idle share, kernel time
and what the host was doing in each idle gap."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import tracefile  # noqa: E402
from chipbench.tracefile import Trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def small() -> Trace:
    # one device; the window is [0, 100] ns on the main thread
    return Trace(
        ops={"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.2", 25, 40),
                               ("custom-call.wkv", 60, 70),
                               ("custom-call.wkv", 80, 90),
                               ("outside", 120, 130)]},
        modules={"/device:TPU:0": [("jit_decode_step", 10, 40),
                                   ("jit_decode_step", 60, 90)]},
        spans={"/host:CPU/main": [("window", 0, 100), ("step", 0, 45),
                                  ("commit", 45, 58), ("step", 58, 92),
                                  ("loss_sync", 40, 45)],
               "/host:CPU/eval": [("snapshot", 0, 100)]})


def test_union_and_gaps():
    ops = small().ops["/device:TPU:0"]
    busy = tracefile.union(ops, 0, 100)
    assert busy == [(10, 40), (60, 70), (80, 90)]
    assert tracefile.gaps(busy, 0, 100) == [(0, 10), (40, 60), (70, 80),
                                            (90, 100)]


def test_innermost_segments_name_the_deepest_open_span():
    segs = tracefile.innermost(small().spans["/host:CPU/main"])
    assert segs == [("step", 0, 40), ("loss_sync", 40, 45),
                    ("commit", 45, 58), ("step", 58, 92)]


def test_reduce_idle_share_and_gap_attribution():
    red = tracefile.reduce(small())
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(50e-9)
    assert red["idle_share"] == pytest.approx(0.5)
    gaps = dict((k, v * 1e9) for k, v in red["idle_gaps"])
    # gap (0,10) and (70,80) under step; (40,60): 5 loss_sync, 13 commit,
    # 2 step; (90,100): 2 step, 8 with no span open
    assert gaps == pytest.approx({"step": 24, "loss_sync": 5, "commit": 13,
                                  "host:none": 8})
    ops = dict((k, v * 1e9) for k, v in red["device_ops"])
    assert ops == pytest.approx({"fusion.1": 20, "fusion.2": 15,
                                 "custom-call.wkv": 20})


def test_kernel_and_module_time_inside_the_window():
    tr = small()
    assert tracefile.matching(tr.ops, "wkv", 0, 100) == [10, 10]
    assert tracefile.matching(tr.modules, "decode_step", 0, 100) == [30, 30]
    assert tracefile.matching(tr.ops, "outside", 0, 100) == []


def test_reduce_averages_busy_time_over_devices():
    tr = small()
    tr.ops["/device:TPU:1"] = [("fusion.9", 0, 100)]
    red = tracefile.reduce(tr)
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["idle_share"] == pytest.approx(0.25)


def test_a_lost_window_span_falls_back_to_the_device_programs():
    tr = small()
    tr.spans["/host:CPU/main"] = [("step", 0, 45), ("commit", 45, 58),
                                  ("step", 58, 92)]
    tr.spans["/host:CPU/eval"] = [("snapshot", 10 * i, 10 * i + 5)
                                  for i in range(10)]
    # the host clock's length, ending where the last device program ended
    assert tracefile.window(tr, wall_ns=85) == (5, 90, "/host:CPU/main",
                                                "host_clock")
    red = tracefile.reduce(tr, wall_ns=85)
    assert red["window_s"] == pytest.approx(85e-9)
    assert red["busy_s"] == pytest.approx(50e-9)
    assert red["window_from"] == "host_clock"
    with pytest.raises(ValueError):
        tracefile.reduce(tr)
    assert tracefile.reduce(small(), wall_ns=85)["window_from"] == "markers"


def test_a_trace_without_a_window_or_device_is_refused():
    tr = small()
    tr.spans["/host:CPU/main"] = [("step", 0, 45)]
    tr.modules = {}
    with pytest.raises(ValueError):
        tracefile.reduce(tr)
    tr = small()
    tr.ops = {}
    with pytest.raises(ValueError):
        tracefile.reduce(tr)


def test_json_round_trip(tmp_path):
    p = str(tmp_path / "t.json")
    tracefile.save(small(), p)
    back = Trace.from_json(json.load(open(p)))
    assert tracefile.reduce(back) == tracefile.reduce(small())


def collectives() -> Trace:
    """Two devices; the window is [0, 100] ns. Device 0: a collective under
    a fusion (hidden), an asynchronous all-gather whose halves show
    (2 + 10 ns), a synchronous all-reduce inside a while loop (10 ns).
    Device 1: a reduce-scatter (10 ns), a permute's wait cut by the window
    (5 ns), one outside the window."""
    op = lambda name, kind, s, e: (f"%{name} = f32[8] {kind}(%p)", s, e)
    return Trace(
        ops={"/device:TPU:0": [
            op("fusion.1", "fusion", 10, 40),
            op("all-reduce.7", "all-reduce", 20, 30),
            op("all-gather-start.1", "all-gather-start", 40, 42),
            op("fusion.2", "fusion", 42, 60),
            op("all-gather-done.1", "all-gather-done", 60, 70),
            op("while.3", "while", 70, 100),
            op("fusion.4", "fusion", 70, 80),
            op("all-reduce.5", "all-reduce", 80, 90),
            op("fusion.6", "fusion", 90, 100)],
            "/device:TPU:1": [
            op("fusion.1", "fusion", 0, 50),
            op("reduce-scatter.2", "reduce-scatter", 50, 60),
            op("collective-permute-done.3", "collective-permute-done", 95,
               110),
            op("all-to-all.4", "all-to-all", 120, 130)]},
        modules={"/device:TPU:0": [("jit_train_step", 10, 100)],
                 "/device:TPU:1": [("jit_train_step", 0, 100)]},
        spans={"/host:CPU/main": [("window", 0, 100), ("step", 0, 95)]})


def test_collective_exposed_share_counts_what_no_other_op_hides(capsys):
    from chipbench.harness import load_module
    reader = load_module("metrics", "collective_exposed_share")
    tr = collectives()
    red = tracefile.reduce(tr)
    # (22 + 15) / 2 ns of a 100 ns window
    assert reader.read({"trace": tr, "trace_window": red["window_ns"]}) == \
        pytest.approx(18.5)
    assert "all-gather-done" in capsys.readouterr().out
    # the profiler dropped the window annotation: the host clock's 100 ns
    # ending where the last device program ended read the same
    tr.spans["/host:CPU/main"] = [("step", 0, 95)]
    red = tracefile.reduce(tr, wall_ns=100)
    assert red["window_from"] == "host_clock"
    assert reader.read({"trace": tr, "trace_window": red["window_ns"]}) == \
        pytest.approx(18.5)
    # a trace with no collective gives nothing
    tr.ops = {d: [x for x in ops if "fusion" in x[0] or "while" in x[0]]
              for d, ops in tr.ops.items()}
    assert reader.read({"trace": tr, "trace_window": (0, 100)}) is None
