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
