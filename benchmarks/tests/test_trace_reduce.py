"""The reduction from device events to busy/idle, per-program time and
the breakdown: on hand-worked events, and on a trace recorded on the chip
(``data/recorded_events.json``: the device events of a traced run of this
benchmark, PR 25, cut to its first events)."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "recorded_events.json"


def test_busy_union_merges_overlaps_and_finds_gaps():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 35, 5],
          ["zero", 100, 0]]
    busy, gaps = tr.busy_union_ns(ev)
    assert busy == 25              # [0,15] + [30,40]
    assert gaps == [(15, 15)]


def test_reduce_hand_worked():
    plane = {"plane": "/device:TPU:0",
             "ops": [["fusion.1", 0, 4e8], ["sort.2", 4e8, 1e8],
                     ["fusion.1", 1e9, 5e8]],
             "modules": [["jit_run(123)", 0, 5e8], ["jit_run(123)", 1e9, 5e8],
                         ["jit_convert(9)", 2e9, 0]]}
    red = tr.reduce_events([plane], window_s=2.0)
    assert red["busy_s"] == pytest.approx(1.0)
    assert red["window_s"] == 2.0
    assert red["modules"]["jit_run"] == {"count": 2, "seconds":
                                         pytest.approx(1.0)}
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.9)]
    assert red["idle_gaps"][0] == ["unattributed:jit_run->jit_run",
                                   pytest.approx(0.5)]


def test_window_is_the_traces_own_span_unless_given():
    plane = {"plane": "/device:TPU:0",
             "ops": [["a", 2e9, 1e9], ["b", 5e9, 1e9]],
             "modules": [["jit_run(1)", 2e9, 4e9]]}
    red = tr.reduce_events([plane])
    assert red["window_s"] == pytest.approx(4.0)     # 2 s .. 6 s
    assert red["busy_s"] == pytest.approx(2.0)
    assert tr.reduce_events([plane], 8.0)["window_s"] == 8.0


def test_two_planes_average():
    a = {"plane": "/device:TPU:0", "ops": [["x", 0, 1e9]], "modules": []}
    b = {"plane": "/device:TPU:1", "ops": [["x", 0, 3e9]], "modules": []}
    assert tr.reduce_events([a, b], 4.0)["busy_s"] == pytest.approx(2.0)


def test_nothing_ran_reads_nothing():
    assert tr.reduce_events([], 1.0) is None
    assert tr.reduce_events(
        [{"plane": "/device:TPU:0", "ops": [], "modules": []}], 1.0) is None


def test_module_name_strips_the_fingerprint():
    assert tr.module_name("jit_run(8230456797123)") == "jit_run"
    assert tr.module_name("jit_run") == "jit_run"


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_trace():
    rec = json.loads(DATA.read_text())
    red = tr.reduce_events(rec["events"], rec["window_s"])
    want = rec["expected"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    for name, ent in want["modules"].items():
        assert red["modules"][name]["count"] == ent["count"]
        assert red["modules"][name]["seconds"] == pytest.approx(
            ent["seconds"], rel=1e-9)
    assert red["device_ops"][0][0] == want["top_op"]
