"""The readers of the interpreter's full collections and of the
scheduler's result wait (``collect_common``; ``gc_pause_pct``,
``starved_in_gc_pct``, ``sched_wait_ms_per_query``) on a hand-worked span
list — one scheduled request, two collections in the slice, one of them
across the edge of a starved stretch, two more in the window outside the
slice — and against the program's own ring; a program without the spans
reads nothing."""

import gc
import time

import pytest

from benchmarks import collect_common as cc
from benchmarks import harness
from benchmarks import span_common as sc

TICK = 1000                       # ns
BASE = 10 ** 6                    # ticks: the slice starts 1 s in
T0, T1 = BASE * TICK, (BASE + 10_000) * TICK
W0, W1 = (BASE - 20_000) * TICK, (BASE + 20_000) * TICK

# (seq, parent, request, name, thread, start, end, cpu) in ticks from BASE
RECORDS = [
    # request 1 on thread 1: a single search through the scheduler
    (1, 0, 1, "rest.handle", 1, 0, 9_000, 3_000),
    (2, 1, 1, "action.search", 1, 100, 8_800, -1),
    (3, 2, 1, "action.shard", 1, 200, 8_500, -1),
    (4, 3, 1, "scheduler.queue", 1, 300, 1_300, -1),
    (5, 3, 1, "scheduler.result", 1, 1_300, 8_000, -1),
    (10, 3, 1, "fetch.hits", 1, 8_050, 8_450, -1),
    # the batch on the scheduler's worker (thread 2): launch, drain
    (6, 0, 2, "scheduler.launch", 2, 1_400, 2_400, 900),
    (7, 6, 2, "jit.enqueue", 2, 2_000, 2_300, -1),
    (8, 0, 2, "scheduler.drain", 2, 2_400, 7_800, 100),
    # collection A, inside the first starved stretch, stops the launch
    (9, 6, 2, "interp.collect", 2, 1_500, 1_900, -1),
    # request 3's body read on thread 3; collection B across the start
    # of the second starved stretch (7,800): 200 of its 400 starved
    (11, 0, 3, "rest.read", 3, 7_500, 8_100, 500),
    (12, 11, 3, "interp.collect", 3, 7_600, 8_000, -1),
]
# the launch opened at jit.enqueue (2,000) and closed at the drain's end
STARVED = [(0, 2_000), (7_800, 10_000)]
# outside the slice, inside the window: a collection with no span open,
# one in the middle, one that the window's end cuts (500 → 200)
WINDOW_ONLY = [
    (20, 0, 0, "interp.collect", 4, -10_000, -9_000, -1),
    (21, 0, 0, "interp.collect", 4, 15_000, 15_500, -1),
    (22, 0, 0, "interp.collect", 4, 19_800, 20_300, -1),
]


def ns(records):
    return [(s, p, rid, name, tid, (BASE + a) * TICK, (BASE + b) * TICK,
             cpu * TICK if cpu >= 0 else -1)
            for s, p, rid, name, tid, a, b, cpu in records]


def gaps():
    return [((BASE + a) * TICK, (BASE + b) * TICK) for a, b in STARVED]


def make_ctx(records=RECORDS):
    recs = ns(records)
    t = 1e-9 * TICK
    return {"traced": {"t0": T0 / 1e9, "t1": T1 / 1e9},
            # request 1, wholly inside the slice: one query
            "records": [(1, (BASE - 1) * t, BASE * t, (BASE + 9_000) * t,
                         200, 1, 1)],
            "t_start": W0 / 1e9, "t_end": W1 / 1e9,
            "before": {"gc": [100, 10, 1]}, "after": {"gc": [500, 40, 6]},
            "_span_analysis": sc.analyse(sc.cut(recs, T0, T1), gaps(),
                                         T0, T1),
            "_collect_slice": (cc.stops(recs, T0, T1), gaps(), T0, T1),
            "_collect_window": recs + ns(WINDOW_ONLY)}


def read(metric, ctx):
    mod = harness.load_module(harness.reader_file("layer_metrics", metric),
                              f"check_{metric}")
    return mod.read(ctx)


def test_interval_arithmetic():
    assert cc.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        [0, 3], [5, 8]]
    assert cc.covered_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert cc.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert cc.overlap_ns([(0, 1)], []) == 0


def test_gc_pause_is_the_windows_union_of_collections(capsys):
    # A 400 + B 400 + 1,000 + 500 + the cut 200 = 2,500 of 40,000 ticks
    assert read("gc_pause_pct.tput", make_ctx()) == pytest.approx(6.25)
    err = capsys.readouterr().err
    lines = [ln for ln in err.splitlines() if "full collection at" in ln]
    assert len(lines) == 5
    assert "+20.500 s" not in err and "0.40 ms, in scheduler.launch" in err
    assert "0.40 ms, in rest.read" in err
    assert "1.00 ms, in no open span" in err
    assert "430 young (10750.0/s of the window), 5 full; 5 full in the " \
        "window" in err


def test_starved_in_gc_counts_the_stop_whole():
    # A lies wholly in a starved stretch (400), B half (200): 600 of
    # 10,000 ticks, though a launch span and a body read were open
    assert read("starved_in_gc_pct.tput", make_ctx()) == pytest.approx(6.0)
    # span_common shares the same stretches among the open leaves
    # instead: request 1's scheduler.result was open beside both, so the
    # collections get half
    an = make_ctx()["_span_analysis"]
    assert an["starved"]["interp.collect"] == pytest.approx(300 * TICK)


def test_sched_wait_and_the_action_layer_that_no_longer_holds_it():
    ctx = make_ctx()
    assert read("sched_wait_ms_per_query.tput", ctx) == pytest.approx(
        (1_000 + 6_700) * TICK / 1e6)
    # action.search 400 + action.shard 8,300 − 1,000 − 6,700 − 400 = 200
    assert read("action_self_ms_per_query.tput", ctx) == pytest.approx(
        600 * TICK / 1e6)
    # without the result span its wait falls back into action.shard: the
    # wait moved, it was not lost
    before = [r for r in RECORDS if r[3] != "scheduler.result"]
    assert read("action_self_ms_per_query.tput", make_ctx(before)) \
        == pytest.approx((600 + 6_700) * TICK / 1e6)
    # a collection leaves its stretch's self time too
    selfs = sc.self_times(ns(RECORDS))
    assert selfs[6] == (1_000 - 300 - 400) * TICK
    assert selfs[11] == (600 - 400) * TICK


def ring_ctx(t0, t1):
    return {"traced": {"t0": t0 / 1e9, "t1": t1 / 1e9}, "records": [],
            "t_start": t0 / 1e9, "t_end": t1 / 1e9}


def test_the_programs_ring_and_a_program_without_the_spans(monkeypatch):
    from elasticsearch_tpu.observability import tracing
    if "interp.collect" not in getattr(tracing, "SPAN_LAYERS", {}):
        # these files laid over a program from before the spans
        now = time.monotonic_ns()
        for metric in ("gc_pause_pct.tput", "starved_in_gc_pct.tput",
                       "sched_wait_ms_per_query.tput"):
            assert read(metric, ring_ctx(now - 10 ** 9, now)) is None
        return
    tracing.reset()
    was = gc.isenabled()
    gc.disable()
    try:
        t0 = time.monotonic_ns()
        with tracing.span("rest.handle"):
            time.sleep(0.002)
            gc.collect()                # nothing in flight: starved
            with tracing.launch_scope() as held:
                with tracing.device_span("dispatch"):
                    pass
            with tracing.span("jit.drain"):
                time.sleep(0.002)
            tracing.close_launches(held)
        t1 = time.monotonic_ns()
        stop = next(r for r in tracing.ring_records(t0, t1)
                    if r[3] == "interp.collect")
        share = 100.0 * (stop[6] - stop[5]) / (t1 - t0)
        assert read("gc_pause_pct.tput", ring_ctx(t0, t1)) \
            == pytest.approx(share)
        assert read("starved_in_gc_pct.tput", ring_ctx(t0, t1)) \
            == pytest.approx(share)
        # no query answered: nothing a query
        assert read("sched_wait_ms_per_query.tput", ring_ctx(t0, t1)) is None
        # a program whose table has neither span: nothing, and no error
        monkeypatch.delitem(tracing.SPAN_LAYERS, "interp.collect")
        monkeypatch.delitem(tracing.SPAN_LAYERS, "scheduler.result")
        for metric in ("gc_pause_pct.tput", "starved_in_gc_pct.tput",
                       "sched_wait_ms_per_query.tput"):
            assert read(metric, ring_ctx(t0, t1)) is None, metric
        ctx = make_ctx()
        del ctx["_collect_window"], ctx["_collect_slice"]
        assert read("gc_pause_pct.tput", ctx) is None
        assert read("sched_wait_ms_per_query.tput", ctx) is None
    finally:
        if was:
            gc.enable()
        tracing.reset()
