"""Percentiles, rates, due times and the traffic schedules."""

import json

import numpy as np
import pytest

from benchmarks import harness, stats
from benchmarks.tools import sets

streams = harness.load_module(
    harness.HERE / "traffic" / "streams.py", "streams")


def rec(rid, due, sent, done, ok=1, items=1, status=200):
    return [rid, due, sent, done, status, ok, items]


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_counts_from_due_not_sent():
    # sent 300 ms late: the wait is the request's, not the generator's
    r = [rec(0, 10.0, 10.3, 10.4)]
    assert stats.latencies_ms(r, 10.0, 20.0) == pytest.approx([400.0])
    assert stats.lateness_ms(r, 10.0, 20.0) == pytest.approx([300.0])


def test_failed_request_counts_as_the_window():
    r = [rec(0, 1.0, 1.0, 1.1), rec(1, 2.0, 2.0, 2.1, ok=0, status=429)]
    lat = stats.latencies_ms(r, 0.0, 10.0)
    assert lat == pytest.approx([100.0, 10_000.0])
    assert stats.attempted_failed(r, 0.0, 10.0) == (2, 1)


def test_requests_due_outside_the_window_are_not_in_the_sample():
    r = [rec(0, -0.5, -0.5, 0.2), rec(1, 10.0, 10.0, 10.1)]
    assert stats.latencies_ms(r, 0.0, 10.0) == []


def test_injected_stall_moves_p95_and_qps():
    """100 requests, one every 100 ms, each served in 10 ms — then the
    same with the server stalled for 2 s in the middle (an open loop:
    requests keep falling due and wait)."""
    def window(stall):
        out, free_at = [], 0.0
        for i in range(100):
            due = i * 0.1
            start = max(due, free_at)
            if stall and 4.0 <= due < 4.05:
                start = max(start, 6.0)
            done = start + 0.01
            free_at = done
            out.append(rec(i, due, due, done))
        return out
    calm, stalled = window(False), window(True)
    p95c = stats.percentile(stats.latencies_ms(calm, 0, 10), 95)
    p95s = stats.percentile(stats.latencies_ms(stalled, 0, 10), 95)
    assert p95c == pytest.approx(10.0)
    assert p95s > 1000.0
    assert stats.percentile(stats.latencies_ms(stalled, 0, 10), 50) \
        == pytest.approx(10.0)
    # a closed loop loses the stalled time as throughput
    closed = [rec(i, i * 0.01, i * 0.01, i * 0.01 + 0.01) for i in range(800)]
    slow = [r for r in closed if not 4.0 <= r[3] < 6.0]
    assert stats.queries_per_second(closed, 0, 10) == pytest.approx(80.0,
                                                                    rel=0.01)
    assert stats.queries_per_second(slow, 0, 10) < 65.0


def test_qps_counts_the_work_inside_the_window():
    r = [rec(0, 0.0, 0.0, 1.0, ok=64, items=64),
         rec(1, 9.0, 9.0, 11.0, ok=64, items=64),      # half inside
         rec(2, 5.0, 5.0, 6.0, ok=60, items=64),       # 4 items failed
         rec(3, 12.0, 12.0, 13.0, ok=64, items=64)]    # after the end
    assert stats.queries_per_second(r, 0.0, 10.0) == pytest.approx(
        (64 + 32 + 60) / 10.0)
    assert stats.attempted_failed(r, 0.0, 10.0) == (192, 4)
    # a slightly slower server reads slightly lower, not a request lower
    fast = [rec(i, 0, i * 2.30, (i + 1) * 2.30, ok=64, items=64)
            for i in range(30)]
    slow = [rec(i, 0, i * 2.31, (i + 1) * 2.31, ok=64, items=64)
            for i in range(30)]
    a = stats.queries_per_second(fast, 0.0, 51.0)
    b = stats.queries_per_second(slow, 0.0, 51.0)
    assert a == pytest.approx(64 / 2.30) and b == pytest.approx(64 / 2.31)


def test_poisson_arrivals_same_gaps_on_every_seed():
    arr = {"process": "poisson", "rate": 50.0}
    rng = np.random.default_rng
    a, ra = streams.arrival_times(arr, 20.0, rng(1), rng(10))
    b, _ = streams.arrival_times(arr, 20.0, rng(2), rng(10))
    assert len(a) == len(b) == 1000
    ga, gb = np.diff(a, prepend=0), np.diff(b, prepend=0)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert a[-1] == pytest.approx(20.0, rel=0.01)
    # exponential gaps: the standard deviation equals the mean
    assert np.std(ga) == pytest.approx(np.mean(ga), rel=0.05)
    # one order, another seed: the same bursts at another time
    c, rc = streams.arrival_times(arr, 20.0, rng(1), rng(11))
    assert ra != rc
    assert np.allclose(np.roll(np.diff(c, prepend=0), rc),
                       np.roll(ga, ra))


def test_bursts_keep_the_mean_rate_and_crowd_the_on_phase():
    arr = {"process": "poisson", "rate": 40.0,
           "burst": {"period_s": 10.0, "on_s": 2.0, "factor": 4.0}}
    t, _ = streams.arrival_times(arr, 50.0, np.random.default_rng(1),
                                 np.random.default_rng(2))
    t = t[t < 50.0]
    assert len(t) == pytest.approx(2000, rel=0.05)
    on = np.sum((t % 10.0) < 2.0)
    # 2 s at 4r against 8 s at r: half of the arrivals in a fifth of the time
    assert on / len(t) == pytest.approx(0.5, abs=0.05)
    assert np.all(np.diff(t) >= 0)


def test_zipf_picks_are_skewed_and_a_walk_never_repeats():
    q = {"pick": "zipf", "popularity_s": 1.0}
    picks = streams.pick_entries(q, 50_000, 1, 10_000,
                                 np.random.default_rng(3))
    share = np.mean(picks == 0)
    want = 1.0 / np.sum(1.0 / np.arange(1, 10_001))
    assert share == pytest.approx(want, rel=0.1)
    assert picks.max() < 10_000 and picks.shape == (50_000, 1)
    walk = streams.pick_entries({"pick": "walk"}, 5, 64, 200, None)
    assert walk.shape == (5, 64)
    assert all(len(set(row.tolist())) == 64 for row in walk)
    assert walk[3, 10] == (3 * 64 + 10) % 200


class Kind:
    """A data kind of integers: a query is a number."""
    @staticmethod
    def query_pool(corpus, params, rng, fixed):
        return list(range(int(params["pool"])))

    @staticmethod
    def request(params, queries, index):
        return {"path": f"/{index}/x", "body": repr(queries),
                "items": len(queries)}

    @staticmethod
    def warm_requests(params, pool, index, max_batch):
        return [Kind.request(params, pool[:1], index)]


def test_streams_build_closed_open_and_side_by_side():
    traffic = {"kind": "streams", "streams": [
        {"arrivals": {"process": "closed", "processes": 2, "connections": 2,
                      "max_requests_per_s": 2.0, "sure_rounds": 1},
         "request": {"items": 8}, "queries": {"pool": 100}},
        {"arrivals": {"process": "poisson", "rate": 5.0, "connections": 3},
         "request": {"items": 1},
         "queries": {"pool": 10, "pick": "zipf", "popularity_s": 1.0},
         "measured": False, "checked": False}]}
    plan = streams.build(traffic, Kind, None, 2**31 + 5, 10.0, "ix", 32)
    closed = [j for j in plan["jobs"] if j["mode"] == "closed"]
    opened = [j for j in plan["jobs"] if j["mode"] == "open"]
    assert len(closed) == 2 and all(len(j["streams"]) == 2 for j in closed)
    assert len(opened) == 1 and opened[0]["connections"] == 3
    assert len(opened[0]["requests"]) == 50
    due = [r["due"] for r in opened[0]["requests"]]
    assert due == sorted(due)
    # 10 s x 2/s over 4 connections, and two rounds to spare
    assert all(len(s) == 7 for j in closed for s in j["streams"])
    assert len(plan["sure"]) == 4, "the first round of every connection"
    assert set(plan["sure"]) <= set(plan["checks"])
    assert len(plan["checks"]) == 28 and len(plan["unmeasured"]) == 50
    assert len(plan["warm"]) == 2
    ids = [r["id"] for j in closed for s in j["streams"] for r in s] \
        + [r["id"] for r in opened[0]["requests"]]
    assert len(set(ids)) == len(ids) == 78
    # the same work on every seed; only the open loop's place differs
    other = streams.build(traffic, Kind, None, 77, 10.0, "ix", 32)
    assert [j["streams"] for j in other["jobs"] if j["mode"] == "closed"] \
        == [j["streams"] for j in closed]


def sched_doc(launched, held, hold_ms, delivered, pace):
    return {"batches_launched": launched, "batches_held": held,
            "hold_ms": hold_ms, "delivered": delivered, "pad_rows": 0,
            "pace": pace}


def test_the_windows_log_says_what_the_run_was(capsys):
    """A hand-made ``before`` / ``after`` pair and six seconds of records
    → the printed depth, held share, rows a batch and per-second rates:
    100 batches of which 90 waited 4.5 ms each, 380 rows; the depth went
    from three to four; second 3 stood still."""
    pace0 = {"knn": {"launch_ms": 15.0, "launch_dev_ms": 3.0,
                     "device_ms": 13.4, "staged_depth": 3}}
    pace1 = {"knn": {"launch_ms": 22.0, "launch_dev_ms": 6.0,
                     "device_ms": 13.5, "staged_depth": 4}}
    before = sched_doc(1000, 900, 4000.0, 3800, pace0)
    after = sched_doc(1100, 990, 4405.0, 4180, pace1)
    per_second = [4, 5, 4, 0, 5, 4]
    records, rid = [], 0
    for sec, n in enumerate(per_second):
        for j in range(n):
            done = 100.0 + sec + (j + 0.5) / n
            records.append(rec(rid, done - 0.05, done - 0.05, done))
            rid += 1
    records.append(rec(rid, 105.9, 105.9, 106.2))     # a third inside
    records.append(rec(rid + 1, 102.1, 102.1, 102.2, ok=0, status=429))
    ctx = {"before": {"scheduler": before}, "after": {"scheduler": after},
           "records": records, "t_start": 100.0, "t_end": 106.0}
    got = harness.window_log(ctx)
    sched = got["scheduler"]
    assert sched["batches_launched"] == 100 and sched["batches_held"] == 90
    assert sched["held_share"] == pytest.approx(0.9)
    assert sched["hold_ms_per_held"] == pytest.approx(4.5)
    assert sched["rows_per_batch"] == pytest.approx(3.8)
    assert sched["pace"]["knn"]["start"]["staged_depth"] == 3
    assert sched["pace"]["knn"]["end"]["staged_depth"] == 4
    assert got["answers_by_second"] == pytest.approx(
        [4, 5, 4, 0, 5, 4 + 1 / 3])
    assert got["seconds"] == pytest.approx(
        {"least": 0, "median": 4 + 1 / 6, "greatest": 5, "stalled": [3]})
    err = capsys.readouterr().err
    assert capsys.readouterr().out == ""
    for piece in ("batches_launched 100", "batches_held 90", "hold_ms 405",
                  "held_share 0.9", "rows_per_batch 3.8",
                  "at the window's start: launch_ms 15.0",
                  "device_ms 13.5, staged_depth 4",
                  "window: 4 5 4 0 5 4", "least 0.0, median 4.2, greatest 5.0",
                  "stalled seconds (under half the median): [3]"):
        assert piece in err, piece
    assert all(line.startswith("[bench] ") for line in err.splitlines())
    # the set runner reads its row back from these very lines
    line = json.dumps({"correct": True, "failed": 0, "metrics": {
        "qps": {"value": 3.7, "unit": "queries/s"}}})
    row = sets.read_run(line, err + "[bench] server process in the window: "
                        "9.10 s of CPU, collections by generation [70, 6, 2]")
    assert row == {"correct": True, "failed": 0, "qps": 3.7,
                   "held_share": 0.9, "hold_ms_per_held": 4.5,
                   "rows_per_batch": 3.8, "staged_depth": {"knn": [3, 4]},
                   "stalled": [3], "full_collections": 2}


def test_a_run_without_a_scheduler_at_work_logs_that_and_no_more(capsys):
    # an _msearch bypasses the scheduler: nothing launched, no lane paced
    idle = sched_doc(0, 0, 0.0, 0, {})
    got = harness.window_log({
        "before": {"scheduler": idle}, "after": {"scheduler": idle},
        "records": [rec(0, 0.1, 0.1, 1.4, ok=64, items=64)],
        "t_start": 0.0, "t_end": 2.0})
    assert "rows_per_batch" not in got["scheduler"]
    # 64 answers over 1.3 s in flight: 0.9 s of it in the first second
    assert got["answers_by_second"] == pytest.approx(
        [64 * 0.9 / 1.3, 64 * 0.4 / 1.3])
    err = capsys.readouterr().err
    assert "batches_launched 0" in err and "scheduler pace" not in err
    # a program from before the hold has no such counters: left out
    old = {"batches_launched": 5, "delivered": 15}
    assert stats.scheduler_window({"batches_launched": 1, "delivered": 3},
                                  old) == {
        "batches_launched": 4, "delivered": 12, "rows_per_batch": 3.0,
        "pace": {}}


def test_the_drivers_spread_leaves_out_the_farthest_run():
    # PR 30's words: "A spread leaves out the run farthest from its
    # median where that narrows it"
    assert stats.driver_spread([280.0, 281.0, 279.0, 282.0, 280.5, 260.0]) \
        == pytest.approx(3.0)
    assert stats.driver_spread([1.0, 2.0]) == pytest.approx(1.0)
    assert stats.driver_spread([5.0, 5.0, 5.0]) == 0.0
    # PR 31's set B: both forms, as shares of the median
    got = sets.summarise([277.56, 281.84, 282.69, 284.65, 273.97, 262.78])
    assert got["median"] == pytest.approx(279.70)
    assert got["iqr_share"] == pytest.approx(0.0429, abs=1e-4)
    assert got["driver_spread"] == pytest.approx(10.68)
    assert got["driver_share"] == pytest.approx(0.0382, abs=1e-4)
