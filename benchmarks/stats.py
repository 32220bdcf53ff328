"""The arithmetic of the end-to-end metrics. Pure Python + numpy; shared
by the readers, the harness and its self-checks."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0–100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if len(v) == 0:
        raise ValueError("percentile of an empty sample")
    rank = int(np.ceil(q / 100.0 * len(v)))
    return float(v[max(rank, 1) - 1])


def latencies_ms(records: list, t_start: float, t_end: float) -> list:
    """Client-side latency of every request that was due in the window,
    from the instant it was DUE (so a stall counts against the requests
    it delayed) to its reply. A failed, refused or timed-out request
    counts as the window's length."""
    window_ms = (t_end - t_start) * 1e3
    out = []
    for _id, due, _sent, done, _status, ok, items in records:
        if not t_start <= due < t_end:
            continue
        out.append((done - due) * 1e3 if ok == items else window_ms)
    return out


def queries_per_second(records: list, t_start: float, t_end: float) -> float:
    """Searches answered without failure over the whole window's seconds.
    A request counts by the share of its time in flight (sent → reply
    complete) that lay inside the window: whole when it was answered
    inside, in part when the window's end (or start) cut it. Counting
    only whole replies would move the rate of long requests in steps of
    one request — 4.5% at 22 requests a window — whatever the change."""
    good = 0.0
    for _id, _due, sent, done, _status, ok, _items in records:
        inside = min(done, t_end) - max(sent, t_start)
        if ok and inside > 0:
            good += ok * min(1.0, inside / max(done - sent, 1e-9))
    return good / (t_end - t_start)


def attempted_failed(records: list, t_start: float, t_end: float) -> tuple:
    """(searches attempted, searches failed) among requests due in the
    window."""
    att = sum(r[6] for r in records if t_start <= r[1] < t_end)
    bad = sum(r[6] - r[5] for r in records if t_start <= r[1] < t_end)
    return int(att), int(bad)


def lateness_ms(records: list, t_start: float, t_end: float) -> list:
    """How late the generator sent each request: sent − due."""
    return [(sent - due) * 1e3 for _id, due, sent, _d, _s, _ok, _it
            in records if t_start <= due < t_end]


def answers_by_second(records: list, t_start: float, t_end: float) -> list:
    """The served rate in each whole second of the window (a last part of
    a second is left out): searches answered without failure, each
    request's spread evenly over its time in flight, as
    :func:`queries_per_second` counts them — whole replies of 64 answers
    every 2.7 s would read as 0, 0, 128. A run at another LEVEL reads flat
    at another height; a stall reads as a few empty seconds in a level
    run."""
    out = [0.0] * int(t_end - t_start)
    for _id, _due, sent, done, _status, ok, _items in records:
        if not ok or done <= t_start:
            continue
        rate = ok / max(done - sent, 1e-9)
        for sec in range(max(int(sent - t_start), 0),
                         min(int(done - t_start) + 1, len(out))):
            inside = min(done, t_start + sec + 1) - max(sent, t_start + sec)
            out[sec] += rate * max(inside, 0.0)
    return out


def second_summary(by_second: list) -> dict:
    """The least, median and greatest second, and the STALLED seconds:
    those that answered under half of the median second."""
    if not by_second:
        return {}
    median = statistics.median(by_second)
    return {"least": min(by_second), "median": median,
            "greatest": max(by_second),
            "stalled": [s for s, n in enumerate(by_second)
                        if n < median / 2.0]}


SCHEDULER_COUNTS = ("batches_launched", "batches_held", "hold_ms",
                    "delivered", "pad_rows")


def scheduler_window(before: dict, after: dict) -> dict:
    """What the scheduler did between two ``stats()`` documents: the
    counters' differences, the share of batches whose formation waited
    under the hold, the mean hold and the real rows a batch — and each
    lane's ``pace`` (mean launch, its mean deviation, mean device time, the
    staged depth they give) at both ends. A program whose ``stats()`` lacks
    a counter leaves it out."""
    out = {k: after[k] - before.get(k, 0) for k in SCHEDULER_COUNTS
           if k in after}
    launched, held = out.get("batches_launched", 0), out.get(
        "batches_held", 0)
    if launched > 0:
        out["rows_per_batch"] = out.get("delivered", 0) / launched
        if "batches_held" in out:
            out["held_share"] = held / launched
    if held > 0:
        out["hold_ms_per_held"] = out["hold_ms"] / held
    lanes = {**before.get("pace", {}), **after.get("pace", {})}
    out["pace"] = {lane: {"start": before.get("pace", {}).get(lane),
                          "end": after.get("pace", {}).get(lane)}
                   for lane in lanes}
    return out


def driver_spread(values: list) -> float:
    """The spread as the driver's verdicts word it: the range of the runs
    once the run farthest from the median is left out (same unit as the
    values)."""
    v = sorted(values)
    if len(v) < 3:
        return v[-1] - v[0] if v else 0.0
    median = statistics.median(v)
    v.remove(max(v, key=lambda x: abs(x - median)))
    return v[-1] - v[0]
