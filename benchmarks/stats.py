"""The arithmetic of the end-to-end metrics. Pure Python + numpy; shared
by the readers, the harness and its self-checks."""

from __future__ import annotations

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
