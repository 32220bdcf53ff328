"""Rolling-window metrics — ring-buffered snapshots of the cumulative
counters, read back as 1m/5m/15m rates and windowed percentiles.

Every stats surface the repo had before this module is
cumulative-since-boot: the lane registry counters (PR 12), the latency
histograms (PR 8), the SLO good/bad tallies. Cumulative numbers answer
"how much ever", never "what is the QPS / p99 / fallback rate RIGHT
NOW". This module closes the gap without touching the hot path: counter
bumps stay plain integer increments; a SNAPSHOT of the cumulative values
is appended to a per-node ring buffer only when something reads stats
(``_nodes/stats``, ``/_prometheus/metrics``, an explicit test tick), and
windowed figures are deltas between ring entries —

    rate(w)        = (counter_now − counter_{t−w}) / (t_now − t_{t−w})
    p99(w)         = percentile of (buckets_now − buckets_{t−w})

so scraping and windowing allocate NOTHING on the request hot path when
idle (tier-1 asserted: the ring does not grow without a tick). Scrapes
are throttled to one snapshot per second; with no recent baseline the
window falls back to the oldest snapshot and reports its actual
``span_s`` honestly.

Gauge-valued series (ledger bytes, breaker occupancy — prefix
``gauge.``) ride the same ring for the Chrome-trace counter track but
are excluded from ``per_second`` rates.
"""

from __future__ import annotations

import threading
import time

from elasticsearch_tpu.observability.histograms import \
    percentile_from_counts

#: window label → seconds (the _nodes/stats.rates sections)
WINDOWS = (("1m", 60.0), ("5m", 300.0), ("15m", 900.0))

#: ring capacity per node: at the 1 s scrape throttle this covers the
#: 15 m window with headroom; older snapshots beyond the largest window
#: are pruned on append
_CAP = 1200

#: snapshots closer together than this are coalesced (scrape storms
#: must not grow the ring)
MIN_INTERVAL_S = 1.0

#: series whose values are levels, not monotone counters
GAUGE_PREFIX = "gauge."


class _Snapshot:
    __slots__ = ("t", "epoch_us", "counters", "buckets")

    def __init__(self, t, epoch_us, counters, buckets):
        self.t = t
        self.epoch_us = epoch_us
        self.counters = counters        # {series: number} (cumulative)
        self.buckets = buckets          # {lane: tuple(bucket counts)}


_rings: dict[str, list] = {}
_lock = threading.Lock()


def record(node_id: str, counters: dict, buckets: dict | None = None,
           now: float | None = None, force: bool = False) -> bool:
    """Append one snapshot of cumulative ``counters`` (+ histogram
    ``buckets``) to ``node_id``'s ring → True when recorded (False when
    coalesced into the previous scrape by the throttle). ``now`` is
    injectable so the offline-oracle tests control the clock."""
    t = time.monotonic() if now is None else now
    snap = _Snapshot(t, time.time_ns() // 1000, dict(counters),
                     {k: tuple(v) for k, v in (buckets or {}).items()})
    horizon = max(w for _, w in WINDOWS) * 1.1
    with _lock:
        ring = _rings.setdefault(node_id, [])
        if ring and not force and t - ring[-1].t < MIN_INTERVAL_S:
            return False
        ring.append(snap)
        while len(ring) > _CAP or (len(ring) > 2 and
                                   t - ring[1].t > horizon):
            ring.pop(0)
    return True


def _baseline(ring: list, t: float, window_s: float):
    """The newest snapshot at least ``window_s`` old (the honest window
    edge), else the oldest one we still hold."""
    base = ring[0]
    for snap in ring:
        if t - snap.t >= window_s:
            base = snap
        else:
            break
    return base


def rates(node_id: str, now: float | None = None) -> dict:
    """Windowed view per :data:`WINDOWS`: per-second rates for every
    counter series and bucket-delta percentiles per histogram lane.
    Counter resets (test clear_cache) clamp to zero, never negative."""
    t = time.monotonic() if now is None else now
    with _lock:
        ring = list(_rings.get(node_id, ()))
    out = {}
    for label, window_s in WINDOWS:
        key = f"window_{label}"
        if len(ring) < 2:
            out[key] = {"span_s": 0.0, "per_second": {}, "latency": {}}
            continue
        cur = ring[-1]
        base = _baseline(ring, t, window_s)
        span = cur.t - base.t
        if span <= 0:
            out[key] = {"span_s": 0.0, "per_second": {}, "latency": {}}
            continue
        per_second = {}
        for series, val in cur.counters.items():
            if series.startswith(GAUGE_PREFIX):
                continue
            delta = val - base.counters.get(series, 0)
            per_second[series] = round(max(delta, 0) / span, 4)
        latency = {}
        for lane, counts in cur.buckets.items():
            prev = base.buckets.get(lane)
            delta = [c - (prev[i] if prev and i < len(prev) else 0)
                     for i, c in enumerate(counts)]
            n = sum(d for d in delta if d > 0)
            if n <= 0:
                continue
            latency[lane] = {
                "count": n,
                "p50_ms": round(percentile_from_counts(delta, 0.50), 4),
                "p95_ms": round(percentile_from_counts(delta, 0.95), 4),
                "p99_ms": round(percentile_from_counts(delta, 0.99), 4),
            }
        out[key] = {"span_s": round(span, 3), "per_second": per_second,
                    "latency": latency}
    return out


def ring_samples(node_id: str) -> list:
    """[(epoch_us, counters)] — the Chrome-trace counter track's input
    (every snapshot, gauges included)."""
    with _lock:
        ring = list(_rings.get(node_id, ()))
    return [(snap.epoch_us, dict(snap.counters)) for snap in ring]


def ring_len(node_id: str) -> int:
    with _lock:
        return len(_rings.get(node_id, ()))


def node_ids() -> list:
    with _lock:
        return sorted(_rings)


def reset() -> None:
    """Drop every ring (tests)."""
    with _lock:
        _rings.clear()


def collect_sample(node_id: str, extra: dict | None = None,
                   ledger=None) -> "tuple[dict, dict]":
    """One flat cumulative sample → (counters, buckets): per-lane event
    counts and bucket vectors from the latency histograms, the node's
    attributed jit/fallback counters plus the process-global data-layer
    traffic, SLO good/bad tallies, and ledger byte gauges. ``extra``
    merges caller series (the node adds hedge counters); ``ledger`` is
    the node's device ledger (process-global books when omitted).
    Lazy imports keep this module import-light — the sample runs on the
    scrape path only."""
    from elasticsearch_tpu.observability import histograms, ledger as _led
    from elasticsearch_tpu.observability import slo as _slo
    from elasticsearch_tpu.search import jit_exec
    counters: dict = {}
    buckets: dict = {}
    for lane, (counts, n, sum_ms, _mx) in \
            histograms.bucket_counts(node_id).items():
        counters[f"lane.{lane}.count"] = n
        counters[f"lane.{lane}.sum_ms"] = round(sum_ms, 3)
        buckets[lane] = counts
    js = jit_exec.cache_stats(node_id)
    for key, val in js.items():
        if isinstance(val, (int, float)):
            counters[f"jit.{key}"] = val
    for reason, n in js.get("fallback_reasons", {}).items():
        counters[f"fallback.plane.{reason}"] = n
    for key, val in jit_exec.cache_stats()["data_layer"].items():
        counters[f"data_layer.{key}"] = val
    for lane, st in _slo.counters(node_id).items():
        counters[f"slo.{lane}.good"] = st["good"]
        counters[f"slo.{lane}.bad"] = st["bad"]
    snap = ledger.snapshot() if ledger is not None \
        else _led.global_snapshot()
    for comp, b in snap["by_component"].items():
        counters[f"{GAUGE_PREFIX}hbm.{comp}.bytes"] = b
    counters[f"{GAUGE_PREFIX}hbm.total.bytes"] = snap["total_bytes"]
    if extra:
        counters.update(extra)
    return counters, buckets


def tick(node_id: str, extra: dict | None = None, ledger=None,
         now: float | None = None, force: bool = False) -> bool:
    """Collect one sample and record it — the scrape-path entry
    (_nodes/stats, /_prometheus, tests)."""
    counters, buckets = collect_sample(node_id, extra=extra,
                                       ledger=ledger)
    return record(node_id, counters, buckets, now=now, force=force)
