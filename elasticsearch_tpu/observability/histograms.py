"""Fixed-bucket latency histograms, per lane, per node — always on.

Closed-loop traffic (and the continuous-batching scheduler it rides)
needs latency DISTRIBUTIONS, not means: a 68 ms device-RTT
floor under 16 clients is invisible in an average but owns the p50. The
reference ships the same idea as the ``search`` / ``indexing`` time
rollups in nodes stats; here every lane gets a log-spaced fixed-bucket
histogram so p50/p95/p99 are O(buckets) to read and O(1) to record —
cheap enough to stay on even when the span tracer is off.

Lanes: ``plane`` (collective-plane searches, per body), ``fanout``
(RPC fan-out searches), ``percolate`` (batched percolation runs),
``bulk`` (bulk requests), ``queue_wait`` (every queue's time on the
node: the thread pools' and the scheduler's — the scheduler keeps a
histogram of its own beside it), ``device_rtt`` (device round trips:
a launch's enqueue → the end of the drain that fetched its result,
fed by the tracing module's in-flight book when the launch closes).

Registries key on node id (see context.py) so multi-node in-process
clusters report per-node numbers in ``_nodes/stats``.
"""

from __future__ import annotations

import bisect
import threading

from elasticsearch_tpu.observability import slo
from elasticsearch_tpu.observability.context import current_node_id

#: log-spaced bucket upper bounds in ms: 0.01 ms → ~650 s, ×√2 per step.
#: Fixed at import so every node/lane agrees and merges are index-wise.
BOUNDS_MS = tuple(0.01 * (2 ** (i / 2.0)) for i in range(33))

#: the lanes _nodes/stats reports even before first observation
LANES = ("plane", "fanout", "percolate", "bulk", "queue_wait",
         "device_rtt")


class LatencyHistogram:
    """One lane's fixed-bucket latency histogram (ms)."""

    __slots__ = ("counts", "count", "sum_ms", "max_ms", "_lock")

    def __init__(self):
        self.counts = [0] * (len(BOUNDS_MS) + 1)   # +1 overflow bucket
        self.count = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0
        self._lock = threading.Lock()

    def observe(self, ms: float) -> None:
        ms = float(ms)
        i = bisect.bisect_left(BOUNDS_MS, ms)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum_ms += ms
            if ms > self.max_ms:
                self.max_ms = ms

    def percentile(self, q: float) -> float:
        """Bucket-resolved percentile (ms): linear interpolation inside
        the winning bucket — exact enough for p50/p95/p99 dashboards at
        √2-spaced buckets."""
        with self._lock:
            total = self.count
            if total == 0:
                return 0.0
            target = q * total
            cum = 0
            for i, c in enumerate(self.counts):
                if c == 0:
                    continue
                if cum + c >= target:
                    lo = BOUNDS_MS[i - 1] if i > 0 else 0.0
                    hi = BOUNDS_MS[i] if i < len(BOUNDS_MS) \
                        else self.max_ms
                    frac = (target - cum) / c
                    est = lo + (hi - lo) * min(max(frac, 0.0), 1.0)
                    # the largest observed value caps every percentile
                    # (bucket upper bounds overshoot the real maximum)
                    return min(est, self.max_ms)
                cum += c
            return self.max_ms

    def summary(self) -> dict:
        out = {
            "count": self.count,
            "sum_ms": round(self.sum_ms, 3),
            "max_ms": round(self.max_ms, 3),
        }
        for name, q in (("p50_ms", 0.50), ("p95_ms", 0.95),
                        ("p99_ms", 0.99)):
            out[name] = round(self.percentile(q), 4)
        return out


#: node id → lane → LatencyHistogram. "" collects unattributed events.
_registry: dict[str, dict[str, LatencyHistogram]] = {}
_reg_lock = threading.Lock()


def _hist(node_id: str, lane: str) -> LatencyHistogram:
    with _reg_lock:
        lanes = _registry.setdefault(node_id, {})
        h = lanes.get(lane)
        if h is None:
            h = lanes[lane] = LatencyHistogram()
        return h


def observe_lane(lane: str, ms: float, node_id: str | None = None) -> None:
    """Record one latency sample on ``lane`` for the current node (or an
    explicit ``node_id``), and classify it against the node's SLO target
    (slo.py) — the same seam feeds both books so they cannot drift."""
    nid = node_id if node_id is not None else (current_node_id() or "")
    _hist(nid, lane).observe(ms)
    slo.observe(lane, ms, nid)


def percentile_from_counts(counts, q: float) -> float:
    """Bucket-resolved percentile over a raw count vector (the windowed
    DELTA between two snapshots of one histogram's buckets) — the same
    interpolation as :meth:`LatencyHistogram.percentile`, minus the
    observed-max cap (deltas carry no max)."""
    total = sum(c for c in counts if c > 0)
    if total <= 0:
        return 0.0
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            lo = BOUNDS_MS[i - 1] if i > 0 else 0.0
            hi = BOUNDS_MS[i] if i < len(BOUNDS_MS) else BOUNDS_MS[-1]
            frac = (target - cum) / c
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        cum += c
    return BOUNDS_MS[-1]


def bucket_counts(node_id: str) -> dict:
    """{lane: (bucket counts tuple, count, sum_ms, max_ms)} — the raw
    cumulative vectors the timeseries ring snapshots for windowed
    percentiles. Only lanes with observations appear (an idle node
    snapshots an empty dict, not |LANES| zero vectors)."""
    with _reg_lock:
        lanes = dict(_registry.get(node_id, {}))
    out = {}
    for lane, h in sorted(lanes.items()):
        with h._lock:
            out[lane] = (tuple(h.counts), h.count, h.sum_ms, h.max_ms)
    return out


def summaries(node_id: str) -> dict:
    """{lane: summary} for one node — every known lane present (zeroed
    when never observed) so stats consumers see a stable shape."""
    with _reg_lock:
        lanes = dict(_registry.get(node_id, {}))
    out = {}
    for lane in LANES:
        h = lanes.pop(lane, None)
        out[lane] = h.summary() if h is not None \
            else LatencyHistogram().summary()
    for lane, h in sorted(lanes.items()):      # ad-hoc lanes, if any
        out[lane] = h.summary()
    return out


def node_ids() -> list:
    with _reg_lock:
        return sorted(_registry)


def reset() -> None:
    """Drop every histogram (tests)."""
    with _reg_lock:
        _registry.clear()
