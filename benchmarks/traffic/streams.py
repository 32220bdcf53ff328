"""Traffic kind ``streams``: the one general generator. A traffic mix is a
data file ``traffic/<name>.json`` with ``"kind": "streams"`` and a list of
``streams``; this file turns it into jobs for the load generators
(``loadgen.py``), and knows nothing of what a query is — that is the data
kind's (``corpora/<kind>.py``: ``query_pool``, ``request``,
``warm_requests``).

A stream::

    {"name": "...",
     "arrivals": {"process": "closed", "processes": 2, "connections": 1,
                  "max_requests_per_s": 1.0, "sure_rounds": 2}
               | {"process": "poisson", "rate": 11.2, "connections": 64,
                  "burst": {"period_s": 10, "on_s": 2, "factor": 4}},
     "request":  {...}   handed to the data kind's ``request`` (op, items,
                         size, ...); ``items`` queries go into one request
     "queries":  {"pool": 8192, "pick": "walk" | "zipf",
                  "popularity_s": 1.0, ...}   the rest is the data kind's
     "measured": true, "checked": true}

Several streams run side by side (searches beside writes): each gets
generator processes of its own. What the mix fixes is the same on every
seed — which pool entry has which shape, which entries each request asks
for, the arrival gaps and their order; the seed gives the corpus, the
content of the queries and, in an open loop, the place in the one cyclic
sequence of arrivals at which the window starts.
"""

from __future__ import annotations

import numpy as np


def arrival_times(arrivals: dict, seconds: float, order_rng,
                  rotate_rng) -> tuple:
    """→ (due times in seconds after the start, the rotation) of an open
    stream: a Poisson
    process at ``rate`` per second, with ``burst`` a rate that is
    ``factor`` times higher for ``on_s`` of every ``period_s`` at the same
    mean. The gaps are the exponential distribution's own quantiles in an
    order drawn from ``order_rng``; the seed rotates that one sequence."""
    rate = float(arrivals["rate"])
    n = int(round(rate * seconds))
    unit = -np.log1p(-(np.arange(n) + 0.5) / n)          # mean 1
    rot = int(rotate_rng.integers(n)) if n else 0
    s = np.cumsum(np.roll(order_rng.permutation(unit), -rot))
    burst = arrivals.get("burst")
    if not burst:
        return s / rate, rot
    # operational time → clock time through the inverse of the cumulative
    # rate, which is piecewise linear: high for on_s, low for the rest
    period, on, f = (float(burst[k]) for k in ("period_s", "on_s", "factor"))
    low = rate / (1.0 + (f - 1.0) * on / period)
    edges_t, edges_n, t, acc = [0.0], [0.0], 0.0, 0.0
    while t < seconds + period:
        for span, r in ((on, low * f), (period - on, low)):
            t, acc = t + span, acc + span * r
            edges_t.append(t)
            edges_n.append(acc)
    return np.interp(s, edges_n, edges_t), rot


def pick_entries(queries: dict, n_requests: int, items: int, pool: int,
                 fixed) -> np.ndarray:
    """Which pool entries each request asks for → ``[n_requests, items]``.
    ``walk``: the pool in its own order, ``items`` at a time, so a
    request never repeats a query; ``zipf``: popularity P(i) ∝
    (i+1)^-popularity_s."""
    if queries.get("pick", "walk") == "walk":
        return (np.arange(n_requests * items) % pool).reshape(
            n_requests, items)
    w = np.arange(1, pool + 1, dtype=np.float64) ** -float(
        queries["popularity_s"])
    return np.searchsorted(np.cumsum(w / w.sum()), fixed.random(
        (n_requests, items)))


def build(traffic: dict, kind, corpus: dict, seed: int, seconds: float,
          index: str, max_batch: int) -> dict:
    """→ ``jobs`` (one per generator process), ``warm`` (requests that
    reach every program the streams can reach), ``checks`` (request id →
    the queries and request parameters its reply is compared by),
    ``sure`` (ids that surely run in the window: the sample is drawn
    among them), ``unmeasured`` (ids of streams that load the system but
    are not what the end-to-end metrics count)."""
    rng = np.random.default_rng([seed, 7])
    out = {"jobs": [], "warm": [], "checks": {}, "sure": [],
           "unmeasured": set()}
    rid = 0
    for si, st in enumerate(traffic["streams"]):
        fixed = np.random.default_rng([si, 1])
        arr, rq = st["arrivals"], st["request"]
        items = int(rq.get("items", 1))
        pool = kind.query_pool(corpus, st["queries"], rng, fixed)
        out["warm"] += kind.warm_requests(rq, pool, index, max_batch)
        if arr["process"] == "closed":
            n_conn = int(arr["processes"]) * int(arr["connections"])
            rounds = int(np.ceil(seconds * float(arr["max_requests_per_s"])
                                 / n_conn)) + 2
            due, rot, n = None, 0, rounds * n_conn
        else:
            due, rot = arrival_times(arr, seconds, fixed, rng)
            n = len(due)
        picks = np.roll(pick_entries(st["queries"], n, items, len(pool),
                                     fixed), -rot, axis=0)
        requests = []
        for j in range(n):
            qs = [pool[i] for i in picks[j]]
            req = {"id": rid, **kind.request(rq, qs, index)}
            if due is not None:
                req["due"] = float(due[j])
            requests.append(req)
            if st.get("checked", True):
                out["checks"][rid] = {"queries": qs, "request": rq}
                if due is not None or j // n_conn < int(
                        arr.get("sure_rounds", 2)):
                    out["sure"].append(rid)
            if not st.get("measured", True):
                out["unmeasured"].add(rid)
            rid += 1
        if due is None:
            # request j goes to connection j mod n_conn: every connection
            # walks a sequence of its own, the same on every seed
            streams = [requests[c::n_conn] for c in range(n_conn)]
            c = int(arr["connections"])
            out["jobs"] += [{"mode": "closed",
                             "streams": streams[p * c:(p + 1) * c]}
                            for p in range(int(arr["processes"]))]
        else:
            out["jobs"].append({"mode": "open", "requests": requests,
                                "connections": int(arr["connections"])})
    return out
