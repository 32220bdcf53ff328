"""Chrome-trace-format export (the LLM-serving tracing playbook's
offline viewer): span records → the Trace Event JSON that
chrome://tracing and Perfetto load directly.

Each span becomes one complete ("X") event; node ids map to pids and
thread idents to tids, so a cross-node search renders as one timeline
with per-node lanes. Counter samples (the timeseries ring's ledger
bytes and per-lane rates) become "C" events — Perfetto renders them as
stacked counter tracks under the node's process, so HBM occupancy and
lane throughput line up against the spans that caused them.
``GET /_nodes/trace`` serves this document.
"""

from __future__ import annotations


def chrome_trace(spans: list, label: str = "elasticsearch-tpu",
                 counters: dict | None = None) -> dict:
    """Span records (tracing.py shape) → a Trace Event Format document:
    ``{"traceEvents": [...], "displayTimeUnit": "ms"}``.

    ``counters`` maps a node id to its sample list
    ``[(ts_us, {series: value})]`` (timeseries.ring_samples shape);
    every series becomes one counter track on that node's pid."""
    events = []
    pids: dict[str, int] = {}

    def pid_for(node: str) -> int:
        pid = pids.get(node)
        if pid is None:
            pid = pids[node] = len(pids) + 1
            events.append({
                "ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": f"node[{node or '-'}]"},
            })
        return pid

    for rec in spans:
        pid = pid_for(rec.get("node", ""))
        args = {"trace_id": rec["trace_id"],
                "span_id": rec["span_id"],
                "status": rec.get("status", "ok")}
        if rec.get("parent_id") is not None:
            args["parent_id"] = rec["parent_id"]
        args.update(rec.get("attrs", {}))
        events.append({
            "name": rec["name"],
            "cat": label,
            "ph": "X",
            "ts": rec["start_us"],
            "dur": max(int(rec["duration_us"]), 1),
            "pid": pid,
            "tid": rec.get("thread", 0),
            "args": args,
        })
    for node, samples in (counters or {}).items():
        pid = pid_for(node)
        for ts_us, values in samples:
            # one "C" event per series per sample: Perfetto draws each
            # named counter as its own track; grouping related series
            # into one event would stack them into a single area chart,
            # which is wrong for unrelated units (bytes vs qps)
            for series, value in values.items():
                events.append({
                    "name": series, "cat": "telemetry", "ph": "C",
                    "ts": int(ts_us), "pid": pid,
                    "args": {"value": round(float(value), 3)},
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
