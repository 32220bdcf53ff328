"""What the readers of the interpreter's full collections share: the
program's ``interp.collect`` ring records (``observability/tracing.py``:
one a collection of generation 2, left by the collector's callback under
the span it stopped, on ``time.monotonic_ns()``), for the traced slice
and for the whole timed window.

A full collection stops every thread of the server, so its interval is
counted whole, never shared among the spans open beside it. Every reader
answers ``None`` where the program has no such record in its span table
(a parent from before it), where the run was not traced, or where the
ring or the book no longer holds what is asked.
"""

from __future__ import annotations

from benchmarks import span_common

COLLECT = "interp.collect"


def program_has(name: str):
    """The program's tracing module where its span table names ``name``,
    else ``None``."""
    try:
        from elasticsearch_tpu.observability import tracing
    except ImportError:
        return None
    return tracing if name in getattr(tracing, "SPAN_LAYERS", {}) else None


def union(intervals) -> list:
    """Disjoint ``[a, b]`` covering the same instants, in order."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def covered_ns(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


def overlap_ns(xs, ys) -> int:
    """ns that lie in both unions of intervals."""
    xs, ys = union(xs), union(ys)
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(hi - lo, 0)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def stops(records: list, t0: int, t1: int) -> list:
    """The collections among ``records``, cut at ``[t0, t1]``."""
    return [(max(r[5], t0), min(r[6], t1)) for r in records
            if r[3] == COLLECT and r[6] > t0 and r[5] < t1]


def slice_stops(ctx) -> tuple | None:
    """(collections, starved stretches, t0, t1) of the traced slice
    ``[t0, t1]``, both cut to it → kept in ``ctx``."""
    if "_collect_slice" not in ctx:
        ctx["_collect_slice"] = _read_slice(ctx)
    return ctx["_collect_slice"]


def _read_slice(ctx) -> tuple | None:
    an = span_common.analysis(ctx)
    tracing = program_has(COLLECT)
    if an is None or tracing is None:
        return None
    t0, t1 = an["t0"], an["t1"]
    records = tracing.ring_records(t0, t1)
    gaps = tracing.starved_intervals(t0, t1)
    if records is None or gaps is None:
        return None
    return stops(records, t0, t1), gaps, t0, t1


def window_records(ctx) -> list | None:
    """Every ring record of the timed window ``[t_start, t_end]`` (not
    the slice: a slice holds one to three collections, a window six to
    seventy) → kept in ``ctx``."""
    if "_collect_window" not in ctx:
        ctx["_collect_window"] = _read_window(ctx)
    return ctx["_collect_window"]


def _read_window(ctx) -> list | None:
    tracing = program_has(COLLECT)
    if not ctx.get("traced") or tracing is None or "t_start" not in ctx:
        return None
    t0, t1 = int(ctx["t_start"] * 1e9), int(ctx["t_end"] * 1e9)
    records = tracing.ring_records(t0, t1)
    st = tracing.ring_stats()
    span_common.log(f"span ring after the run: {st['written']} records "
                    f"written, {st['overwritten']} overwritten")
    if records is None:
        span_common.log("the ring no longer holds the timed window: no "
                        "collection read")
    return records
