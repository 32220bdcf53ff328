"""Share of the timed window in which the server's interpreter stood
still for a full collection: the union of the window's ``interp.collect``
ring records ÷ the window (``collect_common``). Logs one line a
collection — where in the window it began, how long it stopped every
thread, and the span it stopped (its record's parent) — which tells a
collection from a stop of the whole process that no record names; and
the young collections' rate from the counts at the window's ends."""
from benchmarks.collect_common import COLLECT, covered_ns, stops, \
    window_records
from benchmarks.span_common import log


def read(ctx):
    records = window_records(ctx)
    if records is None:
        return None
    t0, t1 = int(ctx["t_start"] * 1e9), int(ctx["t_end"] * 1e9)
    by_seq = {r[0]: r for r in records}
    mine = [r for r in records if r[3] == COLLECT]
    for r in mine:
        parent = by_seq.get(r[1])
        log(f"full collection at +{(r[5] - t0) / 1e9:.3f} s of the window: "
            f"{(r[6] - r[5]) / 1e6:.2f} ms, in "
            f"{parent[3] if parent else 'no open span'} (thread {r[4]})")
    b, a = ctx.get("before", {}).get("gc"), ctx.get("after", {}).get("gc")
    if a and b:
        young = a[0] - b[0] + a[1] - b[1]
        log(f"collections between the counts at the window's ends: "
            f"{young} young ({young / ((t1 - t0) / 1e9):.1f}/s of the "
            f"window), {a[2] - b[2]} full; {len(mine)} full in the window")
    return 100.0 * covered_ns(stops(records, t0, t1)) / (t1 - t0)
