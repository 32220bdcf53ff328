"""Share of the traced slice in which the device was starved (the
in-flight book had no launch) while the interpreter stood still for a
full collection (an ``interp.collect`` ring record). Counted whole, not
shared among the spans open beside it: a collection stops every thread
(``collect_common``)."""
from benchmarks.collect_common import overlap_ns, slice_stops


def read(ctx):
    got = slice_stops(ctx)
    if got is None:
        return None
    collections, gaps, t0, t1 = got
    return 100.0 * overlap_ns(collections, gaps) / (t1 - t0)
