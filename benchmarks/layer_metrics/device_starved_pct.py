"""Share of the traced slice in which no launch was in flight on the
device, by the book the dispatch path keeps (``jit.enqueue`` opens a
launch, the end of its ``jit.drain`` closes it): what the host sees of the
chip's idle time without a profiler. A late drain hides idle time from
this book, so ``device_idle_pct`` bounds it from above."""
from benchmarks.span_common import analysis


def read(ctx):
    an = analysis(ctx)
    if an is None:
        return None
    return 100.0 * an["starved_ns"] / (an["t1"] - an["t0"])
