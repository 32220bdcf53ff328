"""Share of the traced slice in which the device was starved while a span
of the fetch layer was open with no open child (``span_common``)."""
from benchmarks.span_common import starved_pct


def read(ctx):
    return starved_pct(ctx, "fetch.")
