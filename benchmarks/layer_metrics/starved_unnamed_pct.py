"""Share of the traced slice in which the device was starved and no span
was open anywhere: the server waited for a client (``span_common``)."""
from benchmarks.span_common import starved_pct


def read(ctx):
    return starved_pct(ctx, None)
