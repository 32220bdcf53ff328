"""Self time of the rest layer's spans (``rest.read``, ``rest.handle``,
``rest.serialise``, ``rest.write``) in the traced slice, per query: a span's
duration less what its child spans cover (``span_common``)."""
from benchmarks.span_common import self_ms_per_query


def read(ctx):
    return self_ms_per_query(ctx, "rest.")
