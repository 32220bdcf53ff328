"""Self time of the fetch layer's spans (``fetch.hits``: one per
``fetch_phase`` call, never per hit) in the traced slice, per query
(``span_common``)."""
from benchmarks.span_common import self_ms_per_query


def read(ctx):
    return self_ms_per_query(ctx, "fetch.")
