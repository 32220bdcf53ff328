"""Self time of the action layer's spans (``action.*``: the coordinating
task, the msearch groups, the shard-side handler, parse and merge) in the
traced slice, per query (``span_common``)."""
from benchmarks.span_common import self_ms_per_query


def read(ctx):
    return self_ms_per_query(ctx, "action.")
