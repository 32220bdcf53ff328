"""Self time of the collective plane's own host spans (``plane.*``: the
batch's planning ``plane.resolve``, its query constants' upload
``plane.upload``, the program's enqueue ``plane.enqueue``, what
``plane.drain`` spends outside the ``jit.drain`` it holds, and the split
of the global top-k by owning shard ``plane.split``) in the traced slice,
per query (``span_common``). A program without these spans gives no
number."""
from benchmarks.span_common import self_ms_per_query


def read(ctx):
    return self_ms_per_query(ctx, "plane.")
