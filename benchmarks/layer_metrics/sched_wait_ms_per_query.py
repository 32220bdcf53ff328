"""Time a request waits on the scheduler, per query of the traced slice:
self time of ``scheduler.queue`` (enqueue → pick-up, the hold included)
and ``scheduler.result`` (pick-up → its batch's result: the batches
ahead, then its own) — ``span_common``. A program whose span table has
no ``scheduler.result`` reads nothing: its result wait lies in
``action.shard`` / ``action.query`` self time."""
from benchmarks.collect_common import program_has
from benchmarks.span_common import self_ms_per_query


def read(ctx):
    if program_has("scheduler.result") is None:
        return None
    return self_ms_per_query(ctx, ("scheduler.queue", "scheduler.result"))
