"""Real rows per batch the scheduler launched in the window: ``delivered``
÷ ``batches_launched`` from ``ContinuousBatchScheduler.stats()``."""
from benchmarks import stats


def read(ctx):
    b = ctx.get("before", {}).get("scheduler")
    a = ctx.get("after", {}).get("scheduler")
    if not a or not b:
        return None
    return stats.scheduler_window(b, a).get("rows_per_batch")
