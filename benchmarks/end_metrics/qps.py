"""Searches answered without failure (each ``_msearch`` item counts one)
over the window's seconds; a request the window's end cut counts by the
share of its time in flight that lay inside."""
from benchmarks import stats


def read(ctx):
    return stats.queries_per_second(ctx["records"], ctx["t_start"],
                                    ctx["t_end"])
