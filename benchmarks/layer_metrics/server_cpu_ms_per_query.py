"""CPU time of the server process over the window per search answered
(the load generators are other OS processes)."""
from benchmarks import stats


def read(ctx):
    good = stats.queries_per_second(
        ctx["all_records"], ctx["t_start"], ctx["t_end"]) \
        * (ctx["t_end"] - ctx["t_start"])
    if good <= 0:
        return None
    return 1e3 * (ctx["after"]["cpu_s"] - ctx["before"]["cpu_s"]) / good
