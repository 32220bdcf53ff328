"""Median client-side latency over ALL requests due in the window, from
the instant each was due."""
from benchmarks import stats


def read(ctx):
    lat = stats.latencies_ms(ctx["records"], ctx["t_start"], ctx["t_end"])
    return stats.percentile(lat, 50) if lat else None
