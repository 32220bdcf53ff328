"""95th percentile of the same sample as ``p50_ms``; a failed, refused or
timed-out request counts as the window's length."""
from benchmarks import stats


def read(ctx):
    lat = stats.latencies_ms(ctx["records"], ctx["t_start"], ctx["t_end"])
    return stats.percentile(lat, 95) if lat else None
