"""95th percentile of (sent − due): how late the load generator ran. A
starved generator must not be read as a fast server."""
from benchmarks import stats


def read(ctx):
    late = stats.lateness_ms(ctx["records"], ctx["t_start"], ctx["t_end"])
    return stats.percentile(late, 95) if late else None
