"""Share of the traced slice in which no operation ran on the device: 1 −
union of the device operations' intervals ÷ the trace's own span."""
from benchmarks.layer_common import reduced


def read(ctx):
    red = reduced(ctx)
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
