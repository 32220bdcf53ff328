"""Device time per dispatch of the cell's lane: device seconds of the
lane's XLA modules in the traced slice ÷ the lane's dispatches
(``costs.lane_rollup``) between the slice's ends."""
from benchmarks.layer_common import lane_dispatches, lane_seconds


def read(ctx):
    sec = lane_seconds(ctx)
    if sec is None:
        return None
    n = lane_dispatches(ctx)
    return 1e3 * sec / n if n > 0 else None
