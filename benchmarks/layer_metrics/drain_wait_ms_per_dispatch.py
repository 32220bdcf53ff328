"""Time the host spent blocked in ``jit.drain`` (the ``np.asarray`` that
waits until a launch's result is on the host) in the traced slice ÷ the
lane's dispatches between the slice's ends. It reads beside
``lane_device_ms``: equal when the host is never late to a drain, lower
when the result was ready before the host came for it."""
from benchmarks.layer_common import lane_dispatches
from benchmarks.span_common import analysis


def read(ctx):
    an = analysis(ctx)
    ent = an["by_name"].get("jit.drain") if an else None
    n = lane_dispatches(ctx) if ent else 0
    return ent[2] / 1e6 / n if n > 0 else None
