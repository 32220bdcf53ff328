"""What several per-layer readers share: the traced slice's reduction, the
device seconds and the dispatches of the cell's lane in it, and the
client requests whose service fell into it."""

from __future__ import annotations


def reduced(ctx):
    tr = ctx.get("traced")
    return tr["reduced"] if tr and tr.get("reduced") else None


def lane_seconds(ctx):
    """Device seconds of the lane's programs in the traced slice: the XLA
    modules the cell's file names (the program gives no stabler name)."""
    red = reduced(ctx)
    if red is None:
        return None
    names = ctx["cell"].spec["lane_modules"]
    sec = sum(ent["seconds"] for name, ent in red["modules"].items()
              if name in names)
    return sec or None


def lane_dispatches(ctx) -> int:
    tr = ctx["traced"]
    return sum(
        tr["after"]["lanes"].get(ln, {}).get("dispatches", 0)
        - tr["before"]["lanes"].get(ln, {}).get("dispatches", 0)
        for ln in ctx["cell"].spec["expected_lanes"])


def requests_in_slice(ctx) -> float:
    """Client requests served in the traced slice, a request that the
    slice's ends cut counting by the share of its time in flight (sent →
    reply complete) that lay inside."""
    t0, t1 = ctx["traced"]["t0"], ctx["traced"]["t1"]
    n = 0.0
    for _id, _due, sent, done, _st, ok, items in ctx["records"]:
        inside = min(done, t1) - max(sent, t0)
        if ok == items and inside > 0:
            n += min(1.0, inside / max(done - sent, 1e-9))
    return n
