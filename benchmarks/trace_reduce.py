"""From the profiler's ``.xplane.pb`` to busy/idle time, device time per
XLA module (jitted program) and the breakdown. Two steps, so that the
second can be checked against a small recorded trace kept beside the
tests: :func:`device_events` reads the file with nothing but JAX,
:func:`reduce_events` is arithmetic on plain lists.

The program gives its kernels no stable names yet (no ``named_scope``,
no ``TraceAnnotation``): programs are told apart by XLA module name, and
idle gaps are named by the modules on either side of them.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def device_events(xplane_path: str) -> list:
    """→ one entry per device plane: ``{"plane", "ops": [[name, start_ns,
    dur_ns], ...], "modules": [...]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        entry = {"plane": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            entry[key] = [[op_name(ev.name), float(ev.start_ns),
                           float(ev.duration_ns)] for ev in line.events]
        out.append(entry)
    return out


def op_name(event_name: str) -> str:
    """The TPU trace names an operation by its whole HLO line
    (``%fusion.3 = f32[64,1048576]{...} fusion(...)``): keep the name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def module_name(event_name: str) -> str:
    """``jit_run(1234567)`` → ``jit_run``: the program's name without the
    run's fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name)


def busy_union_ns(events: list) -> tuple:
    """Union of the intervals in which an operation ran → (busy ns,
    [(gap start, gap ns), ...]) with the gaps between them."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in spans:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def span_ns(planes: list) -> tuple:
    """(first start, last end) of every event of the device planes."""
    ev = [e for p in planes for e in p["ops"] + p["modules"]]
    return min(e[1] for e in ev), max(e[1] + e[2] for e in ev)


def reduce_events(planes: list, window_s: float | None = None,
                  top: int = 10) -> dict | None:
    """→ ``busy_s`` (mean over the device planes), ``window_s``,
    ``modules`` (name → count and seconds, summed over planes and divided
    by their number), ``device_ops`` and ``idle_gaps`` (the breakdown).
    None where no operation ran on a device. ``window_s`` is the trace's
    own span, first event to last on the trace's clock, unless it is
    given: busy and idle time then share one clock, and no host clock
    that started before the profiler did is divided by. What it cannot
    see is the device idle before the slice's first operation and after
    its last: at most one gap at either end."""
    planes = [p for p in planes if p["ops"] or p["modules"]]
    if not planes:
        return None
    if window_s is None:
        lo, hi = span_ns(planes)
        window_s = (hi - lo) / 1e9
    n = len(planes)
    busy = 0.0
    ops: dict = {}
    modules: dict = {}
    gaps_named: list = []
    for p in planes:
        # the ops line holds every operation; where a backend writes no
        # such line the module line bounds the busy time from above
        b, gaps = busy_union_ns(p["ops"] or p["modules"])
        busy += b
        for name, _s, d in p["ops"]:
            ops[name] = ops.get(name, 0.0) + d
        mods = sorted(p["modules"], key=lambda e: e[1])
        for name, _s, d in mods:
            ent = modules.setdefault(module_name(name),
                                     {"count": 0, "seconds": 0.0})
            ent["count"] += 1
            ent["seconds"] += d / 1e9
        starts = [m[1] for m in mods]
        for g_start, g_len in gaps:
            gaps_named.append((_gap_name(mods, starts, g_start), g_len))
    if busy <= 0:
        return None
    for ent in modules.values():
        ent["count"] /= n
        ent["seconds"] /= n
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps_named.sort(key=lambda g: -g[1])
    return {"busy_s": busy / n / 1e9, "window_s": window_s,
            "modules": modules,
            "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps_named[:top]]}


def _gap_name(mods: list, starts: list, at: float) -> str:
    """No host span reaches the profiler yet, so a gap carries what the
    device ran before it and after it."""
    import bisect
    i = bisect.bisect_right(starts, at)
    before = module_name(mods[i - 1][0]) if i > 0 else "start"
    after = module_name(mods[i][0]) if i < len(mods) else "end"
    return f"unattributed:{before}->{after}"
