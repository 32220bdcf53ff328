"""What the span readers share: the program's always-on span ring and its
in-flight book, read for the traced slice.

The program (``elasticsearch_tpu/observability/tracing.py``) keeps one
fixed-shape record per span at every layer boundary of the served path,

    (seq, parent_seq, request_id, name, thread, start_ns, end_ns, cpu_ns)

on ``time.monotonic_ns()`` — the clock of ``traced.t0`` / ``traced.t1`` —
and a book of the stretches in which no launch was in flight on the
device (*starved*: the host gave the chip nothing). A reader is Python in
the server's own process, so it asks the module for both after the
window; ``ctx`` offers them through :func:`analysis`.

Every reader here answers ``None`` — never 0, never a short sum — where
the program has no ring (a parent commit from before it), where the run
was not traced, or where the ring or the book no longer holds the slice.

Definitions, shared by all eleven metrics:

* records are cut at the slice's ends; a cut record keeps the share of
  its ``cpu_ns`` that its kept duration is of its whole duration;
* a span's **self time** is its duration less what its child spans cover
  (the union of their intervals, on whatever thread they ran);
* ``cpu_ns`` is taken by the outermost span a thread has open (one
  reading of the thread's CPU clock costs 6 µs on the chip's host) and
  is -1 in the spans nested inside it on that thread. So CPU is known
  per **thread stretch**: the outermost span and everything under it on
  its thread. A stretch's *work* is the self time of its spans that do
  not block by design; work less the stretch's CPU is time in which
  runnable work did not run;
* a **query** is one answered ``_msearch`` item (or one answered search):
  a request that the slice's ends cut counts by the share of its time in
  flight that lay inside, as ``layer_common.requests_in_slice`` counts;
* starved time is put down to the spans that were open and had **no open
  child** — a span that only waits on another span of its request
  (``rest.handle`` on ``action.msearch``, that on its groups, a group on
  its shard) is not what holds the device up. Where several threads hold
  such spans the stretch is shared equally among them; where none is
  open anywhere the stretch is *unnamed*: the server waits for a client.
"""

from __future__ import annotations

import sys

#: spans that block by design: their wall time is not time in which
#: runnable work waited for the interpreter
BLOCK_BY_DESIGN = ("rest.read", "rest.write", "jit.drain",
                   "scheduler.queue", "action.msearch")
#: the host-side spans of jit_exec (``jit.drain`` waits for the device)
JIT_HOST = ("jit.pack", "jit.enqueue", "jit.unpack")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def cut(records: list, t0: int, t1: int) -> list:
    """Records that meet ``[t0, t1]``, cut at its ends."""
    out = []
    for seq, parent, rid, name, tid, a, b, cpu in records:
        ka, kb = max(a, t0), min(b, t1)
        if kb < ka or (kb == ka and b > a):
            continue
        if b > a and cpu > 0:
            cpu = cpu * (kb - ka) / (b - a)
        out.append((seq, parent, rid, name, tid, ka, kb, cpu))
    return out


def _children(records: list) -> dict:
    kids: dict = {}
    for r in records:
        kids.setdefault(r[1], []).append(r)
    return kids


def self_times(records: list) -> dict:
    """seq → self wall ns."""
    kids = _children(records)
    out = {}
    for seq, _p, _rid, _name, _tid, a, b, _cpu in records:
        covered, edge = 0, a
        for k in sorted(kids.get(seq, ()), key=lambda r: r[5]):
            ka, kb = max(k[5], edge), min(k[6], b)
            if kb > ka:
                covered, edge = covered + kb - ka, kb
        out[seq] = (b - a) - covered
    return out


def thread_stretches(records: list, selfs: dict) -> list:
    """[(outermost span's name, work ns, cpu ns)] — one per record that
    took its thread's CPU and does not itself block by design: *work* is
    the self time of the spans of its stretch (itself and what is nested
    under it on its own thread) that do not block by design."""
    kids = _children(records)
    out = []
    for rec in records:
        if rec[7] < 0 or rec[3] in BLOCK_BY_DESIGN:
            continue                    # nested, or all of it a wait
        work, todo = 0, [rec]
        while todo:
            r = todo.pop()
            if r[3] not in BLOCK_BY_DESIGN:
                work += selfs[r[0]]
            todo += [k for k in kids.get(r[0], ())
                     if k[4] == rec[4] and k[7] < 0]
        out.append((rec[3], work, rec[7]))
    return out


def leaf_shares(records: list, a: int, b: int) -> dict:
    """name → ns of ``[a, b]`` put down to it, ``None`` → ns with no span
    open: at every instant the stretch belongs, in equal shares, to the
    open spans that have no open child."""
    live = [r for r in records if r[5] < b and r[6] > a]
    edges = sorted({a, b} | {t for r in live for t in (r[5], r[6])
                             if a < t < b})
    out: dict = {}
    for lo, hi in zip(edges, edges[1:]):
        open_now = [r for r in live if r[5] <= lo and r[6] >= hi]
        waiting = {r[1] for r in open_now}
        leaves = [r[3] for r in open_now if r[0] not in waiting]
        for name in leaves or [None]:
            out[name] = out.get(name, 0.0) + (hi - lo) / max(len(leaves), 1)
    return out


def queries_in_slice(ctx) -> float:
    t0, t1 = ctx["traced"]["t0"], ctx["traced"]["t1"]
    n = 0.0
    for _id, _due, sent, done, _st, ok, items in ctx["records"]:
        inside = min(done, t1) - max(sent, t0)
        if ok == items and inside > 0:
            n += items * min(1.0, inside / max(done - sent, 1e-9))
    return n


def analyse(records: list, gaps: list, t0: int, t1: int) -> dict:
    """Everything the eleven readers read, from the ring's records and
    the book's starved stretches of one slice (both already cut)."""
    selfs = self_times(records)
    by_name: dict = {}      # name → [count, self ns, duration ns]
    for r in records:
        ent = by_name.setdefault(r[3], [0, 0.0, 0.0])
        ent[0] += 1
        ent[1] += selfs[r[0]]
        ent[2] += r[6] - r[5]
    threads: dict = {}      # outermost span's name → [count, work, cpu]
    for name, work, cpu in thread_stretches(records, selfs):
        ent = threads.setdefault(name, [0, 0.0, 0.0])
        ent[0] += 1
        ent[1] += work
        ent[2] += cpu
    starved: dict = {}
    worst = []
    for a, b in gaps:
        shares = leaf_shares(records, a, b)
        for name, ns in shares.items():
            starved[name] = starved.get(name, 0.0) + ns
        worst.append((b - a, a, shares))
    worst.sort(key=lambda w: -w[0])
    return {"t0": t0, "t1": t1, "by_name": by_name, "threads": threads,
            "starved": starved,
            "starved_ns": sum(b - a for a, b in gaps),
            "gaps": len(gaps), "worst": worst[:5]}


def analysis(ctx) -> dict | None:
    """The slice's analysis, made once per run and kept in ``ctx``."""
    if "_span_analysis" in ctx:
        return ctx["_span_analysis"]
    ctx["_span_analysis"] = out = _read(ctx)
    if out is not None:
        _log(ctx, out)
    return out


def _read(ctx) -> dict | None:
    tr = ctx.get("traced")
    if not tr:
        return None
    try:
        from elasticsearch_tpu.observability import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "ring_records"):
        return None                     # a program from before the ring
    t0, t1 = int(tr["t0"] * 1e9), int(tr["t1"] * 1e9)
    records = tracing.ring_records(t0, t1)
    gaps = tracing.starved_intervals(t0, t1)
    if not records or gaps is None:
        return None                     # nothing kept, or no longer whole
    return analyse(cut(records, t0, t1), gaps, t0, t1)


def _log(ctx, an: dict) -> None:
    """For the reader of a run's log: self time by span, and the named
    answer to "what was the host doing while the chip had nothing"."""
    n = queries_in_slice(ctx)
    span_s = (an["t1"] - an["t0"]) / 1e9
    per = 1e6 * max(n, 1e-9)
    log(f"spans in the traced slice ({span_s:.3f} s, {n:.1f} queries): "
        "name: count, self ms/query, duration s")
    for name, (cnt, wall, dur) in sorted(an["by_name"].items()):
        log(f"  {name}: {cnt}, {wall / per:.4f}, {dur / 1e9:.4f}")
    log("thread stretches by their outermost span: count, work ms/query "
        "(self time of what does not block by design), CPU ms/query, "
        "work less CPU")
    for name, (cnt, work, cpu) in sorted(an["threads"].items()):
        log(f"  {name}: {cnt}, {work / per:.4f}, {cpu / per:.4f}, "
            f"{(work - cpu) / per:.4f}")
    log(f"device starved {an['starved_ns'] / 1e9:.4f} s of {span_s:.3f} s "
        f"in {an['gaps']} stretch(es); by the span that was open: "
        + ", ".join(f"{name or 'unnamed'} {ns / 1e9:.4f} s" for name, ns in
                    sorted(an["starved"].items(), key=lambda kv: -kv[1])))
    for ns, at, shares in an["worst"]:
        log(f"  starved {ns / 1e9:.4f} s from +{(at - an['t0']) / 1e9:.3f} s"
            ": " + ", ".join(
                f"{name or 'unnamed'} {part / 1e9:.4f} s" for name, part in
                sorted(shares.items(), key=lambda kv: -kv[1])))


def self_ms_per_query(ctx, names) -> float | None:
    """Self time of the spans ``names`` selects (a prefix, or a tuple of
    whole names) per query of the slice."""
    an = analysis(ctx)
    n = queries_in_slice(ctx) if an else 0.0
    if an is None or n <= 0:
        return None
    pick = (lambda s: s.startswith(names)) if isinstance(names, str) \
        else (lambda s: s in names)
    hit = [ent for name, ent in an["by_name"].items() if pick(name)]
    return sum(ent[1] for ent in hit) / 1e6 / n if hit else None


def starved_pct(ctx, prefix: str | None) -> float | None:
    """Share of the slice that was starved while a span of ``prefix`` was
    open with no open child (``None``: while no span was open at all)."""
    an = analysis(ctx)
    if an is None:
        return None
    ns = sum(part for name, part in an["starved"].items()
             if (name is None if prefix is None
                 else name is not None and name.startswith(prefix)))
    return 100.0 * ns / (an["t1"] - an["t0"])
