"""Span tracing: one primitive, two views of what it records.

**The always-on view — the ring.** Every span whose name is in the
closed table :data:`SPAN_LAYERS` (name → layer, as lane reasons are a
closed table) appends one fixed-shape record to a bounded per-process
ring when it closes, whether or not anyone asked for a trace::

    (seq, parent_seq, request_id, name, thread, start_ns, end_ns, cpu_ns)

``start_ns``/``end_ns`` are ``time.monotonic_ns()`` (the clock the load
generators and the benchmark harness stamp with); ``cpu_ns`` is the
thread's own CPU inside the span (``time.thread_time_ns()``) in the
record of the OUTERMOST span a thread has open, and -1 ("not taken: it
is in the outermost span's") in those nested inside it on that thread —
one reading of that clock is a system call that costs 6 µs on the
chip's host (0.3 µs on a desktop kernel), a span without it 3 µs, and
a query opens eight;
``parent_seq`` is the span that caused it (0 for a root), and
``request_id`` is minted once per HTTP request (:func:`request`, in
``rest/server.py``) or by the first span of an in-process caller. Both
ride pool submits on the seam that carries the trace context
(:func:`bind_context` ← ``tasks.bind_current``). The ring holds
:data:`RING_CAP` records of 64 bytes (:data:`RING_BYTES` = 64 MiB,
allocated once), counts what it overwrote, and answers a reader that
asks for an interval it no longer holds with ``None``, never a short
list. Spans sit at LAYER BOUNDARIES only — never inside a per-hit or
per-term loop. Each span also enters a ``jax.profiler.TraceAnnotation``
``es.<name>`` with ``request=<id>``, so a profiler session that
records host events (``host_tracer_level`` ≥ 1) shows the same spans on
the device trace's clock; with no such session it is a no-op.

**The opt-in view — the tree.** A trace is keyed by the COORDINATING
task id (tasks/manager.py mints it), so the span tree and the task tree
describe the same request and ``GET /_tasks/{id}/trace`` can reassemble
one search's spans from every node's store. Context rides the same
seams the task parent links do:

* thread-local :class:`TraceContext` (trace id + innermost span id +
  recording node);
* :data:`TRACE_HEADER` on outbound RPCs — stamped by
  ``TransportService.send_request`` next to the parent-task header,
  re-installed (with the RECEIVING node's id) around handler dispatch;
* ``tasks.bind_current`` carries the context across pool submits via
  :func:`bind_context`.

Disabled-path contract: no active context ⇒ NO :class:`Span` objects
are allocated (counter-verified by :func:`spans_allocated`); a span
outside the table is then the shared no-op singleton, a span inside it
leaves its ring record and nothing else.

**Device seams and the in-flight book.** :func:`device_span` is the
same primitive at a device touchpoint. What it times is the HOST side
of the seam: for a dispatch-class site (:data:`RTT_SITES`) that is the
*enqueue* of a compiled program — JAX returns before the device has
run it — so its duration is neither device time nor a round trip. It
feeds the slow-log attribution (``attribution.device_ms``) and, on
clean exits, ``costs.note_dispatch`` with that enqueue time (the
planner prices from it). The round trip is the in-flight book's: a
dispatch-class span opens one *launch*, the end of the ``jit.drain``
span that fetched its result closes it, and the always-on
``device_rtt`` histogram lane gets the launch's open→close interval.
Between launches the book accumulates the time in which nothing was in
flight — the device was **starved** by the host — which
``_nodes/stats`` reports as ``device.starved_pct``. A launch whose
result is fetched outside a ``jit.drain`` span (the per-segment lane)
closes with its enqueue and is counted in ``launches_without_drain``:
there the book over-reads starved time. (The collective plane's launch
closes at the end of its ``plane.drain``.) A
late drain (the host reached ``np.asarray`` after the result was
ready) hides idle time from the book; a profiler's device idle share
bounds it from above.

Spans end on ALL exits — they are context managers, and an exception
unwinding through one stamps ``status`` ("cancelled" for task
cancellation, "error" otherwise) before recording, so cancelled and
timed-out requests still yield complete, closed trees with zero open
spans left behind; the ring record and the launch close likewise.
"""

from __future__ import annotations

import contextlib
import itertools
import struct
import threading
import time
from collections import OrderedDict, deque

from jax.profiler import TraceAnnotation

from elasticsearch_tpu.common.errors import TaskCancelledError
from elasticsearch_tpu.observability import attribution, histograms
from elasticsearch_tpu.observability.context import (
    _current_override, current_node_id, use_node)

__all__ = [
    "TRACE_HEADER", "TraceContext", "Span", "trace", "adopt", "span",
    "device_span", "active", "wire_header", "bind_context",
    "collect_spans", "profile_sink", "sink_shard_profile",
    "spans_allocated", "spans_for", "all_spans", "store_stats",
    "open_span_count", "build_tree", "reset", "current_node_id",
    "use_node", "SPAN_LAYERS", "RING_CAP", "RING_BYTES", "request",
    "ring_records", "ring_stats", "launch_scope", "close_launches",
    "starved_intervals", "book_stats",
]

#: request-dict key carrying {"id": trace_id, "parent": span_id} across
#: the wire (stripped by TransportService before the handler runs, like
#: the parent-task header)
TRACE_HEADER = "__trace_ctx__"

#: device seam sites that ENQUEUE a compiled program. The span itself
#: times only that enqueue (an asynchronous call on the chip); each one
#: opens a launch in the in-flight book, and the ``device_rtt`` lane is
#: fed when the launch closes — at the end of the ``jit.drain`` span
#: that fetched the result, which makes it the round trip the name
#: promises, or with the enqueue where the lane has no drain span
RTT_SITES = frozenset((
    "dispatch", "plane-dispatch", "percolate", "pruning-dispatch",
    "rescore-dispatch", "fusion-dispatch", "maxsim-dispatch",
    "impact-shard-dispatch", "knn-mesh-merge"))

#: every other device seam site (host→device transfers, compiles,
#: composes): timed for the slow log, no launch
_OTHER_SITES = (
    "compile", "upload", "reader-upload", "compose", "blockmax-compose",
    "impact-upload", "vector-upload", "block-placement-upload")

#: device seam site → its span name: every program enqueue through the
#: plain ``dispatch`` site is ``jit.enqueue``
_SITE_SPAN = {site: "jit.enqueue" if site == "dispatch" else f"jit.{site}"
              for site in sorted(RTT_SITES) + list(_OTHER_SITES)}

#: THE closed table of span names → layer. A name outside it leaves no
#: ring record (the tree still takes any name a test or a tool gives
#: it); ``tests/test_tracing.py`` holds every literal ``span("...")``
#: of the package to this table. Names are ``<layer prefix>.<what>``;
#: the layer is what the per-layer metrics of ``benchmarks/`` group by.
SPAN_LAYERS = {
    # rest — rest/server.py Handler._handle
    "rest.read": "rest", "rest.handle": "rest",
    "rest.serialise": "rest", "rest.write": "rest",
    # action — action/search_action.py
    "action.msearch": "action", "action.msearch_group": "action",
    "action.shard_msearch": "action",
    "action.search": "action", "action.parse": "action",
    "action.plane": "action", "action.query": "action",
    "action.fetch": "action", "action.reduce": "action",
    "action.shard": "action", "action.shard_query": "action",
    "action.shard_fetch": "action",
    # the collective plane under ``action.plane`` — mesh_engine's
    # search_batch (host planning of the batch, its query constants'
    # upload, the program's enqueue — it holds a ``jit.enqueue`` — and
    # the drain that holds ``jit.drain``)
    # and search_action's split of the global top-k by owning shard
    "plane.resolve": "action", "plane.upload": "action",
    "plane.enqueue": "action", "plane.drain": "action",
    "plane.split": "action",
    # scheduler — search/scheduler.py
    "scheduler.queue": "scheduler", "scheduler.launch": "scheduler",
    "scheduler.drain": "scheduler",
    # planner — search/planner.py
    "plan.knn": "planner", "plan.rescore": "planner",
    "plan.impact": "planner", "plan.exact": "planner",
    "plan.cost": "planner",
    # jit_exec — search/jit_exec.py, search/phase.py launch/drain, and
    # the device seams (``jit.enqueue`` among them)
    "jit.pack": "jit_exec", "jit.drain": "jit_exec",
    "jit.unpack": "jit_exec",
    **{name: "jit_exec" for name in _SITE_SPAN.values()},
    # fetch — search/phase.py fetch_phase
    "fetch.hits": "fetch",
}

#: what the tree (Profile API, ``GET /_tasks/{id}/trace``) has always
#: called the spans that the table names by layer
_TREE_LABEL = {"action.search": "search", "action.parse": "parse",
               "action.plane": "plane", "action.query": "query",
               "action.fetch": "fetch", "action.reduce": "reduce",
               "action.shard": "shard",
               "action.shard_query": "shard-query",
               "action.shard_fetch": "shard-fetch"}

SPAN_NAMES = tuple(SPAN_LAYERS)
_SPAN_CODE = {name: i for i, name in enumerate(SPAN_NAMES)}
#: the spans' names in a profiler's trace, told apart from JAX's own
#: host events by the prefix
_ANNOTATIONS = tuple(f"es.{name}" for name in SPAN_NAMES)


class _Tls(threading.local):
    """Per-thread tracing state. Class-level defaults, so a thread that
    never set a field reads it at attribute speed (``getattr`` with a
    default raises and catches an ``AttributeError`` on every miss)."""
    ctx = None              # TraceContext of the opt-in tree
    collectors = None       # collect_spans() stack
    sink = None             # profile_sink() landing zone
    rid = 0                 # request id of the ring's records
    open_seq = 0            # innermost open ring span
    cpu_taken = False       # an open ring span here reads the CPU clock
    launches = None         # launch_scope() list


_tls = _Tls()
_span_seq = itertools.count(1)
#: Span allocations since process start — the tracer-off guard reads
#: this before/after a request and asserts zero delta. Plain int += 1
#: under the GIL; consistency beyond "monotone, exact when quiescent"
#: is not needed.
_alloc = [0]


class TraceContext:
    """Immutable propagation record: children of the current moment
    parent under ``parent_span_id`` inside ``trace_id``, recorded on
    ``node_id``'s store."""

    __slots__ = ("trace_id", "parent_span_id", "node_id")

    def __init__(self, trace_id: str, parent_span_id: str | None,
                 node_id: str):
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.node_id = node_id


def current_ctx() -> "TraceContext | None":
    return _tls.ctx


def active() -> bool:
    return _tls.ctx is not None


# ---------------------------------------------------------------------------
# per-node stores
# ---------------------------------------------------------------------------

class TraceStore:
    """One node's finished spans, grouped by trace id (bounded LRU of
    traces), plus the open-span count the leak guards assert on."""

    TRACE_CAP = 128

    def __init__(self):
        self._traces: "OrderedDict[str, list]" = OrderedDict()
        self._lock = threading.Lock()
        self.open_spans = 0
        self.spans_recorded = 0

    def opened(self) -> None:
        with self._lock:
            self.open_spans += 1

    def finished(self, rec: dict) -> None:
        with self._lock:
            self.open_spans -= 1
            self.spans_recorded += 1
            lst = self._traces.get(rec["trace_id"])
            if lst is None:
                lst = self._traces[rec["trace_id"]] = []
                while len(self._traces) > self.TRACE_CAP:
                    self._traces.popitem(last=False)
            lst.append(rec)
            self._traces.move_to_end(rec["trace_id"])

    def spans(self, trace_id: str) -> list:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def all(self) -> list:
        with self._lock:
            return [rec for lst in self._traces.values() for rec in lst]

    def stats(self) -> dict:
        with self._lock:
            return {"open_spans": self.open_spans,
                    "spans_recorded": self.spans_recorded,
                    "traces": len(self._traces)}


_stores: dict[str, TraceStore] = {}
_stores_lock = threading.Lock()


def _store(node_id: str) -> TraceStore:
    s = _stores.get(node_id)
    if s is None:
        with _stores_lock:
            s = _stores.setdefault(node_id, TraceStore())
    return s


def spans_for(node_id: str, trace_id: str) -> list:
    return _store(node_id).spans(trace_id)


def all_spans(node_id: str) -> list:
    return _store(node_id).all()


def store_stats(node_id: str) -> dict:
    return _store(node_id).stats()


def open_span_count(node_id: str | None = None) -> int:
    """Open spans on one node's store, or across every store."""
    if node_id is not None:
        return _store(node_id).stats()["open_spans"]
    with _stores_lock:
        stores = list(_stores.values())
    return sum(s.stats()["open_spans"] for s in stores)


def spans_allocated() -> int:
    return _alloc[0]


def reset(ring_cap: int | None = None) -> None:
    """Drop every store, and start a fresh ring and a fresh in-flight
    book (tests; ``ring_cap`` lets one overflow a small ring)."""
    global _ring, _book
    with _stores_lock:
        _stores.clear()
    _ring = _Ring(ring_cap or RING_CAP)
    _book = _Book()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()


class Span:
    """One timed region of one trace. Context manager — the only way a
    span ends is ``__exit__``, so every exit path (return, raise,
    cancellation) closes and records it."""

    __slots__ = ("trace_id", "span_id", "parent_id", "node_id", "name",
                 "attrs", "start_us", "_t0", "_prev_ctx")

    def __init__(self, ctx: TraceContext, name: str, attrs: dict):
        _alloc[0] += 1
        self.trace_id = ctx.trace_id
        self.parent_id = ctx.parent_span_id
        self.node_id = ctx.node_id
        self.span_id = f"{ctx.node_id[:8]}-{next(_span_seq)}"
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._prev_ctx = _tls.ctx
        _tls.ctx = TraceContext(self.trace_id, self.span_id, self.node_id)
        # wall clock orders spans ACROSS nodes; the duration is taken on
        # the ring's clock
        self.start_us = time.time_ns() // 1000
        self._t0 = time.monotonic_ns()
        _store(self.node_id).opened()
        return self

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        dur_us = (time.monotonic_ns() - self._t0) // 1000
        _tls.ctx = self._prev_ctx
        if exc_type is None:
            status = "ok"
        elif issubclass(exc_type, TaskCancelledError):
            status = "cancelled"
        else:
            status = "error"
        rec = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "node": self.node_id,
            "name": self.name,
            "start_us": self.start_us,
            "duration_us": dur_us,
            "thread": threading.get_ident(),
            "status": status,
        }
        if self.attrs:
            rec["attrs"] = dict(self.attrs)
        _store(self.node_id).finished(rec)
        stack = _tls.collectors
        if stack:
            stack[-1].append(rec)
        return False


class _RingSpan:
    """THE span primitive: one region at a layer boundary. Always leaves
    one ring record and one ``TraceAnnotation``; builds the tree's
    :class:`Span` as well when a trace context is active; at a device
    seam (``site``) also feeds the side channels — the slow-log
    attribution, the in-flight book for a dispatch-class site, and,
    with ``cost`` = ``(lane, shape_key, n_real, rows)``, the program
    cost observatory (:mod:`~elasticsearch_tpu.observability.costs`)
    with the span's duration as one dispatch sample. Cost recording
    happens on CLEAN exits only: a failed dispatch (device fault,
    breaker-bound error) must never poison the program's EWMA or
    histogram — the chaos suites pin this."""

    __slots__ = ("_code", "_label", "_attrs", "_site", "_cost", "_seq",
                 "_parent", "_rid", "_minted", "_t0", "_cpu0", "_ann",
                 "_tree", "_launch")

    def __init__(self, code: int, label: str, attrs: dict,
                 site: str | None = None, cost: tuple | None = None):
        self._code = code
        self._label = label
        self._attrs = attrs
        self._site = site
        self._cost = cost
        self._tree = None
        self._launch = None

    def __enter__(self):
        tls = _tls
        rid = tls.rid
        self._minted = not rid
        if not rid:
            # an in-process caller (or a background thread): the first
            # span is its request's root
            rid = tls.rid = next(_request_seq)
        self._rid = rid
        self._parent = tls.open_seq
        self._seq = tls.open_seq = next(_ring.seq)
        ctx = tls.ctx
        if ctx is not None:
            self._tree = Span(ctx, self._label, self._attrs).__enter__()
        # TraceMe starts on construction, so the annotation is built here
        self._ann = TraceAnnotation(_ANNOTATIONS[self._code], request=rid)
        if tls.cpu_taken:
            self._cpu0 = -1
        else:
            # the outermost span of this thread: it alone pays for the
            # thread's CPU clock
            tls.cpu_taken = True
            self._cpu0 = time.thread_time_ns()
        self._t0 = time.monotonic_ns()
        if self._site in RTT_SITES:
            self._launch = _Launch(self._t0)
        return self

    def set(self, **attrs) -> "_RingSpan":
        if self._tree is not None:
            self._tree.set(**attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic_ns()
        tls = _tls
        cpu = self._cpu0
        if cpu >= 0:
            cpu = time.thread_time_ns() - cpu
            tls.cpu_taken = False
        self._ann.__exit__(exc_type, exc, tb)
        tls.open_seq = self._parent
        if self._minted:
            tls.rid = 0
        _ring.write(self._seq, self._parent, self._rid, self._code,
                    threading.get_ident(), self._t0, t1, cpu)
        if self._tree is not None:
            self._tree.__exit__(exc_type, exc, tb)
            self._tree = None
        if self._site is not None:
            self._device_exit((t1 - self._t0) / 1e6, exc_type is None)
        return False

    def _device_exit(self, dur_ms: float, clean: bool) -> None:
        attribution.device_ms(self._site, dur_ms)
        launch = self._launch
        if launch is not None:
            held = _tls.launches
            if clean and held is not None:
                held.append(launch)     # open until its drain's end
            else:
                launch.close(drained=False)
        if self._cost is not None and clean:
            from elasticsearch_tpu.observability import costs
            lane, shape_key, n_real, rows = self._cost
            costs.note_dispatch(lane, shape_key, dur_ms,
                                n_real=n_real, rows=rows)


def span(name: str, **attrs):
    """A region at a layer boundary. A ``name`` of :data:`SPAN_LAYERS`
    always leaves its ring record; the tree's :class:`Span` is built
    only under an active trace context. Any other name is the tree's
    alone — and the shared no-op when no trace is active (nothing
    allocated)."""
    code = _SPAN_CODE.get(name)
    if code is not None:
        return _RingSpan(code, _TREE_LABEL.get(name, name), attrs)
    ctx = _tls.ctx
    if ctx is None:
        return _NOOP
    return Span(ctx, name, attrs)


def device_span(site: str, cost: tuple | None = None):
    """The span at a device seam. It times the HOST side of the seam:
    for ``dispatch``-class sites that is the enqueue of the compiled
    call, not the device's work (see the module docstring). The tree
    shows it under ``site``; the ring under ``jit.enqueue`` (every
    plain ``dispatch``) or ``jit.<site>``."""
    name = _SITE_SPAN.get(site)
    if name is None:
        raise ValueError(f"unregistered device seam site {site!r} — add "
                         f"it to RTT_SITES or _OTHER_SITES in "
                         f"observability/tracing.py")
    return _RingSpan(_SPAN_CODE[name], site, {}, site, cost)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

#: records the ring holds; 64 bytes each, allocated once. Sized for a
#: reader that asks about a slice of a minute ago: single searches
#: through the scheduler leave 15 records a request, so 200 requests/s
#: (the knn cell, PERF.md section 5) overwrote 2^17 records in 44 s and
#: a benchmark window's span metrics fell silent; 2^20 keeps a 51 s
#: window whole up to about 1,300 requests/s
RING_CAP = 1 << 20
_REC = struct.Struct("<8q")
RING_BYTES = RING_CAP * _REC.size

_request_seq = itertools.count(1)


class _Ring:
    """Fixed-shape span records in one preallocated buffer. A record's
    slot is its ``seq`` modulo the capacity; ``seq`` is drawn when the
    span opens (children name it as ``parent_seq``) and the record is
    written when the span closes, in one ``pack_into`` (atomic under
    the interpreter lock). Only a write that wraps takes the lock, to
    count what it overwrites."""

    def __init__(self, cap: int):
        assert cap & (cap - 1) == 0, "capacity is a power of two"
        self.cap = cap
        self.buf = bytearray(cap * _REC.size)
        self.seq = itertools.count(1)
        self.lock = threading.Lock()
        self.written = 0
        self.overwritten = 0
        #: the latest end of any record the ring has lost: an interval
        #: that starts before it can no longer be answered whole
        self.lost_end_ns = 0

    def write(self, seq, parent, rid, code, tid, t0, t1, cpu) -> None:
        off = (seq & (self.cap - 1)) * _REC.size
        if seq > self.cap:
            with self.lock:
                old = _REC.unpack_from(self.buf, off)
                if old[0] > seq:
                    # a span that outlived a whole turn of the ring:
                    # its own record is the one that is lost
                    self.overwritten += 1
                    self.lost_end_ns = max(self.lost_end_ns, t1)
                    return
                if old[0]:
                    self.overwritten += 1
                    self.lost_end_ns = max(self.lost_end_ns, old[6])
                _REC.pack_into(self.buf, off, seq, parent, rid, code,
                               tid, t0, t1, cpu)
        else:
            _REC.pack_into(self.buf, off, seq, parent, rid, code, tid,
                           t0, t1, cpu)
        self.written += 1


_ring = _Ring(RING_CAP)


def ring_records(t0_ns: int, t1_ns: int) -> "list[tuple] | None":
    """The closed records whose interval meets ``[t0_ns, t1_ns]``, as
    ``(seq, parent_seq, request_id, name, thread, start_ns, end_ns,
    cpu_ns)`` in ``seq`` order — or ``None`` when the ring has lost a
    record that met the interval: never a short list."""
    ring = _ring
    snap = bytes(ring.buf)               # one atomic copy
    if t0_ns < ring.lost_end_ns:
        return None
    out = [(r[0], r[1], r[2], SPAN_NAMES[r[3]]) + r[4:]
           for r in _REC.iter_unpack(snap)
           if r[0] and r[6] >= t0_ns and r[5] <= t1_ns]
    out.sort()
    return out


def ring_stats() -> dict:
    ring = _ring
    return {"capacity": ring.cap, "bytes": len(ring.buf),
            "written": ring.written, "overwritten": ring.overwritten}


@contextlib.contextmanager
def request():
    """Mint one request id for everything this thread does inside (the
    HTTP ingress: the four ``rest.*`` spans of one request are siblings
    and share it)."""
    prev = _tls.rid
    _tls.rid = next(_request_seq)
    try:
        yield _tls.rid
    finally:
        _tls.rid = prev


# ---------------------------------------------------------------------------
# the in-flight book
# ---------------------------------------------------------------------------

class _Book:
    """When was a launch in flight, and when was the device starved of
    one. One book per process: in-process nodes share the device, and a
    mesh program occupies all its devices together."""

    #: starved intervals kept for a reader to put names to
    GAP_CAP = 1 << 14

    def __init__(self):
        self.lock = threading.Lock()
        self.in_flight = 0
        self.since_ns = self.born_ns = time.monotonic_ns()
        self.starved_ns = 0
        self.in_flight_ns = 0
        self.launches = 0
        self.without_drain = 0
        self.gaps: deque = deque(maxlen=self.GAP_CAP)
        self.lost_end_ns = 0

    def open(self, now: int) -> None:
        with self.lock:
            if self.in_flight == 0:
                now = max(now, self.since_ns)
                self.starved_ns += now - self.since_ns
                if len(self.gaps) == self.GAP_CAP:
                    self.lost_end_ns = self.gaps[0][1]
                self.gaps.append((self.since_ns, now))
                self.since_ns = now
            self.in_flight += 1
            self.launches += 1

    def close(self, now: int, drained: bool) -> None:
        with self.lock:
            self.in_flight -= 1
            self.without_drain += not drained
            if self.in_flight == 0:
                now = max(now, self.since_ns)
                self.in_flight_ns += now - self.since_ns
                self.since_ns = now


_book = _Book()


class _Launch:
    """One compiled program handed to the device and not yet known to
    be back. Closes exactly once: at the end of the ``jit.drain`` that
    fetched its result, at the enqueue's own end where no drain span
    will follow, or when the last reference to a handle that nobody
    drained goes away."""

    __slots__ = ("_t0", "_book")

    def __init__(self, now: int):
        self._t0 = now
        self._book = _book
        self._book.open(now)

    def close(self, drained: bool = True) -> None:
        book, self._book = self._book, None
        if book is None:
            return
        now = time.monotonic_ns()
        book.close(now, drained)
        histograms.observe_lane("device_rtt", (now - self._t0) / 1e6)

    def __del__(self):
        try:
            self.close(drained=False)
        except Exception:               # noqa: BLE001 — interpreter exit
            pass


@contextlib.contextmanager
def launch_scope():
    """Launches opened on this thread inside the scope stay open past
    their enqueue: the caller takes the yielded list into the handle it
    returns and :func:`close_launches` it at the end of the drain. If
    the scope is left by an exception the launches close there."""
    prev = _tls.launches
    _tls.launches = held = []
    try:
        yield held
    except BaseException:
        close_launches(held, drained=False)
        raise
    finally:
        _tls.launches = prev


def close_launches(launches, drained: bool = True) -> None:
    for launch in launches:
        launch.close(drained)


def starved_intervals(t0_ns: int, t1_ns: int) -> "list[tuple] | None":
    """The stretches of ``[t0_ns, t1_ns]`` in which no launch was in
    flight, cut at its ends, oldest first — or ``None`` when the book
    has dropped a stretch that met the interval."""
    book = _book
    with book.lock:
        if t0_ns < book.lost_end_ns:
            return None
        gaps = list(book.gaps)
        if book.in_flight == 0:
            gaps.append((book.since_ns, max(t1_ns, book.since_ns)))
    return [(max(a, t0_ns), min(b, t1_ns)) for a, b in gaps
            if min(b, t1_ns) > max(a, t0_ns)]


def book_stats() -> dict:
    """``_nodes/stats.device``: cumulative since the process (or the
    book) began; two reads give a window."""
    book = _book
    now = time.monotonic_ns()
    with book.lock:
        tail = max(now - book.since_ns, 0)
        starved = book.starved_ns + (tail if book.in_flight == 0 else 0)
        flying = book.in_flight_ns + (tail if book.in_flight else 0)
        doc = {"launches": book.launches,
               "launches_in_flight": book.in_flight,
               "launches_without_drain": book.without_drain,
               "observed_ns": max(now, book.since_ns) - book.born_ns,
               "starved_ns": starved, "in_flight_ns": flying}
    total = starved + flying
    doc["starved_pct"] = round(100.0 * starved / total, 3) if total else 0.0
    return doc


# ---------------------------------------------------------------------------
# context management
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def trace(trace_id: str, node_id: str):
    """Root a new trace on this thread (the coordinator's entry)."""
    prev = _tls.ctx
    _tls.ctx = TraceContext(str(trace_id), None, str(node_id))
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


@contextlib.contextmanager
def adopt(header: dict | None, node_id: str):
    """Re-install a wire-carried context around handler dispatch; spans
    record on the RECEIVING node's store. No-op when the request carried
    no trace header."""
    if not isinstance(header, dict) or "id" not in header:
        yield None
        return
    prev = _tls.ctx
    _tls.ctx = TraceContext(str(header["id"]), header.get("parent"),
                            str(node_id))
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


def wire_header() -> dict | None:
    """The current context as an RPC header value, or None when off."""
    ctx = _tls.ctx
    if ctx is None:
        return None
    return {"id": ctx.trace_id, "parent": ctx.parent_span_id}


def bind_context(fn):
    """Capture this thread's observability context (request id and
    innermost open ring span, trace ctx, span collectors, profile sink,
    node override, attribution record) so ``fn`` runs under it on
    another thread — composed into ``tasks.bind_current`` so every
    existing submit seam carries it."""
    from elasticsearch_tpu.observability import costs as _costs
    rid = _tls.rid
    open_seq = _tls.open_seq
    ctx = _tls.ctx
    colls = list(_tls.collectors or ())
    sink = _tls.sink
    override = _current_override()
    attr = attribution.current()
    prog_colls = _costs.current_collectors()
    if not rid and ctx is None and not colls and sink is None \
            and override is None and attr is None and prog_colls is None:
        return fn

    def bound(*args, **kwargs):
        prev_rid = _tls.rid
        prev_seq = _tls.open_seq
        _tls.rid, _tls.open_seq = rid, open_seq
        prev_ctx = _tls.ctx
        prev_colls = _tls.collectors
        prev_sink = _tls.sink
        prev_attr = attribution._install(attr)
        prev_prog = _costs.install_collectors(prog_colls)
        _tls.ctx = ctx
        _tls.collectors = colls
        _tls.sink = sink
        try:
            if override is not None:
                with use_node(override):
                    return fn(*args, **kwargs)
            return fn(*args, **kwargs)
        finally:
            _tls.rid, _tls.open_seq = prev_rid, prev_seq
            _tls.ctx = prev_ctx
            _tls.collectors = prev_colls
            _tls.sink = prev_sink
            attribution._install(prev_attr)
            _costs.install_collectors(prev_prog)

    return bound


@contextlib.contextmanager
def collect_spans():
    """Collect the span records finished under this scope (innermost
    collector wins — nested scopes don't duplicate into outer ones).
    Yields the list, filled as spans close."""
    out: list = []
    stack = _tls.collectors
    if stack is None:
        stack = _tls.collectors = []
    stack.append(out)
    try:
        yield out
    finally:
        if out in stack:
            stack.remove(out)


@contextlib.contextmanager
def profile_sink():
    """Per-request landing zone for shard profile payloads: the
    coordinator pops ``_profile`` blocks off shard responses wherever
    they surface (fan-out loop, fetch round) and sinks them here for the
    response's ``profile.shards`` section."""
    prev = _tls.sink
    _tls.sink = out = []
    try:
        yield out
    finally:
        _tls.sink = prev


def sink_shard_profile(entry: dict) -> None:
    sink = _tls.sink
    if sink is not None and entry is not None:
        sink.append(entry)


# ---------------------------------------------------------------------------
# tree assembly
# ---------------------------------------------------------------------------

def build_tree(spans: list) -> list:
    """Nest flat span records into trees by parent link: children sort
    by start time under a ``children`` key; spans whose parent is not in
    the set (the coordinator root, or an orphan fragment) become roots.
    Input records are not mutated."""
    by_id = {}
    for rec in spans:
        node = dict(rec)
        node["children"] = []
        by_id[node["span_id"]] = node
    roots = []
    for node in by_id.values():
        parent = by_id.get(node["parent_id"]) \
            if node["parent_id"] is not None else None
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=lambda n: n["start_us"])
    roots.sort(key=lambda n: n["start_us"])
    return roots
