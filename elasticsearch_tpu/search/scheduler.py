"""Continuous-batching device scheduler — the live serving path's
device feeder (ROADMAP item 6, the LLM-serving playbook).

The admission model before this module was drain-then-refill: a formed
micro-batch dispatched, every waiter blocked for its drain, and only
then did the next batch start forming — between dispatches the device
idled for a full host round trip. Iteration-level scheduling
(Orca/vLLM) inverts it: the device never waits for a batch to *form* —
it is fed what accumulated while it was busy.

Mechanics, per node:

* requests join per-lane, shape-bucketed queues — ``plane`` / ``impact``
  / ``knn`` / ``percolate``, keyed by the same pow2 buckets the program
  caches use, so every formed batch is admissible to ONE compiled
  program by construction;
* one dispatcher thread forms the batches and a worker thread per batch
  launches and drains it: while batch N computes on-device, batch N+1
  is host-packed and launched (``query_phase_batch_launch`` is async —
  JAX dispatch returns before the device finishes). An idle device
  serves a lone request at once (no formation deadline). A busy one is
  fed just deep enough: the device runs programs one after another, so
  a batch launched behind others only stands in line there, closed to
  the rows that arrive meanwhile. Launching whenever one of
  ``max_in_flight`` (4) places was free made 16 closed-loop kNN clients
  cycle in five groups that never mixed — 16 / 5 = 3.2 rows a batch
  (3.17 measured), a 9.66 GB column read once per 3 queries, 236.7
  queries/s on a chip 99% busy (ledger, PR 28). So a batch that is not
  full is HELD while the staged depth of batches is launched and
  undrained (``_await_batch_locked``): one running plus as many staged
  as a launch takes in device times, both measured here
  (``_staged_depth_locked``; two where a launch is quicker than the
  program before it). On that cell a launch takes 14-19 ms of a
  contended interpreter against 13.4 ms on the device, the depth
  settles at three, the clients cycle in four groups and the same chip
  answers 277-284 queries/s at 3.8-3.9 rows a batch (chip runs of
  PR 29, ``PERF.md`` section 5; the ledger's PR 29 lines have the
  driver's);
* pickup across queues is weighted-fair (WRR over lanes, FIFO within a
  lane, oldest-head queue first): a low-rate percolate client is never
  starved by a query storm;
* load shedding: a waiter whose task deadline (PR 2) is already blown
  at pickup — or that out-waited ``max_queue_wait_s`` — is shed back to
  the caller's serial path (which owns the timed_out/cancel semantics)
  instead of being dispatched into a blown deadline; and when the
  ``queue_wait`` SLO burn rate (PR 13) exceeds the shed threshold, the
  scheduler sheds lowest-priority lanes first at admission with a typed
  429-shaped :class:`SchedulerRejectedError`. An open plane breaker
  (PR 6) is checked by the CALLER before submit — the scheduler never
  queues toward a device the breaker already declared unhealthy.

Results are bit-identical to the unscheduled path: batches execute the
same ``query_phase_batch_launch``/``_drain`` programs the msearch path
uses (fuzz-pinned in tests/test_scheduler.py). Counters live in the
lane registry (``lanes.JIT_COUNTERS`` ``scheduler_*`` keys, bumped via
``jit_exec.note_scheduler_*``) and shed reasons in
``lanes.LANE_REASONS["scheduler"]`` — the PR 12 counter-discipline and
fallback-taxonomy rules police the scheduler by construction. The
hold's own counters are in :meth:`ContinuousBatchScheduler.stats`
(``_nodes/stats.scheduler``): ``batches_held``, ``hold_ms``, ``pace``.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutTimeout
from contextlib import nullcontext

from elasticsearch_tpu.common.threadpool import EsRejectedExecutionError
from elasticsearch_tpu.search.batching import pow2_bucket, term_bucket
from elasticsearch_tpu.search.query_dsl import FunctionScoreQuery


class SchedulerRejectedError(EsRejectedExecutionError):
    """Typed 429-shaped admission rejection: the scheduler refused to
    queue this request (SLO-burn shedding / queue capacity) — retry
    later or on another node, the work was never started."""

    status = 429

    def __init__(self, lane: str, reason: str, message: str):
        super().__init__(message)
        self.lane = lane
        self.reason = reason


#: internal sentinel a waiter resolves to when the scheduler declines
#: the request (ineligible batch, launch fallback, shutdown) — the
#: caller runs its serial path; never surfaced to users
DECLINED = object()

#: WRR pickup weights (turns per cycle) — fairness, not priority:
#: every lane with queued work gets picked every cycle
DEFAULT_WEIGHTS = {"plane": 4, "impact": 3, "knn": 2, "percolate": 1}

#: shed order under SLO burn: LOWEST priority sheds first (level 1
#: sheds priority ≤ 1, level 2 ≤ 2, level 3 everything)
DEFAULT_PRIORITIES = {"plane": 3, "impact": 2, "knn": 2, "percolate": 1}

#: launched-and-undrained batches that keep the device fed where a
#: launch is quicker than the program it is staged behind: one runs, one
#: is staged. From the depth on (``_staged_depth_locked`` deepens it by
#: what it measures) a batch leaves the queue only full.
STAGED_DEPTH = 2

#: weight of a new sample in a lane's launch / device time means
PACE_ALPHA = 0.125

#: minimum queue_wait samples in a shed window before the burn signal
#: is trusted (a single slow wakeup must not open the shed gate)
SHED_MIN_SAMPLES = 16


def _invoke(span_name, fn, *args, **kwargs):
    """The invoker ``bind_context`` wraps: runs a batch's launch or
    drain under its ``scheduler.*`` span — and, for a single-waiter
    batch, under the submitting thread's observability context."""
    from elasticsearch_tpu.observability import tracing as obs_trace
    with obs_trace.span(span_name):
        return fn(*args, **kwargs)


def query_shape(q_node) -> tuple:
    """Structural fingerprint of a query AST — type, field, operand
    COUNTS (term/value counts change the compiled plan), recursed into
    sub-queries. An approximation of jit_exec's plan signature good
    enough for queue grouping: over-splitting costs nothing (smaller
    batches), under-splitting only a declined batch → serial fallback,
    never a wrong result."""
    parts: list = [type(q_node).__name__,
                   getattr(q_node, "field", None)]
    text = getattr(q_node, "text", None)
    if isinstance(text, str):
        # the compiled plans pad a match's term lists to a term bucket
        # (execute._res_MatchQuery), so the fingerprint buckets the
        # same way — "a b c" and "x y z w" share a program, "a b" meets
        # them only in a batch that holds a longer query (the batch
        # pads to its widest bucket)
        parts.append(term_bucket(max(len(text.split()), 1)))
    values = getattr(q_node, "values", None)
    if isinstance(values, (list, tuple)):
        parts.append(pow2_bucket(max(len(values), 1)))
    msm = getattr(q_node, "minimum_should_match", None)
    if msm is not None:
        parts.append(msm)
    for attr in ("must", "should", "must_not", "filter"):
        subs = getattr(q_node, attr, None)
        if isinstance(subs, (list, tuple)) and subs:
            parts.append((attr, tuple(query_shape(s) for s in subs)))
    for attr in ("query", "positive", "negative"):
        sub = getattr(q_node, attr, None)
        if sub is not None and hasattr(sub, "__dataclass_fields__"):
            parts.append((attr, query_shape(sub)))
    if isinstance(q_node, FunctionScoreQuery):
        parts.append(("functions", q_node.functions_shape(),
                      tuple(query_shape(fn.filter_query)
                            for fn in q_node.functions
                            if fn.filter_query is not None)))
    return tuple(parts)


def _with_geometry(shape):
    """Append the serving mesh's geometry to a shape bucket. Programs
    compiled for different pod slices (or for single-chip vs mesh
    serving) are distinct executables, so requests classified under
    different geometries must never share a queue — one compile per
    (shape, geometry), not a decline-then-recompile churn when the
    serving mesh changes."""
    from elasticsearch_tpu.search import jit_exec
    mesh = jit_exec.serving_mesh()
    if mesh is None:
        return shape
    return shape + (("mesh-geometry",) + jit_exec.mesh_geom(mesh),)


def classify(req, searcher):
    """→ ``(lane, shape key)`` for a request the batched programs can
    serve, ``(None, None)`` otherwise (caller stays serial). The shape
    key mirrors the program caches' pow2 bucketing plus the query's
    structural fingerprint, so one queue's requests share a compiled
    plan family — a formed batch rarely declines on mixed shapes.
    When a serving mesh is installed the bucket also carries the mesh
    geometry (see :func:`_with_geometry`)."""
    from elasticsearch_tpu.search import jit_exec
    from elasticsearch_tpu.search.phase import _is_score_order
    if searcher.ctx.dfs_stats is not None:
        return None, None               # global-idf scoring: serial path
    if req.knn is not None:
        kn = req.knn
        qdims = len(kn.query_vector[0]) if kn.multi \
            else len(kn.query_vector)
        shape = (kn.field, bool(kn.hybrid), bool(kn.multi),
                 kn.num_candidates, qdims,
                 pow2_bucket(max(req.from_ + req.size, 1)))
        if kn.hybrid:
            shape = shape + (query_shape(req.query),)
        if kn.filter is not None:
            # the filter mask resolves IN-PROGRAM (the fused lane's
            # filter machinery) but its structure is part of the
            # compiled plan — fingerprint it so filtered and
            # unfiltered knn never share a queue and mixed-filter
            # batches don't decline at launch
            shape = shape + (("filter", query_shape(kn.filter)),)
        return "knn", _with_geometry(shape)
    if (req.aggs or not _is_score_order(req.sort)
            or req.post_filter is not None or req.min_score is not None
            or req.search_after is not None or req.suggest
            or req.terminate_after is not None
            or req.timeout_ms is not None):
        return None, None               # the batch programs decline these
    k = pow2_bucket(max(req.from_ + req.size, 1))
    if req.rescore:
        # single-pass rescore over an impact-opted index rides the
        # planner's composed impact→rescore arm — its own
        # "fused-program" bucket (window/score_mode/rescore-query are
        # program-static) so continuous batching keeps one-in-flight
        # semantics for fused plans too
        if len(req.rescore) != 1 or jit_exec.impact_plane_config(
                searcher.ctx.index_name) is None:
            return None, None           # multi-pass / exact-lane rescore
        rs = req.rescore[0]
        return "impact", _with_geometry(
            ("fused-program", k,
             pow2_bucket(max(int(rs.window_size), 1)),
             str(rs.score_mode), query_shape(req.query),
             query_shape(rs.query)))
    lane = "impact" if jit_exec.impact_plane_config(
        searcher.ctx.index_name) is not None else "plane"
    return lane, _with_geometry((k, query_shape(req.query)))


class _Waiter:
    __slots__ = ("req", "future", "enq_t", "deadline", "task", "picked",
                 "queue_ms", "bound_run")

    def __init__(self, req, deadline, task):
        self.req = req
        self.future: Future = Future()
        self.enq_t = time.perf_counter()
        self.deadline = deadline        # monotonic, or None
        self.task = task
        self.picked = threading.Event()
        self.queue_ms = 0.0
        # the submitting thread's observability context (trace ctx,
        # span collectors, attribution record) bound to an invoker:
        # single-waiter batches run launch/drain under it, so a
        # profiled / slow-logged request keeps its device spans and
        # program/device attribution even though the dispatch happens
        # on the scheduler's threads. Multi-waiter batches skip it —
        # one dispatch cannot attribute to N requests (the msearch
        # batching trade, unchanged).
        from elasticsearch_tpu.observability import tracing as obs_trace
        self.bound_run = obs_trace.bind_context(_invoke)


#: caller-side backstop on ``execute()``'s picked/result waits — the
#: watchdog abandon resolves every batch's waiters long before this;
#: the backstop only guards a disabled/dead watchdog (a timed-out
#: caller runs its serial path; the waiter's accounting is untouched)
EXECUTE_BACKSTOP_S = 600.0


class _BatchState:
    """Per-launched-batch abandon/finish state: the scheduler lock
    arbitrates the race between the drain worker finishing and the
    watchdog monitor abandoning, so the batch leaves the in-flight
    window exactly once and a late (post-abandon) completion is
    discarded."""

    __slots__ = ("live", "finished", "abandoned")

    def __init__(self, live):
        self.live = live
        self.finished = False
        self.abandoned = False


class _LaneQueue:
    __slots__ = ("key", "lane", "waiters", "launch", "drain")

    def __init__(self, key, lane, launch, drain):
        self.key = key
        self.lane = lane
        self.waiters: deque = deque()
        # the creating waiter's callables serve every batch this queue
        # forms: the key pins reader identity + shape, so any member's
        # launch is interchangeable
        self.launch = launch
        self.drain = drain


class ContinuousBatchScheduler:
    """Per-node continuous-batching scheduler in front of the compiled
    batch programs. ``execute()`` blocks the calling (search-pool)
    thread until its own result is ready; formation, launch and drain
    ride the scheduler's dispatcher + drain workers."""

    def __init__(self, node_id: str | None = None, max_batch: int = 32,
                 max_in_flight: int = 4, max_queue: int = 1024,
                 max_queue_wait_s: float = 2.0,
                 weights: dict | None = None,
                 priorities: dict | None = None,
                 shed_threshold: float | None = 10.0,
                 enabled: bool = True, pad_to_bucket: bool = True):
        self.node_id = node_id
        self.enabled = enabled
        self.max_batch = max(int(max_batch), 1)
        self.max_in_flight = max(int(max_in_flight), 1)
        self.max_queue = max(int(max_queue), 1)
        self.max_queue_wait_s = float(max_queue_wait_s)
        self.pad_to_bucket = pad_to_bucket
        self.weights = dict(DEFAULT_WEIGHTS, **(weights or {}))
        self.priorities = dict(DEFAULT_PRIORITIES, **(priorities or {}))
        #: queue_wait burn multiple that opens the shed gate (None/<=0
        #: disables SLO shedding)
        self.shed_threshold = None if not shed_threshold \
            or float(shed_threshold) <= 0 else float(shed_threshold)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: dict = {}
        self._wrr: list = []            # lane pickup cycle, weight-expanded
        for lane in sorted(self.weights):
            self._wrr.extend([lane] * max(int(self.weights[lane]), 1))
        self._wrr_pos = 0
        self._dispatcher: threading.Thread | None = None
        self._closed = False
        # counters (all under _lock; stats() snapshots one consistent
        # view so submitted == queued + in_flight + delivered + declined
        # + shed holds at EVERY sample)
        self._submitted = 0
        self._queued = 0
        self._inflight_reqs = 0
        self._delivered = 0
        self._declined = 0
        self._shed = 0
        self._shed_reasons: dict = {}
        self._batches_launched = 0
        self._batches_inflight = 0
        self._batches_drained = 0
        self._batches_abandoned = 0
        self._inflight_hw = 0
        self._pad_rows = 0
        self._batches_held = 0
        self._hold_ms = 0.0
        # lane → [mean launch ms, its mean deviation, mean device ms]
        # of its pipelined batches, and the instant the device last
        # finished one
        self._pace: dict = {}
        self._device_free_t = 0.0
        # SLO-burn shed gate: the scheduler's OWN queue-wait good/bad
        # book (classified against the node's queue_wait SLO target) —
        # the shared queue_wait lane also carries threadpool samples,
        # and the scheduler must shed on ITS queue's burn, not a
        # neighbor's. Recompute throttled to 1/s.
        self._shed_gate_lock = threading.Lock()
        self._shed_level = 0
        self._shed_raw_prev = 0
        self._shed_at = 0.0
        self._slo_prev = (0, 0)
        self._qw_good = 0
        self._qw_bad = 0
        self._qw_target_ms = 50.0       # refreshed from slo config

    # ---- admission ---------------------------------------------------------

    def submit(self, lane: str, key, req, launch, drain=None) -> _Waiter:
        """Admission predicate of the ``scheduler`` lane: every shed and
        decline is reason-labeled here or at pickup
        (``jit_exec.note_scheduler_shed`` ←
        ``lanes.LANE_REASONS["scheduler"]``). Raises
        :class:`SchedulerRejectedError` (429) for SLO-burn and
        queue-capacity sheds; a declined waiter resolves to
        :data:`DECLINED` and the caller runs its serial path."""
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.tasks import current_task
        task = current_task()
        deadline = getattr(task, "deadline", None) if task is not None \
            else None
        w = _Waiter(req, deadline, task)
        if self._closed:
            jit_exec.note_scheduler_shed("closed")
            with self._lock:
                self._submitted += 1
                self._note_shed_locked("closed")
            w.picked.set()
            w.future.set_result(DECLINED)
            return w
        # SLO-burn shedding needs LOAD evidence from this scheduler,
        # not just a hot queue_wait book (the threadpool shares the
        # lane): with an empty queue the next pickup is immediate, so
        # shedding would be pure loss — admission throttling starts
        # only when a backlog exists
        level = self._shed_gate() if self._queued else 0
        if level >= self.priorities.get(lane, 2):
            jit_exec.note_scheduler_shed("slo-shed")
            with self._lock:
                self._submitted += 1
                self._note_shed_locked("slo-shed")
            raise SchedulerRejectedError(
                lane, "slo-shed",
                f"scheduler shed [{lane}] work: queue_wait SLO burn at "
                f"shed level {level} (search.scheduler.shed)")
        full = False
        with self._lock:                    # == the condition's lock
            if self._closed:
                pass                        # raced close(): fall through
            elif self._queued >= self.max_queue:
                full = True
                self._submitted += 1
                self._note_shed_locked("queue-full")
            else:
                q = self._queues.get(key)
                if q is None:
                    q = self._queues[key] = _LaneQueue(key, lane, launch,
                                                       drain)
                q.waiters.append(w)
                self._submitted += 1
                self._queued += 1
                self._ensure_dispatcher_locked()
                self._cond.notify()
                return w
        if full:
            jit_exec.note_scheduler_shed("queue-full")
            raise SchedulerRejectedError(
                lane, "queue-full",
                f"scheduler queue at capacity ({self.max_queue}) — "
                f"[{lane}] request rejected")
        jit_exec.note_scheduler_shed("closed")
        with self._lock:
            self._submitted += 1
            self._note_shed_locked("closed")
        w.picked.set()
        w.future.set_result(DECLINED)
        return w

    def execute(self, lane: str, key, req, launch, drain=None):
        """Blocking entry: queue under a ``scheduler.queue`` span
        (enqueue → pick-up), then wait for the batch's result under a
        ``scheduler.result`` span (the batches ahead, then its own).
        → result, or None when the scheduler declined (caller runs its
        serial path). Raises :class:`SchedulerRejectedError` when shed
        at admission."""
        from elasticsearch_tpu.observability import tracing as obs_trace
        w = self.submit(lane, key, req, launch, drain)
        with obs_trace.span("scheduler.queue", lane=lane) as sp:
            w.picked.wait(EXECUTE_BACKSTOP_S)
            sp.set(queue_ms=round(w.queue_ms, 3))
        try:
            with obs_trace.span("scheduler.result", lane=lane):
                out = w.future.result(timeout=EXECUTE_BACKSTOP_S)
        except FutTimeout:
            # the watchdog should have abandoned this batch long ago;
            # the backstop fails the CALLER over to its serial path
            # without touching the waiter's books (a late delivery
            # still reconciles — the caller just isn't listening)
            return None
        if out is DECLINED:
            return None
        return out

    # ---- dispatcher --------------------------------------------------------

    def _ensure_dispatcher_locked(self) -> None:
        if self._dispatcher is None or not self._dispatcher.is_alive():
            t = threading.Thread(target=self._dispatch_loop, daemon=True,
                                 name="sched-dispatch")
            self._dispatcher = t
            t.start()

    def _dispatch_loop(self) -> None:
        from elasticsearch_tpu.observability import use_node
        ctx = use_node(self.node_id) if self.node_id is not None \
            else nullcontext()
        with ctx:
            try:
                self._dispatch_inner()
            finally:
                self._flush_closed()

    def _dispatch_inner(self) -> None:
        while True:
            with self._cond:
                q, batch = self._await_batch_locked()
            if q is None:
                return                  # closed: the flush declines the rest
            live = self._screen_pickup(batch)
            if live:
                self._launch_batch(q, live)

    def _await_batch_locked(self):
        """Wait until a batch may leave the queue, then form it from
        everything queued by then → ``(queue, batch)``, or ``(None,
        None)`` once closed. One wait serves the three reasons to stay:
        nothing is queued, ``max_in_flight`` batches are launched and
        undrained, or the hold: the picked queue is NOT full while the
        staged depth (:meth:`_staged_depth_locked`) of batches is
        undrained. ``_batches_inflight`` counts every lane's batches
        because the device is one and serial across lanes — one of them
        runs, the others are staged behind it or on their way there, so
        the device has its next program and a further launch would only
        stand in line, closed to the rows that arrive meanwhile. Held
        until a batch finishes or is abandoned, the rows start on the
        device at the same instant and take their later neighbours
        along. ``submit``, ``_finish_batch``, ``_abandon_batch`` and
        ``close`` notify; ``_closed`` is re-checked after every wait."""
        held_at = None
        while not self._closed:
            q, wrr_pos = self._pick_queue_locked()
            if q is not None and self._batches_inflight < self.max_in_flight:
                if self._batches_inflight < self._staged_depth_locked(
                        q.lane) or len(q.waiters) >= self.max_batch:
                    break
                if held_at is None:
                    held_at = time.perf_counter()
                    self._batches_held += 1
            self._cond.wait(0.25)
        if held_at is not None:
            self._hold_ms += (time.perf_counter() - held_at) * 1e3
        if self._closed:
            return None, None
        self._wrr_pos = wrr_pos
        batch = []
        while q.waiters and len(batch) < self.max_batch:
            batch.append(q.waiters.popleft())
        self._queued -= len(batch)
        self._inflight_reqs += len(batch)
        if not q.waiters:
            self._queues.pop(q.key, None)
        return q, batch

    def _staged_depth_locked(self, lane: str) -> int:
        """Undrained batches from which on a non-full batch is held. A
        batch formed when one drains has to be ON the device's queue
        before the batches ahead of it have run, so the depth is one
        running plus as many staged as a launch (formation → enqueued:
        worker start, plan, ``jit.pack``, ``jit.enqueue``) takes in
        device times — both measured by this scheduler itself
        (:meth:`_note_pace_locked`), never a setting. A launch counts
        at its mean plus one mean deviation, the usual launch and not
        the latest: one that outlasts the staged work costs the device
        the few ms of its excess, a depth more costs EVERY batch its
        share of the rows (a quarter from three to four). Two where a
        launch is quicker than the program before it, or nothing is
        measured yet; ``max_in_flight`` bounds it from above."""
        pace = self._pace.get(lane)
        if pace is None:
            return STAGED_DEPTH
        launch_ms, launch_dev_ms, device_ms = pace
        return min(self.max_in_flight, max(STAGED_DEPTH, 1 + math.ceil(
            (launch_ms + launch_dev_ms) / max(device_ms, 1e-3))))

    def _note_pace_locked(self, lane: str, t_formed: float,
                          t_enqueued: float, t_done: float) -> None:
        """One pipelined batch's two times into its lane's means: the
        launch, formation → enqueued (and its mean deviation), and the
        device's — the device is one FIFO server, so the batch ran from
        its enqueue or from the finish of the batch before it, whichever
        came later, to its own finish (no assumption that the device
        was kept busy)."""
        launch_ms = (t_enqueued - t_formed) * 1e3
        device_ms = (t_done - max(t_enqueued, self._device_free_t)) * 1e3
        pace = self._pace.get(lane)
        if pace is None:
            self._pace[lane] = [launch_ms, 0.0, device_ms]
            return
        pace[1] += PACE_ALPHA * (abs(launch_ms - pace[0]) - pace[1])
        pace[0] += PACE_ALPHA * (launch_ms - pace[0])
        pace[2] += PACE_ALPHA * (device_ms - pace[2])

    def _pick_queue_locked(self):
        """Weighted-fair pickup: cycle lanes by WRR weight, serve the
        chosen lane's oldest-head queue FIFO → ``(queue, the cycle's next
        position)``; the caller commits the position when it takes the
        batch, so a held pick stays the pick. Empty queues are dropped
        (shape keys churn with reader generations)."""
        nonempty: dict = {}
        for key in list(self._queues):
            q = self._queues[key]
            if not q.waiters:
                del self._queues[key]
                continue
            nonempty.setdefault(q.lane, []).append(q)
        if not nonempty:
            return None, self._wrr_pos
        chosen, wrr_pos = None, self._wrr_pos
        for step in range(len(self._wrr)):
            lane = self._wrr[(self._wrr_pos + step) % len(self._wrr)]
            if lane in nonempty:
                wrr_pos = (self._wrr_pos + step + 1) % len(self._wrr)
                chosen = nonempty[lane]
                break
        if chosen is None:                # lanes outside the WRR table
            chosen = next(iter(nonempty.values()))
        return min(chosen, key=lambda c: c.waiters[0].enq_t), wrr_pos

    def _screen_pickup(self, batch: list) -> list:
        """Queue-time shedding at pickup: a cancelled task aborts (PR 2
        semantics), a blown deadline — the task's or the scheduler's own
        ``max_queue_wait_s`` bound — is shed back to the serial path,
        which owns the timed_out accounting. Returns the live waiters."""
        from elasticsearch_tpu.common.errors import TaskCancelledError
        from elasticsearch_tpu.search import jit_exec
        now_m = time.monotonic()
        now_p = time.perf_counter()
        # the watchdog quarantined the device: redirect the whole
        # pickup to the serial path instead of launching into a known
        # wedge (new arrivals stop at the caller's breaker check; this
        # drains what queued before the quarantine)
        quarantined = jit_exec.plane_breaker.quarantined
        live = []
        for w in batch:
            if quarantined:
                jit_exec.note_scheduler_shed("device-stall")
                with self._lock:
                    self._inflight_reqs -= 1
                    self._note_shed_locked("device-stall")
                w.picked.set()
                w.future.set_result(DECLINED)
                continue
            if w.task is not None and w.task.cancelled:
                jit_exec.note_scheduler_shed("task-cancelled")
                with self._lock:
                    self._inflight_reqs -= 1
                    self._note_shed_locked("task-cancelled")
                w.picked.set()
                w.future.set_exception(TaskCancelledError(
                    f"task [{w.task.task_id}] was cancelled while "
                    f"queued [{w.task.cancel_reason or 'unknown'}]"))
                continue
            blown = (w.deadline is not None and now_m > w.deadline) or \
                (now_p - w.enq_t > self.max_queue_wait_s)
            if blown:
                jit_exec.note_scheduler_shed("queue-deadline")
                with self._lock:
                    self._inflight_reqs -= 1
                    self._note_shed_locked("queue-deadline")
                w.picked.set()
                w.future.set_result(DECLINED)
                continue
            live.append(w)
        return live

    def _launch_batch(self, q: _LaneQueue, live: list) -> None:
        """Commit one formed batch to a drain worker. The worker owns
        BOTH launch and drain — a device dispatch can *hang*, and a
        hang on the dispatcher's own thread would wedge the whole
        scheduler; on a worker the watchdog abandons the wait and the
        dispatcher keeps feeding (the stall-tolerance contract). A
        batch counts ``launched`` when committed here and leaves the
        books exactly once: ``drained`` (worker finished — even on a
        launch error, matching the sync lane's accounting) or
        ``abandoned`` (watchdog gave up on the wait)."""
        from elasticsearch_tpu.observability import histograms as obs_hist
        from elasticsearch_tpu.search import jit_exec
        t_pick = time.perf_counter()
        bad = 0
        for w in live:
            w.queue_ms = (t_pick - w.enq_t) * 1e3
            # the node's queue-time lane (and its SLO book) sees every
            # queue on the node, this one and the thread pools'
            obs_hist.observe_lane("queue_wait", w.queue_ms,
                                  self.node_id or "")
            bad += w.queue_ms > self._qw_target_ms
            w.picked.set()
        with self._lock:
            self._qw_good += len(live) - bad
            self._qw_bad += bad
        state = _BatchState(live)
        runner = live[0].bound_run if len(live) == 1 else None
        if runner is _invoke:
            runner = None               # no context was active at submit
        if q.drain is None:
            with self._lock:
                self._batches_launched += 1
                self._batches_inflight += 1
                self._inflight_hw = max(self._inflight_hw,
                                        self._batches_inflight)
            jit_exec.note_scheduler_batch(len(live), 0)
            self._spawn_worker(self._run_sync, q, live, runner, state)
            return
        reqs = [w.req for w in live]
        padded = 0
        if self.pad_to_bucket and len(reqs) < self.max_batch:
            # pad up to the program cache's pow2 bucket with a no-op
            # replica of the FIRST request: pad rows are sliced off
            # before delivery and excluded from lane stats via n_real —
            # never re-serving other queued requests (the old
            # pad_to_bucket wart double-counted them)
            bucket = pow2_bucket(len(reqs), self.max_batch)
            padded = bucket - len(reqs)
            reqs = reqs + [reqs[0]] * padded
        with self._lock:
            self._batches_launched += 1
            self._batches_inflight += 1
            self._pad_rows += padded
            self._inflight_hw = max(self._inflight_hw,
                                    self._batches_inflight)
        jit_exec.note_scheduler_batch(len(live), padded)
        self._spawn_worker(self._run_pipelined, q, live, runner, reqs,
                           state, t_pick)

    def _spawn_worker(self, fn, *args) -> None:
        """One DAEMON worker thread per committed batch. Not a bounded
        pool on purpose: a wedged batch parks its worker on the device
        indefinitely (non-cancellable), and under repeated stalls a
        bounded pool starves — batches queue behind wedged threads and
        never even reach watchdog registration. Concurrency is still
        bounded by ``max_in_flight`` (an abandon takes its batch out of
        ``_batches_inflight``, so live batches, not wedged threads, own
        the window), and daemon threads never block interpreter exit on
        a wedge. Each worker runs under this scheduler's node context so
        compiles, costs, spans and ledger charges attribute to the
        owning node exactly like the dispatcher thread."""
        def run() -> None:
            from elasticsearch_tpu.observability import use_node
            ctx = use_node(self.node_id) if self.node_id is not None \
                else nullcontext()
            with ctx:
                fn(*args)

        threading.Thread(target=run, daemon=True,
                         name="sched-batch").start()

    def _run_sync(self, q: _LaneQueue, live: list, runner,
                  state: _BatchState) -> None:
        """Whole-batch execution for sync (launch-only) lanes, under a
        registered watchdog wait."""
        from elasticsearch_tpu.search import watchdog as wd
        entry = wd.dispatch_watchdog.register(
            site="dispatch", lane=q.lane, shape_key=q.key,
            n_real=len(live),
            on_stall=lambda err: self._abandon_batch(state))
        try:
            reqs = [w.req for w in live]
            results = (runner or _invoke)("scheduler.launch", q.launch,
                                          reqs)
        except Exception:                # noqa: BLE001 — serial retry owns it
            results = None
        wd.dispatch_watchdog.complete(entry)
        self._finish_batch(state, live, results)

    def _run_pipelined(self, q: _LaneQueue, live: list, runner,
                       reqs: list, state: _BatchState,
                       t_formed: float) -> None:
        """Launch + drain for pipelined lanes, on a worker thread: the
        async launch overlaps the previous batch's drain exactly as
        before (the dispatcher keeps forming batches while this worker
        blocks on the device), but a wedged dispatch now wedges only
        THIS worker — the watchdog abandons the wait and its place
        in the window, and the dispatcher never stops."""
        from elasticsearch_tpu.search import watchdog as wd
        entry = wd.dispatch_watchdog.register(
            site="dispatch", lane=q.lane, shape_key=q.key,
            n_real=len(live),
            on_stall=lambda err: self._abandon_batch(state))
        results = t_enqueued = None
        run = runner or _invoke
        try:
            handle = run("scheduler.launch", q.launch, reqs,
                         n_real=len(live))
            if handle is not None:
                t_enqueued = time.perf_counter()
                results = run("scheduler.drain", q.drain, handle)
        except Exception:                # noqa: BLE001 — serial retry owns it
            results = None
        wd.dispatch_watchdog.complete(entry)
        self._finish_batch(state, live, results,
                           None if results is None else
                           (q.lane, t_formed, t_enqueued))

    def _finish_batch(self, state: _BatchState, live: list,
                      results, paced=None) -> None:
        """Worker-side batch completion: exactly one of finish/abandon
        wins under the lock. A late completion of an abandoned batch
        discards its results — the waiters already failed over and the
        abandon path already gave up its place in the window and
        settled the books."""
        from elasticsearch_tpu.search import jit_exec
        with self._lock:
            if state.abandoned:
                return
            state.finished = True
            self._batches_inflight -= 1
            self._batches_drained += 1
            now = time.perf_counter()
            if paced is not None:
                self._note_pace_locked(*paced, now)
            self._device_free_t = now
            self._cond.notify()         # the window, or a hold, opens
        jit_exec.note_scheduler_drain()
        self._deliver(live, results)

    def _abandon_batch(self, state: _BatchState) -> bool:
        """Watchdog-side batch abandonment (runs on the monitor thread;
        the wedged worker still blocks on the device — only the WAIT is
        abandoned): shed every waiter back to its serial path with
        registered reason ``device-stall``, settle the batch books, and
        give up its place in the in-flight window so the dispatcher's
        window never shrinks under a wedge (and a hold ends)."""
        from elasticsearch_tpu.search import jit_exec
        with self._lock:
            if state.finished or state.abandoned:
                return False
            state.abandoned = True
            self._batches_inflight -= 1
            self._batches_abandoned += 1
            self._inflight_reqs -= len(state.live)
            for _ in state.live:
                self._note_shed_locked("device-stall")
            self._cond.notify()         # the window, or a hold, opens
        jit_exec.note_scheduler_shed("device-stall", len(state.live))
        for w in state.live:
            w.picked.set()
            if not w.future.done():
                w.future.set_result(DECLINED)
        return True

    def _deliver(self, live: list, results) -> None:
        if results is None:
            self._deliver_declined(live)
            return
        # the books settle before any waiter wakes: a client that reads
        # the stats after its reply must find its request counted
        with self._lock:
            self._inflight_reqs -= len(live)
            self._delivered += len(live)
        # slice to the REAL waiters: pad rows never deliver (and never
        # counted — note_scheduler_batch took n_real)
        for w, res in zip(live, results):
            if not w.future.done():
                w.future.set_result(res)

    def _deliver_declined(self, live: list) -> None:
        with self._lock:
            self._inflight_reqs -= len(live)
            self._declined += len(live)
        for w in live:
            w.picked.set()
            if not w.future.done():
                w.future.set_result(DECLINED)

    # ---- SLO-burn shed gate ------------------------------------------------

    def _note_shed_locked(self, reason: str) -> None:
        self._shed += 1
        self._shed_reasons[reason] = self._shed_reasons.get(reason, 0) + 1

    def _shed_gate(self) -> int:
        """Current shed level from the windowed ``queue_wait`` SLO burn
        of THIS scheduler's queue (good/bad classified against the
        node's queue_wait target — the PR 13 SLO book the pickup seam
        feeds): 0 below threshold t, 1 at ≥t, 2 at ≥2t, 3 at ≥4t.
        Recomputed at most 1/s so admission pays a dict read."""
        if self.shed_threshold is None:
            return 0
        now = time.monotonic()
        with self._shed_gate_lock:
            if now - self._shed_at < 1.0:
                return self._shed_level
            self._shed_at = now
            from elasticsearch_tpu.observability import slo
            doc = slo.stats(self.node_id or "")
            st = doc["lanes"].get("queue_wait")
            if st is not None:
                self._qw_target_ms = st["target_ms"]
            with self._lock:
                good, bad = self._qw_good, self._qw_bad
            pg, pb = self._slo_prev
            self._slo_prev = (good, bad)
            dg, db = good - pg, bad - pb
            raw = 0
            if dg + db >= SHED_MIN_SAMPLES:
                burn = slo.burn_rate(dg, db, doc["objective"])
                t = self.shed_threshold
                if burn >= t:
                    raw = 1 + (burn >= 2 * t) + (burn >= 4 * t)
            # hysteresis: shed only on SUSTAINED burn — two consecutive
            # windows at the level. A transient spike (a compile burst
            # stalling the dispatcher for one window) must not 429 users
            self._shed_level = min(raw, self._shed_raw_prev)
            self._shed_raw_prev = raw
            return self._shed_level

    # ---- stats / lifecycle -------------------------------------------------

    def stats(self) -> dict:
        """The ``_nodes/stats.scheduler`` document. ``reconciled`` is
        the sample-time invariant the benchmark and chaos scenarios assert:
        every submitted request is exactly one of queued / in-flight /
        delivered / declined / shed, and every launched batch is
        drained or in flight."""
        with self._lock:
            queues = {}
            for q in self._queues.values():
                queues[q.lane] = queues.get(q.lane, 0) + len(q.waiters)
            doc = {
                "enabled": self.enabled,
                "max_batch": self.max_batch,
                "max_in_flight": self.max_in_flight,
                "queue_depth": self._queued,
                "queue_depth_by_lane": queues,
                "submitted": self._submitted,
                "in_flight_requests": self._inflight_reqs,
                "delivered": self._delivered,
                "declined": self._declined,
                "shed": self._shed,
                "shed_reasons": dict(self._shed_reasons),
                "batches_launched": self._batches_launched,
                "batches_in_flight": self._batches_inflight,
                "batches_drained": self._batches_drained,
                "batches_abandoned": self._batches_abandoned,
                "in_flight_high_water": self._inflight_hw,
                "pad_rows": self._pad_rows,
                "batches_held": self._batches_held,
                "hold_ms": round(self._hold_ms, 3),
                "pace": {lane: {
                    "launch_ms": round(pace[0], 3),
                    "launch_dev_ms": round(pace[1], 3),
                    "device_ms": round(pace[2], 3),
                    "staged_depth": self._staged_depth_locked(lane)}
                    for lane, pace in self._pace.items()},
                "reconciled": (
                    self._submitted == self._queued + self._inflight_reqs
                    + self._delivered + self._declined + self._shed
                    and self._batches_launched == self._batches_drained
                    + self._batches_inflight + self._batches_abandoned),
            }
        return doc

    def _flush_closed(self) -> None:
        """Resolve every queued waiter with DECLINED on shutdown — the
        serial path still serves them; nobody hangs on a future the
        dead dispatcher would never complete."""
        from elasticsearch_tpu.search import jit_exec
        with self._lock:
            leftovers = [w for q in self._queues.values()
                         for w in q.waiters]
            for q in self._queues.values():
                q.waiters.clear()
            self._queues.clear()
            self._queued -= len(leftovers)
            for _ in leftovers:
                self._note_shed_locked("closed")
        if leftovers:
            jit_exec.note_scheduler_shed("closed", len(leftovers))
        for w in leftovers:
            w.picked.set()
            if not w.future.done():
                w.future.set_result(DECLINED)

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
            dispatcher = self._dispatcher
        if dispatcher is not None:
            dispatcher.join(timeout=5.0)
        else:
            self._flush_closed()
        # in-flight batch workers are daemon threads that resolve their
        # own waiters (or the watchdog abandons them) — close() never
        # waits on a possibly-wedged device drain


def settings_for(get) -> dict:
    """Constructor kwargs from node settings (``get`` is
    ``settings.get``-shaped): ``search.scheduler.{enabled,max_batch,
    max_in_flight,max_queue,fairness,shed}``. ``fairness`` is a
    ``lane:weight,...`` string overriding the WRR weights; ``shed`` is
    the queue_wait burn multiple that opens the shed gate (default
    10.0 — i.e. ≥10 % of a window's pickups late under the default
    0.99 objective; "off" disables)."""
    def _flag(key, default):
        val = get(key)
        return default if val is None \
            else str(val).lower() not in ("false", "0")
    kwargs = {
        "enabled": _flag("search.scheduler.enabled", True),
        "max_batch": int(get("search.scheduler.max_batch") or 32),
        "max_in_flight": int(get("search.scheduler.max_in_flight") or 4),
        "max_queue": int(get("search.scheduler.max_queue") or 1024),
    }
    raw = get("search.scheduler.fairness")
    if raw:
        weights = {}
        for part in str(raw).split(","):
            lane, _, wt = part.partition(":")
            if lane.strip() and wt.strip():
                weights[lane.strip()] = int(wt)
        if weights:
            kwargs["weights"] = weights
    shed = get("search.scheduler.shed")
    if shed is not None:
        kwargs["shed_threshold"] = None \
            if str(shed).lower() in ("off", "false", "0") else float(shed)
    return kwargs
