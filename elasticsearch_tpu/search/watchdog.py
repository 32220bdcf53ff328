"""Dispatch watchdog — stall detection for device waits (the hang
half of the fault model; the raise half is the PlaneBreaker's).

A device dispatch that simply *hangs* (wedged XLA program, stuck H2D
transfer, runaway compile) raises nothing: no breaker trips, and every
thread blocked on it is wedged too. This module makes the hang
observable and bounded. Every device wait on the scheduler's workers
registers here — (site, lane, shape_key, n_real, trace/task ids,
monotonic start) — and a monitor thread compares each wait's age
against its **predicted envelope**: ``costs.estimate(lane, shape_key)``
(the PR 15 cost observatory) × ``stall_multiplier``, bounded to
[``floor_s``, ``ceiling_s``]; a shape the cost table has never seen
gets the larger ``cold_floor_s`` (a cold shape's first wait includes
host planning, uploads and first-dispatch set-up).

A trace+compile is host work, not a device wait, and at real widths it
takes tens of seconds (20.7 s for one 2^20-row ``segment`` program on a
v5e host, PR 24): the compile seam brackets it with :meth:`DispatchWatchdog.
compiling`, which stops the clock of the waits the compiling thread
registered. A compile that itself outlasts ``ceiling_s`` — the longest
envelope any wait can get — is still a stall.

The escalation ladder, per overdue wait:

1. a ``dispatch-stall`` flight-recorder event (joinable back to the
   request's trace/task ids);
2. the *wait* is abandoned via the registrant's ``on_stall`` callback
   with a typed :class:`~elasticsearch_tpu.search.jit_exec.
   DeviceStallError`. HONESTY: Python cannot cancel a wedged XLA
   dispatch — the program may still own the device; the wedged worker
   thread is left to finish (or not) while its waiters fail over;
3. the error feeds :func:`~elasticsearch_tpu.search.jit_exec.
   note_device_error` → the PlaneBreaker counts it toward a trip, and
   the request fails over with registered reason ``device-stall``;
4. after ``quarantine_stalls`` CONSECUTIVE stalls: **quarantine** — the
   breaker is held open unconditionally (no half-open probe on live
   traffic) and reopen is gated on a tiny background *probe program*
   (:func:`~elasticsearch_tpu.search.jit_exec.run_probe_program`,
   routed through the same fault seam as live traffic) completing.

Like the PlaneBreaker, the module singleton :data:`dispatch_watchdog`
IS the per-node watchdog: all in-process nodes share one device (one
node = one process = one device in deployment); ``search.watchdog.*``
node settings configure it via :func:`settings_for`.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from elasticsearch_tpu.observability.context import current_node_id


class WaitEntry:
    """One registered device wait. Identity object — state transitions
    (completed/abandoned) are guarded by the watchdog's lock."""

    __slots__ = ("site", "lane", "shape_key", "n_real", "node_id",
                 "trace_id", "task_id", "started", "budget_s",
                 "on_stall", "stalled", "done", "thread_id",
                 "compile_since")

    def __init__(self, site, lane, shape_key, n_real, node_id,
                 trace_id, task_id, started, budget_s, on_stall):
        self.site = site
        self.lane = lane
        self.shape_key = shape_key
        self.n_real = n_real
        self.node_id = node_id
        self.trace_id = trace_id
        self.task_id = task_id
        self.started = started          # monotonic (perf_counter)
        self.budget_s = budget_s
        self.on_stall = on_stall
        self.stalled = False
        self.done = False
        self.thread_id = threading.get_ident()
        self.compile_since: float | None = None   # clock stopped since


def _context_ids() -> tuple:
    """(trace_id, task_id) of the registering thread, best-effort — the
    join keys the dispatch-stall event carries so a stall on the
    monitor thread still points back at the wedged request."""
    trace_id = task_id = None
    try:
        from elasticsearch_tpu.observability import tracing
        ctx = tracing.current_ctx()
        if ctx is not None:
            trace_id = ctx.trace_id
    except Exception:                   # noqa: BLE001 — best-effort join
        pass
    try:
        from elasticsearch_tpu.tasks import current_task
        task = current_task()
        if task is not None:
            task_id = task.task_id
    except Exception:                   # noqa: BLE001 — best-effort join
        pass
    return trace_id, task_id


class DispatchWatchdog:
    """Per-node stall watchdog over registered device waits (module
    singleton :data:`dispatch_watchdog` — see module docstring)."""

    def __init__(self, enabled: bool = True,
                 stall_multiplier: float = 20.0,
                 floor_s: float = 10.0, cold_floor_s: float = 30.0,
                 ceiling_s: float = 120.0, quarantine_stalls: int = 3,
                 tick_s: float = 0.05, probe_interval_s: float = 0.5,
                 probe_budget_s: float = 30.0):
        self._lock = threading.Lock()
        self.enabled = bool(enabled)
        self.stall_multiplier = float(stall_multiplier)
        self.floor_s = float(floor_s)
        self.cold_floor_s = float(cold_floor_s)
        self.ceiling_s = float(ceiling_s)
        self.quarantine_stalls = max(int(quarantine_stalls), 1)
        self.tick_s = float(tick_s)
        self.probe_interval_s = float(probe_interval_s)
        self.probe_budget_s = float(probe_budget_s)
        self._entries: list[WaitEntry] = []
        self._consecutive_stalls = 0
        self._monitor: threading.Thread | None = None
        self._probe: threading.Thread | None = None
        self._probe_started = 0.0
        self._probe_outcome: list = []
        self._next_probe_at = 0.0
        # local tallies (the jit_exec counters are the exported truth;
        # these feed _nodes/stats.watchdog per instance)
        self.stalls = 0
        self.abandoned = 0
        self.quarantines = 0
        self.probe_reopens = 0
        self.probes_attempted = 0

    # ---- configuration -----------------------------------------------------

    def configure(self, *, enabled=None, stall_multiplier=None,
                  floor_s=None, cold_floor_s=None, ceiling_s=None,
                  quarantine_stalls=None, tick_s=None,
                  probe_interval_s=None, probe_budget_s=None) -> None:
        """Apply node settings (None leaves a knob unchanged)."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if stall_multiplier is not None:
                self.stall_multiplier = float(stall_multiplier)
            if floor_s is not None:
                self.floor_s = float(floor_s)
            if cold_floor_s is not None:
                self.cold_floor_s = float(cold_floor_s)
            if ceiling_s is not None:
                self.ceiling_s = float(ceiling_s)
            if quarantine_stalls is not None:
                self.quarantine_stalls = max(int(quarantine_stalls), 1)
            if tick_s is not None:
                self.tick_s = float(tick_s)
            if probe_interval_s is not None:
                self.probe_interval_s = float(probe_interval_s)
            if probe_budget_s is not None:
                self.probe_budget_s = float(probe_budget_s)

    def budget_s(self, lane: str | None, shape_key=None) -> float:
        """The stall envelope for one wait: the cost observatory's
        estimate × the multiplier, floor/ceiling-bounded; a shape with
        no estimate gets the cold floor (its first wait includes
        uploads and first-dispatch set-up; the trace+compile itself is
        excluded by :meth:`compiling`)."""
        est_us = None
        if lane is not None:
            try:
                from elasticsearch_tpu.observability import costs
                est_us = costs.estimate(lane, shape_key)
            except Exception:           # noqa: BLE001 — never block dispatch
                est_us = None
        if est_us is None:
            return max(self.cold_floor_s, self.floor_s)
        budget = (float(est_us) / 1e6) * self.stall_multiplier
        return min(max(budget, self.floor_s), self.ceiling_s)

    # ---- registration ------------------------------------------------------

    def register(self, site: str, lane: str | None = None,
                 shape_key=None, n_real: int = 0,
                 on_stall=None) -> WaitEntry | None:
        """Register one device wait starting NOW → its entry (None when
        the watchdog is disabled). ``on_stall(err)`` runs on the monitor
        thread when the wait outlives its envelope — it must abandon the
        wait's *bookkeeping* (resolve waiters, release slots), never try
        to interrupt the wedged thread."""
        if not self.enabled:
            return None
        trace_id, task_id = _context_ids()
        entry = WaitEntry(site, lane, shape_key, int(n_real),
                          current_node_id(), trace_id, task_id,
                          time.perf_counter(),
                          self.budget_s(lane, shape_key), on_stall)
        with self._lock:
            self._entries.append(entry)
            self._ensure_monitor_locked()
        return entry

    def complete(self, entry: WaitEntry | None) -> bool:
        """The wait finished: deregister → True, or False when the
        monitor already abandoned it (the caller's results belong to a
        failed-over request — discard, don't deliver)."""
        if entry is None:
            return True
        with self._lock:
            entry.done = True
            try:
                self._entries.remove(entry)
            except ValueError:
                pass
            if entry.stalled:
                return False
            self._consecutive_stalls = 0
            return True

    @contextmanager
    def compiling(self):
        """Bracket one trace+compile on the calling thread: host work,
        not a device wait. The waits this thread registered stop their
        clock for the duration (``started`` moves forward by the time
        spent compiling), so a long cold compile is never counted as a
        device stall; while compiling, a wait is overdue only when the
        compile alone outlasts ``ceiling_s`` (a runaway compile)."""
        tid = threading.get_ident()
        t0 = time.perf_counter()
        with self._lock:
            mine = [e for e in self._entries
                    if e.thread_id == tid and e.compile_since is None]
            for entry in mine:
                entry.compile_since = t0
        try:
            yield
        finally:
            spent = time.perf_counter() - t0
            with self._lock:
                for entry in mine:
                    entry.compile_since = None
                    entry.started += spent

    # ---- monitor -----------------------------------------------------------

    def _ensure_monitor_locked(self) -> None:
        if self._monitor is None or not self._monitor.is_alive():
            t = threading.Thread(target=self._monitor_loop, daemon=True,
                                 name="dispatch-watchdog")
            self._monitor = t
            t.start()

    def _monitor_loop(self) -> None:
        while True:
            time.sleep(self.tick_s)
            try:
                self._tick()
            except Exception:           # noqa: BLE001 — the watchdog must
                pass                    # outlive any telemetry error

    def _tick(self) -> None:
        from elasticsearch_tpu.search import jit_exec
        now = time.perf_counter()
        overdue: list[WaitEntry] = []
        quarantine = False
        with self._lock:
            for entry in self._entries:
                if entry.stalled or entry.done:
                    continue
                if (now - entry.compile_since > self.ceiling_s
                        if entry.compile_since is not None
                        else now - entry.started > entry.budget_s):
                    entry.stalled = True
                    overdue.append(entry)
            if overdue:
                self._entries = [e for e in self._entries
                                 if not e.stalled]
                self._consecutive_stalls += len(overdue)
                self.stalls += len(overdue)
                self.abandoned += len(overdue)
                if self._consecutive_stalls >= self.quarantine_stalls \
                        and not jit_exec.plane_breaker.quarantined:
                    quarantine = True
                    self.quarantines += 1
        for entry in overdue:
            self._escalate(entry, now)
        if quarantine:
            self._enter_quarantine()
        # probing the process-global breaker is the SINGLETON's job
        # alone: a secondary instance (tests build them) must never
        # race its own probe/reopen against the per-node watchdog's
        if jit_exec.plane_breaker.quarantined and \
                globals().get("dispatch_watchdog") is self:
            self._probe_step(now)

    def _escalate(self, entry: WaitEntry, now: float) -> None:
        """Rungs 1-3 of the ladder for one overdue wait: flight-record,
        abandon via ``on_stall``, feed the breaker."""
        from elasticsearch_tpu.observability import flightrec
        from elasticsearch_tpu.search import jit_exec
        waited = now - entry.started
        err = jit_exec.DeviceStallError(
            f"device wait stalled at site [{entry.site}] lane "
            f"[{entry.lane}]: {waited:.3f}s exceeds the "
            f"{entry.budget_s:.3f}s envelope; wait abandoned (the "
            f"program may still own the device)")
        attrs = {"site": entry.site, "lane": entry.lane,
                 "n_real": entry.n_real,
                 "wait_seconds": round(waited, 3),
                 "budget_seconds": round(entry.budget_s, 3)}
        if entry.shape_key is not None:
            attrs["shape_key"] = str(entry.shape_key)[:120]
        if entry.trace_id is not None:
            attrs["trace_id"] = entry.trace_id
        if entry.task_id is not None:
            attrs["task_id"] = entry.task_id
        flightrec.note("dispatch-stall", node_id=entry.node_id or "",
                       **attrs)
        jit_exec.note_watchdog_stall()
        jit_exec.note_device_error(err)
        jit_exec.note_watchdog_abandoned()
        if entry.on_stall is not None:
            try:
                entry.on_stall(err)
            except Exception:           # noqa: BLE001 — an abandon-callback
                pass                    # bug must not kill the monitor

    # ---- quarantine + probe ------------------------------------------------

    def _enter_quarantine(self) -> None:
        from elasticsearch_tpu.observability import flightrec
        from elasticsearch_tpu.search import jit_exec
        jit_exec.plane_breaker.quarantine()
        jit_exec.note_watchdog_quarantine()
        flightrec.note("quarantine", phase="enter",
                       consecutive_stalls=self._consecutive_stalls,
                       threshold=self.quarantine_stalls)
        with self._lock:
            self._next_probe_at = 0.0   # probe immediately
            # a stale outcome from an earlier quarantine round must not
            # satisfy this one — only a FRESH probe completion reopens
            # (a still-wedged old probe thread appends to its own list)
            self._probe_outcome = []

    def _probe_step(self, now: float) -> None:
        """One monitor-tick of the probe loop: keep at most ONE probe
        outstanding (a wedged probe thread is left to finish — spawning
        more would stack wedged threads), and on a completed successful
        probe release the quarantine."""
        from elasticsearch_tpu.observability import flightrec
        from elasticsearch_tpu.search import jit_exec
        with self._lock:
            probe = self._probe
            if probe is not None and probe.is_alive() and \
                    now - self._probe_started <= self.probe_budget_s:
                return                  # outstanding, within its budget
            # a probe alive past probe_budget_s is itself wedged: give
            # up WAITING on it (the thread is left to finish or not —
            # same honesty as every abandon) and allow a fresh one; the
            # old thread appends to its own superseded outcome list, so
            # a late completion cannot satisfy a newer round
            outcome = self._probe_outcome
            if outcome and outcome[0] == "ok":
                self._probe = None
                self._probe_outcome = []
                self._consecutive_stalls = 0
                self.probe_reopens += 1
                reopen = True
            else:
                reopen = False
                if now < self._next_probe_at:
                    return
                self._next_probe_at = now + self.probe_interval_s
                self._probe_outcome = outcome = []

                def _run_probe(out=outcome):
                    try:
                        jit_exec.run_probe_program()
                        out.append("ok")
                    except Exception:   # noqa: BLE001 — a failed probe
                        out.append("error")   # just keeps quarantine

                t = threading.Thread(target=_run_probe, daemon=True,
                                     name="watchdog-probe")
                self._probe = t
                self._probe_started = now
                self.probes_attempted += 1
        if reopen:
            jit_exec.plane_breaker.release_quarantine()
            jit_exec.note_watchdog_probe_reopen()
            flightrec.note("quarantine", phase="probe-reopen",
                           probes_attempted=self.probes_attempted)
            return
        t.start()

    # ---- stats -------------------------------------------------------------

    def stats(self) -> dict:
        """The ``_nodes/stats.watchdog`` document: live in-flight waits
        (with the oldest wait's age — the liveness gauge OpenMetrics
        exports), the escalation tallies, and the envelope config."""
        from elasticsearch_tpu.search import jit_exec
        now = time.perf_counter()
        with self._lock:
            ages = [now - e.started for e in self._entries
                    if not e.done and not e.stalled]
            return {
                "enabled": self.enabled,
                "in_flight_waits": len(ages),
                "oldest_wait_age_seconds":
                    round(max(ages), 3) if ages else 0.0,
                "stalls": self.stalls,
                "abandoned": self.abandoned,
                "consecutive_stalls": self._consecutive_stalls,
                "quarantines": self.quarantines,
                "quarantined": jit_exec.plane_breaker.quarantined,
                "probes_attempted": self.probes_attempted,
                "probe_reopens": self.probe_reopens,
                "stall_multiplier": self.stall_multiplier,
                "floor_seconds": self.floor_s,
                "cold_floor_seconds": self.cold_floor_s,
                "ceiling_seconds": self.ceiling_s,
                "quarantine_stalls": self.quarantine_stalls,
            }

    def reset(self) -> None:
        """Drop all registered waits and tallies (tests)."""
        with self._lock:
            self._entries = []
            self._consecutive_stalls = 0
            self._probe_outcome = []
            self._next_probe_at = 0.0
            self.stalls = 0
            self.abandoned = 0
            self.quarantines = 0
            self.probe_reopens = 0
            self.probes_attempted = 0


#: THE per-node dispatch watchdog (module singleton — one process =
#: one device = one plane breaker = one watchdog; see module docstring)
dispatch_watchdog = DispatchWatchdog()


def settings_for(get) -> dict:
    """``configure()`` kwargs from node settings (``get`` is
    ``settings.get``-shaped): ``search.watchdog.{enabled,multiplier,
    floor_ms,cold_floor_ms,ceiling_ms,quarantine_stalls,
    probe_interval_ms,probe_budget_ms}``."""
    def _flag(key, default):
        val = get(key)
        return default if val is None \
            else str(val).lower() not in ("false", "0")
    out: dict = {"enabled": _flag("search.watchdog.enabled", True)}
    mult = get("search.watchdog.multiplier")
    if mult is not None:
        out["stall_multiplier"] = float(mult)
    for key, kwarg in (("search.watchdog.floor_ms", "floor_s"),
                       ("search.watchdog.cold_floor_ms", "cold_floor_s"),
                       ("search.watchdog.ceiling_ms", "ceiling_s"),
                       ("search.watchdog.probe_interval_ms",
                        "probe_interval_s"),
                       ("search.watchdog.probe_budget_ms",
                        "probe_budget_s")):
        val = get(key)
        if val is not None:
            out[kwarg] = float(val) / 1e3
    stalls = get("search.watchdog.quarantine_stalls")
    if stalls is not None:
        out["quarantine_stalls"] = int(stalls)
    return out
