"""Compiled query execution: one fused XLA program per (plan, layout).

This delivers the promise in execute.py's docstring — the production query
path analog of QueryPhase's single collector pass (ref:
core/search/query/QueryPhase.java:99-314, `searcher.search(query,
collector)` :314): the whole per-segment walk — scoring, boolean algebra,
function_score, min_score, post_filter, search-after continuation, hit
counting and top-k — runs as ONE jitted program.

Mechanics (see execute.SegmentResolver):

1. **resolve** — host-side "createWeight": dictionary lookups collect every
   dynamic constant (term ids, idf, bounds) into a ConstTable plus a
   structural signature, and produce emit closures of pure jnp ops.
   Microseconds per query — no tracing, no device work.
2. **cache** — key = (signature, segment layout, BM25 params, output
   wants). Hit → the compiled program runs with this query's constants as
   inputs. Queries differing only in terms/values/boosts share a program;
   segments sharing a shape bucket share it too (the bounded-recompilation
   contract of segment.doc_count_bucket).
3. **emit under jit** — the jitted function rebuilds a segment view from
   traced arrays and calls the emit closures with traced constants.
4. **batch** — B same-signature queries stack their constants on a leading
   axis and run under ``jax.vmap`` as one program (run_reader_batch): the
   TPU-native answer to request-at-a-time dispatch.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import OrderedDict
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.index.device_reader import DeviceSegment
from elasticsearch_tpu.observability import attribution as _attribution
from elasticsearch_tpu.observability.context import current_node_id
from elasticsearch_tpu.observability.tracing import device_span, span
from elasticsearch_tpu.ops import blockmax as blockmax_ops
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.search import lanes
from elasticsearch_tpu.search.execute import (
    ConstTable, EmitCtx, ExecutionContext, SegmentResolver,
    match_term_floor)

_CACHE_CAP = 512
_cache: OrderedDict[tuple, "jax.stages.Wrapped"] = OrderedDict()
_cache_lock = threading.Lock()


# ---------------------------------------------------------------------------
# Device-fault seam + plane circuit breaker (accelerator-fault tolerance)
# ---------------------------------------------------------------------------

class DeviceFaultError(RuntimeError):
    """Simulated accelerator error (testing_disruption.DeviceFaultScheme)
    — shaped like the dispatch/upload/compile failures a sick device
    raises, so every fallback seam treats it exactly like the real
    thing."""


class DeviceOomError(DeviceFaultError):
    """Simulated HBM out-of-memory (the XLA RESOURCE_EXHAUSTED shape):
    the one device error with a recovery action cheaper than degrading —
    evict cold device blocks and let the next build retry smaller."""


class DeviceStallError(DeviceFaultError):
    """A device wait outlived its predicted envelope and the watchdog
    abandoned it. HONESTY: Python cannot cancel a wedged XLA dispatch or
    transfer — the underlying program may still own the device; what was
    abandoned is the *wait*, so the caller fails over while the wedged
    thread is left to finish (or not) on its own."""


#: chaos seam: a callable(site: str) that may raise at each device
#: touchpoint — ``dispatch`` (compiled per-segment/reader programs),
#: ``compile`` (program build), ``upload`` (host→device block/column
#: transfer), ``compose`` (device-side pack stacking), ``plane-dispatch``
#: (the collective-plane mesh program), ``percolate`` (fused percolate
#: lanes). None in production — the check is a single attribute read.
_device_fault_hook = None


def set_device_fault_hook(hook):
    """Install (or with None, remove) the device-fault hook → the
    previous hook, so stacked schemes can chain and restore."""
    global _device_fault_hook
    prev = _device_fault_hook
    _device_fault_hook = hook
    return prev


def device_fault_point(site: str) -> None:
    """One device touchpoint: gives the installed chaos hook the chance
    to raise an accelerator-style error here."""
    hook = _device_fault_hook
    if hook is not None:
        hook(site)


def seam_device_put(a, device=None, site: str = "upload"):
    """Host→device transfer through the fault seam: modules outside the
    seam allowlist (device readers, standalone models, the distributed
    data plane) route uploads here instead of calling ``jax.device_put``
    raw, so chaos injection reaches every transfer and the plane breaker
    observes real upload failures (plane-lint rule device-raw-call).

    ``site`` must be a literal site class at the call site (plane-lint
    checks it): ``upload`` for plane/block transfers, ``reader-upload``
    for the RPC fan-out's baseline reader — the serving FLOOR, which the
    default chaos draw leaves alone (see testing_disruption.
    DEVICE_FAULT_SITES) so degraded-mode serving always has a working
    fallback; targeted tests opt in via ``p_by_site``."""
    with device_span(site):
        device_fault_point(site)
        return jax.device_put(a) if device is None \
            else jax.device_put(a, device)


def seam_jit(fn, **kwargs):
    """Program construction through the fault seam. Callers OWN the
    caching — memoize the result per static shape (plane-lint rule
    recompile-request-path checks call sites); the seam only makes the
    compile injectable and breaker-visible."""
    with device_span("compile"):
        device_fault_point("compile")
        return jax.jit(fn, **kwargs)


def observed_compile(lane: str, shape_key, lower_fn, *,
                     owner: str | None = None):
    """THE program-compile seam: every ``.lower(...).compile(...)`` in
    the seam modules flows through here (plane-lint rule family
    ``program-cost-discipline`` holds the tree to it).

    ``lower_fn()`` returns the ``jax.stages.Lowered``; this seam owns
    the ``.compile()`` so it can stamp, per program key (``lane`` ×
    ``shape_key`` — the program cache's own key), the XLA static cost
    analyses and the compile wall time into the per-node
    ProgramCostTable (observability/costs.py). ``lane`` must be a
    string literal from ``lanes.PROGRAM_LANES`` at the call site;
    ``owner`` (an engine incarnation uuid, when the caller knows one)
    lets the table drain the program's row when the engine closes.
    The fault point and the compile span live here too, so chaos
    injection and the tracer see exactly one compile per flow."""
    assert lane in lanes.PROGRAM_LANES, (
        f"unregistered program lane {lane!r} — add it to "
        f"elasticsearch_tpu.search.lanes.PROGRAM_LANES")
    from elasticsearch_tpu.observability import costs
    from elasticsearch_tpu.search.watchdog import dispatch_watchdog
    with device_span("compile") as dsp:
        device_fault_point("compile")
        t0 = time.perf_counter()
        # host work, not a device wait: the registered wait's clock
        # stops here (the fault point above stays inside the wait)
        with dispatch_watchdog.compiling():
            compiled = lower_fn().compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
        dsp.set(lane=lane, compile_ms=round(compile_ms, 3))
    costs.note_compile(lane, shape_key, compiled, compile_ms,
                       owner=owner)
    return compiled


def is_device_oom(exc: BaseException) -> bool:
    """Does this exception look like device memory exhaustion? Covers
    the injected :class:`DeviceOomError` and the strings real XLA
    runtime errors carry (RESOURCE_EXHAUSTED / out of memory)."""
    if isinstance(exc, DeviceOomError):
        return True
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


class PlaneBreaker:
    """Per-node circuit breaker over the compiled device paths.

    closed → open after ``threshold`` CONSECUTIVE device errors →
    half-open probe after an exponentially backed-off wait. While open,
    admission gates (collective-plane admission in search_action, the
    percolator's fused lanes, ShardSearcher's compiled query phase)
    route straight to the fan-out/eager path, so an unhealthy device
    costs fallback latency — not a failed device dispatch per query.
    In half-open exactly ONE request is admitted as the probe; its
    success closes the breaker, its failure re-opens with a doubled
    backoff (capped at ``max_backoff_s``).

    All in-process nodes share one device, so the module singleton
    ``plane_breaker`` IS the per-node breaker (one node = one process =
    one device in deployment); ``search.plane_breaker.*`` node settings
    configure it.
    """

    #: a claimed half-open probe that never reports back (thread died)
    #: frees the probe slot after this long
    PROBE_TIMEOUT_S = 30.0

    def __init__(self, threshold: int = 3, backoff_s: float = 1.0,
                 max_backoff_s: float = 30.0):
        self._lock = threading.Lock()
        self.threshold = int(threshold)
        self.base_backoff_s = float(backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self._reset_locked()

    def _reset_locked(self) -> None:
        self.state = "closed"
        self.consecutive_errors = 0
        self.trips = 0
        self.probes = 0
        self.errors_total = 0
        self.last_error: str | None = None
        self._backoff_s = self.base_backoff_s
        self._retry_at = 0.0
        self._probe_deadline: float | None = None
        # watchdog quarantine: while set, allow() is False for live
        # traffic unconditionally — reopen is gated on the watchdog's
        # background probe program, never on a live-request probe
        self.quarantined = False

    #: breaker state → registered flight-recorder event type
    _TRANSITION_EVENTS = {"open": "breaker-open",
                          "half_open": "breaker-half-open",
                          "closed": "breaker-closed"}

    @staticmethod
    def _note_transition(state: str, **attrs) -> None:
        """One breaker state transition on the flight recorder (called
        AFTER the breaker lock releases — the ring lock stays a leaf)."""
        from elasticsearch_tpu.observability import flightrec
        flightrec.note(PlaneBreaker._TRANSITION_EVENTS[state],
                       state=state, **attrs)

    def reset(self) -> None:
        with self._lock:
            was = self.state
            self._reset_locked()
        if was != "closed":
            self._note_transition("closed", reset=True)

    def configure(self, threshold=None, backoff_s=None,
                  max_backoff_s=None) -> None:
        """Apply node settings (None leaves a knob unchanged)."""
        with self._lock:
            if threshold is not None:
                self.threshold = max(int(threshold), 1)
            if backoff_s is not None:
                self.base_backoff_s = float(backoff_s)
                if self.state == "closed":
                    self._backoff_s = self.base_backoff_s
            if max_backoff_s is not None:
                self.max_backoff_s = float(max_backoff_s)

    def allow(self) -> bool:
        """May a device dispatch proceed? Open → False (until the
        backoff elapses); half-open → True for exactly one caller (the
        probe), False for everyone else."""
        now = time.monotonic()
        probing = False
        with self._lock:
            if self.quarantined:
                return False
            if self.state == "closed":
                return True
            if self.state == "open":
                if now < self._retry_at:
                    return False
                self.state = "half_open"
                self.probes += 1
                self._probe_deadline = now + self.PROBE_TIMEOUT_S
                probing = True
            elif self._probe_deadline is not None and \
                    now < self._probe_deadline:
                # half_open: one probe in flight at a time
                return False
            else:
                self.probes += 1
                self._probe_deadline = now + self.PROBE_TIMEOUT_S
                return True
        if probing:
            self._note_transition("half_open", probes=self.probes)
        return True

    def record_success(self) -> None:
        """A device dispatch completed: closes a half-open probe, resets
        the consecutive-error count."""
        closed = False
        with self._lock:
            if self.state == "half_open":
                self.state = "closed"
                self._backoff_s = self.base_backoff_s
                closed = True
            self.consecutive_errors = 0
            self._probe_deadline = None
        if closed:
            self._note_transition("closed", probes=self.probes)

    def record_error(self, exc: BaseException) -> None:
        """A device dispatch failed: counts toward the trip threshold;
        a failed half-open probe re-opens with doubled backoff."""
        now = time.monotonic()
        opened = None
        with self._lock:
            self.errors_total += 1
            self.last_error = f"{type(exc).__name__}: {str(exc)[:160]}"
            self.consecutive_errors += 1
            if self.state == "half_open":
                self.state = "open"
                self.trips += 1
                self._backoff_s = min(self._backoff_s * 2,
                                      self.max_backoff_s)
                self._retry_at = now + self._backoff_s
                self._probe_deadline = None
                opened = "probe-failed"
            elif self.state == "closed" and \
                    self.consecutive_errors >= self.threshold:
                self.state = "open"
                self.trips += 1
                self._retry_at = now + self._backoff_s
                opened = "threshold"
        if opened is not None:
            self._note_transition(
                "open", cause=opened, trips=self.trips,
                consecutive_errors=self.consecutive_errors,
                error=self.last_error,
                backoff_seconds=round(self._backoff_s, 3))

    def quarantine(self) -> None:
        """Watchdog escalation: hold the breaker open unconditionally.
        While quarantined, ``allow()`` declines every live request (no
        half-open probe on live traffic); only
        :meth:`release_quarantine` — called by the watchdog after its
        background probe program completes — readmits."""
        with self._lock:
            already = self.quarantined
            self.quarantined = True
            if self.state != "open":
                self.state = "open"
                self.trips += 1
            self._probe_deadline = None
        if not already:
            self._note_transition("open", cause="quarantine",
                                  trips=self.trips)

    def release_quarantine(self) -> None:
        """The watchdog's probe program completed: fully reset to
        closed (the device proved itself end to end)."""
        with self._lock:
            was = self.quarantined
            self._reset_locked()
        if was:
            self._note_transition("closed", probe_reopen=True)

    def stats(self) -> dict:
        now = time.monotonic()
        with self._lock:
            return {
                "state": self.state,
                "threshold": self.threshold,
                "consecutive_errors": self.consecutive_errors,
                "trips": self.trips,
                "probes": self.probes,
                "errors_total": self.errors_total,
                "last_error": self.last_error,
                "quarantined": self.quarantined,
                "backoff_seconds": round(self._backoff_s, 3),
                "open_remaining_seconds":
                    round(max(self._retry_at - now, 0.0), 3)
                    if self.state == "open" and not self.quarantined
                    else 0.0,
            }


#: THE per-node plane breaker (module singleton — see class docstring)
plane_breaker = PlaneBreaker()


def note_device_error(exc: BaseException) -> None:
    """One device error observed at a compiled-path seam: feeds the
    plane breaker, and for HBM-OOM shapes first evicts cold blocks from
    the PR 5 device-block cache — reclaiming headroom is cheaper than
    degrading, and the next (re)build retries against a smaller
    footprint."""
    if is_device_oom(exc):
        try:
            from elasticsearch_tpu.parallel import mesh_engine
            freed = mesh_engine.evict_cold_blocks()
        except Exception:                # noqa: BLE001 — best-effort
            freed = 0
        with _cache_lock:
            _bump("oom_evictions")
            _bump("oom_bytes_evicted", int(freed))
    plane_breaker.record_error(exc)


def note_breaker_skip() -> None:
    """One request routed to the fan-out/eager path because the plane
    breaker was open — the degraded-mode-serving counter. (Collective-
    plane admission declines label ``fallback_reasons`` separately via
    :func:`note_plane_fallback` with reason ``breaker-open``.)"""
    with _cache_lock:
        _bump("breaker_open_skips")
# mesh_program_* count the collective plane's shape-keyed PROGRAM layer
# (mesh_engine._program): a miss is a fresh shard_map trace+compile, a
# hit re-dispatches a compiled program against a new data-layer pack —
# the counters that prove a repeated sorted/terms-agg query re-traces at
# most once per shape, not per refresh generation. plane_fallbacks
# counts ADMISSION declines (the request still succeeds on the RPC
# fan-out) — kept apart from `fallbacks`, which tracks compiled-program
# executions degrading to eager and is held at zero by the jit suites.
# Keys (and their meanings) live in the lane registry — the store is
# built FROM it so every registered counter is surfaced through
# cache_stats() → _nodes/stats by construction, and plane-lint's
# counter-discipline rule can prove registry ⇔ bump-site agreement.
_stats = {k: 0 for k in lanes.JIT_COUNTERS}
#: why searches left the compiled/collective path, by label
#: (ineligible-shape / parse-error / refresh-race / device-error / …)
_fallback_reasons: dict[str, int] = {}
#: the same declines counted in search ITEMS (an _msearch of 64 that
#: falls to the fan-out is one decline of 64 items)
_plane_items_fallback_reasons: dict[str, int] = {}
#: why impact-lane admission declined, by label — only bumped for
#: indices that OPTED IN to the impact plane (the exact scorer is the
#: default; a disabled index never logs an impact fallback)
_impact_fallback_reasons: dict[str, int] = {}
#: why knn/hybrid requests left the compiled lane (the eager
#: per-segment fallback served them), by label
_knn_fallback_reasons: dict[str, int] = {}
#: why fused-percolate dispatches fell to the per-query eager lane
#: (breaker-open / device-error), by label
_percolate_fallback_reasons: dict[str, int] = {}
#: why the continuous-batching scheduler shed requests (queue-deadline /
#: slo-shed / queue-full / task-cancelled / closed), by label
_scheduler_shed_reasons: dict[str, int] = {}
#: planner admission outcomes by label (routed-impact / routed-knn /
#: breaker-open / no-plan / plan-error) — the vocabulary that replaced
#: the pairwise decline edges
_planner_fallback_reasons: dict[str, int] = {}
#: per-INDEX knn-lane accounting — feeds the per-index _stats
#: "search.knn" section and the _cat/indices knn.* columns
_knn_index_stats: dict[str, dict] = {}
#: per-INDEX impact-lane accounting (admissions, blocks scored/skipped)
#: — feeds the per-index _stats "search.impact" section and the
#: _cat/indices impact.{blocks,skip_ratio} columns
_impact_index_stats: dict[str, dict] = {}

# Per-NODE attribution of the rollups above: every in-process node
# shares this module, so without node keying a two-node cluster test
# reads one node's compiles in the other node's _nodes/stats. Counter
# bumps attribute to observability.context.current_node_id() (the
# executing task's node); cache_stats(node_id=...) reads one bucket.
_node_stats: dict[str, dict] = {}
_node_fallback_reasons: dict[str, dict] = {}


def _bump(key: str, n: int = 1) -> None:
    """Count one event on the process-global rollup, the current node's
    bucket, and (for program-cache keys) the per-request slow-log
    attribution. Callers hold ``_cache_lock``."""
    _stats[key] += n
    nid = current_node_id()
    if nid is not None:
        bucket = _node_stats.setdefault(nid, {})
        bucket[key] = bucket.get(key, 0) + n
    if key in _attribution.MIRRORED_COUNTS:
        _attribution.count(key, n)

# data_layer.* count the collective plane's INCREMENTAL data layer
# (mesh_engine._DeviceBlockCache): bytes_uploaded is actual host→device
# transfer (column + live-mask bytes, split out below), bytes_reused is
# the column bytes of already-resident blocks a rebuild composed instead
# of re-uploading. The refresh classifiers prove the contract the tier-1
# guards pin down: a one-segment refresh is `incremental` (uploads O(new
# segment)), a delete-only refresh is `mask_only` (ZERO column bytes),
# and only a cold/changed-layout build is a `full_rebuild`.
_data_layer = {k: 0 for k in lanes.DATA_LAYER_COUNTERS}


def cache_stats(node_id: str | None = None) -> dict:
    """The process-global rollup (default), or — with ``node_id`` — the
    counters attributed to one node's tasks (the per-node view
    ``_nodes/stats`` reports as ``jit.node_local``)."""
    if node_id is not None:
        with _cache_lock:
            bucket = dict(_node_stats.get(node_id, {}))
            reasons = dict(_node_fallback_reasons.get(node_id, {}))
        out = {key: bucket.get(key, 0) for key in _stats}
        out["fallback_reasons"] = reasons
        return out
    with _cache_lock:
        out = {**_stats, "fallback_reasons": dict(_fallback_reasons),
               "plane_items_fallback_reasons":
                   dict(_plane_items_fallback_reasons),
               "impact_fallback_reasons": dict(_impact_fallback_reasons),
               "knn_fallback_reasons": dict(_knn_fallback_reasons),
               "percolate_fallback_reasons":
                   dict(_percolate_fallback_reasons),
               "scheduler_shed_reasons": dict(_scheduler_shed_reasons),
               "planner_fallback_reasons":
                   dict(_planner_fallback_reasons),
               "data_layer": dict(_data_layer)}
    out["plane_breaker"] = plane_breaker.stats()
    return out


def note_data_blocks(col_bytes: int = 0, mask_bytes: int = 0,
                     reused_bytes: int = 0) -> None:
    """Block-cache traffic from one data-layer (re)build: host→device
    uploads (columns / live masks) and resident-block reuse."""
    with _cache_lock:
        _data_layer["bytes_uploaded"] += col_bytes + mask_bytes
        _data_layer["col_bytes_uploaded"] += col_bytes
        _data_layer["mask_bytes_uploaded"] += mask_bytes
        _data_layer["bytes_reused"] += reused_bytes


def note_data_refresh(kind: str) -> None:
    """One data-layer rebuild classified: 'full' (no resident block
    reused), 'incremental' (new column bytes composed with resident
    blocks), or 'mask_only' (zero column bytes uploaded)."""
    key = {"full": "full_rebuilds", "incremental": "incremental_refreshes",
           "mask_only": "mask_only_refreshes"}[kind]
    with _cache_lock:
        _data_layer[key] += 1


def note_plane_dispatch(gather_bytes: int) -> None:
    """One collective-plane program dispatch and the candidate bytes its
    all_gather moved over the shard axis."""
    with _cache_lock:
        _bump("plane_dispatches")
        _bump("plane_gather_bytes", int(gather_bytes))


def note_plane_served(items: int) -> None:
    """``items`` search items answered by the collective plane."""
    with _cache_lock:
        _bump("plane_items_served", int(items))


def note_mesh_program(hit: bool) -> None:
    """One collective-plane program-cache lookup (mesh_engine._program)."""
    with _cache_lock:
        _bump("mesh_program_hits" if hit else "mesh_program_misses")


def note_plane_fallback(reason: str, items: int = 1) -> None:
    """One collective-plane admission decline of ``items`` search items,
    reason-labeled."""
    lanes.check_reason("plane", reason)
    _attribution.label("fallback", reason)
    with _cache_lock:
        _bump("plane_fallbacks")
        _bump("plane_items_fallback", int(items))
        _fallback_reasons[reason] = _fallback_reasons.get(reason, 0) + 1
        _plane_items_fallback_reasons[reason] = \
            _plane_items_fallback_reasons.get(reason, 0) + int(items)
        nid = current_node_id()
        if nid is not None:
            bucket = _node_fallback_reasons.setdefault(nid, {})
            bucket[reason] = bucket.get(reason, 0) + 1


_logged_fallbacks: set = set()


def note_fallback(exc: BaseException | None = None,
                  reason: str | None = None) -> None:
    if reason is not None:
        # compiled-path degradations share the plane vocabulary
        lanes.check_reason("plane", reason)
    with _cache_lock:
        _bump("fallbacks")
        if reason is not None:
            _fallback_reasons[reason] = _fallback_reasons.get(reason, 0) + 1
    if exc is not None:
        # log each distinct failure once — silent fallbacks hide real
        # kernel bugs (round-2 verdict weak #9)
        key = (type(exc).__name__, str(exc)[:120])
        if key not in _logged_fallbacks:
            _logged_fallbacks.add(key)
            import logging
            import traceback
            logging.getLogger("elasticsearch_tpu.jit").warning(
                "jit path fell back to eager: %s",
                "".join(traceback.format_exception(
                    type(exc), exc, exc.__traceback__)[-3:]).strip())


def clear_cache() -> None:
    with _cache_lock:
        _cache.clear()
        _stats.update({k: 0 for k in _stats})
        _fallback_reasons.clear()
        _plane_items_fallback_reasons.clear()
        _impact_fallback_reasons.clear()
        _impact_index_stats.clear()
        _knn_fallback_reasons.clear()
        _knn_index_stats.clear()
        _percolate_fallback_reasons.clear()
        _scheduler_shed_reasons.clear()
        _planner_fallback_reasons.clear()
        _data_layer.update({k: 0 for k in _data_layer})
        _node_stats.clear()
        _node_fallback_reasons.clear()
    # the cost observatory and flight recorder reset with the program
    # cache: their books describe the programs the cache holds
    from elasticsearch_tpu.observability import costs, flightrec
    costs.reset()
    flightrec.reset()


# ---------------------------------------------------------------------------
# Segment flatten/rebuild (the traced-input pytree)
# ---------------------------------------------------------------------------

_KINDS = ("text", "keyword", "numeric", "vector", "mvector", "geo",
          "shape")
_ARRAYS = {
    "text": ("tokens", "uterms", "utf", "doc_len"),
    "keyword": ("ords",),
    "numeric": ("hi", "lo", "exists"),
    "vector": ("vecs", "exists"),
    "mvector": ("vecs", "lens", "exists"),
    "geo": ("lat", "lon", "exists"),
    "shape": ("lats", "lons", "nv", "exists", "rid", "area"),
}


_materialize_lock = threading.Lock()


def _fetch(seg: DeviceSegment, col, attr: str):
    """Read a column array, materializing LAZY host-side columns (tokens /
    vecs) onto the reader's device on first use. The result is cached back
    on the column object so the transfer happens once per reader
    generation; the lock stops concurrent first-phrase-queries from
    shipping the same hundreds of MB twice."""
    a = getattr(col, attr)
    if seg.lazy_put is None or not isinstance(a, np.ndarray):
        return a
    with _materialize_lock:
        a = getattr(col, attr)
        if isinstance(a, np.ndarray):
            a = seg.lazy_put(a)
            setattr(col, attr, a)
    return a


def _keep(kind: str, attr: str, name: str, positions_for, vectors_for
          ) -> bool:
    """Tree-shaking rule for the traced-input pytree: text position
    matrices and vector columns are kept per-FIELD, everything else
    always. `None` for either filter means "keep everything" (the mesh
    engine pre-stacks segments once, before any plan exists)."""
    if kind == "text" and attr == "tokens":
        return positions_for is None or name in positions_for
    if kind in ("vector", "mvector") and attr == "vecs":
        return vectors_for is None or name in vectors_for
    return True


def seg_flatten(seg: DeviceSegment, positions_for: frozenset | None = None,
                vectors_for: frozenset | None = None) -> list:
    """Device arrays of a segment in deterministic order (live first;
    nested child blocks recurse after the flat kinds). Text position
    matrices flatten ONLY for fields in `positions_for`, and vector/geo
    columns only when the plan declared that kind — tracing the [N, L]
    tokens array (or a [N, 768] vector column) no op reads multiplies
    XLA compile time for nothing (measured ~14x at 1M docs)."""
    flat = [seg.live]
    for kind in _KINDS:
        fields = getattr(seg, kind)
        for name in sorted(fields):
            col = fields[name]
            for attr in _ARRAYS[kind]:
                if not _keep(kind, attr, name, positions_for, vectors_for):
                    continue
                flat.append(_fetch(seg, col, attr))
    for path in sorted(seg.nested):
        blk = seg.nested[path]
        flat.append(blk.parent)
        flat.extend(seg_flatten(blk.child, positions_for, vectors_for))
    return flat


def seg_rebuild(seg: DeviceSegment, flat: list,
                positions_for: frozenset | None = None,
                vectors_for: frozenset | None = None) -> DeviceSegment:
    """Shallow-copy `seg` with arrays swapped for (traced) `flat`. Arrays
    excluded from the flatten become None — a plan reading data it never
    declared fails loudly at trace time (and falls back to eager) instead
    of silently baking a device buffer into the compiled program as a
    constant."""
    it = iter(flat)

    def rebuild(s: DeviceSegment) -> DeviceSegment:
        live = next(it)
        kinds = {}
        for kind in _KINDS:
            fields = getattr(s, kind)
            # arrays were flattened in sorted-name order, but the rebuilt
            # dicts must preserve the ORIGINAL iteration order — resolver
            # walks (e.g. the all-fields match loop) iterate these dicts,
            # and the emitted structure depends on it
            rebuilt = {
                name: dc_replace(fields[name],
                                 **{attr: (next(it)
                                           if _keep(kind, attr, name,
                                                    positions_for,
                                                    vectors_for)
                                           else None)
                                    for attr in _ARRAYS[kind]})
                for name in sorted(fields)}
            kinds[kind] = {name: rebuilt[name] for name in fields}
        nested = {}
        for path in sorted(s.nested):
            blk = s.nested[path]
            parent = next(it)
            nested[path] = dc_replace(blk, parent=parent,
                                      child=rebuild(blk.child))
        nested = {path: nested[path] for path in s.nested}
        return dc_replace(s, live=live, nested=nested, **kinds)

    return rebuild(seg)


def layout_key(seg: DeviceSegment) -> tuple:
    out = [seg.padded_docs]
    for kind in _KINDS:
        fields = getattr(seg, kind)
        for name in sorted(fields):
            col = fields[name]
            out.append((kind, name) + tuple(
                (tuple(getattr(col, attr).shape),
                 str(getattr(col, attr).dtype))
                for attr in _ARRAYS[kind]))
    for path in sorted(seg.nested):
        blk = seg.nested[path]
        out.append(("nested", path, tuple(blk.parent.shape),
                    layout_key(blk.child)))
    return tuple(out)


# ---------------------------------------------------------------------------
# The fused per-segment program
# ---------------------------------------------------------------------------

def _plan(seg: DeviceSegment, ctx: ExecutionContext, query, post_filter,
          flags, term_floor: int = 1):
    """Host resolve → (ConstTable, emit_q, emit_pf mask-emit, flag refs).
    ``term_floor``: the batch's widest ``match`` term bucket (a lone
    query pads to its own)."""
    ct = ConstTable()
    resolver = SegmentResolver(seg, ctx, ct, term_floor)
    emit_q = resolver.resolve(query)
    emit_pf = resolver.resolve_mask(post_filter) \
        if post_filter is not None else None
    refs = {}
    if flags["min_score"]:
        refs["min_score"] = ct.add(flags["_min_score"], np.float32)
    if flags["search_after"]:
        refs["sa_score"] = ct.add(flags["_sa_score"], np.float32)
        refs["sa_doc"] = ct.add(flags["_sa_doc"], np.int32)
        refs["doc_base"] = ct.add(flags["_doc_base"], np.int32)
    return ct, emit_q, emit_pf, refs


def _build(view, consts, emit_q, emit_pf, refs, flags, k: int):
    """The program body: emit + phase post-processing + top-k."""
    em = EmitCtx(view, consts)
    scores, mask = emit_q(em)
    mask = mask & view.live
    if "min_score" in refs:
        mask = mask & (scores >= em.get(refs["min_score"]))
    if emit_pf is not None:
        mask_post = mask & emit_pf(em)
    else:
        mask_post = mask
    if "sa_score" in refs:
        last_score = em.get(refs["sa_score"])
        last_doc = em.get(refs["sa_doc"])
        ids = jnp.arange(view.padded_docs, dtype=jnp.int32) + \
            em.get(refs["doc_base"])
        cont = (scores < last_score) | ((scores == last_score) &
                                        (ids > last_doc))
        mask_post = mask_post & cont
    count = mask_post.sum(dtype=jnp.int32)
    outs = {"count": count}
    if flags["want_topk"]:
        ts, td = topk_ops.top_k(scores, mask_post,
                                min(k, view.padded_docs), 0)
        outs["top_scores"], outs["top_docs"] = ts, td
    if flags["want_arrays"]:
        outs["scores"] = scores
        outs["mask"] = mask_post
        # pre-post_filter mask for aggregations (ES computes aggs on the
        # main query result, ignoring post_filter)
        outs["agg_mask"] = mask
    return outs


def _get_compiled(key, lower_fn, lane: str = "segment",
                  owner: str | None = None):
    """Program-cache trampoline: ``lower_fn`` returns the LOWERED
    program; a miss routes it through :func:`observed_compile` (which
    owns the ``.compile()``, the fault point and the cost-table stamp)
    under ``lane``'s books."""
    with _cache_lock:
        fn = _cache.get(key)
        if fn is not None:
            _cache.move_to_end(key)
            _bump("hits")
            return fn
    # compile OUTSIDE the lock (slow); a racing duplicate compile is
    # harmless — last one wins the cache slot
    with _cache_lock:
        _bump("misses")
    fn = observed_compile(lane, key, lower_fn, owner=owner)
    with _cache_lock:
        _cache[key] = fn
        while len(_cache) > _CACHE_CAP:
            _cache.popitem(last=False)
    return fn


def run_segment(seg: DeviceSegment, ctx: ExecutionContext, query,
                *, post_filter=None, min_score=None, search_after=None,
                k: int | None = None, want_arrays: bool = False) -> dict:
    """Execute a query against one device segment as one compiled program.

    Returns {"count": i32 [, "top_scores", "top_docs"] [, "scores",
    "mask", "agg_mask"]} as device arrays; top_docs are segment-local
    (caller adds seg.doc_base).
    """
    flags = {
        "min_score": min_score is not None,
        "_min_score": 0.0 if min_score is None else float(min_score),
        "search_after": search_after is not None,
        "_sa_score": 0.0 if search_after is None
        else float(search_after[0]),
        "_sa_doc": -1 if (search_after is None or len(search_after) < 2)
        else int(search_after[1]),
        "_doc_base": seg.doc_base,
        "want_topk": k is not None,
        "want_arrays": want_arrays,
    }
    k_static = 0 if k is None else int(k)

    ct, emit_q, emit_pf, refs = _plan(seg, ctx, query, post_filter, flags)
    consts = [jnp.asarray(v) for v in ct.values]

    pos_for = frozenset(ct.positions_needed)
    vecs = frozenset(ct.vectors_needed)
    key = (ct.signature(), layout_key(seg), pos_for, vecs,
           float(ctx.bm25.k1), float(ctx.bm25.b),
           flags["min_score"], flags["search_after"], k_static, want_arrays,
           post_filter is not None)
    flat = seg_flatten(seg, pos_for, vecs)

    def compile_fn():
        def run(flat_in, consts_in):
            view = seg_rebuild(seg, flat_in, pos_for, vecs)
            return _build(view, consts_in, emit_q, emit_pf, refs, flags,
                          k_static)
        # AOT lower (observed_compile owns the .compile()) and cache
        # ONLY the executable: a cached jax.jit closure would pin the
        # whole DeviceSegment/DeviceReader (every column's device
        # arrays) for the life of the cache entry — an accumulating
        # device-memory leak across index churn
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (flat, consts))
        return jax.jit(run).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="segment",
                       owner=getattr(ctx.reader, "engine_uuid", None))
    with device_span("dispatch", cost=("segment", key, 1, 1)):
        device_fault_point("dispatch")
        return fn(flat, consts)


def _plan_segment_batch(seg: DeviceSegment, ctx: ExecutionContext,
                        queries: list, k_static: int) -> dict | None:
    """Plan a batch of same-signature queries against one segment and pack
    their dynamic constants per dtype into ONE [B, total] buffer each:
    every host→device transfer pays a fixed per-call dispatch cost, so 2
    packed buffers beat N small ones; the program unpacks by static slicing
    (free under XLA). The spec layout is a pure function of the plan
    signature, so cached programs agree on it. ``match`` queries of
    unequal lengths share a signature: every query pads its term lists to
    the batch's widest term bucket, found by one walk of the parsed
    queries before any is planned. Returns None when the queries do not
    share one plan signature or the shared plan has no dynamic constants
    (callers fall back to per-query execution)."""
    if not queries:
        return None
    term_floor = match_term_floor(queries, ctx.mapper_service)
    flags = {
        "min_score": False, "_min_score": 0.0,
        "search_after": False, "_sa_score": 0.0, "_sa_doc": -1,
        "_doc_base": seg.doc_base,
        "want_topk": True, "want_arrays": False,
    }
    sig0 = None
    emit0 = refs0 = None
    pos_for: frozenset = frozenset()
    vecs: frozenset = frozenset()
    consts_rows: list[list[np.ndarray]] = []
    match_terms: list[tuple[int, int]] = []     # per query: (real, pads)
    for query in queries:
        ct, emit_q, _, refs = _plan(seg, ctx, query, None, flags,
                                    term_floor)
        if sig0 is None:
            sig0, emit0, refs0 = ct.signature(), emit_q, refs
            pos_for = frozenset(ct.positions_needed)
            vecs = frozenset(ct.vectors_needed)
        elif ct.signature() != sig0:
            return None
        consts_rows.append(ct.values)
        match_terms.append((ct.match_terms_real, ct.match_terms_padded))

    b = len(queries)
    # pad the batch axis to the next power of two (repeating the last
    # query's constants) so varying batch sizes share compiled programs
    from elasticsearch_tpu.search.batching import pow2_bucket
    b_pad = pow2_bucket(b)
    if b_pad != b:
        consts_rows = consts_rows + [consts_rows[-1]] * (b_pad - b)
    if not consts_rows[0]:
        # const-free plans (match_none / absent-field zeros): nothing to
        # vmap over — the per-query path handles these (rare) shapes
        return None
    specs = []                       # per const: (dtype, offset, shape, size)
    totals: dict[str, int] = {}
    for v in consts_rows[0]:
        dt = str(v.dtype)
        off = totals.get(dt, 0)
        size = int(v.size)
        specs.append((dt, off, v.shape, size))
        totals[dt] = off + size
    packed = {}
    for dt, total in totals.items():
        packed[dt] = np.empty((b_pad, total), dtype=dt)
    for bi, row in enumerate(consts_rows):
        for v, (dt, off, _shape, size) in zip(row, specs):
            packed[dt][bi, off:off + size] = v.reshape(-1)
    return {
        "seg": seg, "sig": sig0, "emit": emit0, "refs": refs0,
        "pos": pos_for, "vecs": vecs, "flags": flags,
        "specs": tuple(specs), "packed": packed, "b_pad": b_pad,
        "flat": seg_flatten(seg, pos_for, vecs),
        "key": (sig0, layout_key(seg), pos_for, vecs,
                float(ctx.bm25.k1), float(ctx.bm25.b), k_static, b_pad,
                tuple(specs)),
        "k": k_static, "match_terms": match_terms,
    }


def _lane_fn(plan: dict, view: DeviceSegment):
    """One vmap lane: unpack this query's constants by static slicing and
    run the shared program body."""
    def one(packed_one):
        consts_one = [
            packed_one[dt][off:off + size].reshape(shape)
            for dt, off, shape, size in plan["specs"]]
        return _build(view, consts_one, plan["emit"], None, plan["refs"],
                      plan["flags"], plan["k"])
    return one


def run_reader_batch(segments: list, ctx: ExecutionContext, queries: list,
                     *, k: int, pack: bool, n_real: int | None = None):
    """The whole reader's batched query phase as ONE compiled program:
    per-segment vmapped scoring + top-k, cross-segment merge to
    reader-global doc ids (TopDocs.merge tie-break — concat in segment
    order + stable top_k, core/search/controller/SearchPhaseController
    .java:165), hit-count sum, and (with ``pack``) the [B, 2k+1] packed
    fetch layout — a single device dispatch + a single device→host fetch
    per batch instead of S+2 dispatches (each dispatch and each blocking
    fetch has a fixed host-side cost the device idles through).

    Returns a packed [B, 2k+1] f32 array (``pack=True``; exact only while
    doc ids and counts stay below 2**24 — the caller checks max_doc), or
    ``{"top_scores", "top_docs", "count"}`` device arrays. None when any
    segment's queries do not share one plan signature (caller falls back
    to per-query execution).
    """
    if not queries or not segments:
        return None
    k_static = int(k)
    with span("jit.pack"):
        # plans, flats and the packed operands: all host work
        plans = []
        for seg in segments:
            plan = _plan_segment_batch(seg, ctx, queries, k_static)
            if plan is None:
                return None
            plans.append(plan)
        b = len(queries)
        b_pad = plans[0]["b_pad"]
        bases = tuple(int(seg.doc_base) for seg in segments)
        key = ("reader", bases, bool(pack)) + tuple(p["key"] for p in plans)
        flats = [p["flat"] for p in plans]
        packeds = [{dt: jnp.asarray(buf) for dt, buf in p["packed"].items()}
                   for p in plans]
    if os.environ.get("JIT_DEBUG"):
        total = sum(int(a.size) * a.dtype.itemsize
                    for flat in flats for a in flat)
        print(f"[jit-debug] reader batch: {len(plans)} segment(s), "
              f"{sum(len(f) for f in flats)} arrays, {total/1e6:.1f} MB "
              f"traced; pos_for={sorted(plans[0]['pos'])} "
              f"vecs={sorted(plans[0]['vecs'])}", flush=True)

    def compile_fn():
        def run(flats_in, packeds_in):
            ts_list, td_list = [], []
            counts = None
            for i, (plan, flat_in, packed_in) in enumerate(
                    zip(plans, flats_in, packeds_in)):
                view = seg_rebuild(plan["seg"], flat_in,
                                   plan["pos"], plan["vecs"])
                with jax.named_scope(f"segment_{i}"):
                    outs = jax.vmap(_lane_fn(plan, view))(packed_in)
                ts_list.append(outs["top_scores"])
                td_list.append(outs["top_docs"])
                counts = outs["count"] if counts is None \
                    else counts + outs["count"]
            top_s, top_d = topk_ops.merge_top_k_batch_body(
                ts_list, td_list, k_static, bases)
            if pack:
                return topk_ops.pack_batch_result_body(top_s, top_d,
                                                       counts)
            return {"top_scores": top_s, "top_docs": top_d, "count": counts}

        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (flats, packeds))
        return jax.jit(run).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="reader-batch",
                       owner=getattr(ctx.reader, "engine_uuid", None))
    with device_span("dispatch",
                     cost=("reader-batch", key,
                           n_real if n_real is not None else b, b_pad)):
        device_fault_point("dispatch")
        out = fn(flats, packeds)
    note_match_terms(plans[0]["match_terms"][:b if n_real is None
                                             else n_real])
    if b_pad != b:
        out = out[:b] if pack else {name: v[:b] for name, v in out.items()}
    return out


#: how long the streamed consumer waits on the feeder (per segment, and
#: for the teardown join) before declaring the feeder's host→device
#: transfer wedged — generous vs any real DMA; stall tests shrink it
STREAM_FEEDER_STALL_S = 60.0


def run_segments_streamed(segments: list, ctx: ExecutionContext,
                          queries: list, *, k: int,
                          device=None) -> list | None:
    """Batched query phase over HOST-POOL (non-resident) segments: each
    segment's columns are DMA'd host→HBM per batch, double-buffered so
    segment i+1's transfer overlaps segment i's compute, and the device
    buffers are dropped as soon as the program consumes them — corpora
    beyond HBM capacity execute at a bounded footprint of ~two segments'
    columns (SURVEY §7 "HBM budget & residency"; the over-capacity analog
    of the reference's FS-cache paging,
    core/index/store/FsDirectoryService.java mmap).

    Returns one ``{"count", "top_scores", "top_docs"}`` dict per segment
    (batch axis padded like :func:`run_reader_batch` — callers slice),
    or None when any segment's plan is ineligible for batching.
    """
    if not segments:
        return []
    k_static = int(k)
    plans = []
    for seg in segments:
        plan = _plan_segment_batch(seg, ctx, queries, k_static)
        if plan is None:
            return None
        plans.append(plan)
    def put(a, _dev=device):
        with device_span("upload"):
            device_fault_point("upload")
            return jax.device_put(a, _dev) if _dev is not None \
                else jax.device_put(a)

    def get_fn(seg, plan):
        def compile_fn():
            def run(flat_in, packed_in):
                view = seg_rebuild(seg, flat_in, plan["pos"], plan["vecs"])
                return jax.vmap(_lane_fn(plan, view))(packed_in)
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (plan["flat"], plan["packed"]))
            return jax.jit(run).lower(*shapes)
        # keyed by the plan, not the segment: bucketized segments with a
        # common layout share ONE compiled program across the whole sweep
        return _get_compiled(("batch",) + plan["key"], compile_fn,
                             lane="streamed",
                             owner=getattr(ctx.reader, "engine_uuid",
                                           None))

    # transfers run on a DEDICATED feeder thread, one segment ahead:
    # host→HBM DMA overlaps the in-flight program's compute even when
    # device_put itself blocks the calling thread on this interconnect —
    # the same reason the scheduler drains on worker threads. A
    # 2-permit semaphore bounds MATERIALIZED segments to two (the
    # over-capacity contract this path exists for); the consumer blocks
    # on segment i−1's completion before granting the next permit, so
    # async dispatch cannot run ahead of the device and pin every
    # segment's buffers at once.
    prefetch: queue.Queue = queue.Queue()
    feed_err: list = []
    slots = threading.Semaphore(2)
    stop = threading.Event()

    def _feeder():
        try:
            for plan in plans:
                while not slots.acquire(timeout=0.25):
                    if stop.is_set():
                        return
                if stop.is_set():
                    return
                prefetch.put([put(a) for a in plan["flat"]])
        except Exception as e:               # noqa: BLE001 — surfaced below
            feed_err.append(e)
            prefetch.put(None)

    feeder = threading.Thread(target=_feeder, daemon=True,
                              name="hbm-stream-feeder")
    feeder.start()
    outs_all = []
    stats = {"put_wait_s": 0.0, "dispatch_s": 0.0}
    try:
        for i, (seg, plan) in enumerate(zip(segments, plans)):
            t0 = time.perf_counter()
            stall_at = t0 + STREAM_FEEDER_STALL_S
            while True:
                try:
                    cur = prefetch.get(timeout=0.25)
                    break
                except queue.Empty:
                    if feed_err:
                        raise feed_err[0]
                    if time.perf_counter() > stall_at:
                        raise DeviceStallError(
                            f"hbm-stream feeder stalled staging segment "
                            f"{i}/{len(plans)} (no transfer completed in "
                            f"{STREAM_FEEDER_STALL_S:.0f}s)")
            if cur is None:
                raise feed_err[0]
            stats["put_wait_s"] += time.perf_counter() - t0
            fn = get_fn(seg, plan)
            packed = {dt: jnp.asarray(buf)
                      for dt, buf in plan["packed"].items()}
            t1 = time.perf_counter()
            with device_span("dispatch",
                             cost=("streamed", ("batch",) + plan["key"],
                                   len(queries), plan["b_pad"])):
                device_fault_point("dispatch")
                outs = fn(cur, packed)      # async dispatch
            stats["dispatch_s"] += time.perf_counter() - t1
            outs_all.append(outs)
            del cur                         # free as soon as compute drains
            if i >= 1:
                # segment i−1's program has fully drained → its column
                # buffers are free; only then does a permit return so
                # the feeder may stage segment i+1 (keeps exactly two
                # segments materialized: i computing, i+1 staging)
                jax.block_until_ready(  # estpu: allow[host-sync-hot-loop] two-segment residency backpressure — the sync IS the contract (feeder may stage i+1 only after i−1 drains)
                    outs_all[i - 1]["count"])
                slots.release()
    finally:
        stop.set()                          # unblocks a waiting feeder on
        feeder.join(timeout=STREAM_FEEDER_STALL_S)  # any consumer error
        if feeder.is_alive():
            # the feeder is wedged inside a host→device transfer Python
            # cannot cancel: abandon the daemon thread, record the stall
            # (breaker + flight recorder), and let teardown proceed —
            # raising here would mask a propagating consumer error
            stalled = DeviceStallError(
                "hbm-stream feeder wedged in a host→device transfer; "
                "teardown abandoned the join (thread left to finish)")
            note_device_error(stalled)
            from elasticsearch_tpu.observability import flightrec
            flightrec.note("dispatch-stall", site="upload",
                           lane="streamed", where="feeder-join",
                           wait_seconds=STREAM_FEEDER_STALL_S)
            feed_err.append(stalled)
    if feed_err:
        raise feed_err[0]
    run_segments_streamed.last_stats = stats
    return outs_all


# ---------------------------------------------------------------------------
# Percolation lanes: many registered queries × one probe doc, one dispatch
# ---------------------------------------------------------------------------

def pack_query_consts(consts_rows: list) -> tuple | None:
    """Stack B same-signature queries' ConstTable values into one [B_pad,
    total] buffer per dtype (the _plan_segment_batch packing discipline: two
    packed transfers beat N small ones, and the batch axis pads to the
    next power of two so varying registration counts share programs).
    → (specs, packed, b_pad) or None when the shared plan is const-free
    (the caller runs the program once and broadcasts)."""
    from elasticsearch_tpu.search.batching import pow2_bucket
    b = len(consts_rows)
    b_pad = pow2_bucket(b)
    if b_pad != b:
        consts_rows = consts_rows + [consts_rows[-1]] * (b_pad - b)
    if not consts_rows[0]:
        return None
    specs = []                       # per const: (dtype, offset, shape, size)
    totals: dict[str, int] = {}
    for v in consts_rows[0]:
        dt = str(v.dtype)
        off = totals.get(dt, 0)
        size = int(v.size)
        specs.append((dt, off, v.shape, size))
        totals[dt] = off + size
    packed = {dt: np.empty((b_pad, total), dtype=dt)
              for dt, total in totals.items()}
    for bi, row in enumerate(consts_rows):
        for v, (dt, off, _shape, size) in zip(row, specs):
            packed[dt][bi, off:off + size] = v.reshape(-1)
    return tuple(specs), packed, b_pad


def make_percolate_lane(seg: DeviceSegment, emit, sig: tuple,
                        pos_for: frozenset, vecs_for: frozenset,
                        consts_rows: list, bm25) -> dict:
    """One percolate lane = (one probe segment × one same-signature query
    group): the emit closure of the group's first plan plus every member's
    constants packed on a leading batch axis. `consts_rows` must all share
    `sig` (the caller groups by actual plan signature)."""
    packed_spec = pack_query_consts(consts_rows)
    if packed_spec is None:
        specs, packed, b_pad = (), {}, 1     # const-free: run once, broadcast
    else:
        specs, packed, b_pad = packed_spec
    return {
        "seg": seg, "emit": emit, "specs": specs, "packed": packed,
        "pos": pos_for, "vecs": vecs_for, "b_pad": b_pad,
        "b": len(consts_rows),
        "flat": seg_flatten(seg, pos_for, vecs_for),
        "key": (sig, layout_key(seg), pos_for, vecs_for,
                float(bm25.k1), float(bm25.b), b_pad, specs),
    }


def run_percolate_lanes(lanes: list) -> list:
    """Evaluate percolate lanes as ONE compiled dispatch per PLAN SHAPE:
    lanes sharing a key (plan signature × probe layout × batch bucket) —
    e.g. an _mpercolate's D same-shaped probe docs against the same query
    bucket — stack their segment arrays AND their packed constants on a
    leading axis and run as one doubly-vmapped program (docs × queries).
    Inside each lane the probe segment view rebuilds from traced arrays,
    the group's queries run with their constants unpacked by static
    slicing, and the per-query (matched, score) pair reduces in-program
    (ops/percolate.match_reduce_body) so a whole lane's result crosses
    the link as one small [B, 2] pack.

    Keying per lane (not per lane-SET) is what bounds compiles to ≤1 per
    plan shape: a probe-dependent lane (wildcard expansion differing per
    doc) recompiles alone instead of dragging every stable lane with it.

    → one [b, 2] numpy array per lane (match flag, score), batch padding
    dropped; const-free lanes come back as [1, 2] (callers broadcast)."""
    from elasticsearch_tpu.ops import percolate as perc_ops
    from elasticsearch_tpu.search.batching import pow2_bucket
    if not lanes:
        return []
    groups: dict[tuple, list[int]] = {}
    for i, lane in enumerate(lanes):
        groups.setdefault(lane["key"], []).append(i)
    results: list = [None] * len(lanes)
    pending = []
    for key, idxs in groups.items():
        rep = lanes[idxs[0]]
        n = len(idxs)
        n_pad = pow2_bucket(n)          # stack axis bucketed like the
        padded = idxs + [idxs[-1]] * (n_pad - n)   # query batch axis
        flats = [jnp.stack([lanes[i]["flat"][j] for i in padded])
                 for j in range(len(rep["flat"]))]
        packed = {dt: jnp.stack([jnp.asarray(lanes[i]["packed"][dt])
                                 for i in padded])
                  for dt in rep["packed"]}

        def compile_fn(rep=rep):
            def run(flats_in, packed_in):
                def one(flat_one, packed_one):
                    view = seg_rebuild(rep["seg"], flat_one,
                                       rep["pos"], rep["vecs"])
                    if rep["specs"]:
                        def one_q(pq):
                            consts_one = [
                                pq[dt][off:off + size].reshape(shape)
                                for dt, off, shape, size in rep["specs"]]
                            em = EmitCtx(view, consts_one)
                            scores, mask = rep["emit"](em)
                            return perc_ops.match_reduce_body(
                                scores, mask & view.live)
                        matched, best = jax.vmap(one_q)(packed_one)
                    else:
                        # const-free plan (match_all / match_none
                        # shapes): every query in the group IS the same
                        # program — run once; the host broadcasts
                        em = EmitCtx(view, [])
                        scores, mask = rep["emit"](em)
                        matched, best = perc_ops.match_reduce_body(
                            scores, mask & view.live)
                        matched, best = matched[None], best[None]
                    return perc_ops.pack_match_result_body(matched, best)
                return jax.vmap(one)(flats_in, packed_in)

            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (flats, packed))
            return jax.jit(run).lower(*shapes)

        full_key = ("percolate", key, n_pad)
        with _cache_lock:
            hit = full_key in _cache
            _bump("percolate_program_hits" if hit
                  else "percolate_program_misses")
        fn = _get_compiled(full_key, compile_fn, lane="percolate")
        with device_span("percolate",
                         cost=("percolate", full_key, n, n_pad)):
            device_fault_point("percolate")
            out = fn(flats, packed)     # async dispatch: groups pipeline
        pending.append((idxs, out))
    for idxs, out in pending:
        arr = np.asarray(out)           # [n_pad, b(_pad)|1, 2]
        for row, i in enumerate(idxs):
            lane = lanes[i]
            results[i] = arr[row, :lane["b"]] if lane["specs"] \
                else arr[row]
    return results


# ---------------------------------------------------------------------------
# Impact-ordered lane: quantized eager impacts + block-max pruning
#
# The exact forward kernel recomputes idf·tfNorm per (doc, term) on every
# query. The impact lane reads the quantized per-(term, doc) impacts
# precomputed at segment-build time (index/segment.build_impact_column,
# BM25S-style) — a dense compare + integer sum — and, with block maxima,
# sweeps row blocks in descending upper-bound order skipping blocks that
# cannot reach the running k-th score (ops/blockmax.py). Admission is
# opt-in per index (`index.search.impact_plane`): quantized scores agree
# with the exact scorer only within the documented quantization bound,
# so the exact scorer stays the default.
#
# Device residency rides the PR 5 per-segment block cache
# (mesh_engine._DeviceBlockCache.fetch_aux): a refresh uploads impact
# bytes only for NEW (or drift-requantized) segments, counter-verified
# via data_layer.impact_bytes_{uploaded,reused}.
# ---------------------------------------------------------------------------

from dataclasses import dataclass as _dataclass


@_dataclass(frozen=True)
class ImpactPlaneConfig:
    """Per-index impact-lane knobs (index.search.impact.* settings)."""
    bits: int = 8
    block_rows: int = 2048
    prune: bool = True          # block-max sweep when totals not tracked
    max_terms: int = 64         # T cap (term-batched reduction chunks
                                # keep program size ~T/8, so expansion-
                                # sized queries fit the impact arm)


#: index name → config for indices that opted in (None = lane off)
_impact_configs: dict[str, ImpactPlaneConfig] = {}


def validate_impact_settings(settings) -> tuple:
    """Validate the ``index.search.impact.*`` knobs, raising the
    create-index-time 400 on a bad value — mirroring the store.type
    idiom: a typo must fail the CREATE REQUEST, never reach the
    cluster-state applier, and never surface later as a misleading
    'device-error' fallback when the column build rejects it inside the
    dispatch seam. → (bits, block_rows, max_terms)."""
    from elasticsearch_tpu.common.errors import IllegalArgumentError
    from elasticsearch_tpu.index.segment import (IMPACT_BITS,
                                                 IMPACT_BLOCK_ROWS)
    get = settings.get if settings is not None else (lambda *_: None)

    def setting(name, default):
        raw = get(name, default)
        try:
            return int(default if raw is None or raw == "" else raw)
        except (TypeError, ValueError):
            raise IllegalArgumentError(
                f"{name} must be an integer, got [{raw}]")

    bits = setting("index.search.impact.bits", IMPACT_BITS)
    if bits not in (8, 16):
        raise IllegalArgumentError(
            f"index.search.impact.bits must be 8 or 16, got {bits}")
    block_rows = setting("index.search.impact.block_rows",
                         IMPACT_BLOCK_ROWS)
    if block_rows <= 0 or block_rows & (block_rows - 1):
        raise IllegalArgumentError(
            "index.search.impact.block_rows must be a power of two, "
            f"got {block_rows}")
    max_terms = setting("index.search.impact.max_terms", 64)
    if max_terms < 1:
        raise IllegalArgumentError(
            f"index.search.impact.max_terms must be >= 1, got "
            f"{max_terms}")
    # the packed (Σq·256 + matches) reduction must stay inside int32:
    # the match count needs T ≤ 255 (one byte), and 16-bit impacts need
    # T·65535·256 < 2³¹ → T ≤ 127 (ops/blockmax.impact_scores)
    cap = 127 if bits == 16 else 255
    if max_terms > cap:
        raise IllegalArgumentError(
            f"index.search.impact.max_terms must be <= {cap} at "
            f"{bits}-bit impacts, got {max_terms}")
    return bits, block_rows, max_terms


def configure_impact_plane(index_name: str, settings=None) -> None:
    """Register (or with the setting off, clear) an index's impact-lane
    config from its settings. Called at IndexService construction; tests
    call it directly with a dict. Bad values raise here too
    (validate_impact_settings), but the create-index path validates
    BEFORE the cluster state commits, so the applier never sees them."""
    get = settings.get if settings is not None else (lambda *_: None)
    raw = get("index.search.impact_plane", "false")
    if str(raw).lower() not in ("true", "1"):
        _impact_configs.pop(index_name, None)
        return
    bits, block_rows, max_terms = validate_impact_settings(settings)
    _impact_configs[index_name] = ImpactPlaneConfig(
        bits=bits, block_rows=block_rows, max_terms=max_terms,
        prune=str(get("index.search.impact.prune", "true")).lower()
        in ("true", "1"))


def impact_plane_config(index_name: str | None) -> ImpactPlaneConfig | None:
    if index_name is None:
        return None
    return _impact_configs.get(index_name)


def note_impact_fallback(reason: str) -> None:
    """One impact-lane admission decline (the request proceeds on the
    exact scorer), reason-labeled like note_plane_fallback."""
    lanes.check_reason("impact", reason)
    _attribution.label("impact_fallback", reason)
    with _cache_lock:
        _impact_fallback_reasons[reason] = \
            _impact_fallback_reasons.get(reason, 0) + 1


def note_impact_served(index_name: str | None, n_requests: int,
                       blocks_scored: int, blocks_skipped: int) -> None:
    """`n_requests` served by the impact lane plus its block-sweep work
    accounting (eager-lane requests count every block as scored)."""
    with _cache_lock:
        _bump("impact_admissions", n_requests)
        _bump("impact_blocks_scored", int(blocks_scored))
        _bump("impact_blocks_skipped", int(blocks_skipped))
        if index_name:
            bucket = _impact_index_stats.setdefault(
                index_name, {"admissions": 0, "blocks_scored": 0,
                             "blocks_skipped": 0})
            bucket["admissions"] += n_requests
            bucket["blocks_scored"] += int(blocks_scored)
            bucket["blocks_skipped"] += int(blocks_skipped)


def impact_index_stats(index_name: str) -> dict:
    """One index's impact-lane rollup (zeros when never admitted)."""
    with _cache_lock:
        bucket = dict(_impact_index_stats.get(index_name, {}))
    out = {"admissions": bucket.get("admissions", 0),
           "blocks_scored": bucket.get("blocks_scored", 0),
           "blocks_skipped": bucket.get("blocks_skipped", 0)}
    total = out["blocks_scored"] + out["blocks_skipped"]
    out["skip_ratio"] = round(out["blocks_skipped"] / total, 4) \
        if total else 0.0
    return out


class _ImpactPack:
    """One reader generation's device-resident impact pack for a field:
    per-segment (uterms, qimp, live[, block_max]) device arrays plus the
    host ImpactColumns (term dictionaries + quantization metadata)."""

    __slots__ = ("field", "cfg", "k1", "b", "segs", "bases", "can_prune",
                 "total_blocks", "bound_per_term", "scales",
                 "engine_uuid")

    def __init__(self, field, cfg, k1, b):
        self.field = field
        self.cfg = cfg
        self.k1, self.b = k1, b
        self.segs = []          # dicts per segment (see impact_pack_for)
        self.bases = []
        self.can_prune = True
        self.total_blocks = 0
        self.bound_per_term = 0.0
        self.scales = None      # [S] f32 device constant (compose step)
        self.engine_uuid = None  # cost-table owner (drains on close)

    def sig(self) -> tuple:
        out = [self.field, self.cfg.bits, float(self.k1), float(self.b)]
        for s in self.segs:
            bm = s["block_max"]
            out.append((s["np_docs"], s["u"], str(s["qimp"].dtype),
                        None if bm is None else tuple(bm.shape),
                        s["doc_base"]))
        return tuple(out)


def _impact_global_df(reader, field: str, col) -> "np.ndarray":
    """READER-global df for one segment's term dictionary: the segment's
    own df plus every sibling segment's count for the same term string —
    the cross-segment aggregation the exact scorer does per query term,
    done once per impact build over the whole vocabulary. Vectorized as
    a sorted-terms merge (segment term dictionaries are sorted, see
    TextFieldColumn.terms): O(V log V') numpy per sibling instead of a
    per-term dict-lookup loop, so large vocabularies don't stall the
    refresh path host-side."""
    df = np.asarray(col.df, np.int64).copy()
    if not col.terms:
        return df
    terms = np.asarray(col.terms)
    for other in reader.segments:
        ocol = other.seg.text_fields.get(field)
        if ocol is None or ocol is col or not ocol.terms:
            continue
        oterms = np.asarray(ocol.terms)
        pos = np.minimum(np.searchsorted(oterms, terms),
                         len(oterms) - 1)
        hit = oterms[pos] == terms
        df[hit] += np.asarray(ocol.df, np.int64)[pos[hit]]
    return df


def _host_impact_column(reader, dseg, field: str, cfg: ImpactPlaneConfig,
                        k1: float, b: float, doc_count: int,
                        avgdl: float):
    """The host-side quantized column for one segment, cached ON the
    immutable host Segment (it survives reader swaps, so unchanged
    segments never requantize). A cached column is reused while the
    reader's statistics have drifted less than one quantization step
    from its snapshot; beyond that the segment requantizes against
    fresh statistics (impact_requant_refreshes counts these — the
    tier-1 guard proves steady-state refreshes stay at zero)."""
    from elasticsearch_tpu.index.segment import build_impact_column
    host = dseg.seg
    col = host.text_fields.get(field)
    if col is None:
        return None
    cache = host.__dict__.setdefault("_impact_cache", {})
    ckey = (field, cfg.bits, cfg.block_rows, float(k1), float(b))
    icol = cache.get(ckey)
    if icol is not None:
        # requantize only when the statistics drift could move an
        # impact by more than ONE quantization step (score units) —
        # within a step the error stays inside bound_per_term
        if icol.drift_bound(doc_count, avgdl) <= icol.scale:
            return icol
        with _cache_lock:
            _bump("impact_requant_refreshes")
        quant_gen = icol.quant_gen + 1
    else:
        quant_gen = 0
    icol = build_impact_column(
        col, df=_impact_global_df(reader, field, col),
        doc_count=doc_count, avgdl=avgdl, k1=k1, b=b, bits=cfg.bits,
        block_rows=cfg.block_rows, quant_gen=quant_gen)
    cache[ckey] = icol
    return icol


def impact_pack_for(reader, field: str, cfg: ImpactPlaneConfig,
                    k1: float = 1.2, b: float = 0.75) -> _ImpactPack | None:
    """Build (or fetch the cached) impact pack for one reader generation.

    Device arrays come from the PR 5 per-segment block cache keyed by
    (engine uuid, block_uid, impact signature): unchanged segments reuse
    their resident impact blocks outright — a refresh that adds one
    segment uploads impact bytes only for it (data_layer.impact_bytes_*
    counters prove it). Returns None when no segment carries the field.
    """
    packs = reader.__dict__.setdefault("_impact_packs", {})
    pkey = (field, cfg.bits, cfg.block_rows, float(k1), float(b))
    pack = packs.get(pkey)
    if pack is not None:
        return pack
    st = reader.text_stats(field)
    if st.docs_with_field <= 0:
        return None
    from elasticsearch_tpu.parallel.mesh_engine import (
        fetch_impact_block)
    engine_uuid = getattr(reader, "engine_uuid", None) or \
        f"reader:{id(reader)}"
    breaker_service = getattr(reader, "breaker_service", None)
    pack = _ImpactPack(field, cfg, k1, b)
    pack.engine_uuid = getattr(reader, "engine_uuid", None)
    uploaded = reused = 0
    for dseg in reader.segments:
        icol = _host_impact_column(reader, dseg, field, cfg, k1, b,
                                   st.doc_count, st.avgdl)
        if icol is None:
            continue
        dev_qimp, dev_bm, up, re = fetch_impact_block(
            engine_uuid, dseg.seg.block_uid, field, icol,
            breaker_service)
        uploaded += up
        reused += re
        n_blocks = icol.qimp.shape[0] // icol.block_rows
        pack.segs.append({
            "uterms": _fetch(dseg, dseg.text[field], "uterms"),
            "live": dseg.live,
            "qimp": dev_qimp, "block_max": dev_bm,
            "scale": float(icol.scale), "col": icol,
            "host": dseg.seg.text_fields[field],
            "np_docs": int(icol.qimp.shape[0]),
            "u": int(icol.qimp.shape[1]),
            "doc_base": int(dseg.doc_base),
            "n_blocks": int(n_blocks),
            "block_uid": int(dseg.seg.block_uid),
        })
        pack.bases.append(int(dseg.doc_base))
        pack.total_blocks += int(n_blocks)
        pack.bound_per_term = max(pack.bound_per_term,
                                  icol.bound_per_term)
        if dev_bm is None:
            pack.can_prune = False
    if not pack.segs:
        return None
    note_data_blocks_impact(uploaded, reused)
    # compose step: the pack-level device constants (per-segment dequant
    # scales) the compiled lanes take as inputs — the one device
    # placement the pack itself performs, seamed + span-scoped so the
    # breaker/tracer see it like every other compose
    with device_span("blockmax-compose"):
        device_fault_point("blockmax-compose")
        pack.scales = jnp.asarray([s["scale"] for s in pack.segs],
                                  jnp.float32)
    packs[pkey] = pack
    return pack


def note_data_blocks_impact(uploaded: int, reused: int) -> None:
    """Impact-column block-cache traffic from one pack build."""
    with _cache_lock:
        _data_layer["impact_bytes_uploaded"] += int(uploaded)
        _data_layer["impact_bytes_reused"] += int(reused)


def verify_impact_cursor(pack: _ImpactPack, terms: list, boost: float,
                         search_after) -> tuple | None:
    """Admit a score-order search_after cursor to the impact lane only
    when it was produced by the SAME quantization.

    The lane's in-program continuation compares QUANTIZED scores
    against the cursor score; a cursor minted by the exact scorer
    (page 1 fell back — ineligible batch-mate, breaker open, device
    error) or by a pre-requant quantization differs by up to
    bound_per_term per matched term, which can skip or duplicate hits
    across pages. Provenance is verified by recomputation: the cursor
    doc's quantized score, rebuilt host-side from the pack's resident
    columns (the same integer sum and the same float32
    ``qsum · scale · boost`` arithmetic the compiled lanes run), must
    equal the cursor score bit-for-bit as float32 — true for any cursor
    this lane emitted under the current quant generation, and
    essentially never for an exact-scorer float. Score-only cursors
    (no doc tiebreak) carry nothing to verify against and decline the
    same way.

    Returns the canonical ``(float score, doc id)`` pair to feed the
    compiled continuation, or None → the caller declines admission
    (reason ``cross-lane-cursor``) and the exact scorer serves the
    page."""
    if len(search_after) != 2:
        return None
    doc = int(search_after[1])
    want = np.float32(float(search_after[0]))
    for s in pack.segs:
        base = s["doc_base"]
        if not (base <= doc < base + s["np_docs"]):
            continue
        row = doc - base
        ut = np.asarray(s["host"].uterms[row])
        qi = s["col"].qimp[row].astype(np.int64)
        tidx = s["host"].term_index
        qsum = 0
        for term in terms:
            tid = tidx.get(term, -1)
            if tid >= 0:
                qsum += int(qi[ut == tid].sum())
        scale_boost = np.float32(np.float32(s["scale"]) *
                                 np.float32(boost))
        got = np.float32(np.float32(qsum) * scale_boost)
        return (float(want), doc) if got == want else None
    return None


def _impact_query_inputs(pack: _ImpactPack, term_lists: list,
                         boosts: list, cursors: list):
    """Pack B queries' per-segment term ids / boosts / cursors into the
    lanes' input arrays (batch axis padded to a power of two, term axis
    padded to a shared pow2 bucket so varying term counts share
    programs)."""
    from elasticsearch_tpu.search.batching import pow2_bucket
    b = len(term_lists)
    b_pad = pow2_bucket(b)
    t_pad = pow2_bucket(max(max(len(t) for t in term_lists), 1))
    rows = term_lists + [term_lists[-1]] * (b_pad - b)
    boosts_p = list(boosts) + [boosts[-1]] * (b_pad - b)
    cursors_p = list(cursors) + [cursors[-1]] * (b_pad - b)
    qtids = []
    for s in pack.segs:
        tidx = s["host"].term_index
        arr = np.full((b_pad, t_pad), -1, np.int32)
        for bi, terms in enumerate(rows):
            for ti, term in enumerate(terms):
                arr[bi, ti] = tidx.get(term, -1)
        qtids.append(jnp.asarray(arr))
    cs = jnp.asarray([np.float32(c[0]) if c is not None else
                      np.float32(np.inf) for c in cursors_p])
    cd = jnp.asarray([np.int32(c[1]) if c is not None else -1
                      for c in cursors_p], jnp.int32)
    return qtids, jnp.asarray(boosts_p, jnp.float32), cs, cd, b_pad, t_pad


def run_impact_batch(pack: _ImpactPack, term_lists: list, boosts: list,
                     cursors: list, *, k: int,
                     n_real: int | None = None) -> dict:
    """Eager quantized-impact scoring of B queries over the whole
    reader as ONE compiled program: per-segment dense compare + integer
    gather/sum over the precomputed impacts (no per-doc BM25 float
    math), per-query per-segment top-k, cross-segment merge — the same
    output contract as run_reader_batch's unpacked mode. Counts are
    EXACT (the anyhit mask matches the forward kernel's msm1 mask)."""
    from elasticsearch_tpu.ops import blockmax as bm_ops
    from elasticsearch_tpu.ops import topk as topk_ops
    b = len(term_lists)
    k_static = int(k)
    qtids, boosts_a, cs, cd, b_pad, t_pad = _impact_query_inputs(
        pack, term_lists, boosts, cursors)
    bases = tuple(pack.bases)
    key = ("impact-eager", pack.sig(), k_static, b_pad, t_pad)
    seg_arrs = [(s["uterms"], s["qimp"], s["live"]) for s in pack.segs]

    def compile_fn():
        def run(seg_arrs_in, qtids_in, scales_in, boosts_in, cs_in,
                cd_in):
            ts_list, td_list = [], []
            counts = None
            for i, (ut, qi, lv) in enumerate(seg_arrs_in):
                base = bases[i]

                def one(qt, bo, c1, c2, ut=ut, qi=qi, lv=lv, i=i,
                        base=base):
                    return bm_ops.eager_segment_topk(
                        ut, qi, lv, qt, scales_in[i] * bo, k_static,
                        base, c1, c2)
                ts, td, cnt = jax.vmap(one)(qtids_in[i], boosts_in,
                                            cs_in, cd_in)
                ts_list.append(ts)
                td_list.append(td)
                counts = cnt if counts is None else counts + cnt
            top_s, top_d = topk_ops.merge_top_k_batch_body(
                ts_list, td_list, k_static, bases)
            return {"top_scores": top_s, "top_docs": top_d,
                    "count": counts}

        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (seg_arrs, qtids, pack.scales, boosts_a, cs, cd))
        return jax.jit(run).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="impact-eager",
                       owner=pack.engine_uuid)
    with device_span("dispatch",
                     cost=("impact-eager", key,
                           n_real if n_real is not None else b, b_pad)):
        device_fault_point("dispatch")
        out = fn(seg_arrs, qtids, pack.scales, boosts_a, cs, cd)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out


def run_impact_pruned(pack: _ImpactPack, term_lists: list, boosts: list,
                      cursors: list, *, k: int,
                      n_real: int | None = None) -> dict:
    """Block-max pruned top-k of B queries: blocks sweep in descending
    upper-bound order with the running k-th score as the skip threshold,
    carried ACROSS segments so early segments' candidates prune later
    ones (ops/blockmax.pruned_segment_topk). Queries run under lax.map
    so the skip stays a real branch. Returns the eager lane's output
    contract plus per-query ``blocks_scored``/``blocks_skipped``;
    ``count`` is matched docs in SCORED blocks only (a lower bound —
    admission requires track_total_hits=false)."""
    from elasticsearch_tpu.ops import blockmax as bm_ops
    if not pack.can_prune:
        raise ValueError("pack has segments without block maxima")
    b = len(term_lists)
    k_static = int(k)
    qtids, boosts_a, cs, cd, b_pad, t_pad = _impact_query_inputs(
        pack, term_lists, boosts, cursors)
    bases = tuple(pack.bases)
    key = ("impact-pruned", pack.sig(), k_static, b_pad, t_pad)
    seg_arrs = [(s["uterms"], s["qimp"], s["live"], s["block_max"])
                for s in pack.segs]

    def compile_fn():
        def run(seg_arrs_in, qtids_in, scales_in, boosts_in, cs_in,
                cd_in):
            def per_query(args):
                qts, bo, c1, c2 = args
                carry = bm_ops.pruned_carry_init(k_static)
                for i, (ut, qi, lv, bmx) in enumerate(seg_arrs_in):
                    carry = bm_ops.pruned_segment_topk(
                        carry, ut, qi, lv, bmx, qts[i],
                        scales_in[i] * bo, k_static, bases[i], c1, c2)
                ts, td, n_scored, n_skipped, n_matched = carry
                return {"top_scores": ts, "top_docs": td,
                        "count": n_matched, "blocks_scored": n_scored,
                        "blocks_skipped": n_skipped}
            return jax.lax.map(per_query,
                               (tuple(qtids_in), boosts_in, cs_in,
                                cd_in))

        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (seg_arrs, qtids, pack.scales, boosts_a, cs, cd))
        return jax.jit(run).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="impact-pruned",
                       owner=pack.engine_uuid)
    with device_span("pruning-dispatch",
                     cost=("impact-pruned", key,
                           n_real if n_real is not None else b, b_pad)):
        device_fault_point("pruning-dispatch")
        out = fn(seg_arrs, qtids, pack.scales, boosts_a, cs, cd)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out


def run_impact_rescore(pack: _ImpactPack, term_lists: list,
                       boosts: list, sec_term_lists: list,
                       sec_boosts: list, windows: list, qws: list,
                       rws: list, score_mode: str, *, k: int,
                       n_real: int | None = None) -> dict:
    """The planner's composed impact→rescore plan as ONE compiled
    dispatch: eager quantized candidate generation (primary top-k over
    the whole reader, k already widened to the largest rescore window),
    per-candidate secondary impact scoring via per-segment row gathers,
    and the QueryRescorer window combine + re-sort — all in-program, so
    a rescore request costs one dispatch instead of a primary dispatch
    plus a host re-rank pass (ops/blockmax.rescore_gather /
    rescore_window hold the kernels and the f32 op-order contract).

    Both stages score in the QUANTIZED domain (the impact lane's
    opt-in semantics): the bit-identity oracle is the sequential
    recompute — run_impact_batch primary, host-side secondary from the
    same columns, host window combine in the same float32 order.
    ``score_mode`` is static (part of the program key); windows /
    query weights are traced per-query inputs, so heterogeneous
    windows share one program."""
    from elasticsearch_tpu.ops import blockmax as bm_ops
    from elasticsearch_tpu.ops import topk as topk_ops
    b = len(term_lists)
    k_static = int(k)
    none_cursors = [None] * b
    qtids, boosts_a, cs, cd, b_pad, t_pad = _impact_query_inputs(
        pack, term_lists, boosts, none_cursors)
    qtids2, boosts2_a, _, _, _, t2_pad = _impact_query_inputs(
        pack, sec_term_lists, sec_boosts, none_cursors)

    def pad_b(vals, dtype):
        vals = list(vals) + [vals[-1]] * (b_pad - b)
        return jnp.asarray(np.asarray(vals, dtype))
    windows_a = pad_b(windows, np.int32)
    qws_a = pad_b(qws, np.float32)
    rws_a = pad_b(rws, np.float32)
    bases = tuple(pack.bases)
    key = ("impact-rescore", pack.sig(), k_static, b_pad, t_pad,
           t2_pad, str(score_mode))
    seg_arrs = [(s["uterms"], s["qimp"], s["live"]) for s in pack.segs]

    def compile_fn():
        def run(seg_arrs_in, qtids_in, scales_in, boosts_in, cs_in,
                cd_in, qtids2_in, boosts2_in, windows_in, qw_in,
                rw_in):
            # stage 1: eager primary candidate generation (identical
            # arithmetic to run_impact_batch — the oracle's stage 1)
            ts_list, td_list = [], []
            counts = None
            for i, (ut, qi, lv) in enumerate(seg_arrs_in):
                base = bases[i]

                def one(qt, bo, c1, c2, ut=ut, qi=qi, lv=lv, i=i,
                        base=base):
                    return bm_ops.eager_segment_topk(
                        ut, qi, lv, qt, scales_in[i] * bo, k_static,
                        base, c1, c2)
                ts, td, cnt = jax.vmap(one)(qtids_in[i], boosts_in,
                                            cs_in, cd_in)
                ts_list.append(ts)
                td_list.append(td)
                counts = cnt if counts is None else counts + cnt
            top_s, top_d = topk_ops.merge_top_k_batch_body(
                ts_list, td_list, k_static, bases)
            # stage 2: secondary scoring of the [B, K] candidates —
            # each segment gathers only ITS candidates' rows; summing
            # per-segment contributions composes the reader-wide score
            sec = jnp.zeros(top_s.shape, jnp.float32)
            hit = jnp.zeros(top_s.shape, bool)
            for i, (ut, qi, lv) in enumerate(seg_arrs_in):
                base = bases[i]

                def sec_one(docs_row, qt2, bo2, ut=ut, qi=qi, i=i,
                            base=base):
                    qsum, h = bm_ops.rescore_gather(ut, qi, docs_row,
                                                    qt2, base)
                    return (qsum.astype(jnp.float32) *
                            (scales_in[i] * bo2), h)
                s_i, h_i = jax.vmap(sec_one)(top_d, qtids2_in[i],
                                             boosts2_in)
                sec = sec + s_i
                hit = hit | h_i
            # stage 3: window combine + re-sort (the _apply_rescore
            # contract: tail keeps ORIGINAL unweighted primary scores)
            new_s, new_d = jax.vmap(
                lambda s_, d_, se, h, w, qw, rw:
                bm_ops.rescore_window(s_, d_, se, h, w, qw, rw,
                                      score_mode)
            )(top_s, top_d, sec, hit, windows_in, qw_in, rw_in)
            return {"top_scores": new_s, "top_docs": new_d,
                    "count": counts}

        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (seg_arrs, qtids, pack.scales, boosts_a, cs, cd, qtids2,
             boosts2_a, windows_a, qws_a, rws_a))
        return jax.jit(run).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="impact-rescore",
                       owner=pack.engine_uuid)
    with device_span("rescore-dispatch",
                     cost=("impact-rescore", key,
                           n_real if n_real is not None else b, b_pad)):
        device_fault_point("rescore-dispatch")
        out = fn(seg_arrs, qtids, pack.scales, boosts_a, cs, cd,
                 qtids2, boosts2_a, windows_a, qws_a, rws_a)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out


# ---------------------------------------------------------------------------
# Dense + late-interaction retrieval lane (top-level `knn` search section)
#
# Brute-force exact kNN over HBM-resident vector columns,
# fused MaxSim over rank_vectors token matrices (ops/maxsim.py,
# FLASH-MAXSIM-style block accumulation), and IN-PROGRAM hybrid fusion:
# when a request carries both `knn` and `query`, both lanes score in the
# SAME compiled program and reduce on-device via RRF or weighted-sum, so
# a hybrid query is still ONE device dispatch — no second fan-out, no
# host-side merge.
#
# Device residency rides the PR 5 per-segment block cache
# (mesh_engine.fetch_vector_block): a refresh uploads vector bytes only
# for NEW segments, counter-verified via data_layer.vector_bytes_*.
# `index.knn.quantization: int8` stores the columns int8-dense with a
# per-segment scale/offset snapshot (~4x HBM capacity; scores within the
# stamped quantization bound); f32 stays the exact default.
# ---------------------------------------------------------------------------


@_dataclass(frozen=True)
class KnnPlaneConfig:
    """Per-index knn-lane knobs (`index.knn.*` / `index.search.hybrid.*`
    settings). Unlike the impact plane the lane needs no opt-in — the
    `knn` search section itself is the opt-in."""
    quantization: str = "f32"      # f32 | int8
    fusion_mode: str = "rrf"       # rrf | weighted
    rank_constant: int = 60        # RRF k
    lexical_weight: float = 0.5    # weighted-sum lexical leg weight


#: index name → config (indices without an entry use the defaults)
_knn_configs: dict[str, KnnPlaneConfig] = {}


def validate_knn_settings(settings) -> KnnPlaneConfig:
    """Validate the `index.knn.*` / `index.search.hybrid.*` knobs,
    raising the create-index-time 400 on a bad value (the store.type /
    impact-settings idiom: a typo must fail the CREATE REQUEST, never
    reach the cluster-state applier or surface later as a misleading
    device-error fallback)."""
    from elasticsearch_tpu.common.errors import IllegalArgumentError
    get = settings.get if settings is not None else (lambda *_: None)
    quant = str(get("index.knn.quantization", "f32") or "f32").lower()
    if quant not in ("f32", "int8"):
        raise IllegalArgumentError(
            f"index.knn.quantization must be f32 or int8, got [{quant}]")
    mode = str(get("index.search.hybrid.mode", "rrf") or "rrf").lower()
    if mode not in ("rrf", "weighted"):
        raise IllegalArgumentError(
            f"index.search.hybrid.mode must be rrf or weighted, "
            f"got [{mode}]")
    raw_k0 = get("index.search.hybrid.rank_constant", 60)
    try:
        k0 = int(60 if raw_k0 is None or raw_k0 == "" else raw_k0)
    except (TypeError, ValueError):
        raise IllegalArgumentError(
            f"index.search.hybrid.rank_constant must be an integer, "
            f"got [{raw_k0}]") from None
    if k0 < 1:
        raise IllegalArgumentError(
            f"index.search.hybrid.rank_constant must be >= 1, got {k0}")
    raw_w = get("index.search.hybrid.lexical_weight", 0.5)
    try:
        w = float(0.5 if raw_w is None or raw_w == "" else raw_w)
    except (TypeError, ValueError):
        raise IllegalArgumentError(
            f"index.search.hybrid.lexical_weight must be a number, "
            f"got [{raw_w}]") from None
    if not 0.0 <= w <= 1.0:
        raise IllegalArgumentError(
            f"index.search.hybrid.lexical_weight must be in [0, 1], "
            f"got {w}")
    return KnnPlaneConfig(quantization=quant, fusion_mode=mode,
                          rank_constant=k0, lexical_weight=w)


def configure_knn_plane(index_name: str, settings=None) -> None:
    """Register an index's knn-lane config from its settings (called at
    IndexService construction; tests call it directly with a dict)."""
    _knn_configs[index_name] = validate_knn_settings(settings)


def knn_plane_config(index_name: str | None) -> KnnPlaneConfig:
    if index_name is None:
        return KnnPlaneConfig()
    return _knn_configs.get(index_name) or KnnPlaneConfig()


def note_knn_fallback(reason: str) -> None:
    """One knn/hybrid request served by the eager per-segment fallback
    lane instead of the compiled program, reason-labeled."""
    lanes.check_reason("knn", reason)
    _attribution.label("knn_fallback", reason)
    with _cache_lock:
        _knn_fallback_reasons[reason] = \
            _knn_fallback_reasons.get(reason, 0) + 1


def note_percolate_fallback(reason: str) -> None:
    """One fused-percolate dispatch served by the per-query eager lane
    instead (breaker open / device error), reason-labeled like the
    other lanes so the percolator's declines ride the same taxonomy."""
    lanes.check_reason("percolate", reason)
    with _cache_lock:
        _percolate_fallback_reasons[reason] = \
            _percolate_fallback_reasons.get(reason, 0) + 1


def note_scheduler_batch(n_real: int, pad_rows: int = 0) -> None:
    """One continuous-batching scheduler micro-batch launched:
    ``n_real`` queued requests admitted (pad rows counted separately —
    they are no-op replicas, never delivered)."""
    with _cache_lock:
        _bump("scheduler_batches_launched")
        _bump("scheduler_requests_admitted", int(n_real))
        if pad_rows:
            _bump("scheduler_pad_rows", int(pad_rows))


def note_scheduler_drain() -> None:
    """One scheduler batch's device→host drain completed (launched −
    drained = batches in flight, the pipelining evidence)."""
    with _cache_lock:
        _bump("scheduler_batches_drained")


def note_scheduler_shed(reason: str, n: int = 1) -> None:
    """``n`` requests the scheduler shed instead of queueing toward a
    blown deadline / burning SLO, reason-labeled against the closed
    ``scheduler`` vocabulary like the admission lanes. Sheds also land
    on the flight recorder, burst-coalesced, so a 429 storm is
    diagnosable from ``_nodes/diagnostics`` after the fact."""
    lanes.check_reason("scheduler", reason)
    with _cache_lock:
        _bump("scheduler_requests_shed", int(n))
        _scheduler_shed_reasons[reason] = \
            _scheduler_shed_reasons.get(reason, 0) + int(n)
    from elasticsearch_tpu.observability import flightrec
    flightrec.note_shed(reason, int(n))


def note_planner_fallback(reason: str) -> None:
    """One planner admission outcome that left the compiled arms (or
    rerouted the mesh onto a cheaper arm), reason-labeled against the
    closed ``planner`` vocabulary — the taxonomy that replaced the
    pairwise ``impact-preferred``/``knn-lane`` decline edges."""
    lanes.check_reason("planner", reason)
    _attribution.label("planner_fallback", reason)
    with _cache_lock:
        _bump("planner_fallbacks")
        _planner_fallback_reasons[reason] = \
            _planner_fallback_reasons.get(reason, 0) + 1


def note_planner_plan(n_nodes: int, cold: bool = False) -> None:
    """One batch the query planner priced and routed onto a compiled
    arm (``n_nodes`` composed sub-plan nodes rode ONE dispatch);
    ``cold`` marks a plan priced without any measured EWMA, counted
    apart as ``planner_cold_plans``."""
    with _cache_lock:
        _bump("planner_plans")
        if cold:
            _bump("planner_cold_plans")
    _attribution.label("plan_nodes", str(int(n_nodes)))


def note_rescore_fused(n: int = 1) -> None:
    """``n`` impact→rescore plans served as one composed device
    dispatch (candidate generation + secondary scoring + window
    re-sort in-program, no second dispatch for the rescore pass)."""
    with _cache_lock:
        _bump("rescore_fused_dispatches", int(n))


def note_watchdog_stall() -> None:
    """One registered device wait outlived its predicted envelope (the
    watchdog flight-recorded a ``dispatch-stall`` for it)."""
    with _cache_lock:
        _bump("watchdog_stalls")


def note_watchdog_abandoned() -> None:
    """One stalled wait the watchdog abandoned — the waiter failed over
    while the wedged thread keeps whatever it holds (non-cancellable)."""
    with _cache_lock:
        _bump("watchdog_abandoned")


def note_watchdog_quarantine() -> None:
    """One quarantine entry: repeated stalls held the breaker open with
    reopen gated on the background probe program."""
    with _cache_lock:
        _bump("watchdog_quarantines")


def note_watchdog_probe_reopen() -> None:
    """One quarantine lifted by a successful background probe program."""
    with _cache_lock:
        _bump("watchdog_probe_reopens")


def run_probe_program(device=None) -> float:
    """The watchdog's tiny quarantine probe: one host→device transfer
    plus one dispatched reduction, routed through the SAME fault seam as
    live traffic (``upload`` then ``dispatch`` fault points), so a
    still-wedged device holds the probe exactly like it held the
    request that tripped quarantine. Blocks until the device answers —
    run it from a disposable thread with a bounded join."""
    a = jnp.arange(8, dtype=jnp.float32)
    buf = seam_device_put(a, device, site="upload")
    with device_span("dispatch"):
        device_fault_point("dispatch")
        return float(jnp.dot(buf, buf))


def note_knn_served(index_name: str | None, n_requests: int,
                    fused: int = 0, maxsim: int = 0) -> None:
    """`n_requests` served by the compiled knn lane; `fused` of them
    were hybrid (one fusion dispatch each — the counter the one-dispatch
    acceptance reconciles against request count), `maxsim` scored a
    rank_vectors field."""
    with _cache_lock:
        _bump("knn_admissions", n_requests)
        if fused:
            _bump("fusion_dispatches", fused)
        if maxsim:
            _bump("maxsim_dispatches", maxsim)
        if index_name:
            bucket = _knn_index_stats.setdefault(
                index_name, {"admissions": 0, "fusion_dispatches": 0,
                             "maxsim_dispatches": 0})
            bucket["admissions"] += n_requests
            bucket["fusion_dispatches"] += fused
            bucket["maxsim_dispatches"] += maxsim


def note_match_terms(rows: list) -> None:
    """One reader-batch dispatch whose real rows scored ``rows``: per
    query (real BM25 ``match`` terms, pad terms) — the term bucket's
    price: a pad term is compared with every slot like a real one."""
    with _cache_lock:
        _bump("match_terms_real", sum(r for r, _ in rows))
        _bump("match_terms_padded", sum(p for _, p in rows))


def note_msearch_items(n_items: int, batched: bool) -> None:
    """One shard-side ``_msearch`` of ``n_items``: answered by
    ``query_phase_batch`` in one dispatch, or fallen to the one-by-one
    path."""
    with _cache_lock:
        _bump("msearch_items_batched" if batched
              else "msearch_items_serial", int(n_items))


def note_merge(by_score: bool) -> None:
    """One search item whose shard results the coordinator's
    ``merge_responses`` ordered: by one array sort (score order), or by
    the field-sort comparator."""
    with _cache_lock:
        _bump("merge_items_array" if by_score
              else "merge_items_comparator")


def note_knn_rows(real: int, padded: int) -> None:
    """One knn-lane dispatch of ``real`` request rows and ``padded``
    no-op rows (the power-of-two batch bucket's price: a padded row is
    scored and selected like a real one)."""
    with _cache_lock:
        _bump("knn_rows_real", int(real))
        _bump("knn_rows_padded", int(padded))


def knn_index_stats(index_name: str) -> dict:
    """One index's knn-lane rollup (zeros when never admitted)."""
    with _cache_lock:
        bucket = dict(_knn_index_stats.get(index_name, {}))
    return {"admissions": bucket.get("admissions", 0),
            "fusion_dispatches": bucket.get("fusion_dispatches", 0),
            "maxsim_dispatches": bucket.get("maxsim_dispatches", 0)}


def note_data_blocks_vector(uploaded: int, reused: int) -> None:
    """Vector-column block-cache traffic from one pack build."""
    with _cache_lock:
        _data_layer["vector_bytes_uploaded"] += int(uploaded)
        _data_layer["vector_bytes_reused"] += int(reused)


#: a float32 row whose squared length lies this close to 1 is unit
#: length to float32 rounding: dividing it by its own norm again moves
#: components by an ulp and the cosine by less than 5e-7, so it is kept
#: as it is (and a column of such rows is never copied)
_UNIT_TOL = 1e-6


def _unit_rows(vecs, exists):
    """``vecs`` with every row that exists L2-normalized, float32,
    C-contiguous — ``vecs`` ITSELF when it already is all of that (the
    bulk-installed column of a packed segment: 3 GB a segment that no
    second host copy has to hold). Rows are normed in blocks so the
    float64 temporaries stay small. The last axis is the vector's."""
    import numpy as _np
    v = _np.ascontiguousarray(vecs, dtype=_np.float32)
    flat = v.reshape(-1, v.shape[-1])
    keep = _np.broadcast_to(
        _np.asarray(exists, bool).reshape((-1,) + (1,) * (v.ndim - 2)),
        v.shape[:-1]).reshape(-1)
    out = None
    step = 1 << 16
    for lo in range(0, flat.shape[0], step):
        blk = flat[lo:lo + step]
        n2 = _np.einsum("ij,ij->i", blk, blk, dtype=_np.float64)
        fix = keep[lo:lo + step] & (n2 > 0.0) \
            & (_np.abs(n2 - 1.0) > _UNIT_TOL)
        if not fix.any():
            continue
        if out is None:
            out = flat.copy() if v is vecs or _np.shares_memory(v, vecs) \
                else flat
        rows = _np.flatnonzero(fix) + lo
        out[rows] = (flat[rows] / _np.sqrt(n2[fix])[:, None]).astype(
            _np.float32)
    return v if out is None else out.reshape(v.shape)


def _host_knn_column(host_seg, field: str, quant: str):
    """The host-side knn column for one segment — L2-normalized f32, or
    its int8 quantization — cached ON the immutable host Segment (the
    impact-column discipline: survives reader swaps, so unchanged
    segments never renormalize/requantize). Returns
    (arrays dict, multi: bool, dims) or None when the segment lacks the
    field. ONE normalization and at most one host copy per column: the
    compiled pack builder, the eager fallback lane and the device
    reader's lazy ``vecs`` (DeviceReader._pack_segment) all read this
    entry, the int8 form quantizes the f32 entry's rows, and a column
    whose rows are already unit length is used in place."""
    import numpy as _np
    from elasticsearch_tpu.index.segment import quantize_vectors
    col = host_seg.vector_fields.get(field)
    mcol = host_seg.mvector_fields.get(field)
    if col is None and mcol is None:
        return None
    multi = col is None
    cache = host_seg.__dict__.setdefault("_knn_col_cache", {})
    ckey = (field, quant)
    hit = cache.get(ckey)
    if hit is not None:
        return hit
    if quant == "int8":
        base, _, dims = _host_knn_column(host_seg, field, "f32")
        qcol = quantize_vectors(base["vecs"], dims)
        out = {**base, "vecs": qcol.qvecs, "qcol": qcol,
               "scale": qcol.scale, "offset": qcol.offset}
    else:
        src = mcol if multi else col
        exists = _np.asarray(src.exists, bool)
        out = {"lens": _np.asarray(mcol.lens, _np.int32) if multi else None,
               "exists": exists, "vecs": _unit_rows(src.vecs, exists),
               "qcol": None, "scale": 1.0, "offset": 0.0}
        dims = src.dims
    entry = (out, multi, dims)
    cache[ckey] = entry
    return entry


class _VectorPack:
    """One reader generation's device-resident knn pack for a field:
    per-segment vector arrays (f32 or int8 + scale/offset snapshot)
    riding the per-segment block cache, aligned 1:1 with the reader's
    segments (None entries for segments without the field)."""

    __slots__ = ("field", "quant", "multi", "dims", "segs", "scales",
                 "offsets")

    def __init__(self, field, quant):
        self.field = field
        self.quant = quant
        self.multi = False
        self.dims = 0
        self.segs = []          # per reader segment: dict | None
        self.scales = None      # [S_present] f32 device (compose step)
        self.offsets = None

    def sig(self) -> tuple:
        out = [self.field, self.quant, self.multi, self.dims]
        for s in self.segs:
            if s is None:
                out.append(None)
            else:
                out.append((s["np_docs"], s.get("t", 0),
                            str(s["vecs"].dtype), s["doc_base"]))
        return tuple(out)

    def score_bound(self, qn) -> float:
        """Worst per-segment quantization score bound for one query
        (0.0 under f32) — the stamped int8 recall envelope."""
        bound = 0.0
        for s in self.segs:
            if s is not None and s.get("qcol") is not None:
                bound = max(bound, s["qcol"].score_bound(qn))
        return bound


def vector_pack_for(reader, field: str,
                    cfg: KnnPlaneConfig) -> _VectorPack | None:
    """Build (or fetch the cached) knn vector pack for one reader
    generation. Device arrays come from the PR 5 per-segment block
    cache keyed (engine uuid, block_uid, vector sig): unchanged
    segments reuse their resident vector blocks outright — a refresh
    that adds one segment uploads vector bytes only for it
    (data_layer.vector_bytes_* counters prove it). Returns None when no
    segment carries the field."""
    packs = reader.__dict__.setdefault("_vector_packs", {})
    pkey = (field, cfg.quantization)
    pack = packs.get(pkey)
    if pack is not None:
        return pack
    from elasticsearch_tpu.parallel.mesh_engine import fetch_vector_block
    engine_uuid = getattr(reader, "engine_uuid", None) or \
        f"reader:{id(reader)}"
    breaker_service = getattr(reader, "breaker_service", None)
    pack = _VectorPack(field, cfg.quantization)
    uploaded = reused = 0
    any_field = False
    # the whole build — host norms, the block cache's uploads — is one
    # ``jit.upload`` stretch of the span ring (each segment's transfer
    # is a ``jit.vector-upload`` child inside fetch_vector_block)
    with device_span("upload"):
        for dseg in reader.segments:
            entry = _host_knn_column(dseg.seg, field, cfg.quantization)
            if entry is None:
                pack.segs.append(None)
                continue
            host, multi, dims = entry
            any_field = True
            pack.multi = multi
            pack.dims = dims
            arrs, up, re = fetch_vector_block(
                engine_uuid, dseg.seg.block_uid, field,
                (cfg.quantization, multi), lambda h=host: [
                    h["vecs"], h["exists"], h["lens"]], breaker_service)
            uploaded += up
            reused += re
            dev_vecs, dev_exists = arrs[0], arrs[1]
            dev_lens = arrs[2] if multi else None
            pack.segs.append({
                "vecs": dev_vecs, "exists": dev_exists, "lens": dev_lens,
                "live": dseg.live, "qcol": host["qcol"],
                "scale": float(host["scale"]),
                "offset": float(host["offset"]),
                "np_docs": int(dseg.padded_docs),
                "t": int(host["vecs"].shape[1]) if multi else 0,
                "doc_base": int(dseg.doc_base),
                "block_uid": int(dseg.seg.block_uid),
            })
    if not any_field:
        return None
    note_data_blocks_vector(uploaded, reused)
    # compose step: per-segment dequant scale/offset device constants
    # the compiled lanes take as inputs (seamed + span-scoped like the
    # impact pack's scales)
    present = [s for s in pack.segs if s is not None]
    with device_span("compose"):
        device_fault_point("compose")
        pack.scales = jnp.asarray([s["scale"] for s in present],
                                  jnp.float32)
        pack.offsets = jnp.asarray([s["offset"] for s in present],
                                   jnp.float32)
    packs[pkey] = pack
    return pack


def _rrf_fuse_body(ls, ld, ds, dd, boosts, k0: float, k: int):
    """In-program reciprocal-rank fusion of two candidate rankings.

    ls/ld: lexical (scores, GLOBAL doc ids) [B, C]; ds/dd: knn lane
    [B, C]; boosts: [B] knn-lane contribution multiplier. Each doc's
    fused score is the f32 sum of its per-lane ``1/(k0 + rank + 1)``
    contributions — each lane's lists carry unique docs, so a doc gets
    at most two contributions and the sum is order-exact in f32,
    matching the host fusion oracle bit-for-bit. Final top-k orders by
    (score desc, doc asc) — ops/blockmax.merge_topk_by_doc.

    NOTE: blockmax is imported at MODULE level, deliberately — this
    body runs under an active trace, and a first-import there would
    execute blockmax's module-level jnp constants inside the trace,
    caching foreign tracers into its globals (observed as 'compiled
    for N+3 inputs' failures on concurrent multi-shard searches)."""
    bm_ops = blockmax_ops
    c = ld.shape[1]
    rk = 1.0 / (jnp.float32(k0) + jnp.arange(c, dtype=jnp.float32) + 1.0)
    valid_l = ld >= 0
    valid_d = dd >= 0
    r_l = jnp.where(valid_l, rk[None, :], 0.0)
    r_d = jnp.where(valid_d, rk[None, :] * boosts[:, None], 0.0)
    eq = (ld[:, :, None] == dd[:, None, :]) & valid_l[:, :, None] \
        & valid_d[:, None, :]
    f_l = r_l + (eq * r_d[:, None, :]).sum(axis=2)
    f_d = r_d + (eq * r_l[:, :, None]).sum(axis=1)
    dup_d = eq.any(axis=1)
    s_l = jnp.where(valid_l, f_l, -jnp.inf)
    s_d = jnp.where(valid_d & ~dup_d, f_d, -jnp.inf)
    count = valid_l.sum(axis=1, dtype=jnp.int32) + \
        (valid_d & ~dup_d).sum(axis=1, dtype=jnp.int32)

    def one(sl, dl, sd, dd_):
        return bm_ops.merge_topk_by_doc(sl, dl, sd, dd_, k)
    ts, td = jax.vmap(one)(s_l, ld, s_d, dd)
    return ts, td, count


def _weighted_fuse_body(ls, ld, ds, dd, boosts, w_lex: float, k: int):
    """In-program weighted-sum fusion: each leg min-max-normalizes over
    its candidate list (the models/hybrid.py linear mode), then
    ``w·lex + (1-w)·boost·knn`` sums per doc. (Module-level blockmax
    import: see the note in :func:`_rrf_fuse_body`.)"""
    bm_ops = blockmax_ops
    valid_l = ld >= 0
    valid_d = dd >= 0

    def norm(s, valid):
        lo = jnp.where(valid, s, jnp.inf).min(axis=1, keepdims=True)
        hi = jnp.where(valid, s, -jnp.inf).max(axis=1, keepdims=True)
        rng = hi - lo
        rng = jnp.where((rng > 0) & jnp.isfinite(rng), rng, 1.0)
        lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
        return jnp.where(valid, (s - lo) / rng, 0.0)
    r_l = jnp.float32(w_lex) * norm(ls, valid_l)
    r_d = (1.0 - jnp.float32(w_lex)) * boosts[:, None] * norm(ds, valid_d)
    eq = (ld[:, :, None] == dd[:, None, :]) & valid_l[:, :, None] \
        & valid_d[:, None, :]
    f_l = r_l + (eq * r_d[:, None, :]).sum(axis=2)
    f_d = r_d + (eq * r_l[:, :, None]).sum(axis=1)
    dup_d = eq.any(axis=1)
    s_l = jnp.where(valid_l, f_l, -jnp.inf)
    s_d = jnp.where(valid_d & ~dup_d, f_d, -jnp.inf)
    count = valid_l.sum(axis=1, dtype=jnp.int32) + \
        (valid_d & ~dup_d).sum(axis=1, dtype=jnp.int32)

    def one(sl, dl, sd, dd_):
        return bm_ops.merge_topk_by_doc(sl, dl, sd, dd_, k)
    ts, td = jax.vmap(one)(s_l, ld, s_d, dd)
    return ts, td, count


def _plan_knn_segment(dseg, ctx, reqs):
    """Resolve one segment's per-request lexical query (hybrid) and knn
    filter into emit closures + packed constants. → plan dict or None
    when the requests do not share one plan signature."""
    sig0 = None
    emit_q0 = emit_f0 = None
    pos_for: frozenset = frozenset()
    vecs_for: frozenset = frozenset()
    consts_rows = []
    for req in reqs:
        ct = ConstTable()
        resolver = SegmentResolver(dseg, ctx, ct)
        knn = req.knn
        emit_q = resolver.resolve(req.query) if knn.hybrid else None
        emit_f = resolver.resolve_mask(knn.filter) \
            if knn.filter is not None else None
        ct.static("knn-lane", knn.hybrid, knn.filter is not None)
        sig = ct.signature()
        if sig0 is None:
            sig0, emit_q0, emit_f0 = sig, emit_q, emit_f
            pos_for = frozenset(ct.positions_needed)
            vecs_for = frozenset(ct.vectors_needed)
        elif sig != sig0:
            return None
        consts_rows.append(ct.values)
    packed_spec = pack_query_consts(consts_rows)
    if packed_spec is None:
        specs, packed, b_pad = (), {}, None    # const-free plans
    else:
        specs, packed, b_pad = packed_spec
    return {
        "seg": dseg, "sig": sig0, "emit_q": emit_q0, "emit_f": emit_f0,
        "specs": specs, "packed": packed, "b_pad": b_pad,
        "pos": pos_for, "vecs": vecs_for,
        "flat": seg_flatten(dseg, pos_for, vecs_for),
        "key": (sig0, layout_key(dseg), pos_for, vecs_for),
    }


def _knn_query_inputs(reqs, pack):
    """Stack B requests' query vectors / boosts on a padded batch axis.
    → (qv, qmask | None, boosts, b_pad). Dense: qv [B_pad, D] f32
    row-normalized. Multi (rank_vectors): qv [B_pad, Qt_pad, D] with
    per-token normalization and qmask [B_pad, Qt_pad]."""
    from elasticsearch_tpu.search.batching import pow2_bucket
    b = len(reqs)
    b_pad = pow2_bucket(b)
    rows = [req.knn for req in reqs]
    rows = rows + [rows[-1]] * (b_pad - b)
    boosts = np.asarray([kn.boost for kn in rows], np.float32)
    if not pack.multi:
        qv = np.zeros((b_pad, pack.dims), np.float32)
        for i, kn in enumerate(rows):
            v = np.asarray(kn.query_vector, np.float32)
            qv[i] = v / max(float(np.linalg.norm(v)), 1e-12)
        return jnp.asarray(qv), None, jnp.asarray(boosts), b_pad
    qt_pad = pow2_bucket(max(
        max(len(kn.query_vector) for kn in rows), 1))
    qv = np.zeros((b_pad, qt_pad, pack.dims), np.float32)
    qmask = np.zeros((b_pad, qt_pad), bool)
    for i, kn in enumerate(rows):
        m = np.asarray(kn.query_vector, np.float32)
        norms = np.linalg.norm(m, axis=1, keepdims=True)
        qv[i, :m.shape[0]] = m / np.maximum(norms, 1e-12)
        qmask[i, :m.shape[0]] = True
    return jnp.asarray(qv), jnp.asarray(qmask), jnp.asarray(boosts), b_pad


def run_knn_hybrid_batch(reader, ctx, reqs, pack: _VectorPack,
                         cfg: KnnPlaneConfig, *, k: int,
                         num_candidates: int, n_real: int | None = None):
    """B knn (or hybrid BM25+knn) requests over the whole reader as ONE
    compiled program.

    Per segment: the knn lane scores the vector column (dense cosine
    matmul, int8-dequantized matmul, or fused MaxSim over rank_vectors)
    masked by exists ∧ live ∧ the request's `filter`; a hybrid request's
    lexical lane scores the SAME segment view through the standard emit
    closures under the same vmap. Each lane keeps its global
    top-`num_candidates` (per-segment top-C, cross-segment device
    merge), and hybrid requests reduce the two rankings on-device via
    RRF (`rank_constant`) or weighted-sum — the whole thing is one
    dispatch and one device→host fetch.

    Returns {"top_scores" [B, k], "top_docs" [B, k], "count" [B]} or
    None when the batch is not homogeneous (mixed plan signatures —
    callers retry per-request)."""
    from elasticsearch_tpu.ops import maxsim as maxsim_ops
    from elasticsearch_tpu.ops import vector as vector_ops
    segments = reader.segments
    if not segments or not reqs:
        return None
    hybrid = reqs[0].knn.hybrid
    b = len(reqs)
    k_static = int(k)
    c_static = int(num_candidates)
    need_seg = hybrid or any(r.knn.filter is not None for r in reqs)
    plans = None
    if need_seg:
        plans = []
        for dseg in segments:
            plan = _plan_knn_segment(dseg, ctx, reqs)
            if plan is None:
                return None
            plans.append(plan)
    with span("jit.pack"):
        # host work: B query vectors parsed, normed and stacked
        qv, qmask, boosts, b_pad = _knn_query_inputs(reqs, pack)
    if need_seg:
        # const rows pad to the SAME bucket as the query vectors
        for plan in plans:
            if plan["b_pad"] is not None and plan["b_pad"] != b_pad:
                return None
    bases = tuple(int(s.doc_base) for s in segments)
    vec_bases = tuple(s["doc_base"] for s in pack.segs if s is not None)
    fusion_key = (cfg.fusion_mode, int(cfg.rank_constant),
                  float(cfg.lexical_weight)) if hybrid else None
    key = ("knn", pack.sig(), hybrid, need_seg, bases, k_static,
           c_static, b_pad,
           None if qmask is None else tuple(qmask.shape), fusion_key,
           tuple(p["key"] for p in plans) if need_seg else None,
           tuple(tuple(p["specs"]) for p in plans) if need_seg else None)
    flats = [p["flat"] for p in plans] if need_seg else []
    packeds = [{dt: jnp.asarray(buf) for dt, buf in p["packed"].items()}
               for p in plans] if need_seg else []
    vec_arrs = [() if s is None else
                ((s["vecs"], s["exists"], s["live"]) if not pack.multi
                 else (s["vecs"], s["exists"], s["live"], s["lens"]))
                for s in pack.segs]

    def compile_fn():
        def run(flats_in, packeds_in, vec_in, scales_in, offsets_in,
                qv_in, qmask_in, boosts_in):
            # ---- per-segment lexical scores / filter masks ----------
            lex_ts, lex_td = [], []
            fmasks = [None] * len(segments)
            if need_seg:
                for i, (plan, flat_in, packed_in) in enumerate(
                        zip(plans, flats_in, packeds_in)):
                    view = seg_rebuild(plan["seg"], flat_in,
                                       plan["pos"], plan["vecs"])

                    def lane(packed_one, plan=plan, view=view):
                        consts_one = [
                            packed_one[dt][off:off + size].reshape(shape)
                            for dt, off, shape, size in plan["specs"]]
                        em = EmitCtx(view, consts_one)
                        out = {}
                        if plan["emit_q"] is not None:
                            scores, mask = plan["emit_q"](em)
                            mask = mask & view.live
                            ts, td = topk_ops.top_k(
                                scores, mask,
                                min(c_static, view.padded_docs), 0)
                            out["ts"], out["td"] = ts, td
                        if plan["emit_f"] is not None:
                            out["fmask"] = plan["emit_f"](em)
                        return out

                    if plan["specs"]:
                        outs = jax.vmap(lane)(packed_in)
                    else:
                        # const-free plans: every request is the same
                        # program — run once, broadcast the batch axis
                        one = lane({})
                        outs = {kk: jnp.broadcast_to(
                            v, (b_pad,) + v.shape)
                            for kk, v in one.items()}
                    if hybrid:
                        lex_ts.append(outs["ts"])
                        lex_td.append(outs["td"])
                    if "fmask" in outs:
                        fmasks[i] = outs["fmask"]
            # ---- per-segment knn candidates -------------------------
            knn_ts, knn_td = [], []
            knn_counts = jnp.zeros(b_pad, jnp.int32)
            vi = 0
            for i, arrs in enumerate(vec_in):
                if not arrs:
                    continue
                if pack.multi:
                    vecs, exists, live, lens = arrs
                else:
                    vecs, exists, live = arrs
                with jax.named_scope("knn_score"):
                    if pack.multi and pack.quant == "int8":
                        scores = maxsim_ops.maxsim_scores_int8_batch_body(
                            vecs, scales_in[vi], offsets_in[vi], lens,
                            qv_in, qmask_in)
                    elif pack.multi:
                        scores = maxsim_ops.maxsim_scores_batch_body(
                            vecs, lens, qv_in, qmask_in)
                    elif pack.quant == "int8":
                        scores = vector_ops.cosine_scores_int8_batch(
                            vecs, scales_in[vi], offsets_in[vi], exists,
                            qv_in)
                    else:
                        # float32 at Precision.HIGHEST (ops/vector.py)
                        scores = vector_ops.unit_scores_batch(
                            vecs, exists, qv_in)
                    if not hybrid:
                        # knn-only: the section boost scales the
                        # reported scores (rank-preserving — boost > 0
                        # validated)
                        scores = scores * boosts_in[:, None]
                with jax.named_scope("knn_select"):
                    elig = exists & live
                    masks = jnp.broadcast_to(elig[None, :],
                                             (b_pad, elig.shape[0]))
                    if fmasks[i] is not None:
                        masks = masks & fmasks[i]
                    ts, td = vector_ops.filtered_topk_batch(
                        scores, masks, min(c_static, elig.shape[0]), 0)
                    knn_counts = knn_counts + masks.sum(axis=1,
                                                        dtype=jnp.int32)
                knn_ts.append(ts)
                knn_td.append(td)
                vi += 1
            with jax.named_scope("knn_merge"):
                ds, dd = topk_ops.merge_top_k_batch_body(
                    knn_ts, knn_td, c_static, vec_bases)
            if not hybrid:
                ts, td = ds[:, :k_static], dd[:, :k_static]
                return {"top_scores": ts, "top_docs": td,
                        "count": knn_counts}
            ls, ld = topk_ops.merge_top_k_batch_body(
                lex_ts, lex_td, c_static, bases)
            if cfg.fusion_mode == "weighted":
                ts, td, count = _weighted_fuse_body(
                    ls, ld, ds, dd, boosts_in,
                    float(cfg.lexical_weight), k_static)
            else:
                ts, td, count = _rrf_fuse_body(
                    ls, ld, ds, dd, boosts_in,
                    float(cfg.rank_constant), k_static)
            return {"top_scores": ts, "top_docs": td, "count": count}

        args = (flats, packeds, vec_arrs, pack.scales, pack.offsets,
                qv, qmask if qmask is not None else jnp.zeros(0, bool),
                boosts)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        def run_outer(*a):
            return run(a[0], a[1], a[2], a[3], a[4], a[5],
                       a[6] if qmask is not None else None, a[7])
        return jax.jit(run_outer).lower(*shapes)

    fn = _get_compiled(key, compile_fn, lane="knn",
                       owner=getattr(reader, "engine_uuid", None))
    args = (flats, packeds, vec_arrs, pack.scales, pack.offsets,
            qv, qmask if qmask is not None else jnp.zeros(0, bool),
            boosts)
    rows_real = n_real if n_real is not None else b
    note_knn_rows(rows_real, b_pad - rows_real)
    cost = ("knn", key, rows_real, b_pad)
    if hybrid:
        with device_span("fusion-dispatch", cost=cost):
            device_fault_point("fusion-dispatch")
            out = fn(*args)
    elif pack.multi:
        with device_span("maxsim-dispatch", cost=cost):
            device_fault_point("maxsim-dispatch")
            out = fn(*args)
    else:
        with device_span("dispatch", cost=cost):
            device_fault_point("dispatch")
            out = fn(*args)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out


# ---------------------------------------------------------------------------
# Mesh-sharded retrieval lanes: the impact and knn/hybrid lanes served by
# a pod slice as ONE compiled shard_map program.
#
# Partitioning: each segment's impact rows / block-max tables / vector
# columns are doc-axis sharded over the mesh's ``shard`` axis through the
# placement-aware block cache (mesh_engine.fetch_placed_block — blocks
# pinned to owning devices, refresh deltas routed to the owner only),
# while the query batch shards over ``dp``. In-program, each shard runs
# the SAME per-segment kernels the single-chip lanes run on its local
# rows, then the per-shard top-k candidate lists (GLOBAL doc ids)
# all_gather over ICI and re-select under the identical
# (score desc, doc asc) order — so mesh-served results are bit-identical
# to the single-chip lanes (tests/test_mesh_lanes.py fuzzes the
# equivalence across geometries, delete churn and refresh). The pruned
# sweep additionally exchanges the running k-th score across chips
# (ops/blockmax.pruned_segment_topk_mesh's θ-exchange rounds) so
# cross-chip pruning stays conservative.
#
# The serving mesh is an OPT-IN module hook (set_serving_mesh): when no
# mesh is installed every production path is byte-for-byte the
# single-chip lane — the hook gates phase routing, scheduler shape keys
# and planner pricing.
# ---------------------------------------------------------------------------

_serving_mesh = None


def set_serving_mesh(mesh) -> None:
    """Install (or with None, remove) the pod-slice serving mesh the
    retrieval lanes shard over. Returns nothing; callers own clearing
    the program cache when they swap geometries mid-process (the
    program keys carry the geometry, so stale entries are merely
    unused, never wrong)."""
    global _serving_mesh
    _serving_mesh = mesh


def serving_mesh():
    """The installed serving mesh, or None (single-chip serving)."""
    return _serving_mesh


def mesh_geom(mesh) -> tuple:
    """The geometry component mesh-lane program keys and scheduler
    shape buckets carry: axis sizes + flat device ids, so the same
    request shape on two geometries compiles (at most) twice and never
    aliases across device re-enumeration."""
    return (tuple(sorted((str(k), int(v))
                         for k, v in mesh.shape.items())),
            tuple(int(d.id) for d in mesh.devices.flat))


def note_data_blocks_placed(uploaded: int, reused: int) -> None:
    """Placed-block (mesh-lane) cache traffic from one lane build."""
    with _cache_lock:
        _data_layer["placement_bytes_uploaded"] += int(uploaded)
        _data_layer["placement_bytes_reused"] += int(reused)


def _pad_batch_rows(arrs: list, b_new: int) -> list:
    """Pad each array's leading (batch) axis to ``b_new`` by repeating
    the last row — the dp-divisibility companion of the pow2 batch
    bucket (padded rows are trimmed from the output like pad rows)."""
    out = []
    for a in arrs:
        extra = b_new - a.shape[0]
        out.append(a if extra == 0 else
                   jnp.concatenate([a, jnp.repeat(a[-1:], extra,
                                                  axis=0)]))
    return out


def _mesh_place(tree, mesh, spec, kind: str):
    """Commit query-side operands to the serving mesh (dp-sharded batch
    consts or replicated scalars) under the plane's upload seam, so
    chaos injection and the tracer see the transfer like every other
    host→device move."""
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, spec)
    leaves = jax.tree.leaves(tree)
    with device_span("upload") as dsp:
        device_fault_point("upload")
        out = jax.tree.map(lambda a: jax.device_put(a, sh), tree)
        dsp.set(bytes=int(sum(int(a.nbytes) for a in leaves)),
                kind=kind)
    return out


def _placed_impact_arrays(reader, pack: _ImpactPack, mesh) -> list:
    """Per-segment placed (uterms, qimp, live[, block_max]) device
    arrays for the mesh impact lane: rows pad to a whole number of
    blocks per shard (appended blocks carry all-zero block_max rows →
    never swept; pad rows are uterms=-1/live=False → never match), then
    pin to owning devices through the placement-aware block cache. A
    refresh re-ships only the shard slices that changed (the
    placement_bytes_* counters prove it)."""
    from elasticsearch_tpu.parallel.mesh_engine import fetch_placed_block
    s_axis = int(mesh.shape["shard"])
    engine_uuid = getattr(reader, "engine_uuid", None) or \
        f"reader:{id(reader)}"
    breaker_service = getattr(reader, "breaker_service", None)
    seg_arrs = []
    uploaded = reused = 0
    for s in pack.segs:
        icol = s["col"]
        n_blocks = s["n_blocks"]
        r = s["np_docs"] // n_blocks
        nb_pad = -(-n_blocks // s_axis) * s_axis
        rows_pad = nb_pad * r
        has_bm = s["block_max"] is not None

        def build(s=s, nb_pad=nb_pad, rows_pad=rows_pad,
                  n_blocks=n_blocks, has_bm=has_bm):
            pad = rows_pad - s["np_docs"]
            ut = np.pad(np.asarray(s["uterms"]), ((0, pad), (0, 0)),
                        constant_values=-1)
            qi = np.pad(np.asarray(s["qimp"]), ((0, pad), (0, 0)))
            lv = np.pad(np.asarray(s["live"]), (0, pad))
            out = [ut, qi, lv]
            if has_bm:
                out.append(np.pad(np.asarray(s["block_max"]),
                                  ((0, nb_pad - n_blocks), (0, 0))))
            return out

        sig = ("impact-mesh", pack.field, pack.cfg.bits,
               icol.block_rows, icol.quant_gen, has_bm, nb_pad)
        arrs, up, re = fetch_placed_block(
            mesh, engine_uuid, s["block_uid"], sig, build,
            breaker_service, component="impact")
        seg_arrs.append(tuple(arrs))
        uploaded += up
        reused += re
    note_data_blocks_placed(uploaded, reused)
    return seg_arrs


def run_impact_mesh(reader, pack: _ImpactPack, mesh, term_lists: list,
                    boosts: list, cursors: list, *, k: int,
                    prune: bool = False,
                    n_real: int | None = None) -> dict:
    """The impact lane served by the pod slice as ONE compiled
    shard_map dispatch: impact columns and block-max tables doc-axis
    sharded over ``shard``, the query batch over ``dp``; per-shard
    sweeps (eager, or block-max pruned with cross-chip θ-exchange),
    then an in-program all_gather + re-top-k merge. Output contract
    and bits match run_impact_batch / run_impact_pruned exactly —
    except the pruned lane's blocks_scored/blocks_skipped, which
    depend on how much the exchanged θ pruned (counts stay exact
    partitions for the eager lane, psum'd)."""
    from jax.sharding import PartitionSpec as P
    from elasticsearch_tpu.parallel.mesh import shard_map_compat
    if prune and not pack.can_prune:
        raise ValueError("pack has segments without block maxima")
    b = len(term_lists)
    k_static = int(k)
    dp = int(mesh.shape["dp"])
    qtids, boosts_a, cs, cd, b_pad, t_pad = _impact_query_inputs(
        pack, term_lists, boosts, cursors)
    b_pad_m = -(-b_pad // dp) * dp
    if b_pad_m != b_pad:
        qtids = _pad_batch_rows(qtids, b_pad_m)
        boosts_a, cs, cd = _pad_batch_rows([boosts_a, cs, cd], b_pad_m)
        b_pad = b_pad_m
    placed = _placed_impact_arrays(reader, pack, mesh)
    seg_arrs = tuple(a if prune else a[:3] for a in placed)
    bases = tuple(pack.bases)
    geom = mesh_geom(mesh)
    key = ("impact-mesh", pack.sig(), k_static, b_pad, t_pad,
           bool(prune), geom)
    qtids = _mesh_place(qtids, mesh, P("dp"), "mesh-query-consts")
    boosts_a, cs, cd = _mesh_place([boosts_a, cs, cd], mesh, P("dp"),
                                   "mesh-query-consts")
    scales = _mesh_place(pack.scales, mesh, P(), "mesh-scales")

    def compile_fn():
        def step_local(seg_in, qtids_in, scales_in, boosts_in, cs_in,
                       cd_in):
            sidx = jax.lax.axis_index("shard")
            if prune:
                def per_query(args):
                    qts, bo, c1, c2 = args
                    carry = blockmax_ops.pruned_carry_init(k_static)
                    for i, (ut, qi, lv, bmx) in enumerate(seg_in):
                        base = bases[i] + sidx * ut.shape[0]
                        carry = blockmax_ops.pruned_segment_topk_mesh(
                            carry, ut, qi, lv, bmx, qts[i],
                            scales_in[i] * bo, k_static, base, c1, c2)
                    return carry
                ts, td, n_scored, n_skipped, n_matched = jax.lax.map(
                    per_query,
                    (tuple(qtids_in), boosts_in, cs_in, cd_in))
                out = {"count": jax.lax.psum(n_matched, "shard"),
                       "blocks_scored": jax.lax.psum(n_scored, "shard"),
                       "blocks_skipped": jax.lax.psum(n_skipped,
                                                      "shard")}
            else:
                ts_list, td_list, base_list = [], [], []
                counts = None
                for i, (ut, qi, lv) in enumerate(seg_in):
                    base = bases[i] + sidx * ut.shape[0]

                    def one(qt, bo, c1, c2, ut=ut, qi=qi, lv=lv, i=i,
                            base=base):
                        return blockmax_ops.eager_segment_topk(
                            ut, qi, lv, qt, scales_in[i] * bo,
                            k_static, base, c1, c2)
                    s_i, d_i, cnt = jax.vmap(one)(qtids_in[i],
                                                  boosts_in, cs_in,
                                                  cd_in)
                    ts_list.append(s_i)
                    td_list.append(d_i)
                    base_list.append(base)
                    counts = cnt if counts is None else counts + cnt
                ts, td = topk_ops.merge_top_k_batch_body(
                    ts_list, td_list, k_static, tuple(base_list))
                out = {"count": jax.lax.psum(counts, "shard")}
            # cross-chip merge: gather every shard's candidate list
            # (GLOBAL doc ids) over ICI and re-select under the same
            # (score desc, doc asc) order — bit-identical to 1-chip
            # because a global-top-k doc is always in its own shard's
            # local top-k
            ag_s = jax.lax.all_gather(ts, "shard")
            ag_d = jax.lax.all_gather(td, "shard")
            bl = ts.shape[0]
            flat_s = jnp.moveaxis(ag_s, 0, 1).reshape(bl, -1)
            flat_d = jnp.moveaxis(ag_d, 0, 1).reshape(bl, -1)

            def refine(s_row, d_row):
                return blockmax_ops.topk_flat_by_doc(s_row, d_row,
                                                     k_static)
            out["top_scores"], out["top_docs"] = jax.vmap(refine)(
                flat_s, flat_d)
            return out

        seg_specs = tuple(tuple(P("shard") for _ in arrs)
                          for arrs in seg_arrs)
        out_specs = {"top_scores": P("dp"), "top_docs": P("dp"),
                     "count": P("dp")}
        if prune:
            out_specs["blocks_scored"] = P("dp")
            out_specs["blocks_skipped"] = P("dp")
        mapped = shard_map_compat(
            step_local, mesh=mesh,
            in_specs=(seg_specs, [P("dp")] * len(qtids), P(),
                      P("dp"), P("dp"), P("dp")),
            out_specs=out_specs)
        return jax.jit(mapped).lower(seg_arrs, qtids, scales,
                                     boosts_a, cs, cd)

    fn = _get_compiled(key, compile_fn, lane="impact-mesh",
                       owner=pack.engine_uuid)
    with device_span("impact-shard-dispatch",
                     cost=("impact-mesh", key,
                           n_real if n_real is not None else b, b_pad)):
        device_fault_point("impact-shard-dispatch")
        out = fn(seg_arrs, qtids, scales, boosts_a, cs, cd)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out


def _placed_vector_arrays(reader, pack: _VectorPack, mesh) -> list:
    """Per-segment placed (vecs, exists, live[, lens]) device arrays
    for the mesh knn lane — doc axis padded to the shard count (pad
    rows exists=False/live=False → never eligible) and pinned to owning
    devices through the placement-aware block cache. Aligned 1:1 with
    pack.segs (() entries for segments without the field)."""
    from elasticsearch_tpu.parallel.mesh_engine import fetch_placed_block
    s_axis = int(mesh.shape["shard"])
    engine_uuid = getattr(reader, "engine_uuid", None) or \
        f"reader:{id(reader)}"
    breaker_service = getattr(reader, "breaker_service", None)
    placed = []
    uploaded = reused = 0
    for s in pack.segs:
        if s is None:
            placed.append(())
            continue
        np_pad = -(-s["np_docs"] // s_axis) * s_axis

        def build(s=s, np_pad=np_pad):
            pad = np_pad - s["np_docs"]
            vecs = np.asarray(s["vecs"])
            out = [np.pad(vecs,
                          ((0, pad),) + ((0, 0),) * (vecs.ndim - 1)),
                   np.pad(np.asarray(s["exists"]), (0, pad)),
                   np.pad(np.asarray(s["live"]), (0, pad))]
            if s["lens"] is not None:
                out.append(np.pad(np.asarray(s["lens"]), (0, pad)))
            return out

        sig = ("knn-mesh", pack.field, pack.quant, pack.multi, np_pad)
        arrs, up, re = fetch_placed_block(
            mesh, engine_uuid, s["block_uid"], sig, build,
            breaker_service, component="vector")
        placed.append(tuple(arrs))
        uploaded += up
        reused += re
    note_data_blocks_placed(uploaded, reused)
    return placed


def run_knn_hybrid_mesh(reader, ctx, reqs, pack: _VectorPack,
                        cfg: KnnPlaneConfig, mesh, *, k: int,
                        num_candidates: int,
                        n_real: int | None = None):
    """The knn/hybrid lane served by the pod slice as ONE compiled
    shard_map dispatch: vector/token columns doc-axis sharded over
    ``shard`` (per-doc scoring is row-independent, so per-shard scores
    are bit-identical to the full-column pass), per-shard
    top-num_candidates, then an in-program cross-chip all_gather +
    re-top-k BEFORE fusion. A hybrid request's lexical side runs
    replicated on every shard (full segment columns — identical on all
    shards), so RRF / weighted fusion computes replicated from the
    merged global candidate lists and bit-matches run_knn_hybrid_batch.
    Returns the single-chip lane's contract, or None on mixed plan
    signatures (callers retry per-request)."""
    from jax.sharding import PartitionSpec as P
    from elasticsearch_tpu.ops import maxsim as maxsim_ops
    from elasticsearch_tpu.ops import vector as vector_ops
    from elasticsearch_tpu.parallel.mesh import shard_map_compat
    segments = reader.segments
    if not segments or not reqs:
        return None
    hybrid = reqs[0].knn.hybrid
    b = len(reqs)
    k_static = int(k)
    c_static = int(num_candidates)
    dp = int(mesh.shape["dp"])
    s_axis = int(mesh.shape["shard"])
    need_seg = hybrid or any(r.knn.filter is not None for r in reqs)
    plans = None
    if need_seg:
        plans = []
        for dseg in segments:
            plan = _plan_knn_segment(dseg, ctx, reqs)
            if plan is None:
                return None
            plans.append(plan)
    with span("jit.pack"):
        qv, qmask, boosts, b_pad = _knn_query_inputs(reqs, pack)
    if need_seg:
        for plan in plans:
            if plan["b_pad"] is not None and plan["b_pad"] != b_pad:
                return None
    packeds = [{dt: jnp.asarray(buf) for dt, buf in p["packed"].items()}
               for p in plans] if need_seg else []
    b_pad_m = -(-b_pad // dp) * dp
    if b_pad_m != b_pad:
        qv, boosts = _pad_batch_rows([qv, boosts], b_pad_m)
        if qmask is not None:
            (qmask,) = _pad_batch_rows([qmask], b_pad_m)
        packeds = [{dt: _pad_batch_rows([buf], b_pad_m)[0]
                    for dt, buf in pk.items()} for pk in packeds]
        b_pad = b_pad_m
    placed = _placed_vector_arrays(reader, pack, mesh)
    bases = tuple(int(s.doc_base) for s in segments)
    vec_bases = tuple(s["doc_base"] for s in pack.segs if s is not None)
    fusion_key = (cfg.fusion_mode, int(cfg.rank_constant),
                  float(cfg.lexical_weight)) if hybrid else None
    geom = mesh_geom(mesh)
    key = ("knn-mesh", pack.sig(), hybrid, need_seg, bases, k_static,
           c_static, b_pad,
           None if qmask is None else tuple(qmask.shape), fusion_key,
           tuple(p["key"] for p in plans) if need_seg else None,
           tuple(tuple(p["specs"]) for p in plans) if need_seg else None,
           geom)
    flats = [p["flat"] for p in plans] if need_seg else []
    # lexical columns serve REPLICATED (every shard scores the full
    # segment — the lexical candidate lists must be global); the vector
    # columns are the sharded half
    flats = _mesh_place(flats, mesh, P(), "mesh-lexical-replicate")
    packeds = _mesh_place(packeds, mesh, P("dp"), "mesh-query-consts")
    qv, boosts = _mesh_place([qv, boosts], mesh, P("dp"),
                             "mesh-query-consts")
    if qmask is not None:
        (qmask,) = _mesh_place([qmask], mesh, P("dp"),
                               "mesh-query-consts")
    scales, offsets = _mesh_place([pack.scales, pack.offsets], mesh,
                                  P(), "mesh-scales")

    def compile_fn():
        def step_local(flats_in, packeds_in, vec_in, scales_in,
                       offsets_in, qv_in, qmask_in, boosts_in):
            sidx = jax.lax.axis_index("shard")
            bl = qv_in.shape[0]
            # ---- lexical scores / filter masks (replicated) ---------
            lex_ts, lex_td = [], []
            fmasks = [None] * len(segments)
            if need_seg:
                for i, (plan, flat_in, packed_in) in enumerate(
                        zip(plans, flats_in, packeds_in)):
                    view = seg_rebuild(plan["seg"], flat_in,
                                       plan["pos"], plan["vecs"])

                    def lane(packed_one, plan=plan, view=view):
                        consts_one = [
                            packed_one[dt][off:off + size].reshape(shape)
                            for dt, off, shape, size in plan["specs"]]
                        em = EmitCtx(view, consts_one)
                        out = {}
                        if plan["emit_q"] is not None:
                            scores, mask = plan["emit_q"](em)
                            mask = mask & view.live
                            ts, td = topk_ops.top_k(
                                scores, mask,
                                min(c_static, view.padded_docs), 0)
                            out["ts"], out["td"] = ts, td
                        if plan["emit_f"] is not None:
                            out["fmask"] = plan["emit_f"](em)
                        return out

                    if plan["specs"]:
                        outs = jax.vmap(lane)(packed_in)
                    else:
                        one = lane({})
                        outs = {kk: jnp.broadcast_to(
                            v, (bl,) + v.shape)
                            for kk, v in one.items()}
                    if hybrid:
                        lex_ts.append(outs["ts"])
                        lex_td.append(outs["td"])
                    if "fmask" in outs:
                        fmasks[i] = outs["fmask"]
            # ---- per-shard knn candidates ---------------------------
            knn_ts, knn_td = [], []
            knn_counts = jnp.zeros(bl, jnp.int32)
            vi = 0
            for i, arrs in enumerate(vec_in):
                if not arrs:
                    continue
                if pack.multi:
                    vecs, exists, live, lens = arrs
                else:
                    vecs, exists, live = arrs
                n_loc = vecs.shape[0]
                if pack.multi and pack.quant == "int8":
                    scores = maxsim_ops.maxsim_scores_int8_batch_body(
                        vecs, scales_in[vi], offsets_in[vi], lens,
                        qv_in, qmask_in)
                elif pack.multi:
                    scores = maxsim_ops.maxsim_scores_batch_body(
                        vecs, lens, qv_in, qmask_in)
                elif pack.quant == "int8":
                    scores = vector_ops.cosine_scores_int8_batch(
                        vecs, scales_in[vi], offsets_in[vi], exists,
                        qv_in)
                else:
                    scores = vector_ops.unit_scores_batch(
                        vecs, exists, qv_in)
                if not hybrid:
                    scores = scores * boosts_in[:, None]
                elig = exists & live
                masks = jnp.broadcast_to(elig[None, :], (bl, n_loc))
                if fmasks[i] is not None:
                    # the replicated filter mask covers the full
                    # (lexical-padded) doc axis — pad to the vector
                    # lane's shard-divisible width, slice our rows
                    fm = fmasks[i]
                    np_pad_i = n_loc * s_axis
                    if fm.shape[1] < np_pad_i:
                        fm = jnp.pad(
                            fm, ((0, 0), (0, np_pad_i - fm.shape[1])))
                    masks = masks & jax.lax.dynamic_slice_in_dim(
                        fm, sidx * n_loc, n_loc, axis=1)
                ts, td = vector_ops.filtered_topk_batch(
                    scores, masks, min(c_static, n_loc),
                    sidx * n_loc)
                knn_ts.append(ts)
                knn_td.append(td)
                knn_counts = knn_counts + masks.sum(axis=1,
                                                    dtype=jnp.int32)
                vi += 1
            ds, dd = topk_ops.merge_top_k_batch_body(
                knn_ts, knn_td, c_static, vec_bases)
            # ---- cross-chip merge: gather per-shard candidates and
            # re-top-k BEFORE fusion, so the fused ranking sees the
            # same global candidate lists the single-chip lane builds
            ag_s = jax.lax.all_gather(ds, "shard")
            ag_d = jax.lax.all_gather(dd, "shard")
            flat_s = jnp.moveaxis(ag_s, 0, 1).reshape(bl, -1)
            flat_d = jnp.moveaxis(ag_d, 0, 1).reshape(bl, -1)

            def refine(s_row, d_row):
                return blockmax_ops.topk_flat_by_doc(s_row, d_row,
                                                     c_static)
            ds, dd = jax.vmap(refine)(flat_s, flat_d)
            knn_counts = jax.lax.psum(knn_counts, "shard")
            if not hybrid:
                return {"top_scores": ds[:, :k_static],
                        "top_docs": dd[:, :k_static],
                        "count": knn_counts}
            ls, ld = topk_ops.merge_top_k_batch_body(
                lex_ts, lex_td, c_static, bases)
            if cfg.fusion_mode == "weighted":
                ts, td, count = _weighted_fuse_body(
                    ls, ld, ds, dd, boosts_in,
                    float(cfg.lexical_weight), k_static)
            else:
                ts, td, count = _rrf_fuse_body(
                    ls, ld, ds, dd, boosts_in,
                    float(cfg.rank_constant), k_static)
            return {"top_scores": ts, "top_docs": td, "count": count}

        flat_specs = jax.tree.map(lambda _: P(), flats)
        packed_specs = jax.tree.map(lambda _: P("dp"), packeds)
        vec_specs = tuple(tuple(P("shard") for _ in arrs)
                          for arrs in placed)
        qmask_spec = P() if qmask is None else P("dp")
        out_specs = {"top_scores": P("dp"), "top_docs": P("dp"),
                     "count": P("dp")}
        mapped = shard_map_compat(
            step_local, mesh=mesh,
            in_specs=(flat_specs, packed_specs, vec_specs, P(), P(),
                      P("dp"), qmask_spec, P("dp")),
            out_specs=out_specs)

        def run_outer(*a):
            return mapped(a[0], a[1], a[2], a[3], a[4], a[5],
                          a[6] if qmask is not None else None, a[7])
        dummy = jnp.zeros(0, bool) if qmask is None else qmask
        return jax.jit(run_outer).lower(
            flats, packeds, tuple(placed), scales, offsets, qv,
            dummy, boosts)

    fn = _get_compiled(key, compile_fn, lane="knn-mesh",
                       owner=getattr(reader, "engine_uuid", None))
    dummy = jnp.zeros(0, bool) if qmask is None else qmask
    args = (flats, packeds, tuple(placed), scales, offsets, qv, dummy,
            boosts)
    rows_real = n_real if n_real is not None else b
    note_knn_rows(rows_real, b_pad - rows_real)
    with device_span("knn-mesh-merge",
                     cost=("knn-mesh", key, rows_real, b_pad)):
        device_fault_point("knn-mesh-merge")
        out = fn(*args)
    if b_pad != b:
        out = {name: v[:b] for name, v in out.items()}
    return out
