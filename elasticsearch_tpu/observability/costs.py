"""Program cost observatory — per-compiled-program XLA cost/memory
analysis plus a live dispatch cost ledger, reconciled as
predicted-vs-measured accounting.

Every serving lane ends in a compiled XLA program, and XLA already
*knows* what each one costs: ``Compiled.cost_analysis()`` reports flops
and bytes accessed, ``Compiled.memory_analysis()`` the argument/output/
temp HBM footprint — the same roofline inputs ROOFLINE.md derives by
hand. This module keeps ONE per-node table of those numbers keyed by
program identity (lane × the program cache's own shape key: plan
signature, layouts, pow2 batch/term buckets), recorded once at the
``jit_exec.observed_compile`` seam every ``.lower(...).compile(...)``
site flows through, and joins them with live dispatch statistics fed by
the ``device_span`` seam: an EWMA and a √2-bucket histogram of device
RTT, dispatch counts, batch occupancy under the PR 14 ``n_real``
contract, and bytes in/out (static argument/output sizes × dispatches).

Each program therefore carries a *predicted* cost — the roofline
placement ``max(bytes/BW, flops/peak)`` against nominal machine
constants — and a *measured* cost (the RTT EWMA), stamped with their
ratio. ``estimate(lane, shape_key)`` answers the planner's day-one
question ("what will this program cost?") from measurement when the
shape is hot and from the static prediction (or the lane's aggregate)
when it is cold — ROADMAP item 3's cost model, queryable.

Discipline (the PR 13 telemetry rules):

* failed dispatches never poison a program's EWMA/histogram — the
  device-span seam records cost only on a clean exit;
* the table is LRU-bounded with exact eviction accounting
  (``inserted == resident + evicted + dropped`` at every instant);
* rows owned by an engine incarnation drain when the engine closes
  (``drop_owner`` rides the same close listener that returns the
  engine's device blocks — no rows for closed engines, the ledger
  discipline);
* nothing here allocates on the request hot path when idle: recording
  happens only when a program actually compiles or dispatches, and
  snapshots/rollups allocate on the read path only.

Surfaces: ``_nodes/stats.programs``, ``GET /_cat/programs``,
``GET /_nodes/diagnostics`` (with the flight recorder,
:mod:`~elasticsearch_tpu.observability.flightrec`), per-program gauges
in ``/_prometheus/metrics`` (generated from ``lanes.PROGRAM_COST``),
and per-program rows in ``"profile": true`` responses / slow-log
attribution.
"""

from __future__ import annotations

import hashlib
import math
import os
import threading
from collections import OrderedDict

from elasticsearch_tpu.observability import attribution
from elasticsearch_tpu.observability.context import current_node_id

#: EWMA smoothing for the measured dispatch time
EWMA_ALPHA = 0.2
#: per-node table capacity (LRU; evictions counted exactly)
TABLE_CAP = 256
#: a dispatch this many × its program's envelope (max of predicted and
#: EWMA) is an anomaly — recorded on the flight recorder
ANOMALY_FACTOR = 8.0
#: dispatches before the anomaly envelope is trusted (a cold program's
#: first few RTTs include transfer warmup and must not alarm)
ANOMALY_MIN_DISPATCHES = 8
#: dispatches that make a program "hot": a recompile of a hot key is a
#: compile storm (the program cache stopped holding the working set)
HOT_DISPATCHES = 32

#: √2-spaced dispatch-time histogram bounds in µs: 1 µs → ~64 s
BOUNDS_US = tuple(1.0 * (2 ** (i / 2.0)) for i in range(33))

#: roofline peaks keyed by ``device_kind`` — (HBM bytes/s, flop/s).
#: "TPU v5 lite" is what JAX reports for one v5e chip: 819 GB/s HBM and
#: 197 TFLOP/s bf16 (Google Cloud documentation, "TPU v5e"). No f32 peak
#: is published, so f32 programs — every lane today — are held to the
#: same bf16 MXU figure: an upper bound on compute, which leaves the
#: bandwidth wall deciding the prediction for the memory-bound lanes.
#: "cpu" is a laptop-class core (the CPU backend is a correctness rig —
#: its predictions are honest about being nominal). A device that is
#: not in the table is an error, not a default.
#: Override with ESTPU_ROOFLINE_BW_GBS / ESTPU_ROOFLINE_GFLOPS.
PEAKS = {
    "TPU v5 lite": (819.0e9, 197.0e12),
    "cpu": (25.0e9, 5.0e10),
}


def peaks_for(platform: str, device_kind: str) -> "tuple[float, float]":
    """(bytes/s, flop/s) of one device. Every CPU kind shares the
    nominal "cpu" row; any other device must be in :data:`PEAKS`."""
    if platform == "cpu":
        return PEAKS["cpu"]
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device_kind {device_kind!r} "
            f"(platform {platform!r}); known: {sorted(PEAKS)} — add its "
            f"published peaks to observability/costs.py PEAKS, or set "
            f"ESTPU_ROOFLINE_BW_GBS and ESTPU_ROOFLINE_GFLOPS") from None

_machine_lock = threading.Lock()
_machine: "tuple[float, float] | None" = None


def machine_constants() -> "tuple[float, float]":
    """(bytes/s, flop/s) for the attached backend — env-overridable,
    resolved once (jax import deferred to first use)."""
    global _machine
    if _machine is not None:
        return _machine
    with _machine_lock:
        if _machine is not None:
            return _machine
        bw = flops = None
        raw_bw = os.environ.get("ESTPU_ROOFLINE_BW_GBS")
        raw_fl = os.environ.get("ESTPU_ROOFLINE_GFLOPS")
        if raw_bw:
            try:
                bw = float(raw_bw) * 1e9
            except ValueError:
                bw = None
        if raw_fl:
            try:
                flops = float(raw_fl) * 1e9
            except ValueError:
                flops = None
        if bw is None or flops is None:
            import jax
            dev = jax.devices()[0]
            d_bw, d_fl = peaks_for(dev.platform, dev.device_kind)
            bw = bw if bw is not None else d_bw
            flops = flops if flops is not None else d_fl
        _machine = (bw, flops)
    return _machine


def predict_us(flops: float, bytes_accessed: float) -> float:
    """Roofline prediction in µs: the program takes at least as long as
    its HBM traffic at peak bandwidth and its flops at peak throughput —
    whichever wall is higher. Always finite and positive (a zero-cost
    program still pays a floor of 0.01 µs, so ratios stay finite)."""
    bw, peak = machine_constants()
    t_mem = float(bytes_accessed) / bw
    t_cmp = float(flops) / peak
    return max(t_mem, t_cmp, 1e-8) * 1e6


def roofline_regime(flops: float, bytes_accessed: float) -> str:
    """Which roofline wall binds this program on the attached machine:
    ``memory`` (bytes/BW ≥ flops/peak) or ``compute``."""
    bw, peak = machine_constants()
    return "memory" if float(bytes_accessed) / bw >= float(flops) / peak \
        else "compute"


def key_digest(shape_key) -> str:
    """Stable short id of a program-cache shape key (the full tuples run
    to kilobytes — surfaces print this 12-hex digest instead)."""
    return hashlib.blake2b(repr(shape_key).encode(),
                           digest_size=6).hexdigest()


def extract_analysis(compiled) -> dict:
    """Pull the XLA static analyses off a ``jax.stages.Compiled``:
    flops, bytes accessed, and the argument/output/temp HBM footprint
    (peak = their sum — the residency the dispatch needs live at once).
    Analyses a backend doesn't implement come back as zeros; the record
    stays honest via ``analyzed``."""
    out = {"flops": 0.0, "bytes_accessed": 0.0, "argument_bytes": 0,
           "output_bytes": 0, "temp_bytes": 0, "peak_bytes": 0,
           "analyzed": False}
    try:
        ca = compiled.cost_analysis()
    except Exception:                    # noqa: BLE001 — backend-optional
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        out["flops"] = float(ca.get("flops", 0.0) or 0.0)
        out["bytes_accessed"] = float(
            ca.get("bytes accessed", 0.0) or 0.0)
        out["analyzed"] = True
    try:
        ma = compiled.memory_analysis()
    except Exception:                    # noqa: BLE001 — backend-optional
        ma = None
    if ma is not None:
        arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        outb = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        out.update(argument_bytes=arg, output_bytes=outb,
                   temp_bytes=tmp, peak_bytes=arg + outb + tmp)
        out["analyzed"] = True
    return out


class ProgramCostRecord:
    """One resident program's static + live books. Mutated only under
    the owning table's lock."""

    __slots__ = (
        "lane", "key_id", "owner", "flops", "bytes_accessed",
        "argument_bytes", "output_bytes", "temp_bytes", "peak_bytes",
        "analyzed", "compiles", "compile_ms", "predicted_us",
        "dispatches", "ewma_us", "sum_us", "max_us", "hist",
        "n_real_total", "rows_total", "bytes_in_total",
        "bytes_out_total")

    def __init__(self, lane: str, key_id: str, owner: str | None):
        self.lane = lane
        self.key_id = key_id
        self.owner = owner
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.peak_bytes = 0
        self.analyzed = False
        self.compiles = 0
        self.compile_ms = 0.0
        self.predicted_us = predict_us(0.0, 0.0)
        self.dispatches = 0
        self.ewma_us = 0.0
        self.sum_us = 0.0
        self.max_us = 0.0
        self.hist = [0] * (len(BOUNDS_US) + 1)
        self.n_real_total = 0
        self.rows_total = 0
        self.bytes_in_total = 0
        self.bytes_out_total = 0

    # ---- accounting (callers hold the table lock) -----------------------

    def record_compile(self, analysis: dict, compile_ms: float) -> None:
        self.compiles += 1
        self.compile_ms += float(compile_ms)
        if analysis.get("analyzed"):
            self.flops = analysis["flops"]
            self.bytes_accessed = analysis["bytes_accessed"]
            self.argument_bytes = analysis["argument_bytes"]
            self.output_bytes = analysis["output_bytes"]
            self.temp_bytes = analysis["temp_bytes"]
            self.peak_bytes = analysis["peak_bytes"]
            self.analyzed = True
            self.predicted_us = predict_us(self.flops,
                                           self.bytes_accessed)

    def record_dispatch(self, dur_us: float, n_real: int,
                        rows: int) -> None:
        import bisect
        dur_us = float(dur_us)
        self.dispatches += 1
        self.sum_us += dur_us
        if dur_us > self.max_us:
            self.max_us = dur_us
        self.ewma_us = dur_us if self.dispatches == 1 else (
            EWMA_ALPHA * dur_us + (1.0 - EWMA_ALPHA) * self.ewma_us)
        self.hist[bisect.bisect_left(BOUNDS_US, dur_us)] += 1
        self.n_real_total += max(int(n_real), 0)
        self.rows_total += max(int(rows), 0)
        self.bytes_in_total += self.argument_bytes
        self.bytes_out_total += self.output_bytes

    # ---- read side ------------------------------------------------------

    def measured_us(self) -> float:
        return self.ewma_us

    def accuracy_ratio(self) -> "float | None":
        """measured / predicted — stamped only once measurement exists;
        always finite (the prediction floors at a positive value)."""
        if self.dispatches == 0:
            return None
        return self.ewma_us / self.predicted_us

    def occupancy(self) -> "float | None":
        """Real requests per padded program row (the PR 14 ``n_real``
        contract): 1.0 = every row served a queued request."""
        if self.rows_total <= 0:
            return None
        return self.n_real_total / self.rows_total

    def intensity(self) -> "float | None":
        """Arithmetic intensity flop/byte — the roofline x-axis."""
        if self.bytes_accessed <= 0:
            return None
        return self.flops / self.bytes_accessed

    def envelope_us(self) -> float:
        """The anomaly threshold's baseline: whichever of the
        prediction and the running measurement is LARGER (a program
        slower than its model is judged against its own history)."""
        return max(self.predicted_us, self.ewma_us)

    def summary(self) -> dict:
        acc = self.accuracy_ratio()
        occ = self.occupancy()
        ai = self.intensity()
        return {
            "lane": self.lane,
            "key": self.key_id,
            "owner": self.owner,
            "compiles": self.compiles,
            "compile_ms": round(self.compile_ms, 3),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": round(ai, 4) if ai is not None
            else None,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "hbm_peak_bytes": self.peak_bytes,
            "regime": roofline_regime(self.flops, self.bytes_accessed),
            "predicted_us": round(self.predicted_us, 3),
            "dispatches": self.dispatches,
            "measured_us": round(self.ewma_us, 3),
            "device_time_us": round(self.sum_us, 3),
            "max_us": round(self.max_us, 3),
            "accuracy_ratio": round(acc, 4) if acc is not None else None,
            "occupancy": round(occ, 4) if occ is not None else None,
            "bytes_in": self.bytes_in_total,
            "bytes_out": self.bytes_out_total,
        }


class ProgramCostTable:
    """One node's resident-program cost book: LRU-bounded, with exact
    eviction accounting (``inserted == resident + evicted + dropped``
    holds at every instant — the tier-1 invariant)."""

    def __init__(self, cap: int = TABLE_CAP):
        self.cap = int(cap)
        self._recs: "OrderedDict[tuple, ProgramCostRecord]" = \
            OrderedDict()
        self._lock = threading.Lock()
        self.inserted = 0
        self.evicted = 0
        self.dropped = 0
        #: hot keys the LRU pushed out — a recompile of one of these is
        #: a compile storm even though the record looks fresh
        self._evicted_hot: set = set()

    def _get_locked(self, lane: str, shape_key,
                    owner: str | None) -> ProgramCostRecord:
        key = (lane, shape_key)
        rec = self._recs.get(key)
        if rec is not None:
            self._recs.move_to_end(key)
            if owner is not None and rec.owner is None:
                rec.owner = owner
            return rec
        rec = ProgramCostRecord(lane, key_digest(shape_key), owner)
        self._recs[key] = rec
        self.inserted += 1
        while len(self._recs) > self.cap:
            (_, old) = self._recs.popitem(last=False)
            self.evicted += 1
            if old.dispatches >= HOT_DISPATCHES:
                self._evicted_hot.add((old.lane, old.key_id))
        return rec

    def note_compile(self, lane: str, shape_key, analysis: dict,
                     compile_ms: float, owner: str | None
                     ) -> "tuple[ProgramCostRecord, bool]":
        """→ (record, is_storm): ``is_storm`` when this compile hit a
        key that was previously hot (still-resident recompile, or one
        the LRU evicted while hot) — a miss on the working set."""
        with self._lock:
            rec = self._get_locked(lane, shape_key, owner)
            storm = rec.dispatches >= HOT_DISPATCHES or \
                (rec.lane, rec.key_id) in self._evicted_hot
            self._evicted_hot.discard((rec.lane, rec.key_id))
            rec.record_compile(analysis, compile_ms)
            return rec, storm

    def note_dispatch(self, lane: str, shape_key, dur_us: float,
                      n_real: int, rows: int
                      ) -> "tuple[ProgramCostRecord, bool]":
        """→ (record, is_anomaly): ``is_anomaly`` when the dispatch
        blew the program's predicted+EWMA envelope by
        :data:`ANOMALY_FACTOR` with enough history to trust it."""
        with self._lock:
            rec = self._get_locked(lane, shape_key, None)
            anomaly = (rec.dispatches >= ANOMALY_MIN_DISPATCHES and
                       float(dur_us) >=
                       ANOMALY_FACTOR * rec.envelope_us())
            rec.record_dispatch(dur_us, n_real, rows)
            return rec, anomaly

    def drop_owner(self, owner: str) -> int:
        """Drop every record owned by a closed engine incarnation —
        the engine-close drain (the device-block-release discipline)."""
        with self._lock:
            dead = [k for k, rec in self._recs.items()
                    if rec.owner == owner]
            for k in dead:
                del self._recs[k]
            self.dropped += len(dead)
            return len(dead)

    def lookup(self, lane: str, shape_key) -> "ProgramCostRecord | None":
        with self._lock:
            return self._recs.get((lane, shape_key))

    def records(self) -> list:
        with self._lock:
            return list(self._recs.values())

    def items(self) -> list:
        """``[((lane, shape_key), record), ...]`` — records WITH their
        raw table keys. Records themselves carry only the key digest;
        geometry-scoped aggregation (the planner's per-mesh pricing)
        needs the raw shape_key, which lives in the table key."""
        with self._lock:
            return list(self._recs.items())

    def counters(self) -> dict:
        with self._lock:
            return {"resident": len(self._recs),
                    "inserted": self.inserted,
                    "evicted": self.evicted,
                    "dropped": self.dropped,
                    "cap": self.cap}


#: node id → table ("" collects unattributed activity, like histograms)
_tables: dict = {}
_tables_lock = threading.Lock()


def table(node_id: str | None = None) -> ProgramCostTable:
    nid = node_id if node_id is not None else (current_node_id() or "")
    t = _tables.get(nid)
    if t is None:
        with _tables_lock:
            t = _tables.setdefault(nid, ProgramCostTable())
    return t


def node_ids() -> list:
    with _tables_lock:
        return sorted(_tables)


def reset() -> None:
    """Drop every table (tests / jit_exec.clear_cache)."""
    with _tables_lock:
        _tables.clear()


# ---------------------------------------------------------------------------
# recording entry points (the jit_exec / device_span seams call these)
# ---------------------------------------------------------------------------

def note_compile(lane: str, shape_key, compiled, compile_ms: float,
                 owner: str | None = None,
                 node_id: str | None = None) -> None:
    """One program compile through the ``observed_compile`` seam:
    stamp the XLA static analyses and the compile wall time; a miss on
    a previously-hot key lands on the flight recorder as a
    ``compile-storm`` event."""
    analysis = extract_analysis(compiled)
    rec, storm = table(node_id).note_compile(lane, shape_key, analysis,
                                             compile_ms, owner)
    if storm:
        from elasticsearch_tpu.observability import flightrec
        flightrec.note("compile-storm", node_id=node_id, lane=lane,
                       program=rec.key_id,
                       compiles=rec.compiles,
                       dispatches=rec.dispatches,
                       compile_ms=round(float(compile_ms), 3))


def note_dispatch(lane: str, shape_key, dur_ms: float,
                  n_real: int = 1, rows: int = 1,
                  node_id: str | None = None) -> None:
    """One successful program dispatch (the device-span seam calls this
    on clean exits ONLY — a failed dispatch never poisons the EWMA or
    the histogram): EWMA + histogram + occupancy + bytes accounting,
    per-request attribution, and the anomaly check against the
    predicted+EWMA envelope."""
    dur_us = float(dur_ms) * 1e3
    rec, anomaly = table(node_id).note_dispatch(lane, shape_key, dur_us,
                                                n_real, rows)
    attribution.program(lane, rec.key_id, dur_us)
    stack = getattr(_tls, "collectors", None)
    if stack:
        stack[-1].append((lane, rec.key_id, dur_us, int(n_real)))
    if anomaly:
        from elasticsearch_tpu.observability import flightrec
        flightrec.note("dispatch-overrun", node_id=node_id, lane=lane,
                       program=rec.key_id,
                       dispatch_us=round(dur_us, 1),
                       envelope_us=round(rec.envelope_us(), 1),
                       predicted_us=round(rec.predicted_us, 1),
                       ewma_us=round(rec.ewma_us, 1))


# ---------------------------------------------------------------------------
# per-request program collection (profile responses)
# ---------------------------------------------------------------------------

_tls = threading.local()


class _ProgramCollector:
    """Context manager collecting the (lane, key, µs, n_real) rows of
    every dispatch under its scope — the ``profile`` response's
    ``programs`` section. Nothing is installed (and nothing allocates
    per dispatch) when no profile is active."""

    __slots__ = ("rows",)

    def __init__(self):
        self.rows: list = []

    def append(self, row) -> None:
        self.rows.append(row)

    def __enter__(self):
        stack = getattr(_tls, "collectors", None)
        if stack is None:
            stack = _tls.collectors = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        stack = getattr(_tls, "collectors", None)
        if stack and self in stack:
            stack.remove(self)
        return False


def collect_programs() -> _ProgramCollector:
    return _ProgramCollector()


def current_collectors() -> "list | None":
    """The installed collector stack (bind_context carries it across
    pool submits so scheduled dispatches still attribute)."""
    return getattr(_tls, "collectors", None) or None


def install_collectors(stack):
    prev = getattr(_tls, "collectors", None)
    _tls.collectors = stack
    return prev


def render_rows(collector: _ProgramCollector) -> list:
    """Aggregate one collector's dispatch rows per program → the
    profile response's ``programs`` list, hottest first."""
    agg: dict = {}
    for lane, key_id, dur_us, n_real in collector.rows:
        ent = agg.setdefault((lane, key_id),
                             {"lane": lane, "key": key_id,
                              "dispatches": 0, "device_time_us": 0.0,
                              "requests": 0})
        ent["dispatches"] += 1
        ent["device_time_us"] += dur_us
        ent["requests"] += n_real
    out = sorted(agg.values(), key=lambda e: -e["device_time_us"])
    for ent in out:
        ent["device_time_us"] = round(ent["device_time_us"], 1)
    return out


# ---------------------------------------------------------------------------
# read side: estimates, rollups, stats documents
# ---------------------------------------------------------------------------

class CostEstimate(float):
    """A priced program cost (µs) that carries its own provenance.

    Plain ``float`` subclass, so every existing arithmetic consumer
    (the watchdog's stall envelope, the planner's plan pricing, test
    equality against a record's EWMA) keeps working unchanged. The
    extra attributes tell the planner how much to trust the number:

    * ``cold`` — True when no dispatch of the exact ``(lane,
      shape_key)`` was ever measured: the value is static analysis
      (roofline prediction) or a lane-level aggregate, not this
      program's own EWMA. A cold plan is still priceable — the planner
      no longer special-cases ``None`` — but ties break toward the
      measured candidate.
    * ``source`` — where the number came from: ``"measured"`` (exact
      EWMA), ``"static"`` (roofline prediction, never dispatched), or
      ``"lane-mean"`` (dispatch-weighted mean over the lane's hot
      programs).
    """

    __slots__ = ("cold", "source")

    def __new__(cls, value: float, *, cold: bool, source: str):
        self = super().__new__(cls, value)
        self.cold = bool(cold)
        self.source = source
        return self

    def __repr__(self) -> str:          # debugging/log readability
        return (f"CostEstimate({float(self):.1f}us, cold={self.cold}, "
                f"source={self.source!r})")


def mesh_axis(mesh):
    """Normalize the planner's mesh argument to the hashable geometry
    component the mesh-served lanes embed in their program keys.

    Accepts a live ``jax.sharding.Mesh``, an already-normalized
    geometry tuple (``(axis_sizes, device_ids)``), or None (single-chip
    — no geometry axis). The normal form matches
    ``jit_exec.mesh_geom`` exactly, so an estimate keyed through this
    helper resolves against programs compiled for the same pod slice."""
    if mesh is None:
        return None
    shape = getattr(mesh, "shape", None)
    devices = getattr(mesh, "devices", None)
    if shape is not None and devices is not None:
        return (tuple(sorted((str(k), int(v)) for k, v in shape.items())),
                tuple(int(d.id) for d in devices.flat))
    return tuple(mesh)


def _key_has_geom(shape_key, geom) -> bool:
    """Does a raw program shape_key carry this geometry component?
    Mesh-lane keys end with the geom tuple; anything else is a
    single-chip program and never matches."""
    try:
        return geom in tuple(shape_key)
    except TypeError:
        return False


def estimate(lane: str, shape_key=None,
             node_id: str | None = None,
             mesh=None) -> "CostEstimate | None":
    """The planner's cost query → predicted µs for one program
    (a :class:`CostEstimate`), or None when the observatory has
    nothing to say about the lane at all.

    Resolution order: the exact program's MEASURED EWMA (hot shape,
    ``cold=False``), its static roofline prediction (compiled but
    never dispatched, ``cold=True``), the lane's dispatch-weighted
    mean measured cost (a cold shape on a hot lane, ``cold=True``),
    then the mean static prediction over the lane's compiled-but-idle
    programs (``cold=True`` — the never-dispatched-lane case the
    planner prices first requests with). Every non-None return is
    finite and positive.

    ``mesh`` adds a geometry axis to resolution (a Mesh, a normalized
    geometry tuple, or None — see :func:`mesh_axis`). With a geometry:
    the exact lookup first tries the geometry-qualified key
    (``shape_key + (geom,)`` — how the mesh lanes key their programs),
    and the lane-level fallbacks aggregate ONLY over programs compiled
    for that geometry, falling back to the whole lane when the
    geometry has no history yet. This is what lets the planner price
    the same logical shape on a 1-chip lane vs two different pod
    slices and get three distinct numbers."""
    t = table(node_id)
    geom = mesh_axis(mesh)
    if shape_key is not None:
        keys = [shape_key]
        if geom is not None and isinstance(shape_key, tuple) and \
                (len(shape_key) == 0 or shape_key[-1] != geom):
            keys.insert(0, tuple(shape_key) + (geom,))
        for sk in keys:
            rec = t.lookup(lane, sk)
            if rec is None:
                continue
            if rec.dispatches > 0:
                val = rec.ewma_us
                if val > 0 and math.isfinite(val):
                    return CostEstimate(val, cold=False,
                                        source="measured")
            val = rec.predicted_us
            if val > 0 and math.isfinite(val):
                return CostEstimate(val, cold=True, source="static")
    # lane-level aggregates: tally the geometry-scoped and unscoped
    # sums in one pass, prefer the scoped figures when they exist
    scoped = {"sum": 0.0, "n": 0, "psum": 0.0, "pn": 0}
    unscoped = {"sum": 0.0, "n": 0, "psum": 0.0, "pn": 0}
    for (rec_lane, rec_key), rec in t.items():
        if rec_lane != lane:
            continue
        buckets = [unscoped]
        if geom is not None and _key_has_geom(rec_key, geom):
            buckets.append(scoped)
        for b in buckets:
            if rec.dispatches > 0:
                b["sum"] += rec.sum_us
                b["n"] += rec.dispatches
            elif rec.predicted_us > 0 and \
                    math.isfinite(rec.predicted_us):
                b["psum"] += rec.predicted_us
                b["pn"] += 1
    for b in ((scoped, unscoped) if geom is not None else (unscoped,)):
        if b["n"] > 0 and math.isfinite(b["sum"]) and b["sum"] > 0:
            return CostEstimate(b["sum"] / b["n"], cold=True,
                                source="lane-mean")
        if b["pn"] > 0:
            # never-dispatched lane: static analysis is all there is,
            # and a typed cold estimate beats forcing callers to
            # handle None
            return CostEstimate(b["psum"] / b["pn"], cold=True,
                                source="static")
    return None


def lane_rollup(node_id: str | None = None) -> dict:
    """Per-lane aggregates over one node's resident programs — the
    ``_nodes/stats.programs.lanes`` section and the OpenMetrics gauge
    source (field names mirror ``lanes.PROGRAM_COST``)."""
    out: dict = {}
    for rec in table(node_id).records():
        ent = out.setdefault(rec.lane, {
            "resident": 0, "compiles": 0, "compile_ms": 0.0,
            "dispatches": 0, "device_time_us": 0.0, "requests": 0,
            "rows": 0, "predicted_us": 0.0, "measured_us": 0.0,
            "_measured_n": 0})
        ent["resident"] += 1
        ent["compiles"] += rec.compiles
        ent["compile_ms"] += rec.compile_ms
        ent["dispatches"] += rec.dispatches
        ent["device_time_us"] += rec.sum_us
        ent["requests"] += rec.n_real_total
        ent["rows"] += rec.rows_total
        if rec.dispatches > 0:
            # dispatch-weighted means: a hot program's cost dominates
            # its lane figure the way it dominates the device
            ent["predicted_us"] += rec.predicted_us * rec.dispatches
            ent["measured_us"] += rec.ewma_us * rec.dispatches
            ent["_measured_n"] += rec.dispatches
    for lane, ent in out.items():
        n = ent.pop("_measured_n")
        if n > 0:
            ent["predicted_us"] = round(ent["predicted_us"] / n, 3)
            ent["measured_us"] = round(ent["measured_us"] / n, 3)
            ent["accuracy_ratio"] = round(
                ent["measured_us"] / ent["predicted_us"], 4) \
                if ent["predicted_us"] > 0 else None
        else:
            ent["predicted_us"] = ent["measured_us"] = 0.0
            ent["accuracy_ratio"] = None
        ent["occupancy"] = round(ent["requests"] / ent["rows"], 4) \
            if ent["rows"] > 0 else None
        ent["compile_ms"] = round(ent["compile_ms"], 3)
        ent["device_time_us"] = round(ent["device_time_us"], 3)
    return out


def top_programs(node_id: str | None = None, n: int = 10,
                 lane: str | None = None) -> list:
    """The node's hottest resident programs by accumulated device time
    (optionally one lane's)."""
    recs = [rec for rec in table(node_id).records()
            if lane is None or rec.lane == lane]
    recs.sort(key=lambda r: -r.sum_us)
    return [rec.summary() for rec in recs[:max(int(n), 0)]]


def stats_doc(node_id: str | None = None, top: int = 10) -> dict:
    """The ``_nodes/stats.programs`` document: table accounting
    (inserted == resident + evicted + dropped), per-lane rollups, and
    the top-N programs by device time."""
    t = table(node_id)
    counters = t.counters()
    counters["reconciled"] = (
        counters["inserted"] == counters["resident"] +
        counters["evicted"] + counters["dropped"])
    return {"table": counters,
            "lanes": lane_rollup(node_id),
            "top": top_programs(node_id, n=top)}


def drop_owner(owner: str) -> int:
    """Drop a closed engine's rows from EVERY node table (compiles may
    attribute to whichever node's task ran them)."""
    with _tables_lock:
        tabs = list(_tables.values())
    return sum(t.drop_owner(owner) for t in tabs)
