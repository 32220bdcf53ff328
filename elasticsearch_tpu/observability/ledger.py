"""Device-memory ledger — ONE table for every HBM residency reservation.

Before this module, "what exactly is resident in HBM" had five separate
answers: the mesh block cache's per-block ``OneShotCharge``s, the impact
and vector auxiliary blocks riding the same LRU, the collective-plane
pack charge, and the device reader's delta-accounted column bytes — all
of them visible only as one opaque ``fielddata.used`` number. The ledger
unifies them into a per-node table keyed

    (index, engine uuid, component, block id)

with byte counts and creation / last-access stamps, surfaced as
``_nodes/stats.device_memory`` (per-component / per-index breakdown) and
``GET /_cat/hbm`` (resident blocks, hot/cold by recency).

Components (the closed vocabulary :data:`COMPONENTS`):

* ``mesh-columns`` / ``masks`` — the collective plane's per-segment
  device blocks (column bytes vs live-mask bytes of the same charge);
* ``impact`` — the impact lane's quantized columns + block maxima;
* ``vector`` — the knn/late-interaction lane's vector blocks;
* ``pack`` — the stacked collective-plane pack reservation;
* ``reader-columns`` — the device reader's resident column prefix
  (delta-accounted, one absolute entry per engine incarnation);
* ``percolate`` — reserved for the fused percolate lane: its stacked
  constants are per-dispatch operands, not persistent HBM residency, so
  the component reports zero until a future lane pins registrations.

Reconciliation invariant (tier-1 asserted, including under churn, merge,
eviction and injected device faults): the sum of CHARGED ledger bytes
equals the fielddata breaker's ``used`` at every quiescent instant. The
invariant holds by construction — every fielddata reservation flows
through :class:`~elasticsearch_tpu.common.breaker.OneShotCharge` (which
records here, ``untracked`` when a site carries no tag) or through
:func:`account_absolute` (the device reader's delta path).

Each node's ledger lives on its
:class:`~elasticsearch_tpu.common.breaker.HierarchyCircuitBreakerService`
(``breaker_service.device_ledger``) — in-process multi-node clusters get
per-node books for free. The module-level registry gives
:func:`global_snapshot` (the timeseries sampler's view when no node's
ledger is handed in) every live ledger without a node handle.
"""

from __future__ import annotations

import threading
import time
import weakref

#: the closed component vocabulary (every entry's component must be one
#: of these, or the site-specific "untracked" debugging bucket)
COMPONENTS = ("mesh-columns", "masks", "impact", "vector", "pack",
              "reader-columns", "percolate")

#: entries older than this with no access count as cold in /_cat/hbm
DEFAULT_HOT_S = 300.0


class LedgerEntry:
    __slots__ = ("index", "engine_uuid", "component", "block_id",
                 "nbytes", "charged", "created_s", "last_access_s",
                 "device")

    def __init__(self, index, engine_uuid, component, block_id, nbytes,
                 charged, now, device: str = ""):
        self.index = index
        self.engine_uuid = engine_uuid
        self.component = component
        self.block_id = block_id
        self.nbytes = int(nbytes)
        self.charged = bool(charged)
        self.created_s = now
        self.last_access_s = now
        # device placement tag ("" = unplaced / default device): the
        # mesh-sharded lanes' placed blocks record one entry per owning
        # device, so the per_device rollup reconciles bit-exactly with
        # the node total by construction (every entry has exactly one
        # device attribution)
        self.device = device


#: every live ledger (one per breaker service) — what
#: :func:`global_snapshot` merges
_ALL: "weakref.WeakSet" = weakref.WeakSet()


class DeviceMemoryLedger:
    """One node's device-memory table. Thread-safe; every mutator is
    O(1) so charge/release hot paths pay a dict op, nothing more."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict = {}        # token → [LedgerEntry]
        self._seq = 0
        _ALL.add(self)

    # ---- one-shot entries (OneShotCharge's books) --------------------------

    def record(self, nbytes: int, component: str = "untracked",
               index: str = "", engine_uuid: str = "",
               block_id=None, charged: bool = True,
               parts: dict | None = None, device: str = "",
               device_parts: dict | None = None) -> int:
        """One reservation → one token. ``parts`` splits a single charge
        into per-component rows (the mesh block's column vs mask bytes)
        that live and die together under the returned token.
        ``device_parts`` (device → bytes) splits it into per-device rows
        instead — the placed-block path, where each owning device holds
        its shard slice; ``device`` tags every row of a non-split charge
        with one placement."""
        now = time.monotonic()
        if device_parts:
            entries = [LedgerEntry(index, engine_uuid, component,
                                   block_id, b, charged, now, device=d)
                       for d, b in device_parts.items()]
        else:
            split = parts if parts else {component: nbytes}
            entries = [LedgerEntry(index, engine_uuid, comp, block_id,
                                   b, charged, now, device=device)
                       for comp, b in split.items()]
        with self._lock:
            self._seq += 1
            token = self._seq
            self._entries[token] = entries
        return token

    def forget(self, token: int) -> None:
        with self._lock:
            self._entries.pop(token, None)

    def touch(self, token: int) -> None:
        """Refresh the last-access stamp (cache hits on resident blocks
        — the /_cat/hbm hot/cold signal)."""
        now = time.monotonic()
        with self._lock:
            for e in self._entries.get(token, ()):
                e.last_access_s = now

    # ---- absolute entries (the device reader's delta accounting) ----------

    def set_absolute(self, engine_uuid: str, component: str,
                     nbytes: int, index: str = "",
                     charged: bool = True) -> None:
        """Set (not add) one keyed entry's byte count — the companion of
        delta-style breaker accounting where the reservation for a key
        is a moving absolute, not a stack of one-shots. Zero removes."""
        key = ("abs", engine_uuid, component)
        now = time.monotonic()
        with self._lock:
            if not nbytes:
                self._entries.pop(key, None)
                return
            cur = self._entries.get(key)
            if cur:
                cur[0].nbytes = int(nbytes)
                cur[0].last_access_s = now
                if index:
                    cur[0].index = index
            else:
                self._entries[key] = [LedgerEntry(
                    index, engine_uuid, component, None, nbytes, charged,
                    now)]

    # ---- reads -------------------------------------------------------------

    def _all_entries(self) -> list:
        with self._lock:
            return [e for group in self._entries.values() for e in group]

    def total_bytes(self, charged_only: bool = True) -> int:
        return sum(e.nbytes for e in self._all_entries()
                   if e.charged or not charged_only)

    def snapshot(self, resolve_index=None) -> dict:
        """The ``_nodes/stats.device_memory`` document: totals plus
        per-component and per-index/per-component byte breakdowns.
        ``resolve_index`` maps an engine uuid to its index name for
        entries whose charge site didn't know it."""
        entries = self._all_entries()
        by_component = {c: 0 for c in COMPONENTS}
        by_index: dict = {}
        per_device: dict = {}
        charged = uncharged = 0
        for e in entries:
            by_component[e.component] = \
                by_component.get(e.component, 0) + e.nbytes
            name = e.index or (resolve_index(e.engine_uuid)
                               if resolve_index else "") or "_unknown"
            idx = by_index.setdefault(
                name, {"total_bytes": 0, "components": {}})
            idx["total_bytes"] += e.nbytes
            idx["components"][e.component] = \
                idx["components"].get(e.component, 0) + e.nbytes
            # "-" = unplaced (single-device residency): every entry
            # lands in exactly one bucket, so
            # Σ per_device == total_bytes bit-exactly by construction
            per_device[e.device or "-"] = \
                per_device.get(e.device or "-", 0) + e.nbytes
            if e.charged:
                charged += e.nbytes
            else:
                uncharged += e.nbytes
        return {
            "total_bytes": charged + uncharged,
            "charged_bytes": charged,
            "uncharged_bytes": uncharged,
            "entries": len(entries),
            "by_component": by_component,
            "per_device": {k: per_device[k] for k in sorted(per_device)},
            "indices": {k: by_index[k] for k in sorted(by_index)},
        }

    def rows(self, resolve_index=None, now: float | None = None,
             hot_s: float = DEFAULT_HOT_S) -> list:
        """Per-entry rows for ``/_cat/hbm``, hottest first."""
        now = time.monotonic() if now is None else now
        out = []
        for e in self._all_entries():
            idle = max(now - e.last_access_s, 0.0)
            out.append({
                "index": e.index or (resolve_index(e.engine_uuid)
                                     if resolve_index else "")
                or "_unknown",
                "engine": e.engine_uuid,
                "component": e.component,
                "device": e.device or "-",
                "block": e.block_id if e.block_id is not None else "-",
                "bytes": e.nbytes,
                "charged": e.charged,
                "age_s": round(max(now - e.created_s, 0.0), 3),
                "idle_s": round(idle, 3),
                "temp": "hot" if idle <= hot_s else "cold",
            })
        out.sort(key=lambda r: (r["idle_s"], -r["bytes"]))
        return out


def account_absolute(breaker_service, engine_uuid: str, component: str,
                     old_bytes: int, new_bytes: int, label: str,
                     index: str = "") -> None:
    """Move a keyed absolute reservation from ``old_bytes`` to
    ``new_bytes``: apply the delta to the fielddata breaker (raises
    CircuitBreakingError on overflow — the ledger is then left at the
    old figure, matching the breaker) and update the ledger entry."""
    fd = breaker_service.breaker("fielddata")
    if new_bytes > old_bytes:
        fd.add_estimate(new_bytes - old_bytes, label)
    elif old_bytes > new_bytes:
        fd.release(old_bytes - new_bytes)
    led = getattr(breaker_service, "device_ledger", None)
    if led is not None:
        led.set_absolute(engine_uuid, component, new_bytes, index=index)


def global_snapshot() -> dict:
    """Merge every live ledger's per-component/per-index books — the
    process-wide view ``timeseries.collect_sample`` reads when it is
    given no node's ledger (in-process clusters have one ledger per
    node; a process without nodes still sees the device reader / block
    cache charges)."""
    totals = {"total_bytes": 0, "charged_bytes": 0, "uncharged_bytes": 0,
              "entries": 0,
              "by_component": {c: 0 for c in COMPONENTS},
              "per_device": {}, "indices": {}}
    for led in list(_ALL):
        snap = led.snapshot()
        for k in ("total_bytes", "charged_bytes", "uncharged_bytes",
                  "entries"):
            totals[k] += snap[k]
        for comp, b in snap["by_component"].items():
            totals["by_component"][comp] = \
                totals["by_component"].get(comp, 0) + b
        for dev, b in snap["per_device"].items():
            totals["per_device"][dev] = \
                totals["per_device"].get(dev, 0) + b
        for name, idx in snap["indices"].items():
            dst = totals["indices"].setdefault(
                name, {"total_bytes": 0, "components": {}})
            dst["total_bytes"] += idx["total_bytes"]
            for comp, b in idx["components"].items():
                dst["components"][comp] = \
                    dst["components"].get(comp, 0) + b
    return totals
