"""The ENGINE's distributed query plane — shard_map over ("dp", "shard").

Where the host path fans a query out over per-shard RPCs
(action/search_action.py, ref: TransportSearchTypeAction.java:137) and
merges at the coordinator (SearchPhaseController.sortDocs:165), this module
runs the SAME engine artifacts — the segments real Engines built from
indexed documents, their live/delete bitmaps, the query-DSL resolve/emit
closures of search/execute.py — as ONE SPMD program over a device mesh:

* every engine shard's segments are padded to common shape buckets,
  stacked on a leading axis and sharded over the ``shard`` mesh axis
  (doc-partition = the reference's hash-routed shard); when the index has
  more shards than devices (incl. the 1-chip case) each device holds a
  block of ``spd = n_shards // mesh_shard`` stacked shards and merges
  them locally before the collective;
* the query batch is sharded over ``dp`` (concurrent-searches axis);
* term statistics are aggregated globally host-side (search/dfs.py — the
  DFS round; term *ids* stay per-shard constants since segment
  dictionaries differ) so every shard scores with identical idf/avgdl;
* in-program: per-slot emit under ``jax.vmap`` → per-shard top-k →
  ``all_gather`` over ICI + re-top-k, per-shard hit counts via an
  all_gather lane — the whole scatter-gather-reduce with no host round
  trips (SURVEY §2.2/§2.10).

Eligible request shapes (everything else raises QueryParsingError and the
caller falls back to the RPC fan-out):

* score-ordered top-k (the original plane);
* **sort-by-field** — numeric doc-values sort keys ride the merge as
  double-double (hi, lo) pairs; per-shard selection is a multi-key stable
  argsort (value asc/desc, tie by doc id) and the cross-shard merge
  re-sorts the gathered candidate keys with shard-major tie-break, the
  (sort values, shard, position) order of SearchPhaseController.sortDocs;
* **keyword sorts** — ordinal columns lift to ranks in a cross-shard
  UNION vocabulary (the host path's vocab-union, precomputed per data
  generation into an f32 operand lane; exact below 2^24 terms);
* **post_filter** — a second mask emit ANDed into hits but not into the
  aggregation mask (SearchContext.postFilter semantics);
* **min_score** — per-query score threshold const;
* **search_after with a field sort** — the cursor becomes an in-program
  lexicographic strictly-greater mask over the transformed sort keys
  (keyword cursor terms map to union ranks, absent terms to the
  bisect − ½ midpoint);
* **score-order search_after** — the bare [score] cursor runs as the
  same in-program (score, doc) continuation mask run_segment applies;
* **metric aggs** (min/max/sum/avg/value_count/stats) psum'd in-program;
* **terms / histogram bucket aggs** — fixed-width in-program reductions:
  per-(shard, slot) ordinal counts (exact, vocab-sized) and
  double-double histogram scatter-adds against a statically-based bucket
  window, all_gathered and rendered through the same
  ``reduce_aggs`` pipeline the RPC coordinator uses
  (InternalAggregations.reduce analog).

Placement: on a mesh of several devices (the node setting
``search.mesh``) every shard's blocks are uploaded to the device(s) of
its mesh column and stay there; with one shard a device the stacked
``P("shard")`` operands are assembled from those buffers in place — one
resident copy of every column, nothing staged through device 0, the
blocks' own breaker charges the whole booking. On one device
(``spd = n_shards``) blocks go to the default device and are stacked
there, as ever. A batch of BM25 ``match`` queries of unequal lengths
plans to ONE signature (term lists padded to the batch's widest term
bucket, ``execute.match_term_floor``), so a mixed-length ``_msearch``
is one dispatch.

Three-layer caching: per-SEGMENT device blocks live in a module-level
cache keyed by (engine uuid, block uid, slot-layout signature) — a
refresh uploads only newly built segments' columns and changed live
masks (delete-only refreshes ship ZERO column bytes), counter-verified
via jit_exec's data_layer.{bytes_uploaded,bytes_reused,...}; each
MeshEngineSearcher instance is the DATA layer (stacked per-slot
operands COMPOSED device-side from resident blocks per refresh
generation, unchanged slots reusing the previous generation's
operands); compiled shard_map programs live in a module-level
SHAPE-keyed cache (plan signature, slot layouts, k/batch buckets,
sort/agg specs, mesh geometry) that survives data rebuilds — a repeated
sorted/terms-agg query re-traces at most once per shape,
counter-verified via jit_exec.mesh_program_{hits,misses}.

Statistics modes: ``search_batch(global_stats=True)`` scores every shard
with globally aggregated DFS statistics (dfs_query_then_fetch — the
plane's native mode); ``global_stats=False`` scores each shard with its
OWN statistics, bit-matching the default fan-out so plain searches ride
the plane too. Multi-index batches pass one mapper per engine shard
(``mapper_services``) and pack every index's shard columns into the same
program.

Results equal the RPC path's (the host merge concatenates shard
payloads in the same shard order the all_gather does, and the selection
orders are stable): the same documents in the same order and the same
totals — asserted by tests/test_mesh_engine.py, the driver's
dryrun_multichip and tests/test_bm25_4shard_config.py; a BM25 score's
last bit may differ between the two compiled programs (1.4e-7 relative
at the most), and is equal between the one-device and the placed
plane.
"""

from __future__ import annotations

import bisect
import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.common.errors import QueryParsingError
from elasticsearch_tpu.index.device_reader import (
    DeviceKeywordField, DeviceNumericField, DeviceSegment, DeviceTextField,
    dd_split)
from elasticsearch_tpu.index.segment import (
    KeywordFieldColumn, Segment, TextFieldColumn)
from elasticsearch_tpu.observability.tracing import (
    close_launches, device_span, launch_scope, span)
# module-level on purpose: step_local runs under shard_map tracing, and
# an import executed at trace time caches foreign tracers into the
# imported module's globals (trace-purity rule)
from elasticsearch_tpu.ops import aggs_ops
from elasticsearch_tpu.search import dfs as dfs_mod
from elasticsearch_tpu.search.execute import ExecutionContext
from elasticsearch_tpu.search.jit_exec import (
    _build, _plan, seg_flatten, seg_rebuild, layout_key)
from elasticsearch_tpu.search.phase import parse_search_request

_FLAGS = {
    "min_score": False, "_min_score": 0.0,
    "search_after": False, "_sa_score": 0.0, "_sa_doc": -1,
    "_doc_base": 0, "want_topk": True, "want_arrays": False,
}

#: metric aggregations the collective plane reduces IN-PROGRAM: per-shard
#: partials from the query mask and numeric columns, then psum/pmin/pmax
#: over the shard mesh axis (SURVEY §2.10 "aggregation tree reduce" on
#: ICI instead of the host coordinator)
_MESH_METRICS = ("min", "max", "sum", "avg", "value_count", "stats")

#: histogram bucket-window cap — the whole field range must bucketize
#: into this many slots for the static-base scatter-add (matches the RPC
#: device path's _MAX_DEVICE_HISTO_BUCKETS discipline)
_MAX_HISTO_BUCKETS = 4096

#: terms agg budget: padded_vocab × batch × shards cells gathered per agg
_MAX_TERMS_CELLS = 1 << 26

#: keyword-sort union ranks ride the merge as f32 — exact only below 2^24
_MAX_KW_SORT_VOCAB = 1 << 24

# ---------------------------------------------------------------------------
# The PROGRAM layer of the collective plane's two-layer cache.
#
# A MeshEngineSearcher instance is the DATA layer: stacked shard columns,
# templates and extrema, rebuilt whenever a refresh bumps any shard's
# generation. The compiled shard_map programs live here instead, keyed by
# everything that shapes the traced computation (plan signatures, slot
# layouts, k/batch buckets, sort/agg specs, mesh geometry) — so a repeated
# sorted/terms-agg query re-traces at most once per SHAPE, not once per
# refresh generation. jit_exec's mesh_program_{hits,misses} counters prove
# the contract (tier-1 regression guard in tests/test_collective_plane.py).
# ---------------------------------------------------------------------------
_PROGRAM_CACHE_CAP = 64
_program_cache: "OrderedDict[tuple, object]" = OrderedDict()
_program_lock = threading.Lock()
#: one enqueue of a mesh program at a time, process-wide: a program over
#: several devices is enqueued device by device, and two request threads
#: that interleave their enqueues can leave two devices with the two
#: programs in opposite orders — each then waits in its all_gather for
#: the other, for ever. Held for the (asynchronous) enqueue only, never
#: for the wait. (A guard, not a repair of something seen: PR 34's first
#: chip run served 76 dispatches from two threads without it.)
_enqueue_lock = threading.Lock()


def clear_program_cache() -> None:
    with _program_lock:
        _program_cache.clear()


# ---------------------------------------------------------------------------
# The BLOCK layer: per-segment device-resident columns.
#
# Between the per-generation DATA layer (stacked, mesh-sharded operands)
# and the shape-keyed PROGRAM layer sits a module-level cache of
# per-segment device blocks keyed by (engine uuid, block uid, slot-layout
# signature). A refresh that adds one segment uploads ONLY that segment's
# padded columns (plus same-shaped empty fillers for shards that don't
# reach the new slot); every other block is already device-resident and
# the next-generation stacked layer is COMPOSED from resident blocks with
# a device-side stack — no host restack, no host→device re-upload. A
# delete-only refresh re-uploads just the changed live masks (zero column
# bytes). Blocks are fielddata-charged individually (OneShotCharge) and
# released exactly once: on supersession (merge drops the source
# segments from the reader → prune), LRU eviction, or engine close.
# jit_exec's data_layer.* counters prove the contract (tier-1 guards in
# tests/test_incremental_plane.py).
# ---------------------------------------------------------------------------
_BLOCK_CACHE_CAP = 512
#: block_uid sentinel for the shared empty-filler block of a slot layout
#: (shards whose view has fewer segments than n_slots)
_EMPTY_UID = 0


def _upload_block(flat_np: list, owners: tuple | None,
                  kind: str = "block") -> list:
    """One block's host arrays → device, under the ``upload`` seam (a
    fault raised there leaves the caller's block as it was). ``owners``
    None: the default device. Else: every array to each owning device,
    with a leading axis of one (a view on the host, so the device buffer
    IS a shard of the stacked operand) → per array the tuple of its
    owners' buffers."""
    from elasticsearch_tpu.search import jit_exec
    with device_span("upload") as dsp:
        jit_exec.device_fault_point("upload")
        if owners is None:
            arrays = [jax.device_put(a) for a in flat_np]
        else:
            arrays = [tuple(jax.device_put(a[None], d) for d in owners)
                      for a in flat_np]
        dsp.set(bytes=int(sum(a.nbytes for a in flat_np)), kind=kind)
    return arrays


def _compose_placed(mesh: Mesh, spd: int, shard_blocks: list) -> list:
    """The ``P("shard")`` operands of one slot from its owner-placed
    blocks. ``shard_blocks[si][i]``: array ``i`` of shard ``si`` as the
    tuple of its owners' ``[1, ...]`` buffers, dp-major. With one shard
    a device (``spd`` 1) a block's buffer is the operand's shard on its
    device: nothing is copied and nothing passes through another device.
    With ``spd`` shards a device the owner concatenates its own blocks
    (one copy, on the device that keeps it)."""
    sharding = NamedSharding(mesh, P("shard"))
    n_dp, s_mesh = mesh.devices.shape
    out = []
    for i in range(len(shard_blocks[0])):
        bufs = []
        for r in range(n_dp):
            for col in range(s_mesh):
                mine = [shard_blocks[col * spd + li][i][r]
                        for li in range(spd)]
                bufs.append(mine[0] if spd == 1
                            else jnp.concatenate(mine, axis=0))
        shape = (len(shard_blocks),) + tuple(bufs[0].shape[1:])
        out.append(jax.make_array_from_single_device_arrays(
            shape, sharding, bufs))
    return out


class _Block:
    __slots__ = ("key", "template", "arrays", "live_np", "col_bytes",
                 "extrema", "charge")

    def __init__(self, key, template, arrays, live_np, col_bytes,
                 extrema, charge):
        self.key = key
        self.template = template        # DeviceSegment (host numpy views)
        self.arrays = arrays            # device arrays, seg_flatten order
        self.live_np = live_np          # padded live mask (host copy)
        self.col_bytes = col_bytes      # charged column bytes (excl. live)
        self.extrema = extrema          # numeric field → (min, max)
        self.charge = charge            # OneShotCharge | None


class _DeviceBlockCache:
    def __init__(self, cap: int = _BLOCK_CACHE_CAP):
        self.cap = cap
        self._lru: "OrderedDict[tuple, _Block]" = OrderedDict()
        self._lock = threading.Lock()

    def fetch(self, engine_uuid: str, lay_sig: tuple, lay: "_SlotLayout",
              seg, live, doc_base: int, breaker_service, label: str,
              owners: tuple | None = None):
        """→ (template, device arrays, extrema, col_up, mask_up, reused):
        the padded per-segment device block, built+uploaded on miss,
        composed from residency on hit. A hit with a changed live mask
        re-uploads ONLY the mask (the delete path's zero-column-byte
        refresh). Byte counts are actual host→device transfer; `reused`
        is the resident column bytes a rebuild did not re-ship.

        ``owners``: on a multi-device mesh, the devices that own the
        block's shard (one per ``dp`` replica). Every array then goes up
        to each owner directly, with a leading axis of one, and the
        entry of ``arrays`` is the tuple of those single-device buffers:
        the ``P("shard")`` operands are assembled from them in place
        (:func:`_compose_placed`). ``None`` = the default device and
        bare arrays, as ever."""
        from elasticsearch_tpu.search import jit_exec
        uid = seg.block_uid if seg is not None else _EMPTY_UID
        key = (engine_uuid, uid, lay_sig) if owners is None else \
            (engine_uuid, uid, (lay_sig, tuple(int(d.id) for d in owners)))
        live_np = _pad1(live, lay.np_docs, False) if live is not None \
            else None
        with self._lock:
            blk = self._lru.get(key)
            if blk is not None:
                self._lru.move_to_end(key)
                if blk.charge is not None:
                    blk.charge.touch()     # ledger recency (hot/cold)
                mask_up = 0
                if live_np is not None and \
                        not np.array_equal(blk.live_np, live_np):
                    # mask-delta refresh: re-ship ONLY the live rows;
                    # updated under the lock so a racing pack build
                    # captures a consistent (template, arrays) pair
                    # (newest mask wins — equivalent to a refresh landing
                    # mid-build, which the plane already tolerates).
                    # This is a real host→device transfer: it draws from
                    # the fault seam like every other upload (a raise
                    # here leaves the block consistent on the old mask)
                    blk.arrays = _upload_block(
                        [live_np], owners, "mask-delta") + blk.arrays[1:]
                    blk.template = dc_replace(blk.template, live=live_np)
                    blk.live_np = live_np
                    mask_up = int(live_np.nbytes)
                tpl = blk.template
                if tpl.doc_base != doc_base:
                    tpl = dc_replace(tpl, doc_base=doc_base)
                return (tpl, blk.arrays, blk.extrema, 0, mask_up,
                        blk.col_bytes)
        template = _build_template(lay, seg, live, doc_base)
        flat_np = seg_flatten(template)
        arrays = _upload_block(flat_np, owners)
        mask_bytes = int(flat_np[0].nbytes)
        col_bytes = int(sum(a.nbytes for a in flat_np[1:]))
        extrema = _segment_extrema(seg) if seg is not None else {}
        charge = None
        if breaker_service is not None:
            from elasticsearch_tpu.common.breaker import OneShotCharge
            # a placed block's ledger rows name its owning device (dp
            # replicas share the first owner's attribution, as the
            # placed cache's do), so _cat/hbm?totals=true and
            # _nodes/stats device_memory.per_device show the placement
            charge = OneShotCharge(
                breaker_service, col_bytes + mask_bytes,
                engine_uuid=engine_uuid, block_id=uid,
                parts={"mesh-columns": col_bytes,
                       "masks": mask_bytes},
                device="" if owners is None
                else str(int(owners[0].id))).charge(label)
        blk = _Block(key, template, arrays, template.live, col_bytes,
                     extrema, charge)
        evicted = []
        with self._lock:
            cur = self._lru.get(key)
            if cur is not None:
                # raced duplicate build: keep the incumbent, return our
                # charge — counting OUR upload is still honest (the
                # transfer happened)
                self._lru.move_to_end(key)
                if charge is not None:
                    charge.release()
                blk = cur
            else:
                self._lru[key] = blk
                while len(self._lru) > self.cap:
                    evicted.append(self._lru.popitem(last=False)[1])
        for old in evicted:
            if old.charge is not None:
                old.charge.release()
        return blk.template, blk.arrays, blk.extrema, col_bytes, \
            mask_bytes, 0

    def fetch_aux(self, key: tuple, build_np, breaker_service, label: str,
                  component: str = "impact"):
        """Auxiliary per-segment device arrays (the impact lane's
        quantized columns + block maxima) in the SAME LRU as the column
        blocks — same keying discipline (engine uuid, block uid, sig),
        same OneShotCharge accounting, same prune/release/evict sweeps.
        ``build_np`` is called only on miss and returns the host arrays.
        → (device arrays, uploaded bytes, reused bytes). The device
        transfer itself happens at the CALLER'S seam site (the caller
        passes already-uploaded arrays via the build closure would hide
        the seam — instead the closure returns host arrays and the
        upload happens here under the impact-upload site)."""
        from elasticsearch_tpu.search import jit_exec
        with self._lock:
            blk = self._lru.get(key)
            if blk is not None:
                self._lru.move_to_end(key)
                if blk.charge is not None:
                    blk.charge.touch()     # ledger recency (hot/cold)
                return blk.arrays, 0, blk.col_bytes
        flat_np = [np.ascontiguousarray(a) for a in build_np()
                   if a is not None]
        with device_span("impact-upload") as dsp:
            jit_exec.device_fault_point("impact-upload")
            arrays = [jax.device_put(a) for a in flat_np]
            dsp.set(bytes=int(sum(a.nbytes for a in flat_np)),
                    kind="impact-block")
        col_bytes = int(sum(a.nbytes for a in flat_np))
        charge = None
        if breaker_service is not None:
            from elasticsearch_tpu.common.breaker import OneShotCharge
            charge = OneShotCharge(breaker_service, col_bytes,
                                   component=component,
                                   engine_uuid=str(key[0]),
                                   block_id=key[1]).charge(label)
        blk = _Block(key, None, arrays, np.zeros(0, bool), col_bytes,
                     {}, charge)
        evicted = []
        lost_race = False
        with self._lock:
            cur = self._lru.get(key)
            if cur is not None:
                # raced duplicate build: keep the incumbent and return
                # our charge. Report the bytes as REUSED, not uploaded —
                # the impact counters verify the incremental-refresh
                # discipline (unchanged segments upload zero bytes), and
                # the loser's discarded transfer would fail that proof
                # spuriously.
                self._lru.move_to_end(key)
                if charge is not None:
                    charge.release()
                blk = cur
                lost_race = True
            else:
                self._lru[key] = blk
                while len(self._lru) > self.cap:
                    evicted.append(self._lru.popitem(last=False)[1])
        for old in evicted:
            if old.charge is not None:
                old.charge.release()
        if lost_race:
            return blk.arrays, 0, blk.col_bytes
        return blk.arrays, col_bytes, 0

    def aux_lookup(self, key: tuple):
        """LRU-touching lookup of an auxiliary block → (arrays,
        col_bytes) or None. Split out from :meth:`fetch_aux` so lanes
        with their OWN seam site (the knn lane's ``vector-upload``) can
        run the upload under a literal site class at their call site —
        the device-seam lint requires the site be a literal, so the
        shared path cannot take it as a parameter."""
        with self._lock:
            blk = self._lru.get(key)
            if blk is None:
                return None
            self._lru.move_to_end(key)
            if blk.charge is not None:
                blk.charge.touch()         # ledger recency (hot/cold)
            return blk.arrays, blk.col_bytes

    def aux_install(self, key: tuple, arrays: list, col_bytes: int,
                    breaker_service, label: str,
                    component: str = "vector"):
        """Install an already-uploaded auxiliary block → (arrays,
        uploaded, reused). A raced duplicate build keeps the incumbent
        and reports OUR bytes as REUSED (the loser's transfer must not
        fail the incremental-refresh counter proofs spuriously)."""
        charge = None
        if breaker_service is not None:
            from elasticsearch_tpu.common.breaker import OneShotCharge
            charge = OneShotCharge(breaker_service, col_bytes,
                                   component=component,
                                   engine_uuid=str(key[0]),
                                   block_id=key[1]).charge(label)
        blk = _Block(key, None, arrays, np.zeros(0, bool), col_bytes,
                     {}, charge)
        evicted = []
        lost_race = False
        with self._lock:
            cur = self._lru.get(key)
            if cur is not None:
                self._lru.move_to_end(key)
                if charge is not None:
                    charge.release()
                blk = cur
                lost_race = True
            else:
                self._lru[key] = blk
                while len(self._lru) > self.cap:
                    evicted.append(self._lru.popitem(last=False)[1])
        for old in evicted:
            if old.charge is not None:
                old.charge.release()
        if lost_race:
            return blk.arrays, 0, blk.col_bytes
        return blk.arrays, col_bytes, 0

    def drop_stale_aux(self, engine_uuid: str, block_uid: int,
                       sig_prefix: tuple, quant_gen: int) -> int:
        """Release prior-quantization auxiliary blocks of ONE live
        segment: a df-drift requant bumps quant_gen into the cache key,
        so without this sweep the old generation stays keyed to a
        still-live block_uid and prune(live_uids) never evicts it —
        stale device arrays and breaker bytes would persist until
        LRU-cap pressure or engine close. → bytes released."""
        freed = 0
        with self._lock:
            dead = [k for k in self._lru
                    if k[0] == engine_uuid and k[1] == block_uid
                    and isinstance(k[2], tuple)
                    and k[2][:len(sig_prefix)] == sig_prefix
                    and k[2][len(sig_prefix)] < quant_gen]
            gone = [self._lru.pop(k) for k in dead]
        for blk in gone:
            freed += blk.col_bytes + int(blk.live_np.nbytes)
            if blk.charge is not None:
                blk.charge.release()
        return freed

    def prune(self, engine_uuid: str, live_uids: set) -> int:
        """Release blocks of this engine whose segment left the reader
        view (merged away / superseded). Empty fillers and layout
        variants of LIVE segments stay (bounded by the LRU cap) — a
        competing pack with a different slot layout must not thrash.
        → bytes released."""
        freed = 0
        with self._lock:
            dead = [k for k in self._lru
                    if k[0] == engine_uuid and k[1] != _EMPTY_UID
                    and k[1] not in live_uids]
            gone = [self._lru.pop(k) for k in dead]
        for blk in gone:
            freed += blk.col_bytes + int(blk.live_np.nbytes)
            if blk.charge is not None:
                blk.charge.release()
        return freed

    def release_engine(self, engine_uuid: str) -> None:
        """Engine close: drop every block (incl. empty fillers) charged
        against this engine incarnation."""
        with self._lock:
            dead = [k for k in self._lru if k[0] == engine_uuid]
            gone = [self._lru.pop(k) for k in dead]
        for blk in gone:
            if blk.charge is not None:
                blk.charge.release()

    def clear(self) -> None:
        with self._lock:
            gone = list(self._lru.values())
            self._lru.clear()
        for blk in gone:
            if blk.charge is not None:
                blk.charge.release()

    def evict_cold(self, fraction: float = 0.5) -> int:
        """HBM-OOM response: drop the least-recently-used `fraction` of
        cached blocks, releasing their fielddata charges, so the next
        pack (re)build retries against reclaimed headroom. Blocks still
        referenced by a serving pack stay alive through the pack's own
        references — only the cache residency (and its accounting) is
        given up. → bytes released."""
        with self._lock:
            n = int(len(self._lru) * fraction) if self._lru else 0
            n = max(n, 1) if self._lru else 0
            gone = [self._lru.popitem(last=False)[1] for _ in range(n)]
        freed = 0
        for blk in gone:
            freed += blk.col_bytes + int(blk.live_np.nbytes)
            if blk.charge is not None:
                blk.charge.release()
        return freed

    def keys(self) -> list:
        with self._lock:
            return list(self._lru)

    def stats(self) -> dict:
        with self._lock:
            blocks = list(self._lru.values())
        return {"entries": len(blocks),
                "resident_bytes": sum(b.col_bytes + int(b.live_np.nbytes)
                                      for b in blocks),
                "charged_bytes": sum(b.charge.nbytes for b in blocks
                                     if b.charge is not None)}


_block_cache = _DeviceBlockCache()


def clear_block_cache() -> None:
    _block_cache.clear()
    _placed_cache.clear()


def block_cache_stats() -> dict:
    return _block_cache.stats()


def block_cache_keys() -> list:
    """(engine uuid, block uid, layout sig) of every resident block —
    the chaos suites' no-stale-``block_uid`` consistency check."""
    return _block_cache.keys()


def evict_cold_blocks(fraction: float = 0.5) -> int:
    """Module entry for the HBM-OOM response (jit_exec.note_device_error):
    evict the coldest `fraction` of device blocks → bytes released."""
    return _block_cache.evict_cold(fraction)


def fetch_impact_block(engine_uuid: str, block_uid: int, field: str,
                       icol, breaker_service):
    """One segment's impact arrays (quantized column + block maxima),
    device-resident through the per-segment block cache — the PR 5
    discipline: a refresh uploads impact bytes ONLY for segments whose
    block_uid (or quantization generation, after a df-drift requant) is
    new; resident blocks reuse outright. A requant's fresh generation
    evicts the prior one for the same segment (the old key points at a
    still-live block_uid, so the prune(live_uids) sweep alone would
    never reclaim it). → (qimp device array, block_max device array |
    None, uploaded bytes, reused bytes)."""
    has_bm = icol.block_max is not None
    key = (engine_uuid, block_uid,
           ("impact", field, icol.bits, icol.block_rows, icol.quant_gen,
            has_bm))
    arrays, up, re = _block_cache.fetch_aux(
        key, lambda: [icol.qimp, icol.block_max], breaker_service,
        f"impact block [{engine_uuid[:8]}]")
    if icol.quant_gen > 0:
        _block_cache.drop_stale_aux(
            engine_uuid, block_uid,
            ("impact", field, icol.bits, icol.block_rows),
            icol.quant_gen)
    if has_bm:
        return arrays[0], arrays[1], up, re
    return arrays[0], None, up, re


def fetch_vector_block(engine_uuid: str, block_uid: int, field: str,
                       sig: tuple, build_np, breaker_service):
    """One segment's knn-lane vector arrays (normalized f32 or
    int8-quantized columns + exists [+ token lens]), device-resident
    through the per-segment block cache — the PR 5 discipline: a
    refresh uploads vector bytes ONLY for new segments; resident blocks
    reuse outright (counter-verified via data_layer.vector_bytes_*).
    ``build_np`` is called only on miss and returns the host arrays.
    → (device arrays, uploaded bytes, reused bytes)."""
    from elasticsearch_tpu.search import jit_exec
    key = (engine_uuid, block_uid, ("vector", field) + tuple(sig))
    hit = _block_cache.aux_lookup(key)
    if hit is not None:
        return hit[0], 0, hit[1]
    flat_np = [np.ascontiguousarray(a) for a in build_np()
               if a is not None]
    with device_span("vector-upload") as dsp:
        jit_exec.device_fault_point("vector-upload")
        arrays = [jax.device_put(a) for a in flat_np]
        dsp.set(bytes=int(sum(a.nbytes for a in flat_np)),
                kind="vector-block")
    col_bytes = int(sum(a.nbytes for a in flat_np))
    return _block_cache.aux_install(
        key, arrays, col_bytes, breaker_service,
        f"vector block [{engine_uuid[:8]}]")


# ---------------------------------------------------------------------------
# Placement-aware block cache: the mesh-sharded retrieval lanes' sibling
# of _DeviceBlockCache. Where the plain cache parks a block on the
# default device, this one PINS each block's rows to owning devices —
# the host arrays (padded so axis 0 divides by the mesh's shard count)
# upload once under NamedSharding(mesh, P("shard")), and a refresh that
# changes only some rows (a delete flipping one shard's live-mask
# slice, one shard's new segment rows) re-ships ONLY the changed shard
# slices to their owning devices, rebuilding the global array around
# the other shards' still-resident buffers. Keys carry the mesh
# geometry, so a dp×shard re-shape never aliases stale placements.
# Counter contract (data_layer.placement_bytes_{uploaded,reused}):
# uploaded = host bytes of shard slices actually shipped, reused =
# resident slice bytes a fetch did not re-ship.
# ---------------------------------------------------------------------------
_PLACED_CACHE_CAP = 256


class _PlacedBlock:
    __slots__ = ("arrays", "host_slices", "nbytes", "charge")

    def __init__(self, arrays, host_slices, nbytes, charge):
        self.arrays = arrays            # placed jax arrays (shard axis 0)
        self.host_slices = host_slices  # per array: S host slice copies
        self.nbytes = nbytes            # charged host bytes (one copy)
        self.charge = charge            # OneShotCharge | None


def _replace_shard_slices(arr, shape, col_slices, changed_cols, mesh):
    """Rebuild ONE placed array with fresh buffers only on the owning
    devices of the changed shard columns, reusing every other shard's
    resident device buffer — the delta-refresh half of the placement
    contract."""
    from elasticsearch_tpu.search import jit_exec
    s_axis = int(mesh.shape["shard"])
    rows = shape[0] // s_axis
    sharding = NamedSharding(mesh, P("shard"))
    bufs = []
    with device_span("block-placement-upload"):
        jit_exec.device_fault_point("block-placement-upload")
        for sh in arr.addressable_shards:
            col = int(sh.index[0].start or 0) // rows
            if col in changed_cols:
                bufs.append(jax.device_put(col_slices[col], sh.device))
            else:
                bufs.append(sh.data)
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        bufs)


class _PlacedBlockCache:
    def __init__(self, cap: int = _PLACED_CACHE_CAP):
        self.cap = cap
        self._lru: "OrderedDict[tuple, _PlacedBlock]" = OrderedDict()
        self._lock = threading.Lock()

    def fetch(self, mesh, key: tuple, build_np, breaker_service,
              label: str, component: str = "impact"):
        """→ (placed device arrays, uploaded bytes, reused bytes).
        ``build_np`` returns the host arrays, every axis-0 length
        divisible by the mesh's shard count (the caller pads). Called
        on EVERY fetch — the arrays are views over segment columns, and
        the per-slice diff against the resident host copies is what
        routes a refresh delta to owning devices only."""
        from elasticsearch_tpu.search import jit_exec
        s_axis = int(mesh.shape["shard"])
        geom = (tuple(sorted(mesh.shape.items())),
                tuple(int(d.id) for d in mesh.devices.flat))
        full_key = tuple(key) + (geom,)
        flat_np = [np.ascontiguousarray(a) for a in build_np()
                   if a is not None]
        slices = [[np.ascontiguousarray(s)
                   for s in np.split(a, s_axis, axis=0)]
                  for a in flat_np]
        with self._lock:
            blk = self._lru.get(full_key)
            if blk is not None:
                self._lru.move_to_end(full_key)
                if blk.charge is not None:
                    blk.charge.touch()     # ledger recency (hot/cold)
                changed = [(ai, si)
                           for ai, (old_sl, new_sl)
                           in enumerate(zip(blk.host_slices, slices))
                           for si in range(s_axis)
                           if not np.array_equal(old_sl[si], new_sl[si])]
                if not changed:
                    return blk.arrays, 0, blk.nbytes
                up = sum(int(slices[ai][si].nbytes)
                         for ai, si in changed)
                # delta refresh: re-ship ONLY the changed shard slices
                # to their owning devices (updated under the lock so a
                # racing fetch sees a consistent arrays/host pair; a
                # fault raise leaves the block whole on the old data)
                with device_span("block-placement-upload") as dsp:
                    jit_exec.device_fault_point("block-placement-upload")
                    new_arrays = list(blk.arrays)
                    for ai in sorted({a for a, _ in changed}):
                        cols = {si for a2, si in changed if a2 == ai}
                        new_arrays[ai] = _replace_shard_slices(
                            blk.arrays[ai], flat_np[ai].shape,
                            slices[ai], cols, mesh)
                    dsp.set(bytes=up, kind="placed-delta")
                blk.arrays = new_arrays
                blk.host_slices = slices
                return blk.arrays, up, blk.nbytes - up
        with device_span("block-placement-upload") as dsp:
            jit_exec.device_fault_point("block-placement-upload")
            arrays = [jax.device_put(a, NamedSharding(mesh, P("shard")))
                      for a in flat_np]
            nbytes = int(sum(a.nbytes for a in flat_np))
            dsp.set(bytes=nbytes, kind="placed-block")
        charge = None
        if breaker_service is not None:
            from elasticsearch_tpu.common.breaker import OneShotCharge
            # one ledger row per owning device (the shard column's
            # first-row device — dp replicas share its attribution), so
            # _cat/hbm and _nodes/stats.device_memory.per_device show
            # the placement while Σ per_device stays the host bytes
            per_dev: dict = {}
            for si in range(s_axis):
                dev = str(int(mesh.devices[0, si].id))
                per_dev[dev] = per_dev.get(dev, 0) + sum(
                    int(sl[si].nbytes) for sl in slices)
            charge = OneShotCharge(
                breaker_service, nbytes, component=component,
                engine_uuid=str(key[0]), block_id=key[1],
                device_parts=per_dev).charge(label)
        blk = _PlacedBlock(arrays, slices, nbytes, charge)
        evicted = []
        lost_race = False
        with self._lock:
            cur = self._lru.get(full_key)
            if cur is not None:
                # raced duplicate build: keep the incumbent, report our
                # bytes as REUSED (the counter proofs' discipline —
                # same as _DeviceBlockCache.fetch_aux)
                self._lru.move_to_end(full_key)
                if charge is not None:
                    charge.release()
                blk = cur
                lost_race = True
            else:
                self._lru[full_key] = blk
                while len(self._lru) > self.cap:
                    evicted.append(self._lru.popitem(last=False)[1])
        for old in evicted:
            if old.charge is not None:
                old.charge.release()
        if lost_race:
            return blk.arrays, 0, blk.nbytes
        return blk.arrays, nbytes, 0

    def release_engine(self, engine_uuid: str) -> None:
        with self._lock:
            dead = [k for k in self._lru if k[0] == engine_uuid]
            gone = [self._lru.pop(k) for k in dead]
        for blk in gone:
            if blk.charge is not None:
                blk.charge.release()

    def clear(self) -> None:
        with self._lock:
            gone = list(self._lru.values())
            self._lru.clear()
        for blk in gone:
            if blk.charge is not None:
                blk.charge.release()

    def stats(self) -> dict:
        with self._lock:
            blocks = list(self._lru.values())
        return {"entries": len(blocks),
                "resident_bytes": sum(b.nbytes for b in blocks),
                "charged_bytes": sum(b.charge.nbytes for b in blocks
                                     if b.charge is not None)}


_placed_cache = _PlacedBlockCache()


def fetch_placed_block(mesh, engine_uuid: str, block_uid: int,
                       sig: tuple, build_np, breaker_service,
                       component: str = "impact"):
    """One segment's mesh-lane arrays pinned to their owning devices —
    → (placed device arrays, uploaded bytes, reused bytes). ``sig``
    distinguishes lanes/layouts (and must carry anything whose change
    should force a re-place, e.g. the impact quantization generation);
    the mesh geometry joins the key here."""
    key = (engine_uuid, block_uid, tuple(sig))
    return _placed_cache.fetch(
        mesh, key, build_np, breaker_service,
        f"placed block [{engine_uuid[:8]}]", component)


def clear_placed_cache() -> None:
    _placed_cache.clear()


def placed_cache_stats() -> dict:
    return _placed_cache.stats()


def hook_engine_block_release(engine) -> None:
    """Install the engine-close listener that returns every cached
    device block (columns AND impact blocks) charged against this
    engine incarnation — shared by the mesh searcher build and the
    impact pack builder so neither path can strand fielddata bytes."""
    if not getattr(engine, "_block_cache_hooked", False):
        hook = _EngineBlocksRelease(engine.engine_uuid)
        engine.__dict__.setdefault("_close_listeners",
                                   []).append(hook.release)
        engine._block_cache_hooked = True


class _EngineBlocksRelease:
    """Engine close listener: returns every cached device block charged
    against the engine incarnation (a bound method, so search_action's
    spent-one-shot listener pruning leaves it in place)."""

    __slots__ = ("engine_uuid",)

    def __init__(self, engine_uuid: str):
        self.engine_uuid = engine_uuid

    def release(self) -> None:
        _block_cache.release_engine(self.engine_uuid)
        _placed_cache.release_engine(self.engine_uuid)
        # the cost observatory drains with the engine too: programs
        # owned by this incarnation leave the table the same instant
        # their device blocks leave the cache (no rows for closed
        # engines — the ledger discipline)
        from elasticsearch_tpu.observability import costs
        costs.drop_owner(self.engine_uuid)


def _segment_extrema(seg) -> dict:
    """Exact per-segment f64 extrema per numeric field (exists-masked,
    live-independent — deletes never widen a bucket window, matching the
    previous whole-corpus scan) → cached with the block so a rebuild
    merges per-segment results instead of re-reducing the corpus."""
    out: dict[str, tuple[float, float]] = {}
    for name, col in seg.numeric_fields.items():
        vals = col.values[col.exists[:len(col.values)]] \
            if col.exists is not None else col.values
        if vals.size == 0:
            continue
        out[name] = (float(np.min(vals)), float(np.max(vals)))
    return out


def _stable_order(keys: list, kk: int):
    """Lexicographic ascending order over column-stacked keys [B, M]
    (most-significant first), ties broken by original index — composed
    stable argsorts from least- to most-significant key. → idx [B, kk]."""
    b, m = keys[0].shape
    order = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
    for key in keys[::-1]:
        cur = jnp.take_along_axis(key, order, axis=1)
        o2 = jnp.argsort(cur, axis=1, stable=True)
        order = jnp.take_along_axis(order, o2, axis=1)
    return order[:, :kk]


def _gather_payload(payload: dict, idx):
    return {name: jnp.take_along_axis(arr, idx, axis=1)
            for name, arr in payload.items()}


def _dd_fill(v: float) -> tuple[float, float]:
    """dd_split for fill/cursor scalars → plain floats (dd_split itself
    already zeroes the residual for non-finite inputs)."""
    hi, lo = dd_split(np.float64(v))
    return float(hi), float(lo)


@dataclass(frozen=True)
class _SortSpec:
    """One static sort key: _score, a numeric doc-values field, or a
    keyword ordinal column lifted to union ranks."""
    field: str                 # "" for _score
    order: str                 # "asc" | "desc"
    fill: float                # missing fill (±inf, numeric missing, rank)
    kind: str = "numeric"      # "score" | "numeric" | "keyword"

    @property
    def is_score(self) -> bool:
        return self.kind == "score"


def _mesh_sort_spec(reqs, layouts) -> tuple:
    """Validate + extract a batch-uniform field-sort spec.

    → tuple[_SortSpec]. Numeric doc-values sort in-program as
    double-double keys; keyword fields sort via a per-generation
    union-rank column (the host vocab-union, precomputed into an f32
    operand lane). Raises QueryParsingError for sorts the plane can't
    run in-program (analyzed-text/script sorts, _doc, custom keyword
    missing, per-request divergent specs) — callers route those to the
    RPC path."""
    raw0 = reqs[0].sort
    if any(req.sort != raw0 for req in reqs):
        raise QueryParsingError(
            "mesh engine plane requires one sort spec per batch")
    specs = []
    for spec in raw0:
        (fname, opts), = spec.items()
        order = opts.get("order", "asc")
        missing = opts.get("missing", "_last")
        if fname == "_doc":
            raise QueryParsingError(
                "mesh engine plane cannot sort by _doc (doc-id numbering "
                "is plane-local) — use the RPC fan-out path")
        if fname == "_score":
            specs.append(_SortSpec("", order, 0.0, "score"))
            continue
        in_text = any(fname in lay.text for lay in layouts)
        in_kw = any(fname in lay.keyword for lay in layouts)
        in_num = any(fname in lay.numeric for lay in layouts)
        if in_text:
            raise QueryParsingError(
                f"mesh engine plane cannot sort analyzed text "
                f"[{fname}] — use the RPC fan-out path")
        if in_kw and in_num:
            # same name mapped to different column kinds across shards
            # (multi-index batch with conflicting mappings): rank order
            # is undefined in one key space — host merge handles it
            raise QueryParsingError(
                f"sort field [{fname}] maps to both numeric and keyword "
                f"columns — use the RPC fan-out path")
        if in_kw:
            if missing not in ("_last", "_first"):
                raise QueryParsingError(
                    f"keyword sort [{fname}] with a custom missing term "
                    f"stays host-side — use the RPC fan-out path")
            fill = math.inf if (missing == "_last") == (order == "asc") \
                else -math.inf
            specs.append(_SortSpec(fname, order, fill, "keyword"))
            continue
        if missing in ("_last", "_first"):
            fill = math.inf if (missing == "_last") == (order == "asc") \
                else -math.inf
        else:
            try:
                fill = float(missing)
            except (TypeError, ValueError):
                raise QueryParsingError(
                    f"sort [{fname}] has a non-numeric missing "
                    f"substitute — use the RPC fan-out path") from None
        specs.append(_SortSpec(fname, order, fill))
    return tuple(specs)


def _mesh_agg_plan(reqs, layouts, field_extrema) -> tuple:
    """Validate + extract batch-uniform agg lanes.

    → (metric_spec, bucket_specs): metric_spec is the (name, kind, field)
    tuple of the psum lane; bucket_specs is a tuple of
    ("terms", name, resolved_field) / ("histogram", name, field,
    interval, base, n_buckets) entries. Raises QueryParsingError for aggs
    the plane can't reduce (sub-aggs, scripts, other bucket kinds,
    non-uniform specs) — callers route those to the RPC path."""
    metric_sig, bucket_sig = [], []
    for req in reqs:
        met, buck = [], []
        for node in req.aggs:
            if node.subs or node.pipelines:
                raise QueryParsingError(
                    f"mesh engine plane cannot reduce sub/pipeline aggs "
                    f"under [{node.name}] in-program — use the RPC "
                    f"fan-out path")
            if node.type in _MESH_METRICS:
                # 'missing'/'script' change per-doc values — the RPC
                # device path (aggregations.collect_device) rejects them
                # the same way
                if "field" not in node.params or \
                        set(node.params) - {"field", "format"}:
                    raise QueryParsingError(
                        f"mesh engine plane cannot reduce agg "
                        f"[{node.name}:{node.type}] in-program — use the "
                        f"RPC fan-out path")
                met.append((node.name, node.type,
                            str(node.params["field"])))
            elif node.type == "terms":
                if "field" not in node.params or \
                        set(node.params) - {"field", "size", "shard_size",
                                            "order", "min_doc_count",
                                            "format"}:
                    raise QueryParsingError(
                        f"mesh engine plane terms agg [{node.name}] has "
                        f"unsupported params — use the RPC fan-out path")
                fname = str(node.params["field"])
                if any(fname in lay.text for lay in layouts):
                    raise QueryParsingError(
                        f"terms over analyzed text [{fname}] stays "
                        f"host-side — use the RPC fan-out path")
                if any(fname in lay.keyword for lay in layouts) and \
                        any(fname in lay.numeric for lay in layouts):
                    raise QueryParsingError(
                        f"terms field [{fname}] maps to both numeric and "
                        f"keyword columns — use the RPC fan-out path")
                if any(fname in lay.keyword for lay in layouts):
                    resolved = fname
                elif any(f"{fname}.keyword" in lay.keyword
                         for lay in layouts):
                    resolved = f"{fname}.keyword"
                else:
                    raise QueryParsingError(
                        f"terms agg field [{fname}] is not a keyword "
                        f"column — use the RPC fan-out path")
                buck.append(("terms", node.name, resolved))
            elif node.type == "histogram":
                if "field" not in node.params or "interval" not in \
                        node.params or \
                        set(node.params) - {"field", "interval", "offset",
                                            "min_doc_count", "format",
                                            "order"}:
                    raise QueryParsingError(
                        f"mesh engine plane histogram [{node.name}] has "
                        f"unsupported params — use the RPC fan-out path")
                fname = str(node.params["field"])
                interval = float(node.params["interval"])
                offset = float(node.params.get("offset", 0.0))
                if interval <= 0:
                    raise QueryParsingError("histogram interval must be "
                                            "positive")
                ext = field_extrema.get(fname)
                if ext is None:
                    buck.append(("histogram", node.name, fname,
                                 interval, 0.0, 0))
                    continue
                fmin, fmax = ext
                first = math.floor((fmin - offset) / interval)
                last = math.floor((fmax - offset) / interval)
                n_buckets = int(last - first + 1)
                if n_buckets > _MAX_HISTO_BUCKETS:
                    raise QueryParsingError(
                        f"histogram [{node.name}] needs {n_buckets} "
                        f"buckets > {_MAX_HISTO_BUCKETS} — use the RPC "
                        f"fan-out path")
                base = first * interval + offset
                buck.append(("histogram", node.name, fname, interval,
                             base, n_buckets))
            else:
                raise QueryParsingError(
                    f"mesh engine plane cannot reduce agg "
                    f"[{node.name}:{node.type}] in-program — use the RPC "
                    f"fan-out path")
        metric_sig.append(tuple(met))
        bucket_sig.append(tuple(buck))
    if any(s != metric_sig[0] for s in metric_sig) or \
            any(s != bucket_sig[0] for s in bucket_sig):
        raise QueryParsingError(
            "mesh engine plane requires one agg spec per batch")
    return metric_sig[0] or None, bucket_sig[0] or None


def _pad2(a: np.ndarray, rows: int, cols: int, fill) -> np.ndarray:
    out = np.full((rows, cols), fill, a.dtype)
    out[:a.shape[0], :a.shape[1]] = a
    return out


def _pad1(a: np.ndarray, rows: int, fill) -> np.ndarray:
    out = np.full(rows, fill, a.dtype)
    out[:a.shape[0]] = a
    return out


def _fit(a: np.ndarray, shape: tuple, fill) -> np.ndarray:
    """An immutable segment COLUMN at the slot layout's shape: the
    column itself where it already has it (a 2 GB column of a bulk-
    loaded segment is neither copied nor held twice on the host), a
    padded copy otherwise. Live masks keep :func:`_pad1`: the mask-delta
    refresh compares a block's own copy with the view's."""
    if a.shape == shape and a.flags.c_contiguous:
        return a
    return _pad2(a, shape[0], shape[1], fill) if len(shape) == 2 \
        else _pad1(a, shape[0], fill)


@dataclass
class _SlotLayout:
    """Common padded layout of one segment slot across every shard."""
    np_docs: int
    text: dict[str, tuple[int, int]]       # field → (L, U)
    keyword: dict[str, int]                # field → K (ords width)
    kw_vocab: dict[str, int]               # field → padded vocab size
    numeric: list[str]

    def sig(self) -> tuple:
        """Hashable signature of everything that shapes the padded
        column ARRAYS (kw_vocab shapes the terms-agg lanes, not the
        arrays — it stays out, so a vocab-only drift does not re-upload
        a resident block)."""
        return (self.np_docs, tuple(sorted(self.text.items())),
                tuple(sorted(self.keyword.items())),
                tuple(self.numeric))


def _build_template(lay: _SlotLayout, seg, live, doc_base: int
                    ) -> DeviceSegment:
    """One shard/slot padded to the slot layout — numpy arrays + REAL
    host dictionaries (term/ordinal resolution). ``seg=None`` builds the
    empty filler for shards whose view has fewer segments than
    n_slots."""
    n = lay.np_docs
    text = {}
    for name, (L, U) in lay.text.items():
        c = seg.text_fields.get(name) if seg is not None else None
        if c is None:
            c = TextFieldColumn(
                terms=[], tokens=np.full((n, L), -1, np.int32),
                uterms=np.full((n, U), -1, np.int32),
                utf=np.zeros((n, U), np.float32),
                doc_len=np.zeros(n, np.int32),
                df=np.zeros(1, np.int32), total_tokens=0)
            text[name] = DeviceTextField(
                tokens=c.tokens, uterms=c.uterms, utf=c.utf,
                doc_len=c.doc_len, column=c)
        else:
            text[name] = DeviceTextField(
                tokens=_fit(c.tokens, (n, L), -1),
                uterms=_fit(c.uterms, (n, U), -1),
                utf=_fit(c.utf, (n, U), 0.0),
                doc_len=_fit(c.doc_len, (n,), 0), column=c)
    keyword = {}
    for name, kdim in lay.keyword.items():
        c = seg.keyword_fields.get(name) if seg is not None else None
        if c is None:
            c = KeywordFieldColumn(vocab=[],
                                   ords=np.full((n, kdim), -1, np.int32))
        keyword[name] = DeviceKeywordField(
            ords=_pad2(c.ords, n, kdim, -1), column=c)
    numeric = {}
    for name in lay.numeric:
        c = seg.numeric_fields.get(name) if seg is not None else None
        if c is None:
            hi = np.zeros(n, np.float32)
            lo = np.zeros(n, np.float32)
            exists = np.zeros(n, bool)
        else:
            hi, lo = dd_split(c.values)
            hi, lo = _pad1(hi, n, 0.0), _pad1(lo, n, 0.0)
            exists = _pad1(c.exists, n, False)
        numeric[name] = DeviceNumericField(hi=hi, lo=lo, exists=exists,
                                           column=c)
    live_p = _pad1(live, n, False) if live is not None \
        else np.zeros(n, bool)
    host_seg = seg if seg is not None else Segment(
        seg_id=-1, num_docs=0, padded_docs=n, ids=[], sources=[],
        text_fields={}, keyword_fields={}, numeric_fields={},
        vector_fields={}, geo_fields={})
    return DeviceSegment(seg=host_seg, live=live_p,
                         doc_base=doc_base, text=text,
                         keyword=keyword, numeric=numeric, vector={},
                         geo={})


class MeshEngineSearcher:
    """Executes query-DSL searches over all shards of an index as one
    shard_map program on a ``("dp", "shard")`` mesh.

    Built from the engines' current searcher views (point-in-time segment
    sets + live masks — deletes respected); rebuild after refresh, like
    acquiring a new searcher.
    """

    def __init__(self, mesh: Mesh, engines: list, mapper_service,
                 k1: float = 1.2, b: float = 0.75,
                 mapper_services: list | None = None,
                 breaker_service=None, prev: "MeshEngineSearcher" = None,
                 reuse_blocks: bool = True,
                 stats_sinks: list | None = None):
        from elasticsearch_tpu.ops.similarity import BM25Params
        self.mesh = mesh
        self.mapper_service = mapper_service
        # multi-index batches: one mapper per engine shard (aligned with
        # `engines`) so each shard resolves queries against ITS index's
        # mappings; single-index callers pass just mapper_service
        self._mappers = list(mapper_services) if mapper_services \
            else [mapper_service] * len(engines)
        if len(self._mappers) != len(engines):
            raise ValueError("mapper_services must align with engines")
        self.k1, self.b = k1, b
        self._bm25 = BM25Params(k1=k1, b=b)
        s_mesh = mesh.shape["shard"]
        if len(engines) % s_mesh != 0:
            raise ValueError(f"{len(engines)} engine shards not divisible "
                             f"by mesh shard axis {s_mesh}")
        s = len(engines)
        # shards-per-device blocking: when the index has more shards than
        # the mesh's shard axis (incl. the 1-chip case), each device holds
        # a contiguous block of spd shards on the stacked leading axis and
        # merges them locally before the cross-device all_gather — the
        # same program distributes unchanged from 1 chip to a full slice.
        self.spd = s // s_mesh
        self.n_shards = s
        views = [e.acquire_searcher() for e in engines]
        self._views = views
        self.n_slots = max((len(v.segments) for v in views), default=0)
        if self.n_slots == 0:
            raise ValueError("no segments — refresh the engines first")
        self._layouts = [self._slot_layout(j) for j in range(self.n_slots)]
        self.slot_bases = np.cumsum(
            [0] + [lay.np_docs for lay in self._layouts])[:-1].tolist()
        self.shard_stride = int(sum(lay.np_docs for lay in self._layouts))
        lay_sigs = tuple(lay.sig() for lay in self._layouts)
        self._lay_sigs = lay_sigs
        if reuse_blocks:
            # engine-close hook: the moment any backing engine dies, its
            # cached device blocks return their fielddata budget (shard
            # relocation / index teardown must not strand breaker bytes)
            for e in engines:
                hook_engine_block_release(e)
        # ---- DATA layer build: per-segment device blocks ---------------
        # templates[s][j]: host-side DeviceSegment (numpy arrays, real
        # host column dicts) used for resolution; shard 0's templates also
        # give the traced structure in the program body. Blocks come from
        # the module-level device-block cache: a refresh uploads only new
        # segments' columns and changed live masks; resident blocks are
        # REUSED and the per-slot stacked operands compose device-side.
        from elasticsearch_tpu.search import jit_exec
        self._templates = [[None] * self.n_slots for _ in range(s)]
        blocks = [[None] * self.n_slots for _ in range(s)]
        col_up = mask_up = reused = 0
        # OWNER PLACEMENT: on a mesh of several devices a shard's blocks
        # go up to the device(s) of its mesh column and stay there — the
        # stacked operands below are assembled from them in place. On
        # one device (spd = n_shards) blocks and stacks are as ever.
        placed = self.placed(mesh)
        owners_of = [tuple(mesh.devices[:, si // self.spd])
                     if placed else None for si in range(s)]
        # exact f64 extrema per numeric field, merged from per-block
        # caches — gives histogram lanes a STATIC dd base (the whole
        # field range maps to one bucket window, so per-query scatter-
        # adds need no data-dependent base collective)
        self._field_extrema: dict[str, tuple[float, float]] = {}
        for si in range(s):
            e_uuid = engines[si].engine_uuid
            view = views[si]
            sink = stats_sinks[si] if stats_sinks else None
            for j in range(self.n_slots):
                seg = view.segments[j] if j < len(view.segments) else None
                live = view.live_masks[j] if seg is not None else None
                lay = self._layouts[j]
                if reuse_blocks:
                    tpl, arrs, extrema, c_up, m_up, c_re = \
                        _block_cache.fetch(
                            e_uuid, lay_sigs[j], lay, seg, live,
                            self.slot_bases[j], breaker_service,
                            f"mesh block [{e_uuid[:8]}]",
                            owners=owners_of[si])
                else:
                    tpl = _build_template(lay, seg, live,
                                          self.slot_bases[j])
                    flat_np = seg_flatten(tpl)
                    arrs = _upload_block(flat_np, owners_of[si])
                    extrema = _segment_extrema(seg) if seg is not None \
                        else {}
                    m_up = int(flat_np[0].nbytes)
                    c_up = int(sum(a.nbytes for a in flat_np[1:]))
                    c_re = 0
                self._templates[si][j] = tpl
                blocks[si][j] = arrs
                col_up += c_up
                mask_up += m_up
                reused += c_re
                if sink is not None:
                    sink["bytes_uploaded"] = sink.get(
                        "bytes_uploaded", 0) + c_up + m_up
                    sink["col_bytes_uploaded"] = sink.get(
                        "col_bytes_uploaded", 0) + c_up
                    sink["mask_bytes_uploaded"] = sink.get(
                        "mask_bytes_uploaded", 0) + m_up
                    sink["bytes_reused"] = sink.get(
                        "bytes_reused", 0) + c_re
                for name, (lo, hi) in extrema.items():
                    cur = self._field_extrema.get(name)
                    self._field_extrema[name] = (lo, hi) if cur is None \
                        else (min(cur[0], lo), max(cur[1], hi))
        kind = "full" if (reused == 0 or not reuse_blocks) else \
            ("mask_only" if col_up == 0 else "incremental")
        self.data_layer = {"col_bytes_uploaded": col_up,
                           "mask_bytes_uploaded": mask_up,
                           "bytes_uploaded": col_up + mask_up,
                           "bytes_reused": reused, "kind": kind}
        jit_exec.note_data_blocks(col_bytes=col_up, mask_bytes=mask_up,
                                  reused_bytes=reused)
        jit_exec.note_data_refresh(kind)
        if stats_sinks:
            key = {"full": "full_rebuilds",
                   "incremental": "incremental_refreshes",
                   "mask_only": "mask_only_refreshes"}[kind]
            for sink in {id(sk): sk for sk in stats_sinks
                         if sk is not None}.values():
                sink[key] = sink.get(key, 0) + 1
        # ---- next-generation stacked layer, composed from blocks -------
        # double-buffered: the PREVIOUS searcher keeps serving its own
        # stacked arrays untouched while this one composes; a slot whose
        # every contributing block (and live mask) is unchanged reuses
        # the previous generation's stacked operand outright.
        shard_sharding = NamedSharding(mesh, P("shard"))
        self._flats = []
        self._block_tokens = []
        prev_ok = (prev is not None and prev.mesh is mesh
                   and prev.n_shards == s
                   and getattr(prev, "_lay_sigs", None) is not None)
        for j in range(self.n_slots):
            # strong refs, compared by IDENTITY (an `id()` token could
            # alias a freed block's address after GC; holding the arrays
            # both prevents that and costs only references)
            token = tuple(
                buf for si in range(s) for a in blocks[si][j]
                for buf in (a if placed else (a,)))
            self._block_tokens.append(token)
            if prev_ok and j < len(prev._block_tokens) \
                    and len(prev._block_tokens[j]) == len(token) \
                    and all(a is b for a, b in zip(prev._block_tokens[j],
                                                   token)) \
                    and prev._lay_sigs[j] == lay_sigs[j]:
                self._flats.append(prev._flats[j])
                continue
            n_arr = len(blocks[0][j])
            with device_span("compose"):
                jit_exec.device_fault_point("compose")
                if placed:
                    self._flats.append(_compose_placed(
                        mesh, self.spd, [blocks[si][j] for si in range(s)]))
                    continue
                self._flats.append([
                    jax.device_put(jnp.stack([blocks[si][j][i]
                                              for si in range(s)]),
                                   shard_sharding)
                    for i in range(n_arr)])
        if reuse_blocks:
            # supersession sweep: blocks whose segment left the reader
            # (background merge, force_merge, recovered commit) return
            # their fielddata budget NOW — exact release, no stranding
            for si in range(s):
                _block_cache.prune(
                    engines[si].engine_uuid,
                    {g.block_uid for g in views[si].segments})
        # one reader facade a shard for the life of this point-in-time
        # pack: its text statistics are read once, not once a plan
        self._readers = [_TemplateReader(self._templates[si], views[si])
                         for si in range(s)]
        # keyword-sort data layer: per (field, fill) union-rank columns
        # and their vocabularies, built lazily on first keyword sort and
        # cached for this searcher's point-in-time views
        self._kw_rank_cache: dict[tuple, tuple] = {}
        self._kw_sort_vocab: dict[str, list] = {}
        self._kw_operand_cache: dict[tuple, object] = {}

    # ---- packing ----------------------------------------------------------

    @staticmethod
    def placed(mesh: Mesh) -> bool:
        """Are blocks placed on their owning devices (a mesh of several
        devices) or uploaded to the default one and stacked there?"""
        return mesh.devices.size > 1

    @staticmethod
    def composes_in_place(mesh: Mesh, n_shards: int) -> bool:
        """True where the stacked operands ARE the resident blocks (one
        shard a device of the shard axis): the pack then holds no bytes
        of its own for the breaker to book."""
        return MeshEngineSearcher.placed(mesh) \
            and n_shards == int(mesh.shape["shard"])

    def _slot_layout(self, j: int) -> _SlotLayout:
        np_docs = 0
        text: dict[str, tuple[int, int]] = {}
        keyword: dict[str, int] = {}
        kw_vocab: dict[str, int] = {}
        numeric: set[str] = set()
        for v in self._views:
            if j >= len(v.segments):
                continue
            seg = v.segments[j]
            np_docs = max(np_docs, seg.padded_docs)
            for name, c in seg.text_fields.items():
                pl, pu = text.get(name, (0, 0))
                text[name] = (max(pl, c.tokens.shape[1]),
                              max(pu, c.uterms.shape[1]))
            for name, c in seg.keyword_fields.items():
                keyword[name] = max(keyword.get(name, 0), c.ords.shape[1])
                kw_vocab[name] = max(kw_vocab.get(name, 1), len(c.vocab))
            numeric.update(seg.numeric_fields)
            if seg.vector_fields or seg.geo_fields or seg.nested_blocks \
                    or seg.shape_fields:
                raise QueryParsingError(
                    "mesh engine plane does not pack vector/geo/shape/"
                    "nested fields yet — use the RPC fan-out path")
        return _SlotLayout(np_docs=max(np_docs, 8), text=text,
                           keyword=keyword, kw_vocab=kw_vocab,
                           numeric=sorted(numeric))

    def _template(self, si: int, j: int) -> DeviceSegment:
        """Shard ``si`` slot ``j`` padded to the slot layout (see
        :func:`_build_template` — the cacheable module-level builder)."""
        view = self._views[si]
        seg = view.segments[j] if j < len(view.segments) else None
        live = view.live_masks[j] if seg is not None else None
        return _build_template(self._layouts[j], seg, live,
                               self.slot_bases[j])

    # ---- statistics (the DFS round, host-side) ----------------------------

    def _global_dfs(self, queries: list) -> dict:
        shard_results = []
        for si in range(self.n_shards):
            from elasticsearch_tpu.search.query_dsl import BoolQuery
            reader = self._readers[si]
            shard_results.append(dfs_mod.shard_dfs(
                reader, self._mappers[si], BoolQuery(must=list(queries))))
        return dfs_mod.to_execution_stats(
            dfs_mod.aggregate_dfs(shard_results))

    # ---- keyword-sort union ranks (data layer) ----------------------------

    def _kw_sort_ranks(self, field: str, fill: float):
        """→ (ranks [S, stride] f32, union_vocab): every doc's FIRST
        keyword ordinal lifted to a rank in the cross-shard union
        vocabulary (the host path's vocab-union, phase._sort_column),
        missing docs and column-less slots at `fill`. Ranks are exact in
        f32 below 2^24 terms; larger vocabularies stay host-side."""
        key = (field, fill)
        hit = self._kw_rank_cache.get(key)
        if hit is not None:
            return hit
        values: set[str] = set()
        for v in self._views:
            for seg in v.segments:
                c = seg.keyword_fields.get(field)
                if c is not None:
                    values.update(c.vocab)
        if len(values) >= _MAX_KW_SORT_VOCAB:
            raise QueryParsingError(
                f"keyword sort [{field}] vocab exceeds the f32-exact "
                f"rank budget — use the RPC fan-out path")
        union_vocab = sorted(values)
        rank_of = {t: i for i, t in enumerate(union_vocab)}
        ranks = np.full((self.n_shards, self.shard_stride),
                        np.float32(fill), np.float32)
        for si, v in enumerate(self._views):
            for j, lay in enumerate(self._layouts):
                seg = v.segments[j] if j < len(v.segments) else None
                if seg is None:
                    continue
                c = seg.keyword_fields.get(field)
                if c is None:
                    continue
                first = c.ords[:, 0]
                have = first >= 0
                remap = np.array([rank_of[t] for t in c.vocab] or [0],
                                 np.float32)
                col = np.full(lay.np_docs, np.float32(fill), np.float32)
                col[:first.shape[0]][have] = remap[first[have]]
                base = self.slot_bases[j]
                ranks[si, base:base + lay.np_docs] = col
        self._kw_sort_vocab[field] = union_vocab
        self._kw_rank_cache[key] = (ranks, union_vocab)
        return ranks, union_vocab

    def _kw_rank_operand(self, sort_specs):
        """Stacked [S, n_kw, stride] f32 device operand carrying every
        keyword spec's union-rank column (dummy [S, 1, 1] when the sort
        has no keyword keys — program shapes stay deterministic per
        key)."""
        kw_specs = [sp for sp in (sort_specs or ())
                    if sp.kind == "keyword"]
        ckey = tuple((sp.field, sp.fill) for sp in kw_specs)
        hit = self._kw_operand_cache.get(ckey)
        if hit is not None:
            return hit
        if not kw_specs:
            arr = np.zeros((self.n_shards, 1, 1), np.float32)
        else:
            arr = np.stack(
                [self._kw_sort_ranks(sp.field, sp.fill)[0]
                 for sp in kw_specs], axis=1)
        from elasticsearch_tpu.search import jit_exec
        with device_span("upload") as dsp:
            jit_exec.device_fault_point("upload")
            dev = jax.device_put(arr, NamedSharding(self.mesh, P("shard")))
            dsp.set(bytes=int(arr.nbytes), kind="kw-rank")
        self._kw_operand_cache[ckey] = dev
        return dev

    # ---- the program ------------------------------------------------------

    def _program(self, sigs, layouts, k: int, b_pad: int, consts_tree,
                 emits, pfs, refss, templates0, agg_spec=None,
                 bucket_specs=None, sort_specs=None, has_cursor=False,
                 cursors=None, kwsorts=None):
        """→ (compiled program, program key). ``cursors``/``kwsorts``
        are the dispatch-ready operands — a cache miss AOT-lowers
        against them (through ``jit_exec.observed_compile``, which
        stamps the XLA cost/memory analyses per program key) so the
        cached object is the bare executable, same discipline as
        ``_get_compiled``; the key pins every static the shapes derive
        from, so re-dispatches against new data-layer packs match."""
        from elasticsearch_tpu.search import jit_exec
        # metric lanes return a field-ordered TUPLE, so only WHICH
        # fields get partials matters (renamed metric aggs share the
        # executable); bucket lanes return dicts KEYED BY AGG NAME in
        # the output pytree — names must key the program too
        agg_fields = sorted({f for _, _, f in agg_spec}) if agg_spec \
            else []
        bucket_key = tuple(
            (b[0], b[1], b[2]) + ((b[3], b[4], b[5])
                                  if b[0] == "histogram" else ())
            for b in bucket_specs) if bucket_specs else ()
        sort_key = tuple((s.field, s.order, s.fill, s.kind)
                         for s in sort_specs) if sort_specs else None
        # programs outlive this searcher (module-level cache): the key
        # carries every static the closures bake in beyond the plan
        # signatures and slot layouts — mesh geometry + device identity,
        # shard blocking, slot bases/stride (doc numbering), per-slot
        # padded vocab sizes (terms-lane widths), BM25 params, and which
        # const refs exist (min_score / search_after lanes)
        key = (tuple(sigs), tuple(layouts), k, b_pad, tuple(agg_fields),
               bucket_key, sort_key, has_cursor,
               tuple(pf is not None for pf in pfs),
               tuple(sorted(refss[0] or {})),
               tuple(sorted(self.mesh.shape.items())),
               tuple(int(d.id) for d in self.mesh.devices.flat),
               self.n_shards, self.spd, self.n_slots,
               tuple(self.slot_bases), self.shard_stride,
               tuple(tuple(sorted(lay.kw_vocab.items()))
                     for lay in self._layouts),
               float(self.k1), float(self.b))
        with _program_lock:
            fn = _program_cache.get(key)
            if fn is not None:
                _program_cache.move_to_end(key)
        jit_exec.note_mesh_program(fn is not None)
        if fn is not None:
            return fn, key
        n_slots = self.n_slots
        slot_bases = self.slot_bases
        stride = self.shard_stride
        spd = self.spd
        sort_mode = sort_specs is not None
        want_arrays = bool(agg_fields or bucket_specs) or sort_mode
        flags = dict(_FLAGS, want_topk=not sort_mode,
                     want_arrays=want_arrays,
                     min_score=bool(refss[0] and "min_score" in refss[0]))
        # per-bucket static plans
        terms_lanes = [b for b in (bucket_specs or ())
                       if b[0] == "terms"]
        histo_lanes = [b for b in (bucket_specs or ())
                       if b[0] == "histogram"]
        kw_vocab = [lay_obj.kw_vocab for lay_obj in self._layouts]

        def step_local(flats, consts, cursors, kwsorts):
            # flats[j]: arrays [spd, Np_j, ...]; consts[j]: [spd, B_local, ...]
            # kwsorts: [spd, n_kw, stride] keyword-sort union-rank lanes
            dev_idx = jax.lax.axis_index("shard").astype(jnp.int32)
            cand = []                    # per-block payload dicts [B, k]
            counts_blocks = []           # per-block [B] hit counts
            b_local = None
            acc = {f: None for f in agg_fields}
            terms_acc = {(b[1], j): [] for b in terms_lanes
                         for j in range(n_slots)}
            histo_acc = {b[1]: None for b in histo_lanes}
            for li in range(spd):
                seg_scores, seg_docs = [], []
                arr_scores, arr_masks = [], []
                counts = None
                views = []
                for j in range(n_slots):
                    view = seg_rebuild(templates0[j],
                                       [a[li] for a in flats[j]])
                    views.append(view)

                    def one(cs, j=j, view=view):
                        return _build(view, list(cs), emits[j], pfs[j],
                                      refss[j], flags, k)

                    with jax.named_scope("plane_score"):
                        outs = jax.vmap(one)(
                            jax.tree.map(lambda a, li=li: a[li],
                                         consts[j]))
                    b_local = outs["count"].shape[0]
                    if agg_fields:
                        # per-shard metric partials from the query mask,
                        # reduced over ICI after the loop. Values are the
                        # DOUBLE-DOUBLE (hi, lo) split — summing/extrema
                        # on hi alone would drop the f64 residual the
                        # device agg path preserves (aggregations.py
                        # _d_metric / _dd_extrema)
                        amask = outs["agg_mask"]          # [B, N]
                        for f in agg_fields:
                            ncol = view.numeric.get(f)
                            if ncol is None:
                                continue
                            m = amask & ncol.exists[None, :]
                            hi = ncol.hi[None, :]
                            lo = ncol.lo[None, :]
                            p = [
                                jnp.where(m, hi, 0.0).sum(axis=1),
                                jnp.where(m, lo, 0.0).sum(axis=1),
                                m.sum(axis=1).astype(jnp.int32),
                            ]
                            mn_hi = jnp.where(m, hi, jnp.inf).min(axis=1)
                            mn_lo = jnp.where(
                                m & (hi == mn_hi[:, None]), lo,
                                jnp.inf).min(axis=1)
                            mx_hi = jnp.where(m, hi, -jnp.inf).max(axis=1)
                            mx_lo = jnp.where(
                                m & (hi == mx_hi[:, None]), lo,
                                -jnp.inf).max(axis=1)
                            p += [mn_hi, mn_lo, mx_hi, mx_lo]
                            if acc[f] is None:
                                acc[f] = p
                            else:
                                a0 = acc[f]
                                pick_mn = (p[3] < a0[3]) | \
                                    ((p[3] == a0[3]) & (p[4] < a0[4]))
                                pick_mx = (p[5] > a0[5]) | \
                                    ((p[5] == a0[5]) & (p[6] > a0[6]))
                                acc[f] = [
                                    a0[0] + p[0], a0[1] + p[1],
                                    a0[2] + p[2],
                                    jnp.where(pick_mn, p[3], a0[3]),
                                    jnp.where(pick_mn, p[4], a0[4]),
                                    jnp.where(pick_mx, p[5], a0[5]),
                                    jnp.where(pick_mx, p[6], a0[6])]
                    if bucket_specs:
                        amask = outs["agg_mask"]          # [B, N]
                        for lane in terms_lanes:
                            _, name, f = lane
                            kcol = view.keyword.get(f)
                            v_j = kw_vocab[j].get(f, 1)
                            if kcol is None:
                                terms_acc[(name, j)].append(
                                    jnp.zeros((b_local, v_j), jnp.int32))
                            else:
                                terms_acc[(name, j)].append(jax.vmap(
                                    lambda m, kcol=kcol, v_j=v_j:
                                    aggs_ops.ord_value_counts(
                                        kcol.ords, m, v_j))(amask))
                        for lane in histo_lanes:
                            _, name, f, interval, base, nb = lane
                            if nb == 0:
                                continue
                            ncol = view.numeric.get(f)
                            if ncol is None:
                                continue
                            bh, bl = dd_split(np.float64(base))
                            h = jax.vmap(
                                lambda m, ncol=ncol, bh=bh, bl=bl,
                                interval=interval, nb=nb:
                                aggs_ops.histogram_counts_dd(
                                    ncol.hi, ncol.lo, ncol.exists, m,
                                    float(bh), float(bl), interval,
                                    nb))(amask)
                            histo_acc[name] = h if histo_acc[name] is None \
                                else histo_acc[name] + h
                    if sort_mode:
                        arr_scores.append(outs["scores"])
                        arr_masks.append(outs["mask"])
                    else:
                        docs = jnp.where(outs["top_docs"] >= 0,
                                         outs["top_docs"] + slot_bases[j],
                                         -1)
                        seg_scores.append(outs["top_scores"])
                        seg_docs.append(docs)
                    counts = outs["count"] if counts is None \
                        else counts + outs["count"]
                counts_blocks.append(counts)
                shard_off = (dev_idx * spd + li) * stride
                if sort_mode:
                    scores = jnp.concatenate(arr_scores, axis=1)  # [B, str]
                    mask = jnp.concatenate(arr_masks, axis=1)
                    inval = jnp.where(mask, 0.0, 1.0).astype(jnp.float32)
                    thi_list, tlo_list = [], []
                    kw_i = 0
                    for sp in sort_specs:
                        if sp.is_score:
                            raw_hi, raw_lo = scores, \
                                jnp.zeros_like(scores)
                        elif sp.kind == "keyword":
                            # union-rank lane: exact f32 integers (vocab
                            # < 2^24), missing already at the fill rank
                            raw_hi = jnp.broadcast_to(
                                kwsorts[li][kw_i][None, :], scores.shape)
                            raw_lo = jnp.zeros_like(scores)
                            kw_i += 1
                        else:
                            cols_hi, cols_lo = [], []
                            f_hi, f_lo = _dd_fill(sp.fill)
                            for view in views:
                                ncol = view.numeric.get(sp.field)
                                n_j = view.live.shape[0]
                                if ncol is None:
                                    # host absent-column semantics: flat
                                    # +inf raw key (phase._sort_column)
                                    cols_hi.append(jnp.full(
                                        n_j, jnp.inf, jnp.float32))
                                    cols_lo.append(jnp.zeros(
                                        n_j, jnp.float32))
                                else:
                                    cols_hi.append(jnp.where(
                                        ncol.exists, ncol.hi,
                                        jnp.float32(f_hi)))
                                    cols_lo.append(jnp.where(
                                        ncol.exists, ncol.lo,
                                        jnp.float32(f_lo)))
                            raw_hi = jnp.broadcast_to(
                                jnp.concatenate(cols_hi)[None, :],
                                scores.shape)
                            raw_lo = jnp.broadcast_to(
                                jnp.concatenate(cols_lo)[None, :],
                                scores.shape)
                        if sp.order == "desc":
                            raw_hi, raw_lo = -raw_hi, -raw_lo
                        thi_list.append(raw_hi)
                        tlo_list.append(raw_lo)
                    if has_cursor:
                        # strictly-after mask in transformed key space:
                        # lexicographic (k1,k2,...) > (c1,c2,...)
                        cur = cursors[li]                  # [B, 2*nspec]
                        gt = jnp.zeros_like(mask)
                        eq = jnp.ones_like(mask)
                        for i in range(len(sort_specs)):
                            for comp, arr in ((0, thi_list[i]),
                                              (1, tlo_list[i])):
                                c = cur[:, 2 * i + comp][:, None]
                                gt = gt | (eq & (arr > c))
                                eq = eq & (arr == c)
                        mask = mask & gt
                        inval = jnp.where(mask, 0.0, 1.0).astype(
                            jnp.float32)
                    keys = [inval]
                    for hi_a, lo_a in zip(thi_list, tlo_list):
                        keys.append(jnp.where(inval > 0, jnp.inf, hi_a))
                        keys.append(jnp.where(inval > 0, jnp.inf, lo_a))
                    kk = min(k, stride)
                    idx = _stable_order(keys, kk)
                    payload = {"docs": jnp.broadcast_to(
                        jnp.arange(stride, dtype=jnp.int32),
                        mask.shape), "scores": scores, "inval": inval}
                    for i, (hi_a, lo_a) in enumerate(
                            zip(thi_list, tlo_list)):
                        payload[f"khi{i}"] = hi_a
                        payload[f"klo{i}"] = lo_a
                    top = _gather_payload(payload, idx)
                    top["docs"] = jnp.where(
                        top["inval"] > 0, -1, top["docs"] + shard_off)
                    if kk < k:
                        pads = {"docs": -1, "scores": -jnp.inf,
                                "inval": 1.0}
                        top = {name: jnp.pad(
                            arr, ((0, 0), (0, k - kk)),
                            constant_values=pads.get(name, jnp.inf))
                            for name, arr in top.items()}
                    cand.append(top)
                else:
                    scores = jnp.concatenate(seg_scores, axis=1)
                    docs = jnp.concatenate(seg_docs, axis=1)
                    kk = min(k, scores.shape[1])
                    with jax.named_scope("plane_select"):
                        top_s, idx = jax.lax.top_k(
                            jnp.where(docs >= 0, scores, -jnp.inf), kk)
                        top_d = jnp.take_along_axis(docs, idx, axis=1)
                    top_d = jnp.where(top_s > -jnp.inf,
                                      top_d + shard_off, -1)
                    if kk < k:
                        top_s = jnp.pad(top_s, ((0, 0), (0, k - kk)),
                                        constant_values=-jnp.inf)
                        top_d = jnp.pad(top_d, ((0, 0), (0, k - kk)),
                                        constant_values=-1)
                    cand.append({"docs": top_d, "scores": top_s})

            def merge(blocks: list, force: bool = False) -> dict:
                """Exact candidate merge: keeping k of the len(blocks)*k
                candidates loses only entries outranked by >=k better
                same-gather candidates; stable order keeps the earlier
                block on ties — blocks arrive shard-major, so this is the
                (sort key, shard, position) order of
                SearchPhaseController.sortDocs."""
                if len(blocks) == 1 and not force:
                    return blocks[0]
                allp = {name: jnp.concatenate(
                    [blk[name] for blk in blocks], axis=1)
                    for name in blocks[0]}
                if sort_mode:
                    keys = [allp["inval"]]
                    for i in range(len(sort_specs)):
                        keys.append(jnp.where(allp["inval"] > 0, jnp.inf,
                                              allp[f"khi{i}"]))
                        keys.append(jnp.where(allp["inval"] > 0, jnp.inf,
                                              allp[f"klo{i}"]))
                    idx = _stable_order(keys, k)
                else:
                    _, idx = jax.lax.top_k(
                        jnp.where(allp["docs"] >= 0, allp["scores"],
                                  -jnp.inf), k)
                return _gather_payload(allp, idx)

            with jax.named_scope("plane_select"):
                local = merge(cand)
            # ---- reduce over ICI: per-shard count lane + gathered merge
            counts_stack = jnp.stack(counts_blocks)        # [spd, B]
            with jax.named_scope("plane_gather"):
                shard_counts = jax.lax.all_gather(
                    counts_stack, "shard")                 # [s_mesh, spd, B]
                gathered = {name: jax.lax.all_gather(arr, "shard")
                            for name, arr in local.items()}    # [S, B, k]
            s_ax = next(iter(gathered.values())).shape[0]
            with jax.named_scope("plane_merge"):
                flat = {name: jnp.moveaxis(arr, 0, 1).reshape(
                    -1, s_ax * k) for name, arr in gathered.items()}
                g = merge([flat], force=True)
            if sort_mode:
                g["docs"] = jnp.where(g["inval"] > 0, -1, g["docs"])
                g["scores"] = jnp.where(g["inval"] > 0, -jnp.inf,
                                        g["scores"])
            else:
                g["scores"] = jnp.where(g["docs"] >= 0, g["scores"],
                                        -jnp.inf)
            out = {"docs": g["docs"], "scores": g["scores"],
                   "shard_counts": shard_counts,
                   "totals": shard_counts.sum(axis=(0, 1))}
            if sort_mode:
                out["skeys"] = tuple(
                    (g[f"khi{i}"], g[f"klo{i}"])
                    for i in range(len(sort_specs)))

            if agg_fields:
                # metric partials reduce over the shard axis in-program:
                # psum for sums/count; (hi, lo) extrema pairs reduce
                # lexicographically over an all_gather (pmin on hi alone
                # would detach the lo residual from its hi)
                def pair_reduce(hi_v, lo_v, is_min: bool):
                    ah = jax.lax.all_gather(hi_v, "shard")     # [S, B]
                    al = jax.lax.all_gather(lo_v, "shard")
                    rh, rl = ah[0], al[0]
                    for s in range(1, ah.shape[0]):
                        bh, bl = ah[s], al[s]
                        if is_min:
                            pick = (bh < rh) | ((bh == rh) & (bl < rl))
                        else:
                            pick = (bh > rh) | ((bh == rh) & (bl > rl))
                        rh = jnp.where(pick, bh, rh)
                        rl = jnp.where(pick, bl, rl)
                    return rh, rl

                agg_out = []
                for f in agg_fields:
                    a0 = acc[f]
                    if a0 is None:                   # field absent
                        a0 = [jnp.zeros(b_local, jnp.float32),
                              jnp.zeros(b_local, jnp.float32),
                              jnp.zeros(b_local, jnp.int32),
                              jnp.full(b_local, jnp.inf, jnp.float32),
                              jnp.full(b_local, jnp.inf, jnp.float32),
                              jnp.full(b_local, -jnp.inf, jnp.float32),
                              jnp.full(b_local, -jnp.inf, jnp.float32)]
                    mn_hi, mn_lo = pair_reduce(a0[3], a0[4], True)
                    mx_hi, mx_lo = pair_reduce(a0[5], a0[6], False)
                    agg_out.append((
                        jax.lax.psum(a0[0], "shard"),
                        jax.lax.psum(a0[1], "shard"),
                        jax.lax.psum(a0[2], "shard"),
                        mn_hi, mn_lo, mx_hi, mx_lo))
                out["metrics"] = tuple(agg_out)
            if bucket_specs:
                terms_out = {}
                for lane in terms_lanes:
                    _, name, f = lane
                    terms_out[name] = tuple(
                        jax.lax.all_gather(
                            jnp.stack(terms_acc[(name, j)]), "shard")
                        for j in range(n_slots))  # [s_mesh, spd, B, V_j]
                histo_out = {}
                for lane in histo_lanes:
                    _, name, f, interval, base, nb = lane
                    h = histo_acc[name]
                    if h is None:
                        h = jnp.zeros((b_local, max(nb, 1)), jnp.int32)
                    histo_out[name] = jax.lax.psum(h, "shard")
                if terms_out:
                    out["terms"] = terms_out
                if histo_out:
                    out["histo"] = histo_out
            return out

        flat_specs = [[P("shard")] * len(self._flats[j])
                      for j in range(n_slots)]
        const_specs = [jax.tree.map(lambda _: P("shard", "dp"),
                                    consts_tree[j])
                       for j in range(n_slots)]
        cursor_spec = P("shard", "dp")
        kwsort_spec = P("shard")
        # out specs mirror step_local's output pytree
        out_specs = {"docs": P("dp"), "scores": P("dp"),
                     "shard_counts": P(None, None, "dp"),
                     "totals": P("dp")}
        if sort_specs is not None:
            out_specs["skeys"] = tuple((P("dp"), P("dp"))
                                       for _ in sort_specs)
        if agg_fields:
            out_specs["metrics"] = tuple(
                (P("dp"),) * 7 for _ in agg_fields)
        if bucket_specs:
            t_named = {b[1]: tuple(P(None, None, "dp", None)
                                   for _ in range(n_slots))
                       for b in terms_lanes}
            h_named = {b[1]: P("dp", None) for b in histo_lanes}
            if t_named:
                out_specs["terms"] = t_named
            if h_named:
                out_specs["histo"] = h_named
        from elasticsearch_tpu.parallel.mesh import shard_map_compat

        def lower_fn():
            mapped = shard_map_compat(
                step_local, mesh=self.mesh,
                in_specs=(flat_specs, const_specs, cursor_spec,
                          kwsort_spec),
                out_specs=out_specs)
            # AOT-lower against the dispatch-ready operands: their
            # shapes/shardings are pure functions of the key's statics,
            # so the compiled executable re-dispatches across data-layer
            # generations exactly like the jit closure did — but the
            # observatory gets XLA's cost/memory analyses for the plane
            return jax.jit(mapped).lower(self._flats, consts_tree,
                                         cursors, kwsorts)

        fn = jit_exec.observed_compile("mesh", key, lower_fn)
        # built OUTSIDE the lock (tracing is slow); a racing duplicate
        # build is harmless — last one wins the slot, like _get_compiled
        with _program_lock:
            _program_cache[key] = fn
            while len(_program_cache) > _PROGRAM_CACHE_CAP:
                _program_cache.popitem(last=False)
        return fn, key

    def search_batch(self, bodies: list[dict], global_stats: bool = True):
        """Execute B query-DSL request bodies as one mesh program →
        list of {"total", "shard_totals", "scores", "doc_ids"
        [, "sort_values"] [, "aggregations"]} with GLOBAL doc ids
        (resolve via :meth:`resolve`).

        ``global_stats`` selects the scoring statistics: True runs the
        DFS round over every shard (dfs_query_then_fetch semantics — the
        plane's native mode); False scores each shard with its OWN
        statistics, bit-matching the default fan-out's per-shard scoring
        so plain searches can ride the plane too.

        ``terminate_after``/``timeout`` do not bail here: the program's
        count lane gives the caller exact per-shard totals to cap, and
        the task deadline (search_action) owns the time budget."""
        if not bodies:
            return []
        reqs = [parse_search_request(b) for b in bodies]
        for req in reqs:
            if req.suggest or req.rescore:
                raise QueryParsingError(
                    "mesh engine plane does not run suggest/rescore — "
                    "route to the RPC path")
        from elasticsearch_tpu.search.phase import _is_score_order
        score_order = [_is_score_order(req.sort) for req in reqs]
        if any(s != score_order[0] for s in score_order):
            raise QueryParsingError(
                "mesh engine plane requires one sort mode per batch")
        sort_specs = None
        if not score_order[0]:
            sort_specs = _mesh_sort_spec(reqs, self._layouts)
        has_ms = [req.min_score is not None for req in reqs]
        if any(m != has_ms[0] for m in has_ms):
            raise QueryParsingError(
                "mesh engine plane requires uniform min_score presence")
        has_sa = [req.search_after is not None for req in reqs]
        if any(s != has_sa[0] for s in has_sa):
            raise QueryParsingError(
                "mesh engine plane requires uniform search_after presence")
        has_cursor = has_sa[0]
        score_cursor = False
        if has_cursor and sort_specs is None:
            # score-order continuation: admissible for the bare [score]
            # cursor — it becomes the same in-program (score, doc) mask
            # run_segment applies, with no doc pivot. A cursor with a
            # doc-id component is numbering-relative (reader-local in
            # the fan-out, plane-local here) and stays on the RPC path;
            # an EXPLICIT [{"_score": "desc"}] sort makes the fan-out
            # ignore the cursor entirely — match it by bailing.
            for req in reqs:
                sa = req.search_after
                if req.sort or len(sa) != 1 or sa[0] is None or \
                        isinstance(sa[0], str):
                    raise QueryParsingError(
                        "score-order search_after cursors with a doc-id "
                        "component are numbering-relative — use the RPC "
                        "fan-out path")
            score_cursor, has_cursor = True, False
        elif has_cursor:
            for req in reqs:
                sa = req.search_after
                if len(sa) != len(sort_specs):
                    raise QueryParsingError(
                        "mesh engine plane needs a full search_after "
                        "cursor — use the RPC fan-out path")
                for v, sp in zip(sa, sort_specs):
                    if v is None or (sp.kind != "keyword"
                                     and isinstance(v, str)):
                        raise QueryParsingError(
                            "mesh engine plane needs typed search_after "
                            "cursor values — use the RPC fan-out path")
        agg_spec, bucket_specs = _mesh_agg_plan(reqs, self._layouts,
                                                self._field_extrema)
        if bucket_specs:
            for b in bucket_specs:
                if b[0] == "terms":
                    cells = sum(lay.kw_vocab.get(b[2], 1)
                                for lay in self._layouts) * \
                        len(reqs) * self.n_shards
                    if cells > _MAX_TERMS_CELLS:
                        raise QueryParsingError(
                            "terms agg vocab too large for the mesh "
                            "gather budget — use the RPC fan-out path")
        import os
        import time
        from elasticsearch_tpu.search.batching import pow2_bucket
        debug = os.environ.get("MESH_DEBUG")
        t0 = time.perf_counter()
        # k and batch-size BUCKETS: a repeated query shape with a
        # slightly different size/from or arrival count must re-dispatch
        # a cached program, not re-trace one (per-request kq slices the
        # surplus off host-side below)
        k = pow2_bucket(max(max(r.from_ + r.size, 1) for r in reqs))
        queries = [r.query for r in reqs]
        dfs_stats = self._global_dfs(queries) if global_stats else None
        t_dfs = time.perf_counter() - t0
        dp = self.mesh.shape["dp"]
        b_real = len(queries)
        b_pad = pow2_bucket(-(-b_real // dp)) * dp
        reqs_p = reqs + [reqs[-1]] * (b_pad - b_real)

        want_arrays = bool(agg_spec or bucket_specs) or \
            sort_specs is not None
        base_flags = dict(_FLAGS, want_topk=sort_specs is None,
                          want_arrays=want_arrays, min_score=has_ms[0])

        # resolve every (shard, slot, query): consts [S, B, ...]; signature
        # must agree across shards AND queries per slot (uniform field
        # layout makes shard structure uniform; mixed query structures are
        # rejected like _plan_segment_batch's None). BM25 `match` nodes of
        # unequal lengths do agree: every plan pads its term lists to the
        # batch's widest term bucket (execute.match_term_floor), as the
        # reader-batch path's do, so a mixed-length _msearch is ONE
        # dispatch
        sigs, layouts, emits, pfs, refss = [], [], [], [], []
        stacked_np = []
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.search.execute import match_term_floor
        with span("plane.resolve"):
            floors: dict = {}
            for mapper in self._mappers:
                if id(mapper) not in floors:
                    floors[id(mapper)] = match_term_floor(queries, mapper)
            ctxs = [ExecutionContext(
                reader=self._readers[si],
                mapper_service=self._mappers[si],
                bm25=self._bm25, dfs_stats=dfs_stats)
                for si in range(self.n_shards)]
            for j in range(self.n_slots):
                sig_j = emit_j = pf_j = refs_j = None
                rows = []                  # [S][B] → list of const arrays
                for si in range(self.n_shards):
                    floor = floors[id(self._mappers[si])]
                    row = []
                    for req in reqs:
                        flags_q = dict(
                            base_flags,
                            _min_score=float(req.min_score)
                            if req.min_score is not None else 0.0)
                        if score_cursor:
                            # in-program (score, doc) continuation with
                            # no doc pivot: ids > -1 is vacuous, so the
                            # mask reduces to run_segment's score cursor
                            # exactly
                            flags_q.update(
                                search_after=True,
                                _sa_score=float(req.search_after[0]),
                                _sa_doc=-1)
                        ct, emit_q, emit_pf, refs = _plan(
                            self._templates[si][j], ctxs[si], req.query,
                            req.post_filter, flags_q, floor)
                        if sig_j is None:
                            sig_j, emit_j, pf_j, refs_j = \
                                ct.signature(), emit_q, emit_pf, refs
                        elif ct.signature() != sig_j:
                            raise QueryParsingError(
                                "mesh engine plane requires one plan "
                                "signature per batch (mixed query "
                                "structures) — use the RPC fan-out path")
                        row.append(ct.values)
                    # the rows that pad the batch to its bucket repeat
                    # the last request's constants
                    rows.append(row + [row[-1]] * (b_pad - b_real))
                n_c = len(rows[0][0])
                stacked_np.append(tuple(
                    np.stack([np.stack([rows[si][bi][i]
                                        for bi in range(b_pad)])
                              for si in range(self.n_shards)])
                    for i in range(n_c)))
                sigs.append(sig_j)
                layouts.append(layout_key(self._templates[0][j]))
                emits.append(emit_j)
                pfs.append(pf_j)
                refss.append(refs_j)

            # search_after cursor operand: transformed (hi, lo) per spec
            # — the same key space the program sorts in
            n_spec = len(sort_specs) if sort_specs else 0
            cur_np = np.zeros((self.n_shards, b_pad, max(2 * n_spec, 1)),
                              np.float32)
            if has_cursor:
                for bi, req in enumerate(reqs_p):
                    for i, sp in enumerate(sort_specs):
                        if sp.kind == "keyword":
                            # string cursor → union rank; a term absent
                            # from the union sits between its
                            # lexicographic neighbors (the host path's
                            # bisect − 0.5)
                            _, union = self._kw_sort_ranks(sp.field,
                                                           sp.fill)
                            sval = str(req.search_after[i])
                            pos = bisect.bisect_left(union, sval)
                            if pos < len(union) and union[pos] == sval:
                                chi, clo = float(pos), 0.0
                            else:
                                chi, clo = float(pos) - 0.5, 0.0
                        else:
                            chi, clo = _dd_fill(float(req.search_after[i]))
                        if sp.order == "desc":
                            chi, clo = -chi, -clo
                        cur_np[:, bi, 2 * i] = float(chi)
                        cur_np[:, bi, 2 * i + 1] = float(clo)
        # the query constants and the cursors are host→device transfers:
        # one seam draw covers the batch's upload phase
        q_sharding = NamedSharding(self.mesh, P("shard", "dp"))
        with span("plane.upload"), device_span("upload") as dsp:
            jit_exec.device_fault_point("upload")
            consts_dev = [tuple(jax.device_put(a, q_sharding) for a in tup)
                          for tup in stacked_np]
            cursors = jax.device_put(cur_np, q_sharding)
            dsp.set(bytes=int(cur_np.nbytes + sum(
                a.nbytes for tup in stacked_np for a in tup)),
                kind="query-constants")
        kwsorts = self._kw_rank_operand(sort_specs)

        t1 = time.perf_counter()
        fn, prog_key = self._program(
            sigs, layouts, k, b_pad, consts_dev,
            emits, pfs, refss,
            [self._templates[0][j] for j in range(self.n_slots)],
            agg_spec=agg_spec, bucket_specs=bucket_specs,
            sort_specs=sort_specs, has_cursor=has_cursor,
            cursors=cursors, kwsorts=kwsorts)
        # the launch opens at the enqueue and closes at the END of the
        # drain (the in-flight book: the chip is not starved while this
        # program runs). The seam's own span covers enqueue AND drain:
        # the np.asarray calls are where the host waits on the device,
        # so its duration — the cost observatory's sample — is the
        # plane's device round trip, as ever
        launches = []
        try:
            with launch_scope() as launches, \
                    device_span("plane-dispatch",
                                cost=("mesh", prog_key, len(reqs),
                                      b_pad)) as dsp:
                with span("plane.enqueue"):
                    jit_exec.device_fault_point("plane-dispatch")
                    # the enqueue proper under the name every lane's
                    # has (``jit.enqueue``: jit_exec's host time)
                    with _enqueue_lock, span("jit.enqueue"):
                        outs = fn(self._flats, consts_dev, cursors,
                                  kwsorts)
                t2 = time.perf_counter()
                with span("plane.drain"), span("jit.drain"):
                    g_s = np.asarray(outs["scores"])
                    g_d = np.asarray(outs["docs"])
                    totals = np.asarray(outs["totals"])
                    shard_counts = np.asarray(
                        outs["shard_counts"]).reshape(self.n_shards, b_pad)
                    skeys = [(np.asarray(h), np.asarray(l))
                             for h, l in outs["skeys"]] \
                        if sort_specs else None
                dsp.set(batch=b_pad, shards=self.n_shards)
        finally:
            close_launches(launches)
        # S × B × k candidates of (score f32, doc i32[, sort keys]) cross
        # the shard axis a dispatch, and S × B counts
        jit_exec.note_plane_dispatch(
            self.n_shards * b_pad * (k * 4 * (2 + 2 * n_spec
                                              + bool(sort_specs)) + 4))
        if debug:
            print(f"[mesh-debug] dfs {t_dfs*1e3:.0f}ms "
                  f"plan+stack {(t1-t0-t_dfs)*1e3:.0f}ms "
                  f"dispatch {(t2-t1)*1e3:.0f}ms "
                  f"fetch {(time.perf_counter()-t2)*1e3:.0f}ms",
                  flush=True)
        agg_np = None
        if agg_spec:
            fields = sorted({f for _, _, f in agg_spec})
            agg_np = {f: [np.asarray(a) for a in outs["metrics"][i]]
                      for i, f in enumerate(fields)}
        terms_np = {name: [np.asarray(a).reshape(
            (self.n_shards, b_pad) + a.shape[3:])
            for a in arrs]
            for name, arrs in outs.get("terms", {}).items()} \
            if bucket_specs else {}
        histo_np = {name: np.asarray(a)
                    for name, a in outs.get("histo", {}).items()} \
            if bucket_specs else {}
        out = []
        for bi, req in enumerate(reqs):
            kq = max(req.from_ + req.size, 1)
            valid = g_d[bi] >= 0
            res = {"total": int(totals[bi]),
                   "shard_totals": shard_counts[:, bi].astype(np.int64),
                   "scores": g_s[bi][valid][:kq],
                   "doc_ids": g_d[bi][valid][:kq]}
            if sort_specs:
                res["sort_values"] = self._render_sort_values(
                    sort_specs, skeys, bi, int(valid.sum()), kq)
            aggs: dict = {}
            if agg_spec:
                aggs.update(self._render_aggs(agg_spec, agg_np, bi))
            if bucket_specs:
                aggs.update(self._render_buckets(
                    req, bucket_specs, terms_np, histo_np, bi))
            if aggs:
                res["aggregations"] = aggs
            out.append(res)
        return out

    def _render_sort_values(self, sort_specs, skeys, bi: int, n_valid: int,
                            kq: int) -> list:
        """Transformed (hi, lo) keys → per-hit hit["sort"] values: f64
        recombine, un-negate desc (FP negation is exact), inf → None
        (phase._sort_value_out semantics); keyword ranks map back through
        the union vocabulary (missing fills land on ±inf → None, like
        the host path's _last/_first out_fill)."""
        from elasticsearch_tpu.search.phase import _sort_value_out
        rows = []
        for pos in range(min(n_valid, kq)):
            vals = []
            for i, sp in enumerate(sort_specs):
                hi_a, lo_a = skeys[i]
                raw = np.float64(hi_a[bi][pos]) + np.float64(lo_a[bi][pos])
                if sp.order == "desc":
                    raw = -raw
                if sp.kind == "keyword":
                    union = self._kw_sort_vocab.get(sp.field, [])
                    vals.append(
                        union[int(raw)]
                        if np.isfinite(raw) and float(raw).is_integer()
                        and 0 <= int(raw) < len(union) else None)
                else:
                    vals.append(_sort_value_out(raw))
            rows.append(vals)
        return rows

    def _render_buckets(self, req, bucket_specs, terms_np, histo_np,
                        bi: int) -> dict:
        """Gathered bucket lanes → final agg responses through the SAME
        coordinator reduce the RPC path uses (reduce_aggs), fed per-shard
        partial dicts in the device-collect wire shapes."""
        from elasticsearch_tpu.search.aggregations import reduce_aggs
        nodes = {n.name: n for n in req.aggs}
        out: dict = {}
        for lane in bucket_specs:
            if lane[0] == "terms":
                _, name, f = lane
                arrs = terms_np[name]      # per slot: [S, B, V_j]
                parts = []
                for si in range(self.n_shards):
                    merged: dict[str, int] = {}
                    for j in range(self.n_slots):
                        counts = arrs[j][si, bi]
                        segs = self._views[si].segments
                        col = segs[j].keyword_fields.get(f) \
                            if j < len(segs) else None
                        if col is None:
                            continue
                        vocab = col.vocab
                        for oid in np.nonzero(counts)[0]:
                            if int(oid) >= len(vocab):
                                continue
                            key_t = vocab[int(oid)]
                            merged[key_t] = merged.get(key_t, 0) + \
                                int(counts[oid])
                    parts.append({name: {
                        "buckets": [[k_, {"doc_count": n_}]
                                    for k_, n_ in merged.items()],
                        "doc_count_error_upper_bound": 0}})
                out.update(reduce_aggs([nodes[name]], parts))
            else:
                _, name, f, interval, base, nb = lane
                counts = histo_np[name][bi] if nb else np.zeros(0)
                pairs = [[float(base + i * interval),
                          {"doc_count": int(c)}]
                         for i, c in enumerate(counts[:nb]) if c > 0]
                node = nodes[name]
                partial = {"buckets": pairs, "interval": interval,
                           "min_doc_count": int(node.params.get(
                               "min_doc_count", 0))}
                out.update(reduce_aggs([node], [{name: partial}]))
        return out

    @staticmethod
    def _render_aggs(agg_spec, agg_np, bi: int) -> dict:
        """Partials → the reference's metric agg response shapes (hi+lo
        recombined in f64, like aggregations.py's device reductions)."""
        out: dict = {}
        for name, kind, f in agg_spec:
            s_hi, s_lo, c_, mn_hi, mn_lo, mx_hi, mx_lo = \
                (arr[bi] for arr in agg_np[f])
            c_ = int(c_)
            s_ = float(np.float64(s_hi) + np.float64(s_lo))
            mn = float(np.float64(mn_hi) + np.float64(mn_lo)) if c_ \
                else None
            mx = float(np.float64(mx_hi) + np.float64(mx_lo)) if c_ \
                else None
            avg = (s_ / c_) if c_ else None
            out[name] = {
                "min": {"value": mn}, "max": {"value": mx},
                "sum": {"value": s_}, "value_count": {"value": c_},
                "avg": {"value": avg},
                "stats": {"count": c_, "min": mn, "max": mx,
                          "sum": s_, "avg": avg},
            }[kind]
        return out

    # ---- doc id resolution ------------------------------------------------

    def resolve(self, global_doc: int) -> tuple[int, int, int]:
        """global doc id → (shard, slot, local row)."""
        si, local = divmod(int(global_doc), self.shard_stride)
        for j in reversed(range(self.n_slots)):
            if local >= self.slot_bases[j]:
                return si, j, local - self.slot_bases[j]
        raise IndexError(global_doc)

    def doc_id(self, global_doc: int) -> str:
        si, j, row = self.resolve(global_doc)
        return self._views[si].segments[j].ids[row]


def rpc_oracle(mapper_service, engines: list, body: dict,
               k: int) -> tuple[int, list]:
    """The host-path reference the mesh program must match bit-exactly:
    per-shard ShardSearcher with globally aggregated DFS statistics, then
    a coordinator-ordered merge ((-score, shard) like TopDocs.merge).
    → (total_hits, [(score, shard, doc_id), ...][:k]). Used by
    tests/test_mesh_engine.py and __graft_entry__.dryrun_multichip."""
    from elasticsearch_tpu.index.device_reader import DeviceReader
    from elasticsearch_tpu.search.phase import ShardSearcher
    from elasticsearch_tpu.search.query_dsl import parse_query
    readers = [DeviceReader(e.acquire_searcher()) for e in engines]
    query = parse_query(body.get("query"))
    stats = dfs_mod.to_execution_stats(dfs_mod.aggregate_dfs(
        [dfs_mod.shard_dfs(r, mapper_service, query) for r in readers]))
    req = parse_search_request(body)
    rows: list[tuple[float, int, str]] = []
    total = 0
    for si, r in enumerate(readers):
        res = ShardSearcher(si, r, mapper_service,
                            dfs_stats=stats).query_phase(req)
        total += res.total
        for pos in range(len(res.doc_ids)):
            seg, local = r.resolve(int(res.doc_ids[pos]))
            rows.append((float(res.scores[pos]), si, seg.seg.ids[local]))
    rows.sort(key=lambda x: (-x[0], x[1]))
    return total, rows[:k]


class _TemplateReader:
    """Reader facade over one shard's padded templates — df/text stats for
    resolution and the DFS round."""

    def __init__(self, templates, view):
        self.segments = templates          # DeviceSegment-shaped
        self._view = view
        self._stats: dict = {}             # field → TextFieldStats

    @property
    def num_docs(self) -> int:
        return self._view.num_docs

    def text_stats(self, field: str):
        from elasticsearch_tpu.index.device_reader import TextFieldStats
        st = self._stats.get(field)
        if st is not None:
            return st
        doc_count = docs_with = total = 0
        for seg in self._view.segments:
            c = seg.text_fields.get(field)
            if c is not None:
                doc_count += seg.num_docs
                docs_with += int((c.doc_len[:seg.num_docs] > 0).sum())
                total += c.total_tokens
        # the view is a point in time: one pass over the lengths a field
        # (a plan asks for these per term, 512 plans a request of 64)
        st = self._stats[field] = TextFieldStats(doc_count, docs_with,
                                                 total)
        return st

    def df(self, field: str, term: str) -> int:
        out = 0
        for seg in self._view.segments:
            c = seg.text_fields.get(field)
            if c is not None:
                tid = c.tid(term)
                if tid >= 0:
                    out += int(c.df[tid])
        return out
