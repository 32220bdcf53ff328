"""DeviceReader — an engine reader view packed into device (HBM) arrays.

The analog of acquiring an NRT searcher (IndexShard.acquireSearcher,
core/index/shard/IndexShard.java:707): an immutable point-in-time set of
segments, resident on the accelerator. Columns are uploaded once per refresh
generation and cached; queries then run entirely on-device until the final
top-k docs come back for fetch.

Also aggregates per-field corpus statistics across segments host-side
(doc counts, Σ field length, per-term df on demand) — what Lucene exposes as
CollectionStatistics/TermStatistics for query-time IDF.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any

import jax
import jax.numpy as jnp
import threading

import numpy as np

from elasticsearch_tpu.index.engine import SearcherView
from elasticsearch_tpu.index.segment import Segment
from elasticsearch_tpu.observability.tracing import span


@dataclass
class DeviceTextField:
    tokens: Any      # [Np, L] i32 (position-indexed)
    uterms: Any      # [Np, U] i32
    utf: Any         # [Np, U] f32
    doc_len: Any     # [Np] i32
    column: Any      # host TextFieldColumn (term dict, df)
    # ops/lexical.SlotPlan of ``uterms`` (static: how far each row chunk
    # is scored); None scores the whole width
    slot_plan: Any = None


@dataclass
class DeviceKeywordField:
    ords: Any        # [Np, K] i32
    column: Any      # host KeywordFieldColumn (vocab)


@dataclass
class DeviceNumericField:
    """Numeric doc values as a double-double split: ``hi = f32(v)``,
    ``lo = f32(v - hi)``. TPUs have no fast f64, but lexicographic compare on
    (hi, lo) reproduces exact f64 ordering — epoch-millis dates and large
    longs filter exactly. ``hi`` alone feeds scoring/aggregations."""
    hi: Any          # [Np] f32
    lo: Any          # [Np] f32
    exists: Any      # [Np] bool
    column: Any


def dd_split(v: np.ndarray | float) -> tuple[np.ndarray, np.ndarray]:
    hi = np.float32(v)
    with np.errstate(invalid="ignore"):
        lo = np.float32(np.float64(v) - np.float64(hi))
    # ±inf bounds: inf - inf = nan would poison comparisons; lo 0 keeps the
    # (hi, lo) pair correctly ordered.
    lo = np.where(np.isfinite(np.float64(v)), lo, np.float32(0.0)) \
        if isinstance(v, np.ndarray) else \
        (lo if np.isfinite(v) else np.float32(0.0))
    return hi, lo


@dataclass
class DeviceVectorField:
    vecs: Any        # [Np, D] f32, L2-normalized rows (cosine = dot)
    exists: Any
    column: Any


@dataclass
class DeviceMultiVectorField:
    """rank_vectors column: [Np, T, D] token matrices (each real token
    row L2-normalized so per-token dot = cosine), late-interaction
    scored by the fused MaxSim kernel. ``vecs`` is LAZY like the dense
    vector columns; the knn lane reads its device copy through the
    per-segment block cache (mesh_engine.fetch_vector_block), not this
    field."""
    vecs: Any        # [Np, T, D] f32
    lens: Any        # [Np] i32
    exists: Any
    column: Any


@dataclass
class DeviceGeoField:
    lat: Any
    lon: Any
    exists: Any
    column: Any


@dataclass
class DeviceShapeField:
    lats: Any        # [Np, V] f32 concatenated rings
    lons: Any        # [Np, V] f32
    nv: Any          # [Np] i32 edge slots
    exists: Any
    rid: Any         # [Np, V] i32 ring id (-1 pad)
    area: Any        # [Np, V] bool — ring encloses area
    column: Any


@dataclass
class DeviceNestedBlock:
    """A nested path's child segment + child→parent join, device-resident.
    Child ``live`` already folds the PARENT's live mask in (children of
    deleted parents can never match)."""
    child: "DeviceSegment"
    parent: Any                     # [child Np] i32, -1 pad


@dataclass
class DeviceSegment:
    seg: Segment
    live: Any                       # [Np] bool (padding & deletes False)
    doc_base: int                   # global doc id of row 0 within the reader
    text: dict[str, DeviceTextField]
    keyword: dict[str, DeviceKeywordField]
    numeric: dict[str, DeviceNumericField]
    vector: dict[str, DeviceVectorField]
    geo: dict[str, DeviceGeoField]
    nested: dict[str, "DeviceNestedBlock"] = dc_field(default_factory=dict)
    shape: dict[str, DeviceShapeField] = dc_field(default_factory=dict)
    mvector: dict[str, DeviceMultiVectorField] = dc_field(
        default_factory=dict)
    # device_put for LAZY columns (tokens / vecs): those stay host-side
    # numpy until a plan declares it needs them (jit_exec.seg_flatten
    # materializes + caches on first use). Position matrices and dense
    # vectors dominate column bytes (~450 MB and ~3 GB at 1M docs) and a
    # BM25 query reads neither — eager transfer would serialize the first
    # search behind gigabytes of host→HBM traffic. None (mesh-engine
    # templates) means "arrays are host-side by design, don't touch".
    lazy_put: Any = None
    # False → columns live in a pinned HOST pool, not HBM: the segment is
    # beyond the reader's HBM budget and is streamed host→device per query
    # batch, double-buffered (jit_exec.run_segments_streamed) — the
    # over-capacity analog of the reference's FS-cache paging
    # (core/index/store/FsDirectoryService.java mmap).
    resident: bool = True
    # the query-independent part of a batch's pack, built on first use
    # and read by every later batch (jit_exec.layout_key and
    # seg_flatten): a column's shape and dtype never change once packed
    # (lazy materialization keeps both), and a refresh packs new
    # DeviceSegments, so the memo dies with its reader generation.
    # init=False: a dataclasses.replace copy (seg_rebuild's traced view,
    # a mesh template with a new live mask) starts with an empty memo.
    pack_memo: dict = dc_field(default_factory=dict, init=False,
                               repr=False, compare=False)

    @property
    def padded_docs(self) -> int:
        return self.seg.padded_docs


@dataclass
class TextFieldStats:
    doc_count: int          # docs in reader (incl. not-yet-merged deletes)
    docs_with_field: int
    total_tokens: int

    @property
    def avgdl(self) -> float:
        return self.total_tokens / max(self.docs_with_field, 1)


def _lazy_vector_bytes(seg: Segment) -> int:
    """Bytes of the columns the reader keeps host-side (``vecs`` of dense
    and rank_vectors fields): the knn lane uploads them through the
    per-segment block cache, which charges them itself (ledger component
    ``vector``) — charged here too, a vector index would be booked twice
    and trip the fielddata breaker at half its limit."""
    total = sum(c.vecs.nbytes for c in seg.vector_fields.values())
    total += sum(c.vecs.nbytes for c in seg.mvector_fields.values())
    for blk in seg.nested_blocks.values():
        total += _lazy_vector_bytes(blk.segment)
    return total


def resident_prefix_bytes(view: SearcherView,
                          hbm_budget_bytes: int | None) -> int:
    """Bytes the READER uploads for the segment prefix that stays
    HBM-resident under a budget (mirrors DeviceReader's cutoff: the first
    segment whose cumulative size — every column, the vectors too —
    exceeds the budget, and everything after it, streams)."""
    total = 0
    used = 0
    for seg in view.segments:
        b = seg.memory_bytes()
        if hbm_budget_bytes is not None:
            used += b
            if used > hbm_budget_bytes:
                break
        total += b - _lazy_vector_bytes(seg)
    return total


class DeviceReader:
    def __init__(self, view: SearcherView, device=None,
                 hbm_budget_bytes: int | None = None):
        """``hbm_budget_bytes`` caps the column bytes uploaded to HBM: a
        PREFIX of segments (in order) is packed device-resident until the
        budget is spent; every later segment stays in a host pool and is
        streamed per query batch. Prefix-order (not best-fit) keeps the
        cross-segment merge's tie-break identical to the fully-resident
        reader: resident candidates always precede streamed ones in
        segment order."""
        self.generation = view.generation
        self.segments: list[DeviceSegment] = []
        self._text_stats: dict[str, TextFieldStats] = {}
        doc_base = 0
        # uploads ride the device-fault seam (lazy import: jit_exec
        # imports this module at load time). Site class reader-upload:
        # this is the RPC fan-out's serving floor — injectable only by
        # explicit p_by_site opt-in, never by the default chaos draw
        from elasticsearch_tpu.search.jit_exec import seam_device_put
        put = lambda x: seam_device_put(            # noqa: E731
            x, device, site="reader-upload")
        self.device = device
        used = 0
        streaming = False
        for seg, live in zip(view.segments, view.live_masks):
            if hbm_budget_bytes is not None and not streaming:
                used += seg.memory_bytes()
                streaming = used > hbm_budget_bytes
            self.segments.append(self._pack_segment(
                seg, live, doc_base, put, resident=not streaming))
            doc_base += seg.padded_docs
        self.max_doc = doc_base
        self._collect_stats(view)

    # ---- packing ----------------------------------------------------------

    def _pack_segment(self, seg: Segment, live: np.ndarray, doc_base: int,
                      put, resident: bool = True) -> DeviceSegment:
        if not resident:
            # host pool: contiguous numpy (one memcpy per DMA later), no
            # device transfer now, no lazy materialization caching
            put = np.ascontiguousarray
        text = {}
        for name, c in seg.text_fields.items():
            text[name] = DeviceTextField(
                tokens=np.ascontiguousarray(c.tokens),    # lazy (see above)
                uterms=put(c.uterms),
                utf=put(c.utf), doc_len=put(c.doc_len), column=c,
                slot_plan=c.slot_plan)
        keyword = {name: DeviceKeywordField(ords=put(c.ords), column=c)
                   for name, c in seg.keyword_fields.items()}
        numeric = {}
        for name, c in seg.numeric_fields.items():
            with span("jit.numeric_upload"):
                hi, lo = dd_split(c.values)
                numeric[name] = DeviceNumericField(
                    hi=put(hi), lo=put(lo), exists=put(c.exists), column=c)
        # the normalized host columns are the knn lane's, computed once
        # per immutable Segment and shared (jit_exec._host_knn_column):
        # dense rows unit length, rank_vectors per TOKEN (padding rows
        # stay zero, so MaxSim's token dot is the token cosine)
        from elasticsearch_tpu.search.jit_exec import _host_knn_column
        vector = {}
        for name, c in seg.vector_fields.items():
            vector[name] = DeviceVectorField(
                vecs=_host_knn_column(seg, name, "f32")[0]["vecs"],  # lazy
                exists=put(c.exists), column=c)
        mvector = {}
        for name, c in seg.mvector_fields.items():
            mvector[name] = DeviceMultiVectorField(
                vecs=_host_knn_column(seg, name, "f32")[0]["vecs"],  # lazy
                lens=put(c.lens), exists=put(c.exists), column=c)
        geo = {}
        for name, c in seg.geo_fields.items():
            with span("jit.geo_upload"):
                geo[name] = DeviceGeoField(
                    lat=put(c.lat.astype(np.float32)),
                    lon=put(c.lon.astype(np.float32)),
                    exists=put(c.exists), column=c)
        shape = {name: DeviceShapeField(lats=put(c.lats), lons=put(c.lons),
                                        nv=put(c.nv), exists=put(c.exists),
                                        rid=put(c.rid), area=put(c.area),
                                        column=c)
                 for name, c in seg.shape_fields.items()}
        nested = {}
        for path, blk in seg.nested_blocks.items():
            # child live folds the parent's live mask in: children of
            # deleted parents never match (Lucene deletes the hidden
            # nested docs together with the parent)
            valid = blk.parent >= 0
            child_live = np.zeros(blk.segment.padded_docs, bool)
            child_live[valid] = live[blk.parent[valid]]
            nested[path] = DeviceNestedBlock(
                child=self._pack_segment(blk.segment, child_live, 0, put,
                                         resident=resident),
                parent=put(blk.parent))
        return DeviceSegment(seg=seg, live=put(live), doc_base=doc_base,
                             text=text, keyword=keyword, numeric=numeric,
                             vector=vector, geo=geo, nested=nested,
                             shape=shape, mvector=mvector,
                             lazy_put=put if resident else None,
                             resident=resident)

    def _collect_stats(self, view: SearcherView) -> None:
        for seg in view.segments:
            self._collect_seg_stats(seg)

    def _collect_seg_stats(self, seg: Segment) -> None:
        for name, c in seg.text_fields.items():
            st = self._text_stats.setdefault(name, TextFieldStats(0, 0, 0))
            st.doc_count += seg.num_docs
            st.docs_with_field += int((c.doc_len[:seg.num_docs] > 0).sum())
            st.total_tokens += c.total_tokens
        for blk in seg.nested_blocks.values():
            # nested child fields get their own stats over CHILD rows (the
            # reference's nested docs likewise contribute their own
            # field statistics)
            self._collect_seg_stats(blk.segment)

    # ---- stats (CollectionStatistics / TermStatistics analog) -------------

    @property
    def num_docs(self) -> int:
        return sum(s.seg.num_docs for s in self.segments)

    def text_stats(self, field: str) -> TextFieldStats:
        return self._text_stats.get(field, TextFieldStats(self.num_docs, 0, 0))

    def df(self, field: str, term: str) -> int:
        """Doc frequency aggregated across this reader's segments
        (including nested child blocks — their fields are path-prefixed,
        so names never collide with parent fields)."""
        def seg_df(seg: Segment) -> int:
            out = 0
            col = seg.text_fields.get(field)
            if col is not None:
                tid = col.tid(term)
                if tid >= 0:
                    out += int(col.df[tid])
            for blk in seg.nested_blocks.values():
                out += seg_df(blk.segment)
            return out
        return sum(seg_df(s.seg) for s in self.segments)

    # ---- doc id resolution -------------------------------------------------

    def resolve(self, global_doc: int) -> tuple[DeviceSegment, int]:
        """global doc id → (device segment, local row)."""
        for s in self.segments:
            if s.doc_base <= global_doc < s.doc_base + s.padded_docs:
                return s, global_doc - s.doc_base
        raise IndexError(f"doc {global_doc} out of range")

    def doc_id(self, global_doc: int) -> str:
        s, local = self.resolve(global_doc)
        return s.seg.ids[local]

    def source(self, global_doc: int) -> dict:
        s, local = self.resolve(global_doc)
        return s.seg.sources[local]


def device_reader_for(engine, view: SearcherView | None = None,
                      device=None) -> DeviceReader:
    """Reader cache per refresh generation — columns upload to HBM once per
    refresh, like Lucene's per-commit reader reuse. The cache lives ON the
    engine object so its device arrays are released with the engine (no
    global registry to leak HBM across index delete/create churn)."""
    if view is None:
        view = engine.acquire_searcher()
    # serialize cache swap + breaker accounting (concurrent searches after
    # a refresh must not double-pack or double-account); a dedicated lock,
    # not engine._lock, so packing never blocks writes
    lock = getattr(engine, "_device_reader_lock", None)
    if lock is None:
        lock = engine.__dict__.setdefault("_device_reader_lock",
                                          threading.Lock())
    with lock:
        cached = getattr(engine, "_device_reader_cache", None)
        if cached is not None and cached.generation == view.generation:
            return cached
        # account device-resident column memory against the fielddata
        # breaker (HBM is the scarce resource the reference's fielddata
        # breaker models). Reserve only the DELTA vs the generation being
        # replaced: reserving the full new size while the old is still
        # held would spuriously trip once an index passes half the limit.
        bs = getattr(engine, "breaker_service", None)
        budget = None
        st = getattr(engine, "settings", None)
        if st is not None:
            raw = st.get("index.hbm_budget_bytes", None)
            if raw is not None:
                budget = int(raw)
        # under an HBM budget only the resident prefix occupies HBM —
        # streamed segments live in the host pool plus ~2 transient
        # DMA buffers, so accounting the full corpus would trip the
        # breaker on exactly the over-capacity case streaming exists for
        new_bytes = resident_prefix_bytes(view, budget)
        old_bytes = getattr(cached, "_accounted_bytes", 0) if cached else 0
        if bs is not None:
            # delta accounting rides the device-memory ledger so the
            # reader's resident columns appear in _nodes/stats
            # .device_memory / _cat/hbm next to the block-cache charges
            from elasticsearch_tpu.observability.ledger import \
                account_absolute
            account_absolute(bs, engine.engine_uuid, "reader-columns",
                             old_bytes, new_bytes,
                             f"segments gen {view.generation}")
        if cached is not None:
            # the retiring generation's filter-cache counters fold into a
            # cumulative per-engine tally — ES cache stats survive reader
            # swaps (IndicesQueryCache counts per shard, not per reader)
            old_stats = getattr(cached, "_filter_cache_stats", None)
            if old_stats:
                carry = engine.__dict__.setdefault(
                    "_filter_cache_carry",
                    {"hit_count": 0, "miss_count": 0, "evictions": 0})
                for k in carry:
                    carry[k] += old_stats.get(k, 0)
        cached = DeviceReader(view, device=device, hbm_budget_bytes=budget)
        cached._accounted_bytes = new_bytes if bs is not None else 0
        # impact-lane plumbing: the pack builder keys its device blocks
        # by engine uuid (the PR 5 block-cache discipline) and charges
        # them against the fielddata breaker; the close listener returns
        # every cached block when this engine incarnation dies
        cached.engine_uuid = engine.engine_uuid
        cached.breaker_service = bs
        from elasticsearch_tpu.parallel.mesh_engine import (
            hook_engine_block_release)
        hook_engine_block_release(engine)
        engine._device_reader_cache = cached
        return cached


def host_reader_for(engine) -> DeviceReader:
    """The engine's current view as a reader that uploads NOTHING: every
    segment in the host pool (an HBM budget of zero), so it books no
    breaker bytes. For callers that read the row numbering, ids and
    sources only — the fetch side of the collective plane on a mesh of
    several devices, whose columns already sit on their owning devices
    (mesh_engine's placed blocks). Cached per refresh generation on the
    engine, beside the resident reader's cache and apart from it."""
    view = engine.acquire_searcher()
    cached = getattr(engine, "_host_reader_cache", None)
    if cached is None or cached.generation != view.generation:
        cached = DeviceReader(view, hbm_budget_bytes=0)
        engine._host_reader_cache = cached     # benign race: equal readers
    return cached


def release_device_reader(engine) -> None:
    """Drop the engine's cached reader and return its breaker reservation
    (called from Engine.close so budget doesn't leak across index
    delete/create churn). Takes the same lock as device_reader_for so a
    concurrent packer can't install a new reader+reservation between our
    read and clear (which would leak or double-release breaker bytes)."""
    lock = engine.__dict__.setdefault("_device_reader_lock",
                                      threading.Lock())
    with lock:
        cached = getattr(engine, "_device_reader_cache", None)
        bs = getattr(engine, "breaker_service", None)
        if cached is not None and bs is not None:
            from elasticsearch_tpu.observability.ledger import \
                account_absolute
            account_absolute(bs, engine.engine_uuid, "reader-columns",
                             getattr(cached, "_accounted_bytes", 0), 0,
                             "reader close")
        if cached is not None:
            engine._device_reader_cache = None
