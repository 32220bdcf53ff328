"""Query phase and fetch phase (per shard).

Reference split: SearchService.executeQueryPhase/executeFetchPhase
(core/search/SearchService.java:293,385-504) with QueryPhase building the
collector stack and FetchPhase materializing `_source`
(core/search/query/QueryPhase.java:99-314, core/search/fetch/FetchPhase.java:98).

Here the query phase walks segments of the shard's DeviceReader: the
executor lowers the query AST to device ops, the live bitmap and optional
post_filter mask in, then per-segment device top-k results merge (still on
device) into the shard's top-k — only k (score, doc) pairs ever leave the
device. Sort-by-field runs on host columns (numpy argsort) for exact f64
semantics. The fetch phase resolves winning global doc ids to _id/_source
and runs sub-phases (source filtering, highlight, script fields analog).
"""

from __future__ import annotations

import fnmatch
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.common.errors import (QueryParsingError,
                                             TaskCancelledError)
from elasticsearch_tpu.index.device_reader import DeviceReader
from elasticsearch_tpu.observability import tracing as obs_trace
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.search import query_dsl as q
from elasticsearch_tpu.search.aggregations import (
    AggNode, ShardAggContext, collect, parse_aggs)
from elasticsearch_tpu.search.execute import ExecutionContext, SegmentExecutor
from elasticsearch_tpu.search.highlight import highlight_hit
from elasticsearch_tpu.search.query_dsl import parse_query


@dataclass
class RescoreSpec:
    """One rescore pass (ref: core/search/rescore/QueryRescorer.java +
    RescoreParseElement): re-rank the top window_size hits of each shard
    by combining the primary score with a rescore-query score."""
    query: q.Query
    window_size: int = 10
    query_weight: float = 1.0
    rescore_query_weight: float = 1.0
    score_mode: str = "total"          # total | multiply | avg | max | min


@dataclass
class ParsedSearchRequest:
    query: q.Query
    from_: int = 0
    size: int = 10
    sort: list = field(default_factory=list)       # [{"field": {"order": ...}}...]
    aggs: list[AggNode] = field(default_factory=list)
    post_filter: q.Query | None = None
    min_score: float | None = None
    source_filter: Any = True                      # True | False | includes spec
    highlight: dict | None = None
    search_after: list | None = None
    track_total_hits: bool = True
    explain: bool = False
    script_fields: dict = field(default_factory=dict)
    suggest: list = field(default_factory=list)    # [SuggestSpec]
    stored_fields: list = field(default_factory=list)
    docvalue_fields: list = field(default_factory=list)
    version: bool = False                          # render _version per hit
    terminate_after: int | None = None             # per-shard collected cap
    timeout_ms: float | None = None                # per-shard time budget
    rescore: list[RescoreSpec] = field(default_factory=list)
    # top-level "knn" search section (dense / late-interaction lane;
    # combined with `query` → in-program hybrid fusion)
    knn: q.KnnSection | None = None


def _task_budget(req: ParsedSearchRequest):
    """→ (current task, effective monotonic deadline): the tighter of
    the request's own timeout and the executing task's deadline (the
    coordinator wires `timeout` through the task so a shard's budget
    shrinks by the wall time already spent queueing and fanning out)."""
    from elasticsearch_tpu.tasks import current_task
    task = current_task()
    deadline = None if req.timeout_ms is None \
        else time.monotonic() + req.timeout_ms / 1000.0
    if task is not None and task.deadline is not None:
        deadline = task.deadline if deadline is None \
            else min(deadline, task.deadline)
    return task, deadline


def _checkpoint(task) -> None:
    """Cooperative cancellation checkpoint at a segment boundary."""
    if task is not None and task.cancelled:
        raise TaskCancelledError(
            f"task [{task.task_id}] was cancelled "
            f"[{task.cancel_reason or 'unknown'}]")


def parse_search_request(body: dict | None) -> ParsedSearchRequest:
    body = body or {}
    req = ParsedSearchRequest(query=parse_query(body.get("query")))
    req.from_ = int(body.get("from", 0))
    req.size = int(body.get("size", 10))
    raw_sort = body.get("sort", [])
    if isinstance(raw_sort, (str, dict)):
        raw_sort = [raw_sort]
    for s in raw_sort:
        if isinstance(s, str):
            req.sort.append({s: {"order": "desc" if s == "_score" else "asc"}})
        else:
            req.sort.append({k: ({"order": v} if isinstance(v, str) else v)
                             for k, v in s.items()})
    req.aggs = parse_aggs(body.get("aggs", body.get("aggregations")))
    if "post_filter" in body:
        req.post_filter = parse_query(body["post_filter"])
    if body.get("min_score") is not None:
        req.min_score = float(body["min_score"])
    req.source_filter = body.get("_source", True)
    req.highlight = body.get("highlight")
    req.search_after = body.get("search_after")
    req.explain = bool(body.get("explain", False))
    req.version = bool(body.get("version", False))
    req.script_fields = body.get("script_fields", {})
    raw_dvf = body.get("fielddata_fields", body.get("docvalue_fields", []))
    req.docvalue_fields = [raw_dvf] if isinstance(raw_dvf, str) \
        else list(raw_dvf)
    req.stored_fields = body.get("stored_fields", body.get("fields", []))
    if isinstance(req.stored_fields, str):
        req.stored_fields = [req.stored_fields]
    if req.stored_fields and "_source" not in body:
        # `fields` without an explicit _source suppresses the source
        # (FetchSourceContext.DO_NOT_FETCH_SOURCE unless "_source" listed)
        if "_source" in req.stored_fields:
            req.stored_fields = [f for f in req.stored_fields
                                 if f != "_source"]
        else:
            req.source_filter = False
    if body.get("terminate_after"):
        req.terminate_after = int(body["terminate_after"])
    tth = body.get("track_total_hits")
    if tth is not None and str(tth).lower() in ("false", "0"):
        # totals not tracked: the block-max impact lane may skip blocks
        # (a skipped block's matches are never counted); any other value
        # keeps exact totals
        req.track_total_hits = False
    if body.get("timeout") is not None:
        from elasticsearch_tpu.common.settings import parse_time_value
        req.timeout_ms = parse_time_value(body["timeout"], "timeout") * 1000.0
    from elasticsearch_tpu.search.suggest import parse_suggest
    req.suggest = parse_suggest(body.get("suggest"))
    raw_rescore = body.get("rescore")
    if raw_rescore:
        if isinstance(raw_rescore, dict):
            raw_rescore = [raw_rescore]
        for spec in raw_rescore:
            inner = spec.get("query", {})
            if "rescore_query" not in inner:
                raise QueryParsingError("rescore requires [rescore_query]")
            mode = str(inner.get("score_mode", "total")).lower()
            if mode not in ("total", "multiply", "avg", "max", "min"):
                raise QueryParsingError(
                    f"illegal rescore score_mode [{mode}]")
            req.rescore.append(RescoreSpec(
                query=parse_query(inner["rescore_query"]),
                window_size=int(spec.get("window_size", 10)),
                query_weight=float(inner.get("query_weight", 1.0)),
                rescore_query_weight=float(
                    inner.get("rescore_query_weight", 1.0)),
                score_mode=mode))
        if req.sort:
            raise QueryParsingError(
                "rescore cannot be combined with sort (QueryRescorer "
                "re-ranks by score)")
    if body.get("knn") is not None:
        req.knn = q.parse_knn_section(body["knn"])
        req.knn.hybrid = "knn" in body and "query" in body
        # v1 lane surface: the knn section composes with from/size,
        # _source/fields/highlight and its own `filter`; request
        # features that would need rank-fused score arrays over the
        # whole corpus are rejected up front with a clear 400
        bad = [label for cond, label in (
            (bool(req.sort) and not _is_score_order(req.sort), "sort"),
            (bool(req.aggs), "aggs"),
            (req.post_filter is not None, "post_filter"),
            (req.min_score is not None, "min_score"),
            (req.search_after is not None, "search_after"),
            (bool(req.rescore), "rescore"),
            (bool(req.suggest), "suggest"),
            (req.terminate_after is not None, "terminate_after"),
        ) if cond]
        if bad:
            raise QueryParsingError(
                f"[knn] cannot be combined with {bad} — use the knn "
                f"section's own [filter] for filtering")
    return req


def _is_score_order(sort: list) -> bool:
    """True iff results follow the default (_score desc) order: no sort, or
    exactly [{"_score": {"order": "desc"}}]. An ASCENDING _score sort must
    take the field-sort path or its direction would be silently dropped."""
    if not sort:
        return True
    if len(sort) != 1 or "_score" not in sort[0]:
        return False
    return sort[0]["_score"].get("order", "desc") == "desc"


@dataclass
class ShardQueryResult:
    shard_id: int
    total: int
    max_score: float | None
    # top hits as host arrays (scores may be sort keys when sorting by field)
    doc_ids: np.ndarray            # global (reader-local) doc ids
    scores: np.ndarray             # f32 scores
    sort_values: list[list] | None  # per hit, when sort-by-field
    agg_partials: dict
    reader: DeviceReader
    terminated_early: bool = False  # terminate_after tripped on this shard
    timed_out: bool = False         # timeout budget tripped on this shard


class ShardSearcher:
    """Per-shard query execution over a DeviceReader."""

    def __init__(self, shard_id: int, reader: DeviceReader, mapper_service,
                 index_name: str = "", doc_slot: int | None = None,
                 dfs_stats: dict | None = None, version_fn=None):
        self.shard_id = shard_id
        self.reader = reader
        self.mapper_service = mapper_service
        # doc_id → live version (engine.doc_version) for version:true hits
        self.version_fn = version_fn
        # 11-bit (index, shard) slot for the _doc tie-break: doc ids use
        # bits 0-41, the slot bits 42-52 — all within float64's 53-bit
        # mantissa so cross-shard search_after cursors stay exact. The
        # coordinator assigns DENSE slots (its position in the request's
        # shard-group enumeration) so multi-index scrolls are collision-
        # free by construction; the crc fallback only serves local
        # single-index paths that never mix indices in one cursor.
        if doc_slot is None:
            import zlib
            doc_slot = ((zlib.crc32(index_name.encode()) * 31 + shard_id)
                        & 0x7FF)
        self._doc_slot = doc_slot & 0x7FF
        self.ctx = ExecutionContext(reader=reader,
                                    mapper_service=mapper_service,
                                    dfs_stats=dfs_stats,
                                    index_name=index_name or None)

    # -- mask/scores over every segment --------------------------------------

    def _execute_query(self, query: q.Query):
        """→ list of (scores, mask) device pairs, live-masked, per segment."""
        query = self._rewrite_joins(query)
        out = []
        for seg in self.reader.segments:
            ex = SegmentExecutor(seg, self.ctx)
            scores, mask = ex.execute(query)
            mask = mask & seg.live
            out.append((scores, mask))
        return out

    # ---- parent/child joins ------------------------------------------------

    def _rewrite_joins(self, query: q.Query) -> q.Query:
        """Shard-local parent/child join rewrite: children colocate with
        their parent (routing = parent id), so has_child/has_parent reduce
        to (1) run the inner query over the typed docs, (2) lift the
        per-doc scores through the _parent column host-side, (3) replace
        the node with a ParentIdsQuery the device resolves like ids.
        The reference's two-pass join (ChildrenQuery/ParentQuery,
        core/index/search/child/) does the same dance over Lucene
        ordinals; here the join state is a small id→score map."""
        if isinstance(query, q.HasChildQuery):
            inner = q.BoolQuery(
                must=[self._rewrite_joins(query.query)],
                filter=[q.TermQuery(field="_type", value=query.type)])
            scores: dict[str, list] = {}
            for seg, (sc, mask) in zip(self.reader.segments,
                                       self._execute_query(inner)):
                m = np.asarray(mask)
                s = np.asarray(sc)
                col = seg.seg.keyword_fields.get("_parent")
                if col is None:
                    continue
                for local in np.nonzero(m[:seg.seg.num_docs])[0]:
                    o = int(col.ords[int(local), 0])
                    if o >= 0:
                        scores.setdefault(col.vocab[o],
                                          []).append(float(s[int(local)]))
            mode = query.score_mode
            id_scores = {}
            for pid, vals in scores.items():
                n = len(vals)
                if n < max(query.min_children, 1) or \
                        (query.max_children and n > query.max_children):
                    continue
                if mode == "sum":
                    v = sum(vals)
                elif mode == "max":
                    v = max(vals)
                elif mode == "min":
                    v = min(vals)
                elif mode == "avg":
                    v = sum(vals) / n
                else:
                    v = 1.0
                id_scores[pid] = v
            return q.ParentIdsQuery(field="_id", id_scores=id_scores,
                                    boost=query.boost)
        if isinstance(query, q.HasParentQuery):
            inner = q.BoolQuery(
                must=[self._rewrite_joins(query.query)],
                filter=[q.TermQuery(field="_type",
                                    value=query.parent_type)])
            id_scores = {}
            for seg, (sc, mask) in zip(self.reader.segments,
                                       self._execute_query(inner)):
                m = np.asarray(mask)
                s = np.asarray(sc)
                for local in np.nonzero(m[:seg.seg.num_docs])[0]:
                    pid = seg.seg.ids[int(local)]
                    v = float(s[int(local)]) \
                        if query.score_mode == "score" else 1.0
                    id_scores[pid] = max(id_scores.get(pid, 0.0), v)
            return q.ParentIdsQuery(field="_parent", id_scores=id_scores,
                                    boost=query.boost)
        # recurse into compounds
        if isinstance(query, q.BoolQuery):
            return q.BoolQuery(
                must=[self._rewrite_joins(s) for s in query.must],
                should=[self._rewrite_joins(s) for s in query.should],
                must_not=[self._rewrite_joins(s) for s in query.must_not],
                filter=[self._rewrite_joins(s) for s in query.filter],
                minimum_should_match=query.minimum_should_match,
                boost=query.boost)
        for attr in ("query", "positive", "negative"):
            sub = getattr(query, attr, None)
            if isinstance(sub, q.Query):
                new = self._rewrite_joins(sub)
                if new is not sub:
                    import dataclasses as _dc
                    query = _dc.replace(query, **{attr: new})
        return query

    def _filter_masks_np(self, query: q.Query) -> np.ndarray:
        """Filter-context mask over the reader, memoized per (reader
        generation, filter shape) — the Lucene filter/query cache analog
        (ref: core/indices/cache/query/IndicesQueryCache.java:48): the
        same filter repeated across agg requests reuses its bitset until
        a refresh swaps the reader."""
        rd = self.reader.__dict__
        lock = rd.setdefault("_filter_cache_lock", threading.Lock())
        with lock:
            cache = rd.setdefault("_filter_mask_cache", {})
            stats = rd.setdefault(
                "_filter_cache_stats", {"hit_count": 0, "miss_count": 0,
                                        "evictions": 0})
            # key on the PRE-rewrite query: the join rewrite is the
            # expensive part and is deterministic within a reader
            # generation, so a hit must skip it too
            key = repr(query)
            hit = cache.get(key)
            if hit is not None:
                stats["hit_count"] += 1
                return hit
            stats["miss_count"] += 1
        query = self._rewrite_joins(query)   # agg filter contexts too
        masks = []
        for seg in self.reader.segments:
            ex = SegmentExecutor(seg, self.ctx)
            masks.append(np.asarray(ex.match_mask(query) & seg.live))
        out = np.concatenate(masks) if masks else np.zeros(0, bool)
        with lock:
            if len(cache) >= 256:           # bounded like the reference's
                cache.pop(next(iter(cache)))  # LRU-ish eviction
                stats["evictions"] += 1
            cache[key] = out
        return out

    # -- query phase ---------------------------------------------------------

    def query_phase(self, req: ParsedSearchRequest) -> ShardQueryResult:
        """One fused device program per segment (compile-cached across
        queries and same-shaped segments); falls back to the eager
        per-op walk if the plan/trace fails for an exotic query. Only the
        plan/trace seam is guarded — errors in parsing/aggs/sort raise
        normally without double execution."""
        from elasticsearch_tpu.search import jit_exec
        if req.knn is not None:
            # dense / late-interaction lane: compiled knn (or hybrid
            # fusion) program with an eager per-segment fallback —
            # breaker-gated and reason-labeled inside
            return self._knn_query_phase(req)
        rewritten = self._rewrite_joins(req.query)
        if rewritten is not req.query or (
                req.post_filter is not None):
            import dataclasses as _dc
            req = _dc.replace(
                req, query=rewritten,
                post_filter=None if req.post_filter is None
                else self._rewrite_joins(req.post_filter))
        # plane breaker: with the device marked unhealthy, go straight to
        # the eager executor instead of re-paying a failing dispatch per
        # query — the open breaker already knows how this would end; a
        # half-open probe is admitted below and reports back
        if not jit_exec.plane_breaker.allow():
            jit_exec.note_breaker_skip()
            return self._query_phase_eager(req)
        # Single-request fast path: delegate eligible requests to the
        # batched program with B=1. The batch program fuses scoring, merge
        # and packing into ONE dispatch + ONE device→host fetch; the
        # general path below pays one fetch per segment for counts plus
        # two for the merged top-k, each a blocking device→host sync
        # (the request-at-a-time latency story).
        fast = self.query_phase_batch([req])
        if fast is not None:
            return fast[0]
        k = max(req.from_ + req.size, 1)
        if req.rescore:
            # the shard must collect at least the largest rescore window
            # (QueryRescorer re-ranks the top window of EACH shard)
            k = max(k, max(s.window_size for s in req.rescore))
        score_order = _is_score_order(req.sort)
        need_arrays = bool(req.aggs) or not score_order
        sa = req.search_after if (req.search_after is not None
                                  and not req.sort) else None
        terminated_early = timed_out = False
        task, deadline = _task_budget(req)
        try:
            outs = []
            running = 0
            for seg in self.reader.segments:
                _checkpoint(task)
                if deadline is not None and time.monotonic() > deadline:
                    timed_out = True           # partial results, remaining
                    break                      # segments skipped
                o = jit_exec.run_segment(
                    seg, self.ctx, req.query,
                    post_filter=req.post_filter, min_score=req.min_score,
                    search_after=sa, k=(k if score_order else None),
                    want_arrays=need_arrays)
                outs.append((seg, o))
                if req.terminate_after is not None or deadline is not None:
                    # early-termination modes need the running count /
                    # actual device completion → block per segment
                    # (QueryPhase.java:240-310 terminate-after + time-limit
                    # collector wrappers); without blocking, async dispatch
                    # would let device time escape the budget entirely
                    running += int(np.asarray(o["count"]))
                    if req.terminate_after is not None and \
                            running >= req.terminate_after:
                        terminated_early = True
                        break
        except (QueryParsingError, TaskCancelledError):
            # cancellation must ABORT, not fall back to the eager path —
            # re-running a cancelled query eagerly is the opposite of
            # shedding it
            raise
        except Exception as e:                # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            return self._query_phase_eager(req)
        jit_exec.plane_breaker.record_success()

        total = int(sum(int(np.asarray(o["count"])) for _, o in outs))
        if req.terminate_after is not None:
            # the reference reports the number of docs actually collected
            total = min(total, req.terminate_after)
        agg_partials = {}
        if req.aggs:
            # keep masks/scores ON DEVICE: the device agg fast path reduces
            # there and only bucket results cross to host; the numpy
            # fallback materializes lazily (early-terminated segments
            # contribute empty masks so columns stay reader-aligned)
            masks = [o["agg_mask"] for _, o in outs]
            scores = [o["scores"] for _, o in outs]
            for seg in self.reader.segments[len(outs):]:
                masks.append(jnp.zeros(seg.padded_docs, bool))
                scores.append(jnp.zeros(seg.padded_docs, jnp.float32))
            agg_partials = self._collect_aggs(req, masks, scores)

        if not outs:
            res = ShardQueryResult(self.shard_id, 0, None,
                                   np.zeros(0, np.int32),
                                   np.zeros(0, np.float32),
                                   [] if not score_order else None,
                                   agg_partials, self.reader)
        elif not score_order:
            per_seg = [(o["scores"], o["mask"]) for _, o in outs]
            res = self._sorted_query(req, per_seg, total, agg_partials,
                                     segments=[seg for seg, _ in outs])
        else:
            seg_scores = [o["top_scores"] for _, o in outs]
            seg_docs = [jnp.where(o["top_docs"] >= 0,
                                  o["top_docs"] + seg.doc_base, -1)
                        for seg, o in outs]
            res = self._finish_score_order(k, total, seg_scores, seg_docs,
                                           agg_partials)
        res.terminated_early = terminated_early
        res.timed_out = timed_out
        if req.rescore and res.sort_values is None:
            self._apply_rescore(req, res)
        return res

    def query_phase_batch(self, reqs: list[ParsedSearchRequest]
                          ) -> list[ShardQueryResult] | None:
        """Batched query phase: execute B score-ordered requests as ONE
        vmapped program per segment plus one batched cross-segment merge —
        the whole multi-query round trip is S+1 device dispatches instead
        of B×(S+1).

        The reference's _msearch fans requests out one at a time
        (core/action/search/TransportMultiSearchAction.java); on an
        accelerator the batch IS the unit of work, so this is the engine's
        primary high-throughput entry. Returns None when the batch is
        ineligible (aggs / sort-by-field / post_filter / min_score /
        search_after / suggest / partial-results modes) or the queries
        differ in structure (a ``match`` beside a ``range``) — the caller
        then falls back to per-request :meth:`query_phase`. ``match``
        queries of unequal lengths DO share one compiled plan: their term
        lists pad to the batch's widest term bucket.

        Implemented as launch + drain so a pipelined caller (the
        ContinuousBatchScheduler) can overlap batch N's device→host drain with
        batch N+1's device work — a blocking drain otherwise idles the
        chip while the host fetches and unpacks.
        """
        handle = self.query_phase_batch_launch(reqs)
        if handle is None:
            return None
        return self.query_phase_batch_drain(handle)

    def query_phase_batch_launch(self, reqs: list[ParsedSearchRequest],
                                 n_real: int | None = None):
        """Phase 1 of the batched query phase: eligibility screen, ONE
        async device dispatch, and an async device→host copy kick-off.
        Returns an opaque handle for :meth:`query_phase_batch_drain`, or
        None when the batch is ineligible (caller falls back to serial
        :meth:`query_phase`). Never blocks on device results — JAX's
        async dispatch returns immediately and ``copy_to_host_async``
        starts the transfer in the background, so consecutive launches
        pipeline on the device while earlier drains ride the link.

        ``n_real`` (batching layers only): the first ``n_real`` rows are
        real queued requests, the rest pow2-bucket padding — lane
        admission stats count only the real rows, so a padded batch
        never double-counts."""
        from elasticsearch_tpu.search import planner
        from elasticsearch_tpu.tasks import current_task
        _checkpoint(current_task())
        if not reqs:
            return ("empty", [])
        # mixed knn/non-knn batches decline before planning — no single
        # compiled arm serves both shapes (the caller retries per
        # request, where each request plans onto its own arm)
        if any(r.knn is not None for r in reqs) and \
                not all(r.knn is not None for r in reqs):
            return None
        # the planner owns admission from here: it decomposes the batch
        # into priced candidate arms (knn/hybrid fusion, composed
        # impact→rescore, quantized impact, exact batch — each arm's
        # own eligibility screen retained), excludes device arms under
        # an open/quarantined breaker, and launches the cheapest
        # admissible arm under per-plan-node spans
        plan = planner.plan_batch(self, reqs, n_real=n_real)
        if plan is None:
            return None
        # what the arm enqueues stays in flight (the in-flight book)
        # until the end of this handle's drain
        with obs_trace.launch_scope() as launches:
            handle = planner.launch_plan(plan)
        if handle is None:
            obs_trace.close_launches(launches, drained=False)
            return None
        return handle + (launches,)

    def _exact_batch_launch(self, reqs: list, n_real: int | None = None):
        """The exact batched arm (the planner's tier-3 catch-all):
        generic eligibility screen + ONE reader-batch (or streamed)
        dispatch — the pre-planner default path, unchanged."""
        from elasticsearch_tpu.search import jit_exec
        for req in reqs:
            if (req.aggs or not _is_score_order(req.sort)
                    or req.post_filter is not None
                    or req.min_score is not None
                    or req.search_after is not None or req.suggest
                    or req.terminate_after is not None
                    or req.timeout_ms is not None or req.rescore
                    or req.knn is not None):
                return None
        k = max(max(req.from_ + req.size, 1) for req in reqs)
        queries = [req.query for req in reqs]
        if not self.reader.segments:
            return ("empty", reqs)
        # doc ids and counts survive the packed f32 fetch layout exactly
        # only below 2^24
        pack = self.reader.max_doc < (1 << 24)
        streamed = [s for s in self.reader.segments
                    if not getattr(s, "resident", True)]
        if streamed:
            # the streamed path is inherently synchronous (H2D double
            # buffering drives its own loop) — drain gets finished arrays
            res_sm = self._query_phase_batch_streamed(queries, k, streamed)
            if res_sm is None:
                return None
            return ("host", reqs, k, res_sm)
        try:
            out = jit_exec.run_reader_batch(self.reader.segments,
                                            self.ctx, queries, k=k,
                                            pack=pack, n_real=n_real)
        except QueryParsingError:
            raise
        except Exception as e:            # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            return None
        if out is None:                   # mixed plan signatures
            return None
        jit_exec.plane_breaker.record_success()
        for arr in ([out] if pack else
                    [out["top_scores"], out["top_docs"], out["count"]]):
            try:
                arr.copy_to_host_async()
            except Exception:             # noqa: BLE001 — optional fast path
                pass                      # drain's np.asarray still works
        return ("device", reqs, k, pack, out)

    def _impact_batch_launch(self, reqs: list, n_real: int | None = None):
        """Impact-lane admission + dispatch: serve B eligible requests
        from the quantized impact columns (jit_exec.run_impact_batch),
        with the block-max pruned sweep when no request tracks totals
        (jit_exec.run_impact_pruned). Opt-in per index
        (`index.search.impact_plane`) because quantized scores match
        the exact scorer only within the documented quantization bound
        — the exact scorer stays the default. Returns a drain handle or
        None (caller proceeds on the exact path); declines are
        reason-labeled via note_impact_fallback, mirroring the
        collective plane's admission accounting."""
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.search.execute import impact_terms
        cfg = jit_exec.impact_plane_config(self.ctx.index_name)
        if cfg is None or not reqs or not self.reader.segments:
            return None
        if self.ctx.dfs_stats is not None:
            # impacts bake READER-local idf; DFS global statistics
            # would score with different idf than the snapshot
            jit_exec.note_impact_fallback("dfs-stats")
            return None
        if any(not getattr(s, "resident", True)
               for s in self.reader.segments):
            jit_exec.note_impact_fallback("streamed-reader")
            return None
        specs = []
        for req in reqs:
            if (req.aggs or not _is_score_order(req.sort)
                    or req.post_filter is not None
                    or req.min_score is not None or req.suggest
                    or req.terminate_after is not None
                    or req.timeout_ms is not None or req.rescore
                    or req.explain):
                jit_exec.note_impact_fallback("ineligible-shape")
                return None
            if req.search_after is not None and \
                    len(req.search_after) not in (1, 2):
                jit_exec.note_impact_fallback("ineligible-cursor")
                return None
            spec = impact_terms(req.query, self.mapper_service,
                                max_terms=cfg.max_terms)
            if spec is None:
                jit_exec.note_impact_fallback("ineligible-query")
                return None
            specs.append(spec)
        if len({f for f, _, _ in specs}) != 1:
            jit_exec.note_impact_fallback("mixed-fields")
            return None
        field = specs[0][0]
        k = max(max(req.from_ + req.size, 1) for req in reqs)
        term_lists = [terms for _, terms, _ in specs]
        boosts = [boost for _, _, boost in specs]
        prune = cfg.prune and all(req.track_total_hits is False
                                  for req in reqs)
        try:
            pack = jit_exec.impact_pack_for(
                self.reader, field, cfg, k1=self.ctx.bm25.k1,
                b=self.ctx.bm25.b)
            if pack is None:
                jit_exec.note_impact_fallback("no-impact-columns")
                return None
            # cursor provenance: the in-program continuation compares
            # QUANTIZED scores, so a cursor minted by the exact scorer
            # (prior page fell back) or by a pre-requant quantization
            # would skip/duplicate hits across pages — verify each
            # cursor against the pack and decline the batch otherwise
            cursors = []
            for req, terms, boost in zip(reqs, term_lists, boosts):
                if req.search_after is None:
                    cursors.append(None)
                    continue
                cur = jit_exec.verify_impact_cursor(
                    pack, terms, boost, req.search_after)
                if cur is None:
                    jit_exec.note_impact_fallback("cross-lane-cursor")
                    return None
                cursors.append(cur)
            if prune and not pack.can_prune:
                prune = False               # block tables over budget
            mesh = jit_exec.serving_mesh()
            if mesh is not None:
                from elasticsearch_tpu.search.planner import \
                    prefer_mesh_serving
                if not prefer_mesh_serving("impact"):
                    mesh = None          # measured single-chip win
            if mesh is not None:
                out = jit_exec.run_impact_mesh(
                    self.reader, pack, mesh, term_lists, boosts,
                    cursors, k=k, prune=prune, n_real=n_real)
            else:
                run = jit_exec.run_impact_pruned if prune \
                    else jit_exec.run_impact_batch
                out = run(pack, term_lists, boosts, cursors, k=k,
                          n_real=n_real)
        except QueryParsingError:
            raise
        except Exception as e:            # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            jit_exec.note_impact_fallback("device-error")
            return None
        jit_exec.plane_breaker.record_success()
        for name in ("top_scores", "top_docs", "count"):
            try:
                out[name].copy_to_host_async()
            except Exception:             # noqa: BLE001 — optional
                pass
        return ("impact", reqs, k, out, prune, pack.total_blocks,
                n_real if n_real is not None else len(reqs))

    def _rescore_batch_launch(self, reqs: list,
                              n_real: int | None = None):
        """The planner's composed impact→rescore arm: impact-pruned/
        eager candidate generation feeding the QueryRescorer window
        combine as a device-side stage — one dispatch for primary
        scoring, secondary scoring AND the window re-sort
        (jit_exec.run_impact_rescore). Admission: the index opted into
        the impact plane, every request carries exactly ONE rescore
        pass with a shared score_mode, both the primary query and the
        rescore query are impact-scorable on the SAME field, and no
        cursors (rescore + search_after pagination stays serial).
        Declines return None — the quantized-impact and exact arms
        screen next (both reject rescore shapes, so the serial path
        serves the request as before this arm existed)."""
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.search.execute import impact_terms
        cfg = jit_exec.impact_plane_config(self.ctx.index_name)
        if cfg is None or not reqs or not self.reader.segments:
            return None
        if self.ctx.dfs_stats is not None:
            return None                   # impacts bake reader-local idf
        if any(not getattr(s, "resident", True)
               for s in self.reader.segments):
            return None
        specs, specs2, windows, qws, rws, modes = [], [], [], [], [], []
        for req in reqs:
            if (len(req.rescore) != 1 or req.aggs
                    or not _is_score_order(req.sort)
                    or req.post_filter is not None
                    or req.min_score is not None or req.suggest
                    or req.terminate_after is not None
                    or req.timeout_ms is not None or req.explain
                    or req.search_after is not None
                    or req.knn is not None):
                return None
            rs = req.rescore[0]
            spec = impact_terms(req.query, self.mapper_service,
                                max_terms=cfg.max_terms)
            spec2 = impact_terms(rs.query, self.mapper_service,
                                 max_terms=cfg.max_terms)
            if spec is None or spec2 is None:
                jit_exec.note_impact_fallback("ineligible-query")
                return None
            specs.append(spec)
            specs2.append(spec2)
            windows.append(int(rs.window_size))
            qws.append(float(rs.query_weight))
            rws.append(float(rs.rescore_query_weight))
            modes.append(rs.score_mode)
        if len({f for f, _, _ in specs} |
               {f for f, _, _ in specs2}) != 1:
            jit_exec.note_impact_fallback("mixed-fields")
            return None
        if len(set(modes)) != 1:
            return None                   # score_mode is program-static
        field = specs[0][0]
        k = max(max(req.from_ + req.size, 1, w)
                for req, w in zip(reqs, windows))
        try:
            pack = jit_exec.impact_pack_for(
                self.reader, field, cfg, k1=self.ctx.bm25.k1,
                b=self.ctx.bm25.b)
            if pack is None:
                jit_exec.note_impact_fallback("no-impact-columns")
                return None
            out = jit_exec.run_impact_rescore(
                pack, [t for _, t, _ in specs],
                [bo for _, _, bo in specs],
                [t for _, t, _ in specs2],
                [bo for _, _, bo in specs2],
                windows, qws, rws, modes[0], k=k, n_real=n_real)
        except QueryParsingError:
            raise
        except Exception as e:            # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            jit_exec.note_impact_fallback("device-error")
            return None
        jit_exec.plane_breaker.record_success()
        for name in ("top_scores", "top_docs", "count"):
            try:
                out[name].copy_to_host_async()
            except Exception:             # noqa: BLE001 — optional
                pass
        return ("rescore", reqs, k, out, pack.total_blocks,
                n_real if n_real is not None else len(reqs))

    # ---- dense / late-interaction lane (top-level "knn" section) ----------

    def _validate_knn(self, knn: q.KnnSection) -> None:
        """Parse-time mapping validation: the field must be mapped
        dense_vector (flat query_vector) or rank_vectors (list-of-
        vectors), and the query's dims must match the mapping — a clear
        400 before any device work, not a score-time shape error."""
        fm = self.mapper_service.field_mapper(knn.field)
        kind = getattr(fm, "kind", None)
        if fm is None or kind not in ("vector", "mvector"):
            raise QueryParsingError(
                f"[knn] field [{knn.field}] is not mapped as "
                f"dense_vector or rank_vectors")
        if knn.multi and kind != "mvector":
            raise QueryParsingError(
                f"[knn] field [{knn.field}] is dense_vector but "
                f"query_vector is a list of vectors — flat [dims] "
                f"expected")
        if not knn.multi and kind != "vector":
            raise QueryParsingError(
                f"[knn] field [{knn.field}] is rank_vectors — "
                f"query_vector must be a list of [dims] token vectors")
        dims = int(getattr(fm, "dims", 0))
        qdims = len(knn.query_vector[0]) if knn.multi \
            else len(knn.query_vector)
        if qdims != dims:
            raise QueryParsingError(
                f"[knn] query_vector dims [{qdims}] != mapped dims "
                f"[{dims}] of field [{knn.field}]")

    @staticmethod
    def _knn_limit(req: ParsedSearchRequest) -> int:
        """Hits a knn request may return: the from/size window, capped
        by the section's k for pure knn (k IS "how many neighbors");
        hybrid windows read from the fused list (depth bounded by
        num_candidates per lane)."""
        lim = max(req.from_ + req.size, 1)
        return lim if req.knn.hybrid else min(lim, req.knn.k)

    def _rewrite_knn(self, req: ParsedSearchRequest) -> ParsedSearchRequest:
        """Join-rewrite the hybrid lexical query and the knn filter."""
        import dataclasses as _dc
        knn = req.knn
        new_q = self._rewrite_joins(req.query) if knn.hybrid else req.query
        new_f = self._rewrite_joins(knn.filter) \
            if knn.filter is not None else None
        if new_q is req.query and new_f is knn.filter:
            return req
        return _dc.replace(req, query=new_q,
                           knn=_dc.replace(knn, filter=new_f))

    def _knn_batch_launch(self, reqs: list, n_real: int | None = None):
        """knn-lane admission + dispatch: serve B knn/hybrid requests
        as ONE compiled program (jit_exec.run_knn_hybrid_batch) over
        the reader's block-cached vector columns. Returns a drain
        handle or None (callers retry per request / fall back to the
        eager per-segment lane); declines are reason-labeled via
        note_knn_fallback, mirroring the impact lane's admission
        accounting. Mapping violations raise QueryParsingError — those
        are request errors (400), never fallbacks."""
        from elasticsearch_tpu.search import jit_exec
        for r in reqs:
            self._validate_knn(r.knn)
        if not self.reader.segments:
            return ("empty", reqs)
        knns = [r.knn for r in reqs]
        if len({(kn.field, kn.hybrid, kn.multi, kn.num_candidates)
                for kn in knns}) != 1:
            jit_exec.note_knn_fallback("mixed-shapes")
            return None
        if any(not getattr(s, "resident", True)
               for s in self.reader.segments):
            jit_exec.note_knn_fallback("streamed-reader")
            return None
        reqs = [self._rewrite_knn(r) for r in reqs]
        cfg = jit_exec.knn_plane_config(self.ctx.index_name)
        k_prog = max(self._knn_limit(r) for r in reqs)
        try:
            pack = jit_exec.vector_pack_for(self.reader, knns[0].field,
                                            cfg)
            if pack is None:
                # mapped but no segment carries vectors yet: the eager
                # lane returns the same empty result without a compile
                jit_exec.note_knn_fallback("no-vector-columns")
                return None
            if pack.multi != knns[0].multi:
                jit_exec.note_knn_fallback("mixed-shapes")
                return None
            mesh = jit_exec.serving_mesh()
            if mesh is not None:
                from elasticsearch_tpu.search.planner import \
                    prefer_mesh_serving
                if not prefer_mesh_serving("knn"):
                    mesh = None          # measured single-chip win
            if mesh is not None:
                out = jit_exec.run_knn_hybrid_mesh(
                    self.reader, self.ctx, reqs, pack, cfg, mesh,
                    k=k_prog, num_candidates=knns[0].num_candidates,
                    n_real=n_real)
            else:
                out = jit_exec.run_knn_hybrid_batch(
                    self.reader, self.ctx, reqs, pack, cfg, k=k_prog,
                    num_candidates=knns[0].num_candidates,
                    n_real=n_real)
        except QueryParsingError:
            raise
        except Exception as e:            # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            jit_exec.note_knn_fallback("device-error")
            return None
        if out is None:                   # mixed plan signatures
            jit_exec.note_knn_fallback("mixed-shapes")
            return None
        jit_exec.plane_breaker.record_success()
        hybrid = knns[0].hybrid
        n = n_real if n_real is not None else len(reqs)
        jit_exec.note_knn_served(
            self.ctx.index_name, n,
            fused=n if hybrid else 0,
            maxsim=n if pack.multi else 0)
        for name in ("top_scores", "top_docs", "count"):
            try:
                out[name].copy_to_host_async()
            except Exception:             # noqa: BLE001 — optional
                pass
        return ("knn", reqs, k_prog, out)

    def _knn_query_phase(self, req: ParsedSearchRequest
                         ) -> ShardQueryResult:
        """Single-request knn/hybrid entry: compiled lane when the
        breaker admits it, eager per-segment fallback otherwise."""
        from elasticsearch_tpu.search import jit_exec
        self._validate_knn(req.knn)
        if jit_exec.plane_breaker.allow():
            # the launch stays open in the in-flight book until the end
            # of its drain, as the planner's launches do
            with obs_trace.launch_scope() as launches:
                handle = self._knn_batch_launch([req])
            if handle is not None:
                return self._drain_arm(handle, launches)[0]
            obs_trace.close_launches(launches, drained=False)
        else:
            jit_exec.note_breaker_skip()
            jit_exec.note_knn_fallback("breaker-open")
        return self._knn_query_phase_eager(req)

    def _knn_query_phase_eager(self, req: ParsedSearchRequest
                               ) -> ShardQueryResult:
        """Eager fallback lane: host-side per-segment scoring from the
        SAME cached host columns (normalized f32 / int8 snapshot) the
        compiled pack uploads, host candidate selection and host
        fusion — the reference implementation the compiled program is
        tested against."""
        from elasticsearch_tpu.search import jit_exec
        req = self._rewrite_knn(req)
        knn = req.knn
        cfg = jit_exec.knn_plane_config(self.ctx.index_name)
        task, deadline = _task_budget(req)
        qv = np.asarray(knn.query_vector, np.float32)
        if knn.multi:
            qn = qv / np.maximum(
                np.linalg.norm(qv, axis=1, keepdims=True), 1e-12)
        else:
            qn = qv / max(float(np.linalg.norm(qv)), 1e-12)
        knn_s, knn_d = [], []
        lex_s, lex_d = [], []
        eligible = 0
        for dseg in self.reader.segments:
            _checkpoint(task)
            base = dseg.doc_base
            live = np.asarray(dseg.live)
            fmask = None
            if knn.filter is not None:
                ex = SegmentExecutor(dseg, self.ctx)
                fmask = np.asarray(ex.match_mask(knn.filter))
            if knn.hybrid:
                ex = SegmentExecutor(dseg, self.ctx)
                scores, mask = ex.execute(req.query)
                m = np.asarray(mask) & live
                s = np.asarray(scores)
                idx = np.nonzero(m)[0]
                lex_s.append(s[idx].astype(np.float32))
                lex_d.append(idx.astype(np.int64) + base)
            entry = jit_exec._host_knn_column(dseg.seg, knn.field,
                                              cfg.quantization)
            if entry is None:
                continue
            host, multi, _dims = entry
            exists = host["exists"]
            elig = exists & live[:exists.shape[0]]
            if fmask is not None:
                elig = elig & fmask[:exists.shape[0]]
            if multi:
                s = _maxsim_host(host, qn)
            elif host["qcol"] is not None:
                s = (host["vecs"].astype(np.float32) @ qn) \
                    * np.float32(host["scale"]) \
                    + np.float32(host["offset"]) * np.float32(qn.sum())
            else:
                s = host["vecs"] @ qn
            eligible += int(elig.sum())
            idx = np.nonzero(elig)[0]
            knn_s.append(s[idx].astype(np.float32))
            knn_d.append(idx.astype(np.int64) + base)
        c = knn.num_candidates

        def topc(scores_l, docs_l, depth):
            s = np.concatenate(scores_l) if scores_l \
                else np.zeros(0, np.float32)
            d = np.concatenate(docs_l) if docs_l \
                else np.zeros(0, np.int64)
            order = np.lexsort((d, -s.astype(np.float64)))[:depth]
            return s[order], d[order]
        ds, dd = topc(knn_s, knn_d, c)
        kq = self._knn_limit(req)
        if not knn.hybrid:
            s_ = (ds * np.float32(knn.boost))[:kq]
            d_ = dd[:kq]
            total = eligible
        else:
            ls, ld = topc(lex_s, lex_d, c)
            s_, d_, total = fuse_host(ls, ld, ds, dd, knn.boost, cfg, kq)
        return ShardQueryResult(
            self.shard_id, int(total),
            float(s_[0]) if len(s_) else None,
            np.asarray(d_, np.int32), np.asarray(s_, np.float32),
            None, {}, self.reader)

    def query_phase_batch_drain(self, handle
                                ) -> list[ShardQueryResult]:
        """Phase 2: block until the launched batch's results are on host
        (one RTT, overlappable across batches — concurrent drains share
        the link's latency) and build per-request ShardQueryResults."""
        if handle[0] == "plan":
            # planner-wrapped handle: drain the inner arm, then stamp
            # predicted-vs-measured plan cost (a drain-side plan.cost
            # span on profiled responses; mispriced warm plans land on
            # the flight recorder)
            from elasticsearch_tpu.search import planner
            _, node, plan, t0, inner = handle[:5]
            results = self._drain_arm(inner, handle[5] if len(handle) > 5
                                      else ())
            planner.finish_plan(node, plan, t0)
            return results
        return self._drain_arm(handle, ())

    def _drain_arm(self, handle, launches) -> list[ShardQueryResult]:
        """One arm's handle → results: ``jit.drain`` blocks until the
        launched arrays are on the host — its end closes the handle's
        launches in the in-flight book, on every exit — and
        ``jit.unpack`` builds the per-request results from them."""
        tag, reqs = handle[0], handle[1]
        if tag == "empty":
            return [ShardQueryResult(self.shard_id, 0, None,
                                     np.zeros(0, np.int32),
                                     np.zeros(0, np.float32), None, {},
                                     self.reader) for _ in reqs]
        try:
            with obs_trace.span("jit.drain"):
                if tag == "host":
                    host = handle[3]
                elif tag == "device" and handle[3]:
                    # single-fetch fast path: scoring, merge AND result
                    # packing ran as one program — one dispatch + one
                    # device→host fetch per batch
                    host = np.asarray(handle[4])
                else:
                    out = handle[4] if tag == "device" else handle[3]
                    names = ("top_scores", "top_docs", "count")
                    if tag == "impact" and handle[4]:       # pruned
                        names += ("blocks_scored", "blocks_skipped")
                    host = {name: np.asarray(out[name]) for name in names}
        finally:
            obs_trace.close_launches(launches)
        with obs_trace.span("jit.unpack"):
            return self._unpack_arm(handle, host)

    def _unpack_arm(self, handle, host) -> list[ShardQueryResult]:
        tag, reqs, k = handle[0], handle[1], handle[2]
        if tag == "host":
            ms, md, totals = host
        elif tag == "device" and handle[3]:
            ms, md, totals = topk_ops.unpack_batch_result(host, k)
        else:
            ms, md, totals = (host["top_scores"], host["top_docs"],
                              host["count"])
        if tag == "impact":
            from elasticsearch_tpu.observability import attribution
            from elasticsearch_tpu.search import jit_exec
            _, _, _, _, pruned, total_blocks, n_real = handle
            if pruned:
                scored = int(host["blocks_scored"].sum())
                skipped = int(host["blocks_skipped"].sum())
                attribution.label(
                    "pruned", f"{skipped}/{scored + skipped} blocks")
            else:
                # eager impact scoring touches every block — honest
                # effective-work accounting for the skip-ratio surfaces
                # (real rows only: pad replicas are not admissions)
                scored, skipped = total_blocks * n_real, 0
            jit_exec.note_impact_served(self.ctx.index_name, n_real,
                                        scored, skipped)
        elif tag == "rescore":
            from elasticsearch_tpu.search import jit_exec
            _, _, _, _, total_blocks, n_real = handle
            # the composed plan's candidate stage is eager — every
            # block scored — and the whole rescore rode the one
            # dispatch
            jit_exec.note_impact_served(self.ctx.index_name, n_real,
                                        total_blocks * n_real, 0)
            jit_exec.note_rescore_fused(n_real)
        results = []
        for bi, req in enumerate(reqs):
            kq = self._knn_limit(req) if tag == "knn" \
                else max(req.from_ + req.size, 1)
            valid = md[bi] >= 0
            s_, d_ = ms[bi][valid][:kq], md[bi][valid][:kq]
            results.append(ShardQueryResult(
                self.shard_id, int(totals[bi]),
                float(s_[0]) if s_.size else None,
                d_.astype(np.int32), s_.astype(np.float32), None, {},
                self.reader))
        return results

    def _query_phase_batch_streamed(self, queries: list, k: int,
                                    streamed: list):
        """Batched query phase when the reader exceeds its HBM budget: the
        resident prefix runs as the usual one-program merge; streamed
        segments run double-buffered through jit_exec.run_segments_streamed;
        the final cross-part merge happens host-side in segment order (the
        stable (-score, segment) tie-break of the fully-resident path).
        → (ms, md, totals) numpy arrays or None (ineligible plans)."""
        from elasticsearch_tpu.search import jit_exec
        b = len(queries)
        resident = [s for s in self.reader.segments
                    if getattr(s, "resident", True)]
        try:
            out_r = None
            if resident:
                out_r = jit_exec.run_reader_batch(resident, self.ctx,
                                                  queries, k=k, pack=False)
                if out_r is None:
                    return None
            outs_s = jit_exec.run_segments_streamed(
                streamed, self.ctx, queries, k=k,
                device=getattr(self.reader, "device", None))
        except QueryParsingError:
            raise
        except Exception as e:            # noqa: BLE001 — fallback seam
            jit_exec.note_fallback(e, reason="device-error")
            jit_exec.note_device_error(e)
            return None
        if outs_s is None:
            return None
        jit_exec.plane_breaker.record_success()
        ms_parts, md_parts = [], []
        totals = np.zeros(b, np.int64)
        if out_r is not None:
            ms_parts.append(np.asarray(out_r["top_scores"]))
            md_parts.append(np.asarray(out_r["top_docs"]))
            totals = totals + np.asarray(out_r["count"])
        for seg, o in zip(streamed, outs_s):
            s_ = np.asarray(o["top_scores"])[:b]
            d_ = np.asarray(o["top_docs"])[:b]
            ms_parts.append(s_)
            md_parts.append(np.where(d_ >= 0, d_ + seg.doc_base, -1))
            totals = totals + np.asarray(o["count"])[:b]
        S = np.concatenate(ms_parts, axis=1)
        D = np.concatenate(md_parts, axis=1)
        S = np.where(D >= 0, S, -np.inf).astype(np.float32)
        order = np.argsort(-S, axis=1, kind="stable")[:, :k]
        ms = np.take_along_axis(S, order, axis=1)
        md = np.take_along_axis(D, order, axis=1)
        md = np.where(np.isfinite(ms), md, -1)
        return ms, md, totals

    def _apply_rescore(self, req: ParsedSearchRequest,
                       res: ShardQueryResult) -> None:
        """Re-rank the top window of this shard's hits per rescore pass
        (QueryRescorer.rescore: docs matching the rescore query combine
        primary×query_weight with secondary×rescore_query_weight; docs not
        matching keep primary×query_weight; only the window re-sorts)."""
        if not len(res.doc_ids):
            return
        scores = res.scores.astype(np.float32).copy()
        docs = res.doc_ids.copy()
        for spec in req.rescore:
            window = min(spec.window_size, len(docs))
            if window <= 0:
                continue
            per_seg = self._execute_query(spec.query)
            sec_scores = np.concatenate(
                [np.asarray(s) for s, _ in per_seg])
            sec_mask = np.concatenate([np.asarray(m) for _, m in per_seg])
            d = docs[:window]
            prim = scores[:window] * np.float32(spec.query_weight)
            sec = sec_scores[d] * np.float32(spec.rescore_query_weight)
            if spec.score_mode == "total":
                comb = prim + sec
            elif spec.score_mode == "multiply":
                comb = prim * sec
            elif spec.score_mode == "avg":
                comb = (prim + sec) / 2.0
            elif spec.score_mode == "max":
                comb = np.maximum(prim, sec)
            else:                          # min
                comb = np.minimum(prim, sec)
            comb = np.where(sec_mask[d], comb, prim).astype(np.float32)
            order = np.lexsort((d, -comb))  # score desc, doc-id tie-break
            docs[:window] = d[order]
            scores[:window] = comb[order]
        res.doc_ids = docs
        res.scores = scores
        res.max_score = float(scores[0]) if len(scores) else None

    def _collect_aggs(self, req: ParsedSearchRequest,
                      masks: list, scores: list) -> dict:
        """Run top-level agg collectors over the (pre-post_filter) mask —
        shared by the jit and eager query paths. ``masks``/``scores`` are
        per-segment DEVICE arrays: the device fast path (collect_device)
        segment-reduces on the accelerator with only bucket/scalar results
        crossing to host; ineligible nodes fall back to the numpy
        collectors, which materialize the host mask once, lazily."""
        if not req.aggs:
            return {}
        from elasticsearch_tpu.search.aggregations import (
            DEVICE_AGG_STATS, DeviceAggState, PIPELINE_AGGS, collect_device)
        state = DeviceAggState(self.reader, masks, scores)
        out = {}
        np_ctx = None
        for node in req.aggs:
            if node.type in PIPELINE_AGGS:
                continue
            partial = collect_device(node, state)
            if partial is None:
                DEVICE_AGG_STATS["host_fallbacks"] += 1
                if np_ctx is None:
                    np_ctx = ShardAggContext(
                        self.reader, self.mapper_service,
                        self._filter_masks_np, scores=state.np_scores(),
                        exec_ctx=self.ctx)
                partial = collect(node, state.np_mask(), np_ctx)
            out[node.name] = partial
        return out

    def _finish_score_order(self, k: int, total: int, seg_scores: list,
                            seg_docs: list, agg_partials: dict
                            ) -> ShardQueryResult:
        """Device merge of per-segment top-k → shard result (shared by the
        jit and eager query paths)."""
        if seg_scores:
            ms, md = topk_ops.merge_top_k(seg_scores, seg_docs, k)
            ms, md = np.asarray(ms), np.asarray(md)
            valid = md >= 0
            ms, md = ms[valid], md[valid]
        else:
            ms, md = np.zeros(0, np.float32), np.zeros(0, np.int32)
        max_sc = float(ms[0]) if ms.size else None
        return ShardQueryResult(self.shard_id, total, max_sc, md, ms, None,
                                agg_partials, self.reader)

    def _query_phase_eager(self, req: ParsedSearchRequest) -> ShardQueryResult:
        """Eager per-op fallback, same partial-results semantics as the jit
        path: terminate_after / timeout stop between segments (counts here
        are pre-min_score/post_filter — a coarser budget than the jit
        path's, acceptable for the fallback seam)."""
        k = max(req.from_ + req.size, 1)
        if req.rescore:
            k = max(k, max(s.window_size for s in req.rescore))
        terminated_early = timed_out = False
        task, deadline = _task_budget(req)
        per_seg = []
        segments = []
        running = 0
        for seg in self.reader.segments:
            _checkpoint(task)
            if deadline is not None and time.monotonic() > deadline:
                timed_out = True
                break
            ex = SegmentExecutor(seg, self.ctx)
            scores, mask = ex.execute(req.query)
            mask = mask & seg.live
            per_seg.append((scores, mask))
            segments.append(seg)
            if req.terminate_after is not None or deadline is not None:
                running += int(np.asarray(topk_ops.count_matches(mask)))
                if req.terminate_after is not None and \
                        running >= req.terminate_after:
                    terminated_early = True
                    break

        if req.min_score is not None:
            per_seg = [(s, m & (s >= np.float32(req.min_score)))
                       for s, m in per_seg]

        # aggregations run on the pre-post_filter mask (ES semantics);
        # unprocessed segments contribute empty masks; arrays stay on
        # device for the agg fast path
        masks = [m for _, m in per_seg]
        scores_l = [s for s, _ in per_seg]
        for seg in self.reader.segments[len(per_seg):]:
            masks.append(jnp.zeros(seg.padded_docs, bool))
            scores_l.append(jnp.zeros(seg.padded_docs, jnp.float32))
        agg_partials = self._collect_aggs(req, masks, scores_l)

        if req.post_filter is not None:
            post = [SegmentExecutor(seg, self.ctx).match_mask(req.post_filter)
                    for seg in segments]
            per_seg = [(s, m & pm) for (s, m), pm in zip(per_seg, post)]

        if req.search_after is not None and not req.sort:
            # score-ordered continuation: strictly worse than (score, doc)
            last_score = np.float32(float(req.search_after[0]))
            last_doc = int(req.search_after[1]) if len(req.search_after) > 1 else -1
            new = []
            for seg, (s, m) in zip(segments, per_seg):
                ids = jnp.arange(seg.padded_docs, dtype=jnp.int32) + seg.doc_base
                cont = (s < last_score) | ((s == last_score) & (ids > last_doc))
                new.append((s, m & cont))
            per_seg = new

        total = int(sum(int(np.asarray(topk_ops.count_matches(m)))
                        for _, m in per_seg)) if per_seg else 0
        if req.terminate_after is not None:
            total = min(total, req.terminate_after)

        if not _is_score_order(req.sort):
            if per_seg:
                res = self._sorted_query(req, per_seg, total, agg_partials,
                                         segments=segments)
            else:
                res = ShardQueryResult(self.shard_id, 0, None,
                                       np.zeros(0, np.int32),
                                       np.zeros(0, np.float32), [],
                                       agg_partials, self.reader)
        else:
            # score ordering: device top-k per segment, device merge
            seg_scores, seg_docs = [], []
            for seg, (s, m) in zip(segments, per_seg):
                ts, td = topk_ops.top_k(s, m, min(k, seg.padded_docs),
                                        seg.doc_base)
                seg_scores.append(ts)
                seg_docs.append(td)
            res = self._finish_score_order(k, total, seg_scores, seg_docs,
                                           agg_partials)
        res.terminated_early = terminated_early
        res.timed_out = timed_out
        if req.rescore and res.sort_values is None:
            self._apply_rescore(req, res)
        return res

    def _sorted_query(self, req, per_seg, total, agg_partials,
                      segments=None):
        """Sort-by-field path: host numpy argsort over doc-values columns
        (exact f64; matches Lucene FieldComparator semantics incl. missing).
        `segments` restricts to a processed PREFIX of the reader's segments
        (early termination) — concat order keeps global ids aligned."""
        segments = self.reader.segments if segments is None else segments
        mask = np.concatenate([np.asarray(m) for _, m in per_seg])
        scores = np.concatenate([np.asarray(s) for s, _ in per_seg])
        n = mask.shape[0]
        doc_ids = np.arange(n, dtype=np.int64)
        keys = []           # numeric sort keys per spec
        per_hit_out: list = []   # per spec: value to emit in hit["sort"]
        sort_specs = []
        for spec in req.sort:
            (fname, opts), = spec.items()
            order = opts.get("order", "asc")
            missing = opts.get("missing", "_last")
            sort_specs.append((fname, order, missing))
            if fname == "_score":
                vals = scores.astype(np.float64)
                out = vals
            elif fname == "_doc":
                # globally unique across shards AND indices so (.., _doc)
                # search_after cursors are unambiguous at the coordinator
                vals = (doc_ids + (self._doc_slot << 42)).astype(np.float64)
                out = vals
            else:
                vals, out = self._sort_column(fname, n, missing, order,
                                              segments)
            per_hit_out.append(out)
            keys.append(-vals if order == "desc" else vals)
        # np.lexsort: LAST key is primary → (docid tie-break, ..., spec1)
        order_idx = np.lexsort(tuple([doc_ids] + keys[::-1]))
        order_idx = order_idx[mask[order_idx]]
        if req.search_after is not None:
            order_idx = self._apply_search_after(req.search_after, sort_specs,
                                                 per_hit_out, order_idx)
        k = max(req.from_ + req.size, 1)
        top = order_idx[:k]
        sort_values = [[_sort_value_out(per_hit_out[i][d])
                        for i in range(len(req.sort))] for d in top]
        return ShardQueryResult(self.shard_id, total, None,
                                top.astype(np.int32), scores[top],
                                sort_values, agg_partials, self.reader)

    def _sort_column(self, fname: str, n: int, missing, order: str,
                     segments=None):
        """→ (numeric sort key [n] f64, per-hit output values [n] object)."""
        segments = self.reader.segments if segments is None else segments
        cols = []
        outs = []
        # union vocabulary across segments so keyword ordinals are comparable
        union: dict[str, int] | None = None
        if any(fname in seg.seg.keyword_fields for seg in segments):
            values: set[str] = set()
            for seg in segments:
                kcol = seg.seg.keyword_fields.get(fname)
                if kcol is not None:
                    values.update(kcol.vocab)
            union_vocab = sorted(values)
            union = {v: i for i, v in enumerate(union_vocab)}
        # one missing-fill per (field, order, missing) spec, shared by
        # missing DOCS and column-less SEGMENTS alike — a segment that
        # happens to hold no values for the field must rank its docs
        # exactly like a missing doc in a segment that has the column.
        # _last/_first place at the end/start of the list regardless of
        # direction; a custom value/TERM substitutes for comparison
        # (terms absent from the union vocab rank between neighbors).
        if missing in ("_last", "_first"):
            fill = np.inf if (missing == "_last") == (order == "asc") \
                else -np.inf
            out_fill = None if union is not None else fill
        elif union is not None:
            ms = str(missing)
            if ms in union:
                fill = float(union[ms])
            else:
                import bisect
                fill = bisect.bisect_left(union_vocab, ms) - 0.5
            out_fill = ms
        elif any(fname in seg.seg.numeric_fields for seg in segments):
            # numeric field: a non-numeric substitute is a caller error —
            # surface it (float raises), don't silently rank at 0
            fill = float(missing)
            out_fill = fill
        else:
            try:
                fill = float(missing)
                out_fill = fill
            except (TypeError, ValueError):
                # a string substitute on a field with NO column of either
                # kind anywhere in the shard: every doc is missing, so
                # all rank equal at the substitute
                fill = 0.0
                out_fill = str(missing)
        for seg in segments:
            col = seg.seg.numeric_fields.get(fname)
            if col is not None:
                vals = col.values.astype(np.float64).copy()
                vals[~col.exists] = fill
                cols.append(vals)
                outs.append(vals)
                continue
            kcol = seg.seg.keyword_fields.get(fname)
            if kcol is not None and union is not None:
                remap = np.array([union[v] for v in kcol.vocab] or [0],
                                 np.int64)
                first = kcol.ords[:, 0]
                have = first >= 0
                ranks = np.full(first.shape, fill, np.float64)
                ranks[have] = remap[first[have]]
                cols.append(ranks)
                out = np.full(first.shape, out_fill, dtype=object)
                out[have] = [union_vocab[int(r)] for r in ranks[have]]
                outs.append(out)
                continue
            cols.append(np.full(seg.padded_docs, np.float64(fill)))
            outs.append(np.full(seg.padded_docs, out_fill, dtype=object))
        if not cols:
            return np.full(n, np.inf), np.full(n, None, dtype=object)
        return np.concatenate(cols), np.concatenate(outs)

    def _apply_search_after(self, after: list, sort_specs, per_hit_out,
                            order_idx):
        """Keep docs strictly after the cursor in sort order. Cursor values
        are the emitted hit['sort'] values (numbers or keyword strings)."""
        keep = []
        for d in order_idx:
            cmp = 0
            for i, (fname, order, missing) in enumerate(sort_specs):
                if i >= len(after):
                    break
                a, b = per_hit_out[i][d], after[i]
                if a is None and b is None:
                    continue
                # missing docs sit first or last in *result order* per the
                # `missing` option (matches _sort_column's fill and the
                # coordinator merge) — no desc negation
                if a is None or b is None:
                    missing_after = missing != "_first"
                    if a is None:
                        cmp = 1 if missing_after else -1
                    else:
                        cmp = -1 if missing_after else 1
                    break
                if isinstance(a, str) or isinstance(b, str):
                    a, b = str(a), str(b)
                else:
                    a, b = float(a), float(b)
                if a == b:
                    continue
                c = 1 if a > b else -1
                cmp = c if order == "asc" else -c
                break
            if cmp > 0:
                keep.append(d)
        return np.asarray(keep, dtype=order_idx.dtype)

    # -- fetch phase ---------------------------------------------------------

    def fetch_phase(self, req: ParsedSearchRequest, result: ShardQueryResult,
                    index_name: str, positions: list[int]) -> list[dict]:
        # one span per call, never per hit
        with obs_trace.span("fetch.hits"):
            return self._fetch_hits(req, result, index_name, positions)

    def _fetch_hits(self, req: ParsedSearchRequest, result: ShardQueryResult,
                    index_name: str, positions: list[int]) -> list[dict]:
        from elasticsearch_tpu.index.engine import _segment_meta
        meta_wanted = [f for f in req.stored_fields
                       if f in ("_routing", "_parent", "_timestamp", "_ttl")]
        hits = []
        for pos in positions:
            gid = int(result.doc_ids[pos])
            seg, local = self.reader.resolve(gid)
            src = seg.seg.sources[local]
            meta = _segment_meta(seg.seg, local) or {}
            emit_score = result.sort_values is None or any(
                "_score" in spec for spec in req.sort)
            hit = {
                "_index": index_name,
                "_type": meta.get("_type", "_doc"),
                "_id": seg.seg.ids[local],
                "_score": (float(result.scores[pos]) if emit_score else None),
            }
            if req.version:
                # point-in-time version from the segment's _version
                # column (VersionFieldMapper doc-value) — the live map is
                # only a fallback for rows indexed before the column
                # existed; a live read could pair a newer version with
                # this snapshot's _source and defeat optimistic deletes
                v = meta.get("_version")
                if v is None and self.version_fn is not None:
                    v = self.version_fn(hit["_id"])
                if v is not None:
                    hit["_version"] = v
            # requested metadata fields render at the TOP level of the hit
            # (InternalSearchHit.toXContent puts metadata fields beside
            # _id, not under "fields" — the 2.x shape delete-by-query's
            # scroll relies on for _routing/_parent)
            for f in meta_wanted:
                if meta.get(f) is not None:
                    hit[f] = meta[f]
            if result.sort_values is not None:
                hit["sort"] = result.sort_values[pos]
            filtered = _filter_source(src, req.source_filter)
            if filtered is not None:
                hit["_source"] = filtered
            if req.highlight:
                hl = highlight_hit(req.highlight, src, self.mapper_service,
                                   req.query)
                if hl:
                    hit["highlight"] = hl
            if req.script_fields:
                hit["fields"] = self._script_fields(req.script_fields, seg, local)
            elif req.stored_fields or req.docvalue_fields:
                fields = {}
                for f in list(req.stored_fields) + list(
                        req.docvalue_fields):
                    v = src.get(f)
                    if v is None and "." in f:   # dotted path into objects
                        node = src
                        for part in f.split("."):
                            node = node.get(part) \
                                if isinstance(node, dict) else None
                            if node is None:
                                break
                        v = node
                    if v is not None and not isinstance(v, dict):
                        fields[f] = v if isinstance(v, list) else [v]
                if fields:
                    hit["fields"] = fields
            hits.append(hit)
        return hits

    def _script_fields(self, script_fields: dict, seg, local: int) -> dict:
        from elasticsearch_tpu.search.scripts import compile_script, ScriptContext
        from elasticsearch_tpu.search import jit_exec
        out = {}
        for name, spec in script_fields.items():
            script = spec.get("script", spec)
            lang = None
            if isinstance(script, dict):
                src = script.get("source", script.get("inline", ""))
                params = script.get("params", {})
                lang = script.get("lang")
            else:
                src, params = str(script), {}
            def run_interpreted(compile_fn):
                """Per-hit engine run (shared by the explicit-lang path
                and the expression-compile fallback)."""
                from elasticsearch_tpu.search.aggregations import (
                    _AggDocValues)
                dv = _AggDocValues(seg.seg)
                dv.doc = int(local)
                val = compile_fn(src).run({"doc": dv, "params": params})
                out[name] = val if isinstance(val, list) else [val]

            if lang not in (None, "expression"):
                # explicit lang → its registered engine, per hit
                # (ScriptService.compile dispatches by lang the same way)
                from elasticsearch_tpu.search.script_engines import (
                    resolve_engine)
                run_interpreted(resolve_engine(lang))
                continue
            def get_numeric(fld):
                col = seg.numeric.get(fld)
                if col is None:
                    return jnp.zeros(seg.padded_docs, jnp.float32), \
                        jnp.zeros(seg.padded_docs, bool)
                return col.hi, col.exists
            def get_vector(fld):
                col = seg.vector.get(fld)
                if col is None:
                    raise QueryParsingError(f"no vector field [{fld}]")
                # vecs are LAZY (host numpy until first use) — _fetch
                # materializes + caches the device copy once per reader
                return jit_exec._fetch(seg, col, "vecs"), col.exists
            try:
                compiled = compile_script(src)
            except QueryParsingError:
                # not an expression: run the general-purpose language per
                # hit (lang-groovy analog — loops/conditionals/collections)
                from elasticsearch_tpu.search.scriptlang import (
                    compile_groovylite)
                run_interpreted(compile_groovylite)
                continue
            ctx = ScriptContext(get_numeric, get_vector,
                                jnp.zeros(seg.padded_docs, jnp.float32),
                                params)
            vals = compiled.evaluate(ctx)
            arr = np.asarray(jnp.broadcast_to(jnp.asarray(vals),
                                              (seg.padded_docs,)))
            out[name] = [float(arr[local])]
        return out


def _filter_source(src: dict, spec) -> dict | None:
    """_source filtering with DOTTED-PATH globs (ref:
    FetchSourceContext/XContentMapValues.filter): an include pattern
    matching an object path keeps the whole subtree; patterns reach into
    nested objects ("obj.inner.field", "obj.*")."""
    if spec is True:
        return src
    if spec is False:
        return None
    if isinstance(spec, str):
        spec = [spec]
    if isinstance(spec, list):
        includes, excludes = spec, []
    else:
        includes = spec.get("includes", spec.get("include", []))
        excludes = spec.get("excludes", spec.get("exclude", []))
        if isinstance(includes, str):
            includes = [includes]
        if isinstance(excludes, str):
            excludes = [excludes]
    if not includes and not excludes:
        return src

    def prefixes(path: str) -> list[str]:
        parts = path.split(".")
        return [".".join(parts[:i + 1]) for i in range(len(parts))]

    def included(path: str) -> bool:
        if not includes:
            return True
        return any(fnmatch.fnmatch(p, pat)
                   for pat in includes for p in prefixes(path))

    def deeper_include(path: str) -> bool:
        """An include pattern may target something BELOW this object."""
        return any(pat.startswith(path + ".") or
                   fnmatch.fnmatch(path, ".".join(
                       pat.split(".")[:len(path.split("."))]))
                   for pat in includes)

    def excluded(path: str) -> bool:
        return any(fnmatch.fnmatch(p, pat)
                   for pat in excludes for p in prefixes(path))

    def filter_value(v, path: str):
        """→ (keep, filtered value) for one field value at `path` —
        arrays of objects filter element-wise (XContentMapValues reaches
        inside arrays; element indices don't count as path segments)."""
        if isinstance(v, dict):
            if included(path):
                return True, (walk(v, path) if excludes else v)
            if includes and deeper_include(path):
                sub = walk(v, path)
                return bool(sub), sub
            return False, None
        if isinstance(v, list) and any(isinstance(el, dict) for el in v):
            out = []
            for el in v:
                if isinstance(el, dict):
                    keep, sub = filter_value(el, path)
                    if keep:
                        out.append(sub)
                elif included(path):
                    out.append(el)
            return bool(out), out
        return included(path), v

    def walk(obj: dict, prefix: str) -> dict:
        out = {}
        for k, v in obj.items():
            path = f"{prefix}.{k}" if prefix else k
            if excluded(path):
                continue
            keep, sub = filter_value(v, path)
            if keep:
                out[k] = sub
        return out

    return walk(src, "")


def _maxsim_host(host: dict, qn: np.ndarray) -> np.ndarray:
    """Host (numpy) MaxSim over one segment's cached knn column — the
    eager lane's scorer and the kernel tests' oracle. ``qn``: [Qt, D]
    row-normalized query tokens."""
    vecs = host["vecs"].astype(np.float32)        # [N, T, D] (int8→f32)
    lens = host["lens"]
    sim = np.einsum("ntd,qd->nqt", vecs, qn.astype(np.float32))
    t = vecs.shape[1]
    pad = np.arange(t)[None, None, :] >= lens[:, None, None]
    sim = np.where(pad, -np.inf, sim)
    tokmax = sim.max(axis=2)                      # [N, Qt]
    if host["qcol"] is not None:
        tokmax = tokmax * np.float32(host["scale"]) \
            + np.float32(host["offset"]) * qn.sum(axis=1)[None, :] \
            .astype(np.float32)
    tokmax = np.where(np.isfinite(tokmax), tokmax, 0.0)
    return tokmax.sum(axis=1).astype(np.float32)


def fuse_host(ls, ld, ds, dd, boost: float, cfg, k: int):
    """Host-side hybrid fusion — the oracle the in-program fusion is
    bit-matched against (f32 arithmetic, (score desc, doc asc) ties).

    ls/ld: lexical candidates (scores f32, global doc ids) in rank
    order; ds/dd: knn lane; boost scales the knn contribution.
    → (scores [<=k] f32, docs [<=k], fused candidate count)."""
    ls = np.asarray(ls, np.float32)
    ds = np.asarray(ds, np.float32)
    fused: dict[int, np.float32] = {}
    if cfg.fusion_mode == "weighted":
        def norm(s):
            if not len(s):
                return s
            lo, hi = np.float32(s.min()), np.float32(s.max())
            rng = (hi - lo) if hi > lo else np.float32(1.0)
            return ((s - lo) / rng).astype(np.float32)
        for d, v in zip(ld, np.float32(cfg.lexical_weight) * norm(ls)):
            fused[int(d)] = fused.get(int(d), np.float32(0.0)) + v
        wd = np.float32(1.0 - cfg.lexical_weight) * np.float32(boost)
        for d, v in zip(dd, wd * norm(ds)):
            fused[int(d)] = fused.get(int(d), np.float32(0.0)) + v
    else:
        # strict f32 arithmetic mirroring the device body: the rank
        # denominators are small integers (exact in f32), the division
        # and the boost multiply run in f32, and each doc receives at
        # most one contribution per lane (lex first) — so the fused
        # score is BIT-IDENTICAL to the in-program reduction
        k0 = int(cfg.rank_constant)
        bf = np.float32(boost)
        for rank, d in enumerate(ld):
            c = np.float32(1.0) / np.float32(k0 + rank + 1)
            fused[int(d)] = fused.get(int(d), np.float32(0.0)) + c
        for rank, d in enumerate(dd):
            c = (np.float32(1.0) / np.float32(k0 + rank + 1)) * bf
            fused[int(d)] = fused.get(int(d), np.float32(0.0)) + c
    ranked = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return (np.asarray([s for _, s in ranked], np.float32),
            np.asarray([d for d, _ in ranked], np.int64), len(fused))


def _sort_value_out(v):
    if v is None or isinstance(v, str):
        return v
    v = float(v)
    if v in (np.inf, -np.inf):
        return None
    if v.is_integer():
        return int(v)
    return v
