"""Distributed search: scatter query+fetch per shard, reduce at the
coordinator.

Reference: core/action/search/type/TransportSearchTypeAction.java:87-247 —
`start` (:137) fans one request per shard group to the next copy
(`performFirstPhase` :156), failed shards retry the next copy (:205-247),
and `SearchPhaseController` merges (sortDocs :165, merge :300). Each shard
executes query AND fetch of its own top `from+size` hits in one round
(QUERY_AND_FETCH semantics, SearchType.java:29 — correct for any
single-round request and chosen here because fetch-phase hits are small
columnar reads on the TPU host, so the second fan-out round of
QUERY_THEN_FETCH buys nothing); the coordinator reduce then keeps the
global [from, from+size) slice, which is identical to what
query_then_fetch returns.

Scroll pairs a coordinator-side cursor (search_after continuation
re-running the scatter) with data-node reader PINS: the first page pins
each shard's point-in-time SearcherView under the scroll's ctx_uid
(ScrollContext semantics, SearchService.java:533-558), so later pages
never see writes that landed mid-scroll; pins expire with the keep-alive
and die on clear_scroll.
"""

from __future__ import annotations

import base64
import contextlib
import itertools
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from elasticsearch_tpu.common.errors import (
    ElasticsearchTpuError, QueryParsingError, SearchContextMissingError,
    TaskCancelledError)
from elasticsearch_tpu.action.replica_stats import ReplicaStatsTable
from elasticsearch_tpu.common.settings import parse_time_value
from elasticsearch_tpu.index.device_reader import (
    device_reader_for, host_reader_for)
from elasticsearch_tpu.observability import attribution
from elasticsearch_tpu.observability import histograms as obs_hist
from elasticsearch_tpu.observability import tracing as obs_trace
from elasticsearch_tpu.search.controller import merge_shard_payloads
from elasticsearch_tpu.search.phase import ShardSearcher, parse_search_request
from elasticsearch_tpu.tasks import manager as tasks


def wire_safe(obj):
    """Make agg partials transport-serializable (sets → lists, numpy →
    python) without changing what reduce_aggs consumes."""
    if isinstance(obj, dict):
        return {k: wire_safe(v) for k, v in obj.items()}
    if isinstance(obj, (set, frozenset)):
        return sorted(str(x) for x in obj)
    if isinstance(obj, (list, tuple)):
        return [wire_safe(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


class _ScrollContext:
    def __init__(self, index_expr: str, body: dict, keep_alive_s: float,
                 search_type: str | None = None,
                 ctx_uid: str | None = None):
        import uuid as _uuid
        # stable id carried in every page's shard requests: data nodes pin
        # their point-in-time reader views under it (SearchService
        # activeContexts analog — scroll pages must NOT see later writes)
        self.ctx_uid = ctx_uid or _uuid.uuid4().hex
        self.index_expr = index_expr
        self.body = dict(body)
        self.search_type = search_type
        # a routed scroll stays routed on EVERY page, not just page one
        self.routing: str | None = None
        self.preference: str | None = None
        self.dfs_cache: dict = {}
        self.keep_alive_s = keep_alive_s
        self.expires_at = time.monotonic() + keep_alive_s
        self.last_sort_key: list | None = None
        self.finished = False

    def touch(self, keep_alive_s: float | None = None):
        if keep_alive_s is not None:
            self.keep_alive_s = keep_alive_s
        self.expires_at = time.monotonic() + self.keep_alive_s


def rewrite_mlt_likes(node, body: dict, default_index: str = "_all") -> dict:
    """Coordinator-side request rewrites that need cluster access before
    the per-shard fan-out:

    * more_like_this liked DOCUMENTS are fetched here (routing-aware GET,
      any shard/node) and turned into like-texts + `_exclude_ids`, so
      every shard scores them — a shard-local source scan would silently
      match nothing on shards not hosting the liked doc (the reference
      fetches liked docs before query construction too).
    * stored-script references ({"script": {"id": ...}} in script_score /
      function_score, {"id": ...} template queries) resolve against the
      cluster-state script registry (core/script/ScriptService indexed
      scripts) into inline sources shards can execute.

    Returns a rewritten copy (the input body is not mutated); bodies
    without such references pass through unchanged."""
    def walk(obj):
        if isinstance(obj, list):
            return [walk(v) for v in obj]
        if not isinstance(obj, dict):
            return obj
        out = {}
        for key, val in obj.items():
            if key in ("more_like_this", "mlt") and isinstance(val, dict) \
                    and _mlt_has_docs(val):
                out[key] = _fetch_mlt_likes(node, val, default_index)
            elif key == "script" and isinstance(val, dict) \
                    and "id" in val and "source" not in val \
                    and "inline" not in val:
                src = _stored_script_any(node, str(val["id"]),
                                         val.get("lang"))
                if src is None:
                    out[key] = walk(val)
                else:
                    out[key] = {**{k: walk(v) for k, v in val.items()
                                   if k != "id"}, "inline": src}
            elif key == "template" and isinstance(val, dict) \
                    and "id" in val and not any(
                        k in val for k in ("query", "inline", "source")):
                src = _stored_script_any(node, str(val["id"]), "mustache")
                if src is None:
                    out[key] = walk(val)
                else:
                    out[key] = {**{k: walk(v) for k, v in val.items()
                                   if k != "id"}, "inline": src}
            else:
                out[key] = walk(val)
        return out
    return walk(body)


def _stored_script_any(node, sid: str, lang: str | None):
    """Stored-script lookup; without a lang, any registered lang matches
    (the 2.x indexed-script API keys by (lang, id))."""
    if lang:
        return node.stored_script(sid, lang)
    scripts = node.cluster_service.state().customs.get("stored_scripts", {})
    for key, src in scripts.items():
        if key.split("\x00", 1)[1] == sid:
            return src
    return None


def _mlt_has_docs(spec: dict) -> bool:
    raw = spec.get("like", spec.get("like_text"))
    likes = raw if isinstance(raw, list) else [raw] if raw is not None else []
    return any(isinstance(x, dict) for x in likes) or \
        bool(spec.get("ids") or spec.get("docs"))


def _fetch_mlt_likes(node, spec: dict, default_index: str) -> dict:
    spec = dict(spec)
    raw_like = spec.pop("like", None)
    raw_like_text = spec.pop("like_text", None)
    raw = raw_like if raw_like is not None else raw_like_text
    # copy: appending ids/docs below must not mutate the caller's list (a
    # scroll context re-rewrites its stored body every page)
    likes = list(raw) if isinstance(raw, list) \
        else [raw] if raw is not None else []
    raw_ids = spec.pop("ids", None) or []
    raw_docs = spec.pop("docs", None) or []
    for did in list(raw_ids) + list(raw_docs):
        likes.append(did if isinstance(did, dict) else {"_id": did})
    raw_unlike = spec.get("unlike")
    unlikes = list(raw_unlike) if isinstance(raw_unlike, list) \
        else [raw_unlike] if raw_unlike is not None else []
    texts: list = []
    exclude = list(spec.get("_exclude_ids", []))
    fields = spec.get("fields") or []
    unlike_out: list = []
    for item in unlikes:
        if not isinstance(item, dict):
            unlike_out.append(str(item))
            continue
        if "doc" in item:
            unlike_out.extend(str(v) for v in item["doc"].values()
                              if isinstance(v, str))
            continue
        did = item.get("_id")
        if did is None:
            continue
        try:
            got = node.document_actions.get_doc(
                item.get("_index", default_index), str(did),
                routing=item.get("_routing", item.get("routing")))
        except Exception:                  # noqa: BLE001 — missing doc
            continue
        if got.get("found"):
            unlike_out.extend(v for v in (got.get("_source") or
                                          {}).values()
                              if isinstance(v, str))
    if unlike_out:
        spec["unlike"] = unlike_out
    for item in likes:
        if not isinstance(item, dict):
            texts.append(item)
            continue
        if "doc" in item:
            texts.extend(str(v) for v in item["doc"].values()
                         if isinstance(v, str))
            continue
        did = item.get("_id")
        if did is None:
            continue
        index = item.get("_index", default_index)
        routing = item.get("_routing", item.get("routing"))
        try:
            got = node.document_actions.get_doc(index, str(did),
                                                routing=routing)
        except Exception:                  # noqa: BLE001 — missing doc/index
            continue
        if not got.get("found"):
            continue
        src = got.get("_source") or {}
        for f in (fields or [k for k, v in src.items()
                             if isinstance(v, str)]):
            v = src.get(f)
            if isinstance(v, str):
                texts.append(v)
        exclude.append(str(did))
    spec["like"] = texts
    if exclude:
        spec["_exclude_ids"] = exclude
    return spec


class ShardRequestCache:
    """Shard request cache (ref:
    core/indices/cache/request/IndicesRequestCache.java:78): caches whole
    per-shard query+fetch payloads for hits-free requests (size 0 — the
    count/agg shapes the reference caches), keyed by (index, shard, reader
    generation, canonical request bytes). A refresh bumps the generation,
    so stale entries simply stop being hit and age out of the LRU."""

    def __init__(self, cap: int = 256):
        from collections import OrderedDict
        self.cap = cap
        self._lru: "OrderedDict[tuple, dict]" = OrderedDict()
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "evictions": 0}
        # per-engine-incarnation counters (key[0] is the engine uuid):
        # the per-index request_cache section of _stats reads these, so
        # hits/misses/evictions attribute to the index that earned them
        # instead of the node-wide rollup reporting for everyone
        self._by_uuid: dict[str, dict] = {}
        self._sizes: dict[tuple, int] = {}

    def _uuid_stats(self, uuid: str) -> dict:
        return self._by_uuid.setdefault(
            uuid, {"hits": 0, "misses": 0, "evictions": 0})

    def key(self, engine_uuid: str, generation: int, body: dict,
            dfs: dict | None):
        # engine_uuid (an incarnation id) rather than (index, shard):
        # delete+recreate of the same index restarts generations, and a
        # name-keyed entry could otherwise serve the OLD index's results
        return (engine_uuid, generation,
                json.dumps(body, sort_keys=True),
                json.dumps(dfs, sort_keys=True) if dfs else None)

    def get(self, key) -> dict | None:
        with self._lock:
            out = self._lru.get(key)
            bucket = self._uuid_stats(key[0])
            if out is not None:
                self._lru.move_to_end(key)
                self.stats["hits"] += 1
                bucket["hits"] += 1
            else:
                self.stats["misses"] += 1
                bucket["misses"] += 1
            return out

    @staticmethod
    def _approx_bytes(key, payload: dict) -> int:
        """Best-effort resident size of one entry (the payloads are the
        wire-safe size-0 shard responses, so json measures them)."""
        try:
            return len(key[2]) + len(json.dumps(payload, default=str))
        except (TypeError, ValueError):
            return 1024

    def put(self, key, payload: dict) -> None:
        with self._lock:
            self._lru[key] = payload
            self._lru.move_to_end(key)
            self._sizes[key] = self._approx_bytes(key, payload)
            while len(self._lru) > self.cap:
                old_key, _ = self._lru.popitem(last=False)
                self._sizes.pop(old_key, None)
                self.stats["evictions"] += 1
                self._uuid_stats(old_key[0])["evictions"] += 1

    def clear(self, engine_uuids: set | None = None) -> None:
        """Drop everything, or only entries belonging to the given engine
        incarnations (index-scoped /_cache/clear). Cumulative counters
        survive — reference cache stats never reset on a clear."""
        with self._lock:
            if engine_uuids is None:
                self._lru.clear()
                self._sizes.clear()
            else:
                for key in [k for k in self._lru
                            if k[0] in engine_uuids]:
                    del self._lru[key]
                    self._sizes.pop(key, None)

    def stats_dict(self) -> dict:
        with self._lock:
            return {**self.stats, "entries": len(self._lru),
                    "memory_size_in_bytes": sum(self._sizes.values())}

    def stats_for(self, engine_uuids) -> dict:
        """Per-index request_cache section (reference shape): cumulative
        hit/miss/eviction counts plus the resident bytes of the given
        engine incarnations' live entries."""
        uuids = set(engine_uuids)
        with self._lock:
            out = {"hit_count": 0, "miss_count": 0, "evictions": 0,
                   "memory_size_in_bytes": 0}
            for uuid in uuids:
                b = self._by_uuid.get(uuid)
                if b is not None:
                    out["hit_count"] += b["hits"]
                    out["miss_count"] += b["misses"]
                    out["evictions"] += b["evictions"]
            out["memory_size_in_bytes"] = sum(
                n for k, n in self._sizes.items() if k[0] in uuids)
            return out


# One-shot fielddata reservation for a collective-plane mesh pack:
# released exactly once — by supersession (refresh rebuild), cache
# eviction, index close, or any backing engine's close listener —
# whichever comes first. (The per-segment device BLOCKS beneath the pack
# carry their own OneShotCharges inside mesh_engine's block cache.)
from elasticsearch_tpu.common.breaker import OneShotCharge as _PackCharge


class SearchActions:
    QUERY_FETCH = "indices:data/read/search[phase/query+fetch]"
    QUERY_ID = "indices:data/read/search[phase/query]"
    FETCH_ID = "indices:data/read/search[phase/fetch/id]"
    FREE_CONTEXT = "indices:data/read/search[free_context]"
    MSEARCH_SHARD = "indices:data/read/msearch[shard]"
    DFS = "indices:data/read/search[phase/dfs]"
    FIELD_STATS = "indices:data/read/field_stats[s]"

    # fetch amplification break-even: below this window the extra fetch
    # round trip of query_then_fetch costs more than the surplus _source
    # bytes query_and_fetch ships (see `search` docstring)
    QTF_WINDOW_THRESHOLD = 100

    #: coordinator-side wrapper task one hedged copy attempt runs under:
    #: cancelling THIS task (ban machinery) cancels exactly that
    #: attempt's shard work, nothing else in the fan-out
    HEDGE_ACTION = "indices:data/read/search[hedge]"

    #: extra seconds the deadline-bounded collector waits past the
    #: request deadline before abandoning a shard group: shards received
    #: the REMAINING budget at dispatch, so in-budget partials need only
    #: transit time to land — anything slower is the tail the partial
    #: response exists to cut off
    PARTIAL_GRACE_S = 0.1

    #: stall ceiling on coordinator shard-future waits with NO request
    #: deadline: a wedged shard (hung device dispatch) becomes a typed
    #: shard failure after this long, never a hung request — the
    #: deadline-less analog of the PARTIAL_GRACE_S bounded collect
    SHARD_WAIT_CEILING_S = 60.0

    def __init__(self, node):
        self.node = node
        self._pool = ThreadPoolExecutor(max_workers=16,
                                        thread_name_prefix="search")
        # test seam: hold shard execution at a cancellation checkpoint
        # for this many seconds (chaos tests keep a shard task RUNNING
        # while they cancel it / kill its coordinator)
        self.shard_query_delay: float | None = None
        self._rotation = itertools.count()
        # multi-index collective-plane packs: names-tuple → (gens,
        # MeshEngineSearcher, breaker bytes, index identity); single-index
        # packs cache on the index object itself (and die with it)
        from collections import OrderedDict
        self._mesh_multi: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._mesh_multi_lock = threading.Lock()
        # double-buffered plane refresh: engine reader swaps schedule the
        # next-generation data-layer pack here (coalesced per index), so
        # the incremental compose runs OFF the query hot path and the
        # first search after a refresh finds the pack already swapped in
        # (or waits only for the in-flight build, never starts it cold)
        self._plane_warm_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="plane-warm")
        self._plane_warm_pending: set[str] = set()
        self._plane_warm_lock = threading.Lock()
        self._contexts: dict[str, _ScrollContext] = {}
        self._ctx_ids = itertools.count(1)
        # data-node side scroll pins: (ctx_uid, index, shard) →
        # (SearcherView, DeviceReader, expires_at_monotonic)
        self._pinned: dict[tuple, tuple] = {}
        self._lock = threading.Lock()
        node.transport_service.register_request_handler(
            self.QUERY_FETCH, self._handle_shard_query, executor="search",
            sync=True)
        node.transport_service.register_request_handler(
            self.MSEARCH_SHARD, self._handle_shard_msearch,
            executor="search", sync=True)
        node.transport_service.register_request_handler(
            self.DFS, self._handle_shard_dfs, executor="search", sync=True)
        node.transport_service.register_request_handler(
            self.QUERY_ID, self._handle_shard_query_only,
            executor="search", sync=True)
        node.transport_service.register_request_handler(
            self.FETCH_ID, self._handle_shard_fetch,
            executor="search", sync=True)
        node.transport_service.register_request_handler(
            self.FREE_CONTEXT, self._handle_free_context,
            executor="same", sync=True)
        self.request_cache = ShardRequestCache(
            cap=int(node.settings.get("indices.requests.cache.entries", 256))
            if hasattr(node, "settings") else 256)
        # plane-breaker knobs (per-node — one process, one device): an
        # explicit setting reconfigures the jit_exec module breaker
        if hasattr(node, "settings"):
            from elasticsearch_tpu.search import jit_exec
            jit_exec.plane_breaker.configure(
                threshold=node.settings.get(
                    "search.plane_breaker.threshold"),
                backoff_s=node.settings.get(
                    "search.plane_breaker.backoff_seconds"),
                max_backoff_s=node.settings.get(
                    "search.plane_breaker.max_backoff_seconds"))
        # ---- tail-tolerance layer (ARS + hedging + partial results) ----
        # adaptive replica selection: per-node EWMAs + C3 ranks feeding
        # _copy_try_order; hedged requests: per-shard-group latency
        # histograms + the hedge counters (replica_stats.py)
        get = node.settings.get if hasattr(node, "settings") \
            else (lambda *a: None)

        def _flag(key: str, default: bool) -> bool:
            val = get(key)
            return default if val is None \
                else str(val).lower() not in ("false", "0")
        self.ars_enabled = _flag("search.ars.enabled", True)
        self.replica_stats = ReplicaStatsTable(
            alpha=float(get("search.ars.alpha") or 0.3))
        self.hedge_enabled = _flag("search.hedge.enabled", True)
        self.hedge_quantile = float(get("search.hedge.quantile") or 0.9)
        self.hedge_floor_ms = float(get("search.hedge.floor_ms") or 50.0)
        self.hedge_ceiling_ms = float(
            get("search.hedge.ceiling_ms") or 1000.0)
        # deadline-bounded partial results: request param
        # allow_partial_search_results overrides this node default
        self.default_allow_partial = _flag(
            "search.default_allow_partial_results", True)
        # ---- continuous-batching scheduler (ROADMAP item 6) ----
        # per-node device feeder: concurrent single-search traffic on
        # the shard path coalesces into the same batched programs the
        # msearch path uses, with one dispatch always in flight
        # (search/scheduler.py; settings search.scheduler.*)
        from elasticsearch_tpu.search.scheduler import (
            ContinuousBatchScheduler, settings_for)
        self.scheduler = ContinuousBatchScheduler(
            node_id=getattr(node, "node_id", None), **settings_for(get))
        # ---- dispatch watchdog (stall tolerance) ----
        # the module singleton guards every registered device wait (one
        # process = one device, the plane_breaker discipline); each node
        # applies its search.watchdog.* settings to it
        from elasticsearch_tpu.search import watchdog as _watchdog
        self.watchdog = _watchdog.dispatch_watchdog
        self.watchdog.configure(**_watchdog.settings_for(get))
        # background pack-build (plane warm) failure tracking: per-index
        # consecutive failures drive the retry backoff and, past
        # PLANE_WARM_MAX_RETRIES, the plane-degraded marking
        self._plane_warm_failures: dict[str, int] = {}
        # dedicated pool for _msearch item fan-out: sharing _pool with the
        # per-shard futures it spawns could deadlock at saturation
        self._msearch_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="msearch")
        node.transport_service.register_request_handler(
            self.FIELD_STATS, self._handle_field_stats, executor="search",
            sync=True)
        # keep-alive reaper: abandoned scroll contexts must not accumulate
        # for the node's lifetime (SearchService keep-alive reaper,
        # core/search/SearchService.java:1113)
        self._closed = False
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True,
                                        name="scroll-reaper")
        self._reaper.start()

    def _submit(self, fn, *args):
        """Fan-out submit that carries the coordinating task across the
        pool boundary, so shard RPCs sent from pool threads stamp the
        parent-task header (TaskManager wiring)."""
        return self._pool.submit(tasks.bind_current(fn), *args)

    def _task_manager(self):
        return getattr(self.node, "task_manager", None)

    @contextlib.contextmanager
    def _coordinating_task(self, action: str, description: str,
                           timeout_ms: float | None = None):
        """Register the coordinator-side task for a client-entry search
        action, make it current for the duration, and wire the request
        `timeout` through the task's deadline. Yields the task (None
        when the node has no TaskManager — standalone unit tests)."""
        tm = self._task_manager()
        if tm is None:
            yield None
            return
        task = tm.register(action, description=description)
        if timeout_ms is not None:
            task.deadline = time.monotonic() + timeout_ms / 1000.0
        try:
            with tasks.use_task(task):
                yield task
        finally:
            tm.unregister(task)

    def _reap_loop(self) -> None:
        while not self._closed:
            time.sleep(5.0)
            if self._closed:
                return
            self.reap_expired()

    def close(self):
        self._closed = True
        self.scheduler.close()
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._msearch_pool.shutdown(wait=False, cancel_futures=True)
        self._plane_warm_pool.shutdown(wait=False, cancel_futures=True)

    # ---- double-buffered plane refresh -------------------------------------

    def schedule_plane_rebuild(self, index_name: str) -> None:
        """Engine reader-swap hook: pipeline the next-generation
        collective-plane pack for `index_name` in the background.
        Coalesced (one queued build per index — a refresh storm folds
        into the next build, which reads the freshest generations) and
        lazy: only indices whose pack a search already created warm, so
        pure-indexing workloads pay nothing. Searches arriving before
        the build finishes wait on the per-index build lock instead of
        starting the compose cold — the refresh-to-first-search latency
        win the incremental data layer exists for."""
        if self._closed:
            return
        index = self.node.indices_service.indices.get(index_name)
        if index is None or "_mesh_cache" not in index.__dict__:
            return
        with self._plane_warm_lock:
            if index_name in self._plane_warm_pending:
                return
            self._plane_warm_pending.add(index_name)
        try:
            self._plane_warm_pool.submit(self._plane_warm, index_name)
        except RuntimeError:                 # pool shut down
            with self._plane_warm_lock:
                self._plane_warm_pending.discard(index_name)

    #: background pack-build hardening: failed warms retry with
    #: exponential backoff; past the retry budget the index is marked
    #: plane-degraded (searches keep serving the previous generation or
    #: the fan-out — never an error) until a build succeeds again
    PLANE_WARM_MAX_RETRIES = 3
    PLANE_WARM_BACKOFF_S = 0.25

    def _plane_warm(self, index_name: str) -> None:
        # the warm pool has no task context — attribute its compiles and
        # uploads to this node explicitly so per-node jit rollups hold
        from elasticsearch_tpu.observability import use_node
        with use_node(self.node.node_id):
            self._plane_warm_inner(index_name)

    def _plane_warm_inner(self, index_name: str) -> None:
        with self._plane_warm_lock:
            self._plane_warm_pending.discard(index_name)
        if self._closed:
            return
        index = self.node.indices_service.indices.get(index_name)
        if index is None:
            return
        if str(index.index_settings.get(
                "index.search.collective_plane", "true")).lower() \
                in ("false", "0"):
            return
        nshards = index.meta.number_of_shards
        if nshards < 2 or set(index.engines) != set(range(nshards)):
            return
        from elasticsearch_tpu.search import jit_exec
        try:
            if not any(e.acquire_searcher().segments
                       for e in index.shard_engines):
                return
            if not jit_exec.plane_breaker.allow():
                return          # unhealthy device: the breaker's probe,
            self._mesh_searcher_for([index])   # not the warm path, decides
        except Exception as e:               # noqa: BLE001 — warm-path
            # the failed build already returned its pack charge
            # (_mesh_build / _mesh_searcher_for release on the way out);
            # record the device error, then retry with backoff so a
            # transient fault doesn't silently kill the coalesced-
            # rebuild path — and degrade (never error) past the budget
            jit_exec.note_device_error(e)
            with self._plane_warm_lock:
                n = self._plane_warm_failures.get(index_name, 0) + 1
                self._plane_warm_failures[index_name] = n
            if n >= self.PLANE_WARM_MAX_RETRIES:
                index.plane_stats["degraded"] = True
                return
            if self._closed:
                return
            timer = threading.Timer(
                self.PLANE_WARM_BACKOFF_S * (2 ** (n - 1)),
                self.schedule_plane_rebuild, args=(index_name,))
            timer.daemon = True
            timer.start()
        else:
            jit_exec.plane_breaker.record_success()
            with self._plane_warm_lock:
                self._plane_warm_failures.pop(index_name, None)
            index.plane_stats.pop("degraded", None)

    # ---- data-node side ----------------------------------------------------

    @staticmethod
    def _apply_budget(req, budget_ms) -> None:
        """Shard-side deadline wiring: the coordinator ships the
        REMAINING time budget (its `timeout` minus wall time already
        spent queueing and fanning out), which tightens both the parsed
        request's timeout and the executing task's deadline — so
        per-shard ``timed_out`` reflects elapsed time on the whole
        request, not a clock restarted per shard."""
        if budget_ms is None:
            return
        budget_ms = max(float(budget_ms), 1.0)
        if req.timeout_ms is None or budget_ms < req.timeout_ms:
            req.timeout_ms = budget_ms
        cur = tasks.current_task()
        if cur is not None:
            dl = time.monotonic() + budget_ms / 1000.0
            cur.deadline = dl if cur.deadline is None \
                else min(cur.deadline, dl)

    def _scheduled_query_phase(self, searcher, req):
        """Shard-side query phase through the continuous-batching
        scheduler: concurrent single-search traffic targeting the same
        (reader, lane, shape) coalesces into ONE batched device program
        — what ``sched_batch_fill.tput`` reads on the benchmark's knn
        cell (PERF.md section 3). Falls back to
        the serial :meth:`ShardSearcher.query_phase` when the request's
        shape is unbatchable, the scheduler declines (ineligible batch,
        device fallback, shutdown), or the plane breaker is open (the
        serial path owns the breaker-gated eager fallback — the
        scheduler never queues toward an unhealthy device). SLO-burn
        sheds raise the typed 429 (SchedulerRejectedError) through to
        the coordinator."""
        sched = self.scheduler
        if sched is None or not sched.enabled:
            return searcher.query_phase(req)
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.search import scheduler as sched_mod
        lane, shape = sched_mod.classify(req, searcher)
        if lane is None or not jit_exec.plane_breaker.allow():
            return searcher.query_phase(req)
        out = sched.execute(
            lane,
            (searcher.ctx.index_name, searcher.shard_id, lane, shape,
             id(searcher.reader)),
            req, searcher.query_phase_batch_launch,
            searcher.query_phase_batch_drain)
        if out is None:
            return searcher.query_phase(req)
        return out

    def _hold_for_test(self) -> None:
        """Cancellation-checkpointed hold (see ``shard_query_delay``)."""
        delay = self.shard_query_delay
        if not delay:
            return
        deadline = time.monotonic() + float(delay)
        while time.monotonic() < deadline:
            tasks.raise_if_cancelled()
            time.sleep(0.005)

    def _handle_shard_query(self, request: dict, source) -> dict:
        return self._execute_shard(request["index"], request["shard"],
                                   request["body"],
                                   doc_slot=request.get("doc_slot"),
                                   dfs=request.get("dfs"),
                                   scroll_pin=request.get("scroll_pin"),
                                   budget_ms=request.get("budget_ms"))

    def _handle_shard_query_only(self, request: dict, source) -> dict:
        return self._execute_shard_query(
            request["index"], request["shard"], request["body"],
            doc_slot=request.get("doc_slot"), dfs=request.get("dfs"),
            pin=request["pin"], budget_ms=request.get("budget_ms"))

    def _shard_traced(self, phase: str, name: str, shard: int, fn):
        """Run one shard-phase callable under a per-shard attribution
        record (slow-log plane fields) and the shard phase's span — whose
        finished subtree, when a trace is active, is attached to the payload
        as ``_profile`` (the coordinator pops it into the response's
        profile section). The payload is shallow-copied before the
        attach so request-cache entries never carry spans."""
        if not obs_trace.active():
            with attribution.collect(admission="fanout"), \
                    obs_trace.span(phase):
                return fn()
        from elasticsearch_tpu.observability import costs as obs_costs
        with attribution.collect(admission="fanout"), \
                obs_costs.collect_programs() as progs, \
                obs_trace.collect_spans() as spans, \
                obs_trace.span(phase, index=name, shard=shard):
            out = fn()
        out = dict(out)
        out["_profile"] = {"index": name, "shard": shard,
                           "node": self.node.node_id,
                           "spans": obs_trace.build_tree(spans),
                           # this shard phase's compiled programs (cost-
                           # observatory keys + measured µs), hottest
                           # first — joins the spans to /_cat/programs
                           "programs": obs_costs.render_rows(progs)}
        return out

    def _attach_ars(self, out: dict, t0: float) -> dict:
        """Piggyback this data node's adaptive-selection signals on the
        shard payload (the reference ships queue/service stats on the
        QuerySearchResult the same way): search-pool queue depth — the
        _cat/thread_pool accounting — plus the measured service time.
        Shallow-copied so request-cache entries never carry a stale
        snapshot."""
        try:
            queue = self.node.thread_pool.executor(
                "search").stats()["queue"]
        except Exception:        # noqa: BLE001 — pool closed/minimal node
            queue = 0
        out = dict(out)
        out["_ars"] = {"queue": queue,
                       "took_ms": (time.perf_counter() - t0) * 1e3}
        return out

    def _execute_shard_query(self, name: str, shard: int, body: dict,
                             doc_slot: int | None, dfs: dict | None,
                             pin: dict, budget_ms=None) -> dict:
        t0 = time.perf_counter()
        return self._attach_ars(self._shard_traced(
            "action.shard_query", name, shard,
            lambda: self._execute_shard_query_inner(
                name, shard, body, doc_slot, dfs, pin, budget_ms)), t0)

    def _execute_shard_query_inner(self, name: str, shard: int,
                                   body: dict, doc_slot: int | None,
                                   dfs: dict | None, pin: dict,
                                   budget_ms=None) -> dict:
        """Query phase only (QueryPhase.execute without fetch): rank this
        shard's top from+size and return compact hit DESCRIPTORS — ids,
        scores, sort keys — never `_source`. The reader pins under the
        request's context uid so the fetch round sees the same
        point-in-time (the reference holds the docs in the shard's search
        context between phases; ids crossing the wire + a pinned reader
        give the same contract)."""
        t0 = time.perf_counter()
        svc = self.node.indices_service.index(name)
        engine = svc.engine(shard)
        reader = self._pinned_reader(pin, name, shard, engine)
        breaker = None
        if svc.breaker_service is not None:
            breaker = svc.breaker_service.breaker("request")
            est = max(reader.num_docs, 1) * 16
            breaker.add_estimate(est, f"search [{name}][{shard}]")
        try:
            from elasticsearch_tpu.search.dfs import to_execution_stats
            searcher = ShardSearcher(shard, reader, svc.mapper_service,
                                     index_name=name, doc_slot=doc_slot,
                                     dfs_stats=to_execution_stats(dfs),
                                     version_fn=engine.doc_version)
            req = parse_search_request(body)
            self._apply_budget(req, budget_ms)
            self._hold_for_test()
            result = self._scheduled_query_phase(searcher, req)
            q_ms = (time.perf_counter() - t0) * 1000.0
            svc.note_search(body.get("stats"), q_ms)
            k = min(len(result.doc_ids), req.from_ + req.size)
            out = {"total": result.total,
                   "max_score": (float(result.max_score)
                                 if result.max_score is not None else None),
                   "docs": [int(d) for d in result.doc_ids[:k]],
                   "scores": [float(s) for s in result.scores[:k]],
                   "sort": wire_safe(result.sort_values[:k])
                   if result.sort_values is not None else None,
                   "aggs": wire_safe(result.agg_partials),
                   "terminated_early": result.terminated_early,
                   "timed_out": result.timed_out}
            if req.suggest:
                from elasticsearch_tpu.search.suggest import ShardSuggester
                sg = ShardSuggester(reader, svc.mapper_service)
                out["suggest"] = {spec.name: sg.collect(spec)
                                  for spec in req.suggest}
        finally:
            if breaker is not None:
                breaker.release(est)
        if svc.search_slow_log.thresholds:
            svc.search_slow_log.maybe_log(
                time.perf_counter() - t0,
                f"shard[{shard}], source[{json.dumps(body)[:512]}]")
        return out

    def _handle_shard_fetch(self, request: dict, source) -> dict:
        return self._shard_traced(
            "action.shard_fetch", request["index"], request["shard"],
            lambda: self._handle_shard_fetch_inner(request))

    def _handle_shard_fetch_inner(self, request: dict) -> dict:
        """Fetch phase for coordinator-chosen winners (fillDocIdsToLoad →
        the second fan-out, TransportSearchQueryThenFetchAction.java:
        89-150): build full hits for exactly the doc ids that made the
        global page, against the reader pinned by the query round."""
        from elasticsearch_tpu.search.phase import ShardQueryResult
        name, shard = request["index"], request["shard"]
        svc = self.node.indices_service.index(name)
        engine = svc.engine(shard)
        reader = self._pinned_reader({**request["pin"], "require": True},
                                     name, shard, engine)
        req = parse_search_request(request["body"])
        docs = np.asarray(request["docs"], np.int32)
        result = ShardQueryResult(
            shard, 0, None, docs,
            np.asarray(request["scores"], np.float32),
            request.get("sort"), {}, reader)
        searcher = ShardSearcher(shard, reader, svc.mapper_service,
                                 index_name=name,
                                 doc_slot=request.get("doc_slot"),
                                 version_fn=engine.doc_version)
        return {"hits": searcher.fetch_phase(req, result, name,
                                             list(range(len(docs))))}

    def _handle_free_context(self, request: dict, source) -> dict:
        """Release reader pins for a finished context (the reference's
        free-context round after query_then_fetch / on clear_scroll)."""
        self._drop_pins(request["uid"])
        return {}

    def _free_context(self, uid: str, node_ids) -> None:
        """Fire-and-forget pin release on exactly the nodes that served
        the context (the reference's free-context round)."""
        self._drop_pins(uid)
        state = self.node.cluster_service.state()
        for nid in set(node_ids):
            if nid == self.node.node_id:
                continue
            target = state.node(nid)
            if target is None:
                continue
            try:
                self.node.transport_service.send_request(
                    target, self.FREE_CONTEXT, {"uid": uid}, timeout=5.0)
            except Exception:        # noqa: BLE001 — pins age out anyway
                pass

    def _handle_shard_msearch(self, request: dict, source) -> dict:
        with obs_trace.span("action.shard_msearch"):
            return self._handle_shard_msearch_inner(request)

    def _handle_shard_msearch_inner(self, request: dict) -> dict:
        """Shard-side _msearch: B request bodies against one shard in ONE
        batched device program when they share a plan
        (ShardSearcher.query_phase_batch — the TPU-native multi-search;
        ``match`` queries of unequal lengths do, padded to the batch's
        term bucket), per-request execution otherwise (queries of
        different structure, ineligible requests): the counters
        ``msearch_items_batched`` / ``msearch_items_serial`` say which.
        → {"payloads": [per body]}."""
        name, shard = request["index"], request["shard"]
        bodies = request["bodies"]
        svc = self.node.indices_service.index(name)
        engine = svc.engine(shard)
        reader = device_reader_for(engine)
        searcher = ShardSearcher(shard, reader, svc.mapper_service,
                                 index_name=name,
                                 doc_slot=request.get("doc_slot"),
                                 version_fn=engine.doc_version)
        reqs, errors = [], {}
        for i, body in enumerate(bodies):
            try:
                reqs.append(parse_search_request(body))
            except Exception as e:           # noqa: BLE001 — per-item error
                reqs.append(None)
                errors[i] = str(e)
        valid = [(i, r) for i, r in enumerate(reqs) if r is not None]
        results: dict[int, object] = {}
        try:
            batch = searcher.query_phase_batch([r for _, r in valid]) \
                if valid else []
        except Exception:                    # noqa: BLE001 — isolate items
            batch = None
        from elasticsearch_tpu.search import jit_exec
        jit_exec.note_msearch_items(len(valid), batched=batch is not None)
        if batch is not None:
            for (i, _), res in zip(valid, batch):
                results[i] = res
        else:
            for i, r in valid:
                try:
                    results[i] = searcher.query_phase(r)
                except Exception as e:       # noqa: BLE001 — per-item error
                    errors[i] = str(e)       # others must still succeed
        payloads = []
        for i, body in enumerate(bodies):
            if i in errors:
                payloads.append({"error": errors[i]})
                continue
            req, result = reqs[i], results[i]
            try:
                k = min(len(result.doc_ids), req.from_ + req.size)
                hits = searcher.fetch_phase(req, result, name,
                                            list(range(k)))
                out = {
                    "total": result.total,
                    "max_score": (float(result.max_score)
                                  if result.max_score is not None else None),
                    "hits": hits, "aggs": wire_safe(result.agg_partials),
                    "terminated_early": result.terminated_early,
                    "timed_out": result.timed_out}
                if req.suggest:
                    from elasticsearch_tpu.search.suggest import \
                        ShardSuggester
                    sg = ShardSuggester(reader, svc.mapper_service)
                    out["suggest"] = {spec.name: sg.collect(spec)
                                      for spec in req.suggest}
                payloads.append(out)
            except Exception as e:           # noqa: BLE001 — per-item error
                payloads.append({"error": str(e)})
        return {"payloads": payloads}

    def _handle_shard_dfs(self, request: dict, source) -> dict:
        """DFS phase (DfsPhase.execute analog): term/collection statistics
        of this shard for the query's terms."""
        from elasticsearch_tpu.search.dfs import shard_dfs
        from elasticsearch_tpu.search.query_dsl import parse_query
        name, shard = request["index"], request["shard"]
        svc = self.node.indices_service.index(name)
        reader = device_reader_for(svc.engine(shard))
        query = parse_query((request.get("body") or {}).get("query"))
        return shard_dfs(reader, svc.mapper_service, query)

    def _execute_shard(self, name: str, shard: int, body: dict,
                       doc_slot: int | None = None,
                       dfs: dict | None = None,
                       scroll_pin: dict | None = None,
                       budget_ms=None) -> dict:
        t0 = time.perf_counter()
        return self._attach_ars(self._shard_traced(
            "action.shard", name, shard,
            lambda: self._execute_shard_inner(
                name, shard, body, doc_slot=doc_slot, dfs=dfs,
                scroll_pin=scroll_pin, budget_ms=budget_ms)), t0)

    def _execute_shard_inner(self, name: str, shard: int, body: dict,
                             doc_slot: int | None = None,
                             dfs: dict | None = None,
                             scroll_pin: dict | None = None,
                             budget_ms=None) -> dict:
        t0 = time.perf_counter()
        svc = self.node.indices_service.index(name)
        engine = svc.engine(shard)
        if scroll_pin is not None:
            reader = self._pinned_reader(scroll_pin, name, shard, engine)
        else:
            reader = device_reader_for(engine)
        # shard request cache: hits-free (size 0) requests keyed by reader
        # generation + request bytes (IndicesRequestCache.java:78); gated
        # by index.requests.cache.enable
        cache_key = None
        if scroll_pin is None and body.get("size") == 0 and \
                str(svc.index_settings.get(
                "index.requests.cache.enable", "true")).lower() != "false":
            cache_key = self.request_cache.key(engine.engine_uuid,
                                               reader.generation, body, dfs)
            cached = self.request_cache.get(cache_key)
            if cached is not None:
                # a cache hit is still a served query (ShardSearchStats
                # increments outside the request cache)
                svc.note_search(body.get("stats"),
                                (time.perf_counter() - t0) * 1000.0)
                return cached
        # per-request scratch accounting (request breaker): score + mask
        # arrays over every doc of the shard
        breaker = None
        if svc.breaker_service is not None:
            breaker = svc.breaker_service.breaker("request")
            est = max(reader.num_docs, 1) * 16
            breaker.add_estimate(est, f"search [{name}][{shard}]")
        try:
            from elasticsearch_tpu.search.dfs import to_execution_stats
            searcher = ShardSearcher(shard, reader, svc.mapper_service,
                                     index_name=name, doc_slot=doc_slot,
                                     dfs_stats=to_execution_stats(dfs),
                                     version_fn=engine.doc_version)
            req = parse_search_request(body)
            self._apply_budget(req, budget_ms)
            self._hold_for_test()
            result = self._scheduled_query_phase(searcher, req)
            q_ms = (time.perf_counter() - t0) * 1000.0
            k = min(len(result.doc_ids), req.from_ + req.size)
            hits = searcher.fetch_phase(req, result, name, list(range(k)))
            svc.note_search(body.get("stats"), q_ms,
                            (time.perf_counter() - t0) * 1000.0 - q_ms)
            out = {"total": result.total,
                   "max_score": (float(result.max_score)
                                 if result.max_score is not None else None),
                   "hits": hits,
                   "aggs": wire_safe(result.agg_partials),
                   "terminated_early": result.terminated_early,
                   "timed_out": result.timed_out}
            if req.suggest:
                from elasticsearch_tpu.search.suggest import ShardSuggester
                sg = ShardSuggester(reader, svc.mapper_service)
                out["suggest"] = {spec.name: sg.collect(spec)
                                  for spec in req.suggest}
        finally:
            if breaker is not None:
                breaker.release(est)
        if svc.search_slow_log.thresholds:       # skip json.dumps when off
            svc.search_slow_log.maybe_log(
                time.perf_counter() - t0,
                f"shard[{shard}], source[{json.dumps(body)[:512]}]")
        if cache_key is not None and not out.get("timed_out") \
                and not out.get("terminated_early"):
            # partial results must not pin themselves until the next
            # refresh (the reference cache refuses timed-out entries too)
            self.request_cache.put(cache_key, out)
        return out

    # ---- coordinator -------------------------------------------------------

    def _shard_groups(self, state, names: list[str],
                      routing: str | None = None,
                      preference: str | None = None):
        """→ [(index, shard, [copies in try-order])] — active copies only,
        local first, then rotated (preference/rotation,
        performFirstPhase :156). `routing` (comma-separated keys)
        restricts the fan-out to the shards those keys hash to
        (OperationRouting.searchShards with a routing set); `preference`
        selects/orders the copies per the reference's preference grammar
        (_primary/_primary_first/_local/_only_node/_prefer_node/_shards
        and custom sticky strings)."""
        from elasticsearch_tpu.cluster.routing import OperationRouting
        rot = next(self._rotation)
        pref = preference
        shard_filter: set[int] | None = None
        if pref and pref.startswith("_shards:"):
            # 2.x syntax: _shards:0,2[;<nested-preference>]
            spec, _, nested = pref[len("_shards:"):].partition(";")
            try:
                shard_filter = {int(s) for s in spec.split(",")
                                if s.strip()}
            except ValueError:
                from elasticsearch_tpu.common.errors import (
                    IllegalArgumentError)
                raise IllegalArgumentError(
                    f"invalid _shards preference [{preference}]") from None
            pref = nested or None
        groups = []
        for name in names:
            meta = state.indices[name]
            sids = OperationRouting.search_shards(
                meta.number_of_shards, routing=routing)
            for sid in sids:
                if shard_filter is not None and sid not in shard_filter:
                    continue
                copies = [c for c in
                          state.routing_table.shard_copies(name, sid)
                          if c.active]
                # a preference that excludes every copy still keeps the
                # group: the fan-out records a shard FAILURE for it (the
                # reference raises rather than silently shrinking the
                # result set)
                groups.append((name, sid,
                               self._copy_try_order(copies, pref, rot)))
        return groups

    def _copy_try_order(self, copies: list, pref: str | None, rot: int):
        """Adaptive replica selection: the static preference grammar
        still wins when the caller pinned placement (an explicit
        preference IS an ordering instruction), but the default
        try-order is re-ranked by each copy's observed health — C3
        score ascending over the ReplicaStatsTable's per-node EWMAs,
        queue depth and outstanding count — instead of blind rotation.
        The rank sort is stable, so unobserved/healthy-equal copies
        keep the local-first rotated baseline."""
        ordered = self._order_copies(copies, pref, rot)
        if pref is not None or not self.ars_enabled or len(ordered) < 2:
            return ordered
        return self.replica_stats.order(ordered)

    def _order_copies(self, copies: list, pref: str | None, rot: int):
        """Copy try-order under a preference (OperationRouting's
        preference-aware selection, reference :67-71)."""
        local_id = self.node.node_id
        if pref is None or pref == "_local":
            # default: local copy first, then rotate the rest
            local = [c for c in copies if c.node_id == local_id]
            rest = [c for c in copies if c.node_id != local_id]
            if rest:
                k = rot % len(rest)
                rest = rest[k:] + rest[:k]
            return local + rest
        if pref == "_primary":
            return [c for c in copies if c.primary]
        if pref == "_primary_first":
            return [c for c in copies if c.primary] + \
                [c for c in copies if not c.primary]
        if pref.startswith("_only_node:"):
            node_id = pref.split(":", 1)[1]
            return [c for c in copies if c.node_id == node_id]
        if pref.startswith("_prefer_node:"):
            node_id = pref.split(":", 1)[1]
            return [c for c in copies if c.node_id == node_id] + \
                [c for c in copies if c.node_id != node_id]
        # custom string: deterministic sticky rotation — the same
        # preference value always lands on the same copy, on every
        # coordinating node (murmur, NOT Python's per-process hash;
        # Python's % is already non-negative for a positive modulus)
        if copies:
            from elasticsearch_tpu.utils.hashing import murmur3_hash32
            k = murmur3_hash32(str(pref).encode("utf-8")) % len(copies)
            return copies[k:] + copies[:k]
        return []

    def _try_shard(self, state, name: str, sid: int, copies: list,
                   body: dict, doc_slot: int | None = None,
                   dfs: dict | None = None,
                   scroll_pin: dict | None = None,
                   qtf_pin: dict | None = None,
                   budget_deadline: float | None = None,
                   allow_hedge: bool = True):
        """→ ("ok", payload, node_id) or ("fail", reason-dict, None).
        Walks the copy list (shard-failover retry,
        TransportSearchTypeAction.java:205-247). With `qtf_pin`, runs the
        query-ONLY phase (descriptors, reader pinned) instead of
        query+fetch; the returned node_id tells the coordinator where the
        pin — and thus the fetch round — lives. ``budget_deadline`` is
        the request's absolute perf_counter deadline: EACH attempt
        receives only the milliseconds still remaining when IT launches
        (a retried copy must not restart the budget), so per-shard
        ``timed_out`` reflects total elapsed time.

        Single-round requests with ≥2 copies ride the HEDGED path
        (tail tolerance): pinned contexts stay sequential — a hedge
        would pin readers on the losing node the fetch round never
        frees."""
        if (self.hedge_enabled and allow_hedge and len(copies) > 1
                and scroll_pin is None and qtf_pin is None):
            return self._try_shard_hedged(state, name, sid, copies, body,
                                          doc_slot, dfs, budget_deadline)
        return self._try_shard_seq(state, name, sid, copies, body,
                                   doc_slot, dfs, scroll_pin, qtf_pin,
                                   budget_deadline)

    def _remaining_budget_ms(self, budget_deadline: float | None):
        """Milliseconds left on the request's absolute deadline at THIS
        instant — what a (re)launched copy attempt is allowed to spend
        (the 'shards get the REMAINING budget' rule, applied per
        attempt)."""
        if budget_deadline is None:
            return None
        return max((budget_deadline - time.perf_counter()) * 1000.0, 1.0)

    def _launch_copy(self, state, c, name: str, sid: int, body: dict,
                     doc_slot, dfs, scroll_pin, qtf_pin, budget_ms):
        """Launch ONE copy attempt asynchronously → Future resolving to
        the shard payload, or None when the copy's node left the
        cluster state. Local copies still execute ON the bounded search
        pool (the reference dispatches local shard ops to the SEARCH
        threadpool too) so saturation rejects instead of queueing
        unboundedly; a rejection fails over like any shard failure."""
        if c.node_id == self.node.node_id:
            if qtf_pin is not None:
                return self.node.thread_pool.submit(
                    "search", self._execute_shard_query, name, sid,
                    body, doc_slot, dfs, qtf_pin, budget_ms)
            return self.node.thread_pool.submit(
                "search", self._execute_shard, name, sid, body,
                doc_slot=doc_slot, dfs=dfs, scroll_pin=scroll_pin,
                budget_ms=budget_ms)
        target = state.node(c.node_id)
        if target is None:
            return None
        if qtf_pin is not None:
            action = self.QUERY_ID
            request = {"index": name, "shard": sid, "body": body,
                       "doc_slot": doc_slot, "dfs": dfs,
                       "pin": qtf_pin, "budget_ms": budget_ms}
        else:
            action = self.QUERY_FETCH
            request = {"index": name, "shard": sid, "body": body,
                       "doc_slot": doc_slot, "dfs": dfs,
                       "scroll_pin": scroll_pin, "budget_ms": budget_ms}
        return self.node.transport_service.send_request(
            target, action, request, timeout=30.0)

    def _note_copy_response(self, c, name: str, sid: int, t_att: float,
                            payload: dict) -> dict:
        """Feed one consumed copy response into the adaptive-selection
        table: observed response time, plus the piggybacked ``_ars``
        service-time/queue-depth block (popped — it must not leak into
        the merged response), and the shard group's latency histogram
        the hedge delay reads."""
        resp_ms = (time.perf_counter() - t_att) * 1e3
        ars = payload.pop("_ars", None) if isinstance(payload, dict) \
            else None
        self.replica_stats.observe(
            c.node_id, resp_ms,
            service_ms=(ars or {}).get("took_ms"),
            queue=(ars or {}).get("queue"))
        self.replica_stats.observe_group((name, sid), resp_ms)
        return payload

    @staticmethod
    def _shard_failure(name: str, sid: int, last: Exception | None) -> dict:
        fail = {"shard": sid, "index": name,
                "reason": {"type": "shard_search_failure",
                           "reason": str(last) if last
                           else "no active copy"}}
        if isinstance(last, ElasticsearchTpuError):
            fail["reason"] = last.to_xcontent()
            fail["status"] = last.status
        return fail

    def _try_shard_seq(self, state, name: str, sid: int, copies: list,
                       body: dict, doc_slot=None, dfs=None,
                       scroll_pin=None, qtf_pin=None,
                       budget_deadline: float | None = None,
                       last: Exception | None = None):
        """Sequential next-copy failover (the pre-hedging model, and the
        hedged path's tail for copies beyond the first two)."""
        from elasticsearch_tpu.action.replication import unwrap_remote
        from elasticsearch_tpu.common.errors import (
            IllegalArgumentError, MapperParsingError, QueryParsingError)
        rs = self.replica_stats
        for c in copies:
            # per-copy retry budget: remaining time at THIS attempt's
            # launch, never the original full budget
            budget_ms = self._remaining_budget_ms(budget_deadline)
            rs.begin(c.node_id)
            t_att = time.perf_counter()
            try:
                fut = self._launch_copy(state, c, name, sid, body,
                                        doc_slot, dfs, scroll_pin,
                                        qtf_pin, budget_ms)
                if fut is None:
                    continue
                try:
                    payload = fut.result(35.0)
                except Exception:
                    fut.cancel()     # don't leave abandoned work queued
                    raise
                return "ok", self._note_copy_response(
                    c, name, sid, t_att, payload), c.node_id
            except Exception as e:               # noqa: BLE001 — classify
                e = unwrap_remote(e)
                if isinstance(e, TaskCancelledError):
                    # a cancelled shard task must NOT fail over — re-running
                    # a shed query on the next copy defeats the cancel; the
                    # shard reports task_cancelled and the response stays
                    # partial
                    last = e
                    break
                # Deterministic request errors fail the same way on every
                # copy — abort the whole search with the real status.
                # Anything else (engine closed mid-relocation, node gone,
                # state lag) fails over to the next copy.
                if isinstance(e, (QueryParsingError, IllegalArgumentError,
                                  MapperParsingError)):
                    raise e from None
                last = e
            finally:
                rs.end(c.node_id)
        return "fail", self._shard_failure(name, sid, last), None

    # ---- hedged shard requests (tail tolerance) ----------------------------

    def _hedge_attempt(self, state, c, name: str, sid: int, body: dict,
                       doc_slot, dfs, budget_deadline):
        """Launch one hedged copy attempt under its OWN wrapper task —
        a child of the coordinating task, so the remote shard task
        parents on it and a ban on the wrapper id cancels exactly this
        attempt's work (the PR 2 machinery, scoped to one copy).
        → (future, wrapper-task-or-None); raises on synchronous launch
        failure (pool rejection / serialization)."""
        budget_ms = self._remaining_budget_ms(budget_deadline)
        tm = self._task_manager()
        task = None
        if tm is not None:
            task = tm.register(
                self.HEDGE_ACTION,
                description=f"[{name}][{sid}] copy[{c.node_id}]")
        ctx = tasks.use_task(task) if task is not None \
            else contextlib.nullcontext()
        try:
            with ctx:
                fut = self._launch_copy(state, c, name, sid, body,
                                        doc_slot, dfs, None, None,
                                        budget_ms)
        except BaseException:
            if tm is not None:
                tm.unregister(task)
            raise
        if fut is None:
            if tm is not None:
                tm.unregister(task)
            raise ElasticsearchTpuError(
                f"node [{c.node_id}] left the cluster")
        return fut, task

    def _cancel_hedge_loser(self, c, fut, task,
                            reason: str = "hedged request lost") -> None:
        """First response won: cancel the losing attempt through the
        task-ban machinery — the wrapper task (and, via the broadcast
        ban on its id, the remote shard task parented on it) cancels,
        the losing shard work aborts at its next cooperative checkpoint
        releasing every breaker byte and closing every span, and the
        ban lifts when the wrapper unregisters (done-callback: transport
        futures always complete — response, timeout or disconnect)."""
        tm = self._task_manager()
        if tm is not None and task is not None:
            tm.cancel(task, reason)
            if tm.ban_broadcaster is not None:
                # remote children (current and in-flight registrations)
                # cancel via the cluster-wide ban on the wrapper id
                task.ban_sent = True     # unregister lifts it
                try:
                    tm.ban_broadcaster(task.task_id, True, reason)
                except Exception:        # noqa: BLE001 — best effort
                    pass

        def _settle(f):
            self.replica_stats.end(c.node_id)
            if tm is not None and task is not None:
                tm.unregister(task)
            if not f.cancelled():
                f.exception()            # consume, never propagate
        fut.add_done_callback(_settle)
        fut.cancel()                     # unstarted local work: drop now

    def _try_shard_hedged(self, state, name: str, sid: int, copies: list,
                          body: dict, doc_slot, dfs,
                          budget_deadline: float | None):
        """Hedged single-round shard execution ("The Tail at Scale"):
        launch the best-ranked copy; if no response lands within the
        shard group's ADAPTIVE hedge delay (latency-histogram
        p-quantile, floor/ceiling bounded), fire ONE backup at the
        next-ranked copy. First response wins; the loser is cancelled
        through the task-ban machinery and its counters reconcile as
        ``hedges_launched == hedges_won + hedges_cancelled +
        in_flight``. Copies beyond the first two remain sequential
        failover via _try_shard_seq."""
        from concurrent.futures import FIRST_COMPLETED
        from concurrent.futures import wait as futures_wait
        from elasticsearch_tpu.action.replication import unwrap_remote
        from elasticsearch_tpu.common.errors import (
            IllegalArgumentError, MapperParsingError, QueryParsingError)
        rs = self.replica_stats
        deterministic = (QueryParsingError, IllegalArgumentError,
                         MapperParsingError)
        primary, backup = copies[0], copies[1]
        delay_s = rs.hedge_delay_ms(
            (name, sid), self.hedge_quantile, self.hedge_floor_ms,
            self.hedge_ceiling_ms) / 1000.0
        rs.begin(primary.node_id)
        t0 = time.perf_counter()
        try:
            fut0, task0 = self._hedge_attempt(
                state, primary, name, sid, body, doc_slot, dfs,
                budget_deadline)
        except Exception as e:               # noqa: BLE001 — classify
            rs.end(primary.node_id)
            e = unwrap_remote(e)
            if isinstance(e, deterministic):
                raise e from None
            return self._try_shard_seq(state, name, sid, copies[1:],
                                       body, doc_slot, dfs, None, None,
                                       budget_deadline, last=e)
        pend: dict = {fut0: (primary, task0, t0)}
        hedged_fut = None
        last: Exception | None = None
        tried = 1          # copies consumed by this hedged round
        # phase 1: give the primary its hedge-delay head start
        done, _ = futures_wait([fut0], timeout=delay_s)
        if not done:
            # the primary blew the hedge delay — that elapsed wait is a
            # FLOOR on its true latency; recording it is how a browned-
            # out (slow, not failed) copy sinks in the ARS ranks even
            # though its response is never consumed
            rs.observe(primary.node_id,
                       (time.perf_counter() - t0) * 1e3)
            rs.note_hedge_launched()
            rs.begin(backup.node_id)
            try:
                fut1, task1 = self._hedge_attempt(
                    state, backup, name, sid, body, doc_slot, dfs,
                    budget_deadline)
                hedged_fut = fut1
                pend[fut1] = (backup, task1, time.perf_counter())
                tried = 2
            except Exception as e:           # noqa: BLE001 — still-born
                rs.end(backup.node_id)
                rs.note_hedge_cancelled()
                tried = 2
                e = unwrap_remote(e)
                if isinstance(e, deterministic):
                    self._cancel_hedge_loser(primary, fut0, task0,
                                             "request aborted")
                    raise e from None
                last = e
        # phase 2: first successful response wins. The wait is SLICED so
        # a cancel of the coordinating request propagates promptly: the
        # local ban recursion cancels the hedge WRAPPER tasks, but the
        # remote shard tasks parent on the wrapper ids — broadcasting
        # the wrapper bans (via _cancel_hedge_loser) is what reaches
        # them, and only this loop knows the wrappers
        cur = tasks.current_task()
        hard_deadline = time.monotonic() + 35.0
        while pend:
            remaining = hard_deadline - time.monotonic()
            if remaining <= 0:
                break
            done, _ = futures_wait(list(pend),
                                   timeout=min(0.1, remaining),
                                   return_when=FIRST_COMPLETED)
            if not done:
                if cur is not None and cur.cancelled:
                    for lf, (lc, ltask, _) in pend.items():
                        if lf is hedged_fut:
                            rs.note_hedge_cancelled()
                        self._cancel_hedge_loser(lc, lf, ltask,
                                                 "request cancelled")
                    pend.clear()
                    last = TaskCancelledError(
                        f"task [{cur.task_id}] was cancelled "
                        f"[{cur.cancel_reason or 'unknown'}]")
                continue
            for f in done:
                c, task, t_att = pend.pop(f)
                tm = self._task_manager()
                try:
                    payload = f.result(0)
                except Exception as e:       # noqa: BLE001 — classify
                    rs.end(c.node_id)
                    if tm is not None and task is not None:
                        tm.unregister(task)
                    if f is hedged_fut:
                        rs.note_hedge_cancelled()   # backup lost by dying
                    e = unwrap_remote(e)
                    if isinstance(e, TaskCancelledError):
                        # the REQUEST was cancelled: stop, stay partial
                        last = e
                        for lf, (lc, ltask, _) in pend.items():
                            self._cancel_hedge_loser(lc, lf, ltask,
                                                     "request cancelled")
                        pend.clear()
                        break
                    if isinstance(e, deterministic):
                        for lf, (lc, ltask, _) in pend.items():
                            self._cancel_hedge_loser(lc, lf, ltask,
                                                     "request aborted")
                        raise e from None
                    last = e
                    continue
                # winner: cancel every still-pending loser
                rs.end(c.node_id)
                if tm is not None and task is not None:
                    tm.unregister(task)
                if f is hedged_fut:
                    rs.note_hedge_won()
                for lf, (lc, ltask, _) in pend.items():
                    if lf is hedged_fut:
                        rs.note_hedge_cancelled()
                    self._cancel_hedge_loser(lc, lf, ltask)
                return "ok", self._note_copy_response(
                    c, name, sid, t_att, payload), c.node_id
        if pend:
            # hard deadline blown with attempts still in flight: abandon
            # them (their transport timeouts settle the callbacks)
            for lf, (lc, ltask, _) in pend.items():
                if lf is hedged_fut:
                    rs.note_hedge_cancelled()
                self._cancel_hedge_loser(lc, lf, ltask,
                                         "shard request timed out")
            if last is None:
                last = ElasticsearchTpuError(
                    f"[{name}][{sid}] no copy responded in time")
        if not isinstance(last, TaskCancelledError) and \
                len(copies) > tried:
            return self._try_shard_seq(state, name, sid, copies[tried:],
                                       body, doc_slot, dfs, None, None,
                                       budget_deadline, last=last)
        return "fail", self._shard_failure(name, sid, last), None

    # accepted search types (ref: SearchType.fromString,
    # core/action/search/SearchType.java:29 — scan/count are deprecated
    # aliases there; query_and_fetch IS this implementation's execution
    # model, see module docstring)
    SEARCH_TYPES = (None, "query_then_fetch", "query_and_fetch",
                    "dfs_query_then_fetch", "dfs_query_and_fetch",
                    "scan", "count")

    def _tracing_on(self, profile: bool) -> bool:
        """Tracer gate: per-request ``profile`` opt-in, or the node-wide
        ``observability.tracer.enable`` setting (default off — the off
        path allocates no span objects)."""
        if profile:
            return True
        settings = getattr(self.node, "settings", None)
        if settings is None:
            return False
        return str(settings.get("observability.tracer.enable",
                                "false")).lower() in ("true", "1")

    def search(self, index_expr: str, body: dict | None = None,
               scroll: str | None = None,
               search_type: str | None = None,
               routing: str | None = None,
               preference: str | None = None) -> dict:
        """Client entry: registers the COORDINATING task (the root of the
        fan-out's task tree), wires the request `timeout` through its
        deadline, and — when the task was cancelled mid-flight — reports
        the partial response with an explicit ``cancelled`` flag.

        ``"profile": true`` in the body turns the span tracer on for
        this request and returns the resulting span trees (coordinator
        phases + per-shard device seams) under ``response["profile"]``.
        The flag is stripped BEFORE the fan-out, so shards execute the
        byte-identical request — profiled hits are guaranteed
        bit-identical to unprofiled ones."""
        body = dict(body or {})
        profile = bool(body.pop("profile", False))
        timeout_ms = None
        raw_timeout = body.get("timeout")
        if raw_timeout is not None:
            try:
                timeout_ms = parse_time_value(raw_timeout,
                                              "timeout") * 1000.0
            except (ValueError, TypeError):
                pass                     # parse_search_request re-raises
        with self._coordinating_task(
                "indices:data/read/search",
                f"indices[{index_expr}], search_type[{search_type or '-'}]"
                f"{', scroll' if scroll else ''}",
                timeout_ms=timeout_ms) as task:
            if task is not None and self._tracing_on(profile):
                # trace id IS the coordinating task id: the span tree
                # and the task tree describe the same request, and
                # GET /_tasks/{id}/trace joins them back up
                from elasticsearch_tpu.observability import \
                    costs as obs_costs
                with obs_trace.trace(task.task_id, self.node.node_id), \
                        obs_trace.profile_sink() as shard_profiles, \
                        obs_costs.collect_programs() as coord_progs, \
                        obs_trace.collect_spans() as coord_spans, \
                        obs_trace.span("action.search", index=index_expr):
                    resp = self._search(index_expr, body, scroll=scroll,
                                        search_type=search_type,
                                        routing=routing,
                                        preference=preference)
                if profile:
                    resp["profile"] = {
                        "trace_id": task.task_id,
                        "coordinator":
                            obs_trace.build_tree(coord_spans),
                        "shards": shard_profiles,
                        # coordinator-dispatched compiled programs (the
                        # collective plane, scheduler batches bound to
                        # this request) with cost-observatory keys +
                        # measured µs; per-shard rows ride each shard's
                        # profile payload
                        "programs": obs_costs.render_rows(coord_progs),
                    }
            else:
                with obs_trace.span("action.search"):
                    resp = self._search(index_expr, body, scroll=scroll,
                                        search_type=search_type,
                                        routing=routing,
                                        preference=preference)
            if task is not None and task.cancelled:
                resp["cancelled"] = True
            return resp

    def _search(self, index_expr: str, body: dict | None = None,
                scroll: str | None = None,
                search_type: str | None = None,
                routing: str | None = None,
                preference: str | None = None) -> dict:
        from elasticsearch_tpu.common.errors import IllegalArgumentError
        if search_type not in self.SEARCH_TYPES:
            raise IllegalArgumentError(
                f"No search type for [{search_type}]")
        if search_type in ("dfs_query_and_fetch",):
            search_type = "dfs_query_then_fetch"
        t0 = time.perf_counter()
        body = dict(body or {})
        # deadline-bounded partial results: stripped BEFORE the fan-out
        # (like "profile") so shards execute the byte-identical request;
        # None defers to search.default_allow_partial_results
        allow_partial = body.pop("allow_partial_search_results", None)
        if search_type == "count":
            # deprecated alias for size=0 (SearchType.COUNT): hit counting
            # + aggregations, no fetch phase
            body["size"] = 0
            search_type = None
        scan = search_type == "scan"
        if scan:
            # SearchType.SCAN (2.x, deprecated in 2.1): unscored index-
            # order sweep behind a scroll cursor. First response carries
            # the total and a scroll id but NO hits; each scroll pulls
            # size docs per shard in _doc order (QueryPhase.java:161-186
            # MinDocQuery continuation)
            if scroll is None:
                raise IllegalArgumentError(
                    "scan search type requires a [scroll] parameter")
            body["sort"] = ["_doc"]
            search_type = None
        dfs_cache: dict | None = {} if scroll is not None else None
        scroll_pin = None
        if scroll is not None:
            body["sort"] = self._scroll_sort(body.get("sort"))
            import uuid as _uuid
            keep = parse_time_value(scroll, "scroll")
            scroll_pin = {"uid": _uuid.uuid4().hex, "keep_s": keep}
        if scan:
            # per-shard page size, like the reference's scan contexts —
            # counting only the ROUTED shards when routing narrows them
            names = self.node.indices_service.resolve_open(index_expr)
            n_shards = len(self._shard_groups(
                self.node.cluster_service.state(), names,
                routing=routing)) or 1
            body["size"] = int(body.get("size", 10)) * n_shards
            probe = dict(body, size=0)
            resp = self._search_once(index_expr, probe, t0,
                                     dfs_cache=dfs_cache,
                                     scroll_pin=scroll_pin,
                                     routing=routing,
                                     preference=preference,
                                     allow_partial=allow_partial)
            # cursor not advanced: the first scroll() call reads page one
            resp["_scroll_id"] = self._open_scroll(
                index_expr, body, scroll, {"hits": {"hits": [{}]}},
                dfs_cache=dfs_cache, ctx_uid=scroll_pin["uid"],
                routing=routing, preference=preference)
            return resp
        resp = self._search_once(index_expr, body, t0,
                                 search_type=search_type,
                                 dfs_cache=dfs_cache,
                                 scroll_pin=scroll_pin,
                                 routing=routing,
                                 preference=preference,
                                 allow_partial=allow_partial)
        if scroll is not None:
            resp["_scroll_id"] = self._open_scroll(index_expr, body, scroll,
                                                   resp,
                                                   search_type=search_type,
                                                   dfs_cache=dfs_cache,
                                                   ctx_uid=scroll_pin["uid"],
                                                   routing=routing,
                                                   preference=preference)
        return resp

    #: search types the plane can serve: dfs types score with global
    #: statistics (the mesh's native mode); the rest score each shard
    #: with its OWN statistics, bit-matching the default fan-out
    PLANE_SEARCH_TYPES = (None, "query_then_fetch", "query_and_fetch",
                          "dfs_query_then_fetch", "dfs_query_and_fetch")

    @staticmethod
    def _note_plane_fallback(indices, reason: str, items: int = 1) -> None:
        """One plane admission attempt of ``items`` search items that
        fell back to the fan-out: label the node-wide reason counter AND
        each target index's admission stats (surfaced in _stats /
        _nodes/stats). Admission declines are NOT compiled-path
        `fallbacks` — the request still runs correctly on the RPC
        fan-out."""
        from elasticsearch_tpu.search import jit_exec
        jit_exec.note_plane_fallback(reason, items)
        for index in indices:
            index.note_plane_fallback(reason)

    def _try_collective_plane(self, names, bodies: list, reqs: list,
                              t0: float,
                              search_type: str | None = None
                              ) -> list[dict] | None:
        """→ full search responses for a BATCH of bodies served by ONE
        mesh program, or None (opted out / shards not all local /
        ineligible shape — the caller proceeds with the ordinary
        fan-out). DEFAULT-ON: eligible searches ride the plane unless
        `index.search.collective_plane: false` opts the index out. The
        merged global top-k of each item splits back by owning (index,
        shard) so the standard winner-only fetch assembles hits;
        _msearch groups ride the same call with B > 1 (the batch IS the
        accelerator's unit of work), and a multi-index request packs
        every index's shard columns into the SAME program — one mesh
        dispatch for an msearch spanning indices."""
        if not names or search_type not in self.PLANE_SEARCH_TYPES:
            return None
        svc = self.node.indices_service
        indices = []
        for nm in names:
            index = svc.indices.get(nm)
            if index is None:
                return None               # an index without local shards
            if str(index.index_settings.get(
                    "index.search.collective_plane", "true")).lower() \
                    in ("false", "0"):
                return None               # explicit opt-out
            indices.append(index)
        has_knn = any(req.knn is not None for req in reqs)
        if has_knn or self._impact_preferred(indices, reqs, search_type):
            # the planner owns the mesh-vs-lane routing that used to be
            # the pairwise impact-preferred / knn-lane decline edges: a
            # knn section ALWAYS routes to the vector lane (the mesh
            # program has no vector/fusion arms — silently dropping the
            # section would return lexical-only hits); an impact-
            # scorable batch on opted-in indices routes to the
            # quantized impact arm unless the cost observatory has
            # MEASURED the mesh strictly cheaper
            from elasticsearch_tpu.search import planner
            if planner.route_plane(indices, not has_knn,
                                   has_knn) is not None:
                return None
        owners = []                       # (index, local shard id)
        for index in indices:
            nshards = index.meta.number_of_shards
            if set(index.engines) != set(range(nshards)):
                self._note_plane_fallback(indices, "not-local",
                                          len(bodies))
                return None               # not every shard lives here
            owners.extend((index, sid) for sid in range(nshards))
        if len(owners) < 2:
            return None                   # single shard: nothing to merge
        if not any(e.acquire_searcher().segments
                   for index in indices for e in index.shard_engines):
            return None                   # nothing indexed yet: the
                                          # fan-out's empty response
        from elasticsearch_tpu.search import jit_exec
        # plane breaker: an unhealthy device costs fan-out latency, not
        # a failed mesh dispatch per query; a half-open probe is admitted
        # here and reports back through record_success/record_error below
        if not jit_exec.plane_breaker.allow():
            jit_exec.note_breaker_skip()
            self._note_plane_fallback(indices, "breaker-open",
                                          len(bodies))
            return None
        for req in reqs:
            if req.suggest or req.rescore:
                self._note_plane_fallback(indices, "ineligible-shape",
                                          len(bodies))
                return None
        if not all(self._plane_precheck(index, reqs)
                   for index in indices):
            # always-ineligible shape (_doc sort, sub-aggs, doc-id score
            # cursors, …): bail BEFORE the mesh build —
            # _mesh_searcher_for stacks every shard column into HBM, a
            # cost the RPC fallback should not pay per refresh generation
            self._note_plane_fallback(indices, "ineligible-shape",
                                          len(bodies))
            return None
        from elasticsearch_tpu.search.controller import merge_responses
        from elasticsearch_tpu.search.phase import (ShardQueryResult,
                                                    ShardSearcher)
        tasks.raise_if_cancelled()
        global_stats = search_type in ("dfs_query_then_fetch",
                                       "dfs_query_and_fetch")
        # A refresh between the mesh pack and the fetch readers would
        # make (slot, row) resolution disagree — both are immutable
        # point-in-time snapshots, so a generation comparison decides
        # validity once. On a race, retry ONCE against the fresh
        # snapshot (the pack was already built and breaker-charged;
        # throwing it away for the fan-out wastes that HBM), then yield.
        msearch = outs = searchers = None
        for attempt in (0, 1):
            try:
                msearch = self._mesh_searcher_for(indices)
            except QueryParsingError:     # vector/geo/nested layouts
                self._note_plane_fallback(indices, "ineligible-shape",
                                          len(bodies))
                return None
            except jit_exec.DeviceStallError as e:
                # a watchdog-abandoned wait surfacing through the pack:
                # distinct reason so the lane graph separates wedged
                # hardware from ordinary device faults
                jit_exec.note_fallback(e)
                jit_exec.note_device_error(e)
                self._note_plane_fallback(indices, "device-stall",
                                          len(bodies))
                return None
            except Exception as e:        # noqa: BLE001 — fallback seam
                jit_exec.note_fallback(e)
                jit_exec.note_device_error(e)
                self._note_plane_fallback(indices, "device-error",
                                          len(bodies))
                return None
            if any(r.terminate_after is not None for r in reqs) and \
                    msearch.n_slots > 1:
                # terminate_after over multi-segment shards diverges
                # from the fan-out's segment-prefix semantics — stay
                # exact, let the fan-out serve it
                self._note_plane_fallback(indices, "ineligible-shape",
                                          len(bodies))
                return None
            try:
                outs = msearch.search_batch(list(bodies),
                                            global_stats=global_stats)
            except QueryParsingError as e:
                # the mesh's own bails name the RPC path; anything else
                # is a body that failed the plane's re-parse
                self._note_plane_fallback(
                    indices, "ineligible-shape" if "RPC" in str(e)
                    else "parse-error", len(bodies))
                return None
            except TaskCancelledError:
                raise
            except jit_exec.DeviceStallError as e:
                jit_exec.note_fallback(e)
                jit_exec.note_device_error(e)
                self._note_plane_fallback(indices, "device-stall",
                                          len(bodies))
                return None
            except Exception as e:        # noqa: BLE001 — fallback seam
                jit_exec.note_fallback(e)
                jit_exec.note_device_error(e)
                self._note_plane_fallback(indices, "device-error",
                                          len(bodies))
                return None
            # the fetch side's readers: where the pack's blocks sit on
            # their owning devices, a host-side reader (ids, sources and
            # the row numbering are all the fetch reads) — the resident
            # reader would put a second copy of every column on the
            # default device
            reader_for = host_reader_for if msearch.placed(msearch.mesh) \
                else device_reader_for
            searchers = [
                ShardSearcher(sid, reader_for(index.engines[sid]),
                              index.mapper_service,
                              index_name=index.name,
                              version_fn=index.engines[sid].doc_version)
                for index, sid in owners]
            if all(s.reader.generation == msearch._views[si].generation
                   for si, s in enumerate(searchers)):
                break
            if attempt == 1:              # raced twice: fan-out path
                self._note_plane_fallback(indices, "refresh-race",
                                          len(bodies))
                return None
        index_names = [index.name for index, _ in owners]
        responses = []
        q_ms = (time.perf_counter() - t0) * 1e3
        for _ in bodies:
            obs_hist.observe_lane("plane", q_ms / len(bodies))
        # reader doc base of every (shard, slot): a global plane id maps
        # to its owning shard's reader numbering by arithmetic alone
        reader_bases = np.asarray(
            [[s.reader.segments[j].doc_base
              if j < len(s.reader.segments) else 0
              for j in range(msearch.n_slots)] for s in searchers],
            np.int64)
        slot_bases = np.asarray(msearch.slot_bases, np.int64)
        for body, req, out in zip(bodies, reqs, outs):
            sort_vals = out.get("sort_values")
            with obs_trace.span("plane.split"):
                # the merged global top-k back to its owning shards, in
                # rank order within each (the winner-only fetch's input)
                g = np.asarray(out["doc_ids"], np.int64)
                scores = np.asarray(out["scores"], np.float32)
                owner, local = np.divmod(g, msearch.shard_stride)
                slot = np.searchsorted(slot_bases, local,
                                       side="right") - 1
                rdocs = reader_bases[owner, slot] + local \
                    - slot_bases[slot]
                results = []
                ta = req.terminate_after
                for si, s in enumerate(searchers):
                    mine = np.flatnonzero(owner == si)
                    # real per-shard totals from the program's
                    # all_gather count lane; terminate_after caps them
                    # like the fan-out's per-shard collection cap
                    raw_total = int(out["shard_totals"][si])
                    results.append(ShardQueryResult(
                        si,
                        raw_total if ta is None else min(raw_total, ta),
                        float(scores[mine].max()) if len(mine) else None,
                        rdocs[mine].astype(np.int32), scores[mine],
                        [sort_vals[pos] for pos in mine]
                        if sort_vals is not None else None,
                        {}, s.reader))
                    if ta is not None and raw_total >= ta:
                        results[-1].terminated_early = True
            resp = merge_responses(index_names, req, results, searchers,
                                   (time.perf_counter() - t0) * 1e3, None)
            mesh_aggs = out.get("aggregations")
            if req.aggs and mesh_aggs is not None:
                resp["aggregations"] = mesh_aggs
            # elapsed-time truth: the request `timeout` and the task
            # deadline (PR-2 wiring) both bound the plane's one dispatch
            if req.timeout_ms is not None and \
                    (time.perf_counter() - t0) * 1e3 > req.timeout_ms:
                resp["timed_out"] = True
            cur = tasks.current_task()
            if cur is not None and cur.deadline is not None and \
                    time.monotonic() > cur.deadline:
                resp["timed_out"] = True
            responses.append(resp)
            # operators watch _stats/slow logs — the plane must feed
            # them like the fan-out does (one note per request per
            # index; per-shard granularity does not exist in a
            # one-program execution)
            for index in indices:
                index.note_search(body.get("stats"), q_ms / len(bodies))
                if index.search_slow_log.thresholds:
                    index.search_slow_log.maybe_log(
                        q_ms / 1e3 / len(bodies),
                        f"collective-plane, source"
                        f"[{json.dumps(body)[:512]}]")
        # a served plane batch is the breaker's success signal (closes a
        # half-open probe) and clears any plane-degraded marking left by
        # failed background builds
        jit_exec.plane_breaker.record_success()
        with self._plane_warm_lock:
            for index in indices:
                self._plane_warm_failures.pop(index.name, None)
        for index in indices:
            index.plane_stats.pop("degraded", None)
            index.note_plane_served(len(bodies))
        jit_exec.note_plane_served(len(bodies))
        return responses

    @staticmethod
    def _impact_preferred(indices, reqs: list, search_type) -> bool:
        """Should this batch leave the mesh to the impact lane? Only
        when every index opted in (`index.search.impact_plane`), the
        search type is a plain (non-DFS) one — impacts bake shard-local
        idf — and every body resolves to an impact-scorable shape
        against every index's mappings (the same execute.impact_terms
        screen the shard-side admission applies)."""
        from elasticsearch_tpu.search import jit_exec
        from elasticsearch_tpu.search.execute import impact_terms
        from elasticsearch_tpu.search.phase import _is_score_order
        if search_type in ("dfs_query_then_fetch", "dfs_query_and_fetch"):
            return False
        cfgs = [jit_exec.impact_plane_config(index.name)
                for index in indices]
        if not all(cfgs):
            return False
        for req in reqs:
            if (req.aggs or not _is_score_order(req.sort)
                    or req.post_filter is not None
                    or req.min_score is not None or req.suggest
                    or req.terminate_after is not None
                    or req.timeout_ms is not None or req.rescore
                    or req.explain or req.knn is not None):
                return False
            if req.search_after is not None and \
                    len(req.search_after) not in (1, 2):
                return False              # only score-order cursors —
                                          # pagination must stay in the
                                          # quantized score domain
            for index, cfg in zip(indices, cfgs):
                if impact_terms(req.query, index.mapper_service,
                                max_terms=cfg.max_terms) is None:
                    return False
        return True

    @staticmethod
    def _plane_precheck(index, reqs: list) -> bool:
        """Mapping-only eligibility screen, run before committing to the
        mesh pack. Conservative: anything it cannot rule out passes
        through to the searcher's precise layout-based validation (which
        raises QueryParsingError → RPC fallback)."""
        from elasticsearch_tpu.parallel.mesh_engine import _MESH_METRICS
        from elasticsearch_tpu.search.phase import _is_score_order
        for req in reqs:
            if _is_score_order(req.sort):
                if req.search_after is not None and (
                        req.sort or len(req.search_after) != 1):
                    # a doc-id cursor component is numbering-relative
                    # (reader-local vs plane-local); an EXPLICIT _score
                    # sort makes the fan-out ignore the cursor — both
                    # stay host-side
                    return False
            else:
                for spec in req.sort:
                    (fname, opts), = spec.items()
                    if fname == "_doc":
                        return False
                    if fname == "_score":
                        continue
                    fm = index.mapper_service.field_mapper(fname)
                    if fm is not None and fm.type == "text":
                        return False      # analyzed text never sorts
                    if fm is not None and \
                            fm.type in ("keyword", "string") and \
                            opts.get("missing", "_last") not in \
                            ("_last", "_first"):
                        return False      # custom missing TERM: host
            for node in req.aggs:
                if node.subs or node.pipelines:
                    return False
                if node.type not in _MESH_METRICS + ("terms",
                                                     "histogram"):
                    return False
                if node.type == "terms":
                    fname = str(node.params.get("field", ""))
                    fm = index.mapper_service.field_mapper(fname)
                    if fm is not None and fm.type == "text":
                        return False      # analyzed-text terms
        return True

    def _plane_mesh_get(self):
        """The mesh every plane pack on this node is built over: the one
        the node setting ``search.mesh`` installed (``Node.serving_mesh``
        — an index of as many shards as its shard axis then sits one
        shard a device), else one shared 1-device mesh. Re-using the
        SAME Mesh object keeps NamedSharding identity stable so
        shape-keyed programs re-dispatch without retracing."""
        mesh = self.node.serving_mesh or getattr(self, "_plane_mesh", None)
        if mesh is None:
            import jax
            from elasticsearch_tpu.parallel import make_mesh
            mesh = make_mesh(dp=1, shard=1, devices=[jax.devices()[0]])
            self._plane_mesh = mesh      # benign race: equal meshes
        return mesh

    @staticmethod
    def _release_pack(entry) -> None:
        """Return a mesh pack's fielddata reservation (idempotent)."""
        if entry is None:
            return
        charge = getattr(entry[1], "_pack_charge", None)
        if charge is not None:
            charge.release()

    def _mesh_build(self, indices: list, cached):
        """DATA layer build: compose every index's shard columns into one
        MeshEngineSearcher → (gens, msearch, breaker bytes), reusing
        `cached` when no engine's reader generation moved. The build is
        INCREMENTAL: per-segment device blocks come from mesh_engine's
        module-level block cache (keyed engine uuid × block uid × slot
        layout), so a refresh re-uploads only new segments' columns and
        changed live masks, and the superseded pack keeps serving until
        this one swaps in (`prev` hands its unchanged stacked operands
        over). The stacked pack trades HBM for dispatch count —
        accounted against the fielddata breaker like every other HBM
        residency (device_reader_for does the same) via a one-shot
        charge that ALSO releases when any backing engine closes (shard
        relocation / teardown must not strand breaker budget); the
        blocks beneath it carry their own exact per-block charges.
        Compiled programs live in mesh_engine's module-level SHAPE-keyed
        cache, so a rebuild here re-dispatches them instead of
        re-tracing."""
        from elasticsearch_tpu.parallel.mesh_engine import (
            MeshEngineSearcher)
        engines, mappers, sinks = [], [], []
        for index in indices:
            sink = index.plane_stats.setdefault("data_layer", {})
            for sid in sorted(index.engines):
                engines.append(index.engines[sid])
                mappers.append(index.mapper_service)
                sinks.append(sink)
        gens = tuple(e.acquire_searcher().generation for e in engines)
        if cached is not None and cached[0] == gens:
            return cached[:3]
        prev = cached[1] if cached is not None else None
        self._release_pack(cached)       # superseded pack returns first
        bs = getattr(self.node, "breaker_service", None)
        new_bytes = sum(seg.memory_bytes() for e in engines
                        for seg in e.acquire_searcher().segments)
        reuse = all(
            str(index.index_settings.get(
                "index.search.plane_incremental", "true")).lower()
            not in ("false", "0") for index in indices)
        # where the stacked operands ARE the owner-placed blocks (one
        # shard a device) the pack holds no bytes of its own: the blocks'
        # own charges are the whole booking
        mesh = self._plane_mesh_get()
        if MeshEngineSearcher.composes_in_place(mesh, len(engines)):
            new_bytes = 0
        charge = _PackCharge(bs, new_bytes if bs is not None else 0,
                             component="pack",
                             index=",".join(index.name
                                            for index in indices))
        charge.charge(f"mesh plane "
                      f"[{','.join(index.name for index in indices)}]")
        try:
            msearch = MeshEngineSearcher(
                mesh, engines,
                indices[0].mapper_service, mapper_services=mappers,
                breaker_service=bs, prev=prev, reuse_blocks=reuse,
                stats_sinks=sinks)
        except BaseException:
            charge.release()
            raise
        msearch._pack_charge = charge
        for e in engines:
            lst = e.__dict__.setdefault("_close_listeners", [])
            # superseded packs' one-shots are spent — prune them so
            # long-lived engines don't accumulate dead callbacks
            lst[:] = [cb for cb in lst
                      if getattr(cb.__self__, "nbytes", 1)]
            lst.append(charge.release)
        return (gens, msearch, charge.nbytes)

    def _mesh_searcher_for(self, indices: list):
        """Per-generation DATA-layer cache (a refresh on any shard
        rebuilds — reader reacquisition semantics), built under a lock
        so concurrent searches cannot double-pack. Single-index packs
        live on the index object (released by IndexService.close);
        multi-index packs live in a small LRU here, validated against
        live index identity (a deleted/recreated index must not serve a
        stale pack) and breaker-released on eviction."""
        import threading
        if len(indices) == 1:
            index = indices[0]
            lock = index.__dict__.setdefault("_mesh_lock",
                                             threading.Lock())
            with lock:
                try:
                    entry = self._mesh_build(
                        indices, index.__dict__.get("_mesh_cache"))
                except BaseException:
                    # the superseded pack's charge was already released
                    # on the way into the failed build — drop the stale
                    # cache entry so a gens-matched retry can't serve a
                    # zero-charged pack (breaker-byte accounting drift)
                    index.__dict__["_mesh_cache"] = None
                    raise
                index.__dict__["_mesh_cache"] = entry
                return entry[1]
        key = tuple(index.name for index in indices)
        ids = tuple(id(index) for index in indices)
        with self._mesh_multi_lock:
            cached = self._mesh_multi.get(key)
            if cached is not None and cached[3] != ids:
                # an index was deleted/recreated under the same name:
                # the pack is stale, return its budget and rebuild
                self._release_pack(cached)
                del self._mesh_multi[key]
                cached = None
            try:
                entry = self._mesh_build(indices, cached)
            except BaseException:
                self._mesh_multi.pop(key, None)   # same staleness rule
                raise
            self._mesh_multi[key] = entry + (ids,)
            self._mesh_multi.move_to_end(key)
            while len(self._mesh_multi) > 4:
                _, old = self._mesh_multi.popitem(last=False)
                self._release_pack(old)
            return entry[1]

    def _shard_wait_s(self, deadline_at: float | None) -> float:
        """Every coordinator wait on a shard future is BOUNDED: the
        remaining request deadline (+ grace) when one exists, the stall
        ceiling otherwise — a wedged shard becomes a typed shard
        failure / partial result, never a hung request."""
        if deadline_at is None:
            return self.SHARD_WAIT_CEILING_S
        return min(self.SHARD_WAIT_CEILING_S,
                   max(deadline_at - time.perf_counter(), 0.0)
                   + self.PARTIAL_GRACE_S)

    def _dfs_phase(self, state, groups, body: dict,
                   deadline_at: float | None = None) -> dict:
        """The DFS round preceding the query round
        (executeDfsPhase, core/search/SearchService.java:264 +
        aggregateDfs SearchPhaseController.java:105): gather each shard's
        term/collection statistics, reduce to global stats."""
        from concurrent.futures import TimeoutError as FutTimeout
        from elasticsearch_tpu.search.dfs import aggregate_dfs
        futures = [self._submit(
            self._try_shard_action, state, n, s, copies, self.DFS,
            self._handle_shard_dfs, body) for n, s, copies in groups]
        results = []
        for fut in futures:
            try:
                status, payload = fut.result(
                    self._shard_wait_s(deadline_at))
            except FutTimeout:
                # a stalled dfs shard contributes no stats, exactly
                # like a failed one — its query round reports the
                # failure; the dfs wait must never wedge the request
                continue
            if status == "ok":
                results.append(payload)
            # a failed shard contributes no stats — its query round will
            # fail over / report the shard failure itself
        return aggregate_dfs(results)

    def _resolve_allow_partial(self, allow_partial) -> bool:
        """Request-level ``allow_partial_search_results`` overrides the
        node's ``search.default_allow_partial_results`` setting."""
        if allow_partial is None:
            return self.default_allow_partial
        return str(allow_partial).lower() not in ("false", "0")

    def _collect_shard_result(self, fut, name: str, sid: int,
                              deadline_at: float | None,
                              allow_partial: bool):
        """Collect one shard group's fan-out future. When partial
        results are allowed and the request deadline expires before the
        group responds, ABANDON it — deadline-bounded partial results:
        the group is accounted as a failed shard with a timed-out
        reason, and the response ships whatever completed. The
        abandoned shard work self-cancels: it carries the remaining
        budget as its task deadline."""
        from concurrent.futures import TimeoutError as FutTimeout
        if allow_partial and deadline_at is not None:
            wait = max(deadline_at - time.perf_counter(), 0.0) \
                + self.PARTIAL_GRACE_S
            try:
                return fut.result(wait)
            except FutTimeout:
                return "deadline", {
                    "shard": sid, "index": name,
                    "reason": {
                        "type": "timed_out_exception",
                        "reason": "shard group did not respond within "
                                  "the request timeout; partial results "
                                  "returned"},
                    "status": 504}, None
        # no deadline (or partial results disallowed — all-or-block
        # semantics wait out a merely-slow shard): still BOUNDED, by
        # the stall ceiling alone. A shard whose device dispatch
        # wedged must surface as a typed shard failure, never hold
        # the coordinator thread forever.
        try:
            return fut.result(self._shard_wait_s(None))
        except FutTimeout:
            return "stalled", {
                "shard": sid, "index": name,
                "reason": {
                    "type": "shard_stall_exception",
                    "reason": "shard group did not respond within the "
                              "coordinator stall ceiling; the wait was "
                              "abandoned (the shard task may still be "
                              "running)"},
                "status": 504}, None

    def _search_once(self, index_expr: str, body: dict, t0: float,
                     search_type: str | None = None,
                     dfs_cache: dict | None = None,
                     scroll_pin: dict | None = None,
                     routing: str | None = None,
                     preference: str | None = None,
                     allow_partial=None) -> dict:
        with obs_trace.span("action.parse"):
            names = self.node.indices_service.resolve_open(index_expr)
            body = rewrite_mlt_likes(self.node, body,
                                     names[0] if names else "_all")
            state = self.node.cluster_service.state()
            req = parse_search_request(body)
        groups = self._shard_groups(state, names, routing=routing,
                                    preference=preference)
        dfs = None
        if dfs_cache is None and scroll_pin is None and routing is None \
                and preference is None:
            # collective plane (DEFAULT-ON): when this node holds EVERY
            # shard of the target indices, an eligible search runs as
            # ONE shard_map program — per-shard emit, all_gather top-k
            # merge, psum counts, metric/bucket aggs — instead of the
            # per-shard fan-out + host merge (SURVEY §2.2: scatter/
            # gather + reduce onto ICI collectives). dfs types score
            # with global statistics (the plane's native mode); plain
            # searches score each shard with its own statistics,
            # bit-matching the fan-out. Routed/preference-restricted
            # searches skip it (the one-program fan-out always covers
            # EVERY shard; restricting the mesh would cost a recompile
            # per subset) and scroll pages need pinned readers the pack
            # does not provide.
            from elasticsearch_tpu.search import jit_exec
            with attribution.collect(admission="plane"), \
                    obs_trace.span("action.plane") as psp:
                mesh_resp = self._try_collective_plane(
                    names, [body], [req], t0, search_type=search_type)
                psp.set(served=mesh_resp is not None,
                        breaker=jit_exec.plane_breaker.state)
            if mesh_resp is not None:
                return mesh_resp[0]
        if search_type == "dfs_query_then_fetch":
            # scroll contexts reuse the stats gathered for page one: the
            # reference keeps AggregatedDfs in the search context — fresh
            # stats per page would cost S extra RPCs per page and could
            # shift scores across the search_after boundary mid-scroll
            if dfs_cache is not None and "wire" in dfs_cache:
                dfs = dfs_cache["wire"]
            else:
                dfs = self._dfs_phase(
                    state, groups, body,
                    deadline_at=None if req.timeout_ms is None
                    else t0 + req.timeout_ms / 1000.0)
                if dfs_cache is not None:
                    dfs_cache["wire"] = dfs
        # dense, deterministic _doc slots per (index, shard): sorted so a
        # scroll's later pages (same index set) assign identical slots
        slot_of = {(n, s): i for i, (n, s) in
                   enumerate(sorted((n, s) for n, s, _ in groups))}
        # True QUERY_THEN_FETCH (fillDocIdsToLoad + second fan-out,
        # SearchPhaseController.java:289, TransportSearchQueryThenFetch
        # Action.java:89-150) when the window is deep enough that shipping
        # every shard's full from+size `_source` payloads would dominate:
        # the query round moves only ids/scores, the fetch round touches
        # only the shards owning the global page. Shallow windows keep the
        # single-round QUERY_AND_FETCH model (module docstring) — the
        # extra round trip costs more than the surplus hit bytes.
        use_qtf = scroll_pin is None and len(groups) > 1 and (
            search_type in ("query_then_fetch", "dfs_query_then_fetch")
            or (search_type is None
                and req.from_ + req.size >= self.QTF_WINDOW_THRESHOLD))
        # the request's absolute deadline: shards get the REMAINING
        # budget at dispatch, so queue/fan-out time counts against the
        # timeout (wired through the task's deadline on the shard side)
        deadline_at = None if req.timeout_ms is None \
            else t0 + req.timeout_ms / 1000.0
        allow_partial = self._resolve_allow_partial(allow_partial)
        # hedging needs the freedom to pick the copy — an explicit
        # preference pinned placement, so it stays sequential
        allow_hedge = preference is None
        if use_qtf:
            return self._query_then_fetch(state, groups, body, req, t0,
                                          slot_of, dfs, deadline_at,
                                          allow_partial=allow_partial,
                                          allow_hedge=allow_hedge)
        q_t0 = time.perf_counter()
        payloads, failures = [], []
        with obs_trace.span("action.query", shards=len(groups)):
            futures = [self._submit(self._try_shard, state, n, s, copies,
                                    body, slot_of[(n, s)], dfs,
                                    scroll_pin, None, deadline_at,
                                    allow_hedge)
                       for n, s, copies in groups]
            for (n, s, _c), fut in zip(groups, futures):
                status, payload, _node = self._collect_shard_result(
                    fut, n, s, deadline_at, allow_partial)
                if status == "ok":
                    obs_trace.sink_shard_profile(
                        payload.pop("_profile", None))
                    payloads.append(payload)
                else:
                    failures.append(payload)
        q_ms = (time.perf_counter() - q_t0) * 1e3
        r_t0 = time.perf_counter()
        with obs_trace.span("action.reduce"):
            resp = merge_shard_payloads(
                req, payloads, (time.perf_counter() - t0) * 1e3,
                total_shards=len(groups), failures=failures)
        from elasticsearch_tpu.search.controller import attach_phase_took
        attach_phase_took(
            resp, {"query": q_ms,
                   "reduce": (time.perf_counter() - r_t0) * 1e3},
            tasks.current_task())
        obs_hist.observe_lane("fanout", (time.perf_counter() - t0) * 1e3)
        if deadline_at is not None and time.perf_counter() > deadline_at:
            # elapsed-time truth at the coordinator too: a request that
            # blew its budget in fan-out/queueing is timed out even if
            # no shard individually noticed (controller.py:104 only
            # aggregates per-shard flags)
            resp["timed_out"] = True
        return resp

    def _query_then_fetch(self, state, groups, body: dict, req, t0: float,
                          slot_of: dict, dfs: dict | None,
                          budget_deadline: float | None = None,
                          allow_partial: bool = False,
                          allow_hedge: bool = True) -> dict:
        """Two-round distributed search: query (descriptors only) →
        coordinator merge → winner-only fetch → assemble."""
        import uuid as _uuid
        from elasticsearch_tpu.search.controller import _hit_comparator
        pin = {"uid": _uuid.uuid4().hex, "keep_s": 30.0}
        q_t0 = time.perf_counter()
        qpayloads, failures = [], []   # (payload, node_id, name, sid, slot)
        with obs_trace.span("action.query", shards=len(groups)):
            futures = [self._submit(self._try_shard, state, n, s, copies,
                                    body, slot_of[(n, s)], dfs,
                                    None, pin, budget_deadline,
                                    allow_hedge)
                       for n, s, copies in groups]
            for (n, s, _), fut in zip(groups, futures):
                status, payload, node_id = self._collect_shard_result(
                    fut, n, s, budget_deadline, allow_partial)
                if status == "ok":
                    obs_trace.sink_shard_profile(
                        payload.pop("_profile", None))
                    qpayloads.append((payload, node_id, n, s,
                                      slot_of[(n, s)]))
                else:
                    failures.append(payload)
        q_ms = (time.perf_counter() - q_t0) * 1e3
        fetch_ms = 0.0
        try:
            # sortDocs over descriptors → the global [from, from+size)
            entries = []
            for si, (p, _, _, _, _) in enumerate(qpayloads):
                sort_vals = p.get("sort")
                for pos in range(len(p["docs"])):
                    entries.append((
                        sort_vals[pos] if sort_vals is not None else None,
                        p["scores"][pos], si, pos))
            keyfn = _hit_comparator(req)
            entries.sort(key=keyfn)
            page = entries[req.from_: req.from_ + req.size]
            # fillDocIdsToLoad → fetch ONLY from shards owning winners,
            # targeting the exact node whose reader is pinned
            by_shard: dict[int, list[int]] = {}
            for e in page:
                by_shard.setdefault(e[2], []).append(e[3])
            f_t0 = time.perf_counter()
            fetched: dict[tuple[int, int], dict] = {}
            fetch_failed: set[int] = set()
            with obs_trace.span("action.fetch", shards=len(by_shard)):
                fetch_futs = {}
                for si, positions in by_shard.items():
                    p, node_id, name, sid, slot = qpayloads[si]
                    request = {
                        "index": name, "shard": sid, "body": body,
                        "pin": pin, "doc_slot": slot,
                        "docs": [p["docs"][pos] for pos in positions],
                        "scores": [p["scores"][pos]
                                   for pos in positions],
                        "sort": ([p["sort"][pos] for pos in positions]
                                 if p.get("sort") is not None else None)}
                    if node_id == self.node.node_id:
                        fetch_futs[si] = self.node.thread_pool.submit(
                            "search", self._handle_shard_fetch, request,
                            None)
                    else:
                        target = state.node(node_id)
                        if target is None:
                            fetch_futs[si] = None
                            continue
                        fetch_futs[si] = self.node.transport_service.\
                            send_request(target, self.FETCH_ID, request,
                                         timeout=30.0)
                for si, positions in by_shard.items():
                    fut = fetch_futs.get(si)
                    try:
                        if fut is None:
                            raise ElasticsearchTpuError(
                                "fetch target node left the cluster")
                        wait = 35.0
                        if allow_partial and budget_deadline is not None:
                            # deadline-bounded fetch too: a browned-out
                            # pin holder must not stall the partial
                            # response past the deadline
                            wait = min(wait, max(
                                budget_deadline - time.perf_counter(),
                                0.0) + self.PARTIAL_GRACE_S)
                        payload_f = fut.result(wait)
                        obs_trace.sink_shard_profile(
                            payload_f.pop("_profile", None))
                        hits = payload_f["hits"]
                        for pos, hit in zip(positions, hits):
                            fetched[(si, pos)] = hit
                    except Exception as e:  # noqa: BLE001 — per-shard
                        fetch_failed.add(si)
                        _, _, name, sid, _ = qpayloads[si]
                        failures.append({
                            "shard": sid, "index": name,
                            "reason": {"type": "fetch_phase_failure",
                                       "reason": str(e)}})
            fetch_ms = (time.perf_counter() - f_t0) * 1e3
            hits_out = [fetched[(e[2], e[3])] for e in page
                        if (e[2], e[3]) in fetched]
        finally:
            self._free_context(pin["uid"],
                               [nid for _, nid, *_ in qpayloads])
        from elasticsearch_tpu.search.controller import (
            assemble_response, attach_phase_took)
        r_t0 = time.perf_counter()
        payloads = [p for p, *_ in qpayloads]
        with obs_trace.span("action.reduce"):
            resp = assemble_response(
                req, payloads, hits_out,
                (time.perf_counter() - t0) * 1e3,
                total_shards=len(groups), failures=failures,
                successful=len(qpayloads) - len(fetch_failed))
        attach_phase_took(
            resp, {"query": q_ms, "fetch": fetch_ms,
                   "reduce": (time.perf_counter() - r_t0) * 1e3},
            tasks.current_task())
        obs_hist.observe_lane("fanout", (time.perf_counter() - t0) * 1e3)
        if budget_deadline is not None and \
                time.perf_counter() > budget_deadline:
            resp["timed_out"] = True
        return resp

    def count(self, index_expr: str, body: dict | None = None,
              routing: str | None = None,
              preference: str | None = None) -> dict:
        resp = self.search(index_expr, {**(body or {}), "size": 0},
                           routing=routing, preference=preference)
        return {"count": resp["hits"]["total"],
                "_shards": resp["_shards"]}

    # ---- _msearch (ref: core/action/search/TransportMultiSearchAction) ----

    def multi_search(self, items: list) -> dict:
        """Execute B (index_expr, body[, search_type]) search items →
        {"responses": [...]}.

        Consecutive items on the SAME (index expression, search_type)
        batch into one shard fan-out carrying every body — each data node
        then runs the whole batch as one vmapped program when the plans
        align (the reference fans request-at-a-time; an accelerator wants
        the batch); dfs batches on an opted-in local index ride the
        collective plane as ONE mesh program. Per-item failures return an
        {"error": ...} entry (the _msearch contract), never failing the
        whole request.
        """
        items = [(it[0], it[1], it[2] if len(it) > 2 else None)
                 for it in items]
        responses: list[dict | None] = [None] * len(items)
        groups: list[tuple[str, str | None, list[int]]] = []
        for i, (index_expr, _body, stype) in enumerate(items):
            if groups and groups[-1][0] == index_expr \
                    and groups[-1][1] == stype:
                groups[-1][2].append(i)
            else:
                groups.append((index_expr, stype, [i]))
        with obs_trace.span("action.msearch"), \
                self._coordinating_task(
                    "indices:data/read/msearch",
                    f"requests[{len(items)}]"):
            futures = [self._msearch_pool.submit(
                tasks.bind_current(self._msearch_group), expr,
                [items[i][1] for i in idxs],
                stype) for expr, stype, idxs in groups]
            return self._collect_msearch(groups, futures, responses)

    def _collect_msearch(self, groups, futures, responses) -> dict:
        from concurrent.futures import TimeoutError as FutTimeout
        for (expr, stype, idxs), fut in zip(groups, futures):
            try:
                # BOUNDED backstop: every wait inside a group is itself
                # deadline/ceiling bounded, so 2x the shard stall
                # ceiling only fires if a group wedges outside those
                # bounds — the msearch then reports per-item stall
                # errors instead of hanging the whole multi-request
                outs = fut.result(2 * self.SHARD_WAIT_CEILING_S)
            except FutTimeout:
                cause = {"type": "shard_stall_exception",
                         "reason": "msearch group did not respond within "
                                   "the coordinator stall ceiling; the "
                                   "wait was abandoned"}
                outs = [{"error": {"root_cause": [cause], **cause}}] \
                    * len(idxs)
            except Exception as e:           # noqa: BLE001 — per-group error
                from elasticsearch_tpu.common.errors import (
                    ElasticsearchTpuError)
                if isinstance(e, ElasticsearchTpuError):
                    cause = e.to_xcontent()
                else:
                    cause = {"type": "search_phase_execution_exception",
                             "reason": str(e)}
                outs = [{"error": {"root_cause": [cause], **cause}}] \
                    * len(idxs)
            for i, out in zip(idxs, outs):
                responses[i] = out
        return {"responses": responses}

    def _msearch_group(self, index_expr: str, bodies: list[dict],
                       search_type: str | None = None) -> list[dict]:
        with obs_trace.span("action.msearch_group"):
            return self._msearch_group_inner(index_expr, bodies,
                                             search_type)

    def _msearch_group_inner(self, index_expr: str, bodies: list[dict],
                             search_type: str | None) -> list[dict]:
        """One shard fan-out for a group of bodies on one index expr.
        Bodies are parsed ONCE here — invalid items answer immediately and
        never ship; per-item SHARD errors surface as that item's shard
        failures (partial results stay visible as partial)."""
        t0 = time.perf_counter()
        names = self.node.indices_service.resolve_open(index_expr)
        bodies = [rewrite_mlt_likes(self.node, b,
                                    names[0] if names else "_all")
                  for b in bodies]
        outs: list[dict | None] = [None] * len(bodies)
        parsed: dict[int, object] = {}
        for i, body in enumerate(bodies):
            try:
                parsed[i] = parse_search_request(body)
            except Exception as e:           # noqa: BLE001 — per-item error
                outs[i] = {"error": {"type": "parsing_exception",
                                     "reason": str(e)}}
        valid = sorted(parsed)
        if not valid:
            return [o for o in outs]
        send_bodies = [bodies[i] for i in valid]
        if search_type in self.PLANE_SEARCH_TYPES:
            # an msearch group is the collective plane's natural batch:
            # ONE mesh program scores every item — global statistics for
            # dfs groups, per-shard statistics otherwise — and a group
            # whose expression spans several indices still packs into
            # the same single dispatch; fallback runs the items through
            # the ordinary paths
            with attribution.collect(admission="plane"), \
                    obs_trace.span("action.plane", batch=len(send_bodies)):
                mesh_outs = self._try_collective_plane(
                    names, send_bodies, [parsed[i] for i in valid], t0,
                    search_type=search_type)
            if mesh_outs is not None:
                for i, r in zip(valid, mesh_outs):
                    outs[i] = r
                return [o for o in outs]
        if search_type in ("dfs_query_then_fetch", "dfs_query_and_fetch"):
            # per-item dfs fallback, concurrently. A transient pool (not
            # _pool/_msearch_pool) because this frame already RUNS on
            # _msearch_pool and _search_once fans shards onto _pool —
            # same-pool nesting deadlocks under saturation
            from concurrent.futures import ThreadPoolExecutor as _TPE
            from concurrent.futures import TimeoutError as FutTimeout
            pool = _TPE(max_workers=min(len(valid), 4))
            try:
                futs = {i: pool.submit(
                    tasks.bind_current(self._search_once), index_expr,
                    bodies[i], t0, "dfs_query_then_fetch")
                        for i in valid}
                for i in valid:
                    try:
                        outs[i] = futs[i].result(
                            2 * self.SHARD_WAIT_CEILING_S)
                    except FutTimeout:
                        futs[i].cancel()
                        outs[i] = {"error": {
                            "type": "shard_stall_exception",
                            "reason": "dfs msearch item did not respond "
                                      "within the coordinator stall "
                                      "ceiling; the wait was abandoned"}}
            finally:
                # NOT wait=True: joining a wedged worker here would
                # re-introduce the unbounded wait this path just shed —
                # queued items are cancelled, running ones are
                # deadline/ceiling bounded and the pool threads exit
                # on their own when those bounds fire
                pool.shutdown(wait=False, cancel_futures=True)
            return [o for o in outs]
        state = self.node.cluster_service.state()
        groups = self._shard_groups(state, names)
        slot_of = {(n, s): i for i, (n, s) in
                   enumerate(sorted((n, s) for n, s, _ in groups))}
        futures = [self._submit(
            self._try_shard_action, state, n, s, copies, self.MSEARCH_SHARD,
            self._handle_shard_msearch, None,
            {"bodies": send_bodies, "doc_slot": slot_of[(n, s)]})
            for n, s, copies in groups]
        per_shard, group_failures = [], []
        from concurrent.futures import TimeoutError as FutTimeout
        for (n, s, _copies), fut in zip(groups, futures):
            try:
                status, payload = fut.result(self._shard_wait_s(None))
            except FutTimeout:
                status, payload = "stalled", {
                    "shard": s, "index": n,
                    "reason": {
                        "type": "shard_stall_exception",
                        "reason": "msearch shard group did not respond "
                                  "within the coordinator stall ceiling; "
                                  "the wait was abandoned"},
                    "status": 504}
            if status == "ok":
                per_shard.append((n, s, payload["payloads"]))
            else:
                group_failures.append(payload)
        took = (time.perf_counter() - t0) * 1e3
        for pos, i in enumerate(valid):
            item_payloads = []
            item_failures = list(group_failures)
            for n, s, shard_payloads in per_shard:
                p = shard_payloads[pos]
                if "error" in p:
                    # same shape as group-level shard failures
                    item_failures.append({"shard": s, "index": n,
                                          "reason": {
                                              "type": "shard_search_failure",
                                              "reason": p["error"]}})
                else:
                    item_payloads.append(p)
            if not item_payloads and item_failures:
                # every shard failed for this item: an error entry, not a
                # legitimate-looking empty result (the _msearch contract)
                outs[i] = {"error": {
                    "type": "search_phase_execution_exception",
                    "reason": "all shards failed",
                    "failed_shards": item_failures}}
                continue
            outs[i] = merge_shard_payloads(
                parsed[i], item_payloads, took, total_shards=len(groups),
                failures=item_failures)
        return [o for o in outs]

    # ---- field stats (core/action/fieldstats/TransportFieldStatsAction) ----

    def field_stats(self, index_expr: str, fields: list[str],
                    level: str = "cluster",
                    index_constraints: dict | None = None) -> dict:
        """Per-field min/max/doc-count over one copy of every shard,
        reduced cluster-wide or per index (the 2.x _field_stats API
        `level` param)."""
        names = self.node.indices_service.resolve_open(index_expr)
        state = self.node.cluster_service.state()
        groups = self._shard_groups(state, names)
        fetch = list(fields)
        for f in (index_constraints or {}):
            if f not in fetch:
                fetch.append(f)
        body = {"fields": fetch}
        futures = [self._submit(
            self._try_shard_action, state, n, s, copies, self.FIELD_STATS,
            self._handle_field_stats, body) for n, s, copies in groups]
        buckets: dict[str, dict[str, dict]] = {}
        ok = failed = 0

        def fold(merged: dict, payload: dict) -> None:
            for f, st in payload["fields"].items():
                cur = merged.get(f)
                if cur is None:
                    merged[f] = dict(st)
                    continue
                cur["doc_count"] += st["doc_count"]
                cur["max_doc"] += st["max_doc"]
                for k, pick in (("min_value", min), ("max_value", max)):
                    if st.get(k) is None:
                        continue
                    if cur.get(k) is None:
                        cur[k] = st[k]
                    elif isinstance(st[k], str) != isinstance(cur[k], str):
                        # same field name mapped to different types across
                        # indices (numeric vs text) — the values are not
                        # comparable; flag instead of crashing (the
                        # reference reports per-field conflicts)
                        cur[k] = None
                        cur["type_conflict"] = True
                    else:
                        cur[k] = pick(cur[k], st[k])
        from concurrent.futures import TimeoutError as FutTimeout
        for (n, _s, _c), fut in zip(groups, futures):
            try:
                status, payload = fut.result(self._shard_wait_s(None))
            except FutTimeout:
                # a stalled field-stats shard counts as failed — the
                # reduce ships whatever responded inside the ceiling
                failed += 1
                continue
            if status != "ok":
                failed += 1
                continue
            ok += 1
            key = n if level == "indices" else "_all"
            fold(buckets.setdefault(key, {}), payload)
        for merged in buckets.values():
            for st in merged.values():
                st["density"] = int(100 * st["doc_count"] /
                                    max(st["max_doc"], 1))
        if index_constraints:
            # drop indices whose constrained field stats miss the bounds
            # (FieldStatsRequest indexConstraints)
            def meets(merged: dict) -> bool:
                for f, spec in index_constraints.items():
                    st = merged.get(f)
                    if st is None:
                        return False
                    for prop, bounds in spec.items():
                        val = st.get(prop)
                        if val is None:
                            return False
                        for op, want in bounds.items():
                            try:
                                if isinstance(val, str):
                                    w = str(want)
                                else:
                                    try:
                                        w = type(val)(want)
                                    except (TypeError, ValueError):
                                        # date-string constraint against a
                                        # millis-valued field
                                        from elasticsearch_tpu.mapping \
                                            .mapper import parse_date
                                        w = type(val)(parse_date(want))
                            except Exception:  # noqa: BLE001 — no compare
                                return False
                            if op == "gte" and not val >= w:
                                return False
                            if op == "gt" and not val > w:
                                return False
                            if op == "lte" and not val <= w:
                                return False
                            if op == "lt" and not val < w:
                                return False
                return True
            buckets = {k: v for k, v in buckets.items() if meets(v)}
            want_fields = set(fields)
            buckets = {k: {f: st for f, st in v.items()
                           if f in want_fields}
                       for k, v in buckets.items()}
        return {"_shards": {"total": len(groups), "successful": ok,
                            "failed": failed},
                "indices": {k: {"fields": v} for k, v in buckets.items()}}

    def _try_shard_action(self, state, name, sid, copies, action,
                          local_handler, body, extra: dict | None = None):
        """Copy-failover for non-search per-shard actions."""
        from elasticsearch_tpu.action.replication import unwrap_remote
        last = None
        for c in copies:
            try:
                request = {"index": name, "shard": sid, "body": body,
                           **(extra or {})}
                if c.node_id == self.node.node_id:
                    # same bounded-search-pool dispatch as _try_shard:
                    # local msearch/DFS/field_stats work must not bypass
                    # the backpressure the remote path gets
                    fut = self.node.thread_pool.submit(
                        "search", local_handler, request, None)
                    try:
                        return "ok", fut.result(35.0)
                    except Exception:
                        fut.cancel()
                        raise
                target = state.node(c.node_id)
                if target is None:
                    continue
                return "ok", self.node.transport_service.send_request(
                    target, action, request, timeout=30.0).result(35.0)
            except Exception as e:               # noqa: BLE001 — failover
                last = unwrap_remote(e)
        return "fail", {"shard": sid, "index": name, "reason": str(last)}

    def _handle_field_stats(self, request: dict, source) -> dict:
        import numpy as np
        name, shard = request["index"], request["shard"]
        fields = (request.get("body") or {}).get("fields") or []
        svc = self.node.indices_service.index(name)
        engine = svc.engine(shard)
        reader = device_reader_for(engine)
        out: dict[str, dict] = {}
        max_doc = reader.num_docs
        for f in fields:
            doc_count = 0
            min_v = max_v = None
            for s in reader.segments:
                live = np.asarray(s.live)
                ncol = s.seg.numeric_fields.get(f)
                if ncol is not None:
                    exists = np.asarray(ncol.exists)[:live.shape[0]] & live
                    doc_count += int(exists.sum())
                    if exists.any():
                        vals = np.asarray(ncol.values)[:live.shape[0]][exists]
                        lo, hi = float(vals.min()), float(vals.max())
                        min_v = lo if min_v is None else min(min_v, lo)
                        max_v = hi if max_v is None else max(max_v, hi)
                    continue
                all_live = bool(live.all())
                tcol = s.seg.text_fields.get(f)
                if tcol is not None:
                    uterms = np.asarray(tcol.uterms)[:live.shape[0]]
                    has = (uterms >= 0).any(axis=1)
                    doc_count += int((has & live).sum())
                    # min/max over terms with >=1 LIVE posting only —
                    # terms surviving solely in deleted docs must not
                    # skew the bounds. No-deletes fast path: the sorted
                    # dictionary endpoints are already exact.
                    if all_live:
                        bounds = (tcol.terms[0], tcol.terms[-1]) \
                            if tcol.terms else None
                    else:
                        live_tids = np.unique(uterms[live])
                        live_tids = live_tids[live_tids >= 0]
                        bounds = (tcol.terms[int(live_tids[0])],
                                  tcol.terms[int(live_tids[-1])]) \
                            if live_tids.size else None
                    if bounds:
                        min_v = bounds[0] if min_v is None \
                            else min(min_v, bounds[0])
                        max_v = bounds[1] if max_v is None \
                            else max(max_v, bounds[1])
                    continue
                kcol = s.seg.keyword_fields.get(f)
                if kcol is not None:
                    ords = np.asarray(kcol.ords)[:live.shape[0]]
                    has = (ords >= 0).any(axis=1)
                    doc_count += int((has & live).sum())
                    if all_live:
                        bounds = (kcol.vocab[0], kcol.vocab[-1]) \
                            if kcol.vocab else None
                    else:
                        live_ords = np.unique(ords[live])
                        live_ords = live_ords[live_ords >= 0]
                        bounds = (kcol.vocab[int(live_ords[0])],
                                  kcol.vocab[int(live_ords[-1])]) \
                            if live_ords.size else None
                    if bounds:
                        min_v = bounds[0] if min_v is None \
                            else min(min_v, bounds[0])
                        max_v = bounds[1] if max_v is None \
                            else max(max_v, bounds[1])
            if doc_count:
                out[f] = {"max_doc": max_doc, "doc_count": doc_count,
                          "min_value": min_v, "max_value": max_v}
        return {"fields": out}

    def _pinned_reader(self, scroll_pin: dict, name: str, shard: int,
                       engine):
        """Point-in-time reader for a scroll context: the FIRST page pins
        the shard's current SearcherView (segments are immutable, the view
        object keeps them alive); later pages reuse it regardless of
        refreshes — ScrollContext semantics (SearchService.java:533-558).
        Views expire with the scroll keep-alive."""
        from elasticsearch_tpu.index.device_reader import DeviceReader
        key = (scroll_pin["uid"], name, shard)
        now = time.monotonic()
        with self._lock:
            # lazy sweep of expired pins
            dead = [k for k, (_, _, exp) in self._pinned.items()
                    if exp < now]
            for k in dead:
                del self._pinned[k]
            hit = self._pinned.get(key)
            if hit is not None:
                view, reader, _ = hit
                self._pinned[key] = (view, reader,
                                     now + scroll_pin["keep_s"])
                return reader
        if scroll_pin.get("require"):
            # a fetch round arriving after its query-round pin expired
            # MUST fail: re-pinning the current view would resolve the
            # shipped reader-local doc ids against a different point in
            # time and silently return the wrong documents
            raise SearchContextMissingError(
                f"no pinned context [{scroll_pin['uid']}] for "
                f"[{name}][{shard}]")
        view = engine.acquire_searcher()
        reader = device_reader_for(engine, view)
        if reader.generation != view.generation:
            reader = DeviceReader(view)
        with self._lock:
            self._pinned[key] = (view, reader, now + scroll_pin["keep_s"])
        return reader

    def _drop_pins(self, uid: str) -> None:
        with self._lock:
            for k in [k for k in self._pinned if k[0] == uid]:
                del self._pinned[k]

    # ---- scroll ------------------------------------------------------------

    @staticmethod
    def _scroll_sort(sort) -> list:
        """Scroll pages continue via search_after, which needs a total
        order: append a `_doc` tie-break."""
        if not sort:
            sort = [{"_score": {"order": "desc"}}]
        elif isinstance(sort, (str, dict)):
            sort = [sort]
        else:
            sort = list(sort)
        if not any((s == "_doc") or (isinstance(s, dict) and "_doc" in s)
                   for s in sort):
            sort = sort + [{"_doc": {"order": "asc"}}]
        return sort

    def _open_scroll(self, index_expr: str, body: dict, scroll: str,
                     first_page: dict, search_type: str | None = None,
                     dfs_cache: dict | None = None,
                     ctx_uid: str | None = None,
                     routing: str | None = None,
                     preference: str | None = None) -> str:
        keep = parse_time_value(scroll, "scroll")
        ctx = _ScrollContext(index_expr, body, keep, search_type=search_type,
                             ctx_uid=ctx_uid)
        ctx.routing = routing
        ctx.preference = preference
        ctx.dfs_cache = dfs_cache if dfs_cache is not None else {}
        self._note_page(ctx, first_page)
        with self._lock:
            cid = f"ctx{next(self._ctx_ids)}"
            self._contexts[cid] = ctx
        return base64.b64encode(json.dumps({"id": cid}).encode()).decode()

    @staticmethod
    def _note_page(ctx: _ScrollContext, page: dict):
        hits = page["hits"]["hits"]
        if not hits:
            ctx.finished = True
            return
        ctx.last_sort_key = hits[-1].get("sort")

    def scroll(self, scroll_id: str, scroll: str | None = None) -> dict:
        with self._coordinating_task("indices:data/read/scroll",
                                     "scroll page") as task:
            resp = self._scroll_page(scroll_id, scroll)
            if task is not None and task.cancelled:
                resp["cancelled"] = True
            return resp

    def _scroll_page(self, scroll_id: str,
                     scroll: str | None = None) -> dict:
        try:
            cid = json.loads(base64.b64decode(scroll_id))["id"]
        except Exception:                        # noqa: BLE001 — bad id
            raise SearchContextMissingError(
                f"invalid scroll id [{scroll_id}]") from None
        with self._lock:
            ctx = self._contexts.get(cid)
        if ctx is None or ctx.expires_at < time.monotonic():
            with self._lock:
                self._contexts.pop(cid, None)
            raise SearchContextMissingError(f"No search context found for "
                                            f"id [{cid}]")
        ctx.touch(parse_time_value(scroll, "scroll")
                  if scroll is not None else None)
        if ctx.finished:
            resp = {"took": 0, "timed_out": False,
                    "_shards": {"total": 0, "successful": 0, "failed": 0},
                    "hits": {"total": 0,
                             "max_score": None, "hits": []}}
            resp["_scroll_id"] = scroll_id
            return resp
        body = dict(ctx.body)
        body["from"] = 0
        if ctx.last_sort_key is not None:
            body["search_after"] = ctx.last_sort_key
        resp = self._search_once(ctx.index_expr, body, time.perf_counter(),
                                 search_type=ctx.search_type,
                                 dfs_cache=ctx.dfs_cache,
                                 scroll_pin={"uid": ctx.ctx_uid,
                                             "keep_s": ctx.keep_alive_s},
                                 routing=ctx.routing,
                                 preference=ctx.preference)
        self._note_page(ctx, resp)
        resp["_scroll_id"] = scroll_id
        return resp

    def clear_scroll(self, scroll_id: str | None) -> int:
        with self._lock:
            if scroll_id is None:
                n = len(self._contexts)
                self._contexts.clear()
                self._pinned.clear()     # free pinned readers with them
                return n
            try:
                cid = json.loads(base64.b64decode(scroll_id))["id"]
            except Exception:                    # noqa: BLE001 — bad id
                return 0
            ctx = self._contexts.pop(cid, None)
        if ctx is not None:
            # local pins die now; REMOTE nodes' pins age out with the
            # keep-alive (a clear RPC would tighten this cluster-wide)
            self._drop_pins(ctx.ctx_uid)
            return 1
        return 0

    def reap_expired(self) -> int:
        now = time.monotonic()
        with self._lock:
            dead = [k for k, c in self._contexts.items()
                    if c.expires_at < now]
            for k in dead:
                del self._contexts[k]
            # expired reader pins release their device-resident views here
            # too — lazy sweeping inside _pinned_reader alone would leak
            # them on nodes that never serve another pinned search
            for k in [k for k, (_, _, exp) in self._pinned.items()
                      if exp < now]:
                del self._pinned[k]
        return len(dead)

    def active_contexts(self) -> int:
        with self._lock:
            return len(self._contexts)
