"""Query execution: AST → (scores, mask) per device segment.

The analog of Lucene's Query.createWeight/scorer split as driven by
QueryPhase.execute (core/search/query/QueryPhase.java:99-314), re-designed
for XLA in two phases:

* **resolve** (:class:`SegmentResolver`) — host-side "createWeight": walk
  the AST resolving per-segment constants (term ids from the segment term
  dictionary, idf from reader-aggregated df, keyword ordinal bounds,
  double-double range bounds) into a :class:`ConstTable`, and return an
  *emit closure*. Resolution is dictionary lookups only — microseconds per
  query — so planning scales to batched/high-QPS dispatch.
* **emit** — the "scorer": pure jnp ops over the segment's columns, read
  through :class:`EmitCtx` so the SAME closure runs eagerly (numpy
  constants, real columns) or inside jit (traced constants, traced column
  views) — one implementation, no parity drift between the compiled path
  and its fallback oracle.

The ConstTable separates a query's *structure* (static signature tokens +
constant shapes) from its *constants* (values): queries sharing a signature
share one compiled XLA program, with constants as inputs — and a batch of
same-signature queries runs under ``jax.vmap`` with constants stacked on a
leading axis (jit_exec.run_reader_batch).

Term-to-ordinal resolution happens host-side, which is exactly the part of
Lucene's per-segment TermsEnum.seek that has no business running on an
accelerator.
"""

from __future__ import annotations

import fnmatch
import re
from dataclasses import dataclass
from typing import Any, Callable

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.common.errors import QueryParsingError
from elasticsearch_tpu.index.device_reader import (
    DeviceReader, DeviceSegment, dd_split)
from elasticsearch_tpu.mapping.mapper import parse_date, KIND_NUMERIC
from elasticsearch_tpu.ops import (
    lexical, phrase as phrase_ops, boolean as bool_ops, filters as filter_ops,
    vector as vector_ops, functionscore as fs_ops)
from elasticsearch_tpu.ops.similarity import BM25Params, idf as bm25_idf
from elasticsearch_tpu.search import query_dsl as q
from elasticsearch_tpu.search.batching import term_bucket
from elasticsearch_tpu.search.scripts import ScriptContext, compile_script


class ConstTable:
    """A query plan's dynamic constants + structural signature.

    ``add`` registers a constant and returns its index (a *const ref*);
    emit closures fetch it back through ``EmitCtx.get`` — by index, so the
    scheme is insensitive to evaluation order. ``static`` records anything
    that changes the traced structure (field names, clause counts,
    modifiers, slop windows...) into the signature.
    """

    __slots__ = ("values", "sig", "positions_needed", "vectors_needed",
                 "match_terms_real", "match_terms_padded")

    def __init__(self):
        self.values: list[np.ndarray] = []
        self.sig: list = []
        # text fields whose POSITION matrix ([N, L] tokens) the plan
        # reads (phrase/span scoring). Everything else runs on the
        # forward-impact columns, and jit_exec excludes untouched token
        # matrices from the traced inputs — at 1M docs the tokens array
        # alone made XLA compile ~14x slower for plans that never read it
        self.positions_needed: set = set()
        # vector fields whose [N, D] vecs the plan reads — same
        # tree-shaking contract as positions_needed (the [N] bool exists
        # arrays are always traced; only vecs are lazy/shaken)
        self.vectors_needed: set = set()
        # query terms the plan's BM25 match nodes score, and the absent
        # terms that padded their lists to the term bucket
        self.match_terms_real = 0
        self.match_terms_padded = 0

    def add(self, v, dtype=None) -> int:
        arr = np.asarray(v, dtype=dtype)
        self.values.append(arr)
        self.sig.append(("c", arr.shape, str(arr.dtype)))
        return len(self.values) - 1

    def static(self, *tokens) -> None:
        self.sig.append(tokens)

    def signature(self) -> tuple:
        return tuple(self.sig)


class EmitCtx:
    """Hands emit closures their segment view and resolved constants.

    ``seg`` is either the real :class:`DeviceSegment` (eager) or the
    traced rebuild of it inside jit (jit_exec.seg_rebuild); ``consts`` are
    numpy arrays (eager) or traced arrays (jit). Emit closures MUST read
    every array through this object — never through the resolver's
    segment — or the compiled program would bake device buffers in as
    constants instead of taking them as inputs.
    """

    __slots__ = ("seg", "consts", "n")

    def __init__(self, seg: DeviceSegment, consts):
        self.seg = seg
        self.consts = consts
        self.n = seg.padded_docs

    def get(self, ref: int):
        return self.consts[ref]


# emit closure: EmitCtx → (scores [N] f32, mask [N] bool)
Emit = Callable[[EmitCtx], tuple]


@dataclass
class ExecutionContext:
    reader: DeviceReader
    mapper_service: Any
    bm25: BM25Params = BM25Params()
    # Optional global term statistics (DFS_QUERY_THEN_FETCH,
    # core/search/dfs/DfsPhase.java:45), produced by search/dfs.py:
    # {"df": {(field, term): int}, "doc_count": {field: int},
    # "avgdl": {field: float}}. When set, idf and avgdl come from here
    # instead of the shard-local reader, so every shard scores with
    # identical statistics.
    dfs_stats: dict | None = None
    # The shard's index name — resolves the `indices` query per shard
    # (IndicesQueryParser picks query vs no_match_query by index). None →
    # standalone searchers match the listed branch (single-index tests).
    index_name: str | None = None


def impact_terms(query: "q.Query", mapper_service,
                 max_terms: int = 64) -> tuple | None:
    """Impact-lane eligibility: can this query be scored from the
    quantized per-(term, doc) impact columns alone?

    The precomputed impacts bake idf·tfNorm for default-BM25
    OR-semantics term scoring — exactly the disjunctive match/term
    shapes, nothing else. → (field, analyzed terms, boost) when
    eligible, None otherwise (the exact scorer stays the default: any
    shape the quantized path can't reproduce — operators, msm,
    alternative similarities, compounds, functions — declines here).
    Mapping-only (no segment needed) so the collective-plane admission
    can consult the same screen."""
    t = type(query).__name__
    if t == "TermQuery":
        fm = mapper_service.field_mapper(query.field)
        if fm is None or getattr(fm, "kind", None) != "text":
            return None
        # term-on-text scores like a single-term match through the
        # keyword analyzer (the _res_TermQuery rewrite)
        query = q.MatchQuery(field=query.field, text=str(query.value),
                             analyzer="keyword", boost=query.boost)
        t = "MatchQuery"
    if t != "MatchQuery":
        return None
    field = query.field
    if field in ("*", "_all"):
        return None
    fm = mapper_service.field_mapper(field)
    if fm is None or getattr(fm, "kind", None) != "text":
        return None
    sim = fm.params.get("similarity") or \
        getattr(mapper_service, "default_similarity", None)
    if str(sim or "BM25").lower() not in ("bm25",):
        return None
    if query.operator == "and" or \
            query.minimum_should_match not in (None, 1):
        return None
    if not (query.boost >= 0):            # negative boost flips order —
        return None                       # block bounds would invert
    if query.analyzer:
        analyzer = mapper_service.analysis.get(query.analyzer)
    else:
        analyzer = fm.search_analyzer
    if analyzer is None:
        return None
    terms = [tok.term for tok in analyzer.analyze(query.text)]
    if not terms or len(terms) > max_terms:
        return None
    return field, terms, float(query.boost)


def fuzzy_kmax(value: str, fuzziness) -> int:
    """The AUTO edit-distance ladder (FuzzyQuery defaults): 0 below 3
    chars, 1 below 6, else 2."""
    if fuzziness == "AUTO":
        return 0 if len(value) < 3 else (1 if len(value) < 6 else 2)
    return int(fuzziness)


def multi_term_pred(inner):
    """term-predicate for a multi-term query node (prefix / wildcard /
    regexp / fuzzy) — the single rewrite seam shared by the _res_* arms
    and the span_multi expansion (Lucene's MultiTermQuery TermsEnum)."""
    it = type(inner).__name__
    if it == "PrefixQuery":
        val = inner.value
        return lambda term: term.startswith(val)
    if it == "WildcardQuery":
        rx = re.compile(fnmatch.translate(inner.pattern))
        return lambda term: rx.match(term) is not None
    if it == "RegexpQuery":
        rx = re.compile(inner.pattern)
        return lambda term: rx.fullmatch(term) is not None
    if it == "FuzzyQuery":
        v = inner.value
        kmax = fuzzy_kmax(v, inner.fuzziness)
        return lambda term: _edit_distance_le(term, v, kmax)
    return None


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Banded Levenshtein ≤ k (fuzzy query vocab scan)."""
    if abs(len(a) - len(b)) > k:
        return False
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        lo = max(1, i - k)
        hi = min(len(b), i + k)
        if lo > 1:
            cur[lo - 1] = k + 1
        for j in range(lo, hi + 1):
            cost = 0 if ca == b[j - 1] else 1
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + cost)
        for j in range(hi + 1, len(b) + 1):
            cur[j] = k + 1
        prev = cur
        if min(prev) > k:
            return False
    return prev[len(b)] <= k


def _search_analyzer(ms, field: str, override: str | None):
    if override:
        return ms.analysis.get(override)
    fm = ms.field_mapper(field)
    if fm is not None and getattr(fm, "kind", None) == "text":
        return fm.search_analyzer
    return ms.analysis.get("standard")


def match_term_floor(queries: list, mapper_service) -> int:
    """The widest term bucket among a batch's ``match`` nodes: one walk
    of the parsed queries, analysis only (no segment, no statistics).
    Handed to every query's :class:`SegmentResolver`, it makes a batch of
    unequal lengths pad to ONE width and share one compiled plan. A
    ``match`` the walk does not reach (a rewrite made while resolving,
    as ``multi_match`` does) keeps its own bucket: the batch may then
    decline, never answer wrongly."""
    floor = 1
    stack = list(queries)
    while stack:
        node = stack.pop()
        if isinstance(node, q.MatchQuery):
            analyzer = _search_analyzer(mapper_service, node.field,
                                        node.analyzer)
            floor = max(floor, term_bucket(
                len(analyzer.analyze(node.text))))
            continue
        for v in vars(node).values():
            if isinstance(v, (list, tuple)):
                stack.extend(x for x in v
                             if hasattr(x, "__dataclass_fields__"))
            elif hasattr(v, "__dataclass_fields__"):
                stack.append(v)
    return floor


class SegmentResolver:
    """Host-side "createWeight": resolves query ASTs against one segment's
    dictionaries into emit closures + a ConstTable."""

    def __init__(self, seg: DeviceSegment, ctx: ExecutionContext,
                 ct: ConstTable | None = None, term_floor: int = 1):
        self.seg = seg
        self.ctx = ctx
        self.ct = ct if ct is not None else ConstTable()
        self.n = seg.padded_docs
        self.c = self.ct.add
        self.sig = self.ct.static
        # the least width a BM25 match pads its term list to: a batch
        # hands every query its widest bucket (match_term_floor), so
        # queries of unequal lengths share one plan signature
        self.term_floor = term_floor

    # ------------------------------------------------------------------ util

    def _analyzer_for(self, field: str, override: str | None):
        return _search_analyzer(self.ctx.mapper_service, field, override)

    def _similarity_for(self, field: str) -> str:
        """Per-field similarity module (ref: SimilarityModule — BM25 /
        classic (the 2.x "default" TF-IDF) / lm_dirichlet), from the
        field mapping's `similarity` or the index default."""
        fm = self.ctx.mapper_service.field_mapper(field)
        sim = None
        if fm is not None:
            sim = fm.params.get("similarity")
        if sim is None:
            sim = getattr(self.ctx.mapper_service, "default_similarity",
                          None)
        # NOTE: phrase/common/span queries score BM25 regardless — like
        # idf, the alt similarities apply to term-frequency scoring paths
        # (match, term-on-text, multi_match via its match subs)
        sim = str(sim or "BM25").lower()
        if sim in ("default", "classic", "tfidf", "tf/idf"):
            return "classic"
        if sim in ("lmdirichlet", "lm_dirichlet"):
            return "lm_dirichlet"
        return "bm25"

    def _ctf_frac(self, field: str, term: str) -> float:
        """Collection term frequency / collection tokens (LM Dirichlet's
        P(t|C)) — from global DFS statistics when present (like idf),
        else summed over this reader's segments and cached per reader."""
        dfs = self.ctx.dfs_stats
        if dfs is not None and (field, term) in dfs.get("ctf", {}):
            total = dfs.get("total_tokens", {}).get(field, 0)
            if total:
                return dfs["ctf"][(field, term)] / total
        cache = getattr(self.ctx.reader, "_ctf_cache", None)
        if cache is None:
            cache = self.ctx.reader.__dict__.setdefault("_ctf_cache", {})
        key = (field, term)
        if key in cache:
            return cache[key]
        ctf = 0
        total = 0
        for s in self.ctx.reader.segments:
            col = s.seg.text_fields.get(field)
            if col is None:
                continue
            total += int(col.total_tokens)
            t2 = col.tid(term)
            if t2 >= 0:
                ctf += col.ctf(t2)
        frac = ctf / total if total else 0.0
        cache[key] = frac
        return frac

    def _zeros(self) -> Emit:
        self.sig("zeros")
        return lambda em: (jnp.zeros(em.n, jnp.float32),
                           jnp.zeros(em.n, bool))

    def _all(self, boost: float) -> Emit:
        r_boost = self.c(boost, np.float32)
        return lambda em: (jnp.full(em.n, 1.0, jnp.float32) * em.get(r_boost),
                           jnp.ones(em.n, bool))

    def _numeric_value(self, field: str, value):
        fm = self.ctx.mapper_service.field_mapper(field)
        if fm is not None and fm.type == "date" and not isinstance(
                value, (int, float)):
            return parse_date(value)
        if fm is not None and fm.type == "ip" and isinstance(value, str):
            from elasticsearch_tpu.mapping.mapper import ip_to_long
            return float(ip_to_long(value))
        if isinstance(value, bool):
            return 1.0 if value else 0.0
        return float(value)

    def _term_stats(self, field: str, term: str) -> tuple[int, int]:
        """→ (df, doc_count), from global DFS statistics when present
        (aggregateDfs, core/search/controller/SearchPhaseController.java:105)
        else from the shard-local reader. A term the DFS round did not
        cover falls back to local stats (graceful, like a stale
        AggregatedDfs entry)."""
        dfs = self.ctx.dfs_stats
        if dfs is not None and (field, term) in dfs["df"]:
            doc_count = dfs["doc_count"].get(field)
            if doc_count is None:
                doc_count = max(self.ctx.reader.text_stats(field).doc_count,
                                1)
            return int(dfs["df"][(field, term)]), max(int(doc_count), 1)
        st = self.ctx.reader.text_stats(field)
        return self.ctx.reader.df(field, term), max(st.doc_count, 1)

    def _avgdl(self, field: str) -> float:
        dfs = self.ctx.dfs_stats
        if dfs is not None and field in dfs.get("avgdl", {}):
            return max(float(dfs["avgdl"][field]), 1e-9)
        return max(self.ctx.reader.text_stats(field).avgdl, 1e-9)

    # ------------------------------------------------------------- dispatch

    def resolve(self, query: q.Query) -> Emit:
        """→ emit closure producing (scores [N] f32, mask [N] bool);
        live-mask applied by the caller."""
        # cooperative cancellation checkpoint: plan resolution walks the
        # whole AST host-side, so a cancelled task aborts here before the
        # next device dispatch is even built (TaskManager wiring)
        from elasticsearch_tpu.tasks import raise_if_cancelled
        raise_if_cancelled()
        method = getattr(self, f"_res_{type(query).__name__}", None)
        if method is None:
            raise QueryParsingError(
                f"no executor for query type [{type(query).__name__}]")
        self.sig(type(query).__name__, getattr(query, "field", None))
        return method(query)

    def resolve_mask(self, query: q.Query) -> Callable[[EmitCtx], Any]:
        emit = self.resolve(query)
        return lambda em: emit(em)[1]

    # ----------------------------------------------------------------- leafs

    def _res_MatchAllQuery(self, query: q.MatchAllQuery) -> Emit:
        return self._all(query.boost)

    def _res_MatchNoneQuery(self, query: q.MatchNoneQuery) -> Emit:
        return self._zeros()

    def _match_terms(self, field: str, terms: list[str]):
        """Resolve analyzed terms to per-segment ids + idf (reader or DFS
        stats)."""
        col = self.seg.text.get(field)
        if col is None:
            return None
        tids, idfs = [], []
        for t in terms:
            tid = col.column.tid(t)
            df, doc_count = self._term_stats(field, t)
            tids.append(tid)
            idfs.append(bm25_idf(df, doc_count) if df > 0 else 0.0)
        return tids, idfs

    def _res_MatchQuery(self, query: q.MatchQuery) -> Emit:
        field = query.field
        if field in ("*", "_all"):
            # all-fields match (ES _all / query_string default): OR over every
            # text field present in the segment — iteration order is part of
            # the plan signature
            self.sig("all-fields", tuple(self.seg.text))
            subs = [self.resolve(q.MatchQuery(
                field=f, text=query.text, operator=query.operator,
                boost=query.boost)) for f in self.seg.text]
            if not subs:
                return self._zeros()

            def emit_all(em):
                scores = mask = None
                for sub in subs:
                    s, m = sub(em)
                    scores = s if scores is None else jnp.maximum(scores, s)
                    mask = m if mask is None else (mask | m)
                return scores, mask
            return emit_all
        if self.seg.text.get(field) is None and (
                field in self.seg.keyword or field in self.seg.numeric):
            # match on keyword/numeric doc values == exact term (ES behavior)
            return self.resolve(q.TermQuery(
                field=field, value=query.text, boost=query.boost))
        analyzer = self._analyzer_for(field, query.analyzer)
        terms = [t.term for t in analyzer.analyze(query.text)]
        if not terms:
            return self._zeros()
        resolved = self._match_terms(field, terms)
        if resolved is None:
            return self._zeros()
        tids, idfs = resolved
        if query.operator == "and":
            required = len(terms)
        elif query.minimum_should_match is not None:
            required = _resolve_msm(query.minimum_should_match, len(terms))
        else:
            required = 1
        similarity = self._similarity_for(field)
        if similarity != "bm25":
            # classic_match and lm_dirichlet_match make a pass over the
            # columns PER TERM, so a pad would cost a read: these lists
            # are not padded and the signature keeps the exact count.
            # reuse the (df, doc_count) per term already gathered by
            # _match_terms — no second stats pass on the planning path
            stats = [self._term_stats(field, t) for t in terms]
            return self._match_alt_similarity(query, field, terms, tids,
                                              similarity, required, stats)
        # required == 1 (the default OR semantics): a doc matches iff any
        # query term hits, and every present term has idf > 0, so
        # mask ≡ scores > 0 — the nmatch accumulation becomes dead code XLA
        # eliminates (T fewer [N, U] compare/reduce passes, the same
        # shortcut the standalone kernel gets for free)
        # guard for DFS-provided stats: a term present in this segment but
        # with global df 0 would have idf 0 — its matches score 0 and the
        # scores>0 shortcut would drop them, diverging from nmatch
        # semantics; fall back to nmatch counting in that (odd) case.
        # The test is the term's LOCAL df: local df 0 means no posting can
        # match here, so idf 0 is harmless — keeping msm1 makes the plan
        # signature independent of which query terms this shard happens to
        # hold (shards of one index must batch together, and the compile
        # cache keys on the signature)
        col_df = np.asarray(self.seg.text[field].column.df)
        all_idf_pos = all(
            idf > 0 or tid < 0 or col_df[tid] == 0
            for tid, idf in zip(tids, idfs))
        # decided by the query's FORM, not by the number `required`
        # resolves to: a one-term `and` counts matches like its longer
        # batch mates, so the signature does not carry the term count
        counted = query.operator == "and" or \
            query.minimum_should_match not in (None, 1)
        msm1 = not counted and all_idf_pos
        self.sig("msm1" if msm1 else "msm")
        # the plan does not carry the exact number of terms either: the
        # lists are padded to a term bucket — the batch's widest, or the
        # query's own — with absent terms (id -1, idf 0.0). bm25_match
        # maps a negative id to a value no slot holds, so a pad adds 0.0
        # to a slot's weight and 0 to its count after the real terms:
        # the arithmetic is the unpadded query's (nmatch, mask and
        # totals exactly; a score to the last bit a program of another
        # width may round its sum along U to), at a compare, a select
        # and an add a slot inside the one pass
        n_terms = max(term_bucket(len(tids)), self.term_floor)
        pad = n_terms - len(tids)
        self.ct.match_terms_real += len(tids)
        self.ct.match_terms_padded += pad
        r_tids = self.c(tids + [-1] * pad, np.int32)
        r_idfs = self.c(idfs + [0.0] * pad, np.float32)
        r_avgdl = self.c(self._avgdl(field), np.float32)
        r_req = None if msm1 else self.c(required, np.int32)
        r_boost = self.c(query.boost, np.float32)
        p = self.ctx.bm25

        def emit(em):
            col = em.seg.text[field]
            scores, nmatch = lexical.bm25_match(
                col.uterms, col.utf, col.doc_len,
                jnp.asarray(em.get(r_tids)), jnp.asarray(em.get(r_idfs)),
                jnp.ones(n_terms, jnp.float32), p.k1, p.b, em.get(r_avgdl))
            if msm1:
                # OR semantics: the bm25 sum is already 0 on non-matching
                # docs, so the mask is just scores > 0 and no re-zeroing
                # where-pass is needed (boost scales 0 to 0)
                mask = scores > 0
                return scores * em.get(r_boost), mask
            mask = nmatch >= em.get(r_req)
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _match_alt_similarity(self, query, field: str, terms: list[str],
                              tids: list[int], similarity: str,
                              required: int,
                              stats: list[tuple[int, int]]) -> Emit:
        """Non-BM25 similarity scoring for match queries (classic TF-IDF
        and LM Dirichlet); the plan signature carries the module name so
        differently-scored fields never share a program."""
        self.sig("match-sim", similarity)
        n_terms = len(tids)
        r_tids = self.c(tids, np.int32)
        r_req = self.c(required, np.int32)
        r_boost = self.c(query.boost, np.float32)
        if similarity == "classic":
            idfs = []
            for df, doc_count in stats:
                idfs.append(1.0 + np.log(max(doc_count, 1)
                                         / (df + 1.0)) if df > 0 else 0.0)
            r_w = self.c(idfs, np.float32)

            def emit(em):
                col = em.seg.text[field]
                scores, nmatch = lexical.classic_match(
                    col.uterms, col.utf, col.doc_len,
                    jnp.asarray(em.get(r_tids)),
                    jnp.asarray(em.get(r_w)),
                    jnp.ones(n_terms, jnp.float32))
                mask = nmatch >= em.get(r_req)
                return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
            return emit
        # lm_dirichlet
        fm = self.ctx.mapper_service.field_mapper(field)
        mu = float((fm.params.get("similarity_mu", 2000.0))
                   if fm is not None else 2000.0)
        fracs = [self._ctf_frac(field, t) for t in terms]
        r_frac = self.c(fracs, np.float32)
        r_mu = self.c(mu, np.float32)

        def emit(em):
            col = em.seg.text[field]
            scores, nmatch = lexical.lm_dirichlet_match(
                col.uterms, col.utf, col.doc_len,
                jnp.asarray(em.get(r_tids)),
                jnp.asarray(em.get(r_frac)),
                jnp.ones(n_terms, jnp.float32), em.get(r_mu))
            mask = nmatch >= em.get(r_req)
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _res_MatchPhraseQuery(self, query: q.MatchPhraseQuery) -> Emit:
        field = query.field
        analyzer = self._analyzer_for(field, query.analyzer)
        toks = analyzer.analyze(query.text)
        if not toks:
            return self._zeros()
        if len(toks) == 1:
            return self.resolve(q.MatchQuery(
                field=field, text=query.text, analyzer=query.analyzer,
                boost=query.boost))
        col = self.seg.text.get(field)
        if col is not None and not col.column.has_positions:
            raise QueryParsingError(
                f"field [{field}] was not indexed with positions — "
                f"phrase queries need index_options [positions]")
        resolved = self._match_terms(field, [t.term for t in toks])
        if resolved is None:
            return self._zeros()
        tids, idfs = resolved
        deltas = [t.position - toks[0].position for t in toks]
        slop = query.slop
        self.sig("phrase", tuple(deltas), slop)
        self.ct.positions_needed.add(field)
        p = self.ctx.bm25
        r_tids = [self.c(t, np.int32) for t in tids]
        r_idfs = self.c(idfs, np.float32)
        r_sum_idf = self.c(sum(idfs), np.float32)
        r_avgdl = self.c(self._avgdl(field), np.float32)
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            col = em.seg.text[field]
            tid_scalars = [em.get(r) for r in r_tids]
            if slop > 0:
                scores, mask = phrase_ops.sloppy_phrase_score(
                    col.tokens, col.doc_len, tid_scalars, deltas, slop,
                    jnp.asarray(em.get(r_idfs)), p.k1, p.b, em.get(r_avgdl))
            else:
                scores, mask = phrase_ops.phrase_score(
                    col.tokens, col.doc_len, tid_scalars, deltas,
                    em.get(r_sum_idf), p.k1, p.b, em.get(r_avgdl))
            return scores * em.get(r_boost), mask
        return emit

    def _res_MultiMatchQuery(self, query: q.MultiMatchQuery) -> Emit:
        self.sig("multi_match", query.type, query.tie_breaker > 0,
                 len(query.fields))
        subs = []
        for fspec in query.fields:
            fname, _, fboost = fspec.partition("^")
            boost = float(fboost) if fboost else 1.0
            if query.type == "phrase":
                sub = q.MatchPhraseQuery(field=fname, text=query.text,
                                         boost=boost)
            else:
                sub = q.MatchQuery(field=fname, text=query.text,
                                   operator=query.operator, boost=boost)
            subs.append(self.resolve(sub))
        if not subs:
            return self._zeros()
        mm_type = query.type
        tie = query.tie_breaker
        r_tie = self.c(tie, np.float32) if tie > 0 else None
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            scores = mask = None
            for sub in subs:
                s, m = sub(em)
                if scores is None:
                    scores, mask = s, m
                    continue
                mask = mask | m
                if mm_type == "most_fields":
                    scores = scores + s
                else:  # best_fields: max + tie_breaker * others
                    mx = jnp.maximum(scores, s)
                    if r_tie is not None:
                        scores = mx + em.get(r_tie) * (scores + s - mx)
                    else:
                        scores = mx
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _keyword_or_text_term_mask(self, field: str, value):
        """→ mask emit for an exact term on keyword/numeric/text columns."""
        fm = self.ctx.mapper_service.field_mapper(field)
        kcol = self.seg.keyword.get(field)
        if kcol is not None:
            self.sig("term-kw", field)
            r_ord = self.c(kcol.column.ord(str(value)), np.int32)
            return lambda em: filter_ops.keyword_term(
                em.seg.keyword[field].ords, em.get(r_ord))
        ncol = self.seg.numeric.get(field)
        if ncol is not None or (fm is not None and fm.kind == KIND_NUMERIC):
            if ncol is None:
                self.sig("term-none", field)
                return lambda em: jnp.zeros(em.n, bool)
            self.sig("term-num", field)
            hi, lo = dd_split(self._numeric_value(field, value))
            r_hi = self.c(hi, np.float32)
            r_lo = self.c(lo, np.float32)

            def emit(em):
                col = em.seg.numeric[field]
                return filter_ops.numeric_term(col.hi, col.lo, col.exists,
                                               em.get(r_hi), em.get(r_lo))
            return emit
        tcol = self.seg.text.get(field)
        if tcol is not None:
            self.sig("term-text", field)
            r_tid = self.c(tcol.column.tid(str(value)), np.int32)
            return lambda em: lexical.term_filter(
                em.seg.text[field].uterms, em.get(r_tid))
        self.sig("term-none", field)
        return lambda em: jnp.zeros(em.n, bool)

    def _res_TermQuery(self, query: q.TermQuery) -> Emit:
        # term on text fields scores BM25 like a single-term match (Lucene
        # TermQuery); on keyword/numeric doc values it is constant-score.
        fm = self.ctx.mapper_service.field_mapper(query.field)
        if fm is not None and fm.type == "ip" and \
                isinstance(query.value, str) and "/" in query.value:
            # CIDR term → numeric interval (IpFieldMapper termQuery)
            from elasticsearch_tpu.mapping.mapper import cidr_range
            lo, hi = cidr_range(query.value)
            return self.resolve(q.RangeQuery(field=query.field, gte=lo,
                                             lte=hi, boost=query.boost))
        tcol = self.seg.text.get(query.field)
        if tcol is not None and self.seg.keyword.get(query.field) is None:
            return self.resolve(q.MatchQuery(
                field=query.field, text=str(query.value), analyzer="keyword",
                boost=query.boost))
        mask_emit = self._keyword_or_text_term_mask(query.field, query.value)
        r_boost = self.c(query.boost, np.float32)
        return lambda em: bool_ops.constant_score(mask_emit(em),
                                                  em.get(r_boost))

    def _res_TermsQuery(self, query: q.TermsQuery) -> Emit:
        field = query.field
        kcol = self.seg.keyword.get(field)
        r_boost = self.c(query.boost, np.float32)
        if kcol is not None:
            self.sig("terms-kw", field)
            qords = [kcol.column.ord(str(v)) for v in query.values]
            r_ords = self.c(qords or [-1], np.int32)

            def emit(em):
                mask = filter_ops.keyword_terms(
                    em.seg.keyword[field].ords, jnp.asarray(em.get(r_ords)))
                return bool_ops.constant_score(mask, em.get(r_boost))
            return emit
        self.sig("terms-any", field, len(query.values))
        mask_emits = [self._keyword_or_text_term_mask(field, v)
                      for v in query.values]

        def emit(em):
            mask = jnp.zeros(em.n, bool)
            for me in mask_emits:
                mask = mask | me(em)
            return bool_ops.constant_score(mask, em.get(r_boost))
        return emit

    def _res_RangeQuery(self, query: q.RangeQuery) -> Emit:
        field = query.field
        r_boost = self.c(query.boost, np.float32)
        ncol = self.seg.numeric.get(field)
        if ncol is not None:
            # gte/gt (and lte/lt) apply independently; effective bound is
            # the tightest (ES RangeQueryParser applies each given bound).
            # Exclusivity is a comparison-strictness flag, not a
            # nextafter-bumped value — the f64 neighbor of a small bound
            # underflows the f32 dd split (gt:0 would become gte:0).
            lo_v, lo_strict = -np.inf, False
            if query.gte is not None:
                lo_v = np.float64(self._numeric_value(field, query.gte))
            if query.gt is not None:
                g = np.float64(self._numeric_value(field, query.gt))
                if g >= lo_v:
                    lo_v, lo_strict = g, True
            hi_v, hi_strict = np.inf, False
            if query.lte is not None:
                hi_v = np.float64(self._numeric_value(field, query.lte))
            if query.lt is not None:
                l_ = np.float64(self._numeric_value(field, query.lt))
                if l_ <= hi_v:
                    hi_v, hi_strict = l_, True
            self.sig("range-num", field)
            ghi, glo = dd_split(lo_v)
            lhi, llo = dd_split(hi_v)
            r_ghi = self.c(ghi, np.float32)
            r_glo = self.c(glo, np.float32)
            r_lhi = self.c(lhi, np.float32)
            r_llo = self.c(llo, np.float32)
            r_gx = self.c(np.float32(1.0 if lo_strict else 0.0))
            r_lx = self.c(np.float32(1.0 if hi_strict else 0.0))

            def emit(em):
                col = em.seg.numeric[field]
                mask = filter_ops.numeric_range(
                    col.hi, col.lo, col.exists,
                    em.get(r_ghi), em.get(r_glo),
                    em.get(r_lhi), em.get(r_llo),
                    lo_strict=em.get(r_gx), hi_strict=em.get(r_lx))
                return bool_ops.constant_score(mask, em.get(r_boost))
            return emit
        kcol = self.seg.keyword.get(field)
        if kcol is not None:
            self.sig("range-kw", field)
            vocab = kcol.column.vocab
            lo_ord = 0
            hi_ord = len(vocab)
            # tightest-bound combination, same discipline as the numeric
            # branch (each given bound applies; ordinal intervals make
            # gt/lt exact without strictness flags)
            if query.gte is not None:
                lo_ord = max(lo_ord, _bisect_left(vocab, str(query.gte)))
            if query.gt is not None:
                lo_ord = max(lo_ord, _bisect_right(vocab, str(query.gt)))
            if query.lte is not None:
                hi_ord = min(hi_ord, _bisect_right(vocab, str(query.lte)))
            if query.lt is not None:
                hi_ord = min(hi_ord, _bisect_left(vocab, str(query.lt)))
            r_lo = self.c(lo_ord, np.int32)
            r_hi = self.c(hi_ord, np.int32)

            def emit(em):
                mask = filter_ops.keyword_ord_range(
                    em.seg.keyword[field].ords, em.get(r_lo), em.get(r_hi))
                return bool_ops.constant_score(mask, em.get(r_boost))
            return emit
        return self._zeros()

    def _res_ExistsQuery(self, query: q.ExistsQuery) -> Emit:
        f = query.field
        r_boost = self.c(query.boost, np.float32)
        if f in self.seg.numeric:
            self.sig("exists", "num", f)
            mask_emit = lambda em: em.seg.numeric[f].exists   # noqa: E731
        elif f in self.seg.keyword:
            self.sig("exists", "kw", f)
            mask_emit = lambda em: (                          # noqa: E731
                em.seg.keyword[f].ords >= 0).any(axis=1)
        elif f in self.seg.text:
            self.sig("exists", "text", f)
            mask_emit = lambda em: em.seg.text[f].doc_len > 0  # noqa: E731
        elif f in self.seg.vector:
            self.sig("exists", "vec", f)   # reads only the [N] exists mask
            mask_emit = lambda em: em.seg.vector[f].exists    # noqa: E731
        elif f in self.seg.geo:
            self.sig("exists", "geo", f)
            mask_emit = lambda em: em.seg.geo[f].exists       # noqa: E731
        else:
            self.sig("exists", "none", f)
            mask_emit = lambda em: jnp.zeros(em.n, bool)      # noqa: E731
        return lambda em: bool_ops.constant_score(mask_emit(em),
                                                  em.get(r_boost))

    # --- vocab-scan leaf family (prefix/wildcard/regexp/fuzzy) -------------

    def _vocab_scan_mask(self, field: str, pred):
        """Expand a term predicate against per-segment vocabularies —
        Lucene's MultiTermQuery rewrite (TermsEnum scan) stays host-side.
        Matching term-id lists are padded to power-of-2 buckets so queries
        with different expansion counts share compiled programs."""
        kcol = self.seg.keyword.get(field)
        if kcol is not None:
            self.sig("scan-kw", field)
            qords = [i for i, v in enumerate(kcol.column.vocab) if pred(v)]
            if not qords:
                self.sig("scan-empty")
                return lambda em: jnp.zeros(em.n, bool)
            r_ords = self.c(_pad_pow2(qords, -1), np.int32)
            return lambda em: filter_ops.keyword_terms(
                em.seg.keyword[field].ords, jnp.asarray(em.get(r_ords)))
        tcol = self.seg.text.get(field)
        if tcol is not None:
            self.sig("scan-text", field)
            tids = [i for i, t in enumerate(tcol.column.terms) if pred(t)]
            if not tids:
                self.sig("scan-empty")
                return lambda em: jnp.zeros(em.n, bool)
            r_tids = self.c(_pad_pow2(tids, -1), np.int32)

            def emit(em):
                qt = jnp.asarray(em.get(r_tids))
                uterms = em.seg.text[field].uterms
                hit = (uterms[:, :, None] == qt[None, None, :]) & \
                    (qt[None, None, :] >= 0)
                return hit.any(axis=(1, 2))
            return emit
        self.sig("scan-none", field)
        return lambda em: jnp.zeros(em.n, bool)

    def _constant_mask_emit(self, mask_emit, boost: float) -> Emit:
        r_boost = self.c(boost, np.float32)
        return lambda em: bool_ops.constant_score(mask_emit(em),
                                                  em.get(r_boost))

    def _res_PrefixQuery(self, query: q.PrefixQuery) -> Emit:
        kcol = self.seg.keyword.get(query.field)
        if kcol is not None:   # sorted vocab → ordinal interval, no scan
            self.sig("prefix-kw", query.field)
            field = query.field
            vocab = kcol.column.vocab
            r_lo = self.c(_bisect_left(vocab, query.value), np.int32)
            r_hi = self.c(_bisect_left(vocab, query.value + "￿"),
                          np.int32)
            return self._constant_mask_emit(
                lambda em: filter_ops.keyword_ord_range(
                    em.seg.keyword[field].ords, em.get(r_lo), em.get(r_hi)),
                query.boost)
        return self._constant_mask_emit(
            self._vocab_scan_mask(query.field, multi_term_pred(query)),
            query.boost)

    def _res_WildcardQuery(self, query: q.WildcardQuery) -> Emit:
        return self._constant_mask_emit(
            self._vocab_scan_mask(query.field, multi_term_pred(query)),
            query.boost)

    def _res_RegexpQuery(self, query: q.RegexpQuery) -> Emit:
        return self._constant_mask_emit(
            self._vocab_scan_mask(query.field, multi_term_pred(query)),
            query.boost)

    def _res_FuzzyQuery(self, query: q.FuzzyQuery) -> Emit:
        return self._constant_mask_emit(
            self._vocab_scan_mask(query.field, multi_term_pred(query)),
            query.boost)

    def _res_ParentIdsQuery(self, query: q.ParentIdsQuery) -> Emit:
        """Join-result lookup: doc matches when its `field` value (_id or
        the _parent keyword column) keys `id_scores`; score = mapped value
        (host-computed by ShardSearcher._rewrite_joins)."""
        vals = np.zeros(self.n, np.float32)
        hits = np.zeros(self.n, bool)
        seg = self.seg.seg
        if query.field == "_id":
            for local, did in enumerate(seg.ids):
                s = query.id_scores.get(did)
                if s is not None:
                    vals[local] = s
                    hits[local] = True
        else:
            col = seg.keyword_fields.get(query.field)
            if col is not None:
                per_ord = np.array(
                    [query.id_scores.get(v, np.nan) for v in col.vocab],
                    np.float64)
                first = np.asarray(col.ords[:seg.num_docs, 0])
                ok = first >= 0
                looked = np.where(ok, per_ord[np.maximum(first, 0)],
                                  np.nan)
                hit = ~np.isnan(looked)
                hits[:seg.num_docs] = hit
                vals[:seg.num_docs] = np.where(hit, looked, 0.0)
        r_vals = self.c(vals)
        r_hits = self.c(hits)
        r_boost = self.c(query.boost, np.float32)
        return lambda em: (jnp.asarray(em.get(r_vals))
                           * em.get(r_boost),
                           jnp.asarray(em.get(r_hits)))

    def _res_IdsQuery(self, query: q.IdsQuery) -> Emit:
        wanted = set(query.values)
        hits = np.zeros(self.n, bool)
        for local, did in enumerate(self.seg.seg.ids):
            if did in wanted:
                hits[local] = True
        r_hits = self.c(hits)
        r_boost = self.c(query.boost, np.float32)
        return lambda em: bool_ops.constant_score(
            jnp.asarray(em.get(r_hits)), em.get(r_boost))

    # ------------------------------------------------------------- compound

    def _res_BoolQuery(self, query: q.BoolQuery) -> Emit:
        self.sig("bool", len(query.must), len(query.should),
                 len(query.must_not), len(query.filter))
        must = [self.resolve(sub) for sub in query.must]
        should = [self.resolve(sub) for sub in query.should]
        must_not = [self.resolve_mask(sub) for sub in query.must_not]
        filters = [self.resolve_mask(sub) for sub in query.filter]
        if query.minimum_should_match is not None:
            msm = _resolve_msm(query.minimum_should_match, len(query.should))
        else:
            msm = 1 if (query.should and not query.must and not query.filter) \
                else 0
        r_msm = self.c(msm, np.int32) if should else None
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            scores, mask = bool_ops.combine_bool(
                em.n,
                [e(em) for e in must], [e(em) for e in should],
                [e(em) for e in must_not], [e(em) for e in filters],
                em.get(r_msm) if r_msm is not None else 0)
            return scores * em.get(r_boost), mask
        return emit

    def _res_ConstantScoreQuery(self, query: q.ConstantScoreQuery) -> Emit:
        mask_emit = self.resolve_mask(query.filter_query)
        return self._constant_mask_emit(mask_emit, query.boost)

    def _res_DisMaxQuery(self, query: q.DisMaxQuery) -> Emit:
        self.sig("dis_max", len(query.queries), query.tie_breaker > 0)
        subs = [self.resolve(sub) for sub in query.queries]
        if not subs:
            return self._zeros()
        r_tie = self.c(query.tie_breaker, np.float32) \
            if query.tie_breaker > 0 else None
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            best = total = mask = None
            for sub in subs:
                s, m = sub(em)
                s = jnp.where(m, s, 0.0)
                if best is None:
                    best, total, mask = s, s, m
                    continue
                best = jnp.maximum(best, s)
                total = total + s
                mask = mask | m
            scores = best if r_tie is None else \
                best + em.get(r_tie) * (total - best)
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _res_BoostingQuery(self, query: q.BoostingQuery) -> Emit:
        pos = self.resolve(query.positive or q.MatchAllQuery())
        neg = self.resolve_mask(query.negative or q.MatchNoneQuery())
        r_neg = self.c(query.negative_boost, np.float32)
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            scores, mask = pos(em)
            demote = jnp.where(neg(em), em.get(r_neg),
                               jnp.float32(1.0))
            return scores * demote * em.get(r_boost), mask
        return emit

    def _res_CommonTermsQuery(self, query: q.CommonTermsQuery) -> Emit:
        field = query.field
        analyzer = self._analyzer_for(field, query.analyzer)
        terms = [t.term for t in analyzer.analyze(query.text)]
        if not terms or self.seg.text.get(field) is None:
            return self._zeros()
        # split by document frequency (ExtendedCommonTermsQuery: ≥1 means
        # an absolute df cutoff, <1 a fraction of docCount)
        low, high = [], []
        for t in terms:
            df, doc_count = self._term_stats(field, t)
            cutoff = query.cutoff_frequency if query.cutoff_frequency >= 1 \
                else query.cutoff_frequency * doc_count
            idf = bm25_idf(df, doc_count) if df > 0 else 0.0
            tid = self.seg.text[field].column.tid(t)
            (high if df > cutoff else low).append((tid, idf))
        self.sig("common", len(low), len(high))
        msm_low = len(low) if query.low_freq_operator == "and" else \
            _resolve_msm(query.minimum_should_match_low, len(low)) \
            if query.minimum_should_match_low is not None else 1
        msm_high = len(high) if query.high_freq_operator == "and" else \
            _resolve_msm(query.minimum_should_match_high, len(high)) \
            if query.minimum_should_match_high is not None else 1
        r_avgdl = self.c(self._avgdl(field), np.float32)
        r_boost = self.c(query.boost, np.float32)
        p = self.ctx.bm25

        def group(pairs):
            if not pairs:
                return None
            return (self.c([t for t, _ in pairs], np.int32),
                    self.c([i for _, i in pairs], np.float32), len(pairs))
        g_low, g_high = group(low), group(high)
        r_msm_low = self.c(msm_low, np.int32) if g_low else None
        r_msm_high = self.c(msm_high, np.int32) if g_high else None

        def emit(em):
            col = em.seg.text[field]

            def score_group(g):
                r_tids, r_idfs, n = g
                return lexical.bm25_match(
                    col.uterms, col.utf, col.doc_len,
                    jnp.asarray(em.get(r_tids)), jnp.asarray(em.get(r_idfs)),
                    jnp.ones(n, jnp.float32), p.k1, p.b, em.get(r_avgdl))
            if g_low is not None:
                low_s, low_n = score_group(g_low)
                mask = low_n >= em.get(r_msm_low)
                scores = low_s
                if g_high is not None:
                    high_s, _ = score_group(g_high)
                    scores = scores + high_s
            else:
                high_s, high_n = score_group(g_high)
                mask = high_n >= em.get(r_msm_high)
                scores = high_s
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _res_NestedQuery(self, query: q.NestedQuery) -> Emit:
        """Nested query: resolve the inner query against the path's CHILD
        segment; the emit scatter-reduces child matches onto parent rows
        ((.at[].max/add — a segment-reduce, XLA-native). Children of
        deleted parents are already dead in the child live mask
        (device_reader packing)."""
        path = query.path
        block = self.seg.nested.get(path)
        if block is None:
            return self._zeros()
        score_mode = query.score_mode
        self.sig("nested", path, score_mode)
        inner = SegmentResolver(block.child, self.ctx, self.ct,
                                self.term_floor).resolve(
            query.query or q.MatchAllQuery())
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            blk = em.seg.nested[path]
            child_em = EmitCtx(blk.child, em.consts)
            c_scores, c_mask = inner(child_em)
            ok = c_mask & blk.child.live & (blk.parent >= 0)
            idx = jnp.where(blk.parent >= 0, blk.parent, 0)
            matched = jnp.zeros(em.n, bool).at[idx].max(ok, mode="drop")
            if score_mode == "none":
                scores = matched.astype(jnp.float32)
            elif score_mode in ("max", "min"):
                fill = -jnp.inf if score_mode == "max" else jnp.inf
                red = jnp.full(em.n, fill, jnp.float32)
                contrib = jnp.where(ok, c_scores, fill)
                red = red.at[idx].max(contrib, mode="drop") \
                    if score_mode == "max" \
                    else red.at[idx].min(contrib, mode="drop")
                scores = jnp.where(matched, red, 0.0)
            else:
                ssum = jnp.zeros(em.n, jnp.float32).at[idx].add(
                    jnp.where(ok, c_scores, 0.0), mode="drop")
                if score_mode == "avg":
                    cnt = jnp.zeros(em.n, jnp.float32).at[idx].add(
                        ok.astype(jnp.float32), mode="drop")
                    scores = ssum / jnp.maximum(cnt, 1.0)
                else:                    # sum
                    scores = ssum
            return jnp.where(matched, scores * em.get(r_boost), 0.0), \
                matched
        return emit

    def _res_SpanTermQuery(self, query: q.SpanTermQuery) -> Emit:
        # a lone span_term scores like a single-term match (SpanWeight's
        # sloppyFreq over unit-width spans == term frequency)
        return self.resolve(q.MatchQuery(field=query.field,
                                         text=query.value,
                                         analyzer="keyword",
                                         boost=query.boost))

    def _res_SpanNearQuery(self, query: q.SpanNearQuery) -> Emit:
        if not all(type(c).__name__ == "SpanTermQuery"
                   for c in query.clauses):
            # composite clauses (or/not/multi/masking/nested near) run
            # through the span-algebra min-end framework (ordered only)
            return self._span_score_emit(query, query.boost)
        field = query.clauses[0].field
        col = self.seg.text.get(field)
        if col is None:
            return self._zeros()
        if not col.column.has_positions:
            raise QueryParsingError(
                f"field [{field}] was not indexed with positions — "
                f"span queries need index_options [positions]")
        self.ct.positions_needed.add(field)
        terms = [c.value for c in query.clauses]
        resolved = self._match_terms(field, terms)
        if resolved is None:
            return self._zeros()
        tids, idfs = resolved
        slop = query.slop
        self.sig("span_near", len(tids), slop, query.in_order, field)
        r_tids = [self.c(t, np.int32) for t in tids]
        r_sum_idf = self.c(sum(idfs), np.float32)
        r_avgdl = self.c(self._avgdl(field), np.float32)
        r_boost = self.c(query.boost, np.float32)
        in_order = query.in_order
        n_clauses = len(tids)
        p = self.ctx.bm25

        def emit(em):
            tcol = em.seg.text[field]
            tid_scalars = [em.get(r) for r in r_tids]
            if in_order:
                # ordered spans ≡ sloppy phrase with consecutive expected
                # positions; freq counts anchored matches (the 1/(1+d)
                # sloppyFreq weight is a documented simplification away)
                freq = phrase_ops.sloppy_phrase_count(
                    tcol.tokens, tid_scalars, list(range(n_clauses)), slop)
            else:
                freq = phrase_ops.span_near_freq_unordered(
                    tcol.tokens, tid_scalars, slop)
            scores, mask = phrase_ops.freq_score(
                freq, tcol.doc_len, em.get(r_sum_idf), p.k1, p.b,
                em.get(r_avgdl))
            return scores * em.get(r_boost), mask
        return emit

    # ---- span algebra (ops/spans.py min-end maps) -----------------------

    def _span_ends(self, query):
        """Resolve a span query to its min-end map.

        → (emit_ends(em) → [N, L] i32, sum_idf, field) or None when a
        required field/term is absent from the segment (no spans). The
        reported ``field`` supplies doc_len/avgdl for scoring (the masked
        field for field_masking_span, per FieldMaskingSpanQuery docs).
        """
        from elasticsearch_tpu.ops import spans as span_ops
        t = type(query).__name__
        self.sig("span", t)

        def leaf(field, tids, idfs, multi: bool):
            col = self.seg.text.get(field)
            if col is None or not tids:
                return None
            if not col.column.has_positions:
                raise QueryParsingError(
                    f"field [{field}] was not indexed with positions — "
                    f"span queries need index_options [positions]")
            self.ct.positions_needed.add(field)
            # span_multi expansions weight like ONE term (mean idf of the
            # rewritten set); explicit clauses sum like SpanWeight stats
            sum_idf = (sum(idfs) / len(idfs)) if multi else sum(idfs)
            if len(tids) == 1:
                r_tid = self.c(tids[0], np.int32)
                self.sig("span-term", field)
                return (lambda em: span_ops.term_ends(
                    em.seg.text[field].tokens, em.get(r_tid)),
                    sum_idf, field)
            r_tids = self.c(_pad_pow2(tids, -1), np.int32)
            self.sig("span-terms", field, len(_pad_pow2(tids, -1)))
            return (lambda em: span_ops.term_set_ends(
                em.seg.text[field].tokens, jnp.asarray(em.get(r_tids))),
                sum_idf, field)

        if t == "SpanTermQuery":
            resolved = self._match_terms(query.field, [query.value])
            if resolved is None:
                return None
            tids, idfs = resolved
            return leaf(query.field, tids, idfs, multi=False)

        if t == "SpanMultiQuery":
            inner = query.match
            field = getattr(inner, "field", "")
            col = self.seg.text.get(field)
            if col is None:
                return None
            pred = multi_term_pred(inner)
            if pred is None:
                raise QueryParsingError(
                    f"[span_multi] does not support inner query "
                    f"[{type(inner).__name__}]")
            tids = [i for i, term in enumerate(col.column.terms)
                    if pred(term)]
            if not tids:
                return None
            idfs = []
            for tid in tids:
                df, doc_count = self._term_stats(
                    field, col.column.terms[tid])
                idfs.append(bm25_idf(max(df, 1), doc_count))
            return leaf(field, tids, idfs, multi=True)

        if t == "FieldMaskingSpanQuery":
            plan = self._span_ends(query.query)
            if plan is None:
                return None
            if self.seg.text.get(query.field) is None:
                return None
            emit_e, sum_idf, _inner_field = plan
            self.sig("span-mask", query.field)
            return emit_e, sum_idf, query.field

        if t == "SpanOrQuery":
            plans = [self._span_ends(c) for c in query.clauses]
            plans = [p for p in plans if p is not None]
            if not plans:
                return None
            sum_idf = sum(p[1] for p in plans)
            field = plans[0][2]
            emits = [p[0] for p in plans]

            def emit(em):
                # pad to the widest CHILD map (children may span several
                # underlying token matrices via field_masking_span)
                maps = [e(em) for e in emits]
                L = max(m.shape[1] for m in maps)
                return span_ops.or_ends(
                    [span_ops.pad_ends(m, L) for m in maps])
            return emit, sum_idf, field

        if t == "SpanNearQuery":
            plans = [self._span_ends(c) for c in query.clauses]
            if any(p is None for p in plans) or not plans:
                return None
            sum_idf = sum(p[1] for p in plans)
            field = plans[0][2]
            slop = int(query.slop)
            in_order = bool(query.in_order)
            self.sig("span-near-ends", len(plans), slop, in_order)
            emits = [p[0] for p in plans]
            near = span_ops.near_ordered_ends if in_order \
                else span_ops.near_unordered_ends

            def emit(em):
                maps = [e(em) for e in emits]
                L = max(m.shape[1] for m in maps)
                return near([span_ops.pad_ends(m, L) for m in maps],
                            slop)
            return emit, sum_idf, field

        if t == "SpanNotQuery":
            inc = self._span_ends(query.include)
            if inc is None:
                return None
            exc = self._span_ends(query.exclude)
            if exc is None:
                return inc
            pre, post = int(query.pre), int(query.post)
            self.sig("span-not", pre, post)
            inc_e, sum_idf, field = inc
            exc_e = exc[0]

            def emit(em):
                inc_m, exc_m = inc_e(em), exc_e(em)
                L = max(inc_m.shape[1], exc_m.shape[1])
                return span_ops.not_ends(
                    span_ops.pad_ends(inc_m, L),
                    span_ops.pad_ends(exc_m, L), pre, post)
            return emit, sum_idf, field

        if t == "SpanFirstQuery":
            plan = self._span_ends(query.match)
            if plan is None:
                return None
            end = int(query.end)
            self.sig("span-first", end)
            inner_e, sum_idf, field = plan
            return (lambda em: span_ops.first_ends(inner_e(em), end),
                    sum_idf, field)

        if t in ("SpanContainingQuery", "SpanWithinQuery"):
            big = self._span_ends(query.big)
            little = self._span_ends(query.little)
            if big is None or little is None:
                return None
            big_e, big_idf, big_f = big
            lit_e, lit_idf, lit_f = little
            containing = t == "SpanContainingQuery"

            def emit(em):
                b, li = big_e(em), lit_e(em)
                L = max(b.shape[1], li.shape[1])
                b = span_ops.pad_ends(b, L)
                li = span_ops.pad_ends(li, L)
                return span_ops.containing_ends(b, li) if containing \
                    else span_ops.within_ends(li, b)
            return ((emit, big_idf, big_f) if containing
                    else (emit, lit_idf, lit_f))

        raise QueryParsingError(f"[{t}] is not a span query")

    def _span_score_emit(self, query, boost: float) -> Emit:
        """Top-level span query → scored emit: freq = spans per doc,
        BM25 over (freq, Σ idf) like the span_near scorer."""
        from elasticsearch_tpu.ops import spans as span_ops
        plan = self._span_ends(query)
        if plan is None:
            return self._zeros()
        emit_e, sum_idf, field = plan
        r_sum_idf = self.c(sum_idf, np.float32)
        r_avgdl = self.c(self._avgdl(field), np.float32)
        r_boost = self.c(boost, np.float32)
        p = self.ctx.bm25

        def emit(em):
            freq = span_ops.span_freq(emit_e(em))
            scores, mask = phrase_ops.freq_score(
                freq, em.seg.text[field].doc_len, em.get(r_sum_idf),
                p.k1, p.b, em.get(r_avgdl))
            return scores * em.get(r_boost), mask
        return emit

    def _res_SpanOrQuery(self, query: q.SpanOrQuery) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_SpanNotQuery(self, query: q.SpanNotQuery) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_SpanFirstQuery(self, query: q.SpanFirstQuery) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_SpanContainingQuery(self, query) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_SpanWithinQuery(self, query) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_SpanMultiQuery(self, query: q.SpanMultiQuery) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_FieldMaskingSpanQuery(self, query) -> Emit:
        return self._span_score_emit(query, query.boost)

    def _res_MoreLikeThisQuery(self, query: q.MoreLikeThisQuery) -> Emit:
        fields = query.fields or sorted(self.seg.text)
        self.sig("mlt", tuple(fields), query.include,
                 tuple(query.unlike_texts), len(query.unlike_docs))
        # gather like text per field: raw texts apply to every field;
        # liked docs contribute their own field values
        texts_by_field: dict[str, list[str]] = {f: list(query.like_texts)
                                                for f in fields}
        like_rows: list[tuple[int, int]] = []     # (segment idx, local row)
        for spec in query.like_docs:
            did = str(spec.get("_id", ""))
            for si, seg in enumerate(self.ctx.reader.segments):
                host = seg.seg
                for local, hid in enumerate(host.ids[:host.num_docs]):
                    if hid != did:
                        continue
                    like_rows.append((si, local))
                    src = host.sources[local]
                    for f in fields:
                        v = src.get(f)
                        if isinstance(v, str):
                            texts_by_field[f].append(v)
        # `unlike` terms are struck from the candidate set
        # (MoreLikeThisQuery setUnlikeText)
        unlike_terms: dict[str, set] = {}
        unlike_texts = list(query.unlike_texts)
        for spec in query.unlike_docs:
            did = str(spec.get("_id", ""))
            for seg in self.ctx.reader.segments:
                host = seg.seg
                for local, hid in enumerate(host.ids[:host.num_docs]):
                    if hid == did:
                        src = host.sources[local]
                        unlike_texts.extend(
                            v for v in src.values()
                            if isinstance(v, str))
        # significant-term selection: tf in the like text ≥ min_term_freq,
        # df ≥ min_doc_freq, ranked by idf (MoreLikeThis.createQueue)
        candidates: list[tuple[float, str, str, float]] = []
        for f in fields:
            analyzer = self._analyzer_for(f, None)
            if unlike_texts and f not in unlike_terms:
                unlike_terms[f] = {
                    tok.term for text in unlike_texts
                    for tok in analyzer.analyze(text)}
            tf: dict[str, int] = {}
            for text in texts_by_field[f]:
                for tok in analyzer.analyze(text):
                    tf[tok.term] = tf.get(tok.term, 0) + 1
            for term, n in tf.items():
                if term in unlike_terms.get(f, ()):
                    continue
                if n < query.min_term_freq:
                    continue
                df, doc_count = self._term_stats(f, term)
                if df < query.min_doc_freq or df <= 0:
                    continue
                idf = bm25_idf(df, doc_count)
                candidates.append((idf * n, f, term, idf))
        candidates.sort(key=lambda x: (-x[0], x[1], x[2]))
        picked = candidates[:query.max_query_terms]
        if not picked:
            return self._zeros()
        # one scoring group per field PRESENT in this segment (a field's
        # terms can't match where its column doesn't exist — same zeros
        # semantics as _match_terms; minimum_should_match still counts all
        # picked terms, so docs in such segments need the remaining fields)
        by_field: dict[str, list[tuple[int, float]]] = {}
        for _, f, term, idf in picked:
            col = self.seg.text.get(f)
            if col is None:
                continue
            by_field.setdefault(f, []).append((col.column.tid(term), idf))
        if not by_field:
            return self._zeros()
        msm = _resolve_msm(query.minimum_should_match, len(picked)) \
            if query.minimum_should_match is not None else 1
        self.sig("mlt-groups",
                 tuple((f, len(v)) for f, v in sorted(by_field.items())))
        groups = []
        for f in sorted(by_field):
            pairs = by_field[f]
            groups.append((f,
                           self.c([t for t, _ in pairs], np.int32),
                           self.c([i for _, i in pairs], np.float32),
                           len(pairs)))
        r_msm = self.c(msm, np.int32)
        r_boost = self.c(query.boost, np.float32)
        exclude = None
        if (like_rows or query.exclude_ids) and not query.include:
            my_idx = next((i for i, s in
                           enumerate(self.ctx.reader.segments)
                           if s is self.seg), None)
            hits = np.zeros(self.n, bool)
            for sj, local in like_rows:
                if sj == my_idx:
                    hits[local] = True
            if query.exclude_ids:
                wanted = set(query.exclude_ids)
                host = self.seg.seg
                for local, hid in enumerate(host.ids[:host.num_docs]):
                    if hid in wanted:
                        hits[local] = True
            if hits.any():
                exclude = self.c(hits)
        self.sig("mlt-excl", exclude is not None)
        r_avgdl = {f: self.c(self._avgdl(f), np.float32)
                   for f, *_ in groups}
        p = self.ctx.bm25

        def emit(em):
            scores = jnp.zeros(em.n, jnp.float32)
            nmatch = jnp.zeros(em.n, jnp.int32)
            for f, r_tids, r_idfs, n in groups:
                col = em.seg.text[f]
                s, nm = lexical.bm25_match(
                    col.uterms, col.utf, col.doc_len,
                    jnp.asarray(em.get(r_tids)), jnp.asarray(em.get(r_idfs)),
                    jnp.ones(n, jnp.float32), p.k1, p.b,
                    em.get(r_avgdl[f]))
                scores = scores + s
                nmatch = nmatch + nm
            mask = nmatch >= em.get(r_msm)
            if exclude is not None:
                mask = mask & ~jnp.asarray(em.get(exclude))
            return jnp.where(mask, scores * em.get(r_boost), 0.0), mask
        return emit

    def _res_FunctionScoreQuery(self, query: q.FunctionScoreQuery) -> Emit:
        self.sig("function_score", query.score_mode, query.boost_mode,
                 query.max_boost is not None, query.min_score is not None,
                 tuple((fn.kind, fn.weight is not None,
                        fn.filter_query is not None)
                       for fn in query.functions))
        base_emit = self.resolve(query.query or q.MatchAllQuery())
        fn_emits = []
        for fn in query.functions:
            factor_emit = self._function_factor(fn)
            if fn.weight is not None and fn.kind != "weight":
                r_w = self.c(fn.weight, np.float32)
                factor_emit = (lambda fe, rw: lambda em, s:
                               fe(em, s) * em.get(rw))(factor_emit, r_w)
            fmask_emit = self.resolve_mask(fn.filter_query) \
                if fn.filter_query else None
            r_wsum = self.c(fn.weight if fn.weight is not None else 1.0,
                            np.float32)
            fn_emits.append((factor_emit, fmask_emit, r_wsum))
        score_mode, boost_mode = query.score_mode, query.boost_mode
        r_max_boost = None if query.max_boost is None \
            else self.c(query.max_boost, np.float32)
        r_min_score = None if query.min_score is None \
            else self.c(query.min_score, np.float32)
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            base_scores, base_mask = base_emit(em)
            factors, masks, weights = [], [], []
            for factor_emit, fmask_emit, r_wsum in fn_emits:
                factors.append(factor_emit(em, base_scores))
                masks.append(fmask_emit(em) if fmask_emit is not None
                             else jnp.ones(em.n, bool))
                weights.append(em.get(r_wsum))
            combined = fs_ops.combine_functions(factors, masks, score_mode,
                                                weights=weights)
            if combined is None:
                scores = base_scores
            else:
                mb = None if r_max_boost is None else em.get(r_max_boost)
                scores = fs_ops.apply_boost_mode(base_scores, combined,
                                                 boost_mode, mb)
            mask = base_mask
            if r_min_score is not None:
                mask = mask & (scores >= em.get(r_min_score))
            return scores * em.get(r_boost), mask
        return emit

    def _function_factor(self, fn: q.ScoreFunction):
        """→ factor emit: (em, base_scores) → [N] f32."""
        params = fn.params
        if fn.kind == "weight":
            r_w = self.c(fn.weight or 1.0, np.float32)
            return lambda em, s: fs_ops.weight_factor(em.n, em.get(r_w))
        if fn.kind == "random_score":
            seed = int(params.get("seed", 0))
            self.sig("random", seed)
            r_base = self.c(self.seg.doc_base, np.uint32)
            return lambda em, s: fs_ops.random_score(em.n, seed,
                                                     em.get(r_base))
        if fn.kind == "field_value_factor":
            fname = params["field"]
            ncol = self.seg.numeric.get(fname)
            if ncol is None:
                self.sig("fvf-missing", fname)
                r_missing = self.c(params.get("missing", 1.0), np.float32)
                return lambda em, s: (jnp.full(em.n, 1.0, jnp.float32)
                                      * em.get(r_missing))
            modifier = params.get("modifier", "none")
            missing = params.get("missing")
            self.sig("fvf", fname, modifier, missing is None)
            r_factor = self.c(float(params.get("factor", 1.0)), np.float32)
            r_missing = None if missing is None \
                else self.c(float(missing), np.float32)

            def factor_emit(em, s):
                col = em.seg.numeric[fname]
                return fs_ops.field_value_factor(
                    col.hi, col.exists, factor=em.get(r_factor),
                    modifier=modifier,
                    missing=None if r_missing is None else em.get(r_missing))
            return factor_emit
        if fn.kind in ("gauss", "exp", "linear"):
            return self._decay_factor(fn, params)
        if fn.kind == "script_score":
            script = params.get("script", params)
            if isinstance(script, dict):
                src = script.get("source", script.get("inline", ""))
                sparams = script.get("params", {})
            else:
                src, sparams = str(script), {}
            return self._script_factor(src, sparams)
        raise QueryParsingError(f"unknown score function [{fn.kind}]")

    def _decay_factor(self, fn: q.ScoreFunction, params: dict):
        fname, spec = next(iter(params.items()))
        kind = fn.kind
        origin = spec.get("origin")
        fm = self.ctx.mapper_service.field_mapper(fname)
        geo_col = self.seg.geo.get(fname)
        if geo_col is not None:
            self.sig("decay-geo", fname, kind)
            # geo decay: distance to origin in meters
            if isinstance(origin, dict):
                olat, olon = float(origin["lat"]), float(origin["lon"])
            else:
                olat, olon = (float(x) for x in str(origin).split(","))
            r_olat = self.c(olat, np.float32)
            r_olon = self.c(olon, np.float32)
            r_scale = self.c(q.parse_distance(spec["scale"]), np.float32)
            r_offset = self.c(q.parse_distance(spec.get("offset", 0)),
                              np.float32)
            r_decay = self.c(float(spec.get("decay", 0.5)), np.float32)
            r_zero = self.c(0.0, np.float32)

            def factor_emit(em, s):
                col = em.seg.geo[fname]
                olat_t, olon_t = em.get(r_olat), em.get(r_olon)
                r = 6371008.8
                p1 = jnp.radians(col.lat)
                p2 = jnp.radians(olat_t)
                dphi = jnp.radians(col.lat - olat_t)
                dlmb = jnp.radians(col.lon - olon_t)
                a = jnp.sin(dphi / 2) ** 2 + jnp.cos(p1) * jnp.cos(p2) * \
                    jnp.sin(dlmb / 2) ** 2
                dist = 2 * r * jnp.arcsin(jnp.sqrt(a))
                return fs_ops.decay(dist, col.exists, em.get(r_zero),
                                    em.get(r_scale), em.get(r_offset),
                                    em.get(r_decay), kind)
            return factor_emit
        ncol = self.seg.numeric.get(fname)
        if ncol is None:
            self.sig("decay-missing", fname)
            return lambda em, s: jnp.ones(em.n, jnp.float32)
        self.sig("decay", fname, kind)
        if fm is not None and fm.type == "date":
            origin_v = parse_date(origin) if origin is not None else 0.0
            from elasticsearch_tpu.common.settings import parse_time_value
            scale = parse_time_value(spec["scale"]) * 1000.0
            offset = parse_time_value(spec.get("offset", 0)) * 1000.0
        else:
            origin_v = float(origin if origin is not None else 0.0)
            scale = float(spec["scale"])
            offset = float(spec.get("offset", 0))
        r_origin = self.c(origin_v, np.float32)
        r_scale = self.c(scale, np.float32)
        r_offset = self.c(offset, np.float32)
        r_decay = self.c(float(spec.get("decay", 0.5)), np.float32)

        def factor_emit(em, s):
            col = em.seg.numeric[fname]
            return fs_ops.decay(col.hi, col.exists, em.get(r_origin),
                                em.get(r_scale), em.get(r_offset),
                                em.get(r_decay), kind)
        return factor_emit

    def _feed_script_params(self, params: dict) -> dict:
        """Numeric script params become dynamic constants (vector params as
        f32 arrays); anything else is structural. Returns {key: value-or-
        const-ref marker} where refs are wrapped for emit-time lookup."""
        out = {}
        for key in sorted(params):
            v = params[key]
            if isinstance(v, bool) or isinstance(v, str):
                self.sig("sparam", key, v)
                out[key] = ("static", v)
            elif isinstance(v, (int, float)):
                self.sig("sparam", key, "num")
                out[key] = ("ref", self.c(float(v), np.float32))
            elif isinstance(v, (list, tuple)):
                self.sig("sparam", key, "vec", len(v))
                out[key] = ("ref", self.c(np.asarray(v, np.float32)))
            else:
                self.sig("sparam", key, repr(v))
                out[key] = ("static", v)
        return out

    def _script_factor(self, source: str, params: dict):
        """→ (em, base_scores) → [N] f32 evaluating the sandboxed script."""
        self.sig("script", source)
        param_spec = self._feed_script_params(params)
        compiled = compile_script(source)
        vf = compiled.vector_fields()
        # ScriptContext.get_vector pulls vector columns at emit time; a
        # non-literal field argument means "could be any of them"
        self.ct.vectors_needed.update(
            self.seg.vector if vf is None else vf)

        def factor_emit(em, scores):
            sparams = {k: (em.get(v) if tag == "ref" else v)
                       for k, (tag, v) in param_spec.items()}

            def get_numeric(field):
                ncol = em.seg.numeric.get(field)
                if ncol is None:
                    return (jnp.zeros(em.n, jnp.float32),
                            jnp.zeros(em.n, bool))
                return ncol.hi, ncol.exists

            def get_vector(field):
                vcol = em.seg.vector.get(field)
                if vcol is None:
                    raise QueryParsingError(f"no vector field [{field}]")
                return vcol.vecs, vcol.exists

            ctx = ScriptContext(get_numeric, get_vector, scores, sparams)
            out = compiled.evaluate(ctx)
            return jnp.broadcast_to(jnp.asarray(out, jnp.float32), (em.n,))
        return factor_emit

    def _res_ScriptScoreQuery(self, query: q.ScriptScoreQuery) -> Emit:
        base_emit = self.resolve(query.query or q.MatchAllQuery())
        factor_emit = self._script_factor(query.script, query.params)
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            base_scores, base_mask = base_emit(em)
            scores = factor_emit(em, base_scores)
            return jnp.where(base_mask, scores * em.get(r_boost), 0.0), \
                base_mask
        return emit

    def _res_KnnQuery(self, query: q.KnnQuery) -> Emit:
        field = query.field
        if self.seg.vector.get(field) is None:
            return self._zeros()
        self.ct.vectors_needed.add(field)
        r_qv = self.c(query.query_vector, np.float32)
        r_boost = self.c(query.boost, np.float32)

        def emit(em):
            col = em.seg.vector[field]
            qv = jnp.asarray(em.get(r_qv))
            scores = vector_ops.cosine_scores(col.vecs, col.exists, qv)
            return (scores + 1.0) * em.get(r_boost) * \
                col.exists.astype(jnp.float32), col.exists
        return emit

    def _res_GeoDistanceQuery(self, query: q.GeoDistanceQuery) -> Emit:
        field = query.field
        if self.seg.geo.get(field) is None:
            return self._zeros()
        r_lat = self.c(query.lat, np.float32)
        r_lon = self.c(query.lon, np.float32)
        r_dist = self.c(query.distance_m, np.float32)
        return self._constant_mask_emit(
            lambda em: filter_ops.geo_distance(
                em.seg.geo[field].lat, em.seg.geo[field].lon,
                em.seg.geo[field].exists,
                em.get(r_lat), em.get(r_lon), em.get(r_dist)),
            query.boost)

    def _res_GeoBoundingBoxQuery(self, query: q.GeoBoundingBoxQuery) -> Emit:
        field = query.field
        if self.seg.geo.get(field) is None:
            return self._zeros()
        r_top = self.c(query.top, np.float32)
        r_left = self.c(query.left, np.float32)
        r_bottom = self.c(query.bottom, np.float32)
        r_right = self.c(query.right, np.float32)
        return self._constant_mask_emit(
            lambda em: filter_ops.geo_bounding_box(
                em.seg.geo[field].lat, em.seg.geo[field].lon,
                em.seg.geo[field].exists,
                em.get(r_top), em.get(r_left),
                em.get(r_bottom), em.get(r_right)),
            query.boost)

    def _res_GeoPolygonQuery(self, query: q.GeoPolygonQuery) -> Emit:
        field = query.field
        if self.seg.geo.get(field) is None:
            return self._zeros()
        self.sig("geo-poly", len(query.lats))
        r_lats = self.c(np.asarray(query.lats, np.float32), np.float32)
        r_lons = self.c(np.asarray(query.lons, np.float32), np.float32)
        return self._constant_mask_emit(
            lambda em: filter_ops.geo_polygon(
                em.seg.geo[field].lat, em.seg.geo[field].lon,
                em.seg.geo[field].exists,
                jnp.asarray(em.get(r_lats)), jnp.asarray(em.get(r_lons))),
            query.boost)

    def _res_GeoDistanceRangeQuery(self,
                                   query: q.GeoDistanceRangeQuery) -> Emit:
        field = query.field
        if self.seg.geo.get(field) is None:
            return self._zeros()
        # None bounds encode as -1 (the op treats negatives as unbounded)
        enc = [(-1.0 if v is None else float(v))
               for v in (query.gte_m, query.gt_m, query.lte_m, query.lt_m)]
        refs = [self.c(v, np.float32) for v in enc]
        r_lat = self.c(query.lat, np.float32)
        r_lon = self.c(query.lon, np.float32)
        return self._constant_mask_emit(
            lambda em: filter_ops.geo_distance_range(
                em.seg.geo[field].lat, em.seg.geo[field].lon,
                em.seg.geo[field].exists, em.get(r_lat), em.get(r_lon),
                *(em.get(r) for r in refs)),
            query.boost)

    def _res_GeohashCellQuery(self, query: q.GeohashCellQuery) -> Emit:
        from elasticsearch_tpu.utils.geohash import (
            geohash_decode_bbox, geohash_neighbors)
        field = query.field
        if self.seg.geo.get(field) is None:
            return self._zeros()
        cells = [query.geohash]
        if query.neighbors:
            cells += geohash_neighbors(query.geohash)
        self.sig("geohash-cell", len(cells))
        boxes = []
        for gh in cells:
            lat_lo, lat_hi, lon_lo, lon_hi = geohash_decode_bbox(gh)
            boxes.append(tuple(self.c(v, np.float32)
                               for v in (lat_hi, lon_lo, lat_lo, lon_hi)))

        def mask_emit(em):
            g = em.seg.geo[field]
            out = None
            for top, left, bottom, right in boxes:
                m = filter_ops.geo_bounding_box(
                    g.lat, g.lon, g.exists, em.get(top), em.get(left),
                    em.get(bottom), em.get(right))
                out = m if out is None else out | m
            return out
        return self._constant_mask_emit(mask_emit, query.boost)

    def _res_GeoShapeQuery(self, query: q.GeoShapeQuery) -> Emit:
        from elasticsearch_tpu.ops import geoshape as shape_ops
        from elasticsearch_tpu.utils.geoshape import parse_shape_rings
        field = query.field
        if self.seg.shape.get(field) is None:
            return self._zeros()
        qlats, qlons, qrid, qarea = parse_shape_rings(query.shape)
        relation = query.relation
        if relation not in ("intersects", "disjoint", "within", "contains"):
            raise QueryParsingError(
                f"unknown geo_shape relation [{relation}]")
        # ring structure is static (part of the traced program); only
        # the vertex coordinates ride the const table
        qrid_np = np.asarray(qrid, np.int32)
        qarea_np = np.asarray(qarea, bool)
        self.sig("geo-shape", relation, len(qlats),
                 tuple(qrid), tuple(qarea))
        r_lats = self.c(np.asarray(qlats, np.float32), np.float32)
        r_lons = self.c(np.asarray(qlons, np.float32), np.float32)
        return self._constant_mask_emit(
            lambda em: shape_ops.shape_relation(
                em.seg.shape[field].lats, em.seg.shape[field].lons,
                em.seg.shape[field].nv, em.seg.shape[field].exists,
                em.seg.shape[field].rid, em.seg.shape[field].area,
                jnp.asarray(em.get(r_lats)), jnp.asarray(em.get(r_lons)),
                qrid_np, qarea_np, relation),
            query.boost)

    def _res_IndicesQuery(self, query: q.IndicesQuery) -> Emit:
        name = self.ctx.index_name
        # per-shard branch pick (IndicesQueryParser): a standalone
        # searcher with no index name takes the match branch
        if name is None or name in query.indices:
            picked = query.query or q.MatchAllQuery()
        else:
            picked = query.no_match_query or q.MatchAllQuery()
        self.sig("indices", name in query.indices if name else True)
        return self.resolve(picked)


class SegmentExecutor:
    """Eager facade: resolve + emit immediately against the real segment.

    The per-op fallback path and the parity oracle for the compiled path —
    both run the SAME emit closures, so they cannot drift."""

    def __init__(self, seg: DeviceSegment, ctx: ExecutionContext):
        self.seg = seg
        self.ctx = ctx
        self.n = seg.padded_docs

    def execute(self, query: q.Query):
        """→ (scores [N] f32, mask [N] bool); live-mask applied by caller."""
        ct = ConstTable()
        emit = SegmentResolver(self.seg, self.ctx, ct).resolve(query)
        # materialize any LAZY columns the plan touches (tokens / vecs stay
        # host-side numpy until first use — device_reader.DeviceSegment
        # .lazy_put) so the eager path doesn't re-transfer them per query
        from elasticsearch_tpu.search import jit_exec

        def materialize(seg):
            for f in ct.positions_needed:
                col = seg.text.get(f)
                if col is not None:       # nested-child fields live in the
                    jit_exec._fetch(seg, col, "tokens")   # child segment
            for f in ct.vectors_needed:
                col = seg.vector.get(f)
                if col is not None:
                    jit_exec._fetch(seg, col, "vecs")
            for blk in seg.nested.values():
                materialize(blk.child)
        materialize(self.seg)
        return emit(EmitCtx(self.seg, [jnp.asarray(v) for v in ct.values]))

    def match_mask(self, query: q.Query):
        return self.execute(query)[1]


def _resolve_msm(msm, num_clauses: int) -> int:
    """minimum_should_match: int, negative int, or percentage string."""
    if isinstance(msm, int):
        return msm if msm >= 0 else max(num_clauses + msm, 0)
    s = str(msm).strip()
    if s.endswith("%"):
        pct = float(s[:-1])
        val = int(num_clauses * pct / 100.0) if pct >= 0 \
            else num_clauses - int(num_clauses * -pct / 100.0)
        return max(val, 0)
    return int(s)


def _pad_pow2(ids: list[int], fill: int) -> list[int]:
    """Pad an id list to the next power-of-2 length so vocab-expansion
    queries (wildcard/fuzzy/regexp) share compiled programs per bucket."""
    n = max(len(ids), 1)
    target = 1 << (n - 1).bit_length()
    return ids + [fill] * (target - len(ids))


def _bisect_left(vocab: list[str], v: str) -> int:
    import bisect
    return bisect.bisect_left(vocab, v)


def _bisect_right(vocab: list[str], v: str) -> int:
    import bisect
    return bisect.bisect_right(vocab, v)
