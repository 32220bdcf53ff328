#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served search path starts
and answers correctly on the TPU.

One process drives the system the way a user does: a ``Node`` and a
``RestServer`` started as ``elasticsearch_tpu.bootstrap.main`` starts
them, then HTTP only — create an index, ``_bulk`` + ``_refresh``, the
1M-doc seeded corpus installed with the engine's bulk columnar ingest,
the request shapes users send, a second index for the opt-in lanes — and
then reads the books that prove the chip did the work: no compiled lane
fell back to eager, the breaker never tripped, the watchdog saw no
stall, and every expected lane dispatched.

    python chip_smoke.py                  # one TPU chip (the driver's run)
    python chip_smoke.py --chips 4        # the mesh lanes on four chips
    python chip_smoke.py --rehearse-cpu --docs 20000   # no chip: never ok

Exit codes: 0 = every phase passed on a TPU; 3 = a CPU rehearsal got
through every phase (its last line says ``"ok": false``); anything else
= failed. Every timing printed here is smoke, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

VOCAB = 30_000            # make_corpus shape: vocab 30k,
MEAN_LEN = 56             # mean length 56,
MAX_UNIQUE = 80           # unique-term axis trimmed to what is used
BULK_DOCS = 4096          # docs that take the per-document write path
QUERY_TERMS = 4
VEC_DIMS = 768
LANES_DOCS = 32_768       # the opt-in lanes' index
LANES_VOCAB = 2_000


def result_line(ok: bool, device: dict, rehearsal: bool = False) -> str:
    """The last line of standard output. ``"ok": true`` is reserved for
    a run on a TPU; a rehearsal can never produce it."""
    doc: dict = {"ok": bool(ok) and not rehearsal
                 and device.get("platform") == "tpu"}
    if rehearsal:
        doc["rehearsal"] = True
    doc["device"] = {"platform": device["platform"],
                     "kind": device["kind"], "count": device["count"]}
    return json.dumps(doc)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def phase(name: str, fn, *args):
    """Run one phase and print its wall seconds — as information."""
    t0 = time.perf_counter()
    out = fn(*args)
    say(f"phase {name}: {time.perf_counter() - t0:.2f} s "
        f"(smoke, not a benchmark)")
    return out


class Http:
    """A user's client: one keep-alive connection to the REST port."""

    def __init__(self, host: str, port: int):
        self.conn = http.client.HTTPConnection(host, port, timeout=900)

    def call(self, method: str, path: str, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        self.conn.request(method, path, body=body, headers={
            "Content-Type": "application/json"} if body else {})
        resp = self.conn.getresponse()
        raw = resp.read()
        check(resp.status < 300,
              f"{method} {path} → HTTP {resp.status}: {raw[:400]!r}")
        ctype = resp.getheader("Content-Type") or ""
        return json.loads(raw) if "json" in ctype else raw.decode()

    def search(self, index: str, body: dict) -> dict:
        out = self.call("POST", f"/{index}/_search", body)
        check(out["_shards"]["failed"] == 0 and not out.get("timed_out"),
              f"search on [{index}] failed shards: {out['_shards']}")
        return out

    def msearch(self, index: str, bodies: list) -> list:
        lines = []
        for b in bodies:
            lines += [json.dumps({"index": index}), json.dumps(b)]
        out = self.call("POST", "/_msearch", "\n".join(lines) + "\n")
        for r in out["responses"]:
            check("error" not in r and r["_shards"]["failed"] == 0,
                  f"msearch item failed: {str(r)[:300]}")
        return out["responses"]


def hits_of(resp: dict):
    import numpy as np
    hits = resp["hits"]["hits"]
    return (np.array([int(h["_id"]) for h in hits], np.int64),
            np.array([h["_score"] for h in hits], np.float64))


# ---------------------------------------------------------------------------
# corpus → index
# ---------------------------------------------------------------------------

def make_corpus(rng, n_docs: int, vocab: int, mean_len: int, max_unique: int,
                chunk: int = 1_000_000):
    """Vectorized Zipf-ish corpus directly in packed column form (chunked:
    the f64 sampling scratch of a large corpus would not fit at once).
    Poisson lengths clipped to [8, 112], terms from a power law over the
    vocabulary. ``tests/test_chip_smoke.py`` pins the arrays by hash: the
    AOT tests' shapes and the oracle comparisons rest on them."""
    import numpy as np
    lens = np.clip(rng.poisson(mean_len, n_docs), 8, 112).astype(np.int32)
    L = int(lens.max())
    U = max_unique
    toks = np.full((n_docs, L), -1, np.int32)
    uterms = np.full((n_docs, U), -1, np.int32)
    utf = np.zeros((n_docs, U), np.float32)
    df = np.zeros(vocab, np.int64)
    for lo in range(0, n_docs, chunk):
        hi = min(lo + chunk, n_docs)
        n = hi - lo
        ranks = (rng.pareto(1.1, size=(n, L)) + 1)
        tk = np.minimum((ranks * 3).astype(np.int64),
                        vocab - 1).astype(np.int32)
        del ranks
        mask = np.arange(L)[None, :] < lens[lo:hi, None]
        tk = np.where(mask, tk, -1)
        toks[lo:hi] = tk

        # unique terms + counts per row (vectorized)
        order = np.argsort(tk, axis=1, kind="stable")
        st = np.take_along_axis(tk, order, axis=1)
        new = np.ones_like(st, dtype=bool)
        new[:, 1:] = st[:, 1:] != st[:, :-1]
        new &= st >= 0
        uidx = np.cumsum(new, axis=1) - 1          # unique slot per token
        rows = np.broadcast_to(np.arange(lo, hi)[:, None], (n, L))
        valid = (st >= 0) & (uidx < U)
        np.add.at(utf, (rows[valid], uidx[valid]), 1.0)
        first = new & valid
        uterms[rows[first], uidx[first]] = st[first]
        np.add.at(df, uterms[lo:hi][uterms[lo:hi] >= 0], 1)
    # trim the unique-term axis to what the corpus actually used
    used = int(np.argmax((uterms >= 0).any(axis=0)[::-1]))
    u_eff = U - used if (uterms >= 0).any() else 1
    return uterms[:, :u_eff], utf[:, :u_eff], lens, df, toks


def make_queries(rng, n_queries: int, terms: int, df):
    """Query terms sampled from the corpus distribution (common + rare mix)."""
    import numpy as np
    present = np.nonzero(df > 0)[0]
    w = df[present].astype(np.float64)
    w /= w.sum()
    return rng.choice(present, size=(n_queries, terms), p=w).astype(np.int32)


class Corpus:
    """The seeded corpus of ``make_corpus`` plus the columns the filter /
    aggregation requests read."""

    def __init__(self, rng, n_docs: int, vocab: int):
        import numpy as np
        self.n_docs, self.vocab = n_docs, vocab
        (self.uterms, self.utf, self.lens, self.df,
         self.toks) = make_corpus(rng, n_docs, vocab, MEAN_LEN, MAX_UNIQUE)
        w = len(str(vocab - 1))
        self.term_names = [f"t{i:0{w}d}" for i in range(vocab)]
        self.rank = rng.random(n_docs) * 100.0
        self.cat = rng.integers(0, 16, n_docs).astype(np.int32)
        self.cat_names = [f"cat{i:02d}" for i in range(16)]

    def text(self, row: int) -> str:
        return " ".join(self.term_names[t] for t in self.toks[row]
                        if t >= 0)

    def query_text(self, qtids) -> str:
        return " ".join(self.term_names[int(t)] for t in qtids)

    def packed_segment(self, lo: int, hi: int, vecs=None):
        """Rows [lo, hi) as one pow2-bucketed columnar segment — the
        engine's bulk ingest (no positions: BM25 does not read them)."""
        import numpy as np
        from elasticsearch_tpu.index.segment import (
            KeywordFieldColumn, NumericFieldColumn, Segment,
            doc_count_bucket)
        rows = hi - lo
        np_rows = doc_count_bucket(rows)

        def pad(a, fill):
            out = np.full((np_rows,) + a.shape[1:], fill, a.dtype)
            out[:rows] = a[lo:hi]
            return out

        seg_df = np.zeros(self.vocab, np.int64)
        ut = self.uterms[lo:hi]
        np.add.at(seg_df, ut[ut >= 0], 1)
        exists = np.zeros(np_rows, bool)
        exists[:rows] = True
        vectors = None
        if vecs is not None:
            padded = np.zeros((np_rows, vecs.shape[1]), np.float32)
            padded[:rows] = vecs[lo:hi]
            vectors = {"vec": (padded, exists.copy())}
        seg = Segment.from_packed_text(
            0, "body", terms=self.term_names, tokens=None,
            uterms=pad(self.uterms, -1), utf=pad(self.utf, 0.0),
            doc_len=pad(self.lens, 0), df=seg_df, num_docs=rows,
            ids=[str(lo + i) for i in range(rows)]
            + [""] * (np_rows - rows), vectors=vectors)
        seg.numeric_fields["rank"] = NumericFieldColumn(
            values=pad(self.rank, 0.0), exists=exists.copy())
        seg.keyword_fields["cat"] = KeywordFieldColumn(
            vocab=list(self.cat_names), ords=pad(self.cat[:, None], -1))
        return seg


def create_index(http: Http, name: str, *, impact: bool = False,
                 vec_dims: int = 0, shards: int = 1) -> None:
    props = {"body": {"type": "text", "analyzer": "whitespace"},
             "rank": {"type": "double"}, "cat": {"type": "keyword"}}
    if vec_dims:
        props["vec"] = {"type": "dense_vector", "dims": vec_dims}
    settings = {"number_of_shards": shards, "number_of_replicas": 0}
    if impact:
        settings["index.search.impact_plane"] = True
    http.call("PUT", f"/{name}", {
        "settings": settings, "mappings": {"_doc": {"properties": props}}})
    health = http.call(
        "GET", f"/_cluster/health/{name}?wait_for_status=green&timeout=60s")
    check(health["status"] == "green" and not health["timed_out"],
          f"[{name}] did not turn green: {health}")


def bulk_index(http: Http, name: str, corpus: Corpus, lo: int,
               hi: int) -> None:
    """Rows [lo, hi) through ``_bulk``: the per-document write path
    (analysis, translog, segment build) and, at the refresh, the device
    block upload of the segment it makes."""
    for start in range(lo, hi, 1024):
        lines = []
        for row in range(start, min(start + 1024, hi)):
            lines.append(json.dumps({"index": {"_id": str(row)}}))
            lines.append(json.dumps({
                "body": corpus.text(row), "rank": float(corpus.rank[row]),
                "cat": corpus.cat_names[int(corpus.cat[row])]}))
        out = http.call("POST", f"/{name}/_doc/_bulk",
                        "\n".join(lines) + "\n")
        check(not out["errors"], f"_bulk into [{name}] reported errors")
    http.call("POST", f"/{name}/_refresh")


def install_packed(node, http: Http, name: str, corpus: Corpus, lo: int,
                   hi: int, vecs=None) -> None:
    engine = node.indices_service.indices[name].engine(0)
    engine.install_segment(corpus.packed_segment(lo, hi, vecs),
                           track_versions=False)
    http.call("POST", f"/{name}/_refresh")
    count = http.call("GET", f"/{name}/_count")["count"]
    check(count == corpus.n_docs,
          f"[{name}] holds {count} docs, expected {corpus.n_docs}")


def hbm_report(http: Http, totals: bool = False) -> dict:
    """``GET _cat/hbm`` → bytes per index and per device."""
    text = http.call("GET", "/_cat/hbm?h=index,device,bytes"
                     + ("&totals=true" if totals else ""))
    per_index: dict = {}
    per_device: dict = {}
    for line in text.splitlines():
        index, device, nbytes = line.split()
        if index == "_total":
            per_device[device] = int(nbytes)
        else:
            per_index[index] = per_index.get(index, 0) + int(nbytes)
    return {"per_index": per_index, "per_device": per_device}


# ---------------------------------------------------------------------------
# the proof that the chip did the work
# ---------------------------------------------------------------------------

BAD_REASONS = ("device-error", "device-stall", "plan-error")


def lane_dispatches() -> dict:
    from elasticsearch_tpu.observability import costs
    out: dict = {}
    for nid in (costs.node_ids() or [""]):
        for lane, ent in costs.lane_rollup(nid).items():
            out[lane] = out.get(lane, 0) + ent["dispatches"]
    return out


def longest_compile() -> tuple:
    from elasticsearch_tpu.observability import costs
    worst = (0.0, "-", "-")
    for nid in (costs.node_ids() or [""]):
        for rec in costs.table(nid).records():
            if rec.compiles:
                ms = rec.compile_ms / rec.compiles
                if ms > worst[0]:
                    worst = (ms, rec.lane, rec.key_id)
    return worst


def assert_chip_did_the_work(expected_lanes, platform: str) -> None:
    import jax
    from elasticsearch_tpu.search import jit_exec
    from elasticsearch_tpu.search.watchdog import dispatch_watchdog
    st = jit_exec.cache_stats()
    reasons = {k: v for k, v in st.items() if k.endswith("_reasons")}
    breaker = st["plane_breaker"]
    lanes = lane_dispatches()
    say(f"counters: fallbacks={st['fallbacks']} "
        f"watchdog_stalls={st['watchdog_stalls']} "
        f"watchdog_quarantines={st['watchdog_quarantines']} "
        f"breaker_open_skips={st['breaker_open_skips']} "
        f"plane_breaker={{state: {breaker['state']}, trips: "
        f"{breaker['trips']}, errors_total: {breaker['errors_total']}}}")
    say(f"fallback reasons: {json.dumps(reasons, sort_keys=True)}")
    say(f"lane dispatches: {json.dumps(lanes, sort_keys=True)}")
    ms, lane, key = longest_compile()
    say(f"longest cold compile: {ms / 1e3:.2f} s (lane {lane}, program "
        f"{key}); the watchdog's cold floor is "
        f"{dispatch_watchdog.cold_floor_s:.0f} s")
    check(st["fallbacks"] == 0,
          f"{st['fallbacks']} compiled dispatch(es) fell back to eager")
    for name, book in reasons.items():
        bad = {r: n for r, n in book.items() if r in BAD_REASONS and n}
        check(not bad, f"{name} holds {bad}")
    check(breaker["state"] == "closed" and breaker["trips"] == 0
          and breaker["errors_total"] == 0 and not breaker["quarantined"],
          f"plane breaker not clean: {breaker}")
    check(st["watchdog_stalls"] == 0 and st["watchdog_quarantines"] == 0,
          "the dispatch watchdog counted a stall")
    missing = [ln for ln in expected_lanes if lanes.get(ln, 0) <= 0]
    check(not missing, f"no dispatch recorded on lane(s) {missing}")
    placed = {d.platform for a in jax.live_arrays() for d in a.devices()}
    say(f"live device arrays sit on platform(s): {sorted(placed)}")
    check(placed == {platform},
          f"device arrays found on {sorted(placed)}, expected {platform}")


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------

def oracle_check(oracle, qtids, resp: dict, k: int, label: str) -> None:
    """Doc ids and scores of one ``match`` response against the
    independent oracle, tie-tolerant at the cutoff."""
    import numpy as np
    from bm25_oracle import recall_with_tie_tolerance
    ids, scores = hits_of(resp)
    full = oracle.score_query(qtids)
    want_ids, want_scores = oracle.topk(qtids, k, scores=full)
    n_match = int((full > 0).sum())
    check(len(ids) == min(k, n_match),
          f"{label}: {len(ids)} hits, oracle has {min(k, n_match)}")
    check(resp["hits"]["total"] == n_match,
          f"{label}: total {resp['hits']['total']} != oracle {n_match}")
    recall = recall_with_tie_tolerance(want_ids, full, ids, k)
    check(recall == 1.0, f"{label}: oracle recall@{k} = {recall}")
    check(np.allclose(scores, full[ids], rtol=2e-4, atol=1e-4),
          f"{label}: scores differ from the oracle's for the same docs")
    check(np.allclose(scores, want_scores[:len(scores)], rtol=2e-4,
                      atol=1e-4), f"{label}: not the oracle's top-{k}")


@contextlib.contextmanager
def served_node(settings: dict | None = None):
    """A ``Node`` and a ``RestServer`` exactly as ``bootstrap.main``
    starts them — default settings (and ``settings`` over them), HTTP
    ingress last, port 0, data under a temp dir — → (node, the user's
    HTTP client)."""
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.server import RestServer
    data = tempfile.mkdtemp(prefix="chip_smoke_")

    def start():
        node = Node(Settings({**(settings or {}), "path.data": data}),
                    data_path=data).start()
        return node, RestServer(node, host="127.0.0.1", port=0).start()

    node, server = phase("start-up", start)
    try:
        yield node, Http(server.host, server.port)
    finally:
        server.stop()
        node.close()
        shutil.rmtree(data, ignore_errors=True)


def lanes_corpus(args):
    """The opt-in lanes' small corpus: text plus unit 768-dim vectors."""
    import numpy as np
    rng = np.random.default_rng(args.seed + 1)
    n_docs = min(LANES_DOCS, max(args.docs // 4, 512))
    corpus = Corpus(rng, n_docs, LANES_VOCAB)
    raw = rng.standard_normal((n_docs, VEC_DIMS)).astype(np.float32)
    return rng, corpus, raw / np.linalg.norm(raw, axis=1, keepdims=True)


def run_one_chip(args, dev: dict) -> None:
    from elasticsearch_tpu.analysis import analyzers
    say("tokenizer: " + ("native (built from tokenizer.c into _build/)"
                         if analyzers._native is not None
                         else "Python fallback"))
    with served_node() as (node, http):
        # both indices first: shards allocate while the node is young
        # (a disk-watermark sample minutes in must not decide the run)
        create_index(http, "smoke")
        create_index(http, "lanes", impact=True, vec_dims=VEC_DIMS)
        _serve_main_index(args, node, http)
        _serve_lanes_index(args, node, http)
        assert_chip_did_the_work(
            ("reader-batch", "segment", "knn", "impact-pruned",
             "impact-rescore", "percolate"), dev["platform"])


def _serve_main_index(args, node, http: Http) -> None:
    import numpy as np
    from bm25_oracle import BM25Oracle

    rng = np.random.default_rng(args.seed)
    n_docs = args.docs
    n_bulk = min(BULK_DOCS, n_docs // 4)
    corpus = phase("corpus (host)", Corpus, rng, n_docs, VOCAB)
    say(f"corpus: {n_docs} docs, vocab {VOCAB}, unique-term width "
        f"U={corpus.uterms.shape[1]}, avg length "
        f"{corpus.lens.mean():.1f}, seed {args.seed}")
    qtids = make_queries(rng, 64 + 8, QUERY_TERMS, corpus.df)
    oracle = phase("oracle (host)", BM25Oracle, corpus.toks)

    phase("write path: _bulk + _refresh", bulk_index, http, "smoke",
          corpus, 0, n_bulk)
    phase("load: bulk columnar ingest", install_packed, node, http,
          "smoke", corpus, n_bulk, n_docs)

    def match(qi: int, size: int, **extra) -> dict:
        return {"query": {"match": {"body": corpus.query_text(qtids[qi])}},
                "size": size, **extra}

    first = phase("first request incl. upload + compile",
                  http.search, "smoke", match(0, 10))
    oracle_check(oracle, qtids[0], first, 10, "match top-10 (cold)")
    hbm = hbm_report(http)
    say(f"device-resident bytes (HBM ledger, GET _cat/hbm): "
        f"{json.dumps(hbm['per_index'])}")
    check(hbm["per_index"].get("smoke", 0) > 0,
          "the HBM ledger holds nothing for [smoke]")
    warm = phase("warm request", http.search, "smoke", match(1, 10))
    oracle_check(oracle, qtids[1], warm, 10, "match top-10 (warm)")
    for qi in (2, 3):
        oracle_check(oracle, qtids[qi], http.search("smoke", match(qi, 10)),
                     10, f"match top-10 q{qi}")
    deep = phase("match top-1000 (first)", http.search, "smoke",
                 match(4, 1000))
    oracle_check(oracle, qtids[4], deep, 1000, "match top-1000")
    oracle_check(oracle, qtids[5], http.search("smoke", match(5, 1000)),
                 1000, "match top-1000 q5")

    batch = phase("_msearch of 64 (first)", http.msearch, "smoke",
                  [match(8 + i, 10) for i in range(64)])
    for i, resp in enumerate(batch):
        oracle_check(oracle, qtids[8 + i], resp, 10, f"_msearch item {i}")
    phase("_msearch of 64 (warm)", http.msearch, "smoke",
          [match(8 + i, 10) for i in range(64)])

    # bool + range filter: the oracle's scores under the same mask
    lo_r, hi_r = 25.0, 75.0
    resp = http.search("smoke", {"query": {"bool": {
        "must": [{"match": {"body": corpus.query_text(qtids[6])}}],
        "filter": [{"range": {"rank": {"gte": lo_r, "lt": hi_r}}}]}},
        "size": 10})
    ids, scores = hits_of(resp)
    full = oracle.score_query(qtids[6])
    full[(corpus.rank < lo_r) | (corpus.rank >= hi_r)] = 0.0
    want_ids, want_scores = oracle.topk(qtids[6], 10, scores=full)
    check(resp["hits"]["total"] == int((full > 0).sum()),
          "bool+range: total differs from the masked oracle")
    check(np.allclose(scores, want_scores[:len(scores)], rtol=2e-4,
                      atol=1e-4) and
          np.allclose(scores, full[ids], rtol=2e-4, atol=1e-4),
          "bool+range: hits differ from the masked oracle")

    # terms agg over the match's hit set
    resp = http.search("smoke", {
        "query": {"match": {"body": corpus.query_text(qtids[7])}},
        "size": 0, "aggs": {"by_cat": {"terms": {"field": "cat",
                                                  "size": 16}}}})
    matched = oracle.score_query(qtids[7]) > 0
    want = np.bincount(corpus.cat[matched], minlength=16)
    got = {b["key"]: b["doc_count"]
           for b in resp["aggregations"]["by_cat"]["buckets"]}
    check(got == {corpus.cat_names[c]: int(n)
                  for c, n in enumerate(want) if n},
          f"terms agg buckets differ from the oracle's counts: {got}")

    # search_after (score cursor): page 2 holds exactly the oracle's
    # docs between the cursor and its own last score — none skipped,
    # none above the cursor; only docs TIED with the cursor may repeat
    page1 = http.search("smoke", match(3, 10))
    ids1, sc1 = hits_of(page1)
    last = float(sc1[-1])
    page2 = http.search("smoke", match(3, 10, search_after=[last]))
    ids2, sc2 = hits_of(page2)
    full = oracle.score_query(qtids[3])
    eps = 2e-4 * max(last, 1.0)
    check(len(ids2) == 10 and float(sc2.max()) <= last + eps and
          np.allclose(sc2, full[ids2], rtol=2e-4, atol=1e-4),
          "search_after: page 2 outranks the cursor or misstates scores")
    between = np.flatnonzero((full < last - eps)
                             & (full > float(sc2.min()) + eps))
    check(set(between) <= set(ids2),
          "search_after: page 2 skipped docs the oracle ranks inside it")
    repeats = set(ids1) & set(ids2)
    check(all(abs(full[d] - last) <= eps for d in repeats),
          "search_after: page 2 repeats docs that rank above the cursor")
    say("main index: match top-10/top-1000, _msearch×64, bool+range, "
        "terms agg and search_after all agree with the oracle")


def _serve_lanes_index(args, node, http: Http) -> None:
    """The opt-in lanes on a small second index: each dispatches once,
    each is held to the engine's eager path on the same index."""
    import numpy as np
    from elasticsearch_tpu.index.device_reader import device_reader_for
    from elasticsearch_tpu.search import percolator
    from elasticsearch_tpu.search.phase import (ShardSearcher,
                                                parse_search_request)

    rng, corpus, vecs = lanes_corpus(args)
    n_docs = corpus.n_docs
    phase("lanes index: load", install_packed, node, http, "lanes",
          corpus, 0, n_docs, vecs)
    svc = node.indices_service.indices["lanes"]
    eager = ShardSearcher(0, device_reader_for(svc.engine(0)),
                          svc.mapper_service, index_name="lanes")

    def eager_scores(body: dict) -> np.ndarray:
        """The eager per-op executor's score for EVERY doc (0 = no hit),
        by corpus row."""
        res = eager._query_phase_eager(
            parse_search_request({**body, "size": n_docs}))
        out = np.zeros(n_docs, np.float64)
        for gid, sc in zip(res.doc_ids, res.scores):
            out[int(eager.reader.doc_id(int(gid)))] = sc
        return out

    before = lane_dispatches()

    def dispatched(lane: str) -> int:
        return lane_dispatches().get(lane, 0) - before.get(lane, 0)

    # knn: a noisy copy of doc 17's vector; ids and scores must agree
    q = vecs[17] + 0.1 * rng.standard_normal(VEC_DIMS).astype(np.float32)
    knn = {"knn": {"field": "vec", "query_vector": q.tolist(), "k": 10,
                   "num_candidates": 100}}
    resp = phase("knn (first)", http.search, "lanes",
                 {**knn, "size": 10})
    ids, scores = hits_of(resp)
    check(dispatched("knn") > 0, "the knn lane did not dispatch")
    ref = eager._knn_query_phase_eager(
        parse_search_request({**knn, "size": 10}))
    ref_ids = [int(eager.reader.doc_id(int(g))) for g in ref.doc_ids]
    check(len(ids) == 10 and ids[0] == 17 and list(ids) == ref_ids and
          np.allclose(scores, ref.scores, rtol=1e-4, atol=1e-5),
          f"knn: hits {list(ids)} differ from the eager path's {ref_ids}")

    # impact lanes score quantized impacts: each matched term may sit
    # 1.5 quantization steps from its exact value (ImpactColumn.
    # bound_per_term); a step is at most max-idf·(k1+1)/255
    step = float(np.log1p((n_docs - 0.5) / 1.5)) * 2.2 / 255.0

    def tol(n_terms: float) -> float:
        return 1.5 * step * n_terms + 1e-4

    qt = make_lane_queries(rng, corpus)

    def match_body(text: str) -> dict:
        return {"query": {"match": {"body": text}}}

    # impact-pruned: the exact scorer's top-10, up to the envelope
    resp = phase("impact-pruned (first)", http.search, "lanes", {
        **match_body(qt[0]), "size": 10, "track_total_hits": False})
    check(dispatched("impact-pruned") > 0,
          "the impact-pruned lane did not dispatch")
    ids, scores = hits_of(resp)
    ref = eager_scores(match_body(qt[0]))
    t1 = tol(QUERY_TERMS)
    check(len(ids) == 10 and float(np.abs(scores - ref[ids]).max()) <= t1,
          "impact-pruned: scores leave the quantization envelope of the "
          "eager path")
    check(float(ref[ids].min()) >= np.sort(ref)[::-1][9] - 2 * t1,
          "impact-pruned: a hit ranks below the eager path's top-10 by "
          "more than the envelope")

    # impact-rescore: primary + 1.5 × rescore over the top-32 window,
    # candidates and window combine in one program
    window, weight = 32, 1.5
    resp = phase("impact-rescore (first)", http.search, "lanes", {
        **match_body(qt[1]), "size": 10,
        "rescore": {"window_size": window, "query": {
            "rescore_query": match_body(qt[2])["query"],
            "query_weight": 1.0, "rescore_query_weight": weight,
            "score_mode": "total"}}})
    check(dispatched("impact-rescore") > 0,
          "the impact-rescore lane did not dispatch")
    ids, scores = hits_of(resp)
    primary = eager_scores(match_body(qt[1]))
    combined = primary + weight * eager_scores(match_body(qt[2]))
    t2 = tol(QUERY_TERMS * (1 + weight))
    edge = np.sort(primary)[::-1][window - 1]
    check(len(ids) == 10 and
          float(np.abs(scores - combined[ids]).max()) <= t2,
          "impact-rescore: scores leave the quantization envelope of "
          "the eager path's primary + weighted rescore")
    check(float(primary[ids].min()) >= edge - 2 * t1,
          "impact-rescore: a hit comes from outside the top-32 window")
    surely = np.flatnonzero((primary >= edge + 2 * t1)
                            & (combined > float(scores.min()) + 2 * t2))
    check(set(surely) <= set(ids),
          "impact-rescore: a window doc that outranks the page is "
          "missing from it")

    # percolate: registered queries × one probe doc, against the serial
    # per-query loop (the registry's own oracle)
    for i in range(8):
        http.call("PUT", f"/lanes/.percolator/q{i}",
                  {"query": {"match": {"body": qt[3 + i]}}})
    probe = {"doc": {"body": qt[3] + " " + qt[5]}}
    resp = phase("percolate (first)", http.call, "POST",
                 "/lanes/_doc/_percolate", probe)
    check(dispatched("percolate") > 0,
          "the percolate lane did not dispatch")
    meta = node.cluster_service.state().indices["lanes"]
    want = percolator.percolate_serial(meta, probe["doc"])
    got_ids = sorted(m["_id"] for m in resp["matches"])
    check(resp["total"] == want["total"] and got_ids == sorted(
        m["_id"] for m in want["matches"]) and {"q0", "q2"} <= set(got_ids),
        f"percolate: matches {got_ids} differ from the serial loop")
    say("lanes index: knn, impact-pruned, impact-rescore and percolate "
        "each dispatched and agree with the eager path")


def make_lane_queries(rng, corpus: Corpus) -> list:
    qtids = make_queries(rng, 16, QUERY_TERMS, corpus.df)
    return [corpus.query_text(row) for row in qtids]


# ---------------------------------------------------------------------------
# four chips: the mesh lanes against the one-chip lanes
# ---------------------------------------------------------------------------

def run_four_chips(args, dev: dict) -> None:
    """The mesh lanes and the collective plane at (1,4) and (2,2), each
    on a node whose setting ``search.mesh`` installs the geometry — the
    way a deployment gets one — against a node without the setting (one
    chip): the same hits, bit for bit."""
    import jax
    import numpy as np

    check(dev["count"] == 4, f"--chips 4 found {dev['count']} device(s)")
    rng = np.random.default_rng(args.seed)
    corpus = phase("corpus (host)", Corpus, rng, args.docs, VOCAB)
    rng2, small, vecs = lanes_corpus(args)
    texts = make_lane_queries(rng, corpus)
    impact_bodies = [{"query": {"match": {"body": t}}, "size": 10,
                      "track_total_hits": False} for t in texts[:8]]
    plane_bodies = [{"query": {"match": {"body": t}}, "size": 10}
                    for t in texts[:8]]
    knn_bodies = [{"knn": {
        "field": "vec", "k": 10, "num_candidates": 100,
        "query_vector": (vecs[i] + 0.1 * rng2.standard_normal(
            VEC_DIMS).astype(np.float32)).tolist()}, "size": 10}
        for i in range(4)]
    mesh_lanes = {"impact-mesh", "knn-mesh"}
    chip_lanes = {"impact-pruned", "knn"}

    def serve(geometry: str | None) -> list:
        label = f"geometry {geometry}" if geometry else "one chip"
        settings = {"search.mesh": geometry} if geometry else None
        with served_node(settings) as (node, http):
            create_index(http, "smoke", impact=True)
            create_index(http, "lanes", impact=True, vec_dims=VEC_DIMS)
            create_index(http, "sharded", shards=4)
            install_packed(node, http, "smoke", corpus, 0, args.docs)
            install_packed(node, http, "lanes", small, 0, small.n_docs,
                           vecs)
            # the 4-shard index: a contiguous quarter of the rows a shard
            quarter = args.docs // 4
            for sid in range(4):
                node.indices_service.indices["sharded"].engine(sid) \
                    .install_segment(corpus.packed_segment(
                        sid * quarter, (sid + 1) * quarter),
                        track_versions=False)
            http.call("POST", "/sharded/_refresh")
            shown = http.call("GET", "/_nodes/stats")["nodes"]
            (stats,) = shown.values()
            say(f"{label}: _nodes/stats device.mesh "
                f"{json.dumps(stats['device']['mesh'])}")
            before = lane_dispatches()
            out = [[(h["_id"], h["_score"]) for h in r["hits"]["hits"]]
                   for r in http.msearch("smoke", impact_bodies)
                   + http.msearch("lanes", knn_bodies)
                   + http.msearch("sharded", plane_bodies)]
            after = lane_dispatches()
            moved = {ln: after[ln] - before.get(ln, 0) for ln in after
                     if after[ln] != before.get(ln, 0)}
            say(f"{label}: lane dispatches {json.dumps(moved)}")
            want = (mesh_lanes if geometry else chip_lanes) | {"mesh"}
            check(all(moved.get(ln, 0) > 0 for ln in want)
                  and not any(moved.get(ln) for ln in
                              (mesh_lanes | chip_lanes) - want),
                  f"{label}: expected dispatches on {sorted(want)}, "
                  f"saw {moved}")
            if geometry:
                dp, shard = (int(x) for x in geometry.split("x"))
                check((stats["device"]["mesh"]["dp"],
                       stats["device"]["mesh"]["shard"]) == (dp, shard),
                      f"the node serves over {stats['device']['mesh']}")
                hbm = hbm_report(http, totals=True)
                say(f"per-device resident bytes at {geometry} "
                    f"(_cat/hbm?totals=true): "
                    f"{json.dumps(hbm['per_device'], sort_keys=True)}")
                placed = [d for d, b in hbm["per_device"].items()
                          if d != "-" and b > 0]
                # the ledger books a shard column to the first of its
                # dp replicas; the live arrays below sit on all four
                check(len(placed) == shard,
                      f"bytes booked on {len(placed)} devices for "
                      f"{shard} shard columns: {hbm['per_device']}")
                on = {d.id for a in jax.live_arrays()
                      for d in a.devices()}
                check(len(on) == 4,
                      f"live arrays sit on devices {sorted(on)}")
            # before the node closes: its programs leave the cost table
            # with its engines
            assert_chip_did_the_work(sorted(want), dev["platform"])
            return out

    results = {g: phase(f"mesh lanes + plane ({g})", serve, g)
               for g in ("1x4", "2x2")}
    single = phase("one-chip lanes + plane", serve, None)
    for geom, got in results.items():
        check(got == single,
              f"the mesh lanes and the plane at {geom} are not "
              f"bit-identical to the one-chip node's")
    say("mesh lanes and the collective plane at 1x4 and 2x2 (node "
        "setting search.mesh): hits bit-identical to the one-chip "
        "node's (ids and scores)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU backend; can never report ok")
    ap.add_argument("--docs", type=int, default=1_000_000,
                    help="corpus size (shrink it for a rehearsal)")
    ap.add_argument("--seed", type=int, default=20240924)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh lanes vs the one-chip lanes")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if args.chips > 1 and \
                "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    elif args.docs != 1_000_000:
        ap.error("--docs shrinks the corpus only with --rehearse-cpu")

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"[smoke] no TPU: JAX's default backend is "
              f"{dev['platform']} ({dev['kind']} x{dev['count']}). "
              f"This script proves the chip path; --rehearse-cpu runs "
              f"its phases on the CPU and never reports ok.",
              file=sys.stderr, flush=True)
        return 2
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}"
        + (" — REHEARSAL on the CPU, not a chip run"
           if args.rehearse_cpu else ""))
    if args.chips == 1 and not args.rehearse_cpu:
        check(dev["count"] == 1,
              f"the one-chip run found {dev['count']} devices")

    sys.path.insert(0, str(REPO))
    sys.path.insert(0, str(REPO / "scripts"))
    from elasticsearch_tpu.common.device import ensure_compile_cache
    say(f"compile cache: {ensure_compile_cache()}")

    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(args, dev)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s "
        f"(smoke, not a benchmark)")
    print(result_line(True, dev, rehearsal=args.rehearse_cpu), flush=True)
    return 3 if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
