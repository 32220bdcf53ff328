"""The benchmark configuration ``msmarco-passage-bm25-phrase`` at a size a
test run holds, on the CPU:

* the SERVED path — REST → SearchActions → the scheduler's lexical
  (``plane``) lane → one ``reader-batch`` program — for the cell's
  phrase-boost ``bool`` (must ``match`` with ``minimum_should_match``
  30%, should ``match_phrase`` of the same text), 8 concurrent clients,
  three seeds, against a reference written here in this file's own terms
  (Lucene's BooleanQuery over BM25 from the published formula, float64);
* a batch of the cell's bodies of every length 2..12 is ONE program;
* a phrase padded to a wider term bucket counts what the unpadded one
  counts, at a document's last position too;
* a phrase term absent from one segment keeps the batch;
* the bfloat16 control of ``benchmarks/corpora/zipf_text_positions.py``
  fails the cell's limits.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import math
import threading
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.ops import phrase as phrase_ops
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.phase import (ShardSearcher,
                                            parse_search_request)

REPO = Path(__file__).resolve().parent.parent
CELL = "msmarco-phrase.bool-phrase-top10-c16"
K1, B = 1.2, 0.75


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


zp = _load(REPO / "benchmarks" / "corpora" / "zipf_text_positions.py",
           "bench_zipf_text_positions_for_tier1")
CONFIG = json.loads((REPO / "benchmarks" / "configs"
                     / "msmarco-passage-bm25-phrase.json").read_text())
LIMITS = json.loads((REPO / "benchmarks" / "workloads"
                     / f"{CELL}.json").read_text())["limits"]
REQUEST = json.loads((REPO / "benchmarks" / "traffic"
                      / "bool-phrase-top10-c16.json").read_text()
                     )["streams"][0]["request"]
INDEX = CONFIG["index_name"]
N_DOCS, VOCAB = 400, 40


def make_docs(seed: int) -> list:
    """Term ids a document, Zipf over a small vocabulary so that phrases
    recur, lengths 3..30."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, VOCAB + 1, dtype=np.float64) ** -1.07
    p /= p.sum()
    return [[int(t) for t in rng.choice(VOCAB, int(rng.integers(3, 31)),
                                        p=p)]
            for _ in range(N_DOCS)]


def make_queries(seed: int, docs: list, n: int) -> list:
    """``n`` phrases: consecutive distinct terms of a document, lengths
    2..12 in turn."""
    rng = np.random.default_rng([seed, 1])
    out = []
    while len(out) < n:
        want = 2 + len(out) % 11
        d = docs[int(rng.integers(len(docs)))]
        if len(d) < want:
            continue
        s = int(rng.integers(len(d) - want + 1))
        window = d[s:s + want]
        if len(set(window)) == want:
            out.append(window)
    return out


def reference(docs: list, query: list) -> dict:
    """doc index → float64 score of the cell's bool: the ``match`` over
    the terms a document holds, if it holds max(floor(30% n), 1) of
    them, plus the phrase's BM25 (tf = its frequency, idf = Σ idf of its
    terms) where it occurs."""
    n_docs = len(docs)
    avgdl = sum(len(d) for d in docs) / n_docs
    idf = {}
    for t in query:
        df = sum(1 for d in docs if t in d)
        idf[t] = math.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
    need = max(len(query) * 30 // 100, 1)
    n = len(query)
    out = {}
    for i, d in enumerate(docs):
        norm = K1 * (1.0 - B + B * len(d) / avgdl)
        held = [t for t in query if t in d]
        if len(held) < need:
            continue
        score = sum(idf[t] * d.count(t) * (K1 + 1.0)
                    / (d.count(t) + norm) for t in held)
        pf = sum(1 for s in range(len(d) - n + 1) if d[s:s + n] == query)
        if pf:
            score += sum(idf.values()) * pf * (K1 + 1.0) / (pf + norm)
        out[i] = score
    return out


def _text(doc: list) -> str:
    return " ".join(zp.term_name(t) for t in doc)


def _load_docs(node, docs: list, parts: int = 1) -> None:
    node.indices_service.create_index(INDEX, CONFIG["index"])
    per = -(-len(docs) // parts)
    for lo in range(0, len(docs), per):
        for i in range(lo, min(lo + per, len(docs))):
            node.index_doc(INDEX, str(i), {"body": _text(docs[i])})
        node.broadcast_actions.refresh(INDEX)


def _searcher(node) -> ShardSearcher:
    svc = node.indices_service.indices[INDEX]
    return ShardSearcher(0, device_reader_for(svc.engine(0)),
                         svc.mapper_service, index_name=INDEX)


def _call(server, method: str, path: str, body=None):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=120)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"}
                     if body else {})
        resp = conn.getresponse()
        raw = resp.read()
        assert resp.status < 300, (resp.status, raw[:300])
        return json.loads(raw)
    finally:
        conn.close()


def _assert_hits(docs, query, ids, scores, total, k):
    ref = reference(docs, query)
    assert total == len(ref)
    want = min(k, len(ref))
    assert len(ids) == want and len(set(ids)) == want
    assert list(scores) == sorted(scores, reverse=True)
    np.testing.assert_allclose(scores, [ref[i] for i in ids], rtol=2e-6)
    np.testing.assert_allclose(
        scores, sorted(ref.values(), reverse=True)[:want], rtol=2e-6)


@pytest.fixture
def node(tmp_path):
    jit_exec.clear_cache()
    # one batch in flight at a time: while it runs, the other clients'
    # requests meet in the queues and leave as batches of several
    n = Node({"search.scheduler.max_in_flight": 1,
              "cluster.routing.allocation.disk.watermark.low": "1.0"},
             data_path=tmp_path / "n").start()
    yield n
    n.close()
    jit_exec.clear_cache()


@pytest.mark.parametrize("seed", [4100003611, 2**31 + 36, 7])
def test_served_path_matches_the_reference(node, seed):
    docs = make_docs(seed)
    _load_docs(node, docs)
    clients, each = 8, 5
    pool = make_queries(seed, docs, clients * each)
    server = RestServer(node, host="127.0.0.1", port=0).start()
    replies, errors = {}, []
    try:
        # the benchmark's warm-up: every program the clients can reach is
        # compiled before they start, so no request waits behind a
        # compile (seconds on a CPU host) past the scheduler's queue
        # deadline and is served outside the lane
        for req in zp.warm_requests({**REQUEST, "in_flight": clients}, pool,
                                    INDEX, clients):
            _call(server, "POST", req["path"], req["body"])
        before = jit_exec.cache_stats()
        sched0 = node.search_actions.scheduler.stats()

        def client(ci: int) -> None:
            try:
                for qi in range(ci, len(pool), clients):
                    req = zp.request(REQUEST, [pool[qi]], INDEX)
                    replies[qi] = _call(server, "POST", req["path"],
                                        req["body"])
            except Exception as e:       # noqa: BLE001 — reported below
                errors.append(e)
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads) and not errors, errors
    finally:
        server.stop()
    after = jit_exec.cache_stats()
    sched1 = node.search_actions.scheduler.stats()
    for qi, query in enumerate(pool):
        hits = replies[qi]["hits"]
        _assert_hits(docs, query, [int(h["_id"]) for h in hits["hits"]],
                     [h["_score"] for h in hits["hits"]], hits["total"],
                     int(REQUEST["size"]))
    # the scheduler's lexical lane answered every request, in batches of
    # more than one, and no phrase fell to the query-by-query path
    delivered = sched1["delivered"] - sched0["delivered"]
    launched = sched1["batches_launched"] - sched0["batches_launched"]
    assert delivered == len(pool) and sched1["reconciled"], \
        (sched1, jit_exec.plane_breaker.stats())
    assert launched < delivered, "no batch of more than one formed"
    assert after["phrase_items_batched"] - before["phrase_items_batched"] \
        == len(pool)
    assert after["phrase_items_serial"] == before["phrase_items_serial"]
    assert after["fallbacks"] == before["fallbacks"]
    real = after["phrase_terms_real"] - before["phrase_terms_real"]
    assert real == sum(len(q) for q in pool)


def test_every_length_of_the_cell_is_one_program(node):
    """The cell's bodies of 2..12 terms in one batch: ONE compiled
    program (phrase and match padded to 12), equal to the query-by-query
    path and to the reference; the counters say so."""
    docs = make_docs(11)
    _load_docs(node, docs)
    s = _searcher(node)
    pool = make_queries(11, docs, 11)
    assert sorted(len(q) for q in pool) == list(range(2, 13))
    reqs = [parse_search_request(zp.request_body(q, 10, "30%"))
            for q in pool]
    before = jit_exec.cache_stats()
    batch = s.query_phase_batch(reqs)
    after = jit_exec.cache_stats()
    assert batch is not None
    assert after["misses"] - before["misses"] == 1
    assert after["phrase_items_batched"] - before["phrase_items_batched"] \
        == 11
    assert after["phrase_items_serial"] == before["phrase_items_serial"]
    assert after["phrase_terms_real"] - before["phrase_terms_real"] == 77
    assert after["phrase_terms_padded"] \
        - before["phrase_terms_padded"] == 11 * 12 - 77
    for query, req, got in zip(pool, reqs, batch):
        ids = [int(s.reader.doc_id(int(d))) for d in got.doc_ids]
        _assert_hits(docs, query, ids, list(got.scores), got.total, 10)
        one = s.query_phase(req)
        np.testing.assert_array_equal(one.doc_ids, got.doc_ids)
        np.testing.assert_allclose(one.scores, got.scores, rtol=1e-6)


def test_a_padded_phrase_counts_at_a_documents_last_position(node):
    """The longest document ends in the phrase, so its pads stand past the
    last position of the matrix: padded to 12 it counts what it counts
    alone, in the op and through a batch with a 12-term phrase."""
    docs = make_docs(5)
    longest = max(len(d) for d in docs)
    docs.append([(7 * j) % VOCAB for j in range(longest - 2)] + [38, 39])
    assert len(docs[-1]) == longest
    _load_docs(node, docs)
    s = _searcher(node)
    tokens = np.asarray(s.reader.segments[0].seg.text_fields["body"].tokens)
    col = s.reader.segments[0].seg.text_fields["body"]
    x, y = col.tid(zp.term_name(38)), col.tid(zp.term_name(39))
    plain = phrase_ops.phrase_freq(jnp.asarray(tokens), [x, y], [0, 1])
    padded = phrase_ops.exact_phrase_freq(
        jnp.asarray(tokens),
        jnp.asarray([x, y] + [phrase_ops.PAD_TID] * 10, jnp.int32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(padded))
    last = len(docs) - 1
    assert float(padded[last]) >= 1.0
    wide = make_queries(5, docs, 11)[10]
    assert len(wide) == 12
    reqs = [parse_search_request(zp.request_body(q, 10, "30%"))
            for q in ([38, 39], wide)]
    got = s.query_phase_batch(reqs)
    assert got is not None
    ids = [int(s.reader.doc_id(int(d))) for d in got[0].doc_ids]
    assert last in ids
    _assert_hits(docs, [38, 39], ids, list(got[0].scores), got[0].total, 10)


def test_a_term_absent_from_a_segment_keeps_the_batch(node):
    """Two segments; one phrase term is held by the first only (its id in
    the second is -1): the batch keeps one plan and answers right."""
    docs = make_docs(3)
    for i in range(0, 200, 37):
        docs[i] = docs[i][:2] + [VOCAB] + docs[i][2:]     # only in part 1
    _load_docs(node, docs, parts=2)
    s = _searcher(node)
    assert len(s.reader.segments) == 2
    second = s.reader.segments[1].seg.text_fields["body"]
    assert second.tid(zp.term_name(VOCAB)) == -1
    queries = [docs[0][1:4], docs[74][0:3]] \
        + make_queries(3, docs, 4)[1:3]
    assert VOCAB in queries[0] and VOCAB in queries[1]
    reqs = [parse_search_request(zp.request_body(q, 10, "30%"))
            for q in queries]
    before = jit_exec.cache_stats()
    batch = s.query_phase_batch(reqs)
    assert batch is not None
    after = jit_exec.cache_stats()
    assert after["phrase_items_serial"] == before["phrase_items_serial"]
    for query, got in zip(queries, batch):
        ids = [int(s.reader.doc_id(int(d))) for d in got.doc_ids]
        _assert_hits(docs, query, ids, list(got.scores), got.total, 10)


SMALL = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 2048, "vocab": 5000}}


@pytest.mark.parametrize("seed", [4100003611, 2**31 + 36, 7])
def test_a_bfloat16_reference_fails_the_cells_limits(seed):
    """The control: the reference in the program's place with every float
    in bfloat16. It has to come out as not correct on every seed, by the
    cell's own limits; the float64 reference against itself passes."""
    corpus = zp.generate(SMALL, seed)
    pool = zp.query_pool(corpus, {"pool": 16,
                                  "terms": {"2": 1, "5": 1, "12": 1}},
                         np.random.default_rng([seed, 13]),
                         np.random.default_rng(1))
    ref = zp.Reference(corpus, pool)
    worst: dict = {}
    for q in pool:
        got = zp.compare(ref.scores(q), REQUEST,
                         *zp.control_hits(ref, q, 10))
        worst = {n: max(worst.get(n, 0.0), v) for n, v in got.items()}
    assert any(worst[n] > LIMITS[n] for n in LIMITS), worst
    assert worst["score_gap"] > 10 * LIMITS["score_gap"], worst
    assert worst["phrase_hits"] > 0 and worst["phrase_hits_none"] == 0


@pytest.mark.parametrize("width,deltas,rows", [
    (37, (0, 1, 2, 3), [[1, 2, -2, -2], [0, 0, 1, -2], [3, -1, -2, -2],
                        [2, 1, 0, 3]]),
    (224, (0, 2, 3), [[1, 2, 3], [0, -1, 1], [4, 4, 4]]),
    (64, (0, 9), [[1, 1], [2, 3]])])
def test_the_kernel_counts_what_a_plain_scan_counts(width, deltas, rows):
    """The TPU kernel's own code (Pallas interpret mode) and the CPU's
    shifted compares against a scan of every start position: pads
    (``PAD_TID``) match anything, an absent term (-1) nothing, offsets
    with gaps and a matrix width that is no multiple of 8."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(width)
    tokens = rng.integers(0, 5, (256, width)).astype(np.int32)
    lens = rng.integers(1, width + 1, 256)
    tokens[np.arange(width)[None, :] >= lens[:, None]] = -1
    qtids = np.array(rows, np.int32)
    want = np.zeros((len(rows), 256), np.int64)
    for b, row in enumerate(rows):
        for p in range(width):
            ok = np.ones(256, bool)
            for t, d in zip(row, deltas):
                if t == phrase_ops.PAD_TID:
                    continue
                if t < 0 or p + d >= width:
                    ok[:] = False
                    break
                ok &= tokens[:, p + d] == t
            want[b] += ok
    assert want.sum() > 0
    plain = phrase_ops._phrase_counts_xla(jnp.asarray(tokens),
                                          jnp.asarray(qtids), deltas)
    with pltpu.force_tpu_interpret_mode():
        kernel = phrase_ops._phrase_counts_tpu(jnp.asarray(tokens),
                                               jnp.asarray(qtids), deltas)
    np.testing.assert_array_equal(np.asarray(plain), want)
    np.testing.assert_array_equal(np.asarray(kernel), want)
