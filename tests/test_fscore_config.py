"""The benchmark configuration ``geonames-function-score`` at a size a test
run holds, on the CPU:

* the SERVED path — REST → SearchActions → the scheduler's lexical
  (``plane``) lane → one ``reader-batch`` program a signature — for Rally
  geonames' three scoring operations (``field_value_factor``, geo
  ``gauss`` decay, lang-expression ``script_score``), 8 concurrent
  clients, three seeds, against a reference written here in this file's
  own terms (the published functions, float64);
* the scheduler's key: different function lists are different shapes,
  different origins one shape;
* the packed loader's numeric and geo columns, moved with the length
  order, give what ``_bulk`` gives;
* a script the engine cannot evaluate is an error reply, not an empty
  answer;
* a date ``gauss`` reads the double-double column;
* the bfloat16 control of ``benchmarks/corpora/geonames.py`` fails the
  cell's limits.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import math
import threading
from pathlib import Path

import numpy as np
import pytest

from elasticsearch_tpu.index.segment import PackedSources, Segment
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.query_dsl import parse_query
from elasticsearch_tpu.search.scheduler import query_shape

REPO = Path(__file__).resolve().parent.parent
CELL = "geonames-fscore.fscore-mix-top10-c16"


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gn = _load(REPO / "benchmarks" / "corpora" / "geonames.py",
           "bench_geonames_for_tier1")
CONFIG = json.loads((REPO / "benchmarks" / "configs"
                     / "geonames-function-score.json").read_text())
LIMITS = json.loads((REPO / "benchmarks" / "workloads"
                     / f"{CELL}.json").read_text())["limits"]
REQUEST = json.loads((REPO / "benchmarks" / "traffic"
                      / "fscore-mix-top10-c16.json").read_text()
                     )["streams"][0]["request"]
INDEX = CONFIG["index_name"]
N_PLACES = 300
R_EARTH = 6371008.8


def make_places(seed: int) -> list:
    """(name tokens, population, lat, lon) a place: a third unpopulated,
    locations round three centres, coordinates with 5 decimals."""
    rng = np.random.default_rng(seed)
    centres = [(52.0, 5.0), (-33.9, 151.2), (40.7, -74.0)]
    out = []
    for i in range(N_PLACES):
        clat, clon = centres[i % 3]
        lat = round(float(np.clip(clat + rng.normal(0, 2.0), -90, 90)), 5)
        lon = round(float(clon + rng.normal(0, 2.0)), 5)
        pop = 0 if rng.random() < 0.3 else int(rng.integers(1, 5_000_000))
        name = [f"w{int(t)}" for t in rng.integers(0, 40,
                                                   int(rng.integers(1, 6)))]
        out.append((name, pop, lat, lon))
    return out


def reference(places: list, entry: list) -> list:
    """float64 score of every place for one operation: Elasticsearch's
    function over match_all (query score 1, boost_mode multiply)."""
    op, origin = entry
    olat, olon = (float(x) for x in origin.split(","))
    sigma2 = 500_000.0 ** 2 / (2.0 * math.log(1.0 / 0.1))
    out = []
    for _name, pop, lat, lon in places:
        if op == gn.FVF:
            out.append(math.log10(1.2 * pop + 2.0))
        elif op == gn.GAUSS:
            a = math.sin(math.radians(lat - olat) / 2) ** 2 + math.cos(
                math.radians(lat)) * math.cos(math.radians(olat)) * \
                math.sin(math.radians(lon - olon) / 2) ** 2
            d = 2 * R_EARTH * math.asin(math.sqrt(min(a, 1.0)))
            out.append(math.exp(-d * d / (2.0 * sigma2)))
        else:
            out.append(abs(math.log(abs(pop) + 1.0) + lon + lat))
    return out


def make_pool(seed: int, places: list, n: int) -> list:
    """``n`` entries, the operations in turn, origins at populated places."""
    rng = np.random.default_rng([seed, 1])
    populated = [p for p in places if p[1] > 0]
    out = []
    for i in range(n):
        _n, _p, lat, lon = populated[int(rng.integers(len(populated)))]
        out.append([gn.OPS[i % 3], f"{lat:.5f}, {lon:.5f}"])
    return out


def _doc(place: tuple) -> dict:
    name, pop, lat, lon = place
    return {"name": " ".join(name), "population": pop,
            "location": {"lat": lat, "lon": lon}}


def _call(server, method: str, path: str, body=None):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=120)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"}
                     if body else {})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _bulk(server, index: str, places: list) -> None:
    lines = []
    for i, p in enumerate(places):
        lines += [json.dumps({"index": {"_index": index, "_id": str(i)}}),
                  json.dumps(_doc(p))]
    status, reply = _call(server, "POST", "/_bulk?refresh=true",
                          "\n".join(lines) + "\n")
    assert status == 200 and not reply["errors"], reply


def _assert_hits(places, entry, ids, scores, total, k):
    ref = reference(places, entry)
    assert total == len(places)
    assert len(ids) == k and len(set(ids)) == k
    assert list(scores) == sorted(scores, reverse=True)
    np.testing.assert_allclose(scores, [ref[i] for i in ids], rtol=2e-5)
    np.testing.assert_allclose(scores, sorted(ref, reverse=True)[:k],
                               rtol=2e-5)


@pytest.fixture(scope="module", autouse=True)
def programs():
    """The module's tests share the programs they compile (the shapes of
    a seed's places are every seed's), and leave none behind."""
    jit_exec.clear_cache()
    yield
    jit_exec.clear_cache()


@pytest.fixture
def node(tmp_path):
    # one batch in flight at a time: while it runs, the other clients'
    # requests meet in the queues and leave as batches of several
    n = Node({"search.scheduler.max_in_flight": 1,
              "cluster.routing.allocation.disk.watermark.low": "1.0"},
             data_path=tmp_path / "n").start()
    yield n
    n.close()


@pytest.fixture
def server(node):
    s = RestServer(node, host="127.0.0.1", port=0).start()
    yield s
    s.stop()


@pytest.mark.parametrize("seed", [4100003611, 2**31 + 36, 7])
def test_served_path_matches_the_reference(node, server, seed):
    """8 clients send the three operations mixed; every answer is the
    reference's, and the scheduler's queues leave as batches of one
    signature each: no item falls to the query-by-query path."""
    places = make_places(seed)
    node.indices_service.create_index(INDEX, CONFIG["index"])
    _bulk(server, INDEX, places)
    clients, each = 8, 6
    pool = make_pool(seed, places, clients * each)
    # the benchmark's warm-up: every program the clients can reach is
    # compiled before they start, so no request waits behind a compile
    # (seconds on a CPU host) past the scheduler's queue deadline
    for req in gn.warm_requests({**REQUEST, "in_flight": clients}, pool,
                                INDEX, clients):
        status, reply = _call(server, "POST", req["path"], req["body"])
        assert status == 200, reply
    replies, errors = {}, []
    before = jit_exec.cache_stats()
    sched0 = node.search_actions.scheduler.stats()

    def client(ci: int) -> None:
        try:
            for qi in range(ci, len(pool), clients):
                req = gn.request(REQUEST, [pool[qi]], INDEX)
                status, replies[qi] = _call(server, "POST", req["path"],
                                            req["body"])
                assert status == 200, replies[qi]
        except Exception as e:           # noqa: BLE001 — reported below
            errors.append(e)
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    after = jit_exec.cache_stats()
    sched1 = node.search_actions.scheduler.stats()
    for qi, entry in enumerate(pool):
        hits = replies[qi]["hits"]
        _assert_hits(places, entry, [int(h["_id"]) for h in hits["hits"]],
                     [h["_score"] for h in hits["hits"]], hits["total"],
                     int(REQUEST["size"]))
    delivered = sched1["delivered"] - sched0["delivered"]
    launched = sched1["batches_launched"] - sched0["batches_launched"]
    assert delivered == len(pool) and sched1["reconciled"], sched1
    assert launched < delivered, "no batch of more than one formed"
    assert after["fscore_items_batched"] \
        - before["fscore_items_batched"] == len(pool)
    assert after["fscore_items_serial"] == before["fscore_items_serial"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["misses"] == before["misses"], "a program compiled late"
    # a function a row, over every row of the reader
    evals = after["fscore_doc_evals"] - before["fscore_doc_evals"]
    assert evals > 0 and evals % len(pool) == 0


def _shape(fs: dict):
    return query_shape(parse_query({"function_score": {
        "query": {"match_all": {}}, **fs}}))


def test_query_shape_fingerprints_the_function_list():
    """The scheduler's key holds a function's structure — kind, field,
    modifier, decay kind, script source, weight and filter — and leaves
    out what a request only fills in (an origin, a factor, a scale)."""
    bodies = [gn.request_body(e, 10)["query"]["function_score"]
              for e in ([gn.FVF, None], [gn.GAUSS, "52.37, 4.8951"],
                        [gn.EXPRESSION, None])]
    fs = [{k: v for k, v in b.items() if k != "query"} for b in bodies]
    shapes = [_shape(f) for f in fs]
    assert len(set(shapes)) == 3
    gauss = fs[1]["gauss"]["location"]
    assert _shape({"gauss": {"location": {**gauss, "origin": "1.5, -70"}}}) \
        == shapes[1]
    assert _shape({"gauss": {"location": {**gauss, "scale": "20km"}}}) \
        == shapes[1]
    assert _shape({"exp": {"location": gauss}}) != shapes[1]
    fvf = fs[0]["field_value_factor"]
    assert _shape({"field_value_factor": {**fvf, "factor": 3.0}}) \
        == shapes[0]
    assert _shape({"field_value_factor": {**fvf, "modifier": "ln1p"}}) \
        != shapes[0]
    assert _shape({"field_value_factor": {**fvf, "field": "elevation"}}) \
        != shapes[0]
    assert _shape({"functions": [{"script_score": {"script": {
        "inline": "doc['population'] * 2"}}}]}) != shapes[2]
    assert _shape({**fs[0], "boost_mode": "replace"}) != shapes[0]
    assert _shape({"functions": [{"field_value_factor": fvf,
                                  "weight": 2}]}) != shapes[0]


def test_packed_columns_in_length_order_equal_bulk(node, server):
    """One index loaded through ``Segment.from_packed_text`` with numeric
    and geo columns (names of unequal lengths, so the rows move), one
    through ``_bulk``: the same hits and scores for every operation, and
    no pad row is a hit."""
    rng = np.random.default_rng(5)
    places = [(name, int(rng.integers(1, 10_000_000)), lat, lon)
              for name, _pop, lat, lon in make_places(5)]
    node.indices_service.create_index(INDEX, CONFIG["index"])
    node.indices_service.create_index("bulk", CONFIG["index"])
    _bulk(server, "bulk", places)
    vocab = sorted({t for p in places for t in p[0]})
    tid = {t: i for i, t in enumerate(vocab)}
    rows, width, n = 512, 8, len(places)
    uterms = np.full((rows, width), -1, np.int32)
    utf = np.zeros((rows, width), np.float32)
    tokens = np.full((rows, width), -1, np.int32)
    doc_len = np.zeros(rows, np.int32)
    df = np.zeros(len(vocab), np.int32)
    for i, (name, _pop, _lat, _lon) in enumerate(places):
        ids = [tid[t] for t in name]
        tokens[i, :len(ids)] = ids
        doc_len[i] = len(ids)
        for j, (t, c) in enumerate(sorted(
                {t: ids.count(t) for t in ids}.items())):
            uterms[i, j], utf[i, j] = t, c
            df[t] += 1
    exists = np.arange(rows) < n

    def col(k):
        return np.array([p[k] for p in places] + [0] * (rows - n),
                        np.float64)
    seg = Segment.from_packed_text(
        0, "name", terms=vocab, tokens=tokens, uterms=uterms, utf=utf,
        doc_len=doc_len, df=df, num_docs=n,
        numeric={"population": (col(1), exists)},
        geo={"location": (col(2), col(3), exists)})
    assert list(seg.ids[:3]) != ["0", "1", "2"], "the rows did not move"
    assert seg.numeric_fields["population"].values[
        list(seg.ids).index("7")] == places[7][1]
    node.indices_service.indices[INDEX].engine(0).install_segment(
        seg, track_versions=False)
    node.broadcast_actions.refresh(INDEX)
    for entry in make_pool(5, places, 6):
        req = gn.request(REQUEST, [entry], INDEX)
        got = [_call(server, "POST", f"/{name}/_search", req["body"])[1]
               for name in (INDEX, "bulk")]
        a, b = ([(h["_id"], h["_score"]) for h in g["hits"]["hits"]]
                for g in got)
        assert got[0]["hits"]["total"] == got[1]["hits"]["total"] == n
        assert a == b, (entry, a, b)
    with pytest.raises(ValueError, match="padding row"):
        Segment.from_packed_text(
            0, "name", terms=vocab, tokens=None, uterms=uterms, utf=utf,
            doc_len=doc_len, df=df, num_docs=n,
            numeric={"population": (col(1), np.ones(rows, bool))})


WHOLE = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 512, "docs": 700,
                              "clusters": 16, "vocab": 600}}


def test_the_whole_record_through_the_packed_path_equals_bulk(node,
                                                              server):
    """The data kind's places, every field of Rally's mapping, loaded by
    its ``install`` (packed text, keyword, numeric and geo columns, the
    coded fields analyzed by the index's own analyzers, the ``_source``
    packed) and the same documents through ``_bulk``: the same hits,
    scores and ``_source`` for the three operations and for queries on
    the other fields."""
    corpus = gn.generate(WHOLE, 11)
    node.indices_service.create_index(INDEX, CONFIG["index"])
    node.indices_service.create_index("bulk", CONFIG["index"])
    gn.install(corpus, node, INDEX)
    docs = []
    for si, seg in enumerate(corpus["segments"]):
        packed = PackedSources(*seg["source"])
        docs += [(si * corpus["rows"] + i, packed[i])
                 for i in range(seg["n"])]
    lines = []
    for doc_id, src in docs:
        lines += [json.dumps({"index": {"_index": "bulk",
                                        "_id": str(doc_id)}}),
                  json.dumps(src)]
    status, reply = _call(server, "POST", "/_bulk?refresh=true",
                          "\n".join(lines) + "\n")
    assert status == 200 and not reply["errors"], reply
    node.broadcast_actions.refresh(INDEX)
    some = docs[7][1]
    alt = next(src for _, src in docs if "alternatenames" in src)
    bodies = [gn.request_body(e, 10) for e in make_pool(
        11, [(None, 1, *src["location"][::-1]) for _, src in docs], 3)]
    bodies += [{"query": q, "size": 10} for q in (
        {"term": {"country_code.raw": some["country_code"]}},
        {"match": {"timezone": some["timezone"]}},
        {"match": {"name": some["name"]}},
        {"term": {"alternatenames.raw": alt["alternatenames"]}},
        {"range": {"elevation": {"gte": 100}}})]
    for body in bodies:
        got = [_call(server, "POST", f"/{name}/_search",
                     json.dumps(body))[1] for name in (INDEX, "bulk")]
        assert got[0]["hits"]["total"] == got[1]["hits"]["total"] > 0, \
            (body, got)
        a, b = ([(h["_id"], h["_source"]) for h in g["hits"]["hits"]]
                for g in got)
        assert a == b, body
        # a match sums its terms in the order its row holds them: the
        # generator's rows and a built segment's may differ by an ulp
        assert [h["_score"] for h in got[0]["hits"]["hits"]] == \
            pytest.approx([h["_score"] for h in got[1]["hits"]["hits"]],
                          rel=1e-6), body


def test_a_script_the_engine_cannot_evaluate_is_an_error(node, server):
    """``doc['name'].lat`` binds nothing (name is no geo_point): the
    reply is an error status, never HTTP 200 with no hits — and the
    request's own error is no device fault: nothing falls back to the
    eager path and the plane breaker stays closed."""
    node.indices_service.create_index(INDEX, CONFIG["index"])
    _bulk(server, INDEX, make_places(3)[:40])
    body = {"query": {"function_score": {
        "query": {"match_all": {}},
        "functions": [{"script_score": {"script": {
            "lang": "expression", "inline": "doc['name'].lat * _score"}}}]}},
        "size": 10}
    before = jit_exec.cache_stats()
    for _ in range(3):
        status, reply = _call(server, "POST", f"/{INDEX}/_search",
                              json.dumps(body))
        assert status == 400, reply
        assert reply["error"]["type"] == "script_exception", reply
    after = jit_exec.cache_stats()
    assert after["fallbacks"] == before["fallbacks"]
    assert after["plane_breaker"]["state"] == "closed", after["plane_breaker"]
    # the bindings the cell uses answer
    status, reply = _call(server, "POST", f"/{INDEX}/_search", json.dumps(
        gn.request_body([gn.EXPRESSION, None], 10)))
    assert status == 200 and reply["hits"]["total"] == 40, reply


def test_a_date_gauss_reads_the_double_double_column(node):
    """Dates 10 s apart round a 2015 origin: float32 alone steps by
    131,072 ms there, the double-double column does not."""
    node.indices_service.create_index("dates", {"mappings": {"_doc": {
        "properties": {"t": {"type": "date"}}}}})
    origin = 1433116800000.0                       # 2015-06-01T00:00:00Z
    offsets = [10_000.0 * k for k in range(-12, 13)]
    for i, off in enumerate(offsets):
        node.index_doc("dates", str(i), {"t": int(origin + off)})
    node.broadcast_actions.refresh("dates")
    reply = node.search("dates", {"query": {"function_score": {
        "query": {"match_all": {}},
        "gauss": {"t": {"origin": "2015-06-01T00:00:00Z", "scale": "1m",
                        "decay": 0.5}}}}, "size": len(offsets)})
    sigma2 = 60_000.0 ** 2 / (2.0 * math.log(2.0))
    for h in reply["hits"]["hits"]:
        off = offsets[int(h["_id"])]
        assert h["_score"] == pytest.approx(
            math.exp(-off * off / (2.0 * sigma2)), rel=1e-5), (off, h)


SMALL = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 2048, "docs": 3000,
                              "clusters": 32, "vocab": 2000}}


@pytest.mark.parametrize("seed", [4100003611, 2**31 + 36, 7])
def test_a_bfloat16_reference_fails_the_cells_limits(seed):
    """The control: the reference in the program's place with every float
    in bfloat16. It has to come out as not correct on every seed, by the
    cell's own limits; the float64 reference against itself passes."""
    corpus = gn.generate(SMALL, seed)
    pool = gn.query_pool(corpus, {"pool": 12, "ops": {op: 1
                                                      for op in gn.OPS}},
                         np.random.default_rng([seed, 13]),
                         np.random.default_rng(1))
    ref = gn.Reference(corpus, pool)
    worst: dict = {}
    for q in pool:
        got = gn.compare(ref.scores(q), REQUEST, *gn.control_hits(ref, q, 10))
        worst = {n: max(worst.get(n, 0.0), v) for n, v in got.items()}
    assert worst["score_gap"] > LIMITS["score_gap"], worst
    assert worst["ops_unsampled"] == 0
    self_ref = gn.Reference(corpus, pool)
    for q in pool:
        s = self_ref.scores(q)
        top = np.lexsort((np.arange(len(s)), -s))[:10]
        got = gn.compare(s, REQUEST, top, np.asarray(s[top]), len(s))
        # no sources in these answers: ``source_wrong`` is not counted
        assert all(got.get(n, 0) <= LIMITS[n] for n in LIMITS), got
