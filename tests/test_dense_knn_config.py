"""The benchmark configuration ``dense768-cosine-knn`` at a size a test run
holds (768 dimensions kept, rows cut), on the CPU:

* the SERVED path — REST → SearchActions → the scheduler's ``knn`` lane →
  planner → ``run_knn_hybrid_batch`` — against the plain float64 reference
  of ``benchmarks/corpora/dense_vectors.py`` under the cell's own limits,
  three seeds, concurrent clients so that batches of more than one form;
* the precision the configuration states, read from the LOWERED program,
  and the bfloat16 control failing the same comparison; the two-stage
  selection of long rows against ``lax.top_k``;
* ``Segment.from_packed_vectors`` ≡ the same documents through ``_bulk``;
* one host normalization, one host copy, one upload per vector column, and
  ``_cat/hbm`` reporting the vector bytes once;
* the in-flight book and the ``knn_rows_*`` counters reconciled with the
  scheduler's.

Each test carries a time limit of its own (``LIMIT_S``).
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import signal
import threading
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.index.segment import Segment, doc_count_bucket
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.observability import costs
from elasticsearch_tpu.observability import tracing as obs_trace
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.phase import (ShardSearcher,
                                            parse_search_request)

REPO = Path(__file__).resolve().parent.parent
CELL = "dense768-knn.search-k10-c16"
LIMIT_S = 50
DIMS = 768


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dv = _load(REPO / "benchmarks" / "corpora" / "dense_vectors.py",
           "bench_dense_vectors_for_tier1")
CONFIG = json.loads((REPO / "benchmarks" / "configs"
                     / "dense768-cosine-knn.json").read_text())
LIMITS = json.loads((REPO / "benchmarks" / "workloads"
                     / f"{CELL}.json").read_text())["limits"]
REQUEST = json.loads((REPO / "benchmarks" / "traffic"
                      / "search-k10-c16.json").read_text()
                     )["streams"][0]["request"]
SMALL = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 2048, "centres": 32}}


@pytest.fixture(autouse=True)
def time_limit():
    """A time limit of the test's own, well under a minute."""
    if threading.current_thread() is not threading.main_thread() \
            or not hasattr(signal, "SIGALRM"):
        yield
        return

    def over(signum, frame):
        raise TimeoutError(f"test ran longer than {LIMIT_S} s")
    was = signal.signal(signal.SIGALRM, over)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, was)


@pytest.fixture
def node(tmp_path):
    jit_exec.clear_cache()
    # one batch in flight at a time: while it runs, the other clients'
    # requests meet in the queue and leave as ONE batch
    n = Node({"search.scheduler.max_in_flight": 1},
             data_path=tmp_path / "n").start()
    yield n
    n.close()
    jit_exec.clear_cache()


def _create(node, name: str) -> None:
    node.indices_service.create_index(name, CONFIG["index"])


def _call(server, method: str, path: str, body=None):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=120)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"}
                     if body else {})
        resp = conn.getresponse()
        raw = resp.read()
        assert resp.status < 300, (resp.status, raw[:300])
        return json.loads(raw) if raw[:1] in (b"{", b"[") else raw.decode()
    finally:
        conn.close()


def _searcher(node, name: str) -> ShardSearcher:
    svc = node.indices_service.indices[name]
    return ShardSearcher(0, device_reader_for(svc.engine(0)),
                         svc.mapper_service, index_name=name)


def _knn_req(vec, k: int = 10):
    return parse_search_request({
        "knn": {"field": "emb", "query_vector": [float(x) for x in vec],
                "k": k, "num_candidates": 100}, "size": k})


def _lane_dispatches(lane: str) -> int:
    return sum(costs.lane_rollup(nid).get(lane, {}).get("dispatches", 0)
               for nid in (costs.node_ids() or [""]))


# ---------------------------------------------------------------------------
# the served path against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [4100002811, 2**31 + 12, 13])
def test_served_path_matches_the_plain_reference(node, seed):
    corpus = dv.generate(SMALL, seed)
    _create(node, "dense768")
    dv.install(corpus, node, "dense768")
    server = RestServer(node, host="127.0.0.1", port=0).start()
    clients, each = 8, 6
    pool = dv.query_pool(corpus, {"pool": clients * each},
                         np.random.default_rng([seed, 7]), None)
    try:
        _call(server, "POST", "/dense768/_refresh")
        assert _call(server, "GET", "/dense768/_count")["count"] \
            == corpus["n_docs"]
        before = jit_exec.cache_stats()
        sched0 = node.search_actions.scheduler.stats()
        book0 = obs_trace.book_stats()
        lane0 = _lane_dispatches("knn")
        replies, errors = {}, []

        def client(ci: int) -> None:
            try:
                for q in pool[ci::clients]:
                    req = dv.request(REQUEST, [q], "dense768")
                    replies[q["id"]] = _call(server, "POST", req["path"],
                                             req["body"])
            except Exception as e:       # noqa: BLE001 — reported below
                errors.append(e)
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=LIMIT_S)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        assert len(replies) == len(pool)
        (served,) = _call(server, "GET", "/_nodes/stats")["nodes"].values()
    finally:
        server.stop()
    after = jit_exec.cache_stats()
    sched1 = node.search_actions.scheduler.stats()
    ref = dv.Reference(corpus, pool)
    worst: dict = {}
    for q in pool:
        got = dv.compare(ref.scores(q), REQUEST,
                         *dv.parse_reply(replies[q["id"]]))
        worst = {n: max(worst.get(n, 0.0), v) for n, v in got.items()}
    for name, limit in LIMITS.items():
        assert worst[name] <= limit, (name, worst)
    assert worst["hits_total"] == corpus["n_docs"]
    # the knn lane did the work, in batches of more than one, and every
    # launch was closed by its drain
    delivered = sched1["delivered"] - sched0["delivered"]
    launched = sched1["batches_launched"] - sched0["batches_launched"]
    assert delivered == len(pool) and sched1["reconciled"]
    assert launched < delivered, "no batch of more than one formed"
    assert _lane_dispatches("knn") - lane0 == launched
    assert after["knn_fallback_reasons"] == before["knn_fallback_reasons"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["knn_rows_real"] - before["knn_rows_real"] == delivered
    assert after["knn_rows_padded"] - before["knn_rows_padded"] \
        == sched1["pad_rows"] - sched0["pad_rows"]
    book1 = obs_trace.book_stats()
    assert book1["launches"] - book0["launches"] == launched
    assert book1["launches_without_drain"] \
        == book0["launches_without_drain"]
    # what an operator reads: the hold's two counters are served (how
    # often the rule engages is the backend's timing, and not asserted),
    # the books are whole and no launch was left without its drain
    assert {"batches_held", "hold_ms"} <= set(served["scheduler"])
    assert served["scheduler"]["reconciled"]
    assert served["device"]["launches_without_drain"] \
        - book0["launches_without_drain"] == 0      # the book is the process's


def test_a_lone_knn_search_closes_its_launch_at_its_drain(node):
    corpus = dv.generate(SMALL, 5)
    _create(node, "dense768")
    dv.install(corpus, node, "dense768")
    searcher = _searcher(node, "dense768")
    book0 = obs_trace.book_stats()
    res = searcher.query_phase(_knn_req(corpus["segments"][0]["vecs"][3]))
    assert int(res.doc_ids[0]) == 3
    book1 = obs_trace.book_stats()
    assert book1["launches"] - book0["launches"] == 1
    assert book1["launches_in_flight"] == 0
    assert book1["launches_without_drain"] \
        == book0["launches_without_drain"]


# ---------------------------------------------------------------------------
# the precision the configuration states
# ---------------------------------------------------------------------------

def test_the_lowered_knn_program_carries_the_stated_precision(
        node, monkeypatch):
    """Read from ``lower(...).as_text()``, not from the source: every
    ``dot_general`` of the knn program runs at HIGHEST, as
    ``configs/dense768-cosine-knn.json`` says."""
    assert CONFIG["precision"] == "float32, matmul precision HIGHEST"
    corpus = dv.generate(SMALL, 9)
    _create(node, "dense768")
    dv.install(corpus, node, "dense768")
    texts = []

    class Jitted:
        def __init__(self, fn, **kw):
            self.jitted = jax.jit(fn, **kw)

        def lower(self, *args):
            lowered = self.jitted.lower(*args)
            texts.append(lowered.as_text())
            return lowered

    proxy = types.SimpleNamespace(
        **{n: getattr(jax, n) for n in dir(jax) if not n.startswith("__")})
    proxy.jit = Jitted
    monkeypatch.setattr(jit_exec, "jax", proxy)
    searcher = _searcher(node, "dense768")
    reqs = [_knn_req(corpus["segments"][0]["vecs"][i]) for i in range(4)]
    assert searcher.query_phase_batch(reqs) is not None
    dots = [ln for text in texts for ln in text.splitlines()
            if "dot_general" in ln]
    assert len(dots) == len(corpus["segments"])
    for ln in dots:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln


@pytest.mark.parametrize("seed", [4100002811, 2**31 + 12, 13])
def test_a_bfloat16_product_fails_the_cells_limits(seed):
    """The control: the reference in the program's place with vectors and
    query rounded to bfloat16 before the product. It has to come out as
    not correct on every seed, by the cell's own limits."""
    corpus = dv.generate(SMALL, seed)
    pool = dv.query_pool(corpus, {"pool": 16},
                         np.random.default_rng([seed, 13]), None)
    ref = dv.Reference(corpus, pool)
    worst: dict = {}
    for q in pool:
        got = dv.compare(ref.scores(q), REQUEST,
                         *dv.control_hits(ref, q, 10))
        worst = {n: max(worst.get(n, 0.0), v) for n, v in got.items()}
    assert any(worst[n] > LIMITS[n] for n in LIMITS), worst
    assert worst["score_gap"] > 10 * LIMITS["score_gap"], worst


@pytest.mark.parametrize("k,density,ties", [
    (10, 1.0, True), (100, 0.5, True), (37, 0.001, True),
    (100, 1.0, False), (10, 0.0, False)])
def test_two_stage_selection_is_lax_top_k(k, density, ties):
    """Long rows are selected through the block maxima
    (``ops/vector._block_top_k``): the same values and the same indices
    as ``lax.top_k`` over the whole row, ties to the lower index, with
    masked rows, rows with fewer than k eligible scores and none."""
    import jax.numpy as jnp
    from elasticsearch_tpu.ops import vector as vector_ops
    rng = np.random.default_rng([k, int(density * 1000)])
    b, n = 3, 1 << 16
    assert vector_ops._select_block(n, k) > 0
    assert vector_ops._select_block(1 << 15, k) == 0
    scores = (rng.integers(0, 50, (b, n)) / 7).astype(np.float32) if ties \
        else rng.standard_normal((b, n)).astype(np.float32)
    masks = rng.random((b, n)) < density
    ts, td = vector_ops.filtered_topk_batch(
        jnp.asarray(scores), jnp.asarray(masks), k, 5)
    want_s, want_i = jax.lax.top_k(
        jnp.where(jnp.asarray(masks), jnp.asarray(scores), -jnp.inf), k)
    want_d = np.where(np.asarray(want_s) > -np.inf,
                      np.asarray(want_i) + 5, -1)
    assert np.array_equal(np.asarray(ts), np.asarray(want_s))
    assert np.array_equal(np.asarray(td), want_d)


# ---------------------------------------------------------------------------
# the packed constructor, the host copies, the upload, the ledger
# ---------------------------------------------------------------------------

def test_from_packed_vectors_equals_bulk_indexed_documents(node):
    rng = np.random.default_rng(21)
    n = 300
    vecs = rng.standard_normal((n, DIMS)).astype(np.float32)
    vecs[::3] /= np.linalg.norm(vecs[::3], axis=1, keepdims=True)
    server = RestServer(node, host="127.0.0.1", port=0).start()
    try:
        for name in ("bulked", "packed"):
            _create(node, name)
        lines = []
        for i in range(n):
            lines.append(json.dumps({"index": {"_id": str(i)}}))
            lines.append(json.dumps({"emb": [float(x) for x in vecs[i]]}))
        out = _call(server, "POST", "/bulked/_doc/_bulk",
                    "\n".join(lines) + "\n")
        assert not out["errors"]
        np_docs = doc_count_bucket(n)
        padded = np.zeros((np_docs, DIMS), np.float32)
        padded[:n] = vecs
        exists = np.arange(np_docs) < n
        node.indices_service.indices["packed"].engine(0).install_segment(
            Segment.from_packed_vectors(
                0, "emb", padded, exists, n,
                ids=[str(i) for i in range(n)] + [""] * (np_docs - n)),
            track_versions=False)
        for name in ("bulked", "packed"):
            _call(server, "POST", f"/{name}/_refresh")
        for qi in range(6):
            q = vecs[qi * 7] + 0.3 * rng.standard_normal(DIMS)
            body = json.dumps({"knn": {
                "field": "emb", "query_vector": [float(x) for x in q],
                "k": 10, "num_candidates": 100}, "size": 10})
            a = _call(server, "POST", "/bulked/_search", body)["hits"]
            b = _call(server, "POST", "/packed/_search", body)["hits"]
            assert a["total"] == b["total"] == n
            assert [h["_id"] for h in a["hits"]] \
                == [h["_id"] for h in b["hits"]]
            np.testing.assert_allclose(
                [h["_score"] for h in a["hits"]],
                [h["_score"] for h in b["hits"]], rtol=0, atol=1e-6)
    finally:
        server.stop()
    with pytest.raises(ValueError):
        Segment.from_packed_vectors(0, "emb", padded, np.ones(np_docs, bool),
                                    n)


def test_one_normalization_one_host_copy_one_upload(node, monkeypatch):
    """A column of unit rows is used in place (the segment's, the knn
    lane's and the reader's lazy ``vecs`` are ONE array); a column that
    needs norming is normed once; each goes up once, and ``_cat/hbm``
    books the vector bytes once."""
    calls = []
    real = jit_exec._unit_rows

    def counted(vecs, exists):
        calls.append(vecs.shape)
        return real(vecs, exists)
    monkeypatch.setattr(jit_exec, "_unit_rows", counted)
    corpus = dv.generate(SMALL, 17)
    unit, raw = corpus["segments"][0]["vecs"], \
        corpus["segments"][1]["vecs"] * np.float32(3.0)
    rows = corpus["rows"]
    _create(node, "dense768")
    eng = node.indices_service.indices["dense768"].engine(0)
    for col in (unit, raw):
        eng.install_segment(Segment.from_packed_vectors(
            0, "emb", col, np.ones(rows, bool), rows), track_versions=False)
    server = RestServer(node, host="127.0.0.1", port=0).start()
    try:
        _call(server, "POST", "/dense768/_refresh")
        before = jit_exec.cache_stats()["data_layer"]
        searcher = _searcher(node, "dense768")
        segs = searcher.reader.segments
        res = searcher.query_phase(_knn_req(unit[5]))
        assert int(res.doc_ids[0]) == 5
        # the eager lane and a second searcher read the same entries
        eager = searcher._knn_query_phase_eager(_knn_req(unit[5]))
        assert int(eager.doc_ids[0]) == 5
        _searcher(node, "dense768").query_phase(_knn_req(raw[7]))
        assert calls == [unit.shape, raw.shape]
        host = [jit_exec._host_knn_column(s.seg, "emb", "f32")[0]["vecs"]
                for s in segs]
        assert host[0] is unit is segs[0].seg.vector_fields["emb"].vecs
        assert segs[0].vector["emb"].vecs is unit
        assert host[1] is not raw and segs[1].vector["emb"].vecs is host[1]
        np.testing.assert_allclose(
            np.linalg.norm(host[1].astype(np.float64), axis=1), 1.0,
            atol=1e-6)
        after = jit_exec.cache_stats()["data_layer"]
        column = unit.nbytes + rows          # vecs + the exists mask
        assert after["vector_bytes_uploaded"] \
            - before["vector_bytes_uploaded"] == 2 * column
        text = _call(server, "GET", "/_cat/hbm?h=index,device,bytes")
        booked = sum(int(ln.split()[2]) for ln in text.splitlines()
                     if ln.split()[0] == "dense768")
        assert 2 * unit.nbytes <= booked < 2 * unit.nbytes + (1 << 20)
    finally:
        server.stop()
