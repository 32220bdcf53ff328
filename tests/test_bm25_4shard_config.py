"""The deployment ``msmarco-passage-bm25-4shard`` at a small size: an
index of four shards on a node whose setting ``search.mesh: 1x4`` puts
one shard on each of four devices, served over REST by the collective
plane — against a reference written here, in this file's own terms.

The reference is BM25 from the published formula in float64 with the
statistics of the shard a document lives in (Elasticsearch's default
``query_then_fetch``): per shard ``N``, ``df(t)`` and ``avgdl`` are
counted from the raw documents; which shard holds which document is the
one thing taken from the program (its hashed ``_id`` routing). The
expected answer is the top-k of the union.
"""

import http.client
import json
import math

import numpy as np
import pytest

from elasticsearch_tpu.common import IllegalArgumentError
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.observability import tracing
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search import jit_exec

SEEDS = (11, 23, 47)
N_DOCS, VOCAB, K, ITEMS = 700, 400, 50, 64
K1, B = 1.2, 0.75
MAPPINGS = {"_doc": {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"}}}}


def make_docs(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    p = np.arange(1, VOCAB + 1, dtype=np.float64) ** -1.07
    p /= p.sum()
    docs = {}
    for i in range(N_DOCS):
        n = int(rng.integers(5, 40))
        docs[str(i)] = [f"w{t:03d}" for t in rng.choice(VOCAB, n, p=p)]
    return docs


def make_queries(seed: int, docs: dict) -> list:
    """64 queries of 2..12 distinct terms that occur, lengths mixed."""
    rng = np.random.default_rng([seed, 1])
    present = sorted({t for d in docs.values() for t in d})
    return [list(rng.choice(present, 2 + (7 * i) % 11, replace=False))
            for i in range(ITEMS)]


class Rest:
    def __init__(self, server):
        self.conn = http.client.HTTPConnection(server.host, server.port,
                                               timeout=300)

    def call(self, method, path, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        self.conn.request(method, path, body=body, headers={
            "Content-Type": "application/json"} if body else {})
        resp = self.conn.getresponse()
        raw = resp.read()
        assert resp.status < 300, (method, path, resp.status, raw[:300])
        ctype = resp.getheader("Content-Type") or ""
        return json.loads(raw) if "json" in ctype else raw.decode()


def load(rest: Rest, node, name: str, docs: dict, plane: bool) -> dict:
    """The documents through ``_bulk`` in two rounds (two segments a
    shard) → doc id → shard, read back from the shard engines."""
    rest.call("PUT", f"/{name}", {
        "settings": {"number_of_shards": 4, "number_of_replicas": 0,
                     "index.search.collective_plane": plane},
        "mappings": MAPPINGS})
    rest.call("GET", f"/_cluster/health/{name}"
                     "?wait_for_status=green&timeout=30s")
    ids = sorted(docs, key=int)
    for part in (ids[:N_DOCS // 2], ids[N_DOCS // 2:]):
        lines = []
        for i in part:
            lines += [json.dumps({"index": {"_index": name, "_type": "_doc",
                                            "_id": i}}),
                      json.dumps({"body": " ".join(docs[i])})]
        out = rest.call("POST", "/_bulk", "\n".join(lines) + "\n")
        assert not out["errors"]
        rest.call("POST", f"/{name}/_refresh")
    svc = node.indices_service.indices[name]
    shard_of = {}
    for s in range(4):
        for seg in svc.engine(s).acquire_searcher().segments:
            for i in seg.ids[:seg.num_docs]:
                shard_of[i] = s
    assert len(shard_of) == len(docs)
    return shard_of


def msearch(rest: Rest, name: str, queries: list, size: int = K) -> list:
    lines = []
    for q in queries:
        lines += [json.dumps({"index": name}),
                  json.dumps({"query": {"match": {"body": " ".join(q)}},
                              "size": size})]
    out = rest.call("POST", "/_msearch", "\n".join(lines) + "\n")
    assert all("hits" in r for r in out["responses"]), out
    return [([(h["_id"], h["_score"]) for h in r["hits"]["hits"]],
             r["hits"]["total"]) for r in out["responses"]]


def reference(docs: dict, shard_of: dict, query: list) -> dict:
    """doc id → float64 BM25 score under its OWN shard's statistics."""
    scores = {}
    for s in range(4):
        mine = [i for i in docs if shard_of[i] == s]
        n = len(mine)
        avgdl = sum(len(docs[i]) for i in mine) / n
        for t in query:
            df = sum(1 for i in mine if t in docs[i])
            if not df:
                continue
            idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
            for i in mine:
                tf = docs[i].count(t)
                if tf:
                    norm = K1 * (1.0 - B + B * len(docs[i]) / avgdl)
                    scores[i] = scores.get(i, 0.0) \
                        + idf * tf * (K1 + 1.0) / (tf + norm)
    return scores


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """Node A: ``search.mesh: 1x4`` (four of the eight host devices, one
    shard each). Node B: no setting — the one-device plane (index
    ``one-<seed>``) and the RPC fan-out (``fan-<seed>``,
    ``index.search.collective_plane: false``). The same documents and
    the same request on all three, per seed."""
    base = tmp_path_factory.mktemp("bm25_4shard")
    low = {"cluster.routing.allocation.disk.watermark.low": "1.0"}
    a = Node({**low, "search.mesh": "1x4"}, data_path=base / "a").start()
    b = Node(dict(low), data_path=base / "b").start()
    sa, sb = RestServer(a, port=0).start(), RestServer(b, port=0).start()
    ra, rb = Rest(sa), Rest(sb)
    out = {"a": a, "b": b, "ra": ra, "rb": rb, "seeds": {}}
    for seed in SEEDS:
        docs = make_docs(seed)
        queries = make_queries(seed, docs)
        shard_of = load(ra, a, f"mesh-{seed}", docs, True)
        assert load(rb, b, f"one-{seed}", docs, True) == shard_of
        assert load(rb, b, f"fan-{seed}", docs, False) == shard_of
        before = jit_exec.cache_stats()
        book = tracing.book_stats()
        mesh = msearch(ra, f"mesh-{seed}", queries)
        after = jit_exec.cache_stats()
        out["seeds"][seed] = {
            "docs": docs, "queries": queries, "shard_of": shard_of,
            "mesh": mesh,
            "one": msearch(rb, f"one-{seed}", queries),
            "fan": msearch(rb, f"fan-{seed}", queries),
            "counters": {k: after[k] - before[k] for k in (
                "plane_items_served", "plane_items_fallback",
                "plane_dispatches", "plane_gather_bytes",
                "plane_fallbacks", "merge_items_array",
                "merge_items_comparator")},
            "book": {k: tracing.book_stats()[k] - book[k] for k in (
                "launches", "launches_without_drain")}}
    yield out
    sa.stop()
    sb.stop()
    a.close()
    b.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_served_hits_equal_the_per_shard_reference(deployment, seed):
    d = deployment["seeds"][seed]
    for query, (hits, total) in zip(d["queries"], d["mesh"]):
        ref = reference(d["docs"], d["shard_of"], query)
        assert total == len(ref)
        want = min(K, len(ref))
        assert len(hits) == want and len({i for i, _ in hits}) == want
        best = sorted(ref.values(), reverse=True)[:want]
        served = [s for _, s in hits]
        assert served == sorted(served, reverse=True)
        # every served document carries ITS reference score, and the
        # served scores are the reference's k best (a tie at the k-th
        # place may pick either document)
        np.testing.assert_allclose(served, [ref[i] for i, _ in hits],
                                   rtol=2e-6)
        np.testing.assert_allclose(served, best, rtol=2e-6)


@pytest.mark.parametrize("seed", SEEDS)
def test_four_devices_one_device_and_fanout_give_the_same_hits(
        deployment, seed):
    """Ties the shares to the whole: four shards' top-k met by the
    all_gather = the one-device plane's local merge = the coordinator's
    merge of the per-shard RPC results — ids, float32 scores, totals."""
    d = deployment["seeds"][seed]
    assert d["mesh"] == d["one"]
    # the fan-out scores with another compiled program (one a shard's
    # reader), whose sum along a document's slots may round its last bit
    # differently (tests/test_ops.py, PR 33: 1.4e-7 relative at the
    # most): the same documents in the same order, the same totals, the
    # float32 scores to that bit
    for (hits_m, total_m), (hits_f, total_f) in zip(d["mesh"], d["fan"]):
        assert [i for i, _ in hits_m] == [i for i, _ in hits_f]
        assert total_m == total_f
        np.testing.assert_allclose([s for _, s in hits_m],
                                   [s for _, s in hits_f], rtol=3e-7)


def test_global_statistics_would_give_other_scores(deployment):
    """The reference's point: with ONE idf and avgdl over all shards
    (dfs_query_then_fetch, which this deployment does not use) the
    served scores would not be matched."""
    d = deployment["seeds"][SEEDS[0]]
    worst = 0.0
    for query, (hits, _total) in zip(d["queries"], d["mesh"]):
        ref = reference_global(d["docs"], query)
        worst = max(worst, max(abs(s - ref[i]) / ref[i] for i, s in hits))
    assert worst > 1e-3


def reference_global(docs, query):
    # the same formula with every document in one statistics group
    scores = {}
    n = len(docs)
    avgdl = sum(len(v) for v in docs.values()) / n
    for t in query:
        df = sum(1 for v in docs.values() if t in v)
        idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
        for i, v in docs.items():
            tf = v.count(t)
            if tf:
                scores[i] = scores.get(i, 0.0) + idf * tf * (K1 + 1.0) / (
                    tf + K1 * (1.0 - B + B * len(v) / avgdl))
    return scores


@pytest.mark.parametrize("seed", SEEDS)
def test_a_mixed_length_msearch_is_one_mesh_dispatch(deployment, seed):
    c = deployment["seeds"][seed]["counters"]
    assert c["plane_dispatches"] == 1
    assert c["plane_items_served"] == ITEMS
    assert c["plane_items_fallback"] == 0 and c["plane_fallbacks"] == 0
    # S x B x k bucket x (score f32 + doc i32) + S x B counts
    assert c["plane_gather_bytes"] == 4 * ITEMS * (64 * 8 + 4)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_score_ordered_msearch_merges_by_the_array_sort(deployment, seed):
    c = deployment["seeds"][seed]["counters"]
    assert c["merge_items_array"] == ITEMS
    assert c["merge_items_comparator"] == 0


def test_the_plane_launch_closes_at_its_drain(deployment):
    for d in deployment["seeds"].values():
        assert d["book"]["launches"] >= 1
        assert d["book"]["launches_without_drain"] == 0
    names = {r[3] for r in tracing.ring_records(0, 2 ** 62)}
    for span in ("plane.resolve", "plane.upload", "plane.enqueue",
                 "plane.drain", "plane.split", "jit.drain",
                 "jit.enqueue"):
        assert span in names, span


def test_bytes_sit_on_all_four_devices_once(deployment):
    a, ra = deployment["a"], deployment["ra"]
    stats = a.local_node_stats()
    per_device = stats["device_memory"]["per_device"]
    mesh_ids = [str(i) for i in stats["device"]["mesh"]["device_ids"]]
    assert len(mesh_ids) == 4
    placed = [per_device[d] for d in mesh_ids]
    total = sum(per_device.values())
    # nothing outside the four owners (no reader copy, no stacked pack
    # on the default device), a quarter each
    assert total == sum(placed)
    for b in placed:
        assert abs(b - total / 4) <= 0.02 * total / 4
    # one resident copy: the ledger's total is the operands the program
    # reads, which ARE the placed blocks
    columns = 0
    for seed in SEEDS:
        pack = a.indices_service.indices[f"mesh-{seed}"] \
            .__dict__["_mesh_cache"][1]
        assert pack.placed(pack.mesh) and pack.spd == 1
        columns += sum(arr.nbytes for flat in pack._flats for arr in flat)
    assert columns <= total <= 1.05 * columns
    assert stats["breakers"]["fielddata"]["estimated_size_in_bytes"] \
        == total
    # the same rollup over REST
    text = ra.call("GET", "/_cat/hbm?totals=true&h=index,device,bytes")
    rows = [ln.split() for ln in text.splitlines()]
    by_dev = {r[1]: int(r[2]) for r in rows if r[0] == "_total"}
    assert by_dev == {d: per_device[d] for d in mesh_ids}
    # node B, no setting: blocks, the stacked pack and the fan-out's
    # reader on the default device, as ever
    per_b = deployment["b"].local_node_stats()["device_memory"][
        "per_device"]
    assert set(per_b) == {"-"}


def test_the_geometry_is_in_nodes_stats(deployment):
    doc = deployment["ra"].call("GET", "/_nodes/stats")
    (node_doc,) = doc["nodes"].values()
    assert node_doc["device"]["mesh"]["dp"] == 1
    assert node_doc["device"]["mesh"]["shard"] == 4
    assert node_doc["device"]["mesh"]["setting"] == "1x4"
    b = deployment["b"].local_node_stats()["device"]["mesh"]
    assert (b["dp"], b["shard"], b["setting"]) == (1, 1, None)
    for key in ("plane_items_served", "plane_items_fallback",
                "plane_dispatches", "plane_gather_bytes",
                "merge_items_array", "merge_items_comparator",
                "topk_rows_two_stage", "topk_rows_full"):
        assert key in node_doc["indices"]["jit"]


@pytest.mark.parametrize("geometry,says", [
    ("3x3", "valid dp×shard factorizations"),
    ("1x16", "valid dp×shard factorizations"),
    ("0x4", "valid dp×shard factorizations"),
    ("four", "not of the form <dp>x<shard>"),
])
def test_a_bad_geometry_is_refused_at_node_start(tmp_path, geometry, says):
    node = Node({"search.mesh": geometry}, data_path=tmp_path / "n")
    with pytest.raises(IllegalArgumentError, match=says):
        node.start()
    assert node.serving_mesh is None


def test_two_shards_a_device_and_dp_replicas_give_the_same_hits(
        deployment, tmp_path):
    """``2x2``: four shards over a shard axis of two (each owner
    concatenates its own two blocks) and two dp replicas of every
    column — the same hits as one device, and the setting is installed
    and removed with the node."""
    seed = SEEDS[0]
    d = deployment["seeds"][seed]
    node = Node({"search.mesh": "2x2",
                 "cluster.routing.allocation.disk.watermark.low": "1.0"},
                data_path=tmp_path / "n").start()
    server = RestServer(node, port=0).start()
    try:
        mesh = jit_exec.serving_mesh()
        assert mesh is node.serving_mesh
        assert dict(mesh.shape) == {"dp": 2, "shard": 2}
        assert node.search_actions._plane_mesh_get() is mesh
        rest = Rest(server)
        assert load(rest, node, "two", d["docs"], True) == d["shard_of"]
        assert msearch(rest, "two", d["queries"]) == d["one"]
        pack = node.indices_service.indices["two"] \
            .__dict__["_mesh_cache"][1]
        assert pack.spd == 2 and pack.placed(mesh)
        assert not pack.composes_in_place(mesh, 4)
        on = {dev.id for flat in pack._flats for arr in flat
              for dev in arr.devices()}
        assert on == {dev.id for dev in mesh.devices.flat}
    finally:
        server.stop()
        node.close()
    assert jit_exec.serving_mesh() is not mesh


def test_a_field_sorted_search_merges_by_the_comparator(deployment):
    """A ``sort`` on a numeric field, served by the plane on the ``1x4``
    mesh, is ordered at the coordinator by the comparator: one item."""
    ra = deployment["ra"]
    ra.call("PUT", "/sorted", {
        "settings": {"number_of_shards": 4, "number_of_replicas": 0},
        "mappings": {"_doc": {"properties": {
            "body": {"type": "text", "analyzer": "whitespace"},
            "n": {"type": "long"}}}}})
    ra.call("GET", "/_cluster/health/sorted"
                   "?wait_for_status=green&timeout=30s")
    lines = []
    for i in range(40):
        lines += [json.dumps({"index": {"_index": "sorted", "_type": "_doc",
                                        "_id": str(i)}}),
                  json.dumps({"body": f"w{i % 3} w9", "n": (i * 7) % 40})]
    assert not ra.call("POST", "/_bulk", "\n".join(lines) + "\n")["errors"]
    ra.call("POST", "/sorted/_refresh")
    before = jit_exec.cache_stats()
    out = ra.call("POST", "/sorted/_search", {
        "query": {"match": {"body": "w9"}}, "size": 15,
        "sort": [{"n": {"order": "desc"}}]})
    after = jit_exec.cache_stats()
    assert [h["sort"][0] for h in out["hits"]["hits"]] == \
        list(range(39, 24, -1))
    assert after["plane_items_served"] - before["plane_items_served"] == 1
    assert after["merge_items_comparator"] - \
        before["merge_items_comparator"] == 1
    assert after["merge_items_array"] == before["merge_items_array"]
    ra.call("DELETE", "/sorted")


def test_long_slots_select_in_two_stages_as_one_full_sort(deployment,
                                                          monkeypatch):
    """Shards whose slot holds 2^16 rows take each slot's top k in two
    stages (``ops/topk.select_top_k``): the plane serves the same ids,
    scores and totals as with one ``lax.top_k`` over every slot, and
    counts 4 shards x 1 slot x 4 rows a form."""
    from elasticsearch_tpu.index.segment import Segment
    from elasticsearch_tpu.ops import topk
    from elasticsearch_tpu.parallel import mesh_engine
    a, ra = deployment["a"], deployment["ra"]
    ra.call("PUT", "/long", {
        "settings": {"number_of_shards": 4, "number_of_replicas": 0},
        "mappings": MAPPINGS})
    ra.call("GET", "/_cluster/health/long?wait_for_status=green&timeout=30s")
    rows, vocab = 1 << 16, 64
    for s in range(4):
        rng = np.random.default_rng([s, 37])
        uterms = np.sort(np.stack([rng.choice(vocab, 6, replace=False)
                                   for _ in range(64)]), axis=1)
        uterms = uterms[rng.integers(0, 64, rows)].astype(np.int32)
        utf = rng.integers(1, 3, uterms.shape).astype(np.float32)
        a.indices_service.indices["long"].engine(s).install_segment(
            Segment.from_packed_text(
                0, "body", terms=[f"w{i:03d}" for i in range(vocab)],
                tokens=None, uterms=uterms, utf=utf,
                doc_len=utf.sum(axis=1).astype(np.int32),
                df=np.bincount(uterms.ravel(), minlength=vocab),
                num_docs=rows, ids=[f"{s}-{i}" for i in range(rows)]),
            track_versions=False)
    queries = [["w001", "w002"], ["w003"], ["w004", "w005", "w006"],
               ["w007", "w008", "w009", "w010"]]

    def run():
        jit_exec.clear_cache()
        mesh_engine.clear_program_cache()
        hits = msearch(ra, "long", queries, size=100)
        st = jit_exec.cache_stats()
        assert st["plane_items_served"] == len(queries)
        return hits, st["topk_rows_two_stage"], st["topk_rows_full"]

    try:
        with monkeypatch.context() as m:
            m.setattr(topk, "_select_block", lambda n, k: 0)
            full, two_rows, full_rows = run()
        assert (two_rows, full_rows) == (0, 4 * len(queries))
        got, two_rows, full_rows = run()
        assert (two_rows, full_rows) == (4 * len(queries), 0)
        assert got == full
        assert all(total > 100 for _, total in got)
    finally:
        ra.call("DELETE", "/long")
