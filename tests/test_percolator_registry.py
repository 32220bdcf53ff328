"""Percolator registry — tier-1 regression guards + fidelity surface.

The counter-based contract of the persistent compiled-query registry
(ROADMAP item #4, the PR-3 mesh_program_{hits,misses} discipline applied
to reverse search):

* repeated percolates rebuild ZERO registries and compile ≤1 program per
  plan shape (jit_exec percolate_program_{hits,misses});
* register/unregister invalidates exactly the affected shape bucket;
* the batched path beats the per-query loop ≥10x at a few hundred
  registrations (the CPU microbench the acceptance criteria name);
* responses carry the full fidelity surface: score, size + sort-by-score,
  highlight, aggregations over registration metadata — and the REST
  layer's _mpercolate isolates per-item failures.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import jit_exec


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = Node({}, data_path=tmp_path_factory.mktemp("preg") / "n").start()
    n.indices_service.create_index(
        "pr", {"settings": {"number_of_shards": 1,
                            "number_of_replicas": 0},
               "mappings": {"_doc": {"properties": {
                   "t": {"type": "text", "analyzer": "whitespace"},
                   "k": {"type": "keyword"},
                   "n": {"type": "long"}}}}})
    # three plan shapes: match-on-text, term-on-keyword, range-on-long
    for i in range(30):
        if i % 3 == 0:
            q = {"match": {"t": f"w{i % 7} w{(i + 3) % 7}"}}
        elif i % 3 == 1:
            q = {"term": {"k": f"k{i % 5}"}}
        else:
            q = {"range": {"n": {"gte": i}}}
        n.indices_service.put_percolator(
            "pr", f"q{i}", {"query": q, "group": f"g{i % 4}",
                            "prio": i % 3})
    yield n
    n.close()


def _meta(node, name="pr"):
    return node.cluster_service.state().indices[name]


DOC = {"t": "w0 w3 w5", "k": "k1", "n": 17}


def test_repeated_percolates_rebuild_nothing_and_compile_once(node):
    """Acceptance: repeated percolate() calls rebuild zero registries and
    re-trace zero programs — ≤1 compile per plan shape, counter-verified
    like the collective plane's shape-keyed cache guard."""
    from elasticsearch_tpu.search.percolator import (percolate,
                                                     registry_stats)
    meta = _meta(node)
    miss_before_warm = jit_exec.cache_stats()["percolate_program_misses"]
    first = percolate(meta, DOC)              # warm: sync + compiles
    st0 = registry_stats("pr")
    js0 = jit_exec.cache_stats()
    # one doc layout x three shape buckets → at most one program each
    assert js0["percolate_program_misses"] - miss_before_warm <= \
        st0["shape_buckets"]
    for _ in range(5):
        out = percolate(meta, DOC)
        assert out["total"] == first["total"]
        assert [m["_id"] for m in out["matches"]] == \
            [m["_id"] for m in first["matches"]]
    st1 = registry_stats("pr")
    js1 = jit_exec.cache_stats()
    assert st1["builds"] == st0["builds"] == 1
    assert st1["mapper_rebuilds"] == st0["mapper_rebuilds"] == 1
    assert st1["syncs"] == st0["syncs"]       # metadata unchanged → no-op
    # the compiled-program contract: every repeat was a cache HIT
    assert js1["percolate_program_misses"] == \
        js0["percolate_program_misses"]
    assert js1["percolate_program_hits"] > js0["percolate_program_hits"]


def test_register_unregister_invalidates_exactly_one_bucket(node):
    from elasticsearch_tpu.search.percolator import (percolate,
                                                     registry_for)
    meta = _meta(node)
    percolate(meta, DOC)                      # ensure synced
    reg = registry_for(meta)
    gens0 = reg.bucket_generations()
    inv0 = reg.stats["bucket_invalidations"]
    # register one more query of the EXISTING match shape
    node.indices_service.put_percolator(
        "pr", "qx", {"query": {"match": {"t": "w1 w2"}}, "group": "g0",
                     "prio": 1})
    reg = registry_for(_meta(node))           # sync applies the diff
    gens1 = reg.bucket_generations()
    changed = {s for s in set(gens0) | set(gens1)
               if gens0.get(s, 0) != gens1.get(s, 0)}
    assert len(changed) == 1, "register must touch exactly one bucket"
    assert reg.stats["bucket_invalidations"] - inv0 == 1
    # unregister: same contract, same (now re-touched) bucket
    node.indices_service.delete_percolator("pr", "qx")
    reg = registry_for(_meta(node))
    gens2 = reg.bucket_generations()
    changed2 = {s for s in set(gens1) | set(gens2)
                if gens1.get(s, 0) != gens2.get(s, 0)}
    assert changed2 == changed
    assert reg.stats["bucket_invalidations"] - inv0 == 2
    # matching behavior reflects the removal immediately
    out = percolate(_meta(node), DOC)
    assert "qx" not in {m["_id"] for m in out["matches"]}


def test_batched_path_matches_per_query_loop_in_a_few_dispatches(node):
    """With 1k registered queries the batched path answers as the
    per-query loop does, rebuilds no registry, and rides one compiled
    program per plan-shape group: a handful of dispatches a call, none
    of them a compile once warm. (Its speed is a chip's to read: a CPU
    timing is a count of work, never a speed.)"""
    from elasticsearch_tpu.search.percolator import (percolate,
                                                     percolate_serial,
                                                     registry_stats)
    node.indices_service.create_index(
        "prb", {"settings": {"number_of_shards": 1,
                             "number_of_replicas": 0},
                "mappings": {"_doc": {"properties": {
                    "t": {"type": "text", "analyzer": "whitespace"},
                    "k": {"type": "keyword"},
                    "n": {"type": "long"}}}}})
    for i in range(1000):
        if i % 3 == 0:
            q = {"match": {"t": f"w{i % 40} w{(i + 11) % 40}"}}
        elif i % 3 == 1:
            q = {"term": {"k": f"k{i % 20}"}}
        else:
            q = {"range": {"n": {"gte": i % 90}}}
        node.indices_service.put_percolator("prb", f"b{i}", {"query": q})
    meta = _meta(node, "prb")
    doc = {"t": "w1 w12 w30 w39", "k": "k7", "n": 55}
    js_cold = jit_exec.cache_stats()
    warm = percolate(meta, doc)               # compiles every group
    st0, js0 = registry_stats("prb"), jit_exec.cache_stats()
    groups = js0["percolate_program_misses"] - \
        js_cold["percolate_program_misses"]
    assert 1 <= groups <= 8, groups           # three query shapes here
    ser = percolate_serial(meta, doc)
    batched_rounds = 5
    for _ in range(batched_rounds):
        out = percolate(meta, doc)
    assert [m["_id"] for m in out["matches"]] == \
        [m["_id"] for m in ser["matches"]]
    assert out["total"] == ser["total"] == warm["total"]
    st1, js1 = registry_stats("prb"), jit_exec.cache_stats()
    assert st1["builds"] == st0["builds"] == 1     # zero rebuilds at 1k
    assert st1["syncs"] == st0["syncs"]
    # every call: one dispatch a group, each a program-cache hit, all
    # 1,000 queries on the fused lane
    assert js1["percolate_program_misses"] == js0["percolate_program_misses"]
    assert js1["percolate_program_hits"] - js0["percolate_program_hits"] \
        == groups * batched_rounds
    assert st1["fused_queries"] - st0["fused_queries"] \
        == 1000 * batched_rounds
    assert st1["fallback_queries"] == st0["fallback_queries"]
    assert st1["count"] - st0["count"] == batched_rounds


def test_fidelity_score_sort_size_highlight_aggs(node):
    from elasticsearch_tpu.search.percolator import percolate
    meta = _meta(node)
    out = percolate(meta, DOC, score=True)
    assert out["matches"] and all(
        isinstance(m["_score"], float) for m in out["matches"])
    # sort-by-score: descending, size truncates AFTER the total
    ranked = percolate(meta, DOC, sort=True, size=2)
    scores = [m["_score"] for m in ranked["matches"]]
    assert scores == sorted(scores, reverse=True)
    assert len(ranked["matches"]) == 2 and ranked["total"] > 2
    full = percolate(meta, DOC, sort=True)
    assert ranked["matches"] == full["matches"][:2]
    # highlight rides the probe doc through the standard highlighters
    hl = percolate(meta, {"t": "w0 w3 zz"},
                   highlight={"fields": {"t": {}}})
    hits = [m for m in hl["matches"] if "highlight" in m]
    assert hits and any("<em>" in frag
                        for m in hits for frag in m["highlight"]["t"])
    # aggs aggregate over the registration metadata of the MATCHES
    agg = percolate(meta, DOC,
                    aggs={"by_group": {"terms": {"field": "group"}}})
    buckets = agg["aggregations"]["by_group"]["buckets"]
    assert sum(b["doc_count"] for b in buckets) == agg["total"]
    # filter constrains which registrations participate
    filt = percolate(meta, DOC, reg_filter={"term": {"group": "g0"}})
    assert set(m["_id"] for m in filt["matches"]) <= \
        set(m["_id"] for m in out["matches"])


def test_fallback_lane_shapes_still_match(node):
    """Scripts/joins/geo_shape ride the per-query eager lane — behavior
    must not regress for shapes the fused path can't express."""
    from elasticsearch_tpu.search.percolator import (percolate,
                                                     registry_stats)
    node.indices_service.put_percolator(
        "pr", "q-script",
        {"query": {"function_score": {
            "query": {"match": {"t": "w0"}},
            "functions": [{"script_score": {"script": "_score * 2"}}]}}})
    try:
        out = percolate(_meta(node), DOC, score=True)
        ids = {m["_id"] for m in out["matches"]}
        assert "q-script" in ids
        st = registry_stats("pr")
        assert st["fallback_queries"] > 0
    finally:
        node.indices_service.delete_percolator("pr", "q-script")


# ---- REST surface ----------------------------------------------------------

@pytest.fixture(scope="module")
def rest(node):
    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.handlers import register_all
    rc = RestController()
    register_all(rc, node)

    def call(method, uri, body=b""):
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
        elif isinstance(body, str):
            body = body.encode()
        return rc.dispatch(method, uri, body)
    return call


def test_rest_percolate_scores_and_format(rest):
    st, out = rest("GET", "/pr/_percolate",
                   {"doc": DOC, "track_scores": True, "sort": True})
    assert st == 200 and out["matches"]
    assert all("_score" in m for m in out["matches"])
    st, out = rest("GET", "/pr/_percolate?percolate_format=ids",
                   {"doc": DOC})
    assert st == 200 and all(isinstance(m, str) for m in out["matches"])


def test_rest_mpercolate_isolates_per_item_errors(rest):
    lines = [
        json.dumps({"percolate": {"index": "pr"}}),
        json.dumps({"doc": DOC}),
        "{not-json",                                   # malformed header
        json.dumps({"doc": DOC}),
        json.dumps({"percolate": {"index": "pr"}}),
        json.dumps({"nodoc": True}),                   # missing [doc]
        json.dumps({"percolate": {"index": "no_such_index"}}),
        json.dumps({"doc": DOC}),
        json.dumps({"count": {"index": "pr"}}),
        json.dumps({"doc": DOC}),
        json.dumps({"percolate": {"index": "pr"}}),    # trailing header,
    ]                                                  # no doc line
    st, out = rest("POST", "/_mpercolate", "\n".join(lines))
    assert st == 200
    r = out["responses"]
    assert len(r) == 6
    assert "error" not in r[0] and r[0]["total"] > 0
    assert "error" in r[1] and "error" in r[2] and "error" in r[3]
    assert "error" not in r[4] and "matches" not in r[4]   # count verb
    assert "error" in r[5]
    # well-formed items matched despite the broken neighbours
    assert r[0]["total"] == r[4]["total"]


def test_rest_stats_and_cat_expose_registry_counters(rest):
    st, out = rest("GET", "/pr/_stats")
    perc = out["indices"]["pr"]["total"]["percolate"]
    assert perc["total"] > 0 and perc["queries"] >= 30
    assert perc["registry"]["builds"] == 1
    assert perc["registry"]["shape_buckets"] >= 3
    assert perc["registry"]["program_misses"] > 0
    st, cat = rest("GET", "/_cat/indices?v&h=index,percolate.queries,"
                          "percolate.total")
    row = [ln for ln in cat.splitlines() if ln.startswith("pr ")][0]
    cells = row.split()
    assert int(cells[1]) >= 30 and int(cells[2]) > 0
    # node rollup mirrors the per-index section
    st, ns = rest("GET", "/_nodes/stats")
    nid = next(iter(ns["nodes"]))
    roll = ns["nodes"][nid]["indices"]["percolate"]
    assert roll["total"] >= perc["total"] and roll["queries"] >= 30
    jit = ns["nodes"][nid]["indices"]["jit"]
    assert jit["percolate_program_misses"] > 0


def test_mpercolate_multi_doc_packs_shared_programs(node):
    """A multi-doc percolate_many batch: same-layout probes share lanes'
    compiled programs — a second identical batch compiles NOTHING."""
    from elasticsearch_tpu.search.percolator import percolate_many
    meta = _meta(node)
    docs = [{"t": f"w{i % 7} w{(i + 1) % 7} w3", "k": f"k{i % 5}",
             "n": 10 + i} for i in range(8)]
    items = [{"doc": d} for d in docs]
    first = percolate_many(meta, items)
    js0 = jit_exec.cache_stats()
    second = percolate_many(meta, items)
    js1 = jit_exec.cache_stats()
    assert js1["percolate_program_misses"] == \
        js0["percolate_program_misses"]
    for a, b in zip(first, second):
        assert "_exception" not in a
        assert [m["_id"] for m in a["matches"]] == \
            [m["_id"] for m in b["matches"]]
    # per-doc isolation: each item's matches equal a singleton percolate
    from elasticsearch_tpu.search.percolator import percolate
    for d, r in zip(docs, first):
        solo = percolate(meta, d)
        assert [m["_id"] for m in solo["matches"]] == \
            [m["_id"] for m in r["matches"]]
        assert solo["total"] == r["total"]
