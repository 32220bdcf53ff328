"""The collective plane as a production path: with
`index.search.collective_plane: true`, an eligible dfs_query_then_fetch
on a node holding every shard runs as ONE shard_map program
(parallel/mesh_engine) instead of dfs round + per-shard fan-out — the
response must be indistinguishable from the RPC path (SURVEY §2.2's
"scatter/gather + reduce moves onto ICI collectives"; dfs semantics are
the mesh's native semantics, its statistics round IS global)."""

import numpy as np
import pytest

from elasticsearch_tpu.node import Node

DFS = "dfs_query_then_fetch"


@pytest.fixture(scope="module")
def nodes(tmp_path_factory):
    base = tmp_path_factory.mktemp("cp")
    n = Node({}, data_path=base / "n").start()
    # tests of this module create indices minutes after start-up: on a
    # volume over the 85% low watermark the disk-threshold decider would
    # leave their shards UNASSIGNED once ClusterInfoService has sampled
    # the filesystem ("primary shard is not active"). Only a full disk
    # refuses here; the program's default is untouched.
    n.update_cluster_settings({"transient": {
        "cluster.routing.allocation.disk.watermark.low": 1.0}})
    rng = np.random.default_rng(5)
    for name, plane in (("on", True), ("off", False)):
        n.indices_service.create_index(name, {
            "settings": {"number_of_shards": 4, "number_of_replicas": 0,
                         "index.search.collective_plane": plane},
            "mappings": {"_doc": {"properties": {
                "t": {"type": "text", "analyzer": "whitespace"},
                "v": {"type": "long"}}}}})
    for i in range(300):
        words = " ".join(f"w{int(x)}" for x in rng.zipf(1.5, 6) if x < 40)
        doc = {"t": words or "w1", "v": i}
        n.index_doc("on", str(i), doc)
        n.index_doc("off", str(i), doc)
    n.broadcast_actions.refresh("on")
    n.broadcast_actions.refresh("off")
    yield n
    n.close()


BODIES = [
    {"query": {"match": {"t": "w1 w3"}}, "size": 25},
    {"query": {"bool": {"must": [{"match": {"t": "w2"}}],
                        "filter": [{"range": {"v": {"gte": 100}}}]}},
     "size": 10},
    {"query": {"match": {"t": "w1"}}, "from": 5, "size": 10},
]


def test_mesh_path_matches_fanout(nodes):
    n = nodes
    for body in BODIES:
        a = n.search("on", dict(body), search_type=DFS)
        b = n.search("off", dict(body), search_type=DFS)
        assert a["hits"]["total"] == b["hits"]["total"], body
        ia = [(h["_id"], round(h["_score"], 4)) for h in a["hits"]["hits"]]
        ib = [(h["_id"], round(h["_score"], 4)) for h in b["hits"]["hits"]]
        assert ia == ib, body
        assert a["hits"]["hits"][0]["_source"]    # fetch phase ran
    # the plane actually engaged (cache built on the opted-in index)
    assert "_mesh_cache" in n.indices_service.indices["on"].__dict__
    assert "_mesh_cache" not in n.indices_service.indices["off"].__dict__


def test_mesh_path_metric_aggs(nodes):
    n = nodes
    body = {"query": {"match": {"t": "w2"}}, "size": 0,
            "aggs": {"st": {"stats": {"field": "v"}},
                     "mx": {"max": {"field": "v"}}}}
    a = n.search("on", dict(body), search_type=DFS)
    b = n.search("off", dict(body), search_type=DFS)
    assert a["aggregations"]["mx"]["value"] == \
        b["aggregations"]["mx"]["value"]
    for k in ("count", "min", "max", "sum", "avg"):
        av = a["aggregations"]["st"][k]
        bv = b["aggregations"]["st"][k]
        assert av == pytest.approx(bv, rel=1e-6), (k, av, bv)


def test_sorted_query_rides_the_plane(nodes):
    """Round 5: sort-by-numeric-field IS a mesh shape — in-program
    double-double sort keys through the all_gather merge. Response must
    be indistinguishable from the fan-out, incl. hit['sort'] values."""
    n = nodes
    for body in (
            {"query": {"match": {"t": "w1"}}, "size": 5,
             "sort": [{"v": {"order": "desc"}}]},
            {"query": {"match": {"t": "w1"}}, "size": 5,
             "sort": [{"v": {"order": "asc"}}]},
            {"query": {"match": {"t": "w1 w3"}}, "size": 8,
             "sort": [{"v": "desc"}],
             "post_filter": {"range": {"v": {"gte": 50}}}}):
        a = n.search("on", dict(body), search_type=DFS)
        b = n.search("off", dict(body), search_type=DFS)
        assert a["hits"]["total"] == b["hits"]["total"], body
        assert [(h["_id"], h["sort"]) for h in a["hits"]["hits"]] == \
            [(h["_id"], h["sort"]) for h in b["hits"]["hits"]], body


def test_sorted_search_after_rides_the_plane(nodes):
    n = nodes
    base = {"query": {"match": {"t": "w1"}}, "size": 5,
            "sort": [{"v": {"order": "desc"}}]}
    p1 = n.search("on", dict(base), search_type=DFS)
    cursor = p1["hits"]["hits"][-1]["sort"]
    page2 = dict(base, search_after=cursor)
    a = n.search("on", dict(page2), search_type=DFS)
    b = n.search("off", dict(page2), search_type=DFS)
    assert [h["_id"] for h in a["hits"]["hits"]] == \
        [h["_id"] for h in b["hits"]["hits"]]
    assert not ({h["_id"] for h in a["hits"]["hits"]} &
                {h["_id"] for h in p1["hits"]["hits"]})


def test_ineligible_falls_back(nodes):
    n = nodes
    # numeric terms aggs stay host-side: must fall back and still work
    body = {"query": {"match_all": {}}, "size": 0,
            "aggs": {"t": {"terms": {"field": "v"}}}}
    a = n.search("on", dict(body), search_type=DFS)
    b = n.search("off", dict(body), search_type=DFS)
    assert a["aggregations"]["t"]["buckets"] == \
        b["aggregations"]["t"]["buckets"]
    # plain query_then_fetch keeps per-shard statistics (different
    # semantics) — the plane must not hijack it
    a = n.search("on", {"query": {"match": {"t": "w1"}}, "size": 5})
    b = n.search("off", {"query": {"match": {"t": "w1"}}, "size": 5})
    assert [h["_id"] for h in a["hits"]["hits"]] == \
        [h["_id"] for h in b["hits"]["hits"]]


def test_refresh_invalidates_mesh_cache(nodes):
    n = nodes
    idx = n.indices_service.indices["on"]
    n.search("on", {"query": {"match": {"t": "w1"}}}, search_type=DFS)
    gens0, ms0 = idx.__dict__["_mesh_cache"][:2]
    n.index_doc("on", "fresh-1", {"t": "w1 freshterm", "v": 999})
    # keep the comparison index identical (later tests diff on/off)
    n.index_doc("off", "fresh-1", {"t": "w1 freshterm", "v": 999})
    n.broadcast_actions.refresh("on")
    n.broadcast_actions.refresh("off")
    r = n.search("on", {"query": {"match": {"t": "freshterm"}}},
                 search_type=DFS)
    assert r["hits"]["total"] == 1
    gens1, ms1 = idx.__dict__["_mesh_cache"][:2]
    assert gens1 != gens0 and ms1 is not ms0


def test_msearch_dfs_batch_through_mesh(nodes):
    """A dfs _msearch group on an opted-in index runs as ONE mesh
    program; answers must equal per-item dfs searches on the fan-out
    index (and per-item search_type headers are honored at all)."""
    n = nodes
    items_on = [("on", dict(b), DFS) for b in BODIES[:2]]
    items_off = [("off", dict(b), DFS) for b in BODIES[:2]]
    ra = n.search_actions.multi_search(items_on)["responses"]
    rb = n.search_actions.multi_search(items_off)["responses"]
    for a, b in zip(ra, rb):
        assert "error" not in a and "error" not in b
        assert a["hits"]["total"] == b["hits"]["total"]
        assert [(h["_id"], round(h["_score"], 4))
                for h in a["hits"]["hits"]] == \
            [(h["_id"], round(h["_score"], 4)) for h in b["hits"]["hits"]]


def test_msearch_mixed_shapes_fall_back(nodes):
    n = nodes
    items = [("on", {"query": {"match": {"t": "w1"}}, "size": 3}, DFS),
             ("on", {"query": {"match": {"t": "w2"}}, "size": 3,
                     "sort": [{"v": "desc"}]}, DFS)]
    rs = n.search_actions.multi_search(items)["responses"]
    assert all("error" not in r for r in rs)
    assert rs[1]["hits"]["hits"][0]["_source"]["v"] >= \
        rs[1]["hits"]["hits"][-1]["_source"]["v"]


def test_mesh_cache_breaker_accounted(nodes):
    """The stacked mesh copy reserves fielddata budget and returns it
    when the index closes (review r4)."""
    n = nodes
    n.search("on", {"query": {"match": {"t": "w1"}}}, search_type=DFS)
    cached = n.indices_service.indices["on"].__dict__["_mesh_cache"]
    assert len(cached) == 3 and cached[2] > 0
    fd = n.breaker_service.breaker("fielddata")
    assert fd.used >= cached[2]


def test_mesh_feeds_search_stats(nodes):
    n = nodes
    idx = n.indices_service.indices["on"]
    before = idx.search_stats["query_total"]
    n.search("on", {"query": {"match": {"t": "w1"}}}, search_type=DFS)
    assert idx.search_stats["query_total"] == before + 1


def test_bucket_aggs_ride_the_plane(nodes):
    """Keyword terms + histogram bucket aggs reduce in-program (fixed-
    width ordinal counts / dd histogram scatter-adds) — responses equal
    the fan-out path's coordinator reduce."""
    n = nodes
    rng = np.random.default_rng(17)
    langs = ["en", "de", "fr", "ja"]
    for name, plane in (("kon", True), ("koff", False)):
        n.indices_service.create_index(name, {
            "settings": {"number_of_shards": 2, "number_of_replicas": 0,
                         "index.search.collective_plane": plane},
            "mappings": {"_doc": {"properties": {
                "t": {"type": "text", "analyzer": "whitespace"},
                "k": {"type": "keyword"},
                "v": {"type": "long"}}}}})
    for i in range(150):
        doc = {"t": "w1" if i % 2 else "w1 w2",
               "k": langs[int(rng.integers(0, 4))],
               "v": int(rng.integers(0, 500))}
        n.index_doc("kon", str(i), doc)
        n.index_doc("koff", str(i), doc)
    n.broadcast_actions.refresh("kon")
    n.broadcast_actions.refresh("koff")
    body = {"query": {"match": {"t": "w1"}}, "size": 5,
            "sort": [{"v": "desc"}],
            "aggs": {"by_k": {"terms": {"field": "k", "size": 3}},
                     "h": {"histogram": {"field": "v", "interval": 100}},
                     "mx": {"max": {"field": "v"}}}}
    a = n.search("kon", dict(body), search_type=DFS)
    b = n.search("koff", dict(body), search_type=DFS)
    # the plane actually engaged on the opted-in index
    assert "_mesh_cache" in n.indices_service.indices["kon"].__dict__
    assert a["hits"]["total"] == b["hits"]["total"]
    assert [(h["_id"], h["sort"]) for h in a["hits"]["hits"]] == \
        [(h["_id"], h["sort"]) for h in b["hits"]["hits"]]
    assert a["aggregations"]["by_k"] == b["aggregations"]["by_k"]
    assert a["aggregations"]["h"]["buckets"] == \
        b["aggregations"]["h"]["buckets"]
    assert a["aggregations"]["mx"]["value"] == \
        b["aggregations"]["mx"]["value"]


# ---------------------------------------------------------------------------
# The default flip: the collective plane is the DEFAULT data plane.
# index.search.collective_plane now defaults to TRUE; plain (non-dfs)
# searches ride the plane scoring each shard with its OWN statistics,
# multi-index requests pack into one program, keyword sorts /
# terminate_after / timeout / score-order cursors are eligible, and the
# shape-keyed program cache survives refresh generations.
# ---------------------------------------------------------------------------

LANGS = ["de", "en", "fr", "ja", "pt"]


def _mk_pair(n, on_name: str, off_name: str, seed: int, ndocs: int = 120,
             nshards: int = 2):
    """Two IDENTICAL indices: `on_name` with DEFAULT settings (no plane
    setting at all — the flip under test) and `off_name` explicitly
    opted out. → the generated docs list."""
    rng = np.random.default_rng(seed)
    for name, extra in ((on_name, {}),
                        (off_name,
                         {"index.search.collective_plane": False})):
        n.indices_service.create_index(name, {
            "settings": {"number_of_shards": nshards,
                         "number_of_replicas": 0, **extra},
            "mappings": {"_doc": {"properties": {
                "t": {"type": "text", "analyzer": "whitespace"},
                "k": {"type": "keyword"},
                "v": {"type": "long"}}}}})
    docs = []
    for i in range(ndocs):
        words = " ".join(f"w{int(x)}" for x in rng.zipf(1.6, 7) if x < 30)
        docs.append({"t": words or "w1",
                     "k": LANGS[int(rng.integers(0, len(LANGS)))],
                     "v": int(rng.integers(0, 400))})
    for i, d in enumerate(docs):
        n.index_doc(on_name, str(i), d)
        n.index_doc(off_name, str(i), d)
    n.broadcast_actions.refresh(on_name)
    n.broadcast_actions.refresh(off_name)
    return docs


def _hits_norm(resp, rename=""):
    return [((h["_index"].replace(rename, "") if rename else h["_index"]),
             h["_id"], h.get("sort"),
             round(h["_score"], 4) if h.get("_score") is not None
             else None)
            for h in resp["hits"]["hits"]]


def test_default_on_serves_match_sorted_terms(nodes):
    """Acceptance: with NO settings, a 2-shard single-node index serves
    match / sorted / terms-agg searches (plain search_type!) through the
    collective plane — admission counter > 0 — and the responses are
    indistinguishable from the fan-out."""
    n = nodes
    _mk_pair(n, "dflt", "dflt_off", seed=23)
    idx = n.indices_service.indices["dflt"]
    before = idx.plane_stats["served"]
    bodies = [
        {"query": {"match": {"t": "w1 w2"}}, "size": 10},
        {"query": {"match": {"t": "w1"}}, "size": 8,
         "sort": [{"v": {"order": "desc"}}]},
        {"query": {"match": {"t": "w2"}}, "size": 0,
         "aggs": {"by_k": {"terms": {"field": "k", "size": 4}},
                  "st": {"stats": {"field": "v"}}}},
    ]
    for body in bodies:
        a = n.search("dflt", dict(body))
        b = n.search("dflt_off", dict(body))
        assert a["hits"]["total"] == b["hits"]["total"], body
        assert _hits_norm(a) == _hits_norm(b, rename="_off"), body
        assert a.get("aggregations") == b.get("aggregations"), body
    assert idx.plane_stats["served"] - before == len(bodies)
    assert "_mesh_cache" in idx.__dict__
    off = n.indices_service.indices["dflt_off"]
    assert "_mesh_cache" not in off.__dict__ and \
        off.plane_stats["served"] == 0


def test_keyword_sort_and_cursor_ride_plane(nodes):
    """Widened eligibility: keyword sorts run in-program via union-rank
    ordinal lanes, including keyword search_after cursors."""
    n = nodes
    _mk_pair(n, "kws", "kws_off", seed=29)
    idx = n.indices_service.indices["kws"]
    served0 = idx.plane_stats["served"]
    base = {"query": {"match": {"t": "w1"}}, "size": 6,
            "sort": [{"k": {"order": "asc"}}, {"v": {"order": "desc"}}]}
    a = n.search("kws", dict(base))
    b = n.search("kws_off", dict(base))
    assert a["hits"]["total"] == b["hits"]["total"]
    assert _hits_norm(a) == _hits_norm(b, rename="_off")
    assert isinstance(a["hits"]["hits"][0]["sort"][0], str)
    cursor = a["hits"]["hits"][-1]["sort"]
    page2 = dict(base, search_after=cursor)
    a2 = n.search("kws", dict(page2))
    b2 = n.search("kws_off", dict(page2))
    assert _hits_norm(a2) == _hits_norm(b2, rename="_off")
    assert not ({h["_id"] for h in a2["hits"]["hits"]} &
                {h["_id"] for h in a["hits"]["hits"]})
    assert idx.plane_stats["served"] - served0 == 2


def test_score_order_cursor_rides_plane(nodes):
    """A bare [score] score-order cursor becomes the in-program
    continuation mask; a cursor WITH a doc-id component stays host-side
    (numbering-relative)."""
    n = nodes
    _mk_pair(n, "soc", "soc_off", seed=31)
    idx = n.indices_service.indices["soc"]
    served0 = idx.plane_stats["served"]
    base = {"query": {"match": {"t": "w1 w3"}}, "size": 5}
    p1 = n.search("soc", dict(base))
    cur = [p1["hits"]["hits"][-1]["_score"]]
    page2 = dict(base, search_after=cur)
    a = n.search("soc", dict(page2))
    b = n.search("soc_off", dict(page2))
    assert a["hits"]["total"] == b["hits"]["total"]
    assert _hits_norm(a) == _hits_norm(b, rename="_off")
    assert idx.plane_stats["served"] - served0 == 2
    # doc-id component → precheck bails to the fan-out (still correct)
    fb0 = idx.plane_stats["fallback"].get("ineligible-shape", 0)
    a2 = n.search("soc", dict(base, search_after=[cur[0], 7]))
    b2 = n.search("soc_off", dict(base, search_after=[cur[0], 7]))
    assert _hits_norm(a2) == _hits_norm(b2, rename="_off")
    assert idx.plane_stats["fallback"]["ineligible-shape"] == fb0 + 1


def test_terminate_after_and_timeout_ride_plane(nodes):
    """Widened eligibility: terminate_after caps ride the count lane
    (exact on single-segment shards) and `timeout` wires through the
    task deadline instead of bailing the plane."""
    n = nodes
    _mk_pair(n, "talim", "talim_off", seed=37)
    idx = n.indices_service.indices["talim"]
    served0 = idx.plane_stats["served"]
    body = {"query": {"match": {"t": "w1"}}, "size": 5,
            "terminate_after": 3}
    a = n.search("talim", dict(body))
    b = n.search("talim_off", dict(body))
    assert a["hits"]["total"] == b["hits"]["total"]
    assert a.get("terminated_early") == b.get("terminated_early") is True
    assert _hits_norm(a) == _hits_norm(b, rename="_off")
    body2 = {"query": {"match": {"t": "w1"}}, "size": 5, "timeout": "30s"}
    a2 = n.search("talim", dict(body2))
    b2 = n.search("talim_off", dict(body2))
    assert a2["timed_out"] is False
    assert _hits_norm(a2) == _hits_norm(b2, rename="_off")
    assert idx.plane_stats["served"] - served0 == 2


def test_multi_index_one_mesh_dispatch(nodes):
    """Acceptance: an msearch spanning two indices is served by ONE mesh
    dispatch — per-index column groups pack into the same program and
    each hit renders its owning index."""
    n = nodes
    _mk_pair(n, "mia", "mia_off", seed=41)
    _mk_pair(n, "mib", "mib_off", seed=43)
    from elasticsearch_tpu.search import jit_exec
    body = {"query": {"match": {"t": "w1"}}, "size": 12}

    def dispatches():
        st = jit_exec.cache_stats()
        return st["mesh_program_hits"] + st["mesh_program_misses"]

    d0 = dispatches()
    ra = n.search_actions.multi_search(
        [("mia,mib", dict(body), None)])["responses"]
    assert dispatches() - d0 == 1
    rb = n.search_actions.multi_search(
        [("mia_off,mib_off", dict(body), None)])["responses"]
    assert "error" not in ra[0] and "error" not in rb[0]
    assert ra[0]["hits"]["total"] == rb[0]["hits"]["total"]
    assert _hits_norm(ra[0]) == _hits_norm(rb[0], rename="_off")
    assert ra[0]["hits"]["hits"] and all(
        h["_index"] in ("mia", "mib") for h in ra[0]["hits"]["hits"])
    assert n.indices_service.indices["mia"].plane_stats["served"] >= 1
    assert n.indices_service.indices["mib"].plane_stats["served"] >= 1
    # the plain multi-index search API rides the same pack
    a = n.search("mia,mib", dict(body, sort=[{"v": "asc"}]))
    b = n.search("mia_off,mib_off", dict(body, sort=[{"v": "asc"}]))
    assert _hits_norm(a) == _hits_norm(b, rename="_off")


def test_shape_keyed_program_cache_across_generations(nodes):
    """Regression guard (tier-1): repeating a sorted + terms-agg query
    across ≥3 refresh generations rebuilds the DATA layer each time but
    re-traces the program AT MOST once — the shape-keyed program cache
    contract, counter-verified via jit_exec."""
    n = nodes
    from elasticsearch_tpu.search import jit_exec
    docs = _mk_pair(n, "genx", "genx_off", seed=47, ndocs=100)
    for name in ("genx", "genx_off"):
        n.indices_service.indices[name].force_merge(1)
    body = {"query": {"match": {"t": "w1"}}, "size": 10,
            "sort": [{"v": {"order": "desc"}}],
            "aggs": {"by_k": {"terms": {"field": "k", "size": 4}}}}
    idx = n.indices_service.indices["genx"]
    a0 = n.search("genx", dict(body))
    b0 = n.search("genx_off", dict(body))
    assert _hits_norm(a0) == _hits_norm(b0, rename="_off")
    served0 = idx.plane_stats["served"]
    miss0 = jit_exec.cache_stats()["mesh_program_misses"]
    packs = [idx.__dict__["_mesh_cache"][1]]
    for gen in range(3):
        # same-content update + merge: the reader generation moves (data
        # layer rebuild) while every column keeps its shape bucket
        n.index_doc("genx", "0", dict(docs[0]))
        n.index_doc("genx_off", "0", dict(docs[0]))
        n.broadcast_actions.refresh("genx")
        n.broadcast_actions.refresh("genx_off")
        n.indices_service.indices["genx"].force_merge(1)
        n.indices_service.indices["genx_off"].force_merge(1)
        a = n.search("genx", dict(body))
        b = n.search("genx_off", dict(body))
        assert _hits_norm(a) == _hits_norm(b, rename="_off"), gen
        assert a.get("aggregations") == b.get("aggregations"), gen
        packs.append(idx.__dict__["_mesh_cache"][1])
    assert idx.plane_stats["served"] == served0 + 3
    # every generation re-packed the data layer...
    assert len({id(p) for p in packs}) == len(packs)
    # ...and NONE re-traced: the shape-keyed program cache held
    assert jit_exec.cache_stats()["mesh_program_misses"] == miss0


def test_refresh_race_retries_against_fresh_snapshot(nodes, monkeypatch):
    """A refresh landing between the mesh pack and the fetch readers
    used to waste the whole breaker-charged pack (return None). Now the
    plane retries ONCE against the fresh snapshot; only a second race
    yields to the fan-out (reason-counted)."""
    n = nodes
    _mk_pair(n, "race", "race_off", seed=53, ndocs=60)
    from elasticsearch_tpu.parallel import mesh_engine
    idx = n.indices_service.indices["race"]
    real = mesh_engine.MeshEngineSearcher.search_batch
    calls = {"n": 0, "refresh_once": True}

    def racy(self, bodies, global_stats=True):
        out = real(self, bodies, global_stats=global_stats)
        calls["n"] += 1
        if not calls["refresh_once"] or calls["n"] == 1:
            n.index_doc("race", f"fresh-{calls['n']}",
                        {"t": "racefresh", "k": "zz", "v": 999})
            n.broadcast_actions.refresh("race")
        return out

    monkeypatch.setattr(mesh_engine.MeshEngineSearcher, "search_batch",
                        racy)
    served0 = idx.plane_stats["served"]
    r = n.search("race", {"query": {"match": {"t": "racefresh"}}})
    # the retry ran (two search_batch calls) against the POST-refresh
    # snapshot: the raced-in doc is visible and the plane still served
    assert calls["n"] == 2
    assert r["hits"]["total"] == 1
    assert idx.plane_stats["served"] == served0 + 1
    assert idx.plane_stats["fallback"].get("refresh-race", 0) == 0
    # racing EVERY attempt exhausts the one retry → fan-out + reason
    calls["refresh_once"] = False
    r2 = n.search("race", {"query": {"match": {"t": "racefresh"}}})
    assert r2["hits"]["total"] >= 1
    assert idx.plane_stats["fallback"]["refresh-race"] == 1


def test_fallback_reasons_surface_in_stats(nodes):
    """Satellite: forced fallbacks appear by reason in the index _stats
    and the _nodes/stats rollup, alongside the jit/mesh counters."""
    n = nodes
    _mk_pair(n, "obs", "obs_off", seed=59, ndocs=40)
    idx = n.indices_service.indices["obs"]
    n.search("obs", {"query": {"match_all": {}}, "sort": ["_doc"]})
    st = idx.stats()["search"]["collective_plane"]
    assert st["fallback"].get("ineligible-shape", 0) >= 1
    assert st["fallback_total"] >= 1
    ns = n.local_node_stats()["indices"]
    assert ns["collective_plane"]["fallback"].get(
        "ineligible-shape", 0) >= 1
    assert "mesh_program_hits" in ns["jit"]
    assert "fallback_reasons" in ns["jit"]


def test_plane_vs_fanout_equality_fuzz(nodes, rng):
    """Satellite: randomized plane-vs-fanout equality — the same body
    executed with the plane on (default) and forced off must produce
    identical hits, totals, sort values, and aggregations."""
    n = nodes
    _mk_pair(n, "fz", "fz_off", seed=7, ndocs=150)

    def rand_query():
        r = int(rng.integers(0, 5))
        if r == 0:
            return {"match": {"t": f"w{int(rng.integers(1, 8))}"}}
        if r == 1:
            return {"match": {"t": f"w{int(rng.integers(1, 6))} "
                                   f"w{int(rng.integers(1, 6))}"}}
        if r == 2:
            return {"bool": {
                "must": [{"match": {"t": "w1"}}],
                "filter": [{"range": {"v": {
                    "gte": int(rng.integers(0, 300))}}}]}}
        if r == 3:
            return {"term": {"k": LANGS[int(rng.integers(0, len(LANGS)))]}}
        return {"match_all": {}}

    for _ in range(20):
        body = {"query": rand_query(),
                "size": int(rng.integers(0, 15)),
                "from": int(rng.integers(0, 4))}
        if rng.random() < 0.5:
            choice = int(rng.integers(0, 3))
            if choice == 0:
                body["sort"] = [{"v": {"order": "desc" if rng.random()
                                       < 0.5 else "asc"}}]
            elif choice == 1:
                body["sort"] = [{"k": {"order": "asc"}},
                                {"v": {"order": "desc"}}]
            else:
                body["sort"] = [{"v": "asc"}, {"_score": "desc"}]
        if rng.random() < 0.4:
            body["aggs"] = {"m": {"stats": {"field": "v"}},
                            "bk": {"terms": {"field": "k", "size": 3}},
                            "h": {"histogram": {"field": "v",
                                                "interval": 100}}}
        if rng.random() < 0.2:
            body["post_filter"] = {"range": {"v": {
                "lt": int(rng.integers(100, 400))}}}
        if rng.random() < 0.2:
            body["min_score"] = 0.05
        st = "dfs_query_then_fetch" if rng.random() < 0.3 else None
        a = n.search("fz", dict(body), search_type=st)
        b = n.search("fz_off", dict(body), search_type=st)
        assert a["hits"]["total"] == b["hits"]["total"], body
        assert _hits_norm(a) == _hits_norm(b, rename="_off"), body
        assert a.get("aggregations") == b.get("aggregations"), body
