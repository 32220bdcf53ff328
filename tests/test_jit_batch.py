"""Batched query-path tests: B same-plan requests must execute as one
vmapped program over the reader (jit_exec.run_reader_batch) with results
identical to the per-request path, and the bulk columnar ingest
(Segment.from_packed_text + Engine.install_segment) must be search-
equivalent to per-document indexing."""

import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.index.segment import Segment, SegmentBuilder
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.phase import parse_search_request


@pytest.fixture
def node(tmp_path):
    n = Node({}, data_path=tmp_path / "n").start()
    yield n
    n.close()


def _mk(node, name, docs, shards=1):
    node.indices_service.create_index(
        name, {"settings": {"number_of_shards": shards,
                            "number_of_replicas": 0}})
    for i in range(docs):
        node.index_doc(name, str(i),
                       {"t": f"alpha beta word{i % 7} word{i % 11}", "n": i})
    node.broadcast_actions.refresh(name)


def _searcher(node, name):
    svc = node.indices_service.indices[name]
    from elasticsearch_tpu.search.phase import ShardSearcher
    return ShardSearcher(0, device_reader_for(svc.engine(0)),
                         svc.mapper_service)


class TestQueryPhaseBatch:
    def test_matches_per_query_path(self, node):
        _mk(node, "idx", 120)
        s = _searcher(node, "idx")
        reqs = [parse_search_request(
            {"query": {"match": {"t": f"word{i}"}}, "size": 15})
            for i in range(7)]
        batch = s.query_phase_batch(reqs)
        assert batch is not None
        for req, got in zip(reqs, batch):
            ref = s.query_phase(req)
            assert got.total == ref.total
            np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
            np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-6)

    def test_multi_segment_merge(self, node):
        # two refreshes → two segments; batched merge must equal per-query
        node.indices_service.create_index(
            "seg", {"settings": {"number_of_shards": 1,
                                 "number_of_replicas": 0}})
        for i in range(40):
            node.index_doc("seg", str(i), {"t": f"alpha word{i % 5}"})
        node.broadcast_actions.refresh("seg")
        for i in range(40, 90):
            node.index_doc("seg", str(i), {"t": f"alpha word{i % 5}"})
        node.broadcast_actions.refresh("seg")
        s = _searcher(node, "seg")
        assert len(s.reader.segments) >= 2
        reqs = [parse_search_request(
            {"query": {"match": {"t": f"word{i % 5}"}}, "size": 30})
            for i in range(6)]
        batch = s.query_phase_batch(reqs)
        assert batch is not None
        for req, got in zip(reqs, batch):
            ref = s.query_phase(req)
            np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
            np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-6)
            assert got.total == ref.total

    def test_bool_queries_batch(self, node):
        _mk(node, "idx", 100)
        s = _searcher(node, "idx")
        reqs = [parse_search_request({"query": {"bool": {
            "must": [{"match": {"t": f"word{i}"}}],
            "filter": [{"range": {"n": {"gte": 10 * i}}}],
        }}, "size": 20}) for i in range(5)]
        batch = s.query_phase_batch(reqs)
        assert batch is not None
        for req, got in zip(reqs, batch):
            ref = s.query_phase(req)
            np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
            np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-6)

    def test_mixed_plans_fall_back(self, node):
        _mk(node, "idx", 50)
        s = _searcher(node, "idx")
        reqs = [parse_search_request({"query": {"match": {"t": "alpha"}}}),
                parse_search_request({"query": {"range": {"n": {"gte": 3}}}})]
        assert s.query_phase_batch(reqs) is None

    def test_ineligible_requests_fall_back(self, node):
        _mk(node, "idx", 50)
        s = _searcher(node, "idx")
        reqs = [parse_search_request(
            {"query": {"match": {"t": "alpha"}},
             "aggs": {"m": {"max": {"field": "n"}}}})]
        assert s.query_phase_batch(reqs) is None
        reqs = [parse_search_request(
            {"query": {"match": {"t": "alpha"}}, "sort": [{"n": "asc"}]})]
        assert s.query_phase_batch(reqs) is None

    def test_batch_padding_shares_programs(self, node):
        _mk(node, "idx", 60)
        s = _searcher(node, "idx")
        jit_exec.clear_cache()
        reqs = [parse_search_request(
            {"query": {"match": {"t": f"word{i}"}}, "size": 5})
            for i in range(5)]           # B=5 → padded to 8
        s.query_phase_batch(reqs)
        st1 = jit_exec.cache_stats()
        reqs = [parse_search_request(
            {"query": {"match": {"t": f"word{i}"}}, "size": 5})
            for i in range(7)]           # B=7 → padded to 8: same program
        s.query_phase_batch(reqs)
        st2 = jit_exec.cache_stats()
        assert st2["misses"] == st1["misses"]
        assert st2["fallbacks"] == 0


#: lengths 1, 2, 3, 5 and 12 in one batch: the widest term bucket is 12
MIXED_TEXTS = [
    "alpha",
    "beta word3",
    "word1 word2 alpha",
    "word0 word4 beta word5 word9",
    "alpha beta word0 word1 word2 word3 word4 word5 word6 word7 word8 "
    "word10",
]
MIXED_LENGTHS = [len(t.split()) for t in MIXED_TEXTS]


def _mixed_bodies(form: str) -> list:
    def match(text):
        if form == "or":
            return {"match": {"t": text}}
        if form == "and":
            return {"match": {"t": {"query": text, "operator": "and"}}}
        if form == "msm":
            return {"match": {"t": {"query": text,
                                    "minimum_should_match": "50%"}}}
        assert form == "bool"
        return {"bool": {"should": [
            {"match": {"t": text}},
            {"match": {"t": {"query": text.split()[0] + " word6",
                             "boost": 0.5}}}]}}
    return [{"query": match(t), "size": 15} for t in MIXED_TEXTS]


class TestMixedLengthBatch:
    """``match`` queries of unequal lengths share ONE compiled plan: the
    term lists pad to the batch's widest term bucket (PR 33)."""

    @pytest.mark.parametrize("form", ["or", "and", "msm", "bool"])
    def test_mixed_lengths_equal_per_query(self, node, form):
        assert MIXED_LENGTHS == [1, 2, 3, 5, 12]
        _mk(node, "idx", 120)
        s = _searcher(node, "idx")
        reqs = [parse_search_request(b) for b in _mixed_bodies(form)]
        jit_exec.clear_cache()
        before = jit_exec.cache_stats()
        batch = s.query_phase_batch(reqs)
        assert batch is not None
        after = jit_exec.cache_stats()
        assert after["misses"] - before["misses"] == 1    # ONE program
        assert after["fallbacks"] == before["fallbacks"]
        some = 0
        for req, got in zip(reqs, batch):
            ref = s.query_phase(req)
            assert got.total == ref.total
            some += ref.total
            np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
            np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-6)
        assert some > 0

    def test_three_and_four_terms_one_program(self, node):
        _mk(node, "idx", 60)
        s = _searcher(node, "idx")
        jit_exec.clear_cache()
        before = jit_exec.cache_stats()
        for text in ("alpha word1 word2", "beta word3 word4 word5",
                     "word0 word2 beta"):
            assert s.query_phase_batch([parse_search_request(
                {"query": {"match": {"t": text}}, "size": 5})]) is not None
        after = jit_exec.cache_stats()
        assert after["misses"] - before["misses"] == 1
        assert after["hits"] - before["hits"] == 2
        # five terms take the next bucket: another program
        s.query_phase_batch([parse_search_request(
            {"query": {"match": {"t": "alpha beta word1 word2 word3"}},
             "size": 5})])
        assert jit_exec.cache_stats()["misses"] - before["misses"] == 2

    def test_counters_of_a_hand_worked_batch(self, node):
        """Lengths 1, 2, 3, 5, 12 pad to 12 each: 23 real query-terms,
        5 × 12 − 23 = 37 pads — counted once a dispatch (not once a
        segment), over the real rows only (the batch axis pads 5 → 8)."""
        _mk(node, "idx", 60)
        s = _searcher(node, "idx")
        reqs = [parse_search_request(b) for b in _mixed_bodies("or")]
        before = jit_exec.cache_stats()
        assert s.query_phase_batch(reqs) is not None
        after = jit_exec.cache_stats()
        assert after["match_terms_real"] - before["match_terms_real"] \
            == sum(MIXED_LENGTHS) == 23
        assert after["match_terms_padded"] \
            - before["match_terms_padded"] == 5 * 12 - 23
        # a lone query pads to its own bucket: 3 → 4
        before = after
        s.query_phase_batch(reqs[2:3])
        after = jit_exec.cache_stats()
        assert after["match_terms_real"] - before["match_terms_real"] == 3
        assert after["match_terms_padded"] \
            - before["match_terms_padded"] == 1

    def test_alt_similarity_keeps_the_exact_count(self, node):
        """classic_match makes a pass over the columns per term: its
        lists are not padded, so unequal lengths still decline."""
        node.indices_service.create_index("cl", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {
                "t": {"type": "text", "similarity": "classic"}}}})
        for i in range(30):
            node.index_doc("cl", str(i), {"t": f"alpha beta word{i % 7}"})
        node.broadcast_actions.refresh("cl")
        s = _searcher(node, "cl")
        reqs = [parse_search_request({"query": {"match": {"t": t}}})
                for t in ("alpha", "alpha beta word1")]
        before = jit_exec.cache_stats()
        assert s.query_phase_batch(reqs) is None
        after = jit_exec.cache_stats()
        assert after["match_terms_padded"] == before["match_terms_padded"]
        same = [parse_search_request({"query": {"match": {"t": t}}})
                for t in ("alpha beta", "beta word1")]
        assert s.query_phase_batch(same) is not None


class TestBulkIngest:
    def _packed_from_builder(self, docs):
        """Build a reference segment per-document, then re-pack its columns
        through from_packed_text — byte-identical search behavior."""
        from elasticsearch_tpu.mapping import MapperService
        ms = MapperService()
        ms.merge("_doc", {"properties": {"t": {"type": "text",
                                               "analyzer": "whitespace"}}})
        b = SegmentBuilder(seg_id=0)
        for i, text in enumerate(docs):
            b.add(ms.document_mapper().parse(str(i), {"t": text}))
        return b.build(), ms

    def test_packed_equals_builder(self, tmp_path):
        docs = [f"alpha beta word{i % 3}" for i in range(20)]
        ref_seg, ms = self._packed_from_builder(docs)
        col = ref_seg.text_fields["t"]
        packed = Segment.from_packed_text(
            0, "t", terms=col.terms, tokens=col.tokens, uterms=col.uterms,
            utf=col.utf, doc_len=col.doc_len, df=col.df,
            num_docs=ref_seg.num_docs, ids=list(ref_seg.ids),
            sources=list(ref_seg.sources))
        from elasticsearch_tpu.index.engine import Engine
        e1 = Engine(tmp_path / "a", ms)
        e1.install_segment(packed)
        e2 = Engine(tmp_path / "b", ms)
        for i, text in enumerate(docs):
            e2.index(str(i), {"t": text})
        e2.refresh()
        from elasticsearch_tpu.search.phase import ShardSearcher
        req = parse_search_request(
            {"query": {"match": {"t": "word1"}}, "size": 20})
        r1 = ShardSearcher(0, device_reader_for(e1), ms).query_phase(req)
        r2 = ShardSearcher(0, device_reader_for(e2), ms).query_phase(req)
        assert r1.total == r2.total
        np.testing.assert_allclose(np.sort(r1.scores), np.sort(r2.scores),
                                   rtol=1e-6)
        got_ids = {e1._segments[0].ids[d] for d in r1.doc_ids}
        ref_ids = {e2._segments[0].ids[d] for d in r2.doc_ids}
        assert got_ids == ref_ids
        e1.close()
        e2.close()

    def test_force_merge_keeps_sourceless_installed_segment(self, tmp_path):
        # a bulk-ingested segment without stored _source cannot be
        # re-analyzed: force_merge must keep it as-is, not merge it into
        # an empty shell
        docs = ["alpha one", "alpha two", "beta three"]
        ref_seg, ms = self._packed_from_builder(docs)
        col = ref_seg.text_fields["t"]
        packed = Segment.from_packed_text(
            0, "t", terms=col.terms, tokens=col.tokens, uterms=col.uterms,
            utf=col.utf, doc_len=col.doc_len, df=col.df,
            num_docs=ref_seg.num_docs)          # sources=None → incomplete
        from elasticsearch_tpu.index.engine import Engine
        e = Engine(tmp_path / "fm", ms)
        e.install_segment(packed)
        for i in range(4):
            e.index(f"x{i}", {"t": f"alpha extra{i}"})
        e.refresh()
        for i in range(4):
            e.index(f"y{i}", {"t": f"alpha more{i}"})
        e.refresh()
        assert len(e._segments) == 3
        e.force_merge(max_num_segments=1)
        # installed segment kept + per-doc segments merged
        assert len(e._segments) == 2
        from elasticsearch_tpu.search.phase import ShardSearcher
        r = ShardSearcher(0, device_reader_for(e), ms).query_phase(
            parse_search_request({"query": {"match": {"t": "alpha"}},
                                  "size": 20}))
        assert r.total == 2 + 8      # installed alphas still searchable
        e.close()

    def test_score_asc_sort_respected(self, node):
        _mk(node, "idx", 30)
        out = node.search("idx", {"query": {"match": {"t": "alpha"}},
                                  "sort": [{"_score": "asc"}], "size": 30})
        scores = [h["_score"] for h in out["hits"]["hits"]]
        assert scores == sorted(scores), "ascending _score sort ignored"
        out_d = node.search("idx", {"query": {"match": {"t": "alpha"}},
                                    "sort": [{"_score": "desc"}], "size": 30})
        scores_d = [h["_score"] for h in out_d["hits"]["hits"]]
        assert scores_d == sorted(scores_d, reverse=True)

    def test_install_tracks_versions_and_flushes(self, tmp_path):
        docs = ["alpha one", "alpha two", "beta three"]
        ref_seg, ms = self._packed_from_builder(docs)
        col = ref_seg.text_fields["t"]
        packed = Segment.from_packed_text(
            0, "t", terms=col.terms, tokens=col.tokens, uterms=col.uterms,
            utf=col.utf, doc_len=col.doc_len, df=col.df,
            num_docs=ref_seg.num_docs, ids=list(ref_seg.ids),
            sources=[{"t": d} for d in docs] + [{}] * (
                ref_seg.padded_docs - ref_seg.num_docs))
        from elasticsearch_tpu.index.engine import Engine
        e = Engine(tmp_path / "e", ms)
        e.install_segment(packed)
        g = e.get("1")
        assert g.found and g.version == 1
        # deletes against installed docs work through the version map
        e.delete("2")
        e.refresh()
        assert not e.get("2").found
        e.flush()
        e.close()
        # reopen from the commit: installed segment survives restart
        e2 = Engine(tmp_path / "e", ms)
        assert e2.get("0").found
        assert not e2.get("2").found
        e2.close()
