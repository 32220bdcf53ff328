"""Slot plans for the one-pass BM25 ``match`` (``ops/lexical.SlotPlan``).

A column's rows are scored in equal chunks, each only up to the last slot
any of its rows uses; ``Segment.from_packed_text`` stores its rows longest
first so that the chunks of short documents stop early. These tests hold
the bounded pass to the unbounded one, the length-ordered packed segment
to the same documents through ``_bulk``, the collective plane's merged
plans to the shards' own, and the plan of the MS MARCO length
distribution to one shape on every seed.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.segment import Segment, SegmentBuilder
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.ops import lexical
from elasticsearch_tpu.ops.lexical import (SLOT_CHUNKS, SlotPlan,
                                           chunk_bounds, plan_slots)
from elasticsearch_tpu.search import jit_exec

K1, B = 1.2, 0.75
U = 224
SIZE = 200                  # hits a search of the twin indices asks for
MAPPINGS = {"_doc": {"properties": {
    "t": {"type": "text", "analyzer": "whitespace"}}}}


# ---------------------------------------------------------------------------
# the bounded pass against the unbounded one
# ---------------------------------------------------------------------------

def _column(seed: int, layout: str, rows: int = 1 << 16, vocab: int = 300):
    """A [rows, 224] unique-term column with holes: row i holds terms at
    about 70% of its first ``len_i`` slots, -1 elsewhere."""
    rng = np.random.default_rng(seed)
    lens = np.clip(np.exp(np.log(50) + 0.45 * rng.standard_normal(rows)),
                   10, U).astype(np.int32)
    if layout in ("sorted", "pad_chunk", "slot223"):
        lens = np.sort(lens)[::-1]
    slots = np.arange(U)[None, :]
    held = (slots < lens[:, None]) & (rng.random((rows, U)) < 0.7)
    uterms = np.where(held, rng.integers(0, vocab, (rows, U)), -1)
    if layout == "pad_chunk":               # the last chunks: pad rows
        uterms[rows - rows // 8:] = -1
        lens[rows - rows // 8:] = 0
    if layout == "slot223":                 # one short row reaches the end
        uterms[rows // 2, U - 1] = 7
    utf = np.where(uterms >= 0, rng.integers(1, 4, (rows, U)), 0)
    return (uterms.astype(np.int32), utf.astype(np.float32), lens)


def _queries(seed: int, uterms: np.ndarray, n: int, terms: int = 8):
    """``n`` queries of ``terms`` ids: terms the column holds, one absent
    term and the -1 pads of a term bucket."""
    rng = np.random.default_rng(seed + 1)
    present = np.unique(uterms[uterms >= 0])
    q = rng.choice(present, (n, terms)).astype(np.int32)
    q[:, 0] = 7                             # slot 223's term, where put
    q[:, -2] = 100_000                      # a term no row holds
    q[:, -1] = -1                           # a pad
    idf = np.where(q >= 0, rng.uniform(0.5, 4.0, q.shape), 0.0)
    return q, idf.astype(np.float32)


@pytest.mark.parametrize("layout",
                         ["sorted", "unsorted", "pad_chunk", "slot223"])
@pytest.mark.parametrize("seed", [3, 11])
def test_bounded_pass_equals_the_full_width(layout, seed):
    uterms, utf, lens = _column(seed, layout)
    plan = plan_slots(uterms)
    assert plan.rows == uterms.shape[0]
    assert len(plan.widths) == SLOT_CHUNKS
    # every slot past a chunk's width is the pad in every row of it
    for (a, e), w in zip(chunk_bounds(plan.rows), plan.widths):
        assert (uterms[a:e, w:] == -1).all()
        assert w % lexical.SLOT_ALIGN == 0 or w == U
    # mixed lengths leave each chunk's longest rows in it
    if layout == "unsorted":
        assert plan.slots() > 0.6 * plan.rows * U
    else:
        assert plan.slots() < 0.5 * plan.rows * U
    if layout == "pad_chunk":
        assert plan.widths[-2:] == (0, 0)
    if layout == "slot223":
        assert plan.widths[SLOT_CHUNKS // 2] == U
    q, idf = _queries(seed, uterms, 2)
    args = [jnp.asarray(a) for a in (uterms, utf, lens)]
    avgdl = np.float32(max(lens.mean(), 1.0))

    def run(plan_or_none):
        @jax.jit
        def batch(cols, qt, qi):
            return jax.vmap(lambda t, i: lexical.bm25_match(
                *cols, t, i, jnp.ones(t.shape[0], jnp.float32), K1, B,
                avgdl, plan_or_none))(qt, qi)
        return [np.asarray(x) for x in batch(args, q, idf)]

    full_s, full_n = run(None)
    got_s, got_n = run(plan)
    np.testing.assert_array_equal(got_n, full_n)
    np.testing.assert_allclose(got_s, full_s, rtol=1e-6)
    # the msm1 form (a hit scores > 0) and the counted forms (operator
    # "and", minimum_should_match) select the same documents
    np.testing.assert_array_equal(got_s > 0, full_s > 0)
    for need in (1, 3, q.shape[1] - 2):
        np.testing.assert_array_equal(got_n >= need, full_n >= need)
    assert (full_n > 0).any() and (full_s > 0).sum() < full_s.size
    if layout == "slot223":                 # its term is found and scored
        assert (got_n[:, uterms.shape[0] // 2] >= 1).all()


def test_a_short_column_has_no_plan():
    uterms, _, _ = _column(5, "sorted", rows=lexical.SLOT_PLAN_MIN_ROWS // 2)
    assert plan_slots(uterms) is None


def test_a_plan_of_another_row_count_scores_the_whole_width():
    uterms, utf, lens = _column(5, "sorted")
    q, idf = _queries(5, uterms, 1)
    args = (jnp.asarray(uterms), jnp.asarray(utf), jnp.asarray(lens),
            jnp.asarray(q[0]), jnp.asarray(idf[0]),
            jnp.ones(q.shape[1], jnp.float32), K1, B, np.float32(50.0))
    other = SlotPlan(uterms.shape[0] // 2, (16,) * SLOT_CHUNKS)
    want = lexical.bm25_match(*args)
    got = lexical.bm25_match(*args, other)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_merged_plan_is_the_wider_width_chunk_by_chunk():
    a = SlotPlan(64, (32, 16, 0) + (0,) * (SLOT_CHUNKS - 3))
    b = SlotPlan(64, (16, 48, 0) + (16,) * (SLOT_CHUNKS - 3))
    assert a.merge(b).widths == (32, 48, 0) + (16,) * (SLOT_CHUNKS - 3)
    assert a.merge(b).runs()[:3] == [(0, 4, 32), (4, 8, 48), (8, 12, 0)]
    with pytest.raises(ValueError):
        a.merge(SlotPlan(128, a.widths))


# ---------------------------------------------------------------------------
# the MS MARCO lengths give one plan on every seed
# ---------------------------------------------------------------------------

def _cell_lengths(seed: int, rows: int) -> np.ndarray:
    """Document lengths as the benchmark's ``zipf_text`` corpus draws them
    (log-normal, median 50, sigma 0.45, clipped to [10, 224]; its
    configuration ``msmarco-passage-bm25``) from a 64-bit seed's first
    segment — the lengths alone, no tokens."""
    data = jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=jnp.uint32)
    key = jax.random.fold_in(
        jax.random.wrap_key_data(data, impl="threefry2x32"), 0)
    k_len, _ = jax.random.split(key)
    lens = jnp.clip(jnp.exp(np.log(50.0) + 0.45 * jax.random.normal(
        k_len, (rows,))), 10, U).astype(jnp.int32)
    return np.asarray(lens)


def test_the_cell_lengths_give_one_plan_on_every_seed():
    """At the one-chip cell's 2^20 rows a segment and the four-chip
    cell's 1,105,920, ordered longest first, three seeds give one plan —
    one compiled program — scoring about a third of the slots."""
    plans = set()
    for rows in (1 << 20, 1_105_920):
        for seed in (4100003901, 4100003902, 2**31 + 12345):
            lens = np.sort(_cell_lengths(seed, rows))[::-1]
            # a chunk's longest row is its first
            plan = SlotPlan.of(rows, U, [lens[a] for a, _ in
                                         chunk_bounds(rows)])
            plans.add(plan.widths)
            assert 0.25 <= plan.slots() / (rows * U) <= 0.40
    assert plans == {(224, 112, 96, 80, 80, 64, 64, 64, 64, 48, 48, 48, 48,
                      48, 32, 32)}


# ---------------------------------------------------------------------------
# a length-ordered packed segment answers as the same documents via _bulk
# ---------------------------------------------------------------------------

def _docs(seed: int, n: int = lexical.SLOT_PLAN_MIN_ROWS,
          vocab: int = 2000) -> list[str]:
    """``n`` documents of log-normal lengths (median 12 words, 2 to 90)
    over a Zipf-like vocabulary."""
    rng = np.random.default_rng(seed)
    lens = np.clip(np.exp(np.log(12) + 0.6 * rng.standard_normal(n)),
                   2, 90).astype(int)
    ranks = np.minimum((rng.pareto(1.1, (n, 90)) * 8).astype(int),
                       vocab - 1)
    return [" ".join(f"w{r:04d}" for r in ranks[i, :lens[i]])
            for i in range(n)]


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    """One node, two indices of the same documents: ``bulk`` through
    ``_bulk``, ``packed`` as one segment through ``from_packed_text``
    (positions included) re-packed from a per-document build."""
    node = Node({}, data_path=tmp_path_factory.mktemp("slots") / "n").start()
    docs = _docs(17)
    for name in ("bulk", "packed"):
        node.indices_service.create_index(name, {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": MAPPINGS})
    for lo in range(0, len(docs), 8192):
        node.bulk([("index", {"_index": "bulk", "_type": "_doc",
                              "_id": str(i)}, {"t": docs[i]})
                   for i in range(lo, min(lo + 8192, len(docs)))])
    node.broadcast_actions.refresh("bulk")
    svc = node.indices_service.indices["packed"]
    b = SegmentBuilder(seg_id=0)
    for i, d in enumerate(docs):
        b.add(svc.mapper_service.document_mapper().parse(str(i), {"t": d}))
    built = b.build()
    col = built.text_fields["t"]
    packed = Segment.from_packed_text(
        0, "t", terms=col.terms, tokens=col.tokens, uterms=col.uterms,
        utf=col.utf, doc_len=col.doc_len, df=col.df,
        num_docs=built.num_docs, total_tokens=col.total_tokens,
        ids=list(built.ids), sources=list(built.sources))
    svc.engine(0).install_segment(packed, track_versions=False)
    node.broadcast_actions.refresh("packed")
    yield node, docs, packed
    node.close()


def test_packed_rows_stand_longest_first(twin):
    _node, docs, packed = twin
    col = packed.text_fields["t"]
    lens = col.doc_len[:packed.num_docs]
    assert (lens[1:] <= lens[:-1]).all()
    assert list(packed.ids[:packed.num_docs]) != \
        [str(i) for i in range(len(docs))]
    # the ids are a tuple the interpreter's collector no longer walks
    gc.collect()
    assert not gc.is_tracked(packed.ids)
    # every row keeps its document: id, source, length and positions
    for row in (0, 1, packed.num_docs // 2, packed.num_docs - 1):
        i = int(packed.ids[row])
        assert packed.sources[row] == {"t": docs[i]}
        assert col.doc_len[row] == len(docs[i].split())
        words = [col.terms[t] for t in col.tokens[row] if t >= 0]
        assert words == docs[i].split()
    plan = col.slot_plan
    assert plan.slots() < 0.75 * plan.rows * col.uterms.shape[1]


def _hits(resp: dict) -> tuple[dict, int]:
    return ({h["_id"]: h["_score"] for h in resp["hits"]["hits"]},
            resp["hits"]["total"])


def _same(got: tuple, want: tuple, size: int) -> None:
    """Equal totals, and the same (``_id``, score) hits above the lowest
    score served where the page is full (a tie there may be cut either
    way: no order among equal scores is a guarantee)."""
    assert got[1] == want[1]
    cut = min(want[0].values()) if len(want[0]) == size else -1.0
    above = sorted(i for i, sc in want[0].items() if sc > cut)
    assert len(got[0]) == len(want[0])
    assert set(above) <= set(got[0])
    np.testing.assert_allclose([got[0][i] for i in above],
                               [want[0][i] for i in above], rtol=1e-6)


def _texts(docs: list[str], n: int, seed: int) -> list[str]:
    """``n`` query texts of 2-12 distinct words drawn from the documents."""
    rng = np.random.default_rng(seed)
    words = sorted({w for d in docs for w in d.split()})
    return [" ".join(rng.choice(words, 2 + i % 11, replace=False))
            for i in range(n)]


@pytest.mark.parametrize("form", [
    {}, {"operator": "and"}, {"minimum_should_match": "30%"}])
def test_match_answers_as_through_bulk(twin, form):
    node, docs, _ = twin
    for text in _texts(docs, 6, 1) + [docs[5]]:
        body = {"query": {"match": {"t": {"query": text, **form}}},
                "size": SIZE}
        _same(_hits(node.search("packed", body)),
              _hits(node.search("bulk", body)), SIZE)


def test_phrase_boost_bool_answers_as_through_bulk(twin):
    node, docs, _ = twin
    rng = np.random.default_rng(4)
    for i in rng.choice(len(docs), 6, replace=False):
        words = docs[i].split()
        start = int(rng.integers(0, max(len(words) - 3, 1)))
        text = " ".join(words[start:start + 3])
        body = {"query": {"bool": {
            "must": [{"match": {"t": {"query": text,
                                      "minimum_should_match": "30%"}}}],
            "should": [{"match_phrase": {"t": text}}]}},
            "size": SIZE}
        _same(_hits(node.search("packed", body)),
              _hits(node.search("bulk", body)), SIZE)


def test_mixed_length_msearch_answers_as_through_bulk_and_counts(twin):
    node, docs, _ = twin
    texts = _texts(docs, 16, 2)
    before = jit_exec.cache_stats()
    out = {name: node.search_actions.multi_search([
        (name, {"query": {"match": {"t": t}}, "size": SIZE}) for t in texts])
        for name in ("packed", "bulk")}
    after = jit_exec.cache_stats()
    for got, want in zip(out["packed"]["responses"],
                         out["bulk"]["responses"]):
        _same(_hits(got), _hits(want), SIZE)
    scored = after["match_slot_compares_scored"] - \
        before["match_slot_compares_scored"]
    full = after["match_slot_compares_full"] - \
        before["match_slot_compares_full"]
    assert 0 < scored < full
    stats = node.local_node_stats()["indices"]["jit"]
    assert stats["match_slot_compares_scored"] >= scored
    assert stats["match_slot_compares_full"] >= full


# ---------------------------------------------------------------------------
# the collective plane scores every shard of a slot under one plan
# ---------------------------------------------------------------------------

def test_plane_takes_the_widest_plan_of_its_shards(tmp_path):
    from elasticsearch_tpu.parallel.mesh import make_mesh
    from elasticsearch_tpu.parallel.mesh_engine import (MeshEngineSearcher,
                                                        rpc_oracle)
    ms = MapperService()
    ms.merge("_doc", MAPPINGS["_doc"])
    engs = []
    rows = lexical.SLOT_PLAN_MIN_ROWS
    for si, scale in enumerate((80, 12)):    # long documents, short ones
        rng = np.random.default_rng(si)
        lens = rng.integers(scale // 2, scale, rows).astype(np.int32)
        uterms = np.where(np.arange(128)[None, :] < lens[:, None],
                          rng.integers(0, 200, (rows, 128)), -1)
        utf = rng.integers(1, 4, uterms.shape) * (uterms >= 0)
        e = Engine(tmp_path / f"s{si}", ms)
        e.install_segment(Segment.from_packed_text(
            0, "t", terms=[f"w{i:03d}" for i in range(200)], tokens=None,
            uterms=uterms.astype(np.int32), utf=utf.astype(np.float32),
            doc_len=lens, df=np.bincount(uterms[uterms >= 0], minlength=200),
            num_docs=rows, ids=[f"{si}-{i}" for i in range(rows)]),
            track_versions=False)
        engs.append(e)
    try:
        own = [e.acquire_searcher().segments[0].text_fields["t"]
               .slot_plan for e in engs]
        assert own[0] != own[1]
        mesh = make_mesh(dp=1, shard=2, devices=jax.devices()[:2])
        searcher = MeshEngineSearcher(mesh, engs, ms)
        for si in range(2):
            assert searcher._templates[si][0].text["t"].slot_plan == \
                own[0].merge(own[1])
        body = {"query": {"match": {"t": "w001 w002 w017 w150"}},
                "size": SIZE}
        (out,) = searcher.search_batch([body])
        got = {searcher.doc_id(d): float(s)
               for s, d in zip(out["scores"], out["doc_ids"])}
        # the fan-out's own per-shard plans (global statistics, as the
        # plane's)
        total, want = rpc_oracle(ms, engs, body, SIZE)
        assert out["total"] > SIZE
        _same((got, out["total"]),
              ({did: score for score, _, did in want}, total), SIZE)
    finally:
        for e in engs:
            e.close()
