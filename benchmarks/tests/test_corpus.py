"""The device generator against the copied host generator's statistics,
the plain reference against a brute-force BM25, and the lower-precision
control against the comparison (it has to fail)."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks import harness

ROOT = Path(__file__).resolve().parents[2]
MOD = harness.load_module(ROOT / "benchmarks/corpora/zipf_text.py", "zt")
SPEC = {"kind": "zipf_text", "segments": 2, "segment_rows": 8192,
        "vocab": 500_000, "zipf_s": 1.07, "len_median": 50,
        "len_sigma": 0.45, "min_len": 10, "max_len": 224}


def host_corpus(rng, n_docs, vocab=500_000):
    """``bench.make_corpus(realistic=True)``'s arithmetic, copied: log-normal
    lengths, bounded Zipf by the exact inverse CDF, per-row unique terms."""
    lens = np.clip(rng.lognormal(np.log(50.0), 0.45, n_docs),
                   10, 224).astype(np.int32)
    width = int(lens.max())
    w = np.arange(1, vocab, dtype=np.float64) ** -1.07
    cdf = np.cumsum(w / w.sum())
    tk = (np.searchsorted(cdf, rng.random((n_docs, width))) + 1).astype(
        np.int32)
    tk = np.where(np.arange(width)[None, :] < lens[:, None], tk, -1)
    uniq = np.array([len(set(r[r >= 0].tolist())) for r in tk])
    return lens, tk, uniq


@pytest.fixture(scope="module")
def corpus():
    return MOD.generate({"corpus": SPEC}, seed=3_000_000_019)


def test_layout(corpus):
    for seg in corpus["segments"]:
        ut, tf, ln = seg["uterms"], seg["utf"], seg["doc_len"]
        assert ut.shape == tf.shape == (8192, 224) and ut.dtype == np.int32
        assert tf.dtype == np.float32 and ln.dtype == np.int32
        assert ((ut >= 0) == (tf > 0)).all()
        assert (tf.sum(axis=1) == ln).all(), "counts add up to the length"
        assert ln.min() >= 10 and ln.max() <= 224
        assert ut.max() < 500_000 and ut[ut >= 0].min() >= 1
        row = ut[0][ut[0] >= 0]
        assert len(set(row.tolist())) == len(row), "terms of a row differ"
        assert (seg["df"] == np.bincount(ut[ut >= 0],
                                         minlength=500_000)).all()
    assert corpus["n_docs"] == 16384


def test_device_columns_are_the_host_columns_again(corpus):
    ut, tf, ln = corpus["device_columns"](1)
    seg = corpus["segments"][1]
    assert (np.asarray(ut).reshape(8192, 224) == seg["uterms"]).all()
    assert (np.asarray(tf).reshape(8192, 224) == seg["utf"]).all()
    assert seg["uterms"].flags.c_contiguous and seg["utf"].flags.c_contiguous
    assert (np.asarray(ln) == seg["doc_len"]).all()


def test_statistics_match_the_host_generator(corpus):
    lens, tk, uniq = host_corpus(np.random.default_rng(5), 16384)
    ln = np.concatenate([s["doc_len"] for s in corpus["segments"]])
    ut = np.concatenate([s["uterms"] for s in corpus["segments"]])
    tf = np.concatenate([s["utf"] for s in corpus["segments"]])
    assert ln.mean() == pytest.approx(lens.mean(), rel=0.02)
    assert (ut >= 0).sum(axis=1).mean() == pytest.approx(uniq.mean(),
                                                         rel=0.02)
    top_dev = tf[ut == 1].sum() / tf.sum()
    top_host = (tk == 1).sum() / (tk >= 0).sum()
    assert top_dev == pytest.approx(top_host, rel=0.05)
    assert top_dev == pytest.approx(
        MOD.zipf_constants(500_000, 1.07)["top_share"], rel=0.05)
    # the tail, where the analytic inverse stands in for the table
    for lo, hi in ((33, 1000), (1000, 50_000), (50_000, 500_000)):
        dev = tf[(ut >= lo) & (ut < hi)].sum() / tf.sum()
        host = ((tk >= lo) & (tk < hi)).sum() / (tk >= 0).sum()
        assert dev == pytest.approx(host, rel=0.05), (lo, hi)


def test_same_seed_same_corpus_and_large_seeds():
    small = {"corpus": {**SPEC, "segments": 1, "segment_rows": 256}}
    a = MOD.generate(small, seed=2**31 + 12345)
    b = MOD.generate(small, seed=2**31 + 12345)
    c = MOD.generate(small, seed=2**31 + 12346)
    assert (a["segments"][0]["uterms"] == b["segments"][0]["uterms"]).all()
    assert (a["segments"][0]["uterms"] != c["segments"][0]["uterms"]).any()


TRAFFIC = json.loads((ROOT / "benchmarks/traffic/"
                       "msearch64-top1000.json").read_text())["streams"][0]


def test_queries_same_lengths_on_every_seed(corpus):
    w = {"4": 0.3, "6": 0.4, "8": 0.3}
    lens = MOD.length_multiset(w, 1000)
    assert sorted(set(lens.tolist())) == [4, 6, 8] and len(lens) == 1000
    assert np.bincount(lens)[[4, 6, 8]].tolist() == [300, 400, 300]
    qs = MOD.draw_queries(corpus, lens[:50], np.random.default_rng(1))
    for q, n in zip(qs, lens[:50]):
        assert len(q) == n == len(set(q))
        assert all(corpus["df"][t] > 0 for t in q)


def test_the_cells_pool_mixes_2_to_12_terms_the_same_way_on_every_seed(
        corpus):
    rng = np.random.default_rng
    a = MOD.query_pool(corpus, TRAFFIC["queries"], rng(1), rng([0, 1]))
    b = MOD.query_pool(corpus, TRAFFIC["queries"], rng(2), rng([0, 1]))
    la, lb = [len(q) for q in a], [len(q) for q in b]
    assert la == lb and a != b, "the mix fixes the lengths, the seed the terms"
    assert sorted(set(la)) == list(range(2, 13))
    assert np.mean(la) == pytest.approx(5.95, abs=0.02)
    # lengths are mixed inside every request of 64, never sorted
    for lo in range(0, 64 * 20, 64):
        chunk = la[lo:lo + 64]
        assert len(set(chunk)) >= 6 and chunk != sorted(chunk)


def test_requests_and_warm_requests(corpus):
    rng = np.random.default_rng
    pool = MOD.query_pool(corpus, {**TRAFFIC["queries"], "pool": 256},
                          rng(1), rng([0, 1]))
    req = MOD.request(TRAFFIC["request"], pool[:64], "ix")
    lines = req["body"].strip().split("\n")
    assert req["path"] == "/_msearch" and req["items"] == 64
    assert len(lines) == 128 and json.loads(lines[1])["size"] == 1000
    # a mixed _msearch is served query by query, each a batch of one: one
    # request per length, so that none compiles more than one program
    warm = MOD.warm_requests(TRAFFIC["request"], pool, "ix", 32)
    assert len(warm) == 11 and {w["items"] for w in warm} == {1}
    assert all(w["path"] == "/_msearch" for w in warm)
    # single searches meet in the scheduler: every length x power of two,
    # the mixed batch it declines, and the lone search
    single = {"op": "search", "size": 10}
    one = MOD.request(single, pool[:1], "ix")
    assert one["path"] == "/ix/_search" and one["items"] == 1
    warm = MOD.warm_requests(single, pool, "ix", 32)
    assert len(warm) == 11 * 6 + 2
    assert sorted({w["items"] for w in warm}) == [1, 2, 4, 8, 16, 32]
    same = [q for q in pool if len(q) == 5]
    warm = MOD.warm_requests(TRAFFIC["request"], same, "ix", 32)
    assert len(warm) == 1 and warm[0]["items"] == 64
    assert MOD.stats(corpus) == {"docs": 16384,
                                 "postings": int(corpus["df"].sum())}


def brute_force_bm25(corpus, query):
    """BM25 straight from the formula over the rows, one document at a
    time — the slow witness of the reference."""
    n, avgdl = corpus["n_docs"], corpus["avgdl"]
    out = []
    for seg in corpus["segments"]:
        for ut, tf, dl in zip(seg["uterms"], seg["utf"], seg["doc_len"]):
            s = 0.0
            for t in query:
                f = float(tf[ut == t].sum())
                df = float(corpus["df"][t])
                idf = np.log(1 + (n - df + 0.5) / (df + 0.5))
                s += idf * f * 2.2 / (f + 1.2 * (0.25 + 0.75 * dl / avgdl))
            out.append(s)
    return np.array(out)


def test_reference_is_bm25():
    small = MOD.generate({"corpus": {**SPEC, "segments": 2,
                                     "segment_rows": 512}}, seed=11)
    qs = MOD.draw_queries(small, np.array([4, 8]),
                          np.random.default_rng(2))
    ref = MOD.Reference(small, qs)
    for q in qs:
        assert np.allclose(ref.scores(q), brute_force_bm25(small, q),
                           rtol=1e-12, atol=0)


def test_compare_reads_what_is_wrong(corpus):
    qs = MOD.draw_queries(corpus, np.array([6]), np.random.default_rng(3))
    full = MOD.Reference(corpus, qs).scores(qs[0])
    order = np.lexsort((np.arange(len(full)), -full))
    ids, total = order[:10], int((full > 0).sum())
    k10 = {"size": 10}
    clean = MOD.compare(full, k10, ids, full[ids], total)
    assert clean == {"score_gap": 0.0, "rank_gap": 0.0, "total_wrong": 0,
                     "hits_wrong": 0, "order_wrong": 0, "ties_not_by_id": 0}
    wrong_doc = ids.copy()
    wrong_doc[3] = order[5000]
    assert MOD.compare(full, k10, wrong_doc, full[ids],
                       total)["rank_gap"] > 1e-2
    assert MOD.compare(full, k10, ids, full[ids] * 1.001,
                       total)["score_gap"] == pytest.approx(1e-3, rel=1e-3)
    assert MOD.compare(full, k10, ids, full[ids], total + 1)["total_wrong"]
    assert MOD.compare(full, k10, ids[:9], full[ids[:9]],
                       total)["hits_wrong"]
    dup = ids.copy()
    dup[1] = dup[0]
    assert MOD.compare(full, k10, dup, full[dup], total)["hits_wrong"]
    # the right ten hits in another order: not best first
    shuffled = ids[[1, 0, 2, 3, 4, 5, 6, 7, 9, 8]]
    got = MOD.compare(full, k10, shuffled, full[shuffled], total)
    assert got["order_wrong"] == 2 and got["rank_gap"] == 0.0
    # two hits of one score, the higher document id first: counted apart
    # (an observation: Elasticsearch states no order among equal scores)
    lo, hi = sorted(ids[:2].tolist())
    tied = np.array([lo, hi] + ids[2:].tolist())
    sc = full[tied].copy()
    sc[:2] = sc[:2].max()
    assert MOD.compare(full, k10, tied, sc, total)["ties_not_by_id"] == 0
    tied[:2] = [hi, lo]
    got = MOD.compare(full, k10, tied, sc, total)
    assert got["ties_not_by_id"] == 1 and got["order_wrong"] == 0


def test_control_in_bfloat16_fails_the_comparison(corpus):
    """The reference in the program's place, every float in bfloat16 — the
    nearest precision below the configuration's float32 — has to come out
    as not correct under the cells' own limits (seeds 1..3)."""
    limits = json.loads((ROOT / "benchmarks/workloads/"
                         "msmarco-bm25.msearch64-top1000.json").read_text()
                        )["limits"]
    for seed in (1, 2, 3):
        qs = MOD.draw_queries(corpus, np.array([2, 5, 8, 12]),
                              np.random.default_rng(seed))
        ref = MOD.Reference(corpus, qs)
        worst = {k: 0.0 for k in limits}
        for q in qs:
            got = MOD.compare(ref.scores(q), {"size": 1000},
                              *MOD.control_hits(ref, q, 1000))
            worst = {k: max(worst[k], got[k]) for k in worst}
        assert any(worst[k] > limits[k] for k in limits), worst
        assert worst["score_gap"] > 10 * limits["score_gap"], worst
