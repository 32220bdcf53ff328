"""``corpora/zipf_text_sharded.py``: a hand-worked two-shard case in which
per-shard and global statistics order two documents differently (the
reference and the control follow the shards'), the per-shard statistics
against a recount, and the installer's contiguous id ranges."""

import math
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # the sharded cell's rehearsal needs four devices; set before any
    # test of the session first asks JAX for its backend
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np
import pytest

from benchmarks.corpora import zipf_text as base
from benchmarks.corpora import zipf_text_sharded as sharded

A, PAD = 1, 5      # term ids: the query term, and a filler


def two_shard_corpus() -> dict:
    """Two shards of one segment of four documents, every document four
    tokens long (avgdl 4 in both shards, so the length norm is 1.2
    everywhere). Term A: once in document 0 and in no other document of
    shard 0 (df 1 of 4); in every document of shard 1 (df 4 of 4), twice
    in document 4."""
    rows, width = 4, 4
    ut = np.full((2, rows, width), -1, np.int32)
    tf = np.zeros((2, rows, width), np.float32)
    # shard 0: doc 0 = A x1 + PAD x3; docs 1..3 = PAD x4
    ut[0, 0, :2], tf[0, 0, :2] = [A, PAD], [1, 3]
    ut[0, 1:, 0], tf[0, 1:, 0] = PAD, 4
    # shard 1: doc 4 = A x2 + PAD x2; docs 5..7 = A x1 + PAD x3
    ut[1, 0, :2], tf[1, 0, :2] = [A, PAD], [2, 2]
    ut[1, 1:, 0], tf[1, 1:, 0] = A, 1
    ut[1, 1:, 1], tf[1, 1:, 1] = PAD, 3
    segs = []
    for s in range(2):
        segs.append({"uterms": ut[s], "utf": tf[s],
                     "doc_len": np.full(rows, 4, np.int32),
                     "df": np.bincount(ut[s][ut[s] >= 0], minlength=8)})
    import jax.numpy as jnp
    corpus = {"kind": "zipf_text_sharded", "segments": segs,
              "df": segs[0]["df"] + segs[1]["df"], "n_docs": 8,
              "avgdl": 4.0, "vocab": 8, "rows": rows, "width": width,
              "device_columns": lambda si: (
                  jnp.asarray(ut[si]).reshape(-1),
                  jnp.asarray(tf[si]).reshape(-1), None)}
    corpus["shard_stats"] = sharded.shard_stats(
        [s["df"] for s in segs], [s["doc_len"] for s in segs], 2)
    return corpus


def test_per_shard_and_global_statistics_order_two_documents_differently():
    corpus = two_shard_corpus()
    st = corpus["shard_stats"]
    assert [s["n_docs"] for s in st] == [4, 4]
    assert [s["avgdl"] for s in st] == [4.0, 4.0]
    assert [int(s["df"][A]) for s in st] == [1, 4]
    # by hand: norm = k1·(1 − b + b·4/4) = 1.2
    # shard 0, doc 0: idf = ln(1 + 3.5/1.5), tf 1 → idf · 2.2/2.2
    x = math.log(1 + 3.5 / 1.5) * 1.0
    # shard 1, doc 4: idf = ln(1 + 0.5/4.5), tf 2 → idf · 4.4/3.2
    y = math.log(1 + 0.5 / 4.5) * 4.4 / 3.2
    assert x == pytest.approx(1.2039728) and y == pytest.approx(0.1448707)
    got = sharded.Reference(corpus, [[A]]).scores([A])
    assert got[0] == pytest.approx(x, rel=1e-12)
    assert got[4] == pytest.approx(y, rel=1e-12)
    assert got[5] == pytest.approx(math.log(1 + 0.5 / 4.5), rel=1e-12)
    assert list(got[1:4]) == [0.0, 0.0, 0.0]
    assert got[0] > got[4], "query_then_fetch: the rare term's shard wins"
    # ONE idf for all eight documents (dfs_query_then_fetch: df 5 of 8)
    glob = base.Reference(corpus, [[A]]).scores([A])
    idf = math.log(1 + 3.5 / 5.5)
    assert glob[0] == pytest.approx(idf) and glob[4] == pytest.approx(
        idf * 4.4 / 3.2)
    assert glob[4] > glob[0], "global statistics: the higher tf wins"
    # the comparison holds an answer to the per-shard reference: the
    # global order fails it by rank and by score
    ids = np.argsort(-glob)[:2]
    bad = sharded.compare(got, {"size": 2}, ids, glob[ids], 5)
    assert bad["score_gap"] > 0.5
    good = sharded.compare(got, {"size": 2}, np.array([0, 4]),
                           got[[0, 4]], 5)
    assert good["score_gap"] == 0 and good["rank_gap"] == 0 \
        and good["hits_wrong"] == 0 and good["total_wrong"] == 0


def test_control_follows_the_shards_statistics_in_bfloat16():
    corpus = two_shard_corpus()
    ref = sharded.Reference(corpus, [[A]])
    ids, scores, total = sharded.control_hits(ref, [A], 3)
    assert total == 5 and ids[0] == 0
    exact = ref.scores([A])
    # bfloat16 keeps 8 bits: right to 1%, wrong beyond 1e-4
    gap = abs(scores[0] - exact[0]) / exact[0]
    assert 1e-4 < gap < 2e-2


def test_generated_shard_statistics_equal_a_recount():
    config = {"name": "t", "corpus": {
        "kind": "zipf_text_sharded", "shards": 4, "segments": 8,
        "segment_rows": 256, "vocab": 500, "zipf_s": 1.07,
        "len_median": 50, "len_sigma": 0.45, "min_len": 10,
        "max_len": 224}}
    corpus = sharded.generate(config, 2 ** 31 + 5)
    assert corpus["n_docs"] == 8 * 256
    assert sum(st["n_docs"] for st in corpus["shard_stats"]) == 2048
    for s, st in enumerate(corpus["shard_stats"]):
        assert st["segments"] == [2 * s, 2 * s + 1]
        segs = [corpus["segments"][i] for i in st["segments"]]
        ut = np.concatenate([g["uterms"] for g in segs])
        assert np.array_equal(
            st["df"], np.bincount(ut[ut >= 0], minlength=500))
        lens = np.concatenate([g["doc_len"] for g in segs])
        assert st["avgdl"] == lens.sum() / len(lens)
    assert np.array_equal(sum(st["df"] for st in corpus["shard_stats"]),
                          corpus["df"])
    with pytest.raises(ValueError):
        sharded.generate({"corpus": {**config["corpus"], "segments": 6}}, 1)


def test_warm_request_is_the_windows_own_shape():
    pool = [[1, 2], [3, 4, 5], [6] * 12] * 30
    (req,) = sharded.warm_requests(
        {"op": "msearch", "items": 64, "size": 1000}, pool, "ix", 32)
    assert req["items"] == 64 and req["path"] == "/_msearch"
    assert req["body"].count('"size": 1000') == 64
