"""Data kind ``dense_vectors``: the generator is the seed's function, the
plain reference against a hand-worked case, the bfloat16 control against
the comparison (it has to fail), and ``knn_roofline`` on a hand-made
context."""

import json
import types
from pathlib import Path

import numpy as np
import pytest

from benchmarks import harness

ROOT = Path(__file__).resolve().parents[2]
MOD = harness.load_module(ROOT / "benchmarks/corpora/dense_vectors.py", "dv")
CELL = "dense768-knn.search-k10-c16"
CONFIG = json.loads(
    (ROOT / "benchmarks/configs/dense768-cosine-knn.json").read_text())
SPEC = json.loads((ROOT / f"benchmarks/workloads/{CELL}.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmarks/traffic/search-k10-c16.json").read_text())
REQUEST = TRAFFIC["streams"][0]["request"]
SMALL = {"corpus": {**CONFIG["corpus"], "segments": 2,
                    "segment_rows": 4096, "centres": 64}}


@pytest.fixture(scope="module")
def corpus():
    return MOD.generate(SMALL, seed=3_000_000_019)


def test_the_generator_is_the_seeds_function(corpus):
    again = MOD.generate(SMALL, seed=3_000_000_019)
    other = MOD.generate(SMALL, seed=3_000_000_020)
    for a, b, c in zip(corpus["segments"], again["segments"],
                       other["segments"]):
        assert a["vecs"].dtype == np.float32
        assert a["vecs"].shape == (4096, 768)
        assert (a["vecs"] == b["vecs"]).all()
        assert not (a["vecs"] == c["vecs"]).all()
    # the device makes a block again, bit for bit (the reference's source)
    assert (np.asarray(corpus["device_block"](1, 0))
            == corpus["segments"][1]["vecs"]).all()
    assert not (corpus["segments"][0]["vecs"]
                == corpus["segments"][1]["vecs"]).all()


def test_the_corpus_is_clustered_unit_vectors(corpus):
    v = corpus["segments"][0]["vecs"].astype(np.float64)
    assert np.abs((v * v).sum(axis=1) - 1.0).max() < 1e-6
    # one query's best scores: its source first, then cluster mates well
    # above the 0.18 of the best of as many uniform directions
    pool = MOD.query_pool(corpus, {"pool": 8},
                          np.random.default_rng(5), None)
    ref = MOD.Reference(corpus, pool)
    for q in pool:
        s = ref.scores(q)
        top = np.sort(s)[::-1][:10]
        assert int(np.argmax(s)) == q["source"]
        assert 0.7 < top[0] < 0.9 and top[9] > 0.35
        assert np.min(-np.diff(top)) > 1e-6, "no ties among the ten best"


def test_the_pool_is_the_seeds_and_states_its_decimals(corpus):
    a = MOD.query_pool(corpus, {"pool": 16}, np.random.default_rng(3), None)
    b = MOD.query_pool(corpus, {"pool": 16}, np.random.default_rng(3), None)
    c = MOD.query_pool(corpus, {"pool": 16}, np.random.default_rng(4), None)
    assert a == b and a != c
    body = json.loads(MOD.request(REQUEST, a[:1], "dense768")["body"])
    assert body["size"] == 10 and body["knn"]["k"] == 10
    assert body["knn"]["num_candidates"] == 100
    assert body["knn"]["field"] == "emb"
    assert (np.array(body["knn"]["query_vector"])
            == MOD.query_vector(a[0])).all()
    assert all(len(t.split(".")[1]) == 6 for t in a[0]["text"].split(","))
    warm = MOD.warm_requests(REQUEST, a, "dense768", max_batch=32)
    assert [w["items"] for w in warm] == [1, 2, 4, 8, 16, 1]
    assert warm[-1]["path"] == "/dense768/_search"


def hand_corpus():
    vecs = np.array([[1, 0], [0, 1], [3, 4], [-1, 0], [3, 3]],
                    np.float32)
    return {"n_docs": 5, "rows": 5, "block": 5, "dims": 2,
            "segments": [{}], "device_block": lambda si, bi: vecs}


def test_reference_hand_worked():
    """Five vectors in the plane against the query (1, 1): cosines
    √½, √½, 7/(5·√2) = 0.98995, −√½, 1 — two are not unit length, and the
    definition divides by their norms."""
    q = {"id": 0, "source": 4, "text": "1.000000,1.000000"}
    ref = MOD.Reference(hand_corpus(), [q])
    want = [0.5 ** 0.5, 0.5 ** 0.5, 1.4 / 2 ** 0.5, -0.5 ** 0.5, 1.0]
    assert ref.scores(q) == pytest.approx(want, abs=1e-15)
    params = {"k": 2, "size": 2}
    good = MOD.compare(ref.scores(q), params, np.array([4, 2]),
                       np.array([1.0, 0.98995]), 5)
    assert good["score_gap"] == pytest.approx(5.06e-7, abs=1e-8)
    assert good["rank_gap"] == 0 and good["order_wrong"] == 0
    assert good["hits_wrong"] == 0 and good["hits_total"] == 5
    # a document that is not among the two best: the gap to the second
    miss = MOD.compare(ref.scores(q), params, np.array([4, 0]),
                       np.array([1.0, 0.7071]), 5)
    assert miss["rank_gap"] == pytest.approx(1.4 / 2 ** 0.5 - 0.5 ** 0.5)
    swapped = MOD.compare(ref.scores(q), params, np.array([2, 4]),
                          np.array([0.98995, 1.0]), 5)
    assert swapped["order_wrong"] == 1 and swapped["rank_gap"] == 0
    for ids in ([4], [4, 4], [4, 7]):
        bad = MOD.compare(ref.scores(q), params, np.array(ids),
                          np.ones(len(ids)), 5)
        assert bad["hits_wrong"] == 1


@pytest.mark.parametrize("seed", [4100000031, 2**31 + 5, 77])
def test_the_control_fails_the_cells_limits(seed):
    corpus = MOD.generate(SMALL, seed)
    pool = MOD.query_pool(corpus, {"pool": 16},
                          np.random.default_rng([seed, 13]), None)
    ref = MOD.Reference(corpus, pool)
    limits = SPEC["limits"]
    worst = {n: 0.0 for n in limits}
    for q in pool:
        ids, scores, total = MOD.control_hits(ref, q, 10)
        assert len(ids) == 10 and total == corpus["n_docs"]
        got = MOD.compare(ref.scores(q), REQUEST, ids, scores, total)
        worst = {n: max(worst[n], got[n]) for n in worst}
    assert worst["score_gap"] > 10 * limits["score_gap"], worst
    assert worst["order_wrong"] == 0 and worst["hits_wrong"] == 0


def ctx_for(seconds: float, n_requests: int, stats: dict) -> dict:
    cell = types.SimpleNamespace(
        spec={"lane_modules": ["jit_run_outer"], "expected_lanes": ["knn"]},
        traffic=TRAFFIC, config=CONFIG)
    # each request 0.05 s in flight, all inside the slice [0, 8]
    records = [[i, 0.0, 0.1 + 0.04 * i, 0.15 + 0.04 * i, 200, 1, 1]
               for i in range(n_requests)]
    return {"cell": cell, "corpus_stats": stats, "records": records,
            "dev": {"kind": "TPU v5 lite"},
            "traced": {"t0": 0.0, "t1": 8.0, "reduced": {"modules": {
                "jit_run_outer": {"seconds": seconds},
                "jit_other": {"seconds": 9.0}}}}}


def test_knn_roofline_hand_made_context():
    reader = harness.load_module(
        harness.reader_file("layer_metrics", "knn_roofline"), "kr")
    stats = {"docs": 3 * (1 << 20), "dims": 768}
    # 160 queries, 16 clients: ten reads of 9.66 GB at 819 GB/s = 0.118 s
    # (the six-pass flops bound, 2.35 ms a batch, does not bind) over 2 s
    # of the lane's programs
    least = 3 * (1 << 20) * 768 * 4 / 819e9
    assert reader.read(ctx_for(2.0, 160, stats)) == pytest.approx(
        100 * 10 * least / 2.0)
    assert reader.read(ctx_for(2.0, 160, stats)) == pytest.approx(
        5.90, abs=0.01)
    # nothing to read: no trace, no requests, another data kind's stats
    assert reader.read({**ctx_for(2.0, 160, stats), "traced": None}) is None
    assert reader.read(ctx_for(2.0, 0, stats)) is None
    assert reader.read(ctx_for(0.0, 160, stats)) is None
    assert reader.read(ctx_for(2.0, 160, {"docs": 5, "postings": 9})) is None
