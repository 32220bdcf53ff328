"""The data kind ``zipf_text_positions`` on hand-worked cases: the phrase's
frequency and score, the minimum of terms, the positions drawn with
msearch64's documents, the phrase-holding queries, and the bytes of the
phrase roofline."""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from benchmarks import phrase_rooflines
from benchmarks.corpora import zipf_text, zipf_text_positions as zp

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks" / "configs"
                     / "msmarco-passage-bm25-phrase.json").read_text())
SMALL = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 512, "vocab": 3000}}


def test_phrase_counts_hand_worked():
    import jax.numpy as jnp
    tokens = jnp.asarray([[5, 6, 7, 5, 6, -1],
                          [6, 5, 6, 5, 6, 5],
                          [5, 6, -1, -1, -1, -1],
                          [7, 7, 7, 7, 5, 6]], jnp.int32)
    got = zp.phrase_counts(tokens, jnp.asarray([5, 6], jnp.int32))
    assert np.asarray(got).tolist() == [2, 2, 1, 1]
    got = zp.phrase_counts(tokens, jnp.asarray([5, 6, 7], jnp.int32))
    assert np.asarray(got).tolist() == [1, 0, 0, 0]
    # a phrase as long as the row fits once, no further
    got = zp.phrase_counts(tokens, jnp.asarray([6, 5, 6, 5, 6, 5],
                                               jnp.int32))
    assert np.asarray(got).tolist() == [0, 1, 0, 0]


@pytest.mark.parametrize("n,need", [(2, 1), (3, 1), (4, 1), (6, 1),
                                    (7, 2), (9, 2), (10, 3), (12, 3)])
def test_thirty_percent_rounds_down_and_keeps_one(n, need):
    assert zp.required_terms(n, "30%") == need


def test_score_hand_worked():
    """Three documents, phrase "a b" (ids 1, 2): by hand from the
    published formula."""
    tokens = [np.array([[1, 2, 3, 1, 2, -1], [2, 1, 3, -1, -1, -1],
                        [3, 3, 3, 3, -1, -1]], np.int32)]
    lens = np.array([5, 3, 4])
    corpus = _tiny(tokens, lens, vocab=5)
    ref = zp.Reference(corpus, [[1, 2]])
    got = ref.scores([1, 2])
    n, avgdl = 3, 4.0
    idf = math.log(1 + (n - 2 + 0.5) / (2 + 0.5))      # df(1) = df(2) = 2

    def w(f, dl):
        return f * 2.2 / (f + 1.2 * (0.25 + 0.75 * dl / avgdl))
    want0 = idf * w(2, 5) * 2 + 2 * idf * w(2, 5)      # tf 2 each, pf 2
    want1 = idf * w(1, 3) * 2                          # no phrase: "b a"
    assert got[0] == pytest.approx(want0, rel=1e-12)
    assert got[1] == pytest.approx(want1, rel=1e-12)
    assert got[2] == 0.0                               # holds no term
    assert got.phrase.tolist() == [2, 0, 0]


def _tiny(tokens: list, lens, vocab: int) -> dict:
    """A corpus dict of hand-made tokens, the columns derived from them
    (as the generator derives them from its draw)."""
    import jax.numpy as jnp
    segs, df = [], np.zeros(vocab, np.int64)
    for tok in tokens:
        width = tok.shape[1]
        ut = np.full_like(tok, -1)
        tf = np.zeros(tok.shape, np.float32)
        for r, row in enumerate(tok):
            terms, counts = np.unique(row[row >= 0], return_counts=True)
            ut[r, :len(terms)], tf[r, :len(terms)] = terms, counts
        seg_df = np.bincount(ut[ut >= 0], minlength=vocab)
        df += seg_df
        segs.append({"uterms": ut, "utf": tf, "df": seg_df,
                     "doc_len": np.asarray(lens, np.int32),
                     "tokens": tok})
    rows = tokens[0].shape[0]
    return {"segments": segs, "df": df, "n_docs": rows * len(segs),
            "avgdl": float(sum(s["doc_len"].sum() for s in segs))
            / (rows * len(segs)), "rows": rows, "vocab": vocab,
            "width": width,
            "device_columns": lambda si: (
                jnp.asarray(segs[si]["uterms"]).reshape(-1),
                jnp.asarray(segs[si]["utf"]).reshape(-1),
                jnp.asarray(segs[si]["doc_len"])),
            "device_tokens": lambda si: jnp.asarray(
                segs[si]["tokens"]).reshape(-1)}


@pytest.fixture(scope="module")
def corpus():
    return zp.generate(SMALL, 4100003636)


def test_positions_are_the_documents_of_zipf_text(corpus):
    """The same seed gives msearch64's columns, and the positions sort
    into them: every document's unique terms and counts are its tokens'."""
    base = zipf_text.generate(SMALL, 4100003636)
    for mine, theirs in zip(corpus["segments"], base["segments"]):
        for key in ("uterms", "utf", "doc_len", "df"):
            np.testing.assert_array_equal(mine[key], theirs[key])
        tok = mine["tokens"]
        pos = np.arange(tok.shape[1])[None, :]
        assert ((tok >= 0) == (pos < mine["doc_len"][:, None])).all()
        for r in range(0, tok.shape[0], 37):
            terms, counts = np.unique(tok[r][tok[r] >= 0],
                                      return_counts=True)
            ut = mine["uterms"][r]
            assert terms.tolist() == ut[ut >= 0].tolist()
            assert counts.tolist() == mine["utf"][r][ut >= 0].tolist()
    assert zp.stats(corpus)["positions"] == sum(
        int(s["doc_len"].sum()) for s in corpus["segments"])


def test_every_query_is_a_phrase_of_its_corpus(corpus):
    terms = {str(k): 1 for k in range(2, 13)}
    pool = zp.query_pool(corpus, {"pool": 44, "terms": terms},
                         np.random.default_rng(3), np.random.default_rng(1))
    assert sorted({len(q) for q in pool}) == list(range(2, 13))
    ref = zp.Reference(corpus, pool)
    for q in pool:
        assert len(set(q)) == len(q)
        assert ref.pf[tuple(q)].max() >= 1
        s = ref.scores(q)
        # every document that holds the phrase is a hit, and ranks above
        # what its match alone would give
        assert (s[ref.pf[tuple(q)] > 0] > 0).all()


def test_a_run_whose_answers_hold_no_phrase_fails_on_its_last():
    scores = np.array([3.0, 2.0, 1.0]).view(zp.RefScores)
    scores.phrase = np.array([0, 0, 1], np.uint8)
    scores.tally = {"answers": 0, "expected": 2, "hits": 0}
    params = {"size": 2}
    first = zp.compare(scores, params, np.array([0, 1]),
                       np.array([3.0, 2.0]), 3)
    assert first["phrase_hits"] == 0 and first["phrase_hits_none"] == 0
    last = zp.compare(scores, params, np.array([0, 1]),
                      np.array([3.0, 2.0]), 3)
    assert last["phrase_hits_none"] == 1
    scores.tally = {"answers": 0, "expected": 2, "hits": 0}
    zp.compare(scores, params, np.array([0, 2]), np.array([3.0, 1.0]), 3)
    last = zp.compare(scores, params, np.array([0, 1]),
                      np.array([3.0, 2.0]), 3)
    assert last["phrase_hits_none"] == 0


def test_phrase_roofline_bytes_hand_worked():
    # 1,000 docs of 10 distinct terms and 12 tokens each
    assert phrase_rooflines.phrase_batch_bytes(10_000, 12_000, 1000) \
        == 10_000 * 8 + 12_000 * 4 + 1000 * 4
    # the cell: 4 x 2^20 docs, 43 distinct terms and 54 tokens in the
    # mean, against the padded layout's 224 slots of three columns
    n = 4 * (1 << 20)
    least = phrase_rooflines.phrase_batch_bytes(43 * n, 54 * n, n)
    assert least == n * (43 * 8 + 54 * 4 + 4)
    assert least < 0.25 * n * 224 * 12
    t = phrase_rooflines.phrase_batch_min_seconds(
        43 * n, 54 * n, n, {"hbm_bytes_per_s": 819e9})
    assert t == pytest.approx(least / 819e9)
