"""Kernel tests: every ops/ function vs a numpy brute-force reference."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.ops import lexical, phrase, boolean, filters, topk, vector
from elasticsearch_tpu.ops import functionscore as fs
from elasticsearch_tpu.ops import aggs_ops
from elasticsearch_tpu.ops.similarity import idf as bm25_idf, BM25Params


def make_corpus(rng, n_docs=50, vocab=30, max_len=16):
    """Random corpus in both layouts: list-of-term-lists + dense columns."""
    docs = []
    for _ in range(n_docs):
        ln = int(rng.integers(1, max_len))
        docs.append(rng.integers(0, vocab, size=ln).tolist())
    L = max(len(d) for d in docs)
    U = max(len(set(d)) for d in docs)
    tokens = np.full((n_docs, L), -1, np.int32)
    uterms = np.full((n_docs, U), -1, np.int32)
    utf = np.zeros((n_docs, U), np.float32)
    doc_len = np.zeros(n_docs, np.int32)
    for i, d in enumerate(docs):
        tokens[i, :len(d)] = d
        counts = {}
        for t in d:
            counts[t] = counts.get(t, 0) + 1
        for u, (t, c) in enumerate(sorted(counts.items())):
            uterms[i, u] = t
            utf[i, u] = c
        doc_len[i] = len(d)
    return docs, tokens, uterms, utf, doc_len


def np_bm25(docs, qterms, k1=1.2, b=0.75):
    """Brute-force BM25 reference."""
    n = len(docs)
    avgdl = sum(len(d) for d in docs) / n
    scores = np.zeros(n)
    nmatch = np.zeros(n, np.int32)
    for t in set(qterms):
        df = sum(1 for d in docs if t in d)
        idf = math.log(1 + (n - df + 0.5) / (df + 0.5))
        for i, d in enumerate(docs):
            tf = d.count(t)
            if tf:
                dl = len(d)
                scores[i] += idf * tf * (k1 + 1) / (tf + k1 * (1 - b + b * dl / avgdl))
                nmatch[i] += 1
    return scores, nmatch


class TestBM25:
    def test_matches_brute_force(self, rng):
        docs, _, uterms, utf, doc_len = make_corpus(rng)
        qterms = [3, 7, 11]
        n = len(docs)
        avgdl = sum(len(d) for d in docs) / n
        qidf = np.array([bm25_idf(sum(1 for d in docs if t in d), n)
                         for t in qterms], np.float32)
        scores, nmatch = lexical.bm25_match(
            jnp.array(uterms), jnp.array(utf), jnp.array(doc_len),
            jnp.array(qterms, jnp.int32), jnp.array(qidf),
            jnp.ones(len(qterms), jnp.float32), 1.2, 0.75, avgdl)
        ref_scores, ref_nmatch = np_bm25(docs, qterms)
        np.testing.assert_allclose(np.asarray(scores), ref_scores, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(nmatch), ref_nmatch)

    def test_absent_term_padding(self, rng):
        docs, _, uterms, utf, doc_len = make_corpus(rng)
        # qtid -1 (absent term / padding) must contribute nothing and
        # never "match" the -1 padding in uterms
        scores, nmatch = lexical.bm25_match(
            jnp.array(uterms), jnp.array(utf), jnp.array(doc_len),
            jnp.array([-1, -1], jnp.int32), jnp.zeros(2, jnp.float32),
            jnp.ones(2, jnp.float32), 1.2, 0.75, 10.0)
        assert np.asarray(scores).max() == 0.0
        assert np.asarray(nmatch).max() == 0

    def test_jit_compatible(self, rng):
        docs, _, uterms, utf, doc_len = make_corpus(rng)
        f = jax.jit(lambda a, b, c, q, i: lexical.bm25_match(
            a, b, c, q, i, jnp.ones(2, jnp.float32), 1.2, 0.75, 8.0))
        s, _ = f(jnp.array(uterms), jnp.array(utf), jnp.array(doc_len),
                 jnp.array([1, 2], jnp.int32), jnp.array([1.0, 1.0], jnp.float32))
        assert s.shape == (len(docs),)


def np_bm25_slots(uterms, utf, doc_len, qtids, qidf, qweight, k1, b, avgdl):
    """float64 BM25 written from the formula over the forward columns:
    score(d) = Σ_t idf_t · w_t · tf·(k1+1) / (tf + k1·(1 − b + b·dl/avgdl))
    over the query terms present in d (a repeated term counts twice);
    nmatch(d) = how many of the query's entries d holds."""
    scores = np.zeros(len(uterms))
    nmatch = np.zeros(len(uterms), np.int32)
    for d in range(len(uterms)):
        norm = k1 * (1.0 - b + b * float(doc_len[d]) / avgdl)
        for t, tid in enumerate(qtids):
            if tid < 0:
                continue
            for u in np.flatnonzero(uterms[d] == tid):
                tf = float(utf[d, u])
                scores[d] += (float(qidf[t]) * float(qweight[t])
                              * tf * (k1 + 1.0) / (tf + norm))
                nmatch[d] += 1
    return scores, nmatch


class TestBM25OnePass:
    """bm25_match against the formula in float64, by number of query
    terms and by how it is called."""

    @pytest.mark.parametrize("how", ["plain", "jit", "vmap"])
    @pytest.mark.parametrize("n_terms", [1, 2, 6, 12, 16])
    def test_formula(self, rng, n_terms, how):
        vocab, n_queries = 40, 3
        _, _, uterms, utf, doc_len = make_corpus(rng, n_docs=192,
                                                 vocab=vocab, max_len=24)
        # rows 0-2 are all padding; row 3 keeps its terms at doc_len 0
        uterms[:3], utf[:3], doc_len[:4] = -1, 0.0, 0
        qtids = rng.integers(0, vocab, (n_queries, n_terms)).astype(np.int32)
        if n_terms > 1:
            qtids[:, 1] = -1                       # an absent term
            qtids[1, :] = -1                       # a query with no term
        if n_terms > 2:
            qtids[:, 2] = qtids[:, 0]              # a repeated term
        qidf = np.where(qtids >= 0, rng.uniform(
            0.1, 9.0, qtids.shape), 0.0).astype(np.float32)
        qweight = rng.uniform(0.5, 3.0, qtids.shape).astype(np.float32)
        # b = 1 with doc_len 0 makes row 3's norm 0: its padded slots
        # hold 0/0, which must not reach its (or any) score
        k1, b, avgdl = 1.2, 1.0, 17.5

        def f(qt, qi, qw):
            return lexical.bm25_match(jnp.array(uterms), jnp.array(utf),
                                      jnp.array(doc_len), qt, qi, qw,
                                      k1, b, avgdl)
        if how == "vmap":
            scores, nmatch = jax.jit(jax.vmap(f))(
                jnp.array(qtids), jnp.array(qidf), jnp.array(qweight))
        else:
            g = jax.jit(f) if how == "jit" else f
            outs = [g(jnp.array(qtids[q]), jnp.array(qidf[q]),
                      jnp.array(qweight[q])) for q in range(n_queries)]
            scores = np.stack([np.asarray(s) for s, _ in outs])
            nmatch = np.stack([np.asarray(m) for _, m in outs])
        scores, nmatch = np.asarray(scores), np.asarray(nmatch)
        assert scores.dtype == np.float32 and nmatch.dtype == np.int32
        assert np.isfinite(scores).all()
        for q in range(n_queries):
            ref_s, ref_n = np_bm25_slots(uterms, utf, doc_len, qtids[q],
                                         qidf[q], qweight[q], k1, b, avgdl)
            np.testing.assert_allclose(scores[q], ref_s, rtol=1e-5, atol=0)
            np.testing.assert_array_equal(nmatch[q], ref_n)
        assert (scores[:, :3] == 0).all() and (nmatch[:, :3] == 0).all()
        if n_terms > 1:
            assert (scores[1] == 0).all() and (nmatch[1] == 0).all()
        if n_terms > 2:
            assert nmatch.max() >= 2 and scores.max() > 0


@pytest.mark.parametrize("how", ["plain", "jit"])
@pytest.mark.parametrize("n_terms,bucket", [(1, 2), (3, 4), (5, 8),
                                            (12, 16), (2, 16)])
def test_bm25_match_trailing_pads_change_no_bit(rng, n_terms, bucket, how):
    """What the plan compiler leans on (execute._res_MatchQuery pads a
    match's term lists to a term bucket): trailing absent terms (id -1,
    idf 0.0) add 0.0 to a slot's weight and 0 to its count AFTER the
    real terms, so op by op scores and nmatch are the unpadded call's
    bit for bit. Compiled, the two widths are two programs and XLA may
    order each one's sum along U its own way: the last bit of a score
    may differ (1.4e-7 relative at the most over 300 seeds on the CPU),
    nmatch never."""
    vocab = 40
    _, _, uterms, utf, doc_len = make_corpus(rng, n_docs=192, vocab=vocab,
                                             max_len=24)
    qtids = rng.integers(0, vocab, n_terms).astype(np.int32)
    qidf = rng.uniform(0.1, 9.0, n_terms).astype(np.float32)
    pad = bucket - n_terms

    def f(qt, qi):
        return lexical.bm25_match(
            jnp.array(uterms), jnp.array(utf), jnp.array(doc_len), qt, qi,
            jnp.ones(qt.shape[0], jnp.float32), 1.2, 0.75, 17.5)
    g = jax.jit(f) if how == "jit" else f
    s0, n0 = g(jnp.array(qtids), jnp.array(qidf))
    s1, n1 = g(jnp.array(np.concatenate([qtids, np.full(pad, -1, np.int32)])),
               jnp.array(np.concatenate([qidf, np.zeros(pad, np.float32)])))
    assert np.asarray(s0).max() > 0
    if how == "plain":
        np.testing.assert_array_equal(np.asarray(s0).view(np.uint32),
                                      np.asarray(s1).view(np.uint32))
    else:
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   rtol=1e-6, atol=0)
    np.testing.assert_array_equal(np.asarray(n0), np.asarray(n1))


def test_bm25_match_sums_a_repeated_query_term(rng):
    """A query that names one term twice scores as the query that names
    it once at twice the idf (Lucene sums a repeated clause), and both
    agree with the float64 formula; nmatch counts the repeat."""
    vocab = 40
    _, _, uterms, utf, doc_len = make_corpus(rng, n_docs=192, vocab=vocab,
                                             max_len=24)
    t, u = (int(x) for x in rng.choice(vocab, size=2, replace=False))
    idf_t, idf_u = np.float32(2.75), np.float32(0.625)
    k1, b, avgdl = 1.2, 0.75, 17.5

    def run(qtids, qidf):
        qtids = np.array(qtids, np.int32)
        qidf = np.array(qidf, np.float32)
        ones = np.ones(len(qtids), np.float32)
        got = lexical.bm25_match(
            jnp.array(uterms), jnp.array(utf), jnp.array(doc_len),
            jnp.array(qtids), jnp.array(qidf), jnp.array(ones), k1, b, avgdl)
        ref = np_bm25_slots(uterms, utf, doc_len, qtids, qidf, ones,
                            k1, b, avgdl)
        return [np.asarray(a) for a in got], ref

    (twice_s, twice_n), (ref_s, ref_n) = run([t, t, u], [idf_t, idf_t, idf_u])
    (once_s, once_n), _ = run([t, u], [2 * idf_t, idf_u])
    holds_t = (uterms == t).any(axis=1)
    assert holds_t.any() and twice_s[holds_t].min() > 0
    np.testing.assert_allclose(twice_s, ref_s, rtol=1e-5, atol=0)
    np.testing.assert_array_equal(twice_n, ref_n)
    np.testing.assert_allclose(twice_s, once_s, rtol=1e-6, atol=0)
    np.testing.assert_array_equal(twice_n, once_n + holds_t)


class TestPhrase:
    def test_exact_phrase(self):
        # doc0: "a b c", doc1: "b a b c", doc2: "a c b"
        tokens = np.array([[0, 1, 2, -1], [1, 0, 1, 2], [0, 2, 1, -1]], np.int32)
        freq = phrase.phrase_freq(jnp.array(tokens),
                                  [jnp.int32(0), jnp.int32(1)], [0, 1])
        # "a b" occurs in doc0 (pos0) and doc1 (pos1); not doc2
        np.testing.assert_array_equal(np.asarray(freq), [1.0, 1.0, 0.0])

    def test_phrase_with_gap(self):
        # query "a _ c" (stopword removed at position 1): deltas [0, 2]
        tokens = np.array([[0, 1, 2, -1], [0, 2, 1, -1]], np.int32)
        freq = phrase.phrase_freq(jnp.array(tokens),
                                  [jnp.int32(0), jnp.int32(2)], [0, 2])
        np.testing.assert_array_equal(np.asarray(freq), [1.0, 0.0])

    def test_repeated_phrase_counts(self):
        tokens = np.array([[0, 1, 0, 1, 0, 1]], np.int32)
        freq = phrase.phrase_freq(jnp.array(tokens),
                                  [jnp.int32(0), jnp.int32(1)], [0, 1])
        assert np.asarray(freq)[0] == 3.0

    def test_absent_term(self):
        tokens = np.array([[0, 1]], np.int32)
        freq = phrase.phrase_freq(jnp.array(tokens),
                                  [jnp.int32(0), jnp.int32(-1)], [0, 1])
        assert np.asarray(freq)[0] == 0.0

    def test_hole_never_matches(self):
        # position-indexed layout: stopword hole is -1; a phrase spanning the
        # hole with correct deltas still matches
        tokens = np.array([[5, -1, 7, -1]], np.int32)
        freq = phrase.phrase_freq(jnp.array(tokens),
                                  [jnp.int32(5), jnp.int32(7)], [0, 2])
        assert np.asarray(freq)[0] == 1.0

    def test_sloppy_count_counts_each_match(self):
        # "a x b ... a x b": two in-order matches at displacement 1 each —
        # sloppyFreq sums 0.5+0.5=1.0 but the span COUNT must be 2
        tokens = np.array([[0, 9, 1, 7, 0, 9, 1, -1]], np.int32)
        freq = phrase.sloppy_phrase_freq(jnp.array(tokens),
                                         [jnp.int32(0), jnp.int32(1)],
                                         [0, 1], 1)
        np.testing.assert_allclose(np.asarray(freq), [1.0])
        count = phrase.sloppy_phrase_count(jnp.array(tokens),
                                           [jnp.int32(0), jnp.int32(1)],
                                           [0, 1], 1)
        np.testing.assert_allclose(np.asarray(count), [2.0])

    def test_span_near_unordered_freq(self):
        # terms 0,1 within window 2+1: doc0 "1 0" reversed adjacent →
        # match; doc1 far apart → none; doc2 two separate regions → 2
        tokens = np.array([[1, 0, -1, -1, -1, -1, -1, -1],
                           [0, 9, 9, 9, 9, 9, 9, 1],
                           [0, 1, 9, 9, 9, 1, 0, -1]], np.int32)
        freq = phrase.span_near_freq_unordered(
            jnp.array(tokens), [jnp.int32(0), jnp.int32(1)], 1)
        np.testing.assert_allclose(np.asarray(freq), [1.0, 0.0, 2.0])

    def test_sloppy(self):
        # doc0: "0 9 1" — term 1 is displaced by 1 from the exact-phrase
        # position → sloppyFreq 1/(1+1) = 0.5 at slop 1.
        # doc1: "0 9 9 1" — displacement 2 > slop 1 → no match.
        tokens = np.array([[0, 9, 1, -1], [0, 9, 9, 1]], np.int32)
        freq = phrase.sloppy_phrase_freq(jnp.array(tokens),
                                         [jnp.int32(0), jnp.int32(1)], [0, 1], 1)
        np.testing.assert_allclose(np.asarray(freq), [0.5, 0.0])


class TestBoolean:
    def test_combination(self):
        n = 4
        s = lambda *v: (jnp.array(v, jnp.float32), jnp.array([x > 0 for x in v]))
        m = lambda *v: jnp.array([bool(x) for x in v])
        scores, mask = boolean.combine_bool(
            n,
            must=[s(1, 2, 0, 3)],
            should=[s(5, 0, 5, 5)],
            must_not=[m(0, 0, 0, 1)],
            filters=[m(1, 1, 1, 1)],
            minimum_should_match=0)
        np.testing.assert_array_equal(np.asarray(mask), [True, True, False, False])
        np.testing.assert_allclose(np.asarray(scores), [6, 2, 5, 8])

    def test_minimum_should_match(self):
        n = 3
        sh1 = (jnp.ones(n, jnp.float32), jnp.array([True, True, False]))
        sh2 = (jnp.ones(n, jnp.float32), jnp.array([True, False, False]))
        _, mask = boolean.combine_bool(n, [], [sh1, sh2], [], [], 2)
        np.testing.assert_array_equal(np.asarray(mask), [True, False, False])


class TestFilters:
    def test_keyword_term_and_terms(self):
        ords = jnp.array([[0, -1], [1, 2], [-1, -1]], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(filters.keyword_term(ords, jnp.int32(2))),
            [False, True, False])
        np.testing.assert_array_equal(
            np.asarray(filters.keyword_terms(
                ords, jnp.array([0, 2], jnp.int32))), [True, True, False])
        # absent value (-1) matches nothing, including pads
        np.testing.assert_array_equal(
            np.asarray(filters.keyword_term(ords, jnp.int32(-1))),
            [False, False, False])

    def test_ord_range(self):
        ords = jnp.array([[0], [1], [2], [3]], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(filters.keyword_ord_range(ords, 1, 3)),
            [False, True, True, False])

    def test_numeric_range_exact_dates(self):
        from elasticsearch_tpu.index.device_reader import dd_split
        # epoch millis ~1.44e12 differing by 1ms — f32 alone cannot tell apart
        vals = np.array([1443657600000.0, 1443657600001.0, 1443657599999.0])
        hi, lo = dd_split(vals)
        ex = jnp.ones(3, bool)
        ghi, glo = dd_split(1443657600000.0)
        lhi, llo = dd_split(np.inf)
        got = filters.numeric_range(jnp.array(hi), jnp.array(lo), ex,
                                    jnp.float32(ghi), jnp.float32(glo),
                                    jnp.float32(lhi), jnp.float32(llo))
        np.testing.assert_array_equal(np.asarray(got), [True, True, False])

    def test_geo_distance(self):
        lat = jnp.array([40.7128, 48.8566], jnp.float32)   # NYC, Paris
        lon = jnp.array([-74.0060, 2.3522], jnp.float32)
        ex = jnp.ones(2, bool)
        # within 100km of NYC
        got = filters.geo_distance(lat, lon, ex, 40.73, -73.93, 100_000.0)
        np.testing.assert_array_equal(np.asarray(got), [True, False])


class TestTopK:
    def test_basic_and_tiebreak(self):
        scores = jnp.array([1.0, 3.0, 3.0, 2.0, 0.5])
        mask = jnp.ones(5, bool)
        ts, td = topk.top_k(scores, mask, 3)
        # tie at 3.0 → lower doc id first (Lucene semantics)
        np.testing.assert_array_equal(np.asarray(td), [1, 2, 3])

    def test_mask_and_padding(self):
        scores = jnp.array([9.0, 8.0, 7.0])
        mask = jnp.array([False, True, False])
        ts, td = topk.top_k(scores, mask, 3)
        np.testing.assert_array_equal(np.asarray(td), [1, -1, -1])
        assert np.asarray(ts)[1] == -np.inf

    def test_doc_base(self):
        scores = jnp.array([1.0, 5.0])
        _, td = topk.top_k(scores, jnp.ones(2, bool), 1, doc_base=100)
        assert np.asarray(td)[0] == 101

    def test_merge(self):
        s1 = jnp.array([5.0, 3.0, -jnp.inf])
        d1 = jnp.array([0, 1, -1], jnp.int32)
        s2 = jnp.array([4.0, 3.0, 2.0])
        d2 = jnp.array([100, 101, 102], jnp.int32)
        ms, md = topk.merge_top_k([s1, s2], [d1, d2], 4)
        np.testing.assert_array_equal(np.asarray(md), [0, 100, 1, 101])
        np.testing.assert_allclose(np.asarray(ms), [5, 4, 3, 3])


class TestVector:
    def test_cosine_exact(self, rng):
        vecs = rng.standard_normal((10, 8)).astype(np.float32)
        q = rng.standard_normal(8).astype(np.float32)
        normed = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        got = vector.cosine_scores(jnp.array(normed), jnp.ones(10, bool),
                                   jnp.array(q), use_bf16=False)
        ref = normed @ (q / np.linalg.norm(q))
        # atol floors the check: near-zero cosines (random vectors) differ
        # in last f32 ulps between device and numpy reduction orders, and
        # pure-relative tolerance explodes at zero
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5,
                                   atol=1e-6)

    def test_batch_matches_single(self, rng):
        vecs = rng.standard_normal((10, 8)).astype(np.float32)
        normed = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        qs = rng.standard_normal((3, 8)).astype(np.float32)
        batch = vector.cosine_scores_batch(jnp.array(normed),
                                           jnp.ones(10, bool),
                                           jnp.array(qs), use_bf16=False)
        for i in range(3):
            single = vector.cosine_scores(jnp.array(normed), jnp.ones(10, bool),
                                          jnp.array(qs[i]), use_bf16=False)
            # atol floors the check: a near-zero cosine (random vectors)
            # differs in last f32 ulps between the batched matmul and the
            # single matvec reduction orders, and pure-relative tolerance
            # explodes at zero
            np.testing.assert_allclose(np.asarray(batch[i]), np.asarray(single),
                                       rtol=1e-5, atol=1e-6)


class TestFunctionScore:
    def test_field_value_factor(self):
        v = jnp.array([0.0, 10.0, 100.0])
        ex = jnp.ones(3, bool)
        out = fs.field_value_factor(v, ex, factor=1.0, modifier="log1p")
        np.testing.assert_allclose(np.asarray(out),
                                   np.log10([1.0, 11.0, 101.0]), rtol=1e-5)

    @pytest.mark.parametrize("kind", ["gauss", "exp", "linear"])
    def test_decay_properties(self, kind):
        v = jnp.array([10.0, 15.0, 20.0, 1000.0])
        ex = jnp.ones(4, bool)
        out = np.asarray(fs.decay(v, ex, origin=10.0, scale=10.0, offset=0.0,
                                  decay_value=0.5, kind=kind))
        assert out[0] == pytest.approx(1.0)           # at origin
        assert out[2] == pytest.approx(0.5, abs=1e-5)  # at scale → decay value
        assert out[3] < 0.01                           # far away

    def test_combine_and_boost(self):
        f1 = jnp.array([2.0, 3.0])
        f2 = jnp.array([4.0, 5.0])
        m = jnp.ones(2, bool)
        out = fs.combine_functions([f1, f2], [m, m], "multiply")
        np.testing.assert_allclose(np.asarray(out), [8.0, 15.0])
        out = fs.combine_functions([f1, f2], [m, m], "avg")
        np.testing.assert_allclose(np.asarray(out), [3.0, 4.0])
        qs = jnp.array([1.0, 1.0])
        np.testing.assert_allclose(
            np.asarray(fs.apply_boost_mode(qs, f1, "sum")), [3.0, 4.0])

    def test_random_score_deterministic(self):
        a = np.asarray(fs.random_score(100, seed=42))
        b = np.asarray(fs.random_score(100, seed=42))
        c = np.asarray(fs.random_score(100, seed=43))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
        assert (a >= 0).all() and (a < 1).all()


class TestAggOps:
    def test_ord_counts(self):
        ords = jnp.array([[0, 1], [1, -1], [2, -1], [1, -1]], jnp.int32)
        mask = jnp.array([True, True, True, False])
        counts = aggs_ops.ord_value_counts(ords, mask, 4)
        np.testing.assert_array_equal(np.asarray(counts), [1, 2, 1, 0])

    def test_histogram(self):
        v = jnp.array([1.0, 5.0, 5.5, 9.0, 100.0])
        ex = jnp.ones(5, bool)
        mask = jnp.ones(5, bool)
        counts = aggs_ops.histogram_counts(v, ex, mask, base=0.0, interval=5.0,
                                           num_buckets=3)
        np.testing.assert_array_equal(np.asarray(counts), [1, 3, 0])

    def test_stats(self):
        v = jnp.array([1.0, 2.0, 3.0, 999.0])
        ex = jnp.array([True, True, True, False])
        mask = jnp.ones(4, bool)
        cnt, s, mn, mx = aggs_ops.stats_metrics(v, ex, mask)
        assert int(cnt) == 3 and float(s) == 6.0
        assert float(mn) == 1.0 and float(mx) == 3.0

    def test_range_counts(self):
        v = jnp.array([1.0, 5.0, 15.0])
        ex = jnp.ones(3, bool)
        counts = aggs_ops.range_counts(
            v, ex, jnp.ones(3, bool),
            jnp.array([-jnp.inf, 10.0]), jnp.array([10.0, jnp.inf]))
        np.testing.assert_array_equal(np.asarray(counts), [2, 1])
