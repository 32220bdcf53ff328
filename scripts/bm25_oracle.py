"""Independent Lucene-BM25 oracle — written from the published formula.

This module deliberately shares NO code with elasticsearch_tpu's ops: it
consumes raw token-id sequences, builds its own statistics, and scores
in float64 straight from the BM25Similarity
javadoc (Lucene 5.x, the version the reference embeds):

    idf(t)   = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
    tfn(t,d) = tf * (k1 + 1) / (tf + k1 * (1 - b + b * |d| / avgdl))
    score    = sum over query terms of idf(t) * tfn(t, d)

with k1 = 1.2, b = 0.75 (BM25Similarity defaults) and avgdl = total
tokens / N. One deliberate deviation, shared with the engine under test:
document length is exact, not Lucene's lossy byte-encoded norm
(SmallFloat.byte315) — the oracle validates the BM25 math, not Lucene's
norm quantization.

Usage: `BM25Oracle(toks).topk(query_terms, k)` where `toks` is an
[N, L] int token-id matrix padded with -1.
"""

from __future__ import annotations

import numpy as np

K1 = 1.2
B = 0.75


class BM25Oracle:
    def __init__(self, docs_tokens):
        """docs_tokens: [N, L] int array, -1 padding."""
        toks = np.asarray(docs_tokens)
        if toks.ndim != 2:
            raise ValueError("docs_tokens must be a padded 2-D array")
        self.n_docs = toks.shape[0]
        valid = toks >= 0
        self.doc_len = valid.sum(axis=1).astype(np.float64)
        self.avgdl = self.doc_len.sum() / max(self.n_docs, 1)
        # per-term postings — a different aggregation path from any CSR
        # the engine uses: one global stable sort by term (doc order is
        # preserved within a term because the flat layout is doc-major),
        # then one vectorized run-length encoding over (term, doc) pairs.
        # int32 throughout and no np.repeat: at 2M docs × L=224 the naive
        # int64 repeat+per-term-unique build needs >10 GB and minutes.
        self._postings: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._df: dict[int, int] = {}
        L = toks.shape[1]
        flat_idx = np.flatnonzero(valid.ravel())
        flat_docs = (flat_idx // L).astype(np.int32)
        flat_terms = toks.ravel()[flat_idx].astype(np.int32)
        del flat_idx
        order = np.argsort(flat_terms, kind="stable")
        ft, fd = flat_terms[order], flat_docs[order]
        del flat_terms, flat_docs, order
        if len(ft) == 0:
            return
        # collapse equal (term, doc) runs → tf counts
        change = np.empty(len(ft), bool)
        change[0] = True
        np.not_equal(ft[1:], ft[:-1], out=change[1:])
        change[1:] |= fd[1:] != fd[:-1]
        run_starts = np.flatnonzero(change)
        tf = np.diff(np.concatenate([run_starts, [len(ft)]])).astype(
            np.float64)
        u_terms, u_docs = ft[run_starts], fd[run_starts]
        # slice per distinct term
        tchange = np.flatnonzero(u_terms[1:] != u_terms[:-1]) + 1
        tstarts = np.concatenate([[0], tchange])
        tends = np.concatenate([tchange, [len(u_terms)]])
        for s, e in zip(tstarts, tends):
            self._postings[int(u_terms[s])] = (u_docs[s:e], tf[s:e])
            self._df[int(u_terms[s])] = e - s

    def idf(self, term: int) -> float:
        df = self._df.get(int(term), 0)
        return float(np.log1p((self.n_docs - df + 0.5) / (df + 0.5)))

    def score_query(self, terms) -> np.ndarray:
        """→ float64 scores for every document (0 where no term hits)."""
        scores = np.zeros(self.n_docs, np.float64)
        norm_denom = K1 * (1.0 - B + B * self.doc_len / self.avgdl)
        for t in terms:
            post = self._postings.get(int(t))
            if post is None:
                continue
            docs, tf = post
            idf = self.idf(t)
            scores[docs] += idf * tf * (K1 + 1.0) / (tf + norm_denom[docs])
        return scores

    def topk(self, terms, k: int,
             scores: np.ndarray | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
        """→ (doc_ids, scores), score desc then doc id asc (Lucene's
        TopDocs tie order). Pass a precomputed score_query vector to
        avoid rescoring."""
        if scores is None:
            scores = self.score_query(terms)
        k = min(k, self.n_docs)
        part = np.argpartition(-scores, k - 1)[:k]
        order = np.lexsort((part, -scores[part]))
        ids = part[order]
        return ids, scores[ids]


def recall_with_tie_tolerance(oracle_ids, all_scores, engine_ids,
                              k: int, tol: float = 1e-4) -> float:
    """Recall@k that forgives boundary ties: an engine hit missing from
    the oracle's top-k still counts when its full-corpus oracle score
    matches the oracle's k-th score within tolerance (equal-score docs
    are interchangeable at the cutoff).

    `all_scores` is the oracle's full score vector (score_query output)
    so ties OUTSIDE the oracle's own top-k are recognized too."""
    oracle_set = set(int(i) for i in oracle_ids[:k])
    if not oracle_set:
        return 1.0
    kth = float(all_scores[oracle_ids[min(k, len(oracle_ids)) - 1]])
    hit = 0
    compared = list(engine_ids[:k])
    for d in compared:
        d = int(d)
        if d in oracle_set or abs(float(all_scores[d]) - kth) <= \
                tol * max(abs(kth), 1.0):
            hit += 1
    return hit / max(len(compared), 1)
