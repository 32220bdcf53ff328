"""Lexical (BM25) scoring over the forward impact index.

The TPU replacement for Lucene's TermScorer/BooleanScorer postings iteration
(the hot loop behind core/search/query/QueryPhase.java:314): instead of
walking per-term postings lists, every doc row's unique-term array is
compared against the query terms — a dense [N, U]×[T] compare/reduce that
maps straight onto the VPU with zero scatter/gather, exact BM25 scores
(BM25S-style eager scoring, PAPERS.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("bm25_score")
def bm25_match(uterms, utf, doc_len, qtids, qidf, qweight, k1, b, avgdl):
    """Score a (multi-term, OR-semantics) match query against one segment.

    Args:
      uterms:  [N, U] int32  unique term ids per doc (-1 pad)
      utf:     [N, U] f32    term frequency of each unique term
      doc_len: [N]    i32    field length per doc
      qtids:   [T]    int32  per-segment term ids of query terms (-1 = absent)
      qidf:    [T]    f32    idf per query term (0 for absent/padding)
      qweight: [T]    f32    per-term boost (match queries use 1.0)
      k1, b:   BM25 params (python floats — static under jit)
      avgdl:   f32 scalar    average field length (aggregated host-side)

    Returns:
      scores:  [N] f32  Σ_t idf_t · tfNorm(tf_t,d)
      nmatch:  [N] i32  number of distinct query terms matching each doc
               (drives minimum_should_match / operator=and)
    """
    n = uterms.shape[0]
    norm = k1 * (1.0 - b + b * doc_len.astype(jnp.float32) / avgdl)   # [N]
    tf_norm = utf * (k1 + 1.0) / (utf + norm[:, None])                # [N, U]
    scores = jnp.zeros(n, dtype=jnp.float32)
    nmatch = jnp.zeros(n, dtype=jnp.int32)
    T = qtids.shape[0]
    for t in range(T):  # T is static; unrolled and fused by XLA
        tid = qtids[t]
        hit = (uterms == tid) & (tid >= 0)                            # [N, U]
        any_hit = hit.any(axis=1)
        scores = scores + qidf[t] * qweight[t] * jnp.where(
            any_hit, (tf_norm * hit).sum(axis=1), 0.0)
        nmatch = nmatch + any_hit.astype(jnp.int32)
    return scores, nmatch


def term_filter(uterms, qtid):
    """Pure term-presence mask (filter context: no scoring).

    uterms: [N, U] int32; qtid: scalar int32 (-1 = absent → all False).
    """
    return ((uterms == qtid) & (qtid >= 0)).any(axis=1)


def classic_match(uterms, utf, doc_len, qtids, qidf, qweight):
    """Classic TF-IDF scoring (ref: Lucene TFIDFSimilarity / the 2.x
    "default" similarity): score_t = sqrt(tf) * idf^2 * (1/sqrt(dl)).
    `qidf` carries the CLASSIC idf (1 + ln(N/(df+1))); same interface as
    bm25_match."""
    n = uterms.shape[0]
    inv_norm = jnp.where(doc_len > 0,
                         1.0 / jnp.sqrt(doc_len.astype(jnp.float32)), 0.0)
    scores = jnp.zeros(n, dtype=jnp.float32)
    nmatch = jnp.zeros(n, dtype=jnp.int32)
    for t in range(qtids.shape[0]):
        tid = qtids[t]
        hit = (uterms == tid) & (tid >= 0)
        any_hit = hit.any(axis=1)
        tf = (utf * hit).sum(axis=1)
        scores = scores + qweight[t] * (qidf[t] * qidf[t]) * jnp.where(
            any_hit, jnp.sqrt(tf) * inv_norm, 0.0)
        nmatch = nmatch + any_hit.astype(jnp.int32)
    return scores, nmatch


def lm_dirichlet_match(uterms, utf, doc_len, qtids, qctf_frac, qweight,
                       mu):
    """LM Dirichlet smoothing (ref: Lucene LMDirichletSimilarity, the
    reference's lm_dirichlet similarity module): per matched term
    score_t = log(1 + tf/(mu * P(t|C))) + log(mu / (dl + mu)), floored at
    0 like Lucene. `qctf_frac` = collection term frequency / collection
    token count per query term."""
    n = uterms.shape[0]
    dl = doc_len.astype(jnp.float32)
    norm = jnp.log(mu / (dl + mu))                                    # [N]
    scores = jnp.zeros(n, dtype=jnp.float32)
    nmatch = jnp.zeros(n, dtype=jnp.int32)
    for t in range(qtids.shape[0]):
        tid = qtids[t]
        hit = (uterms == tid) & (tid >= 0)
        any_hit = hit.any(axis=1)
        tf = (utf * hit).sum(axis=1)
        term_score = jnp.log1p(tf / (mu * jnp.maximum(qctf_frac[t],
                                                      1e-12))) + norm
        scores = scores + qweight[t] * jnp.where(
            any_hit, jnp.maximum(term_score, 0.0), 0.0)
        nmatch = nmatch + any_hit.astype(jnp.int32)
    return scores, nmatch
