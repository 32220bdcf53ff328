"""Lexical (BM25) scoring over the forward impact index.

The TPU replacement for Lucene's TermScorer/BooleanScorer postings iteration
(the hot loop behind core/search/query/QueryPhase.java:314): instead of
walking per-term postings lists, every doc row's unique-term array is
compared against the query terms — a dense [N, U]×[T] compare that maps
straight onto the VPU with zero scatter/gather, exact BM25 scores
(BM25S-style eager scoring, PAPERS.md).

``bm25_match`` is ONE pass over a segment's columns: every slot is compared
with each of the T query terms and earns the sum of the weights (idf ·
boost) of the terms it holds, elementwise; then one reduction along U sums
``tf_norm · weight`` over the slots that earned any. XLA emits one fusion a
segment that reads ``uterms``, ``utf`` and ``doc_len`` once and writes [N];
no [N, U] temporary reaches HBM, and a further query term costs a compare,
a select and an add a slot, not another read of the columns. On a v5e the
fusion takes 2.47 ms for a [2^20, 224] segment (1.88 GB) at 2 to 12 terms
(PERF.md section 6, PR 27).

That is what lets the plan compiler pad: ``execute._res_MatchQuery`` fills
a match's ``qtids`` / ``qidf`` up to a term bucket (``batching
.term_bucket``: the batch's widest, or the query's own) with absent terms
— id -1, idf 0.0 — AFTER the real ones. A pad compares unequal to every slot (-2 is no term
id and no slot pad), adds 0.0 to ``w`` and 0 to ``cnt``, so op by op
scores and nmatch are the unpadded query's bit for bit (two compiled
programs of different widths may each order the sum along U their own
way: a score's last bit), and queries of unequal lengths share one
compiled program and one batch. Under ``vmap`` at
B = 64 the pass is no longer bound by the column read but by the
compares (64 x T a slot): what a pad costs there is in PERF.md section 6,
PR 33. ``classic_match`` and ``lm_dirichlet_match`` still make one pass
per term, so their lists are not padded.
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
      nmatch:  [N] i32  number of query terms matching each doc (a term
               the query repeats counts twice; drives
               minimum_should_match / operator=and)
    """
    norm = k1 * (1.0 - b + b * doc_len.astype(jnp.float32) / avgdl)   # [N]
    tf_norm = utf * (k1 + 1.0) / (utf + norm[:, None])                # [N, U]
    # an absent term (any negative id) becomes -2, which no slot holds:
    # a slot is a term id >= 0 or the -1 pad
    tids = jnp.where(qtids >= 0, qtids, -2)
    tweight = qidf * qweight
    w = jnp.zeros(uterms.shape, jnp.float32)     # weight a slot earns
    cnt = jnp.zeros(uterms.shape, jnp.int32)     # query terms hitting it
    for t in range(qtids.shape[0]):  # T is static: T compares a slot
        hit = uterms == tids[t]
        w = w + jnp.where(hit, tweight[t], 0.0)
        cnt = cnt + hit
    # a select, not tf_norm * w alone: a slot no term hit has w == 0 and
    # must add 0 even where tf_norm is 0/0 (a pad of a row whose norm is 0)
    scores = jnp.where(w != 0.0, tf_norm * w, 0.0).sum(axis=1)
    nmatch = cnt.sum(axis=1)
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
