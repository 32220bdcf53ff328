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

**The slot plan.** U is as wide as the column's longest row, and a slot
past the last term a row holds is the -1 pad: it earns nothing, yet the
pass compares it with every query term. A :class:`SlotPlan` bounds the
pass: the column's rows in ``SLOT_CHUNKS`` equal chunks, each scored only
up to the last slot any of its rows uses (rounded up to ``SLOT_ALIGN``).
Where rows of every length are mixed every chunk needs the whole width;
a segment whose rows are ordered by length (``Segment.from_packed_text``)
needs a third of it on MS MARCO's lengths. The plan is read from the
column (:func:`plan_slots`), so it is exact for any layout: a slot past a
chunk's width is -1 in every row of the chunk and would add 0.0 and a
count of 0. It is static — programs are keyed on it
(``jit_exec.layout_key``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

#: equal row chunks of a column's slot plan, and the slot multiple each
#: chunk's width is rounded up to — fixed, so that one corpus shape gives
#: one plan (and one compiled program) on every seed
SLOT_CHUNKS = 16
SLOT_ALIGN = 16
#: a column of fewer rows has no plan: its pass is short, and the many
#: small segments the per-document path refreshes into one row bucket
#: keep sharing one compiled program
SLOT_PLAN_MIN_ROWS = 1 << 16


def chunk_bounds(rows: int,
                 chunks: int = SLOT_CHUNKS) -> list[tuple[int, int]]:
    """(first row, end row) of each of ``chunks`` equal chunks of ``rows``."""
    edges = [rows * c // chunks for c in range(chunks + 1)]
    return list(zip(edges[:-1], edges[1:]))


@dataclass(frozen=True)
class SlotPlan:
    """The slots each row chunk of a ``rows``-row column is scored up to:
    chunk c (``chunk_bounds(rows)[c]``) to ``widths[c]``; past it every
    row of the chunk holds the -1 pad."""
    rows: int
    widths: tuple[int, ...]

    @staticmethod
    def of(rows: int, u: int, needed) -> "SlotPlan":
        """The plan whose chunk c needs its first ``needed[c]`` slots."""
        return SlotPlan(rows, tuple(
            min(-(-int(n) // SLOT_ALIGN) * SLOT_ALIGN, u) for n in needed))

    def merge(self, other: "SlotPlan") -> "SlotPlan":
        """A plan exact for both columns (of one row count): the wider
        width chunk by chunk."""
        if other.rows != self.rows or len(other.widths) != len(self.widths):
            raise ValueError("slot plans of different row chunks")
        return SlotPlan(self.rows, tuple(
            max(a, b) for a, b in zip(self.widths, other.widths)))

    def runs(self) -> list[tuple[int, int, int]]:
        """(first row, end row, width) of each run of neighbouring chunks
        of one width — one pass each."""
        out: list[list[int]] = []
        for (a, e), w in zip(chunk_bounds(self.rows, len(self.widths)),
                             self.widths):
            if out and out[-1][2] == w:
                out[-1][1] = e
            else:
                out.append([a, e, w])
        return [tuple(r) for r in out]

    def slots(self) -> int:
        """Row × slot pairs the plan scores (one compare each a term)."""
        return sum((e - a) * w for a, e, w in self.runs())


def plan_slots(uterms) -> SlotPlan | None:
    """The slot plan of a host ``[N, U]`` unique-term column: each chunk
    up to 1 + the last slot that holds a term id (>= 0) in any of its
    rows. None under ``SLOT_PLAN_MIN_ROWS`` rows."""
    n, u = uterms.shape
    if n < SLOT_PLAN_MIN_ROWS:
        return None
    needed = []
    for a, e in chunk_bounds(n):
        used = np.flatnonzero(uterms[a:e].max(axis=0) >= 0)
        needed.append(int(used[-1]) + 1 if used.size else 0)
    return SlotPlan.of(n, u, needed)


def _score_rows(uterms, utf, doc_len, tids, tweight, k1, b, avgdl):
    """The one pass over the rows and slots it is given."""
    norm = k1 * (1.0 - b + b * doc_len.astype(jnp.float32) / avgdl)   # [N]
    tf_norm = utf * (k1 + 1.0) / (utf + norm[:, None])                # [N, U]
    w = jnp.zeros(uterms.shape, jnp.float32)     # weight a slot earns
    cnt = jnp.zeros(uterms.shape, jnp.int32)     # query terms hitting it
    for t in range(tids.shape[0]):  # T is static: T compares a slot
        hit = uterms == tids[t]
        w = w + jnp.where(hit, tweight[t], 0.0)
        cnt = cnt + hit
    # a select, not tf_norm * w alone: a slot no term hit has w == 0 and
    # must add 0 even where tf_norm is 0/0 (a pad of a row whose norm is 0)
    scores = jnp.where(w != 0.0, tf_norm * w, 0.0).sum(axis=1)
    nmatch = cnt.sum(axis=1)
    return scores, nmatch


# a plan's passes, jitted: the segments of a reader share their runs'
# shapes, so a program traces each shape once (traced inline, seven runs
# a segment at 12 terms took a B = 64 program's tracing on a CPU host
# from 0.3 s to 1.5 s)
_score_run = jax.jit(_score_rows, static_argnames=("k1", "b"))


@jax.named_scope("bm25_score")
def bm25_match(uterms, utf, doc_len, qtids, qidf, qweight, k1, b, avgdl,
               slot_plan: SlotPlan | None = None):
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
      slot_plan: the column's :class:`SlotPlan` (static): every chunk of
               rows is scored up to its width and the chunks' results
               concatenated — the unbounded pass's up to the order of a
               float sum along U. None, or a plan of another row count
               (not this column's), scores the whole width.

    Returns:
      scores:  [N] f32  Σ_t idf_t · tfNorm(tf_t,d)
      nmatch:  [N] i32  number of query terms matching each doc (a term
               the query repeats counts twice; drives
               minimum_should_match / operator=and)
    """
    # an absent term (any negative id) becomes -2, which no slot holds:
    # a slot is a term id >= 0 or the -1 pad
    tids = jnp.where(qtids >= 0, qtids, -2)
    tweight = qidf * qweight
    n, u = uterms.shape
    if slot_plan is None or slot_plan.rows != n \
            or all(w == u for w in slot_plan.widths):
        return _score_rows(uterms, utf, doc_len, tids, tweight, k1, b, avgdl)
    scores, nmatch = [], []
    for a, e, w in slot_plan.runs():
        if w == 0:                    # every row of the run is all pad
            scores.append(jnp.zeros(e - a, jnp.float32))
            nmatch.append(jnp.zeros(e - a, jnp.int32))
            continue
        s, m = _score_run(uterms[a:e, :w], utf[a:e, :w], doc_len[a:e],
                          tids, tweight, k1, b, avgdl)
        scores.append(s)
        nmatch.append(m)
    return jnp.concatenate(scores), jnp.concatenate(nmatch)


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
