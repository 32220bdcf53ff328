"""Top-k selection and cross-shard/segment merge.

Lucene's TopScoreDocCollector heap (core/search/query/QueryPhase.java:196)
becomes ``lax.top_k``; the coordinator's cross-shard merge
(SearchPhaseController.sortDocs via TopDocs.merge,
core/search/controller/SearchPhaseController.java:165-268) becomes a
concat + re-top-k that stays on device — inside shard_map it runs after an
all_gather over the shard mesh axis so the whole scatter-gather-reduce is
one XLA program over ICI.

Tie-breaking matches Lucene exactly because ``lax.top_k`` is stable (equal
values → lower index first): within a segment, index order == doc id order;
across shards, concatenating in shard order before re-top-k reproduces
TopDocs.merge's (shard index, position) tie-break. (Measured otherwise on
the TPU v5e at k = 1000: PERF.md section 7, fault 1 — the benchmark counts
such pairs as ``ties_not_by_id``, for the BM25 and the knn cells alike.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)


@jax.named_scope("topk_select")
def top_k(scores, mask, k: int, doc_base: int = 0):
    """Per-segment/shard top-k.

    Args:
      scores: [N] f32; mask: [N] bool (padding/deleted/filtered-out rows False)
      k: static int; doc_base: global doc id of row 0 (segment/shard offset)

    Returns (top_scores[k] f32, top_docs[k] int32 global ids); empty slots
    have score -inf and doc id -1.
    """
    masked = jnp.where(mask, scores, NEG_INF)
    kk = min(k, masked.shape[-1])
    top_scores, idx = jax.lax.top_k(masked, kk)
    valid = top_scores > NEG_INF
    top_docs = jnp.where(valid, idx.astype(jnp.int32) + doc_base, -1)
    top_scores = jnp.where(valid, top_scores, NEG_INF)
    if kk < k:   # corpus smaller than k: pad to the requested static width
        top_scores = jnp.pad(top_scores, (0, k - kk), constant_values=NEG_INF)
        top_docs = jnp.pad(top_docs, (0, k - kk), constant_values=-1)
    return top_scores, top_docs


def merge_top_k(scores_list, docs_list, k: int):
    """Merge several (scores[k_i], docs[k_i]) rankings → global top-k.

    Inputs must be concatenated in shard/segment order; stability of top_k
    then reproduces the reference's merge tie-breaking.
    """
    scores = jnp.concatenate(scores_list)
    docs = jnp.concatenate(docs_list)
    masked = jnp.where(docs >= 0, scores, NEG_INF)
    top_scores, idx = jax.lax.top_k(masked, min(k, scores.shape[0]))
    valid = top_scores > NEG_INF
    return (jnp.where(valid, top_scores, NEG_INF),
            jnp.where(valid, docs[idx], -1))


def merge_top_k_batch(scores_list, docs_list, k: int, bases):
    """Batched cross-segment merge: per-segment ``([B, k_s], [B, k_s])``
    rankings (segment-LOCAL doc ids) → global ``([B, k], [B, k])``.

    The batch-axis companion of :func:`merge_top_k` for the vmapped query
    path (jit_exec.run_reader_batch): `bases` maps each segment's local
    ids to reader-global ids inside the program, and concatenation in
    segment order + stable top_k keeps the reference's merge tie-break
    (TopDocs.merge, core/search/controller/SearchPhaseController.java:165).
    """
    return _merge_top_k_batch(tuple(scores_list), tuple(docs_list), k,
                              tuple(int(b) for b in bases))


@jax.named_scope("topk_merge")
def merge_top_k_batch_body(scores_list, docs_list, k: int, bases):
    """Traceable body shared by the standalone jitted entry below and the
    fused reader program (jit_exec.run_reader_batch) — ONE copy of the
    tie-break / -inf-pad contract."""
    docs = jnp.concatenate(
        [jnp.where(d >= 0, d + b, -1) for d, b in zip(docs_list, bases)],
        axis=1)
    scores = jnp.concatenate(scores_list, axis=1)
    masked = jnp.where(docs >= 0, scores, NEG_INF)
    kk = min(k, masked.shape[1])
    top_scores, idx = jax.lax.top_k(masked, kk)
    valid = top_scores > NEG_INF
    top_docs = jnp.where(valid, jnp.take_along_axis(docs, idx, axis=1), -1)
    top_scores = jnp.where(valid, top_scores, NEG_INF)
    if kk < k:
        top_scores = jnp.pad(top_scores, ((0, 0), (0, k - kk)),
                             constant_values=NEG_INF)
        top_docs = jnp.pad(top_docs, ((0, 0), (0, k - kk)),
                           constant_values=-1)
    return top_scores, top_docs


_merge_top_k_batch = partial(jax.jit, static_argnames=("k", "bases"))(
    merge_top_k_batch_body)


def pack_batch_result(top_scores, top_docs, counts):
    """Pack a batched merge result into ONE f32 array ``[B, 2k+1]``
    (scores ‖ doc-ids ‖ count) so the host needs a single device→host
    fetch per batch — each D2H is a blocking sync with a fixed per-call
    cost, and the payload is tiny. Doc ids and counts are exact in f32
    below 2**24; callers must use the unpacked path beyond that."""
    return _pack_batch_result(top_scores, top_docs, counts)


@jax.named_scope("pack_result")
def pack_batch_result_body(top_scores, top_docs, counts):
    """Traceable body (shared with the fused reader program)."""
    return jnp.concatenate(
        [top_scores, top_docs.astype(jnp.float32),
         counts.astype(jnp.float32)[:, None]], axis=1)


_pack_batch_result = jax.jit(pack_batch_result_body)


def unpack_batch_result(packed: "np.ndarray", k: int):
    """Host-side inverse of :func:`pack_batch_result` →
    (scores [B,k] f32, docs [B,k] i32, counts [B] i64)."""
    import numpy as np
    scores = packed[:, :k]
    docs = packed[:, k:2 * k].astype(np.int32)
    counts = packed[:, 2 * k].astype(np.int64)
    return scores, docs, counts


def count_matches(mask):
    """Total hits (the search response's hits.total)."""
    return mask.sum(dtype=jnp.int32)


def max_score(scores, mask):
    return jnp.max(jnp.where(mask, scores, NEG_INF))
