"""Top-k selection and cross-shard/segment merge.

Lucene's TopScoreDocCollector heap (core/search/query/QueryPhase.java:196)
becomes :func:`select_top_k`; the coordinator's cross-shard merge
(SearchPhaseController.sortDocs via TopDocs.merge,
core/search/controller/SearchPhaseController.java:165-268) becomes a
concat + re-top-k that stays on device — inside shard_map it runs after an
all_gather over the shard mesh axis so the whole scatter-gather-reduce is
one XLA program over ICI.

Every top-k of a long row of scores is taken in two exact stages
(:func:`select_top_k`): rank the maxima of fixed-length blocks, then rank
the scores of the k chosen blocks, gathered in index order. Exactness:
every score above the k-th chosen block maximum lies in a chosen block,
and the k chosen maxima are k scores at or above it, so the k served
scores are, as a multiset, the whole row's top k — whatever order the
device gives equal maxima. Short rows (the merges over shards × k
candidates among them) keep one ``lax.top_k``.

Ties: the CPU backend's ``lax.top_k`` puts equal values at the lower index
first, and the two stages then give its indices too (a block left out has
k chosen blocks before it, each holding a score that is larger, or equal
and at a lower index). The TPU's is not stable: at k = 1000 equal scores
leave a segment out of document order (PERF.md section 7, fault 1; the
benchmark counts such pairs as ``ties_not_by_id``). Elasticsearch promises
no order among equal scores. Concatenating in shard/segment order before a
merge keeps TopDocs.merge's (shard index, position) order where the
backend is stable.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)


def _select_block(n: int, k: int) -> int:
    """Block length of the two-stage selection for ``k`` of ``n`` scores,
    or 0 where one ``lax.top_k`` over the row is as good: the power of two
    nearest ``sqrt(n / k)``, which balances the ``n / block`` maxima the
    first stage ranks against the ``k · block`` candidates of the second,
    and 32 at the least; for rows of 2^16 scores and more that it divides
    into 2k blocks or more. On a TPU v5e (PERF.md section 6) it gives the
    fastest of the blocks tried at k = 1000 (32), k = 100 (128) and
    k = 10 (256) over rows of 2^20."""
    if n < (1 << 16) or k < 1:
        return 0
    block = 1 << max(5, round(math.log2(n / k) / 2))
    return block if n % block == 0 and n // block >= 2 * k else 0


def _block_top_k(masked, k: int, block: int):
    """``lax.top_k(masked, k)`` without ranking the whole row: rank the
    block maxima, gather the k chosen blocks in index order and rank
    their ``k · block`` scores (the module docstring has why this is
    exact). On the TPU XLA takes a large k of a whole row by sorting all
    of it, in chunks of 8,192 scores, and then the chunks' top k.

    masked: [..., N] f32, N a multiple of ``block``, N // block >= k."""
    *lead, n = masked.shape
    blocks = masked.reshape(*lead, n // block, block)
    _, bid = jax.lax.top_k(blocks.max(axis=-1), k)
    bid = jnp.sort(bid, axis=-1)         # candidates in index order
    cand = jnp.take_along_axis(blocks, bid[..., None], axis=-2)
    ts, pos = jax.lax.top_k(cand.reshape(*lead, k * block), k)
    idx = jnp.take_along_axis(bid, pos // block, axis=-1) * block \
        + pos % block
    return ts, idx


def select_top_k(masked, k: int):
    """``lax.top_k(masked, k)`` over the last axis of ``masked`` ([N], as
    under ``vmap``, or [B, N]): in two stages wherever
    :func:`_select_block` gives the row's length and k a block, else in
    one. The form follows the shape alone."""
    block = _select_block(masked.shape[-1], k)
    if block:
        return _block_top_k(masked, k, block)
    return jax.lax.top_k(masked, k)


def two_stage(n: int, k: int) -> bool:
    """Whether :func:`top_k` selects ``k`` of ``n`` scores in two stages
    (the host counts a dispatch's rows by form)."""
    return _select_block(n, min(k, n)) > 0


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
    top_scores, idx = select_top_k(masked, kk)
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
