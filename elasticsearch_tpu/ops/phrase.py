"""Phrase matching over the position-indexed token matrix.

Lucene's ExactPhraseScorer walks position postings of every phrase term in
lockstep (core/index/query/MatchQueryParser.java → Lucene PhraseQuery). Here
the token matrix is position-indexed (``tokens[doc, p]`` = term id at
position ``p``), so an exact-phrase occurrence at start position ``p`` is

    AND_k  tokens[:, p + delta_k] == qtid_k

— a stack of shifted dense compares, vectorized over all docs and all start
positions at once. Query-side position gaps (stopwords removed by the
analyzer) are honored via ``deltas``, matching ES match_phrase semantics.

XLA keeps each shifted copy of the matrix it compares (T copies of 940 MB
a segment at [2^20, 224]), so on the TPU an exact phrase — and a batch of
them, padded to one term bucket — is a Pallas kernel
(:func:`exact_phrase_freq`) that reads the matrix once for the whole
batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the term id that pads an exact phrase's term list to its term bucket:
#: it matches every position, past the document's end too, so a padded
#: phrase counts exactly the occurrences of the unpadded one
PAD_TID = -2


def _shift_left(tokens, d: int, fill: int = -(2**31) + 1):
    """tokens[..., p] → tokens[..., p+d] with out-of-range = fill (matches
    no tid)."""
    if d == 0:
        return tokens
    return jnp.pad(tokens[..., d:], ((0, 0),) * (tokens.ndim - 1)
                   + ((0, d),), constant_values=fill)


def phrase_freq(tokens, qtids: list, deltas: list[int]):
    """Phrase frequency per doc.

    Args:
      tokens: [N, L] int32 position-indexed term ids (-1 holes)
      qtids:  list of T scalar int32 per-segment term ids (device scalars;
              -1 = term absent from segment → freq 0 everywhere)
      deltas: list of T static python ints — query token position offsets
              from the first query token (e.g. [0, 1] for adjacent terms,
              [0, 2] when a stopword was removed between them)

    Returns:
      freq: [N] f32 — number of phrase occurrences per doc.
    """
    return exact_phrase_freq(
        tokens, jnp.stack([jnp.asarray(t, jnp.int32) for t in qtids]),
        tuple(int(d) for d in deltas))


def freq_score(freq, doc_len, sum_idf, k1, b, avgdl):
    """BM25 over a positional frequency (tf = freq, idf = Σ idf of the
    participating terms — Lucene PhraseWeight/SpanWeight combined stats).

    Returns (scores[N] f32, mask[N] bool)."""
    norm = k1 * (1.0 - b + b * doc_len.astype(jnp.float32) / avgdl)
    tf_norm = freq * (k1 + 1.0) / (freq + norm)
    mask = freq > 0
    return jnp.where(mask, sum_idf * tf_norm, 0.0), mask


def phrase_score(tokens, doc_len, qtids: list, deltas: list[int],
                 sum_idf, k1, b, avgdl):
    """BM25 phrase scoring: tf = phrase frequency, idf = Σ idf(term)
    (Lucene PhraseWeight builds its stats from all phrase terms).

    Returns (scores[N] f32, mask[N] bool)."""
    freq = phrase_freq(tokens, qtids, deltas)
    return freq_score(freq, doc_len, sum_idf, k1, b, avgdl)


#: a term the kernel must never match (absent from the segment): no token,
#: hole (-1) or fill holds it
_NEVER_TID = -3
_FILL = -(2**31) + 1
#: documents a kernel step reads: [L, 512] int32 is 458 KB of VMEM
_KERNEL_ROWS = 512


def _phrase_counts_xla(tokens, qtids, deltas: tuple):
    """[N, L] tokens (or [B, N, L], one matrix a phrase), [B, T] term ids
    at the static offsets ``deltas`` → [B, N] int32 occurrences: shifted
    compares. XLA keeps every shifted copy of the matrix (T of them), so
    this form is for small matrices and backends without the kernel."""
    window = None
    for k, d in enumerate(deltas):
        tid = qtids[:, k][:, None, None]
        hit = ((_shift_left(tokens, d) == tid) & (tid >= 0)) \
            | (tid == PAD_TID)
        window = hit if window is None else (window & hit)
    return window.sum(axis=-1, dtype=jnp.int32)


def _phrase_kernel(q_ref, tok_ref, out_ref, buf_ref, *, deltas: tuple):
    """One block of documents, positions on sublanes: ``tok_ref`` [L, R],
    ``q_ref`` [B, T] in SMEM, ``out_ref`` [B, R]. The block is copied
    into ``buf_ref`` above rows of fill, so the term at offset d is one
    load at sublane offset d; each query ANDs its T compares and sums
    along the positions."""
    n_pos = tok_ref.shape[0]
    n_queries = q_ref.shape[0]
    buf_ref[0:n_pos, :] = tok_ref[...]
    buf_ref[n_pos:, :] = jnp.full(
        (buf_ref.shape[0] - n_pos, buf_ref.shape[1]), _FILL, jnp.int32)

    def query(b, carry):
        window = None
        for k, d in enumerate(deltas):
            tid = q_ref[b, k]
            hit = (buf_ref[d:d + n_pos, :] == tid).astype(jnp.int32) \
                | (tid == PAD_TID).astype(jnp.int32)
            window = hit if window is None else (window & hit)
        out_ref[pl.ds(b, 1), :] = jnp.sum(window, axis=0, keepdims=True)
        return carry
    jax.lax.fori_loop(0, n_queries, query, 0)


def _phrase_counts_tpu(tokens, qtids, deltas: tuple):
    """The kernel form of :func:`_phrase_counts_xla`: the matrix is read
    once a batch, whatever B and T. On the chip an [N, 224] int32 array
    is kept with N minor, so ``tokens.T`` is free."""
    n_docs, width = tokens.shape
    if n_docs % 128:
        return _phrase_counts_xla(tokens, qtids, deltas)
    n_queries = qtids.shape[0]
    n_pos = -(-width // 8) * 8
    spare = max(-(-max(deltas) // 8) * 8, 8)
    by_pos = jnp.pad(tokens.T, ((0, n_pos - width), (0, 0)),
                     constant_values=_FILL)
    q = jnp.where(qtids >= 0, qtids,
                  jnp.where(qtids == PAD_TID, PAD_TID, _NEVER_TID))
    rows = min(_KERNEL_ROWS, n_docs)
    return pl.pallas_call(
        functools.partial(_phrase_kernel, deltas=deltas),
        out_shape=jax.ShapeDtypeStruct((n_queries, n_docs), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_docs // rows,),
            in_specs=[pl.BlockSpec((n_pos, rows), lambda i, q: (0, i))],
            out_specs=pl.BlockSpec((n_queries, rows), lambda i, q: (0, i)),
            scratch_shapes=[pltpu.VMEM((n_pos + spare, rows), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(q.astype(jnp.int32), by_pos)


def _phrase_counts(tokens, qtids, deltas: tuple):
    return jax.lax.platform_dependent(
        tokens, qtids,
        tpu=functools.partial(_phrase_counts_tpu, deltas=deltas),
        default=functools.partial(_phrase_counts_xla, deltas=deltas))


@functools.lru_cache(maxsize=None)
def _counts_at(deltas: tuple):
    """The phrase count at static offsets ``deltas``, whose batching rule
    makes a batch of phrases over one matrix ONE pass (the reader batch
    vmaps its queries over the segment's columns)."""
    @jax.custom_batching.custom_vmap
    def counts(tokens, qtids):
        return _phrase_counts(tokens, qtids[None], deltas)[0]

    @counts.def_vmap
    def counts_batched(axis_size, in_batched, tokens, qtids):
        tokens_batched, qtids_batched = in_batched
        if not qtids_batched:
            qtids = jnp.broadcast_to(qtids, (axis_size,) + qtids.shape)
        if tokens_batched:
            return _phrase_counts_xla(tokens, qtids, deltas), True
        return _phrase_counts(tokens, qtids, deltas), True
    return counts


def exact_phrase_freq(tokens, qtids, deltas: tuple | None = None):
    """Exact-phrase frequency per doc.

    Args:
      tokens: [N, L] int32 position-indexed term ids (-1 holes)
      qtids:  [T] int32 term ids: -1 = term absent from the segment (freq
              0 everywhere), :data:`PAD_TID` = a pad that matches every
              position
      deltas: T static offsets from the first term; consecutive (0..T-1)
              when None

    Returns:
      freq: [N] f32. Under ``vmap`` over ``qtids`` the B phrases are ONE
      kernel that reads the matrix once and writes [B, N] (on the TPU);
      no [B, N, L] array and no shifted copy of the matrix is kept.
    """
    if deltas is None:
        deltas = tuple(range(qtids.shape[0]))
    with jax.named_scope("phrase_match"):
        return _counts_at(deltas)(tokens, qtids).astype(jnp.float32)


_INF_SLOP = jnp.float32(1e9)


def _sloppy_displacement(tokens, qtids: list, deltas: list[int], slop: int):
    """→ [N, L] total displacement of the best in-order match anchored at
    each start position (> slop ⇒ no match there). Matches are ANCHORED at
    the first term's actual position (its shift pinned to 0) so each
    occurrence is counted exactly once; every later term takes its NEAREST
    admissible position (min shift in [0, slop]).

    Two departures from Lucene's SloppyPhraseScorer, so a sloppy phrase
    here is not Lucene's and no configuration that states Lucene's
    semantics may use one:

    * the displacement is the SUM of every term's shift, where Lucene's
      matchLength is max − min of (position − query offset) over the
      terms: "a x b c" against "a b c" reads 1 + 1 = 2 here and 1 in
      Lucene, so a phrase of more than two terms is weighed (and, near
      the slop, matched) otherwise;
    * matches are IN ORDER only: a term found before its predecessor
      (Lucene reaches "b a" from "a b" at slop 2) is not found.

    And a phrase repeating one term can map two query terms onto one
    token position."""
    total = None
    for i, (tid, d) in enumerate(zip(qtids, deltas)):
        shifts = (0,) if i == 0 else range(slop + 1)
        best = None
        for s in shifts:
            h = (_shift_left(tokens, d + s) == tid) & (tid >= 0)
            cand = jnp.where(h, jnp.float32(s), _INF_SLOP)
            best = cand if best is None else jnp.minimum(best, cand)
        total = best if total is None else total + best
    return total


def sloppy_phrase_freq(tokens, qtids: list, deltas: list[int], slop: int):
    """Proximity-weighted sloppy phrase frequency — Lucene
    SloppyPhraseScorer semantics for in-order matches: each match at total
    displacement d contributes ``1 / (d + 1)`` to the phrase frequency
    (SloppyPhraseScorer.sloppyFreq: 1/(1+matchLength)).

    Returns freq[N] f32. See :func:`_sloppy_displacement` for anchoring
    semantics and documented deviations.
    """
    total = _sloppy_displacement(tokens, qtids, deltas, slop)
    valid = total <= slop
    return jnp.where(valid, 1.0 / (1.0 + total), 0.0).sum(axis=1)


def sloppy_phrase_count(tokens, qtids: list, deltas: list[int], slop: int):
    """Number of in-order matches within the slop budget (each anchored
    occurrence counts 1, NOT the 1/(1+d) sloppyFreq weight) — span_near's
    frequency semantics (NearSpansOrdered enumerates spans; SpanScorer
    then weighs each by sloppyFreq, which this implementation simplifies
    to plain counting, documented in the span_near resolver).

    Returns freq[N] f32.
    """
    total = _sloppy_displacement(tokens, qtids, deltas, slop)
    return (total <= slop).sum(axis=1).astype(jnp.float32)


def sloppy_phrase_score(tokens, doc_len, qtids: list, deltas: list[int],
                        slop: int, idfs, k1, b, avgdl):
    """BM25 over the proximity-weighted sloppy frequency (tf = sloppyFreq,
    idf = Σ idf of the phrase terms, like PhraseWeight's combined stats).

    Returns (scores[N] f32, mask[N] bool)."""
    freq = sloppy_phrase_freq(tokens, qtids, deltas, slop)
    return freq_score(freq, doc_len, jnp.asarray(idfs).sum(), k1, b, avgdl)


def span_near_freq_unordered(tokens, qtids: list, slop: int):
    """Unordered span-near frequency (Lucene NearSpansUnordered analog): a
    span starts at position ``p`` when EVERY clause term occurs somewhere
    in the window ``tokens[p : p+T+slop]``; runs of overlapping starts
    collapse to their first position so each distinct region counts once.
    Deviations from Lucene, documented: per-span width does not feed a
    sloppyFreq weighting (plain freq scoring), and two clause terms may
    map onto one token occurrence when the phrase repeats a term.

    Returns freq[N] f32.
    """
    window = len(qtids) + slop
    match = None
    for tid in qtids:
        present = None
        for d in range(window):
            h = (_shift_left(tokens, d) == tid) & (tid >= 0)
            present = h if present is None else (present | h)
        match = present if match is None else (match & present)
    prev = jnp.pad(match[:, :-1], ((0, 0), (1, 0)), constant_values=False)
    return (match & ~prev).sum(axis=1).astype(jnp.float32)
