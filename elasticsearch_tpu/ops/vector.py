"""Dense-vector scoring — brute-force exact kNN on the MXU.

Precision, stated: the default scores float32 vectors with a float32
product, ``precision=jax.lax.Precision.HIGHEST`` (:data:`EXACT`). Without
it XLA's TPU default rounds both operands of a float32 matmul to bfloat16
(one MXU pass, 8 bits of mantissa): scores move by about 1e-3 and near-tie
cosine rankings reorder. HIGHEST costs six bfloat16 passes; the product
of a [B, D] query block with an [N, D] float32 column stays bound by the
read of the column (PERF.md section 4, ``dense768-cosine-knn``), so the
exact product is the default and what ``index.knn.quantization: f32``
promises. ``use_bf16=True`` is the explicit trade: both operands rounded
to bfloat16, one pass, float32 accumulation — about 3 decimal digits of
score for a sixth of the MXU time. No serving lane sets it.

The reference era has no dense_vector type; its equivalent is binary doc
values + script cosine (BASELINE.md config 4,
core/common/lucene/search/function/ScriptScoreFunction.java). Here vectors
are first-class [N, D] matrices: batched cosine/dot scoring is one matmul
over the whole column.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from elasticsearch_tpu.ops import topk as topk_ops

#: the matmul precision of every float32 score in this module and in the
#: knn lane's programs (jit_exec.run_knn_hybrid_batch / _mesh)
EXACT = jax.lax.Precision.HIGHEST


def l2_normalize(x, axis=-1, eps=1e-12):
    return x / jnp.sqrt((x * x).sum(axis=axis, keepdims=True) + eps)


def cosine_scores(vecs, exists, q, use_bf16: bool = False):
    """Cosine similarity of one query vector against all docs.

    vecs: [N, D] f32 (pre-normalized at reader build); q: [D] f32.
    Returns scores[N] f32 in [-1, 1]; non-existent rows score 0.
    """
    qn = l2_normalize(q)
    if use_bf16:
        s = (vecs.astype(jnp.bfloat16) @ qn.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        s = jnp.matmul(vecs, qn, precision=EXACT)
    return jnp.where(exists, s, 0.0)


def unit_scores_batch(vecs, exists, qn):
    """The knn lane's dense product: ``qn`` [Q, D] f32 rows ALREADY unit
    length against ``vecs`` [N, D] f32 unit rows → cosines [Q, N] f32 at
    :data:`EXACT`; non-existent rows score 0."""
    s = jnp.matmul(qn, vecs.T, precision=EXACT)
    return jnp.where(exists[None, :], s, 0.0)


def cosine_scores_batch(vecs, exists, qs, use_bf16: bool = False):
    """qs: [Q, D] → scores [Q, N]. One MXU matmul for the whole batch."""
    qn = l2_normalize(qs, axis=-1)
    if use_bf16:
        s = (qn.astype(jnp.bfloat16) @ vecs.astype(jnp.bfloat16).T).astype(jnp.float32)
        return jnp.where(exists[None, :], s, 0.0)
    return unit_scores_batch(vecs, exists, qn)


def dot_scores(vecs, exists, q):
    return jnp.where(exists, jnp.matmul(vecs, q, precision=EXACT), 0.0)


def cosine_scores_int8_batch(qvecs, scale, offset, exists, qs):
    """Batched cosine over an int8-quantized column.

    qvecs: [N, D] int8 with ``v ≈ q·scale + offset`` per component
    (per-segment scale/offset snapshot); qs: [Q, D] f32 row-normalized.
    The dequantized dot expands to ``scale·(qint·qn) + offset·Σqn`` —
    one matmul on the dense integer column plus a rank-1 correction, so
    the column stays int8-dense in HBM (~4× the f32 corpus capacity).
    → scores [Q, N] f32; non-existent rows score 0.
    """
    qn = l2_normalize(qs, axis=-1)
    s = jnp.matmul(qn, qvecs.astype(jnp.float32).T, precision=EXACT) * scale \
        + offset * qn.sum(axis=-1, keepdims=True)
    return jnp.where(exists[None, :], s, 0.0)


def filtered_topk_batch(scores, masks, k: int, doc_base: int = 0):
    """Batched filtered-kNN candidate selection: per-query top-k over
    pre-computed score rows with per-query eligibility masks (exists ∧
    live ∧ knn-filter) — the candidate-oversample step of the knn lane
    (``num_candidates`` rows per segment survive to the merge). The whole
    batch is one selection, :func:`ops.topk.select_top_k` (two stages
    over long rows; ties as ``ops/topk.py`` states).

    scores: [B, N] f32; masks: [B, N] bool → ([B, k] f32, [B, k] i32).
    """
    neg_inf = jnp.float32(-jnp.inf)
    masked = jnp.where(masks, scores, neg_inf)
    kk = min(k, masked.shape[-1])
    ts, idx = topk_ops.select_top_k(masked, kk)
    valid = ts > neg_inf
    td = jnp.where(valid, idx.astype(jnp.int32) + doc_base, -1)
    ts = jnp.where(valid, ts, neg_inf)
    if kk < k:    # corpus smaller than k: pad to the static width
        ts = jnp.pad(ts, ((0, 0), (0, k - kk)), constant_values=neg_inf)
        td = jnp.pad(td, ((0, 0), (0, k - kk)), constant_values=-1)
    return ts, td


def script_cosine_scores(vecs, exists, q):
    """`script_score: cosineSimilarity(params.query_vector, 'field') + 1.0`
    — the ES idiom for non-negative cosine ranking (BASELINE config 4)."""
    return jnp.where(exists, cosine_scores(vecs, exists, q) + 1.0, 0.0)
