"""Masked many-query match reduction — the percolation kernel.

Percolation inverts the search workload: B registered queries score ONE
probe document (a one-doc segment padded to the row bucket). Each vmap
lane produces per-row (scores, mask); what the caller needs per QUERY is
just (matched?, score-of-the-probe-doc). Reducing that inside the fused
program keeps the device→host fetch at O(B) scalars instead of O(B·Np)
row arrays: one D2H per batch, O(B) bytes,
so the result of a whole percolate rides back as one small packed array
(the same single-fetch discipline as topk.pack_batch_result_body).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def match_reduce_body(scores, mask):
    """[..., Np] (scores f32, mask bool) → (matched bool, best f32) with
    the trailing row axis reduced: matched = any live row matches, best =
    the max matching score (0.0 when nothing matched — percolate scores
    are non-negative BM25-family sums, and the reference reports 0 for
    no-score modes). Runs under jit/vmap; the mask must already be
    live-masked so padding rows can never match."""
    matched = jnp.any(mask, axis=-1)
    best = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1)
    best = jnp.where(matched, best, jnp.float32(0.0))
    return matched, best.astype(jnp.float32)


def pack_match_result_body(matched, best):
    """[B] matched bool + [B] best f32 → ONE [B, 2] f32 array (column 0:
    0/1 match flag, column 1: score) so a percolate lane's whole result
    crosses the link in a single fetch."""
    return jnp.stack([matched.astype(jnp.float32), best], axis=-1)


def unpack_match_result(packed: np.ndarray, b: int):
    """Host side of pack_match_result_body: → (matched [b] bool,
    scores [b] f32), dropping the pow2 batch padding."""
    arr = np.asarray(packed)
    return arr[:b, 0] > 0.5, arr[:b, 1].astype(np.float32)
