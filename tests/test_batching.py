"""``pow2_bucket`` — the one bucketing rule every batched/jitted layer
shares (the scheduler's pad rows, jit_exec's vmap batch axis, the mesh
plane's k and batch buckets): the smallest power of two that holds n,
clamped to a cap — and ``term_bucket``, the width a BM25 match pads its
term lists to."""

from __future__ import annotations

import pytest

from elasticsearch_tpu.search.batching import pow2_bucket, term_bucket


@pytest.mark.parametrize("n, cap, want", [
    (0, None, 1),           # nothing still takes a row
    (1, None, 1),
    (2, None, 2),           # a power of two is its own bucket
    (3, None, 4),
    (64, None, 64),
    (65, None, 128),
    (5, 4, 4),              # a cap below the bucket clamps it
    (5, 32, 8),             # a cap above it changes nothing
])
def test_pow2_bucket(n, cap, want):
    assert pow2_bucket(n, cap) == want


@pytest.mark.parametrize("n, want", [
    (0, 1), (1, 1), (2, 2), (3, 4), (5, 8), (8, 8),     # as pow2_bucket
    (9, 12), (12, 12), (13, 16), (16, 16), (17, 20),    # steps of 4 above 8
])
def test_term_bucket(n, want):
    assert term_bucket(n) == want


def test_the_scheduler_queues_by_the_plans_term_bucket():
    """Queries that pad to one width share a compiled plan, so they share
    a queue: the fingerprint buckets a match's text as the plan does."""
    from elasticsearch_tpu.search.query_dsl import MatchQuery
    from elasticsearch_tpu.search.scheduler import query_shape

    def shape(n):
        return query_shape(MatchQuery(field="t", text=" ".join(["w"] * n)))
    assert shape(3) == shape(4) != shape(5)
    assert shape(9) == shape(12) != shape(13)
    assert shape(13) == shape(16)
