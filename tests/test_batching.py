"""``pow2_bucket`` — the one bucketing rule every batched/jitted layer
shares (the scheduler's pad rows, jit_exec's vmap batch axis, the mesh
plane's k and batch buckets): the smallest power of two that holds n,
clamped to a cap."""

from __future__ import annotations

import pytest

from elasticsearch_tpu.search.batching import pow2_bucket


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
