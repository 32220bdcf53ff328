"""``pow2_bucket`` — the one bucketing rule every batched/jitted layer
shares — and ``term_bucket``, its form for a BM25 match's term lists.

This module is only the helpers' home (eight import sites name it); the
batch scheduler is ``search/scheduler.py``.
"""

from __future__ import annotations


def pow2_bucket(n: int, cap: int | None = None) -> int:
    """Smallest power of two >= n (>= 1), clamped to `cap` when given.

    The one bucketing rule every batched/jitted layer shares — the
    scheduler's pad rows, jit_exec's vmap batch axis, and the mesh
    plane's k and batch buckets — so a jagged size distribution compiles
    O(log N) programs instead of one per distinct count."""
    b = 1 if n <= 1 else 1 << (n - 1).bit_length()
    if cap is not None and b > cap:
        return cap
    return b


def term_bucket(n: int) -> int:
    """The width a BM25 ``match`` pads its term lists to: a power of two
    up to 8, steps of 4 above (12, 16, 20, ...).

    A pad term is compared with every slot of a segment's columns like a
    real one, and at B = 64 those compares — not the column read — are
    what a dispatch costs: padded to 12, a request of 2 to 12 terms read
    70.3 queries/s on the chip where padded to 16 it read 60.5 (PERF.md
    section 6, PR 33). ``execute._res_MatchQuery`` pads by this rule and
    ``scheduler.query_shape`` keys its queues by it."""
    return pow2_bucket(n) if n <= 8 else -(-n // 4) * 4
