"""``pow2_bucket`` — the one bucketing rule every batched/jitted layer
shares.

This module is only the helper's home (eight import sites name it); the
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
