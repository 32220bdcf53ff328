"""Device mesh construction.

The shard axis maps the reference's doc-partitioned shards
(OperationRouting.java:238) onto devices; the dp axis parallelizes the query
batch (the analog of concurrent search requests spread over replicas,
IndexShardRoutingTable copy rotation). Multi-host: `jax.devices()` already
spans hosts under jax.distributed, and the same named axes ride ICI within
a slice and DCN across slices — collectives need no code change.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def shard_map_compat(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the programs
    return per-shard lanes on purpose)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def valid_geometries(n: int) -> list:
    """Every dp×shard factorization of ``n`` devices, dp ascending —
    the menu :func:`make_mesh` offers in its rejection message and the
    geometry sweep the multi-chip benches/tests iterate."""
    return [(d, n // d) for d in range(1, n + 1) if n % d == 0]


def make_mesh(dp: int | None = None, shard: int | None = None,
              devices=None) -> Mesh:
    from elasticsearch_tpu.common import IllegalArgumentError
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shard is None and dp is None:
        dp = 1
        shard = n
    elif shard is None:
        if dp <= 0 or n % dp:
            raise IllegalArgumentError(
                f"mesh geometry dp={dp} does not divide {n} devices; "
                f"valid dp×shard factorizations: {valid_geometries(n)}")
        shard = n // dp
    elif dp is None:
        if shard <= 0 or n % shard:
            raise IllegalArgumentError(
                f"mesh geometry shard={shard} does not divide {n} "
                f"devices; valid dp×shard factorizations: "
                f"{valid_geometries(n)}")
        dp = n // shard
    if dp <= 0 or shard <= 0 or dp * shard != n:
        raise IllegalArgumentError(
            f"mesh geometry {dp}x{shard} != {n} devices; valid "
            f"dp×shard factorizations: {valid_geometries(n)}")
    arr = np.asarray(devices).reshape(dp, shard)
    return Mesh(arr, ("dp", "shard"))
