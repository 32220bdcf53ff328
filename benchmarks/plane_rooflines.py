"""What a sharded BM25 request needs of several chips, from shapes alone
(``rooflines.py``'s rule: the ALGORITHM's count, never the program's)."""

from __future__ import annotations


def plane_batch_bytes(postings: int, docs: int) -> int:
    """Least bytes one BM25 batch over the whole sharded index has to
    read, all shards together: every posting (term id int32 + count
    float32) and every document's length (int32), once — as
    ``rooflines.bm25_batch_bytes`` counts one shard's. The candidates
    that cross the shard axis (shards x batch x k x 8 bytes) and the
    queries are noise beside it and are not counted."""
    return postings * (4 + 4) + docs * 4


def plane_batch_min_seconds(postings: int, docs: int, chips: int,
                            peaks: dict) -> float:
    """The shards are read side by side, one a chip: the bound is those
    bytes over ``chips`` times one chip's HBM bandwidth."""
    return plane_batch_bytes(postings, docs) / (
        chips * peaks["hbm_bytes_per_s"])
