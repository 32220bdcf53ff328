"""What a batch of the phrase-boost query needs of one chip, from shapes
alone (``rooflines.py``'s rule: the ALGORITHM's count, never the
program's)."""

from __future__ import annotations


def phrase_batch_bytes(postings: int, positions: int, docs: int) -> int:
    """Least bytes one batch of phrase-boost queries has to read: the
    ``match`` reads every posting (term id int32 + count float32), the
    phrase every position (a term id, int32), and both every document's
    length (int32), once. Counted from the corpus, not from the layout:
    the engine pads each document's terms and positions to the widest
    (224 slots for a mean of 43 terms and 54 tokens). The queries and the
    [B, k] result are noise beside it."""
    return postings * (4 + 4) + positions * 4 + docs * 4


def phrase_batch_min_seconds(postings: int, positions: int, docs: int,
                             peaks: dict) -> float:
    """Compares, no matrix product: the bound is bytes over HBM
    bandwidth."""
    return phrase_batch_bytes(postings, positions, docs) \
        / peaks["hbm_bytes_per_s"]
