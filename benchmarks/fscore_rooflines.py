"""What a batch of Rally geonames' function_score operations needs of one
chip, from shapes alone (``rooflines.py``'s rule: the ALGORITHM's count,
never the program's)."""

from __future__ import annotations

#: bytes a document of each doc-values field the functions read: a long
#: (population) and a geo_point's two doubles (location)
FIELD_BYTES = {"population": 8, "location": 16}

#: the fields each operation's function reads
OP_FIELDS = {
    "field_value_function_score": ("population",),
    "decay_geo_gauss_function_score": ("location",),
    "expression": ("population", "location"),
}


def fscore_batch_bytes(docs: int, op: str) -> int:
    """Least bytes one batch of ``op`` has to read: every document's
    value of each field its function reads, once (a batch holds one
    operation: the scheduler's key fingerprints the function list).
    Counted at the mapping's widths, not the program's float32 copies.
    The query constants and the [B, k] result are noise beside it."""
    return docs * sum(FIELD_BYTES[f] for f in OP_FIELDS[op])


def fscore_batch_min_seconds(docs: int, shares: dict, peaks: dict) -> float:
    """The least time of one batch, averaged over the operations in
    ``shares`` (operation → share): elementwise functions, no matrix
    product, so the bound is bytes over HBM bandwidth."""
    total = sum(float(w) for w in shares.values())
    return sum(float(w) / total * fscore_batch_bytes(docs, op)
               for op, w in shares.items()) / peaks["hbm_bytes_per_s"]
