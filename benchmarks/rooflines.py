"""What the ALGORITHM needs, from shapes alone — never from what the
current program happens to do — so that a PR which replaces a kernel is
judged against the same count. And the table of peaks."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks_for(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind [{device_kind}] in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


def bm25_batch_bytes(postings: int, docs: int) -> int:
    """Least bytes one BM25 batch has to read: every posting of the
    resident documents — a document's distinct terms, each with its term
    id (int32) and its count (float32) — and every document's length
    (int32), once. Counted from the corpus, not from the layout: the
    engine's forward layout pads each document to the widest (224 slots
    for a mean of 43 terms) and reads five times this. The queries and
    the [B, k] result are noise beside it."""
    return postings * (4 + 4) + docs * 4


def bm25_batch_min_seconds(postings: int, docs: int, peaks: dict) -> float:
    """BM25 over a forward index has no matrix product: the bound is
    bytes over HBM bandwidth."""
    return bm25_batch_bytes(postings, docs) / peaks["hbm_bytes_per_s"]


def knn_batch_min_seconds(docs: int, dims: int, batch: int,
                          flops_per_s: float, peaks: dict) -> tuple:
    """Brute-force cosine of ``batch`` queries against ``docs`` float32
    vectors → (seconds, which bound): one read of the vectors, or
    2·B·N·D operations at the rate of the precision the configuration
    states, whichever is larger."""
    by_bytes = docs * dims * 4 / peaks["hbm_bytes_per_s"]
    by_flops = 2.0 * batch * docs * dims / flops_per_s
    return (by_bytes, "bytes") if by_bytes >= by_flops \
        else (by_flops, "flops")
