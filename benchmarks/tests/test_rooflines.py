"""The bytes / FLOP functions on hand-worked shapes, and the peaks."""

import pytest

from benchmarks import rooflines


def test_peaks_known_kind_and_unknown_kind():
    p = rooflines.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        rooflines.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        rooflines.peaks_for("_source")


def test_bm25_bytes_hand_worked():
    # 1,000 docs with 10 distinct terms each: 10,000*(4+4) + 1000*4
    assert rooflines.bm25_batch_bytes(10_000, 1000) == 84_000
    # the cell: 4 x 2^20 docs of 43 distinct terms in the mean — counted
    # from the postings, not from the 224 slots of the padded layout
    n = 4 * (1 << 20)
    post = 43 * n
    assert rooflines.bm25_batch_bytes(post, n) == n * (43 * 8 + 4)
    t = rooflines.bm25_batch_min_seconds(post, n, {"hbm_bytes_per_s": 819e9})
    assert t == pytest.approx(1.782e-3, rel=1e-3)
    assert rooflines.bm25_batch_bytes(post, n) \
        < 0.2 * (n * 224 * 8 + n * 4), "a fifth of the padded layout"


def test_knn_bound_switches_from_bytes_to_flops():
    peaks = {"hbm_bytes_per_s": 819e9}
    n, d = 3 * (1 << 20), 768
    t, bound = rooflines.knn_batch_min_seconds(n, d, 16, 197e12, peaks)
    assert bound == "bytes" and t == pytest.approx(n * d * 4 / 819e9)
    t, bound = rooflines.knn_batch_min_seconds(n, d, 4096, 197e12, peaks)
    assert bound == "flops" and t == pytest.approx(2 * 4096 * n * d / 197e12)
