"""The two readers the four-chip cell brings — ``plane_roofline`` on
hand-worked shapes and a hand-made reduction, ``plane_host_ms_per_query``
on a hand-worked span list — and that each reads nothing, not 0, where
there is nothing to read."""

import types

import pytest

from benchmarks import harness, plane_rooflines
from benchmarks import span_common as sc


def read(metric, ctx):
    mod = harness.load_module(harness.reader_file("layer_metrics", metric),
                              f"check_{metric}")
    return mod.read(ctx)


def test_plane_bytes_hand_worked():
    # 1,000 docs of 10 distinct terms: 10,000 x 8 + 1,000 x 4
    assert plane_rooflines.plane_batch_bytes(10_000, 1000) == 84_000
    peaks = {"hbm_bytes_per_s": 819e9}
    one = plane_rooflines.plane_batch_min_seconds(10_000, 1000, 1, peaks)
    four = plane_rooflines.plane_batch_min_seconds(10_000, 1000, 4, peaks)
    assert one == pytest.approx(84_000 / 819e9) and four == one / 4
    # the cell: 8,847,360 docs of 43 distinct terms in the mean over four
    # chips: 3.08 GB a request, 0.94 ms
    n = 8 * 1_105_920
    t = plane_rooflines.plane_batch_min_seconds(43 * n, n, 4, peaks)
    assert t == pytest.approx(n * (43 * 8 + 4) / (4 * 819e9))
    assert t == pytest.approx(0.94e-3, rel=0.01)


def roofline_ctx(modules, records):
    return {"traced": {"t0": 10.0, "t1": 18.0,
                       "reduced": {"modules": modules} if modules else None},
            "records": records,
            "corpus_stats": {"docs": 8_847_360, "postings": 43 * 8_847_360},
            "dev": {"kind": "TPU v5 lite"},
            "cell": types.SimpleNamespace(
                chips=4, spec={"lane_modules": ["jit_step_local"]})}


def test_plane_roofline_on_a_hand_made_reduction():
    # two requests wholly inside the slice and one half inside it: 2.5;
    # the plane's module busy for 1.25 s (the mean over the four planes,
    # as trace_reduce hands it over): 0.5 s a request
    recs = [[1, 10.5, 10.5, 11.5, 200, 64, 64],
            [2, 12.0, 12.0, 13.0, 200, 64, 64],
            [3, 17.5, 17.5, 18.5, 200, 64, 64],
            [4, 14.0, 14.0, 15.0, 500, 0, 64]]      # failed: not counted
    ctx = roofline_ctx({"jit_step_local": {"count": 2.5, "seconds": 1.25},
                        "jit_other": {"count": 9, "seconds": 3.0}}, recs)
    least = 8_847_360 * (43 * 8 + 4) / (4 * 819e9)
    assert read("plane_roofline", ctx) == pytest.approx(
        100.0 * 2.5 * least / 1.25)
    assert read("plane_roofline", ctx) == pytest.approx(0.188, rel=0.01)
    # nothing to read: no trace, the module absent, no request served
    assert read("plane_roofline", roofline_ctx(None, recs)) is None
    assert read("plane_roofline", roofline_ctx(
        {"jit_other": {"count": 9, "seconds": 3.0}}, recs)) is None
    assert read("plane_roofline", roofline_ctx(
        {"jit_step_local": {"count": 1, "seconds": 0.5}}, [])) is None


def test_plane_host_ms_on_a_hand_worked_span_list():
    """One request of 64 queries in a slice of 1000 ticks of 1 ms:
    action.plane [0, 900] holds plane.resolve [10, 50], plane.upload
    [50, 60] (jit.upload [52, 58] inside), jit.plane-dispatch [60, 700]
    holding plane.enqueue [60, 65] and plane.drain [65, 700] (jit.drain
    [66, 699] inside), then 64 plane.split of 1 ms each."""
    ms = 1_000_000
    recs = [(1, 0, 1, "action.plane", 1, 0, 900 * ms, 5),
            (2, 1, 1, "plane.resolve", 1, 10 * ms, 50 * ms, -1),
            (3, 1, 1, "plane.upload", 1, 50 * ms, 60 * ms, -1),
            (4, 3, 1, "jit.upload", 1, 52 * ms, 58 * ms, -1),
            (5, 1, 1, "jit.plane-dispatch", 1, 60 * ms, 700 * ms, -1),
            (6, 5, 1, "plane.enqueue", 1, 60 * ms, 65 * ms, -1),
            (7, 5, 1, "plane.drain", 1, 65 * ms, 700 * ms, -1),
            (8, 7, 1, "jit.drain", 1, 66 * ms, 699 * ms, -1)]
    recs += [(9 + i, 1, 1, "plane.split", 1, (700 + 2 * i) * ms,
              (701 + 2 * i) * ms, -1) for i in range(64)]
    t0, t1 = 0, 1000 * ms
    ctx = {"traced": {"t0": 0.0, "t1": 1.0},
           "records": [[1, 0.0, 0.0, 0.95, 200, 64, 64]],
           "_span_analysis": sc.analyse(sc.cut(recs, t0, t1), [], t0, t1)}
    # self time: resolve 40 + upload (10 − 6) + enqueue 5 + drain
    # (635 − 633) + split 64 = 115 ms for 64 queries
    assert read("plane_host_ms_per_query.tput", ctx) == pytest.approx(
        115 / 64)
    # a program without the spans (a parent commit), an untraced run, an
    # empty ring: nothing, not 0
    bare = dict(ctx, _span_analysis=sc.analyse(
        sc.cut(recs[:1], t0, t1), [], t0, t1))
    assert read("plane_host_ms_per_query.tput", bare) is None
    assert read("plane_host_ms_per_query.tput",
                dict(ctx, _span_analysis=None)) is None
    assert read("plane_host_ms_per_query.tput", {"traced": None}) is None
