"""The data kind ``geonames`` on hand-worked cases: each operation's score,
the comparison's numbers, the control, the places and the queries drawn
from the seed, and the bytes of the function_score roofline."""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from benchmarks import fscore_rooflines
from benchmarks.corpora import geonames as gn

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks" / "configs"
                     / "geonames-function-score.json").read_text())
SMALL = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 2,
                              "segment_rows": 1024, "docs": 1500,
                              "clusters": 16, "vocab": 1000}}
#: degrees of latitude along a meridian that make 500 km on the sphere
DEG_500KM = math.degrees(500_000.0 / gn.EARTH_RADIUS_M)


def reference_of(pop, lat, lon, expected=3):
    """A Reference over hand-made columns (no corpus, no seed)."""
    ref = gn.Reference.__new__(gn.Reference)
    ref.pop = np.asarray(pop, np.float64)
    ref.lat, ref.lon = np.asarray(lat, float), np.asarray(lon, float)
    ref._fixed = {}
    ref.tally = {"answers": 0, "expected": expected,
                 "ops": {op: 0 for op in gn.OPS}}
    return ref


def test_field_value_factor_log2p_hand_worked():
    """log10(1.2 · population + 2): 0 → log10 2, 15 → log10 20,
    165 → log10 200."""
    ref = reference_of([0, 15, 165], [0, 0, 0], [0, 0, 0])
    got = ref.scores([gn.FVF, None])
    np.testing.assert_allclose(got, [0.30102999566, 1.30102999566,
                                     2.30102999566], rtol=1e-10)


def test_gauss_hand_worked():
    """At the origin 1; at the scale (500 km) the decay itself, 0.1; at
    √2 scales 0.1² — along a meridian and along the equator alike."""
    lat = [52.37, 52.37 + DEG_500KM, 52.37 - math.sqrt(2) * DEG_500KM,
           0.0]
    lon = [4.8951, 4.8951, 4.8951, DEG_500KM]
    ref = reference_of([0] * 4, lat, lon)
    got = ref.scores([gn.GAUSS, "52.37, 4.8951"])
    np.testing.assert_allclose(got[:3], [1.0, 0.1, 0.01], rtol=1e-9)
    got = ref.scores([gn.GAUSS, "0.0, 0.0"])
    np.testing.assert_allclose(got[3], 0.1, rtol=1e-9)


def test_expression_hand_worked():
    """|ln(|population| + 1) + lon + lat| · _score (1 under match_all)."""
    ref = reference_of([0, 0, 99], [10.0, -45.5, 1.0], [20.0, 10.0, 2.0])
    got = ref.scores([gn.EXPRESSION, None])
    np.testing.assert_allclose(got, [30.0, 35.5, math.log(100.0) + 3.0],
                               rtol=1e-12)


def test_compare_hand_worked():
    ref = reference_of([0, 15, 165, 1500], [0] * 4, [0] * 4, expected=2)
    s = ref.scores([gn.FVF, None])
    params = {"size": 2}
    ids, scores = np.array([3, 2]), np.asarray(s[[3, 2]]) * (1 + 1e-6)
    got = gn.compare(s, params, ids, scores, 4)
    assert got["score_gap"] == pytest.approx(1e-6, rel=1e-6)
    assert got["rank_gap"] == 0.0 and got["order_wrong"] == 0
    assert got["total_wrong"] == 0 and got["hits_wrong"] == 0
    assert got["ops_unsampled"] == 0          # the tally is not full yet
    # the second hit is the third best: rank_gap is its shortfall
    got = gn.compare(s, params, np.array([3, 1]), np.asarray(s[[3, 1]]), 3)
    assert got["rank_gap"] == pytest.approx((s[2] - s[1]) / s[2])
    assert got["total_wrong"] == 1
    # the run's last answer, and two operations never sampled
    assert got["ops_unsampled"] == 1
    got = gn.compare(s, params, np.array([2, 3]), np.asarray(s[[2, 3]]), 4)
    assert got["order_wrong"] == 1


def test_a_hits_source_is_held_to_its_place():
    ref = reference_of([0, 15], [1.5, -2.25], [-3.0, 4.125])
    s = ref.scores([gn.FVF, None])
    right = {"geonameid": 2, "population": 15, "location": [4.125, -2.25]}
    got = gn.compare(s, {"size": 1}, np.array([1]), np.asarray(s[[1]]), 2,
                     [right])
    assert got["source_wrong"] == 0
    for wrong in ({**right, "geonameid": 1}, {**right, "population": 0},
                  {**right, "location": [-2.25, 4.125]}, None):
        got = gn.compare(s, {"size": 1}, np.array([1]),
                         np.asarray(s[[1]]), 2, [wrong])
        assert got["source_wrong"] == 1, wrong
    # the control answers without sources: nothing to hold
    assert "source_wrong" not in gn.compare(
        s, {"size": 1}, np.array([1]), np.asarray(s[[1]]), 2)


def test_the_sources_are_the_records_json_hand_worked():
    """Numbers, negative ones and 5-decimal degrees, codes, an empty
    field left out, and alternate names, each as ``json`` reads it."""
    seg = {"exists": np.array([True, True, False]),
           "tokens": np.array([[12, 345678, -1], [7, -1, -1],
                               [-1, -1, -1]]),
           "geonameid": np.array([1, 12_000_000, 0]),
           "population": np.array([0, 1_234_567, 0]),
           "elevation": np.array([-12, 3000, 0]),
           "elevation_given": np.array([True, False, False]),
           "dem": np.array([-9999, 5, 0]),
           "lat": np.array([-0.5, 89.99999, 0.0]),
           "lon": np.array([-179.00001, 0.0, 0.0]),
           "alt_k": np.array([2, 0, 0]), "alt_tokens": np.array([2, 1]),
           "alt_tids": np.array([1, 2, 3])}
    tables = gn.code_tables({"countries": 3, "time_zones": 2,
                             "admin2_codes": 2, "admin3_codes": 2,
                             "admin4_codes": 2})
    codes = {"feature_class": [3, 0], "feature_code": [0, 1],
             "country_code": [2, 0], "cc2": [-1, 1],
             "admin1_code": [4, -1], "admin2_code": [-1, -1],
             "admin3_code": [1, -1], "admin4_code": [-1, 0],
             "timezone": [1, 0]}
    for f, ids in codes.items():
        seg[f] = np.array(ids + [-1])
    data, starts, ends = gn.sources(seg, tables)
    docs = [json.loads(data[a:b].tobytes())
            for a, b in zip(starts[:2], ends[:2])]
    assert ends[2] == starts[2]                   # a pad row has none
    assert docs[0] == {
        "geonameid": 1, "name": "t000012 t345678",
        "asciiname": "t000012 t345678",
        "alternatenames": "t000001 t000002,t000003",
        "feature_class": "P", "feature_code": "ADM4",
        "country_code": tables["country"][2], "admin1_code": "05",
        "admin3_code": "10001", "population": 0, "elevation": -12,
        "dem": "-9999", "timezone": "America/Tz001",
        "location": [-179.00001, -0.5]}
    assert docs[1] == {
        "geonameid": 12_000_000, "name": "t000007", "asciiname": "t000007",
        "feature_class": "A", "feature_code": "ADM3",
        "country_code": tables["country"][0],
        "cc2": tables["country"][1], "admin4_code": "1000000",
        "population": 1_234_567, "dem": "5", "timezone": "Africa/Tz000",
        "location": [0.0, 89.99999]}


def test_the_control_answers_in_bfloat16():
    """The control's hits from bfloat16 columns: populations a bfloat16
    cannot tell apart score alike."""
    ref = reference_of([1000, 1001, 1002, 5], [0] * 4, [0] * 4)
    ids, scores, total = gn.control_hits(ref, [gn.FVF, None], 3)
    assert total == 4 and ids.tolist() == [0, 1, 2]
    assert scores[0] == scores[1] == scores[2]


def test_places_from_the_seed():
    spec = SMALL["corpus"]
    centres = gn.cluster_centres(spec, 7)
    a = gn.place_columns(spec, 7, 1, gn.segment_docs(spec, 1), centres)
    b = gn.place_columns(spec, 7, 1, gn.segment_docs(spec, 1), centres)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    n = gn.segment_docs(spec, 1)
    assert n == 1500 - 1024 and a["exists"].sum() == n
    assert not a["population"][n:].any() and not a["lat"][n:].any()
    assert np.all(np.abs(a["lat"]) <= 90) and np.all(np.abs(a["lon"]) <= 180)
    np.testing.assert_array_equal(np.round(a["lat"], 5), a["lat"])
    c = gn.place_columns(spec, 8, 1, n, gn.cluster_centres(spec, 8))
    assert not np.array_equal(a["lat"], c["lat"])


def test_the_pool_mixes_the_operations_in_thirds():
    corpus = gn.generate(SMALL, 2**31 + 36)
    assert corpus["n_docs"] == 1500
    assert corpus["segments"][1]["doc_len"][476:].sum() == 0
    params = {"pool": 30, "ops": {op: 1 for op in gn.OPS}}
    one = gn.query_pool(corpus, params, np.random.default_rng(1),
                        np.random.default_rng([0, 1]))
    two = gn.query_pool(corpus, params, np.random.default_rng(2),
                        np.random.default_rng([0, 1]))
    assert [e[0] for e in one] == [e[0] for e in two]
    assert sorted(e[0] for e in one) == sorted(list(gn.OPS) * 10)
    populated = {(round(la, 5), round(lo, 5))
                 for s in corpus["segments"]
                 for la, lo, p in zip(s["lat"], s["lon"], s["population"])
                 if p > 0}
    for _op, origin in one:
        assert gn.parse_origin(origin) in populated
        assert len(origin.split(",")[1].split(".")[1]) == 5


def test_roofline_bytes_hand_worked():
    assert fscore_rooflines.fscore_batch_bytes(10, gn.FVF) == 80
    assert fscore_rooflines.fscore_batch_bytes(10, gn.GAUSS) == 160
    assert fscore_rooflines.fscore_batch_bytes(10, gn.EXPRESSION) == 240
    peaks = {"hbm_bytes_per_s": 819e9}
    got = fscore_rooflines.fscore_batch_min_seconds(
        11_396_503, {op: 1 for op in gn.OPS}, peaks)
    assert got == pytest.approx(11_396_503 * 16 / 819e9)


def test_the_two_readers_hand_worked():
    """``fscore_roofline`` from a traced slice of 2 s of the lane's device
    time that served 160 requests of 16 clients; ``fscore_serial_share``
    from the window's counters; each reads nothing where there is
    nothing to read."""
    import importlib.util
    from types import SimpleNamespace

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "benchmarks" / "layer_metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    traffic = json.loads((ROOT / "benchmarks" / "traffic"
                          / "fscore-mix-top10-c16.json").read_text())
    cell = SimpleNamespace(traffic=traffic, spec={
        "lane_modules": ["jit_run"], "expected_lanes": ["reader-batch"]})
    records = [[i, 0.0, 1.0 + i * 0.01, 1.005 + i * 0.01, 200, 1, 1]
               for i in range(160)]
    ctx = {"corpus_stats": {"places": 11_396_503}, "cell": cell,
           "dev": {"kind": "TPU v5 lite"}, "records": records,
           "traced": {"reduced": {"modules": {"jit_run": {"seconds": 2.0}}},
                      "t0": 0.5, "t1": 5.0}}
    least = 11_396_503 * 16 / 819e9
    assert reader("fscore_roofline")(ctx) == pytest.approx(
        100.0 * (160 / 16) * least / 2.0)
    assert reader("fscore_roofline")({**ctx, "corpus_stats": {
        "docs": 1, "postings": 1}}) is None
    serial = reader("fscore_serial_share")
    ctx = {"before": {"jit": {"fscore_items_batched": 10,
                              "fscore_items_serial": 1}},
           "after": {"jit": {"fscore_items_batched": 40,
                             "fscore_items_serial": 1}}}
    assert serial(ctx) == 0.0
    ctx["after"]["jit"]["fscore_items_serial"] = 11
    assert serial(ctx) == pytest.approx(25.0)
    assert serial({"before": {"jit": {}}, "after": {"jit": {}}}) is None
