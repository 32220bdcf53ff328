"""Program cost observatory (tier-1): XLA static cost/memory analysis
present for every serving lane's programs on CPU, predicted-vs-measured
accounting finite and stamped, LRU-bounded table with exact eviction
accounting, occupancy reconciling with the scheduler's ``n_real``
counters, engine-close drains, the anomaly flight recorder's typed
ring, and the REST/stats/OpenMetrics/diagnostics round-trips —
including the profile-response ``programs`` bit staying absent when
``profile`` is off (the PR 13 idle-hot-path discipline)."""

from __future__ import annotations

import json
import math
import threading

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.observability import costs, flightrec
from elasticsearch_tpu.rest.controller import RestController
from elasticsearch_tpu.rest.handlers import register_all
from elasticsearch_tpu.search import jit_exec, lanes


@pytest.fixture(autouse=True)
def _clean():
    jit_exec.clear_cache()               # resets costs + flightrec too
    jit_exec.plane_breaker.reset()
    yield
    jit_exec.clear_cache()
    jit_exec.plane_breaker.reset()


@pytest.fixture
def node(tmp_path):
    n = Node({}, data_path=tmp_path / "n").start()
    yield n
    n.close()


def _mk_lexical(node, name="lex", docs=60):
    node.indices_service.create_index(
        name, {"settings": {"number_of_shards": 1,
                            "number_of_replicas": 0}})
    for i in range(docs):
        node.index_doc(name, str(i),
                       {"t": f"alpha beta word{i % 5}", "n": i})
    node.broadcast_actions.refresh(name)


def _mk_impact(node, name="imp", docs=80):
    node.indices_service.create_index(name, {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0,
                     "index.search.impact_plane": True,
                     "index.search.impact.block_rows": 64},
        "mappings": {"_doc": {"properties": {
            "t": {"type": "text", "analyzer": "whitespace"}}}}})
    for i in range(docs):
        node.index_doc(name, str(i), {"t": f"w{i % 7} w{(i + 2) % 11}"})
    node.broadcast_actions.refresh(name)


def _mk_knn(node, name="vec", docs=40):
    node.indices_service.create_index(name, {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"_doc": {"properties": {
            "v": {"type": "dense_vector", "dims": 4},
            "t": {"type": "text"}}}}})
    for i in range(docs):
        node.index_doc(name, str(i),
                       {"v": [float(i % 7), 1.0, float(i % 3), 0.5],
                        "t": "alpha"})
    node.broadcast_actions.refresh(name)


def _all_records():
    return [rec for nid in (costs.node_ids() or [""])
            for rec in costs.table(nid).records()]


def _lanes_seen():
    return {rec.lane for rec in _all_records()}


# ---------------------------------------------------------------------------
# roofline peaks: keyed by device_kind, an unknown device is an error
# ---------------------------------------------------------------------------

def test_v5e_peaks_are_the_published_ones():
    bw, flops = costs.peaks_for("tpu", "TPU v5 lite")
    assert bw == 819.0e9 and flops == 197.0e12
    # every CPU kind shares the nominal row
    assert costs.peaks_for("cpu", "cpu") == \
        costs.peaks_for("cpu", "some-host-cpu") == costs.PEAKS["cpu"]


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v9 imaginary"), ("gpu", "NVIDIA H100")])
def test_unknown_device_kind_raises(platform, kind):
    with pytest.raises(ValueError, match="no roofline peaks"):
        costs.peaks_for(platform, kind)


def test_machine_constants_raise_on_an_unknown_device(monkeypatch):
    """No silent CPU default: a backend whose device_kind has no peaks
    fails machine_constants() — unless both env overrides are given."""
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    monkeypatch.setattr(costs, "_machine", None)
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        costs.machine_constants()
    monkeypatch.setenv("ESTPU_ROOFLINE_BW_GBS", "100")
    monkeypatch.setenv("ESTPU_ROOFLINE_GFLOPS", "1000")
    assert costs.machine_constants() == (100e9, 1000e9)
    monkeypatch.setattr(costs, "_machine", None)   # next reader re-resolves


# ---------------------------------------------------------------------------
# static cost analysis: present and positive for every serving lane
# ---------------------------------------------------------------------------

def test_cost_analysis_present_for_all_four_lanes(node):
    """Drive every serving lane on CPU and assert each lane's program
    records carry the XLA static analyses: flops and bytes-accessed
    positive, HBM peak positive, compile time stamped — the roofline
    inputs ROOFLINE.md used to derive by hand."""
    _mk_lexical(node)
    _mk_impact(node)
    _mk_knn(node)
    node.indices_service.put_percolator(
        "lex", "pq1", {"query": {"match": {"t": "alpha"}}})
    # lexical (plane/fan-out compiled batch programs)
    for term in ("alpha", "word1"):
        r = node.search("lex", {"query": {"match": {"t": term}}})
        assert r["_shards"]["failed"] == 0
    # impact lane (opted in at create)
    r = node.search("imp", {"query": {"match": {"t": "w1"}},
                            "track_total_hits": False})
    assert r["_shards"]["failed"] == 0
    # knn lane
    r = node.search("vec", {"knn": {"field": "v",
                                    "query_vector": [1.0, 0.5, 0.2, 0.1],
                                    "k": 3, "num_candidates": 16},
                            "size": 3})
    assert len(r["hits"]["hits"]) == 3
    # percolate lane
    from elasticsearch_tpu.search.percolator import percolate
    meta = node.cluster_service.state().indices["lex"]
    out = percolate(meta, {"t": "alpha probe"})
    assert out["total"] == 1

    seen = _lanes_seen()
    # the four serving lanes' program classes all produced records
    assert seen & {"segment", "reader-batch", "mesh"}, seen
    assert seen & {"impact-eager", "impact-pruned"}, seen
    assert "knn" in seen, seen
    assert "percolate" in seen, seen
    for rec in _all_records():
        assert rec.lane in lanes.PROGRAM_LANES
        assert rec.analyzed, (rec.lane, rec.key_id)
        assert rec.flops > 0, (rec.lane, rec.summary())
        assert rec.bytes_accessed > 0, (rec.lane, rec.summary())
        assert rec.peak_bytes > 0, (rec.lane, rec.summary())
        assert rec.compiles >= 1 and rec.compile_ms > 0
        s = rec.summary()
        assert s["regime"] in ("memory", "compute")
        assert s["arithmetic_intensity"] > 0


def test_predicted_vs_measured_ratio_finite_and_stamped(node):
    _mk_lexical(node)
    for term in ("alpha", "word1", "word2"):
        node.search("lex", {"query": {"match": {"t": term}}})
    dispatched = [rec for rec in _all_records() if rec.dispatches > 0]
    assert dispatched
    for rec in dispatched:
        assert rec.predicted_us > 0 and math.isfinite(rec.predicted_us)
        assert rec.ewma_us > 0 and math.isfinite(rec.ewma_us)
        ratio = rec.accuracy_ratio()
        assert ratio is not None and math.isfinite(ratio) and ratio > 0
        assert rec.summary()["accuracy_ratio"] == round(ratio, 4)
        # bytes in/out accounting: static sizes × dispatches
        assert rec.bytes_in_total == \
            rec.argument_bytes * rec.dispatches
        assert rec.bytes_out_total == \
            rec.output_bytes * rec.dispatches


def test_estimate_returns_finite_for_hot_shapes(node):
    """costs.estimate — the planner's day-one cost model: exact hot
    shapes answer from measurement, cold shapes from the lane
    aggregate, unknown lanes honestly answer None."""
    _mk_lexical(node)
    for term in ("alpha", "word1"):
        node.search("lex", {"query": {"match": {"t": term}}})
    answered = 0
    for nid in costs.node_ids():
        t = costs.table(nid)
        for (lane, shape_key), rec in list(t._recs.items()):
            if rec.dispatches == 0:
                continue
            est = costs.estimate(lane, shape_key, node_id=nid)
            assert est is not None and math.isfinite(est) and est > 0
            # the hot shape answers from its own EWMA
            assert est == pytest.approx(rec.ewma_us)
            # a cold shape on a hot lane falls back to the lane mean
            cold = costs.estimate(lane, ("no-such-shape",), node_id=nid)
            assert cold is not None and math.isfinite(cold) and cold > 0
            answered += 1
    assert answered > 0
    assert costs.estimate("mesh", node_id="no-such-node") is None


# ---------------------------------------------------------------------------
# table accounting: LRU bound, eviction exactness, engine-close drain
# ---------------------------------------------------------------------------

class _StubCompiled:
    def __init__(self, flops=100.0, nbytes=1000.0):
        self._f, self._b = flops, nbytes

    def cost_analysis(self):
        return [{"flops": self._f, "bytes accessed": self._b}]

    def memory_analysis(self):
        class M:
            argument_size_in_bytes = 64
            output_size_in_bytes = 16
            temp_size_in_bytes = 8
        return M()


def test_table_lru_bounded_with_exact_eviction_accounting():
    t = costs.ProgramCostTable(cap=4)
    for i in range(10):
        t.note_compile("segment", ("shape", i),
                       costs.extract_analysis(_StubCompiled()),
                       1.0, owner=None)
    c = t.counters()
    assert c["resident"] == 4 and c["cap"] == 4
    assert c["inserted"] == 10 and c["evicted"] == 6
    assert c["inserted"] == c["resident"] + c["evicted"] + c["dropped"]
    # dispatches on a surviving key keep the invariant
    t.note_dispatch("segment", ("shape", 9), 50.0, 1, 1)
    c = t.counters()
    assert c["inserted"] == c["resident"] + c["evicted"] + c["dropped"]
    # a dispatch on an evicted key lazily re-inserts (counted)
    t.note_dispatch("segment", ("shape", 0), 50.0, 1, 1)
    c = t.counters()
    assert c["inserted"] == 11
    assert c["inserted"] == c["resident"] + c["evicted"] + c["dropped"]


def test_drop_owner_unit():
    t = costs.ProgramCostTable(cap=8)
    ana = costs.extract_analysis(_StubCompiled())
    t.note_compile("segment", ("a",), ana, 1.0, owner="e1")
    t.note_compile("segment", ("b",), ana, 1.0, owner="e1")
    t.note_compile("segment", ("c",), ana, 1.0, owner="e2")
    assert t.drop_owner("e1") == 2
    c = t.counters()
    assert c["resident"] == 1 and c["dropped"] == 2
    assert c["inserted"] == c["resident"] + c["evicted"] + c["dropped"]
    assert not any(rec.owner == "e1" for rec in t.records())


def test_cost_table_drains_with_the_engine(node):
    """No rows for closed engines — the ledger discipline: deleting the
    index fires the engine-close listeners, which drop the engine's
    cost rows the same instant its device blocks release."""
    _mk_lexical(node, "drain")
    node.search("drain", {"query": {"match": {"t": "alpha"}}})
    svc = node.indices_service.indices["drain"]
    uuids = {e.engine_uuid for e in svc.engines.values()}
    owned = [rec for rec in _all_records() if rec.owner in uuids]
    assert owned, "searches should produce engine-owned cost rows"
    node.indices_service.delete_index("drain")
    left = [rec for rec in _all_records() if rec.owner in uuids]
    assert left == [], [(r.lane, r.key_id, r.owner) for r in left]
    for nid in costs.node_ids():
        c = costs.table(nid).counters()
        assert c["inserted"] == \
            c["resident"] + c["evicted"] + c["dropped"]


# ---------------------------------------------------------------------------
# occupancy ↔ scheduler n_real reconciliation
# ---------------------------------------------------------------------------

def test_occupancy_reconciles_with_scheduler_n_real(node):
    """Every scheduler-launched batch dispatches with the live-waiter
    count as n_real: the cost table's per-lane requests/rows books must
    reconcile exactly with the scheduler's admitted/pad counters."""
    from elasticsearch_tpu.index.device_reader import device_reader_for
    from elasticsearch_tpu.search.phase import (ShardSearcher,
                                                parse_search_request)
    from elasticsearch_tpu.search.scheduler import (
        ContinuousBatchScheduler, classify)
    _mk_lexical(node, "occ", docs=100)
    svc = node.indices_service.indices["occ"]
    s = ShardSearcher(0, device_reader_for(svc.engine(0)),
                      svc.mapper_service, index_name="occ")
    reqs = [parse_search_request(
        {"query": {"match": {"t": f"word{i % 5}"}}, "size": 5})
        for i in range(24)]
    lane0, shape0 = classify(reqs[0], s)
    assert lane0 == "plane"
    # warm the program shapes OUTSIDE the measured window
    s.query_phase_batch([reqs[0]])
    jit_exec.clear_cache()
    sched = ContinuousBatchScheduler(node_id=node.node_id, max_batch=8,
                                     max_in_flight=2)
    try:
        errs: list = []

        def client(i):
            try:
                out = sched.execute(
                    "plane", ("occ", 0, "plane", shape0, id(s.reader)),
                    reqs[i], s.query_phase_batch_launch,
                    s.query_phase_batch_drain)
                if out is None:
                    errs.append(("declined", i))
            except Exception as e:       # noqa: BLE001 — surfaced below
                errs.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs, errs[:3]
    finally:
        sched.close()
    js = jit_exec.cache_stats()
    admitted = js["scheduler_requests_admitted"]
    pads = js["scheduler_pad_rows"]
    assert admitted == len(reqs)
    rollup: dict = {}
    for nid in costs.node_ids():
        for lane, ent in costs.lane_rollup(nid).items():
            agg = rollup.setdefault(lane, {"requests": 0, "rows": 0})
            agg["requests"] += ent["requests"]
            agg["rows"] += ent["rows"]
    batch_lanes = {"reader-batch", "streamed"}
    got_reqs = sum(rollup.get(ln, {}).get("requests", 0)
                   for ln in batch_lanes)
    got_rows = sum(rollup.get(ln, {}).get("rows", 0)
                   for ln in batch_lanes)
    # every admitted request is exactly one real row; every pad row is
    # accounted — occupancy is the ratio, reconciled
    assert got_reqs == admitted, (rollup, js)
    assert got_rows == admitted + pads, (rollup, admitted, pads)


# ---------------------------------------------------------------------------
# anomaly flight recorder
# ---------------------------------------------------------------------------

def test_dispatch_overrun_event():
    ana = costs.extract_analysis(_StubCompiled())
    t = costs.table("frnode")
    t.note_compile("segment", ("k",), ana, 1.0, owner=None)
    for _ in range(costs.ANOMALY_MIN_DISPATCHES):
        costs.note_dispatch("segment", ("k",), 0.1, node_id="frnode")
    # 0.1 ms EWMA → a 100 ms dispatch blows the envelope
    costs.note_dispatch("segment", ("k",), 100.0, node_id="frnode")
    evs = [e for e in flightrec.events("frnode")
           if e["type"] == "dispatch-overrun"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["lane"] == "segment" and ev["dispatch_us"] >= 1e5
    assert ev["envelope_us"] > 0 and "epoch_us" in ev


def test_compile_storm_event():
    ana = costs.extract_analysis(_StubCompiled())
    costs.table("frs").note_compile("mesh", ("k",), ana, 1.0, None)
    for _ in range(costs.HOT_DISPATCHES):
        costs.note_dispatch("mesh", ("k",), 1.0, node_id="frs")
    # a recompile of the now-hot key is a storm
    costs.note_compile("mesh", ("k",), _StubCompiled(), 2.0,
                       node_id="frs")
    evs = [e for e in flightrec.events("frs")
           if e["type"] == "compile-storm"]
    assert len(evs) == 1 and evs[0]["lane"] == "mesh"


def test_shed_burst_coalesces():
    for _ in range(25):
        flightrec.note_shed("slo-shed", node_id="frb")
    evs = [e for e in flightrec.events("frb")
           if e["type"] == "shed-burst"]
    assert len(evs) == 1 and evs[0]["count"] == 25
    assert evs[0]["reason"] == "slo-shed"


def test_breaker_transitions_recorded():
    b = jit_exec.PlaneBreaker(threshold=2, backoff_s=0.0)
    boom = RuntimeError("injected")
    b.record_error(boom)
    b.record_error(boom)                 # threshold → open
    assert b.stats()["state"] == "open"
    assert b.allow()                     # backoff 0 → half-open probe
    b.record_success()                   # probe succeeds → closed
    types = [e["type"] for e in flightrec.events()]
    assert "breaker-open" in types
    assert "breaker-half-open" in types
    assert "breaker-closed" in types
    opened = next(e for e in flightrec.events()
                  if e["type"] == "breaker-open")
    assert opened["cause"] == "threshold" and "injected" in opened["error"]


def test_ring_bounded_with_exact_overflow_accounting():
    for i in range(flightrec.RING_CAP + 44):
        flightrec.note("breaker-open", node_id="frr", i=i)
    st = flightrec.stats("frr")
    assert st["resident"] == flightrec.RING_CAP
    assert st["recorded"] == flightrec.RING_CAP + 44
    assert st["overflowed"] == 44
    # oldest entries fell off; the newest survived
    assert flightrec.events("frr")[-1]["i"] == flightrec.RING_CAP + 43


def test_unregistered_event_type_rejected():
    with pytest.raises(AssertionError):
        flightrec.note("made-up-event")


# ---------------------------------------------------------------------------
# surfaces: stats / _cat/programs / diagnostics / OpenMetrics / profile
# ---------------------------------------------------------------------------

def test_nodes_stats_programs_section(node):
    _mk_lexical(node)
    node.search("lex", {"query": {"match": {"t": "alpha"}}})
    doc = node.local_node_stats()
    progs = doc["programs"]
    assert progs["table"]["reconciled"] is True
    assert progs["table"]["inserted"] >= 1
    assert progs["lanes"], progs
    assert progs["top"] and progs["top"][0]["dispatches"] >= 1
    top = progs["top"][0]
    for key in ("lane", "key", "predicted_us", "measured_us", "regime",
                "hbm_peak_bytes", "occupancy"):
        assert key in top
    assert doc["flight_recorder"]["cap"] == flightrec.RING_CAP


def test_cat_programs_and_param_validation(node):
    _mk_lexical(node)
    node.search("lex", {"query": {"match": {"t": "alpha"}}})
    rc = RestController()
    register_all(rc, node)
    st, out = rc.dispatch("GET", "/_cat/programs?v=true", b"")
    assert st == 200
    header, *rows = [ln for ln in out.splitlines() if ln.strip()]
    assert "lane" in header and "measured_us" in header \
        and "regime" in header
    assert rows, out
    lane_col = header.split().index("lane")
    got_lanes = {r.split()[lane_col] for r in rows}
    assert got_lanes <= set(lanes.PROGRAM_LANES)
    # ?lane filter: registered lane filters, unknown lane is a 400
    st, out = rc.dispatch(
        "GET", "/_cat/programs?v=true&lane=reader-batch", b"")
    assert st == 200
    st, err = rc.dispatch("GET", "/_cat/programs?lane=warp", b"")
    assert st == 400 and "PROGRAM_LANES" not in str(err) \
        and "warp" in json.dumps(err)
    st, err = rc.dispatch("GET", "/_cat/programs?top=nope", b"")
    assert st == 400 and "integer" in json.dumps(err)
    st, err = rc.dispatch("GET", "/_cat/programs?top=0", b"")
    assert st == 400


def test_nodes_diagnostics_bundle(node):
    _mk_lexical(node)
    node.search("lex", {"query": {"match": {"t": "alpha"}}})
    flightrec.note("breaker-open", node_id=node.node_id, cause="test")
    rc = RestController()
    register_all(rc, node)
    st, out = rc.dispatch("GET", "/_nodes/diagnostics", b"")
    assert st == 200
    doc = out["nodes"][node.node_id]
    for key in ("flight_recorder", "programs", "device_memory",
                "rates", "slo", "scheduler", "breakers"):
        assert key in doc, sorted(doc)
    assert doc["breakers"]["plane"]["state"] == "closed"
    assert any(e["type"] == "breaker-open"
               for e in doc["flight_recorder"]["events"])
    assert doc["programs"]["table"]["reconciled"] is True
    # local-node path params resolve; unknown nodes 404
    st, _ = rc.dispatch(
        "GET", f"/_nodes/{node.node_id}/diagnostics", b"")
    assert st == 200
    st, err = rc.dispatch("GET", "/_nodes/nope/diagnostics", b"")
    assert st == 404
    st, err = rc.dispatch("GET", "/_nodes/diagnostics?top=x", b"")
    assert st == 400


def test_openmetrics_program_cost_gauges(node):
    _mk_lexical(node)
    node.search("lex", {"query": {"match": {"t": "alpha"}}})
    rc = RestController()
    register_all(rc, node)
    st, text = rc.dispatch("GET", "/_prometheus/metrics", b"")
    assert st == 200
    for key in lanes.PROGRAM_COST:
        assert f"estpu_program_cost_{key}" in text, key
    assert 'estpu_program_cost_dispatches{lane="' in text


def test_profile_programs_present_only_when_profiling(node):
    _mk_lexical(node)
    body = {"query": {"match": {"t": "alpha"}}, "size": 5}
    plain = node.search("lex", dict(body))
    assert "profile" not in plain
    # idle discipline: no program collector is installed off-profile
    assert costs.current_collectors() is None
    prof = node.search("lex", {**body, "profile": True})
    assert "programs" in prof["profile"]
    shard_rows = [row for sh in prof["profile"]["shards"]
                  for row in sh.get("programs", ())]
    coord_rows = prof["profile"]["programs"]
    rows = coord_rows + shard_rows
    assert rows, prof["profile"]
    for row in rows:
        assert row["lane"] in lanes.PROGRAM_LANES
        assert row["dispatches"] >= 1
        assert row["device_time_us"] > 0
    # hits are bit-identical (flag stripped pre-fan-out)
    assert [h["_id"] for h in prof["hits"]["hits"]] == \
        [h["_id"] for h in plain["hits"]["hits"]]


def test_stats_reads_allocate_nothing(node):
    """Reading the observatory repeatedly never grows it — snapshots
    are pure reads (the PR 13 idle-hot-path discipline)."""
    _mk_lexical(node)
    node.search("lex", {"query": {"match": {"t": "alpha"}}})
    before = {nid: costs.table(nid).counters()
              for nid in costs.node_ids()}
    for _ in range(5):
        costs.stats_doc(node.node_id)
        costs.lane_rollup(node.node_id)
        costs.top_programs(node.node_id)
        flightrec.stats(node.node_id)
    after = {nid: costs.table(nid).counters()
             for nid in costs.node_ids()}
    assert before == after


def test_slowlog_attribution_names_hot_program(node):
    """The slow-log fragment extends programs[Nh/Mm] with the hot
    program's key and measured µs."""
    from elasticsearch_tpu.observability import attribution
    with attribution.collect(admission="plane"):
        attribution.count("hits", 2)
        attribution.program("mesh", "abcdef123456", 1500.0)
        attribution.program("mesh", "ffffff000000", 300.0)
        frag = attribution.render_current(took_s=0.01)
    assert "programs[2h/0m hot=mesh:abcdef123456/1500us×1]" in frag
