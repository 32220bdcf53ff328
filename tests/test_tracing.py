"""Span tracing (observability/tracing.py): unit mechanics, cross-node
propagation over BOTH transports, trace reassembly under the
coordinating task id, and the zero-leaked-open-spans contract on
completion, cancellation, and timeout."""

import gc
import threading
import time

import pytest

from elasticsearch_tpu.common.errors import TaskCancelledError
from elasticsearch_tpu.observability import (attribution, chrome,
                                             histograms, tracing,
                                             use_node)
from elasticsearch_tpu.testing import InternalTestCluster
from elasticsearch_tpu.testing_disruption import wait_until


# ---- unit: spans, context, stores ------------------------------------------

def test_span_tree_nests_by_parent_and_sorts_by_start():
    with tracing.trace("t-unit-1", "nA"):
        with tracing.collect_spans() as got:
            with tracing.span("root"):
                with tracing.span("a"):
                    pass
                with tracing.span("b"):
                    with tracing.span("b1"):
                        pass
    tree = tracing.build_tree(got)
    assert [t["name"] for t in tree] == ["root"]
    root = tree[0]
    assert [c["name"] for c in root["children"]] == ["a", "b"]
    assert [c["name"] for c in root["children"][1]["children"]] == ["b1"]
    assert tracing.open_span_count("nA") == 0


def test_tracer_off_allocates_no_span_objects():
    before = tracing.spans_allocated()
    with tracing.span("ignored", attr=1):
        with tracing.device_span("dispatch"):
            pass
    assert tracing.spans_allocated() == before
    # the no-op singleton supports the full surface
    sp = tracing.span("x")
    assert sp.set(k=1) is sp


def test_span_status_on_error_and_cancellation():
    with tracing.trace("t-unit-2", "nB"):
        with tracing.collect_spans() as got:
            with pytest.raises(ValueError):
                with tracing.span("boom"):
                    raise ValueError("x")
            with pytest.raises(TaskCancelledError):
                with tracing.span("shed"):
                    raise TaskCancelledError("cancelled")
    by_name = {r["name"]: r for r in got}
    assert by_name["boom"]["status"] == "error"
    assert by_name["shed"]["status"] == "cancelled"
    # every span closed despite the raises
    assert tracing.open_span_count("nB") == 0


def test_collect_spans_innermost_collector_wins():
    with tracing.trace("t-unit-3", "nC"):
        with tracing.collect_spans() as outer:
            with tracing.span("coordinator"):
                with tracing.collect_spans() as inner:
                    with tracing.span("shard"):
                        pass
    assert [r["name"] for r in inner] == ["shard"]
    assert [r["name"] for r in outer] == ["coordinator"]


def test_device_span_feeds_rtt_histogram_and_attribution():
    histograms.reset()
    with use_node("rtt-node"), attribution.collect(admission="fanout"):
        with tracing.device_span("dispatch"):
            time.sleep(0.002)
        with tracing.device_span("upload"):   # not a dispatch site
            pass
        frag = attribution.render_current(took_s=0.01)
    lanes = histograms.summaries("rtt-node")
    assert lanes["device_rtt"]["count"] == 1
    assert lanes["device_rtt"]["p50_ms"] > 0.5
    assert "admission[fanout]" in frag and "device[" in frag


def test_slowlog_line_carries_plane_attribution(caplog):
    import logging

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.slowlog import SearchSlowLog
    slog = SearchSlowLog("idx", Settings(
        {"index.search.slowlog.threshold.query.warn": "1ms"}))
    with attribution.collect(admission="fanout"):
        attribution.count("hits", 3)
        attribution.count("misses", 1)
        attribution.device_ms("dispatch", 5.0)
        with caplog.at_level(logging.WARNING,
                             logger="index.search.slowlog"):
            assert slog.maybe_log(0.02, "q") == "warn"
    msg = caplog.records[-1].getMessage()
    assert "admission[fanout]" in msg
    assert "programs[3h/1m]" in msg
    assert "device[5.0ms/25%]" in msg
    # without an attribution record the line is unchanged
    with caplog.at_level(logging.WARNING, logger="index.search.slowlog"):
        slog.maybe_log(0.02, "q2")
    assert "admission[" not in caplog.records[-1].getMessage()


def test_wire_header_roundtrip_adopt():
    with tracing.trace("t-wire", "sender"):
        with tracing.span("outer"):
            hdr = tracing.wire_header()
            assert hdr["id"] == "t-wire" and hdr["parent"]
            with tracing.adopt(hdr, "receiver"):
                with tracing.span("remote"):
                    pass
    remote = [r for r in tracing.spans_for("receiver", "t-wire")
              if r["name"] == "remote"]
    assert remote and remote[0]["parent_id"] == hdr["parent"]
    # adopt of a header-less request is a no-op context
    with tracing.adopt(None, "receiver"):
        assert not tracing.active()


def test_histogram_percentiles_and_node_isolation():
    histograms.reset()
    for ms in (1.0, 2.0, 4.0, 8.0, 100.0):
        histograms.observe_lane("fanout", ms, node_id="iso-a")
    histograms.observe_lane("fanout", 1000.0, node_id="iso-b")
    a = histograms.summaries("iso-a")["fanout"]
    b = histograms.summaries("iso-b")["fanout"]
    assert a["count"] == 5 and b["count"] == 1
    assert a["p50_ms"] <= a["p95_ms"] <= a["p99_ms"] <= a["max_ms"]
    assert a["max_ms"] == 100.0 and b["max_ms"] == 1000.0
    # lanes report a stable shape even when empty
    assert histograms.summaries("iso-a")["percolate"]["count"] == 0


def test_chrome_trace_export_shape():
    with tracing.trace("t-chrome", "nD"):
        with tracing.collect_spans() as got:
            with tracing.span("search"):
                with tracing.span("query"):
                    pass
    doc = chrome.chrome_trace(got)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert e["dur"] >= 1 and e["ts"] > 0
        assert e["args"]["trace_id"] == "t-chrome"
    assert any(e["ph"] == "M" for e in doc["traceEvents"])


# ---- cluster: propagation + reassembly -------------------------------------

@pytest.fixture(scope="module", params=["local", "tcp"])
def cluster(request, tmp_path_factory):
    n = 3 if request.param == "local" else 2
    with InternalTestCluster(
            n, base_path=tmp_path_factory.mktemp("trace"),
            transport=request.param) as c:
        c.wait_for_nodes(n)
        m = c.master()
        m.indices_service.create_index(
            "traced", {"settings": {"number_of_shards": n,
                                    "number_of_replicas": 0}})
        c.wait_for_health("green")
        for i in range(24):
            m.index_doc("traced", str(i), {"body": f"hello world {i}"})
        m.broadcast_actions.refresh("traced")
        yield c


def _zero_open_everywhere(cluster):
    return all(
        tracing.store_stats(n.node_id)["open_spans"] == 0
        for n in cluster.nodes)


def test_profile_search_reassembles_one_cross_node_tree(cluster):
    m = cluster.master()
    resp = m.search_actions.search(
        "traced", {"query": {"match": {"body": "hello"}}, "size": 5,
                   "profile": True})
    trace_id = resp["profile"]["trace_id"]
    # trace id IS the coordinating task id (node_id:seq shape)
    assert trace_id.startswith(m.node_id + ":")
    out = m.collect_trace(trace_id)
    assert out["span_count"] > 0 and out["open_spans"] == 0
    # ONE root — the coordinator's search span — even though spans were
    # recorded on several nodes
    assert [t["name"] for t in out["tree"]] == ["search"]
    assert len(out["nodes"]) >= 2
    phases = [c["name"] for c in out["tree"][0]["children"]]
    assert "query" in phases and "reduce" in phases
    # every shard subtree reassembled under the fan-out
    def collect(t, acc):
        acc.append(t["name"])
        for c in t["children"]:
            collect(c, acc)
    names: list = []
    collect(out["tree"][0], names)
    assert names.count("shard") == 3 if cluster.transport == "local" \
        else names.count("shard") == 2
    assert _zero_open_everywhere(cluster)


def test_cancelled_search_leaves_complete_closed_tree(cluster):
    m = cluster.master()
    for n in cluster.nodes:
        n.search_actions.shard_query_delay = 8.0
    try:
        out: dict = {}
        th = threading.Thread(target=lambda: out.update(r=m.search(
            "traced", {"query": {"match_all": {}}, "profile": True})))
        th.start()
        coord: dict = {}

        def coord_visible():
            for tid, t in m.task_manager.list_tasks().items():
                if t["action"] == "indices:data/read/search" \
                        and "parent_task_id" not in t:
                    coord["id"] = tid
                    return True
            return False
        assert wait_until(coord_visible, timeout=5.0)
        assert m.cancel_task(coord["id"], reason="test cancel")["found"]
        th.join(15.0)
        assert out["r"].get("cancelled") is True
    finally:
        for n in cluster.nodes:
            n.search_actions.shard_query_delay = None
    # the cancelled request still yielded a complete, ENDED span tree:
    # zero open spans anywhere, and the recorded spans carry their
    # cancellation status
    assert wait_until(lambda: _zero_open_everywhere(cluster),
                      timeout=10.0)
    spans = [s for n in cluster.nodes
             for s in tracing.spans_for(n.node_id, coord["id"])]
    assert spans, "cancelled trace recorded no spans"
    assert any(s["status"] == "cancelled" for s in spans)


def test_timed_out_search_closes_every_span(cluster):
    m = cluster.master()
    for n in cluster.nodes:
        n.search_actions.shard_query_delay = 0.3
    try:
        resp = m.search_actions.search(
            "traced", {"query": {"match_all": {}}, "timeout": "30ms",
                       "profile": True})
        assert resp["timed_out"] is True
        assert "profile" in resp
    finally:
        for n in cluster.nodes:
            n.search_actions.shard_query_delay = None
    assert wait_until(lambda: _zero_open_everywhere(cluster),
                      timeout=10.0)


def test_per_node_stats_isolation_under_fanout(cluster):
    """A search coordinated on node A must land on A's histograms, not
    on every node's (module-level state is per-node keyed)."""
    m = cluster.master()
    others = [n for n in cluster.nodes if n is not m]
    before_m = m.local_node_stats()["latency"]["fanout"]["count"]
    before_o = [n.local_node_stats()["latency"]["fanout"]["count"]
                for n in others]
    m.search_actions.search("traced",
                            {"query": {"match": {"body": "hello"}}})
    after_m = m.local_node_stats()["latency"]["fanout"]["count"]
    after_o = [n.local_node_stats()["latency"]["fanout"]["count"]
               for n in others]
    assert after_m == before_m + 1
    assert after_o == before_o          # no smear onto other nodes
    # per-node jit slices stay within the process-global rollup
    total = m.local_node_stats()["indices"]["jit"]
    per_node = [n.local_node_stats()["indices"]["jit"]["node_local"]
                for n in cluster.nodes]
    for key in ("hits", "misses"):
        assert sum(p[key] for p in per_node) <= total[key]


# ---- the always-on ring, the in-flight book, the annotations ---------------
# The ring and the book are module state: every test below starts from a
# fresh ring and a fresh book and leaves fresh ones behind, so none of
# them depends on what ran before it (ROADMAP D12).

@pytest.fixture
def fresh_ring():
    tracing.reset()
    yield
    tracing.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One Node + RestServer over a real socket, a small index in it,
    and every program the tests below reach already compiled."""
    import http.client
    import json

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.server import RestServer
    node = Node({}, data_path=str(tmp_path_factory.mktemp("ring"))).start()
    server = RestServer(node, port=0).start()
    conn = http.client.HTTPConnection(server.host, server.port, timeout=60)

    def call(method, path, body=None):
        if isinstance(body, list):          # NDJSON
            body = "".join(json.dumps(line) + "\n" for line in body)
        elif body is not None:
            body = json.dumps(body)
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    assert call("PUT", "/ring", {"settings": {
        "number_of_shards": 1, "number_of_replicas": 0}})[0] == 200
    bulk = []
    for i in range(40):
        bulk += [{"index": {"_index": "ring", "_type": "_doc",
                            "_id": str(i)}},
                 {"t": f"alpha w{i % 3} w{i % 5} w{i % 7}"}]
    assert call("POST", "/_bulk", bulk)[0] == 200
    assert call("POST", "/ring/_refresh")[0] == 200
    call("POST", "/_msearch", MSEARCH)              # compiles
    call("POST", "/ring/_search", SEARCH)
    try:
        yield node, call
    finally:
        conn.close()
        server.stop()
        node.close()


#: 8 items of mixed lengths (1, 2, 3 terms): their term lists pad to the
#: batch's term bucket (4), so ONE compiled plan serves them and the
#: shard answers all eight in one dispatch — the benchmark cell's path
MSEARCH = [line for i in range(8) for line in (
    {"index": "ring"},
    {"query": {"match": {"t": " ".join(
        ["alpha", f"w{i % 3}", f"w{i % 5}"][:1 + i % 3])}}, "size": 5})]
SEARCH = {"query": {"match": {"t": "alpha w1"}}, "size": 5}


def _self_ns(records):
    """seq → the span's duration less what its child spans cover (the
    union of their intervals, cut to the span's own)."""
    kids: dict = {}
    for r in records:
        kids.setdefault(r[1], []).append(r)
    out = {}
    for seq, _p, _rid, _name, _tid, t0, t1, _cpu in records:
        covered, edge = 0, t0
        for k in sorted(kids.get(seq, ()), key=lambda r: r[5]):
            a, b = max(k[5], edge), min(k[6], t1)
            if b > a:
                covered, edge = covered + b - a, b
        out[seq] = (t1 - t0) - covered
    return out


def _written(t0: int) -> int:
    """The instant the request's ``rest.write`` has closed: the client
    holds the reply a moment before the server's write span ends."""
    assert wait_until(lambda: any(
        r[3] == "rest.write"
        for r in tracing.ring_records(t0, time.monotonic_ns())))
    return time.monotonic_ns()


def test_msearch_over_rest_one_request_every_layer(served, fresh_ring):
    node, call = served
    before = tracing.spans_allocated()
    jit0 = node.local_node_stats()["indices"]["jit"]
    t0 = time.monotonic_ns()
    status, reply = call("POST", "/_msearch", MSEARCH)
    t1 = _written(t0)
    assert status == 200 and len(reply["responses"]) == 8
    assert all(r["hits"]["total"] > 0 for r in reply["responses"])
    # the tracer is off: the ring took its records, no Span was built
    assert tracing.spans_allocated() == before
    recs = tracing.ring_records(t0, t1)
    assert recs and tracing.ring_stats()["overwritten"] == 0
    # one request id on every record, minted by the rest layer
    assert len({r[2] for r in recs}) == 1, sorted({r[3] for r in recs})
    names = [r[3] for r in recs]
    for name in ("rest.read", "rest.handle", "rest.serialise",
                 "rest.write", "action.msearch", "action.msearch_group",
                 "action.shard_msearch"):
        assert names.count(name) == 1, name
    # one batch: one plan, one enqueue, one drain — and a fetch per item
    for name in ("plan.exact", "jit.pack", "jit.enqueue", "jit.drain",
                 "jit.unpack"):
        assert names.count(name) == 1, (name, names.count(name))
    assert names.count("fetch.hits") == 8
    # an _msearch bypasses the scheduler; every other layer is there
    layers = {tracing.SPAN_LAYERS[n] for n in names}
    assert layers == {"rest", "action", "planner", "jit_exec", "fetch"}
    # every parent resolves; only the rest layer's four are roots
    seqs = {r[0] for r in recs}
    assert all(r[1] in seqs for r in recs if r[1])
    assert sorted(r[3] for r in recs if not r[1]) == [
        "rest.handle", "rest.read", "rest.serialise", "rest.write"]
    # three threads served it: ingress, msearch pool, search pool
    assert len({r[4] for r in recs}) == 3
    # self times account for the root's duration, to the nanosecond:
    # the children of every span lie inside it and follow one another
    root = next(r for r in recs if r[3] == "rest.handle")
    below, grew = {root[0]}, True
    while grew:
        grew = False
        for r in recs:
            if r[1] in below and r[0] not in below:
                below.add(r[0])
                grew = True
    selfs = _self_ns(recs)
    assert sum(selfs[s] for s in below) == root[6] - root[5]
    # cpu_ns: taken by the outermost span of each thread, -1 inside it
    assert sorted(r[3] for r in recs if r[7] >= 0) == [
        "action.msearch_group", "action.shard_msearch", "rest.handle",
        "rest.read", "rest.serialise", "rest.write"]
    assert all(r[7] == -1 or 0 <= r[7] <= r[6] - r[5] + 20_000_000
               for r in recs)
    # the book: one launch, closed by its drain
    book = tracing.book_stats()
    assert book["launches"] == 1 and book["launches_in_flight"] == 0
    assert book["launches_without_drain"] == 0
    assert book["starved_ns"] + book["in_flight_ns"] == book["observed_ns"]
    # ...and the device_rtt lane got the one round trip
    stats = node.local_node_stats()
    assert stats["device"]["launches"] == 1
    assert stats["tracing"]["ring"]["bytes"] == tracing.RING_BYTES
    # the counters that say how often the mechanism engages: all eight
    # items batched; lengths 1, 2, 3, 1, 2, 3, 1, 2 padded to 4 each
    jit = stats["indices"]["jit"]
    assert jit["msearch_items_batched"] - jit0["msearch_items_batched"] == 8
    assert jit["msearch_items_serial"] == jit0["msearch_items_serial"]
    assert jit["match_terms_real"] - jit0["match_terms_real"] == 15
    assert jit["match_terms_padded"] - jit0["match_terms_padded"] == 17


def test_single_search_over_rest_passes_the_scheduler(served, fresh_ring):
    node, call = served
    sched = node.search_actions.scheduler
    sched0 = histograms.summaries(sched.node_id or "")["queue_wait"]
    t0 = time.monotonic_ns()
    status, reply = call("POST", "/ring/_search", SEARCH)
    t1 = time.monotonic_ns()
    assert status == 200 and reply["hits"]["total"] > 0
    recs = tracing.ring_records(t0, t1)
    by_rid: dict = {}
    for r in recs:
        by_rid.setdefault(r[2], []).append(r[3])
    mine = next(v for v in by_rid.values() if "rest.handle" in v)
    for name in ("action.search", "action.parse", "action.query",
                 "action.reduce", "action.shard", "scheduler.queue",
                 "fetch.hits"):
        assert name in mine, (name, by_rid)
    # the batch is the scheduler's own work: with one waiter it runs
    # under the caller's request, else under a request of its own
    everything = [n for v in by_rid.values() for n in v]
    assert "scheduler.launch" in everything
    assert "scheduler.drain" in everything
    # the pick-up fed the node's queue-time lane (and its SLO book)
    sched1 = histograms.summaries(sched.node_id or "")["queue_wait"]
    assert sched1["count"] >= sched0["count"] + 1
    assert 0 <= sched1["p50_ms"] <= sched1["p95_ms"]


def test_the_wait_for_a_batchs_result_is_a_span_of_its_own(served,
                                                           fresh_ring):
    node, call = served
    t0 = time.monotonic_ns()
    status, _ = call("POST", "/ring/_search", SEARCH)
    t1 = time.monotonic_ns()
    assert status == 200
    recs = tracing.ring_records(t0, t1)
    rid = next(r[2] for r in recs if r[3] == "rest.handle")
    mine = {r[3]: r for r in recs if r[2] == rid}
    shard = mine["action.shard"]
    queue, result = mine["scheduler.queue"], mine["scheduler.result"]
    # queue, then result, both under action.shard on its thread
    assert queue[1] == result[1] == shard[0]
    assert queue[4] == result[4] == shard[4]
    assert queue[6] <= result[5] and result[7] == -1
    # the batch ran inside the two waits: launched after the enqueue,
    # its result delivered after its drain
    launch = next(r for r in recs if r[3] == "scheduler.launch")
    drain = next(r for r in recs if r[3] == "scheduler.drain")
    assert queue[5] <= launch[5] and drain[6] <= result[6]
    # so action.shard's self time holds neither wait
    selfs = _self_ns(recs)
    waits = (queue[6] - queue[5]) + (result[6] - result[5])
    assert selfs[shard[0]] <= (shard[6] - shard[5]) - waits
    assert selfs[result[0]] == result[6] - result[5]


def test_tracer_off_contract_holds_with_the_ring_on(fresh_ring):
    before = tracing.spans_allocated()
    t0 = time.monotonic_ns()
    with tracing.span("rest.handle", some="attr") as sp:
        assert sp.set(k=1) is sp
        with tracing.device_span("dispatch"):
            pass
        with tracing.span("not-in-the-table"):
            pass
    assert tracing.spans_allocated() == before
    recs = tracing.ring_records(t0, time.monotonic_ns())
    assert [r[3] for r in recs] == ["rest.handle", "jit.enqueue"]
    assert recs[1][1] == recs[0][0] and recs[0][2] == recs[1][2]
    # under a trace the same calls build the tree as well, under the
    # names the Profile API has always shown
    with tracing.trace("t-ring", "nR"), tracing.collect_spans() as got:
        with tracing.span("action.search"):
            with tracing.device_span("dispatch"):
                pass
    assert [r["name"] for r in got] == ["dispatch", "search"]
    assert tracing.spans_allocated() == before + 2
    assert tracing.ring_stats()["written"] == 4
    assert tracing.open_span_count("nR") == 0


def test_context_crosses_a_pool_submit(fresh_ring):
    from concurrent.futures import ThreadPoolExecutor

    from elasticsearch_tpu import tasks

    def shard():
        with tracing.span("action.shard_msearch"):
            return threading.get_ident()

    t0 = time.monotonic_ns()
    with ThreadPoolExecutor(1) as pool:
        with tracing.request() as rid, tracing.span("action.msearch"):
            other = pool.submit(tasks.bind_current(shard)).result(5)
        with tracing.span("fetch.hits"):    # outside: a request of its own
            pass
        pool.submit(shard).result(5)        # unbound: likewise
    a, b, c, d = tracing.ring_records(t0, time.monotonic_ns())
    assert (a[3], b[3]) == ("action.msearch", "action.shard_msearch")
    assert b[1] == a[0] and a[2] == b[2] == rid
    assert b[4] == other != a[4]
    assert len({rid, c[2], d[2]}) == 3 and c[1] == d[1] == 0


def test_ring_overflow_is_counted_and_a_lost_interval_reads_nothing():
    tracing.reset(ring_cap=8)
    try:
        t0 = time.monotonic_ns()
        for _ in range(8):
            with tracing.span("fetch.hits"):
                pass
        t_full = time.monotonic_ns()
        assert len(tracing.ring_records(t0, t_full)) == 8
        for _ in range(5):
            with tracing.span("jit.pack"):
                pass
        t1 = time.monotonic_ns()
        stats = tracing.ring_stats()
        assert stats == {"capacity": 8, "bytes": 8 * 64, "written": 13,
                         "overwritten": 5}
        # the first interval lost records: nothing, not the 3 left
        assert tracing.ring_records(t0, t_full) is None
        assert tracing.ring_records(t0, t1) is None
        # an interval that begins after the last lost record is whole
        got = tracing.ring_records(t_full, t1)
        assert [r[3] for r in got] == ["jit.pack"] * 5
    finally:
        tracing.reset()
    assert tracing.ring_stats()["capacity"] == tracing.RING_CAP
    assert tracing.RING_BYTES == 64 * 1024 * 1024


def test_book_starved_plus_in_flight_is_the_interval(fresh_ring):
    histograms.reset()
    t0 = time.monotonic_ns()
    time.sleep(0.002)                                   # starved
    with use_node("book-node"), tracing.launch_scope() as held:
        with tracing.device_span("dispatch"):           # launch 1
            pass
        time.sleep(0.002)
        with tracing.device_span("dispatch"):           # 2, overlapping
            pass
    assert tracing.book_stats()["launches_in_flight"] == 2
    with use_node("book-node"), tracing.span("jit.drain"):
        time.sleep(0.002)
        tracing.close_launches(held)
    time.sleep(0.002)                                   # starved again
    with tracing.device_span("upload"):                 # not a launch
        pass
    with tracing.device_span("dispatch"):               # no drain span
        time.sleep(0.001)
    t1 = time.monotonic_ns()
    book = tracing.book_stats()
    assert book["launches"] == 3 and book["launches_in_flight"] == 0
    assert book["launches_without_drain"] == 1
    assert book["starved_ns"] + book["in_flight_ns"] == book["observed_ns"]
    gaps = tracing.starved_intervals(t0, t1)
    assert len(gaps) == 3 and gaps[0][0] == t0 and gaps[-1][1] == t1
    starved = sum(b - a for a, b in gaps)
    # ≥ 2 + 2 ms starved; ≥ 4 + 1 ms flew
    assert 4e6 <= starved <= (t1 - t0) - 5e6
    assert 0 < book["starved_pct"] < 100
    # device_rtt is the launch's open → close: the two drained launches
    # flew ≥ 4 and ≥ 2 ms, the third the 1 ms of its own enqueue
    rtt = histograms.summaries("book-node")["device_rtt"]
    assert rtt["count"] == 2 and rtt["max_ms"] >= 4.0
    assert histograms.summaries("")["device_rtt"]["count"] == 1


def test_fault_inside_a_span_closes_its_record_and_its_launch(fresh_ring):
    from elasticsearch_tpu.observability import costs
    from elasticsearch_tpu.search import jit_exec

    def hook(site):
        raise RuntimeError(f"injected at {site}")

    prev = jit_exec.set_device_fault_hook(hook)
    t0 = time.monotonic_ns()
    try:
        with use_node("fault-node"), pytest.raises(RuntimeError), \
                tracing.launch_scope() as held, \
                tracing.span("plan.exact"), \
                tracing.device_span("dispatch",
                                    cost=("reader-batch", "k", 1, 1)):
            jit_exec.device_fault_point("dispatch")
    finally:
        jit_exec.set_device_fault_hook(prev)
    recs = tracing.ring_records(t0, time.monotonic_ns())
    assert [r[3] for r in recs] == ["plan.exact", "jit.enqueue"]
    assert held == []
    book = tracing.book_stats()
    assert book["launches"] == 1 and book["launches_in_flight"] == 0
    # a failed dispatch never reaches the cost table
    assert costs.table("fault-node").lookup("reader-batch", "k") is None
    # a handle nobody drains closes its launch when it is dropped
    with tracing.launch_scope() as held:
        with tracing.device_span("dispatch"):
            pass
    assert tracing.book_stats()["launches_in_flight"] == 1
    del held
    assert tracing.book_stats()["launches_in_flight"] == 0
    with pytest.raises(ValueError, match="unregistered device seam"):
        tracing.device_span("no-such-site")


@pytest.fixture
def no_automatic_collection():
    """Only the test's own ``gc.collect()`` collects, on any thread."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def test_a_full_collection_is_a_record_under_the_span_it_stopped(
        served, fresh_ring, no_automatic_collection):
    node, _call = served

    def old():
        return node.local_node_stats()["jvm"]["gc"]["collectors"]["old"]

    old0 = old()
    t0 = time.monotonic_ns()
    with tracing.request() as rid, tracing.span("fetch.hits"):
        time.sleep(0.001)
        gc.collect()
        time.sleep(0.001)
    t1 = time.monotonic_ns()
    old1 = old()
    me = threading.get_ident()
    recs = [r for r in tracing.ring_records(t0, t1) if r[4] == me]
    hits = next(r for r in recs if r[3] == "fetch.hits")
    stops = [r for r in recs if r[3] == "interp.collect"]
    assert len(stops) == 1
    stop = stops[0]
    # nested under the span it stopped, in its request, CPU not taken
    assert stop[1] == hits[0] and stop[2] == rid and stop[7] == -1
    assert hits[5] <= stop[5] < stop[6] <= hits[6]
    assert tracing.SPAN_LAYERS["interp.collect"] == "interpreter"
    # the span's self time no longer holds the stop
    assert _self_ns(recs)[hits[0]] == (hits[6] - hits[5]) - (stop[6] - stop[5])
    # ...and the operator's counters saw it
    assert old1["collection_count"] == old0["collection_count"] + 1
    assert old1["collection_time_in_millis"] >= old0[
        "collection_time_in_millis"]


def test_a_collection_under_the_rings_own_lock_is_parked_not_lost(
        no_automatic_collection):
    """The collector runs on whatever thread allocated — perhaps inside
    a wrapping ring write that holds the ring's lock. Its record must
    neither wait for that lock (a deadlock) nor be dropped."""
    tracing.reset(ring_cap=8)
    try:
        for _ in range(8):
            with tracing.span("jit.pack"):
                pass
        t_full = time.monotonic_ns()
        parked = []

        def collect_holding_the_lock():
            with tracing._ring.lock:
                gc.collect()
                parked.extend(tracing._ring.parked)

        worker = threading.Thread(target=collect_holding_the_lock)
        worker.start()
        worker.join(30)
        assert not worker.is_alive()
        assert [tracing.SPAN_NAMES[r[3]] for r in parked] == [
            "interp.collect"]
        with tracing.span("fetch.hits"):    # the next close writes it
            pass
        assert tracing._ring.parked == []
        recs = tracing.ring_records(t_full, time.monotonic_ns())
        assert [r[3] for r in recs] == ["interp.collect", "fetch.hits"]
        assert recs[0][4] == worker.ident and recs[0][1] == 0
    finally:
        tracing.reset()


def test_collections_are_counted_exactly_under_contention():
    """The per-generation integers are written by the collector's
    callback alone, one collection at a time: many threads allocating
    at a short switch interval lose no count against the interpreter's
    own."""
    import sys

    def counts():
        # both books read with the collector held still between them
        was = gc.isenabled()
        gc.disable()
        try:
            return ([g["collections"] for g in gc.get_stats()],
                    tracing.collector_stats())
        finally:
            if was:
                gc.enable()

    before, mine0 = counts()
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def churn():
            for i in range(300):
                junk = [[j] for j in range(200)]
                if i % 100 == 0:
                    gc.collect(i // 100 % 3)
                del junk

        threads = [threading.Thread(target=churn) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(prev)
    after, mine1 = counts()
    young = after[0] - before[0] + after[1] - before[1]
    assert mine1["young"]["collection_count"] \
        - mine0["young"]["collection_count"] == young > 0
    assert mine1["old"]["collection_count"] \
        - mine0["old"]["collection_count"] == after[2] - before[2] > 0


@pytest.mark.parametrize("level", [0, 1])
def test_annotations_reach_a_trace_only_at_host_tracer_level_1(
        level, tmp_path, fresh_ring):
    """``benchmarks/harness.traced_slice`` records at
    ``host_tracer_level`` 0, where a ``TraceAnnotation`` leaves no
    event; at level 1 every ring record of the session — a full
    collection's too — is in the ``.xplane.pb`` as ``es.<name>`` with
    its ``request``, at one constant offset from the ring's clock: the
    join that names a device trace's idle gaps by span."""
    import glob

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = level
    was = gc.isenabled()
    gc.disable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t0 = time.monotonic_ns()
        with tracing.request() as rid, tracing.span("fetch.hits"):
            with tracing.device_span("dispatch"):
                time.sleep(0.001)
            time.sleep(0.002)
            gc.collect()
            time.sleep(0.002)
            with tracing.span("jit.unpack"):
                time.sleep(0.001)
        t1 = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
        if was:
            gc.enable()
    me = threading.get_ident()
    recs = [r for r in tracing.ring_records(t0, t1) if r[4] == me]
    assert sorted(r[3] for r in recs) == [
        "fetch.hits", "interp.collect", "jit.enqueue", "jit.unpack"]
    found = {}
    for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True):
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("es."):
                        found.setdefault(ev.name, []).append(
                            (ev.start_ns, {k: v for k, v in ev.stats}))
    if level == 0:
        assert found == {}
        return
    assert set(found) == {f"es.{r[3]}" for r in recs}
    offsets = []
    for r in recs:
        (start, stats), = found[f"es.{r[3]}"]
        assert int(stats["request"]) == rid
        offsets.append(start - r[5])
    assert max(offsets) - min(offsets) <= 100_000


def test_every_span_name_in_the_package_is_in_the_table():
    import pathlib
    import re
    root = pathlib.Path(tracing.__file__).resolve().parents[1]
    literal = re.compile(
        r'(?:obs_trace|tracing)\.span\(\s*"([^"]+)"|[^_.\w]span\("([^"]+)"')
    used = set()
    for path in root.rglob("*.py"):
        if path.name == "tracing.py":
            continue
        for a, b in literal.findall(path.read_text()):
            used.add(a or b)
    assert len(used) >= 15 and used <= set(tracing.SPAN_LAYERS), \
        used - set(tracing.SPAN_LAYERS)
    # ...and the names that reach span() through a variable
    for name in ("plan.knn", "plan.rescore", "plan.impact", "plan.exact",
                 "action.shard", "action.shard_query",
                 "action.shard_fetch", "scheduler.launch",
                 "scheduler.drain", "interp.collect"):
        assert name in tracing.SPAN_LAYERS
    assert set(tracing.SPAN_LAYERS.values()) == {
        "rest", "action", "scheduler", "planner", "jit_exec", "fetch",
        "interpreter"}
