"""The span readers (``span_common`` and the eleven ``layer_metrics`` files
that read through it) on a hand-worked span list kept beside the recorded
trace (``data/recorded_spans.json``), and against the program's own ring
for what no list can show: an empty, an overwritten and a missing ring
all read as nothing."""

import json
import time
import types
from pathlib import Path

import pytest

from benchmarks import harness
from benchmarks import span_common as sc

DOC = json.loads((Path(__file__).parent / "data"
                  / "recorded_spans.json").read_text())
TICK = DOC["tick_ns"]
T0, T1 = (t * TICK for t in DOC["slice"])
WANT = DOC["expected"]


def scaled():
    recs = [(s, p, rid, name, tid, a * TICK, b * TICK,
             cpu * TICK if cpu >= 0 else -1)
            for s, p, rid, name, tid, a, b, cpu in DOC["records"]]
    gaps = [(max(a * TICK, T0), min(b * TICK, T1)) for a, b in DOC["starved"]]
    return recs, gaps


def make_ctx(analysis="worked"):
    recs, gaps = scaled()

    def lanes(n):
        return {"lanes": {"reader-batch": {"dispatches": n}}}

    ctx = {"traced": {"t0": T0 / 1e9, "t1": T1 / 1e9, "before": lanes(7),
                      "after": lanes(7 + DOC["dispatches"])},
           "records": DOC["generator_records"],
           "cell": types.SimpleNamespace(
               spec={"expected_lanes": ["reader-batch"]})}
    if analysis == "worked":
        ctx["_span_analysis"] = sc.analyse(sc.cut(recs, T0, T1), gaps, T0, T1)
    elif analysis is None:
        ctx["_span_analysis"] = None
    return ctx


def read(metric, ctx):
    mod = harness.load_module(harness.reader_file("layer_metrics", metric),
                              f"check_{metric}")
    return mod.read(ctx)


def test_the_cut_at_the_slices_ends():
    recs, _ = scaled()
    kept = {r[0]: r for r in sc.cut(recs, T0, T1)}
    assert len(kept) == 15
    assert kept[1][5:7] == (T0, 1100 * TICK)        # rest.read, by the start
    assert kept[1][7] == pytest.approx(50 * TICK)   # half its CPU with it
    assert kept[3][7] == -1                         # "not taken" stays so
    assert kept[13][5:7] == (8000 * TICK, T1)       # rest.handle, by the end
    assert kept[13][7] == pytest.approx(200 * TICK * 1000 / 1500)
    assert kept[5] == recs[4]                       # inside: untouched
    # a record that only touches the slice's edge from outside is not in it
    assert sc.cut([(1, 0, 1, "rest.read", 1, 0, T0, 5)], T0, T1) == []
    assert sc.cut(recs, 20000 * TICK, 30000 * TICK) == []


def test_self_time_with_nested_and_cross_thread_children():
    recs, _ = scaled()
    selfs = sc.self_times(sc.cut(recs, T0, T1))
    for seq, ticks in WANT["self_ticks"].items():
        assert selfs[int(seq)] == ticks * TICK, seq
    # the children of one span that overlap (two shards at once) are
    # taken out once, not twice
    both = [(1, 0, 1, "action.msearch_group", 1, 0, 100, 10),
            (2, 1, 1, "action.shard_msearch", 2, 10, 60, 50),
            (3, 1, 1, "action.shard_msearch", 3, 40, 90, 50)]
    assert sc.self_times(both)[1] == 20


def test_cpu_is_known_per_thread_stretch():
    """The outermost span of a thread took the thread's CPU; its work is
    the self time of what does not block by design under it on that
    thread. ``rest.read`` and ``rest.write`` are waits through and
    through, ``action.msearch`` and ``jit.drain`` are left out of the
    stretches they lie in."""
    recs, gaps = scaled()
    an = sc.analyse(sc.cut(recs, T0, T1), gaps, T0, T1)
    got = {name: [cnt, work / TICK, cpu / TICK]
           for name, (cnt, work, cpu) in an["threads"].items()}
    assert got == {name: pytest.approx(want) for name, want
                   in WANT["stretches_ticks"].items()}


def test_starved_time_goes_to_the_open_span_with_no_open_child():
    recs, gaps = scaled()
    an = sc.analyse(sc.cut(recs, T0, T1), gaps, T0, T1)
    assert an["starved_ns"] == WANT["starved_ticks"] * TICK
    got = {name or "unnamed": ns / TICK for name, ns in an["starved"].items()}
    assert got == pytest.approx(WANT["starved_by_name_ticks"])
    assert sum(got.values()) == pytest.approx(WANT["starved_ticks"])
    # two threads at once share the stretch equally: fetch.hits on the
    # search pool beside another request's rest.serialise
    shares = sc.leaf_shares(sc.cut(recs, T0, T1), 4000 * TICK, 4900 * TICK)
    assert shares == {"fetch.hits": 450 * TICK, "rest.serialise": 450 * TICK}
    # the longest stretch first, with what was open in it
    assert an["worst"][0][0] == 5100 * TICK and an["gaps"] == 2


@pytest.mark.parametrize("metric, want", [
    ("rest_self_ms_per_query.tput", WANT["layer_self_ticks"]["rest."]),
    ("action_self_ms_per_query.tput", WANT["layer_self_ticks"]["action."]),
    ("fetch_self_ms_per_query.tput", WANT["layer_self_ticks"]["fetch."]),
    ("jit_exec_host_ms_per_query.tput",
     WANT["layer_self_ticks"]["jit_host"]),
    ("host_wait_ms_per_query.tput", WANT["host_wait_ticks"]),
])
def test_per_query_readers(metric, want):
    assert sc.queries_in_slice(make_ctx()) == pytest.approx(WANT["queries"])
    assert read(metric, make_ctx()) == pytest.approx(
        want * TICK / 1e6 / WANT["queries"])


@pytest.mark.parametrize("metric, want", [
    ("device_starved_pct.tput", WANT["starved_pct"]["all"]),
    ("starved_in_rest_pct.tput", WANT["starved_pct"]["rest."]),
    ("starved_in_action_pct.tput", WANT["starved_pct"]["action."]),
    ("starved_in_fetch_pct.tput", WANT["starved_pct"]["fetch."]),
    ("starved_unnamed_pct.tput", WANT["starved_pct"]["unnamed"]),
    ("drain_wait_ms_per_dispatch.tput",
     WANT["drain_ticks"] * TICK / 1e6 / DOC["dispatches"]),
])
def test_share_readers(metric, want):
    assert read(metric, make_ctx()) == pytest.approx(want)


def test_every_new_metric_has_its_reader_and_reads_nothing_from_nothing():
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    # the rule, not a count: every metric read from the program's spans
    # or counters has its reader file, moves an end-to-end metric, and
    # reads nothing from nothing; ``better`` is whatever the metric says
    new = [m for m in bench["per_layer"]
           if m["source"] in ("program_span", "program_counter")]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert new
    for m in new:
        assert m["moves"] in end_to_end
        assert m["better"] in ("lower", "higher")
        assert harness.reader_file("layer_metrics", m["name"]).name \
            == m["name"].rsplit(".", 1)[0] + ".py"
        # no analysis (no ring, an untraced run, a slice no longer held)
        assert read(m["name"], make_ctx(analysis=None)) is None, m["name"]
        assert read(m["name"], {"traced": None, "records": []}) is None
    # queries but no span of the kind, or spans but no query: nothing
    ctx = make_ctx()
    ctx["_span_analysis"]["by_name"].pop("fetch.hits")
    assert read("fetch_self_ms_per_query.tput", ctx) is None
    ctx["records"] = []
    assert read("rest_self_ms_per_query.tput", ctx) is None


def ring_ctx(t0, t1):
    return {"traced": {"t0": t0 / 1e9, "t1": t1 / 1e9}, "records": []}


def test_the_programs_ring_empty_overwritten_or_missing_reads_nothing(
        monkeypatch, capsys):
    from elasticsearch_tpu.observability import tracing
    if not hasattr(tracing, "ring_records"):
        # these files laid over a program from before the ring
        now = time.monotonic_ns()
        assert sc.analysis(ring_ctx(now - 10 ** 9, now)) is None
        return
    tracing.reset(ring_cap=8)
    try:
        t0 = time.monotonic_ns()
        assert sc.analysis(ring_ctx(t0, t0 + 1000)) is None      # empty
        with tracing.span("rest.handle"):
            with tracing.launch_scope() as held:
                with tracing.device_span("dispatch"):
                    pass
            with tracing.span("jit.drain"):
                time.sleep(0.002)
            tracing.close_launches(held)
            with tracing.span("fetch.hits"):
                time.sleep(0.002)
        t1 = time.monotonic_ns()
        an = sc.analysis(ring_ctx(t0, t1))
        assert set(an["by_name"]) == {"rest.handle", "jit.enqueue",
                                      "jit.drain", "fetch.hits"}
        assert an["starved"]["fetch.hits"] >= 2e6
        assert 0 < an["starved_ns"] < t1 - t0 - 2e6
        assert "device starved" in capsys.readouterr().err
        # a program from before the ring: nothing, and no error
        with monkeypatch.context() as m:
            m.delattr(tracing, "ring_records")
            assert sc.analysis(ring_ctx(t0, t1)) is None
        for _ in range(8):                                       # overflow
            with tracing.span("jit.pack"):
                pass
        assert tracing.ring_stats()["overwritten"] == 4
        assert sc.analysis(ring_ctx(t0, t1)) is None
        assert read("device_starved_pct.tput", ring_ctx(t0, t1)) is None
    finally:
        tracing.reset()
