"""Stall tolerance (search/watchdog.py + StallScheme) — tier-1.

The hang half of the fault model, unit-level (the chaos matrix's
``stall_during_search_storm`` drives the same ladder end-to-end):

* watchdog envelope math — cost-observatory estimate × multiplier,
  floor/ceiling-clamped, with the cold-shape floor for shapes the cost
  table has never seen;
* abandon-then-failover equality: a wedged scheduler batch is
  abandoned by the watchdog, its waiters fail over to the serial path,
  and the failover results are bit-identical to the eager oracle;
* wedged-batch recovery: the scheduler survives a permanently wedged
  batch with EXACT counter reconciliation (``launched == drained +
  in_flight + abandoned``), zero leaked request-breaker bytes, and
  zero open spans once the wedge heals;
* probe-gated reopen: quarantine holds the breaker open while the
  device is wedged — probes are attempted but never reopen — and after
  ``heal()`` a FRESH successful probe program releases it;
* StallScheme seed replay: the same seed over the same touchpoint
  sequence injects identically (the PR 1 matrix discipline).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.phase import (ShardSearcher,
                                            parse_search_request)
from elasticsearch_tpu.search.scheduler import (ContinuousBatchScheduler,
                                                classify)
from elasticsearch_tpu.search.watchdog import (DispatchWatchdog,
                                               dispatch_watchdog,
                                               settings_for)
from elasticsearch_tpu.testing_disruption import StallScheme, wait_until


@pytest.fixture
def node(tmp_path):
    n = Node({}, data_path=tmp_path / "n").start()
    yield n
    n.close()


def _mk(node, name="idx", docs=96, shards=1):
    node.indices_service.create_index(
        name, {"settings": {"number_of_shards": shards,
                            "number_of_replicas": 0}})
    for i in range(docs):
        node.index_doc(name, str(i),
                       {"t": f"alpha beta word{i % 7} word{i % 11}",
                        "n": i})
    node.broadcast_actions.refresh(name)


def _searcher(node, name="idx", shard=0):
    svc = node.indices_service.indices[name]
    return ShardSearcher(shard, device_reader_for(svc.engine(shard)),
                         svc.mapper_service, index_name=name)


TINY = dict(stall_multiplier=1.0, floor_s=0.3, cold_floor_s=0.3,
            ceiling_s=0.5, tick_s=0.02, probe_interval_s=0.05,
            probe_budget_s=2.0)

_SAVE_KEYS = ("enabled", "stall_multiplier", "floor_s", "cold_floor_s",
              "ceiling_s", "quarantine_stalls", "tick_s",
              "probe_interval_s", "probe_budget_s")


@pytest.fixture
def tiny_watchdog():
    """The singleton watchdog with sub-second envelopes, restored (and
    the plane breaker reset) afterwards."""
    wd = dispatch_watchdog
    saved = {k: getattr(wd, k) for k in _SAVE_KEYS}
    try:
        yield wd
    finally:
        wd.configure(**saved)
        wd.reset()
        jit_exec.plane_breaker.reset()


# ---------------------------------------------------------------------------
# envelope math
# ---------------------------------------------------------------------------

def test_envelope_cold_shape_gets_cold_floor(monkeypatch):
    wd = DispatchWatchdog(stall_multiplier=10.0, floor_s=2.0,
                          cold_floor_s=9.0, ceiling_s=60.0)
    from elasticsearch_tpu.observability import costs
    monkeypatch.setattr(costs, "estimate",
                        lambda lane, shape_key=None, node_id=None: None)
    # no estimate → the cold floor (first wait includes trace+compile)
    assert wd.budget_s("plane", ("idx", 0)) == 9.0
    # no lane at all (coordinator-side waits) → same cold floor
    assert wd.budget_s(None) == 9.0
    # the cold floor never undercuts the plain floor
    wd.cold_floor_s = 0.5
    assert wd.budget_s("plane", ("idx", 0)) == 2.0


def test_envelope_estimate_times_multiplier_clamped(monkeypatch):
    wd = DispatchWatchdog(stall_multiplier=20.0, floor_s=1.0,
                          cold_floor_s=3.0, ceiling_s=10.0)
    from elasticsearch_tpu.observability import costs
    est = {"us": 250_000.0}            # 0.25 s predicted
    monkeypatch.setattr(
        costs, "estimate",
        lambda lane, shape_key=None, node_id=None: est["us"])
    # 0.25 s × 20 = 5 s — inside the clamp
    assert wd.budget_s("plane", ("idx", 0)) == pytest.approx(5.0)
    # a microsecond-fast program still gets the floor
    est["us"] = 5.0
    assert wd.budget_s("plane", ("idx", 0)) == 1.0
    # a monster estimate is ceiling-bounded: stalls stay observable
    est["us"] = 30_000_000.0
    assert wd.budget_s("plane", ("idx", 0)) == 10.0


def test_envelope_never_raises_through_costs(monkeypatch):
    wd = DispatchWatchdog(floor_s=1.0, cold_floor_s=4.0)
    from elasticsearch_tpu.observability import costs

    def boom(lane, shape_key=None, node_id=None):
        raise RuntimeError("cost table offline")

    monkeypatch.setattr(costs, "estimate", boom)
    assert wd.budget_s("plane", ("idx", 0)) == 4.0


# ---------------------------------------------------------------------------
# register / complete / abandon (fresh instance — no singleton bleed)
# ---------------------------------------------------------------------------

def test_abandoned_wait_escalates_and_complete_returns_false():
    wd = DispatchWatchdog(stall_multiplier=1.0, floor_s=0.15,
                          cold_floor_s=0.15, ceiling_s=0.3,
                          quarantine_stalls=99, tick_s=0.02)
    stalls: list = []
    try:
        entry = wd.register(site="dispatch", lane=None, n_real=3,
                            on_stall=stalls.append)
        assert entry is not None and entry.budget_s == \
            pytest.approx(0.15)
        assert wait_until(lambda: wd.stats()["abandoned"] >= 1,
                          timeout=5.0), wd.stats()
        # rung 2: the on_stall callback got the typed error
        assert wait_until(lambda: len(stalls) == 1, timeout=5.0)
        assert isinstance(stalls[0], jit_exec.DeviceStallError)
        assert "envelope" in str(stalls[0])
        # the late completion is told its results belong to a
        # failed-over request — discard, don't deliver
        assert wd.complete(entry) is False
        st = wd.stats()
        assert st["stalls"] == st["abandoned"] == 1, st
        assert st["consecutive_stalls"] == 1, st
        # a healthy wait completing resets the consecutive run
        ok = wd.register(site="dispatch", lane=None)
        assert wd.complete(ok) is True
        assert wd.stats()["consecutive_stalls"] == 0
        # rung 1: the stall was flight-recorded with its envelope
        from elasticsearch_tpu.observability import flightrec
        ev = [e for nid in (flightrec.node_ids() or [""])
              for e in flightrec.events(nid)
              if e["type"] == "dispatch-stall"]
        assert any(e.get("site") == "dispatch" and
                   e.get("n_real") == 3 and
                   "budget_seconds" in e for e in ev), ev[:3]
    finally:
        wd.reset()
        jit_exec.plane_breaker.reset()


def test_compile_time_is_not_device_wait():
    """A trace+compile is host work: the compile seam's bracket stops
    the clock of the waits its thread registered, so a compile longer
    than the envelope is no stall — and the envelope still applies to
    the time around it."""
    wd = DispatchWatchdog(stall_multiplier=1.0, floor_s=0.15,
                          cold_floor_s=0.15, ceiling_s=5.0,
                          quarantine_stalls=99, tick_s=0.02)
    stalls: list = []
    try:
        entry = wd.register(site="dispatch", lane=None,
                            on_stall=stalls.append)
        with wd.compiling():
            time.sleep(0.5)                 # > 3 envelopes of compile
        assert not entry.stalled and not stalls
        assert entry.compile_since is None
        assert wd.complete(entry) is True
        assert wd.stats()["stalls"] == 0
        # the clock restarts after the compile: a wedged dispatch that
        # follows it is still abandoned inside its envelope
        entry = wd.register(site="dispatch", lane=None,
                            on_stall=stalls.append)
        with wd.compiling():
            time.sleep(0.2)
        assert wait_until(lambda: len(stalls) == 1, timeout=5.0)
        assert wd.complete(entry) is False
        # another thread's compile never touches this thread's wait
        other = wd.register(site="dispatch", lane=None)
        t = threading.Thread(target=lambda: wd.compiling().__enter__())
        t.start()
        t.join()
        assert other.compile_since is None
        assert wd.complete(other) is True
    finally:
        wd.reset()
        jit_exec.plane_breaker.reset()


def test_runaway_compile_is_still_a_stall():
    """While compiling, a wait is overdue once the compile alone
    outlasts ceiling_s — the longest envelope any wait can get."""
    wd = DispatchWatchdog(stall_multiplier=1.0, floor_s=0.1,
                          cold_floor_s=0.1, ceiling_s=0.3,
                          quarantine_stalls=99, tick_s=0.02)
    stalls: list = []
    try:
        entry = wd.register(site="dispatch", lane=None,
                            on_stall=stalls.append)
        with wd.compiling():
            time.sleep(0.2)                 # past the envelope: fine
            assert not stalls
            assert wait_until(lambda: len(stalls) == 1, timeout=5.0)
        assert wd.complete(entry) is False
    finally:
        wd.reset()
        jit_exec.plane_breaker.reset()


def test_disabled_watchdog_registers_nothing():
    wd = DispatchWatchdog(enabled=False)
    assert wd.register(site="dispatch") is None
    assert wd.complete(None) is True
    assert wd.stats()["in_flight_waits"] == 0


def test_settings_parse_ms_to_seconds():
    cfg = {"search.watchdog.enabled": "true",
           "search.watchdog.multiplier": "8",
           "search.watchdog.floor_ms": "2500",
           "search.watchdog.cold_floor_ms": "7000",
           "search.watchdog.ceiling_ms": "90000",
           "search.watchdog.quarantine_stalls": "2",
           "search.watchdog.probe_interval_ms": "250",
           "search.watchdog.probe_budget_ms": "5000"}
    out = settings_for(cfg.get)
    assert out == {"enabled": True, "stall_multiplier": 8.0,
                   "floor_s": 2.5, "cold_floor_s": 7.0,
                   "ceiling_s": 90.0, "quarantine_stalls": 2,
                   "probe_interval_s": 0.25, "probe_budget_s": 5.0}
    assert settings_for({"search.watchdog.enabled": "false"}.get) \
        == {"enabled": False}


# ---------------------------------------------------------------------------
# wedged scheduler batch: abandon → failover equality + reconciliation
# ---------------------------------------------------------------------------

def test_wedged_batch_abandon_failover_and_recovery(node, tiny_watchdog):
    _mk(node)
    s = _searcher(node)
    reqs = [parse_search_request(
        {"query": {"match": {"t": f"alpha word{i % 7}"}}, "size": 10})
        for i in range(6)]
    # the eager oracle, BEFORE any disruption
    refs = [s.query_phase(r) for r in reqs]
    tiny_watchdog.configure(quarantine_stalls=99, **TINY)
    base_abandoned = tiny_watchdog.stats()["abandoned"]
    sched = ContinuousBatchScheduler(node_id=node.node_id, max_batch=8,
                                     max_in_flight=2)
    scheme = StallScheme(seed=4242, p_by_site={"dispatch": 1.0},
                         delay_range=None)    # permanent wedge
    outs: dict = {}
    errs: list = []

    def client(i):
        try:
            lane, shape = classify(reqs[i], s)
            outs[i] = sched.execute(
                lane, ("idx", 0, lane, shape, id(s.reader)),
                reqs[i], s.query_phase_batch_launch,
                s.query_phase_batch_drain)
        except Exception as e:          # noqa: BLE001 — surfaced below
            errs.append((i, repr(e)))

    try:
        with scheme.applied():
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(reqs))]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            waited = time.perf_counter() - t0
            assert not any(t.is_alive() for t in threads), \
                "a client stayed wedged past the watchdog envelope"
            # bounded latency: every waiter was abandoned well inside
            # the ceiling + scheduling slack, not EXECUTE_BACKSTOP_S
            assert waited < 15.0, waited
            assert not errs, errs
            assert scheme.holding >= 1, \
                "the wedge never held a worker — nothing was tested"
            st = tiny_watchdog.stats()
            assert st["abandoned"] > base_abandoned, st
            scheme.heal()               # release the wedged worker(s)
        # every abandoned waiter came back DECLINED → serial failover;
        # the failover result must equal the eager oracle bit-exactly
        assert sorted(outs) == list(range(len(reqs)))
        assert any(outs[i] is None for i in outs), \
            "no waiter was actually abandoned to the serial path"
        for i, out in outs.items():
            got = out if out is not None else s.query_phase(reqs[i])
            assert got.total == refs[i].total, i
            assert np.array_equal(got.doc_ids, refs[i].doc_ids), i
            assert np.array_equal(got.scores, refs[i].scores), i
        # exact batch books: the wedged batch left them exactly once
        assert wait_until(
            lambda: sched.stats()["batches_in_flight"] == 0
            and sched.stats()["in_flight_requests"] == 0, timeout=15.0), \
            sched.stats()
        st = sched.stats()
        assert st["batches_abandoned"] >= 1, st
        assert st["batches_launched"] == st["batches_drained"] \
            + st["batches_in_flight"] + st["batches_abandoned"], st
        assert st["shed_reasons"].get("device-stall", 0) >= 1, st
        assert st["reconciled"], st
        # nothing leaked: request-breaker bytes and spans drain to zero
        assert wait_until(
            lambda: node.breaker_service.breaker("request").used == 0,
            timeout=15.0), node.breaker_service.breaker("request").used
        from elasticsearch_tpu.observability import tracing as obs_trace
        assert wait_until(
            lambda: obs_trace.open_span_count(node.node_id) == 0,
            timeout=15.0), obs_trace.store_stats(node.node_id)
        # the scheduler still serves after recovery
        lane, shape = classify(reqs[0], s)
        out = sched.execute(lane, ("idx", 0, lane, shape, id(s.reader)),
                            reqs[0], s.query_phase_batch_launch,
                            s.query_phase_batch_drain)
        got = out if out is not None else s.query_phase(reqs[0])
        assert got.total == refs[0].total
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# quarantine: breaker held open, reopen gated on a fresh probe
# ---------------------------------------------------------------------------

def test_quarantine_reopens_only_via_probe_after_heal(tiny_watchdog):
    wd = tiny_watchdog
    wd.configure(quarantine_stalls=1, **TINY)
    base = wd.stats()
    scheme = StallScheme(seed=7, p_by_site={"dispatch": 1.0},
                         delay_range=None)
    with scheme.applied():
        # one stalled wait trips straight into quarantine
        wd.register(site="dispatch", lane=None, on_stall=lambda e: None)
        assert wait_until(lambda: wd.stats()["quarantined"],
                          timeout=10.0), wd.stats()
        assert jit_exec.plane_breaker.allow() is False
        assert wd.stats()["quarantines"] == base["quarantines"] + 1
        # probes run while wedged — and wedge too: no reopen. The probe
        # routes through the SAME fault seam as live traffic, so the
        # scheme holds it at its dispatch touchpoint.
        assert wait_until(
            lambda: wd.stats()["probes_attempted"]
            > base["probes_attempted"], timeout=10.0), wd.stats()
        st = wd.stats()
        assert st["quarantined"], st
        assert st["probe_reopens"] == base["probe_reopens"], st
        assert jit_exec.plane_breaker.allow() is False
        # heal: held probe releases, and ONLY a fresh successful probe
        # completion lifts the quarantine
        scheme.heal()
        assert wait_until(lambda: not wd.stats()["quarantined"],
                          timeout=15.0), wd.stats()
        st = wd.stats()
        assert st["probe_reopens"] > base["probe_reopens"], st
        assert st["consecutive_stalls"] == 0, st
        assert jit_exec.plane_breaker.allow() is True
    from elasticsearch_tpu.observability import flightrec
    phases = [e.get("phase") for nid in (flightrec.node_ids() or [""])
              for e in flightrec.events(nid)
              if e["type"] == "quarantine"]
    assert "enter" in phases and "probe-reopen" in phases, phases


# ---------------------------------------------------------------------------
# StallScheme: seed replay + heal releases held threads
# ---------------------------------------------------------------------------

def _drive(scheme, sequence):
    with scheme.applied():
        for site in sequence:
            jit_exec.device_fault_point(site)
    return dict(calls_by_site=dict(scheme.calls_by_site),
                injected=dict(scheme.injected), calls=scheme.calls)


def test_stall_scheme_seed_replay():
    sequence = (["dispatch", "upload", "compose", "percolate"] * 12
                + ["compile", "plane-dispatch"] * 6)
    a = _drive(StallScheme(seed=99173, p=0.5,
                           delay_range=(0.0, 0.002)), sequence)
    b = _drive(StallScheme(seed=99173, p=0.5,
                           delay_range=(0.0, 0.002)), sequence)
    assert a == b, (a, b)
    assert sum(a["injected"].values()) >= 1, a
    # a different seed draws a different hold pattern (overwhelmingly)
    c = _drive(StallScheme(seed=99174, p=0.5,
                           delay_range=(0.0, 0.002)), sequence)
    assert a["calls"] == c["calls"] == len(sequence)
    assert a["injected"] != c["injected"], a["injected"]


def test_stall_scheme_heal_releases_wedged_threads():
    scheme = StallScheme(seed=3, p_by_site={"upload": 1.0},
                         delay_range=None)
    released: list = []
    with scheme.applied():
        def wedged():
            jit_exec.device_fault_point("upload")
            released.append(True)

        t = threading.Thread(target=wedged, daemon=True)
        t.start()
        assert wait_until(lambda: scheme.holding == 1, timeout=5.0)
        assert not released
        scheme.heal()
        t.join(5.0)
        assert released and scheme.holding == 0
    assert scheme.injected == {"upload": 1}
