"""Continuous-batching device scheduler (search/scheduler.py) — tier-1.

Acceptance pins:

* scheduler results are BIT-IDENTICAL to the unscheduled path (fuzz:
  the same requests through concurrent ``scheduler.execute`` vs direct
  ``query_phase_batch``);
* padded batches never double-deliver or double-count lane stats (the
  pad_to_bucket fix: pad rows are no-op replicas excluded via n_real);
* shedding — queue-deadline back to the serial path, SLO-burn as a
  typed 429 (:class:`SchedulerRejectedError`), queue capacity — with
  every shed reason-labeled in the registered ``scheduler`` vocabulary;
* weighted-fair pickup: a low-rate lane is never starved by a storm;
* counters reconcile at every sample and surface through
  ``_nodes/stats.scheduler`` / ``_cat/thread_pool`` / the exporter;
* the LIVE path routes concurrent single-search traffic through the
  scheduler (fan-out shard execution) and stays correct.
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
from elasticsearch_tpu.search.scheduler import (
    ContinuousBatchScheduler, SchedulerRejectedError, classify,
    settings_for)


@pytest.fixture
def node(tmp_path):
    n = Node({}, data_path=tmp_path / "n").start()
    yield n
    n.close()


def _mk(node, name="idx", docs=120, shards=1):
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


# ---------------------------------------------------------------------------
# bit-identity fuzz: scheduler vs direct query_phase_batch
# ---------------------------------------------------------------------------

def test_scheduler_bit_identical_to_direct_batch(node):
    _mk(node)
    s = _searcher(node)
    rng = np.random.default_rng(20260804)
    reqs = []
    for _ in range(24):
        terms = " ".join(
            f"word{rng.integers(0, 13)}"
            for _ in range(int(rng.integers(1, 3))))
        reqs.append(parse_search_request(
            {"query": {"match": {"t": f"alpha {terms}"}},
             "size": int(rng.integers(1, 20))}))
    refs = [s.query_phase_batch([r]) for r in reqs]
    sched = ContinuousBatchScheduler(node_id=node.node_id, max_batch=8,
                                     max_in_flight=2)
    try:
        outs: dict = {}
        errs: list = []

        def client(i):
            try:
                lane, shape = classify(reqs[i], s)
                assert lane == "plane"
                out = sched.execute(
                    lane, ("idx", 0, lane, shape, id(s.reader)),
                    reqs[i], s.query_phase_batch_launch,
                    s.query_phase_batch_drain)
                outs[i] = out if out is not None \
                    else s.query_phase(reqs[i])
            except Exception as e:     # noqa: BLE001 — surfaced below
                errs.append(e)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errs, errs[:3]
        assert len(outs) == len(reqs)
        for i, ref in enumerate(refs):
            got, want = outs[i], ref[0]
            assert got.total == want.total
            np.testing.assert_array_equal(got.doc_ids, want.doc_ids)
            np.testing.assert_array_equal(
                np.asarray(got.scores), np.asarray(want.scores))
        st = sched.stats()
        assert st["reconciled"], st
        assert st["delivered"] == len(reqs)
        # concurrency actually coalesced: fewer batches than requests
        assert st["batches_launched"] <= len(reqs)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# pad_to_bucket fix: no double delivery, no double counting
# ---------------------------------------------------------------------------

def test_padded_batch_single_delivery_and_exact_counts():
    launches: list = []
    gate = threading.Event()

    def launch(reqs, n_real=None):
        launches.append((list(reqs), n_real))
        return list(reqs[:n_real])

    def drain(handle):
        gate.wait(5)
        return [r * 10 for r in handle]

    sched = ContinuousBatchScheduler(node_id=None, max_batch=4,
                                     max_in_flight=1)
    js0 = jit_exec.cache_stats()
    try:
        f_a = sched.submit("plane", "k", 1, launch, drain)
        # the first pickup takes req 1 alone and BLOCKS in drain (the
        # one in-flight slot): the next three queue and form one batch
        for _ in range(100):
            if launches:
                break
            time.sleep(0.01)
        fs = [sched.submit("plane", "k", r, launch, drain)
              for r in (2, 3, 4)]
        gate.set()
        assert f_a.future.result(5) == 10
        assert [f.future.result(5) for f in fs] == [20, 30, 40]
        # batch 2 carried 3 real rows padded to the pow2 bucket (4),
        # with the FIRST request replicated — never another queued one
        assert len(launches) == 2
        reqs2, n_real2 = launches[1]
        assert n_real2 == 3 and reqs2 == [2, 3, 4, 2]
        js1 = jit_exec.cache_stats()
        assert js1["scheduler_requests_admitted"] - \
            js0["scheduler_requests_admitted"] == 4
        assert js1["scheduler_pad_rows"] - js0["scheduler_pad_rows"] == 1
        st = sched.stats()
        assert st["delivered"] == 4 and st["reconciled"], st
    finally:
        gate.set()
        sched.close()


def test_n_real_excludes_pad_rows_from_lane_stats(node):
    """The launch-layer contract the scheduler/batcher rely on: a
    padded knn batch counts only its REAL rows in knn_admissions."""
    node.indices_service.create_index(
        "vec", {"settings": {"number_of_shards": 1,
                             "number_of_replicas": 0},
                "mappings": {"doc": {"properties": {
                    "v": {"type": "dense_vector", "dims": 4}}}}})
    for i in range(8):
        node.index_doc("vec", str(i),
                       {"v": [float(i), 1.0, 0.0, 0.5]})
    node.broadcast_actions.refresh("vec")
    s = _searcher(node, "vec")
    req = parse_search_request(
        {"knn": {"field": "v", "query_vector": [1.0, 0.5, 0.0, 0.2],
                 "k": 3, "num_candidates": 8}, "size": 3})
    js0 = jit_exec.cache_stats()
    handle = s.query_phase_batch_launch([req, req, req, req], n_real=3)
    assert handle is not None
    out = s.query_phase_batch_drain(handle)
    assert len(out) >= 3
    js1 = jit_exec.cache_stats()
    assert js1["knn_admissions"] - js0["knn_admissions"] == 3


# ---------------------------------------------------------------------------
# shedding
# ---------------------------------------------------------------------------

def test_queue_deadline_shed_declines_to_serial():
    gate = threading.Event()

    def launch(reqs, n_real=None):
        return list(reqs)

    def drain(handle):
        gate.wait(5)
        return list(handle)

    sched = ContinuousBatchScheduler(node_id=None, max_batch=4,
                                     max_in_flight=1,
                                     max_queue_wait_s=0.05)
    js0 = jit_exec.cache_stats()
    try:
        first = sched.submit("plane", "k", 0, launch, drain)
        time.sleep(0.05)                 # first batch holds the window
        late = sched.submit("plane", "k", 1, launch, drain)
        time.sleep(0.15)                 # out-waits max_queue_wait_s
        gate.set()
        assert first.future.result(5) == 0
        from elasticsearch_tpu.search.scheduler import DECLINED
        assert late.future.result(5) is DECLINED
        st = sched.stats()
        assert st["shed_reasons"].get("queue-deadline") == 1, st
        assert st["reconciled"], st
        js1 = jit_exec.cache_stats()
        assert js1["scheduler_shed_reasons"].get("queue-deadline", 0) > \
            js0["scheduler_shed_reasons"].get("queue-deadline", 0)
    finally:
        gate.set()
        sched.close()


def _backlogged_scheduler(nid, **kw):
    """Scheduler whose one in-flight slot is held by a blocked drain
    and whose queue carries a waiter — the load evidence SLO-burn
    shedding requires. → (scheduler, release gate, [waiters])."""
    gate = threading.Event()

    def launch(reqs, n_real=None):
        return list(reqs)

    def drain(handle):
        gate.wait(10)
        return list(handle)

    sched = ContinuousBatchScheduler(node_id=nid, max_batch=1,
                                     max_in_flight=1, **kw)
    ws = [sched.submit("plane", "bk", 100, launch, drain)]
    time.sleep(0.05)                     # first batch holds the window
    ws.append(sched.submit("plane", "bk", 101, launch, drain))
    return sched, gate, ws


def test_slo_burn_shed_is_typed_429():
    """Real queue waits past the 50 ms queue_wait target burn the
    window; SUSTAINED burn (two consecutive windows) plus a backlog
    sheds admission with a typed 429 — one burning window alone (a
    transient compile burst) does not."""
    holder = {"gate": threading.Event()}

    def launch(reqs, n_real=None):
        return list(reqs)

    def drain(handle):
        holder["gate"].wait(10)
        return list(handle)

    sched = ContinuousBatchScheduler(node_id="sched-slo-test",
                                     max_batch=1, max_in_flight=1,
                                     shed_threshold=2.0)
    try:
        levels = []
        for burst in range(2):
            # 20 waiters out-wait the 50 ms target behind a blocked
            # in-flight window → the scheduler's queue-wait book burns
            holder["gate"] = threading.Event()
            ws = [sched.submit("plane", "k", i, launch, drain)
                  for i in range(21)]
            time.sleep(0.08)
            holder["gate"].set()
            for w in ws:
                assert w.future.result(10) is not None
            sched._shed_at = 0.0         # bypass the 1/s gate throttle
            levels.append(sched._shed_gate())
        # hysteresis: the first burning window sheds nothing, the
        # second (sustained) opens the gate at the top level
        assert levels[0] == 0 and levels[1] == 3, levels
        # with a backlog present, admission now sheds with the 429
        holder["gate"] = threading.Event()
        sched.submit("plane", "k", 100, launch, drain)
        time.sleep(0.05)
        sched.submit("plane", "k", 101, launch, drain)
        with pytest.raises(SchedulerRejectedError) as ei:
            sched.submit("plane", "k", 0, launch, drain)
        assert ei.value.status == 429
        assert ei.value.reason == "slo-shed"
        st = sched.stats()
        assert st["shed_reasons"].get("slo-shed") == 1
    finally:
        holder["gate"].set()
        sched.close()


def test_shed_priority_order_lowest_first():
    """At shed level 1 only priority ≤ 1 lanes (percolate) shed; plane
    keeps serving — lowest-priority work sheds first."""
    sched, gate, ws = _backlogged_scheduler("sched-prio-test")
    sched._shed_level = 1                 # gate forced; recompute throttled
    sched._shed_at = time.monotonic() + 60
    try:
        with pytest.raises(SchedulerRejectedError):
            sched.submit("percolate", "p", 0, lambda items: items)
        w = sched.submit("plane", "k", 1,
                         lambda reqs, n_real=None: list(reqs),
                         lambda handle: list(handle))
        gate.set()
        assert w.future.result(5) == 1
        for prior in ws:
            assert prior.future.result(5) is not None
    finally:
        gate.set()
        sched.close()


def test_queue_full_shed_is_typed_429():
    gate = threading.Event()

    def launch(reqs, n_real=None):
        return list(reqs)

    def drain(handle):
        gate.wait(5)
        return list(handle)

    sched = ContinuousBatchScheduler(node_id=None, max_batch=1,
                                     max_in_flight=1, max_queue=2)
    try:
        sched.submit("plane", "k", 0, launch, drain)
        time.sleep(0.05)                 # batch 1 in flight
        sched.submit("plane", "k", 1, launch, drain)
        sched.submit("plane", "k", 2, launch, drain)
        with pytest.raises(SchedulerRejectedError) as ei:
            sched.submit("plane", "k", 3, launch, drain)
        assert ei.value.status == 429 and ei.value.reason == "queue-full"
    finally:
        gate.set()
        sched.close()


# ---------------------------------------------------------------------------
# when a batch may leave the queue: the hold behind a staged batch
# ---------------------------------------------------------------------------

WAIT_S = 10.0


class _GatedDevice:
    """Fake ``launch`` / ``drain`` pair: every launch is recorded and
    announced, every drain blocks until the test releases that batch
    (``release()`` without a batch: every one, those to come too)."""

    def __init__(self):
        self.launched: list = []        # the real rows of each launch
        self.gates: list = []
        self._open = False
        self._cv = threading.Condition()

    def launch(self, reqs, n_real=None):
        rows = list(reqs[:n_real])
        with self._cv:
            gate = threading.Event()
            if self._open:
                gate.set()
            self.launched.append(rows)
            self.gates.append(gate)
            self._cv.notify_all()
        return gate, rows

    def drain(self, handle):
        gate, rows = handle
        assert gate.wait(WAIT_S)
        return [r * 10 for r in rows]

    def wait_launches(self, n: int) -> None:
        with self._cv:
            assert self._cv.wait_for(lambda: len(self.launched) >= n,
                                     WAIT_S), self.launched

    def release(self, *batches) -> None:
        with self._cv:
            self._open = self._open or not batches
            gates = [self.gates[b] for b in batches] if batches \
                else list(self.gates)
        for gate in gates:
            gate.set()


def _reconciled(sched) -> dict:
    st = sched.stats()
    assert st["reconciled"], st
    return st


def _until(sched, key: str, value) -> dict:
    """Poll the scheduler's own book until it reads ``value``."""
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline:
        st = _reconciled(sched)
        if st[key] == value:
            return st
        time.sleep(0.001)
    raise AssertionError(f"{key} never read {value}: {sched.stats()}")


@pytest.mark.parametrize("case", [
    "idle-lone-request", "held-until-a-drain", "full-batch-leaves",
    "abandon-ends-the-hold", "close-during-a-hold", "one-in-flight",
    "slow-launch-deepens"])
def test_a_non_full_batch_is_held_behind_a_staged_one(case):
    from elasticsearch_tpu.search.scheduler import DECLINED
    dev = _GatedDevice()
    states: list = []
    sched = ContinuousBatchScheduler(
        node_id=None, max_batch=4,
        max_in_flight={"one-in-flight": 1,
                       "slow-launch-deepens": 8}.get(case, 4))
    run_pipelined = sched._run_pipelined

    def spy(q, live, runner, reqs, state, t_formed):
        states.append(state)
        run_pipelined(q, live, runner, reqs, state, t_formed)
    sched._run_pipelined = spy

    def submit(row):
        w = sched.submit("knn", ("hold", case), row, dev.launch, dev.drain)
        _reconciled(sched)
        return w
    try:
        # an idle scheduler serves a lone request at once, and the next
        # one behind it: with fewer than two undrained nothing is held
        ws = [submit(1)]
        dev.wait_launches(1)
        if case == "idle-lone-request":
            st = _reconciled(sched)
            assert st["batches_held"] == 0 and st["hold_ms"] == 0
            dev.release()
            assert ws[0].future.result(WAIT_S) == 10
            _until(sched, "delivered", 1)
            return
        if case == "one-in-flight":
            # the window of one holds everything, as before: the rule
            # never engages
            ws += [submit(2), submit(3)]
            _until(sched, "queue_depth", 2)
            assert dev.launched == [[1]]
            dev.release(0)
            dev.wait_launches(2)
            assert dev.launched == [[1], [2, 3]]
            dev.release()
            assert [w.future.result(WAIT_S) for w in ws] == [10, 20, 30]
            st = _until(sched, "delivered", 3)
            assert st["batches_held"] == 0 and st["hold_ms"] == 0
            return
        if case == "slow-launch-deepens":
            # a lane whose launch takes 2.5 device times needs three
            # staged behind the one that runs: the hold starts at four
            sched._pace["knn"] = [25.0, 0.0, 10.0]
            assert sched.stats()["pace"]["knn"]["staged_depth"] == 4
        for row in range(2, 5 if case == "slow-launch-deepens" else 3):
            ws.append(submit(row))
            dev.wait_launches(row)
        assert dev.launched == [[w.req] for w in ws]
        # one runs, the others are staged — the next row waits
        ws.append(submit(len(ws) + 1))
        _until(sched, "batches_held", 1)
        if case == "held-until-a-drain":
            ws += [submit(4), submit(5)]
            st = _until(sched, "queue_depth", 3)
            assert st["batches_launched"] == 2 and len(dev.launched) == 2
            dev.release(0)
            dev.wait_launches(3)
            assert dev.launched[2] == [3, 4, 5]     # ONE batch of three
            st = _until(sched, "batches_launched", 3)
            assert st["batches_held"] == 1 and st["hold_ms"] > 0
        elif case == "slow-launch-deepens":
            assert len(dev.launched) == 4
        elif case == "full-batch-leaves":
            ws += [submit(4), submit(5), submit(6)]
            dev.wait_launches(3)                    # no drain was released
            assert dev.launched[2] == [3, 4, 5, 6]
            st = _until(sched, "batches_launched", 3)
            assert st["batches_drained"] == 0 and st["batches_held"] == 1
        elif case == "abandon-ends-the-hold":
            assert sched._abandon_batch(states[0])  # the watchdog's path
            dev.wait_launches(3)
            assert dev.launched[2] == [3]
            assert ws.pop(0).future.result(WAIT_S) is DECLINED
            st = _until(sched, "batches_launched", 3)
            assert st["batches_abandoned"] == 1 and st["hold_ms"] > 0
        elif case == "close-during-a-hold":
            closer = threading.Thread(target=sched.close)
            closer.start()
            closer.join(WAIT_S)
            assert not closer.is_alive()
            sched._dispatcher.join(WAIT_S)
            assert not sched._dispatcher.is_alive()
            assert ws.pop().future.result(WAIT_S) is DECLINED
            st = _reconciled(sched)
            assert st["shed_reasons"] == {"closed": 1}, st
            assert st["batches_launched"] == 2 and st["hold_ms"] > 0
        dev.release()
        assert [w.future.result(WAIT_S) for w in ws] \
            == [w.req * 10 for w in ws]
        _until(sched, "delivered", len(ws))
    finally:
        dev.release()
        sched.close()


@pytest.mark.parametrize("fault", [
    "launch-returns-none", "launch-raises", "drain-returns-none",
    "drain-raises"])
def test_launch_and_drain_outcomes_reach_every_waiter(fault):
    """A batch whose launch or drain declines (None) or raises sends
    EVERY waiter back to its serial path (:data:`DECLINED` — the error
    stays with the worker: "serial retry owns it"), leaves the window,
    and the book reconciles; the scheduler then serves the next batch."""
    from elasticsearch_tpu.search.scheduler import DECLINED
    dev = _GatedDevice()
    armed = [True]

    def launch(reqs, n_real=None):
        handle = dev.launch(reqs, n_real)
        if armed[0] and fault == "launch-raises":
            raise RuntimeError("launch failed")
        return None if armed[0] and fault == "launch-returns-none" \
            else handle

    def drain(handle):
        out = dev.drain(handle)
        if armed[0] and fault == "drain-returns-none":
            return None
        if armed[0] and fault == "drain-raises":
            raise RuntimeError("drain failed")
        return out

    sched = ContinuousBatchScheduler(node_id=None, max_batch=4,
                                     max_in_flight=1)
    try:
        # the window of one: the first batch blocks in its drain, the
        # next three rows queue behind it and leave as ONE batch
        # (a queue keeps its creator's callables, so the healthy first
        # request has a key of its own)
        first = sched.submit("plane", ("outcome", fault, "first"), 1,
                             dev.launch, dev.drain)
        dev.wait_launches(1)
        ws = [sched.submit("plane", ("outcome", fault), r, launch, drain)
              for r in (2, 3, 4)]
        _until(sched, "queue_depth", 3)
        dev.release()
        assert first.future.result(WAIT_S) == 10
        assert [w.future.result(WAIT_S) for w in ws] == [DECLINED] * 3
        assert dev.launched == [[1], [2, 3, 4]]
        st = _until(sched, "declined", 3)
        assert st["batches_in_flight"] == 0 and st["queue_depth"] == 0
        assert st["in_flight_requests"] == 0 and st["delivered"] == 1
        assert st["batches_launched"] == st["batches_drained"] == 2
        assert st["shed"] == 0 and st["batches_abandoned"] == 0
        # the fault was the batch's own: the next one is served
        armed[0] = False
        assert sched.execute("plane", ("outcome", fault), 5, launch,
                             drain) == 50
        st = _until(sched, "delivered", 2)
        assert st["batches_in_flight"] == 0 and st["declined"] == 3
    finally:
        dev.release()
        sched.close()


def test_staged_depth_follows_the_measured_launch_and_device_times():
    """The depth's means, by hand: the device is one FIFO server, so a
    batch ran from its enqueue or from the finish before it, whichever
    came later; a launch runs from formation to enqueue and counts at
    its mean plus one mean deviation."""
    from elasticsearch_tpu.search.scheduler import PACE_ALPHA as a
    sched = ContinuousBatchScheduler(node_id=None, max_in_flight=8)
    try:
        assert sched._staged_depth_locked("knn") == 2   # nothing measured
        with sched._lock:
            # formed 0, on the device's queue at 20 ms, done at 30 ms,
            # the device free since 0: launch 20, device 10
            sched._note_pace_locked("knn", 0.0, 0.020, 0.030)
            assert sched._pace["knn"] == pytest.approx([20.0, 0.0, 10.0])
            # a launch of 10 ms, enqueued at 40 ms behind a batch that
            # ran until 60 ms, done at 70 ms: it ran 10 ms, not 30
            sched._device_free_t = 0.060
            sched._note_pace_locked("knn", 0.030, 0.040, 0.070)
            assert sched._pace["knn"] == pytest.approx(
                [20.0 - a * 10.0, a * 10.0, 10.0])
            # a launch of 20 ms, enqueued at 100 ms on a device free
            # since 75 ms, done at 130 ms: it ran 30 ms
            sched._device_free_t = 0.075
            sched._note_pace_locked("knn", 0.080, 0.100, 0.130)
            mean = 20.0 - a * 10.0
            assert sched._pace["knn"] == pytest.approx(
                [mean + a * (20.0 - mean),
                 a * 10.0 + a * (20.0 - mean - a * 10.0), 10.0 + a * 20.0])
        # 18.75 + 1.1 ms of launch over 12.5 ms: one runs, two staged
        sched._pace["knn"] = [18.75, 1.1, 12.5]
        assert sched.stats()["pace"]["knn"] == {
            "launch_ms": 18.75, "launch_dev_ms": 1.1, "device_ms": 12.5,
            "staged_depth": 3}
        sched._pace["knn"] = [12.0, 2.0, 13.0]   # the usual launch shows
        assert sched._staged_depth_locked("knn") == 3
        sched._pace["knn"] = [19.0, 5.0, 13.5]   # not the latest
        assert sched._staged_depth_locked("knn") == 3
        sched._pace["plane"] = [3.0, 1.0, 14.0]  # a quick launch
        assert sched._staged_depth_locked("plane") == 2
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# weighted-fair pickup
# ---------------------------------------------------------------------------

def test_percolate_not_starved_by_plane_storm():
    order: list = []
    lock = threading.Lock()

    def launch_for(tag):
        def launch(reqs, n_real=None):
            with lock:
                order.append((tag, len(reqs)))
            return list(reqs)
        return launch

    def drain(handle):
        time.sleep(0.005)
        return list(handle)

    def perc_launch(items):
        with lock:
            order.append(("percolate", len(items)))
        time.sleep(0.005)
        return list(items)

    sched = ContinuousBatchScheduler(node_id=None, max_batch=4,
                                     max_in_flight=1)
    try:
        plane_launch = launch_for("plane")
        futs = [sched.submit("plane", "k", i, plane_launch, drain)
                for i in range(40)]
        time.sleep(0.02)                 # the storm is queued and flowing
        perc = sched.submit("percolate", "p", "doc", perc_launch)
        assert perc.future.result(10) == "doc"
        for f in futs:
            assert f.future.result(10) is not None
        # the percolate pickup happened well before the storm drained
        idx = [i for i, (tag, _) in enumerate(order)
               if tag == "percolate"]
        assert idx and idx[0] < len(order) - 1, order
        st = sched.stats()
        assert st["reconciled"] and st["delivered"] == 41, st
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def test_classify_lanes_and_serial_shapes(node):
    _mk(node)
    s = _searcher(node)
    lane, shape = classify(parse_search_request(
        {"query": {"match": {"t": "alpha"}}, "size": 10}), s)
    assert lane == "plane" and shape[0] == 16
    # the structural fingerprint splits plan families: a 2-term match
    # must not share a queue (= batch) with a 1-term match
    lane2, shape2 = classify(parse_search_request(
        {"query": {"match": {"t": "alpha beta"}}, "size": 10}), s)
    assert lane2 == "plane" and shape2 != shape
    lane3, shape3 = classify(parse_search_request(
        {"query": {"match": {"t": "gamma delta"}}, "size": 10}), s)
    assert shape3 == shape2              # same family → same queue
    for body in (
            {"query": {"match_all": {}}, "aggs": {
                "a": {"terms": {"field": "n"}}}},
            {"query": {"match_all": {}}, "sort": [{"n": "asc"}]},
            {"query": {"match_all": {}}, "search_after": [1.0],
             "sort": ["_score"]},
            {"query": {"match_all": {}}, "timeout": "5s"},
    ):
        lane, _ = classify(parse_search_request(body), s)
        assert lane is None, body


def test_settings_parse():
    conf = {"search.scheduler.enabled": "true",
            "search.scheduler.max_batch": "16",
            "search.scheduler.max_in_flight": "2",
            "search.scheduler.fairness": "plane:8,percolate:2",
            "search.scheduler.shed": "off"}
    kw = settings_for(conf.get)
    assert kw["max_batch"] == 16 and kw["max_in_flight"] == 2
    assert kw["weights"] == {"plane": 8, "percolate": 2}
    assert kw["shed_threshold"] is None
    sched = ContinuousBatchScheduler(**kw)
    assert sched._shed_gate() == 0
    sched.close()


# ---------------------------------------------------------------------------
# live path + stats surfaces
# ---------------------------------------------------------------------------

def test_live_concurrent_searches_ride_the_scheduler(node):
    """Concurrent single-search clients on a 1-shard index (the
    fan-out path — no mesh to intercept) coalesce into scheduler
    batches, with correct per-request responses."""
    _mk(node, docs=60)
    st0 = node.search_actions.scheduler.stats()
    errs: list = []

    def client(ci):
        for qi in range(4):
            try:
                r = node.search("idx", {"query": {"match": {
                    "t": f"word{(ci + qi) % 7}"}}, "size": 5})
                ref_total = r["hits"]["total"]
                assert r["_shards"]["failed"] == 0
                assert ref_total > 0
            except Exception as e:     # noqa: BLE001 — surfaced below
                errs.append(e)
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs, errs[:3]
    st1 = node.search_actions.scheduler.stats()
    assert st1["delivered"] - st0["delivered"] >= 8
    assert st1["reconciled"], st1
    # the scheduler's queue time fed the queue_wait histogram + SLO book
    stats = node.local_node_stats()
    assert stats["scheduler"]["delivered"] >= 8
    assert stats["latency"]["queue_wait"]["count"] > 0
    assert stats["slo"]["lanes"]["queue_wait"]["good"] + \
        stats["slo"]["lanes"]["queue_wait"]["bad"] > 0


def test_scheduler_results_match_serial_on_live_path(node, tmp_path):
    """The same body through a scheduler-enabled and a scheduler-
    disabled node returns identical hits (ids, scores, totals)."""
    _mk(node, docs=80)
    n2 = Node({"search.scheduler.enabled": "false"},
              data_path=tmp_path / "n2").start()
    try:
        assert not n2.search_actions.scheduler.enabled
        _mk(n2, docs=80)
        for qi in range(6):
            body = {"query": {"match": {"t": f"alpha word{qi}"}},
                    "size": 10}
            a = node.search("idx", dict(body))
            b = n2.search("idx", dict(body))
            assert a["hits"]["total"] == b["hits"]["total"]
            assert [h["_id"] for h in a["hits"]["hits"]] == \
                [h["_id"] for h in b["hits"]["hits"]]
            assert [h["_score"] for h in a["hits"]["hits"]] == \
                [h["_score"] for h in b["hits"]["hits"]]
    finally:
        n2.close()


def test_cat_thread_pool_has_scheduler_columns(node):
    import json as _json

    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.handlers import register_all
    c = RestController()
    register_all(c, node)
    _mk(node, docs=20)
    node.search("idx", {"query": {"match": {"t": "alpha"}}})
    st, out = c.dispatch(
        "GET", "/_cat/thread_pool?v&h=host,scheduler.queue,"
        "scheduler.inflight,scheduler.rejected", b"")
    assert st == 200
    header = out.splitlines()[0]
    for col in ("scheduler.queue", "scheduler.inflight",
                "scheduler.rejected"):
        assert col in header, out
    # and the exporter carries the scheduler families by construction
    st, text = c.dispatch("GET", "/_prometheus/metrics", b"")
    assert st == 200
    assert "estpu_jit_scheduler_batches_launched_total" in text
    assert 'estpu_lane_fallbacks_total{lane="scheduler",' \
        'reason="slo-shed"}' in text
    _ = _json          # keep the import style consistent with siblings


def test_percolate_rides_scheduler(node):
    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.handlers import register_all
    import json as _json
    c = RestController()
    register_all(c, node)
    node.indices_service.create_index(
        "perc", {"settings": {"number_of_shards": 1,
                              "number_of_replicas": 0}})
    node.indices_service.put_percolator(
        "perc", "q1", {"query": {"match": {"t": "alpha"}}})
    st0 = node.search_actions.scheduler.stats()
    st, out = c.dispatch(
        "GET", "/perc/doc/_percolate",
        _json.dumps({"doc": {"t": "alpha beta"}}).encode())
    assert st == 200 and out["total"] == 1
    st1 = node.search_actions.scheduler.stats()
    assert st1["delivered"] > st0["delivered"]
    assert st1["queue_depth_by_lane"].get("percolate", 0) == 0


def test_close_flushes_waiters_declined():
    gate = threading.Event()

    def launch(reqs, n_real=None):
        return list(reqs)

    def drain(handle):
        gate.wait(2)
        return list(handle)

    sched = ContinuousBatchScheduler(node_id=None, max_batch=1,
                                     max_in_flight=1)
    first = sched.submit("plane", "k", 0, launch, drain)
    time.sleep(0.05)
    queued = [sched.submit("plane", "k", i, launch, drain)
              for i in (1, 2)]
    closer = threading.Thread(target=sched.close)
    closer.start()
    gate.set()
    closer.join(10)
    assert not closer.is_alive()
    from elasticsearch_tpu.search.scheduler import DECLINED
    assert first.future.result(5) == 0
    for w in queued:
        assert w.future.result(5) is DECLINED
    st = sched.stats()
    assert st["reconciled"], st
    # post-close submits decline immediately (serial fallback), and
    # execute() maps DECLINED to None for the caller
    assert sched.execute("plane", "k", 9, launch, drain) is None
